"""kernel_roofline.hooked: ``kernel_roofline`` in the cells whose hook runs
before every step, where it moves ``gpts_per_s.hooked``."""
import cells

read = cells.load_module("metrics", "kernel_roofline").read
