"""layout_share.hooked: ``layout_share`` in the cells whose hook runs
before every step, where it moves ``gpts_per_s.hooked``."""
import cells

read = cells.load_module("metrics", "layout_share").read
