"""device_idle_share.hooked: ``device_idle_share.prop`` in the cells whose
hook runs before every step, where it moves ``gpts_per_s.hooked``."""
import cells

read = cells.load_module("metrics", "device_idle_share.prop").read
