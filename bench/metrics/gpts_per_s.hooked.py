"""gpts_per_s.hooked: ``gpts_per_s`` of the cells whose hook runs before
every step.  A metric of its own, with a bound of its own: the host's
per-step path makes their windows spread far wider than those of fused
jobs, and one bound for both would hide a small gain in the fused cells."""
import cells

read = cells.load_module("metrics", "gpts_per_s").read
