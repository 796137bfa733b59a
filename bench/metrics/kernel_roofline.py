"""kernel_roofline: the generated stencil kernels' share of the HBM
roofline, in %.

Numerator: the compulsory bytes of the steps run in the traced window
(every grid the kernel reads read once and every grid it writes written
once per step, at the configuration's extent; ``Kernel
.compulsory_bytes_per_point`` of the plain reference).  Denominator: the
chip's peak HBM bandwidth times the summed device time of the
``stencil_*`` kernel events.  A per-step streaming bound: a kernel that
carries several steps in on-chip memory could read above 100 %, which
would call for a new numerator.  Silent where no kernel event ran."""


def read(run):
    tr = run.trace
    if tr is None or not tr.has("kernel"):
        return None
    kernel_s = sum(tr.summed_ns(d, "kernel") for d in tr.devices) / 1e9
    moved = run.jobs * run.work_points * run.bytes_per_point
    return 100.0 * moved / (run.peaks["hbm_bytes_per_s"] * kernel_s)
