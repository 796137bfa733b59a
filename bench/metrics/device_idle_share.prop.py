"""device_idle_share.prop: 1 − (union of the intervals in which an
operation ran on the device ÷ the traced window), in %, on the most idle
device, in the cells of fused propagation jobs."""


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    return 100.0 * max(tr.idle_share(d) for d in tr.devices)
