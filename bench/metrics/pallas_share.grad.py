"""pallas_share.grad: device time of the generated ``stencil_*`` kernels
over the device's busy time during gradients, in %: how much of a
gradient runs on the generated kernels rather than on XLA operations."""
from devtrace import length


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    shares = [length(tr.ops(d, "kernel")) / tr.busy_ns(d)
              for d in tr.devices if tr.busy_ns(d)]
    return 100.0 * sum(shares) / len(shares) if shares else None
