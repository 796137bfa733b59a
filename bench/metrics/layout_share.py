"""layout_share: device time of every operation that is not a generated
``stencil_*`` kernel (the layout stage's pads and crops, the engine's
eager operations, the hook) over the device's busy time, in %, averaged
over the devices.  Silent where no kernel event ran."""
from devtrace import length


def read(run):
    tr = run.trace
    if tr is None or not tr.has("kernel"):
        return None
    shares = [length(tr.ops(d, "other")) / tr.busy_ns(d)
              for d in tr.devices if tr.busy_ns(d)]
    return 100.0 * sum(shares) / len(shares) if shares else None
