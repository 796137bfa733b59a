"""exposed_collective_share.mesh: on the device where it is largest, the
time in which a collective is in flight and no other operation runs, over
the traced window, in %: halo exchange the per-shard compute does not
hide.  On a chip whose trace has no ``Async XLA Ops`` line that is the
time its core spends issuing collectives and waiting for them
(``meshtrace``).  Nothing where no collective ran."""
from devtrace import length, subtract
from meshtrace import split_ops


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    shares = []
    for d in tr.devices:
        coll, other = split_ops(tr, d)
        if coll:
            shares.append(length(subtract(coll, other)) / tr.window_ns)
    return 100.0 * max(shares) if shares else None
