"""compute_roofline.mesh: the compulsory HBM bytes of the window's steps
(``Kernel.compulsory_bytes_per_point`` of the plain reference × the
domain's points × the steps) over the chips' peak HBM bandwidth times the
time in which some operation that is not a collective runs, summed over
the devices, in %: how near the per-shard compute runs to streaming each
grid once per step.  The time is each device's union of those
operations' intervals, not the sum of their durations: an asynchronous
copy's ``-start`` event spans its flight, under the operations that run
meanwhile, and a sum would count that time twice.  Nothing where no
collective ran (a cell that is not sharded)."""
from devtrace import length
from meshtrace import split_ops


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    split = [split_ops(tr, d) for d in tr.devices]
    if not any(coll for coll, _ in split):
        return None
    secs = sum(length(other) for _, other in split) / 1e9
    moved = run.jobs * run.work_points * run.bytes_per_point
    return 100.0 * moved / (run.peaks["hbm_bytes_per_s"] * secs)
