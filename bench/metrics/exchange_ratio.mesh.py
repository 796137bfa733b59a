"""exchange_ratio.mesh: the per-chip collective bytes of one step as the
program models what it sends (``repro.core.distributed.EXCHANGE_STATS``,
over every window sent in the process), over the benchmark's compulsory
halo bytes of one step per chip (``meshtrace``), in %: at least 100, the
excess being what the program exchanges beyond what the stencil reads.
Nothing where the program does not count its exchanges."""
import math

from meshtrace import halo_bytes_per_chip_step


def read(run):
    try:
        from repro.core.distributed import EXCHANGE_STATS as stats
    except ImportError:
        return None
    per_step = halo_bytes_per_chip_step(run.spec)
    if not stats.get("windows") or not per_step:
        return None
    spec = run.spec
    per_job = math.ceil(spec.steps / (spec.fuse or spec.steps))
    steps = stats["windows"] / per_job * spec.steps
    return 100.0 * stats["collective_bytes"] / steps / per_step
