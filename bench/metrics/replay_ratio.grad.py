"""replay_ratio.grad: forward windows the adjoint's backward pass
recomputes from its checkpoints, over the forward's windows, in %, as the
program counts them for the most recently traced gradient
(``repro.core.adjoint.CHECKPOINT_STATS``).  Nothing where the program
does not count forward windows."""


def read(run):
    try:
        from repro.core.adjoint import CHECKPOINT_STATS as stats
    except ImportError:
        return None
    if not stats.get("forward_windows"):
        return None
    return 100.0 * stats["replayed_windows"] / stats["forward_windows"]
