"""gradient_s: the window's wall time over the whole misfit gradients
completed in it (jobs of a ``gradient`` traffic mix)."""


def read(run):
    if run.spec.traffic["job"] != "gradient" or not run.jobs:
        return None
    return run.window_s / run.jobs
