"""gpts_per_s: interior grid-point updates completed in the window, in
billions, over the window's wall time.  The window runs whole jobs: it
closes when the first job that ends past ``--seconds`` has ended, so every
step counted is finished and every second of the window is counted."""


def read(run):
    pts = run.jobs * run.work_points
    if not pts:
        return None
    return pts / run.window_s / 1e9
