"""SOI middle: share of the window's generate steps in which the
compressed middle ran (its ``lax.cond`` fired), from the program's own
telemetry vector ``[occupancy by phase..., mid_fired, n_active]`` (%)."""


def read(run):
    steps = run.steps_in_window()
    if not len(steps):
        return None
    live = steps[:, -1] > 0
    if not live.any():
        return None
    return 100.0 * steps[live, -2].sum() / live.sum()
