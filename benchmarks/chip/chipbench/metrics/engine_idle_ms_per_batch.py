"""Engine: device idle time inside the engine's host phases (the
``batch.*`` profiler annotations) in the traced window per batch (one
``batch.prepare`` span each), in milliseconds."""


def read(run):
    s = run.scopes
    if s is None or s.batches == 0:
        return None
    return 1e3 * s.phase_idle_s / s.batches
