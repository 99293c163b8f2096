"""Device: the share of the window in which no operation ran on the chip
(profiler trace), in percent."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
