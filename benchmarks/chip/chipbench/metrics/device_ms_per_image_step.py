"""Sampler: device busy time in the window (profiler trace) per image and
denoising step served in it, in milliseconds. In drift mode the busy time
holds the drift sampler and its clean twin."""


def read(run):
    if run.trace is None or run.window_images == 0:
        return None
    return 1e3 * run.trace.busy_s / (run.window_images * run.steps)
