"""Model: the share of the chip's peak that the served images need, in
percent, over the traced window: the images completed in it times
denoising steps times the least time one denoiser evaluation of one image
can take at the published peaks (int8 GEMM operations at the int8 peak,
float attention and modulation operations at the bf16 peak;
``chipbench.opcount``), over the window's length on the trace's clock.
Protection work and the clean twin do not count."""
from chipbench import opcount


def read(run):
    if run.trace is None or run.window_images == 0:
        return None
    per_eval = opcount.roofline_s_per_eval(run.model, run.peaks)
    return (100.0 * run.window_images * run.steps * per_eval
            / run.trace.window_s)
