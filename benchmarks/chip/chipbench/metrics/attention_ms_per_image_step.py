"""Model: device busy time under the ``dit.attention`` scope in the traced
window per image and denoising step served in it, in milliseconds. In
drift mode it holds the clean twin's attention too."""


def read(run):
    if run.scopes is None:
        return None
    return run.scopes.ms_per_image_step(("dit.attention",),
                                        run.window_images, run.steps)
