"""Protection: device busy time under the ``drift.correct`` and
``drift.ckpt`` scopes (rollback and the checkpoint store) in the traced
window per image and denoising step served in it, in milliseconds. In
drift mode it holds the clean twin's too."""


def read(run):
    if run.scopes is None:
        return None
    return run.scopes.ms_per_image_step(("drift.correct", "drift.ckpt"),
                                        run.window_images, run.steps)
