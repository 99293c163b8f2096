"""Protection: device busy time under the ``drift.checksum`` scope (the
tile checksums, with the rollback select and store write fused into the
mask's pass) in the traced window per image and denoising step served in
it, in milliseconds. In drift mode it holds the clean twin's too."""


def read(run):
    if run.scopes is None:
        return None
    return run.scopes.ms_per_image_step(("drift.checksum",),
                                        run.window_images, run.steps)
