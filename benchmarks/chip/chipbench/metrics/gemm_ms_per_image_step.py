"""Protection: device busy time under the ``drift.gemm`` scope (the int8
projection GEMMs; in drift mode their fusions carry the fault draws) in
the traced window per image and denoising step served in it, in
milliseconds. In drift mode it holds the clean twin's GEMMs too."""


def read(run):
    if run.scopes is None:
        return None
    return run.scopes.ms_per_image_step(("drift.gemm",),
                                        run.window_images, run.steps)
