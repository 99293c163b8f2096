"""Batcher: live slots over the slots of the batches the engine ran
(``EngineStats.batches`` and ``padded_slots`` over the served path), in
percent."""


def read(run):
    batches, padded = run.engine_batches, run.engine_padded_slots
    if batches <= 0:
        return None
    slots = batches * run.bucket
    return 100.0 * (slots - padded) / slots
