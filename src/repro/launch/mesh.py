"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (device count is locked at first backend init --
the dry-run sets XLA_FLAGS before any jax import).
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(model: int = 2):
    """Tiny mesh for CI-scale sharding tests (requires >=4 host devices)."""
    n = len(jax.devices())
    data = max(n // model, 1)
    return jax.make_mesh((data, model), ("data", "model"))


def make_serving_mesh(model_parallel: int = 1, devices=None):
    """(data, model) mesh for the sharded serving engine.

    ``data`` gets every device not claimed by ``model_parallel`` -- the
    serving engine spreads one micro-batch bucket over it, so bucket sizes
    should be multiples of the data-axis size (otherwise the batch stays
    replicated; see ``distributed.sharding.batch_spec``). ``devices``
    restricts the mesh to a subset (tests carve a 4-device mesh out of 8
    fake CPU devices); default is all local devices, ordered by
    ``jax.make_mesh`` from their physical topology.
    """
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    if model_parallel < 1 or n % model_parallel:
        raise ValueError(
            f"model_parallel={model_parallel} does not divide {n} devices")
    auto = jax.sharding.AxisType.Auto     # GSPMD propagates the shardings
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), (auto, auto), devices=devices)
