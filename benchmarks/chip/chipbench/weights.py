"""Random weights from a seed, made on the device in one call.

The tree is the one the configuration's reference module states
(``param_shapes(cfg) -> {leaf path: (shape, std or SINCOS)}``), so a new
architecture brings its tree with its reference and edits nothing here.
Each leaf is drawn from a normal distribution of its std, or is the frozen
2-D sine-cosine position table (``SINCOS``). Leaves are float32, the type
the models serve their parameters in.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import spec

SINCOS = "sincos"       # in place of a std: the fixed position table


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def sincos_2d(tokens: int, d: int) -> jax.Array:
    """DiT's 2-D sine-cosine position embedding of a square token grid:
    sin and cos of the row index, then of the column index, at ``d / 4``
    frequencies ``10000^(-i / (d / 4))``."""
    grid = math.isqrt(tokens)
    quarter = d // 4
    omega = 1.0 / 10000.0 ** (jnp.arange(quarter, dtype=jnp.float32)
                              / quarter)
    idx = jnp.arange(tokens)
    row = (idx // grid).astype(jnp.float32)[:, None] * omega
    col = (idx % grid).astype(jnp.float32)[:, None] * omega
    return jnp.concatenate([jnp.sin(row), jnp.cos(row), jnp.sin(col),
                            jnp.cos(col)], axis=1)


def make(cfg: dict, seed: int):
    """The weights for ``seed``: one jitted call, leaves on the default
    device in float32."""
    tree = spec.reference_module(cfg).param_shapes(cfg)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=_is_leaf)

    def leaf(k, shape, std):
        if std == SINCOS:
            return sincos_2d(*shape)
        return std * jax.random.normal(k, shape, jnp.float32)

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            leaf(k, shape, std) for k, (shape, std) in zip(keys, leaves)])

    return jax.jit(draw)(jax.random.PRNGKey(seed))
