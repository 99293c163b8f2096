"""Random DiT weights from a seed, made on the device in one call.

The tree has the layout the served model reads (``params["blocks"]``
stacked over depth, as ``models/dit.py`` keeps it) and is built here from the
configuration file's sizes alone. Every leaf but the position embedding is
drawn, the adaLN-Zero modulation and output projections and the biases
included: zero-initialised modulation makes every block an identity and the
predicted noise exactly zero, which no trained checkpoint has. The position
embedding is DiT's frozen 2-D sine-cosine table (arXiv:2212.09748, Sec. 3.2),
as every DiT checkpoint carries it: a small drawn one leaves each token's
state to the image alone, and the sampler then amplifies float rounding
into the finals as no trained model does. Leaves are float32, the type the
model serves its parameters in.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

# Scale of the leaves a trained DiT starts from zero (adaLN, output
# projection, biases) and of the class embedding.
SMALL_STD = 0.02
SINCOS = "sincos"       # in place of a std: the fixed position table


def shapes(cfg: dict) -> Dict:
    """{leaf path: (shape, std)} of the served tree."""
    d, f, n_layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    tokens = (cfg["latent_size"] // cfg["patch_size"]) ** 2
    pdim = cfg["patch_size"] ** 2 * cfg["latent_channels"]

    def dense(k, n):
        return ((k, n), 1.0 / math.sqrt(k))

    def stacked(leaf):
        shape, std = leaf
        return ((n_layers,) + shape, std)

    attn = {"wq": stacked(dense(d, d)), "wk": stacked(dense(d, d)),
            "wv": stacked(dense(d, d)), "wo": stacked(dense(d, d))}
    blocks = {"adaln_w": stacked(((d, 6 * d), SMALL_STD)),
              "adaln_b": stacked(((6 * d,), SMALL_STD)),
              "attn": attn,
              "mlp_w1": stacked(dense(d, f)),
              "mlp_w2": stacked(dense(f, d))}
    tree = {
        "patch_w": dense(pdim, d), "patch_b": ((d,), SMALL_STD),
        "pos_embed": ((tokens, d), SINCOS),
        "t_w1": dense(256, d), "t_b1": ((d,), SMALL_STD),
        "t_w2": dense(d, d), "t_b2": ((d,), SMALL_STD),
        "blocks": blocks,
        "final_adaln_w": ((d, 2 * d), SMALL_STD),
        "final_adaln_b": ((2 * d,), SMALL_STD),
        "final_w": ((d, pdim), SMALL_STD), "final_b": ((pdim,), SMALL_STD),
        "class_embed": ((cfg["num_classes"] + 1, d), SMALL_STD),
    }
    return tree


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
    spec = shapes(cfg)
    leaves, treedef = jax.tree.flatten(spec, is_leaf=_is_leaf)

    def leaf(k, shape, std):
        if std == SINCOS:
            return sincos_2d(*shape)
        return std * jax.random.normal(k, shape, jnp.float32)

    def draw(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            leaf(k, shape, std) for k, (shape, std) in zip(keys, leaves)])

    return jax.jit(draw)(jax.random.PRNGKey(seed))
