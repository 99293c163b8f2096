"""Pallas TPU kernel: fused (flash) attention for DiT / LM serve.

The attention score+mix pair is the one non-projection compute hotspot of
the paper's DiT workload; on TPU the win is keeping the (Bq x Bk) score
tile in VMEM through the online-softmax recurrence instead of
materializing (S x S) scores in HBM.

Grid (batch*heads, q_blocks, kv_blocks), kv innermost with running
(m, l, acc) scratch carried across the kv dimension -- the classic flash
recurrence. Supports non-causal (DiT) and causal (LM) masking. Validated
bit-close against ref.flash_attention_ref / models.attention in interpret
mode (tests/test_kernels_flash.py); on TPU the same code compiles to
Mosaic with MXU-aligned (128, 128) default tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, n_kv: int, scale: float, causal: bool,
            bq: int, bk: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                              # (bq, d)
    k = k_ref[0]                              # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    if causal:
        q_i = pl.program_id(1)
        rows = q_i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = kv_i * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(rows >= cols, s, NEG_INF)

    m_prev = m_ref[...]                       # (bq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)                    # (bq, bk) f32
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = m_cur

    @pl.when(kv_i == n_kv - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-37)
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False,
                    bq: int = 128, bk: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q, k, v: (BH, S, D) -> (BH, S, D). S % bq == S % bk == 0."""
    bh, s, d = q.shape
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    nq, nk = s // bq, s // bk
    scale = d ** -0.5

    return pl.pallas_call(
        functools.partial(_kernel, n_kv=nk, scale=scale, causal=causal,
                          bq=bq, bk=bk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def mha_flash(q: jax.Array, k: jax.Array, v: jax.Array,
              causal: bool = False, bq: int = 128, bk: int = 128,
              interpret: bool = False) -> jax.Array:
    """(B, S, H, D) convenience wrapper (no GQA: repeat KV before calling)."""
    b, s, h, d = q.shape
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    o = flash_attention(fold(q), fold(k), fold(v), causal=causal,
                        bq=bq, bk=bk, interpret=interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
