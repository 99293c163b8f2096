"""Jit'd wrappers: the kernel-backed DRIFT GEMM pipeline.

``drift_gemm``: quantize -> fused faulty-ABFT GEMM (Pallas) -> dequantize ->
rollback correction (Pallas). Pure function of (x, w, ckpt, key, ber);
the unit the kernel tests sweep against the ref.py oracles.

The kernels compile with Mosaic for the TPU unless the caller passes
``interpret=True`` (the CPU tests do).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import fault, quant
from repro.kernels import abft_matmul as _abft
from repro.kernels import rollback_correct as _rc


def _pad2(x: jax.Array, bm: int, bn: int) -> jax.Array:
    m, n = x.shape
    return jnp.pad(x, ((0, (-m) % bm), (0, (-n) % bn)))


class DriftGemmOut(NamedTuple):
    y: jax.Array               # (M, N) f32 corrected output
    n_flagged_tiles: jax.Array  # scalar int32
    row_diff: jax.Array        # (Mp, Ntp) int32 (padded grid)
    col_diff: jax.Array        # (Mtp, Np) int32


@functools.partial(jax.jit,
                   static_argnames=("threshold_bit", "bm", "bn", "bk",
                                    "union", "interpret"))
def drift_gemm(x: jax.Array, w: jax.Array, ckpt: Optional[jax.Array],
               key: jax.Array, ber: jax.Array,
               threshold_bit: int = 10,
               bm: int = 128, bn: int = 128, bk: int = 128,
               union: bool = True,
               interpret: bool = False) -> DriftGemmOut:
    """Kernel-backed DRIFT-protected GEMM: x (M,K) f32 @ w (K,N) f32."""
    m, k = x.shape
    n = w.shape[1]

    xq = quant.quantize(x, axis=None)
    wq = quant.quantize(w, axis=1)
    aq = _pad2(xq.q, bm, bk)
    bq = _pad2(wq.q, bk, bn)
    mp, kp = aq.shape
    np_ = bq.shape[1]

    # Functional DVFS error injection: per-element uint32 xor masks.
    kf, kb = jax.random.split(key)
    p = fault.word_flip_prob(ber)
    flip = jax.random.uniform(kf, (mp, np_)) < p
    pos = jax.random.randint(kb, (mp, np_), 0, 32, dtype=jnp.uint32)
    flips = jnp.where(flip, jnp.left_shift(jnp.uint32(1), pos), jnp.uint32(0))

    c, act_row, exp_row, act_col, exp_col = _abft.abft_matmul(
        aq, bq, flips, bm=bm, bn=bn, bk=bk, interpret=interpret)

    row_diff = act_row - exp_row          # (Mp, Nt)
    col_diff = act_col - exp_col          # (Mt, Np)

    w_scale = wq.scale.reshape(1, -1)
    y_faulty = (c[:m, :n].astype(jnp.float32) * xq.scale * w_scale)
    y_faulty_p = _pad2(y_faulty, bm, bn)
    ckpt_p = (_pad2(ckpt, bm, bn) if ckpt is not None
              else jnp.zeros_like(y_faulty_p))

    corrected, tile_flag = _rc.rollback_correct(
        y_faulty_p, ckpt_p, row_diff, col_diff,
        threshold=1 << threshold_bit, bm=bm, bn=bn, union=union,
        interpret=interpret)
    return DriftGemmOut(corrected[:m, :n], jnp.sum(tile_flag),
                        row_diff, col_diff)
