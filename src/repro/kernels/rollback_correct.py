"""Pallas TPU kernel: rollback correction (the recovery scheduler's splice).

Consumes the per-tile checksum differences emitted by abft_matmul, builds the
correction mask in-register (union or cross policy, Fig 10a) and overwrites
masked positions of the dequantized GEMM output with the checkpointed values
from a previous timestep (Sec 5.3 Step 3-4). Elementwise + broadcast only --
the tile is VMEM-resident and the checkpoint tile arrives via its own
BlockSpec stream (on hardware: the DMA the recovery scheduler coalesces).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(c_ref, ckpt_ref, rdiff_ref, cdiff_ref, thr_ref,
            out_ref, flag_ref, *, union: bool):
    thr = thr_ref[0]
    rd = rdiff_ref[...]                      # (bm, 1) int32
    cd = cdiff_ref[...]                      # (1, bn) int32
    rflag = (rd >= thr) | (rd <= -thr)
    cflag = (cd >= thr) | (cd <= -thr)
    mask = (rflag | cflag) if union else (rflag & cflag)
    out_ref[...] = jnp.where(mask, ckpt_ref[...], c_ref[...])
    hit = jnp.max(mask.astype(jnp.int32), axis=1, keepdims=True)   # (bm, 1)
    flag_ref[...] = jnp.max(hit, axis=0, keepdims=True)            # (1, 1)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "union", "interpret"))
def rollback_correct(c: jax.Array, ckpt: jax.Array,
                     row_diff: jax.Array, col_diff: jax.Array,
                     threshold: int,
                     bm: int = 128, bn: int = 128,
                     union: bool = True,
                     interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """c, ckpt: (M, N) f32; row_diff: (M, Nt) int32; col_diff: (Mt, N) int32.

    Returns (corrected (M, N) f32, tile_flag (Mt, Nt) int32).
    """
    m, n = c.shape
    assert m % bm == 0 and n % bn == 0
    mt, nt = m // bm, n // bn
    thr = jnp.asarray([threshold], jnp.int32)
    # A leading tile axis keeps every block's last two dims (8, 128)-aligned
    # or spanning the array (see abft_matmul): (bm, 1) of (nt, M, 1),
    # (1, bn) of (mt, 1, N), (1, 1) of (mt, nt, 1, 1).
    rows = row_diff.T[..., None]                       # (nt, M, 1)
    cols = col_diff[:, None, :]                        # (mt, 1, N)

    out, flag = pl.pallas_call(
        functools.partial(_kernel, union=union),
        grid=(mt, nt),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((None, bm, 1), lambda i, j: (j, i, 0)),
            pl.BlockSpec((None, 1, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((None, None, 1, 1), lambda i, j: (i, j, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m, n), c.dtype),
            jax.ShapeDtypeStruct((mt, nt, 1, 1), jnp.int32),
        ),
        interpret=interpret,
    )(c, ckpt, rows, cols, thr)
    return out, flag[:, :, 0, 0]
