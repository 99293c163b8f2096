"""Pallas TPU kernel: fused faulty INT8 GEMM + ABFT checksums.

This is the paper's "ABFT-wrapping" of the systolic array (Fig 3 / Sec 5.1)
as a TPU kernel: one pass over (M, N, K) tiles computes

  * the INT32 accumulator C = Aq @ Bq (the MXU int8 pass),
  * the simulated DVFS timing-error injection (xor of a precomputed
    per-element flip mask -- the functional analogue of late-latching bits),
  * per-(row, tile-col) and per-(tile-row, col) actual AND expected
    checksums, fused into the same K-loop so the "checksum row/column" of
    the classic ABFT systolic formulation costs one extra MAC lane instead
    of a second GEMM pass.

Block shapes are BlockSpec tiles resident in VMEM; defaults (128, 128, 128)
match MXU granularity (int8 wants >= (32, 128) sublane x lane packing).
Checksum arithmetic is int32 with two's-complement wraparound => bit-exact
against the pure-jnp oracle in ref.py (validated in interpret mode on CPU).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(a_ref, b_ref, flip_ref,
            c_ref, act_row_ref, exp_row_ref, act_col_ref, exp_col_ref,
            acc_ref, exp_row_acc, exp_col_acc, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        exp_row_acc[...] = jnp.zeros_like(exp_row_acc)
        exp_col_acc[...] = jnp.zeros_like(exp_col_acc)

    a = a_ref[...]                      # (bm, bk) int8
    b = b_ref[...]                      # (bk, bn) int8
    a32 = a.astype(jnp.int32)
    b32 = b.astype(jnp.int32)

    # Main MAC pass (MXU int8 -> int32 on hardware).
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32)

    # Fused checksum lanes: expected row sums need B's block row-sum vector,
    # expected col sums need A's block col-sum vector -- both rank-1, so the
    # extra work is one MAC column + one MAC row per tile (the "+1 lane" of
    # the ABFT-wrapped systolic array). The MXU takes no int32 operands, so
    # the lanes are broadcast-multiply-reduce passes on the vector unit.
    b_rowsum = jnp.sum(b32.T, axis=0, keepdims=True)      # (1, bk)
    a_colsum = jnp.sum(a32.T, axis=1, keepdims=True)      # (bk, 1)
    exp_row_acc[...] += jnp.sum(a32 * b_rowsum, axis=1,
                                keepdims=True)            # (bm, 1)
    exp_col_acc[...] += jnp.sum(a_colsum * b32, axis=0,
                                keepdims=True)            # (1, bn)

    @pl.when(k == n_k - 1)
    def _finalize():
        # DVFS timing errors land on the accumulator as it streams out.
        bits = jax.lax.bitcast_convert_type(acc_ref[...], jnp.uint32)
        c_faulty = jax.lax.bitcast_convert_type(
            jax.lax.bitwise_xor(bits, flip_ref[...]), jnp.int32)
        c_ref[...] = c_faulty
        act_row_ref[...] = jnp.sum(c_faulty, axis=1, keepdims=True)
        act_col_ref[...] = jnp.sum(c_faulty, axis=0, keepdims=True)
        exp_row_ref[...] = exp_row_acc[...]
        exp_col_ref[...] = exp_col_acc[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def abft_matmul(aq: jax.Array, bq: jax.Array, flips: jax.Array,
                bm: int = 128, bn: int = 128, bk: int = 128,
                interpret: bool = False
                ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused faulty-ABFT GEMM. See ref.abft_matmul_ref for semantics.

    aq: (M, K) int8, bq: (K, N) int8, flips: (M, N) uint32.
    M % bm == N % bn == K % bk == 0 (callers pad; ops.py does).
    """
    m, k = aq.shape
    k2, n = bq.shape
    assert k == k2 and m % bm == 0 and n % bn == 0 and k % bk == 0
    mt, nt, kt = m // bm, n // bn, k // bk

    grid = (mt, nt, kt)
    # Checksums carry a leading tile axis so every block's last two dims
    # are (8, 128)-aligned or span the array: (bm, 1) of (nt, M, 1) and
    # (1, bn) of (mt, 1, N), where (bm, 1) of (M, Nt) would be refused.
    out_shapes = (
        jax.ShapeDtypeStruct((m, n), jnp.int32),        # c_faulty
        jax.ShapeDtypeStruct((nt, m, 1), jnp.int32),    # act_row
        jax.ShapeDtypeStruct((nt, m, 1), jnp.int32),    # exp_row
        jax.ShapeDtypeStruct((mt, 1, n), jnp.int32),    # act_col
        jax.ShapeDtypeStruct((mt, 1, n), jnp.int32),    # exp_col
    )
    in_specs = [
        pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
    ]
    out_specs = (
        pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        pl.BlockSpec((None, bm, 1), lambda i, j, kk: (j, i, 0)),
        pl.BlockSpec((None, bm, 1), lambda i, j, kk: (j, i, 0)),
        pl.BlockSpec((None, 1, bn), lambda i, j, kk: (i, 0, j)),
        pl.BlockSpec((None, 1, bn), lambda i, j, kk: (i, 0, j)),
    )
    scratch = [
        pltpu.VMEM((bm, bn), jnp.int32),
        pltpu.VMEM((bm, 1), jnp.int32),
        pltpu.VMEM((1, bn), jnp.int32),
    ]
    c, act_row, exp_row, act_col, exp_col = pl.pallas_call(
        functools.partial(_kernel, n_k=kt),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shapes,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(aq, bq, flips)
    rows = lambda x: x[..., 0].T                       # (nt, M, 1) -> (M, Nt)
    cols = lambda x: x[:, 0, :]                        # (mt, 1, N) -> (Mt, N)
    return c, rows(act_row), rows(exp_row), cols(act_col), cols(exp_col)
