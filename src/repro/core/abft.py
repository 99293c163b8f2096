"""Algorithm-based fault tolerance (ABFT) for GEMM, Huang & Abraham style.

For C = A @ B, maintain
    row checksum:  C @ 1  ==  A @ (B @ 1)
    col checksum:  1T @ C ==  (1T @ A) @ B
A mismatch in row i and column j localizes an error at (i, j) and the
mismatch magnitude equals the (summed) error value (Fig 3 of the paper).

Integer exactness
-----------------
For INT8xINT8->INT32 GEMMs, all checksum arithmetic is performed in int32
*with two's-complement wraparound*, which is a ring homomorphism mod 2^32:
the expected and actual checksums agree exactly in the error-free case even
when the mathematical sums exceed int32 range. A flip of bit b in one
element changes the checksum by exactly +/-2^b (mod 2^32), so interpreting
the wrapped difference as a signed int32 recovers the *exact* signed error
sum whenever |error| < 2^31. This removes any float rounding from detection:
thresholding is exact, with zero false positives -- strictly stronger than
the float-epsilon comparisons typical of GPU ABFT implementations and a good
match for TPU int8 MXU passes.

Float path: for bf16/f32 GEMMs, checksums are computed in f32 and compared
with a magnitude threshold scaled by a rounding-noise floor.

Tiled variant
-------------
The paper's recovery is tile-by-tile (Sec 5.4); ``tile_checksum_diff``
evaluates per-(tile-row, tile-col) checksums so the correction mask and the
DRAM-row accounting operate at tile granularity. The sums are taken in the
accumulator's own (M, N) layout: per row and column tile (M, N/tile_n),
per column and row tile (M/tile_m, N). Splitting the minor dimension into
32-wide tiles would be a relayout of the whole accumulator on the TPU's
128-lane vregs. So the column-tile sums reduce a split of the row
dimension, and the row-tile sums are a product with a 0/1 block indicator
on the MXU, the int32 accumulator entering as four exact int8 digits.
``tile_error_mask`` expands the row and column flags through indicators
too, and counts the masked elements from the flags. Drift mode takes its
whole-row error count from the tile row sums (``tile_row_errors``), so the
accumulator is read by one checksum pass, not two. The Pallas kernel in
``repro.kernels.abft_matmul`` fuses per-tile sums into the GEMM epilogue.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


def _exceeds(diff: jax.Array, thr) -> jax.Array:
    """|diff| >= thr robust to int32 overflow: abs(INT32_MIN) wraps negative,
    so a bit-31 flip (delta = -2^31) would escape an abs()-based check."""
    return (diff >= thr) | (diff <= -thr)


class AbftReport(NamedTuple):
    """Detection output for one GEMM."""

    row_diff: jax.Array   # (M,) signed error sum per row (int32 or f32)
    col_diff: jax.Array   # (N,) signed error sum per column
    row_flag: jax.Array   # (M,) bool, |row_diff| >= threshold
    col_flag: jax.Array   # (N,) bool
    n_row_err: jax.Array  # scalar int32
    n_col_err: jax.Array  # scalar int32


@dataclasses.dataclass(frozen=True)
class AbftConfig:
    threshold_bit: int = 10        # errors >= 2**threshold_bit are "large"
    tile_m: int = 32               # systolic-array tile (paper default 32)
    tile_n: int = 32
    enabled: bool = True
    # 'cross' = paper-faithful Fig 10(a): flagged-rows x flagged-cols.
    # 'union' = beyond-paper: whole flagged rows AND whole flagged cols of a
    #           tile. Same DRAM cost (recovery fetches whole repacked tiles),
    #           but also catches "paired large errors that cancel within the
    #           same row or column" -- the blind spot Sec 5.3 Step 2 accepts.
    mask_policy: str = "union"

    @property
    def threshold(self) -> int:
        return 1 << self.threshold_bit


# ----------------------------------------------------------------------------
# Full-matrix checksums
# ----------------------------------------------------------------------------

def expected_checksums_int(aq: jax.Array, bq: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(A @ B1, 1TA @ B) in wraparound int32. aq:(M,K) int8, bq:(K,N) int8."""
    a32 = aq.astype(jnp.int32)
    b32 = bq.astype(jnp.int32)
    b_rowsum = jnp.sum(b32, axis=1)                 # (K,) fits int32: K*127
    a_colsum = jnp.sum(a32, axis=0)                 # (K,)
    exp_row = a32 @ b_rowsum                        # (M,) wraps mod 2^32
    exp_col = a_colsum @ b32                        # (N,)
    return exp_row, exp_col


def checksum_diff_int(acc: jax.Array, exp_row: jax.Array, exp_col: jax.Array
                      ) -> Tuple[jax.Array, jax.Array]:
    """Signed per-row / per-col error sums (exact mod-2^32 arithmetic)."""
    act_row = jnp.sum(acc, axis=1)
    act_col = jnp.sum(acc, axis=0)
    return act_row - exp_row, act_col - exp_col


def detect_int(acc: jax.Array, aq: jax.Array, bq: jax.Array,
               cfg: AbftConfig) -> AbftReport:
    """Detect large errors in an int32 accumulator C=(A@B)."""
    exp_row, exp_col = expected_checksums_int(aq, bq)
    row_diff, col_diff = checksum_diff_int(acc, exp_row, exp_col)
    thr = jnp.int32(cfg.threshold)
    row_flag = _exceeds(row_diff, thr)
    col_flag = _exceeds(col_diff, thr)
    return AbftReport(row_diff, col_diff, row_flag, col_flag,
                      jnp.sum(row_flag.astype(jnp.int32)),
                      jnp.sum(col_flag.astype(jnp.int32)))


def detect_f32(c: jax.Array, a: jax.Array, b: jax.Array,
               cfg: AbftConfig, rel_floor: float = 1e-3) -> AbftReport:
    """Float-path detection with a rounding-noise floor.

    threshold_eff = max(2**threshold_bit_scaled, rel_floor * mean|C|) where
    the bit threshold is interpreted on the same scale as C.
    """
    exp_row = a @ jnp.sum(b, axis=1)
    exp_col = jnp.sum(a, axis=0) @ b
    row_diff = jnp.sum(c, axis=1) - exp_row
    col_diff = jnp.sum(c, axis=0) - exp_col
    thr = jnp.maximum(jnp.float32(cfg.threshold),
                      rel_floor * jnp.mean(jnp.abs(c)) * c.shape[1])
    row_flag = jnp.abs(row_diff) >= thr
    col_flag = jnp.abs(col_diff) >= thr
    return AbftReport(row_diff, col_diff, row_flag, col_flag,
                      jnp.sum(row_flag.astype(jnp.int32)),
                      jnp.sum(col_flag.astype(jnp.int32)))


def correction_mask(report: AbftReport) -> jax.Array:
    """Cross-combine flagged rows x cols into the paper's correction mask.

    Fig 10(a): all (flagged row, flagged col) intersections are treated as
    potential error sites. Conservative (a superset of true sites), which is
    safe because replacement values come from a near-identical checkpoint.
    """
    return jnp.outer(report.row_flag, report.col_flag)


# ----------------------------------------------------------------------------
# Tile-level checksums (the granularity the recovery scheduler works at)
# ----------------------------------------------------------------------------

def _pad_to(x: jax.Array, m: int, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block_indicator(n: int, t: int) -> jax.Array:
    """(n, n // t) int8 0/1 matrix: entry (i, b) is 1 iff i // t == b."""
    return (jnp.arange(n)[:, None] // t
            == jnp.arange(n // t)[None, :]).astype(jnp.int8)


def _digit_count(bound: int) -> int:
    """Balanced int8 digits needed for every integer of magnitude <= bound
    (four hold any int32 mod 2^32)."""
    count = 1
    while count < 4 and 127 * (256 ** count - 1) // 255 < bound:
        count += 1
    return count


def _digits(x: jax.Array, count: int):
    """``count`` int8 digits d_j with x == sum_j d_j * 2^(8j), mod 2^32.

    Each digit is the sign-extended low byte of the running remainder, so
    it lies in [-128, 127] and the remainder less the digit shifts down
    exactly; wrapping int32 keeps every step congruent mod 2^32."""
    digits = []
    for _ in range(count):
        d = (x << 24) >> 24
        digits.append(d.astype(jnp.int8))
        x = (x - d) >> 8
    return digits


def _wide_dot(lhs: jax.Array, rhs: jax.Array, wide: str, count: int = 4
              ) -> jax.Array:
    """lhs @ rhs in wrapping int32, where the ``wide`` operand ("lhs" or
    "rhs") is int32 and the other int8: one int8 MXU pass per int8 digit
    of the wide operand, recombined mod 2^32. Exact."""
    out = None
    for j, d in enumerate(_digits(lhs if wide == "lhs" else rhs, count)):
        part = jnp.dot(d if wide == "lhs" else lhs,
                       rhs if wide == "lhs" else d,
                       preferred_element_type=jnp.int32) << (8 * j)
        out = part if out is None else out + part
    return out


def tile_checksum_diff(acc: jax.Array, aq: jax.Array, bq: jax.Array,
                       cfg: AbftConfig) -> Tuple[jax.Array, jax.Array]:
    """Per-tile checksum differences, in the accumulator's own layout.

    Returns (row_diff, col_diff):
      row_diff: (Mp, Nt) -- per row, summed over each column tile
      col_diff: (Mt, Np) -- per column, summed over each row tile
    where Mt = ceil(M/tile_m), Nt = ceil(N/tile_n), Mp = Mt*tile_m and
    Np = Nt*tile_n (padded rows and columns read zero). Exact int32
    arithmetic.

    No intermediate has a tile-wide minor dimension, which on the TPU's
    128-lane vregs would relayout the whole accumulator. The column sums
    reduce a split of the row (sublane) dimension, which is free. The row
    sums and the expected sides are products with 0/1 block indicators on
    the MXU, their int32 operands entering as int8 digits (``_wide_dot``).
    """
    tm, tn = cfg.tile_m, cfg.tile_n
    aqp = _pad_to(aq, tm, 0)
    bqp = _pad_to(bq, tn, 1)
    accp = _pad_to(_pad_to(acc, tm, 0), tn, 1)
    ind_m = _block_indicator(accp.shape[0], tm)               # (Mp, Mt)
    ind_n = _block_indicator(accp.shape[1], tn)               # (Np, Nt)

    # Expected: A @ (B's column-tile row sums), (A's row-tile column sums) @ B
    b_blocksum = jnp.dot(bqp, ind_n, preferred_element_type=jnp.int32)
    a_blocksum = jnp.dot(ind_m.T, aqp, preferred_element_type=jnp.int32)
    exp_row = _wide_dot(aqp, b_blocksum, "rhs",
                        _digit_count(128 * tn))               # (Mp, Nt)
    exp_col = _wide_dot(a_blocksum, bqp, "lhs",
                        _digit_count(128 * tm))               # (Mt, Np)

    act_row = _wide_dot(accp, ind_n, "lhs")                   # (Mp, Nt)
    act_col = accp.reshape(ind_m.shape[1], tm, -1).sum(axis=1)  # (Mt, Np)
    return act_row - exp_row, act_col - exp_col


def tile_row_errors(row_diff: jax.Array, cfg: AbftConfig) -> jax.Array:
    """Rows whose whole-row checksum difference passes the threshold.

    A row's full difference is the wrapping sum of its tile differences,
    so this is ``detect_int(...).n_row_err`` without a second pass over
    the accumulator."""
    thr = jnp.int32(cfg.threshold)
    return jnp.sum(_exceeds(row_diff.sum(axis=1), thr).astype(jnp.int32))


def tile_error_mask(row_diff: jax.Array, col_diff: jax.Array,
                    cfg: AbftConfig, out_shape: Tuple[int, int]
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Element mask (M, N), per-tile flag (Mt, Nt) and the number of masked
    elements, from tile checksums.

    The row flags (Mp, Nt) and column flags (Mt, Np) expand to (Mp, Np)
    without a tile-wide minor dimension, through 0/1 block indicators in
    one MXU pass that counts each element's flagged row and column. The
    count comes from the flags' per-tile counts, so only the mask's one
    consumer reads the full mask.
    """
    thr = (jnp.int32(cfg.threshold) if row_diff.dtype == jnp.int32
           else jnp.float32(cfg.threshold))
    m, n = out_shape
    tm, tn = cfg.tile_m, cfg.tile_n
    row_flag = _exceeds(row_diff, thr)                        # (Mp, Nt)
    col_flag = _exceeds(col_diff, thr)                        # (Mt, Np)
    (mp, nt), (mt, np_) = row_flag.shape, col_flag.shape
    # hits[i, j] = row_flag[i, j // tn] + col_flag[i // tm, j], in one dot
    lhs = jnp.concatenate([row_flag, _block_indicator(mp, tm)], axis=1)
    rhs = jnp.concatenate([_block_indicator(np_, tn).T, col_flag], axis=0)
    hits = jnp.dot(lhs.astype(jnp.bfloat16), rhs.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)
    # flagged rows / columns per tile; padded rows and columns read zero
    # difference, so they are never flagged
    n_rows = row_flag.reshape(mt, tm, nt).sum(axis=1, dtype=jnp.int32)
    n_cols = col_flag.reshape(mt, nt, tn).sum(axis=2, dtype=jnp.int32)
    if cfg.mask_policy == "cross":
        mask = hits > 1
        per_tile = n_rows * n_cols
    else:  # union
        mask = hits > 0
        tile_rows = jnp.clip(m - tm * jnp.arange(mt), 0, tm)[:, None]
        tile_cols = jnp.clip(n - tn * jnp.arange(nt), 0, tn)[None, :]
        per_tile = (n_rows * tile_cols + n_cols * tile_rows
                    - n_rows * n_cols)
    return mask[:m, :n], per_tile > 0, jnp.sum(per_tile)
