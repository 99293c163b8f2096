"""Property tests for the tile-contiguous repack transform and a
brute-force cross-check of the perfmodel's DRAM row accounting.

``core.repack`` is the layout the checkpoint-offload store ships
snapshots in (serving/offload/layout.py), so the round trip must be
exact for every shape -- including non-tile-aligned ones, where the
transform pads and the inverse crops -- and every dtype the stores
carry. The DRAM row counts in ``perfmodel.dram`` price tile recovery for
the planner and the energy model; on alignment-friendly (power-of-two)
geometries they must agree exactly with enumerating the DRAM row of
every element's byte address.
"""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import repack
from repro.perfmodel import dram


# ------------------------------------------------------------ round trip
@settings(max_examples=40, deadline=None)
@given(m=st.integers(1, 40), n=st.integers(1, 40),
       tm=st.integers(1, 9), tn=st.integers(1, 9),
       dtype=st.sampled_from(["float32", "int8", "int32", "bfloat16"]))
def test_repack_unpack_round_trip(m, n, tm, tn, dtype):
    """repack -> unpack is the identity for any shape/tile/dtype combo,
    aligned or not (padding is cropped away bit-exactly)."""
    x = jnp.arange(m * n).reshape(m, n).astype(dtype)
    xt = repack.repack(x, tm, tn)
    mt, nt = -(-m // tm), -(-n // tn)
    assert xt.shape == (mt, nt, tm * tn)
    assert xt.dtype == x.dtype
    back = repack.unpack(xt, (m, n), tm, tn)
    assert back.shape == (m, n) and back.dtype == x.dtype
    assert np.array_equal(np.asarray(back), np.asarray(x))


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 24), n=st.integers(1, 24),
       tm=st.integers(1, 8), tn=st.integers(1, 8))
def test_repack_tiles_are_contiguous_runs(m, n, tm, tn):
    """Each (ti, tj) slot of the repacked tensor is exactly the padded
    source tile flattened row-major -- the property that makes a tile
    read one contiguous run."""
    x = jnp.arange(m * n, dtype=jnp.float32).reshape(m, n)
    xp = np.asarray(repack.pad_to_tiles(x, tm, tn))
    xt = np.asarray(repack.repack(x, tm, tn))
    for ti in range(xt.shape[0]):
        for tj in range(xt.shape[1]):
            tile = xp[ti * tm:(ti + 1) * tm, tj * tn:(tj + 1) * tn]
            assert np.array_equal(xt[ti, tj], tile.reshape(-1))


def test_gather_tiles_zeroes_unflagged():
    x = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)
    xt = repack.repack(x, 4, 4)
    flags = jnp.asarray([[True, False], [False, True]])
    g = np.asarray(repack.gather_tiles(xt, flags))
    assert np.array_equal(g[0], np.asarray(xt).reshape(4, -1)[0])
    assert np.all(g[1] == 0) and np.all(g[2] == 0)
    assert np.array_equal(g[3], np.asarray(xt).reshape(4, -1)[3])


# ------------------------------------------- DRAM row-count cross-check
def _brute_force_rows_rowmajor(tm, tn, n_cols, elem_bytes, row_bytes):
    """Distinct DRAM rows touched by tile (0, 0) of a row-major matrix:
    enumerate every element's byte address."""
    return len({(i * n_cols + j) * elem_bytes // row_bytes
                for i in range(tm) for j in range(tn)})


def _brute_force_rows_repacked(tm, tn, elem_bytes, row_bytes):
    """Tile 0 of a tile-contiguous layout: one run from offset 0."""
    return len({k * elem_bytes // row_bytes for k in range(tm * tn)})


@settings(max_examples=40, deadline=None)
@given(n_cols=st.sampled_from([16, 64, 256, 512, 1024, 4096]),
       tm=st.sampled_from([1, 2, 4, 8, 16, 32]),
       tn=st.sampled_from([2, 4, 8, 16]))
def test_rows_per_tile_matches_brute_force_enumeration(n_cols, tm, tn):
    """On power-of-two geometries (tiles align with DRAM rows, the regime
    the closed forms model) the perfmodel row counts equal a brute-force
    enumeration of touched rows."""
    if tn > n_cols:
        return
    eb, rb = 4, 2048
    assert dram.rows_per_tile_rowmajor(tm, tn, n_cols, eb, rb) == \
        _brute_force_rows_rowmajor(tm, tn, n_cols, eb, rb)
    assert dram.rows_per_tile_repacked(tm, tn, eb, rb) == \
        _brute_force_rows_repacked(tm, tn, eb, rb)


def test_repack_speedup_matches_paper_shape():
    """The q_proj-class Fig 13(b) geometry: a 32x32 tile in a wide
    activation matrix -- row-major pays one row per matrix row, repacked
    packs the tile into ceil(4KiB / 2KiB) = 2 rows."""
    assert dram.rows_per_tile_rowmajor(32, 32, 1152) == 32
    assert dram.rows_per_tile_repacked(32, 32) == 2
    assert dram.repack_speedup(32, 32, 1152) == pytest.approx(16.0)
