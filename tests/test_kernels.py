"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles.

Shape/dtype sweeps + property tests per the kernel contract: ABFT checksum
arithmetic must be bit-exact (int32 wraparound), rollback must cover every
injected above-threshold error (union policy, isolated flips).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import fault, quant
from repro.kernels import abft_matmul as ak
from repro.kernels import fault_inject as fik
from repro.kernels import ops, ref
from repro.kernels import rollback_correct as rk

SHAPES = [
    (32, 32, 32, 32, 32, 32),
    (64, 96, 128, 32, 32, 32),
    (128, 64, 64, 32, 64, 32),
    (96, 128, 96, 32, 32, 64),
    (256, 128, 128, 128, 128, 128),   # MXU-aligned production tile
    (64, 32, 64, 64, 64, 32),
]


def _rand_int8(key, shape):
    return jax.random.randint(key, shape, -127, 128, dtype=jnp.int8)


def _rand_flips(key, shape, p=0.01):
    k1, k2 = jax.random.split(key)
    hit = jax.random.uniform(k1, shape) < p
    pos = jax.random.randint(k2, shape, 0, 32, dtype=jnp.uint32)
    return jnp.where(hit, jnp.left_shift(jnp.uint32(1), pos), jnp.uint32(0))


@pytest.mark.parametrize("m,k,n,bm,bn,bk", SHAPES)
def test_abft_matmul_exact(m, k, n, bm, bn, bk):
    key = jax.random.PRNGKey(m * 7 + n)
    aq = _rand_int8(key, (m, k))
    bq = _rand_int8(jax.random.fold_in(key, 1), (k, n))
    flips = _rand_flips(jax.random.fold_in(key, 2), (m, n))
    got = ak.abft_matmul(aq, bq, flips, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.abft_matmul_ref(aq, bq, flips, bm, bn)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("m,k,n,bm,bn,bk", SHAPES[:4])
@pytest.mark.parametrize("union", [True, False])
def test_rollback_correct_matches_ref(m, k, n, bm, bn, bk, union):
    key = jax.random.PRNGKey(n)
    aq = _rand_int8(key, (m, k))
    bq = _rand_int8(jax.random.fold_in(key, 1), (k, n))
    flips = _rand_flips(jax.random.fold_in(key, 2), (m, n), p=0.02)
    c_f, ar, er, ac, ec = ref.abft_matmul_ref(aq, bq, flips, bm, bn)
    cf32 = c_f.astype(jnp.float32)
    ckpt = jax.random.normal(jax.random.fold_in(key, 3), (m, n))
    got_c, got_f = rk.rollback_correct(cf32, ckpt, ar - er, ac - ec,
                                       1 << 10, bm=bm, bn=bn, union=union,
                                       interpret=True)
    want_c, want_f = ref.rollback_correct_ref(cf32, ckpt, ar - er, ac - ec,
                                              1 << 10, bm, bn, union=union)
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))
    np.testing.assert_array_equal(np.asarray(got_f).astype(bool),
                                  np.asarray(want_f))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.int32])
@pytest.mark.parametrize("m,n", [(32, 64), (128, 128), (64, 256)])
def test_fault_inject_kernel(dtype, m, n):
    key = jax.random.PRNGKey(3)
    if dtype == jnp.float32:
        x = jax.random.normal(key, (m, n), dtype)
    else:
        x = jax.random.randint(key, (m, n), -1000, 1000, dtype=dtype)
    flips = _rand_flips(jax.random.fold_in(key, 1), (m, n), p=0.05)
    got = fik.fault_inject(x, flips, bm=32, bn=32, interpret=True)
    want_bits = jax.lax.bitcast_convert_type(x, jnp.uint32) ^ flips
    want = jax.lax.bitcast_convert_type(want_bits, dtype)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_drift_gemm_corrects_large_errors():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (100, 70))
    w = jax.random.normal(jax.random.fold_in(key, 1), (70, 90))
    clean = x @ w
    out = ops.drift_gemm(x, w, clean, jax.random.fold_in(key, 2),
                         jnp.float32(3e-3), bm=32, bn=32, bk=32,
                         interpret=True)
    # Residual error bounded by quantization noise + sub-threshold flips:
    # threshold 2^10 on the int accumulator ~ 2^10 * sx * sw in f32.
    xq = quant.quantize(x)
    wq = quant.quantize(w, axis=1)
    bound = float((1 << 11) * xq.scale * jnp.max(wq.scale)) + 1.0
    assert float(jnp.abs(out.y - clean).max()) < bound


def test_drift_gemm_clean_when_ber_zero():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (64, 64))
    out = ops.drift_gemm(x, w, None, key, jnp.float32(0.0),
                         bm=32, bn=32, bk=32, interpret=True)
    # no faults -> matches the quantized clean GEMM, zero flagged tiles
    y_clean, *_ = quant.quantized_matmul(x, w)
    np.testing.assert_allclose(np.asarray(out.y), np.asarray(y_clean),
                               rtol=1e-6)
    assert int(out.n_flagged_tiles) == 0


@settings(max_examples=25, deadline=None)
@given(bit=st.integers(min_value=10, max_value=31),
       row=st.integers(min_value=0, max_value=63),
       col=st.integers(min_value=0, max_value=63))
def test_single_high_flip_always_covered(bit, row, col):
    """Property: one isolated >=threshold flip is always detected & masked."""
    key = jax.random.PRNGKey(bit * 101 + row)
    aq = _rand_int8(key, (64, 32))
    bq = _rand_int8(jax.random.fold_in(key, 1), (32, 64))
    flips = jnp.zeros((64, 64), jnp.uint32).at[row, col].set(
        jnp.uint32(1) << jnp.uint32(bit))
    c_f, ar, er, ac, ec = ref.abft_matmul_ref(aq, bq, flips, 32, 32)
    _, mask_flag = ref.rollback_correct_ref(
        c_f.astype(jnp.float32), jnp.zeros((64, 64)), ar - er, ac - ec,
        1 << 10, 32, 32, union=True)
    assert bool(mask_flag[row // 32, col // 32])


@settings(max_examples=25, deadline=None)
@given(bit=st.integers(min_value=0, max_value=8),
       row=st.integers(min_value=0, max_value=63),
       col=st.integers(min_value=0, max_value=63))
def test_single_low_flip_never_flagged(bit, row, col):
    """Property: sub-threshold flips are left alone (Sec 4.1 tolerance)."""
    key = jax.random.PRNGKey(bit * 77 + col)
    aq = _rand_int8(key, (64, 32))
    bq = _rand_int8(jax.random.fold_in(key, 1), (32, 64))
    flips = jnp.zeros((64, 64), jnp.uint32).at[row, col].set(
        jnp.uint32(1) << jnp.uint32(bit))
    c_f, ar, er, ac, ec = ref.abft_matmul_ref(aq, bq, flips, 32, 32)
    corrected, flag = ref.rollback_correct_ref(
        c_f.astype(jnp.float32), jnp.zeros((64, 64)), ar - er, ac - ec,
        1 << 10, 32, 32, union=True)
    assert not bool(flag.any())
    np.testing.assert_array_equal(np.asarray(corrected),
                                  np.asarray(c_f, dtype=np.float32))
