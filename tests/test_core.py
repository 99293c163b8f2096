"""Unit + property tests for the DRIFT core (quant, fault, abft, dvfs,
rollback, exec context, baselines, repack, metrics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (abft, baselines, dvfs, exec_ctx, fault, metrics,
                        policies, quant, repack, rollback)


# ---------------------------------------------------------------- quant
def test_quant_roundtrip_error_bound():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 64)) * 3.0
    qt = quant.quantize(x)
    err = jnp.abs(qt.dequantize() - x)
    assert float(err.max()) <= float(qt.scale) * 0.5 + 1e-6


def test_quant_per_channel_scales():
    x = jnp.stack([jnp.ones(8) * 0.01, jnp.ones(8) * 100.0], axis=1)
    qt = quant.quantize(x, axis=1)
    assert qt.scale.shape == (1, 2)
    np.testing.assert_allclose(np.asarray(qt.dequantize()), np.asarray(x),
                               rtol=0.02)


def test_int32_accumulator_headroom():
    # Largest assigned contraction (gemma3 d_ff=21504) must not saturate.
    assert quant.quant_error_bound(21504) < 2 ** 31


# ---------------------------------------------------------------- fault
def test_fault_rate_matches_ber():
    key = jax.random.PRNGKey(1)
    acc = jnp.zeros((512, 512), jnp.int32)
    ber = 1e-3
    out = fault.inject_int32(acc, key, jnp.float32(ber))
    flipped = int(jnp.sum(out != 0))
    expect = 512 * 512 * 32 * ber  # one flip per word approximation
    assert 0.7 * expect < flipped < 1.3 * expect


def test_fault_zero_ber_is_identity():
    key = jax.random.PRNGKey(1)
    acc = jax.random.randint(key, (64, 64), -10000, 10000, dtype=jnp.int32)
    out = fault.inject_int32(acc, key, jnp.float32(0.0))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(acc))


def test_inject_at_deterministic():
    acc = jnp.zeros((8, 8), jnp.int32)
    out = fault.inject_at(acc, flat_index=9, bit=14)
    assert int(out.reshape(-1)[9]) == 1 << 14
    assert int(jnp.sum(out != 0)) == 1


# ---------------------------------------------------------------- abft
@settings(max_examples=30, deadline=None)
@given(bit=st.integers(min_value=0, max_value=31),
       idx=st.integers(min_value=0, max_value=64 * 48 - 1))
def test_abft_detects_iff_above_threshold(bit, idx):
    key = jax.random.PRNGKey(bit)
    a = jax.random.normal(key, (64, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 48))
    aq, wq = quant.quantize(a), quant.quantize(w, axis=1)
    acc = quant.int32_matmul(aq.q, wq.q)
    accf = fault.inject_at(acc, idx, bit)
    rep = abft.detect_int(accf, aq.q, wq.q, abft.AbftConfig(threshold_bit=10))
    detected = bool(rep.n_row_err > 0) and bool(rep.n_col_err > 0)
    assert detected == (bit >= 10)
    if detected:
        assert bool(rep.row_flag[idx // 48]) and bool(rep.col_flag[idx % 48])


def test_abft_error_free_no_flags():
    key = jax.random.PRNGKey(2)
    a = jax.random.normal(key, (128, 256)) * 5
    w = jax.random.normal(jax.random.fold_in(key, 1), (256, 64)) * 5
    aq, wq = quant.quantize(a), quant.quantize(w, axis=1)
    acc = quant.int32_matmul(aq.q, wq.q)
    rep = abft.detect_int(acc, aq.q, wq.q, abft.AbftConfig(threshold_bit=0))
    # exact integer checksums: zero diff even at threshold bit 0
    assert int(rep.n_row_err) == 0 and int(rep.n_col_err) == 0
    assert int(jnp.abs(rep.row_diff).max()) == 0


def test_abft_bit31_flip_detected():
    """abs(INT32_MIN) overflow regression: delta=-2^31 must still flag."""
    key = jax.random.PRNGKey(3)
    a = jax.random.normal(key, (32, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 32))
    aq, wq = quant.quantize(a), quant.quantize(w, axis=1)
    acc = quant.int32_matmul(aq.q, wq.q)
    accf = fault.inject_at(acc, 5, 31)
    rep = abft.detect_int(accf, aq.q, wq.q, abft.AbftConfig(threshold_bit=10))
    assert int(rep.n_row_err) >= 1 and int(rep.n_col_err) >= 1


def test_tile_checksums_match_global():
    key = jax.random.PRNGKey(4)
    aq = jax.random.randint(key, (64, 96), -127, 128, dtype=jnp.int8)
    bq = jax.random.randint(jax.random.fold_in(key, 1), (96, 64),
                            -127, 128, dtype=jnp.int8)
    acc = quant.int32_matmul(aq, bq)
    cfg = abft.AbftConfig(tile_m=32, tile_n=32)
    rd, cd = abft.tile_checksum_diff(acc, aq, bq, cfg)
    assert int(jnp.abs(rd).max()) == 0 and int(jnp.abs(cd).max()) == 0


# ---------------------------------------------------------------- dvfs
def test_ber_anchor_points():
    assert dvfs.ber_of(dvfs.NOMINAL) < 1e-10
    assert abs(dvfs.ber_of(dvfs.UNDERVOLT) - 3e-3) < 1e-4
    assert abs(dvfs.ber_of(dvfs.OVERCLOCK) - 3e-3) < 1e-4


def test_ber_monotone_in_voltage():
    bers = [dvfs.ber_of(dvfs.OperatingPoint(v, 2.0))
            for v in [0.65, 0.7, 0.75, 0.8, 0.85, 0.9]]
    assert all(b1 >= b2 for b1, b2 in zip(bers, bers[1:]))


def test_fine_grained_schedule_protects():
    sched = dvfs.fine_grained_schedule(10, dvfs.UNDERVOLT, nominal_steps=2)
    t = np.asarray(sched.ber_table)
    assert (t[:2] == 0).all()                         # first steps nominal
    assert (t[:, dvfs.CLASS_EMBED] == 0).all()        # embeddings nominal
    assert (t[2:, dvfs.CLASS_BODY] > 0).all()         # body aggressive


def test_ber_monitor_walks_ladder():
    st_ = dvfs.ber_monitor_init()
    # consistently hot measurements walk the index up
    for _ in range(5):
        st_ = dvfs.ber_monitor_update(st_, jnp.int32(1000), 4096, 10, 1e-4)
    assert int(st_.op_index) > 0
    # sustained cold measurements eventually walk it back down (EMA decay)
    for _ in range(80):
        st_ = dvfs.ber_monitor_update(st_, jnp.int32(0), 4096, 10, 1e-4)
    assert int(st_.op_index) == 0


# ------------------------------------------------------------- rollback
def test_rollback_interval_semantics():
    assert bool(rollback.should_checkpoint(jnp.int32(0), 10))
    assert bool(rollback.should_checkpoint(jnp.int32(10), 10))
    assert not bool(rollback.should_checkpoint(jnp.int32(5), 10))


def test_rollback_correct_fallback_zeroes():
    cur = jnp.ones((4, 4))
    mask = jnp.zeros((4, 4), bool).at[1, 2].set(True)
    out = rollback.correct(cur, None, mask, jnp.asarray(False))
    assert float(out[1, 2]) == 0.0 and float(out[0, 0]) == 1.0


# ------------------------------------------------------------- exec ctx
@pytest.mark.parametrize("mode", ["clean", "faulty", "drift", "thundervolt",
                                  "approx_abft", "dmr", "stat_abft"])
def test_exec_ctx_modes_run(mode):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 48))
    w = jax.random.normal(jax.random.fold_in(key, 1), (48, 64))
    ctx = exec_ctx.ExecContext(
        exec_ctx.DriftSystemConfig(mode=mode), key=key, step=3,
        ber_by_class=jnp.array([0.0, 0.0, 1e-3]),
        state_in={"g": x @ w}, have_ckpt=True)
    y = ctx.matmul(x, w, name="g")
    assert y.shape == (64, 64)
    assert not bool(jnp.any(jnp.isnan(y)))


def test_exec_ctx_drift_beats_faulty():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 48))
    w = jax.random.normal(jax.random.fold_in(key, 1), (48, 64))
    clean = exec_ctx.ExecContext(
        exec_ctx.DriftSystemConfig(mode="clean")).matmul(x, w, name="g")
    errs = {}
    for mode in ["faulty", "drift"]:
        ctx = exec_ctx.ExecContext(
            exec_ctx.DriftSystemConfig(mode=mode), key=key, step=3,
            ber_by_class=jnp.array([0.0, 0.0, 3e-3]),
            state_in={"g": clean}, have_ckpt=True)
        errs[mode] = float(jnp.abs(ctx.matmul(x, w, name="g") - clean).max())
    assert errs["drift"] < errs["faulty"] * 1e-3


def test_exec_ctx_jit_and_scan_compatible():
    """The context must be usable inside jit with threaded state."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (32, 32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 32))
    cfg = exec_ctx.DriftSystemConfig(mode="drift")

    @jax.jit
    def step(carry, step_idx):
        state, = carry
        ctx = exec_ctx.ExecContext(cfg, key=key, step=step_idx,
                                   ber_by_class=jnp.array([0., 0., 1e-3]),
                                   state_in=state, have_ckpt=step_idx > 0)
        y = ctx.matmul(x, w, name="g")
        return (ctx.state_out,), y

    carry = ({"g": jnp.zeros((32, 32))},)
    carry, ys = jax.lax.scan(step, carry, jnp.arange(4))
    assert ys.shape == (4, 32, 32)
    assert not bool(jnp.any(jnp.isnan(ys)))


# ------------------------------------------------------------ baselines
def test_baseline_costs_ordering():
    """DMR must charge more recompute than StatABFT; DRIFT charges none."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (64, 48))
    w = jax.random.normal(jax.random.fold_in(key, 1), (48, 64))
    costs = {}
    for mode in ["dmr", "stat_abft", "drift"]:
        ctx = exec_ctx.ExecContext(
            exec_ctx.DriftSystemConfig(mode=mode), key=key, step=3,
            ber_by_class=jnp.array([0.0, 0.0, 1e-3]),
            state_in={"g": x @ w}, have_ckpt=True)
        ctx.matmul(x, w, name="g")
        costs[mode] = float(ctx.stats["extra_compute_flops"])
    assert costs["dmr"] > costs["stat_abft"] > 0
    assert costs["drift"] == 0.0


# --------------------------------------------------------------- repack
@settings(max_examples=20, deadline=None)
@given(m=st.integers(min_value=1, max_value=70),
       n=st.integers(min_value=1, max_value=70),
       tm=st.sampled_from([8, 16, 32]),
       tn=st.sampled_from([8, 16, 32]))
def test_repack_roundtrip(m, n, tm, tn):
    x = jnp.arange(m * n, dtype=jnp.float32).reshape(m, n)
    xt = repack.repack(x, tm, tn)
    np.testing.assert_array_equal(np.asarray(repack.unpack(xt, (m, n), tm, tn)),
                                  np.asarray(x))


# -------------------------------------------------------------- metrics
def test_metrics_basics():
    key = jax.random.PRNGKey(0)
    img = jax.random.uniform(key, (2, 32, 32, 3)) * 2 - 1
    assert float(metrics.lpips_proxy(img, img)) == 0.0
    noisy = img + 0.5 * jax.random.normal(key, img.shape)
    d1 = float(metrics.lpips_proxy(img, img + 0.1 * jax.random.normal(key, img.shape)))
    d2 = float(metrics.lpips_proxy(img, noisy))
    assert d2 > d1 > 0.0
    assert float(metrics.psnr(img, img)) > 100
    assert float(metrics.ssim(img, img)) > 0.999


# ------------------------------------------------------------- policies
def test_policy_classification():
    pol = policies.PAPER_DEFAULT
    assert pol.classify("embed", 0) == dvfs.CLASS_EMBED
    assert pol.classify("block", 0) == dvfs.CLASS_FIRST_BLOCK
    assert pol.classify("block", 5) == dvfs.CLASS_BODY
