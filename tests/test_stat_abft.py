"""Property tests for the statistical-ABFT threshold (kernels/stat_abft.py).

The detection contract the AR serving path leans on (docs/servable.md):

* fault-free GEMMs NEVER trip the threshold -- the checksum residual of a
  clean product stays inside the calibrated rounding envelope for every
  dtype/shape combination (bounded false-positive rate; here: zero over
  the sampled space, by the gamma_K envelope's construction);
* an injected perturbation above ``min_detectable_magnitude`` (2x the
  row threshold) is ALWAYS detected, wherever the clean residual sits
  inside the envelope;
* perturbations far below the envelope sail through undetected -- that
  is the ReaLM point: decoding tolerates them, so detection (and the
  rollback replay it triggers) shouldn't fire.

Plus unit coverage for the quantized Pallas backend (exact INT32
checksums + magnitude cutoff) and the decode-loop execution context.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dvfs
from repro.kernels import stat_abft


def _operands(m, k, n, dtype, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((m, k)), dtype)
    w = jnp.asarray(rng.standard_normal((k, n)), dtype)
    return x, w


# ----------------------------------------------------------- float envelope
@settings(max_examples=40, deadline=None)
@given(m=st.integers(1, 9), k=st.integers(1, 96), n=st.integers(1, 96),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 5))
def test_clean_gemm_under_threshold(m, k, n, dtype, seed):
    """No false positives: the residual of a fault-free product stays
    inside the envelope for every shape/dtype sampled."""
    x, w = _operands(m, k, n, dtype, seed)
    y = x @ w
    flags = np.asarray(stat_abft.detect(x, w, y))
    assert not flags.any(), (
        f"clean GEMM flagged: residual "
        f"{np.asarray(stat_abft.residuals(x, w, y))} vs threshold "
        f"{np.asarray(stat_abft.threshold(x, w))}")


@settings(max_examples=40, deadline=None)
@given(m=st.integers(1, 9), k=st.integers(2, 96), n=st.integers(2, 96),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 5))
def test_flip_above_cutoff_detected(m, k, n, dtype, seed):
    """A single corrupted element whose magnitude clears the cutoff is
    detected in exactly its row, and nowhere else."""
    x, w = _operands(m, k, n, dtype, seed)
    y = x @ w
    rng = np.random.default_rng(seed + 1000)
    i, j = int(rng.integers(m)), int(rng.integers(n))
    delta = 2.0 * float(stat_abft.min_detectable_magnitude(x, w)[i])
    y_bad = y.astype(jnp.float32).at[i, j].add(delta)
    flags = np.asarray(stat_abft.detect(x, w, y_bad))
    assert flags[i], "above-cutoff corruption missed"
    assert flags.sum() == 1, "uncorrupted rows flagged"


@settings(max_examples=25, deadline=None)
@given(m=st.integers(1, 9), k=st.integers(2, 96), n=st.integers(2, 96),
       dtype=st.sampled_from(["float32", "bfloat16"]),
       seed=st.integers(0, 5))
def test_flip_far_below_cutoff_tolerated(m, k, n, dtype, seed):
    """Perturbations an order of magnitude under the envelope don't fire
    detection -- small numerical noise must not trigger rollbacks."""
    x, w = _operands(m, k, n, dtype, seed)
    y = x @ w
    delta = 0.05 * float(stat_abft.threshold(x, w)[0])
    y_bad = y.astype(jnp.float32).at[0, 0].add(delta)
    assert not np.asarray(stat_abft.detect(x, w, y_bad))[0]


# -------------------------------------------------------- quantized backend
def test_quantized_backend_exact_and_thresholded():
    """threshold_mag=0 reproduces exact ABFT on the Pallas kernel; a
    threshold above the flip magnitude suppresses the detection."""
    rng = np.random.default_rng(0)
    aq = rng.integers(-16, 16, (16, 16)).astype(np.int8)
    bq = rng.integers(-16, 16, (16, 16)).astype(np.int8)
    flips = np.zeros((16, 16), np.uint32)
    c, det = stat_abft.stat_abft_matmul(aq, bq, flips, threshold_mag=0,
                                        bm=8, bn=8, bk=8, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(c), aq.astype(np.int32) @ bq.astype(np.int32))
    assert not np.asarray(det).any()

    flips[3, 5] = np.uint32(1) << 30          # high-bit accumulator flip
    _, det2 = stat_abft.stat_abft_matmul(aq, bq, flips, threshold_mag=0,
                                         bm=8, bn=8, bk=8, interpret=True)
    det2 = np.asarray(det2)                   # (M, n_col_tiles)
    assert det2[3, 0] and det2.sum() == 1

    _, det3 = stat_abft.stat_abft_matmul(aq, bq, flips,
                                         threshold_mag=2 ** 31 - 1,
                                         bm=8, bn=8, bk=8, interpret=True)
    assert not np.asarray(det3).any()


# ------------------------------------------------------- decode-loop context
def test_stat_abft_context_detects_injected_faults():
    """The serving execution context: BER 0 returns the clean product with
    zero detections; an aggressive BER on the same site key both perturbs
    the output and reports detections."""
    from repro.serving.ar import StatAbftContext
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    key = jax.random.PRNGKey(0)

    ctx0 = StatAbftContext(key, jnp.int32(0),
                           jnp.zeros((dvfs.N_CLASSES,)), detect=True)
    y0 = ctx0.matmul(x, w, name="attn.q", rclass=dvfs.CLASS_BODY)
    np.testing.assert_array_equal(np.asarray(y0), np.asarray(x @ w))
    assert float(ctx0.stats["detected_rows"]) == 0.0
    assert float(ctx0.stats["gemm_words"]) == 4 * 128

    ctx1 = StatAbftContext(key, jnp.int32(0),
                           jnp.full((dvfs.N_CLASSES,), 3e-2), detect=True)
    y1 = ctx1.matmul(x, w, name="attn.q", rclass=dvfs.CLASS_BODY)
    assert float(ctx1.stats["detected_rows"]) > 0
    assert not np.array_equal(np.asarray(y1), np.asarray(y0))
