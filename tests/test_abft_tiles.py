"""The tile checksums in the accumulator's own layout against the earlier
transposed formulation, kept here verbatim as the oracle.

The oracle reshapes the (M, N) accumulator to (Mt, tile_m, Nt, tile_n)
and takes the whole-row error count from a separate full-matrix pass
(``abft.detect_int``). The program takes the same sums without a tile-wide
minor dimension and derives the count from the tile row sums, so every
tile difference, mask element, tile flag and count must agree exactly,
and the samplers' outputs bit for bit on this backend.
"""
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import abft, dvfs, exec_ctx, fault, quant
from repro.core.exec_ctx import DriftSystemConfig
from repro.diffusion import sampler as sampler_lib
from repro.train import steps as steps_lib


# ------------------------------------------------------------------ oracle
def oracle_tile_checksum_diff(acc: jax.Array, aq: jax.Array, bq: jax.Array,
                              cfg: abft.AbftConfig
                              ) -> Tuple[jax.Array, jax.Array]:
    """Per-tile checksum differences.

    Returns (row_diff_t, col_diff_t):
      row_diff_t: (Mt, Nt, tile_m) -- per tile, per local row
      col_diff_t: (Mt, Nt, tile_n) -- per tile, per local col
    where Mt = ceil(M/tile_m), Nt = ceil(N/tile_n). Exact int32 arithmetic.
    """
    m, n = acc.shape
    k = aq.shape[1]
    tm, tn = cfg.tile_m, cfg.tile_n
    a32 = abft._pad_to(aq.astype(jnp.int32), tm, 0)
    b32 = abft._pad_to(bq.astype(jnp.int32), tn, 1)
    accp = abft._pad_to(abft._pad_to(acc, tm, 0), tn, 1)
    mt, nt = accp.shape[0] // tm, accp.shape[1] // tn
    acc_t = accp.reshape(mt, tm, nt, tn)

    # Expected per-tile row sums: A_tile @ (B col-block row-sum)
    b_blocksum = b32.reshape(k, nt, tn).sum(axis=2)          # (K, Nt)
    exp_row = jnp.einsum("mk,kn->mn", a32, b_blocksum,
                         preferred_element_type=jnp.int32)    # (Mp, Nt)
    exp_row_t = exp_row.reshape(mt, tm, nt).transpose(0, 2, 1)  # (Mt, Nt, tm)
    act_row_t = acc_t.sum(axis=3).transpose(0, 2, 1)            # (Mt, Nt, tm)

    a_blocksum = a32.reshape(mt, tm, k).sum(axis=1)          # (Mt, K)
    exp_col = jnp.einsum("mk,kn->mn", a_blocksum, b32,
                         preferred_element_type=jnp.int32)    # (Mt, Np)
    exp_col_t = exp_col.reshape(mt, nt, tn)                   # (Mt, Nt, tn)
    act_col_t = acc_t.sum(axis=1).reshape(mt, nt, tn)

    return act_row_t - exp_row_t, act_col_t - exp_col_t


def oracle_tile_error_mask(row_diff_t: jax.Array, col_diff_t: jax.Array,
                           cfg: abft.AbftConfig, out_shape: Tuple[int, int]
                           ) -> Tuple[jax.Array, jax.Array]:
    """Element mask (M, N) + per-tile flag (Mt, Nt) from tile checksums."""
    thr = jnp.int32(cfg.threshold) if row_diff_t.dtype == jnp.int32 else jnp.float32(cfg.threshold)
    row_flag = abft._exceeds(row_diff_t, thr)                 # (Mt, Nt, tm)
    col_flag = abft._exceeds(col_diff_t, thr)                 # (Mt, Nt, tn)
    if cfg.mask_policy == "cross":
        mask_t = row_flag[:, :, :, None] & col_flag[:, :, None, :]
    else:  # union
        mask_t = row_flag[:, :, :, None] | col_flag[:, :, None, :]
    tile_flag = jnp.any(mask_t, axis=(2, 3))                  # (Mt, Nt)
    mt, nt, tm = row_flag.shape
    tn = col_flag.shape[2]
    mask = mask_t.transpose(0, 2, 1, 3).reshape(mt * tm, nt * tn)
    return mask[: out_shape[0], : out_shape[1]], tile_flag


class _OracleRows(NamedTuple):
    """What the oracle carries from the checksum pass to the count and the
    mask: the transposed tile row differences and the full-matrix report."""
    row_diff_t: jax.Array
    report: abft.AbftReport


@pytest.fixture
def oracle_abft(monkeypatch):
    """Route ``ExecContext.matmul``'s drift branch through the oracle: tile
    sums in the transposed layout, the count from ``detect_int``."""
    def checksum_diff(acc, aq, bq, cfg):
        rd_t, cd_t = oracle_tile_checksum_diff(acc, aq, bq, cfg)
        return _OracleRows(rd_t, abft.detect_int(acc, aq, bq, cfg)), cd_t

    monkeypatch.setattr(abft, "tile_checksum_diff", checksum_diff)
    monkeypatch.setattr(abft, "tile_row_errors",
                        lambda rows, cfg: rows.report.n_row_err)

    def error_mask(rows, cd_t, cfg, shape):
        mask, tile_flag = oracle_tile_error_mask(rows.row_diff_t, cd_t, cfg,
                                                 shape)
        return mask, tile_flag, jnp.sum(mask.astype(jnp.int32))

    monkeypatch.setattr(abft, "tile_error_mask", error_mask)


# ------------------------------------------------------- the sums, exactly
def _operands(m, k, n, seed):
    key = jax.random.PRNGKey(seed)
    aq = jax.random.randint(key, (m, k), -127, 128, dtype=jnp.int8)
    bq = jax.random.randint(jax.random.fold_in(key, 1), (k, n), -127, 128,
                            dtype=jnp.int8)
    return key, aq, bq


def _accumulators(m, k, n, seed):
    """A full-range random int32 accumulator (every difference wraps), and
    the true product with flips planted at bits 9, 10, 30 and 31, two of
    them in one row and two in one column."""
    key, aq, bq = _operands(m, k, n, seed)
    full = jax.random.bits(jax.random.fold_in(key, 2), (m, n), jnp.uint32)
    full = jax.lax.bitcast_convert_type(full, jnp.int32)
    planted = quant.int32_matmul(aq, bq)
    rng = np.random.default_rng(seed)
    i, j = int(rng.integers(m)), int(rng.integers(n))
    sites = [(i, j, 9), (i, (j + 37) % n, 10),
             ((i + 1) % m, j, 30), (int(rng.integers(m)),
                                    int(rng.integers(n)), 31)]
    for r, c, bit in sites:
        planted = fault.inject_at(planted, r * n + c, bit)
    return aq, bq, {"full_range": full, "planted": planted}


@pytest.mark.parametrize("policy", ["union", "cross"])
@pytest.mark.parametrize("k", [96, 1152])
@pytest.mark.parametrize("n", [96, 160, 1152])
@pytest.mark.parametrize("m", [4, 64, 100])
def test_tile_sums_match_oracle(m, n, k, policy):
    cfg = abft.AbftConfig(mask_policy=policy)
    aq, bq, accs = _accumulators(m, k, n, seed=m * 7 + n + k)
    mt, nt = -(-m // cfg.tile_m), -(-n // cfg.tile_n)
    for name, acc in accs.items():
        rd_t, cd_t = oracle_tile_checksum_diff(acc, aq, bq, cfg)
        rd, cd = abft.tile_checksum_diff(acc, aq, bq, cfg)
        assert rd.shape == (mt * cfg.tile_m, nt)
        assert cd.shape == (mt, nt * cfg.tile_n)
        np.testing.assert_array_equal(
            np.asarray(rd), np.asarray(rd_t.transpose(0, 2, 1)
                                       ).reshape(-1, nt), err_msg=name)
        np.testing.assert_array_equal(
            np.asarray(cd), np.asarray(cd_t).reshape(mt, -1), err_msg=name)

        mask_o, flag_o = oracle_tile_error_mask(rd_t, cd_t, cfg, acc.shape)
        mask, flag, n_masked = abft.tile_error_mask(rd, cd, cfg, acc.shape)
        np.testing.assert_array_equal(np.asarray(mask), np.asarray(mask_o),
                                      err_msg=name)
        np.testing.assert_array_equal(np.asarray(flag), np.asarray(flag_o),
                                      err_msg=name)
        assert int(n_masked) == int(np.asarray(mask_o).sum()), name
        assert int(abft.tile_row_errors(rd, cfg)) == int(
            abft.detect_int(acc, aq, bq, cfg).n_row_err), name
    # the planted flips at bits 10, 30 and 31 are seen; the one at 9 is not
    _, _, n_masked = abft.tile_error_mask(
        *abft.tile_checksum_diff(accs["planted"], aq, bq, cfg), cfg,
        (m, n))
    assert int(n_masked) > 0


def test_digits_recombine_full_range():
    x = jax.lax.bitcast_convert_type(
        jax.random.bits(jax.random.PRNGKey(5), (256, 128), jnp.uint32),
        jnp.int32)
    x = x.at[0, :4].set(jnp.array([2**31 - 1, -2**31, -1, 0x7FFF7F80],
                                  jnp.int32))
    digits = abft._digits(x, 4)
    assert all(d.dtype == jnp.int8 for d in digits)
    back = sum(d.astype(jnp.int32) << (8 * j) for j, d in enumerate(digits))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))
    # two digits hold every block sum of a 32-wide tile of int8, both signs
    assert abft._digit_count(128 * 32) == 2
    assert abft._digit_count(32640) == 3
    edge = jnp.array([128 * 32, -128 * 32, 32639, -32640], jnp.int32)
    back = sum(d.astype(jnp.int32) << (8 * j)
               for j, d in enumerate(abft._digits(edge, 2)))
    np.testing.assert_array_equal(np.asarray(back), np.asarray(edge))


# ------------------------------------------------ the served path, bitwise
def _matmul(mode_ber):
    key = jax.random.PRNGKey(11)
    x = jax.random.normal(key, (100, 96))
    w = jax.random.normal(jax.random.fold_in(key, 1), (96, 160))
    ckpt = jax.random.normal(jax.random.fold_in(key, 2), (100, 160))
    ctx = exec_ctx.ExecContext(
        DriftSystemConfig(mode="drift"), key=key, step=3,
        ber_by_class=jnp.array([0.0, 0.0, mode_ber]),
        state_in={"g": ckpt}, have_ckpt=True)
    y = ctx.matmul(x, w, name="g")
    return jax.tree_util.tree_map(np.asarray, (y, ctx.state_out, ctx.stats))


@pytest.mark.parametrize("ber", [3e-3, 0.0], ids=["drift", "clean"])
def test_exec_ctx_bit_identical_to_oracle(ber, request):
    new = _matmul(ber)
    request.getfixturevalue("oracle_abft")
    old = _matmul(ber)
    jax.tree_util.tree_map(np.testing.assert_array_equal, new, old)
    if ber:
        assert new[2]["detected_row_errors"] > 0
        assert new[2]["corrected_elems"] > 0


def _sample(mode):
    cfg = configs.get_config("dit-xl-512", smoke=True)
    key = jax.random.PRNGKey(0)
    params = steps_lib.init_model_params(cfg, key)
    params["blocks"]["adaln_w"] = 0.1 * jax.random.normal(
        jax.random.fold_in(key, 1), params["blocks"]["adaln_w"].shape)
    lat0 = jax.random.normal(jax.random.fold_in(key, 3), (2, 8, 8, 4))
    scfg = sampler_lib.SamplerConfig(
        num_sample_steps=3, drift=DriftSystemConfig(mode=mode),
        schedule=dvfs.uniform_schedule(3, dvfs.UNDERVOLT))
    out = sampler_lib.sample(cfg, params, jax.random.PRNGKey(9), lat0,
                             jnp.array([1, 2]), None, scfg)
    return jax.tree_util.tree_map(np.asarray, out)


@pytest.mark.parametrize("mode", ["drift", "clean"])
def test_dit_sampler_bit_identical_to_oracle(mode, request):
    new = _sample(mode)
    request.getfixturevalue("oracle_abft")
    old = _sample(mode)
    jax.tree_util.tree_map(np.testing.assert_array_equal, new, old)
    if mode == "drift":
        assert new.heatmap.sum() > 0 and new.total_corrected > 0
