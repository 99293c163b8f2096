"""ExecContext: the composable execution layer for DRIFT.

Every model in ``repro.models`` routes its projections through
``ctx.matmul(x, w, name=..., rclass=...)``. The context decides, per call:

  * whether to run the float path or the quantized INT8->INT32 path,
  * the BER for this GEMM (from the fine-grained DVFS schedule: resilience
    class x current timestep),
  * fault injection (functional bit flips keyed by (step, site)),
  * detection + correction strategy (DRIFT rollback-ABFT or a baseline),
  * checkpoint-store reads/writes (rollback source, refreshed every n steps).

Each phase of ``matmul`` runs under a ``jax.named_scope`` (``drift.quantize``,
``drift.gemm``, ``drift.inject``, ``drift.checksum``, ``drift.correct``,
``drift.ckpt``), which a device trace keeps in the ops' metadata
(docs/tracing.md). Scopes change metadata only, never the computation.

The context is created fresh inside each traced step; its mutable Python
dicts are trace-time containers (a la Flax mutable collections): the caller
extracts ``ctx.state_out`` / ``ctx.stats`` and threads them through the
sampling scan carry.

Modes
-----
  float_clean  pure f32 matmuls (training / reference)
  clean        quantized path, no faults (the quality baseline "w/o DRIFT")
  faulty       quantized + fault injection, no protection (characterization)
  drift        quantized + faults + ABFT + rollback  (the paper's system)
  thundervolt / approx_abft / dmr / stat_abft        (Fig 12 baselines)
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import abft as abft_lib
from repro.core import baselines, fault, quant, rollback
from repro.core.dvfs import CLASS_BODY

MODES = ("float_clean", "clean", "faulty", "drift",
         "thundervolt", "approx_abft", "dmr", "stat_abft")


@dataclasses.dataclass(frozen=True)
class DriftSystemConfig:
    mode: str = "float_clean"
    abft: abft_lib.AbftConfig = dataclasses.field(default_factory=abft_lib.AbftConfig)
    rollback: rollback.RollbackConfig = dataclasses.field(default_factory=rollback.RollbackConfig)
    protect_attention_gemms: bool = False   # also wrap QK^T / AV batched GEMMs
    double_flip: bool = False
    force_bit: int = -1                     # pin flipped bit (Sec 4.1 sweeps)

    def __post_init__(self):
        assert self.mode in MODES, self.mode


def _site_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


class ExecContext:
    """Per-step execution context. Not a pytree; create inside the trace."""

    def __init__(self,
                 cfg: DriftSystemConfig,
                 key: Optional[jax.Array] = None,
                 step: jax.Array | int = 0,
                 ber_by_class: Optional[jax.Array] = None,
                 state_in: Optional[rollback.CkptStore] = None,
                 have_ckpt: jax.Array | bool = False):
        self.cfg = cfg
        self.key = key if key is not None else jax.random.PRNGKey(0)
        self.step = jnp.asarray(step, jnp.int32)
        # (N_CLASSES,) BERs for this step; zeros = error-free nominal point.
        self.ber_by_class = (ber_by_class if ber_by_class is not None
                             else jnp.zeros((3,), jnp.float32))
        self.state_in: rollback.CkptStore = state_in if state_in is not None else {}
        self.have_ckpt = jnp.asarray(have_ckpt, bool)
        self.state_out: rollback.CkptStore = {}
        self.stats: Dict[str, jax.Array] = {
            "detected_row_errors": jnp.int32(0),
            "corrected_elems": jnp.int32(0),
            "extra_compute_flops": jnp.float32(0.0),
            "extra_dram_bytes": jnp.float32(0.0),
            "gemm_words": jnp.int32(0),
        }
        self._names = set()

    # ------------------------------------------------------------------
    def matmul(self, x: jax.Array, w: jax.Array, *, name: str,
               rclass: int | jax.Array = CLASS_BODY) -> jax.Array:
        """Protected projection: x (..., K) @ w (K, N) -> (..., N)."""
        if self.cfg.mode == "float_clean":
            return x @ w

        lead = x.shape[:-1]
        k = x.shape[-1]
        n = w.shape[-1]
        x2 = x.reshape(-1, k)

        with jax.named_scope("drift.quantize"):
            xq = quant.quantize(x2, axis=None)
            wq = quant.quantize(w, axis=1)
            # Under pjit, gather FSDP-sharded int8 weights over the data
            # axis before the GEMM: int8 gathers cost half the clean path's
            # bf16 gathers, and the INT32 accumulator stays shard-local
            # (otherwise GSPMD all-reduces (M, N) int32 partial sums per
            # GEMM -- measured 3.4x collective blowup on the 512-chip drift
            # dry-run).
            from repro.distributed.constraints import constrain
            wq_q = constrain(wq.q, "w2d_model")
        w_scale = wq.scale.reshape(1, -1)
        with jax.named_scope("drift.gemm"):
            acc = quant.int32_matmul(xq.q, wq_q)

        if self.cfg.mode == "clean":
            with jax.named_scope("drift.gemm"):
                y = quant.dequantize_matmul(acc, xq.scale, w_scale)
                return y.reshape(*lead, n).astype(x.dtype)

        ber = self.ber_by_class[jnp.asarray(rclass, jnp.int32)]
        site = _site_id(name)
        with jax.named_scope("drift.inject"):
            fkey = fault.site_key(self.key, self.step, site, 0)
            acc_faulty = fault.inject_int32(acc, fkey, ber,
                                            double_flip=self.cfg.double_flip,
                                            force_bit=self.cfg.force_bit)

        if self.cfg.mode == "faulty":
            with jax.named_scope("drift.gemm"):
                y = quant.dequantize_matmul(acc_faulty, xq.scale, w_scale)
                return y.reshape(*lead, n).astype(x.dtype)

        if self.cfg.mode == "drift":
            # Tile-granular recovery (Sec 5.4): the recovery scheduler works
            # tile-by-tile, so the row x col cross-combine happens *within*
            # each systolic tile -- far sparser masks than a full-matrix
            # cross at high BER, and exactly what the Pallas kernel emits.
            # The whole-row count comes from the same tile sums.
            with jax.named_scope("drift.checksum"):
                rd, cd = abft_lib.tile_checksum_diff(acc_faulty, xq.q, wq_q,
                                                     self.cfg.abft)
                n_row_err = abft_lib.tile_row_errors(rd, self.cfg.abft)
                mask, tile_flag, n_corr = abft_lib.tile_error_mask(
                    rd, cd, self.cfg.abft, acc.shape)
        else:
            with jax.named_scope("drift.checksum"):
                report = abft_lib.detect_int(acc_faulty, xq.q, wq_q,
                                             self.cfg.abft)
                n_row_err = report.n_row_err
        with jax.named_scope("drift.checksum"):
            self._bump("detected_row_errors", n_row_err)
            self._bump("gemm_words", jnp.int32(acc.size))
        with jax.named_scope("drift.gemm"):
            y_faulty = quant.dequantize_matmul(acc_faulty, xq.scale, w_scale)

        if self.cfg.mode == "drift":
            with jax.named_scope("drift.correct"):
                ckpt = self.state_in.get(name)
                y = rollback.correct(y_faulty, ckpt, mask, self.have_ckpt)
                # DRAM cost: one repacked-tile read per flagged tile.
                tile_bytes = self.cfg.abft.tile_m * self.cfg.abft.tile_n * 4
                cost = baselines.RecoveryCost(
                    jnp.float32(0.0),
                    jnp.sum(tile_flag.astype(jnp.float32)) * tile_bytes,
                    n_corr)
            with jax.named_scope("drift.ckpt"):
                self._write_ckpt(name, y)
        elif self.cfg.mode == "thundervolt":
            with jax.named_scope("drift.correct"):
                y, cost = baselines.thundervolt(y_faulty, report)
        elif self.cfg.mode == "approx_abft":
            with jax.named_scope("drift.correct"):
                y, cost = baselines.approx_abft(y_faulty, report)
        elif self.cfg.mode == "dmr":
            with jax.named_scope("drift.gemm"):
                y_clean = quant.dequantize_matmul(acc, xq.scale, w_scale)
            with jax.named_scope("drift.correct"):
                y, cost = baselines.dmr(y_clean, report.n_row_err,
                                        gemm_flops=2.0 * x2.shape[0] * k * n)
        elif self.cfg.mode == "stat_abft":
            with jax.named_scope("drift.gemm"):
                y_clean = quant.dequantize_matmul(acc, xq.scale, w_scale)
            with jax.named_scope("drift.checksum"):
                rd, cd = abft_lib.tile_checksum_diff(acc_faulty, xq.q, wq_q,
                                                     self.cfg.abft)
                _, tile_flag, _ = abft_lib.tile_error_mask(
                    rd, cd, self.cfg.abft, acc.shape)
            with jax.named_scope("drift.correct"):
                y, cost = baselines.stat_abft(
                    y_clean, y_faulty, tile_flag,
                    tile_elems=self.cfg.abft.tile_m * self.cfg.abft.tile_n,
                    k_dim=k)
        else:  # pragma: no cover
            raise ValueError(self.cfg.mode)

        with jax.named_scope("drift.correct"):
            self._bump("corrected_elems", cost.corrected_elems)
            self._bump("extra_compute_flops", cost.extra_compute_flops)
            self._bump("extra_dram_bytes", cost.extra_dram_bytes)
        with jax.named_scope("drift.gemm"):
            return y.reshape(*lead, n).astype(x.dtype)

    # ------------------------------------------------------------------
    def bmm(self, a: jax.Array, b: jax.Array, *, name: str,
            rclass: int | jax.Array = CLASS_BODY) -> jax.Array:
        """Batched GEMM (attention scores / mixing). Protected only when
        ``protect_attention_gemms`` -- these are activation x activation
        GEMMs, so rollback uses the same named checkpoint slot."""
        if (self.cfg.mode == "float_clean"
                or not self.cfg.protect_attention_gemms):
            return a @ b
        lead = a.shape[:-2]
        a2 = a.reshape((-1,) + a.shape[-2:])
        b2 = b.reshape((-1,) + b.shape[-2:])
        # vmap would duplicate trace-time state writes; loop over a small
        # static batch instead (heads x batch is static under jit).
        outs = [self.matmul(a2[i], b2[i], name=f"{name}.{i}", rclass=rclass)
                for i in range(a2.shape[0])]
        y = jnp.stack(outs, axis=0)
        return y.reshape(*lead, *y.shape[-2:])

    # ------------------------------------------------------------------
    def _write_ckpt(self, name: str, y: jax.Array) -> None:
        do = rollback.should_checkpoint(self.step, self.cfg.rollback.interval)
        prev = self.state_in.get(name, jnp.zeros_like(y))
        self.state_out[name] = jnp.where(do, y, prev)

    def _bump(self, stat: str, v: jax.Array) -> None:
        self.stats[stat] = self.stats[stat] + v

    # ------------------------------------------------------------------
    @property
    def protected(self) -> bool:
        return self.cfg.mode not in ("float_clean", "clean")


def clean_ctx() -> ExecContext:
    """Convenience: pure-f32 context for training / dry-runs."""
    return ExecContext(DriftSystemConfig(mode="float_clean"))
