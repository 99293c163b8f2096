"""The DRIFT sampling loop: DDIM scan with fine-grained DVFS, rollback-ABFT
checkpointing, BER monitoring, and optional TaylorSeer caching.

This is the paper's end-to-end system (Fig 8): one lax.scan over denoising
steps whose carry holds (latents, rollback checkpoint stores, BER-monitor
state, TaylorSeer table). Per step:

  1. the DVFS schedule chooses the BER per resilience class
     (nominal for the first ``nominal_steps`` and for embedding GEMMs),
  2. the model runs with fault injection + ABFT + tile rollback
     (ExecContext inside the model),
  3. every ``interval`` steps the checkpoint stores refresh ("offload"),
  4. the BER monitor folds the step's detected-error count into its
     estimate (Sec 5.1 feedback loop),
  5. DDIM updates the latents.

Works for DiT/PixArt (scanned or unrolled blocks) and the SD1.5 UNet (flat
checkpoint store derived by eval_shape).

Two execution shapes share one step function:

  * ``sample`` / ``make_sampler()`` -- the whole chain as ONE ``lax.scan``
    (a single XLA while-loop, no host round-trips),
  * ``sample_stream`` / ``make_sampler(stream_window=k)`` -- the same scan
    chunked into windows of ``k`` steps, surfacing the carry's latents
    between windows as ``StreamEvent`` previews. The per-step math is
    identical, so the streamed chain's final latents are bit-identical to
    the one-shot scan (the serving tests assert this).

The carry layout, checkpoint-offload semantics, and the shard-aware
``make_sampler(mesh=...)`` contract are documented in ``docs/sampler.md``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dvfs as dvfs_lib
from repro.core import quant as quant_lib
from repro.core.exec_ctx import DriftSystemConfig, ExecContext
from repro.diffusion import schedule as sched_lib
from repro.diffusion import taylorseer as ts_lib
from repro.models import dit as dit_lib
from repro.models import unet as unet_lib
from repro.models.common import ModelConfig


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    num_sample_steps: int = 50
    num_train_steps: int = 1000
    drift: DriftSystemConfig = dataclasses.field(
        default_factory=lambda: DriftSystemConfig(mode="clean"))
    schedule: Optional[dvfs_lib.DvfsSchedule] = None   # None -> error-free
    taylorseer: ts_lib.TaylorSeerConfig = dataclasses.field(
        default_factory=lambda: ts_lib.TaylorSeerConfig(enabled=False))
    # Resilience-aware precision plan (core.quant.PRECISION_PLANS): the
    # default "int8" plan is a strict no-op (no extra op in the trace), so
    # pre-plan samplers are bit-identical. Narrowed plans fake-quantize
    # the denoiser output on resilient timesteps (step >= protect_steps)
    # -- the output-level simplification of layer-wise mixed precision.
    precision: quant_lib.PrecisionPlan = quant_lib.DEFAULT_PLAN
    monitor_target_ber: float = 3e-3
    # Fig 6 block-level study: per-layer / embed BER multipliers
    layer_gate: Optional[Any] = None
    embed_gate: Optional[Any] = None


class SampleOutput(NamedTuple):
    latents: jax.Array
    monitor: dvfs_lib.BerMonitorState
    total_corrected: jax.Array
    n_model_evals: jax.Array
    # Resilience heatmap: detected row errors per (denoising step, site),
    # shape (num_sample_steps, detection_rows(model_cfg)) int32 -- row 0 is
    # the embedding/conditioning GEMMs for DiT-family models, rows 1..L the
    # blocks; the UNet accumulates a single row. Batch-reduced (psum under
    # the sharded engine), and always computed in-trace, so recording it
    # never perturbs the latents. None for stub samplers that predate it.
    heatmap: Optional[jax.Array] = None


def detection_rows(model_cfg: ModelConfig) -> int:
    """Rows in the per-step detection heatmap: one per block plus one for
    the embedding/conditioning GEMMs (DiT family); the UNet's ExecContext
    accumulates a single scalar, so it gets one row."""
    if model_cfg.family == "unet":
        return 1
    return model_cfg.n_layers + 1


class StreamEvent(NamedTuple):
    """Intermediate preview from a streaming sampler: the carry's latents
    after ``step`` completed denoising steps (1-based, < num_sample_steps --
    the final state arrives as the terminating ``SampleOutput``, never as a
    ``StreamEvent``)."""
    step: int
    latents: jax.Array


def _model_eval(model_cfg: ModelConfig, params, latents, t, cond, text,
                drift_inputs, gates=(None, None)):
    """One denoiser evaluation, optionally DRIFT-protected."""
    scfg, key, step_idx, ber_by_class, stores, have_ckpt = drift_inputs
    if scfg.mode == "clean":
        # quantized error-free baseline == drift path at BER 0 (same GEMMs,
        # detections provably empty); reuse the store plumbing.
        scfg = dataclasses.replace(scfg, mode="drift")
        ber_by_class = jnp.zeros_like(ber_by_class)
    zero_rows = jnp.zeros((detection_rows(model_cfg),), jnp.int32)
    if scfg.mode == "float_clean":
        if model_cfg.family == "unet":
            return unet_lib.forward(model_cfg, params, latents, t, text), \
                stores, jnp.int32(0), jnp.int32(0), zero_rows
        eps, _, _ = dit_lib.forward(model_cfg, params, latents, t, cond,
                                    text=text)
        return eps, stores, jnp.int32(0), jnp.int32(0), zero_rows

    if model_cfg.family == "unet":
        ctx = ExecContext(scfg, key=key, step=step_idx,
                          ber_by_class=ber_by_class, state_in=stores,
                          have_ckpt=have_ckpt)
        eps = unet_lib.forward(model_cfg, params, latents, t, text, ctx=ctx)
        new_stores = ctx.state_out if ctx.state_out else stores
        detected = ctx.stats["detected_row_errors"]
        return eps, new_stores, ctx.stats["corrected_elems"], detected, \
            jnp.asarray(detected, jnp.int32)[None]

    embed_store, block_store = stores
    ds = dit_lib.DriftState(cfg=scfg, key=key, step=step_idx,
                            ber_by_class=ber_by_class,
                            embed_store=embed_store,
                            block_store=block_store, have_ckpt=have_ckpt,
                            layer_gate=gates[0], embed_gate=gates[1])
    eps, new_ds, stats = dit_lib.forward(model_cfg, params, latents, t, cond,
                                         text=text, drift=ds)
    corrected = stats.get("corrected_elems", jnp.int32(0))
    detected = stats.get("detected_row_errors", jnp.int32(0))
    det_blocks = stats.get("detected_per_block", zero_rows)
    # Modes that never write checkpoints (faulty / zeroing / recompute
    # baselines) return empty stores; keep the carry structure stable.
    new_embed = new_ds.embed_store if new_ds.embed_store else embed_store
    new_block = (new_ds.block_store
                 if jax.tree_util.tree_leaves(new_ds.block_store)
                 else block_store)
    return eps, (new_embed, new_block), corrected, detected, det_blocks


def init_stores(model_cfg: ModelConfig, params, latents, t, cond, text,
                scfg: DriftSystemConfig):
    """Zero checkpoint stores with the right structure (eval_shape, no run)."""
    if scfg.mode == "float_clean":
        return ()
    if model_cfg.family == "unet":
        def probe():
            ctx = ExecContext(dataclasses.replace(scfg, mode="drift"),
                              key=jax.random.PRNGKey(0), step=0,
                              ber_by_class=jnp.zeros(3), state_in={},
                              have_ckpt=False)
            unet_lib.forward(model_cfg, params, latents, t, text, ctx=ctx)
            return ctx.state_out
        spec = jax.eval_shape(probe)
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), spec)
    return dit_lib.drift_store_spec(model_cfg, latents.shape[0])


def _schedule_arrays(cfg: SamplerConfig):
    """(DDPM schedule, DDIM timesteps, next-timesteps, BER table) -- the
    trace-free per-run constants shared by one-shot and streamed sampling."""
    sched = sched_lib.DdpmSchedule.default(cfg.num_train_steps)
    ts = sched_lib.ddim_timesteps(cfg.num_train_steps, cfg.num_sample_steps)
    t_prev = np.concatenate([ts[1:], [-1]]).astype(np.int32)
    if cfg.schedule is not None:
        ber_table = cfg.schedule.ber_table
    else:
        ber_table = jnp.zeros((cfg.num_sample_steps, dvfs_lib.N_CLASSES))
    return sched, ts, t_prev, ber_table


def _init_carry(model_cfg: ModelConfig, params, latents0, cond, text,
                cfg: SamplerConfig, monitor0, ts):
    b = latents0.shape[0]
    t0 = jnp.full((b,), float(ts[0]), jnp.float32)
    with jax.named_scope("drift.ckpt"):
        stores0 = init_stores(model_cfg, params, latents0, t0, cond, text,
                              cfg.drift)
    taylor0 = ts_lib.init_state(latents0.shape)
    mon0 = monitor0 if monitor0 is not None else dvfs_lib.ber_monitor_init()
    # The corrected-element total is carried in float32: one step's count
    # fits int32, but a full-width batch's does not (DiT-XL/2 at bucket 4
    # rolls back about 2e10 elements in 20 undervolted steps, which wraps
    # int32). Exact up to 2^24, then to about 1 part in 10^7 per step.
    return (latents0, stores0, taylor0, mon0, jnp.float32(0), jnp.int32(0))


def _make_step_fn(model_cfg: ModelConfig, cfg: SamplerConfig, sched,
                  ber_table, params, key, cond, text):
    """One denoising step of the sampling scan. Everything step-dependent
    (step index, timesteps) flows through the scan inputs, so the SAME step
    function drives both the one-shot full-length scan and the chunked
    streaming windows -- that is what makes the two paths bit-identical."""

    def step_fn(carry, inp):
        latents, stores, taylor, mon, corrected, nevals = carry
        i, t_now, t_nxt = inp
        b = latents.shape[0]
        with jax.named_scope("sampler.step"):
            tvec = jnp.full((b,), t_now, jnp.float32)
            ber_by_class = ber_table[jnp.minimum(i, ber_table.shape[0] - 1)]
            drift_inputs = (cfg.drift, jax.random.fold_in(key, i), i,
                            ber_by_class, stores, i > 0)

        def do_compute(_):
            eps, new_stores, corr, detected, det_blocks = _model_eval(
                model_cfg, params, latents, tvec, cond, text, drift_inputs,
                gates=(cfg.layer_gate, cfg.embed_gate))
            new_taylor = ts_lib.update_on_compute(taylor, eps)
            return (eps, new_stores, new_taylor, corr, detected, det_blocks,
                    jnp.int32(1))

        def do_forecast(_):
            k = i % cfg.taylorseer.interval
            eps = ts_lib.forecast(taylor, k, cfg.taylorseer.interval,
                                  cfg.taylorseer.order)
            return (eps, stores, taylor, jnp.int32(0), jnp.int32(0),
                    jnp.zeros((detection_rows(model_cfg),), jnp.int32),
                    jnp.int32(0))

        if cfg.taylorseer.enabled:
            eps, stores2, taylor2, corr, detected, det_blocks, ran = \
                jax.lax.cond(ts_lib.should_compute(i, cfg.taylorseer),
                             do_compute, do_forecast, operand=None)
        else:
            eps, stores2, taylor2, corr, detected, det_blocks, ran = \
                do_compute(None)

        if cfg.precision.narrowed:
            # Narrowed precision plan: fake-quantize the denoiser output on
            # resilient timesteps only; the first ``protect_steps`` steps
            # stay full-width (the same protection window the DVFS schedule
            # gives ``nominal_steps``). Python-gated, so the default plan
            # adds nothing to the trace.
            qeps = quant_lib.fake_quant(eps, cfg.precision.body_bits)
            eps = jnp.where(i >= cfg.precision.protect_steps, qeps, eps)

        with jax.named_scope("sampler.step"):
            n_words = max(int(np.prod(latents.shape)), 1)
            mon2 = dvfs_lib.ber_monitor_update(
                mon, detected, n_words, cfg.drift.abft.threshold_bit,
                cfg.monitor_target_ber)
            new_latents = sched.ddim_step(latents, eps, t_now, t_nxt)
            corrected2 = corrected + corr.astype(jnp.float32)
            nevals2 = nevals + ran
        # The per-site detection vector rides the scan's ys slot: stacked
        # over steps it becomes the (steps, rows) resilience heatmap.
        return (new_latents, stores2, taylor2, mon2, corrected2, nevals2), \
            det_blocks

    return step_fn


def _scan_xs(ts, t_prev):
    return (jnp.arange(len(ts), dtype=jnp.int32),
            jnp.asarray(ts), jnp.asarray(t_prev))


def sample(model_cfg: ModelConfig, params, key: jax.Array,
           latents0: jax.Array, cond, text,
           cfg: SamplerConfig,
           monitor0: Optional[dvfs_lib.BerMonitorState] = None
           ) -> SampleOutput:
    """Run the full denoising chain from Gaussian latents.

    ``monitor0`` seeds the runtime BER monitor; passing the previous batch's
    ``SampleOutput.monitor`` carries the Sec 5.1 feedback loop across batches
    (the serving engine does), while ``None`` starts from a fresh estimate.
    """
    sched, ts, t_prev, ber_table = _schedule_arrays(cfg)
    carry0 = _init_carry(model_cfg, params, latents0, cond, text, cfg,
                         monitor0, ts)
    step_fn = _make_step_fn(model_cfg, cfg, sched, ber_table, params, key,
                            cond, text)
    (latents, _, _, mon, corrected, nevals), heatmap = jax.lax.scan(
        step_fn, carry0, _scan_xs(ts, t_prev))
    return SampleOutput(latents, mon, corrected, nevals, heatmap)


def sample_stream(model_cfg: ModelConfig, params, key: jax.Array,
                  latents0: jax.Array, cond, text,
                  cfg: SamplerConfig,
                  monitor0: Optional[dvfs_lib.BerMonitorState] = None,
                  window: int = 1,
                  on_window: Optional[Callable[[int], None]] = None,
                  on_carry: Optional[Callable[[int, Tuple], None]] = None,
                  _window_runner: Optional[Callable] = None):
    """Generator form of :func:`sample`: the same denoising scan chunked
    into windows of ``window`` steps, yielding a :class:`StreamEvent`
    (completed-step count + current latents) after every window except the
    last, then the final :class:`SampleOutput` as the terminating item.

    The per-step computation is the one-shot scan's step function verbatim
    (all step-dependent state rides the scan inputs), so the final latents
    are bit-identical to ``sample``'s. Call with ``_window_runner`` from
    ``make_sampler(stream_window=...)`` to drive a pre-jitted window (the
    serving path); without it each window scans un-jitted (fine for tests
    and small smoke runs). ``on_window`` is a host-side tap fired with the
    completed-step count after every window (including the last) -- the
    serving telemetry counts stream windows with it; it never runs inside
    a trace, so it cannot perturb the computation. ``on_carry`` is the
    same tap handed the full scan carry as well (completed steps, carry)
    -- the checkpoint-offload store snapshots the carry's rollback stores
    through it (``repro.serving.offload``); like ``on_window`` it runs
    strictly host-side between windows, so enabling it cannot change the
    computed latents.
    """
    assert window >= 1, window
    sched, ts, t_prev, ber_table = _schedule_arrays(cfg)
    carry = _init_carry(model_cfg, params, latents0, cond, text, cfg,
                        monitor0, ts)
    xs = _scan_xs(ts, t_prev)
    n = len(ts)

    if _window_runner is None:
        def _window_runner(params, key, cond, text, carry, xs_slice):
            step_fn = _make_step_fn(model_cfg, cfg, sched, ber_table,
                                    params, key, cond, text)
            return jax.lax.scan(step_fn, carry, xs_slice)

    heat_chunks = []
    for start in range(0, n, window):
        xs_slice = tuple(x[start:start + window] for x in xs)
        carry, heat = _window_runner(params, key, cond, text, carry, xs_slice)
        heat_chunks.append(heat)
        done = min(start + window, n)
        if on_carry is not None:
            on_carry(done, carry)
        if on_window is not None:
            on_window(done)
        if done < n:
            yield StreamEvent(step=done, latents=carry[0])
    latents, _, _, mon, corrected, nevals = carry
    # Concatenating the windows' stacked ys reproduces the one-shot scan's
    # (steps, rows) heatmap exactly -- integer counts, no accumulation
    # order to differ on.
    heatmap = jnp.concatenate(heat_chunks, axis=0)
    yield SampleOutput(latents, mon, corrected, nevals, heatmap)


def make_sampler(model_cfg: ModelConfig, cfg: SamplerConfig,
                 on_trace: Optional[Callable[[], None]] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 stream_window: int = 0,
                 on_window: Optional[Callable[[int], None]] = None,
                 on_carry: Optional[Callable[[int, Tuple], None]] = None):
    """Build a reusable jitted sampling entry point for one configuration.

    Returns ``run(params, key, latents0, cond, text, monitor0)`` ->
    ``SampleOutput``. The model/sampler configs are closed over, so repeated
    calls with same-shaped arrays never retrace: this is the unit the serving
    engine caches per (arch, steps, mode, operating point, batch bucket).

    ``on_trace`` fires once per (re)trace -- a Python side effect that only
    runs while JAX is staging the function, so the serving tests use it as an
    exact compile counter.

    ``mesh`` makes the sampler shard-aware (the ``ShardedDriftServeEngine``
    path): the latents batch is pinned to the mesh's data axes with
    ``repro.distributed.sharding.batch_spec`` on entry and exit, and the
    scalar outputs (BER-monitor state, corrected-element / model-eval
    counts) are pinned to replicated -- the detected-error sums feeding the
    monitor are reduced over the batch-sharded dimension, so GSPMD lowers
    them to a cross-device psum and every device carries the same ladder
    state. ``mesh=None`` is the single-device path, byte-for-byte the old
    behavior.

    ``stream_window=k`` (k >= 1) returns a STREAMING entry point instead:
    calling it yields :class:`StreamEvent` previews every ``k`` denoising
    steps and terminates with the :class:`SampleOutput` (see
    :func:`sample_stream`). One window of ``k`` steps is jitted once and
    reused for every full window of every call; a trailing partial window
    (when ``k`` does not divide the step count) is a second, shorter trace
    -- so a streamed configuration costs at most two traces where the
    one-shot sampler costs one. The serving engine keys its compiled-sampler
    cache on the window size (``SamplerKey.stream``). ``on_window`` (only
    meaningful with ``stream_window``) fires host-side after each completed
    window with the done-step count -- the serving telemetry's stream tap.
    ``on_carry`` additionally hands that tap the scan carry itself: the
    async checkpoint-offload store (``repro.serving.offload``) commits the
    carry's rollback stores host-side through it, overlapped with the next
    window. Both hooks run outside any trace and cannot change the
    computation.
    """
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.distributed import sharding as shd

        replicated = NamedSharding(mesh, PartitionSpec())

        def _pin_batch(x):
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, shd.batch_spec(x.shape, mesh)))

        def _pin_carry(carry):
            """Same placement contract as the one-shot wrapper, applied at
            window boundaries: latents on the data axes, monitor + scalar
            counters replicated; stores/taylor follow GSPMD propagation."""
            latents, stores, taylor, mon, corrected, nevals = carry
            pin_rep = lambda x: jax.lax.with_sharding_constraint(x,
                                                                 replicated)
            return (_pin_batch(latents), stores, taylor,
                    jax.tree.map(pin_rep, mon), pin_rep(corrected),
                    pin_rep(nevals))

    if stream_window:
        assert stream_window >= 1, stream_window
        sched, _, _, ber_table = _schedule_arrays(cfg)

        def _window(params, key, cond, text, carry, xs_slice):
            if on_trace is not None:
                on_trace()
            if mesh is not None:
                carry = _pin_carry(carry)
            step_fn = _make_step_fn(model_cfg, cfg, sched, ber_table,
                                    params, key, cond, text)
            carry, heat = jax.lax.scan(step_fn, carry, xs_slice)
            if mesh is not None:
                # Per-step detection rows are already batch-reduced sums,
                # so replicating them lowers to the same psum the monitor
                # state uses.
                carry = _pin_carry(carry)
                heat = jax.lax.with_sharding_constraint(heat, replicated)
            return carry, heat

        # the device trace names the executable jit_sample_<mode>_window
        _window.__name__ = f"sample_{cfg.drift.mode}_window"
        window_jit = jax.jit(_window)

        def _run_stream(params, key, latents0, cond, text, monitor0):
            return sample_stream(model_cfg, params, key, latents0, cond,
                                 text, cfg, monitor0=monitor0,
                                 window=stream_window, on_window=on_window,
                                 on_carry=on_carry,
                                 _window_runner=window_jit)
        return _run_stream

    def _run(params, key, latents0, cond, text, monitor0):
        if on_trace is not None:
            on_trace()
        if mesh is None:
            return sample(model_cfg, params, key, latents0, cond, text, cfg,
                          monitor0=monitor0)
        out = sample(model_cfg, params, key, _pin_batch(latents0), cond,
                     text, cfg, monitor0=monitor0)
        pin_rep = lambda x: jax.lax.with_sharding_constraint(x, replicated)
        return SampleOutput(
            latents=_pin_batch(out.latents),
            monitor=jax.tree.map(pin_rep, out.monitor),
            total_corrected=pin_rep(out.total_corrected),
            n_model_evals=pin_rep(out.n_model_evals),
            heatmap=pin_rep(out.heatmap))
    # the device trace names the executable jit_sample_<mode>: in a drift
    # batch, jit_sample_drift is the served sampler, jit_sample_clean the
    # clean twin
    _run.__name__ = f"sample_{cfg.drift.mode}"
    return jax.jit(_run)
