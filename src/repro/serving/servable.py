"""ServableModel: the protocol between the serving engine and a paradigm.

The engine (``serving/engine.py``) owns everything paradigm-agnostic --
request queue, micro-batcher, compiled-fn cache, BER monitor, virtual
clock, telemetry, perfmodel attribution, offload plumbing. What it does
NOT own is how one micro-batch actually computes: how request seeds
become model inputs, what the compiled function looks like, how a batch
iterates (denoising steps vs decode windows), and how a finished batch
turns into per-request quality numbers and a perfmodel ``RunConfig``.
That surface is a ``ServableModel``:

  ================  =====================================================
  hook              contract
  ================  =====================================================
  validate_request  reject/coerce paradigm-irrelevant request fields at
                    submit time (clear errors, nothing silently ignored)
  batch_inputs      seeds -> stacked model inputs for one bucket (placed
                    on the engine's mesh via ``engine.place_inputs``)
  build_fn          ``CompiledSamplerCache`` builder: SamplerKey -> the
                    compiled callable(s) for one configuration
  execute           run one prepared micro-batch, return its output
  execute_stream    generator twin: previews, then ('final', output)
  finalize          output -> ``BatchOutcome`` (per-slot metrics + the
                    perfmodel RunConfig + telemetry word count)
  ================  =====================================================

Two implementations ship:

* ``DiffusionServable`` -- the DRIFT denoising path, code moved verbatim
  from the pre-refactor engine so finals stay bit-identical (the
  serving tests pin exact trace/compile counts and the CI legs compare
  latent digests single-device vs 8-fake-device).
* ``AutoregressiveServable`` -- token-by-token decode over
  ``models/transformer.py`` with ReaLM-style statistical ABFT on the
  projection GEMMs and KV-cache snapshot/rollback (``serving/ar.py``).

Families partition (``tests/test_servable.py`` asserts totality over
``configs.list_archs()``): dit/unet -> diffusion; dense/moe/ssm/hybrid ->
autoregressive; encdec/vlm -> explicitly unsupported (multi-modal input
staging the request schema has no fields for).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import dvfs as dvfs_lib
from repro.core import metrics
from repro.core import quant as quant_lib
from repro.core.exec_ctx import DriftSystemConfig
from repro.core.rollback import RollbackConfig
from repro.diffusion import sampler as sampler_lib
from repro.diffusion.taylorseer import TaylorSeerConfig
from repro.perfmodel import energy
from repro.serving.cache import SamplerKey

# ---------------------------------------------------------------- registry
# family -> serving paradigm. Every config family must appear in exactly
# one of these two tables; test_servable.py asserts the partition is total
# so a new config can't silently fall through to a confusing trace error.
PARADIGM_BY_FAMILY: Dict[str, str] = {
    "dit": "diffusion",
    "unet": "diffusion",
    "dense": "autoregressive",
    "moe": "autoregressive",
    "ssm": "autoregressive",
    "hybrid": "autoregressive",
}

# family -> reason it cannot be served (named explicitly, not inferred).
UNSUPPORTED_FAMILIES: Dict[str, str] = {
    "encdec": "encoder-decoder models need an audio/encoder input the "
              "request schema has no fields for (use launch/train.py)",
    "vlm": "vision-language models need image inputs the request schema "
           "has no fields for (use launch/train.py)",
}


class UnsupportedArchError(ValueError):
    """Raised at submit time for archs no ServableModel family covers."""


def paradigm_for(arch: str) -> str:
    """Serving paradigm for an arch name; raises UnsupportedArchError with
    the registry's reason when the family is explicitly unsupported."""
    family = configs.get_config(arch).family
    paradigm = PARADIGM_BY_FAMILY.get(family)
    if paradigm is None:
        reason = UNSUPPORTED_FAMILIES.get(
            family, f"family {family!r} is not in the ServableModel "
                    "registry (add it to servable.PARADIGM_BY_FAMILY or "
                    "servable.UNSUPPORTED_FAMILIES)")
        raise UnsupportedArchError(f"arch {arch!r}: {reason}")
    return paradigm


# ---------------------------------------------------------------- protocol
@dataclasses.dataclass
class BatchOutcome:
    """What ``finalize`` hands back to the engine's generic accounting."""
    corrected: int                 # rollback-corrected elems / replayed slots
    n_model_evals: int             # computed steps (incl. rollback replays)
    rc: energy.RunConfig           # perfmodel run shape for this batch
    n_words: int                   # telemetry BER denominator (GEMM words)
    per_slot: List[dict]           # extra RequestResult fields per live slot
    # Resilience heatmap summary (serving/trace/heatmap.py): nested tuple
    # (sites, timestep bins) of detection counts plus its row labels; None
    # when the batch produced none (stub samplers, unmonitored paths).
    heatmap: Optional[tuple] = None
    heatmap_blocks: Optional[tuple] = None


class ServableModel:
    """Base protocol; subclasses hold a back-reference to their engine."""

    paradigm: str = ""
    #: Whether ``run_stream`` previews exist for this paradigm.
    supports_streaming: bool = False

    def __init__(self, engine):
        self.eng = engine

    # -- intake --------------------------------------------------------
    def validate_request(self, fields: dict) -> dict:
        """Check paradigm-irrelevant knobs before enqueueing; return the
        (possibly coerced) fields or raise ValueError."""
        return fields

    # -- batch construction -------------------------------------------
    def batch_inputs(self, model_cfg, seeds: List[int]) -> Tuple:
        raise NotImplementedError

    def build_fn(self, key: SamplerKey) -> Callable:
        """CompiledSamplerCache builder for one configuration."""
        raise NotImplementedError

    # -- execution -----------------------------------------------------
    def execute(self, mb, ctx):
        """Run one prepared micro-batch; returns the batch output object
        (must expose ``.monitor`` for monitored modes)."""
        raise NotImplementedError

    def execute_stream(self, mb, ctx, preview_interval: int) -> Iterator:
        """Yield ``PreviewEvent``s, then ``('final', output)``."""
        raise NotImplementedError

    # -- accounting ----------------------------------------------------
    def finalize(self, mb, ctx, out) -> BatchOutcome:
        raise NotImplementedError


# ---------------------------------------------------------- diffusion path
class DiffusionServable(ServableModel):
    """The DRIFT denoising path, re-expressed through the protocol.

    Every method body is the pre-refactor engine code moved here intact
    (same fold-in constants, same clip points, same cache-key edits), so
    diffusion finals are bit-identical to PR 5 -- the refactor moved
    code, it did not touch math.
    """

    paradigm = "diffusion"
    supports_streaming = True

    # (validate_request: the base identity -- every GenerationRequest
    # field is diffusion-meaningful; modes are validated by
    # DriftSystemConfig at build time.)

    # -- batch construction -------------------------------------------
    def batch_inputs(self, model_cfg, seeds: List[int]) -> Tuple:
        """Per-request initial latents + conditioning, stacked to the
        bucket and placed via the engine (mesh batch-spec when sharded)."""
        shape = (model_cfg.latent_size, model_cfg.latent_size,
                 model_cfg.latent_channels)
        lat = jnp.stack([
            jax.random.normal(jax.random.fold_in(jax.random.PRNGKey(s), 7),
                              shape) for s in seeds])
        if model_cfg.cond_tokens:
            text = jnp.stack([
                0.1 * jax.random.normal(
                    jax.random.fold_in(jax.random.PRNGKey(s), 8),
                    (model_cfg.cond_tokens, model_cfg.cond_dim))
                for s in seeds])
            return self.eng.place_inputs((lat, None, text))
        cond = jnp.asarray([s % max(model_cfg.num_classes, 1) for s in seeds],
                           dtype=jnp.int32)
        return self.eng.place_inputs((lat, cond, None))

    def build_fn(self, key: SamplerKey) -> Callable:
        eng = self.eng
        model_cfg = configs.get_config(key.arch, smoke=key.smoke)
        if key.mode == "clean" or not key.op:
            schedule = None
        else:
            from repro.serving.engine import OP_BY_NAME
            schedule = dvfs_lib.fine_grained_schedule(
                key.steps, OP_BY_NAME[key.op],
                nominal_steps=eng.nominal_steps)
        scfg = sampler_lib.SamplerConfig(
            num_sample_steps=key.steps,
            drift=DriftSystemConfig(
                mode=key.mode,
                rollback=RollbackConfig(interval=key.rollback_interval)),
            schedule=schedule,
            taylorseer=TaylorSeerConfig(enabled=key.taylorseer),
            # protect_steps rides the engine's nominal_steps so the
            # precision protection window matches the DVFS one
            precision=quant_lib.get_plan(key.precision).with_protect_steps(
                eng.nominal_steps),
            monitor_target_ber=eng.monitor_target_ber)
        return eng._sampler_factory(key, model_cfg, scfg,
                                    eng.cache.note_trace)

    def _clean_reference(self, key: SamplerKey, seeds: Tuple[int, ...],
                         params, latents, cond, text) -> jax.Array:
        """Error-free reference latents for this batch, cached by
        (configuration, latent seeds) in the engine's bounded LRU."""
        eng = self.eng
        # stream=0: previews never need a reference, and streamed finals
        # are bit-identical to one-shot, so both share one clean sample.
        # precision="int8": references are always full-width -- a narrowed
        # run is scored against the error-free full-precision sample.
        ckey = dataclasses.replace(key, mode="clean", op="", stream=0,
                                   precision="int8")
        sample_id = (ckey, seeds)
        cached = eng._clean_samples.get(sample_id)
        if cached is not None:
            eng._clean_samples.move_to_end(sample_id)
            eng.stats.clean_sample_hits += 1
            return cached
        fn = eng.cache.get(ckey, self.build_fn)
        out = fn(params, jax.random.PRNGKey(0), latents, cond, text,
                 dvfs_lib.ber_monitor_init())
        clean = jnp.clip(out.latents, -1, 1)
        eng._clean_samples[sample_id] = clean
        while len(eng._clean_samples) > eng._clean_cache_size:
            eng._clean_samples.popitem(last=False)
        eng.stats.clean_samples_computed += 1
        return clean

    # -- execution -----------------------------------------------------
    def execute(self, mb, ctx):
        eng = self.eng
        store = eng._offload_for(mb.key)
        if store is None:
            fn = eng.cache.get(mb.key, self.build_fn)
            latents, cond, text = ctx.inputs
            return fn(ctx.params, ctx.run_key, latents, cond, text,
                      eng.monitor)
        # Offload-enabled one-shot path: run the windowed sampler with the
        # refresh interval as the window so every committed snapshot
        # offloads between windows, overlapped with the next window's
        # dispatch. Streamed finals are bit-identical to the one-shot
        # scan (the PR 3 invariant), so enabling offload cannot change a
        # single latent bit -- tests/test_offload.py asserts exactly that.
        window = min(mb.key.rollback_interval, mb.key.steps)
        skey = dataclasses.replace(mb.key, stream=window)
        fn = eng.cache.get(skey, self.build_fn)
        out = None
        store.begin_batch(interval=mb.key.rollback_interval,
                          batch_index=ctx.batch_index)
        eng._active_offload = store
        try:
            latents, cond, text = ctx.inputs
            for ev in fn(ctx.params, ctx.run_key, latents, cond, text,
                         eng.monitor):
                if isinstance(ev, sampler_lib.SampleOutput):
                    out = ev           # previews are discarded: run() only
        finally:
            eng._active_offload = None
            # join the in-flight commit; the settled delta feeds the
            # telemetry tap in _finish_batch
            ctx.offload_delta = store.finish_batch()
        assert out is not None, "offload sampler ended without SampleOutput"
        return out

    def execute_stream(self, mb, ctx, preview_interval: int) -> Iterator:
        from repro.serving.request import PreviewEvent
        eng = self.eng
        skey = dataclasses.replace(mb.key, stream=preview_interval)
        fn = eng.cache.get(skey, self.build_fn)
        out = None
        store = eng._offload_for(mb.key)
        if store is not None:
            # commits ride the preview windows: the store itself decides
            # which window boundaries crossed a refresh step
            store.begin_batch(interval=mb.key.rollback_interval,
                              batch_index=ctx.batch_index)
            eng._active_offload = store
        try:
            latents, cond, text = ctx.inputs
            for ev in fn(ctx.params, ctx.run_key, latents, cond, text,
                         eng.monitor):
                if isinstance(ev, sampler_lib.SampleOutput):
                    out = ev
                    break           # terminating item; nothing follows
                preview = jnp.clip(ev.latents, -1, 1)
                for slot, req in enumerate(mb.requests):  # live slots only
                    eng.stats.preview_events += 1
                    eng.telemetry.on_preview()
                    yield PreviewEvent(request_id=req.request_id,
                                       batch_index=ctx.batch_index,
                                       step=int(ev.step),
                                       total_steps=mb.key.steps,
                                       latents=preview[slot])
        finally:
            if store is not None:
                eng._active_offload = None
                ctx.offload_delta = store.finish_batch()
        assert out is not None, "streaming sampler ended without SampleOutput"
        yield ("final", out)

    # -- accounting ----------------------------------------------------
    def finalize(self, mb, ctx, out) -> BatchOutcome:
        from repro.serving.engine import OP_BY_NAME, _MONITORED_MODES
        key = mb.key
        tracer = self.eng.tracer
        latents, cond, text = ctx.inputs
        img = jnp.clip(out.latents, -1, 1)
        if key.mode == "clean":
            clean = img       # the run IS the reference; don't jit a twin
        else:
            with tracer.phase("batch.twin"):
                clean = self._clean_reference(key, ctx.padded_seeds,
                                              ctx.params, latents, cond, text)
        from repro.serving.trace import heatmap as heatmap_lib
        with tracer.phase("batch.sync"):    # the first blocking reads
            corrected = int(out.total_corrected)
            nevals = int(out.n_model_evals)
            heat, blocks = heatmap_lib.summarize(getattr(out, "heatmap",
                                                         None))
        op_point = OP_BY_NAME.get(key.op, dvfs_lib.NOMINAL)
        # only protected modes pay ABFT compute + checkpoint DRAM traffic;
        # clean/faulty/float_clean run neither mechanism
        protected = key.mode in _MONITORED_MODES
        rc = energy.RunConfig(
            num_steps=key.steps, nominal_steps=self.eng.nominal_steps,
            aggressive=op_point,
            ckpt_interval=key.rollback_interval if protected else 10 ** 9,
            abft_enabled=protected,
            taylorseer_interval=3 if key.taylorseer else 0,
            body_bits=quant_lib.get_plan(key.precision).body_bits,
            recovery_tiles_per_step=corrected / max(key.steps, 1)
            / (32 * 32))
        per_slot = []
        with tracer.phase("batch.quality"):
            for slot, req in enumerate(mb.requests):
                a, b = img[slot:slot + 1], clean[slot:slot + 1]
                per_slot.append(dict(
                    lpips_vs_clean=float(metrics.lpips_proxy(a, b)),
                    psnr_vs_clean_db=float(metrics.psnr(a, b)),
                    latents=a[0]))
        return BatchOutcome(
            corrected=corrected, n_model_evals=nevals, rc=rc,
            n_words=int(latents.size) * max(key.steps, 1),
            per_slot=per_slot, heatmap=heat, heatmap_blocks=blocks)


# ----------------------------------------------------- autoregressive path
class AutoregressiveServable(ServableModel):
    """Token-by-token decode with statistical ABFT + KV-cache rollback.

    The heavy lifting -- compiled prefill/window functions, the
    detection-only statistical-ABFT execution context, the KV snapshot
    store, and the host decode loop -- lives in ``serving/ar.py``; this
    adapter maps it onto the protocol so the engine's queue, cache,
    monitor, scheduler, and telemetry drive it unchanged.
    """

    paradigm = "autoregressive"
    supports_streaming = False

    #: modes the AR path implements. "drift" (inline tile rollback) is a
    #: diffusion mechanism; the AR protection story is detection + window
    #: rollback, so everything else is rejected at submit time.
    ALLOWED_MODES = ("clean", "faulty", "stat_abft")

    # -- intake --------------------------------------------------------
    def validate_request(self, fields: dict) -> dict:
        arch = fields.get("arch", "?")
        if fields.get("taylorseer"):
            raise ValueError(
                f"request for AR arch {arch!r} sets taylorseer=True: "
                "TaylorSeer caches diffusion denoiser features across "
                "timesteps and does not apply to token decoding. Drop the "
                "flag (or serve a dit/unet arch).")
        if fields.get("precision", "int8") != "int8":
            raise ValueError(
                f"request for AR arch {arch!r} sets precision="
                f"{fields['precision']!r}: precision plans narrow the "
                "diffusion denoiser body per timestep and do not apply to "
                "token decoding. Use the default 'int8' (or serve a "
                "dit/unet arch).")
        if fields.get("energy_budget_j") is not None \
                or fields.get("quality_floor") is not None:
            raise ValueError(
                f"request for AR arch {arch!r} sets a frontier objective "
                "(energy_budget_j/quality_floor): the compute-optimal "
                "frontier enumerates diffusion knobs (steps x precision x "
                "TaylorSeer x DVFS) and is not built for autoregressive "
                "serving. Use deadline_s/step_budget instead.")
        mode = fields.get("mode", "drift")
        if mode not in self.ALLOWED_MODES:
            raise ValueError(
                f"request for AR arch {arch!r} has mode={mode!r}: "
                "autoregressive serving supports modes "
                f"{'/'.join(self.ALLOWED_MODES)} (statistical ABFT with "
                "KV-cache window rollback). Diffusion-only modes like "
                "'drift' do inline tile rollback inside the denoiser and "
                "do not apply to decode.")
        return fields

    # -- batch construction -------------------------------------------
    def batch_inputs(self, model_cfg, seeds: List[int]) -> Tuple:
        from repro.serving import ar
        tokens = ar.prompt_tokens(model_cfg, seeds)
        return self.eng.place_inputs((tokens,))

    def build_fn(self, key: SamplerKey):
        from repro.serving import ar
        from repro.serving.engine import OP_BY_NAME
        eng = self.eng
        model_cfg = configs.get_config(key.arch, smoke=key.smoke)
        if key.mode == "clean" or not key.op:
            schedule = None
        else:
            schedule = dvfs_lib.fine_grained_schedule(
                key.steps, OP_BY_NAME[key.op],
                nominal_steps=eng.nominal_steps)
        return ar.make_decoder(
            model_cfg,
            ar.DecodeConfig(
                steps=key.steps,
                window=min(int(key.rollback_interval), key.steps),
                mode=key.mode,
                monitor_target_ber=eng.monitor_target_ber),
            schedule=schedule,
            on_trace=eng.cache.note_trace,
            mesh=getattr(eng, "mesh", None))

    # -- execution -----------------------------------------------------
    def execute(self, mb, ctx):
        from repro.serving import ar
        eng = self.eng
        fns = eng.cache.get(mb.key, self.build_fn)
        (tokens,) = ctx.inputs
        tracer = getattr(eng, "tracer", None)
        if tracer is None:
            return ar.decode_batch(fns, ctx.params, tokens, eng.monitor,
                                   ctx.run_key)

        # Window/replay spans carry joules (docs/slo.md): decoded tokens
        # use the engine's per-step estimate for the window just finished,
        # replays charge their re-decoded window length at the same rate.
        def on_window(done_steps: int) -> None:
            tracer.on_window(done_steps,
                             energy_j=eng._window_energy_delta_j(done_steps))

        def on_replay(window_start: int, window_len: int) -> None:
            tracer.on_replay(window_start, window_len,
                             energy_j=window_len * eng._window_step_j)

        return ar.decode_batch(
            fns, ctx.params, tokens, eng.monitor, ctx.run_key,
            on_window=on_window, on_replay=on_replay)

    def execute_stream(self, mb, ctx, preview_interval: int) -> Iterator:
        raise ValueError(
            "run_stream() previews are latent images -- a diffusion "
            "mechanism. Autoregressive requests return their tokens in "
            "RequestResult.tokens via run().")

    def _clean_tokens(self, mb, ctx):
        """Fault-free reference decode for this (configuration, prompts)
        batch, cached in the engine's clean-sample LRU exactly like the
        diffusion clean reference (stream forced to 0 for key hygiene)."""
        from repro.serving import ar
        eng = self.eng
        key = mb.key
        ckey = dataclasses.replace(key, mode="clean", op="", stream=0)
        sample_id = (ckey, ctx.padded_seeds)
        cached = eng._clean_samples.get(sample_id)
        if cached is not None:
            eng._clean_samples.move_to_end(sample_id)
            eng.stats.clean_sample_hits += 1
            return cached
        fns = eng.cache.get(ckey, self.build_fn)
        (tokens,) = ctx.inputs
        out = ar.decode_batch(fns, ctx.params, tokens,
                              dvfs_lib.ber_monitor_init(),
                              jax.random.PRNGKey(0))
        clean = out.tokens
        eng._clean_samples[sample_id] = clean
        while len(eng._clean_samples) > eng._clean_cache_size:
            eng._clean_samples.popitem(last=False)
        eng.stats.clean_samples_computed += 1
        return clean

    # -- accounting ----------------------------------------------------
    def finalize(self, mb, ctx, out) -> BatchOutcome:
        import numpy as np
        from repro.serving.engine import OP_BY_NAME, _MONITORED_MODES
        key = mb.key
        toks = np.asarray(out.tokens)                  # (B, steps)
        if key.mode == "clean":
            clean = toks
        else:
            clean = np.asarray(self._clean_tokens(mb, ctx))
        op_point = OP_BY_NAME.get(key.op, dvfs_lib.NOMINAL)
        protected = key.mode in _MONITORED_MODES
        # Rollback replays are real decode steps: charge them in the
        # perfmodel run shape (per-token cost x computed steps), and tell
        # the ledger which evals were replays -- evals = 1 prefill +
        # key.steps first-pass decodes + window re-decodes, so everything
        # past the first two terms bills as compute_replay.
        rc = energy.RunConfig(
            num_steps=int(out.n_model_evals),
            nominal_steps=self.eng.nominal_steps,
            aggressive=op_point,
            ckpt_interval=key.rollback_interval if protected else 10 ** 9,
            abft_enabled=protected,
            taylorseer_interval=0,
            recovery_tiles_per_step=0.0,
            replay_evals=max(int(out.n_model_evals) - 1 - key.steps, 0))
        per_slot = []
        for slot, req in enumerate(mb.requests):
            mismatch = float(np.mean(toks[slot] != clean[slot]))
            # token-space proxies for the image metrics the result schema
            # requires: lpips ~ mismatch fraction, psnr ~ -10log10 of it
            psnr = 99.0 if mismatch == 0.0 else float(
                -10.0 * np.log10(mismatch))
            per_slot.append(dict(
                lpips_vs_clean=mismatch,
                psnr_vs_clean_db=psnr,
                latents=None,
                tokens=tuple(int(t) for t in toks[slot]),
                token_match_vs_clean=1.0 - mismatch,
                ar_detections=int(out.detections),
                ar_rollbacks=int(out.rollbacks)))
        from repro.serving.trace import heatmap as heatmap_lib
        heat, blocks = heatmap_lib.summarize(getattr(out, "heatmap", None))
        return BatchOutcome(
            corrected=int(out.rollbacks),
            n_model_evals=int(out.n_model_evals),
            rc=rc,
            n_words=max(int(out.n_words), 1),
            per_slot=per_slot, heatmap=heat, heatmap_blocks=blocks)


_SERVABLE_CLASSES = {
    "diffusion": DiffusionServable,
    "autoregressive": AutoregressiveServable,
}


def build_servable(paradigm: str, engine) -> ServableModel:
    return _SERVABLE_CLASSES[paradigm](engine)
