"""The DRIFT batched serving engine.

Replaces the per-batch re-launch hack (old ``launch/serve.py`` +
``examples/drift_serve.py``, which re-parsed argv and re-jitted the full
sampler for every batch) with one process-resident engine:

  * a FIFO ``RequestQueue`` + ``MicroBatcher`` grouping pending requests
    into fixed-size same-configuration batch buckets (short tails padded),
  * a ``CompiledSamplerCache`` keyed by (arch, steps, mode, operating
    point, bucket, ...) so each configuration jits exactly once per
    process,
  * per-request DVFS operating-point selection: requests name a point or
    say ``"auto"``, which reads the engine's shared BER-monitor ladder
    index -- the Sec 5.1 feedback loop, with monitor state carried across
    batches via ``sampler.sample(monitor0=...)``,
  * a clean-reference cache: the error-free sample for a given
    (configuration, latent seeds) batch is computed once through the same
    compiled-sampler cache and reused for quality metrics,
  * per-request quality + energy accounting returned as structured
    ``RequestResult`` records (perfmodel bucket cost split across live
    requests),
  * a **virtual clock** (``clock_s``): each served batch advances it by the
    batch's perfmodel latency, giving deadline semantics a deterministic
    time base in modeled-accelerator seconds (host wall-clock of a CPU
    smoke run means nothing),
  * **streaming** (``run_stream``): the same queue drain, but each batch
    runs the windowed sampler (``SamplerKey.stream``) and yields
    ``PreviewEvent`` latent previews between windows before the final
    ``RequestResult`` records -- with final latents bit-identical to the
    one-shot ``run()`` path.

Typical use::

    engine = DriftServeEngine(bucket=2)
    for i, op in enumerate(["undervolt", "overclock", "auto"]):
        engine.submit(steps=10, mode="drift", op=op, seed=i)
    for res in engine.run():
        print(res.request_id, res.op, res.psnr_vs_clean_db, res.energy_j)

    engine.submit(steps=10, mode="drift", op="auto", seed=3)
    for ev in engine.run_stream(preview_interval=2):
        ...   # PreviewEvent previews, then the RequestResult

Deadline-aware admission control, (op, step-budget) degradation, and
priority batch formation live one layer up in
``serving/scheduler.DeadlineScheduler`` (see docs/scheduler.md); the bare
engine only records deadline misses.

Telemetry (``serving/telemetry``, docs/telemetry.md) is on by default and
entirely host-side: every served batch feeds a metrics registry (exposed
over HTTP as Prometheus text), a served-batch latency history the
scheduler consults for learned admission estimates, and -- for monitored
modes -- an adaptive guardband controller that floors the ``op="auto"``
ladder when detection counts spike (``auto_op_index`` is the single
resolution point). ``telemetry=EngineTelemetry(enabled=False)`` turns all
of it off; explicit-op workloads then serve bit-identically, while
``op="auto"`` may resolve to a more aggressive point (no guardband floor)
-- changing that resolution is exactly what the controller is for.

The engine is single-threaded by design: batches run sequentially so the
BER-monitor feedback is well-ordered. ``serving/sharded.py`` extends this
exact loop across a device mesh (one micro-batch spread over the ``data``
axis, params sharded per ``repro.distributed.sharding``) without changing
the ordering guarantee.

Async checkpoint offload (``offload=OffloadConfig()``, the CLIs'
``--offload``; docs/offload.md): monitored-mode batches run the windowed
sampler with the rollback refresh interval as the window, and a
double-buffered host store snapshots the scan carry's checkpoint stores
between windows on a background thread -- overlapped with the next
window's compute, which is the only concurrency in the engine and is
invisible to it (the store is joined before batch accounting). Finals are
bit-identical with offload on or off; the modeled residual refresh stall
is charged on the virtual clock and in the scheduler's projections, and
``rollback_interval="auto"`` requests resolve their refresh interval
through the offload planner (``auto_rollback_interval``, the
``auto_op_index`` analogue).

Architecture walk-through: ``docs/serving.md``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import zlib
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import dvfs as dvfs_lib
from repro.core import quant as quant_lib
from repro.diffusion import sampler as sampler_lib
from repro.perfmodel import energy
from repro.serving.batcher import MicroBatch, MicroBatcher
from repro.serving.cache import CompiledSamplerCache, SamplerKey
from repro.serving import servable as servable_lib
from repro.serving.offload import OffloadConfig, OffloadPlanner, OffloadStore
from repro.serving.request import (GenerationRequest, RequestQueue,
                                   RequestResult)
from repro.serving.telemetry import EngineTelemetry
from repro.serving.trace import FlightRecorder
from repro.train import steps as steps_lib

# Named operating points a request (or the auto ladder) can resolve to.
OP_BY_NAME: Dict[str, dvfs_lib.OperatingPoint] = {
    p.name: p
    for p in (dvfs_lib.NOMINAL, dvfs_lib.UNDERVOLT, dvfs_lib.OVERCLOCK)
    + dvfs_lib.OP_LADDER
}

# Modes whose ABFT detections feed the BER monitor; other modes produce no
# detection signal, so folding their zero counts would drag the EMA down.
_MONITORED_MODES = ("drift", "thundervolt", "approx_abft", "dmr", "stat_abft")


@dataclasses.dataclass
class EngineStats:
    batches: int = 0
    padded_slots: int = 0
    clean_samples_computed: int = 0
    clean_sample_hits: int = 0
    preview_events: int = 0        # streamed previews yielded (live slots)
    deadline_misses: int = 0       # requests completed past their deadline


@dataclasses.dataclass
class _BatchCtx:
    """Everything _prepare_batch stages for one micro-batch run."""
    batch_index: int
    params: object
    padded_seeds: Tuple[int, ...]
    # Paradigm-shaped staged inputs: (latents, cond, text) for diffusion,
    # (prompt_tokens,) for autoregressive -- the batch's ServableModel
    # built them and is the only code that unpacks them.
    inputs: Tuple
    run_key: object
    # Filled by the offload-enabled drains after joining the store: this
    # batch's OffloadStats delta for the telemetry tap. None = no offload
    # ran for this batch.
    offload_delta: Optional[object] = None


class DriftServeEngine:
    """Continuous-batching serving engine for DRIFT diffusion sampling."""

    def __init__(self, arch: str = "dit-xl-512", smoke: bool = True,
                 bucket: int = 2, base_seed: int = 0,
                 nominal_steps: int = 2,
                 monitor_target_ber: float = 3e-3,
                 clean_cache_size: int = 8,
                 sampler_factory: Optional[Callable] = None,
                 energy_model: Optional[energy.EnergyModel] = None,
                 telemetry: Optional[EngineTelemetry] = None,
                 offload: Optional[OffloadConfig] = None,
                 tracer: Optional[FlightRecorder] = None):
        self.default_arch = arch
        self.default_smoke = smoke
        self.nominal_steps = nominal_steps
        self.monitor_target_ber = monitor_target_ber
        self.queue = RequestQueue()
        self.batcher = MicroBatcher(bucket,
                                    key_extra=self._sampler_key_extra(bucket))
        self.cache = CompiledSamplerCache()
        self.stats = EngineStats()
        # Telemetry bundle (metrics registry, latency-history estimator,
        # guardband controller): default ON -- every tap is a host-side
        # Python call per batch, nothing traced. Pass
        # EngineTelemetry(enabled=False) (the CLIs' --no-telemetry) for a
        # telemetry-free engine (bit-identical for explicit ops; "auto"
        # loses the guardband floor).
        self.telemetry = (telemetry if telemetry is not None
                          else EngineTelemetry()).bind(monitor_target_ber)
        # Flight recorder (repro.serving.trace, docs/tracing.md): span
        # ring buffer for per-request forensics. Default ON -- every tap
        # is host-side between traced computations, so finals are
        # bit-identical with it enabled, disabled, or replaced
        # (tests/test_trace.py asserts it on both engines). Pass
        # FlightRecorder(enabled=False) for a recorder-free engine.
        self.tracer = tracer if tracer is not None else FlightRecorder()
        self.cache.on_compile = self._on_compile
        self.monitor = dvfs_lib.ber_monitor_init()
        # Virtual clock in modeled-accelerator seconds: advanced by each
        # batch's perfmodel latency. Deadlines/aging are measured on it.
        self.clock_s = 0.0
        self._base_key = jax.random.PRNGKey(base_seed)
        self._batch_counter = 0
        self._params: Dict[Tuple[str, bool], object] = {}
        # LRU: exact seed batches rarely repeat in open-ended serving, so
        # the clean-sample store is bounded (the compiled clean *sampler*
        # stays cached in self.cache regardless).
        self._clean_samples: "collections.OrderedDict[Tuple[SamplerKey, Tuple[int, ...]], jax.Array]" = \
            collections.OrderedDict()
        self._clean_cache_size = clean_cache_size
        self._sampler_factory = sampler_factory or (
            lambda key, model_cfg, scfg, on_trace:
            sampler_lib.make_sampler(model_cfg, scfg, on_trace=on_trace,
                                     stream_window=key.stream,
                                     on_window=self._on_stream_window,
                                     on_carry=self._offload_on_carry))
        self._energy_model = energy_model
        self._full_cfgs: Dict[str, object] = {}
        # Async checkpoint offload (repro.serving.offload, docs/offload.md):
        # one double-buffered host store for the whole (single-threaded)
        # engine, rebound per batch; None = disabled, which is also the
        # bit-identical baseline the offload tests compare against. The
        # planner exists regardless so rollback_interval="auto" requests
        # resolve even on an offload-free engine.
        self.offload_cfg = offload if (offload is None or offload.enabled) \
            else None
        self._offload_store = (OffloadStore(self.offload_cfg)
                               if self.offload_cfg is not None else None)
        if self._offload_store is not None:
            self._offload_store.on_event = self.tracer.on_offload
        self._active_offload: Optional[OffloadStore] = None
        self._planner: Optional[OffloadPlanner] = None
        self._interval_memo: Dict[Tuple, int] = {}
        self._stall_memo: Dict[Tuple, float] = {}
        # Per-window energy attribution (docs/slo.md): a prospective
        # per-computed-step estimate memoized per configuration, so
        # window/replay trace spans can carry joules while the batch is
        # still in flight (the exact ledger lands at finalize).
        self._window_j_memo: Dict[Tuple, float] = {}
        self._window_step_j = 0.0
        self._window_prev_steps = 0
        # One ServableModel per paradigm (they're stateless adapters over
        # the engine; per-batch state rides _BatchCtx).
        self._servables: Dict[str, servable_lib.ServableModel] = {}

    # ---------------------------------------------------------- servables
    def servable_for(self, arch: str) -> servable_lib.ServableModel:
        """The ServableModel adapter serving this arch's paradigm; raises
        ``UnsupportedArchError`` for families outside the registry."""
        paradigm = servable_lib.paradigm_for(arch)
        sv = self._servables.get(paradigm)
        if sv is None:
            sv = self._servables[paradigm] = servable_lib.build_servable(
                paradigm, self)
        return sv

    def placement(self):
        """Context in which this engine traces and runs its compiled
        functions: nothing here; the sharded engine's mesh and activation
        sharding policy."""
        return contextlib.nullcontext()

    def place_inputs(self, tree):
        """Device placement hook for servable-built batch inputs: identity
        here; the sharded engine device_puts each leaf with its mesh
        batch spec."""
        return tree

    # ------------------------------------------------------------- intake
    def submit(self, **fields) -> int:
        """Queue one generation request; returns its request id.

        Normalization the engine applies before enqueueing:

        * ``arch``/``smoke`` default to the engine's;
        * ``steps`` is clamped to ``step_budget`` when one is given (the
          DiffPro-style per-request quality/latency knob -- fewer denoising
          steps, cheaper request);
        * ``submitted_at_s`` is stamped with the engine's virtual clock
          (callers normally leave it unset), anchoring deadline-miss
          accounting and scheduler aging.
        """
        fields.setdefault("arch", self.default_arch)
        fields.setdefault("smoke", self.default_smoke)
        budget = fields.get("step_budget")
        if budget is not None:
            default_steps = GenerationRequest.__dataclass_fields__[
                "steps"].default
            fields["steps"] = min(fields.get("steps", default_steps),
                                  budget)
        fields.setdefault("submitted_at_s", self.clock_s)
        # Paradigm resolution + paradigm-irrelevant-knob validation: raises
        # UnsupportedArchError for families outside the ServableModel
        # registry, ValueError for e.g. an AR request with taylorseer=True.
        fields = self.servable_for(fields["arch"]).validate_request(fields)
        rid = self.queue.submit(**fields)
        self.telemetry.on_submit()
        self.tracer.on_submit(rid, self.clock_s,
                              arch=fields["arch"],
                              mode=fields.get("mode", "drift"),
                              op=fields.get("op", "undervolt"),
                              steps=fields.get("steps", 10),
                              priority=fields.get("priority", "standard"))
        return rid

    # ------------------------------------------------------------ serving
    def run(self) -> List[RequestResult]:
        """Drain the queue, one micro-batch at a time; results come back in
        submission order regardless of how batching regrouped them."""
        results: Dict[int, RequestResult] = {}
        while len(self.queue):
            mb = self.batcher.next_batch(self.queue, self._resolve_op,
                                         self._resolve_interval)
            for res in self._run_batch(mb):
                results[res.request_id] = res
        return [results[rid] for rid in sorted(results)]

    def run_stream(self, preview_interval: int = 1):
        """Drain the queue as a generator of streamed events.

        Per micro-batch: a ``PreviewEvent`` for every live request after
        each ``preview_interval`` denoising steps (the sampler's chunked
        scan window), then the batch's ``RequestResult`` records. Events
        arrive in batch-formation order (priority order under the
        scheduler), not globally sorted by request id -- streaming exists
        to surface results early, so no cross-batch reordering happens.
        Final latents are bit-identical to the ``run()`` path; a streamed
        configuration gets its own compiled-sampler cache slot
        (``SamplerKey.stream = preview_interval``).
        """
        assert preview_interval >= 1, preview_interval
        while len(self.queue):
            mb = self.batcher.next_batch(self.queue, self._resolve_op,
                                         self._resolve_interval)
            yield from self._run_batch_stream(mb, preview_interval)

    def _resolve_op(self, req: GenerationRequest) -> str:
        if req.op == "auto":
            return self.auto_op_name()
        return req.op

    def auto_op_index(self) -> int:
        """Ladder index an ``op="auto"`` request resolves to right now: the
        BER monitor's index, floored by the telemetry guardband controller
        (identity when telemetry is disabled). The single source of truth
        for "auto" -- batch formation and scheduler cost estimation both
        route here, so admission prices the point that will actually run."""
        return self.telemetry.clamp_ladder_index(int(self.monitor.op_index))

    def auto_op_name(self) -> str:
        return dvfs_lib.ladder_op(self.auto_op_index()).name

    # -------------------------------------------- rollback-interval auto
    def _resolve_interval(self, req: GenerationRequest) -> int:
        """Concrete checkpoint-refresh interval for one request: its own
        int, or -- for ``rollback_interval="auto"`` -- the offload
        planner's choice for (arch, resolved op, steps, bucket)."""
        if req.rollback_interval == "auto":
            return self.auto_rollback_interval(req.arch,
                                               self._resolve_op(req),
                                               req.steps)
        return int(req.rollback_interval)

    # public alias: the scheduler prices learned-estimator keys with it
    resolve_interval = _resolve_interval

    def auto_rollback_interval(self, arch: str, op_name: str,
                               steps: int) -> int:
        """The ``rollback_interval="auto"`` resolution point (the
        ``auto_op_index`` analogue): the offload planner's argmin interval
        for this configuration, with the detection rate taken from the
        telemetry history (guardband controller's realized BER for the
        operating point) and falling back to the monitor target.
        Memoized per (arch, op, steps, bucket, quantized detection rate)
        so the ladder's adaptation can move the choice without re-running
        the sweep every submit."""
        rate = self._detect_rate(op_name, arch)
        bucket = self.batcher.bucket
        key = (arch, op_name, steps, bucket, f"{rate:.1e}")
        cached = self._interval_memo.get(key)
        if cached is None:
            op = OP_BY_NAME.get(op_name, dvfs_lib.NOMINAL)
            plan = self._planner_for().plan(self._full_cfg(arch), op,
                                            steps, bucket,
                                            detect_rate=rate)
            cached = self._interval_memo[key] = plan.interval
        return cached

    def _detect_rate(self, op_name: str, arch: str) -> float:
        """Expected rollback-triggering detections per denoising step, in
        [0, 1]: realized BER (telemetry EWMA for this op when history
        exists, monitor target otherwise) times the per-step GEMM word
        count, saturated -- at realistic BERs every step sees a
        detection, so the planner's trade is refresh traffic vs
        staleness, exactly Sec 6.4's."""
        ber = None
        ctrl = self.telemetry.controller if self.telemetry.enabled else None
        if ctrl is not None:
            ber = ctrl.realized_ber.get(op_name)
        if ber is None:
            ber = self.monitor_target_ber
        words = energy.activation_bytes(self._full_cfg(arch), 1) / 4.0
        return min(1.0, float(ber) * words)

    def _planner_for(self) -> OffloadPlanner:
        if self._planner is None:
            cfg = self.offload_cfg or OffloadConfig()
            self._planner = OffloadPlanner(
                em=self._energy_model_for(),
                nominal_steps=self.nominal_steps,
                repacked=cfg.repacked, overlapped=cfg.async_commit,
                tile_m=cfg.tile_m, tile_n=cfg.tile_n)
        return self._planner

    def offload_stall_s(self, arch: str, op_name: str, steps: int,
                        interval, mode: str = "drift") -> float:
        """Modeled residual refresh stall one batch of this configuration
        pays with offload enabled (0.0 when offload is off or the mode
        never writes checkpoints). Charged on the virtual clock by
        ``_finish_batch`` and by the scheduler's perfmodel projection --
        the learned estimator sees it implicitly through observed batch
        latencies."""
        if self._offload_store is None or mode not in _MONITORED_MODES:
            return 0.0
        if interval == "auto":
            interval = self.auto_rollback_interval(arch, op_name, steps)
        key = (arch, op_name, steps, int(interval))
        cached = self._stall_memo.get(key)
        if cached is None:
            op = OP_BY_NAME.get(op_name, dvfs_lib.NOMINAL)
            cached = self._stall_memo[key] = \
                self._planner_for().residual_stall_s(
                    self._full_cfg(arch), op, steps, self.batcher.bucket,
                    int(interval))
        return cached

    @property
    def offload_store(self) -> Optional[OffloadStore]:
        """The engine's checkpoint-offload store, or None when offload is
        disabled -- the public handle for CLIs/benchmarks reading commit
        stats or driving a restore."""
        return self._offload_store

    def _offload_for(self, key: SamplerKey) -> Optional[OffloadStore]:
        """This batch's offload store, or None: only monitored modes
        write rollback checkpoints worth offloading (clean/faulty/
        float_clean batches run storeless semantics)."""
        if self._offload_store is None or key.mode not in _MONITORED_MODES:
            return None
        # Host refresh traffic is DRAM traffic in the paper's accounting:
        # arm the store with the calibrated per-byte cost so its commit/
        # restore trace events carry the joules they moved.
        self._offload_store.energy_per_byte_j = \
            self._energy_model_for().e_dram_pj_per_byte * 1e-12
        return self._offload_store

    def window_energy_per_step_j(self, key: SamplerKey) -> float:
        """Prospective per-computed-step energy for one batch of this
        configuration: the perfmodel batch cost (recovery traffic unknown
        mid-flight, charged zero) over its computed steps. Attached to
        window/replay spans so /flight shows joules as a batch progresses;
        the billed ledger (exact, including recovery) lands at finalize."""
        memo = (key.arch, key.op, key.steps, key.mode, key.precision,
                key.taylorseer, key.rollback_interval, key.bucket)
        cached = self._window_j_memo.get(memo)
        if cached is None:
            op = OP_BY_NAME.get(key.op, dvfs_lib.NOMINAL)
            protected = key.mode in _MONITORED_MODES
            rc = energy.RunConfig(
                num_steps=key.steps, nominal_steps=self.nominal_steps,
                aggressive=op,
                ckpt_interval=(key.rollback_interval if protected
                               else 10 ** 9),
                abft_enabled=protected,
                taylorseer_interval=3 if key.taylorseer else 0,
                body_bits=quant_lib.get_plan(key.precision).body_bits)
            cost = energy.run_cost(self._full_cfg(key.arch), rc,
                                   batch=key.bucket,
                                   em=self._energy_model_for())
            n = max(int(cost.get("n_computed_steps", key.steps)), 1)
            cached = self._window_j_memo[memo] = cost["energy_j"] / n
        return cached

    def _window_energy_delta_j(self, done_steps: int) -> float:
        """Joules attributed to the window that just completed: newly
        finished steps (since the last window tap) times the batch's
        per-step estimate. Single-threaded like the engine itself."""
        delta = max(int(done_steps) - self._window_prev_steps, 0)
        self._window_prev_steps = int(done_steps)
        return delta * self._window_step_j

    def _on_stream_window(self, done_steps: int) -> None:
        """Combined window-boundary tap handed to ``make_sampler``: the
        telemetry stream counter plus a flight-recorder window span. Both
        are host-side Python between windows -- zero trace impact."""
        self.telemetry.on_stream_window(done_steps)
        self.tracer.on_window(done_steps,
                              energy_j=self._window_energy_delta_j(
                                  done_steps))

    def _on_compile(self, key: SamplerKey, elapsed_s: float) -> None:
        """CompiledSamplerCache miss tap: a compile span with the factory's
        wall cost and enough key fields to identify the configuration."""
        self.tracer.on_compile(elapsed_s, arch=key.arch, mode=key.mode,
                               op=key.op, steps=key.steps,
                               stream=key.stream, bucket=key.bucket)

    def _offload_on_carry(self, done_steps: int, carry) -> None:
        """Sampler window-boundary tap (``make_sampler(on_carry=...)``):
        forwards the scan carry to the batch's bound offload store. A
        no-op unless ``_run_batch[_stream]`` armed a store -- so the hook
        is threaded unconditionally and costs one attribute read when
        offload is off."""
        store = self._active_offload
        if store is not None:
            store.on_window(done_steps, carry)

    def _sampler_key_extra(self, bucket: int) -> Dict[str, object]:
        """SamplerKey fields stamped by the engine rather than the request
        (the sharded subclass adds its mesh placement here)."""
        return {}

    # ------------------------------------------------------------ helpers
    def load_params(self, arch: str, smoke: bool, params) -> None:
        """Serve ``params`` for (arch, smoke) in place of the seeded random
        initialisation: a trained checkpoint, or any weights with the
        config's tree structure. The sharded engine places them on its
        mesh."""
        self._params[(arch, smoke)] = params

    def _params_for(self, arch: str, smoke: bool):
        k = (arch, smoke)
        if k not in self._params:
            cfg = configs.get_config(arch, smoke=smoke)
            # crc32, not hash(): Python randomizes str hashes per process,
            # and param init must be reproducible across runs.
            tag = zlib.crc32(f"{arch}:{smoke}".encode()) & 0x7FFFFFFF
            self.load_params(arch, smoke, steps_lib.init_model_params(
                cfg, jax.random.fold_in(self._base_key, tag)))
        return self._params[k]

    def _energy_model_for(self):
        if self._energy_model is None:
            self._energy_model = energy.calibrate()
        return self._energy_model

    def _full_cfg(self, arch: str):
        if arch not in self._full_cfgs:
            self._full_cfgs[arch] = configs.get_config(arch)
        return self._full_cfgs[arch]

    # ---------------------------------------------------------- one batch
    def _prepare_batch(self, mb: MicroBatch) -> _BatchCtx:
        """Stage params + servable-built inputs for one micro-batch (shared
        by the one-shot and streaming execution paths)."""
        key = mb.key
        batch_index = self._batch_counter
        self._batch_counter += 1
        self.stats.batches += 1
        self.stats.padded_slots += mb.n_pad

        model_cfg = configs.get_config(key.arch, smoke=key.smoke)
        params = self._params_for(key.arch, key.smoke)
        live_seeds = [r.seed for r in mb.requests]
        padded_seeds = tuple(live_seeds + [live_seeds[-1]] * mb.n_pad)
        inputs = self.servable_for(key.arch).batch_inputs(
            model_cfg, list(padded_seeds))
        run_key = jax.random.fold_in(self._base_key, batch_index)
        # queue_wait spans per member + the batch_assembly span; window/
        # offload/detect spans until the next batch attach to this context
        self.tracer.begin_batch(batch_index,
                                [r.request_id for r in mb.requests],
                                self.clock_s, arch=key.arch, mode=key.mode,
                                op=key.op, steps=key.steps,
                                bucket=key.bucket, n_live=len(mb.requests),
                                n_pad=mb.n_pad)
        # arm per-window energy attribution for this batch's spans
        self._window_prev_steps = 0
        self._window_step_j = self.window_energy_per_step_j(key)
        return _BatchCtx(batch_index=batch_index, params=params,
                         padded_seeds=padded_seeds, inputs=inputs,
                         run_key=run_key)

    def _run_batch(self, mb: MicroBatch) -> List[RequestResult]:
        # host phases (docs/tracing.md): profiler annotations + phase spans
        with self.tracer.phase("batch.prepare"):
            ctx = self._prepare_batch(mb)
        with self.tracer.phase("batch.sample"):
            out = self.servable_for(mb.key.arch).execute(mb, ctx)
        return self._finish_batch(mb, ctx, out)

    def _run_batch_stream(self, mb: MicroBatch, preview_interval: int):
        """Streaming twin of ``_run_batch``: the servable yields per-request
        ``PreviewEvent``s between windows, then ``('final', out)``, and the
        batch finishes through the same accounting as the one-shot path --
        so a streamed request's result record is indistinguishable from an
        unstreamed one apart from having produced previews on the way.
        Paradigms without previews (autoregressive) raise a clear error."""
        with self.tracer.phase("batch.prepare"):
            ctx = self._prepare_batch(mb)
        out = None
        sv = self.servable_for(mb.key.arch)
        # the phase stays open while the consumer handles each preview
        with self.tracer.phase("batch.sample"):
            for ev in sv.execute_stream(mb, ctx, preview_interval):
                if isinstance(ev, tuple) and ev and ev[0] == "final":
                    out = ev[1]
                    break           # terminating item; nothing follows
                yield ev
        assert out is not None, "servable stream ended without a final"
        yield from self._finish_batch(mb, ctx, out)

    def _finish_batch(self, mb: MicroBatch, ctx: _BatchCtx,
                      out) -> List[RequestResult]:
        """Metrics, energy attribution, monitor/clock carry, and per-request
        result records for a completed batch -- paradigm specifics come
        back from the servable as a ``BatchOutcome``."""
        key = mb.key
        protected = key.mode in _MONITORED_MODES
        if protected:
            self.monitor = out.monitor   # Sec 5.1 carry-over across batches

        # finalize opens its own phases (batch.twin/sync/quality)
        outcome = self.servable_for(key.arch).finalize(mb, ctx, out)
        with self.tracer.phase("batch.account"):
            return self._account_batch(mb, ctx, outcome, protected)

    def _account_batch(self, mb: MicroBatch, ctx: _BatchCtx, outcome,
                       protected: bool) -> List[RequestResult]:
        """Pricing, the virtual clock, result records, telemetry and the
        recorder's batch close, from the servable's ``BatchOutcome``."""
        key = mb.key
        batch_index = ctx.batch_index
        # report the engine's post-batch state: for unmonitored modes the
        # sampler's internal EMA decays toward zero on no-detection steps,
        # which would misrepresent the actual error estimate
        mon_ber = float(self.monitor.ema_ber)
        mon_idx = int(self.monitor.op_index)
        corrected = outcome.corrected
        nevals = outcome.n_model_evals

        # perfmodel attribution: full-arch energy model, bucket cost split
        # across the live requests (padding overhead lands on them). The
        # batch is priced once (run_cost) and the per-request view shares
        # that exact ledger, so batch and request breakdowns reconcile
        # bitwise (serving.telemetry.energy.verify_cost).
        em = self._energy_model_for()
        full = self._full_cfg(key.arch)
        rc = outcome.rc
        n_live = len(mb.requests)
        bcost = energy.run_cost(full, rc, batch=key.bucket, em=em)
        cost = energy.per_request_cost(full, rc, batch=key.bucket,
                                       n_live=n_live, em=em, cost=bcost)
        base = energy.per_request_cost(full, energy.baseline_rc(key.steps),
                                       batch=key.bucket, n_live=n_live,
                                       em=em)

        # advance the virtual clock by the batch's (shared) modeled latency
        # -- plus, with offload enabled, the planner's residual refresh
        # stall (the part of the host offload the next window's compute
        # could not hide); every request completes at the new timestamp
        stall_s = self.offload_stall_s(key.arch, key.op or "nominal",
                                       key.steps, key.rollback_interval,
                                       key.mode)
        batch_latency_s = cost["latency_s"] + stall_s
        self.clock_s += batch_latency_s
        completed_at = self.clock_s

        results = []
        for slot, req in enumerate(mb.requests):
            missed = (req.absolute_deadline_s is not None
                      and completed_at > req.absolute_deadline_s + 1e-9)
            self.stats.deadline_misses += int(missed)
            results.append(RequestResult(
                request_id=req.request_id,
                batch_index=batch_index,
                bucket_size=key.bucket,
                op=key.op or "nominal",
                mode=key.mode,
                steps=key.steps,
                taylorseer=key.taylorseer,
                precision=key.precision,
                batch_corrected_elems=corrected,
                n_model_evals=nevals,
                energy_j=cost["energy_j"],
                energy_breakdown=cost["breakdown"],
                latency_s=batch_latency_s,
                baseline_energy_j=base["energy_j"],
                baseline_latency_s=base["latency_s"],
                monitor_ber=mon_ber,
                monitor_op_index=mon_idx,
                priority=req.priority,
                deadline_s=req.deadline_s,
                completed_at_s=completed_at,
                queue_wait_s=max(
                    completed_at - req.submitted_at_s - batch_latency_s,
                    0.0),
                deadline_missed=missed,
                detect_heatmap=outcome.heatmap,
                detect_heatmap_blocks=outcome.heatmap_blocks,
                **outcome.per_slot[slot],
            ))
        # telemetry tap: metrics + latency history for the scheduler's
        # learned estimates, and (monitored modes) one guardband-controller
        # observation of the batch's realized BER / rollback intensity
        self.telemetry.on_batch(
            key=key, n_live=n_live, n_pad=mb.n_pad,
            latency_s=batch_latency_s, ema_ber=mon_ber, op_index=mon_idx,
            corrected=corrected,
            n_words=outcome.n_words,
            monitored=protected, clock_s=self.clock_s,
            queue_depth=len(self.queue), results=results,
            energy_breakdown=bcost["breakdown"])
        if ctx.offload_delta is not None:
            # settled by the drain's finish_batch() join before this ran
            self.telemetry.on_offload(ctx.offload_delta,
                                      interval=key.rollback_interval,
                                      stall_s=stall_s)
        # resilience-heatmap export (monitored batches with a real
        # sampler): labeled counters for /metrics, and a detect span in
        # the flight recorder summarizing where this batch's errors landed
        detect_attrs = None
        if outcome.heatmap is not None:
            self.telemetry.on_heatmap(outcome.heatmap,
                                      outcome.heatmap_blocks)
            detect_attrs = dict(heatmap=outcome.heatmap,
                                blocks=outcome.heatmap_blocks,
                                corrected=corrected)
        self.tracer.finish_batch(self.clock_s, detect_attrs=detect_attrs,
                                 latency_s=batch_latency_s,
                                 energy_j=cost["energy_j"],
                                 energy_breakdown=dict(bcost["breakdown"]),
                                 stall_s=stall_s, mode=key.mode,
                                 op=key.op or "nominal",
                                 n_model_evals=nevals)
        return results
