"""One run of one cell: set up, warm up, measure, read, check.

Set-up (``setup_s``, from process start) makes the weights on the device in
one jitted call from the seed, hands them to the engine, and serves one
bucket of the cell's own shape, which compiles the cell's samplers (and,
in drift mode, the clean twin) or loads them from JAX's persistent cache in
the checkout. The window then serves the cell's traffic (``drive``); no
compilation may happen in it or in the drain. With ``trace`` the profiler
records the window, and the per-layer metrics are read from it.

After the window: the peak device memory is read, the engine is dropped,
and the sampled batches are run through the plain reference to decide
``correct`` (``compare``).
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from chipbench import attribute, compare, drive, peaks as peaks_lib, spec
from chipbench import stats, trace as trace_lib, traffic, weights

# Model fields the served program's configuration must share with the file.
MODEL_KEYS = ("n_layers", "d_model", "n_heads", "d_ff", "latent_size",
              "latent_channels", "patch_size", "num_classes", "cond_dim",
              "cond_tokens")
GAP_SPANS = ("submit", "engine.run", "collect", "wait")
OUT_DIR = spec.REPO_ROOT / ".chipbench"


@dataclasses.dataclass
class Run:
    """What the per-layer readers see."""
    cell: spec.Cell
    model: dict
    record: drive.Record
    bucket: int
    steps: int
    engine_batches: int
    engine_padded_slots: int
    peaks: Optional[dict]
    trace: Optional[trace_lib.Summary]
    # the traced window's device time by program scope, module and host
    # phase (``chipbench.attribute``)
    scopes: Optional[attribute.Scopes] = None

    @property
    def window_images(self) -> int:
        return self.record.window_images()

    @property
    def images_per_s(self) -> float:
        return self.window_images / self.record.window_s


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def derived_seed(seed: int, stream: int) -> int:
    return int(np.random.default_rng([seed, stream]).integers(0, 2 ** 31 - 1))


def import_program():
    src = spec.REPO_ROOT / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"chipbench: the program is not in this checkout "
                         f"({src / 'repro'} is missing)")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


def check_model(model: dict) -> None:
    from repro import configs
    served = configs.get_config(model["arch"], smoke=model["smoke"])
    for k in MODEL_KEYS:
        if getattr(served, k) != model[k]:
            raise SystemExit(f"chipbench: the served {model['arch']} has "
                             f"{k}={getattr(served, k)}, the configuration "
                             f"file {model[k]}")


def build_engine(model: dict, request: dict, engine_seed: int):
    from repro.launch import serve
    serving = model["serving"]
    argv = ["--arch", model["arch"],
            "--smoke" if model["smoke"] else "--no-smoke",
            "--batch", str(serving["bucket"]), "--steps", str(serving["steps"]),
            "--mode", request["mode"], "--op", request["op"],
            "--seed", str(engine_seed)]
    eng = serve.build_engine(serve.build_parser().parse_args(argv))
    if eng.nominal_steps != serving["nominal_steps"]:
        raise SystemExit(f"chipbench: the engine protects "
                         f"{eng.nominal_steps} nominal steps, the "
                         f"configuration {serving['nominal_steps']}")
    return eng


def _top(busy: Dict[str, float]) -> List:
    return [[k, v] for k, v in sorted(busy.items(), key=lambda kv: -kv[1])]


def read_trace(trace_dir) -> Tuple[trace_lib.Summary, attribute.Scopes]:
    """The window's summary (busy time, top ops, named idle gaps) and its
    device time by scope, module and host phase, from one reading of the
    ``.xplane.pb`` under ``trace_dir``."""
    att = attribute.read(trace_lib.xplane_file(str(trace_dir)))
    tr = att.trace()
    window = trace_lib.find_span(tr, "window")
    return (trace_lib.reduce(tr, window, GAP_SPANS),
            attribute.window_scopes(att, window))


class CompileCounter:
    """Counts executables built while armed: XLA backend compiles and loads
    from the persistent compilation cache. (Re-tracing an eager primitive
    makes no executable and is not counted.)"""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/compile_time_saved_sec")

    def __init__(self):
        self.armed, self.events = False, []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.armed and event in self.EVENTS:
            self.events.append(f"{event} {kwargs.get('fun_name', '')}")


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, control: bool = False,
             clock: Callable[[], float] = time.perf_counter) -> Dict:
    """One run; returns the result line's object (and, with ``control``,
    the control's readings under ``"control"``)."""
    model, mix = cell.config, cell.traffic
    serving = model["serving"]
    bucket, steps = serving["bucket"], serving["steps"]
    request = dict(mix["request"])
    fields = dict(arch=model["arch"], smoke=model["smoke"], steps=steps,
                  rollback_interval=serving["rollback_interval"], **request)
    mode = request["mode"]
    keep_twin = mode == "drift"

    cache = import_program()
    check_model(model)
    engine_seed = derived_seed(seed, 4)
    eng = build_engine(model, request, engine_seed)
    params = weights.make(model, derived_seed(seed, 6))
    jax.block_until_ready(params)
    eng.load_params(model["arch"], model["smoke"], params)
    t_params = time.monotonic() - t_start

    warm = [drive.Request(seed=s, due=clock())
            for s in traffic.warmup_seeds(seed, bucket)]
    drive.serve_batch(eng, warm, fields, bucket, clock, keep_twin)
    setup_s = time.monotonic() - t_start
    log(f"set-up {setup_s:.3f}s (weights on device at {t_params:.3f}s); "
        f"compile cache {cache}; {eng.cache.traces} sampler traces")

    counter = CompileCounter()
    traces0 = eng.cache.traces
    batches0, padded0 = eng.stats.batches, eng.stats.padded_slots
    trace_dir = OUT_DIR / cell.name / f"trace-{seed}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # the harness's spans only
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    counter.armed = True
    record = drive.drive(eng, mix, fields, bucket, seed, seconds, keep_twin,
                         clock=clock)
    counter.armed = False
    if trace:
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        log(f"profiler stopped in {time.monotonic() - t_stop:.3f}s")
    if counter.events or eng.cache.traces != traces0:
        raise RuntimeError(f"compiled inside the window: "
                           f"{len(counter.events)} compile events "
                           f"{sorted(set(counter.events))}, "
                           f"{eng.cache.traces - traces0} sampler traces")

    device = jax.devices()[0]
    mem = device.memory_stats() or {}
    due = record.due_in_window()
    latencies = [r.done - r.due for r in due]
    log(f"window {record.window_s:.3f}s, {len(record.batches)} batches "
        f"({sum(b.in_window for b in record.batches)} in the window), "
        f"{record.window_images()} images; {len(due)} requests due in the "
        f"window (latency samples); generator late by "
        f"{record.late_s:.6f}s ({mix['arrival']}); 0 compiles in the "
        f"window")

    run = Run(cell=cell, model=model, record=record, bucket=bucket,
              steps=steps, engine_batches=eng.stats.batches - batches0,
              engine_padded_slots=eng.stats.padded_slots - padded0,
              peaks=None, trace=None)
    metrics: Dict[str, dict] = {}
    device_info = {"platform": device.platform, "kind": device.device_kind,
                   "count": len(jax.devices()),
                   "memory_peak_bytes": mem.get("peak_bytes_in_use")}
    breakdown = None
    if trace:
        t_read = time.monotonic()
        run.trace, run.scopes = read_trace(trace_dir)
        log(f"trace read in {time.monotonic() - t_read:.3f}s")
        log(f"busy s by scope {_top(run.scopes.by_scope)}; by module "
            f"{_top(run.scopes.by_module)}; idle in batch phases "
            f"{run.scopes.phase_idle_s!r}s over {run.scopes.batches} "
            f"batches")
        run.peaks = peaks_lib.for_kind(device.device_kind)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device_info.update(busy_s=run.trace.busy_s,
                           window_s=run.trace.window_s)
        breakdown = {"device_ops": [[k, v] for k, v in run.trace.op_seconds],
                     "idle_gaps": [[k, v] for k, v in run.trace.gaps]}
        for m in cell.per_layer:
            value = spec.metric_reader(m.name)(run)
            if value is not None:
                metrics[m.name] = {"value": value, "unit": m.unit}
    else:
        e2e = {"images_per_s": run.images_per_s,
               "latency_p50_s": stats.percentile(latencies, 50),
               "latency_p90_s": stats.percentile(latencies, 90),
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m.name] = {"value": e2e[m.name], "unit": m.unit}

    # the program's state goes before the reference runs
    del eng
    gc.collect()
    t_ref = time.monotonic()
    sample = compare.sample_batches(record, compare.sample_size(cell.limits),
                                    seed)
    ber = compare.engine_ber(model, mode, request["op"])
    readings = compare.readings(model, params, sample, mode, engine_seed,
                                ber, with_control=control)
    correct, checks = compare.decide(readings["program"],
                                     spec.readings(cell.limits))
    log(f"reference over {len(sample)} batches {[b.index for b in sample]} "
        f"took {time.monotonic() - t_ref:.3f}s")
    out = {"correct": bool(correct), "attempted": len(due),
           "failed": sum(not np.isfinite(r.done) for r in due),
           "metrics": metrics, "device": device_info}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["control"] = readings["control"]
    out["checks"] = checks
    return out


def require_devices(chips: int) -> List:
    """The accelerator's devices; exits non-zero without a TPU."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: no TPU (JAX platform is "
                         f"{devices[0].platform!r}); the benchmark runs only "
                         f"on a TPU")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} chips, JAX "
                         f"finds {len(devices)}")
    return devices
