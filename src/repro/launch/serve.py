"""DRIFT serving launcher: thin CLI over ``repro.serving``.

    PYTHONPATH=src python -m repro.launch.serve --arch dit-xl-512 --smoke \
        --batch 2 --steps 10 --mode drift --op undervolt

``--smoke`` (the default) serves the arch's small test config;
``--no-smoke`` serves its published widths, e.g. the 28 x 1152 DiT-XL/2
at bucket 4 on one 16 GiB TPU v5e chip (``chip_smoke.py`` drives this):

    PYTHONPATH=src python -m repro.launch.serve --arch dit-xl-512 \
        --no-smoke --batch 4 --steps 20 --mode drift --op undervolt \
        --requests 8

Submits ``--requests`` generation requests (default: one bucket's worth)
to a single engine instance and prints the structured per-request results:
quality vs the engine's cached clean reference, and the perfmodel's
energy/latency attribution (``perfmodel.energy.per_request_cost``: the
bucket's cost split across live requests, so padding overhead is visible).
The engine jits each (arch, steps, mode, op, bucket, stream, mesh)
configuration once and computes the clean reference once per
(configuration, latent seeds) batch -- repeated invocations of ``main()``
in one process reuse both caches when given the same engine.

``--op auto`` defers each request's DVFS operating point to the engine's
BER-monitor ladder (``core.dvfs.OP_LADDER``), the Sec 5.1 feedback loop
carried across batches.

``--priority`` / ``--deadline`` / ``--step-budget`` route submissions
through ``serving.scheduler.DeadlineScheduler``: admission control
projects each request's completion on the engine's virtual (perfmodel)
clock and jointly picks its (operating point, step count) -- urgent
requests get overclocked or step-trimmed, hopeless ones are rejected,
background ones keep the energy-saving ladder. See docs/scheduler.md.

``--energy-budget`` / ``--quality-floor`` state a compute-optimal
objective: the scheduler resolves the request against the joint
(steps x precision x TaylorSeer x DVFS) Pareto frontier
(``serving.frontier``) and rewrites all four knobs -- min-energy meeting
the deadline, min-latency at/above the floor, or max-quality inside the
budget. See docs/frontier.md.

``--stream K`` streams each batch: a latent preview is yielded for every
live request after each K denoising steps, before the final results --
final latents are bit-identical to the unstreamed path.

``--sharded`` spreads each micro-batch across every local device on a
(data, model) mesh (``--model-parallel`` sets the model-axis width) via
``ShardedDriftServeEngine``; with one device it degrades to the plain
engine. See docs/serving.md.

``--metrics-port PORT`` serves the telemetry HTTP front-end for the run
(``/metrics`` Prometheus text, ``/healthz``, SSE ``/events``; 0 binds an
ephemeral port and prints it). ``--no-telemetry`` disables the whole
telemetry subsystem -- metrics, learned latency estimates, adaptive BER
guardband. Explicit-op workloads serve bit-identically without it;
``op=auto`` loses the guardband floor (that adaptation is the point of
the controller). See docs/telemetry.md.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time
from typing import Optional, Sequence

from repro import configs
from repro.core import dvfs as dvfs_lib
from repro.core.rollback import DEFAULT_INTERVAL
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import (DeadlineScheduler, DriftServeEngine,
                           EngineTelemetry, OffloadConfig, PreviewEvent,
                           ShardedDriftServeEngine, make_engine,
                           serve_telemetry)
from repro.core.quant import PRECISION_PLANS
from repro.serving.request import REQUEST_OPS, REQUEST_PRIORITIES
from repro.serving.servable import PARADIGM_BY_FAMILY, paradigm_for

# Derived from code so --help can never drift out of sync with the ladder
# (tools/check_help_sync.py asserts every name appears in the help text).
OP_LADDER_HELP = " -> ".join(p.name for p in dvfs_lib.OP_LADDER)


def arch_family_help() -> str:
    """--arch help text derived from the ServableModel registry: every
    known arch grouped by serving paradigm, unsupported ones named.
    tools/check_help_sync.py asserts all of it shows up in --help."""
    by_paradigm = {}
    unsupported = []
    for arch in configs.list_archs():
        fam = configs.get_config(arch).family
        paradigm = PARADIGM_BY_FAMILY.get(fam)
        if paradigm is None:
            unsupported.append(arch)
        else:
            by_paradigm.setdefault(paradigm, []).append(arch)
    parts = [f"{p}: {', '.join(archs)}"
             for p, archs in sorted(by_paradigm.items())]
    parts.append(f"unsupported: {', '.join(unsupported)}")
    return "; ".join(parts)


def default_mode_for(arch: str) -> str:
    """Paradigm-appropriate default when --mode is omitted: the DRIFT
    denoiser protection for diffusion archs, statistical ABFT with
    KV-window rollback for autoregressive ones."""
    return "drift" if paradigm_for(arch) == "diffusion" else "stat_abft"


def rollback_interval_arg(value: str):
    """--rollback-interval parser: a positive int or 'auto' (the offload
    planner picks per configuration)."""
    if value.strip().lower() == "auto":
        return "auto"
    iv = int(value)
    if iv < 1:
        raise argparse.ArgumentTypeError(
            f"rollback interval must be >= 1 or 'auto', got {value}")
    return iv


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve",
        description="Serve DRIFT diffusion requests through one "
                    "continuous-batching engine.",
        epilog=f"DVFS ladder (op 'auto', walked by the BER monitor): "
               f"{OP_LADDER_HELP}. Scheduling (--priority/--deadline/"
               f"--step-budget) and streaming (--stream) are documented in "
               f"docs/scheduler.md.")
    ap.add_argument("--arch", default="dit-xl-512",
                    help="model to serve; the engine picks the paradigm "
                         "from the ServableModel registry -- "
                         f"{arch_family_help()}")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the arch's small smoke config; --no-smoke "
                         "serves its published widths")
    ap.add_argument("--batch", type=int, default=2,
                    help="micro-batch bucket size")
    ap.add_argument("--requests", type=int, default=0,
                    help="requests to submit (0 = one bucket's worth)")
    ap.add_argument("--steps", type=int, default=10,
                    help="denoising steps (diffusion) or tokens to decode "
                         "(autoregressive)")
    ap.add_argument("--mode", default=None,
                    choices=["clean", "faulty", "drift", "thundervolt",
                             "approx_abft", "dmr", "stat_abft"],
                    help="protection mode (default: 'drift' for diffusion "
                         "archs, 'stat_abft' for autoregressive ones; AR "
                         "serving accepts clean/faulty/stat_abft only)")
    ap.add_argument("--op", default="undervolt", choices=list(REQUEST_OPS),
                    help="DVFS operating point; 'auto' walks the BER-monitor "
                         f"ladder core.dvfs.OP_LADDER ({OP_LADDER_HELP})")
    ap.add_argument("--rollback-interval", "--interval",
                    type=rollback_interval_arg, default=DEFAULT_INTERVAL,
                    metavar="N|auto", dest="rollback_interval",
                    help="rollback checkpoint-refresh interval in steps "
                         f"(default: {DEFAULT_INTERVAL}, from "
                         "core.rollback.DEFAULT_INTERVAL); 'auto' lets the "
                         "offload planner pick per (arch, op, steps, "
                         "bucket) from modeled energy+stall and the "
                         "telemetry detection history")
    ap.add_argument("--offload", action="store_true",
                    help="offload rollback checkpoints to a host-side "
                         "double buffer asynchronously, overlapped with "
                         "the next denoising window (tile-contiguous "
                         "layout; finals stay bit-identical -- see "
                         "docs/offload.md)")
    ap.add_argument("--taylorseer", action="store_true")
    ap.add_argument("--precision", default="int8",
                    choices=sorted(PRECISION_PLANS),
                    help="precision plan for the resilient denoiser body "
                         "(core.quant.PRECISION_PLANS); 'int8' is the "
                         "baseline path bit for bit. Usually left to the "
                         "frontier (--energy-budget/--quality-floor) but "
                         "requestable directly like --op")
    ap.add_argument("--priority", default="standard",
                    choices=list(REQUEST_PRIORITIES),
                    help="scheduling class for all submitted requests; "
                         "interactive buckets form before standard before "
                         "background")
    ap.add_argument("--deadline", type=float, default=None, metavar="SEC",
                    help="per-request relative deadline in engine virtual "
                         "(perfmodel) seconds; enables deadline-aware "
                         "admission control -- requests get overclocked or "
                         "step-trimmed to fit, or rejected when hopeless")
    ap.add_argument("--step-budget", type=int, default=None, metavar="N",
                    help="cap denoising steps per request (DiffPro-style "
                         "quality/latency knob; the scheduler may trim "
                         "further for a deadline)")
    ap.add_argument("--energy-budget", type=float, default=None,
                    metavar="J",
                    help="per-request energy budget in Joules (perfmodel "
                         "attribution); routes admission through the "
                         "compute-optimal (steps x precision x TaylorSeer "
                         "x DVFS) frontier -- min-energy meeting the "
                         "deadline, or max-quality inside the budget "
                         "without one (docs/frontier.md)")
    ap.add_argument("--quality-floor", type=float, default=None,
                    metavar="Q",
                    help="minimum acceptable quality proxy in (0, 1] "
                         "(1.0 = as-requested fidelity); frontier "
                         "admission picks the fastest point at or above "
                         "the floor (docs/frontier.md)")
    ap.add_argument("--stream", type=int, default=0, metavar="K",
                    help="stream a latent preview every K denoising steps "
                         "(0 = off); final latents are bit-identical to "
                         "the unstreamed path")
    ap.add_argument("--sharded", action="store_true",
                    help="shard each micro-batch across the local device "
                         "mesh (single device: plain engine)")
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="mesh model-axis width for --sharded")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve the telemetry HTTP front-end (/metrics "
                         "Prometheus text, /healthz, SSE /events) on this "
                         "port for the duration of the run (0 = ephemeral, "
                         "printed at startup; omit = no server)")
    ap.add_argument("--trace-dir", default=None, metavar="DIR",
                    help="write the engine's flight-recorder ring buffer "
                         "as Chrome/Perfetto trace-event JSON to "
                         "DIR/flight.json after the drain (load it at "
                         "ui.perfetto.dev; see docs/tracing.md)")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable the telemetry subsystem (metrics, learned "
                         "latency estimates, adaptive BER guardband); "
                         "explicit-op serving is bit-identical, op=auto "
                         "loses the guardband floor")
    ap.add_argument("--seed", type=int, default=0)
    return ap


def build_engine(args) -> DriftServeEngine:
    common = dict(arch=args.arch, smoke=args.smoke, bucket=args.batch,
                  base_seed=args.seed,
                  telemetry=EngineTelemetry(enabled=not args.no_telemetry),
                  offload=OffloadConfig() if args.offload else None)
    if args.sharded:
        return make_engine(model_parallel=args.model_parallel, **common)
    if args.model_parallel != 1:
        raise SystemExit("--model-parallel requires --sharded")
    return DriftServeEngine(**common)


def main(argv: Optional[Sequence[str]] = None,
         engine: Optional[DriftServeEngine] = None) -> list:
    args = build_parser().parse_args(argv)
    enable_compile_cache()

    eng = engine if engine is not None else build_engine(args)
    if isinstance(eng, ShardedDriftServeEngine):
        print(f"[serve] mesh {dict(eng.mesh.shape)}")
    bucket = eng.batcher.bucket        # an injected engine's bucket wins
    n_requests = args.requests or bucket

    server = None
    if args.metrics_port is not None:
        server = serve_telemetry(eng, port=args.metrics_port)
        print(f"[serve] telemetry at {server.url} "
              f"(/metrics /healthz /events)")
    try:
        return _drive(args, eng, server, n_requests, bucket)
    finally:
        # main() is also called in-process: never leak the bound port /
        # server thread when the drain raises
        if server is not None:
            server.close()


def _drive(args, eng, server, n_requests: int, bucket: int) -> list:
    use_scheduler = (args.deadline is not None
                     or args.priority != "standard"
                     or args.step_budget is not None
                     or args.energy_budget is not None
                     or args.quality_floor is not None)
    sched = DeadlineScheduler(eng) if use_scheduler else None
    mode = args.mode if args.mode is not None else default_mode_for(args.arch)
    fields = dict(arch=args.arch, smoke=args.smoke, steps=args.steps,
                  mode=mode, op=args.op, taylorseer=args.taylorseer,
                  precision=args.precision,
                  rollback_interval=args.rollback_interval)
    # Hold the server's engine lock from first submission through the
    # drain: a concurrent /events client gets a clean 503 instead of
    # interleaving batches -- or stealing the just-submitted queue.
    drain_lock = server.engine_lock if server is not None \
        else contextlib.nullcontext()
    with drain_lock:
        for i in range(n_requests):
            if sched is not None:
                adm = sched.submit(seed=args.seed + i,
                                   priority=args.priority,
                                   deadline_s=args.deadline,
                                   step_budget=args.step_budget,
                                   energy_budget_j=args.energy_budget,
                                   quality_floor=args.quality_floor,
                                   **fields)
                knobs = f"op {adm.op}, {adm.steps} steps"
                if adm.action == "frontier":
                    knobs += (f", {adm.precision}, taylorseer "
                              f"{'on' if adm.taylorseer else 'off'}, "
                              f"quality {adm.quality:.3f}, "
                              f"{adm.projected_energy_j:.2f}J projected")
                print(f"[admission] req {adm.request_id}: {adm.action} "
                      f"({knobs})"
                      + (f" -- {adm.reason}" if adm.reason else ""))
            else:
                eng.submit(seed=args.seed + i, **fields)

        t0 = time.time()
        results = []
        previews = 0
        if args.stream:
            for ev in eng.run_stream(args.stream):
                if isinstance(ev, PreviewEvent):
                    previews += 1
                    print(f"  [preview] req {ev.request_id} step "
                          f"{ev.step}/{ev.total_steps}")
                else:
                    results.append(ev)
            results.sort(key=lambda r: r.request_id)
        else:
            results = eng.run()
    wall = time.time() - t0

    print(f"[serve] {args.arch} mode={mode} op={args.op} "
          f"steps={args.steps} requests={n_requests} bucket={bucket} "
          f"wall={wall:.1f}s"
          + (f" previews={previews}" if args.stream else ""))
    for r in results:
        miss = "  DEADLINE MISSED" if r.deadline_missed else ""
        if r.tokens is not None:
            print(f"  req {r.request_id} (batch {r.batch_index}, op {r.op}, "
                  f"{r.priority}): {len(r.tokens)} tokens  "
                  f"match-vs-clean {r.token_match_vs_clean:.3f}  "
                  f"abft-detections {r.ar_detections}  "
                  f"kv-rollbacks {r.ar_rollbacks}  "
                  f"evals {r.n_model_evals}{miss}")
        else:
            print(f"  req {r.request_id} (batch {r.batch_index}, op {r.op}, "
                  f"{r.priority}): "
                  f"lpips-proxy {r.lpips_vs_clean:.4f}  "
                  f"psnr {r.psnr_vs_clean_db:.2f} dB  "
                  f"corrected(batch) {r.batch_corrected_elems}  "
                  f"evals {r.n_model_evals}{miss}")
        print(f"    perfmodel/request: baseline "
              f"{r.baseline_energy_j:.2f}J/{r.baseline_latency_s:.3f}s -> "
              f"{r.energy_j:.2f}J/{r.latency_s:.3f}s "
              f"({100 * (1 - r.energy_j / r.baseline_energy_j):.1f}% energy, "
              f"{r.baseline_latency_s / r.latency_s:.2f}x speed)")
    print(f"  engine: {eng.cache.traces} traces, {eng.cache.hits} cache "
          f"hits, {eng.stats.batches} batches, "
          f"{eng.stats.padded_slots} padded slots; monitor "
          f"ber={float(eng.monitor.ema_ber):.2e} "
          f"ladder={int(eng.monitor.op_index)}; clock {eng.clock_s:.3f}s, "
          f"{eng.stats.deadline_misses} deadline misses")
    if eng.offload_store is not None:
        ost = eng.offload_store.stats
        print(f"  offload: {ost.commits} commits "
              f"({ost.bytes_offloaded / 1e6:.2f} MB tile-contiguous), "
              f"{ost.skipped} spike-skipped, {ost.restores} restores; "
              f"last committed step {eng.offload_store.committed_step}")
    if sched is not None:
        s = sched.stats
        print(f"  scheduler: {s.admitted}/{s.submitted} admitted "
              f"({s.rejected} rejected, {s.escalated_op} op-escalated, "
              f"{s.trimmed_steps} step-trimmed, {s.frontier_selected} "
              f"frontier-selected, {s.projected_misses} projected misses)")
    tele = eng.telemetry
    if tele.enabled:
        ctrl = tele.controller
        print(f"  telemetry: {tele.estimator.total_observations} latency "
              f"observations over {len(tele.estimator)} configs; guardband "
              f"floor {ctrl.guard_index if ctrl else 0} "
              f"({ctrl.guard_op_name() if ctrl else 'n/a'})")
        if tele.ledger is not None and tele.ledger.batches:
            top = sorted(tele.ledger.shares().items(),
                         key=lambda kv: -kv[1])[:3]
            burning = tele.slo.breached_objectives()
            print(f"  energy: {tele.ledger.energy_per_request_j():.2f} "
                  f"J/request ("
                  + ", ".join(f"{c} {s:.0%}" for c, s in top)
                  + "); slo breached: "
                  + (", ".join(burning) if burning else "none")
                  + (f" -- GET {server.url}/slo" if server is not None
                     else ""))
    if args.trace_dir is not None:
        from repro.serving.trace import write_chrome_trace
        os.makedirs(args.trace_dir, exist_ok=True)
        path = os.path.join(args.trace_dir, "flight.json")
        write_chrome_trace(path, eng.tracer.spans(),
                           eng.tracer.epoch_unix_ns)
        print(f"  trace: {len(eng.tracer)} spans -> {path} "
              f"(ui.perfetto.dev)")
    return results


if __name__ == "__main__":
    main()
