"""Flight-recorder / decision-audit / resilience-heatmap tests.

The PR's acceptance bar:

* **zero perturbation** -- final latents are bit-identical with the
  recorder on and off, one-shot and streamed, on the plain engine (the
  8-fake-device twin lives at the bottom behind ``needs_mesh``);
* **span coverage** -- a streamed, monitored, offload-enabled request's
  trace contains a span for every jitted window, every offload commit,
  and the batch detect/finalize pair, with the scheduler's decision
  record attached; the AR paradigm records a replay span per KV-window
  rollback;
* **heatmap** -- ``RequestResult.detect_heatmap`` is present for
  monitored batches, streamed == one-shot, and the protected early
  timesteps carry no mass;
* the recorder itself: bounded ring, drop counting, disabled no-op; the
  Chrome exporter and ``/trace``/``/flight`` HTTP surfaces (404 paths
  included); SSE under two genuinely concurrent clients and one slow
  consumer; and docs/telemetry.md's catalog staying in sync with the
  registry (the tier-1 twin of tools/check_metrics_catalog.py).
"""
import json
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.serving import (DeadlineScheduler, DriftServeEngine,
                           OffloadConfig, PreviewEvent, serve_telemetry)
from repro.serving.telemetry.http import latents_sha256
from repro.serving.trace import (FlightRecorder, SPAN_KINDS, bin_heatmap,
                                 request_tree, site_labels, summarize,
                                 to_chrome_trace)

ARCH = "dit-xl-512"
REPO = Path(__file__).resolve().parents[1]

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


def kind_counts(tracer, request_id=None):
    counts = {}
    for s in tracer.spans(request_id):
        counts[s.kind] = counts.get(s.kind, 0) + 1
    return counts


def fetch(url):
    # generous timeout: SSE drains jit the streaming sampler in-handler
    with urllib.request.urlopen(url, timeout=600) as resp:
        return resp.headers, resp.read().decode("utf-8")


def parse_sse(payload):
    events, kind = [], None
    for line in payload.splitlines():
        if line.startswith("event: "):
            kind = line[len("event: "):]
        elif line.startswith("data: "):
            events.append((kind, json.loads(line[len("data: "):])))
    return events


# ---------------------------------------------------------- recorder core
def test_recorder_ring_buffer_bounds_and_drop_count():
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record(f"s{i}", "window", request_ids=(i,))
    assert len(rec) == 4
    assert rec.recorded == 10 and rec.dropped == 6
    # newest-last snapshot keeps the most recent spans
    assert [s.name for s in rec.spans()] == ["s6", "s7", "s8", "s9"]
    assert rec.spans(request_id=3) == []
    assert [s.name for s in rec.spans(request_id=8)] == ["s8"]


def test_recorder_disabled_is_a_noop():
    rec = FlightRecorder(enabled=False)
    rec.on_submit(0, 0.0)
    rec.begin_batch(0, [0], 0.0)
    rec.on_compile(0.1)
    rec.on_window(2)
    rec.on_offload("commit", 0, 0.01, nbytes=8)
    rec.on_replay(0, 4)
    rec.finish_batch(1.0, detect_attrs={"heatmap": ((1,),)})
    with rec.phase("batch.sample"):
        pass
    assert len(rec) == 0 and rec.recorded == 0


def test_recorder_thread_safe_under_concurrent_records():
    # offload commits record from a background thread; pound the ring
    # from four threads and check the counters stay consistent
    rec = FlightRecorder(capacity=256)

    def pound(tid):
        for i in range(500):
            rec.record(f"t{tid}.{i}", "offload_commit", batch_index=tid)

    threads = [threading.Thread(target=pound, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert rec.recorded == 2000
    assert len(rec) == 256
    assert rec.dropped == 2000 - 256


def test_span_kinds_taxonomy_is_closed():
    # every engine/scheduler tap emits a kind from the documented taxonomy
    assert set(SPAN_KINDS) == {
        "submit", "admission", "queue_wait", "batch_assembly", "compile",
        "window", "offload_commit", "offload_restore", "replay", "detect",
        "finalize", "phase"}


# ------------------------------------------------------- heatmap plumbing
def test_bin_heatmap_and_site_labels():
    heat = np.zeros((8, 3), np.int32)
    heat[5, 1] = 4          # step 5, block0
    heat[7, 2] = 2          # step 7, block1
    binned = bin_heatmap(heat, n_bins=4)
    assert binned.shape == (3, 4)
    assert binned[1, 2] == 4 and binned[2, 3] == 2
    assert binned.sum() == heat.sum()
    # fewer steps than bins degrades to one bin per step
    assert bin_heatmap(np.ones((2, 1), np.int32), n_bins=4).shape == (1, 2)
    assert site_labels(1) == ("all",)
    assert site_labels(3) == ("embed", "block0", "block1")
    nested, labels = summarize(heat)
    assert labels == ("embed", "block0", "block1")
    assert nested == tuple(tuple(int(v) for v in row) for row in binned)
    assert summarize(None) == (None, None)


# -------------------------------------------- zero-perturbation + heatmap
def _drain(engine, stream=0):
    if not stream:
        return engine.run()
    results = [ev for ev in engine.run_stream(preview_interval=stream)
               if not isinstance(ev, PreviewEvent)]
    results.sort(key=lambda r: r.request_id)
    return results


def _engine(tracer=None, offload=False):
    return DriftServeEngine(
        arch=ARCH, smoke=True, bucket=1, tracer=tracer,
        offload=OffloadConfig() if offload else None)


def test_bit_identity_tracing_on_off_one_shot_and_streamed():
    """Acceptance: finals bit-identical with the recorder on vs off, for
    the one-shot AND the streamed path; heatmaps agree everywhere too."""
    digests, heatmaps = {}, {}
    for label, tracer, stream in (
            ("on/one-shot", None, 0),
            ("off/one-shot", FlightRecorder(enabled=False), 0),
            ("on/streamed", None, 2),
            ("off/streamed", FlightRecorder(enabled=False), 2)):
        eng = _engine(tracer=tracer)
        eng.submit(steps=4, mode="drift", op="undervolt", seed=0)
        (res,) = _drain(eng, stream=stream)
        digests[label] = latents_sha256(res.latents)
        heatmaps[label] = res.detect_heatmap
        if tracer is not None:
            assert len(eng.tracer) == 0    # disabled recorder stayed mute
    assert len(set(digests.values())) == 1, digests
    assert len(set(heatmaps.values())) == 1
    heat = heatmaps["on/one-shot"]
    assert heat is not None
    assert sum(map(sum, heat)) > 0         # undervolt at smoke BER detects
    # the engine protects the first nominal_steps (= 2 of 4) timesteps ->
    # the early half of every site's row is empty: the live Fig 5-6
    # structure, asserted on a real served sample
    n_bins = len(heat[0])
    assert all(sum(row[: n_bins // 2]) == 0 for row in heat)


def test_heatmap_engine_level_with_fewer_steps_than_bins():
    """A served request with steps < N_STEP_BINS degrades to one bin per
    step (no empty phantom bins), and the detect span's heatmap stays the
    result's heatmap."""
    from repro.serving.trace import N_STEP_BINS
    steps = N_STEP_BINS - 1
    eng = _engine()
    eng.submit(steps=steps, mode="drift", op="undervolt", seed=0)
    (res,) = _drain(eng)
    heat = res.detect_heatmap
    assert heat is not None
    assert all(len(row) == steps for row in heat)
    # the protected head (nominal_steps = 2) maps to the first two
    # per-step bins exactly -- no detections there by construction
    assert all(row[0] == row[1] == 0 for row in heat)
    (detect,) = [s for s in eng.tracer.spans() if s.kind == "detect"]
    assert detect.attrs["heatmap"] == heat


def test_recorder_offload_thread_racing_batch_lifecycle():
    """The offload store's background thread records commits while the
    engine thread opens/closes batches: with capacity headroom, nothing
    drops, every span lands exactly once, and batch-lifecycle spans stay
    one-per-batch."""
    n_batches, n_commits = 100, 400
    rec = FlightRecorder(capacity=8192)
    start = threading.Event()

    def offloader():
        start.wait()
        for i in range(n_commits):
            rec.on_offload("commit", i, 0.0, nbytes=64)

    t = threading.Thread(target=offloader)
    t.start()
    start.set()
    for b in range(n_batches):
        rec.begin_batch(b, [b], float(b))
        rec.on_window(2)
        rec.finish_batch(float(b) + 0.5)
    t.join()
    # queue_wait + batch_assembly + window + finalize per batch + commits
    assert rec.recorded == 4 * n_batches + n_commits
    assert rec.dropped == 0 and len(rec) == rec.recorded
    spans = rec.spans()
    by_kind = kind_counts(rec)
    assert by_kind == {"queue_wait": n_batches,
                       "batch_assembly": n_batches,
                       "window": n_batches,
                       "finalize": n_batches,
                       "offload_commit": n_commits}
    # no duplicated or lost commits: every step recorded exactly once
    commit_steps = sorted(s.attrs["step"] for s in spans
                          if s.kind == "offload_commit")
    assert commit_steps == list(range(n_commits))
    # batch-lifecycle spans are unique per batch index
    finals = [s.batch_index for s in spans if s.kind == "finalize"]
    assert sorted(finals) == list(range(n_batches))


def test_streamed_offloaded_span_coverage_with_decision_record():
    """Acceptance: a streamed, monitored, offload-enabled request's trace
    has spans for every window and commit plus the decision record."""
    eng = _engine(offload=True)
    sched = DeadlineScheduler(eng)
    window = 2
    adm = sched.submit(steps=6, mode="drift", op="undervolt", seed=0,
                       energy_budget_j=1e9)
    assert adm.admitted and adm.action == "frontier"
    results = _drain(eng, stream=window)
    assert len(results) == 1

    counts = kind_counts(eng.tracer, request_id=adm.request_id)
    assert counts.get("submit") == 1
    assert counts.get("admission") == 1
    assert counts.get("queue_wait") == 1
    assert counts.get("batch_assembly") == 1
    assert counts.get("compile", 0) >= 1   # drift trace (+ clean ref)
    assert counts.get("window") == -(-adm.steps // window)
    assert counts.get("offload_commit") == eng.offload_store.stats.commits
    assert eng.offload_store.stats.commits >= 1
    assert counts.get("detect") == 1 and counts.get("finalize") == 1

    tree = request_tree(eng.tracer.spans(), adm.request_id)
    dec = tree["decision"]
    assert dec["action"] == "frontier" and dec["admitted"]
    assert dec["frontier_points"] >= dec["frontier_ok"] >= 1
    assert len(dec["frontier_considered"]) == dec["frontier_points"]
    assert dec["chosen"].startswith(f"{dec['op']}/{dec['steps']}st/")
    # window spans carry contiguous step ranges covering the whole run
    windows = [s for s in eng.tracer.spans(adm.request_id)
               if s.kind == "window"]
    edges = [(s.attrs["from_step"], s.attrs["done_steps"]) for s in windows]
    assert edges[0][0] == 0 and edges[-1][1] == adm.steps
    assert all(a[1] == b[0] for a, b in zip(edges, edges[1:]))
    # the detect span carries the same heatmap the result reports
    (detect,) = [s for s in eng.tracer.spans(adm.request_id)
                 if s.kind == "detect"]
    assert detect.attrs["heatmap"] == results[0].detect_heatmap
    assert detect.attrs["blocks"] == results[0].detect_heatmap_blocks


def test_ar_replay_spans_and_token_heatmap():
    """The AR paradigm records a replay span per KV-window rollback and a
    single-site per-token-bin heatmap whose mass equals the detections."""
    eng = DriftServeEngine(arch="olmo-1b", smoke=True, bucket=2)
    for i in range(2):
        eng.submit(steps=8, mode="stat_abft", op="undervolt", seed=i)
    results = eng.run()
    counts = kind_counts(eng.tracer)
    # rollbacks are batch-level: every request in the bucket reports the
    # batch's count, one replay span each
    batch_rollbacks = results[0].ar_rollbacks
    assert batch_rollbacks >= 1            # undervolt at smoke BER rolls
    assert counts.get("replay", 0) == batch_rollbacks
    heat = results[0].detect_heatmap
    assert heat is not None
    assert results[0].detect_heatmap_blocks == ("all",)
    assert len(heat) == 1                  # one site row, binned tokens
    assert sum(heat[0]) == int(results[0].ar_detections) > 0


def test_rejected_decisions_recorded_without_request_id():
    eng = _engine()
    sched = DeadlineScheduler(eng)
    with pytest.raises(ValueError):
        sched.submit(steps=4, mode="not-a-mode", seed=0)
    adm = sched.submit(steps=8, mode="drift", op="undervolt", seed=1,
                       deadline_s=1e-9)
    assert not adm.admitted
    rejected = [s for s in eng.tracer.spans() if s.kind == "admission"
                and not s.attrs.get("admitted", True)]
    assert len(rejected) == 2
    assert all(s.request_ids == () for s in rejected)
    reasons = [s.attrs["reason"] for s in rejected]
    assert any(r.startswith("validation:") for r in reasons)
    rej = eng.telemetry.registry.counter("drift_scheduler_rejections_total",
                                         label_names=("reason",))
    assert rej.labels(reason="validation").value == 1
    assert rej.labels(reason="projected-miss").value == 1


# ------------------------------------------------------------- exporters
def _fake_trace():
    rec = FlightRecorder()
    rec.on_submit(7, 0.5, arch=ARCH)
    rec.begin_batch(3, [7], 1.0, n_live=1)
    rec.on_window(2)
    rec.finish_batch(1.5, latency_s=0.5)
    return rec


def test_chrome_trace_export_shape():
    rec = _fake_trace()
    doc = to_chrome_trace(rec.spans())
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    spans = [e for e in events if e["ph"] == "X"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert "scheduler/queue" in names and "batch 3" in names
    assert len(spans) == len(rec.spans())
    for e in spans:
        assert e["pid"] == 1 and e["dur"] >= 1.0
        assert "virtual_t0_s" in e["args"]
    submit = next(e for e in spans if e["cat"] == "submit")
    assert submit["tid"] == 0              # pre-batch track
    window = next(e for e in spans if e["cat"] == "window")
    assert window["tid"] == 4              # batch 3 -> tid 4
    assert "otherData" not in doc
    json.dumps(doc)                        # wire-serializable
    doc = to_chrome_trace(rec.spans(), rec.epoch_unix_ns)
    assert doc["otherData"] == {"epoch_unix_ns": rec.epoch_unix_ns}


def test_request_tree_shape():
    rec = _fake_trace()
    tree = request_tree(rec.spans(), 7)
    assert tree["request_id"] == 7
    assert tree["n_spans"] == len(rec.spans(request_id=7)) == 5
    assert tree["decision"] is None        # no scheduler in this trace
    assert [s["kind"] for s in tree["spans"]] == \
        ["submit", "queue_wait", "batch_assembly", "window", "finalize"]
    empty = request_tree(rec.spans(), 99)
    assert empty["n_spans"] == 0 and empty["spans"] == []


# -------------------------------------------------- HTTP: /trace, /flight
@pytest.fixture()
def served_engine():
    eng = _engine()
    server = serve_telemetry(eng, port=0)
    yield eng, server
    server.close()


def test_trace_endpoint_200_and_flight(served_engine):
    eng, server = served_engine
    rid = eng.submit(steps=4, mode="drift", op="undervolt", seed=0)
    eng.run()
    headers, body = fetch(f"{server.url}/trace/{rid}")
    assert headers["Content-Type"].startswith("application/json")
    tree = json.loads(body)
    assert tree["request_id"] == rid and tree["n_spans"] >= 4
    kinds = {s["kind"] for s in tree["spans"]}
    assert {"submit", "batch_assembly", "detect", "finalize"} <= kinds
    doc = json.loads(fetch(f"{server.url}/flight")[1])
    assert len([e for e in doc["traceEvents"] if e.get("ph") == "X"]) \
        == len(eng.tracer)


def test_trace_endpoint_404_paths(served_engine):
    eng, server = served_engine
    # non-integer id
    with pytest.raises(urllib.error.HTTPError) as exc:
        fetch(f"{server.url}/trace/abc")
    assert exc.value.code == 404
    assert "bad request id" in exc.value.read().decode()
    # unknown id against an empty recorder
    with pytest.raises(urllib.error.HTTPError) as exc:
        fetch(f"{server.url}/trace/999")
    assert exc.value.code == 404
    assert "no trace" in exc.value.read().decode()


def test_trace_endpoint_404_when_recorder_disabled():
    eng = DriftServeEngine(arch=ARCH, smoke=True, bucket=1,
                           tracer=FlightRecorder(enabled=False))
    rid = eng.submit(steps=4, mode="drift", op="undervolt", seed=0)
    eng.run()
    with serve_telemetry(eng, port=0) as server:
        with pytest.raises(urllib.error.HTTPError) as exc:
            fetch(f"{server.url}/trace/{rid}")
        assert exc.value.code == 404
        # /flight still answers: an empty, well-formed trace
        doc = json.loads(fetch(f"{server.url}/flight")[1])
        assert [e["ph"] for e in doc["traceEvents"]] == ["M"]


# ------------------------------------------- HTTP: SSE under real clients
def test_two_concurrent_sse_clients_one_drains_other_503(served_engine):
    """Two genuinely concurrent /events clients: the first holds the
    drain for seconds (the handler jits in-line), the second must get a
    clean 503 -- never interleaved batches -- and a retry after the
    first finishes succeeds."""
    eng, server = served_engine
    for i in range(2):
        eng.submit(steps=4, mode="drift", op="undervolt", seed=i)
    first = {}

    def drain():
        first["events"] = parse_sse(fetch(f"{server.url}/events")[1])

    t = threading.Thread(target=drain)
    t.start()
    time.sleep(0.5)        # handler has the lock; the jit keeps it busy
    with pytest.raises(urllib.error.HTTPError) as exc:
        fetch(f"{server.url}/events")
    assert exc.value.code == 503
    t.join()
    # default interval 1: a 4-step request previews after steps 1-3 (the
    # final window yields the result instead), so 3 previews per request
    assert first["events"][-1] == ("end", {"served": 2, "previews": 6})
    # lock released: the loser's retry drains the (now empty) queue fine
    events = parse_sse(fetch(f"{server.url}/events")[1])
    assert events == [("end", {"served": 0, "previews": 0})]


def test_slow_sse_consumer_still_receives_every_frame(served_engine):
    """A consumer reading 32 bytes at a time with pauses: the drain
    completes engine-side and every frame still arrives intact."""
    eng, server = served_engine
    for i in range(2):
        eng.submit(steps=4, mode="drift", op="undervolt", seed=i)
    resp = urllib.request.urlopen(f"{server.url}/events?interval=2",
                                  timeout=600)
    chunks = []
    while True:
        chunk = resp.read(32)
        if not chunk:
            break
        chunks.append(chunk)
        time.sleep(0.002)
    events = parse_sse(b"".join(chunks).decode("utf-8"))
    kinds = [k for k, _ in events]
    assert kinds.count("result") == 2
    assert kinds.count("preview") == 2     # 2 requests x (4/K - 1) = 2
    assert events[-1] == ("end", {"served": 2, "previews": 2})
    assert eng.queue.pending() == ()


# ------------------------------------------------- nearest_rank hardening
def test_nearest_rank_empty_and_bounds():
    from repro.serving.telemetry.metrics import nearest_rank
    with pytest.raises(ValueError):
        nearest_rank([], 50)
    with pytest.raises(ValueError):
        nearest_rank([1.0], -1)
    with pytest.raises(ValueError):
        nearest_rank([1.0], 100.5)
    # single sample: every quantile is that sample
    assert nearest_rank([3.0], 0) == 3.0
    assert nearest_rank([3.0], 50) == 3.0
    assert nearest_rank([3.0], 100) == 3.0
    # endpoints clamp to the extremes
    data = [1.0, 2.0, 3.0, 4.0]
    assert nearest_rank(data, 0) == 1.0
    assert nearest_rank(data, 100) == 4.0
    assert nearest_rank(data, 50) in data


def test_histogram_empty_percentile_is_none():
    from repro.serving.telemetry.metrics import MetricsRegistry
    h = MetricsRegistry().histogram("t_seconds", "t")
    assert h.percentile(50) is None
    h.observe(2.5)
    assert h.percentile(0) == h.percentile(100) == 2.5


# --------------------------------------------------- metrics catalog twin
def test_metrics_catalog_covers_registry():
    """Tier-1 twin of tools/check_metrics_catalog.py: every registered
    metric family has a row in docs/telemetry.md."""
    import sys
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import check_metrics_catalog as cmc
    finally:
        sys.path.pop(0)
    doc = (REPO / "docs" / "telemetry.md").read_text(encoding="utf-8")
    names = cmc.registered_metric_names()
    assert len(names) >= 30
    assert cmc.missing_from_catalog(doc, names) == []
    # the satellite metrics are among them
    for name in ("drift_build_info", "drift_engine_uptime_seconds",
                 "drift_scheduler_rejections_total",
                 "drift_detect_heatmap_total"):
        assert name in names


def test_build_info_uptime_and_heatmap_metrics():
    from repro.version import __version__
    eng = _engine()
    text = eng.telemetry.registry.expose()
    assert f'version="{__version__}"' in text
    assert "drift_build_info" in text
    eng.submit(steps=4, mode="drift", op="undervolt", seed=0)
    (res,) = eng.run()
    text = eng.telemetry.registry.expose()
    assert "drift_engine_uptime_seconds" in text
    assert 'drift_detect_heatmap_total{block="block' in text
    # the counter's total equals the served heatmap's mass
    total = 0.0
    for line in text.splitlines():
        if line.startswith("drift_detect_heatmap_total{"):
            total += float(line.rsplit(" ", 1)[1])
    assert total == sum(map(sum, res.detect_heatmap)) > 0


# ------------------------------------- phases on the profiler's clock
PHASES = ("batch.prepare", "batch.sample", "batch.twin", "batch.sync",
          "batch.quality", "batch.account")


@pytest.fixture(scope="module")
def profiled_drift_batch(tmp_path_factory):
    """A warm smoke drift batch served under ``jax.profiler`` on the CPU:
    (engine, the trace's ProfileData)."""
    from jax.profiler import ProfileData
    eng = _engine()
    eng.submit(steps=3, mode="drift", op="undervolt", seed=0)
    eng.run()                       # compiles the sampler and its twin
    trace_dir = tmp_path_factory.mktemp("profile")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        # a fresh seed, so the twin runs instead of hitting its cache
        eng.submit(steps=3, mode="drift", op="undervolt", seed=1)
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    return eng, ProfileData.from_file(str(path))


def test_drift_batch_records_phase_spans_in_order(profiled_drift_batch):
    eng, _ = profiled_drift_batch
    for batch in (0, 1):
        phases = [s for s in eng.tracer.spans()
                  if s.kind == "phase" and s.batch_index == batch]
        assert tuple(s.name for s in phases) == PHASES
        assert all(s.request_ids == (batch,) for s in phases)
        assert all(s.t1_wall_s >= s.t0_wall_s for s in phases)
        assert all(a.t1_wall_s <= b.t0_wall_s
                   for a, b in zip(phases, phases[1:]))


def test_phase_annotations_on_the_profiler_clock(profiled_drift_batch):
    """Each ``batch.*`` annotation on the host plane matches its ring span
    mapped through the recorder's epoch to within 1 ms, and the device
    work of the batch runs under the mode-named samplers."""
    eng, data = profiled_drift_batch
    start_ns = next(dict(p.stats)["profile_start_time"] for p in data.planes
                    if p.name == "Task Environment")
    annotations, modules = [], set()
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("batch."):
                    annotations.append((ev.start_ns,
                                        ev.start_ns + ev.duration_ns,
                                        ev.name))
                modules.add(dict(ev.stats).get("hlo_module"))
    annotations.sort()
    assert tuple(n for _, _, n in annotations) == PHASES
    phases = [s for s in eng.tracer.spans()
              if s.kind == "phase" and s.batch_index == 1]
    for (t0, t1, name), span in zip(annotations, phases):
        assert span.name == name
        for wall_s, at in ((span.t0_wall_s, t0), (span.t1_wall_s, t1)):
            mapped = eng.tracer.profiler_ns(wall_s) - start_ns
            assert abs(mapped - at) < 1e6, (name, mapped, at)
    assert {"jit_sample_drift", "jit_sample_clean"} <= modules


def test_drift_sampler_hlo_names_protection_scopes():
    """The compiled smoke drift sampler carries each protection phase's
    scope and the attention scope in its op_name metadata, under the
    mode-named module."""
    import re

    from repro import configs
    from repro.core import dvfs
    from repro.core.exec_ctx import DriftSystemConfig
    from repro.diffusion import sampler as sampler_lib
    from repro.serving.engine import OP_BY_NAME
    from repro.train import steps as steps_lib

    cfg = configs.get_config(ARCH, smoke=True)
    scfg = sampler_lib.SamplerConfig(
        num_sample_steps=3, drift=DriftSystemConfig(mode="drift"),
        schedule=dvfs.fine_grained_schedule(3, OP_BY_NAME["undervolt"],
                                            nominal_steps=1))
    fn = sampler_lib.make_sampler(cfg, scfg)
    shape = (1, cfg.latent_size, cfg.latent_size, cfg.latent_channels)
    params = steps_lib.init_model_params(cfg, jax.random.PRNGKey(0))
    hlo = fn.lower(params, jax.random.PRNGKey(1), jax.numpy.zeros(shape),
                   jax.numpy.zeros((1,), "int32"), None,
                   dvfs.ber_monitor_init()).compile().as_text()
    assert "jit_sample_drift" in hlo
    op_names = re.findall(r'op_name="([^"]*)"', hlo)
    scopes = {part for name in op_names for part in name.split("/")}
    assert {"drift.quantize", "drift.gemm", "drift.inject", "drift.checksum",
            "drift.correct", "drift.ckpt", "dit.attention", "dit.modulation",
            "sampler.step"} <= scopes


# ------------------------------------------------------------------ mesh
@needs_mesh
def test_sharded_bit_identity_tracing_on_off():
    """8-fake-device twin: streamed + monitored on the mesh, recorder on
    vs off, finals and heatmaps bit-identical."""
    from repro.serving import make_engine

    def run(tracer):
        eng = make_engine(arch=ARCH, smoke=True, bucket=2, tracer=tracer)
        for i in range(2):
            eng.submit(steps=4, mode="drift", op="undervolt", seed=i)
        return eng, _drain(eng, stream=2)

    eng_on, res_on = run(None)
    eng_off, res_off = run(FlightRecorder(enabled=False))
    assert [latents_sha256(r.latents) for r in res_on] == \
        [latents_sha256(r.latents) for r in res_off]
    assert [r.detect_heatmap for r in res_on] == \
        [r.detect_heatmap for r in res_off]
    assert res_on[0].detect_heatmap is not None
    counts = kind_counts(eng_on.tracer)
    assert counts.get("window") == 2 and counts.get("detect") == 1
    assert len(eng_off.tracer) == 0
