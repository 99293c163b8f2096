"""CPU tests of the benchmark's yardstick: trace reduction, operation
counts, the weights and counts a configuration's reference module states,
traffic generation, the engine-driving loops and the loading of files by
name. None of them needs an accelerator."""
from __future__ import annotations

import hashlib
import json
import subprocess
import sys
import types
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import drive, opcount, peaks, spec, stats  # noqa: E402
from chipbench import trace as trace_lib, traffic  # noqa: E402

BENCH = json.loads((spec.REPO_ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [w["name"] for w in BENCH["workloads"]]


# ------------------------------------------------------------ trace reduction
def _trace():
    # two devices; ns clock; window [100, 1100)
    dev0 = [("fusion.1", 100, 300), ("fusion.2", 250, 400),   # overlap
            ("dot.1", 600, 900), ("fusion.1", 1000, 1200)]   # clipped end
    dev1 = [("fusion.1", 0, 150), ("dot.1", 500, 1100)]      # clipped start
    host = [("window", 100, 1100), ("engine.run", 100, 950),
            ("collect", 950, 1000), ("submit", 1050, 1100),
            ("other-thing", 400, 600)]
    return trace_lib.Trace(device_ops={"/device:TPU:0": dev0,
                                       "/device:TPU:1": dev1},
                           host_spans=host)


def test_union_clip_gaps():
    u = trace_lib.union([(5, 7), (1, 3), (2, 4), (7, 8), (9, 9)])
    assert u == [(1, 4), (5, 8)]
    assert trace_lib.clip(u, 2, 6) == [(2, 4), (5, 6)]
    assert trace_lib.gaps(u, 0, 10) == [(0, 1), (4, 5), (8, 10)]
    assert trace_lib.gaps([], 0, 10) == [(0, 10)]


def test_reduce_busy_idle_and_gaps():
    tr = _trace()
    window = trace_lib.find_span(tr, "window")
    s = trace_lib.reduce(tr, window, ("submit", "engine.run", "collect"))
    # dev0 busy in window: [100,400) + [600,900) + [1000,1100) = 700 ns
    # dev1 busy in window: [100,150) + [500,1100) = 650 ns; mean 675
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(675e-9)
    assert s.idle_share == pytest.approx(1 - 0.675)
    ops = dict(s.op_seconds)
    # fusion.1: dev0 200 + 100, dev1 50 -> 350 / 2 devices
    assert ops["fusion.1"] == pytest.approx(175e-9)
    assert ops["dot.1"] == pytest.approx((300 + 600) / 2 * 1e-9)
    # dev0's gaps: [400,600) under engine.run, [900,1000) split between
    # engine.run (50) and collect (50) -> the first wins; dev1's [150,500)
    longest = s.gaps[0]
    assert longest == ("engine.run", pytest.approx(350e-9))
    assert all(name in ("engine.run", "collect", "other")
               for name, _ in s.gaps)


def test_busy_time_per_image_step():
    from chipbench.metrics import device_ms_per_image_step, device_idle_share
    tr = _trace()
    summary = trace_lib.reduce(tr, (100, 1100), ())
    rec = drive.Record(t0=0.0, t_end=2.0)
    rec.batches = [drive.BatchRecord(index=1, seeds=(1, 2, 3), padded=(),
                                     dispatched=0, done=1, in_window=True),
                   drive.BatchRecord(index=2, seeds=(4,), padded=(),
                                     dispatched=1, done=2, in_window=False)]
    run = types.SimpleNamespace(trace=summary,
                                window_images=rec.window_images(), steps=20)
    want = 1e3 * 675e-9 / (3 * 20)
    assert device_ms_per_image_step.read(run) == pytest.approx(want)
    assert device_idle_share.read(run) == pytest.approx(100 * (1 - 0.675))
    assert device_idle_share.read(types.SimpleNamespace(trace=None)) is None


def test_async_start_is_neither_busy_nor_an_op():
    # a copy-start in flight from 200 to 900 spans the idle gap [400, 600)
    copy = ("%copy-start.3 = (u32[4096]{0}, u32[4096]{0}, u32[]{:S(2)}) "
            "copy-start(u32[4096]{0} %p)")
    done = "%copy-done.3 = u32[4096]{0} copy-done((u32[4096]{0}) %c)"
    ops = [("fusion.1", 100, 400), (trace_lib.op_label(copy), 200, 900),
           ("fusion.2", 600, 880), (trace_lib.op_label(done), 880, 900)]
    tr = trace_lib.Trace(device_ops={"/device:TPU:0": ops},
                         host_spans=[("engine.run", 100, 1000)])
    s = trace_lib.reduce(tr, (100, 1000), ("engine.run",))
    assert s.busy_s == pytest.approx(600e-9)
    labels = dict(s.op_seconds)
    assert "copy-done u32[4096]" in labels
    assert not any(trace_lib.is_async_start(k) for k in labels)
    assert sorted(g for _, g in s.gaps) == pytest.approx([100e-9, 200e-9])
    assert trace_lib.is_async_start("slice-start.12")
    assert not trace_lib.is_async_start("fusion (s32[4096],s32[4096,4608])")


def test_mfu_over_the_traced_window():
    from chipbench.metrics import mfu
    cfg = spec.load_json(HERE / "configs" / "dit-xl-512.json")
    v5e = peaks.for_kind("TPU v5 lite")
    summary = trace_lib.Summary(window_s=10.0, busy_s=9.0, op_seconds=[],
                                gaps=[])
    run = types.SimpleNamespace(trace=summary, window_images=4, steps=20,
                                model=cfg, peaks=v5e)
    want = 100 * 4 * 20 * opcount.roofline_s_per_eval(cfg, v5e) / 10.0
    assert mfu.read(run) == pytest.approx(want)
    assert mfu.read(types.SimpleNamespace(trace=None)) is None


def test_op_labels_group_layers_and_skip_control_flow():
    fusion = ("%fusion.872 = (s32[4096]{0:T(1024)}, s32[4096,4608]{1,0:T(8,"
              "128)S(1)}) fusion(u32[]{:T(128)S(6)} %xor.1), kind=kOutput, "
              "calls=%fused_computation.1308")
    assert trace_lib.op_label(fusion) == "fusion (s32[4096],s32[4096,4608])"
    assert trace_lib.op_label(
        "%reduce.14 = s32[128,32,144]{2,1,0:T(8,128)} reduce(s32[1]{0} %r), "
        "dimensions={3}") == "reduce s32[128,32,144]"
    assert trace_lib.op_label("jit__run") == "jit__run"
    loop = "%while.80 = (s32[]{:T(128)}, f32[4]{0}) while((s32[]) %t)"
    assert trace_lib.op_label(loop).split(" ")[0] in trace_lib.CONTROL_FLOW


def test_reduce_refuses_a_trace_without_device_ops():
    tr = trace_lib.Trace(device_ops={"/device:TPU:0": []}, host_spans=[])
    with pytest.raises(ValueError):
        trace_lib.reduce(tr, (0, 1), ())


def test_read_xplane_finds_host_spans(tmp_path):
    """A trace recorded on the CPU holds the harness's annotations."""
    code = f"""
import sys, jax, jax.numpy as jnp
sys.path.insert(0, {str(HERE)!r})
from chipbench import trace as t
f = jax.jit(lambda x: x @ x)
x = jnp.ones((64, 64)); f(x).block_until_ready()
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0
jax.profiler.start_trace({str(tmp_path)!r}, profiler_options=opts)
with jax.profiler.TraceAnnotation("window"):
    with jax.profiler.TraceAnnotation("engine.run"):
        f(x).block_until_ready()
jax.profiler.stop_trace()
tr = t.read_xplane(t.xplane_file({str(tmp_path)!r}))
s, e = t.find_span(tr, "window")
names = [n for n, a, b in tr.host_spans if a >= s and b <= e]
assert "engine.run" in names, names
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={**__import__("os").environ,
                              "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("ok")


# ------------------------------------------------------------ operation count
def test_opcount_dit_xl_512_by_hand():
    cfg = spec.load_json(HERE / "configs" / "dit-xl-512.json")
    ops = opcount.per_eval(cfg)
    t, d, f = 1024, 1152, 4608
    gemm_block = t * (4 * d * d + 2 * d * f)          # 1.63e10
    attn_block = 2 * t * t * d                        # 2.42e9
    assert gemm_block == pytest.approx(1.63e10, rel=2e-3)
    assert attn_block == pytest.approx(2.42e9, rel=2e-3)
    total = ops["int8_macs"] + ops["float_macs"]
    assert total == pytest.approx(5.24e11, rel=3e-3)
    embed = t * 16 * d * 2 + 256 * d + d * d
    assert ops["int8_macs"] == 28 * gemm_block + embed
    assert ops["float_macs"] == 28 * (attn_block + 6 * d * d) + 2 * d * d


def test_opcount_matches_perfmodel_for_dit():
    """The copied counter agrees with the program's perfmodel for DiT (the
    perfmodel is the original the benchmark copied)."""
    sys.path.insert(0, str(spec.REPO_ROOT / "src"))
    from repro import configs
    from repro.perfmodel import flops
    cfg = spec.load_json(HERE / "configs" / "dit-xl-512.json")
    ops = opcount.per_eval(cfg)
    want = flops.gemm_macs_per_model_eval(configs.get_config("dit-xl-512"))
    # the perfmodel leaves out the final adaLN modulation dot (2 d^2)
    assert ops["int8_macs"] + ops["float_macs"] - 2 * 1152 ** 2 == \
        pytest.approx(want, rel=1e-12)


# the parent's DiT trees and weights, recorded before the tree moved into
# the reference module: sha256 of the (path, shape, std) listing, and of the
# smoke weights' paths and bytes at two seeds
TREE_DIGESTS = {
    "testdata/dit-smoke.json":
        "6c2410ef404c7aa60be502422c451d42fa09883c7ef2348671e6ada2fb3f43af",
    "configs/dit-xl-512.json":
        "d3c46dbaacc929a800bf256c9010f87466f6d026c4bb0c3c9a00d35a34189733",
}
WEIGHT_DIGESTS = {
    0: "8f2a4822d9730ea902cb2a9ede01de440161acbb77eefaba2db37bc68b5058bf",
    2 ** 31 + 77:
        "f558733c58d4aa3725ce268ac2413a519e6f8f94312573dd70d4e7fab88a5c7c",
}


def _tree_listing(tree):
    import jax
    from chipbench import weights
    flat = jax.tree_util.tree_flatten_with_path(tree,
                                                is_leaf=weights._is_leaf)[0]
    return [[jax.tree_util.keystr(p), list(shape), std]
            for p, (shape, std) in flat]


@pytest.mark.parametrize("path", sorted(TREE_DIGESTS))
def test_dit_tree_keeps_its_leaf_paths_shapes_and_stds(path):
    cfg = spec.load_json(HERE / path)
    listing = _tree_listing(spec.reference_module(cfg).param_shapes(cfg))
    assert len(listing) == 20
    digest = hashlib.sha256(json.dumps(listing).encode()).hexdigest()
    assert digest == TREE_DIGESTS[path], listing


@pytest.mark.parametrize("seed", sorted(WEIGHT_DIGESTS))
def test_dit_smoke_weights_match_the_recorded_digest(seed):
    import jax
    from chipbench import weights
    cfg = spec.load_json(HERE / "testdata" / "dit-smoke.json")
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            weights.make(cfg, seed))[0]:
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHT_DIGESTS[seed]


def test_a_stand_in_reference_module_needs_no_harness_edit(monkeypatch):
    """Another architecture's tree and count reach ``weights.make``,
    ``opcount`` and ``mfu`` through its reference module alone."""
    import jax
    from chipbench import weights
    from chipbench.metrics import mfu
    cfg = {"name": "stand-in", "reference": "ref_stand_in", "d": 8}
    stand_in = types.SimpleNamespace(
        param_shapes=lambda c: {
            "caption": {"w1": ((16, c["d"]), 0.5), "w2": ((c["d"],), 0.0)},
            "xattn": {"wq": ((2, c["d"], c["d"]), 1.0)},
            "pos": ((4, c["d"]), weights.SINCOS)},
        per_eval=lambda c: {"int8_macs": 3e9, "float_macs": 1e9})
    asked = []

    def reference_module(config):
        asked.append(config["reference"])
        return stand_in
    monkeypatch.setattr(spec, "reference_module", reference_module)
    params = weights.make(cfg, 2 ** 31 + 3)
    assert sorted(params) == ["caption", "pos", "xattn"]
    assert params["caption"]["w1"].shape == (16, 8)
    assert params["xattn"]["wq"].shape == (2, 8, 8)
    assert not np.any(np.asarray(params["caption"]["w2"]))
    np.testing.assert_array_equal(params["pos"], weights.sincos_2d(4, 8))
    assert all(leaf.dtype == np.float32 for leaf in jax.tree.leaves(params))
    v5e = peaks.for_kind("TPU v5 lite")
    roof = 2 * 3e9 / v5e["int8_ops_per_s"] + 2 * 1e9 / v5e["bf16_flops_per_s"]
    assert opcount.roofline_s_per_eval(cfg, v5e) == pytest.approx(roof)
    summary = trace_lib.Summary(window_s=2.0, busy_s=1.0, op_seconds=[],
                                gaps=[])
    run = types.SimpleNamespace(trace=summary, window_images=3, steps=10,
                                model=cfg, peaks=v5e)
    assert mfu.read(run) == pytest.approx(100 * 3 * 10 * roof / 2.0)
    assert set(asked) == {"ref_stand_in"}


def test_peaks_by_device_kind():
    v5e = peaks.for_kind("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["int8_ops_per_s"] == 393e12
    assert v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")
    roof = opcount.roofline_s_per_eval(
        spec.load_json(HERE / "configs" / "dit-xl-512.json"), v5e)
    assert 2.5e-3 < roof < 3.5e-3


# ------------------------------------------------------------ traffic
def test_request_seeds_repeat_by_seed():
    a = [s for _, s in zip(range(300), traffic.request_seeds(2 ** 31 + 77))]
    b = [s for _, s in zip(range(300), traffic.request_seeds(2 ** 31 + 77))]
    c = [s for _, s in zip(range(300), traffic.request_seeds(5))]
    assert a == b and a != c
    assert all(0 <= s < 2 ** 31 for s in a)
    assert traffic.warmup_seeds(9, 4) == traffic.warmup_seeds(9, 4)


class _FakeEngine:
    """Stands in for DriftServeEngine: each run() takes ``batch_s`` on the
    fake clock."""

    def __init__(self, clock, batch_s):
        self.clock, self.batch_s = clock, batch_s
        self.queue, self.batches = [], 0
        self._clean_samples = {}

    def submit(self, seed, **_):
        self.queue.append(seed)

    def run(self):
        self.clock.t += self.batch_s
        out = [types.SimpleNamespace(latents=np.full((2,), s, np.float32),
                                     batch_index=self.batches)
               for s in self.queue]
        self.queue, self.batches = [], self.batches + 1
        return out


class _Clock:
    def __init__(self):
        self.t, self.slept = 100.0, 0.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        assert s >= 0
        self.t += s
        self.slept += s


def test_closed_loop_two_batches_outstanding():
    clock = _Clock()
    eng = _FakeEngine(clock, 1.0)
    mix = {"arrival": "closed", "clients": 8, "request": {}}
    rec = drive.drive(eng, mix, {}, 4, seed=1, seconds=3.5, keep_twin=False,
                      clock=clock)
    # batches dispatched at t0+0,1,2,3 are in the window; the window closes
    # at the completion of the one dispatched at 3 -> 4 s
    assert rec.window_s == pytest.approx(4.0)
    assert rec.window_images() == 16
    lat = sorted(r.done - r.due for r in rec.due_in_window())
    # first bucket waits nothing; every later request waits one batch
    assert lat[:4] == [1.0] * 4 and set(lat[4:]) == {2.0}
    # the 4 requests due before the close are drained and counted
    assert len(rec.due_in_window()) == 20
    assert [b.in_window for b in rec.batches] == [True] * 4 + [False]
    # every client keeps one request outstanding, in the seed's order
    seeds = [s for _, s in zip(range(24), traffic.request_seeds(1))]
    assert [r.seed for r in rec.requests] == seeds[:20]
    assert sorted(r.client for r in rec.requests[:8]) == list(range(8))


def test_closed_loop_refuses_other_arrivals():
    """An arrival process that no generator knows is refused, before any
    request is sent; so is a closed loop without clients."""
    bursty = {"arrival": "bursty", "clients": 8, "rate_per_s": 1.0,
              "request": {}}
    for ask in (traffic.arrival, traffic.clients, traffic.rate):
        with pytest.raises(ValueError, match="unknown arrival"):
            ask(bursty)
    clock = _Clock()
    with pytest.raises(ValueError, match="unknown arrival"):
        drive.drive(_FakeEngine(clock, 1.0), bursty, {}, 4, seed=1,
                    seconds=3.0, keep_twin=False, clock=clock)
    with pytest.raises(ValueError):
        traffic.clients({"arrival": "closed", "clients": 0})
    with pytest.raises(ValueError):
        traffic.rate({"arrival": "poisson", "rate_per_s": 0})


# ------------------------------------------------------------ open loop
POISSON = {"arrival": "poisson", "rate_per_s": 1.6,
           "request": {"mode": "clean", "op": "undervolt"}}


def test_poisson_due_times_repeat_by_seed():
    def offsets(seed):
        return traffic.arrival_offsets(seed, 1.6, 51.0)
    a, b, c = offsets(2 ** 31 + 77), offsets(2 ** 31 + 77), offsets(5)
    assert a == b and a != c
    assert a[0] == 0.0 and all(y > x for x, y in zip(a, a[1:]))
    # every seed offers the same number of requests in the window
    assert {len(offsets(s)) for s in (1, 5, 2 ** 31 + 77, 2 ** 32 + 9)} \
        == {82}
    assert a[-1] < 51.0
    # the requests are the closed loop's, in the same order
    seeds = [s for _, s in zip(range(10), traffic.request_seeds(9))]
    clock = _Clock()
    rec = drive.drive(_FakeEngine(clock, 1.0), POISSON, {}, 4, seed=9,
                      seconds=4.0, keep_twin=False, clock=clock,
                      sleep=clock.sleep)
    assert [r.seed for r in rec.requests] == seeds[:len(rec.requests)]


def test_poisson_mean_rate_over_ten_thousand_draws():
    for seed, rate in ((2 ** 31 + 9, 1.6), (3, 0.25), (4, 40.0)):
        t = traffic.arrival_offsets(seed, rate, 10_000 / rate)
        assert len(t) == 10_000
        gaps = np.diff(t)
        assert 1 / gaps.mean() == pytest.approx(rate, rel=0.05)
        # about exponential: the standard deviation equals the mean, and
        # a gap exceeds the mean with probability 1/e
        assert gaps.std() == pytest.approx(gaps.mean(), rel=0.05)
        assert np.mean(gaps > 1 / rate) == pytest.approx(np.exp(-1),
                                                         rel=0.05)


def test_open_loop_waits_and_dispatches_partial_buckets():
    clock = _Clock()
    eng = _FakeEngine(clock, 1.0)
    rate = 1.6
    rec = drive.drive(eng, POISSON, {}, 4, seed=21, seconds=30.0,
                      keep_twin=False, clock=clock, sleep=clock.sleep)
    offsets = traffic.arrival_offsets(21, rate, 30.0)
    due = [r.due - rec.t0 for r in rec.requests]
    assert due == pytest.approx(offsets)
    # the engine was handed only requests already due, up to a bucket
    for b in rec.batches:
        assert 1 <= len(b.seeds) <= 4
        assert len(b.padded) == 4 and b.padded[len(b.seeds):] == \
            (b.seeds[-1],) * (4 - len(b.seeds))
    for r in rec.requests:
        assert r.dispatched >= r.due
    assert any(len(b.seeds) < 4 for b in rec.batches)
    # it waited when nothing was due: the engine ran less than the window
    assert sum(b.done - b.dispatched for b in rec.batches) < rec.window_s
    assert clock.slept > 0 and rec.late_s == 0
    # a request is handed over when the engine frees or when it falls due
    frees = {b.done for b in rec.batches} | {rec.t0}
    for r in rec.requests:
        assert r.dispatched == r.due or r.dispatched in frees


def test_open_loop_drains_and_counts_due_requests():
    clock = _Clock()
    # batches of 3 s against 1.6 requests/s: the queue grows
    eng = _FakeEngine(clock, 3.0)
    rec = drive.drive(eng, POISSON, {}, 4, seed=2 ** 31 + 1, seconds=10.0,
                      keep_twin=False, clock=clock, sleep=clock.sleep)
    offsets = traffic.arrival_offsets(2 ** 31 + 1, 1.6, 10.0)
    in_window = [b for b in rec.batches if b.in_window]
    # dispatched at 0, 3, 6, 9: the window closes at 12, past its 10 s
    assert [b.dispatched - rec.t0 for b in in_window] == [0, 3, 6, 9]
    assert rec.window_s == pytest.approx(12.0)
    due_by_close = sum(t < rec.window_s for t in offsets)
    assert len(rec.due_in_window()) == due_by_close == len(rec.requests)
    assert all(np.isfinite(r.done) for r in rec.requests)
    assert rec.window_images() == sum(len(b.seeds) for b in in_window)
    assert not rec.batches[-1].in_window
    # the engine was never idle while requests were due
    assert clock.slept == 0


def test_open_loop_window_ends_at_its_length_when_idle():
    clock = _Clock()
    eng = _FakeEngine(clock, 0.1)
    rec = drive.drive(eng, {**POISSON, "rate_per_s": 0.2}, {}, 4, seed=4,
                      seconds=20.0, keep_twin=False, clock=clock,
                      sleep=clock.sleep)
    assert rec.window_s == pytest.approx(20.0)
    assert all(b.in_window for b in rec.batches)
    assert len(rec.due_in_window()) == len(rec.requests)


def test_a_poisson_cell_loads_by_name(tmp_path):
    """A cell with an open-loop mix is a data file and an entry: it loads
    through ``spec.load_cell`` and drives the open loop unedited."""
    bench = dict(BENCH)
    cell = {"name": "dit-xl-512.clean-poisson", "config": "dit-xl-512",
            "traffic": "clean-poisson", "chips": 1, "why": "test"}
    bench["workloads"] = [cell]
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic" / "clean-poisson.json").write_text(
        json.dumps(POISSON))
    (tmp_path / "configs" / "dit-xl-512.json").write_text(
        (HERE / "configs" / "dit-xl-512.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    loaded = spec.load_cell(cell["name"], tmp_path / "BENCHMARK.json",
                            tmp_path)
    assert loaded.traffic == POISSON
    assert traffic.arrival(loaded.traffic) == "poisson"
    assert traffic.rate(loaded.traffic) == 1.6
    clock = _Clock()
    rec = drive.drive(_FakeEngine(clock, 1.0), loaded.traffic, {}, 4,
                      seed=7, seconds=5.0, keep_twin=False, clock=clock,
                      sleep=clock.sleep)
    assert rec.batches and rec.requests


def test_percentiles():
    assert stats.percentile([], 90) is None
    assert stats.percentile([1.0, 2.0, 3.0], 50) == 2.0
    assert stats.percentile(list(range(11)), 90) == pytest.approx(9.0)


# ------------------------------------------------------------ files by name
@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_loads_its_files_by_name(workload):
    cell = spec.load_cell(workload)
    assert cell.config["name"] == cell.config_name
    assert {"arrival", "request"} <= set(cell.traffic)
    assert cell.traffic["request"]["mode"] in ("drift", "clean")
    assert {m.name for m in cell.end_to_end} == {
        "images_per_s", "latency_p50_s", "latency_p90_s", "setup_s"}
    assert cell.per_layer
    for m in cell.per_layer:
        assert callable(spec.metric_reader(m.name))
    assert spec.reference_module(cell.config).sample
    limits = cell.limits
    assert limits, f"limits/{workload}.json is missing"
    names = {"final_gap"} | ({"twin_gap"} if cell.traffic["request"]["mode"]
                             == "drift" else set())
    assert set(spec.readings(limits)) == names


def test_benchmark_json_names_existing_files():
    for c in BENCH["configs"]:
        path = spec.REPO_ROOT / c["file"]
        assert path.is_file(), path
        assert spec.load_json(path)["name"] == c["name"]
    for path in BENCH["paths"]:
        assert (spec.REPO_ROOT / path).is_dir()


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configuration_matches_the_served_model(name):
    sys.path.insert(0, str(spec.REPO_ROOT / "src"))
    from chipbench import runner
    runner.check_model(spec.load_json(HERE / "configs" / f"{name}.json"))


def test_weights_tree_matches_the_served_layout():
    sys.path.insert(0, str(spec.REPO_ROOT / "src"))
    import jax
    from repro import configs
    from repro.train import steps
    from chipbench import weights
    cfg = spec.load_json(HERE / "testdata" / "dit-smoke.json")
    served = jax.eval_shape(lambda: steps.init_model_params(
        configs.get_config("dit-xl-512", smoke=True), jax.random.PRNGKey(0)))
    mine = jax.eval_shape(lambda: weights.make(cfg, 0))
    assert jax.tree.structure(served) == jax.tree.structure(mine)
    for a, b in zip(jax.tree.leaves(served), jax.tree.leaves(mine)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_imports_start_no_backend():
    """Importing the harness initialises no JAX backend (so no TPU)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    code = f"""
import sys
sys.path.insert(0, {str(HERE)!r})
import runpy
import chipbench.runner, chipbench.compare, chipbench.ref_dit
import chipbench.weights, chipbench.trace
from chipbench import spec
for m in {names!r}:
    spec.metric_reader(m)
from jax._src import xla_bridge
assert not xla_bridge._backends, xla_bridge._backends
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


def test_run_refuses_a_machine_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(HERE / "run.py"), "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_compile_counter_sees_a_compile_and_not_a_cached_call():
    import jax
    import jax.numpy as jnp
    from chipbench import runner
    counter = runner.CompileCounter()
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(7.0)
    f(x).block_until_ready()
    counter.armed = True
    f(x).block_until_ready()
    assert counter.events == []
    jax.jit(lambda x: x - 5)(x).block_until_ready()
    counter.armed = False
    # a compile, or a load from the persistent cache (which may report both)
    assert counter.events
