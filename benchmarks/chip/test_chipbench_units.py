"""CPU tests of the benchmark's yardstick: trace reduction, operation
counts, traffic generation, the engine-driving loop and the loading of
files by name. None of them needs an accelerator."""
from __future__ import annotations

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
        self.t = 100.0

    def __call__(self):
        return self.t


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
    with pytest.raises(ValueError):
        traffic.clients({"arrival": "poisson", "clients": 8})
    with pytest.raises(ValueError):
        traffic.clients({"arrival": "closed", "clients": 0})


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
    code = f"""
import sys
sys.path.insert(0, {str(HERE)!r})
import runpy
import chipbench.runner, chipbench.compare, chipbench.ref_dit
import chipbench.weights, chipbench.trace
for m in ("queue_wait_p90_s", "bucket_fill", "device_ms_per_image_step",
          "mfu", "device_idle_share"):
    __import__("chipbench.metrics." + m)
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
