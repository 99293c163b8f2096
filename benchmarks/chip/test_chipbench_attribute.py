"""CPU tests of the attribution of device time to the program's modules,
scopes and host phases (``chipbench.attribute``), and of the per-layer
readers that take their numbers from it, on hand-built traces."""
from __future__ import annotations

import os
import subprocess
import sys
import types
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import attribute as att_lib  # noqa: E402
from chipbench import spec, trace as trace_lib  # noqa: E402

Op = att_lib.Op
DRIFT, TWIN = "jit_sample_drift", "jit_sample_clean"


def test_scope_of_takes_the_innermost_program_scope():
    assert att_lib.scope_of(
        "jit(sample_drift)/while/body/closed_call/drift.inject/"
        "jit(_randint)/while/body/add") == "drift.inject"
    assert att_lib.scope_of(
        "jit(sample_drift)/while/body/dit.modulation/drift.gemm/mul"
    ) == "drift.gemm"
    assert att_lib.scope_of(
        "jit(sample_clean)/while/body/closed_call/sampler.step/add"
    ) == "sampler.step"
    assert att_lib.scope_of("jit(sample_drift)/while/body/dynamic_slice"
                            ) == "other"
    assert att_lib.scope_of("") == "other"
    # neither a source file nor a look-alike is a scope
    assert att_lib.scope_of("dit.py/drift/sampler.stepper/x") == "other"


def test_op_name_from_instruction_text():
    text = ('%fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop, '
            'metadata={op_name="jit(sample_drift)/drift.ckpt/select_n" '
            'source_file="exec_ctx.py"}')
    assert att_lib.op_name_of(text) == "jit(sample_drift)/drift.ckpt/select_n"
    assert att_lib.op_name_of("%fusion.3 = f32[4]{0} fusion()") == ""


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    """One protobuf field: an int as a varint, bytes or str delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _xspace(planes):
    """An XSpace of (plane name, {stat id: name}, [(event name, [(stat id,
    value)])]), each stat value a str, an int or a reference to a stat
    name (``("ref", id)``)."""
    space = b""
    for name, stat_names, events in planes:
        plane = _field(2, name) + _field(3, _field(2, "XLA Ops"))
        for sid, sname in stat_names.items():
            plane += _field(5, _field(1, sid) + _field(
                2, _field(1, sid) + _field(2, sname)))
        for i, (ev_name, stats) in enumerate(events):
            meta = _field(1, i) + _field(2, ev_name)
            for sid, value in stats:
                if isinstance(value, tuple):
                    stat = _field(1, sid) + _field(7, value[1])
                elif isinstance(value, int):
                    stat = _field(1, sid) + _field(3, value)
                else:
                    stat = _field(1, sid) + _field(5, value)
                meta += _field(5, stat)
            plane += _field(4, _field(1, i) + _field(2, meta))
        space += _field(1, plane)
    return space


def test_op_names_read_from_event_metadata(tmp_path):
    """The TPU keeps an op's op_name as the ``tf_op`` stat of its event
    metadata, beside the module's ``program_id``; host planes are
    skipped."""
    big = 2 ** 63 + 5                     # program ids use all 64 bits
    stats = {1: "tf_op", 2: "program_id", 3: "hlo_category",
             4: "jit(sample_drift)/while/body/drift.inject/xor:"}
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace([
        ("/device:TPU:0", stats, [
            ("%fusion.1 = s32[4]{0} fusion()",
             [(3, "loop fusion"), (1, ("ref", 4)), (2, big)]),
            ("%reduce.2 = s32[4]{0} reduce()",
             [(1, "jit(sample_clean)/drift.checksum/reduce_sum:"),
              (2, 7)]),
            ("%copy.3 = s32[4]{0} copy()", [(2, 7)])]),
        ("/host:CPU", stats, [("batch.sample", [(1, "x"), (2, 1)])])]))
    assert att_lib.op_names(str(path)) == {
        (big, "%fusion.1 = s32[4]{0} fusion()"):
            "jit(sample_drift)/while/body/drift.inject/xor:",
        (7, "%reduce.2 = s32[4]{0} reduce()"):
            "jit(sample_clean)/drift.checksum/reduce_sum:"}


def test_module_by_containment():
    events = [("%reduce.1 = s32[8]{0} reduce(s32[8,4]{1,0} %a)", 10, 20),
              ("%reduce.1 = s32[8]{0} reduce(s32[8,4]{1,0} %a)", 110, 120),
              ("%while.2 = (s32[]) while((s32[]) %t)", 5, 150),
              ("%fusion.9 = f32[2]{0} fusion()", 200, 210)]
    modules = [(f"{DRIFT}(7)", 0, 100), (f"{TWIN}(8)", 100, 190)]
    names = {(7, events[0][0]): "jit(sample_drift)/drift.checksum/add:",
             (8, events[0][0]): "jit(sample_clean)/dit.attention/exp:"}
    ops = att_lib.attribute_plane(events, modules, names)
    assert [(o.module, o.scope) for o in ops] == [
        (DRIFT, "drift.checksum"), (TWIN, "dit.attention"), ("", "other")]
    assert ops[0].label == "reduce s32[8]"          # control flow skipped
    assert att_lib.module_of("jit_sample_drift_window(12)") == \
        ("jit_sample_drift_window", 12)
    assert att_lib.module_of("") == ("", None)


def _attributed():
    """One device, window [0, 1000) ns, two batches of phases."""
    ops = [Op("fusion a", 0, 100, DRIFT, "drift.inject"),
           Op("reduce b", 100, 250, DRIFT, "drift.checksum"),
           Op("fusion c", 250, 300, DRIFT, "drift.correct"),
           Op("fusion d", 280, 320, DRIFT, "drift.ckpt"),   # overlaps c
           Op("fusion e", 320, 400, DRIFT, "dit.attention"),
           Op("copy-start f", 400, 900, DRIFT, "other"),    # async: no op
           Op("fusion g", 450, 650, TWIN, "drift.checksum"),
           Op("fusion h", 700, 760, TWIN, "other"),
           Op("clamp i", 800, 850, "jit_clip", "other"),
           Op("fusion a", 950, 1100, DRIFT, "drift.inject")]  # clipped
    host = [("window", 0, 1000),
            ("batch.prepare", 0, 10), ("batch.sample", 10, 400),
            ("batch.twin", 400, 780), ("batch.sync", 780, 800),
            ("batch.quality", 800, 860), ("batch.account", 860, 900),
            ("batch.prepare", 900, 950), ("batch.sample", 950, 1000),
            ("engine.run", 0, 1000)]
    return att_lib.Attributed(device_ops={"/device:TPU:0": ops},
                              host_spans=host)


# the readers of the attributed trace, by metric name
SCOPE_READERS = ("attention_ms_per_image_step", "gemm_ms_per_image_step",
                 "checksum_ms_per_image_step", "rollback_ms_per_image_step",
                 "twin_share", "engine_idle_ms_per_batch")


def _read_all(att, window, images, steps):
    run = types.SimpleNamespace(scopes=att_lib.window_scopes(att, window),
                                window_images=images, steps=steps)
    return {name: spec.metric_reader(name)(run) for name in SCOPE_READERS}


def test_metrics_on_a_hand_built_trace():
    att = _attributed()
    m = _read_all(att, (0, 1000), images=2, steps=5)
    busy = 400 + 200 + 60 + 50 + 50          # union of ops in the window
    assert att_lib.busy_s(att, (0, 1000)) == pytest.approx(busy * 1e-9)
    assert m["twin_share"] == pytest.approx(100 * 260 / busy)
    # idle under batch.* spans: [400,450) [650,700) [760,800) [850,950)
    assert m["engine_idle_ms_per_batch"] == pytest.approx(
        1e3 * (50 + 50 + 40 + 100) * 1e-9 / 2)
    per = 1e3 * 1e-9 / (2 * 5)
    assert m["gemm_ms_per_image_step"] is None        # no drift.gemm op
    gemm = att_lib.Attributed(
        device_ops={p: [o._replace(scope="drift.gemm")
                        if o.label == "fusion h" else o for o in ops]
                    for p, ops in att.device_ops.items()},
        host_spans=att.host_spans)
    assert _read_all(gemm, (0, 1000), images=2, steps=5)[
        "gemm_ms_per_image_step"] == pytest.approx(60 * per)
    assert m["checksum_ms_per_image_step"] == pytest.approx((150 + 200)
                                                            * per)
    # by scope, then summed: correct [250,300) and ckpt [280,320) overlap
    # here, which ops of one core never do
    assert m["rollback_ms_per_image_step"] == pytest.approx((50 + 40) * per)
    assert m["attention_ms_per_image_step"] == pytest.approx(80 * per)
    scopes = att_lib.window_scopes(att, (0, 1000))
    assert scopes.by_scope["drift.inject"] == pytest.approx(150e-9)
    assert scopes.by_module["jit_clip"] == pytest.approx(50e-9)
    assert scopes.batches == 2


def test_breakdown_charges_scopes_and_eager_modules():
    b = att_lib.breakdown(_attributed(), (0, 1000), top=3)
    # unscoped sampler time: fusion h (60 ns); jit_clip is an eager module
    assert b["charged_share"] == pytest.approx(100 * (760 - 60) / 760)
    assert b["top_ops"][0] == ["fusion g", "drift.checksum",
                               pytest.approx(200e-9)]
    assert b["by_module_scope"][0] == [TWIN, "drift.checksum",
                                       pytest.approx(200e-9)]


def test_metrics_return_none_where_nothing_is_named():
    """A program without scopes, mode names or phase spans (the parent of
    PR 13's naming) reads None for every metric; so does an untraced run,
    a clean cell's sampler (no twin beside a drift sampler), and a window
    that served no image."""
    bare = att_lib.Attributed(
        device_ops={"/device:TPU:0": [Op("fusion a", 0, 100, "jit__run",
                                         "other")]},
        host_spans=[("window", 0, 200), ("engine.run", 0, 200)])
    m = _read_all(bare, (0, 200), images=4, steps=20)
    assert set(m) == set(SCOPE_READERS)
    assert all(v is None for v in m.values()), m
    untraced = types.SimpleNamespace(scopes=None, window_images=4, steps=20)
    assert all(spec.metric_reader(n)(untraced) is None
               for n in SCOPE_READERS)
    clean = att_lib.Attributed(
        device_ops={"/device:TPU:0": [
            Op("fusion a", 0, 100, TWIN, "dit.attention"),
            Op("fusion b", 100, 150, TWIN, "drift.checksum")]},
        host_spans=[("window", 0, 200), ("batch.prepare", 0, 1)])
    m = _read_all(clean, (0, 200), images=4, steps=20)
    assert m["twin_share"] is None
    assert m["attention_ms_per_image_step"] == pytest.approx(
        1e3 * 100e-9 / 80)
    assert m["rollback_ms_per_image_step"] is None
    assert m["gemm_ms_per_image_step"] is None
    assert _read_all(_attributed(), (0, 1000), images=0, steps=20)[
        "checksum_ms_per_image_step"] is None
    # a scope whose ops all lie outside the window is absent, not 0
    assert _read_all(_attributed(), (0, 240), images=2, steps=5)[
        "attention_ms_per_image_step"] is None


def test_attributed_trace_reduces_as_the_plain_trace():
    """The attributed reading hands ``trace.reduce`` the trace
    ``read_xplane`` would, so every existing metric reads the same."""
    att = _attributed()
    tr = att.trace()
    plain = trace_lib.Trace(
        device_ops={"/device:TPU:0": [(o.label, o.start, o.end)
                                      for o in att.device_ops[
                                          "/device:TPU:0"]]},
        host_spans=list(att.host_spans))
    names = ("submit", "engine.run", "collect")
    window = trace_lib.find_span(tr, "window")
    assert trace_lib.reduce(tr, window, names) == \
        trace_lib.reduce(plain, window, names)
    assert att_lib.busy_s(att, window) == pytest.approx(
        trace_lib.reduce(tr, window, names).busy_s)


def test_read_matches_read_xplane_on_a_cpu_trace(tmp_path):
    code = f"""
import sys, jax, jax.numpy as jnp
sys.path.insert(0, {str(HERE)!r})
from chipbench import attribute as a, trace as t
f = jax.jit(lambda x: x @ x)
x = jnp.ones((64, 64)); f(x).block_until_ready()
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0
jax.profiler.start_trace({str(tmp_path)!r}, profiler_options=opts)
with jax.profiler.TraceAnnotation("window"):
    with jax.profiler.TraceAnnotation("batch.sample"):
        f(x).block_until_ready()
jax.profiler.stop_trace()
path = t.xplane_file({str(tmp_path)!r})
got, want = a.read(path).trace(), t.read_xplane(path)
assert got == want, (got, want)
assert any(n == "batch.sample" for n, _, _ in got.host_spans)
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip().endswith("ok")


def test_runner_builds_the_same_summary_from_the_attributed_trace(
        monkeypatch):
    """``runner.read_trace`` reads the trace once, through
    ``attribute.read``; its summary is the one that ``trace.reduce`` gives
    on the plain trace (``read_xplane``'s, which ``Attributed.trace``
    equals: ``test_read_matches_read_xplane_on_a_cpu_trace``), and its
    scopes are ``window_scopes`` of the window."""
    from chipbench import runner
    att = _attributed()
    plain = trace_lib.Trace(
        device_ops={p: [(o.label, o.start, o.end) for o in ops]
                    for p, ops in att.device_ops.items()},
        host_spans=list(att.host_spans))
    monkeypatch.setattr(trace_lib, "xplane_file", lambda d: f"{d}/t.pb")
    monkeypatch.setattr(att_lib, "read", lambda path: att)
    summary, scopes = runner.read_trace("trace-dir")
    window = trace_lib.find_span(plain, "window")
    assert summary == trace_lib.reduce(plain, window, runner.GAP_SPANS)
    assert scopes == att_lib.window_scopes(att, window)
    assert "wait" in runner.GAP_SPANS


def _idle_in_spans_reference(att, window, spans):
    """Device idle inside ``spans``, span by span over every busy interval
    (the reading ``window_scopes`` replaced with one merged pass)."""
    lo, hi = window
    inside = trace_lib.union(trace_lib.clip(spans, lo, hi))
    planes = att_lib._planes(att)
    total = 0.0
    for ops in planes:
        busy = trace_lib.union(trace_lib.clip(
            [(o.start, o.end) for o in ops], lo, hi))
        for s, e in inside:
            total += sum(b - a for a, b in trace_lib.gaps(
                trace_lib.clip(busy, s, e), s, e))
    return total * 1e-9 / max(len(planes), 1)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_window_scopes_in_one_pass_match_the_unions(seed):
    """On random traces of two devices, with overlapping and unsorted ops,
    asynchronous starts and ops crossing the window's edges, the one-pass
    reading equals the per-key unions of ``busy_by`` and the span-by-span
    idle time."""
    import random
    rng = random.Random(seed)
    scopes = ["drift.gemm", "drift.checksum", "dit.attention", "other"]
    device_ops, host = {}, [("window", 500, 90_000)]
    for d in range(2):
        ops, t = [], 0
        while t < 100_000:
            dur = rng.randint(1, 400)
            label = rng.choice(["fusion a", "reduce b", "copy-start c"])
            ops.append(Op(label, t, t + dur, rng.choice([DRIFT, TWIN, ""]),
                          rng.choice(scopes)))
            t += rng.randint(-100, 500)
        rng.shuffle(ops)
        device_ops[f"/device:TPU:{d}"] = ops
    t = 0
    while t < 100_000:
        n = rng.randint(50, 3000)
        host.append((rng.choice(["batch.prepare", "batch.sample", "submit"]),
                     t, t + n))
        t += n + rng.randint(0, 2000)
    att = att_lib.Attributed(device_ops=device_ops, host_spans=host)
    window = (500, 90_000)
    got = att_lib.window_scopes(att, window)
    assert got.busy_s == pytest.approx(att_lib.busy_s(att, window))
    for kind, key in (("by_scope", lambda o: o.scope),
                      ("by_module", lambda o: o.module)):
        want = att_lib.busy_by(att, window, key)
        assert getattr(got, kind) == pytest.approx(want), kind
    assert got.phase_idle_s == pytest.approx(_idle_in_spans_reference(
        att, window, att_lib.phase_spans(att, window)))
    assert got.batches == len(att_lib.phase_spans(att, window,
                                                  "batch.prepare"))
