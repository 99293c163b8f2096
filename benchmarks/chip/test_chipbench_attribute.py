"""CPU tests of the attribution of device time to the program's modules,
scopes and host phases (``chipbench.attribute``), on hand-built traces."""
from __future__ import annotations

import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench import attribute as att_lib  # noqa: E402
from chipbench import trace as trace_lib  # noqa: E402

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


def test_metrics_on_a_hand_built_trace():
    att = _attributed()
    m = att_lib.metrics(att, (0, 1000), images=2, steps=5)
    busy = 400 + 200 + 60 + 50 + 50          # union of ops in the window
    assert att_lib.busy_s(att, (0, 1000)) == pytest.approx(busy * 1e-9)
    assert m["twin_share"] == pytest.approx(100 * 260 / busy)
    # idle under batch.* spans: [400,450) [650,700) [760,800) [850,950)
    assert m["engine_idle_ms_per_batch"] == pytest.approx(
        1e3 * (50 + 50 + 40 + 100) * 1e-9 / 2)
    per = 1e3 * 1e-9 / (2 * 5)
    assert m["inject_ms_per_image_step"] == pytest.approx((100 + 50) * per)
    assert m["checksum_ms_per_image_step"] == pytest.approx((150 + 200)
                                                            * per)
    assert m["rollback_ms_per_image_step"] == pytest.approx(70 * per)
    assert m["attention_ms_per_image_step"] == pytest.approx(80 * per)


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
    this change) reads None for every metric; an empty trace reads
    nothing."""
    bare = att_lib.Attributed(
        device_ops={"/device:TPU:0": [Op("fusion a", 0, 100, "jit__run",
                                         "other")]},
        host_spans=[("window", 0, 200), ("engine.run", 0, 200)])
    m = att_lib.metrics(bare, (0, 200), images=4, steps=20)
    assert set(m) == {"twin_share", "engine_idle_ms_per_batch",
                      *att_lib.SCOPE_METRICS}
    assert all(v is None for v in m.values())
    assert att_lib.metrics(att_lib.Attributed({}, []), (0, 1), 4, 20) == {}
    assert att_lib.metrics(_attributed(), (0, 1000), images=0,
                           steps=20)["inject_ms_per_image_step"] is None


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
