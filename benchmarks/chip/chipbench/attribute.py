"""Charge a profiler trace's device time to the program's own names.

The program names its device work in two ways. Each jitted sampler is
named by mode (``jit_sample_drift`` is the served drift sampler,
``jit_sample_clean`` the clean twin or the clean cell's sampler), and each
phase of the work runs under a ``jax.named_scope`` (``drift.quantize``,
``drift.gemm``, ``drift.inject``, ``drift.checksum``, ``drift.correct``,
``drift.ckpt``, ``dit.attention``, ``dit.modulation``, ``dit.embed``,
``sampler.step``) that the compiler keeps in each instruction's
``op_name`` metadata. Its host phases (``batch.prepare``,
``batch.sample``, ``batch.twin``, ``batch.sync``, ``batch.quality``,
``batch.account``) are profiler annotations on the trace's clock.

:func:`read` reads an ``.xplane.pb`` and keeps each device op with its
module and its innermost scope. The module is the event of the plane's
``XLA Modules`` line that contains the op (``jit_sample_drift(<program
id>)``). On a TPU the op's event is named by its HLO instruction, and its
op_name is the ``tf_op`` stat of the event's metadata, which
``jax.profiler.ProfileData`` does not expose: :func:`op_names` reads it,
keyed by program id and instruction, from the file's protobuf itself. An
instruction text that carries ``metadata={op_name="..."}`` serves as well.
A fusion carries the metadata of its root instruction and is charged to
that scope. An op without a scope is charged to ``other``. Lookups are
cached per module and distinct event name.

:func:`window_scopes` turns that into what the per-layer readers
(``chipbench/metrics/``) take: the window's busy seconds by innermost scope
and by module, and the device's idle time inside the engine's host phases
with the number of batches they hold. Busy time is a union of op intervals
clipped to the window and averaged over the devices used, as in
:func:`chipbench.trace.reduce`; control flow and asynchronous ``*-start``
events are not ops here either.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

from chipbench import trace as trace_lib

OTHER = "other"
MODULES_LINE = "XLA Modules"
DRIFT_MODULE = "jit_sample_drift"
TWIN_MODULE = "jit_sample_clean"
PHASE_PREFIX = "batch."
_SCOPE = re.compile(r"^(?:(?:drift|dit)\.(?!py$)[A-Za-z_]+|sampler\.step)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE_ID = re.compile(r"\(\d+\)$")


class Op(NamedTuple):
    label: str          # opcode (result type), as trace.op_label gives it
    start: float        # ns
    end: float
    module: str         # jit_sample_drift, jit_sample_clean, ... or ""
    scope: str          # innermost program scope, or OTHER


@dataclasses.dataclass
class Attributed:
    # device plane name -> ops
    device_ops: Dict[str, List[Op]]
    # [(span name, start_ns, end_ns)] of the host's annotations
    host_spans: List[Tuple[str, float, float]]

    def trace(self) -> trace_lib.Trace:
        """The same trace as :func:`chipbench.trace.read_xplane` reads."""
        return trace_lib.Trace(
            device_ops={p: [(o.label, o.start, o.end) for o in ops]
                        for p, ops in self.device_ops.items()},
            host_spans=list(self.host_spans))


def scope_of(op_name: str) -> str:
    """The innermost program scope on an op_name path, else ``other``."""
    for part in reversed(op_name.split("/")):
        if _SCOPE.match(part):
            return part
    return OTHER


def op_name_of(name: str) -> str:
    """The op_name an instruction's text carries, else ""."""
    m = _OP_NAME.search(name)
    return m.group(1) if m else ""


def _varint(buf, i: int) -> Tuple[int, int]:
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a protobuf message; a
    length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in an xplane")
        yield key >> 3, value


def _map_values(entries):
    """The messages of a protobuf map's entries (key 1, value 2)."""
    for entry in entries:
        for f, v in _fields(entry):
            if f == 2:
                yield v


def op_names(path: str) -> Dict[Tuple[int, str], str]:
    """{(program id, instruction text): op_name} of every device op, from
    the ``tf_op`` and ``program_id`` stats of the device planes' event
    metadata (``XSpace.planes[].event_metadata[].stats``)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[Tuple[int, str], str] = {}
    for field, plane in _fields(space):
        if field != 1:                              # XSpace.planes
            continue
        name, events, stats = "", [], []
        for f, v in _fields(plane):
            if f == 2:                              # XPlane.name
                name = bytes(v).decode()
            elif f == 4:                            # event_metadata map
                events.append(v)
            elif f == 5:                            # stat_metadata map
                stats.append(v)
        if not name.startswith(trace_lib.DEVICE_PREFIX):
            continue
        stat_name = {}
        for meta in _map_values(stats):
            sid, sname = 0, ""
            for f, v in _fields(meta):
                if f == 1:
                    sid = v
                elif f == 2:
                    sname = bytes(v).decode()
            stat_name[sid] = sname
        for meta in _map_values(events):
            ev_name, tf_op, program = "", "", None
            for f, v in _fields(meta):
                if f == 2:                          # XEventMetadata.name
                    ev_name = bytes(v).decode()
                elif f == 5:                        # XEventMetadata.stats
                    sid, value = 0, None
                    for sf, sv in _fields(v):
                        if sf == 1:
                            sid = sv
                        elif sf in (3, 4):          # uint64, int64
                            value = sv
                        elif sf == 5:               # str
                            value = bytes(sv).decode()
                        elif sf == 7:               # ref to a stat name
                            value = stat_name.get(sv, "")
                    if stat_name.get(sid) == "tf_op":
                        tf_op = str(value)
                    elif stat_name.get(sid) == "program_id":
                        program = value
            if tf_op and program is not None:
                out[(program, ev_name)] = tf_op
    return out


def module_of(event_name: str) -> Tuple[str, Optional[int]]:
    """``jit_sample_drift(12)`` -> (``jit_sample_drift``, 12)."""
    m = _MODULE_ID.search(event_name)
    if m is None:
        return event_name.strip(), None
    return event_name[:m.start()].strip(), int(m.group(0)[1:-1])


class _Modules:
    """Module events of one plane, for lookup by containment."""

    def __init__(self, events: Iterable[Tuple[str, float, float]]):
        self.spans = sorted((s, e, n) for n, s, e in events)
        self.starts = [s for s, _, _ in self.spans]

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        if i >= 0 and t < self.spans[i][1]:
            return self.spans[i][2]
        return ""


def attribute_plane(ops: Iterable[Tuple[str, float, float]],
                    modules: Iterable[Tuple[str, float, float]],
                    names: Dict[Tuple[int, str], str],
                    cache: Optional[Dict] = None) -> List[Op]:
    """Ops of one device plane, (event name, start, end), with module and
    scope: ``modules`` holds the plane's ``XLA Modules`` events, ``names``
    what :func:`op_names` reads. Control flow is left out."""
    cache = {} if cache is None else cache
    lookup = _Modules(modules)
    out = []
    for name, start, end in ops:
        module_event = lookup.at(start)
        info = cache.get((module_event, name))
        if info is None:
            label = trace_lib.op_label(name)
            module, program = module_of(module_event)
            op_name = names.get((program, name)) or op_name_of(name)
            info = cache[(module_event, name)] = (
                label, label.split(" ", 1)[0] in trace_lib.CONTROL_FLOW,
                module, scope_of(op_name))
        label, control, module, scope = info
        if not control:
            out.append(Op(label, start, end, module, scope))
    return out


def read(path: str) -> Attributed:
    """Device ops with module and scope, and host annotations, of an
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    names = op_names(path)
    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Op]] = {}
    host: List[Tuple[str, float, float]] = []
    cache: Dict = {}
    for plane in data.planes:
        if plane.name.startswith(trace_lib.DEVICE_PREFIX):
            lines = list(plane.lines)
            modules = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                       for line in lines if line.name == MODULES_LINE
                       for ev in line.events]
            ops = device_ops.setdefault(plane.name, [])
            for line in lines:
                if line.name == trace_lib.OPS_LINE:
                    ops.extend(attribute_plane(
                        ((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events), modules, names, cache))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
    return Attributed(device_ops=device_ops, host_spans=host)


def _planes(att: Attributed) -> List[List[Op]]:
    labels = {o.label for ops in att.device_ops.values() for o in ops}
    starts = {label for label in labels if trace_lib.is_async_start(label)}
    planes = [[o for o in ops if o.label not in starts]
              for ops in att.device_ops.values()]
    return [ops for ops in planes if ops]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def busy_by(att: Attributed, window: trace_lib.Interval, key
            ) -> Dict[object, float]:
    """Busy seconds in ``window`` by ``key(op)``, averaged over devices;
    a key with no time in the window is left out."""
    lo, hi = window
    planes = _planes(att)
    out: Dict[object, float] = collections.Counter()
    for ops in planes:
        groups = collections.defaultdict(list)
        for o in ops:
            groups[key(o)].append((o.start, o.end))
        for k, iv in groups.items():
            out[k] += _length(trace_lib.union(trace_lib.clip(iv, lo, hi)))
    n = max(len(planes), 1)
    return {k: v * 1e-9 / n for k, v in out.items() if v > 0}


def busy_s(att: Attributed, window: trace_lib.Interval) -> float:
    return sum(busy_by(att, window, lambda o: None).values())


def phase_spans(att: Attributed, window: trace_lib.Interval,
                name: Optional[str] = None) -> List[trace_lib.Interval]:
    """The engine's ``batch.*`` annotations (or those named ``name``) that
    start in ``window``."""
    lo, hi = window
    return [(s, e) for n, s, e in att.host_spans
            if (n == name if name else n.startswith(PHASE_PREFIX))
            and lo <= s < hi]


@dataclasses.dataclass(frozen=True)
class Scopes:
    """What a traced window's device time was spent on."""
    busy_s: float                   # busy seconds, all ops
    by_scope: Dict[str, float]      # busy seconds by innermost scope
    by_module: Dict[str, float]     # busy seconds by module
    phase_idle_s: float             # idle seconds inside ``batch.*`` spans
    batches: int                    # ``batch.prepare`` spans

    def ms_per_image_step(self, scopes, images: int, steps: int
                          ) -> Optional[float]:
        """Busy milliseconds under ``scopes`` per image and step; None
        where none of them ran or nothing was served."""
        found = [self.by_scope[s] for s in scopes if s in self.by_scope]
        if not found or not images or not steps:
            return None
        return 1e3 * sum(found) / (images * steps)


def _overlap(a: List[trace_lib.Interval], b: List[trace_lib.Interval]
             ) -> float:
    """Length of the intersection of two sorted, disjoint interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def window_scopes(att: Attributed, window: trace_lib.Interval) -> Scopes:
    """The busy time of ``window`` by scope and by module, and the device's
    idle time inside the engine's phases, in one pass over each device's
    ops in start order: each key's intervals are merged as they come, so
    every number is a union, as :func:`busy_by` takes it."""
    lo, hi = window
    phases = trace_lib.union(trace_lib.clip(phase_spans(att, window),
                                            lo, hi))
    phase_ns = _length(phases)
    totals: Dict[object, float] = collections.Counter()
    idle_ns = 0.0
    planes = _planes(att)
    for ops in planes:
        open_iv: Dict[object, list] = {}
        busy: List[list] = []
        for o in sorted(ops, key=lambda o: o.start):
            start, end = max(o.start, lo), min(o.end, hi)
            if end <= start:
                continue
            for key in (("scope", o.scope), ("module", o.module)):
                cur = open_iv.get(key)
                if cur is not None and start <= cur[1]:
                    cur[1] = max(cur[1], end)
                    continue
                if cur is not None:
                    totals[key] += cur[1] - cur[0]
                open_iv[key] = [start, end]
            if busy and start <= busy[-1][1]:
                busy[-1][1] = max(busy[-1][1], end)
            else:
                busy.append([start, end])
        for key, (start, end) in open_iv.items():
            totals[key] += end - start
        totals[("all", None)] += _length(busy)
        idle_ns += phase_ns - _overlap(busy, phases)
    n = max(len(planes), 1)

    def seconds(kind):
        return {k: v * 1e-9 / n for (c, k), v in totals.items()
                if c == kind and v > 0}
    return Scopes(
        busy_s=totals[("all", None)] * 1e-9 / n,
        by_scope=seconds("scope"), by_module=seconds("module"),
        phase_idle_s=idle_ns * 1e-9 / n,
        batches=len(phase_spans(att, window, PHASE_PREFIX + "prepare")))


def breakdown(att: Attributed, window: trace_lib.Interval, top: int = 10
              ) -> Dict[str, object]:
    """Busy seconds by module and scope, the share of busy time charged to
    a program scope or to an eager module (a module that is not a
    sampler), and the top ops by time with the scope each was charged
    to."""
    total = busy_s(att, window)
    by_key = busy_by(att, window, lambda o: (o.module, o.scope))
    unscoped = busy_by(att, window, lambda o: o.scope == OTHER and (
        not o.module or o.module.startswith("jit_sample_"))).get(True, 0.0)
    lo, hi = window
    per_op: Dict[Tuple[str, str], float] = collections.Counter()
    planes = _planes(att)
    for ops in planes:
        for o in ops:
            c = min(o.end, hi) - max(o.start, lo)
            if c > 0:
                per_op[(o.label, o.scope)] += c
    n = max(len(planes), 1)
    ops_top = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": total,
        "by_module_scope": [[m, s, v] for (m, s), v in
                            sorted(by_key.items(), key=lambda kv: -kv[1])],
        "charged_share": 100.0 * (1 - unscoped / total) if total else None,
        "top_ops": [[label, scope, v * 1e-9 / n]
                    for (label, scope), v in ops_top],
    }
