"""Reduce a profiler trace to device busy time, idle gaps and op totals.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``.
:func:`read_xplane` turns it into plain lists: each device plane's
operations from its ``XLA Ops`` line, and the host's annotation spans
(``jax.profiler.TraceAnnotation``), all in nanoseconds on the trace's one
clock. :func:`reduce` works on those lists only, so it is checked on small
synthetic traces.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the window and averaged over the devices used. Control
flow (``while``, ``conditional``, ``call``) is not an operation of its own:
its event spans the operations of its body, and the gaps between them are
idle. Nor is an asynchronous ``*-start`` (``copy-start``, ``slice-start``,
``all-reduce-start``): its event lasts from issuing a transfer until the
matching ``*-done``, while other operations or nothing run beside it, so it
is left out of busy time and of the op totals. A transfer in flight while
no operation runs is idle time. The ``*-done`` is the core waiting for the
transfer and counts as an operation. An idle gap is a stretch of the window in which no operation ran; it
is named by the host span that overlaps it most.

A TPU names each op event by its HLO instruction
(``%fusion.872 = (s32[4096]{...}, ...) fusion(...), kind=kOutput, ...``).
Op totals group them by opcode and result type (``fusion
(s32[4096],s32[4096,4608])``), which stays the same across the layers of a
scanned model and across recompiles.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]
DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
CONTROL_FLOW = ("while", "conditional", "call")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_ASYNC_START = re.compile(r"-start(\.\d+)?$")


def op_label(hlo: str) -> str:
    """``opcode (result type)`` of an HLO instruction's text; other names
    are kept as they are."""
    if " = " not in hlo:
        return hlo
    rhs = hlo.split(" = ", 1)[1]
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        typ, rest = rhs[:i + 1], rhs[i + 1:]
    else:
        typ, _, rest = rhs.partition(" ")
    opcode = rest.strip().split("(", 1)[0]
    typ = re.sub(r"\s+", "", _LAYOUT.sub("", _LAYOUT.sub("", typ)))
    return f"{opcode} {typ}"[:160]


@dataclasses.dataclass
class Trace:
    # device plane name -> [(op name, start_ns, end_ns)]
    device_ops: Dict[str, List[Tuple[str, float, float]]]
    # [(span name, start_ns, end_ns)] of the host's annotations
    host_spans: List[Tuple[str, float, float]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    op_seconds: List[Tuple[str, float]]       # descending
    gaps: List[Tuple[str, float]]             # descending

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def is_async_start(label: str) -> bool:
    """Whether an op label is an asynchronous ``*-start``."""
    return bool(_ASYNC_START.search(label.split(" ", 1)[0]))


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[list] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Sequence[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Stretches of [lo, hi] that ``busy`` (sorted, disjoint) leaves out."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _name_gap(gap: Interval, spans, names: Sequence[str]) -> str:
    best, best_overlap = "other", 0.0
    for name, s, e in spans:
        if name not in names:
            continue
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def find_span(trace: Trace, name: str) -> Interval:
    hits = [(s, e) for n, s, e in trace.host_spans if n == name]
    if len(hits) != 1:
        raise ValueError(f"expected one host span {name!r} in the trace, "
                         f"found {len(hits)}")
    return hits[0]


def reduce(trace: Trace, window: Interval, gap_names: Sequence[str],
           top: int = 10) -> Summary:
    """Busy time, op totals and idle gaps of the devices in ``window``."""
    lo, hi = window
    planes = [[op for op in ops if not is_async_start(op[0])]
              for ops in trace.device_ops.values()]
    planes = [ops for ops in planes if ops]
    if not planes:
        raise ValueError("the trace holds no device operation")
    busy_ns, per_op = 0.0, collections.Counter()
    all_gaps: List[Interval] = []
    for ops in planes:
        busy = union(clip([(s, e) for _, s, e in ops], lo, hi))
        busy_ns += sum(e - s for s, e in busy)
        for name, s, e in ops:
            c = min(e, hi) - max(s, lo)
            if c > 0:
                per_op[name] += c
        all_gaps.extend(gaps(busy, lo, hi))
    n = len(planes)
    ops = sorted(((k, v * 1e-9 / n) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])
    # a long window holds a gap between almost every pair of ops: name
    # only the longest, against the named spans only
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:top]
    spans = [sp for sp in trace.host_spans if sp[0] in gap_names]
    gap_list = [(_name_gap(g, spans, gap_names), (g[1] - g[0]) * 1e-9)
                for g in longest]
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / n,
                   op_seconds=ops[:top], gaps=gap_list)


def xplane_file(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {found}")
    return found[0]


def read_xplane(path: str) -> Trace:
    """Device ops and host annotations of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops: Dict[str, list] = {}
    host: List[Tuple[str, float, float]] = []
    labels: Dict[str, str] = {}     # a scanned loop repeats its op names
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = device_ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    label = labels.get(ev.name)
                    if label is None:
                        label = labels[ev.name] = op_label(ev.name)
                    if label.split(" ", 1)[0] in CONTROL_FLOW:
                        continue
                    ops.append((label, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.name, ev.start_ns,
                                 ev.start_ns + ev.duration_ns))
    return Trace(device_ops=device_ops, host_spans=host)
