"""Drive the serving engine with a traffic mix and time every request.

``DriftServeEngine.run()`` drains its whole queue and returns only at the
end, so it cannot stand for a server that admits arrivals between batches.
The harness therefore keeps the client-side FIFO of due requests itself and
hands the engine at most one bucket at a time (``submit`` for each, then
``run``). A request is timed from when it was due (when its client sent
it) to when its final latent is on the host.

The window opens at the first dispatch after warm-up and closes at the
completion of the last batch dispatched before ``seconds`` ran out, so no
batch is cut (in an open loop whose engine was idle when ``seconds`` ran
out, at ``seconds``). Requests due before the window closed and not yet
served are drained after it and count towards the latencies.

In an open loop (``poisson``) the engine, when free, is handed up to one
bucket of the requests already due, and pads a partial bucket; when none is
due, the harness waits for the next under a ``wait`` annotation.
"""
from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Callable, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import traffic as traffic_lib


@dataclasses.dataclass
class Request:
    seed: int
    due: float
    client: int = -1
    dispatched: float = float("nan")
    done: float = float("nan")
    batch: int = -1                  # position in Record.batches


@dataclasses.dataclass
class BatchRecord:
    index: int                       # the engine's batch counter
    seeds: tuple                     # live seeds, in slot order
    padded: tuple                    # as the engine padded them
    dispatched: float
    done: float
    in_window: bool
    finals: Optional[np.ndarray] = None     # (live, H, W, C) on the host
    twin: Optional[object] = None           # clean twin (device), drift only


@dataclasses.dataclass
class Record:
    t0: float = 0.0
    t_end: float = 0.0
    late_s: float = 0.0              # longest a wait overshot its end
    requests: List[Request] = dataclasses.field(default_factory=list)
    batches: List[BatchRecord] = dataclasses.field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.t_end - self.t0

    def window_images(self) -> int:
        return sum(len(b.seeds) for b in self.batches if b.in_window)

    def due_in_window(self) -> List[Request]:
        return [r for r in self.requests if r.due < self.t_end]


def serve_batch(eng, batch_reqs: List[Request], fields: dict, bucket: int,
                clock, keep_twin: bool) -> BatchRecord:
    """Submit one bucket, run it, and bring its finals to the host."""
    t = clock()
    with TraceAnnotation("submit"):
        for r in batch_reqs:
            r.dispatched = t
            eng.submit(seed=r.seed, **fields)
    with TraceAnnotation("engine.run"):
        results = eng.run()
    with TraceAnnotation("collect"):
        finals = np.stack(jax.device_get([r.latents for r in results]))
        done = clock()
        twin = None
        if keep_twin:
            (ckey, seeds), twin = next(reversed(eng._clean_samples.items()))
    live = tuple(r.seed for r in batch_reqs)
    padded = live + (live[-1],) * (bucket - len(live))
    if keep_twin and tuple(seeds) != padded:
        raise RuntimeError(f"the clean twin's seeds {seeds} are not the "
                           f"batch's {padded}")
    for r in batch_reqs:
        r.done = done
    return BatchRecord(index=results[0].batch_index, seeds=live,
                       padded=padded, dispatched=t, done=done,
                       in_window=True, finals=finals, twin=twin)


def _serve_next(eng, rec: Record, pending, fields: dict, bucket: int,
                clock, keep_twin: bool, in_window: bool) -> List[Request]:
    """Hand the engine up to one bucket from the head of ``pending``."""
    take = [pending.popleft() for _ in range(min(bucket, len(pending)))]
    for r in take:
        r.batch = len(rec.batches)
        rec.requests.append(r)
    b = serve_batch(eng, take, fields, bucket, clock, keep_twin)
    b.in_window = in_window
    rec.batches.append(b)
    return take


def drive(eng, mix: dict, fields: dict, bucket: int, seed: int,
          seconds: float, keep_twin: bool,
          clock: Callable[[], float] = time.perf_counter,
          sleep: Callable[[float], None] = time.sleep) -> Record:
    """Serve ``mix`` for ``seconds`` and drain what was due in the window.

    Closed loop: every client sends its first request when the window
    opens and its next one the moment the previous one completes, so the
    FIFO never runs dry and the harness never waits for a client. Open
    loop: see :func:`drive_open`."""
    if traffic_lib.arrival(mix) == "poisson":
        return drive_open(eng, mix, fields, bucket, seed, seconds,
                          keep_twin, clock, sleep)
    rec = Record()
    seeds = traffic_lib.request_seeds(seed)
    rec.t0 = t0 = clock()
    pending: "collections.deque[Request]" = collections.deque(
        Request(seed=next(seeds), due=t0, client=c)
        for c in range(traffic_lib.clients(mix)))

    def run_one(in_window: bool) -> None:
        take = _serve_next(eng, rec, pending, fields, bucket, clock,
                           keep_twin, in_window)
        if in_window:
            pending.extend(Request(seed=next(seeds), due=rec.batches[-1].done,
                                   client=r.client) for r in take)

    with TraceAnnotation("window"):
        while clock() - t0 < seconds:
            run_one(in_window=True)
        rec.t_end = rec.batches[-1].done

    # drain: everything due before the window closed; the requests sent at
    # its close were not due in it
    due = [r for r in pending if r.due < rec.t_end]
    pending.clear()
    pending.extend(due)
    while pending:
        run_one(in_window=False)
    return rec


def drive_open(eng, mix: dict, fields: dict, bucket: int, seed: int,
               seconds: float, keep_twin: bool,
               clock: Callable[[], float] = time.perf_counter,
               sleep: Callable[[float], None] = time.sleep) -> Record:
    """Open loop: requests fall due at the mix's offsets from the window's
    open (the first at the open), whatever the engine is doing; none falls
    due after ``seconds``."""
    rec = Record()
    seeds = traffic_lib.request_seeds(seed)
    offsets = iter(traffic_lib.arrival_offsets(seed, traffic_lib.rate(mix),
                                               seconds))
    pending: "collections.deque[Request]" = collections.deque()
    rec.t0 = t0 = clock()
    end = t0 + seconds
    nxt = t0 + next(offsets)

    def admit(now: float) -> None:
        nonlocal nxt
        while nxt <= now:
            pending.append(Request(seed=next(seeds), due=nxt))
            nxt = t0 + next(offsets, math.inf)

    with TraceAnnotation("window"):
        while (now := clock()) < end:
            admit(now)
            if pending:
                _serve_next(eng, rec, pending, fields, bucket, clock,
                            keep_twin, in_window=True)
                continue
            until = min(nxt, end)
            with TraceAnnotation("wait"):
                sleep(until - now)
            rec.late_s = max(rec.late_s, clock() - until)
        rec.t_end = max(rec.batches[-1].done, end)

    # drain: everything due before the window closed
    admit(rec.t_end)
    while pending:
        _serve_next(eng, rec, pending, fields, bucket, clock, keep_twin,
                    in_window=False)
    return rec
