"""One general generator for every traffic mix, read from its data file.

A mix is a JSON object, one of:

    {"arrival": "closed", "clients": 8,
     "request": {"mode": "drift", "op": "undervolt"}}
    {"arrival": "poisson", "rate_per_s": 1.6,
     "request": {"mode": "clean", "op": "undervolt"}}

``closed``: a fixed pool of clients, each sending its next request at the
moment its previous one completes. ``poisson``: an open loop of independent
users, whose requests fall due whether or not earlier ones have finished:
a Poisson process of rate ``rate_per_s`` over the window, conditioned on
its count, so that every seed offers the same work in another order. The
window's ``round(rate_per_s * seconds)`` requests fall due the first as it
opens and the others at uniform times in it (given their count, the arrival
times of a Poisson process are independent and uniform), drawn from the
run's seed on a stream of their own.

``request`` holds the fields every request is submitted with. Each
request's own seed (its initial latents and class id) is drawn from the
run's seed, so the same seed gives the same requests in the same order, due
at the same offsets from the window's open, and every seed gives the same
work.
"""
from __future__ import annotations

from typing import Iterator, List

import numpy as np

ARRIVALS = ("closed", "poisson")
MAX_REQUEST_SEED = 2 ** 31 - 1


def request_seeds(seed: int) -> Iterator[int]:
    """The request seeds of a run, in order of sending."""
    rng = np.random.default_rng([seed, 1])
    while True:
        for s in rng.integers(0, MAX_REQUEST_SEED, size=256):
            yield int(s)


def warmup_seeds(seed: int, n: int) -> List[int]:
    rng = np.random.default_rng([seed, 2])
    return [int(s) for s in rng.integers(0, MAX_REQUEST_SEED, size=n)]


def arrival(mix: dict) -> str:
    """The mix's arrival process; an unknown one is an error."""
    if mix["arrival"] not in ARRIVALS:
        raise ValueError(f"unknown arrival process {mix['arrival']!r}; "
                         f"known: {ARRIVALS}")
    return mix["arrival"]


def clients(mix: dict) -> int:
    """Size of the closed loop's client pool."""
    if arrival(mix) != "closed":
        raise ValueError(f"a {mix['arrival']} mix has no client pool")
    n = int(mix["clients"])
    if n < 1:
        raise ValueError(f"a closed loop needs >= 1 client, got {n}")
    return n


def rate(mix: dict) -> float:
    """Requests per second of an open loop."""
    if arrival(mix) != "poisson":
        raise ValueError(f"a {mix['arrival']} mix has no arrival rate")
    r = float(mix["rate_per_s"])
    if not r > 0:
        raise ValueError(f"a Poisson mix needs a rate above 0, got {r}")
    return r


def arrival_offsets(seed: int, rate_per_s: float, seconds: float
                    ) -> List[float]:
    """Seconds from the window's open at which a Poisson mix's requests fall
    due, in order: ``round(rate_per_s * seconds)`` of them (at least one),
    the first at 0."""
    n = max(1, round(rate_per_s * seconds))
    rng = np.random.default_rng([seed, 3])
    return [0.0] + sorted(float(t) for t in rng.uniform(0.0, seconds, n - 1))
