"""One general generator for every traffic mix, read from its data file.

A mix is a JSON object:

    {"arrival": "closed", "clients": 8,
     "request": {"mode": "drift", "op": "undervolt"}}

``closed``: a fixed pool of clients, each sending its next request at the
moment its previous one completes. ``request`` holds the fields every
request is submitted with. Each request's own seed (its initial latents and
class id) is drawn from the run's seed, so the same seed gives the same
requests in the same order, and every seed gives the same work.
"""
from __future__ import annotations

from typing import Iterator, List

import numpy as np

ARRIVALS = ("closed",)
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


def clients(mix: dict) -> int:
    """Size of the closed loop's client pool."""
    if mix["arrival"] not in ARRIVALS:
        raise ValueError(f"unknown arrival process {mix['arrival']!r}; "
                         f"known: {ARRIVALS}")
    n = int(mix["clients"])
    if n < 1:
        raise ValueError(f"a closed loop needs >= 1 client, got {n}")
    return n
