"""Decide ``correct``: served finals against the plain reference.

After the window has closed, a sample of the served batches, drawn from the
run's seed, is run again through the configuration's plain reference with
the same request seeds, batch composition and engine seed. Two numbers are
compared, each the widest over the sampled requests of the relative L2 gap
``|served - reference| / |reference|`` of the clipped final latents:

* ``final_gap``: the served finals against the reference run at the
  configuration's semantics: int8 GEMMs (the configuration's
  ``gemm_bits``), and in ``drift`` mode the operating point's faults and
  tile rollback too.
* ``twin_gap`` (``drift`` mode only): the clean twin that the served path
  computes for every drift batch, against the reference's int8 GEMMs
  without faults.

The control puts the reference itself in the program's place, one
precision step down (int4 GEMMs), and has to fail.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from chipbench import spec


def rel_gap(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest relative L2 gap over the leading (request) axis."""
    served = np.asarray(served, np.float64)
    ref = np.asarray(ref, np.float64)
    num = np.sqrt(((served - ref) ** 2).reshape(len(ref), -1).sum(axis=1))
    den = np.sqrt((ref ** 2).reshape(len(ref), -1).sum(axis=1))
    return float(np.max(num / den))


def sample_batches(record, n: int, seed: int) -> list:
    """``n`` of the served batches (window and drain), drawn from the seed."""
    rng = np.random.default_rng([seed, 5])
    idx = sorted(rng.choice(len(record.batches),
                            size=min(n, len(record.batches)), replace=False))
    return [record.batches[i] for i in idx]


def readings(cfg: dict, params, batches: list, mode: str, engine_seed: int,
             ber: float, with_control: bool = False) -> Dict[str, dict]:
    """{"program": numbers} of ``batches``, and with ``with_control`` also
    {"control": numbers} of the reference one precision step down (int4
    GEMMs) put in the program's place."""
    ref = spec.reference_module(cfg)
    drift = mode == "drift"
    low_bits = cfg["gemm_bits"] // 2
    # (number, reference precision, control precision, served output)
    plans = [("final_gap",
              ref.Precision(bits=cfg["gemm_bits"], faults=drift),
              ref.Precision(bits=low_bits, faults=drift),
              lambda b: b.finals)]
    if drift:
        plans.append(("twin_gap", ref.Precision(bits=cfg["gemm_bits"]),
                      ref.Precision(bits=low_bits),
                      lambda b: np.asarray(b.twin)[:len(b.seeds)]))
    gaps = {"program": {}, "control": {}}
    for b in batches:
        rb = ref.Batch(seeds=b.padded, batch_index=b.index,
                       engine_seed=engine_seed, ber=ber if drift else 0.0)
        live = len(b.seeds)
        for name, stated, low, served in plans:
            want = ref.sample(cfg, params, rb, stated)[:live]
            gaps["program"].setdefault(name, []).append(
                rel_gap(served(b), want))
            if with_control:
                gaps["control"].setdefault(name, []).append(
                    rel_gap(ref.sample(cfg, params, rb, low)[:live], want))
    out = {side: {k: max(v) for k, v in g.items()}
           for side, g in gaps.items() if g}
    return out


def decide(numbers: Dict[str, float], limits: Dict[str, float]
           ) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and {name: {value, limit}}; a number with no limit, or a
    limit with no number, is not correct."""
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in numbers.items()}
    ok = (set(numbers) == set(limits)
          and all(np.isfinite(v) and v <= limits[k]
                  for k, v in numbers.items()))
    return ok, checks


def sample_size(limits: dict, default: int = 1) -> int:
    return int(limits.get("sample_batches", default))


def engine_ber(cfg: dict, mode: str, op: str) -> float:
    return float(cfg["operating_points"][op]) if mode == "drift" else 0.0


def describe(checks: Dict[str, dict]) -> List[str]:
    return [f"{k} {c['value']!r} limit {c['limit']!r}"
            for k, c in checks.items()]
