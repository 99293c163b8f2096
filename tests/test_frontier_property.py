"""Property tests for Pareto-dominance pruning, run against BOTH pruners
the serving stack ships: the three-objective ``serving.frontier``
helpers (quality max, energy min, latency min) and the two-objective
``serving/offload/planner.pareto_frontier`` (energy min, stall min).

The two invariants every randomized cost table must satisfy:

1. no returned frontier point is dominated by another returned point;
2. every pruned point is dominated by some kept point.

Together they pin down the non-dominated set exactly (up to ties, which
both implementations keep), which is what makes the scheduler's
"search the pruned set" == "search the full enumeration" argument hold.
"""
import pytest
from hypothesis import given, settings, strategies as st

from repro.serving import frontier
from repro.serving.offload import planner as offload_planner


def _points_from_seed(n, seed, levels):
    """Deterministic pseudo-random cost table. ``levels`` coarsens each
    axis so ties and duplicate cost vectors actually occur."""
    import random
    rng = random.Random(seed)
    pts = []
    for i in range(n):
        pts.append(frontier.FrontierPoint(
            op=f"op{i % 3}", steps=4 + i % 5, precision=f"p{i % 2}",
            taylorseer=bool(i % 2),
            quality=rng.randrange(levels) / levels,
            energy_j=float(rng.randrange(levels)),
            latency_s=float(rng.randrange(levels))))
    return pts


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 40), seed=st.integers(0, 10_000),
       levels=st.integers(2, 6))
def test_frontier_pareto_properties(n, seed, levels):
    """Three-objective pruner: kept points mutually non-dominated, every
    pruned point dominated by a kept one."""
    pts = _points_from_seed(n, seed, levels)
    front = frontier.pareto_front(pts)
    assert front, "non-empty input must keep at least one point"
    for p in front:
        assert not any(frontier.dominates(q, p) for q in front)
    kept = set(map(id, front))
    for p in pts:
        if id(p) not in kept and p not in front:
            assert any(frontier.dominates(q, p) for q in front), p


@settings(max_examples=30, deadline=None)
@given(n=st.integers(1, 30), seed=st.integers(0, 10_000))
def test_frontier_matches_bruteforce_nondominated(n, seed):
    """The pruned set IS the non-dominated set (ties kept): brute force
    over the full table agrees point-for-point."""
    pts = _points_from_seed(n, seed, levels=4)
    front = frontier.pareto_front(pts)
    brute = [p for p in pts
             if not any(frontier.dominates(q, p) for q in pts)]
    assert sorted(front, key=frontier.sort_key) \
        == sorted(brute, key=frontier.sort_key)


def _plans_from_seed(n, seed, levels):
    import random
    rng = random.Random(seed)
    plans = []
    for i in range(n):
        refresh = float(rng.randrange(levels))
        penalty = float(rng.randrange(levels))
        stall = float(rng.randrange(levels))
        plans.append(offload_planner.IntervalPlan(
            interval=i + 1, n_refreshes=1, refresh_s=0.0,
            stall_serialized_s=stall, stall_s=stall,
            refresh_energy_j=refresh, rollback_penalty_j=penalty,
            total_j=refresh + penalty))
    return plans


@settings(max_examples=40, deadline=None)
@given(n=st.integers(1, 40), seed=st.integers(0, 10_000),
       levels=st.integers(2, 6))
def test_offload_pareto_properties(n, seed, levels):
    """Two-objective (energy_j, stall_s) pruner obeys the same two
    invariants over randomized plan tables."""
    plans = _plans_from_seed(n, seed, levels)
    front = offload_planner.pareto_frontier(plans)
    assert front

    def dominates(a, b):
        return ((a.energy_j <= b.energy_j and a.stall_s <= b.stall_s)
                and (a.energy_j < b.energy_j or a.stall_s < b.stall_s))

    for p in front:
        assert not any(dominates(q, p) for q in front)
    kept = set(map(id, front))
    for p in plans:
        if id(p) not in kept:
            assert any(dominates(q, p) for q in front), p


def test_pareto_front_keeps_ties():
    """Duplicate cost vectors are ties, not mutual dominators: both
    survive (matching the offload planner's ties-kept contract)."""
    a = frontier.FrontierPoint("nominal", 10, "int8", False, 0.9, 1.0, 0.1)
    b = frontier.FrontierPoint("uv-safe", 10, "int8", False, 0.9, 1.0, 0.1)
    c = frontier.FrontierPoint("nominal", 8, "int8", False, 0.8, 2.0, 0.2)
    assert not frontier.dominates(a, b)
    assert not frontier.dominates(b, a)
    front = frontier.pareto_front([a, b, c])
    assert a in front and b in front and c not in front


def test_dominates_needs_strict_edge():
    """Equal on every axis is NOT dominance; one strict improvement is."""
    a = frontier.FrontierPoint("nominal", 10, "int8", False, 0.9, 1.0, 0.1)
    b = frontier.FrontierPoint("nominal", 10, "int8", False, 0.9, 1.0, 0.2)
    assert frontier.dominates(a, b)
    assert not frontier.dominates(b, a)
    assert not frontier.dominates(a, a)


def test_real_builder_frontier_is_nondominated():
    """The real priced enumeration (not synthetic): the memoized frontier
    equals the non-dominated subset of the full knob sweep."""
    from repro import configs
    builder = frontier.FrontierBuilder()
    cfg = configs.get_config("dit-xl-512")
    full = builder.enumerate(cfg, 10, 2)
    front = builder.frontier(cfg, 10, 2)
    brute = [p for p in full
             if not any(frontier.dominates(q, p) for q in full)]
    assert sorted(front, key=frontier.sort_key) \
        == sorted(brute, key=frontier.sort_key)
    # Memo hit returns the identical list object.
    assert builder.frontier(cfg, 10, 2) is front
