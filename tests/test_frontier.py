"""Compute-optimal frontier tests: quality-proxy invariants, the
scheduler's frontier resolution proven optimal against brute force over
the FULL knob enumeration, and the degenerate-point bit-identity bar --
a frontier pick at (requested op, requested steps, baseline precision,
TaylorSeer off) serves latents bit-identical to the pre-frontier
as-requested path, one-shot and streamed, on both engines (the
8-fake-device sharded twin skips on a single-device run).

Scheduler-policy tests ride the fake sampler factory (admission and
frontier resolution are pure arithmetic); bit-identity runs the real
smoke DiT.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import dvfs
from repro.core import quant
from repro.diffusion.sampler import SampleOutput, StreamEvent
from repro.serving import (DeadlineScheduler, DriftServeEngine,
                           FrontierBuilder, RequestResult, SchedulerConfig,
                           ShardedDriftServeEngine, frontier)
from repro.serving.request import GenerationRequest

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

ARCH = "dit-xl-512"


def fake_factory(key, model_cfg, scfg, on_trace):
    """Echo-latents sampler stub (test_scheduler.py's), for policy tests
    that never need a real model."""
    on_trace()

    def output(latents, monitor0):
        mon = dvfs.BerMonitorState(monitor0.ema_ber, monitor0.op_index,
                                   monitor0.n_updates + 1)
        return SampleOutput(latents, mon, jnp.int32(0),
                            jnp.int32(scfg.num_sample_steps))

    if not key.stream:
        return lambda params, rng, latents, cond, text, monitor0: \
            output(latents, monitor0)

    def run_stream(params, rng, latents, cond, text, monitor0):
        for done in range(key.stream, scfg.num_sample_steps, key.stream):
            yield StreamEvent(step=done, latents=latents)
        yield output(latents, monitor0)
    return run_stream


def make_sched(bucket=2, **cfg_kw):
    eng = DriftServeEngine(arch=ARCH, smoke=True, bucket=bucket,
                           sampler_factory=fake_factory)
    return DeadlineScheduler(eng, SchedulerConfig(**cfg_kw))


def brute_force_pick(sched, req, objective):
    """Argmin over the FULL unpruned knob enumeration (not the Pareto
    set) under the same constraints/tie-breaks the scheduler uses -- the
    ground truth its pruned-set search must match."""
    eng = sched.engine
    builder = sched.frontier_builder()
    full = builder.enumerate(eng._full_cfg(req.arch), req.steps,
                             eng.batcher.bucket, req.mode,
                             eng.resolve_interval(req))
    budget = None
    if req.deadline_s is not None:
        budget = req.deadline_s - sched.projected_wait_s(req)
    lat = {p: sched.frontier_latency_s(req, p) for p in full}
    ok = [p for p in full
          if (req.quality_floor is None
              or p.quality >= req.quality_floor - 1e-12)
          and (req.energy_budget_j is None
               or p.energy_j <= req.energy_budget_j + 1e-12)
          and (budget is None or lat[p] <= budget)]
    if not ok:
        return None
    keys = {
        "min-energy": lambda p: (p.energy_j, -p.quality, lat[p],
                                 frontier.sort_key(p)),
        "min-latency": lambda p: (lat[p], -p.quality, p.energy_j,
                                  frontier.sort_key(p)),
        "max-quality": lambda p: (-p.quality, p.energy_j, lat[p],
                                  frontier.sort_key(p)),
    }
    return min(ok, key=keys[objective])


# ---------------------------------------------------- quality invariants
@settings(max_examples=40, deadline=None)
@given(steps=st.integers(2, 20), requested=st.integers(20, 30),
       plan_name=st.sampled_from(list(quant.PRECISION_PLANS)),
       ts=st.sampled_from([False, True]),
       op_i=st.integers(0, len(frontier.FRONTIER_OPS) - 1))
def test_quality_monotone_in_steps(steps, requested, plan_name, ts, op_i):
    """Shrinking the step count never raises the proxy, whatever the
    other knobs (the TaylorSeer term's bounded gain can't outrun the
    step factor's loss)."""
    plan = quant.get_plan(plan_name)
    op = frontier.FRONTIER_OPS[op_i]
    q_hi = frontier.quality_proxy(steps, requested, plan, ts, op)
    q_lo = frontier.quality_proxy(steps - 1, requested, plan, ts, op)
    assert q_lo <= q_hi + 1e-12
    assert 0.0 < q_lo <= 1.0 and 0.0 < q_hi <= 1.0


@settings(max_examples=40, deadline=None)
@given(steps=st.integers(1, 20), requested=st.integers(20, 30),
       ts=st.sampled_from([False, True]),
       op_i=st.integers(0, len(frontier.FRONTIER_OPS) - 1))
def test_quality_monotone_in_precision(steps, requested, ts, op_i):
    """Narrowing the body precision at a fixed op never raises the
    proxy: int8 >= int8-body6 >= int8-body4."""
    op = frontier.FRONTIER_OPS[op_i]
    qs = [frontier.quality_proxy(steps, requested, quant.get_plan(n),
                                 ts, op)
          for n in ("int8", "int8-body6", "int8-body4")]
    assert qs[0] >= qs[1] >= qs[2]


def test_quality_one_only_as_requested():
    """The proxy is ~1.0 exactly for (requested steps, int8, TS off) at
    the BER-free nominal point, and strictly below for every single-knob
    degradation."""
    nominal = dvfs.NOMINAL
    base = frontier.quality_proxy(10, 10, quant.DEFAULT_PLAN, False,
                                  nominal)
    assert base == pytest.approx(1.0, abs=1e-6)
    assert frontier.quality_proxy(9, 10, quant.DEFAULT_PLAN, False,
                                  nominal) < base
    assert frontier.quality_proxy(10, 10, quant.get_plan("int8-body6"),
                                  False, nominal) < base
    assert frontier.quality_proxy(10, 10, quant.DEFAULT_PLAN, True,
                                  nominal) < base
    assert frontier.quality_proxy(10, 10, quant.DEFAULT_PLAN, False,
                                  dvfs.UNDERVOLT) < base


# ------------------------------------------- scheduler pick == brute force
def test_frontier_pick_is_min_energy_among_deadline_meeting():
    """Deadline + budget/floor objective: the scheduler's pick equals the
    argmin-energy deadline-meeting point of the FULL enumeration."""
    sched = make_sched()
    probe = GenerationRequest(request_id=-1, arch=ARCH, steps=10,
                              mode="drift", op="auto", deadline_s=2.0,
                              energy_budget_j=10.0)
    expect = brute_force_pick(sched, probe, "min-energy")
    assert expect is not None
    adm = sched.submit(steps=10, mode="drift", op="auto", deadline_s=2.0,
                       energy_budget_j=10.0)
    assert adm.action == "frontier"
    assert (adm.op, adm.steps, adm.precision, adm.taylorseer) \
        == expect.knobs()
    assert adm.projected_energy_j == pytest.approx(expect.energy_j)
    assert adm.quality == pytest.approx(expect.quality)
    assert sched.stats.frontier_selected == 1
    # The pick honors the deadline under the scheduler's own projection.
    assert adm.projected_total_s <= 2.0


def test_frontier_pick_is_min_latency_among_floor_meeting():
    """Quality floor without a deadline: argmin-latency among points at
    or above the floor."""
    sched = make_sched()
    probe = GenerationRequest(request_id=-1, arch=ARCH, steps=10,
                              mode="drift", op="auto", quality_floor=0.9)
    expect = brute_force_pick(sched, probe, "min-latency")
    assert expect is not None
    adm = sched.submit(steps=10, mode="drift", op="auto",
                       quality_floor=0.9)
    assert adm.action == "frontier"
    assert (adm.op, adm.steps, adm.precision, adm.taylorseer) \
        == expect.knobs()
    assert adm.quality >= 0.9


def test_frontier_pick_is_max_quality_within_budget():
    """Energy budget without a deadline: best quality the budget buys."""
    sched = make_sched()
    probe = GenerationRequest(request_id=-1, arch=ARCH, steps=10,
                              mode="drift", op="auto",
                              energy_budget_j=0.4)
    expect = brute_force_pick(sched, probe, "max-quality")
    assert expect is not None
    # the budget actually binds: the as-requested-ish corner is pricier
    assert any(p.energy_j > 0.4 for p in
               sched.frontier_builder().enumerate(
                   sched.engine._full_cfg(ARCH), 10, 2))
    adm = sched.submit(steps=10, mode="drift", op="auto",
                       energy_budget_j=0.4)
    assert adm.action == "frontier"
    assert (adm.op, adm.steps, adm.precision, adm.taylorseer) \
        == expect.knobs()
    assert adm.projected_energy_j <= 0.4 + 1e-12


def test_frontier_pick_brute_force_sweep():
    """Optimality across a grid of objectives/constraints, not one lucky
    corner: every admitted frontier pick matches brute force; every
    brute-force-infeasible case falls back to the ladder."""
    sched = make_sched()
    cases = [
        dict(deadline_s=d, energy_budget_j=b, quality_floor=f)
        for d in (None, 0.5, 1.0, 3.0)
        for b in (None, 0.3, 0.6, 5.0)
        for f in (None, 0.8, 0.95)
        if b is not None or f is not None
    ]
    for fields in cases:
        probe = GenerationRequest(request_id=-1, arch=ARCH, steps=8,
                                  mode="drift", op="auto", **fields)
        if fields["deadline_s"] is not None:
            objective = "min-energy"
        elif fields["quality_floor"] is not None:
            objective = "min-latency"
        else:
            objective = "max-quality"
        expect = brute_force_pick(sched, probe, objective)
        adm = sched.plan(probe)
        if expect is None:
            assert adm.action != "frontier", fields
        else:
            assert adm.action == "frontier", fields
            assert (adm.op, adm.steps, adm.precision, adm.taylorseer) \
                == expect.knobs(), fields


def test_empty_frontier_falls_back_to_reject_and_projected_miss():
    """Impossible deadline with a frontier objective: no qualifying
    point, so the PR 3 ladder decides -- reject by default, admitted as a
    projected miss with reject_hopeless=False."""
    sched = make_sched()
    adm = sched.submit(steps=10, mode="drift", op="auto",
                       deadline_s=1e-6, energy_budget_j=10.0)
    assert not adm.admitted and adm.action == "rejected"
    assert sched.stats.rejected == 1 and sched.stats.frontier_selected == 0

    lenient = make_sched(reject_hopeless=False)
    adm = lenient.submit(steps=10, mode="drift", op="auto",
                         deadline_s=1e-6, quality_floor=0.9)
    assert adm.admitted and adm.action == "projected-miss"


def test_unsatisfiable_floor_without_deadline_is_best_effort():
    """A floor above every point's quality (e.g. 1.0 with only lossy
    ladder ops enumerated at nonzero BER... use >max) degrades to the
    documented best-effort as-requested path, not a rejection."""
    sched = make_sched()
    points = sched.frontier_builder().frontier(
        sched.engine._full_cfg(ARCH), 10, 2)
    floor = max(p.quality for p in points)
    if floor >= 1.0:                      # pragma: no cover
        pytest.skip("every knob point is perfect; floor cannot exceed it")
    adm = sched.submit(steps=10, mode="drift", op="undervolt",
                       quality_floor=1.0)
    assert adm.admitted and adm.action == "as-requested"
    assert adm.op == "undervolt" and adm.steps == 10


def test_deadline_only_requests_never_touch_frontier():
    """No energy_budget_j / quality_floor: the PR 3 ladder runs
    unchanged (as-requested here), and the request's own precision/
    taylorseer knobs survive admission."""
    sched = make_sched()
    adm = sched.submit(steps=10, mode="drift", op="undervolt",
                       deadline_s=100.0, taylorseer=True,
                       precision="int8-body6")
    assert adm.action == "as-requested"
    assert sched.stats.frontier_selected == 0
    req = sched.engine.queue.pending()[0]
    assert req.taylorseer is True and req.precision == "int8-body6"


def test_frontier_memoized_across_submissions():
    """Repeat submissions of one configuration reuse the memoized
    frontier (auto_rollback_interval-style): the builder's memo holds one
    entry, not one per request."""
    sched = make_sched()
    for seed in range(4):
        sched.submit(steps=10, mode="drift", op="auto", seed=seed,
                     quality_floor=0.9)
    assert len(sched.frontier_builder()._memo) == 1
    assert sched.stats.frontier_selected == 4


# -------------------------------------------------- submit-time validation
def test_budget_and_floor_validation():
    """Nonsensical objectives fail loudly at submit time, on the bare
    engine and through the scheduler, and never touch the queue."""
    eng = DriftServeEngine(arch=ARCH, smoke=True, bucket=2,
                           sampler_factory=fake_factory)
    sched = DeadlineScheduler(eng)
    for bad in (dict(energy_budget_j=0.0), dict(energy_budget_j=-1.0),
                dict(quality_floor=0.0), dict(quality_floor=-0.5),
                dict(quality_floor=1.5), dict(precision="fp4"),
                dict(precision="")):
        with pytest.raises(ValueError):
            eng.submit(steps=10, mode="drift", **bad)
        with pytest.raises(ValueError):
            sched.submit(steps=10, mode="drift", **bad)
    assert len(eng.queue) == 0
    # boundary values that must be accepted
    eng.submit(steps=10, mode="drift", quality_floor=1.0,
               energy_budget_j=1e-9)
    assert len(eng.queue) == 1


# ------------------------------------------------- degenerate bit-identity
def _degenerate_pair(eng_a, eng_b, stream=False):
    """Submit the as-requested baseline on ``eng_a`` and the same request
    through a frontier-resolving scheduler on ``eng_b`` with a quality
    floor only the (nominal op, full steps, int8, TS off) corner meets;
    returns (baseline results, frontier results, admission)."""
    sched = DeadlineScheduler(eng_b)
    eng_a.submit(steps=6, mode="drift", op="nominal", seed=0)
    adm = sched.submit(steps=6, mode="drift", op="nominal", seed=0,
                       quality_floor=0.99)
    assert adm.action == "frontier"
    assert (adm.op, adm.steps, adm.precision, adm.taylorseer) \
        == ("nominal", 6, "int8", False)
    if not stream:
        return eng_a.run(), sched.run(), adm
    ev_a = list(eng_a.run_stream(preview_interval=2))
    ev_b = list(sched.run_stream(preview_interval=2))
    return ev_a, ev_b, adm


def _assert_results_identical(res_a, res_b):
    assert len(res_a) == len(res_b)
    for a, b in zip(res_a, res_b):
        if not isinstance(a, RequestResult):        # PreviewEvent
            assert type(a) is type(b) and a.step == b.step
            assert np.array_equal(np.asarray(a.latents),
                                  np.asarray(b.latents))
            continue
        assert np.array_equal(np.asarray(a.latents),
                              np.asarray(b.latents)), \
            "frontier degenerate point must be bit-identical"
        assert (a.op, a.steps, a.precision, a.taylorseer) \
            == (b.op, b.steps, b.precision, b.taylorseer)
        assert a.energy_j == pytest.approx(b.energy_j)
        assert a.latency_s == pytest.approx(b.latency_s)


@pytest.mark.slow
def test_degenerate_frontier_point_bit_identical_single_device():
    """Real smoke DiT: the frontier's full-fidelity corner serves the
    exact bytes of the pre-frontier as-requested path, one-shot AND
    streamed."""
    mk = lambda: DriftServeEngine(arch=ARCH, smoke=True, bucket=1)
    res_a, res_b, _ = _degenerate_pair(mk(), mk())
    _assert_results_identical(res_a, res_b)
    ev_a, ev_b, _ = _degenerate_pair(mk(), mk(), stream=True)
    assert any(not isinstance(e, RequestResult) for e in ev_a)
    _assert_results_identical(ev_a, ev_b)


@needs_mesh
@pytest.mark.slow
def test_degenerate_frontier_point_bit_identical_sharded():
    """The 8-fake-device twin of the bit-identity bar."""
    from repro.launch import mesh as mesh_lib

    def mk():
        mesh = mesh_lib.make_serving_mesh(model_parallel=1)
        return ShardedDriftServeEngine(mesh=mesh, arch=ARCH, smoke=True,
                                       bucket=1)

    res_a, res_b, _ = _degenerate_pair(mk(), mk())
    _assert_results_identical(res_a, res_b)
    ev_a, ev_b, _ = _degenerate_pair(mk(), mk(), stream=True)
    _assert_results_identical(ev_a, ev_b)


@pytest.mark.slow
def test_narrowed_precision_gets_its_own_trace_and_cheaper_bill():
    """A narrowed-precision request compiles its own sampler (SamplerKey
    carries the plan) and is billed less energy than the int8 twin; the
    clean reference stays full-width so quality metrics remain
    comparable."""
    eng = DriftServeEngine(arch=ARCH, smoke=True, bucket=1)
    eng.submit(steps=6, mode="drift", op="undervolt", seed=0)
    eng.submit(steps=6, mode="drift", op="undervolt", seed=0,
               precision="int8-body4")
    results = eng.run()
    # 2 drift configs + 1 shared clean reference
    assert eng.cache.traces == 3
    base, narrow = results
    assert base.precision == "int8" and narrow.precision == "int8-body4"
    assert narrow.energy_j < base.energy_j
    assert narrow.latency_s < base.latency_s
