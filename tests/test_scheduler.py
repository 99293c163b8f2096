"""Deadline-aware scheduler + streaming tests.

Covers the PR 3 acceptance bar:

* a deadline-constrained request demonstrably receives a different
  (operating point, step budget) assignment than a background request;
* a streamed request yields >= 1 intermediate preview and its final
  latents are bit-identical to the non-streaming path (single-device here;
  the 8-fake-device sharded twin lives in test_serving_sharded.py);
* starvation / deadline-miss accounting;
* RequestQueue edge cases (empty peek, mixed-config take_matching limits).

Scheduler-logic tests ride the fake sampler factory (no jit, no model) so
admission arithmetic, priority formation, and clock bookkeeping run in
milliseconds; the streaming-equivalence tests run the real smoke DiT.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dvfs
from repro.diffusion.sampler import SampleOutput, StreamEvent
from repro.serving import (DeadlineScheduler, DriftServeEngine,
                           PreviewEvent, RequestResult, SchedulerConfig)
from repro.serving.request import GenerationRequest, RequestQueue


def fake_factory(key, model_cfg, scfg, on_trace):
    """Echo-latents sampler stub; handles both one-shot and streamed keys
    (key.stream > 0 returns a generator, like the real make_sampler)."""
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
        n = scfg.num_sample_steps
        for done in range(key.stream, n, key.stream):
            yield StreamEvent(step=done, latents=latents)
        yield output(latents, monitor0)
    return run_stream


def make_engine(bucket=2, **kw):
    return DriftServeEngine(arch="dit-xl-512", smoke=True, bucket=bucket,
                            sampler_factory=fake_factory, **kw)


# ------------------------------------------------------- admission policy
def test_deadline_vs_background_assignments_differ():
    """THE acceptance test: same submitted configuration, but the
    deadline-constrained request is escalated/trimmed while the background
    request keeps the energy-saving assignment."""
    eng = make_engine(bucket=1)
    sched = DeadlineScheduler(eng)
    bg = sched.submit(steps=10, mode="drift", op="undervolt",
                      priority="background", seed=0)
    assert bg.admitted and bg.action == "as-requested"
    assert (bg.op, bg.steps) == ("undervolt", 10)

    # a deadline even (overclock, 10 steps) cannot meet, but a trimmed
    # step count can: force the joint (op, step_budget) policy to use both
    # knobs at once
    lat_oc_full = sched.batch_latency_s("dit-xl-512", "overclock", 10)
    lat_oc_min = sched.batch_latency_s("dit-xl-512", "overclock",
                                       sched.cfg.min_steps)
    deadline = (lat_oc_full + lat_oc_min) / 2
    ur = sched.submit(steps=10, mode="drift", op="undervolt",
                      priority="interactive", deadline_s=deadline, seed=1)
    assert ur.admitted and ur.action == "trimmed-steps"
    assert ur.op == "overclock" and ur.steps < 10
    assert (ur.op, ur.steps) != (bg.op, bg.steps)

    # the assignment flows through to the served results
    results = {r.request_id: r for r in sched.run()}
    assert results[bg.request_id].op == "undervolt"
    assert results[bg.request_id].steps == 10
    assert results[ur.request_id].op == "overclock"
    assert results[ur.request_id].steps == ur.steps
    assert not results[ur.request_id].deadline_missed


def test_op_escalation_without_trimming():
    eng = make_engine(bucket=1)
    sched = DeadlineScheduler(eng)
    lat_uv = sched.batch_latency_s("dit-xl-512", "undervolt", 10)
    lat_oc = sched.batch_latency_s("dit-xl-512", "overclock", 10)
    assert lat_oc < lat_uv          # overclock is the speed mode
    adm = sched.submit(steps=10, mode="drift", op="undervolt",
                       deadline_s=(lat_oc + lat_uv) / 2, seed=0)
    assert adm.action == "escalated-op"
    assert adm.op == "overclock" and adm.steps == 10


def test_hopeless_deadline_rejected_and_never_enqueued():
    eng = make_engine(bucket=1)
    sched = DeadlineScheduler(eng)
    adm = sched.submit(steps=10, mode="drift", op="undervolt",
                       deadline_s=1e-6, seed=0)
    assert not adm.admitted and adm.action == "rejected"
    assert adm.request_id == -1 and "deadline" in adm.reason
    assert len(eng.queue) == 0
    assert sched.stats.rejected == 1 and sched.stats.admitted == 0


def test_reject_hopeless_false_admits_projected_miss():
    eng = make_engine(bucket=1)
    sched = DeadlineScheduler(eng, SchedulerConfig(reject_hopeless=False))
    adm = sched.submit(steps=10, mode="drift", op="undervolt",
                       deadline_s=1e-6, seed=0)
    assert adm.admitted and adm.action == "projected-miss"
    assert adm.steps == sched.cfg.min_steps
    (res,) = sched.run()
    assert res.deadline_missed
    assert eng.stats.deadline_misses == 1


def test_step_budget_caps_even_without_deadline():
    eng = make_engine(bucket=1)
    sched = DeadlineScheduler(eng)
    adm = sched.submit(steps=10, step_budget=5, mode="drift",
                       op="undervolt", seed=0)
    assert adm.admitted and adm.steps == 5
    (res,) = sched.run()
    assert res.steps == 5
    # the bare engine honors step_budget too (no scheduler needed)
    eng2 = make_engine(bucket=1)
    eng2.submit(steps=10, step_budget=3, mode="drift", op="undervolt",
                seed=0)
    assert eng2.queue.peek().steps == 3


def test_backlog_projection_counts_only_higher_urgency():
    eng = make_engine(bucket=1)
    sched = DeadlineScheduler(eng)
    # queue three standard-priority requests
    for i in range(3):
        sched.submit(steps=10, mode="drift", op="undervolt", seed=i)
    lat = sched.batch_latency_s("dit-xl-512", "undervolt", 10)
    # an interactive newcomer outranks all of them: zero projected wait
    probe_hi = GenerationRequest(request_id=-1, priority="interactive",
                                 steps=10, op="undervolt")
    assert sched.projected_wait_s(probe_hi) == 0.0
    # a standard newcomer waits behind all three (FIFO tie-break)
    probe_std = GenerationRequest(request_id=-1, priority="standard",
                                  steps=10, op="undervolt")
    assert sched.projected_wait_s(probe_std) == pytest.approx(3 * lat)


# --------------------------------------------------- priority formation
def test_interactive_batches_form_before_earlier_background():
    eng = make_engine(bucket=2)
    sched = DeadlineScheduler(eng)
    ids = {}
    for i, prio in enumerate(["background", "background", "interactive",
                              "interactive"]):
        adm = sched.submit(steps=4, mode="drift", op="undervolt",
                           priority=prio, seed=i)
        ids[adm.request_id] = prio
    results = {r.request_id: r for r in sched.run()}
    inter = [r for r in results.values() if r.priority == "interactive"]
    backg = [r for r in results.values() if r.priority == "background"]
    # interactive bucket ran first despite later submission...
    assert all(i.batch_index < b.batch_index for i in inter for b in backg)
    # ...and background still completed (no starvation in a drain)
    assert len(backg) == 2
    assert all(b.completed_at_s > i.completed_at_s
               for i in inter for b in backg)


def test_earlier_deadline_wins_within_a_priority_class():
    eng = make_engine(bucket=1)
    sched = DeadlineScheduler(eng)
    # both standard, generous deadlines; a2's is earlier
    lat = sched.batch_latency_s("dit-xl-512", "undervolt", 4)
    a1 = sched.submit(steps=4, mode="drift", op="undervolt",
                      deadline_s=50 * lat, seed=0)
    a2 = sched.submit(steps=4, mode="drift", op="undervolt",
                      deadline_s=10 * lat, seed=1)
    results = {r.request_id: r for r in sched.run()}
    assert results[a2.request_id].batch_index \
        < results[a1.request_id].batch_index


def test_aging_promotes_starved_background_work():
    eng = make_engine(bucket=1)
    age = 1e-4
    sched = DeadlineScheduler(eng, SchedulerConfig(age_s=age))
    bg = sched.submit(steps=4, mode="drift", op="undervolt",
                      priority="background", seed=0)
    hi1 = sched.submit(steps=4, mode="drift", op="undervolt",
                       priority="interactive", seed=1)
    # serve one batch: the interactive request wins it, and the clock
    # advance pushes the waiting background request past age_s
    mb = eng.batcher.next_batch(eng.queue, eng._resolve_op)
    first = eng._run_batch(mb)
    assert first[0].request_id == hi1.request_id
    assert eng.clock_s - 0.0 >= age
    # now an even newer interactive request arrives -- but the aged
    # background request outranks it at formation time
    sched.submit(steps=4, mode="drift", op="undervolt",
                 priority="interactive", seed=2)
    mb2 = eng.batcher.next_batch(eng.queue, eng._resolve_op)
    assert [r.request_id for r in mb2.requests] == [bg.request_id]


def test_uniform_priorities_degenerate_to_fifo():
    """Scheduler wrapped around an all-standard stream must batch exactly
    like the bare FIFO engine (launchers can wrap unconditionally)."""
    plain = make_engine(bucket=2)
    for i in range(4):
        plain.submit(steps=4, mode="drift", op="undervolt", seed=i)
    wrapped = make_engine(bucket=2)
    sched = DeadlineScheduler(wrapped)
    for i in range(4):
        sched.submit(steps=4, mode="drift", op="undervolt", seed=i)
    ref = [(r.request_id, r.batch_index) for r in plain.run()]
    got = [(r.request_id, r.batch_index) for r in sched.run()]
    assert ref == got


# ------------------------------------------------------ queue edge cases
def test_empty_queue_peek_and_pending():
    q = RequestQueue()
    assert q.peek() is None
    assert q.pending() == ()
    assert len(q) == 0
    assert q.take_matching("anything", lambda r: r.op, limit=3) == []


def test_take_matching_respects_limit_across_mixed_configs():
    q = RequestQueue()
    for op in ["undervolt", "overclock", "undervolt", "overclock",
               "undervolt"]:
        q.submit(op=op)
    taken = q.take_matching("undervolt", lambda r: r.op, limit=2)
    assert [r.request_id for r in taken] == [0, 2]      # FIFO among matches
    # the un-taken match and both non-matches kept their relative order
    assert [r.request_id for r in q.pending()] == [1, 3, 4]
    # limit larger than remaining matches drains them all
    taken = q.take_matching("overclock", lambda r: r.op, limit=99)
    assert [r.request_id for r in taken] == [1, 3]
    assert [r.request_id for r in q.pending()] == [4]


def test_pending_is_a_snapshot():
    q = RequestQueue()
    q.submit(op="undervolt")
    snap = q.pending()
    q.take_matching("undervolt", lambda r: r.op, limit=1)
    assert len(snap) == 1 and len(q) == 0


def test_request_field_validation():
    with pytest.raises(ValueError):
        GenerationRequest(request_id=0, priority="vip")
    with pytest.raises(ValueError):
        GenerationRequest(request_id=0, deadline_s=0.0)
    with pytest.raises(ValueError):
        GenerationRequest(request_id=0, step_budget=0)
    req = GenerationRequest(request_id=0, deadline_s=2.0, submitted_at_s=1.0)
    assert req.absolute_deadline_s == 3.0
    assert GenerationRequest(request_id=0).absolute_deadline_s is None


# --------------------------------------------- deadline-miss bookkeeping
def test_deadline_miss_accounting_on_bare_engine():
    """The bare engine (no admission control) still stamps misses: two
    same-config requests with a deadline only the first batch can meet."""
    eng = make_engine(bucket=1)
    eng.submit(steps=10, mode="drift", op="undervolt", seed=0,
               deadline_s=1.0)
    (probe,) = eng.run()
    lat = probe.latency_s
    # deadline fits one batch but not two: the second request (same
    # config, so it lands in the later bucket) must miss
    eng.submit(steps=10, mode="drift", op="undervolt", seed=1,
               deadline_s=1.5 * lat)
    eng.submit(steps=10, mode="drift", op="undervolt", seed=2,
               deadline_s=1.5 * lat)
    results = eng.run()
    assert [r.deadline_missed for r in results] == [False, True]
    assert eng.stats.deadline_misses == 1
    missed = results[1]
    assert missed.completed_at_s > missed.deadline_s + probe.latency_s
    assert missed.queue_wait_s == pytest.approx(lat)


def test_result_records_carry_scheduling_fields():
    eng = make_engine(bucket=1)
    eng.submit(steps=4, mode="drift", op="undervolt", seed=0,
               priority="interactive", deadline_s=5.0)
    (res,) = eng.run()
    assert res.priority == "interactive"
    assert res.deadline_s == 5.0
    assert res.completed_at_s == pytest.approx(res.latency_s)
    assert not res.deadline_missed


# ----------------------------------------------------- streaming (fakes)
def test_run_stream_yields_previews_then_results():
    eng = make_engine(bucket=2)
    for i in range(2):
        eng.submit(steps=6, mode="drift", op="undervolt", seed=i)
    events = list(eng.run_stream(preview_interval=2))
    previews = [e for e in events if isinstance(e, PreviewEvent)]
    results = [e for e in events if isinstance(e, RequestResult)]
    # 6 steps, window 2 -> previews at steps 2 and 4, per live request
    assert [(p.step, p.request_id) for p in previews] == \
        [(2, 0), (2, 1), (4, 0), (4, 1)]
    assert all(p.total_steps == 6 for p in previews)
    assert sorted(r.request_id for r in results) == [0, 1]
    assert eng.stats.preview_events == 4
    # previews of a batch strictly precede its results
    assert max(events.index(p) for p in previews) \
        < min(events.index(r) for r in results)


def test_streamed_key_gets_own_cache_slot_and_clean_ref_is_shared():
    eng = make_engine(bucket=1)
    eng.submit(steps=4, mode="drift", op="undervolt", seed=0)
    list(eng.run_stream(preview_interval=2))
    eng.submit(steps=4, mode="drift", op="undervolt", seed=0)
    eng.run()
    keys = list(eng.cache._fns)
    streams = sorted(k.stream for k in keys)
    # streamed drift fn, one-shot drift fn, one-shot clean ref
    assert streams == [0, 0, 2]
    # the clean reference batch was computed once and shared across paths
    assert eng.stats.clean_samples_computed == 1
    assert eng.stats.clean_sample_hits == 1


# ------------------------------------------------- streaming (real model)
@pytest.mark.slow
def test_streaming_bit_identical_to_one_shot_single_device():
    """Acceptance: streamed final latents == one-shot latents, bit for bit,
    with >= 1 intermediate preview, on the single-device engine."""
    steps, bucket = 4, 2
    ref_eng = DriftServeEngine(arch="dit-xl-512", smoke=True, bucket=bucket)
    for i in range(2):
        ref_eng.submit(steps=steps, mode="drift", op="undervolt", seed=i)
    ref = ref_eng.run()

    str_eng = DriftServeEngine(arch="dit-xl-512", smoke=True, bucket=bucket)
    for i in range(2):
        str_eng.submit(steps=steps, mode="drift", op="undervolt", seed=i)
    events = list(str_eng.run_stream(preview_interval=2))
    previews = [e for e in events if isinstance(e, PreviewEvent)]
    results = sorted((e for e in events if isinstance(e, RequestResult)),
                     key=lambda r: r.request_id)

    assert len(previews) >= 1
    assert all(p.step < steps for p in previews)
    for a, b in zip(ref, results):
        assert a.request_id == b.request_id
        assert np.array_equal(np.asarray(a.latents), np.asarray(b.latents))
        assert a.n_model_evals == b.n_model_evals
        assert a.psnr_vs_clean_db == pytest.approx(b.psnr_vs_clean_db)
    # previews differ from the final image (they are intermediate states)
    p0 = next(p for p in previews if p.request_id == 0)
    assert not np.array_equal(np.asarray(p0.latents),
                              np.asarray(results[0].latents))
    # monitor feedback carried identically through the windowed path
    assert int(str_eng.monitor.n_updates) == int(ref_eng.monitor.n_updates)
    assert float(str_eng.monitor.ema_ber) == \
        pytest.approx(float(ref_eng.monitor.ema_ber))


@pytest.mark.slow
def test_streaming_through_scheduler_cli_shape():
    """Streaming + scheduler compose: a deadline'd interactive request and
    a background request, streamed, both produce previews and results."""
    eng = DriftServeEngine(arch="dit-xl-512", smoke=True, bucket=1)
    sched = DeadlineScheduler(eng)
    lat_full = sched.batch_latency_s("dit-xl-512", "overclock", 6)
    hi = sched.submit(steps=6, mode="drift", op="undervolt",
                      priority="interactive", deadline_s=lat_full * 1.1,
                      seed=0)
    bg = sched.submit(steps=6, mode="drift", op="undervolt",
                      priority="background", seed=1)
    assert hi.op == "overclock" and bg.op == "undervolt"
    events = list(sched.run_stream(preview_interval=3))
    results = {e.request_id: e for e in events
               if isinstance(e, RequestResult)}
    previews = [e for e in events if isinstance(e, PreviewEvent)]
    assert {p.request_id for p in previews} == {hi.request_id,
                                                bg.request_id}
    # interactive request's batch ran (and streamed) first
    assert previews[0].request_id == hi.request_id
    assert not results[hi.request_id].deadline_missed
    assert results[hi.request_id].op == "overclock"
    assert results[bg.request_id].op == "undervolt"


# ------------------------------------------------------------- help sync
def _example_parser():
    import importlib.util
    import pathlib
    path = (pathlib.Path(__file__).resolve().parents[1] / "examples"
            / "drift_serve.py")
    spec = importlib.util.spec_from_file_location("drift_serve_example",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.build_parser()


def _serve_parser():
    from repro.launch import serve as serve_cli
    return serve_cli.build_parser()


@pytest.mark.parametrize("build_parser", [_serve_parser, _example_parser],
                         ids=["launch.serve", "examples.drift_serve"])
def test_serve_cli_help_enumerates_ladder_and_flags(build_parser):
    """Tier-1 twin of tools/check_help_sync.py for both serving CLIs."""
    text = build_parser().format_help()
    for p in dvfs.OP_LADDER:
        assert p.name in text, f"--help lost ladder point {p.name}"
    for flag in ("--priority", "--deadline", "--step-budget", "--stream",
                 "--op", "--metrics-port", "--no-telemetry", "--smoke",
                 "--no-smoke"):
        assert flag in text, f"--help lost {flag}"


def test_no_smoke_selects_published_widths():
    from repro.launch import serve as serve_cli
    parse = serve_cli.build_parser().parse_args
    assert parse([]).smoke is True
    assert parse(["--no-smoke"]).smoke is False
    assert _example_parser().parse_args(["--no-smoke"]).smoke is False
