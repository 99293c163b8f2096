"""Serving-engine tests: queue ordering, bucketing, cache-key hygiene,
mixed per-request operating points, BER-monitor carry-over, and one real
end-to-end compile-once run.

Logic tests inject a fake sampler factory (no jit, no model) so queue /
batcher / cache behavior is exercised in milliseconds; the end-to-end test
runs the real smoke DiT sampler and asserts on the exact JAX trace count.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dvfs
from repro.diffusion.sampler import SampleOutput
from repro.serving import DriftServeEngine, SamplerKey
from repro.serving.request import GenerationRequest, RequestQueue
from repro.serving import sharded as sharded_mod
from repro.serving.sharded import ShardedDriftServeEngine


def fake_factory(calls=None):
    """Sampler factory stub: echoes latents, advances the monitor by one
    update per batch, and (like the real jit path) fires on_trace once."""
    def factory(key: SamplerKey, model_cfg, scfg, on_trace):
        on_trace()

        def run(params, rng, latents, cond, text, monitor0):
            if calls is not None:
                calls.append(key)
            mon = dvfs.BerMonitorState(monitor0.ema_ber,
                                       monitor0.op_index,
                                       monitor0.n_updates + 1)
            return SampleOutput(latents, mon, jnp.int32(0),
                                jnp.int32(scfg.num_sample_steps))
        return run
    return factory


def make_engine(bucket=2, **kw):
    return DriftServeEngine(arch="dit-xl-512", smoke=True, bucket=bucket,
                            sampler_factory=fake_factory(kw.pop("calls",
                                                                None)),
                            **kw)


# ------------------------------------------------------------------ queue
def test_queue_fifo_and_take_matching():
    q = RequestQueue()
    ids = [q.submit(op="undervolt", seed=i) for i in range(3)]
    ids += [q.submit(op="overclock", seed=9)]
    assert ids == [0, 1, 2, 3]
    taken = q.take_matching("undervolt", lambda r: r.op, limit=2)
    assert [r.request_id for r in taken] == [0, 1]
    # non-matching request kept its place behind the remaining match
    assert [r.request_id for r in (q.peek(),)] == [2]
    assert len(q) == 2


def test_results_in_submission_order_across_groups():
    eng = make_engine(bucket=2)
    # interleaved ops force regrouping: [uv, oc, uv, oc] -> 2 batches
    for i, op in enumerate(["undervolt", "overclock"] * 2):
        eng.submit(steps=2, mode="drift", op=op, seed=i)
    results = eng.run()
    assert [r.request_id for r in results] == [0, 1, 2, 3]
    assert [r.op for r in results] == ["undervolt", "overclock"] * 2
    # same-op requests shared a batch despite interleaved submission
    assert results[0].batch_index == results[2].batch_index
    assert results[1].batch_index == results[3].batch_index


# -------------------------------------------------------------- bucketing
def test_odd_stream_padded_into_fixed_buckets():
    eng = make_engine(bucket=2)
    for i in range(5):
        eng.submit(steps=2, mode="drift", op="undervolt", seed=i)
    results = eng.run()
    assert len(results) == 5                      # every request answered
    assert eng.stats.batches == 3                 # ceil(5 / 2)
    assert eng.stats.padded_slots == 1            # one dummy slot total
    assert all(r.bucket_size == 2 for r in results)


def test_bucket_one_stream():
    eng = make_engine(bucket=1)
    for i in range(3):
        eng.submit(steps=2, mode="drift", op="undervolt", seed=i)
    assert len(eng.run()) == 3
    assert eng.stats.batches == 3
    assert eng.stats.padded_slots == 0


# ------------------------------------------------------------- cache keys
def test_cache_key_hygiene_no_recompile_on_repeat():
    calls = []
    eng = make_engine(bucket=2, calls=calls)
    for round_ in range(3):
        for i in range(2):
            eng.submit(steps=2, mode="drift", op="undervolt",
                       seed=round_ * 2 + i)
        eng.run()
    # one drift config + one clean-reference config, compiled once each
    assert eng.cache.compiles == 2
    assert eng.cache.traces == 2
    assert eng.cache.hits >= 2
    assert len({k for k in calls}) == 2


def test_distinct_configs_get_distinct_cache_entries():
    eng = make_engine(bucket=2)
    eng.submit(steps=2, mode="drift", op="undervolt", seed=0)
    eng.submit(steps=3, mode="drift", op="undervolt", seed=1)   # steps differ
    eng.submit(steps=2, mode="faulty", op="undervolt", seed=2)  # mode differs
    eng.submit(steps=2, mode="drift", op="overclock", seed=3)   # op differs
    results = eng.run()
    assert len(results) == 4
    assert eng.stats.batches == 4                 # nothing co-batchable
    # 4 serving configs + clean references for (steps=2) and (steps=3)
    assert eng.cache.compiles == 6


def test_clean_reference_cached_per_seed_batch():
    eng = make_engine(bucket=2)
    for round_ in range(2):                        # identical seed stream
        for i in range(2):
            eng.submit(steps=2, mode="drift", op="undervolt", seed=i)
        eng.run()
    assert eng.stats.clean_samples_computed == 1   # computed once...
    assert eng.stats.clean_sample_hits == 1        # ...reused on round 2


# --------------------------------------------- mixed ops + monitor state
def test_mixed_ops_one_run_and_auto_resolution():
    eng = make_engine(bucket=2)
    for i, op in enumerate(["undervolt", "overclock", "auto", "auto"]):
        eng.submit(steps=2, mode="drift", op=op, seed=i)
    results = eng.run()
    ops = [r.op for r in results]
    assert ops[0] == "undervolt" and ops[1] == "overclock"
    # fresh monitor: ladder index 0 -> most aggressive point
    assert ops[2] == ops[3] == dvfs.OP_LADDER[0].name
    # auto resolves to the same SamplerKey as the explicit undervolt
    # request, so the first auto request co-batches with it
    assert results[2].batch_index == results[0].batch_index


def test_monitor_carries_over_between_batches():
    eng = make_engine(bucket=1)
    for i in range(4):
        eng.submit(steps=2, mode="drift", op="undervolt", seed=i)
    eng.run()
    # fake sampler bumps n_updates once per batch, and the engine feeds each
    # batch the previous batch's monitor state
    assert int(eng.monitor.n_updates) == 4
    eng.submit(steps=2, mode="drift", op="undervolt", seed=9)
    eng.run()
    assert int(eng.monitor.n_updates) == 5         # persists across run()s


def test_clean_mode_requests_do_not_feed_monitor():
    eng = make_engine(bucket=1)
    eng.submit(steps=2, mode="clean", op="nominal", seed=0)
    eng.run()
    assert int(eng.monitor.n_updates) == 0


# --------------------------------------------- single-device degradation
def test_load_params_serves_the_given_weights():
    seen = []
    inner = fake_factory()

    def factory(key, model_cfg, scfg, on_trace):
        run = inner(key, model_cfg, scfg, on_trace)

        def recording(params, *args):
            seen.append(params)
            return run(params, *args)
        return recording

    eng = DriftServeEngine(arch="dit-xl-512", smoke=True, bucket=1,
                           sampler_factory=factory)
    weights = {"checkpoint": jnp.ones(3)}
    eng.load_params("dit-xl-512", True, weights)
    eng.submit(steps=3, mode="clean", seed=0)
    eng.run()
    assert seen and all(p is weights for p in seen)


def test_make_engine_falls_back_on_one_device(monkeypatch):
    """With nothing to shard over, the factory must return the plain
    single-device engine (same class PR 1 shipped), not a mesh wrapper."""
    monkeypatch.setattr(sharded_mod.jax, "device_count", lambda: 1)
    eng = sharded_mod.make_engine(bucket=2)
    assert type(eng) is DriftServeEngine
    # plain-engine cache keys carry no mesh placement
    eng.submit(steps=2, mode="drift", op="undervolt", seed=0)
    mb = eng.batcher.next_batch(eng.queue, eng._resolve_op)
    assert mb.key.mesh_shape == () and mb.key.batch_spec == ""


def test_make_engine_falls_back_on_size_one_mesh():
    from jax.sharding import Mesh
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    eng = sharded_mod.make_engine(mesh=mesh, bucket=2)
    assert type(eng) is DriftServeEngine
    assert not isinstance(eng, ShardedDriftServeEngine)


# ------------------------------------------------------------ end-to-end
@pytest.mark.slow
def test_end_to_end_real_sampler_compiles_once_per_config():
    eng = DriftServeEngine(arch="dit-xl-512", smoke=True, bucket=2)
    ops = ["undervolt", "overclock"]
    for i in range(4):
        eng.submit(steps=3, mode="drift", op=ops[i % 2], seed=i)
    results = eng.run()
    assert len(results) == 4
    # 2 drift configs + 1 shared clean-reference config, each traced once
    assert eng.cache.traces == 3
    assert eng.stats.batches == 2

    # second identical round: cache hits only, zero new traces
    for i in range(4):
        eng.submit(steps=3, mode="drift", op=ops[i % 2], seed=i)
    results += eng.run()
    assert eng.cache.traces == 3
    # round 2: both drift fns hit; clean refs short-circuit at the sample
    # cache, never reaching the compiled-fn cache
    assert eng.cache.hits >= 3
    assert eng.stats.clean_sample_hits == 2

    # monitor saw every drift batch (3 steps x 4 batches)
    assert int(eng.monitor.n_updates) == 12

    for r in results:
        assert r.lpips_vs_clean >= 0.0
        assert r.psnr_vs_clean_db > 20.0           # DRIFT stays near clean
        assert r.energy_j > 0.0 and r.latency_s > 0.0
        assert r.baseline_energy_j > 0.0
        assert r.n_model_evals == 3
    # undervolt saves energy vs overclock's speed mode at equal steps
    uv = [r for r in results if r.op == "undervolt"]
    oc = [r for r in results if r.op == "overclock"]
    assert uv[0].energy_j < oc[0].energy_j
    assert oc[0].latency_s < uv[0].latency_s
