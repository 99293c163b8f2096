"""Sharded DRIFT serving: one micro-batch spread across a device mesh.

``ShardedDriftServeEngine`` is ``DriftServeEngine`` with placement: the
``MicroBatcher``'s fixed-size buckets land on a ``(data, model)``
``jax.sharding.Mesh`` (built by ``launch.mesh.make_serving_mesh``) instead
of one device. The serving loop, request/bucket semantics, caches, and the
Sec 5.1 BER-monitor feedback are byte-identical to the single-device
engine -- only where arrays live changes:

  ======================  =========================  =====================
  array                   axes                       rule
  ======================  =========================  =====================
  latents / batch inputs  batch on ``data``          ``sharding.batch_spec``
  model params            TP on ``model``, FSDP on   ``sharding.param_specs``
                          ``data`` (DiT rules)
  BER-monitor state       replicated                 ``sharding.replicated``
  detected-error counts   psum over ``data``         GSPMD (sum over the
                                                     sharded batch dim)
  checkpoint stores       follow their activations   GSPMD propagation
  ======================  =========================  =====================

Because the batch dimension never mixes examples inside the sampler, a
data-parallel mesh computes bit-identical latents to the single-device
engine for the same seeds (the sharded CI job asserts this); a ``model``
axis > 1 re-associates GEMM reductions and is only numerically close.

The BER-monitor ladder stays well-ordered exactly as before: batches run
sequentially, each batch's ABFT detection counts are reduced across the
mesh into a replicated scalar before the monitor update, and the engine
carries the replicated monitor state into the next batch -- so per-request
``op="auto"`` reads one shared ladder no matter how many devices served
the bucket.

Single-device degradation: ``make_engine`` returns the plain
``DriftServeEngine`` when there is nothing to shard over
(``jax.device_count() == 1`` or a size-1 mesh), so callers can use it
unconditionally::

    from repro.serving.sharded import make_engine

    engine = make_engine(bucket=8, model_parallel=1)   # sharded if >1 dev
    engine.submit(steps=10, mode="drift", op="auto", seed=0)
    results = engine.run()

Streaming (``run_stream``) and the deadline scheduler compose unchanged:
the windowed sampler pins the same placements at window boundaries, so a
streamed data-parallel run stays bit-identical to the single-device
one-shot path (asserted in tests/test_serving_sharded.py), and
``DeadlineScheduler`` only swaps the batcher -- nothing mesh-related.

Testable on CPU via ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
(set before the first jax import); see tests/test_serving_sharded.py and
docs/serving.md.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding

from repro.diffusion import sampler as sampler_lib
from repro.distributed import constraints
from repro.distributed import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.serving.cache import SamplerKey
from repro.serving.engine import DriftServeEngine


class ShardedDriftServeEngine(DriftServeEngine):
    """DriftServeEngine whose micro-batches run SPMD across a device mesh."""

    def __init__(self, mesh: Optional[Mesh] = None, model_parallel: int = 1,
                 **kw):
        self.mesh = mesh if mesh is not None else \
            mesh_lib.make_serving_mesh(model_parallel)
        if "pod" in self.mesh.axis_names:
            raise ValueError("serving meshes are (data, model); multi-pod "
                             "training meshes do not apply here")
        self._mesh_shape = tuple(
            (a, int(self.mesh.shape[a])) for a in self.mesh.axis_names)
        kw.setdefault("sampler_factory", self._sharded_sampler_factory)
        super().__init__(**kw)
        bucket = self.batcher.bucket
        dsize = shd.axis_size(self.mesh, "data")
        if bucket % dsize:
            # batch_spec degrades to a replicated batch; correct but wasteful
            print(f"[sharded] bucket={bucket} not divisible by data axis "
                  f"{dsize}: batch will be replicated, not sharded")

    # ------------------------------------------------------------ placement
    def _sampler_key_extra(self, bucket: int) -> Dict[str, object]:
        bucket_spec = shd.batch_spec((bucket, 1, 1, 1), self.mesh)
        return {"mesh_shape": self._mesh_shape,
                "batch_spec": shd.spec_str(bucket_spec)}

    def _sharded_sampler_factory(self, key: SamplerKey, model_cfg, scfg,
                                 on_trace):
        # on_carry: the checkpoint-offload tap works unchanged on the mesh
        # -- snapshots read the shard-resident store leaves (device->host
        # per addressable shard, shardings recorded for restore), and the
        # commit decision consumes only replicated inputs: the trace-
        # static step count and the monitor state, whose detection sums
        # were already psum-reduced across the mesh. Every shard therefore
        # agrees on every commit/skip with no extra collective.
        return sampler_lib.make_sampler(model_cfg, scfg, on_trace=on_trace,
                                        mesh=self.mesh,
                                        stream_window=key.stream,
                                        on_window=self._on_stream_window,
                                        on_carry=self._offload_on_carry)

    def load_params(self, arch: str, smoke: bool, params) -> None:
        super().load_params(arch, smoke, jax.device_put(
            params, shd.shardings_for(params, self.mesh)))

    def place_inputs(self, tree):
        # Batch-shaped staged inputs (whatever the paradigm's ServableModel
        # built) get sharded along ``data``; jax.tree.map skips None leaves.
        put = lambda x: jax.device_put(
            x, NamedSharding(self.mesh, shd.batch_spec(x.shape, self.mesh)))
        return jax.tree.map(put, tree)

    # ------------------------------------------------------------ one batch
    @contextlib.contextmanager
    def placement(self):
        # the MeshPolicy anchors activation shardings inside the model (see
        # distributed/constraints.py) and the ambient mesh lets bare
        # PartitionSpecs inside the jitted sampler resolve; restore both so
        # a sharded engine can coexist with single-device ones in-process.
        prev = constraints.get_policy()
        constraints.set_policy(constraints.MeshPolicy(self.mesh))
        try:
            with self.mesh:
                yield
        finally:
            constraints.set_policy(prev)

    def _run_batch(self, mb):
        with self.placement():
            return super()._run_batch(mb)

    def _run_batch_stream(self, mb, preview_interval):
        # held open across the whole generator: every window (and the
        # consumer code between yields) runs inside it. The engine is
        # single-threaded, so don't interleave another engine's batches
        # while a stream is mid-batch.
        with self.placement():
            yield from super()._run_batch_stream(mb, preview_interval)


def make_engine(mesh: Optional[Mesh] = None, model_parallel: int = 1,
                **kw) -> DriftServeEngine:
    """Build the widest engine the process supports.

    Returns ``ShardedDriftServeEngine`` on a multi-device mesh, or the
    plain single-device ``DriftServeEngine`` when ``jax.device_count() == 1``
    (or the caller hands in a size-1 mesh) -- the graceful-degradation
    entry point launchers should use.
    """
    if mesh is not None and model_parallel != 1:
        raise ValueError("pass either an explicit mesh or model_parallel, "
                         "not both")
    if mesh is None and jax.device_count() == 1:
        return DriftServeEngine(**kw)
    if mesh is not None and mesh.size == 1:
        return DriftServeEngine(**kw)
    return ShardedDriftServeEngine(mesh=mesh, model_parallel=model_parallel,
                                   **kw)
