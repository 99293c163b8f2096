"""Activation sharding constraints (the GSPMD anchor points).

Without explicit activation constraints, sharding propagation is free to
resolve weight-vs-activation conflicts by REPLICATING activations -- e.g.
the FSDP-sharded embedding table (d_model on 'data') clashing with
batch-on-'data' token activations silently un-shards the batch for the
whole network (observed: per-device attention scores with the full global
batch). Production JAX frameworks pin activations with
``with_sharding_constraint`` at layer boundaries; this module is that
mechanism, behind a process-global policy so single-device tests/smoke
runs pay nothing.

Usage (launcher):
    constraints.set_policy(constraints.MeshPolicy(mesh))
    ... lower/compile under `with mesh:` ...
Models call ``constrain(x, "act")`` at anchor points.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

_POLICY: Optional["MeshPolicy"] = None


def axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape[name] if name in mesh.axis_names else 1


@dataclasses.dataclass
class MeshPolicy:
    mesh: Mesh
    # shard the embedding dim of activations on 'model' (sequence-parallel
    # style)? default off; the perf pass flips it per-cell.
    shard_act_dmodel: bool = False
    # treat EVERY mesh axis as data parallel (small models: replicate
    # weights, shard batch 1-per-chip; hillclimb #3)
    dp_over_all: bool = False

    @property
    def data_axes(self) -> Tuple[str, ...]:
        if self.dp_over_all:
            return tuple(self.mesh.axis_names)
        return tuple(a for a in ("pod", "data") if a in self.mesh.axis_names)

    @property
    def dp(self):
        d = self.data_axes
        return d if len(d) > 1 else (d[0] if d else None)

    @property
    def dsize(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    def msize(self) -> int:
        return axis_size(self.mesh, "model")

    def spec(self, kind: str, shape: Tuple[int, ...]) -> Optional[P]:
        batch_ok = shape[0] % max(self.dsize, 1) == 0 and self.dsize > 1
        dp = self.dp if batch_ok else None
        last_model = "model" if self.shard_act_dmodel else None
        if kind == "act":        # (B, S, D) and friends
            mid = [None] * (len(shape) - 2)
            return P(dp, *mid, last_model)
        if kind == "logits":     # (B, S, V) -- vocab stays model-sharded
            mid = [None] * (len(shape) - 2)
            return P(dp, *mid, "model")
        if kind == "batch_only":
            return P(dp, *([None] * (len(shape) - 1)))
        if kind == "replicated":  # whole array on every device
            return P(*([None] * len(shape)))
        if kind == "tokens2d":   # (T, d) flattened token streams (MoE)
            return P(dp, None)
        if kind == "slots2d":    # (E*C, d) expert-major flat slot space
            msize = axis_size(self.mesh, "model")
            if shape[0] % max(msize, 1) == 0 and msize > 1:
                return P("model", None)
            return None
        if kind == "w2d_model":  # (K, N) GEMM weights whose K must not
            # be split: gathered over data, output dim on model (the DRIFT
            # quantized-GEMM layout; also the DiT adaLN modulation)
            msize = axis_size(self.mesh, "model")
            if len(shape) == 2 and shape[1] % max(msize, 1) == 0 and msize > 1:
                return P(None, "model")
            return P(*([None] * len(shape)))
        if kind == "experts":    # (E, C, d) dispatched slots -- EP layout;
            # E on 'model' AND capacity on data: the expert GEMM is then
            # fully partitioned (E/m x C/d x d x f per device). E-only
            # sharding lets GSPMD replicate the einsum over the data axis
            # (measured 6.5x compute blowup; see EXPERIMENTS.md Perf #2).
            msize = axis_size(self.mesh, "model")
            cap_dp = (self.dp if len(shape) >= 2
                      and shape[1] % max(self.dsize, 1) == 0
                      and self.dsize > 1 else None)
            if shape[0] % max(msize, 1) == 0 and msize > 1:
                return P("model", cap_dp, None)
            return None
        return None


def set_policy(policy: Optional[MeshPolicy]) -> None:
    global _POLICY
    _POLICY = policy


def get_policy() -> Optional[MeshPolicy]:
    return _POLICY


def constrain(x: jax.Array, kind: str = "act") -> jax.Array:
    if _POLICY is None or not hasattr(x, "ndim") or x.ndim < 2:
        return x
    spec = _POLICY.spec(kind, x.shape)
    if spec is None:
        return x
    return jax.lax.with_sharding_constraint(x, spec)
