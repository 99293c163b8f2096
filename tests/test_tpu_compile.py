"""Compile the main-path kernels and a DiT-XL/2 block for a described TPU.

Nothing runs: the TPU compiler that ships with jaxlib compiles for a
``v5e:2x2`` topology that is described, not attached, and refuses what the
chip would refuse (block shapes off the (8, 128) tiling, operand types the
MXU does not take, programs that do not fit). Interpret-mode tests cannot
see any of that.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every xdist worker imports this
file. The persistent compilation cache stays off around these compiles; a
described chip's executables cannot be read back.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro import configs
from repro.core.exec_ctx import DriftSystemConfig, ExecContext
from repro.distributed import constraints
from repro.kernels import abft_matmul, flash_attention, rollback_correct
from repro.launch.mesh import make_serving_mesh
from repro.models import dit

# DiT-XL/2 at bucket 2: 2 x 1024 tokens, d=1152, d_ff=4608.
M, K, N = 2048, 1152, 4608


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_abft_matmul_compiles(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    c = _compile(abft_matmul.abft_matmul, s((M, K), jnp.int8),
                 s((K, N), jnp.int8), s((M, N), jnp.uint32))
    assert "tpu_custom_call" in c.as_text()


def test_rollback_correct_compiles(one_chip):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = lambda c, ckpt, rd, cd: rollback_correct.rollback_correct(
        c, ckpt, rd, cd, threshold=1 << 10)
    c = _compile(fn, s((M, N), jnp.float32), s((M, N), jnp.float32),
                 s((M, N // 128), jnp.int32), s((M // 128, N), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_flash_attention_compiles(one_chip):
    qkv = jax.ShapeDtypeStruct((32, 1024, 72), jnp.bfloat16,
                               sharding=one_chip)
    c = _compile(flash_attention.flash_attention, qkv, qkv, qkv)
    assert "tpu_custom_call" in c.as_text()


def test_drift_dit_block_compiles(one_chip):
    """One drift-mode DiT-XL/2 block (the jnp main path: int8 GEMMs, fault
    injection, ABFT checksums, rollback, checkpoint write) at bucket 2."""
    cfg = configs.get_config("dit-xl-512")
    layer = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), tree)
    params = jax.eval_shape(lambda k: dit.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    store = jax.eval_shape(lambda: dit.drift_store_spec(cfg, 2)[1])
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))

    def block(p, x, c, store, key):
        ctx = ExecContext(DriftSystemConfig(mode="drift"), key=key, step=3,
                          ber_by_class=jnp.asarray([0.0, 1e-4, 3e-3]),
                          state_in=store, have_ckpt=True)
        y = dit.dit_block(cfg, p, x, c, ctx=ctx)
        return y, ctx.state_out, ctx.stats["detected_row_errors"]

    c = _compile(block,
                 _shapes(layer(params["blocks"]), one_chip),
                 _shapes(jax.ShapeDtypeStruct((2, 1024, cfg.d_model),
                                              cfg.dtype), one_chip),
                 _shapes(jax.ShapeDtypeStruct((2, cfg.d_model), cfg.dtype),
                         one_chip),
                 _shapes(layer(store), one_chip),
                 _shapes(key, one_chip))
    mem = c.memory_analysis()
    assert mem.temp_size_in_bytes < 16 * 2 ** 30


def test_adaln_modulation_keeps_the_batched_dot_on_a_data_mesh(one_chip, topo):
    """On a data=4 mesh at bucket 4 the adaLN modulation runs the same
    (4, d) @ (d, 6d) MXU dot on every device as one device runs. Sharded
    over ``data``, each device would keep one row, XLA would rewrite the
    dot as a bf16 multiply-reduce, and data-parallel finals would stop
    matching one device's bit for bit."""
    mesh = make_serving_mesh(1, devices=topo.devices)
    d = configs.get_config("dit-xl-512").d_model
    s = lambda shape, dt, spec: jax.ShapeDtypeStruct(
        shape, dt, sharding=NamedSharding(mesh, spec))
    prev = constraints.get_policy()
    constraints.set_policy(constraints.MeshPolicy(mesh))
    try:
        with mesh:
            hlo = _compile(
                lambda c, w, b: dit._modulation(c, w, b, jnp.bfloat16),
                s((4, d), jnp.bfloat16, P("data", None)),
                s((d, 6 * d), jnp.float32, P("data", None)),
                s((6 * d,), jnp.float32, P(None))).as_text()
    finally:
        constraints.set_policy(prev)
    assert re.search(rf"bf16\[4,{6 * d}\]\S* convolution\(", hlo)
