#!/usr/bin/env python3
"""Serve DiT-XL/2-512 at full width on a TPU through the normal engine.

    python chip_smoke.py                 # one chip
    python chip_smoke.py --four-chips    # meshes on a four-chip host

One chip: two batches of drift-mode requests through
``repro.launch.serve.main`` at the published widths (28 x 1152), the second
served by the compiled-sampler cache, then a check of the chip's quantized
DiT forward against its f32 forward on one latent. ``--four-chips`` serves
the same seeds through the sharded engine on a (data=4, model=1) mesh and
on one device, requires bit-identical finals, and reports a (2, 2) mesh's
PSNR against the one-device finals.

The weights are random, made from ``--seed``. DiT's adaLN-Zero
initialisation zeroes the modulation and output projections, which makes
every block an identity and the predicted noise exactly zero whatever the
GEMMs compute; a trained checkpoint has neither, so those leaves are drawn
at random too.

The script needs a TPU and never falls back to the CPU. It exits non-zero
when any phase fails; its last line on success is one JSON object naming
the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

ARCH = "dit-xl-512"
# The largest bucket whose drift sampler stays near 12 GB of the chip's
# 16 GiB: argument + output + temp bytes from compiled.memory_analysis()
# for a described v5e chip are 8.06e9 at bucket 2, 10.06e9 at 3 and
# 12.06e9 at 4. On a v5e chip, bucket 4 peaked at 4.50e9 bytes in use.
BUCKET = 4
# Relative L2 error of the int8 DiT forward against the f32 one that the
# reference check accepts (bf16 activations + per-tensor int8 quantization).
REF_TOL = 0.1


def _log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(count: int) -> list:
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is "
                 f"{devices[0].platform!r}); this script runs only on a TPU")
    if len(devices) < count:
        sys.exit(f"chip_smoke: needs {count} TPU chips, found {len(devices)}")
    return devices


def live_params(cfg, seed: int):
    """Seeded random DiT weights with the adaLN-Zero leaves filled in."""
    from repro.train import steps as steps_lib
    params = steps_lib.init_model_params(cfg, jax.random.PRNGKey(seed))
    k_blocks, k_mod, k_out = jax.random.split(jax.random.PRNGKey(seed + 1), 3)
    draw = lambda k, a: 0.02 * jax.random.normal(k, a.shape, a.dtype)
    blocks = dict(params["blocks"])
    blocks["adaln_w"] = draw(k_blocks, blocks["adaln_w"])
    return dict(params, blocks=blocks,
                final_adaln_w=draw(k_mod, params["final_adaln_w"]),
                final_w=draw(k_out, params["final_w"]))


def serve_argv(bucket: int, steps: int, seed: int, mode: str) -> list:
    """The main path's command line for one batch of ``bucket`` requests."""
    return ["--arch", ARCH, "--no-smoke", "--batch", str(bucket),
            "--steps", str(steps), "--mode", mode, "--op", "undervolt",
            "--requests", str(bucket), "--seed", str(seed)]


def serve_batch(serve, eng, argv: list) -> tuple:
    """One served batch through ``serve.main``; (results, wall seconds)."""
    t0 = time.perf_counter()
    results = serve.main(argv, engine=eng)
    jax.block_until_ready([r.latents for r in results])
    return results, time.perf_counter() - t0


def check_finals(results, cfg) -> np.ndarray:
    shape = (cfg.latent_size, cfg.latent_size, cfg.latent_channels)
    finals = np.stack([np.asarray(r.latents) for r in results])
    if finals.shape[1:] != shape:
        raise RuntimeError(f"final latents have shape {finals.shape[1:]}, "
                           f"expected {shape}")
    if not np.isfinite(finals).all():
        raise RuntimeError("non-finite final latents")
    for r in results:
        _log(f"req {r.request_id}: psnr {r.psnr_vs_clean_db:.2f} dB, "
             f"lpips-proxy {r.lpips_vs_clean:.5f} vs the clean reference, "
             f"{r.batch_corrected_elems} corrected elements in its batch")
    return finals


def reference_check(cfg, params, seed: int) -> None:
    """The chip's int8 DiT forward (the clean reference's GEMMs) against
    the f32 forward at full matmul precision, on one latent."""
    import dataclasses

    from repro.core.exec_ctx import DriftSystemConfig
    from repro.models import dit
    key = jax.random.PRNGKey(seed + 2)
    lat = jax.random.normal(key, (1, cfg.latent_size, cfg.latent_size,
                                  cfg.latent_channels))
    t = jnp.full((1,), 500.0)
    cond = jnp.asarray([seed % cfg.num_classes], jnp.int32)

    @jax.jit
    def quantized(params, lat):
        embed, block = dit.drift_store_spec(cfg, 1)
        ds = dit.DriftState(cfg=DriftSystemConfig(mode="clean"), key=key,
                            step=jnp.int32(0), ber_by_class=jnp.zeros(3),
                            embed_store=embed, block_store=block)
        return dit.forward(cfg, params, lat, t, cond, drift=ds)[0]

    f32_cfg = dataclasses.replace(cfg, dtype=jnp.float32)

    @jax.jit
    def reference(params, lat):
        with jax.default_matmul_precision("highest"):
            return dit.forward(f32_cfg, params, lat, t, cond)[0]

    got = np.asarray(quantized(params, lat))
    want = np.asarray(reference(params, lat))
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise RuntimeError("non-finite DiT forward")
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    _log(f"reference: int8 DiT forward vs f32 forward, relative L2 error "
         f"{rel:.5f} (limit {REF_TOL}), |eps| rms {np.sqrt(np.mean(want ** 2)):.4f}")
    if not rel < REF_TOL:
        raise RuntimeError(f"int8 forward is off the f32 reference by "
                           f"{rel:.5f}")


def one_chip(args) -> None:
    from repro import configs
    from repro.launch import serve

    cfg = configs.get_config(ARCH, smoke=False)
    b = BUCKET
    eng = serve.build_engine(serve.build_parser().parse_args(
        serve_argv(b, args.steps, args.seed, "drift")))
    t0 = time.perf_counter()
    params = live_params(cfg, args.seed)
    eng.load_params(ARCH, False, params)
    jax.block_until_ready(params)
    _log(f"bucket {b}, {args.steps} steps; params made in "
         f"{time.perf_counter() - t0:.1f}s")

    _, setup_s = serve_batch(serve, eng,
                             serve_argv(b, args.steps, args.seed, "drift"))
    _log(f"set-up (first batch: compiles the drift sampler and its clean "
         f"twin) {setup_s:.2f}s")
    results, second_s = serve_batch(
        serve, eng, serve_argv(b, args.steps, args.seed + b, "drift"))
    _log(f"second batch wall time {second_s:.3f}s "
         f"(drift sampler + clean twin, {args.steps} steps, bucket {b})")
    traces, hits = eng.cache.traces, eng.cache.hits
    _log(f"compiled-sampler cache: {traces} traces, {hits} hits")
    if traces != 2 or hits < 1:
        raise RuntimeError(f"expected 2 traces and >= 1 hit, got {traces} "
                           f"traces and {hits} hits")
    check_finals(results, cfg)
    stats = jax.devices()[0].memory_stats() or {}
    _log(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
         f"of bytes_limit {stats.get('bytes_limit')}")
    reference_check(cfg, params, args.seed)


def precompile(engines, cfg, steps: int, seeds) -> None:
    """Compile each engine's drift sampler and clean twin before serving.

    XLA compiles outside the interpreter lock, so the six full-width
    programs of the comparison compile side by side on threads instead of
    one after another; the served batches then load them from the
    persistent compilation cache. Lowering needs only shapes, so no
    engine's weights are on the chips yet.
    """
    import dataclasses
    from concurrent.futures import ThreadPoolExecutor

    from repro.core import dvfs as dvfs_lib
    from repro.distributed import sharding as shd
    from repro.serving.cache import SamplerKey

    shapes = jax.eval_shape(lambda: live_params(cfg, 0))
    lowered = []
    for eng in engines:
        mesh = getattr(eng, "mesh", None)
        params = shapes if mesh is None else jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, shd.shardings_for(shapes, mesh))
        sv = eng.servable_for(ARCH)
        lat, cond, text = sv.batch_inputs(cfg, list(seeds))
        key = SamplerKey(arch=ARCH, smoke=False, steps=steps, mode="drift",
                         op="undervolt", bucket=eng.batcher.bucket,
                         **eng.batcher.key_extra)
        with eng.placement():
            for k in (key, dataclasses.replace(key, mode="clean", op="")):
                lowered.append(sv.build_fn(k).lower(
                    params, jax.random.PRNGKey(0), lat, cond, text,
                    dvfs_lib.ber_monitor_init()))
    with ThreadPoolExecutor(len(lowered)) as pool:
        for done in [pool.submit(low.compile) for low in lowered]:
            done.result()


def four_chips(args) -> None:
    """Data-parallel finals against one device, then a (2, 2) mesh."""
    import gc

    from repro import configs
    from repro.core import metrics
    from repro.launch import serve

    cfg = configs.get_config(ARCH, smoke=False)
    b = BUCKET
    argv = serve_argv(b, args.steps, args.seed, "drift")
    layouts = {"one device": [],
               "data=4 model=1": ["--sharded"],
               "data=2 model=2": ["--sharded", "--model-parallel", "2"]}
    engines = {name: serve.build_engine(serve.build_parser().parse_args(
        argv + extra)) for name, extra in layouts.items()}
    t0 = time.perf_counter()
    precompile(engines.values(), cfg, args.steps,
               range(args.seed, args.seed + b))
    _log(f"compiled {2 * len(engines)} programs side by side in "
         f"{time.perf_counter() - t0:.1f}s")
    finals, cleans = {}, {}
    for name, extra in layouts.items():
        eng = engines.pop(name)
        mesh = getattr(eng, "mesh", None)
        _log(f"{name}: mesh {dict(mesh.shape) if mesh is not None else None}"
             f", devices {[d.id for d in mesh.devices.flat] if mesh is not None else [0]}")
        eng.load_params(ARCH, False, live_params(cfg, args.seed))
        results, wall = serve_batch(serve, eng, argv + extra)
        _log(f"{name}: {wall:.2f}s for one batch of {b}")
        finals[name] = check_finals(results, cfg)
        # the batch's clean twin, to tell fault-path from float-path drift
        cleans[name] = np.asarray(next(iter(eng._clean_samples.values())))
        del eng, results
        gc.collect()

    one, dp, mp = (finals[k] for k in layouts)
    c_one, c_dp = (cleans[k] for k in list(layouts)[:2])
    _log(f"clean twins, data-parallel vs one device: "
         f"{int((c_dp != c_one).sum())} of {c_one.size} values differ")
    diff = np.abs(dp.astype(np.float64) - one)
    _log(f"data-parallel vs one device: {int((dp != one).sum())} of "
         f"{one.size} values differ, max |diff| {diff.max():.3e}")
    psnr = float(metrics.psnr(jnp.clip(mp, -1, 1), jnp.clip(one, -1, 1)))
    _log(f"(2, 2) mesh vs one device: psnr {psnr:.2f} dB, max |diff| "
         f"{np.abs(mp.astype(np.float64) - one).max():.3e}")
    if not np.array_equal(dp, one):
        raise RuntimeError("data-parallel finals are not bit-identical to "
                           "the one-device finals")
    if not np.isfinite(psnr):
        raise RuntimeError("(2, 2) finals give a non-finite PSNR")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="the sharded meshes against one device (needs four "
                         "chips)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = require_tpu(4 if args.four_chips else 1)
    _log(f"device_kind {devices[0].device_kind!r}, {len(devices)} devices")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    _log(f"compile cache {cache}: {entries} entries at start")
    events = {"requests": 0, "hits": 0}

    def count(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            events["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            events["hits"] += 1
    jax.monitoring.register_event_listener(count)

    if args.four_chips:
        four_chips(args)
    else:
        one_chip(args)
    _log(f"compile cache {'warm' if events['hits'] else 'cold'}: "
         f"{events['hits']} hits of {events['requests']} cached compiles")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
