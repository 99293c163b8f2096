"""Plain reference of DiT-XL/2 as served.

Written from the paper (arXiv:2212.09748) and the configuration file, in straightforward ``jax.numpy`` and float32. It imports
nothing of the program under test and takes only what the benchmark made:
the weights (``weights.make``) and each request's seed.

The model: patchify (p = 2) and a linear patch embedding plus position
embedding; a sinusoidal timestep embedding (256 wide) through a SiLU MLP;
the class embedding added to it as the conditioning ``c``. Each block:
adaLN modulation ``silu(c) @ W + b`` split into shift, scale and gate for
attention and for the MLP; non-affine layer norms (eps 1e-5); 16-head
self-attention; a tanh-GELU MLP. The final
layer modulates, projects to p*p*C noise channels and unpatchifies. The
sampler is DDIM (eta 0) over a linear beta schedule, clipping the predicted
clean latents to [-4, 4] at each step; served finals are clipped to
[-1, 1].

The projection GEMMs are computed in one of three ways (``Precision``):

* ``bits=None``: float32 at the highest matmul precision. The reference.
* ``bits=b``: symmetric ``b``-bit operands, activations per tensor and
  weights per output column, accumulated exactly in int32. ``b = 4`` is the
  control: the reference one step below the int8 the configuration states.
* ``faults=True`` adds the DRIFT semantics the configuration states: at the
  operating point's bit-error rate, one uniformly placed bit flip per int32
  accumulator word with probability ``1 - (1 - ber)^32``, drawn from the
  fault site's key; per 32 x 32 tile, a row or column whose error sum
  reaches ``2^threshold_bit`` is flagged, and the union of flagged rows and
  columns is replaced by the checkpoint of that GEMM's output, which is
  refreshed every ``rollback_interval`` steps (from step 0). The first
  ``nominal_steps`` steps, the embedding GEMMs and block 0 run at BER 0.
  The fault sites are keyed as the served sampler keys them, which is part
  of the configuration's contract (a fault pattern is a function of the
  engine seed, the batch index, the step, the layer and the GEMM's name):
  ``fold_in(fold_in(fold_in(layer_key, step), crc32(name)), 0)`` with
  ``layer_key = fold_in(fold_in(fold_in(PRNGKey(engine_seed), batch),
  step), layer or 1000)``.

Everything else (layer norms, softmax, GELU, modulation, DDIM) is float32
at the highest precision in every mode.

As every configuration's reference module, it also states the
architecture to the rest of the harness: :func:`param_shapes`, the served
parameter tree that ``chipbench.weights`` draws, and :func:`per_eval`, the
operations of one denoiser evaluation that ``chipbench.opcount`` prices.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import zlib
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.weights import SINCOS

HI = jax.lax.Precision.HIGHEST
EMBED_LAYER = 1000      # fold-in tag of the embedding GEMMs' fault key


@dataclasses.dataclass(frozen=True)
class Precision:
    bits: Optional[int] = None      # None: float32 GEMMs
    faults: bool = False            # DRIFT injection + tile rollback

    def __post_init__(self):
        if self.faults and self.bits is None:
            raise ValueError("fault injection needs integer GEMMs")


REFERENCE = Precision()


@dataclasses.dataclass(frozen=True)
class Batch:
    """One served batch as the engine formed it."""
    seeds: tuple            # padded to the bucket (padding repeats the last)
    batch_index: int        # the engine's batch counter
    engine_seed: int        # the engine's base seed
    ber: float              # body-block BER at the batch's operating point


# Scale of the leaves a trained DiT starts from zero (adaLN, output
# projection, biases) and of the class embedding.
SMALL_STD = 0.02


# ------------------------------------------------------------ architecture
def param_shapes(cfg: dict) -> Dict:
    """{leaf path: (shape, std or ``SINCOS``)} of the served tree.

    The layout is the one the served model reads (``params["blocks"]``
    stacked over depth, as ``models/dit.py`` keeps it), from the
    configuration's sizes alone. Every leaf but the position embedding is
    drawn, the adaLN-Zero modulation and output projections and the biases
    included: zero-initialised modulation makes every block an identity
    and the predicted noise exactly zero, which no trained checkpoint has.
    The position embedding is DiT's frozen 2-D sine-cosine table
    (arXiv:2212.09748, Sec. 3.2), as every DiT checkpoint carries it: a
    small drawn one leaves each token's state to the image alone, and the
    sampler then amplifies float rounding into the finals as no trained
    model does."""
    d, f, n_layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    tokens = (cfg["latent_size"] // cfg["patch_size"]) ** 2
    pdim = cfg["patch_size"] ** 2 * cfg["latent_channels"]

    def dense(k, n):
        return ((k, n), 1.0 / math.sqrt(k))

    def stacked(leaf):
        shape, std = leaf
        return ((n_layers,) + shape, std)

    attn = {"wq": stacked(dense(d, d)), "wk": stacked(dense(d, d)),
            "wv": stacked(dense(d, d)), "wo": stacked(dense(d, d))}
    blocks = {"adaln_w": stacked(((d, 6 * d), SMALL_STD)),
              "adaln_b": stacked(((6 * d,), SMALL_STD)),
              "attn": attn,
              "mlp_w1": stacked(dense(d, f)),
              "mlp_w2": stacked(dense(f, d))}
    tree = {
        "patch_w": dense(pdim, d), "patch_b": ((d,), SMALL_STD),
        "pos_embed": ((tokens, d), SINCOS),
        "t_w1": dense(256, d), "t_b1": ((d,), SMALL_STD),
        "t_w2": dense(d, d), "t_b2": ((d,), SMALL_STD),
        "blocks": blocks,
        "final_adaln_w": ((d, 2 * d), SMALL_STD),
        "final_adaln_b": ((2 * d,), SMALL_STD),
        "final_w": ((d, pdim), SMALL_STD), "final_b": ((pdim,), SMALL_STD),
        "class_embed": ((cfg["num_classes"] + 1, d), SMALL_STD),
    }
    return tree


def per_eval(cfg: dict) -> Dict[str, float]:
    """MACs of one denoiser evaluation of one image: ``int8_macs``, the
    projection GEMMs the served model quantizes (patch, timestep MLP, per
    block q/k/v/o and the MLP, the final projection), and ``float_macs``,
    the GEMMs it runs in floating point (attention scores and mixing, the
    adaLN modulation dots). Matches the DiT count of the program's
    ``perfmodel.flops.gemm_macs_per_model_eval``."""
    d, f, n_layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    t = (cfg["latent_size"] // cfg["patch_size"]) ** 2
    pdim = cfg["patch_size"] ** 2 * cfg["latent_channels"]

    block_int8 = t * (4 * d * d + 2 * d * f)
    block_float = 2 * t * t * d + 6 * d * d          # attention + modulation
    embed_int8 = t * pdim * d + 256 * d + d * d + t * d * pdim
    return {"int8_macs": float(n_layers * block_int8 + embed_int8),
            "float_macs": float(n_layers * block_float + 2 * d * d)}


def _site_id(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


# ------------------------------------------------------------------ inputs
def inputs(cfg: dict, seeds: Sequence[int]):
    """Initial latents and class id of each request, from its seed."""
    size, ch = cfg["latent_size"], cfg["latent_channels"]
    lat = jnp.stack([jax.random.normal(
        jax.random.fold_in(jax.random.PRNGKey(s), 7), (size, size, ch))
        for s in seeds])
    cond = jnp.asarray([s % cfg["num_classes"] for s in seeds], jnp.int32)
    return lat, cond


# ------------------------------------------------------------------ GEMMs
def _quant(x, bits: int, per_column: bool):
    levels = float(2 ** (bits - 1) - 1)
    amax = (jnp.max(jnp.abs(x), axis=0, keepdims=True) if per_column
            else jnp.max(jnp.abs(x)))
    scale = jnp.maximum(amax, 1e-8) / levels
    q = jnp.clip(jnp.round(x / scale), -levels, levels).astype(jnp.int8)
    return q, scale


def _flips(acc, key, ber):
    """One bit flip per flipped word, as the fault model states."""
    kf, kb, _, _ = jax.random.split(key, 4)
    p = -jnp.expm1(32 * jnp.log1p(-jnp.clip(jnp.asarray(ber, jnp.float32),
                                           0.0, 0.5)))
    flip = jax.random.uniform(kf, acc.shape) < p
    pos = jax.random.randint(kb, acc.shape, 0, 32, dtype=jnp.uint32)
    mask = jnp.where(flip, jnp.left_shift(jnp.uint32(1), pos), jnp.uint32(0))
    bits = jax.lax.bitcast_convert_type(acc, jnp.uint32)
    return jax.lax.bitcast_convert_type(jnp.bitwise_xor(bits, mask),
                                        jnp.int32)


def _tile_mask(err, abft: dict):
    """Union of the tile rows and columns whose error sum is large."""
    tm, tn = abft["tile_m"], abft["tile_n"]
    thr = jnp.int32(1 << abft["threshold_bit"])
    m, n = err.shape
    pm, pn = (-m) % tm, (-n) % tn
    e = jnp.pad(err, ((0, pm), (0, pn))).reshape((m + pm) // tm, tm,
                                                   (n + pn) // tn, tn)
    rows = e.sum(axis=3)                          # (Mt, tm, Nt), wraps
    cols = e.sum(axis=1)                          # (Mt, Nt, tn)
    big = lambda s: (s >= thr) | (s <= -thr)
    mask = big(rows)[:, :, :, None] | big(cols)[:, None, :, :]
    return mask.reshape(m + pm, n + pn)[:m, :n]


class Gemms:
    """The projection GEMMs of one denoiser evaluation."""

    def __init__(self, prec: Precision, abft: dict, key=None, step=None,
                 ber=0.0, store=None, have_ckpt=False, refresh=False):
        self.prec, self.abft = prec, abft
        self.key, self.step, self.ber = key, step, ber
        self.store = store if store is not None else {}
        self.have_ckpt, self.refresh = have_ckpt, refresh
        self.store_out: Dict[str, jax.Array] = {}

    def __call__(self, x, w, name: str):
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        if self.prec.bits is None:
            return jnp.dot(x2, w, precision=HI).reshape(*lead, -1)
        xq, xs = _quant(x2, self.prec.bits, per_column=False)
        wq, ws = _quant(w, self.prec.bits, per_column=True)
        acc = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.int32)
        if not self.prec.faults:
            return (acc.astype(jnp.float32) * xs * ws).reshape(*lead, -1)
        key = jax.random.fold_in(jax.random.fold_in(
            jax.random.fold_in(self.key, self.step), _site_id(name)), 0)
        faulty = _flips(acc, key, self.ber)
        mask = _tile_mask(faulty - acc, self.abft)
        y = faulty.astype(jnp.float32) * xs * ws
        ckpt = self.store.get(name, jnp.zeros_like(y))
        y = jnp.where(mask, jnp.where(self.have_ckpt, ckpt, 0.0), y)
        self.store_out[name] = jnp.where(self.refresh, y, ckpt)
        return y.reshape(*lead, -1)


# ------------------------------------------------------------------ model
def _layernorm(x):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-5)


def _attention(q, k, v, heads: int):
    b, tq, d = q.shape
    hd = d // heads
    split = lambda a: a.reshape(b, a.shape[1], heads, hd)
    s = jnp.einsum("bqhd,bkhd->bhqk", split(q), split(k),
                   precision=HI) / math.sqrt(hd)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, split(v),
                      precision=HI).reshape(b, tq, d)


def _modulation(c, w, b):
    return jnp.dot(jax.nn.silu(c), w, precision=HI) + b


def _block(cfg, p, x, c, gemm):
    h = cfg["n_heads"]
    s1, sc1, g1, s2, sc2, g2 = jnp.split(
        _modulation(c, p["adaln_w"], p["adaln_b"]), 6, axis=-1)
    xn = _layernorm(x) * (1 + sc1[:, None]) + s1[:, None]
    a = p["attn"]
    o = _attention(gemm(xn, a["wq"], "attn.q"), gemm(xn, a["wk"], "attn.k"),
                   gemm(xn, a["wv"], "attn.v"), h)
    x = x + g1[:, None] * gemm(o, a["wo"], "attn.o")
    xn = _layernorm(x) * (1 + sc2[:, None]) + s2[:, None]
    hid = jax.nn.gelu(gemm(xn, p["mlp_w1"], "mlp.w1"), approximate=True)
    return x + g2[:, None] * gemm(hid, p["mlp_w2"], "mlp.w2")


def _patchify(cfg, lat):
    b, hh, ww, ch = lat.shape
    p = cfg["patch_size"]
    x = lat.reshape(b, hh // p, p, ww // p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (hh // p) * (ww // p), p * p * ch)


def _unpatchify(cfg, x, hh, ww):
    b = x.shape[0]
    p, ch = cfg["patch_size"], cfg["latent_channels"]
    x = x.reshape(b, hh // p, ww // p, p, p, ch).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, hh, ww, ch)


def _timestep_embedding(t, dim: int = 256):
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = t[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


# ---------------------------------------------------------------- sampler
def schedule(serving: dict):
    """(cumulative alphas, DDIM timesteps, next timesteps)."""
    betas = np.linspace(serving["beta_start"], serving["beta_end"],
                        serving["train_steps"], dtype=np.float32)
    alphas_cum = np.cumprod(1.0 - betas)
    ts = np.linspace(serving["train_steps"] - 1, 0, serving["steps"]
                     ).round().astype(np.int32)
    t_next = np.concatenate([ts[1:], [-1]]).astype(np.int32)
    return alphas_cum, ts, t_next


def store_shapes(cfg: dict, bucket: int) -> Dict:
    """Checkpoint store of the DRIFT semantics: every projection GEMM's
    output, (rows, cols); block GEMMs stacked over depth."""
    d, f, n_layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    rows = bucket * (cfg["latent_size"] // cfg["patch_size"]) ** 2
    pdim = cfg["patch_size"] ** 2 * cfg["latent_channels"]
    embed = {"patch": (rows, d), "t.w1": (bucket, d), "t.w2": (bucket, d),
             "final": (rows, pdim)}
    block = {n: (n_layers, rows, d) for n in
             ("attn.q", "attn.k", "attn.v", "attn.o", "mlp.w2")}
    block["mlp.w1"] = (n_layers, rows, f)
    return {"embed": embed, "block": block}


def _zero_store(cfg, bucket):
    return jax.tree.map(lambda s: jnp.zeros(s, jnp.float32),
                        store_shapes(cfg, bucket),
                        is_leaf=lambda s: isinstance(s, tuple))


@functools.partial(jax.jit, static_argnums=(0, 1), donate_argnums=(11,))
def _step(cfg_items, prec, params, lat, cond, i, t_now, t_next,
          alphas_cum, run_key, ber, store):
    """One DDIM step: the denoiser evaluation and the update."""
    cfg = dict(cfg_items)
    abft = dict(cfg["abft"])
    serving = dict(cfg["serving"])
    b, hh, ww, _ = lat.shape
    step_key = jax.random.fold_in(run_key, i) if prec.faults else None
    ber_now = jnp.where(i < serving["nominal_steps"], 0.0, ber)
    refresh = (i % serving["rollback_interval"]) == 0
    have_ckpt = i > 0

    def gemms(layer, layer_ber, st):
        key = (jax.random.fold_in(step_key, layer) if prec.faults
               else None)
        return Gemms(prec, abft, key=key, step=i, ber=layer_ber, store=st,
                     have_ckpt=have_ckpt, refresh=refresh)

    eg = gemms(EMBED_LAYER, 0.0, store["embed"])
    x = eg(_patchify(cfg, lat), params["patch_w"], "patch")
    x = x + params["patch_b"] + params["pos_embed"]
    tvec = jnp.full((b,), t_now, jnp.float32)
    temb = eg(_timestep_embedding(tvec), params["t_w1"], "t.w1")
    temb = eg(jax.nn.silu(temb + params["t_b1"]), params["t_w2"], "t.w2")
    c = temb + params["t_b2"] + params["class_embed"][cond]

    def body(carry, per_layer):
        # the store rides the carry and is rewritten in place, layer by
        # layer, so the step holds one copy of it
        x, block_store = carry
        p, layer = per_layer
        st = jax.tree.map(lambda a: a[layer], block_store)
        g = gemms(layer, jnp.where(layer < 1, 0.0, ber_now), st)
        x = _block(cfg, p, x, c, g)
        block_store = jax.tree.map(lambda a, v: a.at[layer].set(v),
                                   block_store, g.store_out)
        return (x, block_store), None

    (x, block_store), _ = jax.lax.scan(
        body, (x, store["block"]),
        (params["blocks"], jnp.arange(cfg["n_layers"])))
    shift, scale = jnp.split(_modulation(c, params["final_adaln_w"],
                                         params["final_adaln_b"]), 2, axis=-1)
    x = _layernorm(x) * (1 + scale[:, None]) + shift[:, None]
    x = eg(x, params["final_w"], "final") + params["final_b"]
    eps = _unpatchify(cfg, x, hh, ww)

    a_t = alphas_cum[jnp.maximum(t_now, 0)]
    a_p = jnp.where(t_next >= 0, alphas_cum[jnp.maximum(t_next, 0)], 1.0)
    x0 = jnp.clip((lat - jnp.sqrt(1 - a_t) * eps) / jnp.sqrt(a_t), -4, 4)
    lat = jnp.sqrt(a_p) * x0 + jnp.sqrt(1 - a_p) * eps
    new_store = ({"embed": eg.store_out, "block": block_store}
                 if prec.faults else store)
    return lat, new_store


def _freeze(cfg: dict):
    """Hashable view of the configuration for the jitted step."""
    keep = ("n_layers", "d_model", "n_heads", "d_ff", "latent_size",
            "latent_channels", "patch_size", "num_classes")
    items = [(k, cfg[k]) for k in keep]
    items.append(("abft", tuple(sorted(cfg["abft"].items()))))
    items.append(("serving", tuple(sorted(cfg["serving"].items()))))
    return tuple(items)


def sample(cfg: dict, params, batch: Batch,
           prec: Precision = REFERENCE) -> np.ndarray:
    """Served finals of one batch (clipped to [-1, 1]), on the host."""
    alphas_cum, ts, t_next = schedule(cfg["serving"])
    lat, cond = inputs(cfg, batch.seeds)
    run_key = jax.random.fold_in(jax.random.PRNGKey(batch.engine_seed),
                                 batch.batch_index)
    store = (_zero_store(cfg, len(batch.seeds)) if prec.faults
             else {"embed": {}, "block": {}})
    frozen = _freeze(cfg)
    for i in range(len(ts)):
        lat, store = _step(frozen, prec, params, lat, cond, jnp.int32(i),
                           jnp.int32(ts[i]), jnp.int32(t_next[i]),
                           jnp.asarray(alphas_cum), run_key,
                           jnp.float32(batch.ber), store)
    return np.asarray(jnp.clip(lat, -1.0, 1.0))
