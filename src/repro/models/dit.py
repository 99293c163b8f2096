"""Diffusion Transformer (DiT) -- the paper's primary model family.

Faithful DiT (Peebles & Xie) with adaLN-Zero conditioning; PixArt-alpha
variant adds cross-attention to (stub-encoded) text tokens. This is the
model DRIFT protects end-to-end: every projection GEMM routes through an
optional ExecContext, with resilience classes
    patch/timestep/class/text embeddings -> CLASS_EMBED   (Sec 4.3: global
        influence through conditioning at every step -> protected)
    block 0                              -> CLASS_FIRST_BLOCK
    remaining blocks                     -> CLASS_BODY
The rollback checkpoint store is stacked (L, ...) for the block GEMMs plus
a flat dict for the embedding GEMMs, carried by the sampler's scan.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import dvfs
from repro.core.exec_ctx import DriftSystemConfig, ExecContext
from repro.distributed.constraints import constrain
from repro.models import attention, common
from repro.models.common import ModelConfig, Params, dense_init, layernorm


# ---------------------------------------------------------------- params
def _init_attn(cfg: ModelConfig, key, kv_dim: Optional[int] = None) -> Params:
    ks = jax.random.split(key, 4)
    d = cfg.d_model
    kv = kv_dim or d
    h, hd = cfg.n_heads, cfg.hd
    return {"wq": dense_init(ks[0], d, h * hd, cfg.param_dtype),
            "wk": dense_init(ks[1], kv, h * hd, cfg.param_dtype),
            "wv": dense_init(ks[2], kv, h * hd, cfg.param_dtype),
            "wo": dense_init(ks[3], h * hd, d, cfg.param_dtype)}


def _init_block(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 5)
    d, f = cfg.d_model, cfg.d_ff
    p = {
        "adaln_w": jnp.zeros((d, 6 * d), cfg.param_dtype),   # adaLN-Zero
        "adaln_b": jnp.zeros((6 * d,), cfg.param_dtype),
        "attn": _init_attn(cfg, ks[0]),
        "mlp_w1": dense_init(ks[1], d, f, cfg.param_dtype),
        "mlp_w2": dense_init(ks[2], f, d, cfg.param_dtype),
    }
    if cfg.cond_tokens:   # PixArt: cross-attention to text tokens
        p["xattn"] = _init_attn(cfg, ks[3])
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 8)
    d = cfg.d_model
    t = (cfg.latent_size // cfg.patch_size) ** 2
    pdim = cfg.patch_size ** 2 * cfg.latent_channels
    p: Params = {
        "patch_w": dense_init(ks[0], pdim, d, cfg.param_dtype),
        "patch_b": jnp.zeros((d,), cfg.param_dtype),
        "pos_embed": common.trunc_normal(ks[1], (t, d), 0.02, cfg.param_dtype),
        "t_w1": dense_init(ks[2], 256, d, cfg.param_dtype),
        "t_b1": jnp.zeros((d,), cfg.param_dtype),
        "t_w2": dense_init(ks[3], d, d, cfg.param_dtype),
        "t_b2": jnp.zeros((d,), cfg.param_dtype),
        "blocks": common.stack_layer_params(
            lambda k: _init_block(cfg, k), cfg.n_layers, ks[4]),
        "final_adaln_w": jnp.zeros((d, 2 * d), cfg.param_dtype),
        "final_adaln_b": jnp.zeros((2 * d,), cfg.param_dtype),
        "final_w": jnp.zeros((d, pdim), cfg.param_dtype),     # zero-init out
        "final_b": jnp.zeros((pdim,), cfg.param_dtype),
    }
    if cfg.cond_tokens:
        p["text_proj"] = dense_init(ks[5], cfg.cond_dim, d, cfg.param_dtype)
    else:
        p["class_embed"] = common.trunc_normal(
            ks[6], (cfg.num_classes + 1, d), 0.02, cfg.param_dtype)
    return p


# --------------------------------------------------------------- helpers
def timestep_embedding(t: jax.Array, dim: int = 256) -> jax.Array:
    half = dim // 2
    freqs = jnp.exp(-jnp.log(10000.0) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    args = t.astype(jnp.float32)[:, None] * freqs[None]
    return jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)


def patchify(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """(B, H, W, C) -> (B, T, p*p*C)."""
    b, hh, ww, c = x.shape
    p = cfg.patch_size
    x = x.reshape(b, hh // p, p, ww // p, p, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (hh // p) * (ww // p),
                                                 p * p * c)


def unpatchify(cfg: ModelConfig, x: jax.Array, hh: int, ww: int) -> jax.Array:
    b, t, _ = x.shape
    p = cfg.patch_size
    gh, gw = hh // p, ww // p
    x = x.reshape(b, gh, gw, p, p, cfg.latent_channels)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(b, hh, ww,
                                                 cfg.latent_channels)


def _modulate(x: jax.Array, shift: jax.Array, scale: jax.Array) -> jax.Array:
    return x * (1.0 + scale[:, None, :]) + shift[:, None, :]


def _modulation(c: jax.Array, w: jax.Array, b: jax.Array,
                dtype) -> jax.Array:
    """adaLN modulation silu(c) @ w + b, for the whole batch on every device
    of a mesh, so that a data-parallel batch matches the single-device one
    bit for bit. Sharded over ``data``, the dot would keep one row per
    device when the bucket equals the data size, and XLA rewrites such a
    dot as a bf16 multiply-reduce whose sums differ from the batched dot's;
    the weight is gathered over ``data`` because GSPMD would otherwise sum
    per-device partial products of its FSDP-sharded K dim. Both operands
    are small (B x d and d x 6d), so replicating them costs little."""
    w = constrain(w.astype(dtype), "w2d_model")
    c = constrain(jax.nn.silu(c.astype(jnp.float32)).astype(dtype),
                  "replicated")
    return constrain(c @ w + b.astype(dtype), "replicated")


def _proj(ctx, x, w, name, rclass):
    if ctx is None:
        return x @ w.astype(x.dtype)
    lead = x.shape[:-1]
    y = ctx.matmul(x.reshape(-1, x.shape[-1]), w.astype(x.dtype),
                   name=name, rclass=rclass)
    return y.reshape(*lead, -1)


# ---------------------------------------------------------------- blocks
def dit_block(cfg: ModelConfig, p: Params, x: jax.Array, c: jax.Array,
              text: Optional[jax.Array] = None,
              ctx: Optional[ExecContext] = None,
              rclass=dvfs.CLASS_BODY) -> jax.Array:
    b, t, d = x.shape
    h, hd = cfg.n_heads, cfg.hd
    mod = _modulation(c, p["adaln_w"], p["adaln_b"], x.dtype)
    s1, sc1, g1, s2, sc2, g2 = jnp.split(mod, 6, axis=-1)

    xn = _modulate(layernorm(x, None, None), s1, sc1)
    q = _proj(ctx, xn, p["attn"]["wq"], "attn.q", rclass).reshape(b, t, h, hd)
    k = _proj(ctx, xn, p["attn"]["wk"], "attn.k", rclass).reshape(b, t, h, hd)
    v = _proj(ctx, xn, p["attn"]["wv"], "attn.v", rclass).reshape(b, t, h, hd)
    o = attention.attention_any(q, k, v, causal=False)
    o = _proj(ctx, o.reshape(b, t, h * hd), p["attn"]["wo"], "attn.o", rclass)
    x = x + g1[:, None, :] * o

    if text is not None and "xattn" in p:
        xn = layernorm(x, None, None)
        q = _proj(ctx, xn, p["xattn"]["wq"], "xattn.q", rclass
                  ).reshape(b, t, h, hd)
        k = _proj(ctx, text, p["xattn"]["wk"], "xattn.k", rclass
                  ).reshape(b, -1, h, hd)
        v = _proj(ctx, text, p["xattn"]["wv"], "xattn.v", rclass
                  ).reshape(b, -1, h, hd)
        o = attention.full_attention(q, k, v, causal=False)
        x = x + _proj(ctx, o.reshape(b, t, h * hd), p["xattn"]["wo"],
                      "xattn.o", rclass)

    xn = _modulate(layernorm(x, None, None), s2, sc2)
    hdn = _proj(ctx, xn, p["mlp_w1"], "mlp.w1", rclass)
    hdn = jax.nn.gelu(hdn.astype(jnp.float32)).astype(x.dtype)
    x = x + g2[:, None, :] * _proj(ctx, hdn, p["mlp_w2"], "mlp.w2", rclass)
    return x


@dataclasses.dataclass
class DriftState:
    """Checkpoint store + per-step drift inputs threaded by the sampler."""
    cfg: DriftSystemConfig
    key: jax.Array
    step: jax.Array
    ber_by_class: jax.Array
    embed_store: Dict[str, jax.Array]
    block_store: Dict[str, jax.Array]   # leaves stacked (L, ...)
    have_ckpt: Any = False
    # Per-site gates for the block-level resilience study (Fig 6): BER is
    # multiplied by layer_gate[layer] / embed_gate. None = all-on.
    layer_gate: Any = None              # (L,) f32 or None
    embed_gate: Any = None              # scalar f32 or None


def forward(cfg: ModelConfig, params: Params, latents: jax.Array,
            t: jax.Array, cond: jax.Array,
            text: Optional[jax.Array] = None,
            drift: Optional[DriftState] = None
            ) -> Tuple[jax.Array, Optional[DriftState], Dict[str, jax.Array]]:
    """Predict noise. latents: (B,H,W,C); t: (B,); cond: class ids (B,) or
    pooled text if cfg.cond_tokens (then ``text`` is (B, Tt, cond_dim)).

    Returns (eps_pred, new_drift_state_or_None, stats).
    """
    b, hh, ww, _ = latents.shape
    stats: Dict[str, jax.Array] = {}

    ectx = None
    if drift is not None:
        e_ber = drift.ber_by_class
        if drift.embed_gate is not None:
            e_ber = e_ber * drift.embed_gate
        ectx = ExecContext(drift.cfg, key=jax.random.fold_in(drift.key, 1000),
                           step=drift.step, ber_by_class=e_ber,
                           state_in=drift.embed_store,
                           have_ckpt=drift.have_ckpt)

    x = patchify(cfg, latents.astype(cfg.dtype))
    x = _proj(ectx, x, params["patch_w"], "patch", dvfs.CLASS_EMBED)
    x = x + params["patch_b"].astype(x.dtype) + params["pos_embed"].astype(x.dtype)
    x = constrain(x, "act")

    temb = timestep_embedding(t).astype(cfg.dtype)
    temb = _proj(ectx, temb, params["t_w1"], "t.w1", dvfs.CLASS_EMBED)
    temb = jax.nn.silu(temb + params["t_b1"].astype(temb.dtype))
    temb = _proj(ectx, temb, params["t_w2"], "t.w2", dvfs.CLASS_EMBED)
    temb = temb + params["t_b2"].astype(temb.dtype)

    text_proj = None
    if cfg.cond_tokens:
        text_proj = _proj(ectx, text.astype(cfg.dtype), params["text_proj"],
                          "text", dvfs.CLASS_EMBED)
        c = temb + text_proj.mean(axis=1)
    else:
        c = temb + params["class_embed"].astype(cfg.dtype)[cond]

    def body(xc, p_i, extra):
        layer_idx, store_i = extra
        bctx = None
        if drift is not None:
            rcl = jnp.where(layer_idx < 1, dvfs.CLASS_FIRST_BLOCK,
                            dvfs.CLASS_BODY)
            b_ber = drift.ber_by_class
            if drift.layer_gate is not None:
                b_ber = b_ber * jnp.asarray(drift.layer_gate)[layer_idx]
            bctx = ExecContext(drift.cfg,
                               key=jax.random.fold_in(drift.key, layer_idx),
                               step=drift.step,
                               ber_by_class=b_ber,
                               state_in=store_i, have_ckpt=drift.have_ckpt)
            y = dit_block(cfg, p_i, xc, c, text_proj, ctx=bctx, rclass=rcl)
            return constrain(y, "act"), (bctx.state_out,
                                         bctx.stats["corrected_elems"],
                                         bctx.stats["detected_row_errors"])
        y = dit_block(cfg, p_i, xc, c, text_proj)
        return constrain(y, "act"), (None, jnp.int32(0), jnp.int32(0))

    n_layers = cfg.n_layers
    xs = (jnp.arange(n_layers, dtype=jnp.int32),
          drift.block_store if drift is not None else None)
    x, ys = common.scan_layers(body, x, params["blocks"], xs_extra=xs,
                               remat=cfg.remat and drift is None,
                               unroll=not cfg.scan_layers)
    new_block_store, corrected, detected = ys

    mod = _modulation(c, params["final_adaln_w"], params["final_adaln_b"],
                      x.dtype)
    shift, scale = jnp.split(mod, 2, axis=-1)
    x = _modulate(layernorm(x, None, None), shift, scale)
    x = _proj(ectx, x, params["final_w"], "final", dvfs.CLASS_EMBED)
    x = x + params["final_b"].astype(x.dtype)
    eps = unpatchify(cfg, x, hh, ww).astype(jnp.float32)

    new_drift = None
    if drift is not None:
        stats["corrected_elems"] = (jnp.sum(corrected)
                                    + ectx.stats["corrected_elems"])
        stats["detected_row_errors"] = (jnp.sum(detected)
                                        + ectx.stats["detected_row_errors"])
        # Per-site detection vector for the resilience heatmap (paper
        # Figs 5-6): row 0 = embedding/conditioning GEMMs, rows 1..L =
        # transformer blocks. Integer counts, so the scalar above stays
        # exactly sum(detected_per_block).
        stats["detected_per_block"] = jnp.concatenate(
            [ectx.stats["detected_row_errors"][None],
             jnp.asarray(detected, jnp.int32)])
        new_drift = dataclasses.replace(
            drift, embed_store=ectx.state_out, block_store=new_block_store)
    return eps, new_drift, stats


def drift_store_spec(cfg: ModelConfig, batch: int
                     ) -> Tuple[Dict[str, jax.Array], Dict[str, jax.Array]]:
    """(embed_store, block_store) zero-init checkpoint stores.

    Block-store leaves are stacked (L, ...) to ride the layer scan.
    """
    d, f, h, hd = cfg.d_model, cfg.d_ff, cfg.n_heads, cfg.hd
    t = (cfg.latent_size // cfg.patch_size) ** 2
    pdim = cfg.patch_size ** 2 * cfg.latent_channels
    bt = batch * t

    embed = {
        "patch": jnp.zeros((bt, d), jnp.float32),
        "t.w1": jnp.zeros((batch, d), jnp.float32),
        "t.w2": jnp.zeros((batch, d), jnp.float32),
        "final": jnp.zeros((bt, pdim), jnp.float32),
    }
    if cfg.cond_tokens:
        embed["text"] = jnp.zeros((batch * cfg.cond_tokens, d), jnp.float32)

    def zb(nout, rows=bt):
        return jnp.zeros((cfg.n_layers, rows, nout), jnp.float32)
    block = {
        "attn.q": zb(h * hd), "attn.k": zb(h * hd), "attn.v": zb(h * hd),
        "attn.o": zb(d), "mlp.w1": zb(f), "mlp.w2": zb(d),
    }
    if cfg.cond_tokens:
        block.update({
            "xattn.q": zb(h * hd),
            "xattn.k": zb(h * hd, batch * cfg.cond_tokens),
            "xattn.v": zb(h * hd, batch * cfg.cond_tokens),
            "xattn.o": zb(d),
        })
    return embed, block


def param_count(cfg: ModelConfig) -> int:
    d, f = cfg.d_model, cfg.d_ff
    per_block = 6 * d * d + 4 * d * d + 2 * d * f
    if cfg.cond_tokens:
        per_block += 4 * d * d
    t = (cfg.latent_size // cfg.patch_size) ** 2
    pdim = cfg.patch_size ** 2 * cfg.latent_channels
    base = (pdim * d + t * d + 256 * d + d * d + 2 * d * d + d * pdim)
    return cfg.n_layers * per_block + base
