"""Operations of one denoiser evaluation, counted from the configuration's
shapes.

Only the model's own work counts: one forward per denoising step per served
image. Fault injection, checksums, checkpoint writes, rollback and the clean
twin are protection work and are left out, so removing them raises the
share of the peak. Counted per image:

* ``int8_macs``: the projection GEMMs the served model quantizes (patch,
  timestep MLP, per block q/k/v/o and the MLP, the final projection);
* ``float_macs``: the GEMMs it runs in floating point: attention scores
  and mixing, and the adaLN modulation dots.

Follows ``perfmodel.flops.gemm_macs_per_model_eval``, whose DiT count this
matches.
"""
from __future__ import annotations

from typing import Dict


def per_eval(cfg: dict) -> Dict[str, float]:
    """MACs of one denoiser evaluation of one image."""
    d, f, n_layers = cfg["d_model"], cfg["d_ff"], cfg["n_layers"]
    t = (cfg["latent_size"] // cfg["patch_size"]) ** 2
    pdim = cfg["patch_size"] ** 2 * cfg["latent_channels"]

    block_int8 = t * (4 * d * d + 2 * d * f)
    block_float = 2 * t * t * d + 6 * d * d          # attention + modulation
    embed_int8 = t * pdim * d + 256 * d + d * d + t * d * pdim
    return {"int8_macs": float(n_layers * block_int8 + embed_int8),
            "float_macs": float(n_layers * block_float + 2 * d * d)}


def roofline_s_per_eval(cfg: dict, peaks: dict) -> float:
    """Least time one evaluation of one image could take on the chip: its
    int8 operations at the int8 peak plus its float operations at the bf16
    peak (two operations per MAC)."""
    ops = per_eval(cfg)
    return (2 * ops["int8_macs"] / peaks["int8_ops_per_s"]
            + 2 * ops["float_macs"] / peaks["bf16_flops_per_s"])
