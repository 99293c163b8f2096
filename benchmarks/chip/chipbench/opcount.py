"""Operations of one denoiser evaluation, as the configuration's reference
module counts them (``per_eval(cfg) -> {"int8_macs", "float_macs"}``), and
the least time they could take on the chip.

Only the model's own work counts: one forward per denoising step per served
image. Fault injection, checksums, checkpoint writes, rollback and the clean
twin are protection work and are left out, so removing them raises the
share of the peak. ``int8_macs`` are the projection GEMMs the served model
quantizes, ``float_macs`` the GEMMs it runs in floating point (attention,
modulation, and whatever else the architecture keeps unquantized).
"""
from __future__ import annotations

from typing import Dict

from chipbench import spec


def per_eval(cfg: dict) -> Dict[str, float]:
    """MACs of one denoiser evaluation of one image."""
    return spec.reference_module(cfg).per_eval(cfg)


def roofline_s_per_eval(cfg: dict, peaks: dict) -> float:
    """Least time one evaluation of one image could take on the chip: its
    int8 operations at the int8 peak plus its float operations at the bf16
    peak (two operations per MAC)."""
    ops = per_eval(cfg)
    return (2 * ops["int8_macs"] / peaks["int8_ops_per_s"]
            + 2 * ops["float_macs"] / peaks["bf16_flops_per_s"])
