"""Analytic ViT training-step FLOP math — ONE copy.

This was born in ``bench.py`` (the MFU self-audit on the headline
number); the live-telemetry MFU gauge (:mod:`.spans`) needs the same
arithmetic, and two copies of a FLOP count drift. ``bench.py`` now
delegates here, so the bench's published ``flops_per_image``/``mfu``
and the run-log ``tel_mfu`` gauge can never disagree about the model's
cost model.

Convention (unchanged from the bench): FLOPs = 2 x MACs over every
matmul, backward ~ 2x forward (dL/dW and dL/dx each cost one
forward-sized matmul per layer) -> x3 total; remat recompute is NOT
counted — this is model FLOPs (the MFU numerator convention), not
hardware FLOPs.
"""

from __future__ import annotations

from typing import Optional

# Published per-chip peaks, keyed by jax's ``device_kind`` — the ONE
# table every utilization in this repo divides by. Source: Google Cloud
# documentation, "TPU v5e". A kind that is not here has no peak: its
# MFU is not reported, never computed against another chip's number.
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gb_per_s": 819.0, "hbm_gb": 16.0},
}


def peak_bf16_tflops(device_kind: str) -> Optional[float]:
    """bf16 dense peak of one chip of this kind; None when unknown."""
    return CHIP_PEAKS.get(device_kind, {}).get("bf16_tflops")


def train_step_flops_per_image(cfg) -> float:
    """Analytic FLOPs of one training step, per image, for a ViT config
    (anything with ``seq_len``/``embedding_dim``/``mlp_size``/
    ``num_layers``/``patch_size``/``color_channels``/``num_patches``/
    ``num_classes`` — :class:`..configs.ViTConfig`)."""
    t, d, m, l = cfg.seq_len, cfg.embedding_dim, cfg.mlp_size, cfg.num_layers
    p, c = cfg.patch_size, cfg.color_channels
    patchify = 2 * cfg.num_patches * (p * p * c) * d
    per_layer = (
        2 * t * d * 3 * d          # qkv projection
        + 2 * t * t * d            # QK^T
        + 2 * t * t * d            # attn · V
        + 2 * t * d * d            # out projection
        + 2 * t * d * m            # fc1
        + 2 * t * m * d            # fc2
    )
    head = 2 * d * cfg.num_classes
    forward = patchify + l * per_layer + head
    return 3.0 * forward


def analytic_mfu(images_per_sec_per_chip: float, flops_per_image: float,
                 peak_tflops: float) -> float:
    """Model-FLOPs utilization from a per-chip image rate and that
    chip's peak (:func:`peak_bf16_tflops`)."""
    return images_per_sec_per_chip * flops_per_image / 1e12 / peak_tflops
