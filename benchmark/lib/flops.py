"""Model FLOPs and chip peaks: the benchmark's own copy.

Copied from ``pytorch_vit_paper_replication_tpu/telemetry/flops.py``
(``train_step_flops_per_image``, ``CHIP_PEAKS``) so that no later PR can
move an MFU by editing the program; ``tests/test_copies.py`` holds the
two equal for B/16 and L/16. Convention: FLOPs = 2 x MACs over every
matmul, backward = 2 x forward, so a train step is 3 x forward;
recomputed operations (remat, the kernel's fc1 recompute) do not count.
"""

from __future__ import annotations

# Published peaks of one chip, keyed by jax's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect).
CHIP_PEAKS = {
    "TPU v5 lite": {"bf16_tflops": 197.0, "int8_tops": 393.0,
                    "hbm_gb_per_s": 819.0, "hbm_gb": 16.0,
                    "ici_gbit_per_s": 1600.0},
}


def peaks(device_kind: str) -> dict:
    """The chip's peaks. A kind that is not in the table is an error:
    no utilisation is ever computed against another chip's number."""
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device_kind!r} is not in the benchmark's table "
            f"of peaks ({sorted(CHIP_PEAKS)}); add it with its source "
            "before reporting a utilisation") from None


def seq_len(cfg: dict) -> int:
    n = (cfg["image_size"] // cfg["patch_size"]) ** 2
    return n + (1 if cfg.get("pool", "cls") == "cls" else 0)


def forward_flops_per_image(cfg: dict) -> float:
    """Analytic forward FLOPs of one image for a ViT configuration file
    (``image_size``, ``patch_size``, ``color_channels``, ``num_layers``,
    ``embedding_dim``, ``mlp_size``, ``num_classes``, ``pool``)."""
    t, d, m = seq_len(cfg), cfg["embedding_dim"], cfg["mlp_size"]
    p, c = cfg["patch_size"], cfg.get("color_channels", 3)
    patchify = 2 * (cfg["image_size"] // p) ** 2 * (p * p * c) * d
    per_layer = (
        2 * t * d * 3 * d          # qkv projection
        + 2 * t * t * d            # QK^T
        + 2 * t * t * d            # attn . V
        + 2 * t * d * d            # out projection
        + 2 * t * d * m            # fc1
        + 2 * t * m * d            # fc2
    )
    head = 2 * d * cfg["num_classes"]
    return float(patchify + cfg["num_layers"] * per_layer + head)


def train_step_flops_per_image(cfg: dict) -> float:
    return 3.0 * forward_flops_per_image(cfg)
