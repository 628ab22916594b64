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


def visible_pairs(tokens: int, window: int = 0) -> int:
    """Query-key pairs of causal attention over ``tokens`` positions
    (key j <= query i), with ``window`` > 0 also ``i - j < window``."""
    if not window or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def conv_mixer_flops(cfg, tokens: int) -> float:
    """Forward FLOPs of a gated short convolution over ``tokens``
    positions: its in (``D -> 3D``) and out (``D -> D``) projections and
    the convolution's taps (a product a tap, channel and position); the
    two gates are elementwise and not counted."""
    d = cfg.embedding_dim
    return 2 * tokens * d * 4 * d + 2 * tokens * cfg.conv_kernel * d


def _attention_flops(cfg, layer: int, t: int) -> float:
    """Forward FLOPs of block ``layer``'s attention over ``t``
    positions (:func:`forward_flops_per_sequence`)."""
    d, dh, hq, hkv = cfg.embedding_dim, cfg.head_dim, cfg.num_heads, \
        cfg.kv_heads
    _, window = cfg.attention_kind(layer)
    total = 0.0
    if cfg.kv_lora_rank:
        # queries and keys/values down to their latents and up again
        total += 2 * t * (d * cfg.q_lora_rank + cfg.q_lora_rank * hq * dh)
        total += 2 * t * (
            d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
            + cfg.kv_lora_rank * hq * (cfg.qk_nope_head_dim + cfg.v_head_dim))
    else:
        total += 2 * t * d * (hq + 2 * hkv) * dh        # q, k, v
    # QK^T, PV (a selection of ``topk`` keys counts as a window does)
    total += 2 * 2 * visible_pairs(t, window) * hq * dh
    if cfg.sa_topk:
        # the indexer: its three projections, its scores of every causal
        # pair
        heads, width = cfg.sa_index_heads, cfg.sa_index_head_dim
        total += 2 * t * d * (heads * width + width + heads)
        total += 2 * visible_pairs(t) * heads * width
    return total + 2 * t * hq * dh * d                  # out projection


def forward_flops_per_sequence(cfg, seq_len: Optional[int] = None) -> float:
    """Analytic forward FLOPs of one sequence of a token model
    (:class:`..configs.ViTConfig` with ``vocab_size`` > 0): visible
    query-key pairs only (causal, and the window where the layer has
    one); by each layer's kind the dense feed-forward, or the router
    over all experts, the expected pairs on the experts HELD
    (``experts_per_token x held / num_experts`` a token: routing is
    counted as uniform) and the shared experts over every token; the
    head over the vocabulary rows held. Attention that an indexer selects
    counts the selected pairs for the core, and the indexer's projections
    and its scores of every causal pair. A multi-token-prediction module
    is its merge (``2D -> D``), one more block of the last kind and the
    head a second time. A conv layer counts its mixer
    (:func:`conv_mixer_flops`) in the attention's place. The embedding is
    a lookup and the rotary embedding elementwise: neither is counted."""
    t = seq_len or cfg.max_seq_len
    d = cfg.embedding_dim
    gated = lambda tokens, width: 3 * 2 * tokens * d * width
    total = 0.0
    for layer in range(cfg.num_layers + cfg.mtp_modules):
        if cfg.layer_mixer(layer) == "conv":
            total += conv_mixer_flops(cfg, t)
        else:
            total += _attention_flops(cfg, layer, t)
        if cfg.layer_routed(layer):
            total += 2 * t * d * cfg.num_experts        # router
            pairs = t * cfg.experts_per_token * cfg.num_experts_held \
                / cfg.num_experts
            total += gated(pairs, cfg.expert_width)     # gate, up, down
            total += gated(t, cfg.shared_experts * cfg.expert_width)
        elif cfg.num_experts:
            total += gated(t, cfg.dense_width)
        else:
            total += 2 * 2 * t * d * cfg.mlp_size
    total += cfg.mtp_modules * 2 * t * 2 * d * d        # [emb ; hidden]
    return total + (1 + cfg.mtp_modules) * 2 * t * d * cfg.vocab_size


def train_step_flops_per_sequence(cfg, seq_len: Optional[int] = None
                                  ) -> float:
    """3 x forward (module docstring's convention): the token model's
    ``flops_per_image``, a sequence being its "image". A model whose
    attention an indexer selects departs from it in the indexer alone,
    whose loss is local to its layer: its scores' two backward products
    run over the selected pairs, not over every causal pair, and the
    head-mean probabilities it is aligned to cost one more ``q k^T``
    over the selected pairs, forward only."""
    total = 3.0 * forward_flops_per_sequence(cfg, seq_len)
    if cfg.sa_topk:
        t = seq_len or cfg.max_seq_len
        score = 2 * cfg.sa_index_heads * cfg.sa_index_head_dim
        selected = visible_pairs(t, cfg.sa_topk)
        total += cfg.num_layers * (
            2 * score * (selected - visible_pairs(t))
            + 2 * cfg.num_heads * cfg.head_dim * selected)
    return total


def analytic_mfu(images_per_sec_per_chip: float, flops_per_image: float,
                 peak_tflops: float) -> float:
    """Model-FLOPs utilization from a per-chip image rate and that
    chip's peak (:func:`peak_bf16_tflops`)."""
    return images_per_sec_per_chip * flops_per_image / 1e12 / peak_tflops
