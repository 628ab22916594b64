"""FLOPs of a token model's train step: the benchmark's own copy.

Copied from ``pytorch_vit_paper_replication_tpu/telemetry/flops.py``
(``train_step_flops_per_sequence``) so that no later PR can move
``lm_step_mfu_pct`` by editing the program; ``tests/test_lm_files.py``
holds the two equal. It reads the ``model`` block of a configuration
file. Convention as ``flops.py``: 2 x MACs over every matmul, backward =
2 x forward, recomputation not counted. What is counted is what the
algorithm needs on THIS chip: visible query-key pairs only (causal, and
the window where the layer has one), the expected token-expert pairs on
the experts held (routing counted as uniform), the router over all
experts, the head over the vocabulary rows held. The embedding is a
lookup and the rotary embedding elementwise: neither is counted.
"""

from __future__ import annotations


def visible_pairs(tokens: int, window: int = 0) -> int:
    """Query-key pairs of causal attention over ``tokens`` positions (key
    j <= query i), with ``window`` > 0 also ``i - j < window``."""
    if not window or window >= tokens:
        return tokens * (tokens + 1) // 2
    return window * (window + 1) // 2 + (tokens - window) * window


def layer_window(model: dict, layer: int) -> int:
    lay = model.get("sliding_window_layout") or ()
    return model["sliding_window"] if lay and lay[layer % len(lay)] else 0


def forward_flops_per_sequence(model: dict, seq_len: int) -> float:
    t, d = seq_len, model["embedding_dim"]
    dh, hq, hkv = model["head_dim_override"], model["num_heads"], \
        model["num_kv_heads"]
    held = model.get("experts_held") or model["num_experts"]
    total = 0.0
    for layer in range(model["num_layers"]):
        total += 2 * t * d * (hq + 2 * hkv) * dh             # q, k, v
        total += 2 * 2 * visible_pairs(t, layer_window(model, layer)) \
            * hq * dh                                        # QK^T, PV
        total += 2 * t * hq * dh * d                         # out
        total += 2 * t * d * model["num_experts"]            # router
        pairs = t * model["experts_per_token"] * held / model["num_experts"]
        total += 3 * 2 * pairs * d * model["expert_width"]   # gate, up, down
    return total + 2 * t * d * model["vocab_size"]           # head


def train_step_flops_per_sequence(model: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_sequence(model, seq_len)
