"""FLOPs of a latent-attention token model's train step: the benchmark's
own copy.

Copied from ``pytorch_vit_paper_replication_tpu/telemetry/flops.py``
(``train_step_flops_per_sequence``, as it counts a model with latent
attention, layer kinds, a shared expert and a multi-token-prediction
module) so that no later PR can move ``mla_step_mfu_pct`` by editing the
program; ``tests/test_mla_files.py`` holds the two equal. It reads the
``model`` block of a configuration file. Convention as ``flops.py``: 2 x
MACs over every matmul, backward = 2 x forward, recomputation not
counted (the latent attention's projections taken again in the backward
pass show as lower utilisation). What is counted is what the algorithm
needs on THIS chip: visible query-key pairs only; the latent projections
down and up; by each layer's kind the dense feed-forward, or the router
over all experts, the expected token-expert pairs on the experts held
(routing counted as uniform) and the shared expert over every token; the
module's merge, its block and the head a second time; the head over the
vocabulary rows held. The embedding is a lookup and the rotary embedding
elementwise: neither is counted.
"""

from __future__ import annotations


def visible_pairs(tokens: int) -> int:
    """Query-key pairs of causal attention over ``tokens`` positions."""
    return tokens * (tokens + 1) // 2


def blocks(model: dict) -> int:
    """Blocks that run: the layers and the module's."""
    return model["num_layers"] + model.get("mtp_modules", 0)


def forward_flops_per_sequence(model: dict, seq_len: int) -> float:
    t, d = seq_len, model["embedding_dim"]
    dh = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    hq = model["num_heads"]
    held = model.get("experts_held") or model["num_experts"]
    gated = lambda tokens, width: 3 * 2 * tokens * d * width
    total = 0.0
    for layer in range(blocks(model)):
        total += 2 * t * (d * model["q_lora_rank"]
                          + model["q_lora_rank"] * hq * dh)
        total += 2 * t * (
            d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * hq
            * (model["qk_nope_head_dim"] + model["v_head_dim"]))
        total += 2 * 2 * visible_pairs(t) * hq * dh          # QK^T, PV
        total += 2 * t * hq * dh * d                         # out
        if layer >= model.get("dense_layers", 0):
            total += 2 * t * d * model["num_experts"]        # router
            pairs = t * model["experts_per_token"] * held \
                / model["num_experts"]
            total += gated(pairs, model["expert_width"])
            total += gated(t, model.get("shared_experts", 0)
                           * model["expert_width"])
        else:
            total += gated(t, model["dense_width"])
    total += model.get("mtp_modules", 0) * 2 * t * 2 * d * d
    return total + (1 + model.get("mtp_modules", 0)) \
        * 2 * t * d * model["vocab_size"]


def train_step_flops_per_sequence(model: dict, seq_len: int) -> float:
    return 3.0 * forward_flops_per_sequence(model, seq_len)
