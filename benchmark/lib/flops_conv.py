"""FLOPs of the train step of a token model whose layers mix gated short
convolutions with attention: the benchmark's own copy.

Copied from ``pytorch_vit_paper_replication_tpu/telemetry/flops.py``
(``train_step_flops_per_sequence``, as it counts a model with a
``mixer_layout``) so that no later PR can move ``conv_step_mfu_pct`` by
editing the program; ``tests/test_copies.py`` and
``benchmark/tests/test_conv_files.py`` hold the two equal. It reads the
``model`` block of a configuration file. Convention as ``flops.py``: 2 x
MACs over every matmul, backward = 2 x forward, recomputation not
counted. Counted, a layer at a time:

* a conv layer's mixer: its in (``D -> 3D``) and out (``D -> D``)
  projections and the convolution's taps (a product a tap, channel and
  position); the two gates are elementwise and not counted;
* an attention layer: q, k, v and out projections, ``q k^T`` and ``p v``
  over the causal query-key pairs only;
* the feed-forward: the dense layer's three products, or the router over
  all experts and the expected token-expert pairs on the experts held
  (routing counted as uniform);
* the head over the vocabulary rows held (tied to the embedding or not:
  the same products).

The embedding is a lookup, the norms and the rotary embedding
elementwise: none is counted.
"""

from __future__ import annotations


def causal_pairs(tokens: int) -> int:
    """Query-key pairs of causal attention over ``tokens`` positions."""
    return tokens * (tokens + 1) // 2


def _is_conv(model: dict, layer: int) -> bool:
    lay = model.get("mixer_layout") or ()
    return bool(lay and lay[layer % len(lay)])


def mixer_flops(model: dict, seq_len: int, layer: int) -> float:
    """Forward FLOPs of block ``layer``'s mixer over one sequence."""
    t, d = seq_len, model["embedding_dim"]
    if _is_conv(model, layer):
        return 2 * t * d * 4 * d + 2 * t * model["conv_kernel"] * d
    hq = model["num_heads"]
    hkv = model.get("num_kv_heads") or hq
    dh = model.get("head_dim_override") or d // hq
    return (2 * t * d * (hq + 2 * hkv) * dh             # q, k, v
            + 2 * 2 * causal_pairs(t) * hq * dh         # q k^T, p v
            + 2 * t * hq * dh * d)                      # out


def forward_flops_per_sequence(model: dict, seq_len: int) -> float:
    t, d = seq_len, model["embedding_dim"]
    held = model.get("experts_held") or model["num_experts"]
    gated = lambda tokens, width: 3 * 2 * tokens * d * width
    total = 0.0
    for layer in range(model["num_layers"]):
        total += mixer_flops(model, t, layer)
        if layer < model.get("dense_layers", 0):
            total += gated(t, model["dense_width"])
        else:
            total += 2 * t * d * model["num_experts"]   # router
            total += gated(t * model["experts_per_token"] * held
                           / model["num_experts"], model["expert_width"])
    return total + 2 * t * d * model["vocab_size"]


def train_step_flops_per_sequence(model: dict, seq_len: int) -> float:
    """3 x forward."""
    return 3.0 * forward_flops_per_sequence(model, seq_len)
