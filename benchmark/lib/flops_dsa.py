"""FLOPs of the train step of a token model whose attention an indexer
selects: the benchmark's own copy.

Copied from ``pytorch_vit_paper_replication_tpu/telemetry/flops.py``
(``train_step_flops_per_sequence``, as it counts a model with
``sa_topk``) so that no later PR can move ``dsa_step_mfu_pct`` by editing
the program; ``tests/test_copies.py`` and ``tests/test_dsa_files.py``
hold the two equal. It reads the ``model`` block of a configuration
file. Convention as ``flops.py``: 2 x MACs over every matmul, backward =
2 x forward, recomputation not counted. What is counted is what the
algorithm needs on THIS chip:

* the core over the SELECTED query-key pairs only (``min(t + 1, topk)``
  a query): forward ``q k^T`` and ``p v``, 3 x forward in all; a program
  that visits unselected pairs and masks them (today's) is that much
  further from its peak, and one that skips them is read by the same
  count;
* the indexer: its three projections (3 x forward), its scores over
  EVERY causal pair forward (it has to score a key to leave it out), the
  scores' two backward products over the selected pairs only (the
  alignment loss lives on the selection), and the one more ``q k^T``
  over the selected pairs that the head-mean probabilities cost, forward
  only;
* q, k, v and out projections, the router over all experts, the expected
  token-expert pairs on the experts held (routing counted as uniform),
  the head over the vocabulary rows held: 3 x forward.

The embedding is a lookup, the norms and the rotary embedding
elementwise, the selection a comparison: none is counted.
"""

from __future__ import annotations


def causal_pairs(tokens: int) -> int:
    """Query-key pairs of causal attention over ``tokens`` positions."""
    return tokens * (tokens + 1) // 2


def selected_pairs(tokens: int, topk: int) -> int:
    """Pairs the selection keeps: ``min(t + 1, topk)`` a query."""
    k = min(topk, tokens)
    return k * (k + 1) // 2 + (tokens - k) * k


def forward_flops_per_sequence(model: dict, seq_len: int) -> float:
    t, d = seq_len, model["embedding_dim"]
    dh, hq, hkv = (model["head_dim_override"], model["num_heads"],
                   model["num_kv_heads"])
    heads, width = model["sa_index_heads"], model["sa_index_head_dim"]
    held = model.get("experts_held") or model["num_experts"]
    layer = 2 * t * d * (hq + 2 * hkv) * dh                 # q, k, v
    layer += 2 * 2 * selected_pairs(t, model["sa_topk"]) * hq * dh
    layer += 2 * t * d * (heads * width + width + heads)    # the indexer
    layer += 2 * causal_pairs(t) * heads * width            # its scores
    layer += 2 * t * hq * dh * d                            # out
    layer += 2 * t * d * model["num_experts"]               # router
    pairs = t * model["experts_per_token"] * held / model["num_experts"]
    layer += 3 * 2 * pairs * d * model["expert_width"]      # gate, up, down
    return model["num_layers"] * layer + 2 * t * d * model["vocab_size"]


def train_step_flops_per_sequence(model: dict, seq_len: int) -> float:
    """3 x forward, but in the indexer's scores (backward over the
    selected pairs, not over every causal pair) and with the head-mean
    probabilities' ``q k^T`` over the selected pairs once."""
    selected = selected_pairs(seq_len, model["sa_topk"])
    score = 2 * model["sa_index_heads"] * model["sa_index_head_dim"]
    return 3.0 * forward_flops_per_sequence(model, seq_len) \
        + model["num_layers"] * (
            2 * score * (selected - causal_pairs(seq_len))
            + 2 * model["num_heads"] * model["head_dim_override"] * selected)
