"""FLOPs of the train step of a token model whose layers mix Mamba-2
state-space layers with attention: the benchmark's own copy.

Copied from ``pytorch_vit_paper_replication_tpu/telemetry/flops.py``
(``train_step_flops_per_sequence``, as it counts a model with state-space
layers and a dense feed-forward in every layer) so that no later PR can
move ``ssm_step_mfu_pct`` by editing the program; ``tests/test_copies.py``
holds the two equal. It reads the ``model`` block of a configuration
file. Convention as ``flops.py``: 2 x MACs over every matmul, backward =
2 x forward, recomputation not counted. Counted, a layer at a time:

* a state-space layer's mixer: its in (``D -> 2 H P + 2 G N + H``) and
  out (``H P -> D``) projections, the convolution's taps over ``H P + 2
  G N`` channels, and the chunked scan's products at ``ssm_chunk``
  (:func:`scan_flops`);
* an attention layer: q, k, v and out projections, ``q k^T`` and ``p v``
  over the causal query-key pairs only;
* the gated feed-forward of ``dense_width`` in every layer;
* the head over the vocabulary rows held (tied to the embedding).

The embedding is a lookup, the norms, gates and decays elementwise:
none is counted.
"""

from __future__ import annotations


def causal_pairs(tokens: int) -> int:
    """Query-key pairs of causal attention over ``tokens`` positions."""
    return tokens * (tokens + 1) // 2


def chunk_pairs(tokens: int, chunk: int) -> int:
    """Causal position pairs inside the chunks of ``chunk`` positions."""
    whole, rest = divmod(tokens, chunk)
    return whole * chunk * (chunk + 1) // 2 + rest * (rest + 1) // 2


def _is_ssm(model: dict, layer: int) -> bool:
    lay = model.get("mixer_layout") or ()
    return bool(lay) and lay[layer % len(lay)] == 2


def scan_flops(model: dict, seq_len: int) -> float:
    """Forward FLOPs of one state-space layer's scan over one sequence:
    ``C B^T`` a group and the blocks' product with ``dt x`` a head over
    the causal pairs inside each chunk, each position's part of its
    chunk's state and its read of the state entering the chunk (``P x
    N`` a head each)."""
    h, p = model["ssm_heads"], model["ssm_head_dim"]
    g, n = model.get("ssm_groups", 1), model["ssm_state"]
    pairs = chunk_pairs(seq_len, model["ssm_chunk"])
    return 2 * pairs * (g * n + h * p) + 2 * 2 * seq_len * h * p * n


def mixer_flops(model: dict, seq_len: int, layer: int) -> float:
    """Forward FLOPs of block ``layer``'s mixer over one sequence."""
    t, d = seq_len, model["embedding_dim"]
    if _is_ssm(model, layer):
        h, p = model["ssm_heads"], model["ssm_head_dim"]
        width = h * p + 2 * model.get("ssm_groups", 1) * model["ssm_state"]
        return (2 * t * d * (h * p + width + h) + 2 * t * h * p * d
                + 2 * t * model["ssm_conv_kernel"] * width
                + scan_flops(model, t))
    hq = model["num_heads"]
    hkv = model.get("num_kv_heads") or hq
    dh = model.get("head_dim_override") or d // hq
    return (2 * t * d * (hq + 2 * hkv) * dh             # q, k, v
            + 2 * 2 * causal_pairs(t) * hq * dh         # q k^T, p v
            + 2 * t * hq * dh * d)                      # out


def forward_flops_per_sequence(model: dict, seq_len: int) -> float:
    t, d = seq_len, model["embedding_dim"]
    total = 0.0
    for layer in range(model["num_layers"]):
        total += mixer_flops(model, t, layer)
        total += 3 * 2 * t * d * model["dense_width"]   # gate, up, down
    return total + 2 * t * d * model["vocab_size"]


def train_step_flops_per_sequence(model: dict, seq_len: int) -> float:
    """3 x forward."""
    return 3.0 * forward_flops_per_sequence(model, seq_len)
