"""What the benchmark knows about sparse attention chosen by an indexer,
from shapes alone: the least work of the core over the selected pairs
and of the indexer's scores. Both read the same work whatever implements
it: neither can pass 100% by skipping unselected blocks, because neither
counts one.

**The sparse core** (scope ``attn_core``: from q ``[T, H, Dh]``, k, v
``[T, Hkv, Dh]`` and the selection to o), per layer and sequence:

* FLOPs: 6 GEMMs over the SELECTED query-key pairs (forward q k^T and
  p v; backward dv, dp, dq, dk), ``2 * pairs * H * Dh`` each, ``pairs =
  sum over queries of min(t + 1, topk)``. A program that computes every
  causal pair and masks (today's) does 4.3 times that at T = 16,384 and
  reads accordingly.
* bytes: forward q, k, v read and o written; backward q, k, v, o, do
  read and dq, dk, dv written, in the compute dtype; key/value heads
  counted once, not once a query head. The selection itself is not
  counted (how it is handed over is the implementation's: a byte a pair
  today), nor the ``[T, T]`` logits.

**The indexer's scores** (scope ``indexer/scores``), per layer and
sequence, forward (the alignment loss's products are the row
``indexer_loss``'s):

* FLOPs: ``2 * J * Di`` a CAUSAL pair: every key a query may select has
  to be scored.
* bytes: the indexer's q ``[T, J, Di]`` and k ``[T, Di]`` in the compute
  dtype and its head weights ``[T, J]`` in float32, read once. The scores
  need not reach HBM: a selection fused with them keeps a threshold a
  row. A program that writes them (today's writes ``J`` of them a pair)
  is that much further from this bound.
"""

from __future__ import annotations

from . import flops_dsa


def sparse_core_cost(model: dict, seq_len: int, sequences: int, *,
                     act_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes of the sparse core of one train step on one
    chip (every layer, forward and backward)."""
    hq, hkv, dh = (model["num_heads"], model["num_kv_heads"],
                   model["head_dim_override"])
    layers = model["num_layers"]
    pairs = flops_dsa.selected_pairs(seq_len, model["sa_topk"])
    flops = layers * 6 * 2.0 * pairs * hq * dh
    bytes_ = layers * 6 * seq_len * (hq + hkv) * dh * act_bytes
    return {"flops": sequences * flops, "bytes": float(sequences * bytes_)}


def indexer_cost(model: dict, seq_len: int, sequences: int, *,
                 act_bytes: int = 2, weight_bytes: int = 4) -> dict:
    """FLOPs and HBM bytes of the indexer's scores of one train step on
    one chip (every layer, forward: the step scores once)."""
    heads, width = model["sa_index_heads"], model["sa_index_head_dim"]
    layers = model["num_layers"]
    flops = layers * 2.0 * flops_dsa.causal_pairs(seq_len) * heads * width
    bytes_ = layers * seq_len * ((heads * width + width) * act_bytes
                                 + heads * weight_bytes)
    return {"flops": sequences * flops, "bytes": float(sequences * bytes_)}
