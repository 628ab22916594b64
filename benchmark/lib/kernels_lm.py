"""What the benchmark knows about a token model's kernels, from shapes
alone: the least work of the causal / causal-window grouped-query
attention core and of the routed experts' grouped matrix products.

**The attention core** (scope ``attn_core``: from q ``[T, H, Dh]`` and k,
v ``[T, Hkv, Dh]`` to o, whatever computes it), per layer and sequence:

* FLOPs: 6 GEMMs over the VISIBLE query-key pairs only (forward q k^T
  and p v; backward dv, dp, dq, dk), ``2 * pairs * H * Dh`` each. A
  causal layer has ``T (T + 1) / 2`` pairs, a window layer
  ``flops_lm.visible_pairs(T, w)``. Rebuilding the logits in the backward
  pass is recomputation and is not counted.
* bytes: forward q, k, v read and o written; backward q, k, v, o, do
  read and dq, dk, dv written: six tensors at H heads (q, o, and q, o,
  do, dq) and six at Hkv heads (k, v twice, dk, dv), in the compute
  dtype. The ``[T, T]`` logits are not counted: the algorithm does not
  need them in HBM. Key/value heads are counted ONCE, not once per
  query head: a program that repeats them is that much further from
  its roofline.

**The grouped products** (kernels ``moe_gmm_fwd`` / ``moe_gmm_dx`` /
``moe_gmm_dw``), per layer, over the token-expert ``pairs`` that fall to
the held experts (the program's counter, not an expectation):

* FLOPs: ``2 * pairs * D * F`` for each of gate, up and down forward,
  and twice that backward (input and weight gradients). The first
  product taken again in the backward pass is recomputation.
* bytes: the held experts' three weight matrices read once forward and
  once backward in the compute dtype and their gradients written once in
  float32; the pairs' activations once each way (rows in and rows out of
  width D, forward; rows and their cotangents in, gradients out,
  backward).
"""

from __future__ import annotations

from . import flops_lm


def attention_core_cost(model: dict, seq_len: int, sequences: int, *,
                        act_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes of the attention core of one train step on
    one chip (every layer, forward and backward)."""
    hq, hkv, dh = model["num_heads"], model["num_kv_heads"], \
        model["head_dim_override"]
    flops = bytes_ = 0.0
    for layer in range(model["num_layers"]):
        pairs = flops_lm.visible_pairs(
            seq_len, flops_lm.layer_window(model, layer))
        flops += 6 * 2.0 * pairs * hq * dh
        bytes_ += 6 * seq_len * (hq + hkv) * dh * act_bytes
    return {"flops": sequences * flops, "bytes": float(sequences * bytes_)}


def moe_gmm_cost(model: dict, pairs_per_layer: float, *,
                 act_bytes: int = 2, w_bytes: int = 2,
                 grad_bytes: int = 4) -> dict:
    """FLOPs and HBM bytes of the grouped products of one train step on
    one chip, ``pairs_per_layer`` token-expert pairs on the held experts
    of each layer."""
    d, f = model["embedding_dim"], model["expert_width"]
    held = model.get("experts_held") or model["num_experts"]
    layers = model["num_layers"]
    weights = held * 3 * d * f
    flops = 3 * 3 * 2.0 * pairs_per_layer * d * f
    bytes_ = (weights * (2 * w_bytes + grad_bytes)
              + pairs_per_layer * d * act_bytes * (2 + 3))
    return {"flops": layers * flops, "bytes": float(layers * bytes_)}
