"""What the benchmark knows about a gated short convolution's mix, from
shapes alone: the least work of the gate-conv-gate between a conv
layer's two projections (scope ``conv/mix``: from ``[B | C | u]``, the
in projection's output ``[N, 3D]``, to ``C * conv(B * u)`` ``[N, D]``,
whatever computes it; N the tokens of a step). It reads the same work
whatever implements the mix, so a kernel that takes XLA's place is
judged on it.

* bytes, in the compute dtype (bf16): forward, ``[N, 3D]`` read and
  ``[N, D]`` written; backward, ``[N, 3D]`` and the result's cotangent
  ``[N, D]`` read and the projection's cotangent ``[N, 3D]`` written,
  and the taps' gradient ``[K, D]`` (float32). Nothing in between
  (``v``, the convolution, the gates' products) need reach HBM: one pass
  each way holds them in VMEM, the K - 1 rows a block needs from the
  block before it included.
* FLOPs: the convolution's multiply-adds, ``2 * K`` an element of
  ``[N, D]`` forward and twice that backward (the input's gradient and
  the taps'), as ``flops_conv.py`` counts them; the gates' products are
  elementwise and not counted. They are far under the bytes' time: the
  mix is bound by memory.

The attention core's and the grouped products' least work are
``kernels_lm.py``'s, counted over the layers that have them: the
attention layers and the routed blocks.
"""

from __future__ import annotations

from . import kernels_lm


def _conv_layers(model: dict) -> int:
    lay = model.get("mixer_layout") or ()
    return sum(1 for layer in range(model["num_layers"])
               if lay and lay[layer % len(lay)])


def conv_mix_cost(model: dict, seq_len: int, sequences: int, *,
                  act_bytes: int = 2, tap_bytes: int = 4) -> dict:
    """FLOPs and HBM bytes of the mixes of one train step on one chip
    (every conv layer, forward and backward)."""
    n, d, k = sequences * seq_len, model["embedding_dim"], \
        model["conv_kernel"]
    layers = _conv_layers(model)
    forward = (3 * n * d + n * d) * act_bytes
    backward = (3 * n * d + n * d + 3 * n * d) * act_bytes + k * d * tap_bytes
    flops = 3 * 2.0 * k * n * d
    return {"flops": layers * flops,
            "bytes": float(layers * (forward + backward))}


def _attention_layers(model: dict) -> int:
    return model["num_layers"] - _conv_layers(model)


def _routed_layers(model: dict) -> int:
    return model["num_layers"] - model.get("dense_layers", 0)


def attention_core_cost(model: dict, seq_len: int, sequences: int) -> dict:
    """``kernels_lm.attention_core_cost`` over the attention layers alone
    (that function counts every layer as attention), the head size the
    width over the heads where the model states none."""
    dh = model.get("head_dim_override") or \
        model["embedding_dim"] // model["num_heads"]
    return kernels_lm.attention_core_cost(
        {**model, "num_layers": _attention_layers(model),
         "sliding_window_layout": (), "head_dim_override": dh},
        seq_len, sequences)


def moe_gmm_cost(model: dict, pairs_per_layer: float) -> dict:
    """``kernels_lm.moe_gmm_cost`` over the routed blocks alone (that
    function counts every layer as routed; the leading dense layers are
    not)."""
    return kernels_lm.moe_gmm_cost(
        {**model, "num_layers": _routed_layers(model)}, pairs_per_layer)
