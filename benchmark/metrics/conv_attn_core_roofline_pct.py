"""Least time the chip could take for the attention core of a model
whose layers mix gated short convolutions with attention
(``lib/kernels_conv.py::attention_core_cost``: ``kernels_lm``'s six GEMMs
over the causal pairs, on the attention layers alone) /
``attn_core_ms``: the same work whatever implements it. The accepted
``lm_attn_core_roofline_pct`` counts every layer as attention."""
from benchmark.lib import kernels, kernels_conv
from benchmark.metrics import attn_core_ms

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "attention core", "train_img_s"


def read(obs):
    ms, conv = attn_core_ms.read(obs), obs.get("conv")
    if not ms or not conv or not obs.get("peak"):
        return None
    cost = kernels_conv.attention_core_cost(
        obs["model"], conv["seq_len"], obs["train"]["batch_per_chip"])
    least = kernels.roofline_seconds(cost, obs["peak"])
    print(f"[conv_attn_core_roofline_pct] bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step) over {ms:.3f} ms",
          flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
