"""Least time the chip could take for the sparse attention core in
training (``lib/kernels_dsa.py::sparse_core_cost``: six GEMMs over the
SELECTED pairs, q, k, v, o and their cotangents once, neither the
selection nor ``[T, T]`` counted) / ``attn_core_ms``: the same work
whatever implements it, so a core that computes every causal pair and
masks reads low, and one that skips unselected blocks cannot pass 100%
by it."""
from benchmark.lib import kernels, kernels_dsa
from benchmark.metrics import attn_core_ms

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "attention core", "train_img_s"


def read(obs):
    ms, dsa = attn_core_ms.read(obs), obs.get("dsa")
    if not ms or not dsa or not obs.get("peak"):
        return None
    cost = kernels_dsa.sparse_core_cost(
        obs["model"], dsa["seq_len"], obs["train"]["batch_per_chip"])
    least = kernels.roofline_seconds(cost, obs["peak"])
    print(f"[dsa_attn_core_roofline_pct] bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step)", flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
