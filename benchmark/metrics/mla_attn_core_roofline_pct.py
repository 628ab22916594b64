"""Least time the chip could take for a latent-attention model's
attention core in training (``lib/kernels_mla.py::attention_core_cost``:
six GEMMs over the visible pairs at head size 256, the key's shared
rotary columns counted once, ``[T, T]`` not counted) / ``attn_core_ms``:
the same work whatever implements it."""
from benchmark.lib import kernels, kernels_mla
from benchmark.metrics import attn_core_ms

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "attention core", "train_img_s"


def read(obs):
    ms, mla = attn_core_ms.read(obs), obs.get("mla")
    if not ms or not mla or not obs.get("peak"):
        return None
    cost = kernels_mla.attention_core_cost(
        obs["model"], mla["seq_len"], obs["train"]["batch_per_chip"])
    least = kernels.roofline_seconds(cost, obs["peak"])
    print(f"[mla_attn_core_roofline_pct] bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step)", flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
