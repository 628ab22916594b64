"""Least time the chip could take for the attention core's FLOPs and
bytes (``lib/kernels.py::attention_core_cost``, from the cell's shapes;
the ``[T, T]`` tensors not counted) / ``attn_core_ms``."""
from benchmark.lib import flops, kernels
from benchmark.metrics import attn_core_ms

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "attention core", "train_img_s"


def read(obs):
    ms = attn_core_ms.read(obs)
    if not ms or not obs.get("peak"):
        return None
    m = obs["model"]
    cost = kernels.attention_core_cost(
        obs["train"]["batch_per_chip"], m["num_heads"], flops.seq_len(m),
        m["embedding_dim"] // m["num_heads"], layers=m["num_layers"])
    least = kernels.roofline_seconds(cost, obs["peak"])
    print(f"[attn_core_roofline_pct] bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step)", flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
