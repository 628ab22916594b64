"""Least time the chip could take for the MLP kernels' FLOPs and bytes
(``lib/kernels.py``, from the cell's shapes) / ``mlp_kernel_ms``."""
from benchmark.lib import flops, kernels
from benchmark.metrics import mlp_kernel_ms

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "MLP half-block kernel", "train_img_s"


def read(obs):
    ms = mlp_kernel_ms.read(obs)
    if not ms or not obs.get("peak"):
        return None
    m = obs["model"]
    cost = kernels.mlp_half_block_cost(
        obs["train"]["batch_per_chip"] * flops.seq_len(m),
        m["embedding_dim"], m["mlp_size"], layers=m["num_layers"])
    least = kernels.roofline_seconds(cost, obs["peak"])
    print(f"[mlp_kernel_roofline_pct] bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step)", flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
