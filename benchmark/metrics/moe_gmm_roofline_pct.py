"""Least time the chip could take for the routed experts' grouped
products (``lib/kernels_lm.py::moe_gmm_cost``, over the token-expert
pairs the program counted on its held experts) / device time in the
Mosaic kernels ``moe_gmm_fwd`` + ``moe_gmm_dx`` + ``moe_gmm_dw``, which
is printed beside it (the rest of the row ``mlp_xla``, this cell's
``mlp_glue_ms``, is XLA's: router, sort, gathers, combine). Left out
where XLA computes the products (no such kernel in the trace)."""
import numpy as np

from benchmark.lib import kernels, kernels_lm
from benchmark.metrics._common import train_trace

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "routed experts", "train_img_s"


def read(obs):
    lm = obs.get("lm")
    found = {k: ms for k, ms in (
        train_trace(obs, "mosaic_by_kernel_ms") or {}).items()
        if k.startswith("moe_gmm_")}
    ms = sum(found.values())
    if not lm or not ms or not obs.get("peak"):
        return None
    m = obs["model"]
    held = m.get("experts_held") or m["num_experts"]
    pairs = float(np.mean(lm["pairs_per_expert_mean"])) * held
    least = kernels.roofline_seconds(
        kernels_lm.moe_gmm_cost(m, pairs), obs["peak"])
    print(f"[moe_gmm_roofline_pct] {pairs:.0f} pairs a layer in {found} "
          f"ms; bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step)", flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
