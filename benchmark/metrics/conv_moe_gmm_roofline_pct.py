"""Least time the chip could take for the routed experts' grouped
products of a model whose layers mix gated short convolutions with
attention (``lib/kernels_conv.py::moe_gmm_cost``: ``kernels_lm``'s, over
the pairs the program counted on its held experts, on the routed blocks
alone) / device time in the Mosaic kernels ``moe_gmm_fwd`` +
``moe_gmm_dx`` + ``moe_gmm_dw``. The accepted ``moe_gmm_roofline_pct``
counts the leading dense layer as routed too. Left out where XLA
computes the products (no such kernel in the trace)."""
import numpy as np

from benchmark.lib import kernels, kernels_conv
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
    if not lm or not obs.get("conv") or not ms or not obs.get("peak"):
        return None
    m = obs["model"]
    held = m.get("experts_held") or m["num_experts"]
    pairs = float(np.mean(lm["pairs_per_expert_mean"])) * held
    least = kernels.roofline_seconds(kernels_conv.moe_gmm_cost(m, pairs),
                                     obs["peak"])
    print(f"[conv_moe_gmm_roofline_pct] {pairs:.0f} pairs a routed block "
          f"in {found} ms; bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step)", flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
