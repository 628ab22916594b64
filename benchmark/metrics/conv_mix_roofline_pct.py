"""Least time the chip could take for the conv layers' mix
(``lib/kernels_conv.py::conv_mix_cost``: ``[N, 3D]`` read and ``[N, D]``
written forward, ``[N, 3D]`` and the cotangent read and ``[N, 3D]`` and
the taps' gradient written backward, in bf16; bound by memory at the
table's 819 GB/s) / device time under the scope ``conv/mix`` (the finer
table ``lib/scopes_conv.py``). It reads the same work whatever
implements the mix. Left out where the program has no such scope."""
from benchmark.lib import kernels, kernels_conv

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "gated short convolution", "train_img_s"


def read(obs):
    conv = obs.get("conv") or {}
    ms = (conv.get("fine_rows_ms") or {}).get("conv_mix")
    if not ms or not obs.get("peak"):
        return None
    cost = kernels_conv.conv_mix_cost(
        obs["model"], conv["seq_len"], obs["train"]["batch_per_chip"])
    least = kernels.roofline_seconds(cost, obs["peak"])
    print(f"[conv_mix_roofline_pct] bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step) over {ms:.3f} ms",
          flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
