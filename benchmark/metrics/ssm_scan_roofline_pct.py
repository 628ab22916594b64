"""Least time the chip could take for the state-space layers' scans
(``lib/kernels_ssm.py::ssm_scan_cost``: ``x``, ``B``, ``C`` and ``dt``
read and ``y`` written forward; those, ``y``'s cotangent read and their
cotangents written backward; the chunked products' FLOPs) / device time
under the scope ``ssm/scan`` (the finer table ``lib/scopes_ssm.py``). It
reads the same work whatever implements the scan. Left out where the
program has no such scope."""
from benchmark.lib import kernels, kernels_ssm

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "state-space layer", "train_img_s"


def read(obs):
    ssm = obs.get("ssm") or {}
    ms = (ssm.get("fine_rows_ms") or {}).get("ssm_scan")
    if not ms or not obs.get("peak"):
        return None
    cost = kernels_ssm.ssm_scan_cost(
        obs["model"], ssm["seq_len"], obs["train"]["batch_per_chip"])
    least = kernels.roofline_seconds(cost, obs["peak"])
    print(f"[ssm_scan_roofline_pct] bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step) over {ms:.3f} ms",
          flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
