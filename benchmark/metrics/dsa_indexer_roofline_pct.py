"""Least time the chip could take for the indexer's scores
(``lib/kernels_dsa.py::indexer_cost``: ``2 * J * Di`` a CAUSAL pair
forward, the indexer's q, k and head weights read once, the scores not
written) / device time under the scope ``indexer/scores`` (the finer
table ``lib/scopes_dsa.py``; a program that takes the scores twice, once
for the selection and once for the loss, has both under it). Left out
where the program has no such scope."""
from benchmark.lib import kernels, kernels_dsa

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "sparse-attention indexer", "train_img_s"


def read(obs):
    dsa = obs.get("dsa") or {}
    ms = (dsa.get("fine_rows_ms") or {}).get("indexer/scores")
    if not ms or not obs.get("peak"):
        return None
    cost = kernels_dsa.indexer_cost(
        obs["model"], dsa["seq_len"], obs["train"]["batch_per_chip"])
    least = kernels.roofline_seconds(cost, obs["peak"])
    print(f"[dsa_indexer_roofline_pct] bound: {least['bound']} (compute "
          f"{least['compute_s'] * 1e3:.3f} ms, memory "
          f"{least['memory_s'] * 1e3:.3f} ms per step) over {ms:.3f} ms",
          flush=True)
    return 100.0 * least["seconds"] / (ms / 1e3)
