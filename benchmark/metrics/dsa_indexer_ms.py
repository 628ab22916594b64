"""Device time a step of the sparse-attention indexer but its
selection: every op under ``msa/indexer/proj`` (its three products, the
key's norm, the rotary embedding), ``msa/indexer/scores`` (wherever the
scores are taken) and ``msa/indexer_loss`` (the head-mean probabilities'
pass, the KL and the loss's gradients), all layers and phases, by the
finer table ``lib/scopes_dsa.py`` that the driver reads the capture
with. Left out where the program has no such scope."""
from benchmark.lib import scopes_dsa

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", "lower"
LAYER, MOVES = "sparse-attention indexer", "train_img_s"


def read(obs):
    rows = (obs.get("dsa") or {}).get("fine_rows_ms") or {}
    found = [rows[r] for r in scopes_dsa.INDEXER_ROWS if r in rows]
    return sum(found) if found else None
