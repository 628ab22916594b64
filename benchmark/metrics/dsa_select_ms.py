"""Device time a step of the selection: every op under
``msa/indexer/select`` (from a row's scores to its ``topk`` keys: no
FLOP, and as a bisection over bit patterns many passes over the scores),
all layers, by the finer table ``lib/scopes_dsa.py``. Left out where the
program has no such scope."""
UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", "lower"
LAYER, MOVES = "sparse-attention indexer", "train_img_s"


def read(obs):
    return ((obs.get("dsa") or {}).get("fine_rows_ms") or {}).get(
        "indexer/select")
