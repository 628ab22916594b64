"""Median of the batcher's ``batch.queue_wait`` spans in the window
(submit -> dispatch), sampling 1.0."""
import numpy as np

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "program_span", \
    "lower"
LAYER, MOVES = "serving: batcher", "serve_p50_ms"


def span_ms_p50(obs, name):
    s = obs.get("serve")
    d = [sp["t1"] - sp["t0"] for sp in (s or {}).get("spans", ())
         if sp["name"] == name]
    return 1e3 * float(np.percentile(d, 50)) if d else None


def read(obs):
    return span_ms_p50(obs, "batch.queue_wait")
