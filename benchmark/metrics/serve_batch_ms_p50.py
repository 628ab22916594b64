"""Median of the batcher's ``batch.device`` spans, per request: the
host's wall around stack + H2D + forward + fetch of its batch."""
from benchmark.metrics.serve_queue_ms_p50 import span_ms_p50

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "program_span", \
    "lower"
LAYER, MOVES = "serving: engine forward", "serve_p50_ms"


def read(obs):
    return span_ms_p50(obs, "batch.device")
