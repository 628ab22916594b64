"""Device time per step in ops whose scope matches no layer of
``lib/scopes.py::LAYERS``: near 0 while the join of the trace to the
step's HLO works; a broken join shows here."""
from benchmark.metrics._common import rows_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention + projections + LN + head + loss + optimizer", \
    "train_img_s"


def read(obs):
    return rows_ms(obs, "other")
