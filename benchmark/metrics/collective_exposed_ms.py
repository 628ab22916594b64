"""The part of ``collective_ms`` during which no compute op runs on
that chip."""
from benchmark.metrics._common import train_trace

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "mesh / partition", "train_img_s"


def read(obs):
    if not train_trace(obs, "collective_ms"):
        return None             # one chip: no collective, nothing to read
    return train_trace(obs, "collective_exposed_ms")
