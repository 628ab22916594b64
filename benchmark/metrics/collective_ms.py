"""Device time per step in collective ops (union per chip)."""
from benchmark.metrics._common import train_trace

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "mesh / partition", "train_img_s"


def read(obs):
    return train_trace(obs, "collective_ms") or None
