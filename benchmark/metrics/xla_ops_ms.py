"""Device busy time per step outside Mosaic calls and collectives:
attention core, MSA projections, LayerNorms, head, loss, optimizer. An
honest residual until the program has ``named_scope``s."""
from benchmark.metrics._common import train_trace

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention + projections + LN + head + loss + optimizer", \
    "train_img_s"


def read(obs):
    return train_trace(obs, "xla_ms")
