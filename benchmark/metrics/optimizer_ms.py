"""Device time per step under the scope ``optimizer``: what of the
update the compiler did not fuse into the weight-gradient fusions."""
from benchmark.metrics._common import rows_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention + projections + LN + head + loss + optimizer", \
    "train_img_s"


def read(obs):
    return rows_ms(obs, "optimizer")
