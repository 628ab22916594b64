"""Device time per step under ``msa`` and under none of its norm, qkv,
core and out: the slices that cut q, k, v out of the packed projection
and the layout copies. 0 (not nothing) where the compiler fuses them
into the projection, as on L/16."""
from benchmark.metrics._common import rows_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention + projections + LN + head + loss + optimizer", \
    "train_img_s"


def read(obs):
    return rows_ms(obs, "msa_glue")
