"""Device time per step in the attention block's LayerNorm
(``msa/norm``; the MLP block's LayerNorm is inside the MLP kernel)."""
from benchmark.metrics._common import rows_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention + projections + LN + head + loss + optimizer", \
    "train_img_s"


def read(obs):
    return rows_ms(obs, "msa_norm")
