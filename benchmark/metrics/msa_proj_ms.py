"""Device time per step in the attention block's two projections,
``msa/qkv`` and ``msa/out`` (with what the compiler fuses under their
roots: the bias gradients, and the preceding LayerNorm's backward)."""
from benchmark.metrics._common import rows_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention + projections + LN + head + loss + optimizer", \
    "train_img_s"


def read(obs):
    return rows_ms(obs, "msa_qkv", "msa_out")
