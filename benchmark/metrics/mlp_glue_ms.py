"""Device time per step in the XLA ops under ``mlp`` around the kernel
(the row ``mlp_xla``): the reshapes ``[B,T,D] <-> [B*T,D]`` that the
compiler materialises, and the dropout keys."""
from benchmark.metrics._common import rows_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "MLP half-block kernel", "train_img_s"


def read(obs):
    return rows_ms(obs, "mlp_xla")
