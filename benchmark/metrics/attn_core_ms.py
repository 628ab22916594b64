"""Device time per step under the scope ``attn_core`` (``ops/attention.py
::dot_product_attention``, forward, backward and recompute): whatever
computes o from q, k, v there, XLA fusions today, a kernel tomorrow."""
from benchmark.metrics._common import rows_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention core", "train_img_s"


def read(obs):
    return rows_ms(obs, "attn_core")
