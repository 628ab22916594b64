"""Device time per step in the MLP half-block kernel's forward calls,
by the kernel's name (``lib/kernels.py::MLP_FORWARD``)."""
from benchmark.lib import kernels
from benchmark.metrics._common import rows_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "MLP half-block kernel", "train_img_s"


def read(obs):
    return rows_ms(obs, *kernels.MLP_FORWARD)
