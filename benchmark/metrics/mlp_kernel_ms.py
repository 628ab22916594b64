"""Device time per step in the fused MLP half-block kernel (forward and
backward of each layer): the Mosaic calls whose kernel the program names
``lnmlp_fwd`` / ``lnmlp_bwd`` / ``mlp_fwd`` / ``mlp_bwd``
(``lib/kernels.py::MLP_KERNELS``). Another kernel (flash attention, a
kernel to come) is not counted here: its time is under the layer its
scope names (``attn_core_ms`` for one under ``attn_core``)."""
from benchmark.lib import kernels
from benchmark.metrics._common import train_trace

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "MLP half-block kernel", "train_img_s"


def read(obs):
    by_kernel = train_trace(obs, "mosaic_by_kernel_ms")
    if not by_kernel:
        return None
    others = {k: ms for k, ms in by_kernel.items()
              if k not in kernels.MLP_KERNELS}
    if others:
        print(f"[mlp_kernel_ms] other Mosaic kernels, counted under their "
              f"scope's layer and not here: {others}", flush=True)
    return sum(ms for k, ms in by_kernel.items()
               if k in kernels.MLP_KERNELS) or None
