"""Device time per step in the fused MLP half-block kernel (forward and
backward of each layer): the Mosaic custom calls whose result type is
the kernel's (``lib/kernels.py::is_mlp_half_block``). At T=197 that is
every Mosaic call of the step; a flash-attention kernel is not counted
here."""
from benchmark.lib import kernels
from benchmark.metrics._common import train_trace

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "MLP half-block kernel", "train_img_s"


def read(obs):
    by_out = train_trace(obs, "mosaic_by_out_ms")
    if not by_out:
        return None
    mine = {out: ms for out, ms in by_out.items()
            if kernels.is_mlp_half_block(out, obs["model"]["mlp_size"])}
    if len(mine) < len(by_out):
        print(f"[mlp_kernel_ms] other Mosaic kernels, not counted here: "
              f"{({o: v for o, v in by_out.items() if o not in mine})}",
              flush=True)
    return sum(mine.values()) or None
