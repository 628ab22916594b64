"""Model FLOPs utilisation of the step of a token model whose layers mix
gated short convolutions with attention, while it runs: the chip's
sequences x copied FLOPs a sequence (``lib/flops_conv.py``: the conv
mixers' projections and taps, the attention layer's projections and its
core over causal pairs, the dense layer, the routers and the expected
pairs on the held experts, the head, 3 x forward) / median device
duration of the step's XLA module / peak. Idle gaps between steps are
excluded (they are ``device_idle_pct``)."""
from benchmark.lib import flops_conv

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "encoder (whole step program)", "train_img_s"


def read(obs):
    tr, t, conv = obs.get("trace"), obs.get("train"), obs.get("conv")
    if not tr or not t or not conv or not tr.get("step_ms") \
            or not obs.get("peak"):
        return None
    per_step = t["batch_per_chip"] * flops_conv.train_step_flops_per_sequence(
        obs["model"], conv["seq_len"])
    return 100.0 * per_step / (tr["step_ms"] / 1e3) / (
        obs["peak"]["bf16_tflops"] * 1e12)
