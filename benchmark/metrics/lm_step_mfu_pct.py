"""Model FLOPs utilisation of a token model's step while it runs: the
chip's sequences x copied FLOPs a sequence (``lib/flops_lm.py``: visible
pairs only, expected pairs on the experts held, 3 x forward) / median
device duration of the step's XLA module / peak. Idle gaps between steps
are excluded (they are ``device_idle_pct``)."""
from benchmark.lib import flops_lm

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "encoder (whole step program)", "train_img_s"


def read(obs):
    tr, t, lm = obs.get("trace"), obs.get("train"), obs.get("lm")
    if not tr or not t or not lm or not tr.get("step_ms") \
            or not obs.get("peak"):
        return None
    per_step = t["batch_per_chip"] * flops_lm.train_step_flops_per_sequence(
        obs["model"], lm["seq_len"])
    return 100.0 * per_step / (tr["step_ms"] / 1e3) / (
        obs["peak"]["bf16_tflops"] * 1e12)
