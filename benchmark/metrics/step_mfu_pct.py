"""Model FLOPs utilisation while the step runs: per-chip batch x copied
FLOPs/img / median device duration of the step's XLA module / peak.
Idle gaps between steps are excluded (they are ``device_idle_pct``)."""
from benchmark.lib import flops

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "encoder (whole step program)", "train_img_s"


def read(obs):
    tr, t = obs.get("trace"), obs.get("train")
    if not tr or not t or not tr.get("step_ms") or not obs.get("peak"):
        return None
    per_step = t["batch_per_chip"] * flops.train_step_flops_per_image(
        obs["model"])
    return 100.0 * per_step / (tr["step_ms"] / 1e3) / (
        obs["peak"]["bf16_tflops"] * 1e12)
