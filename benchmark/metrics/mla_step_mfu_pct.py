"""Model FLOPs utilisation of a latent-attention token model's step
while it runs: the chip's sequences x copied FLOPs a sequence
(``lib/flops_mla.py``: visible pairs only, latent projections, layer
kinds, shared + expected held routed pairs, the module and the head
twice, 3 x forward) / median device duration of the step's XLA module /
peak. What the program computes twice (the latent projections in the
backward pass) is not counted and shows as lower utilisation. Idle gaps
between steps are excluded (they are ``device_idle_pct``)."""
from benchmark.lib import flops_mla

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "encoder (whole step program)", "train_img_s"


def read(obs):
    tr, t, mla = obs.get("trace"), obs.get("train"), obs.get("mla")
    if not tr or not t or not mla or not tr.get("step_ms") \
            or not obs.get("peak"):
        return None
    per_step = t["batch_per_chip"] * flops_mla.train_step_flops_per_sequence(
        obs["model"], mla["seq_len"])
    return 100.0 * per_step / (tr["step_ms"] / 1e3) / (
        obs["peak"]["bf16_tflops"] * 1e12)
