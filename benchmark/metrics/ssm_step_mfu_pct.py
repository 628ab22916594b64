"""Model FLOPs utilisation of the step of a token model whose layers mix
Mamba-2 state-space layers with attention, while it runs: the chip's
sequences x copied FLOPs a sequence (``lib/flops_ssm.py``: the mixers'
projections, taps and chunked scans, the attention layer's projections
and its core over causal pairs, the dense feed-forward of every layer,
the head, 3 x forward; recomputation not counted) / median device
duration of the step's XLA module / peak. Idle gaps between steps are
excluded (they are ``device_idle_pct``)."""
from benchmark.lib import flops_ssm

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "encoder (whole step program)", "train_img_s"


def read(obs):
    tr, t, ssm = obs.get("trace"), obs.get("train"), obs.get("ssm")
    if not tr or not t or not ssm or not tr.get("step_ms") \
            or not obs.get("peak"):
        return None
    per_step = t["batch_per_chip"] * flops_ssm.train_step_flops_per_sequence(
        obs["model"], ssm["seq_len"])
    return 100.0 * per_step / (tr["step_ms"] / 1e3) / (
        obs["peak"]["bf16_tflops"] * 1e12)
