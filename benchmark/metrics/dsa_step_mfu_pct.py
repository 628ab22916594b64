"""Model FLOPs utilisation of the step of a token model whose attention
an indexer selects, while it runs: the chip's sequences x copied FLOPs a
sequence (``lib/flops_dsa.py``: the core and the head-mean
probabilities over SELECTED pairs, the indexer's scores over every
causal pair forward and over the selected pairs backward, projections,
expected held routed pairs and the head 3 x forward) / median device
duration of the step's XLA module / peak. What the program computes
beyond that (unselected pairs it masks, scores taken twice, projections
taken again in the backward pass) is not counted and shows as lower
utilisation. Idle gaps between steps are excluded (they are
``device_idle_pct``)."""
from benchmark.lib import flops_dsa

UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "higher"
LAYER, MOVES = "encoder (whole step program)", "train_img_s"


def read(obs):
    tr, t, dsa = obs.get("trace"), obs.get("train"), obs.get("dsa")
    if not tr or not t or not dsa or not tr.get("step_ms") \
            or not obs.get("peak"):
        return None
    per_step = t["batch_per_chip"] * flops_dsa.train_step_flops_per_sequence(
        obs["model"], dsa["seq_len"])
    return 100.0 * per_step / (tr["step_ms"] / 1e3) / (
        obs["peak"]["bf16_tflops"] * 1e12)
