"""Token-expert pairs on the fullest held expert over the mean of the
held experts (the program's step counters ``moe_pairs_per_expert_max`` /
``_mean``, over all layers), median over the window's steps: 1.0 is a
perfectly even routing; the fullest expert bounds an expert-parallel
deployment's step."""
import numpy as np

UNIT, KIND, SOURCE, BETTER = "x", "per_layer", "program_counter", \
    "lower"
LAYER, MOVES = "routed experts", "train_img_s"


def read(obs):
    lm = obs.get("lm")
    if not lm or not len(lm.get("load_max_over_mean", ())):
        return None
    return float(np.median(lm["load_max_over_mean"]))
