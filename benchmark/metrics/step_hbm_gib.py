"""What the compiled step holds per chip: ``memory_analysis()``
arguments + temporaries + outputs - aliased, of the step the cell runs."""
UNIT, KIND, SOURCE, BETTER = "GiB", "per_layer", "program_counter", \
    "lower"
LAYER, MOVES = "step program (compiler)", "train_img_s"


def read(obs):
    t = obs.get("train")
    if not t or not t.get("step_hbm_bytes"):
        return None
    return t["step_hbm_bytes"] / 2**30
