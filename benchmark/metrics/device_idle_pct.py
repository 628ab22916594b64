"""Share of the traced window in which no operation ran on the chip
(mean over chips): 100 - busy share, by construction. One reader for
every driver; which end-to-end metric it moves is the entry's in
``BENCHMARK.json``."""
UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "device", "train_img_s"


def read(obs):
    tr = obs.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
