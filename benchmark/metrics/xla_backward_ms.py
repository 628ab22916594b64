"""Device time per step in the backward pass outside Mosaic calls and
collectives: the ``backward`` and ``recompute`` phases of ``xla_ops_ms``
(an op is backward when its scope holds ``transpose(``; recompute is
XLA's own rematerialisation)."""
from benchmark.metrics._common import train_trace

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention + projections + LN + head + loss + optimizer", \
    "train_img_s"


def read(obs):
    by_phase = train_trace(obs, "xla_by_phase_ms")
    if not by_phase:
        return None
    return by_phase.get("backward", 0.0) + by_phase.get("recompute", 0.0)
