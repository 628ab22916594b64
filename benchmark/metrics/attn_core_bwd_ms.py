"""Device time per step under the scope ``attn_core`` in the backward
pass: ``rows_ms["attn_core"]`` in the phases ``backward`` and
``recompute`` (as ``xla_backward_ms`` takes them: an op is backward when
its scope holds ``transpose(``; recompute is XLA's own
rematerialisation), whatever computes dq, dk, dv there: two kernels a
layer today, one tomorrow. With ``attn_core_fwd_ms`` it sums to
``attn_core_ms``."""
from benchmark.metrics._common import rows_phase_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention core", "train_img_s"


def read(obs):
    return rows_phase_ms(obs, "attn_core", "backward", "recompute")
