"""Device time per step under the scope ``attn_core`` in the forward
pass: ``rows_ms["attn_core"]["forward"]``, whatever computes o from q,
k, v there (XLA fusions, one kernel, several). With
``attn_core_bwd_ms`` it sums to ``attn_core_ms``. By the phase of the
op's scope and not by a kernel's name: a PR that cuts the core into
other calls is read by this file as it stands."""
from benchmark.metrics._common import rows_phase_ms

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", \
    "lower"
LAYER, MOVES = "attention core", "train_img_s"


def read(obs):
    return rows_phase_ms(obs, "attn_core", "forward")
