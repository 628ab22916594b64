"""Device time a step of the conv layers' mixers but their norms: every
op under ``msa/conv/in_proj``, ``msa/conv/mix`` and ``msa/conv/out_proj``
(the two projections and the gate-conv-gate between them), all layers
and phases, by the finer table ``lib/scopes_conv.py`` that the driver
reads the capture with. Left out where the program has no such scope."""
from benchmark.lib import scopes_conv

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", "lower"
LAYER, MOVES = "gated short convolution", "train_img_s"


def read(obs):
    rows = (obs.get("conv") or {}).get("fine_rows_ms") or {}
    found = [rows[r] for r, _ in scopes_conv.ROWS if r in rows]
    return sum(found) if found else None
