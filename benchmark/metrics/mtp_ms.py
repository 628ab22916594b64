"""Device time a step of the multi-token-prediction module: every op
under the module's scope (``mtp``), its merge, its block, its norm and
its pass through the shared head with its loss, forward and backward,
by the finer table ``lib/scopes_mla.py`` that the driver reads the
capture with. Left out where the program has no such scope."""
from benchmark.lib import scopes_mla

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", "lower"
LAYER, MOVES = "multi-token prediction", "train_img_s"


def read(obs):
    rows = (obs.get("mla") or {}).get("fine_rows_ms") or {}
    found = [rows[r] for r in scopes_mla.MTP_ROWS if r in rows]
    return sum(found) if found else None
