"""Device time a step of the state-space layers' mixers but their
pre-norms: every op under ``msa/ssm/in_proj``, ``conv``, ``scan``,
``gate_norm`` and ``out_proj``, all layers and phases, by the finer table
``lib/scopes_ssm.py`` that the driver reads the capture with. Left out
where the program has no such scope."""
from benchmark.lib import scopes_ssm

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", "lower"
LAYER, MOVES = "state-space layer", "train_img_s"


def read(obs):
    rows = (obs.get("ssm") or {}).get("fine_rows_ms") or {}
    found = [rows[r] for r, _ in scopes_ssm.ROWS if r in rows]
    return sum(found) if found else None
