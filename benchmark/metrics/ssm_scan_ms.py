"""Device time a step of the state-space layers' scans alone: every op
under ``msa/ssm/scan`` (from ``x``, ``dt``, ``B``, ``C`` to ``y``, and its
backward pass), all layers, by the finer table ``lib/scopes_ssm.py``.
Left out where the program has no such scope."""
UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", "lower"
LAYER, MOVES = "state-space layer", "train_img_s"


def read(obs):
    return ((obs.get("ssm") or {}).get("fine_rows_ms") or {}).get(
        "ssm_scan")
