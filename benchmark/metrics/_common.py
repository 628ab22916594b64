"""Shared by the metric files (not a metric: the harness skips names
that start with ``_``)."""
from benchmark.lib.xplane import layer_ms


def train_trace(obs, key):
    """``obs["trace"][key]`` of a train cell's traced run, or None."""
    tr = obs.get("trace")
    if not tr or not obs.get("train"):
        return None
    return tr.get(key)


def rows_ms(obs, *layers):
    """Milliseconds per step under ``layers`` in the traced run's
    ``rows_ms`` table (a layer no op ran under counts as 0), or None
    where there is no table."""
    rows = train_trace(obs, "rows_ms")
    return layer_ms(rows, *layers) if rows else None


def rows_phase_ms(obs, layer, *phases):
    """Milliseconds per step under ``layer`` in ``phases`` of the same
    table (a phase no op of the layer ran in counts as 0), or None where
    there is no table."""
    rows = train_trace(obs, "rows_ms")
    if not rows:
        return None
    return sum(rows.get(layer, {}).get(phase, 0.0) for phase in phases)
