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
