"""Shared by the metric files (not a metric: the harness skips names
that start with ``_``)."""


def train_trace(obs, key):
    """``obs["trace"][key]`` of a train cell's traced run, or None."""
    tr = obs.get("trace")
    if not tr or not obs.get("train"):
        return None
    return tr.get(key)
