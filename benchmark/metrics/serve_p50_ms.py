"""Median latency of answered requests, each timed from when it was due
to be sent to when its future resolved."""
import numpy as np

UNIT, KIND, SOURCE, BETTER = "ms", "end_to_end", "host_clock", \
    "lower"


def read(obs):
    s = obs.get("serve")
    if not s or not len(s["latency_s"]):
        return None
    return 1e3 * float(np.percentile(s["latency_s"], 50))
