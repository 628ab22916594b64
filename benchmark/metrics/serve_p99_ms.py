"""99th percentile of the same latencies (the sample count is printed on
an earlier line; a refused, expired or failed request counts in
``failed``)."""
import numpy as np

UNIT, KIND, SOURCE, BETTER = "ms", "end_to_end", "host_clock", \
    "lower"


def read(obs):
    s = obs.get("serve")
    if not s or not len(s["latency_s"]):
        return None
    return 1e3 * float(np.percentile(s["latency_s"], 99))
