"""How late the load generator ran: 99th percentile of actual send -
due time. A starved generator must not read as a fast server."""
import numpy as np

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "host_clock", \
    "lower"
LAYER, MOVES = "load generator (the benchmark's own)", "serve_p99_ms"


def read(obs):
    s = obs.get("serve")
    if not s or not len(s["late_s"]):
        return None
    return 1e3 * float(np.percentile(s["late_s"], 99))
