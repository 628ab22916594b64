"""Seconds of set-up spent reading entries of the persistent compile
cache, over the programs whose first call ended before the window
opened (part of ``setup_backend_s``; 0 in a cold run). From the
program's own counters, as ``setup_trace_lower_s``."""
from benchmark.metrics.setup_trace_lower_s import stats_until

UNIT, KIND, SOURCE, BETTER = "s", "per_layer", "program_counter", \
    "lower"
LAYER, MOVES = "compile cache", "setup_s"


def read(obs):
    stats, until_s = stats_until(obs)
    return None if stats is None else \
        stats.stage_seconds(until_s)["cache_read"]
