"""Seconds from there to the first train step applied
(``engine.train``'s one-off barrier): the first batch, the step's
trace, lowering, compile or cache read (``setup_trace_lower_s`` and
``setup_backend_s`` split those by program) and its first execution.
The program's start-up stage ``first_step``, as ``setup_imports_s``;
its end is the program's ``time_to_first_step``."""
from benchmark.metrics.setup_imports_s import stage_seconds

UNIT, KIND, SOURCE, BETTER = "s", "per_layer", "program_span", "lower"
LAYER, MOVES = "entry, loop, feed", "setup_s"


def read(obs):
    return stage_seconds(obs, "first_step")
