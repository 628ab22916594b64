"""Seconds from there to ``parallel.make_parallel_train_step()``
returning: the model and optimizer state made and laid out, the step
function built. Work dispatched and not waited for (the initialiser's
execution) is paid by the stage that waits, ``first_step``. The
program's start-up stage ``state``, as ``setup_imports_s``."""
from benchmark.metrics.setup_imports_s import stage_seconds

UNIT, KIND, SOURCE, BETTER = "s", "per_layer", "program_span", "lower"
LAYER, MOVES = "entry, loop, feed", "setup_s"


def read(obs):
    return stage_seconds(obs, "state")
