"""Seconds from there to ``parallel.make_mesh()`` returning: reaching
the chip (the backend's initialisation, which the harness's
``jax.devices()`` pays just before the call) and the device mesh. The
program's start-up stage ``mesh``, as ``setup_imports_s``."""
from benchmark.metrics.setup_imports_s import stage_seconds

UNIT, KIND, SOURCE, BETTER = "s", "per_layer", "program_span", "lower"
LAYER, MOVES = "entry, loop, feed", "setup_s"


def read(obs):
    return stage_seconds(obs, "mesh")
