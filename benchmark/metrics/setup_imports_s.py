"""Seconds from the process's start to the program's first call
returning (``compile_cache.configure()``): the interpreter, ``import
jax``, the package's imports and the harness's own before it. The first
of the four start-up stages the program keeps on its own clock
(``compile_cache.STATS.startup()``: ``imports``, ``mesh``, ``state``,
``first_step``, each beginning where the one before it ended), which
with the warm-up steps add up to ``setup_s``. A program without the
record reports nothing.

Both clocks are ``CLOCK_BOOTTIME`` less the process's start ticks, so a
stage that ended before the window opened ended before ``setup_s``."""
UNIT, KIND, SOURCE, BETTER = "s", "per_layer", "program_span", "lower"
LAYER, MOVES = "entry, loop, feed", "setup_s"


def stage_seconds(obs, name):
    """Seconds of start-up stage ``name`` if it ended before the window
    opened, or None where there is nothing to read."""
    if obs.get("setup_s") is None:
        return None
    from pytorch_vit_paper_replication_tpu import compile_cache

    startup = getattr(compile_cache.STATS, "startup", None)
    if startup is None:
        return None          # a program from before the stages
    stage = startup().get(name)
    if stage is None or stage["end_s"] > obs["setup_s"]:
        return None
    return stage["seconds"]


def read(obs):
    return stage_seconds(obs, "imports")
