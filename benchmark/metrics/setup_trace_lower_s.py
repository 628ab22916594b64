"""Seconds of set-up spent tracing Python to jaxprs and lowering them to
MLIR modules, over every program whose first call ended before the
window opened: the part of ``setup_s`` that no compile cache can save
(the cache's key needs the lowered module). From the program's own
counters (``compile_cache.STATS.stage_seconds``, fed by
``jax.monitoring``); a program without them reports nothing.

Both clocks count from the process's start: the benchmark's on
``CLOCK_BOOTTIME``, the program's through ``btime`` in whole seconds, so
they may differ by up to a second. Nothing compiles within seconds of
the window's opening (the checks come ``--seconds`` later), so the
filter allows that second. Prints the ``[programs]`` line: the same
seconds per program."""
UNIT, KIND, SOURCE, BETTER = "s", "per_layer", "program_counter", \
    "lower"
LAYER, MOVES = "entry, loop, feed", "setup_s"

CLOCK_SLACK_S = 1.0


def stats_until(obs):
    """The program's ``CacheStats`` and the instant that ends set-up on
    its clock, or ``(None, None)`` where there is nothing to read."""
    if obs.get("setup_s") is None:
        return None, None
    from pytorch_vit_paper_replication_tpu import compile_cache

    stats = compile_cache.STATS
    if not hasattr(stats, "stage_seconds"):
        return None, None        # a program from before the counters
    return stats, obs["setup_s"] + CLOCK_SLACK_S


def read(obs):
    stats, until_s = stats_until(obs)
    if stats is None:
        return None
    if obs.get("peak"):      # a rehearsal (no chip's peak) prints no time
        print(f"[programs] first calls before the window opened, seconds: "
              f"{stats.programs_line(until_s)}", flush=True)
    stages = stats.stage_seconds(until_s)
    return stages["trace"] + stages["lower"]
