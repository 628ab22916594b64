"""Process start -> first measured step or window open: imports,
reaching the chip, weights, compile or cache restore, warm-up."""
UNIT, KIND, SOURCE, BETTER = "s", "end_to_end", "host_clock", \
    "lower"


def read(obs):
    return obs.get("setup_s")
