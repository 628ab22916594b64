"""Images trained per second per chip: whole steps between two
``block_until_ready`` barriers, divided by the measured time."""
UNIT, KIND, SOURCE, BETTER = "img/s/chip", "end_to_end", "host_clock", \
    "higher"


def read(obs):
    t = obs.get("train")
    if not t or not t["steps"]:
        return None
    return t["images"] / t["elapsed_s"] / t["chips"]
