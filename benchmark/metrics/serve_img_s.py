"""Images answered per second: requests due inside the window and
answered, over the window (or until the last of them was answered, if
that came later)."""
UNIT, KIND, SOURCE, BETTER = "img/s", "end_to_end", "host_clock", \
    "higher"


def read(obs):
    s = obs.get("serve")
    if not s or not s["answered"]:
        return None
    return s["answered"] / s["elapsed_s"]
