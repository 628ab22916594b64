"""Padded rows / bucket rows dispatched, from ``ServeStats`` counter
deltas over the window (``padded_rows`` and the completed rows)."""
UNIT, KIND, SOURCE, BETTER = "%", "per_layer", "program_counter", \
    "lower"
LAYER, MOVES = "serving: batcher", "serve_img_s"


def read(obs):
    s = obs.get("serve")
    if not s:
        return None
    c = s["counters"]
    rows = c.get("padded_rows", 0) + c.get("completed", 0)
    return 100.0 * c.get("padded_rows", 0) / rows if rows else None
