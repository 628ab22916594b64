"""Device time a step of the shared expert of the routed layers: every
op under ``mlp/moe_shared`` (plain XLA GEMMs over every token), forward
and backward, all routed blocks, by the finer table
``lib/scopes_mla.py`` that the driver reads the capture with. Left out
where the program has no such scope."""
UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", "lower"
LAYER, MOVES = "routed experts", "train_img_s"


def read(obs):
    return ((obs.get("mla") or {}).get("fine_rows_ms") or {}).get(
        "moe_shared")
