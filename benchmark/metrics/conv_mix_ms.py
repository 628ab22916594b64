"""Device time a step of the conv layers' mix alone: every op under
``msa/conv/mix`` (from the in projection's ``[B | C | u]`` to ``C *
conv(B * u)``, and its backward pass), all layers, by the finer table
``lib/scopes_conv.py``. Left out where the program has no such scope."""
UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "device_trace", "lower"
LAYER, MOVES = "gated short convolution", "train_img_s"


def read(obs):
    return ((obs.get("conv") or {}).get("fine_rows_ms") or {}).get(
        "conv_mix")
