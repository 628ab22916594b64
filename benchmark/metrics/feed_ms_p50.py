"""Median host time per step in the feed call (``parallel.shard_batch``
of one pool batch), over the window. On the v5e the call returns in
about a millisecond: the layout change and the copy to the chip run on
the runtime's own threads, behind the step that is executing, and are
not in this number (the second interval of the ``[window]`` line, fed
with nothing in flight, shows them: +35-50 ms for 154 MB). It rises when
the feed becomes synchronous work of the loop, as a loader's would."""
import numpy as np

UNIT, KIND, SOURCE, BETTER = "ms", "per_layer", "host_clock", \
    "lower"
LAYER, MOVES = "entry, loop, feed", "train_img_s"


def read(obs):
    t = obs.get("train")
    if not t or not len(t.get("feed_ms", ())):
        return None
    return float(np.median(t["feed_ms"]))
