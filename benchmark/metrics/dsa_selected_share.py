"""Share of the causal query-key pairs that the indexer's selection
keeps (the program's step counters ``dsa_selected_pairs`` /
``dsa_causal_pairs``, a sequence a layer, median over the window's
steps): what the sparse core has to compute of what a dense causal core
would. 0.2344 at T = 16,384 with ``topk`` 2,048; 1.0 while T <= topk.
Left out where the program counts no selection."""
UNIT, KIND, SOURCE, BETTER = "share", "per_layer", "program_counter", \
    "lower"
LAYER, MOVES = "sparse-attention indexer", "train_img_s"


def read(obs):
    dsa = obs.get("dsa") or {}
    if not dsa.get("causal_pairs"):
        return None
    return dsa["selected_pairs"] / dsa["causal_pairs"]
