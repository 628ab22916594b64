"""Which draws of the weights put the balanced load on the held experts,
and which std the tied table's rows start at (no part of a run): for
each candidate work seed, the weights and the pool as
``drivers/train_conv.py`` draws them, one eval-mode forward of every
pool batch at the cell's sizes, and the token-expert pairs a held expert
over all routed blocks (the program's own counter), the fullest held
expert over the mean, the loss at initialisation and the spread of the
logits. A cell's ``work_seeds`` are the draws that read what a balanced
router sends (``tokens x experts_per_token / num_experts``): the step's
time follows that load, so every seed of the cell does the same work.
``--embedding-std`` redraws the table's rows at another std after the
model's own initialiser (the head is tied to it: its rows are the
logits' weights too), for the reading the configuration's choice rests
on.

    python3 benchmark/tools/conv_load_probe.py --workload lfm2_train_8k --seeds 1:101
    python3 benchmark/tools/conv_load_probe.py --seeds 1:9 --embedding-std 1.0
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lfm2_train_8k")
    ap.add_argument("--seeds", default="1:13",
                    help="first:last+1, or seeds joined by commas")
    ap.add_argument("--embedding-std", type=float, default=None,
                    help="redraw the table's rows at this std (default: "
                         "the model's initialiser, init_std when tied)")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers import train_conv
    from benchmark.lib import harness
    from pytorch_vit_paper_replication_tpu import engine

    cell, config = harness.load_cell(args.workload, rehearsal=args.rehearsal)
    p = cell[cell["driver"]]
    harness.configure_cache()
    cfg, model = harness.build_model(config)
    harness.claim_devices(cell["chips"], rehearsal=args.rehearsal)
    seq_len = min(p["seq_len"], cfg.max_seq_len)
    batch = p["batch_per_chip"] * cell["chips"]
    want = batch * seq_len * cfg.experts_per_token / cfg.num_experts

    @jax.jit
    def init(key):
        params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
        if args.embedding_std is None:
            return params
        table = params["backbone"]["token_embedding"]["embedding"]
        rows = args.embedding_std * jax.random.normal(
            jax.random.fold_in(key, 1), table.shape, table.dtype)
        return {**params, "backbone": {
            **params["backbone"], "token_embedding": {"embedding": rows}}}

    @jax.jit
    def load(params, tokens, labels):
        (loss, _), sown = model.apply({"params": params}, tokens, False,
                                      labels=labels, mutable=["moe_stats"])
        logits = model.apply({"params": params}, tokens[:1, :512], False)
        return {**engine._moe_metrics(sown["moe_stats"]), "loss": loss,
                "logit_std": jnp.std(logits)}

    if ":" in args.seeds:
        first, last = (int(x) for x in args.seeds.split(":"))
        seeds = range(first, last)
    else:
        seeds = [int(x) for x in args.seeds.split(",")]
    std = args.embedding_std
    if std is None:
        std = cfg.init_std if cfg.tie_embedding else 1.0
    print(f"[load] embedding rows at std {std}; balanced "
          f"{want:.0f} pairs a held expert", flush=True)
    for seed in seeds:
        params = init(jax.random.key(seed))
        pool = train_conv.make_pool(seed, p["pool_batches"], batch, seq_len,
                                    cfg.vocab_size, p["successors"])
        seen = jax.device_get([load(params, b["tokens"], b["label"])
                               for b in pool])
        mean = np.mean([float(m["moe_pairs_per_expert_mean"]) for m in seen])
        over = np.mean([float(m["moe_pairs_per_expert_max"])
                        / float(m["moe_pairs_per_expert_mean"])
                        for m in seen])
        print(f"[load] seed {seed}: pairs a held expert {mean:.1f} "
              f"({mean - want:+.1f}), by batch "
              + " ".join(f"{float(m['moe_pairs_per_expert_mean']):.0f}"
                         for m in seen)
              + f"; fullest over mean {over:.3f}; most passes "
              f"{max(float(m['moe_passes_max']) for m in seen):.0f}; loss "
              f"at initialisation {np.mean([float(m['loss']) for m in seen]):.4f}"
              f" (log V = {np.log(cfg.vocab_size):.4f}); logits' std "
              f"{float(seen[0]['logit_std']):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
