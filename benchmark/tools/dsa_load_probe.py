"""Which draws of the weights put the balanced load on the held experts
(no part of a run): for each candidate work seed, the weights and the
pool as ``drivers/train_dsa.py`` draws them, one eval-mode forward of
every pool batch at the cell's sizes (the indexer's selection and the
sparse core with it), and the token-expert pairs a held expert over all
routed blocks (the program's own counter). A cell's
``work_seeds`` are the draws that read what a balanced router sends
(``tokens x experts_per_token / num_experts``): the step's time follows
that load, so every seed of the cell does the same work. The share of a
layer's chunks of tokens served in ONE pass over the routed row buffer
is printed too: a pass more is 10 ms of this cell's step, and draws of
one load differ in it (PERF.md section 6, PR 34).

    python3 benchmark/tools/dsa_load_probe.py --workload keye2_train_16k --seeds 1:49
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="keye2_train_16k")
    ap.add_argument("--seeds", default="1:13",
                    help="first:last+1, or seeds joined by commas")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers import train_dsa
    from benchmark.lib import harness
    from pytorch_vit_paper_replication_tpu import engine

    cell, config = harness.load_cell(args.workload, rehearsal=args.rehearsal)
    p = cell[cell["driver"]]
    harness.configure_cache()
    cfg, model = harness.build_model(config)
    harness.claim_devices(cell["chips"], rehearsal=args.rehearsal)
    seq_len = min(p["seq_len"], cfg.max_seq_len)
    want = seq_len * cfg.experts_per_token / cfg.num_experts
    init = jax.jit(lambda key: model.init(
        key, jnp.zeros((1, 8), jnp.int32))["params"])

    @jax.jit
    def load(params, batch):
        _, sown = model.apply({"params": params}, batch["tokens"], False,
                              labels=batch["label"], mutable=["moe_stats"])
        return engine._moe_metrics(sown["moe_stats"])

    if ":" in args.seeds:
        first, last = (int(x) for x in args.seeds.split(":"))
        seeds = range(first, last)
    else:
        seeds = [int(x) for x in args.seeds.split(",")]
    for seed in seeds:
        params = init(jax.random.key(seed))
        pool = train_dsa.make_pool(seed, p["pool_batches"], 1, seq_len,
                                   cfg.vocab_size, p["successors"])
        seen = jax.device_get([load(params, b) for b in pool])
        mean = np.mean([float(m["moe_pairs_per_expert_mean"]) for m in seen])
        most = max(float(m["moe_pairs_per_expert_max"]) for m in seen)
        print(f"[load] seed {seed}: pairs a held expert {mean:.1f} "
              f"(balanced {want:.0f}; {mean - want:+.1f}), by batch "
              + " ".join(f"{float(m['moe_pairs_per_expert_mean']):.0f}"
                         for m in seen)
              + f", fullest expert of any block {most:.0f}; chunks served "
              "in one pass over the row buffer, share by batch "
              + " ".join(f"{float(m['moe_one_pass_share']):.3f}"
                         for m in seen)
              + f", most passes {max(float(m['moe_passes_max']) for m in seen):.0f}",
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
