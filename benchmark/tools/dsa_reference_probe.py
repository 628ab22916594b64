"""The readings the sparse-attention cell's reference limits are set from
(no part of a run): on the chip, at the cell's sizes, with seeded random
weights, (1) the program's bf16 forward against the float32 reference
(logits, main loss, the indexer's alignment loss, the selection's
agreement in the first and the last layer) and (2) the reference with
its matmul inputs rounded to fp8 e4m3 -- the nearest precision below the
one the configuration states -- against the same reference, everywhere
and confined to families of products (``reference_dsa.FAMILIES``; by
default the indexer's and the core's together, then each alone): what a
fault in that part alone would read. Each limit lies between (1) and
(2) (``drivers/train_dsa.py``). PERF.md section 6 gives the readings.

    python3 benchmark/tools/dsa_reference_probe.py --workload keye2_train_16k --seeds 7 11

One process takes every seed in turn (one compile of each program).
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
    ap.add_argument("--seeds", type=int, nargs="+", default=[0])
    ap.add_argument("--only", nargs="*",
                    default=["indexer+attn_core", "indexer", "attn_core"],
                    help="families to confine the rounding to, in turn "
                         "(several joined by +)")
    ap.add_argument("--no-everywhere", action="store_true",
                    help="skip the control that rounds every product")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_dsa
    from benchmark.lib import harness
    from pytorch_vit_paper_replication_tpu import parallel
    from pytorch_vit_paper_replication_tpu.configs import MeshConfig

    cell, config = harness.load_cell(args.workload, rehearsal=args.rehearsal)
    p = cell["train_dsa"]
    harness.configure_cache()
    cfg, model = harness.build_model(config)
    devices = harness.claim_devices(cell["chips"], rehearsal=args.rehearsal)
    mesh = parallel.make_mesh(MeshConfig(), devices=devices)
    seq_len = min(p["seq_len"], cfg.max_seq_len)
    init = jax.jit(model.init)
    said = lambda r: (
        f"logits rms {r['rms']:.5f} max {r['max']:.3f} of its std, main "
        f"loss relative {r['loss_error']:.2e}, indexer loss relative "
        f"{r['indexer_loss_error']:.2e}, selection agreement "
        f"{r['selection_agreement']}")
    controls = ([] if args.no_everywhere else [None]) + [
        tuple(o.split("+")) for o in args.only]
    for seed in args.seeds:
        params = init(jax.random.key(seed),
                      jnp.zeros((1, 8), jnp.int32))["params"]
        batch = train_dsa.make_pool(seed, 1, cell["chips"], seq_len,
                                    cfg.vocab_size, p["successors"])[0]
        compare = lambda **kw: train_dsa.compare_with_reference(
            model, config["model"], params, batch, mesh, **kw)
        got = compare()
        print(f"[probe] seed {seed}: program (bf16 compute) against the "
              f"float32 reference: {said(got)} (main {got['loss']:.5f} / "
              f"{got['reference_loss']:.5f}, indexer "
              f"{got['indexer_loss']:.5f} / "
              f"{got['reference_indexer_loss']:.5f})", flush=True)
        for only in controls:
            low = compare(dtype=jnp.float8_e4m3fn, only=only)
            print(f"[probe] seed {seed}: reference with fp8 e4m3 matmul "
                  f"inputs ({'+'.join(only) if only else 'everywhere'}) "
                  f"against the same: {said(low)}", flush=True)
        del params
    print(f"[probe] limits {train_dsa.LOGITS_RMS_TOLERANCE} (logits rms), "
          f"{train_dsa.SELECTION_AGREEMENT_MIN} (selection agreement, at "
          f"least), {train_dsa.LOSS_TOLERANCE} (main loss), "
          f"{train_dsa.INDEXER_LOSS_TOLERANCE} (indexer loss)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
