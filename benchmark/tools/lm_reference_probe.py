"""The two readings a token cell's reference limit is set from
(no part of a run): on the chip, at the cell's sizes, with seeded random
weights, (1) the program's bf16 forward against the float32 reference
and (2) the reference with its matmul inputs rounded to fp8 e4m3 — the
nearest precision below the one the configuration states — against the
same reference. The limit lies between them; PERF.md section 5 gives
both. Also (3), (4): the same rounding confined to the products of one
kernel (the attention core's; the held experts'), which is what a fault
in that kernel alone would read.

    python3 benchmark/tools/lm_reference_probe.py --workload st21b_train_16k --seed 7
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent
sys.path.insert(0, str(ROOT))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="st21b_train_16k")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_lm
    from benchmark.lib import harness
    from pytorch_vit_paper_replication_tpu import parallel
    from pytorch_vit_paper_replication_tpu.configs import MeshConfig

    cell, config = harness.load_cell(args.workload, rehearsal=args.rehearsal)
    p = cell["train_lm"]
    harness.configure_cache()
    cfg, model = harness.build_model(config)
    devices = harness.claim_devices(cell["chips"], rehearsal=args.rehearsal)
    mesh = parallel.make_mesh(MeshConfig(), devices=devices)
    params = jax.jit(model.init)(
        jax.random.key(args.seed), jnp.zeros((1, 8), jnp.int32))["params"]
    seq_len = min(p["seq_len"], cfg.max_seq_len)
    batch = train_lm.make_pool(args.seed, 1, cell["chips"], seq_len,
                               cfg.vocab_size, p["successors"])[0]
    got = train_lm.compare_with_reference(model, config["model"], params,
                                          batch, mesh)
    low = {only: train_lm.compare_with_reference(
        model, config["model"], params, batch, mesh,
        dtype=jnp.float8_e4m3fn, only=only)
        for only in (None, "attn_core", "experts")}
    print(f"[probe] seed {args.seed}: program (bf16 compute) against the "
          f"float32 reference: logits rms {got['rms']:.5f} max "
          f"{got['max']:.3f} of its std, loss {got['loss']:.5f} against "
          f"{got['reference_loss']:.5f} (relative {got['loss_error']:.2e})"
          " | reference with fp8 e4m3 matmul inputs against the same: "
          + "; ".join(
              f"{only or 'everywhere'}: logits rms {r['rms']:.5f} max "
              f"{r['max']:.3f}, loss relative {r['loss_error']:.2e}"
              for only, r in low.items())
          + f" | limits {train_lm.LOGITS_RMS_TOLERANCE} and "
          f"{train_lm.LOSS_TOLERANCE}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
