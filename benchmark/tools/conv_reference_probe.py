"""The readings the conv cell's reference limits are set from (no part
of a run): on the chip, at the cell's sizes, with seeded random weights,
(1) the program's bf16 forward against the float32 reference (logits and
layer 0's conv mixer output) and (2) the reference with its matmul
inputs rounded to fp8 e4m3 -- the nearest precision below the one the
configuration states -- against the same reference, everywhere and
confined to families of products (``reference_conv.FAMILIES``; by
default the conv mixers' two products, ``W_in`` and ``W_out``): what a
fault in that part alone would read. Each limit lies between (1) and (2)
(``drivers/train_conv.py``). PERF.md section 6 gives the readings. The
step's limits are read by the cell itself, under ``TRAIN_CONV_CONTROL``
(``benchmark/README_conv.md``).

    python3 benchmark/tools/conv_reference_probe.py --workload lfm2_train_8k --seeds 7 11

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
    ap.add_argument("--workload", default="lfm2_train_8k")
    ap.add_argument("--seeds", type=int, nargs="+", default=[0])
    ap.add_argument("--only", nargs="*", default=["conv"],
                    help="families to confine the rounding to, in turn "
                         "(several joined by +)")
    ap.add_argument("--no-everywhere", action="store_true",
                    help="skip the control that rounds every product")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_conv
    from benchmark.lib import harness
    from pytorch_vit_paper_replication_tpu import parallel
    from pytorch_vit_paper_replication_tpu.configs import MeshConfig

    cell, config = harness.load_cell(args.workload, rehearsal=args.rehearsal)
    p = cell["train_conv"]
    harness.configure_cache()
    cfg, model = harness.build_model(config)
    devices = harness.claim_devices(cell["chips"], rehearsal=args.rehearsal)
    mesh = parallel.make_mesh(MeshConfig(), devices=devices)
    seq_len = min(p["seq_len"], cfg.max_seq_len)
    init = jax.jit(model.init)
    said = lambda r: (
        f"logits rms {r['rms']:.5f} max {r['max']:.3f} of its std, "
        f"layer 0's conv mixer rms "
        f"{r['conv_rms']:.5f} max {r['conv_max']:.3f} of its std")
    controls = ([] if args.no_everywhere else [None]) + [
        tuple(o.split("+")) for o in args.only]
    for seed in args.seeds:
        params = init(jax.random.key(seed),
                      jnp.zeros((1, 8), jnp.int32))["params"]
        batch = train_conv.make_pool(seed, 1, cell["chips"], seq_len,
                                     cfg.vocab_size, p["successors"])[0]
        compare = lambda **kw: train_conv.compare_with_reference(
            model, config["model"], params, batch, mesh, **kw)
        got = compare()
        print(f"[probe] seed {seed}: program (bf16 compute) against the "
              f"float32 reference: {said(got)}", flush=True)
        for only in controls:
            low = compare(dtype=jnp.float8_e4m3fn, only=only)
            print(f"[probe] seed {seed}: reference with fp8 e4m3 matmul "
                  f"inputs ({'+'.join(only) if only else 'everywhere'}) "
                  f"against the same: {said(low)}", flush=True)
        del params
    print(f"[probe] limits {train_conv.LOGITS_RMS_TOLERANCE} (logits rms), "
          f"{train_conv.CONV_RMS_TOLERANCE} (layer 0's conv mixer rms)",
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
