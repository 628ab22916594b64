"""Isolated attention-core A/B: XLA einsum path vs Pallas flash kernel.

Round-5 context: the step breakdown (tools/step_breakdown.py) showed the
attention core (QK^T + softmax + PV) costs ~117 ms of the 307 ms ViT-B/16
step — 38%, dominated by the materialized [B, H, T, T] softmax HBM
traffic, NOT by FLOPs (the attention matmuls are ~4% of step FLOPs).
Round 3 measured the flash kernel "equal-or-slower" than XLA in
isolation and set the dispatch policy to memory-only; this tool
re-measures both paths at the step's exact shapes (and the 384px
transfer shape), fwd+bwd, to decide whether short-sequence dispatch
should prefer the kernel.

Timing: forward value + full vjp with a loop-carried dependency (the
output feeds the next iteration's q) so nothing is dead-code-eliminated;
fenced by a device->host readback.

Usage: python tools/attn_bench.py [--reps 3] [--iters 10]
"""

from __future__ import annotations

import argparse
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp


def time_vjp(fn, q, k, v, iters, reps):
    """ms per fwd+bwd of fn(q, k, v), loop-carried on q."""

    @jax.jit
    def run(q, k, v):
        def body(q, _):
            out, vjp = jax.vjp(fn, q, k, v)
            dq, dk, dv = vjp(out)  # cotangent = out: full bwd, data-dep
            return (q + 0.01 * dq).astype(q.dtype), None

        q, _ = jax.lax.scan(body, q, None, length=iters)
        return jnp.float32(q[0, 0, 0, 0])

    float(run(q, k, v))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(run(q, k, v))
        best = min(best, (time.perf_counter() - t0) / iters)
    return best * 1e3


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--block", type=int, default=None,
                    help="flash block size override (q and k)")
    args = ap.parse_args()

    from pytorch_vit_paper_replication_tpu.ops.attention import (
        _xla_attention)
    from pytorch_vit_paper_replication_tpu.ops.flash_attention import (
        flash_attention)

    xla = functools.partial(_xla_attention, dropout_rate=0.0,
                            dropout_rng=None, deterministic=True)
    fl_kw = {}
    if args.block:
        fl_kw = dict(block_q=args.block, block_k=args.block)
    flash = functools.partial(flash_attention, deterministic=True, **fl_kw)

    out = {}
    # (label, B, T, H, Dh): the B/16 train shape, the 384px transfer
    # shape, and one long-sequence point for continuity with r3.
    shapes = [("b16_224px", 256, 197, 12, 64),
              ("b16_384px", 64, 577, 12, 64),
              ("long_2048", 8, 2048, 12, 64)]
    for label, b, t, h, dh in shapes:
        ks = jax.random.split(jax.random.key(0), 3)
        q, k, v = (jax.random.normal(kk, (b, t, h, dh), jnp.bfloat16)
                   for kk in ks)
        xla_ms = time_vjp(xla, q, k, v, args.iters, args.reps)
        flash_ms = time_vjp(flash, q, k, v, args.iters, args.reps)
        out[label] = {"xla_ms": round(xla_ms, 3),
                      "flash_ms": round(flash_ms, 3),
                      "flash_speedup": round(xla_ms / flash_ms, 3)}
        print(f"[attn] {label} B={b} T={t}: xla {xla_ms:.2f} ms, "
              f"flash {flash_ms:.2f} ms ({xla_ms / flash_ms:.2f}x)",
              flush=True)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
