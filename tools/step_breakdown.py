"""Post-fusion train-step itemization (r4 VERDICT #1).

Where the ~306 ms ViT-B/16 step goes AFTER the fused-MLP round: component
costs measured by ablation of the jitted train step (fwd+bwd+clip+Adam,
bf16, bs 256, dropout on, unsafe_rbg — the bench.py headline config).

Method: each variant rebuilds and re-jits the full step with ONE component
surgically removed, so `cost(component) = T(full) - T(without it)`:

* MLP half-blocks   — `ops.fused_mlp.fused_ln_mlp_residual` patched to
                      identity (params stay declared, so optimizer/donation
                      shape is unchanged; the kernel and its backward drop
                      out of the program).
* attention core    — `models.vit.dot_product_attention` patched to return
                      q (QK^T + softmax + PV removed; LN/qkv/out
                      projections and their backward kept).
* MSA half          — attention-core patch PLUS qkv/out projections
                      removed via a zero-layer delta: computed as
                      per-layer total minus the MLP half.
* patchify+head     — `num_layers=0` model (keeps embed dropout,
                      encoder_norm, pool, head, loss; optimizer runs on
                      the small param set — noted, Adam totals ~3 ms).
* dropout           — all rates 0.
* optimizer chain   — tx = optax.scale(0) instead of clip/L2/Adam/LR.

Timing: 3 warm steps, then best-of-reps over timed chains of `--steps`
steps, fenced by a device->host metric readback.

Usage (on the TPU host):  python tools/step_breakdown.py [--steps 20]
Prints one JSON object; the PERF.md table is derived from it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax
import jax.numpy as jnp


def time_step(make_state_and_step, steps: int, reps: int = 3) -> float:
    """ms/step of a jitted (state, batch) -> (state, metrics) step."""
    state, step, batch = make_state_and_step()
    for _ in range(3):
        state, metrics = step(state, batch)
    float(jax.tree.leaves(metrics)[0])
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(steps):
            state, metrics = step(state, batch)
        float(jax.tree.leaves(metrics)[0])
        best = min(best, (time.perf_counter() - t0) / steps)
    # Free the variant's state before the next one compiles (B/16 + Adam
    # is ~1.2 GB; two resident copies + a compile spike can OOM).
    del state, batch, step
    import gc
    gc.collect()
    return best * 1e3


def build(cfg_kwargs=None, dropout_on=True, trivial_tx=False,
          fwd_only=False, batch_size=256):
    """Returns a thunk creating (state, jitted step, device batch)."""

    def thunk():
        import optax

        from pytorch_vit_paper_replication_tpu import configs, engine
        from pytorch_vit_paper_replication_tpu.configs import TrainConfig
        from pytorch_vit_paper_replication_tpu.data import synthetic_batch
        from pytorch_vit_paper_replication_tpu.models import ViT
        from pytorch_vit_paper_replication_tpu.optim import make_optimizer

        kw = dict(num_classes=1000, dtype="bfloat16")
        kw.update(cfg_kwargs or {})
        cfg = configs.vit_b16(**kw)
        if not dropout_on:
            cfg = cfg.replace(attn_dropout=0.0, mlp_dropout=0.0,
                              embedding_dropout=0.0)
        model = ViT(cfg)
        rng = jax.random.key(0, impl="unsafe_rbg")
        params = model.init(
            rng, jnp.zeros((1, cfg.image_size, cfg.image_size, 3)))["params"]
        tx = (optax.scale(0.0) if trivial_tx
              else make_optimizer(TrainConfig(), total_steps=10_000))
        state = engine.TrainState.create(
            apply_fn=model.apply, params=params, tx=tx, rng=rng)
        if fwd_only:
            def step_fn(state, batch):  # loss only: no grad, no update
                logits = state.apply_fn(
                    {"params": state.params}, batch["image"], True,
                    rngs={"dropout": jax.random.fold_in(state.rng,
                                                        state.step)})
                loss = engine.cross_entropy_loss(logits, batch["label"])
                return state.replace(step=state.step + 1), \
                    {"loss_sum": loss}
            step = jax.jit(step_fn)
        else:
            step = jax.jit(engine.make_train_step(), donate_argnums=0)
        batch = jax.device_put(jax.tree.map(jnp.asarray, synthetic_batch(
            batch_size, cfg.image_size, cfg.num_classes)))
        return state, step, batch

    return thunk


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--only", type=str, default=None,
                    help="comma-separated variant names to (re)run; the "
                         "derived table is only computed on a full run")
    args = ap.parse_args()
    bs = args.batch_size
    only = set(args.only.split(",")) if args.only else None

    import importlib

    import pytorch_vit_paper_replication_tpu.models.vit as vit_mod

    # `ops/__init__` re-exports the fused_mlp FUNCTION, which shadows the
    # submodule on attribute lookup — resolve the module explicitly.
    fm = importlib.import_module(
        "pytorch_vit_paper_replication_tpu.ops.fused_mlp")

    out = {}

    def run(name, **kw):
        if only is not None and name not in only:
            return
        out[name] = round(time_step(build(batch_size=bs, **kw),
                                    args.steps), 2)
        print(f"[breakdown] {name}: {out[name]} ms/step", flush=True)

    run("full")
    run("full_fwd_only", fwd_only=True)
    run("no_dropout", dropout_on=False)
    run("trivial_update", trivial_tx=True)
    run("layers_0", cfg_kwargs={"num_layers": 0})
    run("layers_6", cfg_kwargs={"num_layers": 6})

    # Attention core -> identity (projections kept).
    orig_attn = vit_mod.dot_product_attention
    vit_mod.dot_product_attention = lambda q, k, v, **kw: q
    try:
        run("attn_core_identity")
        run("attn_core_identity_fwd", fwd_only=True)
    finally:
        vit_mod.dot_product_attention = orig_attn

    # MLP half-block -> identity (params declared, kernel+backward gone).
    orig_fused = fm.fused_ln_mlp_residual
    fm.fused_ln_mlp_residual = lambda x, *a, **kw: x
    try:
        run("mlp_half_identity")
        run("mlp_half_identity_fwd", fwd_only=True)
    finally:
        fm.fused_ln_mlp_residual = orig_fused

    # Derived itemization (ms/step).
    if only is not None:
        print(json.dumps(out, indent=2))
        return
    full = out["full"]
    per_layer = (full - out["layers_0"]) / 12.0
    mlp_half = full - out["mlp_half_identity"]
    attn_core = full - out["attn_core_identity"]
    layers_total = full - out["layers_0"]
    msa_half = layers_total - mlp_half
    out["derived"] = {
        "per_layer_ms": round(per_layer, 2),
        "layers_linear_check_6": round(
            out["layers_0"] + 6 * per_layer, 1),
        "encoder_total": round(layers_total, 2),
        "mlp_half_total": round(mlp_half, 2),
        "msa_half_total": round(msa_half, 2),
        "attn_core": round(attn_core, 2),
        "msa_projections": round(msa_half - attn_core, 2),
        "patch_embed_head_loss": round(out["layers_0"], 2),
        "optimizer_chain": round(full - out["trivial_update"], 2),
        "dropout_total": round(full - out["no_dropout"], 2),
        "backward_total": round(full - out["full_fwd_only"], 2),
        "mlp_half_fwd": round(
            out["full_fwd_only"] - out["mlp_half_identity_fwd"], 2),
        "attn_core_fwd": round(
            out["full_fwd_only"] - out["attn_core_identity_fwd"], 2),
        # Components that partition the step (dropout lives inside its
        # halves; optimizer overlaps layers_0's small-param update):
        "sum_partition": round(
            msa_half + mlp_half + out["layers_0"]
            + (full - out["trivial_update"]), 2),
        "sum_vs_full_pct": round(100.0 * (
            msa_half + mlp_half + out["layers_0"]
            + (full - out["trivial_update"])) / full - 100.0, 2),
    }
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
