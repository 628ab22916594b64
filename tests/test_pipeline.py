"""Pipeline parallelism (parallel/pipeline.py): layout conversion, exact
forward/step parity with the standard per-layer model, dp x pp composition,
and the CLI path — on the virtual 8-device CPU mesh."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_vit_paper_replication_tpu import engine, parallel
from pytorch_vit_paper_replication_tpu.configs import (
    MeshConfig, TrainConfig, ViTConfig)
from pytorch_vit_paper_replication_tpu.data import synthetic_batch
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.optim import make_optimizer


# Dropout off: the exact-parity tests compare against the standard model,
# and pipeline dropout draws DIFFERENT (equally valid) masks by design —
# covered separately by test_pipeline_dropout_trains_and_varies.
CFG = ViTConfig(image_size=32, patch_size=8, num_layers=4, num_heads=2,
                embedding_dim=32, mlp_size=64, num_classes=3,
                dtype="float32", attention_impl="xla", attn_dropout=0.0,
                mlp_dropout=0.0, embedding_dropout=0.0)


def _params(seed=1):
    return ViT(CFG).init(jax.random.key(seed),
                         jnp.zeros((1, 32, 32, 3)))["params"]


def test_stack_unstack_roundtrip():
    params = _params()
    stacked = parallel.stack_block_params(params, CFG.num_layers)
    assert "encoder_block_0" not in stacked["backbone"]
    lead = jax.tree.leaves(stacked[parallel.pipeline.BLOCKS_KEY])[0]
    assert lead.shape[0] == CFG.num_layers
    back = parallel.unstack_block_params(stacked)
    fa = jax.tree_util.tree_leaves_with_path(params)
    fb = dict(jax.tree_util.tree_leaves_with_path(back))
    assert len(fa) == len(fb)
    for path, leaf in fa:
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(fb[path]))


def test_pipeline_forward_matches_standard(devices):
    """dp=2 x pipe=4, M=2 microbatches: deterministic pipelined logits
    equal the per-layer model's (same modules, same params, staged)."""
    params = _params()
    x = jax.random.normal(jax.random.key(0), (8, 32, 32, 3))
    ref = ViT(CFG).apply({"params": params}, x, False)
    mesh = parallel.make_mesh(MeshConfig(data=2, pipe=4))
    apply_fn = parallel.make_pipeline_apply(CFG, mesh, num_microbatches=2)
    out = apply_fn(
        {"params": parallel.stack_block_params(params, CFG.num_layers)},
        x, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_pipeline_train_step_matches_standard(devices):
    """THREE full optimizer steps through the GPipe schedule (grads flow
    through scan + ppermute + psum) equal the single-device trajectory —
    three so the layout-aware weight-decay mask matters: with the naive
    ndim>1 rule the stacked 2-D biases/LN params would decay and drift
    past tolerance (round-3 review finding)."""
    params = _params()
    batch = jax.tree.map(jnp.asarray, synthetic_batch(8, 32, 3))
    tx = make_optimizer(TrainConfig(warmup_fraction=0.1), 10)

    s1 = engine.TrainState.create(apply_fn=ViT(CFG).apply, params=params,
                                  tx=tx, rng=jax.random.key(2))
    step1 = jax.jit(engine.make_train_step())

    mesh = parallel.make_mesh(MeshConfig(data=2, pipe=4))
    parallel.validate_pipeline(CFG, mesh, 2, 8)
    tx_pp = make_optimizer(TrainConfig(warmup_fraction=0.1), 10,
                           decay_mask_fn=parallel.pipeline_decay_mask)
    sp = engine.TrainState.create(
        apply_fn=parallel.make_pipeline_apply(CFG, mesh,
                                              num_microbatches=2),
        params=parallel.stack_block_params(params, CFG.num_layers),
        tx=tx_pp, rng=jax.random.key(2))
    sp = parallel.shard_train_state(sp, mesh)
    # Stacked block params are sharded over 'pipe' on the layer axis (the
    # TP rule rides along one axis right; 'model' is size 1 here).
    from jax.sharding import PartitionSpec as P
    qkv = sp.params[parallel.pipeline.BLOCKS_KEY]["msa"]["qkv"]["kernel"]
    assert qkv.sharding.spec == P("pipe", None, None, "model", None)
    step_pp = parallel.make_parallel_train_step(sp, mesh)

    pbatch = parallel.shard_batch(batch, mesh)
    for _ in range(3):
        s1, m1 = step1(s1, batch)
        sp, mp = step_pp(sp, pbatch)
        np.testing.assert_allclose(float(m1["loss_sum"]),
                                   float(mp["loss_sum"]), rtol=1e-5)

    back = parallel.unstack_block_params(jax.device_get(sp.params))
    ref_leaves = dict(jax.tree_util.tree_leaves_with_path(
        jax.device_get(s1.params)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(back):
        key = jax.tree_util.keystr(path)
        # The K-projection bias has analytically zero gradient (softmax
        # shift invariance — test_recipe_parity.py proves it), so Adam
        # amplifies fp32 reduction-order noise there; everything else —
        # including the LN scales whose ~1e-3/step drift is the
        # decay-mask regression signal — stays tight.
        # Bound: a few lr-sized (1e-3) random-walk steps; a genuine
        # layout/mapping bug would diverge by O(weight scale) ~ 0.1.
        atol = 5e-3 if key.endswith("['qkv']['bias']") else 1e-6
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(ref_leaves[path]), rtol=1e-5,
            atol=atol, err_msg=key)


def test_pipeline_decay_mask_matches_standard_rule():
    """Stacked biases/LN params (2-D with the [L] axis) must NOT decay;
    stacked kernels must — elementwise equal to the standard-layout mask
    after stacking."""
    from pytorch_vit_paper_replication_tpu.optim import decay_mask

    params = _params()
    std = parallel.stack_block_params(
        jax.tree.map(lambda m: jnp.asarray(m), decay_mask(params)),
        CFG.num_layers)
    pp_mask = parallel.pipeline_decay_mask(
        parallel.stack_block_params(params, CFG.num_layers))
    for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(std),
            jax.tree_util.tree_leaves_with_path(pp_mask)):
        assert pa == pb
        assert bool(np.asarray(a).all()) == bool(b), jax.tree_util.keystr(pa)


def test_pipeline_dropout_trains_and_varies(devices):
    """Dropout through the pipeline: masks differ across steps (rng folds
    step), loss stays finite and decreases over a few steps of overfitting
    one batch."""
    import dataclasses

    cfg = dataclasses.replace(CFG, mlp_dropout=0.1, embedding_dropout=0.1)
    params = ViT(cfg).init(jax.random.key(1),
                           jnp.zeros((1, 32, 32, 3)))["params"]
    mesh = parallel.make_mesh(MeshConfig(data=2, pipe=4))
    tx = make_optimizer(TrainConfig(warmup_fraction=0.0), 8)
    state = engine.TrainState.create(
        apply_fn=parallel.make_pipeline_apply(cfg, mesh,
                                              num_microbatches=2),
        params=parallel.stack_block_params(params, cfg.num_layers),
        tx=tx, rng=jax.random.key(4))
    state = parallel.shard_train_state(state, mesh)
    step = parallel.make_parallel_train_step(state, mesh)
    batch = parallel.shard_batch(
        jax.tree.map(jnp.asarray, synthetic_batch(8, 32, 3)), mesh)
    losses = []
    for _ in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss_sum"]) / float(m["count"]))
    assert all(math.isfinite(x) for x in losses)
    assert losses[-1] < losses[0]


def test_validate_pipeline_rejects_bad_configs(devices):
    mesh = parallel.make_mesh(MeshConfig(data=2, pipe=4))
    with pytest.raises(ValueError, match="num_layers"):
        parallel.validate_pipeline(
            ViTConfig(num_layers=3, dtype="float32"), mesh, 2, 8)
    with pytest.raises(ValueError, match="microbatches"):
        parallel.validate_pipeline(CFG, mesh, 3, 8)
    mesh_sp = parallel.make_mesh(MeshConfig(data=1, seq=2, pipe=4))
    with pytest.raises(ValueError, match="sequence"):
        parallel.validate_pipeline(CFG, mesh_sp, 2, 8)
    # pp×tp is allowed but still subject to TP divisibility (heads=2, tp=4)
    mesh_tp4 = parallel.make_mesh(MeshConfig(data=1, model=4, pipe=2))
    with pytest.raises(ValueError, match="num_heads"):
        parallel.validate_pipeline(CFG, mesh_tp4, 2, 8)


def test_pipeline_with_tensor_parallel_matches_standard(devices):
    """dp=2 × tp=2 × pp=2 (all three axes at once): manual Megatron psums
    inside the GPipe stages. Biases are perturbed PER-CHANNEL — a uniform
    shift hides bias double-counting behind LayerNorm's shift invariance
    (the exact trap a round-3 probe fell into), so this asserts the
    1/tp-scaled replicated biases reconstruct exactly once. Forward
    logits and a 2-step optimizer trajectory must match the standard
    single-device model."""
    params = jax.tree_util.tree_map_with_path(
        lambda p, a: a + 0.02 * jnp.arange(a.shape[-1]) / max(1, a.shape[-1])
        if jax.tree_util.keystr(p).endswith("['bias']") else a, _params())
    batch = jax.tree.map(jnp.asarray, synthetic_batch(8, 32, 3))
    ref_logits = ViT(CFG).apply({"params": params}, batch["image"], False)

    mesh = parallel.make_mesh(MeshConfig(data=2, model=2, pipe=2))
    parallel.validate_pipeline(CFG, mesh, 2, 8)
    apply_fn = parallel.make_pipeline_apply(CFG, mesh, num_microbatches=2)
    pp = parallel.stack_block_params(params, CFG.num_layers)
    out = apply_fn({"params": pp}, batch["image"], False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_logits),
                               rtol=1e-4, atol=1e-5)
    # Stacked TP leaves carry BOTH axes.
    from jax.sharding import PartitionSpec as P
    specs = parallel.tree_pspecs(pp)[parallel.pipeline.BLOCKS_KEY]
    assert specs["mlp"]["fc1"]["kernel"] == P("pipe", None, "model")

    tx = make_optimizer(TrainConfig(warmup_fraction=0.1), 10)
    s1 = engine.TrainState.create(apply_fn=ViT(CFG).apply, params=params,
                                  tx=tx, rng=jax.random.key(2))
    step1 = jax.jit(engine.make_train_step())
    tx_pp = make_optimizer(TrainConfig(warmup_fraction=0.1), 10,
                           decay_mask_fn=parallel.pipeline_decay_mask)
    sp = engine.TrainState.create(apply_fn=apply_fn, params=pp, tx=tx_pp,
                                  rng=jax.random.key(2))
    sp = parallel.shard_train_state(sp, mesh)
    step_pp = parallel.make_parallel_train_step(sp, mesh)
    pbatch = parallel.shard_batch(batch, mesh)
    for _ in range(2):
        s1, m1 = step1(s1, batch)
        sp, mp = step_pp(sp, pbatch)
        np.testing.assert_allclose(float(m1["loss_sum"]),
                                   float(mp["loss_sum"]), rtol=1e-5)
    back = parallel.unstack_block_params(jax.device_get(sp.params))
    ref_leaves = dict(jax.tree_util.tree_leaves_with_path(
        jax.device_get(s1.params)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(back):
        key = jax.tree_util.keystr(path)
        atol = 5e-3 if key.endswith("['qkv']['bias']") else 1e-6
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(ref_leaves[path]), rtol=1e-5,
            atol=atol, err_msg=key)


def test_cli_pipeline_end_to_end(devices, tmp_path):
    """--mesh-pipe 4 through train.main, incl. a RAGGED eval set (9
    images, batch 8: the final batch must pad to dp*microbatches, not
    just dp) and the standard-layout final export: predict-compatible
    params come out of a pipeline run."""
    from pytorch_vit_paper_replication_tpu.data import (
        make_synthetic_image_folder)
    from pytorch_vit_paper_replication_tpu.train import main as train_main

    train_dir, test_dir = make_synthetic_image_folder(
        tmp_path / "ds", train_per_class=8, test_per_class=3, image_size=32)
    ck = tmp_path / "ckpt"
    results = train_main([
        "--train-dir", str(train_dir), "--test-dir", str(test_dir),
        "--preset", "ViT-Ti/16", "--image-size", "32",
        "--patch-size", "16", "--dtype", "float32", "--attention", "xla",
        "--epochs", "1", "--batch-size", "8",
        "--mesh-data", "2", "--mesh-pipe", "4",
        "--checkpoint-dir", str(ck),
    ])
    assert len(results["train_loss"]) == 1
    assert math.isfinite(results["train_loss"][0])
    # final/ export is standard layout: loadable with a standard template.
    import orbax.checkpoint as ocp

    ckptr = ocp.StandardCheckpointer()
    try:
        exported = ckptr.restore(ck / "final")
    finally:
        ckptr.close()
    assert "encoder_block_0" in exported["backbone"]
    assert parallel.pipeline.BLOCKS_KEY not in exported


def test_pipeline_composes_with_grad_accum(devices):
    """--grad-accum through the pipeline: K micro-steps through the GPipe
    schedule average into one optimizer update, equal to the standard
    model's accumulated update."""
    params = _params()
    tx_kwargs = dict(grad_accum_steps=2)
    tx1 = make_optimizer(TrainConfig(warmup_fraction=0.0), 5, **tx_kwargs)
    s1 = engine.TrainState.create(apply_fn=ViT(CFG).apply, params=params,
                                  tx=tx1, rng=jax.random.key(2))
    step1 = jax.jit(engine.make_train_step())

    mesh = parallel.make_mesh(MeshConfig(data=2, pipe=4))
    tx_pp = make_optimizer(TrainConfig(warmup_fraction=0.0), 5,
                           decay_mask_fn=parallel.pipeline_decay_mask,
                           **tx_kwargs)
    sp = engine.TrainState.create(
        apply_fn=parallel.make_pipeline_apply(CFG, mesh,
                                              num_microbatches=2),
        params=parallel.stack_block_params(params, CFG.num_layers),
        tx=tx_pp, rng=jax.random.key(2))
    sp = parallel.shard_train_state(sp, mesh)
    step_pp = parallel.make_parallel_train_step(sp, mesh)

    b1 = jax.tree.map(jnp.asarray, synthetic_batch(8, 32, 3))
    b2 = jax.tree.map(jnp.asarray, synthetic_batch(8, 32, 3, seed=9))
    for b in (b1, b2):   # one full accumulation group
        s1, _ = step1(s1, b)
        sp, _ = step_pp(sp, parallel.shard_batch(b, mesh))
    back = parallel.unstack_block_params(jax.device_get(sp.params))
    ref_leaves = dict(jax.tree_util.tree_leaves_with_path(
        jax.device_get(s1.params)))
    for path, leaf in jax.tree_util.tree_leaves_with_path(back):
        key = jax.tree_util.keystr(path)
        atol = 5e-3 if key.endswith("['qkv']['bias']") else 1e-6
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(ref_leaves[path]), rtol=1e-5,
            atol=atol, err_msg=key)


def test_pipeline_composes_with_nan_guard(devices):
    """nan_guard through the pipeline: a poisoned batch is skipped (no
    param change, skipped=1), a clean batch still applies."""
    params = _params()
    mesh = parallel.make_mesh(MeshConfig(data=2, pipe=4))
    tx = make_optimizer(TrainConfig(warmup_fraction=0.0), 5,
                        decay_mask_fn=parallel.pipeline_decay_mask)
    state = engine.TrainState.create(
        apply_fn=parallel.make_pipeline_apply(CFG, mesh,
                                              num_microbatches=2),
        params=parallel.stack_block_params(params, CFG.num_layers),
        tx=tx, rng=jax.random.key(2))
    state = parallel.shard_train_state(state, mesh)
    step = parallel.make_parallel_train_step(state, mesh, nan_guard=True)

    bad = jax.tree.map(jnp.asarray, synthetic_batch(8, 32, 3))
    bad = dict(bad, image=bad["image"].at[0, 0, 0, 0].set(jnp.nan))
    before = jax.device_get(jax.tree.leaves(state.params)[0])
    state, m = step(state, parallel.shard_batch(bad, mesh))
    assert float(m["skipped"]) == 1.0
    np.testing.assert_array_equal(
        before, jax.device_get(jax.tree.leaves(state.params)[0]))

    good = jax.tree.map(jnp.asarray, synthetic_batch(8, 32, 3, seed=4))
    state, m = step(state, parallel.shard_batch(good, mesh))
    assert float(m["skipped"]) == 0.0


def test_cli_pipeline_resume_and_eval_only(devices, tmp_path):
    """Pipeline runs share the generic checkpoint machinery: a pipeline
    training run resumes from its (pipeline-layout) checkpoint, and
    --eval-only works against both the step checkpoint and the
    standard-layout final/ export (which is re-stacked on load)."""
    import shutil

    from pytorch_vit_paper_replication_tpu.data import (
        make_synthetic_image_folder)
    from pytorch_vit_paper_replication_tpu.train import main as train_main

    train_dir, test_dir = make_synthetic_image_folder(
        tmp_path / "ds", train_per_class=8, test_per_class=3, image_size=32)
    ck = tmp_path / "ckpt"
    common = [
        "--train-dir", str(train_dir), "--test-dir", str(test_dir),
        "--preset", "ViT-Ti/16", "--image-size", "32",
        "--patch-size", "16", "--dtype", "float32", "--attention", "xla",
        "--batch-size", "8", "--mesh-data", "2", "--mesh-pipe", "4",
        "--num-workers", "1", "--checkpoint-dir", str(ck),
    ]
    r1 = train_main(common + ["--epochs", "1"])
    # Resume: asking for 2 epochs continues from the epoch-1 checkpoint.
    # Extending past the recorded horizon re-scales the LR schedule and
    # needs the explicit opt-in since r5 (--extend-schedule, VERDICT r4
    # #6; the no-flag rejection itself is covered by
    # test_cli.py::test_cli_resume_schedule_horizon_guard).
    r2 = train_main(common + ["--epochs", "2", "--extend-schedule"])
    assert len(r2["train_loss"]) == 1            # only the remaining epoch
    assert r2["train_loss"][0] < r1["train_loss"][0]

    ev = train_main(common + ["--eval-only"])
    np.testing.assert_allclose(ev["test_loss"][0], r2["test_loss"][-1],
                               rtol=1e-6)
    # final/-export fallback: standard layout re-stacked on load.
    for d in ck.iterdir():
        if d.is_dir() and d.name.isdigit():
            shutil.rmtree(d)
    ev2 = train_main(common + ["--eval-only"])
    np.testing.assert_allclose(ev2["test_loss"][0], r2["test_loss"][-1],
                               rtol=1e-6)
