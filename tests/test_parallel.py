"""Distributed tests on the virtual 8-device CPU mesh (SURVEY.md §4d):
data-parallel equivalence to single-device, tensor-parallel sharding rules,
ring-attention exactness, and the combined dp x tp train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pytorch_vit_paper_replication_tpu import engine, parallel
from pytorch_vit_paper_replication_tpu.configs import (
    MeshConfig, TrainConfig, ViTConfig)
from pytorch_vit_paper_replication_tpu.data import synthetic_batch
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.optim import make_optimizer


def _make_state(cfg, total_steps=10, seed=0):
    model = ViT(cfg)
    rng = jax.random.key(seed)
    x = jnp.zeros((1, cfg.image_size, cfg.image_size, 3))
    params = model.init(rng, x)["params"]
    tx = make_optimizer(TrainConfig(warmup_fraction=0.1), total_steps)
    return engine.TrainState.create(
        apply_fn=model.apply, params=params, tx=tx, rng=rng)


def test_mesh_construction(devices):
    mesh = parallel.make_mesh(MeshConfig(data=4, model=2, seq=1))
    assert mesh.shape == {"data": 4, "model": 2, "seq": 1, "pipe": 1}
    mesh2 = parallel.make_mesh(MeshConfig(data=-1, model=2))
    assert mesh2.shape["data"] == 4


def test_mesh_bad_factorization(devices):
    with pytest.raises(ValueError):
        parallel.make_mesh(MeshConfig(data=3, model=2, seq=1))


def test_tp_rules_cover_vit_params(tiny_config):
    """Every encoder matmul is sharded; LN/embeddings/head replicated."""
    state_like = _make_state(tiny_config).params
    pspecs = parallel.tree_pspecs(state_like)
    blk = pspecs["backbone"]["encoder_block_0"]
    assert blk["msa"]["qkv"]["kernel"] == P(None, None, "model", None)
    assert blk["msa"]["out"]["kernel"] == P("model", None, None)
    assert blk["mlp"]["fc1"]["kernel"] == P(None, "model")
    assert blk["mlp"]["fc2"]["kernel"] == P("model", None)
    assert pspecs["backbone"]["encoder_norm"]["scale"] == P()
    assert pspecs["head"]["kernel"] == P()
    pe = pspecs["backbone"]["patch_embedding"]
    assert pe["pos_embedding"] == P()


def test_rules_apply_to_opt_state(tiny_config):
    """Adam mu/nu carry the same sub-paths, so TP rules shard them too —
    optimizer state memory scales down with the model axis."""
    state = _make_state(tiny_config)
    pspecs = parallel.tree_pspecs(state)
    # opt_state -> chain -> scale_by_adam state (mu) mirrors params paths.
    found = []
    jax.tree_util.tree_map_with_path(
        lambda path, leaf: found.append(
            parallel.pspec_for_path(path, leaf)) if any(
                getattr(k, "key", None) == "fc1" for k in path) else None,
        state.opt_state)
    assert any(spec == P(None, "model") for spec in found)


def test_validate_tp_divisibility(devices):
    mesh = parallel.make_mesh(MeshConfig(data=2, model=4))
    cfg = ViTConfig(image_size=32, patch_size=8, num_heads=2,
                    embedding_dim=32, mlp_size=64, num_layers=1,
                    dtype="float32")
    with pytest.raises(ValueError, match="num_heads"):
        parallel.validate_tp_divisibility(cfg, mesh)


def test_data_parallel_matches_single_device(tiny_config, devices):
    """DP over 8 devices computes the same loss/update as one device —
    gradient psum semantics equal the reference's full-batch step."""
    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        16, tiny_config.image_size, tiny_config.num_classes))

    # Single-device baseline.
    state1 = _make_state(tiny_config)
    step1 = jax.jit(engine.make_train_step())
    state1, m1 = step1(state1, batch)

    # 8-way data parallel.
    mesh = parallel.make_mesh(MeshConfig(data=8))
    state8 = parallel.shard_train_state(_make_state(tiny_config), mesh)
    step8 = parallel.make_parallel_train_step(state8, mesh)
    state8, m8 = step8(state8, parallel.shard_batch(batch, mesh))

    np.testing.assert_allclose(
        float(m1["loss_sum"]), float(m8["loss_sum"]), rtol=1e-4)
    l1 = jax.tree.leaves(jax.device_get(state1.params))
    l8 = jax.tree.leaves(jax.device_get(state8.params))
    for a, b in zip(l1, l8):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def test_tensor_parallel_matches_single_device(tiny_config, devices):
    """dp=4 x tp=2: same numerics, params physically sharded over 'model'."""
    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        8, tiny_config.image_size, tiny_config.num_classes))
    state1 = _make_state(tiny_config)
    step1 = jax.jit(engine.make_train_step())
    state1, m1 = step1(state1, batch)

    mesh = parallel.make_mesh(MeshConfig(data=4, model=2))
    parallel.validate_tp_divisibility(tiny_config, mesh)
    state_tp = parallel.shard_train_state(_make_state(tiny_config), mesh)
    # fc1 kernel really is sharded over the model axis.
    fc1 = state_tp.params["backbone"]["encoder_block_0"]["mlp"]["fc1"]["kernel"]
    assert fc1.sharding.spec == P(None, "model")

    step_tp = parallel.make_parallel_train_step(state_tp, mesh)
    state_tp, mtp = step_tp(state_tp, parallel.shard_batch(batch, mesh))
    np.testing.assert_allclose(
        float(m1["loss_sum"]), float(mtp["loss_sum"]), rtol=1e-4)
    a = jax.device_get(state1.params["backbone"]["encoder_block_0"]["mlp"]
                       ["fc1"]["kernel"])
    # Re-read from the post-step state (the pre-step array was donated).
    b = jax.device_get(state_tp.params["backbone"]["encoder_block_0"]["mlp"]
                       ["fc1"]["kernel"])
    np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def test_ring_attention_exact(devices):
    """Ring attention over the 'seq' axis equals full attention."""
    mesh = parallel.make_mesh(MeshConfig(data=1, model=1, seq=8))
    b, t, h, d = 2, 64, 2, 16   # t divisible by seq=8
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    ref = jax.nn.dot_product_attention(q, k, v)
    ring = parallel.make_ring_attention(mesh)
    out = ring(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_ring_attention_with_dp(devices):
    """SP composes with DP on a 2x1x4 mesh."""
    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    b, t, h, d = 4, 32, 2, 16
    ks = jax.random.split(jax.random.key(1), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    ref = jax.nn.dot_product_attention(q, k, v)
    out = parallel.make_ring_attention(mesh)(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_ragged_eval_batch_padded_dp(tiny_config, devices):
    """A ragged eval batch (11 examples on dp=8) must work via pad_batch +
    mask and produce example-exact metrics equal to single-device eval."""
    from pytorch_vit_paper_replication_tpu.data import pad_batch

    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        11, tiny_config.image_size, tiny_config.num_classes))
    state1 = _make_state(tiny_config)
    m1 = jax.jit(engine.make_eval_step())(state1, batch)

    mesh = parallel.make_mesh(MeshConfig(data=8))
    state8 = parallel.shard_train_state(_make_state(tiny_config), mesh)
    padded = pad_batch(jax.tree.map(np.asarray, batch), 8)
    assert padded["label"].shape[0] == 16
    m8 = parallel.make_parallel_eval_step(state8, mesh)(
        state8, parallel.shard_batch(padded, mesh))
    assert float(m8["count"]) == 11.0
    np.testing.assert_allclose(float(m1["loss_sum"]),
                               float(m8["loss_sum"]), rtol=1e-4)
    np.testing.assert_allclose(float(m1["correct"]), float(m8["correct"]))


def test_ring_attention_gradient(devices):
    """ppermute/scan are differentiable; the ring backward must equal the
    full-attention backward (VERDICT r1: ring had no gradient coverage)."""
    mesh = parallel.make_mesh(MeshConfig(data=1, model=1, seq=8))
    b, t, h, d = 2, 32, 2, 8
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    ring = parallel.make_ring_attention(mesh)

    def loss_ring(q, k, v):
        return jnp.sum(jnp.sin(ring(q, k, v)))

    def loss_full(q, k, v):
        return jnp.sum(jnp.sin(jax.nn.dot_product_attention(q, k, v)))

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_full = jax.grad(loss_full, argnums=(0, 1, 2))(q, k, v)
    for gr, gf in zip(g_ring, g_full):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gf),
                                   rtol=2e-2, atol=2e-4)


def _gap_config():
    """16 tokens (no CLS), divisible by seq-axis sizes 2/4/8."""
    return ViTConfig(image_size=32, patch_size=8, num_layers=2, num_heads=2,
                     embedding_dim=32, mlp_size=64, num_classes=3,
                     dtype="float32", attention_impl="xla", pool="gap")


def test_fused_mlp_train_step_on_dp_tp_mesh(tiny_config, devices):
    """PRODUCTION numerics multi-device (VERDICT r5 weak #4): the TPU
    default's fused Pallas MLP half-block (interpret mode on CPU —
    identical kernel code) + bf16 compute, jitted over the dp=4 x tp=2
    mesh. The reference for the loss is the SAME fused config on a
    single device: the mesh must not change the numerics (up to bf16
    reduction-order noise). Dropout is off for the equivalence: the
    fused kernel's positional-hash masks key on grid-LOCAL row indices,
    which differ between the sharded and single-device layouts (same
    statistics, different draws — the documented mask-stream caveat in
    ops/fused_mlp.py)."""
    fused_cfg = tiny_config.replace(mlp_impl="fused", dtype="bfloat16",
                                    mlp_dropout=0.0,
                                    embedding_dropout=0.0)
    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        8, fused_cfg.image_size, fused_cfg.num_classes))

    state1 = _make_state(fused_cfg)
    step1 = jax.jit(engine.make_train_step())
    state1, m1 = step1(state1, batch)

    mesh = parallel.make_mesh(MeshConfig(data=4, model=2))
    parallel.validate_tp_divisibility(fused_cfg, mesh)
    state_f = parallel.shard_train_state(_make_state(fused_cfg), mesh)
    step_f = parallel.make_parallel_train_step(state_f, mesh)
    state_f, mf = step_f(state_f, parallel.shard_batch(batch, mesh))

    loss1 = float(m1["loss_sum"]) / float(m1["count"])
    loss_f = float(mf["loss_sum"]) / float(mf["count"])
    assert 0.0 < loss_f < 20.0, loss_f
    # bf16 compute: per-example losses are summed in different orders
    # under dp sharding, so the tolerance is bf16-scale, not f32-scale.
    np.testing.assert_allclose(loss1, loss_f, rtol=2e-2)
    # One optimizer step really applied on the sharded fused path.
    assert int(state_f.step) == 1


def test_seq_parallel_train_step_matches_single_device(devices):
    """A full ViT train step on a data=2 x seq=4 mesh routes attention
    through the ring (ops.partition.on_mesh) and produces the
    same loss and parameter update as one device."""
    cfg = _gap_config()
    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        8, cfg.image_size, cfg.num_classes))

    state1 = _make_state(cfg)
    step1 = jax.jit(engine.make_train_step())
    state1, m1 = step1(state1, batch)

    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    parallel.validate_mesh_for_config(cfg, mesh)
    state_sp = parallel.shard_train_state(_make_state(cfg), mesh)
    step_sp = parallel.make_parallel_train_step(state_sp, mesh)
    state_sp, msp = step_sp(state_sp, parallel.shard_batch(batch, mesh))

    np.testing.assert_allclose(
        float(m1["loss_sum"]), float(msp["loss_sum"]), rtol=1e-4)
    l1 = jax.tree.leaves(jax.device_get(state1.params))
    lsp = jax.tree.leaves(jax.device_get(state_sp.params))
    for a, b in zip(l1, lsp):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-5)


def test_seq_parallel_composes_with_tp(devices):
    """dp=2 x tp=2 x sp=2: heads shard over 'model' inside the ring
    shard_map, tokens over 'seq' — one step, same numerics."""
    cfg = _gap_config()
    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        4, cfg.image_size, cfg.num_classes))
    state1 = _make_state(cfg)
    state1, m1 = jax.jit(engine.make_train_step())(state1, batch)

    mesh = parallel.make_mesh(MeshConfig(data=2, model=2, seq=2))
    parallel.validate_mesh_for_config(cfg, mesh)
    state3 = parallel.shard_train_state(_make_state(cfg), mesh)
    step3 = parallel.make_parallel_train_step(state3, mesh)
    state3, m3 = step3(state3, parallel.shard_batch(batch, mesh))
    np.testing.assert_allclose(
        float(m1["loss_sum"]), float(m3["loss_sum"]), rtol=1e-4)


def test_seq_parallel_eval_step(devices):
    """Eval also routes through the ring and stays example-exact."""
    cfg = _gap_config()
    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        8, cfg.image_size, cfg.num_classes))
    state1 = _make_state(cfg)
    m1 = jax.jit(engine.make_eval_step())(state1, batch)

    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    state_sp = parallel.shard_train_state(_make_state(cfg), mesh)
    msp = parallel.make_parallel_eval_step(state_sp, mesh)(
        state_sp, parallel.shard_batch(batch, mesh))
    np.testing.assert_allclose(
        float(m1["loss_sum"]), float(msp["loss_sum"]), rtol=1e-4)
    np.testing.assert_allclose(float(m1["correct"]), float(msp["correct"]))


def test_validate_sp_divisibility(devices):
    """CLS pool gives 17 tokens on 32/8 — indivisible by seq=4; the error
    must point at pool='gap'."""
    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    cfg = ViTConfig(image_size=32, patch_size=8, num_layers=1, num_heads=2,
                    embedding_dim=32, mlp_size=64, dtype="float32")
    with pytest.raises(ValueError, match="gap"):
        parallel.validate_sp_divisibility(cfg, mesh)
    parallel.validate_sp_divisibility(_gap_config(), mesh)  # 16 % 4 == 0


def test_grad_accum_composes_with_dp_tp_mesh(tiny_config, devices):
    """optax.MultiSteps adds a params-shaped grad accumulator to
    opt_state; the path-based sharding rules must cover it so accumulation
    works on a dp x tp mesh (effective-batch scaling on few chips)."""
    mesh = parallel.make_mesh(MeshConfig(data=4, model=2))
    model = ViT(tiny_config)
    rng = jax.random.key(0)
    x = jnp.zeros((1, tiny_config.image_size, tiny_config.image_size, 3))
    params = model.init(rng, x)["params"]
    tx = make_optimizer(TrainConfig(warmup_fraction=0.1), 5,
                        grad_accum_steps=2)
    state = engine.TrainState.create(
        apply_fn=model.apply, params=params, tx=tx, rng=rng)
    state = parallel.shard_train_state(state, mesh)
    step = parallel.make_parallel_train_step(state, mesh)
    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        8, tiny_config.image_size, tiny_config.num_classes))

    p0 = jax.device_get(state.params)
    state, _ = step(state, parallel.shard_batch(batch, mesh))
    p1 = jax.device_get(state.params)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(p0), jax.tree.leaves(p1)))   # micro-step 1: no update
    state, m = step(state, parallel.shard_batch(batch, mesh))
    p2 = jax.device_get(state.params)
    assert not all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(p1), jax.tree.leaves(p2)))   # micro-step 2: update
    assert np.isfinite(float(m["loss_sum"]))


# --- in-ring attention dropout (round 3) -----------------------------------


def _recover_ring_mask(mesh, b, h, t, rate, rng):
    """v=identity trick: with q=k=0 the ring's output rows ARE the dropped
    attention-weight rows (M * (1/t) / keep) — zero exactly where
    dropped."""
    z = jnp.zeros((b, t, h, t), jnp.float32)
    eye = jnp.broadcast_to(jnp.eye(t, dtype=jnp.float32)[None, :, None, :],
                           (b, t, h, t))
    ring = parallel.make_ring_attention(mesh, dropout_rate=rate,
                                        dropout_rng=rng,
                                        deterministic=False)
    weights = np.asarray(ring(z, z, eye)).transpose(0, 2, 1, 3)  # [B,H,T,T]
    return weights > 0.0, weights


def test_ring_dropout_mask_statistics(devices):
    """In-ring dropout drops at the quantized rate with exact unbiased
    survivor rescale, and masks differ across (example, head)."""
    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    rate, b, h, t = 0.25, 2, 2, 128           # threshold 64, keep 0.75
    mask, weights = _recover_ring_mask(mesh, b, h, t, rate,
                                       jax.random.key(5))
    frac = 1.0 - mask.mean()
    assert abs(frac - 0.25) < 0.015, f"drop fraction {frac}"
    np.testing.assert_allclose(weights[mask], (1.0 / t) / 0.75, rtol=1e-5)
    assert (mask[0, 0] != mask[0, 1]).mean() > 0.1   # heads differ
    assert (mask[0, 0] != mask[1, 0]).mean() > 0.1   # examples differ


def test_ring_dropout_matches_masked_reference_and_grads(devices):
    """EXACT fwd+bwd check: recover the ring's own mask (a pure function
    of (seed, example·head, global row/col) — independent of q/k/v), build
    the explicit masked-softmax reference, require outputs and all three
    gradients to agree. Also pins topology-invariance: the same seed on a
    different ring size must produce the same mask."""
    rate, b, t, h, d = 0.25, 2, 128, 2, 16
    rng = jax.random.key(7)
    mesh4 = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    mask, _ = _recover_ring_mask(mesh4, b, h, t, rate, rng)
    mask2, _ = _recover_ring_mask(
        parallel.make_mesh(MeshConfig(data=2, model=2, seq=2)),
        b, h, t, rate, rng)
    np.testing.assert_array_equal(mask, mask2)   # layout-independent
    mask = jnp.asarray(mask)

    ks = jax.random.split(jax.random.key(8), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    ring = parallel.make_ring_attention(mesh4, dropout_rate=rate,
                                        dropout_rng=rng,
                                        deterministic=False)

    def ring_loss(args):
        return (ring(*args).astype(jnp.float32) ** 2).sum()

    def ref_loss(args):
        q, k, v = args
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        z = jnp.where(mask, p, 0.0) / 0.75
        return (jnp.einsum("bhqk,bkhd->bqhd", z, v) ** 2).sum()

    np.testing.assert_allclose(ring_loss((q, k, v)), ref_loss((q, k, v)),
                               rtol=1e-4)
    g = jax.grad(ring_loss)((q, k, v))
    g_ref = jax.grad(ref_loss)((q, k, v))
    for name, a, r in zip("qkv", g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=2e-3,
                                   atol=2e-4, err_msg=f"d{name}")


def test_sequence_parallel_dispatch_runs_dropout_in_ring(devices):
    """attn dropout no longer forces the sequence-parallel fallback: under
    the context the call must go through the ring (different rngs give
    different outputs; deterministic matches the no-dropout ring)."""
    from pytorch_vit_paper_replication_tpu.ops.attention import (
        dot_product_attention)
    from pytorch_vit_paper_replication_tpu.ops import on_mesh

    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    b, t, h, d = 2, 32, 2, 16
    ks = jax.random.split(jax.random.key(2), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, d)) for kk in ks)
    with on_mesh(mesh):
        a1 = dot_product_attention(q, k, v, dropout_rate=0.3,
                                   dropout_rng=jax.random.key(1),
                                   deterministic=False)
        a2 = dot_product_attention(q, k, v, dropout_rate=0.3,
                                   dropout_rng=jax.random.key(2),
                                   deterministic=False)
        det = dot_product_attention(q, k, v, dropout_rate=0.3,
                                    deterministic=True)
    assert not np.allclose(np.asarray(a1), np.asarray(a2))
    ref = jax.nn.dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(det), np.asarray(ref), rtol=2e-2,
                               atol=2e-2)


def test_ring_and_flash_dropout_masks_identical(devices):
    """The positional-hash mask is THE same function in both accelerated
    paths (ops.dropout.positional_keep_u8): for equal (seed, example·head,
    row, col) the flash kernel and the ring must drop the exact same
    attention weights."""
    from test_ops import _recover_drop_mask

    rate, b, h, t = 0.25, 2, 2, 128
    rng = jax.random.key(21)
    flash_mask, _ = _recover_drop_mask(rng, b, h, t, rate)   # [b*h, t, t]
    mesh = parallel.make_mesh(MeshConfig(data=2, model=1, seq=4))
    ring_mask, _ = _recover_ring_mask(mesh, b, h, t, rate, rng)  # [b,h,t,t]
    np.testing.assert_array_equal(ring_mask.reshape(b * h, t, t),
                                  flash_mask)
