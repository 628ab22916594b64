"""Fused Pallas MLP kernels (ops/fused_mlp.py) — parity vs the XLA path.

Runs the REAL kernel code under the Pallas interpreter (the wrappers
auto-select interpret mode off-TPU), mirroring how test_ops.py exercises
the flash-attention kernel. Reference semantics: the MLP half of the
encoder block, reference ``models/vit.py:100-131`` (+ residual at :168).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_vit_paper_replication_tpu.configs import vit_ti16
from pytorch_vit_paper_replication_tpu.models.vit import (
    MLPBlock, TransformerEncoderBlock)
from pytorch_vit_paper_replication_tpu.ops.dropout import (
    _threshold, derive_positional_seed, positional_keep_u8, quantized_rate)
from pytorch_vit_paper_replication_tpu.ops.fused_mlp import (
    fused_ln_mlp_residual, fused_mlp)


D, F = 64, 256


def _params(key, d=D, f=F):
    ks = jax.random.split(key, 7)
    return dict(
        x=jax.random.normal(ks[0], (2, 25, d), jnp.float32),
        gamma=1.0 + 0.1 * jax.random.normal(ks[1], (d,)),
        beta=0.1 * jax.random.normal(ks[2], (d,)),
        w1=jax.random.normal(ks[3], (d, f)) * 0.1,
        b1=0.1 * jax.random.normal(ks[4], (f,)),
        w2=jax.random.normal(ks[5], (f, d)) * 0.1,
        b2=0.1 * jax.random.normal(ks[6], (d,)),
    )


def _ref_mlp(x, w1, b1, w2, b2):
    g = jax.nn.gelu(x @ w1 + b1, approximate=False)
    return g @ w2 + b2


def _ref_ln_mlp_res(x, gamma, beta, w1, b1, w2, b2, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    c = x32 - mu
    var = (c * c).mean(-1, keepdims=True)
    y = c * jax.lax.rsqrt(var + eps) * gamma + beta
    return x32 + _ref_mlp(y, w1, b1, w2, b2)


def test_fused_mlp_forward_matches_xla(rng):
    p = _params(rng)
    out = fused_mlp(p["x"], p["w1"], p["b1"], p["w2"], p["b2"])
    ref = _ref_mlp(p["x"], p["w1"], p["b1"], p["w2"], p["b2"])
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_fused_mlp_grads_match_xla(rng):
    p = _params(rng)
    ct = jax.random.normal(jax.random.fold_in(rng, 1), p["x"].shape)
    args = (p["x"], p["w1"], p["b1"], p["w2"], p["b2"])
    g_f = jax.grad(lambda a: (fused_mlp(*a) * ct).sum())(args)
    g_r = jax.grad(lambda a: (_ref_mlp(*a) * ct).sum())(args)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)


def test_fused_ln_mlp_residual_forward(rng):
    p = _params(rng)
    out = fused_ln_mlp_residual(**p)
    ref = _ref_ln_mlp_res(**p)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


def test_fused_ln_mlp_residual_grads(rng):
    p = _params(rng)
    ct = jax.random.normal(jax.random.fold_in(rng, 1), p["x"].shape)
    keys = list(p)
    g_f = jax.grad(lambda a: (fused_ln_mlp_residual(
        **dict(zip(keys, a))) * ct).sum())(tuple(p.values()))
    g_r = jax.grad(lambda a: (_ref_ln_mlp_res(
        **dict(zip(keys, a))) * ct).sum())(tuple(p.values()))
    for a, b, name in zip(g_f, g_r, keys):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3,
                                   err_msg=f"grad {name}")


def test_fused_mlp_dropout_matches_positional_mask(rng):
    """The in-kernel hidden dropout equals a hand-applied positional-hash
    mask (same definition the flash kernel shares), forward AND backward."""
    p = _params(rng)
    drng = jax.random.fold_in(rng, 7)
    seed = derive_positional_seed(drng)
    thr = _threshold(0.3)
    inv = 256.0 / (256.0 - thr)
    x2 = p["x"].reshape(-1, D)
    keep = positional_keep_u8(seed[0], jnp.int32(0),
                              jnp.arange(x2.shape[0])[:, None],
                              jnp.arange(F)[None, :], thr)

    def ref(a):
        x, w1, b1, w2, b2 = a
        g = jax.nn.gelu(x.reshape(-1, D) @ w1 + b1, approximate=False)
        g = jnp.where(keep, g * inv, 0.0)
        return (g @ w2 + b2).reshape(x.shape)

    args = (p["x"], p["w1"], p["b1"], p["w2"], p["b2"])
    out = fused_mlp(*args, dropout_rate=0.3, dropout_rng=drng,
                    deterministic=False)
    np.testing.assert_allclose(out, ref(args), atol=1e-4, rtol=1e-4)

    ct = jax.random.normal(jax.random.fold_in(rng, 1), p["x"].shape)
    g_f = jax.grad(lambda a: (fused_mlp(
        *a, dropout_rate=0.3, dropout_rng=drng,
        deterministic=False) * ct).sum())(args)
    g_r = jax.grad(lambda a: (ref(a) * ct).sum())(args)
    for a, b in zip(g_f, g_r):
        np.testing.assert_allclose(a, b, atol=2e-3, rtol=2e-3)


def test_fused_ln_mlp_dropout_statistics(rng):
    """Both dropout sites drop at the quantized rate and the output is
    mean-preserving in expectation (spot-check via drop fraction on the
    hidden mask's direct evaluation)."""
    thr = _threshold(0.25)
    keep = positional_keep_u8(jnp.int32(1234), jnp.int32(0),
                              jnp.arange(512)[:, None],
                              jnp.arange(512)[None, :], thr)
    frac = float(jnp.mean(keep))
    assert abs(frac - (1 - quantized_rate(0.25))) < 0.01
    # hidden (bh=0) and output (bh=1) masks are distinct streams
    keep2 = positional_keep_u8(jnp.int32(1234), jnp.int32(1),
                               jnp.arange(512)[:, None],
                               jnp.arange(512)[None, :], thr)
    assert float(jnp.mean(keep == keep2)) < 0.9


def test_fused_mlp_nondivisible_rows_padded(rng):
    """Row counts not divisible by the block size pad correctly, and the
    padded rows contribute nothing to weight grads."""
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (3, 13, D), jnp.float32)  # 39 rows
    w1 = jax.random.normal(ks[1], (D, F)) * 0.1
    b1 = jnp.zeros((F,))
    w2 = jax.random.normal(ks[2], (F, D)) * 0.1
    b2 = jnp.zeros((D,))
    out = fused_mlp(x, w1, b1, w2, b2, block_rows=16)
    ref = _ref_mlp(x, w1, b1, w2, b2)
    np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)
    g_f = jax.grad(lambda w: fused_mlp(x, w, b1, w2, b2,
                                       block_rows=16).sum())(w1)
    g_r = jax.grad(lambda w: _ref_mlp(x, w, b1, w2, b2).sum())(w1)
    np.testing.assert_allclose(g_f, g_r, atol=2e-3, rtol=2e-3)


# --------------------------------------------------------------------------
# Model integration: mlp_impl paths agree and share one param tree
# --------------------------------------------------------------------------

def _block_params_and_input(rng, impl):
    cfg = vit_ti16(num_classes=10, mlp_impl=impl, dtype="float32")
    block = TransformerEncoderBlock(cfg)
    x = jax.random.normal(jax.random.fold_in(rng, 2),
                          (2, 17, cfg.embedding_dim), jnp.float32)
    params = block.init(rng, x)["params"]
    return cfg, block, params, x


def test_mlp_impl_param_trees_identical(rng):
    _, _, p_xla, _ = _block_params_and_input(rng, "xla")
    _, _, p_fused, _ = _block_params_and_input(rng, "fused")
    assert (jax.tree_util.tree_structure(p_xla)
            == jax.tree_util.tree_structure(p_fused))
    for a, b in zip(jax.tree_util.tree_leaves(p_xla),
                    jax.tree_util.tree_leaves(p_fused)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b)  # same init stream


def test_mlp_impl_forward_parity(rng):
    """fused and xla encoder blocks agree (deterministic mode) on the SAME
    params — the whole point of keeping param trees identical."""
    cfg_x, block_x, params, x = _block_params_and_input(rng, "xla")
    cfg_f = cfg_x.replace(mlp_impl="fused")
    block_f = TransformerEncoderBlock(cfg_f)
    out_x = block_x.apply({"params": params}, x)
    out_f = block_f.apply({"params": params}, x)
    np.testing.assert_allclose(out_f, out_x, atol=1e-4, rtol=1e-4)


def test_mlp_impl_grad_parity(rng):
    cfg_x, block_x, params, x = _block_params_and_input(rng, "xla")
    block_f = TransformerEncoderBlock(cfg_x.replace(mlp_impl="fused"))
    g_x = jax.grad(lambda p: block_x.apply({"params": p}, x).sum())(params)
    g_f = jax.grad(lambda p: block_f.apply({"params": p}, x).sum())(params)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_x),
            jax.tree_util.tree_leaves_with_path(g_f)):
        np.testing.assert_allclose(a, b, atol=3e-3, rtol=3e-3,
                                   err_msg=str(ka))


def test_mlp_impl_manual_tp_core_mode(rng):
    """Under a tp_axis (shard_map manual TP) the fused path uses the core
    kernel with the psum outside — forward must still match xla."""
    from jax.sharding import Mesh
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    cfg = vit_ti16(num_classes=10, dtype="float32")
    x = jax.random.normal(jax.random.fold_in(rng, 2),
                          (2, 17, cfg.embedding_dim), jnp.float32)
    block = MLPBlock(cfg)
    params = block.init(rng, x)["params"]

    devs = np.array(jax.devices()[:2]).reshape(2)
    mesh = Mesh(devs, ("model",))
    local_cfg = cfg.replace(mlp_size=cfg.mlp_size // 2)

    def run(impl):
        lcfg = local_cfg.replace(mlp_impl=impl)

        def shard_fn(p_local, x):
            return MLPBlock(lcfg, tp_axis="model").apply(
                {"params": p_local}, x)

        p_sharded = {
            "norm": params["norm"],
            "fc1": {"kernel": params["fc1"]["kernel"],
                    "bias": params["fc1"]["bias"]},
            # Replicated fc2 bias fed as b/tp so the post-fc2 psum
            # reconstructs it exactly once (pipeline.py's
            # scale_replicated_biases convention).
            "fc2": {"kernel": params["fc2"]["kernel"],
                    "bias": params["fc2"]["bias"] / 2.0},
        }
        fn = shard_map(
            shard_fn, mesh=mesh,
            in_specs=({"norm": P(), "fc1": {"kernel": P(None, "model"),
                                            "bias": P("model")},
                       "fc2": {"kernel": P("model", None), "bias": P()}},
                      P()),
            out_specs=P(), check_vma=False)
        return fn(p_sharded, x)

    out_x = run("xla")
    out_f = run("fused")
    ref = block.apply({"params": params}, x)
    np.testing.assert_allclose(out_x, ref, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(out_f, ref, atol=1e-4, rtol=1e-4)


def test_fused_mlp_dropout_needs_rng(rng):
    p = _params(rng)
    with pytest.raises(ValueError, match="dropout_rng"):
        fused_mlp(p["x"], p["w1"], p["b1"], p["w2"], p["b2"],
                  dropout_rate=0.1, deterministic=False)


def test_fused_ln_mlp_residual_shape_check(rng):
    p = _params(rng)
    with pytest.raises(ValueError, match="residual"):
        fused_ln_mlp_residual(p["x"], p["gamma"], p["beta"],
                              p["w1"], p["b1"],
                              jnp.zeros((F, D + 8)), jnp.zeros((D + 8,)))


def test_fused_under_gspmd_mesh_train_step(devices, rng):
    """The fused MLP path composes with GSPMD dp x tp meshes (the
    non-pipeline parallel path): a full parallel train step runs and
    matches the xla-impl step's loss when dropout is off (same params,
    same batch; the kernels are numerically equivalent)."""
    import numpy as np

    from pytorch_vit_paper_replication_tpu import engine
    from pytorch_vit_paper_replication_tpu.configs import (MeshConfig,
                                                           TrainConfig)
    from pytorch_vit_paper_replication_tpu.configs import vit_s16
    from pytorch_vit_paper_replication_tpu.data import synthetic_batch
    from pytorch_vit_paper_replication_tpu.models import ViT
    from pytorch_vit_paper_replication_tpu.optim import make_optimizer
    from pytorch_vit_paper_replication_tpu.parallel.mesh import make_mesh
    from pytorch_vit_paper_replication_tpu.parallel.api import (
        make_parallel_train_step, shard_batch, shard_train_state)

    def run(impl):
        # Fresh keys per run: the donated train step consumes the state's
        # rng buffer, so a shared fixture key dies after the first run.
        key = jax.random.key(0)
        cfg = vit_s16(num_classes=10, dtype="float32", image_size=32,
                      patch_size=8, mlp_impl=impl, attn_dropout=0.0,
                      mlp_dropout=0.0, embedding_dropout=0.0)
        model = ViT(cfg)
        params = model.init(key, jnp.zeros((1, 32, 32, 3)))["params"]
        tx = make_optimizer(TrainConfig(), total_steps=100)
        state = engine.TrainState.create(apply_fn=model.apply,
                                         params=params, tx=tx,
                                         rng=jax.random.key(1))
        mesh = make_mesh(MeshConfig(data=4, model=2))
        state = shard_train_state(state, mesh)
        step = make_parallel_train_step(state, mesh)
        batch = shard_batch(jax.tree.map(
            jnp.asarray, synthetic_batch(16, 32, 10)), mesh)
        state2, m = step(state, batch)
        return float(m["loss_sum"]), float(jax.device_get(
            jnp.sum(jnp.abs(state2.params["head"]["kernel"]))))

    loss_f, head_f = run("fused")
    loss_x, head_x = run("xla")
    np.testing.assert_allclose(loss_f, loss_x, rtol=1e-4)
    np.testing.assert_allclose(head_f, head_x, rtol=1e-3)


@pytest.mark.parametrize("dp,tp", [(4, 1), (2, 2)])
def test_kernels_on_a_mesh_draw_the_unsharded_dropout_masks(devices, rng,
                                                            dp, tp):
    """Traced under a mesh the kernels run per shard (XLA cannot split a
    Mosaic call). Each shard restarts its grid at row 0 with the same
    replicated seed, so its global row/column offset must enter the mask
    hash: shards then draw different masks — in fact exactly the masks
    the unsharded call draws, so values AND grads agree with dropout ON
    however the batch (dp) and the hidden dim (tp) are split."""
    from pytorch_vit_paper_replication_tpu.configs import MeshConfig
    from pytorch_vit_paper_replication_tpu.ops import on_mesh
    from pytorch_vit_paper_replication_tpu.parallel.mesh import make_mesh

    p = _params(rng)
    p["x"] = jax.random.normal(rng, (8, 6, D), jnp.float32)
    kw = dict(dropout_rate=0.25, dropout_rng=jax.random.key(7),
              deterministic=False)

    def full(x, gamma, beta, w1, b1, w2, b2):
        return (fused_ln_mlp_residual(x, gamma, beta, w1, b1, w2, b2,
                                      **kw) ** 2).sum()

    def core(x, w1, b1, w2, b2):
        return (fused_mlp(x, w1, b1, w2, b2, **kw) ** 2).sum()

    full_args = [p[k] for k in ("x", "gamma", "beta", "w1", "b1", "w2", "b2")]
    core_args = [p[k] for k in ("x", "w1", "b1", "w2", "b2")]
    mesh = make_mesh(MeshConfig(data=dp, model=tp), devices[:dp * tp])
    for fn, args in ((full, full_args), (core, core_args)):
        grad = jax.value_and_grad(fn, argnums=tuple(range(len(args))))
        want = grad(*args)
        with on_mesh(mesh):
            got = jax.jit(grad)(*args)
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            # f32 sums over shards in another order; a different mask
            # would move these by O(1).
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-3)

    # And directly: the same rows on every shard still come out different.
    same = jnp.broadcast_to(p["x"][:1], p["x"].shape)
    with on_mesh(mesh):
        out = jax.jit(lambda x: fused_mlp(x, *core_args[1:], **kw))(same)
    per_shard = np.asarray(out).reshape(dp, -1)
    assert not np.allclose(per_shard[0], per_shard[1])
