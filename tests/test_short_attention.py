"""The short-sequence attention kernel's dispatch, its life on a mesh and
in the model (ops/short_attention.py, ops/attention.py::self_attention,
models/vit.py). The kernels' numerics are in tests/test_ops.py; what the
v5e compiler makes of them is in tests/test_v5e_compile.py.

``"auto"`` reads ``jax.default_backend()``, ``cpu`` here, so the tests
that stand on a TPU's side of it patch it, and where the kernel then has
to run they hand it the interpreter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from pytorch_vit_paper_replication_tpu import engine, parallel
from pytorch_vit_paper_replication_tpu.configs import (
    MeshConfig, TrainConfig, ViTConfig)
from pytorch_vit_paper_replication_tpu.data import synthetic_batch
from pytorch_vit_paper_replication_tpu.models import (
    MultiHeadSelfAttentionBlock, ViT)
from pytorch_vit_paper_replication_tpu.ops import (
    attention, partition, short_attention)
from pytorch_vit_paper_replication_tpu.optim import make_optimizer

CALL = dict(impl="auto", dropout_rate=0.0, deterministic=True, mask=None,
            probs_dtype="bf16", residual_dtype=None)


@pytest.fixture()
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture()
def interpreted(on_tpu, monkeypatch):
    """The dispatch believes in a TPU; the kernel runs in the
    interpreter."""
    monkeypatch.setattr(
        short_attention, "short_attention",
        functools.partial(short_attention.short_attention, interpret=True))


def _mesh(config):
    """`config`'s mesh over as many of the virtual devices as it names."""
    n = config.data * config.model * config.seq
    return parallel.make_mesh(config, jax.devices()[:n])


def _ok(shape, dtype=jnp.bfloat16, **changed):
    return attention.short_attention_ok(shape, dtype, **{**CALL, **changed})


@pytest.mark.parametrize("shape,mesh,dtype", [
    ((256, 197, 3, 12, 64), None, jnp.bfloat16),             # b16_train
    ((96, 197, 3, 16, 64), None, jnp.bfloat16),              # l16_train
    # b16_train_dp4: a shard is b16_train's operand
    ((1024, 197, 3, 12, 64), MeshConfig(data=4), jnp.bfloat16),
    ((64, 197, 3, 12, 64), MeshConfig(data=2, model=2), jnp.bfloat16),
    ((8, 197, 3, 6, 64), None, jnp.bfloat16),                # S/16
    ((8, 257, 3, 16, 128), None, jnp.bfloat16),              # one head a slab
    ((8, 197, 3, 12, 64), None, jnp.float32),
], ids=str)
def test_auto_takes_the_kernel(on_tpu, devices, shape, mesh, dtype):
    with partition.on_mesh(None if mesh is None else _mesh(mesh)):
        assert _ok(shape, dtype)


@pytest.mark.parametrize("why,shape,changed", [
    ("a mask", (8, 197, 3, 12, 64), dict(mask=jnp.ones((1, 1, 197, 197), bool))),
    ("active attention dropout", (8, 197, 3, 12, 64),
     dict(dropout_rate=0.1, deterministic=False)),
    ("Dh = 16, the rehearsal model", (8, 17, 3, 2, 16), {}),
    ("T = 577, over the VMEM budget", (8, 577, 3, 12, 64), {}),
    ("quantised probabilities", (8, 197, 3, 12, 64), dict(probs_dtype="u8")),
    ("a quantised residual", (8, 197, 3, 12, 64),
     dict(residual_dtype="fp8_e4m3")),
    ("forced xla", (8, 197, 3, 12, 64), dict(impl="xla")),
    ("forced flash", (8, 197, 3, 12, 64), dict(impl="flash")),
    ("three heads are a slab and a half", (8, 197, 3, 3, 64), {}),
    ("f16", (8, 197, 3, 12, 64), dict(dtype=jnp.float16)),
], ids=lambda x: x if isinstance(x, str) else "")
def test_auto_keeps_the_old_paths(on_tpu, why, shape, changed):
    assert not _ok(shape, **changed), why


def test_auto_stays_xla_off_the_tpu():
    assert jax.default_backend() == "cpu"
    assert not _ok((256, 197, 3, 12, 64))


@pytest.mark.parametrize("why,mesh,shape", [
    ("a seq axis of 2", MeshConfig(data=2, model=1, seq=2),
     (8, 196, 3, 12, 64)),
    ("a batch the data axis does not divide", MeshConfig(data=4),
     (6, 197, 3, 12, 64)),
    ("a shard left with a slab and a half", MeshConfig(data=1, model=4),
     (8, 197, 3, 12, 64)),
], ids=lambda x: x if isinstance(x, str) else "")
def test_auto_keeps_the_old_paths_on_a_mesh(on_tpu, devices, why, mesh,
                                            shape):
    with partition.on_mesh(_mesh(mesh)):
        assert not _ok(shape), why


def test_the_length_limit_is_computed_from_the_shapes():
    """Images a grid step fall as the ``[T, T]`` tile grows, to none:
    where, depends on the head size and the dtype, not on a constant."""
    plan = short_attention.plan
    assert plan(256, 197, 64, 2) == short_attention.MAX_IMAGES
    assert plan(2, 197, 64, 2) == 2 and plan(6, 197, 64, 2) == 4
    assert [plan(64, t, 64, 2) for t in (384, 512, 540, 577)] == [8, 3, 1,
                                                                 None]
    assert plan(64, 577, 128, 2) == 4      # one head in flight, not two
    # f32 operands and temporaries: shorter
    assert [plan(64, t, 64, 4) for t in (384, 512, 540)] == [4, 1, None]
    for t in (197, 512, 540):
        heads = 2
        tp, tk = short_attention._padded(t, 2)
        images = plan(64, t, 64, 2)
        assert (heads * tk * tp * 20 + images * (12 * tp * 128 * 2
                                                 + 2 * heads * tp * 4)
                <= short_attention.VMEM_BUDGET)


def _name_stacks(jaxpr, prims):
    """The scope of every equation with one of these primitives, the
    bodies of the kernels left out."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in prims:
            found.append(str(eqn.source_info.name_stack))
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += _name_stacks(sub, prims)
    return found


def test_the_fallbacks_slices_stay_outside_the_core_scope():
    """A program that does not engage the kernel reads as the parent's
    does: q, k, v are cut from the projection before the scope
    ``attn_core`` opens (a device trace counts them as ``msa_glue``),
    and the core's own ops are inside it."""
    qkv = jnp.zeros((2, 17, 3, 2, 16))
    jaxpr = jax.make_jaxpr(
        lambda x: attention.self_attention(x, impl="xla"))(qkv).jaxpr
    cuts = _name_stacks(jaxpr, ("slice", "squeeze", "dynamic_slice",
                                "gather"))
    assert len(cuts) >= 3
    assert not any("attn_core" in s for s in cuts), cuts
    core = _name_stacks(jaxpr, ("dot_general", "exp"))
    assert len(core) >= 3 and all("attn_core" in s for s in core), core


def test_the_kernel_is_traced_under_the_core_scope(interpreted):
    qkv = jnp.zeros((2, 17, 3, 2, 64))
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x: attention.self_attention(x).sum()))(qkv).jaxpr
    calls = _name_stacks(jaxpr, ("pallas_call",))
    assert len(calls) == 2 and all("attn_core" in s for s in calls), calls
    assert not _name_stacks(jaxpr, ("slice", "squeeze", "gather"))


@pytest.mark.parametrize("mesh", [MeshConfig(data=4),
                                  MeshConfig(data=2, model=2)], ids=str)
def test_the_kernel_runs_per_shard_on_a_mesh(devices, mesh):
    """Batch over ``data``, heads over ``model``: each shard's call sees
    its images and its slabs of heads, and the result and the packed
    cotangent are the one-device ones."""
    qkv = jax.random.normal(jax.random.key(0), (8, 37, 3, 4, 64))

    def f(x):
        return short_attention.short_attention(x, interpret=True)

    def loss(x):
        return jnp.sum(jnp.sin(f(x)))

    ref, g_ref = f(qkv), jax.grad(loss)(qkv)
    mesh = _mesh(mesh)
    sharded = jax.device_put(qkv, NamedSharding(
        mesh, P("data", None, None, "model", None)))
    with partition.on_mesh(mesh):
        out = jax.jit(f)(sharded)
        g = jax.jit(jax.grad(loss))(sharded)
        hlo = jax.jit(f).lower(sharded).as_text()
    assert "shard_map" in hlo or "manual" in hlo
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-5)


MSA = ViTConfig(image_size=32, patch_size=8, num_layers=2, num_heads=2,
                embedding_dim=128, mlp_size=64, num_classes=3,
                dtype="float32", mlp_impl="xla")


def test_the_block_with_the_kernel_is_the_block_without(interpreted):
    """Engaged, the block takes its projections as flat GEMMs
    (``_FlatDenseGeneral``): the same parameters - names, shapes and,
    from the same key, values - and the same function as the
    ``nn.DenseGeneral`` block on the XLA path."""
    x = jax.random.normal(jax.random.key(1), (2, 17, 128))
    on = MultiHeadSelfAttentionBlock(MSA)
    off = MultiHeadSelfAttentionBlock(MSA.replace(attention_impl="xla"))
    p_on = on.init(jax.random.key(2), x)
    p_off = off.init(jax.random.key(2), x)
    assert (jax.tree.structure(p_on) == jax.tree.structure(p_off))
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jaxpr = jax.make_jaxpr(lambda p: on.apply(p, x))(p_on).jaxpr
    assert len(_name_stacks(jaxpr, ("pallas_call",))) == 1
    assert all("/qkv" in s or "/out" in s
               for s in _name_stacks(jaxpr, ("dot_general",)))

    def loss(module):
        return lambda p: jnp.sum(jnp.sin(module.apply(p, x)))

    np.testing.assert_allclose(
        np.asarray(on.apply(p_on, x)), np.asarray(off.apply(p_on, x)),
        rtol=2e-2, atol=2e-3)
    for a, b in zip(jax.tree.leaves(jax.grad(loss(on))(p_on)),
                    jax.tree.leaves(jax.grad(loss(off))(p_on))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-2, atol=2e-3)


def _state(cfg):
    model = ViT(cfg)
    params = model.init(jax.random.key(0), jnp.zeros(
        (1, cfg.image_size, cfg.image_size, 3)))["params"]
    return engine.TrainState.create(
        apply_fn=model.apply, params=params,
        tx=make_optimizer(TrainConfig(batch_size=8), 10),
        rng=jax.random.key(1))


def test_the_dp4_step_with_the_kernel_matches_one_device_without(
        interpreted, devices):
    """The train step on a ``data=4`` mesh of virtual devices, kernel
    engaged in every layer (per shard), against the one-device step on
    the XLA path: the same loss and the same update."""
    batch = jax.tree.map(jnp.asarray, synthetic_batch(
        8, MSA.image_size, MSA.num_classes))
    state1 = _state(MSA.replace(attention_impl="xla"))
    state1, m1 = jax.jit(engine.make_train_step())(state1, batch)

    mesh = _mesh(MeshConfig(data=4))
    state4 = parallel.shard_train_state(_state(MSA), mesh)
    step4 = parallel.make_parallel_train_step(state4, mesh)
    jaxpr = jax.make_jaxpr(lambda s, b: step4(s, b))(
        state4, parallel.shard_batch(batch, mesh)).jaxpr
    assert len(_name_stacks(jaxpr, ("pallas_call",))) == 2 * MSA.num_layers
    state4, m4 = step4(state4, parallel.shard_batch(batch, mesh))
    np.testing.assert_allclose(float(m1["loss_sum"]), float(m4["loss_sum"]),
                               rtol=1e-3)
    for a, b in zip(jax.tree.leaves(jax.device_get(state1.params)),
                    jax.tree.leaves(jax.device_get(state4.params))):
        np.testing.assert_allclose(a, b, rtol=5e-3, atol=5e-5)
