"""Which implementation serves an attention call
(ops/attention.py::choose), and the short-sequence kernel's life on a
mesh and in the model (ops/short_attention.py,
ops/attention.py::self_attention, models/vit.py). The kernels' numerics
are in tests/test_ops.py; what the v5e compiler makes of them is in
tests/test_v5e_compile.py.

``choose`` takes the backend as an argument, so the table below asks it
as a TPU would without patching anything. The tests that run a model on
the TPU's side of the choice patch ``jax.default_backend()``, ``cpu``
here, and hand the kernel the interpreter."""

import flax.linen as nn
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


@pytest.fixture()
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture()
def interpreted(on_tpu, monkeypatch):
    """The dispatch believes in a TPU; the kernel runs in the
    interpreter, and the shape of every projection it was handed is
    returned."""
    calls, real = [], short_attention.short_attention

    def kernel(qkv):
        calls.append(qkv.shape)
        return real(qkv, interpret=True)

    monkeypatch.setattr(short_attention, "short_attention", kernel)
    return calls


def _mesh(config):
    """`config`'s mesh over as many of the virtual devices as it names."""
    n = config.data * config.model * config.seq
    return parallel.make_mesh(config, jax.devices()[:n])


B16 = (8, 197, 3, 12, 64)       # B/16's projection, a small batch of it
SEQ2 = MeshConfig(data=2, model=1, seq=2)


def _case(why, shape, served, *, mesh=None, sp_impl="ring", cpu="xla",
          reason=None, **call):
    """One row: a call's facts (``shape`` the packed projection's or
    q's, ``call`` the keywords of :func:`attention.choose`), the mesh it
    is traced on, the name a TPU is served by and the name the CPU is
    (off the TPU no rule but a ``seq`` mesh or a forced ``"flash"`` says
    anything but xla); ``reason`` is a part of the warning a ``seq``
    mesh that could not be honoured gives."""
    return pytest.param(shape, mesh, sp_impl, call,
                        {"tpu": served, "cpu": cpu}, reason, id=why)


THE_CHOICE = [
    # --- the four cells, as the ledger runs them ---
    _case("b16_train", (256, 197, 3, 12, 64), "short"),
    _case("l16_train", (96, 197, 3, 16, 64), "short"),
    _case("b16_train_dp4: a shard is b16_train's operand",
          (1024, 197, 3, 12, 64), "short", mesh=MeshConfig(data=4)),
    _case("st21b_train_16k, a full causal layer", (1, 16384, 28, 128),
          "flash", k_shape=(1, 16384, 4, 128), kind="causal"),
    _case("st21b_train_16k, a window layer", (1, 16384, 28, 128), "flash",
          k_shape=(1, 16384, 4, 128), kind="causal_window"),
    _case("keye2_train_16k: the keys an indexer selects, a mask a row",
          (1, 16384, 32, 128), "flash", k_shape=(1, 16384, 4, 128),
          kind="causal_topk"),
    # --- other models the repo names ---
    _case("dsa-tiny: T 64, Dh 16, selected keys", (8, 64, 4, 16), "xla",
          k_shape=(8, 64, 2, 16), kind="causal_topk"),
    _case("selected keys, forced flash", (8, 64, 4, 16), "flash",
          cpu="flash", k_shape=(8, 64, 2, 16), kind="causal_topk",
          impl="flash"),
    _case("lm-tiny: T 64, Dh 16", (8, 64, 4, 16), "xla",
          k_shape=(8, 64, 2, 16), kind="causal"),
    _case("H/14: T 257, Dh 80", (64, 257, 3, 16, 80), "xla"),
    _case("B/16 at 384 px, bs 64: 1.5 GiB of logits x 3",
          (64, 577, 3, 12, 64), "xla"),
    _case("B/16 at 384 px, bs 256: 6 GiB, over the 4 GiB rule",
          (256, 577, 3, 12, 64), "flash"),
    _case("B/16 at 384 px, bs 256 over data=4: a shard's 1.5 GiB",
          (256, 577, 3, 12, 64), "xla", mesh=MeshConfig(data=4)),
    _case("S/16", (8, 197, 3, 6, 64), "short"),
    _case("Dh 128: one head a slab", (8, 257, 3, 16, 128), "short"),
    _case("float32", B16, "short", dtype=jnp.float32),
    _case("data=2 x model=2: whole slabs a shard", (64, 197, 3, 12, 64),
          "short", mesh=MeshConfig(data=2, model=2)),
    # --- what the short-sequence kernel does not do ---
    _case("a mask", B16, "xla", mask=jnp.ones((1, 1, 197, 197), bool)),
    _case("active attention dropout", B16, "xla", dropout_rate=0.1,
          deterministic=False),
    _case("eval mode with attn_dropout 0.1", B16, "short",
          dropout_rate=0.1, deterministic=True),
    _case("forced xla", B16, "xla", impl="xla"),
    _case("forced flash", B16, "flash", cpu="flash", impl="flash"),
    _case("float16", B16, "xla", dtype=jnp.float16),
    _case("three heads are a slab and a half", (8, 197, 3, 3, 64), "xla"),
    _case("Dh = 16, the rehearsal model", (8, 17, 3, 2, 16), "xla"),
    _case("T = 577, over the VMEM budget", (8, 577, 3, 12, 64), "xla"),
    _case("a causal packed call", (256, 197, 3, 12, 64), "xla",
          kind="causal"),
    _case("q, k and v, not the packed projection", (256, 197, 12, 64),
          "xla", k_shape=(256, 197, 12, 64)),
    _case("a batch the data axis does not divide", (6, 197, 3, 12, 64),
          "xla", mesh=MeshConfig(data=4)),
    _case("a shard left with a slab and a half", B16, "xla",
          mesh=MeshConfig(data=1, model=4)),
    # --- flash by memory alone (tests/test_ops.py had these three) ---
    _case("64 MB of logits: xla", (8, 577, 12, 64), "xla"),
    _case("12.9 GB of logits: only flash fits", (8, 8192, 12, 64), "flash"),
    _case("below flash's tiling floor", (1024, 256, 12, 64), "xla"),
    _case("a head size flash has not", (64, 8192, 12, 80), "xla"),
    # --- a seq axis decides alone, on any backend ---
    _case("seq=2", (8, 196, 3, 12, 64), "ring", cpu="ring", mesh=SEQ2),
    _case("seq=2, forced flash: still the ring", (8, 196, 3, 12, 64),
          "ring", cpu="ring", mesh=SEQ2, impl="flash"),
    _case("seq=2, ulysses", (8, 196, 3, 12, 64), "ulysses", cpu="ulysses",
          mesh=SEQ2, sp_impl="ulysses"),
    _case("seq=2, ulysses, heads the axis does not divide",
          (8, 196, 3, 3, 64), "xla", mesh=SEQ2, sp_impl="ulysses",
          reason="needs heads (3) divisible by the seq axis (2)"),
    _case("seq=2 x model=2, ulysses, 2 global heads are 1 a shard",
          (8, 196, 2, 64), "xla", sp_impl="ulysses",
          mesh=MeshConfig(data=1, model=2, seq=2),
          reason="needs heads (1) divisible by the seq axis (2)"),
    _case("seq=2 x model=2, ulysses, 2 heads already local",
          (8, 196, 2, 64), "ulysses", cpu="ulysses", sp_impl="ulysses",
          mesh=MeshConfig(data=1, model=2, seq=2),
          heads_already_local=True),
    _case("seq=2, a mask", (8, 196, 3, 12, 64), "xla", mesh=SEQ2,
          mask=jnp.ones((1, 1, 196, 196), bool),
          reason="masks are not supported by ring/ulysses"),
    _case("seq=2, T the axis does not divide", B16, "xla", mesh=SEQ2,
          reason="shape (batch=8, tokens=197) not divisible by mesh axes"),
    _case("seq=2, a batch the data axis does not divide",
          (7, 196, 3, 12, 64), "xla", mesh=SEQ2,
          reason="shape (batch=7, tokens=196) not divisible by mesh axes"),
    _case("seq=2, causal", (8, 196, 12, 64), "xla", mesh=SEQ2,
          kind="causal", reason="bidirectional attention with equal head"),
    _case("seq=2, selected keys", (8, 196, 12, 64), "xla", mesh=SEQ2,
          k_shape=(8, 196, 12, 64), kind="causal_topk",
          reason="bidirectional attention with equal head"),
    _case("seq=2, grouped heads", (8, 196, 12, 64), "xla", mesh=SEQ2,
          k_shape=(8, 196, 4, 64), reason="equal head counts only"),
]


@pytest.mark.parametrize("backend", ["tpu", "cpu"])
@pytest.mark.parametrize("shape,mesh,sp_impl,call,served,reason",
                         THE_CHOICE)
def test_the_choice_of_implementation(devices, backend, shape, mesh,
                                      sp_impl, call, served, reason):
    """The one function that decides, asked for a call's facts: nothing
    runs. A reason comes exactly where a ``seq`` mesh was not honoured,
    and ends as the warning always has."""
    call = dict(call)
    dtype = call.pop("dtype", jnp.bfloat16)
    k_shape = call.pop("k_shape", None)
    with partition.on_mesh(None if mesh is None else _mesh(mesh),
                           sp_impl=sp_impl):
        got, why = attention.choose(shape, dtype, k_shape, backend=backend,
                                    **call)
    assert got == served[backend]
    if reason is None:
        assert why is None
    else:
        assert why.startswith("sequence_parallel: ") and reason in why
        assert "using the (gathered) XLA path instead" in why


def test_the_choice_reads_the_backend_it_is_not_given(on_tpu):
    assert attention.choose((256, 197, 3, 12, 64), jnp.bfloat16) == (
        "short", None)
    assert attention.choose(
        (256, 197, 3, 12, 64), jnp.bfloat16, backend="cpu") == ("xla", None)


@pytest.mark.parametrize("call", [dict(impl="short"), dict(kind="window")],
                         ids=str)
def test_the_choice_refuses_a_name_it_does_not_know(call):
    for ask in (lambda: attention.choose(B16, jnp.bfloat16, **call),
                lambda: attention.self_attention(jnp.zeros((1, 4, 3, 2, 8)),
                                                 **call),
                lambda: attention.dot_product_attention(
                    *[jnp.zeros((1, 4, 2, 8))] * 3, **call)):
        with pytest.raises(ValueError, match="unknown attention"):
            ask()


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


def _projections(block, params, x):
    """The classes of the block's ``qkv`` and ``out`` projections in a
    call."""
    seen = {}

    def spy(call, args, kwargs, context):
        if context.module.name in ("qkv", "out"):
            seen[context.module.name] = type(context.module).__name__
        return call(*args, **kwargs)

    with nn.intercept_methods(spy):
        block.apply(params, x)
    return seen


@pytest.mark.parametrize("tokens,served", [(17, "short"), (577, "xla")])
def test_the_block_with_the_kernel_is_the_block_without(interpreted, tokens,
                                                        served):
    """Engaged, the block takes its projections as flat GEMMs
    (``_FlatDenseGeneral``): the same parameters - names, shapes and,
    from the same key, values - and the same function as the
    ``nn.DenseGeneral`` block on the XLA path. And the block and the
    dispatch cannot disagree: the projections are flat exactly where the
    kernel is then called, and both exactly where the one function says
    ``"short"`` (a length the kernel plans for, and one whose ``[T, T]``
    tile is over its VMEM budget)."""
    x = jax.random.normal(jax.random.key(1), (2, tokens, 128))
    qkv_shape = (2, tokens, 3, MSA.num_heads, MSA.head_dim)
    assert attention.choose(qkv_shape, x.dtype)[0] == served
    engaged = served == "short"
    on = MultiHeadSelfAttentionBlock(MSA)
    off = MultiHeadSelfAttentionBlock(MSA.replace(attention_impl="xla"))
    p_on = on.init(jax.random.key(2), x)
    p_off = off.init(jax.random.key(2), x)
    assert (jax.tree.structure(p_on) == jax.tree.structure(p_off))
    for a, b in zip(jax.tree.leaves(p_on), jax.tree.leaves(p_off)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    interpreted.clear()
    dense = "_FlatDenseGeneral" if engaged else "DenseGeneral"
    assert _projections(on, p_on, x) == {"qkv": dense, "out": dense}
    assert interpreted == [qkv_shape] * engaged
    assert _projections(off, p_on, x) == {"qkv": "DenseGeneral",
                                          "out": "DenseGeneral"}
    assert interpreted == [qkv_shape] * engaged
    jaxpr = jax.make_jaxpr(lambda p: on.apply(p, x))(p_on).jaxpr
    assert len(_name_stacks(jaxpr, ("pallas_call",))) == engaged
    if engaged:
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
