"""The latent-attention model's operators at tiny shapes on the CPU
(Pallas in the interpreter): the latent attention alone against the
reference's (``benchmark/lib/reference_mla.py``), the flash kernels at
head size 256 with 20 ungrouped heads against the plain product, what the
block keeps for the backward pass, the sigmoid router, the expert
activation, and the shares of an expert-parallel routed layer with its
shared expert.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_mla
from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS
from pytorch_vit_paper_replication_tpu.models import vit as vit_module
from pytorch_vit_paper_replication_tpu.ops import moe
from pytorch_vit_paper_replication_tpu.ops.attention import choose
from pytorch_vit_paper_replication_tpu.ops.flash_attention import (
    flash_attention)


def _tiny(**kw):
    # float32 compute: the comparison is of the mathematics
    return LM_PRESETS["mla-tiny"](dtype="float32", **kw)


def _randomised(params, seed=0):
    return jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(
            jax.random.key(seed + a.size), a.shape), params)


# ------------------------------------------------------ latent attention
@pytest.mark.parametrize("impl", ["xla", "flash"])
def test_latent_attention_equals_the_references(impl):
    """The 8 / 8 split of a 16-wide head (192 / 64 of 256 at the real
    size), rotary positions on the query's rotary columns and on the ONE
    key head that every query head reads, values from the same latent:
    the block's attention alone, forward and every gradient, by the XLA
    product and by the flash kernels in the interpreter."""
    cfg = _tiny(attention_impl=impl)
    block = vit_module.MultiHeadSelfAttentionBlock(cfg, layer=1)
    x = jax.random.normal(jax.random.key(0), (2, 40, cfg.embedding_dim))
    params = _randomised(block.init(jax.random.key(1), x)["params"])
    fields = dataclasses.asdict(cfg)

    def want(p, x):
        normed = jax.vmap(lambda s: reference_mla.rms_norm(
            s, p["norm"]["scale"], cfg.ln_epsilon))(x)
        return jnp.stack([reference_mla.latent_attention(s, p, fields)
                          for s in normed])

    got = block.apply({"params": params}, x)
    np.testing.assert_allclose(got, want(params, x), atol=2e-5)
    cot = jax.random.normal(jax.random.key(2), got.shape)
    g = jax.grad(lambda p, x: jnp.sum(
        block.apply({"params": p}, x) * cot), (0, 1))(params, x)
    w = jax.grad(lambda p, x: jnp.sum(want(p, x) * cot), (0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(w)):
        np.testing.assert_allclose(
            a, b, atol=3e-5 * float(jnp.abs(b).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


def test_the_key_rotary_head_is_one_for_all_query_heads():
    """Moving the rotary columns of ``kv_down`` moves every head's
    output; the key's other columns are a head's own."""
    cfg = _tiny()
    block = vit_module.MultiHeadSelfAttentionBlock(cfg, layer=1)
    x = jax.random.normal(jax.random.key(0), (1, 24, cfg.embedding_dim))
    params = _randomised(block.init(jax.random.key(1), x)["params"])
    assert params["kv_down"]["kernel"].shape == (64, 16 + 8)
    assert params["kv_up"]["kernel"].shape == (16, 4, 8 + 16)
    assert params["q_up"]["kernel"].shape == (24, 4, 8 + 8)

    def per_head(p):
        # the out projection of head h alone: zero the others' rows
        outs = []
        for h in range(cfg.num_heads):
            only = p["out"]["kernel"] * (
                jnp.arange(cfg.num_heads) == h)[:, None, None]
            outs.append(block.apply(
                {"params": {**p, "out": {"kernel": only}}}, x))
        return jnp.stack(outs)

    base = per_head(params)
    shared = dict(params, kv_down={"kernel": params["kv_down"]["kernel"]
                                   .at[:, 16:].multiply(1.5)})
    moved = jnp.abs(per_head(shared) - base).max(axis=(1, 2, 3))
    assert bool(jnp.all(moved > 1e-6)), moved
    own = dict(params, kv_up={"kernel": params["kv_up"]["kernel"]
                              .at[:, 2, :8].multiply(1.5)})
    moved = jnp.abs(per_head(own) - base).max(axis=(1, 2, 3))
    assert float(moved[2]) > 1e-6 and float(
        jnp.delete(moved, 2).max()) < 1e-7, moved


def test_the_block_keeps_the_cores_output_and_not_its_heads():
    """The latent attention runs under a checkpoint that keeps the
    core's output and row statistic by name: its backward pass takes the
    projections again (a second ``q_up`` product) and the flash forward
    kernel once, not twice."""
    cfg = _tiny(attention_impl="flash")
    block = vit_module.TransformerEncoderBlock(cfg, layer=1)
    x = jax.random.normal(jax.random.key(0), (1, 32, cfg.embedding_dim))
    params = block.init(jax.random.key(1), x)["params"]
    text = str(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        block.apply({"params": p}, x, True))))(params))
    assert text.count("name=flash_fwd") == 1
    assert text.count("name=flash_bwd") == 1
    assert "name=attn_core_out" in text and "name=attn_core_lse" in text
    # SmallThinker's block has no such checkpoint
    plain = LM_PRESETS["lm-tiny"](dtype="float32", attention_impl="flash")
    block = vit_module.TransformerEncoderBlock(plain, layer=0)
    params = block.init(jax.random.key(1), x)["params"]
    text = str(jax.make_jaxpr(jax.grad(lambda p: jnp.sum(
        block.apply({"params": p}, x, True))))(params))
    assert "checkpoint" not in text and text.count("name=flash_fwd") == 1


# ------------------------------------------- q, k, v on the flat layout
def _four_d(cfg, c_q, c_kv, k_rope, wq, wkv):
    """q, k, v as the block made them until PR 33: ``[B, T, H, Dh]``
    products, :func:`rotary` on a slice, two concatenations and the
    broadcast of the shared key head."""
    import flax.linen as nn

    nope, dt = cfg.qk_nope_head_dim, c_q.dtype
    dense = lambda w, x: nn.DenseGeneral(
        features=w.shape[1:], use_bias=False, dtype=dt).apply(
            {"params": {"kernel": w}}, x)
    q, kv = dense(wq, c_q), dense(wkv, c_kv)
    q = jnp.concatenate([q[..., :nope], vit_module.rotary(
        q[..., nope:], cfg.rope_theta)], -1)
    k_rope = vit_module.rotary(k_rope[:, :, None, :], cfg.rope_theta)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_rope, kv.shape[:3] + k_rope.shape[3:])], -1)
    return {"q": q, "k": k, "v": kv[..., nope:]}


def _flat(cfg, c_q, c_kv, k_rope, wq, wkv):
    """The same three on ``[B, T, H x Dh]``, by the block's own pieces."""
    dt = c_q.dtype
    q = vit_module._FlatProduct(wq.shape, cfg.init_std, dt).apply(
        {"params": {"kernel": wq}}, c_q)
    q = vit_module._flat_rotary(q, cfg.num_heads, cfg.qk_rope_head_dim,
                                cfg.rope_theta)
    k_rope = vit_module._flat_rotary(k_rope, 1, cfg.qk_rope_head_dim,
                                     cfg.rope_theta)
    k, v = vit_module._LatentKeyValueUp(cfg.replace(dtype=dt.name)).apply(
        {"params": {"kernel": wkv}}, c_kv, k_rope)
    return {"q": q, "k": k, "v": v}


def _latents(cfg, dtype, seed=5):
    ks = jax.random.split(jax.random.key(seed), 5)
    shapes = ((2, 24, cfg.q_lora_rank), (2, 24, cfg.kv_lora_rank),
              (2, 24, cfg.qk_rope_head_dim),
              (cfg.q_lora_rank, cfg.num_heads, cfg.head_dim),
              (cfg.kv_lora_rank, cfg.num_heads,
               cfg.qk_nope_head_dim + cfg.v_head_dim))
    acts = [jax.random.normal(k, s).astype(dtype)
            for k, s in zip(ks[:3], shapes[:3])]
    return acts + [jax.random.normal(k, s) * 0.3      # float32 parameters
                   for k, s in zip(ks[3:], shapes[3:])]


@pytest.mark.parametrize("which", ["q", "k", "v"])
def test_flat_q_k_v_equal_the_four_dimensional_ones(which):
    """What the block hands the flash kernels, made on the flat layout
    (a flat product, the rotary part turned in place with the tokens on
    the lanes, the one rotary key head placed by k's own product): on
    bfloat16 latents BIT-equal to the 4-D formulation it replaces, and
    in float32 every gradient - the latents, the shared rotary key head,
    both up-projection kernels - equal to 1e-6 of its size."""
    cfg = _tiny()
    args = _latents(cfg, jnp.bfloat16)
    want = _four_d(cfg, *args)[which]
    got = _flat(cfg, *args)[which]
    assert got.dtype == want.dtype == jnp.bfloat16
    assert got.shape == want.shape[:2] + (cfg.num_heads * want.shape[-1],)
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)).reshape(want.shape),
        np.asarray(want.astype(jnp.float32)))
    # the turned columns are turned: k and q differ from the plain product
    if which != "v":
        plain = _four_d(cfg.replace(rope_theta=1e30), *args)[which]
        assert not np.array_equal(np.asarray(plain, np.float32),
                                  np.asarray(want, np.float32))
    args = _latents(cfg, jnp.float32)
    cot = jax.random.normal(jax.random.key(3), want.shape)
    loss = lambda f: lambda *a: jnp.sum(
        f(cfg, *a)[which].reshape(cot.shape) * cot)
    g = jax.grad(loss(_flat), range(5))(*args)
    w = jax.grad(loss(_four_d), range(5))(*args)
    used = {"q": (0, 3), "k": (1, 2, 4), "v": (1, 4)}[which]
    for i, name in enumerate(("c_q", "c_kv", "k_rope", "q_up", "kv_up")):
        scale = float(jnp.abs(w[i]).max())
        assert (scale > 0) == (i in used), name
        np.testing.assert_allclose(g[i], w[i], rtol=0, atol=1e-6 * scale,
                                   err_msg=name)


def test_flat_products_keep_dense_generals_parameters():
    """``q_up``, ``kv_up`` and ``out`` are ``nn.DenseGeneral``'s
    parameters - name, shape, and the values its flat-shape
    initialisation draws from the same key - so the tree, its count and
    a checkpoint's leaves are what they were."""
    import flax.linen as nn

    cfg = _tiny()
    x = jnp.zeros((1, 8, cfg.q_lora_rank))
    shape = (cfg.q_lora_rank, cfg.num_heads, cfg.head_dim)
    ours = vit_module._FlatProduct(shape, cfg.init_std, jnp.float32).init(
        jax.random.key(4), x)["params"]
    theirs = nn.DenseGeneral(
        features=shape[1:], use_bias=False,
        kernel_init=nn.initializers.normal(cfg.init_std)).init(
            jax.random.key(4), x)["params"]
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    np.testing.assert_array_equal(ours["kernel"], theirs["kernel"])
    # contracted over two leading dims, as ``out`` is
    o = jnp.zeros((1, 8, cfg.num_heads * cfg.v_head_dim))
    shape = (cfg.num_heads, cfg.v_head_dim, cfg.embedding_dim)
    ours = vit_module._FlatProduct(shape, cfg.init_std, jnp.float32,
                                   n_in=2).init(jax.random.key(4), o)
    theirs = nn.DenseGeneral(
        features=shape[2], axis=(-2, -1), use_bias=False,
        kernel_init=nn.initializers.normal(cfg.init_std)).init(
            jax.random.key(4), o.reshape(1, 8, *shape[:2]))
    np.testing.assert_array_equal(ours["params"]["kernel"],
                                  theirs["params"]["kernel"])
    kv = vit_module._LatentKeyValueUp(cfg).init(
        jax.random.key(4), jnp.zeros((1, 8, cfg.kv_lora_rank)),
        jnp.zeros((1, 8, cfg.qk_rope_head_dim)))["params"]
    shape = (cfg.kv_lora_rank, cfg.num_heads,
             cfg.qk_nope_head_dim + cfg.v_head_dim)
    theirs = nn.DenseGeneral(
        features=shape[1:], use_bias=False,
        kernel_init=nn.initializers.normal(cfg.init_std)).init(
            jax.random.key(4), jnp.zeros((1, 8, shape[0])))["params"]
    np.testing.assert_array_equal(kv["kernel"], theirs["kernel"])


# ----------------------------------------------------- flash at head 256
def _dense_causal(q, k, v):
    t, d = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    visible = jnp.arange(t)[None] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("t,blocks", [(160, (64, 64)), (136, (64, 32))])
def test_flash_at_head_size_256_with_20_ungrouped_heads(t, blocks):
    """The cell's head layout (20 query heads, 20 key/value heads, 256
    columns each: two lane tiles a head on the flat ``[B, T, H x Dh]``
    layout), causal, forward and the one-pass backward, through the
    interpreter against the plain product; T not a multiple of the
    blocks."""
    shape = (1, t, 20, 256)
    ks = jax.random.split(jax.random.key(7), 4)
    q, k, v, cot = (jax.random.normal(key, shape) for key in ks)
    flash = lambda q, k, v: flash_attention(
        q, k, v, kind="causal", block_q=blocks[0], block_k=blocks[1],
        interpret=True)
    np.testing.assert_allclose(flash(q, k, v), _dense_causal(q, k, v),
                               atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * cot), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_dense_causal(*a) * cot),
                    (0, 1, 2))(q, k, v)
    for g, w, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(g, w, atol=5e-5, err_msg=f"d{name}")
    # no transposed copy: the kernels read the projection where it lies
    # (inside, the forward turns its [Dh, Bq] accumulator once in VMEM)
    jaxpr = jax.make_jaxpr(flash)(q, k, v).jaxpr
    assert f"f32[1,{t},5120]" in str(jaxpr)
    assert "transpose" not in _outside_the_kernels(jaxpr)


def _outside_the_kernels(jaxpr):
    """The primitives of ``jaxpr`` and of the jaxprs it calls, the
    Pallas kernels' bodies left out."""
    names = []
    for eqn in jaxpr.eqns:
        names.append(eqn.primitive.name)
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                names += _outside_the_kernels(sub)
    return names


def test_the_dispatch_sends_the_cells_core_to_flash():
    """16,384 tokens, 20 heads of 256, bf16, causal: the logits would be
    10 GiB, so ``auto`` on a TPU takes flash; the tiny size takes XLA."""
    shape = (1, 16384, 20, 256)
    assert choose(shape, jnp.bfloat16, shape, kind="causal",
                  backend="tpu") == ("flash", None)
    assert choose((2, 64, 4, 16), jnp.float32, (2, 64, 4, 16),
                  kind="causal", backend="tpu") == ("xla", None)


# ----------------------------------------------------------------- router
def test_sigmoid_router_selects_by_score_and_bias_and_weights_by_score():
    logits = jax.random.normal(jax.random.key(0), (50, 8)) * 2
    ids, probs = moe.route(logits, 3, scoring="sigmoid", scale=1.8)
    scores = jax.nn.sigmoid(logits)
    np.testing.assert_array_equal(ids, jax.lax.top_k(scores, 3)[1])
    picked = jnp.take_along_axis(scores, ids, -1)
    np.testing.assert_allclose(
        probs, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(probs.sum(-1), 1.8, rtol=1e-6)
    # a bias moves the selection and not the weights of what is selected
    bias = jnp.zeros(8).at[5].set(10.0).at[0].set(-10.0)
    ids_b, probs_b = moe.route(logits, 3, scoring="sigmoid", bias=bias,
                               scale=1.8)
    assert bool(jnp.all(jnp.any(ids_b == 5, -1)))
    assert not bool(jnp.any(ids_b == 0))
    picked = jnp.take_along_axis(scores, ids_b, -1)
    np.testing.assert_allclose(
        probs_b, 1.8 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # no gradient reaches the bias; the logits get one
    g_bias, g_logits = jax.grad(
        lambda b, l: jnp.sum(moe.route(l, 3, scoring="sigmoid", bias=b)[1]
                             * jnp.arange(3.0)), (0, 1))(bias, logits)
    assert float(jnp.abs(g_bias).max()) == 0.0
    assert float(jnp.abs(g_logits).max()) > 0.0
    # SmallThinker's rule is what it was
    ids_s, probs_s = moe.route(logits, 3)
    vals, want = jax.lax.top_k(logits, 3)
    np.testing.assert_array_equal(ids_s, want)
    np.testing.assert_allclose(probs_s, jax.nn.softmax(vals, -1), rtol=1e-6)
    with pytest.raises(ValueError, match="router scoring"):
        moe.route(logits, 3, scoring="tanh")


@pytest.mark.parametrize("activation", sorted(moe.ACTIVATIONS))
def test_the_activation_is_stated_once_for_forward_and_backward(activation):
    """The hand-written backward pass of the experts reads the same table
    as the forward: value and gradient against plain differentiation."""
    act, through = moe.ACTIVATIONS[activation]
    gate = jnp.linspace(-4.0, 4.0, 41)
    up = jnp.linspace(0.5, 2.0, 41)
    dh = jnp.linspace(-1.0, 1.0, 41)
    np.testing.assert_allclose(moe.gated(gate, up, activation),
                               act(gate) * up)
    want = jax.vjp(lambda g: act(g) * up, gate)[1](dh)[0]
    np.testing.assert_allclose(through(gate, dh, up), want, atol=1e-6)


def _layer(cfg, seed=3, tokens=40):
    """A routed block's parameters with all experts held, and an input."""
    block = vit_module.RoutedMLPBlock(cfg.replace(experts_held=None))
    x = jax.random.normal(jax.random.key(seed), (2, tokens,
                                                 cfg.embedding_dim))
    params = _randomised(block.init(jax.random.key(seed + 1), x, x)["params"],
                         seed)
    return params, x


def test_silu_experts_equal_the_dense_loop():
    cfg = _tiny()
    params, x = _layer(cfg)
    whole = cfg.replace(experts_held=None)
    fields = dataclasses.asdict(whole)
    run = lambda p, x: vit_module.RoutedMLPBlock(whole).apply(
        {"params": p}, x, x) - x

    def want(p, x):
        u = jax.vmap(lambda s: reference_mla.rms_norm(
            s, p["norm"]["scale"], cfg.ln_epsilon))(x)
        return jnp.stack([reference_mla.routed_ffn(s, p, fields)
                          for s in u])

    np.testing.assert_allclose(run(params, x), want(params, x), atol=2e-5)
    cot = jax.random.normal(jax.random.key(9), x.shape)
    g = jax.grad(lambda p, x: jnp.sum(run(p, x) * cot), (0, 1))(params, x)
    w = jax.grad(lambda p, x: jnp.sum(want(p, x) * cot), (0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(w)):
        np.testing.assert_allclose(
            a, b, atol=3e-5 * float(jnp.abs(b).max()) + 1e-7,
            err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("shares", [8, 4, 2])
def test_the_shares_add_up_with_the_shared_expert_counted_once(shares):
    """An expert-parallel deployment's chips each add the part of their
    own experts; the shared expert is replicated and belongs to the sum
    once: the shares' routed parts plus the shared expert equal the uncut
    reference layer (8 shares of 1 expert as the cell's 8 chips of 8)."""
    cfg = _tiny()
    params, x = _layer(cfg)
    held = cfg.num_experts // shares
    fields = dataclasses.asdict(cfg.replace(experts_held=None))
    u = jax.vmap(lambda s: reference_mla.rms_norm(
        s, params["norm"]["scale"], cfg.ln_epsilon))(x)
    uncut = jnp.stack([reference_mla.routed_ffn(s, params, fields)
                       for s in u])
    shared = jnp.stack([reference_mla.gated(s, params["shared"])
                        for s in u])
    total = shared
    for share in range(shares):
        part = cfg.replace(experts_held=held, expert_offset=share * held)
        cut = {**params, **{k: params[k][share * held:(share + 1) * held]
                            for k in ("gate", "up", "down")}}
        y = vit_module.RoutedMLPBlock(part).apply({"params": cut}, x, x) - x
        # a share's block adds its routed part and the (replicated)
        # shared expert
        total = total + (y - shared)
        one = jnp.stack([reference_mla.routed_ffn(
            s, cut, fields, offset=share * held, shared=False) for s in u])
        np.testing.assert_allclose(y - shared, one, atol=2e-5)
    np.testing.assert_allclose(total, uncut, atol=5e-5)
