"""Attention-op tests: flash kernel (Pallas interpret mode on CPU) vs the
XLA reference path, forward and backward, aligned and ragged lengths."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pytorch_vit_paper_replication_tpu.ops.attention import (
    dot_product_attention)
from pytorch_vit_paper_replication_tpu.ops.flash_attention import (
    flash_attention)

# oneDNN's relaxed f32 matmuls on CPU introduce ~3e-3 noise in every path
# (measured); tolerances sit above that floor.
TOL = dict(rtol=2e-2, atol=2e-2)


def _qkv(seed, b, t, h, d, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), dtype) for k in ks)


@pytest.mark.parametrize("t", [128, 200, 577])
def test_flash_matches_xla_forward(t):
    q, k, v = _qkv(0, 2, t, 4, 64)
    ref = jax.nn.dot_product_attention(q, k, v)
    out = flash_attention(q, k, v, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_flash_matches_xla_backward():
    q, k, v = _qkv(1, 2, 256, 2, 64)

    def loss(fn):
        return lambda args: (fn(*args) ** 2).sum()

    g_ref = jax.grad(loss(jax.nn.dot_product_attention))((q, k, v))
    g = jax.grad(loss(
        lambda *a: flash_attention(*a, interpret=True)))((q, k, v))
    for name, a, b in zip("qkv", g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), err_msg=f"d{name}", **TOL)


def test_flash_backward_ragged_length():
    """Padded rows/cols must not leak gradient mass."""
    q, k, v = _qkv(2, 1, 200, 2, 64)

    def loss(fn):
        return lambda args: (fn(*args) ** 2).sum()

    g_ref = jax.grad(loss(jax.nn.dot_product_attention))((q, k, v))
    g = jax.grad(loss(
        lambda *a: flash_attention(*a, interpret=True)))((q, k, v))
    for name, a, b in zip("qkv", g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), err_msg=f"d{name}", **TOL)


def test_flash_bfloat16():
    q, k, v = _qkv(3, 2, 256, 2, 64, jnp.bfloat16)
    ref = jax.nn.dot_product_attention(q, k, v).astype(jnp.float32)
    out = flash_attention(q, k, v, interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("t", [197, 577])
def test_flash_bfloat16_full_forward_and_backward_at_vit_shapes(t):
    """``kind="full"`` (what a ViT run gets from ``--attention flash``,
    or from ``auto`` where ``[B,H,T,T]`` cannot fit) takes bf16 operands
    on the MXU and rounds p and ds to bf16 before PV, dq, dk and dv
    (since PR 27; float32 before). Against the float32 XLA result, as rms
    in units of its standard deviation: out 0.0022, dq / dk / dv 0.0028-
    0.0029 at both lengths over 3 seeds, which is what XLA's own bf16
    path reads there (0.0023-0.0024, 0.0028-0.0029): the rounding of the
    results, not of p."""
    ks = jax.random.split(jax.random.key(t), 4)
    q, k, v = (jax.random.normal(kk, (2, t, 3, 64), jnp.bfloat16)
               for kk in ks[:3])
    w = jax.random.normal(ks[3], (2, t, 3, 64))
    f32 = lambda x: x.astype(jnp.float32)

    def out_and_grads(fn, args):
        grads = jax.grad(lambda a: jnp.sum(f32(fn(*a)) * w))(args)
        return (fn(*args), *grads)

    want = out_and_grads(jax.nn.dot_product_attention,
                         tuple(map(f32, (q, k, v))))
    got = out_and_grads(lambda *a: flash_attention(*a, interpret=True),
                        (q, k, v))
    assert got[0].dtype == jnp.bfloat16
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        b = np.asarray(b, np.float64)
        err = np.sqrt(np.mean((np.asarray(f32(a), np.float64) - b) ** 2))
        assert err / b.std() < 0.004, (name, err / b.std())


def test_dispatch_xla_on_cpu():
    """auto must choose the XLA path on CPU regardless of length."""
    q, k, v = _qkv(4, 1, 640, 2, 64)
    out = dot_product_attention(q, k, v, impl="auto")
    ref = jax.nn.dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_attention_dropout_path():
    """attn_dropout > 0 takes the manual path and actually drops."""
    q, k, v = _qkv(5, 1, 64, 2, 32)
    a = dot_product_attention(q, k, v, impl="xla", dropout_rate=0.5,
                              dropout_rng=jax.random.key(1),
                              deterministic=False)
    b = dot_product_attention(q, k, v, impl="xla", dropout_rate=0.5,
                              dropout_rng=jax.random.key(2),
                              deterministic=False)
    assert not np.allclose(np.asarray(a), np.asarray(b))
    det = dot_product_attention(q, k, v, impl="xla", dropout_rate=0.5,
                                deterministic=True)
    ref = jax.nn.dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(det), np.asarray(ref), **TOL)


def test_xla_attention_bf16_scores_close_to_f32():
    """bfloat16 inputs store bf16 logits (the HBM optimization) but the
    result must stay close to the all-f32 computation."""
    q, k, v = _qkv(6, 2, 197, 4, 64)
    ref = np.asarray(dot_product_attention(q, k, v, impl="xla"))
    out = dot_product_attention(q.astype(jnp.bfloat16),
                                k.astype(jnp.bfloat16),
                                v.astype(jnp.bfloat16), impl="xla")
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), ref,
                               rtol=5e-2, atol=5e-2)


def test_xla_attention_bf16_gradients_finite_and_close():
    q, k, v = _qkv(7, 1, 64, 2, 32)

    def loss(args):
        return (dot_product_attention(*args, impl="xla")
                .astype(jnp.float32) ** 2).sum()

    g_ref = jax.grad(loss)((q, k, v))
    g_bf16 = jax.grad(loss)(tuple(a.astype(jnp.bfloat16) for a in (q, k, v)))
    for name, a, b in zip("qkv", g_bf16, g_ref):
        a = np.asarray(a, np.float32)
        assert np.isfinite(a).all(), f"d{name} not finite"
        np.testing.assert_allclose(a, np.asarray(b), rtol=1e-1, atol=1e-1,
                                   err_msg=f"d{name}")


# --- flash-attention in-kernel dropout (VERDICT r2 #7) ---------------------


def _recover_drop_mask(seed_rng, b, h, t, rate):
    """Extract the kernel's [bh, t, t] keep mask: with q=k=0 the attention
    weights are uniform 1/t > 0, and v=I makes each output row the dropped
    weight row itself — zero exactly where the mask dropped."""
    z = jnp.zeros((b, t, h, t), jnp.float32)
    eye = jnp.broadcast_to(jnp.eye(t, dtype=jnp.float32)[None, :, None, :],
                           (b, t, h, t))
    out = flash_attention(z, z, eye, dropout_rate=rate,
                          dropout_rng=seed_rng, deterministic=False,
                          interpret=True)
    # out[b, q, h, j] = M[bh, q, j] * (1/t) / keep
    weights = np.asarray(out).transpose(0, 2, 1, 3).reshape(b * h, t, t)
    return weights > 0.0, weights


def test_flash_dropout_mask_statistics():
    """The in-kernel hash mask drops at the quantized rate, independently
    across rows/heads, and survivors are rescaled exactly unbiased."""
    rate = 0.25                      # threshold 64: keep = 192/256 = 0.75
    b, h, t = 2, 2, 256
    mask, weights = _recover_drop_mask(jax.random.key(9), b, h, t, rate)
    frac = 1.0 - mask.mean()
    # 262k Bernoulli(0.25) draws: 5 sigma ~ 0.004
    assert abs(frac - 0.25) < 0.01, f"drop fraction {frac}"
    # Survivors carry exactly (1/t)/keep — the unbiased rescale.
    np.testing.assert_allclose(weights[mask], (1.0 / t) / 0.75, rtol=1e-5)
    # Per-(head, row) drop counts stay near t*rate (no row/head banding).
    per_row = 1.0 - mask.mean(axis=-1)           # [bh, t]
    assert abs(per_row.mean() - 0.25) < 0.01
    assert per_row.std() < 4 * np.sqrt(0.25 * 0.75 / t)
    # Different heads get different masks.
    assert (mask[0] != mask[1]).mean() > 0.1


def test_flash_dropout_seeding():
    q, k, v = _qkv(6, 2, 256, 2, 64)
    kw = dict(dropout_rate=0.3, deterministic=False, interpret=True)
    a1 = flash_attention(q, k, v, dropout_rng=jax.random.key(1), **kw)
    a2 = flash_attention(q, k, v, dropout_rng=jax.random.key(1), **kw)
    b2 = flash_attention(q, k, v, dropout_rng=jax.random.key(2), **kw)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    assert not np.allclose(np.asarray(a1), np.asarray(b2))
    det = flash_attention(q, k, v, dropout_rate=0.3, deterministic=True,
                          interpret=True)
    ref = flash_attention(q, k, v, interpret=True)
    np.testing.assert_array_equal(np.asarray(det), np.asarray(ref))


def test_flash_dropout_forward_backward_match_masked_reference():
    """EXACT check of the dropout fwd+bwd kernels: recover the kernel's own
    mask (it depends only on (seed, head, row, col), never on q/k/v), build
    the explicit masked-attention reference with it, and require outputs
    AND all three gradients to agree."""
    rate, b, t, h, d = 0.25, 2, 256, 2, 64
    rng = jax.random.key(4)
    mask, _ = _recover_drop_mask(rng, b, h, t, rate)
    mask = jnp.asarray(mask.reshape(b, h, t, t))
    q, k, v = _qkv(7, b, t, h, d)

    def flash_fn(args):
        out = flash_attention(*args, dropout_rate=rate, dropout_rng=rng,
                              deterministic=False, interpret=True)
        return (out.astype(jnp.float32) ** 2).sum()

    def ref_fn(args):
        q, k, v = args
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
        p = jax.nn.softmax(s, axis=-1)
        z = jnp.where(mask, p, 0.0) / 0.75
        out = jnp.einsum("bhqk,bkhd->bqhd", z, v)
        return (out ** 2).sum()

    np.testing.assert_allclose(flash_fn((q, k, v)), ref_fn((q, k, v)),
                               rtol=1e-3)
    g = jax.grad(flash_fn)((q, k, v))
    g_ref = jax.grad(ref_fn)((q, k, v))
    for name, a, r in zip("qkv", g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   err_msg=f"d{name}", **TOL)


def test_flash_backward_with_mask_dropout_and_ragged_length_at_once():
    """The one backward kernel with everything a ViT caller can ask of
    it in one call: a key-padding mask, in-kernel dropout (the mask
    recovered from the kernel itself), T = 200 over unequal blocks of 64
    queries and 32 keys (padding on both sides): output and the three
    gradients against the explicit masked reference."""
    rate, b, t, h, d = 0.25, 2, 200, 2, 64
    rng = jax.random.key(4)
    drop, _ = _recover_drop_mask(rng, b, h, t, rate)
    drop = jnp.asarray(drop.reshape(b, h, t, t))
    attend = jax.random.bernoulli(jax.random.key(21), 0.8, (b, 1, 1, t))
    attend = attend.at[..., 0].set(True)
    q, k, v = _qkv(9, b, t, h, d)

    def flash_fn(args):
        out = flash_attention(*args, mask=attend, dropout_rate=rate,
                              dropout_rng=rng, deterministic=False,
                              block_q=64, block_k=32, interpret=True)
        return (out.astype(jnp.float32) ** 2).sum()

    def ref_fn(args):
        q, k, v = args
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
        p = jax.nn.softmax(jnp.where(attend, s, -jnp.inf), axis=-1)
        z = jnp.where(drop, p, 0.0) / 0.75
        return (jnp.einsum("bhqk,bkhd->bqhd", z, v) ** 2).sum()

    np.testing.assert_allclose(flash_fn((q, k, v)), ref_fn((q, k, v)),
                               rtol=1e-3)
    g = jax.grad(flash_fn)((q, k, v))
    g_ref = jax.grad(ref_fn)((q, k, v))
    for name, a, r in zip("qkv", g, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   err_msg=f"d{name}", **TOL)


@pytest.mark.parametrize("blocks", [(16, 16), (16, 32), (32, 16)], ids=str)
def test_flash_backward_adds_each_visible_block_once(blocks):
    """What the backward kernel keeps from grid step to grid step: a
    window of 20 over T = 96 (6 blocks of 16 a side, 3 query heads to a
    key/value head, 2 of those, 2 sequences). A query block adds to two
    or three key blocks' rows of the key/value head's ``dk`` / ``dv``
    slabs and to nothing of the rest, a key block's first add comes from
    an edge block (the diagonal), the slabs go on through the group's
    three query heads, and every key/value head after the first finds
    them as the last one left them: a slab not zeroed at its first step,
    a block added twice or one left out moves a gradient by its own
    size, not by 1e-5."""
    t, window = 96, 20
    ks = jax.random.split(jax.random.key(8), 4)
    q = jax.random.normal(ks[0], (2, t, 6, 16))
    k = jax.random.normal(ks[1], (2, t, 2, 16))
    v = jax.random.normal(ks[2], (2, t, 2, 16))
    cot = jax.random.normal(ks[3], q.shape)

    def dense(q, k, v):
        kk, vv = jnp.repeat(k, 3, axis=2), jnp.repeat(v, 3, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * 16 ** -0.5
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
        p = jax.nn.softmax(
            jnp.where((j <= i) & (i - j < window), s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, vv)

    flash = lambda q, k, v: flash_attention(
        q, k, v, kind="causal_window", window=window, block_q=blocks[0],
        block_k=blocks[1], interpret=True)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * cot), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * cot), (0, 1, 2))(q, k, v)
    for name, g, w in zip("qkv", got, want):
        np.testing.assert_allclose(g, w, atol=5e-5, err_msg=f"d{name}")


def test_flash_dropout_actually_drops():
    """Kernel-path dropout visibly perturbs the output vs deterministic
    (and VERDICT r2 #7's done-criterion: dropout no longer forces the
    dispatch fallback — see the mask-only warning in attention.py)."""
    q, k, v = _qkv(8, 1, 128, 2, 32)
    out = flash_attention(q, k, v, dropout_rate=0.5,
                          dropout_rng=jax.random.key(3),
                          deterministic=False, interpret=True)
    base = flash_attention(q, k, v, interpret=True)
    assert not np.allclose(np.asarray(out), np.asarray(base))


# --------------------------------------------------------------------------
# Attention masks in the flash kernel (round 4 — previously an XLA
# fallback; VERDICT r3 #8). Broadcast layouts stream unmaterialized.
# --------------------------------------------------------------------------

def _xla_masked(q, k, v, mask):
    from pytorch_vit_paper_replication_tpu.ops.attention import (
        _xla_attention)
    return _xla_attention(q, k, v, dropout_rate=0.0, dropout_rng=None,
                          deterministic=True, mask=mask)


@pytest.mark.parametrize("mask_shape", [
    (2, 1, 1, 200),      # key-padding, streams O(B*T)
    (1, 1, 200, 200),    # shared full mask
    (1, 2, 200, 200),    # per-head
    (2, 2, 200, 200),    # fully materialized
])
def test_flash_mask_matches_xla(mask_shape):
    q, k, v = _qkv(3, 2, 200, 2, 64)
    mask = jax.random.bernoulli(jax.random.key(11), 0.8, mask_shape)
    mask = mask.at[..., 0].set(True)  # no fully-masked rows (degenerate)
    out = flash_attention(q, k, v, mask=mask, interpret=True)
    ref = _xla_masked(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_flash_mask_backward_matches_xla():
    q, k, v = _qkv(4, 2, 256, 2, 64)
    mask = jax.random.bernoulli(jax.random.key(12), 0.7, (2, 1, 1, 256))
    mask = mask.at[..., 0].set(True)

    def loss(fn):
        return lambda args: (fn(*args) ** 2).sum()

    g_ref = jax.grad(loss(lambda *a: _xla_masked(*a, mask)))((q, k, v))
    g = jax.grad(loss(lambda *a: flash_attention(
        *a, mask=mask, interpret=True)))((q, k, v))
    for name, a, b in zip("qkv", g, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), err_msg=f"d{name}", **TOL)


def test_flash_mask_composes_with_dropout():
    q, k, v = _qkv(5, 2, 128, 2, 32)
    mask = jax.random.bernoulli(jax.random.key(13), 0.8, (2, 1, 1, 128))
    mask = mask.at[..., 0].set(True)
    out = flash_attention(q, k, v, mask=mask, dropout_rate=0.3,
                          dropout_rng=jax.random.key(14),
                          deterministic=False, interpret=True)
    assert bool(jnp.isfinite(out).all())
    base = flash_attention(q, k, v, mask=mask, interpret=True)
    assert not np.allclose(np.asarray(out), np.asarray(base))


def test_xla_saturating_softmax_semantics():
    """r5: the XLA path's softmax drops the row-max read for a constant
    shift + clamp + eps (PERF.md r5). Contract: (a) bit-comparable to
    the textbook max-subtracted softmax at healthy logit scales, (b)
    finite (saturated), not NaN, at absurd logit scales, (c) zero output
    for fully-masked rows — agreeing with the flash kernel."""
    from pytorch_vit_paper_replication_tpu.ops.attention import (
        _xla_attention)

    b, t, h, dh = 2, 48, 2, 16
    ks = jax.random.split(jax.random.key(21), 3)
    q, k, v = (jax.random.normal(kk, (b, t, h, dh), jnp.float32)
               for kk in ks)

    def textbook(q, k, v):
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
        w = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", w, v)

    got = _xla_attention(q, k, v, dropout_rate=0.0, dropout_rng=None,
                         deterministic=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(textbook(q, k, v)),
                               rtol=2e-5, atol=2e-5)

    # (b) logits ~ 64*1000/4 >> the 96 saturation point: finite, and the
    # saturated entries share the mass uniformly.
    big = _xla_attention(1000.0 * q, 1000.0 * k, v, dropout_rate=0.0,
                         dropout_rng=None, deterministic=True)
    assert bool(jnp.isfinite(big).all())

    # (b') the documented NEGATIVE edge: rows whose logits ALL sit
    # below the f32 exp-underflow point (post-shift ~-87) collapse to
    # the defined zero output via 0/eps — not NaN from 0/0. q = c,
    # k = -c makes every logit exactly -dh*c^2/sqrt(dh) = -sqrt(16)*36
    # = -144 here.
    qn = jnp.full_like(q, 6.0)
    kn = jnp.full_like(k, -6.0)
    neg = _xla_attention(qn, kn, v, dropout_rate=0.0,
                         dropout_rng=None, deterministic=True)
    np.testing.assert_array_equal(np.asarray(neg), 0.0)
    # The "exact" flavor stays a true softmax there (all-equal logits
    # -> uniform weights -> mean of v), magnitude notwithstanding.
    neg_ex = _xla_attention(qn, kn, v, dropout_rate=0.0,
                            dropout_rng=None, deterministic=True,
                            softmax="exact")
    np.testing.assert_allclose(np.asarray(neg_ex),
                               np.asarray(jnp.broadcast_to(
                                   v.mean(axis=1, keepdims=True),
                                   v.shape)), rtol=1e-5, atol=1e-5)

    # The "exact" escape hatch (config.attention_softmax, for
    # attention-logit-growth regimes): max-subtracted, so the same huge
    # logits produce the TRUE argmax-dominated distribution, not the
    # saturated-uniform one — and at healthy scales it matches textbook.
    ex = _xla_attention(q, k, v, dropout_rate=0.0, dropout_rng=None,
                        deterministic=True, softmax="exact")
    np.testing.assert_allclose(np.asarray(ex), np.asarray(textbook(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    big_ex = _xla_attention(1000.0 * q, 1000.0 * k, v, dropout_rate=0.0,
                            dropout_rng=None, deterministic=True,
                            softmax="exact")
    assert bool(jnp.isfinite(big_ex).all())
    assert not np.allclose(np.asarray(big_ex), np.asarray(big))

    # (c) fully-masked row -> zero (flash agreement).
    mask = jnp.ones((1, 1, t, t), bool).at[:, :, 3].set(False)
    out = _xla_attention(q, k, v, dropout_rate=0.0, dropout_rng=None,
                         deterministic=True, mask=mask)
    np.testing.assert_array_equal(np.asarray(out[:, 3]), 0.0)


@pytest.mark.parametrize("blocks", [(None, None), (32, 16)], ids=str)
def test_flash_mask_fully_masked_rows_zero_and_consistent(blocks):
    """ADVICE r4: a query row attending to NO key must have a DEFINED
    result — zero output with zero gradient, forward and backward
    agreeing (previously the forward degenerated to uniform attention
    while the backward kernels zeroed p, so fwd and bwd disagreed).
    The forward tells such a row apart in its epilogue (its running
    maximum never left ``_NEG_INF``): with blocks of 16 keys the row's
    masked keys pass through eight blocks, interior steps of three among
    them, before it does."""
    t = 128
    q, k, v = _qkv(15, 1, t, 2, 32)
    mask = jnp.ones((1, 1, t, t), bool).at[:, :, 5].set(False)
    kw = dict(mask=mask, block_q=blocks[0], block_k=blocks[1],
              interpret=True)

    out, lse = flash_attention(q, k, v, return_lse=True, **kw)
    np.testing.assert_array_equal(np.asarray(out[:, 5]), 0.0)
    np.testing.assert_array_equal(np.asarray(lse[:, :, 5]), np.float32(-1e30))
    out = flash_attention(q, k, v, **kw)
    # Other rows are untouched by the degenerate one.
    ref = _xla_masked(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out[:, :5]),
                               np.asarray(ref[:, :5]), **TOL)

    def loss(args):
        return (flash_attention(*args, **kw) ** 2).sum()

    gq, gk, gv = jax.grad(loss)((q, k, v))
    assert bool(jnp.isfinite(gq).all() and jnp.isfinite(gk).all()
                and jnp.isfinite(gv).all())
    # The masked row's query gets no gradient (its output is constant 0);
    # k/v gradients receive nothing FROM that row (checked via a probe:
    # perturbing row 5's query cannot change the loss).
    np.testing.assert_array_equal(np.asarray(gq[:, 5]), 0.0)


@pytest.mark.parametrize("kind", ["full", "causal"])
def test_flash_row_whose_first_blocks_select_nothing(kind):
    """A row whose first visited key blocks hold none of its selected
    keys (the forward's maximum stays ``_NEG_INF`` over them and every
    masked key adds 1 to its sum) and whose later ones do: the blocks
    before are wiped by the first correction, ``exp2(_NEG_INF - m) =
    0``, so the row is the exact softmax of its selected keys (output
    and ``lse``), whichever of the forward's loops saw them."""
    t = 128
    q, k, v = _qkv(17, 1, t, 2, 32)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
    chosen = jax.random.bernoulli(jax.random.key(18), 0.5, (t, t))
    # rows 100-103 select keys from 80 on only: blocks 0-4 of 16 are bare
    late = (i >= 100) & (i < 104)
    mask = jnp.where(late, chosen & (j >= 80), chosen) | (i == j)
    if kind == "causal":
        mask = mask & (j <= i)
    out, lse = flash_attention(q, k, v, kind=kind, mask=mask[None, None],
                               block_q=32, block_k=16, interpret=True,
                               return_lse=True)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 32 ** -0.5
    s = jnp.where(mask, s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)
    np.testing.assert_allclose(out[:, 100:104], want[:, 100:104],
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse[:, :, 100:104],
                               jax.nn.logsumexp(s, axis=-1)[:, :, 100:104],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)


def test_flash_mask_bad_shape_raises():
    q, k, v = _qkv(6, 2, 128, 2, 32)
    with pytest.raises(ValueError, match="broadcast"):
        flash_attention(q, k, v, mask=jnp.ones((3, 1, 1, 128), bool),
                        interpret=True)


def test_dispatch_forced_flash_with_mask_stays_flash():
    """impl='flash' + mask no longer falls back: results still match the
    XLA reference (they agree numerically, so equality of values is the
    observable; absence of the old warning is the contract)."""
    import warnings
    q, k, v = _qkv(7, 1, 128, 2, 32)
    mask = jax.random.bernoulli(jax.random.key(15), 0.8, (1, 1, 1, 128))
    mask = mask.at[..., 0].set(True)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the old path warned once
        out = dot_product_attention(q, k, v, impl="flash", mask=mask)
    ref = _xla_masked(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


def test_flash_mask_key_broadcast_dim():
    """A [B,1,Tq,1] query-row mask (key dim broadcast) worked via the old
    XLA fallback; the kernel path must keep accepting it (it broadcasts
    the Tk axis internally — round-4 review finding). A False row here
    masks the ENTIRE query row: those rows get the defined zero output
    (ADVICE r4), every attending row must match the XLA reference."""
    q, k, v = _qkv(8, 2, 128, 2, 32)
    mask = jax.random.bernoulli(jax.random.key(16), 0.7, (2, 1, 128, 1))
    mask = mask.at[:, :, 0].set(True)
    out = np.asarray(flash_attention(q, k, v, mask=mask, interpret=True))
    ref = np.asarray(_xla_masked(q, k, v, mask))
    rows = np.asarray(mask)[:, 0, :, 0]  # [B, Tq] True = row attends
    np.testing.assert_allclose(out[rows], ref[rows], **TOL)
    np.testing.assert_array_equal(out[~rows], 0.0)


def test_flash_on_a_mesh_draws_the_unsharded_dropout_masks(devices):
    """Under a dp x tp mesh the flash kernel runs per shard of batch and
    heads; its mask hash is keyed on the GLOBAL batch·head index (the
    shard's offsets ride the scalar prefetch), so no two shards share a
    mask and the result — values and grads, key-padding mask included —
    is the unsharded call's."""
    from pytorch_vit_paper_replication_tpu.configs import MeshConfig
    from pytorch_vit_paper_replication_tpu.ops import on_mesh
    from pytorch_vit_paper_replication_tpu.parallel.mesh import make_mesh

    ks = jax.random.split(jax.random.key(0), 4)
    q, k, v = (jax.random.normal(kk, (4, 24, 4, 32)) for kk in ks[:3])
    mask = jax.random.bernoulli(ks[3], 0.8, (4, 1, 1, 24))

    def loss(q, k, v, mask):
        return (flash_attention(
            q, k, v, mask=mask, dropout_rate=0.25,
            dropout_rng=jax.random.key(3), deterministic=False,
            block_q=8, block_k=8) ** 2).sum()

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    for m, dp, tp in ((None, 4, 1), (mask, 2, 2)):
        want = grad(q, k, v, m)
        mesh = make_mesh(MeshConfig(data=dp, model=tp), devices[:dp * tp])
        with on_mesh(mesh):
            got = jax.jit(grad)(q, k, v, m)
        for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(g, w, rtol=2e-4, atol=5e-3)


# --------------------------------------------------------------------------
# The short-sequence kernel pair on the packed qkv projection
# (ops/short_attention.py), in the interpreter
# --------------------------------------------------------------------------

from jax.experimental import pallas as pl  # noqa: E402

from pytorch_vit_paper_replication_tpu.ops.attention import (  # noqa: E402
    _xla_attention)
from pytorch_vit_paper_replication_tpu.ops.short_attention import (  # noqa: E402
    short_attention)

# (B, T, H, Dh): the cells' length; an odd batch and more than one slab of
# heads; a sequence of a few rows; one that fills its block; one head a
# slab; a batch that overhangs its last block of images (6 in blocks of 4)
SHORT_SHAPES = [(2, 197, 2, 64), (3, 197, 4, 64), (2, 5, 2, 64),
                (2, 256, 2, 64), (2, 37, 1, 128), (6, 37, 2, 64)]
SHORT_DTYPES = [(jnp.float32, TOL), (jnp.bfloat16, dict(rtol=5e-2, atol=5e-2))]


def _packed(seed, b, t, h, d, dtype, scale=1.0):
    qkv = scale * jax.random.normal(jax.random.key(seed), (b, t, 3, h, d))
    return qkv.astype(dtype)


def _exact_on_slices(qkv):
    """The XLA path's exact softmax on slices of the same array."""
    return _xla_attention(qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                          dropout_rate=0.0, dropout_rng=None,
                          deterministic=True, softmax="exact")


def _short(qkv):
    return short_attention(qkv, interpret=True)


@pytest.mark.parametrize("dtype,tol", SHORT_DTYPES,
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", SHORT_SHAPES, ids=str)
def test_short_attention_forward_matches_exact_xla(shape, dtype, tol):
    qkv = _packed(10, *shape, dtype)
    out = _short(qkv)
    assert out.shape == shape[:2] + shape[2:] and out.dtype == dtype
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_exact_on_slices(qkv), np.float32), **tol)


@pytest.mark.parametrize("dtype,tol", SHORT_DTYPES,
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", SHORT_SHAPES, ids=str)
def test_short_attention_grad_of_the_packed_projection(shape, dtype, tol):
    """One packed cotangent ``[B, T, 3, H, Dh]``: dq, dk and dv in the
    thirds the projection's backward reads them from."""
    qkv = _packed(11, *shape, dtype)
    do = jax.random.normal(jax.random.key(12), shape).astype(dtype)

    def loss(fn):
        return lambda x: jnp.sum((fn(x) * do).astype(jnp.float32))

    g = jax.grad(loss(_short))(qkv)
    g_ref = jax.grad(loss(_exact_on_slices))(qkv)
    assert g.shape == qkv.shape and g.dtype == dtype
    for i, name in enumerate(("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(g[:, :, i], np.float32),
            np.asarray(g_ref[:, :, i], np.float32), err_msg=name, **tol)


@pytest.mark.parametrize("what", ["o", "dq", "dk", "dv"])
def test_short_attention_overhang_carries_nothing(what):
    """T = 197 lies in one block of 256 rows; what the block holds past
    T is whatever was there. The interpreter fills it with NaN (shown
    first), so outputs that are finite and right have taken nothing
    from it: the kernels zero the overhang of q, k, v and do and mask
    the key rows and the log-sum-exp lanes past T."""
    probe = pl.pallas_call(
        lambda x_ref, o_ref: o_ref.__setitem__(..., x_ref[...]),
        grid=(1,),
        in_specs=[pl.BlockSpec((1, 256, 128), lambda i: (0, 0, 0))],
        out_specs=pl.BlockSpec((1, 256, 128), lambda i: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, 256, 128), jnp.float32),
        interpret=True)(jnp.ones((1, 197, 128)))
    assert np.isnan(np.asarray(probe[:, 197:])).all()

    qkv = _packed(13, 2, 197, 2, 64, jnp.float32)
    if what == "o":
        out, ref = _short(qkv), _exact_on_slices(qkv)
    else:
        i = ("dq", "dk", "dv").index(what)
        out = jax.grad(lambda x: jnp.sum(_short(x) ** 2))(qkv)[:, :, i]
        ref = jax.grad(
            lambda x: jnp.sum(_exact_on_slices(x) ** 2))(qkv)[:, :, i]
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_short_attention_exact_softmax_at_logits_of_200(dtype):
    """The saturating flavour's exact range ends near 96; the kernel's
    max-subtracted softmax has none."""
    # q . k of magnitude ~ 200 * sqrt(Dh) before the 1/sqrt(Dh) scale
    qkv = _packed(14, 2, 197, 2, 64, dtype, scale=200.0 ** 0.5)
    assert float(jnp.abs(jnp.einsum(
        "bqhd,bkhd->bhqk", qkv[:, :, 0], qkv[:, :, 1],
        preferred_element_type=jnp.float32)).max()) / 8 > 200
    out = _short(qkv)
    g = jax.grad(lambda x: jnp.sum(_short(x).astype(jnp.float32)))(qkv)
    assert np.isfinite(np.asarray(out, np.float32)).all()
    assert np.isfinite(np.asarray(g, np.float32)).all()
    # against f32 logits of the same inputs: the XLA path would round
    # them to the compute dtype, 0.8 of a logit at this magnitude in bf16
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(_exact_on_slices(qkv.astype(jnp.float32))),
        rtol=5e-2, atol=5e-2 * float(jnp.abs(qkv).max()))
