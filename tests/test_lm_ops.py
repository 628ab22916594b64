"""The token model's operators at tiny shapes on the CPU (Pallas in the
interpreter): the flash kernels' causal / causal-window structure with
grouped heads against an explicit visibility matrix, the dispatch's
structure argument, rotary positions per layer, the routed experts'
shares against the plain reference (``benchmark/lib/reference_lm.py``),
and the head + loss in chunks.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_lm
from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.models import vit as vit_module
from pytorch_vit_paper_replication_tpu.ops import moe
from pytorch_vit_paper_replication_tpu.ops.attention import (
    choose, dot_product_attention)
from pytorch_vit_paper_replication_tpu.ops.flash_attention import (
    flash_attention)
from pytorch_vit_paper_replication_tpu.ops.lm_loss import head_cross_entropy


def _tiny(**kw):
    # float32 compute: the comparison is of the mathematics
    return LM_PRESETS["lm-tiny"](dtype="float32", **kw)


# ------------------------------------------------------- attention kinds
def _dense_attention(q, k, v, kind, window):
    b, t, h, d = q.shape
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
    visible = j <= i
    if kind == "causal_window":
        visible = visible & (i - j < window)
    p = jax.nn.softmax(jnp.where(visible, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def _flash_against_dense(shape, kv_heads, kind, window, blocks):
    """Forward and the three gradients of the flash kernels, through the
    Pallas interpreter, against the explicit visibility matrix."""
    b, t, _, d = shape
    ks = jax.random.split(jax.random.key(5), 4)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], (b, t, kv_heads, d))
    v = jax.random.normal(ks[2], (b, t, kv_heads, d))
    cot = jax.random.normal(ks[3], shape)
    flash = lambda q, k, v: flash_attention(
        q, k, v, kind=kind, window=window, block_q=blocks[0],
        block_k=blocks[1], interpret=True)
    dense = lambda q, k, v: _dense_attention(q, k, v, kind, window)
    np.testing.assert_allclose(flash(q, k, v), dense(q, k, v), atol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * cot), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * cot), (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=5e-5)


@pytest.mark.parametrize("kind,window,blocks", [
    ("causal", 0, (16, 16)),
    ("causal_window", 12, (16, 16)),
    ("causal_window", 40, (16, 16)),      # wider than a block
    ("causal_window", 12, (32, 8)),       # unequal blocks
    ("causal", 0, (8, 8)),                # interior steps of 3 and left over
    ("causal_window", 33, (8, 8)),
])
def test_flash_kernels_compute_the_structure_from_positions(
        kind, window, blocks):
    """7 query heads to a key/value head, T = 50 (no multiple of a
    block): the folded layout (Dh = 16). With blocks of 8 a query block
    has up to 6 interior key blocks: the forward takes them three a step
    and the rest one a step."""
    _flash_against_dense((2, 50, 7, 16), 1, kind, window, blocks)


@pytest.mark.parametrize("shape,kv_heads,kind,window,blocks", [
    ((2, 50, 7, 16), 1, "causal", 0, (8, 8)),            # folded, Dh 16
    ((1, 70, 4, 64), 2, "causal_window", 21, (16, 8)),   # folded, Dh 64
    ((1, 70, 4, 128), 2, "causal", 0, (16, 16)),         # flat, Dh 128
    ((1, 40, 2, 256), 2, "causal", 0, (8, 8)),           # flat, Dh 256
    ((2, 57, 3, 64), 3, "full", 0, (16, 8)),             # padded keys
], ids=["folded16", "folded64-window", "flat128", "flat256", "full-padded"])
def test_flash_lse_is_the_log_sum_exp_of_the_visible_logits(
        shape, kv_heads, kind, window, blocks):
    """The forward's row statistic, which leaves its loop in base 2
    (``exp2`` of logits scaled by ``log2 e``) and is turned to natural
    units in the epilogue, is ``logsumexp`` over the visible keys to
    float32 rounding, as the parent's ``m + log l`` was."""
    b, t, h, d = shape
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], shape)
    k = jax.random.normal(ks[1], (b, t, kv_heads, d))
    v = jax.random.normal(ks[2], (b, t, kv_heads, d))
    _, lse = flash_attention(q, k, v, kind=kind, window=window,
                             block_q=blocks[0], block_k=blocks[1],
                             interpret=True, return_lse=True)
    kk = jnp.repeat(k, h // kv_heads, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) * d ** -0.5
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None]
    visible = (j <= i) if kind != "full" else jnp.ones((t, t), bool)
    if window:
        visible = visible & (i - j < window)
    want = jax.nn.logsumexp(jnp.where(visible, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse, want, rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("kv_heads", [2, 1])
@pytest.mark.parametrize("kind,window,blocks", [
    ("causal", 0, (16, 16)),
    ("causal_window", 21, (32, 16)),      # no multiple of either block
    ("causal_window", 21, (16, 32)),
])
def test_flash_kernels_read_heads_as_column_blocks_of_the_projection(
        kind, window, blocks, kv_heads):
    """The flat layout (Dh = 128: q ``[B, T, H*Dh]`` read where the
    projection left it, 4 query heads over 2 and over 1 key/value
    heads), T = 70: the one backward kernel takes the group's sum
    inside."""
    _flash_against_dense((2, 70, 4, 128), kv_heads, kind, window, blocks)


@pytest.mark.parametrize("shape,kv_heads,extra", [
    ((2, 300, 14, 128), 2, dict(kind="causal")),            # flat, group 7
    ((2, 300, 14, 128), 2, dict(kind="causal_window", window=100)),
    ((2, 577, 4, 64), 4, dict(                               # folded, group 1
        mask=np.ones((2, 1, 1, 577), bool), dropout_rate=0.1,
        dropout_rng=jax.random.key(0), deterministic=False)),
    ((2, 300, 6, 64), 2, dict(kind="causal")),               # folded, group 3
], ids=["flat-causal", "flat-window", "folded-mask-dropout",
        "folded-grouped"])
def test_the_backward_of_flash_attention_is_one_mosaic_call(
        shape, kv_heads, extra):
    """Lowered for the TPU (nothing compiled, nothing run): the forward
    kernel and ONE backward kernel, whatever the group, the layout, the
    structure, a mask or dropout."""
    from pytorch_vit_paper_replication_tpu.ops.partition import mosaic_calls

    b, t, _, dh = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, t, kv_heads, dh), jnp.bfloat16)
    grad = jax.jit(jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, interpret=False, **extra).astype(jnp.float32)),
        argnums=(0, 1, 2)))
    text = grad.trace(q, kv, kv).lower(lowering_platforms=("tpu",)).as_text()
    assert [name for name, _ in mosaic_calls(text)] == [
        "flash_fwd", "flash_bwd"]


@pytest.mark.parametrize("kind,window", [("causal", 0),
                                         ("causal_window", 5)])
def test_dispatch_takes_the_kind_as_structure(kind, window):
    """Off the TPU the XLA path builds the matrix the kind stands for,
    for grouped heads too; and the short-sequence kernel goes on
    refusing a kind it cannot do."""
    ks = jax.random.split(jax.random.key(6), 3)
    q = jax.random.normal(ks[0], (1, 20, 4, 8))
    k = jax.random.normal(ks[1], (1, 20, 2, 8))
    v = jax.random.normal(ks[2], (1, 20, 2, 8))
    got = dot_product_attention(q, k, v, kind=kind, window=window)
    np.testing.assert_allclose(got, _dense_attention(q, k, v, kind, window),
                               atol=2e-5)
    ask = dict(impl="auto", dropout_rate=0.0, deterministic=True,
               mask=None, backend="tpu")
    shape = (256, 197, 3, 12, 64)
    assert choose(shape, jnp.bfloat16, **ask)[0] == "short"
    assert choose(shape, jnp.bfloat16, kind=kind, **ask)[0] == "xla"


def test_rotary_follows_rope_layout():
    """Layer 0 (rope_layout 0) is position-free: with every layer's
    window lifted, a model of that layer alone gives a permutation of
    the earlier tokens the same last-token output; a layer with rotary
    positions does not. The matrices start at 1/sqrt(width), so that the
    layer adds as much to the stream as the embedding's rows hold."""
    def last_token(layout):
        cfg = _tiny(num_layers=1, rope_layout=layout,
                    sliding_window_layout=(0,), init_std=0.125)
        model = ViT(cfg)
        ids = jax.random.randint(jax.random.key(7), (1, 12), 0,
                                 cfg.vocab_size)
        params = model.init(jax.random.key(8), ids)["params"]
        perm = jnp.concatenate([ids[:, :11][:, ::-1], ids[:, 11:]], 1)
        a = model.apply({"params": params}, ids, False)[0, -1]
        b = model.apply({"params": params}, perm, False)[0, -1]
        return float(jnp.abs(a - b).max())

    assert last_token((0,)) < 1e-5
    assert last_token((1,)) > 1e-3
    cfg = _tiny()
    assert [cfg.layer_rope(i) for i in range(4)] == [False, True, True, True]
    assert [cfg.attention_kind(i)[0] for i in range(4)] == [
        "causal", "causal_window", "causal_window", "causal_window"]
    x = jax.random.normal(jax.random.key(9), (1, 6, 2, 16))
    want = jnp.stack([reference_lm.rotary(x[0], cfg.rope_theta)])
    np.testing.assert_allclose(vit_module.rotary(x, cfg.rope_theta), want,
                               atol=1e-6)


# ------------------------------------------------------------ the experts
def _layer(held, offset, key=10):
    """A routed layer's inputs and 8 experts' weights; ``held`` from
    ``offset`` are handed to the program and to the reference."""
    ks = jax.random.split(jax.random.key(key), 6)
    d, f, e = 32, 16, 8
    u = jax.random.normal(ks[0], (2, 24, d))
    h = jax.random.normal(ks[1], (2, 24, d))
    p = {"router": {"kernel": jax.random.normal(ks[2], (d, e))},
         "gate": 0.2 * jax.random.normal(ks[3], (e, d, f)),
         "up": 0.2 * jax.random.normal(ks[4], (e, d, f)),
         "down": 0.2 * jax.random.normal(ks[5], (e, f, d))}
    share = {**p, **{k: p[k][offset:offset + held]
                     for k in ("gate", "up", "down")}}
    return u, h, p, share


def _program_share(u, h, share, offset, tile=8):
    logits = jnp.einsum("btd,de->bte", h, share["router"]["kernel"],
                        precision="highest")
    ids, probs = moe.route(logits, 2)
    return moe.moe_experts(u, ids, probs, share["gate"], share["up"],
                           share["down"], expert_offset=offset,
                           num_experts=logits.shape[-1], tile=tile)


@pytest.mark.parametrize("shares", [((4, 0), (4, 4)),
                                    ((2, 0), (2, 2), (2, 4), (2, 6))])
def test_the_shares_add_up_to_the_uncut_layer(shares):
    """The parts of ``y`` that every chip's experts give sum to the uncut
    reference's ``y`` for the whole layer (the attention part, which
    every chip computes alike, is not in ``y`` and so is counted once)."""
    model = {"experts_per_token": 2}
    u, h, whole, _ = _layer(8, 0)
    want = jnp.stack([reference_lm.routed_ffn(u[b], h[b], whole, model)
                      for b in range(2)])
    total, pairs = 0.0, 0
    for held, offset in shares:
        _, _, _, share = _layer(held, offset)
        y, stats = _program_share(u, h, share, offset)
        ref = jnp.stack([reference_lm.routed_ffn(
            u[b], h[b], share, {**model, "expert_offset": offset})
            for b in range(2)])
        np.testing.assert_allclose(y, ref, atol=2e-5)
        assert int(stats["kept"]) == int(stats["routed"]) \
            == int(stats["counts"].sum())
        total, pairs = total + y, pairs + int(stats["kept"])
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert pairs == 2 * 24 * 2          # every pair computed once


def test_no_pair_is_dropped_when_every_token_goes_to_one_expert():
    """A router forced onto expert 5 (and 6 as its second): the share
    that holds them computes all 2 x 24 x 2 pairs, groups of 48 rows on
    tiles of 8, and the other share's experts get an empty tile each."""
    u, h, p, _ = _layer(8, 0)
    forced = jnp.zeros((2, 24, 8)).at[..., 5].set(9.0).at[..., 6].set(8.0)
    ids, probs = moe.route(forced, 2)
    assert set(np.unique(ids)) == {5, 6}
    y, stats = moe.moe_experts(u, ids, probs, p["gate"][4:], p["up"][4:],
                               p["down"][4:], expert_offset=4,
                               num_experts=8, tile=8)
    assert stats["counts"].tolist() == [0, 48, 48, 0]
    assert int(stats["kept"]) == int(stats["routed"]) == 96
    # 14 tiles through a buffer of 8 + 4 (4/3 of half the pairs, and a
    # tile a group): a second pass, and no pair dropped
    assert moe.buffer_rows(96, 4, 8, 8) == 96 < moe.worst_case_rows(96, 4, 8)
    assert stats["passes"].tolist() == [2]
    want = 0.0
    for slot, e in enumerate((5, 6)):
        hid = jax.nn.relu(u @ p["gate"][e]) * (u @ p["up"][e])
        want = want + probs[..., slot:slot + 1] * (hid @ p["down"][e])
    np.testing.assert_allclose(y, want, atol=5e-5)
    y0, stats0 = moe.moe_experts(u, ids, probs, p["gate"][:4], p["up"][:4],
                                 p["down"][:4], expert_offset=0,
                                 num_experts=8, tile=8)
    assert int(stats0["kept"]) == 0 and float(jnp.abs(y0).max()) == 0.0
    assert stats0["passes"].tolist() == [1]
    # and the empty groups' weight gradients are written (zeros)
    g = jax.grad(lambda w: jnp.sum(moe.moe_experts(
        u, ids, probs, w, p["up"][4:], p["down"][4:], expert_offset=4,
        num_experts=8, tile=8)[0]))(p["gate"][4:])
    assert float(jnp.abs(g[0]).max()) == 0.0 < float(jnp.abs(g[1]).max())


# ------------------------------------------------------------- the passes
# 48 tokens x 2 over 16 experts, experts 3 and 4 held, tiles of 8: the
# buffer is 8 x (ceil(96 x 2/16 x 4/3 / 8) + 2) = 32 rows (4 tiles) of
# the worst case's 112. ``counts``: tokens sent to expert 3 (the first
# so many, in slot 0) and to expert 4 (the last so many, in slot 1).
_PASS_CASES = {
    # name: (counts, chunks of tokens, passes of each chunk)
    "one_pass": ((9, 7), 1, [1]),
    "ends_on_the_boundary": ((16, 16), 1, [1]),
    "group_straddles_two_passes": ((40, 20), 1, [2]),
    "expert_without_a_pair_in_the_later_pass": ((8, 48), 1, [2]),
    "every_pair_held_three_passes": ((48, 48), 1, [3]),
    # 24 tokens a chunk: 24 rows (3 tiles) of 64
    "two_chunks_one_pass_each": ((9, 7), 2, [1, 1]),
    "two_chunks_every_pair_held": ((48, 48), 2, [2, 2]),
    "two_chunks_of_which_the_first_takes_two": ((24, 8), 2, [2, 1]),
}


def _pass_case(counts):
    ks = jax.random.split(jax.random.key(21), 7)
    n, d, f = 48, 32, 16
    token = jnp.arange(n)
    ids = jnp.stack([jnp.where(token < counts[0], 3, 0),
                     jnp.where(token >= n - counts[1], 4, 1)],
                    axis=-1).astype(jnp.int32).reshape(2, 24, 2)
    return {"u": jax.random.normal(ks[0], (2, 24, d)), "ids": ids,
            "logits": jax.random.normal(ks[1], (2, 24, 2)),
            "gate": 0.2 * jax.random.normal(ks[2], (2, d, f)),
            "up": 0.2 * jax.random.normal(ks[3], (2, d, f)),
            "down": 0.2 * jax.random.normal(ks[4], (2, f, d)),
            "dy": jax.random.normal(ks[5], (2, 24, d))}


def _dense_share(u, logits, gate, up, down, ids):
    """The held experts' part, every token through every held expert in
    float32 and weighted afterwards: the plain reference."""
    probs = jax.nn.softmax(logits, axis=-1)
    y = 0.0
    for e in range(gate.shape[0]):
        weight = jnp.sum(jnp.where(ids == 3 + e, probs, 0.0), axis=-1)
        hidden = jax.nn.relu(jnp.einsum(
            "btd,df->btf", u, gate[e], precision="highest")) * jnp.einsum(
            "btd,df->btf", u, up[e], precision="highest")
        y = y + weight[..., None] * jnp.einsum(
            "btf,fd->btd", hidden, down[e], precision="highest")
    return y


@pytest.mark.parametrize("case", list(_PASS_CASES))
def test_passes_over_the_row_buffer_equal_the_dense_layer(case, monkeypatch):
    """Output and every gradient (input, router probabilities through
    their logits, the three weights) of the held experts' part against
    the dense reference, at loads that take one, two and three passes
    over the buffer, alone and with the tokens in two chunks (whose
    weight gradients go on from the first chunk's); no pair dropped."""
    counts, chunks, passes = _PASS_CASES[case]
    c = _pass_case(counts)
    monkeypatch.setattr(moe, "MAX_PAIRS", 96 // chunks)
    assert moe.buffer_rows(96 // chunks, 2, 16, 8) == (32 if chunks == 1
                                                       else 24)

    def program(u, logits, gate, up, down):
        y, stats = moe.moe_experts(
            u, c["ids"], jax.nn.softmax(logits, axis=-1), gate, up, down,
            expert_offset=3, num_experts=16, tile=8)
        return y, stats

    args = tuple(c[k] for k in ("u", "logits", "gate", "up", "down"))
    y, pull, stats = jax.vjp(program, *args, has_aux=True)
    want, want_pull = jax.vjp(
        lambda *a: _dense_share(*a, c["ids"]), *args)
    assert stats["passes"].tolist() == passes
    assert stats["counts"].tolist() == list(counts)
    assert int(stats["kept"]) == int(stats["routed"]) == sum(counts)
    np.testing.assert_allclose(y, want, atol=3e-5)
    for name, got, ref in zip(("du", "dlogits", "dgate", "dup", "ddown"),
                              pull(c["dy"]), want_pull(c["dy"])):
        np.testing.assert_allclose(got, ref, atol=1e-4, err_msg=name)


def _whiles(fn, *args):
    """``while`` equations in the jaxpr of ``fn`` and of its gradient,
    inner jaxprs included."""
    def count(jaxpr):
        total = 0
        for eqn in jaxpr.eqns:
            total += eqn.primitive.name == "while"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                total += count(sub)
        return total

    grad = jax.grad(lambda *a: jnp.sum(fn(*a)), argnums=(0, 1, 2, 3, 4))
    return count(jax.make_jaxpr(grad)(*args).jaxpr)


def test_no_loop_is_built_where_every_expert_is_held():
    """A buffer as long as the layout is one pass by construction: the
    caller that holds every expert runs the program without passes (its
    jaxpr has no ``while``); one that holds a share gets one loop a
    chunk of tokens forward and one backward."""
    c = _pass_case((9, 7))
    args = tuple(c[k] for k in ("u", "logits", "gate", "up", "down"))

    def share(num_experts):
        return lambda u, logits, gate, up, down: moe.moe_experts(
            u, c["ids"] - 3, jax.nn.softmax(logits, axis=-1), gate, up,
            down, num_experts=num_experts, tile=8)[0]

    assert moe.buffer_rows(96, 2, 2, 8) == moe.worst_case_rows(96, 2, 8)
    assert _whiles(share(None), *args) == _whiles(share(2), *args) == 0
    assert _whiles(share(16), *args) == 2


def test_head_cross_entropy_in_chunks_equals_the_whole():
    ks = jax.random.split(jax.random.key(11), 3)
    hid = jax.random.normal(ks[0], (40, 16))
    w = jax.random.normal(ks[1], (16, 50))
    y = jax.random.randint(ks[2], (40,), 0, 50)

    def whole(hid, w):
        lg = hid @ w
        return jnp.mean(jax.nn.logsumexp(lg, -1)
                        - jnp.take_along_axis(lg, y[:, None], 1)[:, 0])

    chunked = lambda hid, w: head_cross_entropy(hid, w, y, 16)[0]
    got, got_g = jax.value_and_grad(chunked, (0, 1))(hid, w)
    want, want_g = jax.value_and_grad(whole, (0, 1))(hid, w)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, wg in zip(got_g, want_g):
        np.testing.assert_allclose(g, wg, atol=1e-6)
    right = head_cross_entropy(hid, w, y, 16)[1]
    assert float(right) == float(jnp.sum(jnp.argmax(hid @ w, -1) == y))
