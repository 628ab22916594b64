"""The token model whose attention an indexer selects (``--preset
dsa-tiny``: Keye-VL-2.0-30B-A3B's blocks at a size for tests) against
its plain reference (``benchmark/lib/reference_dsa.py``) on the CPU: the
indexer, the selection, the sparse core, the alignment loss that alone
teaches the indexer, per-head q / k norms and the softmax router; the
operators alone; the train step's counters and their way to the
telemetry, the FLOP and parameter counts against hand counts, the device
trace's rows, the other presets left as they were, and the entry point.
"""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_dsa
from pytorch_vit_paper_replication_tpu import engine
from pytorch_vit_paper_replication_tpu.configs import (LM_PRESETS, PRESETS,
                                                       TrainConfig, ViTConfig)
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.ops import sparse_attention as sa
from pytorch_vit_paper_replication_tpu.optim import make_optimizer

T = 48      # not a multiple of the reference's block; three chunks of 16
INDEXER = ("index_q", "index_k", "index_k_norm", "index_w")


def _tiny(**kw):
    # float32 compute: the comparison is of the mathematics
    return LM_PRESETS["dsa-tiny"](dtype="float32", **kw)


def _params(model, cfg, key=1):
    ids = jax.random.randint(jax.random.key(0), (2, T + 1), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.key(key), ids[:, :-1])["params"]
    # scales and biases that are not their initial ones
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.key(a.size),
                                               a.shape), params)
    return params, ids[:, :-1], ids[:, 1:]


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny()
    model = ViT(cfg)
    return (cfg, model) + _params(model, cfg)


def _objective(model, tokens, labels):
    def program(p):
        (loss, _), sown = model.apply(
            {"params": p}, tokens, True, labels=labels,
            mutable=["lm_stats", "dsa_stats", "dsa_probe"])
        return loss, sown
    return program


# ------------------------------------------------------------ the reference
def test_logits_equal_the_reference(tiny):
    cfg, model, params, tokens, _ = tiny
    got = model.apply({"params": params}, tokens, False)
    want = reference_dsa.forward(params, tokens, dataclasses.asdict(cfg))
    assert got.shape == (2, T, cfg.vocab_size) and got.dtype == jnp.float32
    assert reference_dsa.agreement(got, want)["max"] < 1e-4


def test_both_losses_and_every_gradient_leaf_equal_the_reference(tiny):
    cfg, model, params, tokens, labels = tiny
    fields = dataclasses.asdict(cfg)
    (got, sown), got_g = jax.value_and_grad(
        _objective(model, tokens, labels), has_aux=True)(params)
    want, want_g = jax.value_and_grad(
        lambda p: reference_dsa.loss(p, tokens, labels, fields))(params)
    main, indexer = reference_dsa.losses(params, tokens, labels, fields)
    stats = sown["lm_stats"]
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(stats["main_loss"][0], main, rtol=1e-5)
    # the counter is the layers' mean, the objective's term their sum
    np.testing.assert_allclose(stats["indexer_loss"][0] * cfg.num_layers,
                               indexer, rtol=1e-5)
    np.testing.assert_allclose(got, main + indexer,
                               rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    # embedding, final norm, head; a block's attention has 10 leaves (its
    # norm, qkv, the q and k norms, out, the indexer's three products and
    # its key norm's two), its routed feed-forward 5
    assert len(flat_got) == len(flat_want) == 3 + cfg.num_layers * 15
    for path, g in flat_got:
        w = flat_want[path]
        name = jax.tree_util.keystr(path)
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-8, err_msg=name)


def test_the_selection_equals_the_references(tiny):
    cfg, model, params, tokens, labels = tiny
    _, sown = _objective(model, tokens, labels)(params)
    want = reference_dsa.selections(params, tokens, dataclasses.asdict(cfg),
                                    range(cfg.num_layers))
    for layer in range(cfg.num_layers):
        got = sown["dsa_probe"]["backbone"][f"encoder_block_{layer}"][
            "msa"]["mask"][0]
        assert got.dtype == jnp.int8 and got.shape == (2, T, T)
        assert reference_dsa.selection_agreement(got, want[layer]) == 1.0
        np.testing.assert_array_equal(np.asarray(got) != 0, want[layer])


@pytest.mark.parametrize("term", ["indexer", "main"])
def test_the_indexer_learns_from_its_own_loss_alone(tiny, term):
    """``L_I`` moves the indexer's leaves and no other; the
    language-model loss moves every other leaf and none of the
    indexer's: the selection passes no gradient and the indexer reads
    its input with the gradient cut."""
    cfg, model, params, tokens, labels = tiny

    def one_term(p):
        _, sown = _objective(model, tokens, labels)(p)
        return sown["lm_stats"][f"{term}_loss"][0]

    grads = jax.tree_util.tree_leaves_with_path(jax.grad(one_term)(params))
    for path, g in grads:
        name = jax.tree_util.keystr(path)
        of_indexer = any(f"'{part}'" in name for part in INDEXER)
        moved = float(jnp.abs(g).max()) > 0
        assert moved == (of_indexer == (term == "indexer")), name


def test_selection_beyond_the_sequence_is_causal_attention():
    """With ``sa_topk`` >= T every causal key is selected, and the model
    equals the same model with plain causal attention to rounding."""
    cfg = _tiny(sa_topk=64)
    model = ViT(cfg)
    params, tokens, _ = _params(model, cfg)
    causal = ViT(cfg.replace(sa_topk=0, sa_index_heads=0,
                             sa_index_head_dim=0))
    plain = jax.tree_util.tree_map_with_path(
        lambda path, a: a, params)
    for layer in range(cfg.num_layers):
        msa = dict(plain["backbone"][f"encoder_block_{layer}"]["msa"])
        for part in INDEXER:
            msa.pop(part)
        plain["backbone"][f"encoder_block_{layer}"] = {
            **plain["backbone"][f"encoder_block_{layer}"], "msa": msa}
    got = model.apply({"params": params}, tokens, False)
    want = causal.apply({"params": plain}, tokens, False)
    assert reference_dsa.agreement(got, want)["max"] < 1e-4


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The parts that all 2 shares of the experts give, attention counted
    once, add up to the uncut reference's layer."""
    cfg, model, params, tokens, _ = tiny
    fields = dataclasses.asdict(cfg)
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     params["backbone"]["encoder_block_0"])
    x = jax.random.normal(jax.random.key(5), (T, cfg.embedding_dim))
    with jax.default_matmul_precision("highest"):
        u = reference_dsa.rms_norm(x, p["mlp"]["norm"]["scale"],
                                   cfg.ln_epsilon)
        held = cfg.num_experts_held
        rng = np.random.default_rng(0)
        # an uncut layer: the held experts and as many more
        more = {k: jnp.asarray(rng.normal(0, 0.05, p["mlp"][k].shape),
                               jnp.float32) for k in ("gate", "up", "down")}
        whole = {**p["mlp"], **{k: jnp.concatenate([p["mlp"][k], more[k]])
                                for k in more}}
        uncut = reference_dsa.routed_ffn(u, whole, {**fields,
                                                    "expert_offset": 0})
        shares = reference_dsa.routed_ffn(u, p["mlp"], fields, offset=0) \
            + reference_dsa.routed_ffn(u, {**p["mlp"], **more}, fields,
                                       offset=held)
    np.testing.assert_allclose(shares, uncut, atol=1e-5)
    assert float(jnp.abs(uncut).max()) > 0


def test_bfloat16_forward_is_near_the_reference_and_fp8_inputs_are_not():
    """The measures the chip's check uses tell the stated precision from
    the next one down at the tiny size too, and the control can be
    confined to the indexer's products and the core's."""
    cfg = LM_PRESETS["dsa-tiny"]()
    model = ViT(cfg)
    fields = dataclasses.asdict(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, T), 0, cfg.vocab_size)
    params = model.init(jax.random.key(4), ids)["params"]
    want = reference_dsa.forward(params, ids, fields)
    got = model.apply({"params": params}, ids, False)
    low = reference_dsa.forward(params, ids, fields,
                                dtype=jnp.float8_e4m3fn)
    near = reference_dsa.agreement(got, want)["rms"]
    far = reference_dsa.agreement(low, want)["rms"]
    assert near < 0.02 < far, (near, far)
    sets = reference_dsa.selections(params, ids, fields, (0, 1))
    confined = reference_dsa.selections(
        params, ids, fields, (0, 1), dtype=jnp.float8_e4m3fn,
        only=("indexer", "attn_core"))
    _, sown = model.apply({"params": params}, ids, False,
                          mutable=["dsa_probe"])
    program = sown["dsa_probe"]["backbone"]["encoder_block_1"]["msa"][
        "mask"][0]
    assert reference_dsa.selection_agreement(confined[1], sets[1]) \
        < reference_dsa.selection_agreement(program, sets[1]) <= 1.0
    with pytest.raises(AssertionError):
        reference_dsa.hidden(params, ids, fields, only="experts2")


# ------------------------------------------------------------ the operators
def _scores(key, t=T, b=2, j=2, d=8):
    ks = jax.random.split(jax.random.key(key), 3)
    return (jax.random.normal(ks[0], (b, t, j, d)),
            jax.random.normal(ks[1], (b, t, d)),
            jax.random.normal(ks[2], (b, t, j)))


def _by_top_k(total, topk):
    t = total.shape[-1]
    causal = np.tril(np.ones((t, t), bool))
    _, ids = jax.lax.top_k(jnp.where(causal, total + 0.0, -jnp.inf),
                           min(topk, t))
    want = np.zeros(total.shape, bool)
    for b in range(total.shape[0]):
        for row in range(t):
            want[b, row, np.asarray(ids[b, row, :min(row + 1, topk)])] = True
    return want


@pytest.mark.parametrize("topk", [1, 8, 40, 64])
def test_every_query_selects_its_largest_scores(topk):
    """``S_t`` is all of ``0..t`` for t < topk and exactly topk
    positions after: what ``lax.top_k`` over the causal scores
    selects."""
    q_i, k_i, w = _scores(topk)
    got = np.asarray(sa.select(q_i, k_i, w, topk=topk, chunk=16)) != 0
    total = jnp.einsum("btj,bjts->bts", w, jax.nn.relu(
        jnp.einsum("btjd,bsd->bjts", q_i, k_i)))
    np.testing.assert_array_equal(got, _by_top_k(total, topk))
    sizes = got.sum(-1)
    np.testing.assert_array_equal(
        sizes, np.broadcast_to(np.minimum(np.arange(T) + 1, topk), (2, T)))
    assert not np.triu(got, 1).any()
    for row in range(min(topk, T)):
        assert got[:, row, :row + 1].all()


@pytest.mark.parametrize("levels", [1, 3, 9])
def test_ties_go_to_the_lower_position(levels):
    """Scores of a few distinct values: the tied threshold value's keys
    are taken from the lowest position up."""
    total = jnp.round(jax.random.normal(jax.random.key(levels), (2, T, T))
                      * (levels - 1) / 2)
    got = np.asarray(sa.select_rows(total, jnp.arange(T), 8)) != 0
    np.testing.assert_array_equal(got, _by_top_k(total, 8))
    if levels == 1:        # every score equal: the first 8 positions
        assert got[:, -1, :8].all() and not got[:, -1, 8:].any()


def _tied_rows(total, rows, topk, tied_rows):
    """``total`` with, in each of ``tied_rows`` (positions among
    ``rows``), its first ``want + 3`` keys at one value above every other
    key of the row: three more keys at the threshold than the row takes,
    so the tie pass decides it (ties to the lower position)."""
    total = np.array(total)
    for r in tied_rows:
        want = min(int(rows[r]) + 1, topk)
        total[:, r, :want + 3] = total[:, r].max() + 1.0
    return jnp.asarray(total)


@pytest.mark.parametrize("case", [
    # random scores with negative values, -0.0 and +0.0 among them
    dict(t=384, c=128, row0=128, topk=100),
    # a chunk at a row offset, two query blocks of 64
    dict(t=512, c=128, row0=256, topk=64, block_q=64),
    # a block entirely under topk: every causal key, no search
    dict(t=256, c=128, row0=0, topk=200),
    # one block under topk and one that straddles it
    dict(t=256, c=128, row0=0, topk=100, block_q=64),
    # rows tied at their threshold beyond what they take
    dict(t=384, c=128, row0=128, topk=100, tied=(0, 5, 127)),
    # every score one of three values: ties in every row
    dict(t=256, c=128, row0=128, topk=40, levels=3),
    # causal widths that are no whole number of 128-key tiles, two key
    # blocks
    dict(t=200, c=40, row0=120, topk=50, block_k=128),
], ids=["random", "offset", "under_topk", "straddles_topk", "tied",
        "three_levels", "ragged_width"])
def test_the_selection_kernel_equals_select_rows(case):
    """``ops/indexer_select.py`` (``dsa_select``, the interpreter here)
    writes a chunk's selection into the layer's buffer element for
    element as ``select_rows`` selects it from the same float32 scores,
    and flags exactly the rows the tie pass decides."""
    from pytorch_vit_paper_replication_tpu.ops import indexer_select

    t, c, row0, topk = case["t"], case["c"], case["row0"], case["topk"]
    total = jax.random.normal(jax.random.key(t + row0), (2, c, t))
    if "levels" in case:
        total = jnp.round(total * (case["levels"] - 1) / 2)
    if "tied" not in case:
        total = total.at[:, :, 1].set(-0.0).at[:, :, 2].set(0.0)
    rows = row0 + jnp.arange(c, dtype=jnp.int32)
    total = _tied_rows(total, rows, topk, case.get("tied", ()))
    want, more = sa._select_rows(total, rows, topk)
    picked, tied = indexer_select.select(
        indexer_select.empty(2, t), total, jnp.int32(row0), topk,
        block_q=case.get("block_q"), block_k=case.get("block_k"))
    got = np.swapaxes(np.asarray(picked)[:, :t], 1, 2)[:, row0:row0 + c]
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got, np.asarray(want))
    np.testing.assert_array_equal(np.asarray(tied), np.asarray(more))
    # what lax.top_k over the causal scores selects, ties to the lower
    # position
    by_top_k = _by_top_k(jnp.pad(total, ((0, 0), (row0, t - row0 - c),
                                         (0, 0))), topk)[:, row0:row0 + c]
    np.testing.assert_array_equal(got != 0, by_top_k)
    if case.get("tied"):
        assert sorted(np.flatnonzero(np.asarray(tied)[0])) == list(
            case["tied"])
        for r in case["tied"]:
            wanted = min(row0 + r + 1, topk)
            assert got[:, r, :wanted].all() and not got[:, r, wanted:].any()
    if row0 + c <= topk:
        np.testing.assert_array_equal(
            got, np.broadcast_to(np.tril(np.ones((c, t), np.int8)),
                                 got.shape))


@pytest.mark.parametrize("impl,served", [("flash", 1.0), ("xla", 0.0)])
def test_the_selections_counters(impl, served):
    """``select_served`` says which search took the layer's selection (the
    kernel where flash serves the core, ``select_rows`` else) and
    ``select_tie_rows`` counts, a sequence, the rows the tie pass
    decided: the same number from both."""
    q, k, v = _core_inputs()
    q_i, k_i, w = _scores(3)
    # products all positive: no score is 0 but those of 6 rows whose
    # indexer queries are 0, which score every key alike
    q_i, k_i = jnp.abs(q_i).at[:, 20:26].set(0.0), jnp.abs(k_i)
    _, _, stats = sa.sparse_attention(q, k, v, q_i, k_i, w, topk=8,
                                      chunk=16, impl=impl)
    assert float(stats["select_served"]) == served
    # rows 20-25 score every key 0 and take 8 of their 21-26: tied
    assert float(stats["select_tie_rows"]) == 6.0
    total = jnp.einsum("btj,bjts->bts", w, jax.nn.relu(
        jnp.einsum("btjd,bsd->bjts", q_i, k_i)))
    np.testing.assert_array_equal(np.asarray(stats["mask"]) != 0,
                                  _by_top_k(total, 8))


def test_the_kept_selection_is_a_bit_a_pair():
    mask = (jax.random.uniform(jax.random.key(0), (2, T, T)) > 0.5).astype(
        jnp.int8)
    packed = sa.pack(mask)
    assert packed.shape == (2, T, T // 8) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(sa.unpack(packed), mask)


def _core_inputs():
    ks = jax.random.split(jax.random.key(9), 3)
    q = jax.random.normal(ks[0], (2, T, 4, 16))
    k = jax.random.normal(ks[1], (2, T, 2, 16))
    v = jax.random.normal(ks[2], (2, T, 2, 16))
    return q, k, v


def test_the_flash_kernels_serve_the_selection_as_xla_does():
    """The core by the flash kernel pair with the int8 selection (the
    interpreter here) equals the core on the ``[T, T]`` logits: output,
    row statistic and the three gradients."""
    q, k, v = _core_inputs()
    mask = sa.select(*_scores(1), topk=8, chunk=16)

    def total(q, k, v, impl):
        out, lse = sa.core(q, k, v, mask, impl=impl)
        return jnp.sum(out ** 2), lse

    (a, lse_a), ga = jax.value_and_grad(total, (0, 1, 2), has_aux=True)(
        q, k, v, "xla")
    (b, lse_b), gb = jax.value_and_grad(total, (0, 1, 2), has_aux=True)(
        q, k, v, "flash")
    np.testing.assert_allclose(a, b, rtol=1e-5)
    np.testing.assert_allclose(lse_a, lse_b, atol=1e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, atol=1e-4)


def test_the_alignment_loss_and_its_gradient_against_autodiff():
    """``indexer_loss`` takes its gradient by hand in the forward pass:
    the same numbers as differentiating the formula, the mass 1, and
    nothing reaches q, k or the row statistic."""
    q, k, v = _core_inputs()
    q_i, k_i, w = _scores(2)
    mask = sa.select(q_i, k_i, w, topk=8, chunk=16)
    _, lse = sa.core(q, k, v, mask, impl="xla")
    chosen = mask != 0

    def by_formula(q_i, k_i, w):
        total = jnp.einsum("btj,bjts->bts", w, jax.nn.relu(
            jnp.einsum("btjd,bsd->bjts", q_i, k_i)))
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) \
            * 16 ** -0.5
        pbar = jnp.mean(jax.nn.softmax(
            jnp.where(chosen[:, None], logits, -jnp.inf), -1), 1)
        log_soft = jax.nn.log_softmax(jnp.where(chosen, total, -jnp.inf), -1)
        return jnp.mean(jnp.sum(jnp.where(
            chosen & (pbar > 0), pbar * (jnp.log(jnp.where(
                pbar > 0, pbar, 1.0)) - jnp.where(chosen, log_soft, 0.0)),
            0.0), -1))

    by_hand = lambda *a: sa.indexer_loss(*a, mask, q, k, lse, 16)
    want, want_g = jax.value_and_grad(by_formula, (0, 1, 2))(q_i, k_i, w)
    (got, mass), got_g = jax.value_and_grad(
        lambda *a: by_hand(*a), (0, 1, 2), has_aux=True)(q_i, k_i, w)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(mass, 1.0, atol=1e-5)
    for g, w_ in zip(got_g, want_g):
        np.testing.assert_allclose(g, w_, atol=1e-6)
    others = jax.grad(lambda q, k, lse: sa.indexer_loss(
        q_i, k_i, w, mask, q, k, lse, 16)[0], (0, 1, 2))(q, k, lse)
    assert all(float(jnp.abs(g).max()) == 0 for g in others)


def _plain_kl(q_i, k_i, w, chosen, q, k, lse):
    """The alignment loss by its formula, for autodiff: ``pbar`` from the
    core's own row statistic, a constant."""
    group = q.shape[2] // k.shape[2]
    f32 = lambda x: x.astype(jnp.float32)
    total = jnp.einsum("btj,bjts->bts", f32(w), jax.nn.relu(
        jnp.einsum("btjd,bsd->bjts", f32(q_i), f32(k_i))))
    logits = jnp.einsum("bqhd,bkhd->bhqk", f32(q), jnp.repeat(
        f32(k), group, axis=2)) * q.shape[-1] ** -0.5
    pbar = jnp.mean(jnp.where(chosen[:, None],
                              jnp.exp(logits - lse[..., None]), 0.0), 1)
    log_soft = jax.nn.log_softmax(jnp.where(chosen, total, -jnp.inf), -1)
    cross = pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0))
                    - jnp.where(chosen, log_soft, 0.0))
    return jnp.mean(jnp.sum(cross, -1)), jnp.mean(jnp.sum(pbar, -1))


@pytest.mark.parametrize("case", [
    dict(),                                   # 3 x 3 blocks of 16, group 2
    dict(heads=8, kv_heads=1, index_heads=4),     # one group of 8
    dict(heads=2, kv_heads=2),                    # groups of 1
    dict(t=40),                     # padded rows and keys: nothing of them
    dict(t=40, block_q=16, block_k=32),
    dict(topk=64),                            # every causal key selected
    dict(topk=1),                         # every row selects one key
    dict(dtype="bfloat16"),
    dict(dtype="bfloat16", heads=8, kv_heads=1, t=40),
], ids=lambda case: "-".join(f"{k}{v}" for k, v in case.items()) or "plain")
def test_the_loss_kernels_against_the_xla_pass_and_autodiff(case):
    """``ops/indexer_loss.py`` under the Pallas interpreter: the loss,
    ``pbar``'s mass and the three gradients equal the XLA pass's
    (``_loss_pass``, which every CPU run and ``dsa-tiny`` keep) and
    autodiff of the plain KL. Row 0 selects one key in every case (its
    own: loss 0, gradient 0)."""
    from pytorch_vit_paper_replication_tpu.ops import indexer_loss

    case = dict(dict(t=T, heads=4, kv_heads=2, index_heads=2, topk=8,
                     dtype="float32", block_q=16, block_k=16), **case)
    t, dtype = case["t"], jnp.dtype(case["dtype"])
    ks = jax.random.split(jax.random.key(11), 6)
    cast = lambda x: x.astype(dtype)
    q = cast(jax.random.normal(ks[0], (2, t, case["heads"], 16)))
    k = cast(jax.random.normal(ks[1], (2, t, case["kv_heads"], 16)))
    v = cast(jax.random.normal(ks[2], (2, t, case["kv_heads"], 16)))
    q_i = cast(jax.random.normal(ks[3], (2, t, case["index_heads"], 8)))
    k_i = cast(jax.random.normal(ks[4], (2, t, 8)))
    w = jax.random.normal(ks[5], (2, t, case["index_heads"])) * 0.25
    mask = sa.select(q_i, k_i, w, topk=case["topk"], chunk=8)
    _, lse = sa.core(q, k, v, mask, impl="xla")

    (loss, mass), got = indexer_loss.loss_pass(
        q_i, k_i, w, mask, q, k, lse, True, block_q=case["block_q"],
        block_k=case["block_k"], interpret=True)
    alone = indexer_loss.loss_pass(
        q_i, k_i, w, mask, q, k, lse, False, block_q=case["block_q"],
        block_k=case["block_k"], interpret=True)
    np.testing.assert_allclose(alone, (loss, mass), rtol=1e-6)
    (xla_loss, xla_mass), xla = sa._loss_pass(q_i, k_i, w, mask, q, k, lse,
                                              8, True)
    (want, want_mass), by_autodiff = jax.value_and_grad(
        _plain_kl, (0, 1, 2), has_aux=True)(q_i, k_i, w, mask != 0, q, k,
                                            lse)
    exact = dtype == jnp.float32
    for other in (xla_loss, want):
        np.testing.assert_allclose(loss, other, atol=1e-6,
                                   rtol=1e-5 if exact else 2e-3)
    np.testing.assert_allclose(mass, xla_mass, atol=1e-5)
    np.testing.assert_allclose(mass, want_mass, atol=1e-5)
    assert [g.dtype for g in got] == [g.dtype for g in xla]
    for name, g, x, a in zip(("g_q", "g_k", "g_w"), got, xla, by_autodiff):
        g, x, a = (np.asarray(y, np.float32) for y in (g, x, a))
        # bf16: one rounding of d_act an element in both passes, the
        # scores' products rounded in the XLA pass alone
        for other in (x, a):
            np.testing.assert_allclose(
                g, other, err_msg=name,
                atol=(1e-6 if exact else 0.03) * np.abs(a).max() + 1e-9)
        # one key a row: softmax = pbar = 1, nothing to learn
        assert (np.abs(g).max() > 1e-4) == (case["topk"] > 1), name
    assert float(np.abs(np.asarray(got[0], np.float32)[:, 0]).max()) == 0
    assert float(np.abs(np.asarray(got[2], np.float32)[:, 0]).max()) == 0


def test_where_the_kernels_take_the_losss_pass(monkeypatch):
    """The loss's pass goes where the core goes: the kernel pair where
    :func:`attention.choose` gives the core to the flash kernels, at whole
    lane blocks when compiled for the chip and on one device; the XLA
    pass everywhere else."""
    from pytorch_vit_paper_replication_tpu.ops import indexer_loss, partition

    cell = ((1, 16384, 32, 128), (1, 16384, 16, 64))
    small = ((2, 48, 4, 16), (2, 48, 2, 8))
    assert indexer_loss.serves("flash", *small)       # the interpreter
    with monkeypatch.context() as on_the_chip:
        on_the_chip.setattr(jax, "default_backend", lambda: "tpu")
        assert indexer_loss.serves("flash", *cell)
        assert not indexer_loss.serves("xla", *cell)
        # head size 64, or indexer heads that fill no lane block: XLA
        assert not indexer_loss.serves("flash", *small)
        assert not indexer_loss.serves("flash", (1, 512, 8, 64), cell[1])
        assert not indexer_loss.serves("flash", cell[0], (1, 512, 3, 48))
        mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
        with partition.on_mesh(mesh):
            assert not indexer_loss.serves("flash", *cell)
    # the CPU's verdict for the tiny preset, and a forced one
    from pytorch_vit_paper_replication_tpu.ops import attention
    q, k, _ = _core_inputs()
    served = lambda impl: attention.choose(
        q.shape, q.dtype, k.shape, impl=impl, kind="causal_topk")[0]
    assert not indexer_loss.serves(served("auto"), q.shape, (2, T, 2, 8))
    assert indexer_loss.serves(served("flash"), q.shape, (2, T, 2, 8))


def test_the_forced_flash_path_is_the_kernels_and_trains_the_indexer():
    """``impl="flash"`` through :func:`sparse_attention.sparse_attention`
    (the interpreter here): the loss and the gradient that reaches the
    indexer equal the XLA path's."""
    q, k, v = _core_inputs()
    q_i, k_i, w = _scores(2)

    def objective(q_i, k_i, w, impl):
        _, loss, stats = sa.sparse_attention(q, k, v, q_i, k_i, w, topk=8,
                                             chunk=16, impl=impl)
        return loss, stats["pbar_mass"]

    (a, mass_a), ga = jax.value_and_grad(objective, (0, 1, 2), has_aux=True)(
        q_i, k_i, w, "xla")
    (b, mass_b), gb = jax.value_and_grad(objective, (0, 1, 2), has_aux=True)(
        q_i, k_i, w, "flash")
    np.testing.assert_allclose(a, b, rtol=1e-5)
    np.testing.assert_allclose(mass_a, mass_b, atol=1e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, atol=1e-6)
    calls = str(jax.make_jaxpr(jax.grad(
        lambda *a: objective(*a, "flash")[0], (0, 1, 2)))(q_i, k_i, w))
    for kernel in ("flash_fwd", "indexer_loss_fwd", "indexer_loss_bwd",
                   "dsa_select_buffer", "dsa_select"):
        assert f"name={kernel}" in calls, kernel


@pytest.mark.parametrize("cut_input", [False, True])
def test_a_tied_products_gradients_are_the_plain_products(cut_input):
    """The product an indexed block's projections take
    (``models/vit.py::_tied_product``: the input's gradient leaves with
    the kernel's, an order and not a value): the result and the kernel's
    gradient are ``lax.dot_general``'s, the input's too, or zeros where
    the product reads its input as a constant; ``jax.checkpoint`` takes
    it again as it takes the plain one."""
    from pytorch_vit_paper_replication_tpu.models.vit import _tied_product

    x = jax.random.normal(jax.random.key(0), (2, 5, 8))
    w = jax.random.normal(jax.random.key(1), (8, 3, 4))
    dims = (((2,), (0,)), ((), ()))

    def plain(x, w):
        x = jax.lax.stop_gradient(x) if cut_input else x
        return jnp.sum(jnp.sin(jax.lax.dot_general(x, w, dims)))

    def tied(x, w):
        return jnp.sum(jnp.sin(_tied_product(x, w, dims,
                                             cut_input=cut_input)))

    want, (want_x, want_w) = jax.value_and_grad(plain, (0, 1))(x, w)
    for program in (tied, jax.checkpoint(tied)):
        got, (got_x, got_w) = jax.jit(jax.value_and_grad(program, (0, 1)))(
            x, w)
        np.testing.assert_allclose(got, want, rtol=1e-6)
        np.testing.assert_allclose(got_w, want_w, rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(got_x, want_x, rtol=1e-6, atol=1e-6)
    assert bool(jnp.all(want_x == 0)) == cut_input


def test_dot_product_attention_refuses_a_selection_without_an_indexer():
    from pytorch_vit_paper_replication_tpu.ops import attention
    with pytest.raises(ValueError, match="unknown attention kind"):
        attention.dot_product_attention(*_core_inputs(), kind="causal_topk")


# ---------------------------------------------------------------- the step
def test_train_step_learns_and_counts(tiny):
    cfg, model, params, tokens, labels = tiny
    tx = make_optimizer(TrainConfig(batch_size=2), 100)
    state = engine.TrainState.create(apply_fn=model.apply, params=params,
                                     tx=tx, rng=jax.random.key(2))
    step = jax.jit(engine.make_train_step())
    batch = {"tokens": tokens, "label": labels}
    seen = []
    for _ in range(6):
        state, m = step(state, batch)
        seen.append(m)
    first, m = seen[0], seen[-1]
    assert float(m["loss_sum"]) < float(first["loss_sum"])
    np.testing.assert_allclose(
        float(m["loss_sum"]) / 2,
        float(m["main_loss"]) + cfg.num_layers
        * float(m["indexer_loss"]), rtol=1e-5)
    # 8 keys a query once it has them: 1 + .. + 8 and 8 a row after
    assert float(m["dsa_selected_pairs"]) == 36 + (T - 8) * 8
    assert float(m["dsa_causal_pairs"]) == T * (T + 1) / 2
    assert float(m["dsa_pbar_mass_min"]) == pytest.approx(1.0, abs=1e-5)
    # the CPU's core is XLA's, and so is the search
    assert float(m["dsa_select_served"]) == 0.0
    assert float(m["dsa_select_tie_rows"]) >= 0.0
    assert float(m["moe_dropped_pairs"]) == 0.0
    assert float(m["moe_pairs_kept_share"]) == 1.0
    ev = jax.jit(engine.make_eval_step())(state, batch)
    assert float(ev["count"]) == 2.0 and np.isfinite(float(ev["loss_sum"]))


def test_evaluation_reads_the_main_loss_alone(tiny):
    """Without ``dsa_stats`` made mutable nothing is sown and the model
    returns the language-model loss: what ``make_eval_step`` reports."""
    cfg, model, params, tokens, labels = tiny
    loss, _ = model.apply({"params": params}, tokens, False, labels=labels)
    main, _ = reference_dsa.losses(params, tokens, labels,
                                   dataclasses.asdict(cfg))
    np.testing.assert_allclose(loss, main, rtol=1e-5)


def test_counters_reach_step_telemetry_and_the_registry():
    from pytorch_vit_paper_replication_tpu.telemetry import (
        HELP_TEXT, INSTRUMENTS, StepTelemetry, TelemetryRegistry)

    reg = TelemetryRegistry()
    tel = StepTelemetry(None, registry=reg, sample_every=1)
    tel.step(data_wait_s=0.0, exec_s=0.1, images=1, step=1, blocked=True,
             counters={"main_loss": 9.5, "indexer_loss": 0.25,
                       "dsa_selected_pairs": 31458304.0,
                       "dsa_causal_pairs": 134225920.0,
                       "dsa_pbar_mass_min": 1.0, "dsa_select_served": 1.0,
                       "dsa_select_tie_rows": 12.0})
    gauges = reg.snapshot()["gauges"]
    assert (gauges["tel_main_loss"], gauges["tel_indexer_loss"],
            gauges["tel_dsa_selected_pairs"],
            gauges["tel_dsa_causal_pairs"],
            gauges["tel_dsa_pbar_mass_min"], gauges["tel_dsa_select_served"],
            gauges["tel_dsa_select_tie_rows"]) == (
        9.5, 0.25, 31458304.0, 134225920.0, 1.0, 1.0, 12.0)
    for name in engine.LM_COUNTERS:
        assert f"tel_{name}" in INSTRUMENTS and f"tel_{name}" in HELP_TEXT


# ------------------------------------------------------------- hand counts
def test_flop_count_against_a_hand_count():
    """Keye-VL-2.0-30B-A3B's cut by hand (ISSUE 34's arithmetic), TFLOP a
    16,384-token sequence of a train step."""
    from pytorch_vit_paper_replication_tpu.telemetry import flops

    cfg = LM_PRESETS["keye-vl-2.0-30b-a3b-ep8"]()
    t = cfg.max_seq_len
    selected = 2048 * 2049 // 2 + (t - 2048) * 2048
    causal = t * (t + 1) // 2
    assert (selected, causal) == (31_458_304, 134_225_920)
    assert flops.visible_pairs(t, cfg.sa_topk) == selected
    assert selected / causal == pytest.approx(0.2344, abs=5e-5)
    projections = 3 * 6 * t * 2 * (2048 * 40 * 128 + 4096 * 2048)
    indexer_proj = 3 * 6 * t * 2 * 2048 * (16 * 64 + 64 + 16)
    router = 3 * 6 * t * 2 * 2048 * 128
    experts = 3 * 6 * (t * 8 * 16 / 128) * 3 * 2 * 2048 * 768
    head = 3 * t * 2 * 2048 * 18992
    core = 6 * 6 * 2 * 32 * 128 * selected          # 6 GEMMs a pair
    pbar = 6 * 2 * 32 * 128 * selected              # 1, forward only
    scores = 6 * 2 * 16 * 64 * (causal + 2 * selected)
    by_hand = (projections + indexer_proj + router + experts + head + core
               + pbar + scores)
    got = flops.train_step_flops_per_sequence(cfg)
    assert got == pytest.approx(by_hand, rel=1e-12)
    assert got / 1e12 == pytest.approx(32.47, abs=0.01)
    mechanism = core + pbar + scores + indexer_proj
    assert mechanism / got == pytest.approx(0.449, abs=0.001)
    assert projections / got == pytest.approx(0.343, abs=0.001)
    assert head / got == pytest.approx(0.118, abs=0.001)
    assert experts / got == pytest.approx(0.086, abs=0.001)
    # a dense causal core alone would be more than the whole counted step
    assert 6 * 6 * 2 * 32 * 128 * causal / 1e12 == pytest.approx(39.6,
                                                                 abs=0.1)
    # the other token models' counts are what they were
    for name, tflop in (("smallthinker-21b-a3b-ep4", 34.70),
                        ("glm-4.7-flash-ep8", 84.14)):
        assert flops.train_step_flops_per_sequence(
            LM_PRESETS[name]()) / 1e12 == pytest.approx(tflop, abs=0.01)


def test_parameters_of_the_cut():
    """659.2 M parameters = 10.55 GB = 9.82 GiB at 16 bytes each (ISSUE
    34's table, re-counted from the shapes the model makes)."""
    model = ViT(LM_PRESETS["keye-vl-2.0-30b-a3b-ep8"]())
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    block = shapes["backbone"]["encoder_block_3"]
    qkv, out = 2048 * (32 + 2 * 4) * 128, 4096 * 2048
    indexer = 2048 * 16 * 64 + 2048 * 64 + 2048 * 16 + 2 * 64
    assert (qkv, out, indexer) == (10_485_760, 8_388_608, 2_261_120)
    norms = 2 * 2048 + 2 * 128
    assert count(block["msa"]) == qkv + out + indexer + 2048 + 2 * 128
    router, experts = 2048 * 128, 16 * 3 * 2048 * 768
    assert count(block["mlp"]) == router + experts + 2048
    layer = qkv + out + indexer + router + experts + norms
    assert count(block) == layer == 96_899_456
    total = 6 * layer + 2 * 18992 * 2048 + 2048
    assert count(shapes) == total == 659_190_016
    assert total * 16 / 1e9 == pytest.approx(10.55, abs=0.01)
    assert total * 16 / 2**30 == pytest.approx(9.82, abs=0.01)


def test_presets_state_every_published_width():
    cfg = LM_PRESETS["keye-vl-2.0-30b-a3b-ep8"]()
    assert (cfg.embedding_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.expert_width, cfg.num_experts, cfg.experts_per_token,
            cfg.rope_theta, cfg.ln_epsilon) == (
        2048, 32, 4, 128, 768, 128, 8, 1e7, 1e-6)
    assert (cfg.sa_topk, cfg.sa_index_heads, cfg.sa_index_head_dim,
            cfg.sa_chunk, cfg.qk_norm) == (
        2048, 16, 64, 512, True)
    assert (cfg.num_layers, cfg.num_experts_held, cfg.vocab_size,
            cfg.max_seq_len, cfg.shared_experts, cfg.dense_layers) == (
        6, 16, 18992, 16384, 0, 0)
    assert (cfg.router_scoring, cfg.router_input,
            cfg.expert_activation) == ("softmax", "block", "silu")
    assert all(cfg.layer_rope(i) and cfg.layer_routed(i)
               and cfg.attention_kind(i) == ("causal_topk", 2048)
               for i in range(6))
    with pytest.raises(ValueError, match="sparse attention"):
        cfg.replace(sa_index_heads=0)
    with pytest.raises(ValueError, match="sparse attention"):
        cfg.replace(sliding_window_layout=(1,), sliding_window=128)
    with pytest.raises(ValueError, match="sparse attention"):
        ViTConfig(sa_topk=8, sa_index_heads=2, sa_index_head_dim=8)


# ------------------------------------------------------- the device trace
BLOCK = "jit(train_step)/jvp(ViT)/backbone/encoder_block_1/checkpoint"


@pytest.mark.parametrize("path,row,frozen", [
    (f"{BLOCK}/msa/indexer/proj/index_q/dot_general", "indexer/proj",
     "msa_glue"),
    (f"{BLOCK}/msa/indexer/proj/index_k_norm/mul", "indexer/proj",
     "msa_glue"),
    # a chunk of query rows at a time: the loop is the block's, the scopes
    # are inside its body
    (f"{BLOCK}/msa/while/body/indexer/scores/dot_general", "indexer/scores",
     "msa_glue"),
    (f"{BLOCK}/msa/while/body/indexer/select/while/body/reduce_sum",
     "indexer/select", "msa_glue"),
    (f"{BLOCK}/msa/indexer/select/shift_right_logical", "indexer/select",
     "msa_glue"),
    # (an op of a loop's body may carry the path from the body on: the
    # program's rows take it, the frozen table has no row for it)
    ("indexer/select/reduce_sum", "indexer/select", "other"),
    # the scores taken again by the loss's pass count as scores
    (f"{BLOCK}/msa/indexer_loss/while/body/indexer/scores/dot_general",
     "indexer/scores", "msa_glue"),
    (f"{BLOCK}/msa/indexer_loss/while/body/exp", "indexer_loss",
     "msa_glue"),
    (f"{BLOCK}/msa/attn_core/flash_fwd/pallas_call", "attn_core",
     "attn_core"),
    ("jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_1/"
     "checkpoint/msa/attn_core/flash_bwd/pallas_call", "attn_core",
     "attn_core"),
    (f"{BLOCK}/msa/q_norm/mul", "msa_glue", "msa_glue"),
    (f"{BLOCK}/msa/rope/concatenate", "rope", "msa_glue"),
    (f"{BLOCK}/msa/out/dot_general", "msa_out", "msa_out"),
])
def test_device_trace_rows_of_the_new_scopes(path, row, frozen):
    """The trainer's table has a row for each new scope, the benchmark's
    finer table the same rows, and the frozen table reads the same op
    under the row a reader expects, never ``other``."""
    from benchmark.lib import scopes, scopes_dsa
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    assert device_trace.classify(path)[0] == row
    assert scopes.classify(path)[0] == frozen
    assert scopes_dsa.row_of(path) == (row if "indexer" in row else None)


def test_the_lowered_step_names_every_new_scope(tiny):
    cfg, model, params, tokens, labels = tiny
    tx = make_optimizer(TrainConfig(batch_size=2), 100)
    state = engine.TrainState.create(apply_fn=model.apply, params=params,
                                     tx=tx, rng=jax.random.key(2))
    text = jax.jit(engine.make_train_step()).lower(
        state, {"tokens": tokens, "label": labels}).as_text(debug_info=True)
    for scope in ("/msa/indexer/proj/index_q/", "/msa/indexer/proj/index_k/",
                  "/msa/indexer/proj/index_k_norm/",
                  "/msa/indexer/proj/index_w/", "indexer/scores/",
                  "/msa/indexer/select/", "/msa/attn_core/",
                  "/msa/indexer_loss/", "/msa/q_norm/", "/msa/k_norm/",
                  "/msa/rope/", "/msa/out/", "/mlp/moe_router/",
                  "checkpoint/rematted_computation/msa/qkv/"):
        assert scope in text, scope
    # the selection, the core and the loss's pass are kept, not taken again
    # (the bits kept are spread to bytes again: no loop)
    for scope in ("rematted_computation/msa/while",
                  "rematted_computation/msa/indexer_loss"):
        assert scope not in text, scope


# ---------------------------------------------------------- defaults as were
def _lowered_sha(cfg, example):
    model = ViT(cfg)
    tx = make_optimizer(TrainConfig(batch_size=2), 100)

    def abstract_state():
        params = model.init(jax.random.key(0), example["x"])["params"]
        return engine.TrainState.create(apply_fn=model.apply, params=params,
                                        tx=tx, rng=jax.random.key(0))

    state = jax.eval_shape(abstract_state)
    batch = {k: v for k, v in example.items() if k != "x"}
    text = jax.jit(engine.make_train_step()).lower(state, batch).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


IDS = jax.ShapeDtypeStruct((2, 64), jnp.int32)
TOKENS = {"x": jnp.zeros((1, 8), jnp.int32), "tokens": IDS, "label": IDS}
IMAGES = {"x": jnp.zeros((1, 32, 32, 3)),
          "image": jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32),
          "label": jax.ShapeDtypeStruct((2,), jnp.int32)}


@pytest.mark.parametrize("name,example,sha", [
    ("lm-tiny", TOKENS,
     "5e7593dcfdb455564d7e653b4bd3d4f11dcb19c029f9f7b9df75e5b60bcf32c1"),
    ("mla-tiny", TOKENS,
     "660126c6da25f9f895e98443c0ebd0a9f68a5bf26141a4ebde4a4377948150b7"),
    ("ViT-Ti/16", IMAGES,
     "6236f5cff96a4a20cf24ada6b7d8fcd32bb75b504379e552841584e995a65a99"),
])
def test_every_other_preset_lowers_to_the_parents_text(name, example, sha):
    """SmallThinker's, GLM-4.7-Flash's and a ViT's blocks lower to the
    text they lowered to before this model's options existed (sha256 of
    ``lower(avals).as_text()`` at a small size on the CPU, recorded on
    the parent commit 5008e6f, where no Mosaic payload carries a source
    line): the new fields' defaults, the third collection the step makes
    mutable and the kernels' int8 masks leave all three programs as they
    were.

    The constants hold for PR 34's parent only. A later PR that changes
    one of the programs on purpose, or an upgrade of jax, deletes them
    with this test: ``test_other_presets_take_none_of_this_models_
    options`` is the check that stays."""
    if name in LM_PRESETS:
        cfg = LM_PRESETS[name]()
    else:
        cfg = PRESETS[name](image_size=32, num_classes=3)
    assert _lowered_sha(cfg, example) == sha


def test_other_presets_take_none_of_this_models_options():
    fields = ("sa_topk", "sa_index_heads", "sa_index_head_dim", "qk_norm")
    # (LFM2's attention has per-head q / k norms too: tests/test_conv.py)
    shares_qk_norm = ("lfm2-24b-a2b-ep8", "conv-tiny")
    for name, make in {**PRESETS, **LM_PRESETS}.items():
        if name in ("keye-vl-2.0-30b-a3b-ep8", "dsa-tiny"):
            continue
        taken = fields[:3] if name in shares_qk_norm else fields
        assert not any(getattr(make(), f) for f in taken), name
        assert make().attention_kind(0)[0] != "causal_topk", name
    assert LM_PRESETS["dsa-tiny"]().attention_kind(1) == ("causal_topk", 8)


def test_entry_point_trains_the_tiny_preset(tmp_path, capsys):
    """``train --model lm --preset dsa-tiny --synthetic`` through the
    trainer's own loop: mesh, compile cache, checkpoint, telemetry; the
    language-model loss and the indexer's loss both fall. (Over these
    first steps: the indexer follows a target that moves, the main
    attention's own probabilities, and once those sharpen its loss rises
    before it falls again: PERF.md section 6, PR 34.)"""
    from pytorch_vit_paper_replication_tpu.train import main

    results = main([
        "--model", "lm", "--preset", "dsa-tiny", "--synthetic",
        "--batch-size", "8", "--epochs", "2", "--steps-per-epoch", "3",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--compile-cache-dir", str(tmp_path / "cache"),
        "--telemetry-jsonl", str(tmp_path / "tel.jsonl"),
        "--telemetry-every", "1"])
    assert results["train_loss"][1] < results["train_loss"][0] < 6.5
    assert (tmp_path / "ckpt" / "final").is_dir()
    assert "model: dsa-tiny | params: 111,264" in capsys.readouterr().out
    rows = [json.loads(l) for l in (tmp_path / "tel.jsonl").read_text()
            .splitlines()]
    sampled = [r for r in rows if "tel_indexer_loss" in r]
    assert sampled and all(
        r["tel_moe_pairs_kept_share"] == 1.0 and r["tel_main_loss"] > 0
        and r["tel_dsa_selected_pairs"] == 36 + (64 - 8) * 8
        and r["tel_dsa_causal_pairs"] == 64 * 65 / 2
        and abs(r["tel_dsa_pbar_mass_min"] - 1.0) < 0.02 for r in sampled)
    assert sampled[-1]["tel_indexer_loss"] < sampled[0]["tel_indexer_loss"]
