"""The token model whose layers mix gated short convolutions with
attention (``--preset conv-tiny``: LFM2-24B-A2B's blocks at a size for
tests) against its plain reference (``benchmark/lib/reference_conv.py``)
on the CPU: the conv mixer and its causal convolution, grouped-query
attention with per-head q / k norms, the dense and the sigmoid-routed
feed-forward, the head tied to the token embedding; the parameter and
FLOP counts of the cut against hand counts, the device trace's rows, and
the accepted presets' programs left as they were.
"""

import dataclasses
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_conv
from pytorch_vit_paper_replication_tpu import engine
from pytorch_vit_paper_replication_tpu.configs import (LM_PRESETS, PRESETS,
                                                       TrainConfig, ViTConfig)
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.ops.short_conv import short_conv
from pytorch_vit_paper_replication_tpu.optim import make_optimizer

T = 48


def _tiny(**kw):
    # float32 compute: the comparison is of the mathematics
    return LM_PRESETS["conv-tiny"](dtype="float32", **kw)


def _params(model, cfg, key=1):
    ids = jax.random.randint(jax.random.key(0), (2, T + 1), 0,
                             cfg.vocab_size)

    @jax.jit
    def draw(key):
        params = model.init(key, ids[:, :-1])["params"]
        # scales, taps and the expert bias not at their initial values
        # (the bias moves the selection only)
        return jax.tree.map(
            lambda a: a + 0.05 * jax.random.normal(
                jax.random.key(a.size), a.shape), params)

    return draw(jax.random.key(key)), ids[:, :-1], ids[:, 1:]


def _probed(model, params, tokens):
    """Eval-mode logits and the sown mixers' outputs."""
    return jax.jit(lambda p, x: model.apply(
        {"params": p}, x, False, mutable=["conv_probe"]))(params, tokens)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny()
    model = ViT(cfg)
    return (cfg, model) + _params(model, cfg)


# ------------------------------------------------------------ the reference
def test_logits_and_the_mixers_equal_the_reference(tiny):
    cfg, model, params, tokens, _ = tiny
    fields = dataclasses.asdict(cfg)
    got, sown = _probed(model, params, tokens)
    hid, mixed = reference_conv.hidden(params, tokens, fields,
                                       mixers=(0, 2))
    want = reference_conv.logits(params, hid)
    assert got.shape == (2, T, cfg.vocab_size) and got.dtype == jnp.float32
    assert reference_conv.agreement(got, want)["max"] < 1e-4
    probe = sown["conv_probe"]["backbone"]
    assert sorted(probe) == ["encoder_block_0", "encoder_block_2"]
    for layer in (0, 2):
        assert reference_conv.agreement(
            probe[f"encoder_block_{layer}"]["msa"]["out"][0],
            mixed[layer])["max"] < 1e-4


def test_the_loss_and_every_gradient_leaf_equal_the_reference(tiny):
    cfg, model, params, tokens, labels = tiny
    fields = dataclasses.asdict(cfg)

    def program(p):
        return model.apply({"params": p}, tokens, True, labels=labels)[0]

    got, got_g = jax.jit(jax.value_and_grad(program))(params)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: reference_conv.loss(p, tokens, labels, fields)))(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    # the tied table and the final norm; a conv mixer has 4 leaves (its
    # norm, in_proj, taps, out_proj), an attention block 5 (its norm, qkv,
    # the q and k norms, out); the dense feed-forward 4, a routed one 6
    # (its norm, router, expert bias, gate, up, down)
    assert len(flat_got) == len(flat_want) == 2 + (4 + 4) + (5 + 6) + (4 + 6)
    for path, g in flat_got:
        w = flat_want[path]
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:       # it moves the selection only
            assert float(jnp.abs(g).max()) == float(jnp.abs(w).max()) == 0
            continue
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-8, err_msg=name)


def test_the_references_gradient_a_block_at_a_time_is_autodiffs(tiny):
    """``reference_conv.gradients`` (each block's forward taken again
    inside its own pull-back, the size at which the chip's check takes
    the reference's step on the cell's three sequences) gives the loss
    and every leaf that ``jax.grad`` of the reference's loss gives."""
    cfg, _, params, tokens, labels = tiny
    fields = dataclasses.asdict(cfg)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p: reference_conv.loss(p, tokens, labels, fields)))(params)
    got, got_g = reference_conv.gradients(params, np.asarray(tokens),
                                          np.asarray(labels), fields)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    for path, g in jax.tree_util.tree_leaves_with_path(got_g):
        w = flat_want.pop(path)
        np.testing.assert_allclose(
            g, w, atol=1e-5 * float(jnp.abs(w).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    assert not flat_want


def test_the_tied_tables_gradient_is_the_untied_pairs_summed(tiny):
    """One table for the lookup and the head: its gradient is what
    autodiff gives the untied pair (embedding, head = the table
    transposed), summed."""
    cfg, model, params, tokens, labels = tiny
    untied = ViT(cfg.replace(tie_embedding=False))
    table = params["backbone"]["token_embedding"]["embedding"]
    pair = {**params, "head": {"kernel": table.T}}

    def loss(m, p):
        return m.apply({"params": p}, tokens, True, labels=labels)[0]

    tied, tied_g = jax.jit(jax.value_and_grad(
        lambda p: loss(model, p)))(params)
    untied_loss, pair_g = jax.jit(jax.value_and_grad(
        lambda p: loss(untied, p)))(pair)
    np.testing.assert_allclose(tied, untied_loss, rtol=1e-6)
    lookup = pair_g["backbone"]["token_embedding"]["embedding"]
    head = pair_g["head"]["kernel"].T
    assert float(jnp.abs(lookup).max()) > 0 and float(
        jnp.abs(head).max()) > 0
    np.testing.assert_allclose(
        tied_g["backbone"]["token_embedding"]["embedding"], lookup + head,
        atol=1e-6 * float(jnp.abs(head).max()))
    assert "head" not in params


def test_bfloat16_is_near_the_reference_and_fp8_in_the_conv_is_not():
    """The measures the chip's check uses tell the stated precision from
    the next one down at the tiny size too: the logits with fp8 inputs
    everywhere, and layer 0's mixer output with fp8 confined to the conv
    mixers' two products."""
    cfg = LM_PRESETS["conv-tiny"]()
    model = ViT(cfg)
    fields = dataclasses.asdict(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, T), 0, cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.key(4), ids)["params"]
    got, sown = _probed(model, params, ids)
    fp8 = jnp.float8_e4m3fn
    # (each under one jit: the reference's block programs inline there)
    reference = jax.jit(lambda p, x, **kw: reference_conv.hidden(
        p, x, fields, mixers=(0,), **kw), static_argnames=("dtype", "only"))
    hid, mixed = reference(params, ids)
    want = reference_conv.logits(params, hid)
    low = reference_conv.logits(params, reference(params, ids, dtype=fp8)[0],
                                dtype=fp8)
    near = reference_conv.agreement(got, want)
    far = reference_conv.agreement(low, want)
    assert near["rms"] < 0.02 < far["rms"], (near, far)
    _, conv_low = reference(params, ids, dtype=fp8, only="conv")
    program = sown["conv_probe"]["backbone"]["encoder_block_0"]["msa"][
        "out"][0]
    near = reference_conv.agreement(program, mixed[0])["rms"]
    far = reference_conv.agreement(conv_low[0], mixed[0])["rms"]
    assert near < 0.02 < far, (near, far)
    with pytest.raises(AssertionError):
        reference_conv.hidden(params, ids, fields, only="convs")


# ------------------------------------------------------------ the operator
def _plain(bcu, taps):
    """``C * conv(B * u)`` by torch's ``Conv1d(groups=D, padding=K-1)``
    sliced to T, a sequence at a time."""
    d = bcu.shape[-1] // 3
    b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    k = taps.shape[0]
    v = jnp.pad(b * u, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(taps[i] * v[:, i:i + bcu.shape[1]] for i in range(k))
    return c * conv


def test_the_convolution_is_causal_from_each_sequences_first_position():
    """At the first two positions of each sequence of a batch only the
    taps that reach back into the sequence count; no tap reads the
    sequence before it in the batch or a later position; the gradient
    written by hand is autodiff's of the plain form."""
    bcu = jax.random.normal(jax.random.key(0), (3, 10, 24))
    taps = jax.random.normal(jax.random.key(1), (3, 8))
    conv = jax.jit(short_conv)
    got = conv(bcu, taps)
    b, c, u = bcu[..., :8], bcu[..., 8:16], bcu[..., 16:]
    v = b * u
    np.testing.assert_allclose(got[:, 0], c[:, 0] * taps[2] * v[:, 0],
                               rtol=1e-6)
    np.testing.assert_allclose(
        got[:, 1], c[:, 1] * (taps[1] * v[:, 0] + taps[2] * v[:, 1]),
        rtol=1e-5)
    np.testing.assert_allclose(got, _plain(bcu, taps), rtol=1e-5,
                               atol=1e-6)
    # a change to the last position of sequence 0 reaches nothing else
    moved = conv(bcu.at[0, -1].add(1.0), taps)
    np.testing.assert_array_equal(moved[1:], got[1:])
    np.testing.assert_array_equal(moved[0, :-1], got[0, :-1])
    cot = jax.random.normal(jax.random.key(2), got.shape)
    g = jax.jit(jax.grad(lambda x, w: jnp.sum(short_conv(x, w) * cot),
                         (0, 1)))(bcu, taps)
    want = jax.jit(jax.grad(lambda x, w: jnp.sum(_plain(x, w) * cot),
                            (0, 1)))(bcu, taps)
    for x, y in zip(g, want):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-5)
    half = jax.eval_shape(short_conv, bcu.astype(jnp.bfloat16), taps)
    assert half.dtype == jnp.bfloat16
    assert jax.eval_shape(jax.grad(lambda x: jnp.sum(short_conv(
        x, taps).astype(jnp.float32))), bcu.astype(jnp.bfloat16)).dtype \
        == jnp.bfloat16


def test_the_shares_add_up_to_the_uncut_layer(tiny):
    """The parts that the 2 shares of the experts give (experts 0-3 and
    4-7) add up to the uncut reference's routed layer."""
    cfg, model, params, tokens, _ = tiny
    fields = dataclasses.asdict(cfg)
    p = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32),
                     params["backbone"]["encoder_block_1"]["mlp"])
    x = jax.random.normal(jax.random.key(5), (T, cfg.embedding_dim))
    with jax.default_matmul_precision("highest"):
        z = reference_conv.rms_norm(x, p["norm"]["scale"], cfg.ln_epsilon)
        held = cfg.num_experts_held
        rng = np.random.default_rng(0)
        more = {k: jnp.asarray(rng.normal(0, 0.05, p[k].shape), jnp.float32)
                for k in ("gate", "up", "down")}
        whole = {**p, **{k: jnp.concatenate([p[k], more[k]]) for k in more}}
        uncut = reference_conv.routed_ffn(z, whole,
                                          {**fields, "expert_offset": 0})
        shares = reference_conv.routed_ffn(z, p, fields, offset=0) \
            + reference_conv.routed_ffn(z, {**p, **more}, fields,
                                        offset=held)
    np.testing.assert_allclose(shares, uncut, atol=1e-5)
    assert float(jnp.abs(uncut).max()) > 0


# ---------------------------------------------------------------- the step
def test_train_step_learns_and_counts(tiny):
    cfg, model, params, tokens, labels = tiny
    tx = make_optimizer(TrainConfig(batch_size=2), 100)
    state = engine.TrainState.create(apply_fn=model.apply, params=params,
                                     tx=tx, rng=jax.random.key(2))
    step = jax.jit(engine.make_train_step())
    batch = {"tokens": tokens, "label": labels}
    seen = []
    for _ in range(5):
        state, m = step(state, batch)
        seen.append(m)
    assert float(seen[-1]["loss_sum"]) < float(seen[0]["loss_sum"])
    # the routed blocks' counters as every routed model has them
    assert float(seen[-1]["moe_dropped_pairs"]) == 0.0
    assert float(seen[-1]["moe_pairs_kept_share"]) == 1.0
    text = step.lower(state, batch).as_text(debug_info=True)
    for scope in ("/msa/conv/in_proj/", "/msa/conv/mix/",
                  "/msa/conv/out_proj/", "/msa/norm/", "/msa/attn_core/",
                  "/msa/q_norm/", "/msa/rope/", "/mlp/moe_router/",
                  "/mlp/dense/"):
        assert scope in text, scope


# ------------------------------------------------------------- hand counts
def test_parameters_of_the_cut():
    """The cut counted by hand and from the shapes the model makes:
    469,284,992 in the mixers, feed-forwards, norms and the table, and
    256 more, the expert bias (64 a routed block: a parameter that takes
    no gradient, held with its moments all the same). 16 bytes a
    parameter: 7.51 GB = 6.99 GiB."""
    model = ViT(LM_PRESETS["lfm2-24b-a2b-ep8"]())
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    bb = shapes["backbone"]
    conv = 2048 * 6144 + 3 * 2048 + 2048 * 2048
    attention = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 2 * 64
    dense = 3 * 2048 * 11776
    router, experts, bias = 2048 * 64, 8 * 3 * 2048 * 1536, 64
    assert (conv, attention, dense, experts) == (
        16_783_360, 10_485_888, 72_351_744, 75_497_472)
    assert count(bb["encoder_block_0"]["msa"]) == conv + 2048
    assert count(bb["encoder_block_1"]["msa"]) == attention + 2048
    assert count(bb["encoder_block_0"]) == 89_139_200
    assert count(bb["encoder_block_1"]) == 86_118_528 + bias
    assert count(bb["encoder_block_3"]) == conv + router + experts + bias \
        + 2 * 2048 == 92_416_064
    total = 89_139_200 + 86_118_528 + 3 * 92_416_000 + 2048 + 8192 * 2048
    assert total == 469_284_992
    assert "head" not in shapes                    # tied: one table
    assert count(shapes) == total + 4 * bias == 469_285_248
    assert count(shapes) * 16 / 1e9 == pytest.approx(7.51, abs=0.01)
    assert count(shapes) * 16 / 2**30 == pytest.approx(6.99, abs=0.01)


def test_flop_count_against_a_hand_count():
    """Forward MFLOP a token of the cut at 8,192 tokens, by part, and
    the step's TFLOP at three sequences."""
    from pytorch_vit_paper_replication_tpu.telemetry import flops

    cfg = LM_PRESETS["lfm2-24b-a2b-ep8"]()
    t = 8192
    conv = 4 * (2 * 2048 * 4 * 2048 + 2 * 3 * 2048)
    dense = 3 * 2 * 2048 * 11776
    projections = 2 * 2048 * 48 * 64 + 2 * 2048 * 2048
    core = 2 * 2 * (t * (t + 1) // 2) * 32 * 64 / t
    experts = 4 * (4 * 8 / 64) * 3 * 2 * 2048 * 1536
    head = 2 * 2048 * 8192
    routers = 4 * 2 * 2048 * 64
    by_hand = conv + dense + projections + core + experts + head + routers
    got = flops.forward_flops_per_sequence(cfg) / t
    assert got == pytest.approx(by_hand, rel=1e-12)
    for part, mflop in ((conv, 134.3), (dense, 144.7), (projections, 21.0),
                        (core, 33.6), (experts, 37.7), (head, 33.6),
                        (routers, 1.0), (by_hand, 405.9)):
        assert part / 1e6 == pytest.approx(mflop, abs=0.05)
    assert conv / by_hand == pytest.approx(0.33, abs=0.005)
    step = 3 * flops.train_step_flops_per_sequence(cfg)
    assert step / 1e12 == pytest.approx(29.9, abs=0.05)


def test_presets_state_every_published_width():
    cfg = LM_PRESETS["lfm2-24b-a2b-ep8"]()
    assert (cfg.embedding_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.dense_width, cfg.expert_width, cfg.num_experts,
            cfg.experts_per_token, cfg.conv_kernel, cfg.rope_theta,
            cfg.ln_epsilon) == (2048, 32, 8, 64, 11776, 1536, 64, 4, 3,
                                1e6, 1e-5)
    assert (cfg.num_layers, cfg.dense_layers, cfg.num_experts_held,
            cfg.vocab_size, cfg.max_seq_len, cfg.shared_experts) == (
        5, 1, 8, 8192, 8192, 0)
    assert (cfg.router_scoring, cfg.router_scale, cfg.router_input,
            cfg.expert_activation, cfg.qk_norm, cfg.tie_embedding,
            cfg.attn_bias) == ("sigmoid", 1.0, "block", "silu", True, True,
                               False)
    assert [cfg.layer_mixer(i) for i in range(5)] == [
        "conv", "attention", "conv", "conv", "conv"]
    assert [cfg.layer_rope(i) for i in range(5)] == [
        False, True, False, False, False]
    assert [cfg.layer_routed(i) for i in range(5)] == [
        False, True, True, True, True]
    for bad in (dict(rope_layout=(1,)),
                dict(sliding_window_layout=(1,), sliding_window=128),
                dict(conv_kernel=0)):
        with pytest.raises(ValueError, match="gated short convolutions"):
            cfg.replace(**bad)
    with pytest.raises(ValueError, match="gated short convolutions"):
        ViTConfig(mixer_layout=(1,))
    with pytest.raises(ValueError, match="tie_embedding"):
        ViTConfig(tie_embedding=True)


def test_other_presets_take_none_of_this_models_options():
    # (granite-4.0-h-micro's presets mix state-space layers, not conv
    # layers, with attention, and tie their head too)
    for name, make in {**PRESETS, **LM_PRESETS}.items():
        if name in ("lfm2-24b-a2b-ep8", "conv-tiny"):
            continue
        cfg = make()
        assert not any(cfg.layer_mixer(i) == "conv"
                       for i in range(cfg.num_layers)), name
        if name not in ("granite-4.0-h-micro-pp4", "ssm-tiny"):
            assert not cfg.mixer_layout and not cfg.tie_embedding, name


@pytest.mark.parametrize("tied,std", [(False, 1.0), (True, 0.02)])
def test_the_tables_rows_start_at_init_std_only_when_tied(tied, std):
    """An untied table's rows start at N(0, 1), as every accepted token
    model's do; a tied one's at ``init_std``, or its head's initial
    logits would be sqrt(D) wide."""
    cfg = _tiny(tie_embedding=tied, vocab_size=4096, embedding_dim=256)
    params = jax.jit(ViT(cfg).init)(jax.random.key(0),
                                    jnp.zeros((1, 8), jnp.int32))["params"]
    rows = params["backbone"]["token_embedding"]["embedding"]
    assert float(jnp.std(rows)) == pytest.approx(std, rel=0.01)


# ------------------------------------------------------- the device trace
BLOCK = "jit(train_step)/jvp(ViT)/backbone/encoder_block_2"


@pytest.mark.parametrize("path,row", [
    (f"{BLOCK}/msa/conv/in_proj/dot_general", "conv_proj"),
    (f"{BLOCK}/msa/conv/out_proj/dot_general", "conv_proj"),
    ("jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_2/msa/"
     "conv/in_proj/dot_general", "conv_proj"),
    (f"{BLOCK}/msa/conv/mix/mul", "conv_mix"),
    ("jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_0/msa/"
     "conv/mix/concatenate", "conv_mix"),
])
def test_device_trace_rows_of_the_new_scopes(path, row):
    """The trainer's table has a row for each part of the mixer, the
    benchmark's finer table the same rows, and the frozen table reads
    the mixer under ``msa_glue`` (it keeps the attention's module name),
    never ``other``."""
    from benchmark.lib import scopes, scopes_conv
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    assert device_trace.classify(path)[0] == row
    assert scopes_conv.row_of(path) == row
    assert scopes.classify(path)[0] == "msa_glue"
    assert scopes.classify(f"{BLOCK}/msa/norm/mul")[0] == "msa_norm"


# ---------------------------------------------------------- defaults as were
def _lowered_sha(cfg, example):
    """sha256 of the train step lowered from avals on the CPU, the
    payloads of Mosaic calls masked (a kernel's bytecode carries its
    callers' source paths and lines)."""
    model = ViT(cfg)
    tx = make_optimizer(TrainConfig(batch_size=2), 100)

    def abstract_state():
        params = model.init(jax.random.key(0), example["x"])["params"]
        return engine.TrainState.create(apply_fn=model.apply, params=params,
                                        tx=tx, rng=jax.random.key(0))

    state = jax.eval_shape(abstract_state)
    batch = {k: v for k, v in example.items() if k != "x"}
    text = jax.jit(engine.make_train_step()).lower(state, batch).as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = ""', text)
    return hashlib.sha256(text.encode()).hexdigest()


IDS = jax.ShapeDtypeStruct((1, 64), jnp.int32)
TOKENS = {"x": jnp.zeros((1, 8), jnp.int32), "tokens": IDS, "label": IDS}
IMAGES = {"x": jnp.zeros((1, 32, 32, 3)),
          "image": jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32),
          "label": jax.ShapeDtypeStruct((2,), jnp.int32)}


@pytest.mark.parametrize("name,example,sha", [
    ("ViT-B/16", IMAGES,
     "d8020ba44c6c5471ef5c80b4a95358cc8b281d1b837a90685b4d34394be39973"),
    ("ViT-L/16", IMAGES,
     "cfdfee1ee19e00e27caf872df45a3e9e0d8502c2b731c5ac73dbb3517cfdb16c"),
    ("smallthinker-21b-a3b-ep4", TOKENS,
     "b5a44777b14d0b0e5aba047226f2a4c9c2179907901257bc75a9daaf6b12509f"),
    ("glm-4.7-flash-ep8", TOKENS,
     "3cc9c473e34e317b59f3d8da94cb2ca562a9083ff792de3dd48dd378c3186fac"),
    ("keye-vl-2.0-30b-a3b-ep8", TOKENS,
     "6503ba31bac5594dcaab63ae53401e1adbf33ee970144b3e1aafeb923952b61e"),
    ("lfm2-24b-a2b-ep8", TOKENS,
     "ade88219ced53febf4a7eaad517143d5fb2f2e054f408053676313a260bf1887"),
])
def test_every_accepted_preset_lowers_to_the_parents_text(name, example,
                                                          sha):
    """The five configurations the benchmark has (the ViTs at 32 px, the
    token models at 64 tokens, every width published) lower to the text
    they lowered to before this model's options existed (recorded on the
    parent commit a1ee8d1): the new fields' defaults, the tied head's
    branch of the chunked loss and the FLOP count's split leave every
    accepted cell's program as it was.

    The constants hold for that parent only: a later PR that changes one
    of these programs on purpose, or an upgrade of jax, deletes them with
    this test; ``test_other_presets_take_none_of_this_models_options``
    is the check that stays. Keye-VL's was recorded again when its
    selection gained its two counters (``select_served``,
    ``select_tie_rows``) and, where flash serves the core, its search
    kernel: that program changed on purpose, the other four did not."""
    if name in LM_PRESETS:
        cfg = LM_PRESETS[name]()
    else:
        cfg = PRESETS[name](image_size=32, num_classes=3)
    assert _lowered_sha(cfg, example) == sha
