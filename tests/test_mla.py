"""The latent-attention token model (``--preset mla-tiny``: GLM-4.7-Flash's
blocks at a size for tests) against its plain reference
(``benchmark/lib/reference_mla.py``) on the CPU: latent attention, the
layer kind per layer, the sigmoid router with a shared expert beside the
routed ones, the multi-token-prediction module and the two-term
objective; the train step's counters and their way to the telemetry, the
FLOP count against a hand count, the device trace's rows, the defaults
left as they were, and the entry point. The operators alone:
``tests/test_mla_ops.py``.
"""

import dataclasses
import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_mla
from pytorch_vit_paper_replication_tpu import engine
from pytorch_vit_paper_replication_tpu.configs import (LM_PRESETS, PRESETS,
                                                       TrainConfig, ViTConfig)
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.optim import make_optimizer

T = 48      # not a multiple of the reference's or the kernels' blocks


def _tiny(**kw):
    # float32 compute: the comparison is of the mathematics
    return LM_PRESETS["mla-tiny"](dtype="float32", **kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny()
    model = ViT(cfg)
    ids = jax.random.randint(jax.random.key(0), (2, T + 1), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.key(1), ids[:, :-1])["params"]
    # scales, a table and a correction bias that are not their initial ones
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.key(a.size),
                                               a.shape), params)
    return cfg, model, params, ids[:, :-1], ids[:, 1:]


def test_main_logits_equal_the_reference(tiny):
    """Eval with ``labels=None`` returns the main logits, as a one-term
    model does: the module is not run."""
    cfg, model, params, tokens, _ = tiny
    got = model.apply({"params": params}, tokens, False)
    want = reference_mla.forward(params, tokens, dataclasses.asdict(cfg))
    assert got.shape == (2, T, cfg.vocab_size) and got.dtype == jnp.float32
    assert reference_mla.agreement(got, want)["max"] < 1e-4


def test_both_losses_and_every_gradient_leaf_equal_the_reference(tiny):
    cfg, model, params, tokens, labels = tiny
    fields = dataclasses.asdict(cfg)

    def program(p):
        (loss, _), sown = model.apply({"params": p}, tokens, True,
                                      labels=labels, mutable=["lm_stats"])
        return loss, sown["lm_stats"]

    (got, stats), got_g = jax.value_and_grad(program, has_aux=True)(params)
    want, want_g = jax.value_and_grad(
        lambda p: reference_mla.loss(p, tokens, labels, fields))(params)
    main, module = reference_mla.losses(params, tokens, labels, fields)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(stats["main_loss"][0], main, rtol=1e-5)
    np.testing.assert_allclose(stats["mtp_loss"][0], module, rtol=1e-5)
    np.testing.assert_allclose(got, main + cfg.mtp_loss_weight * module,
                               rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    # embedding, final norm, head; a block's attention has 8 leaves; the
    # dense layer's feed-forward 4, a routed one's 9; the module's merge 3
    # and its norm
    assert len(flat_got) == len(flat_want) == 3 + 4 * 8 + 4 + 3 * 9 + 3 + 1
    for path, g in flat_got:
        w = flat_want[path]
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            # it moves the selection only: no gradient reaches it
            assert float(jnp.abs(g).max()) == float(jnp.abs(w).max()) == 0
            continue
        assert float(jnp.abs(w).max()) > 0, name
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-8, err_msg=name)


def test_the_modules_last_position_carries_no_loss_and_the_head_sums(tiny):
    """The module's target at position i is token i + 2 = ``labels[i +
    1]``; a sequence's last position has none and is left out of the
    mean. The head's gradient is the sum of the two terms' gradients."""
    cfg, model, params, tokens, labels = tiny
    fields = dataclasses.asdict(cfg)
    terms = lambda p: model.apply(
        {"params": p}, tokens, True, labels=labels,
        mutable=["lm_stats"])[1]["lm_stats"]
    _, drafted = reference_mla.hidden(params, tokens, labels, fields)
    logits = reference_mla.logits(params, drafted)
    wrapped = jnp.roll(labels, -1, axis=1)
    nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, wrapped[..., None], -1)[..., 0]
    got = float(terms(params)["mtp_loss"][0])
    np.testing.assert_allclose(got, float(nll[:, :-1].mean()), rtol=1e-5)
    assert abs(got - float(nll.mean())) > 1e-4 * got
    head = lambda which: jax.grad(
        lambda p: terms(p)[which][0])(params)["head"]["kernel"]
    both = jax.grad(lambda p: model.apply(
        {"params": p}, tokens, True, labels=labels)[0])(params)
    np.testing.assert_allclose(
        both["head"]["kernel"],
        head("main_loss") + cfg.mtp_loss_weight * head("mtp_loss"),
        atol=1e-7)


def test_left_out_positions_add_nothing():
    from pytorch_vit_paper_replication_tpu.ops.lm_loss import \
        head_cross_entropy

    hid = jax.random.normal(jax.random.key(0), (40, 16))
    w = jax.random.normal(jax.random.key(1), (16, 32))
    y = jax.random.randint(jax.random.key(2), (40,), 0, 32)
    counted = jnp.arange(40) % 5 != 4
    keep = np.flatnonzero(np.asarray(counted))
    some = lambda h, w: head_cross_entropy(h, w, y, 16, counted)[0]
    want = lambda h, w: head_cross_entropy(h[keep], w, y[keep], 16)[0]
    got, got_g = jax.value_and_grad(some, (0, 1))(hid, w)
    ref, ref_g = jax.value_and_grad(want, (0, 1))(hid, w)
    np.testing.assert_allclose(got, ref, rtol=1e-6)
    np.testing.assert_allclose(got_g[1], ref_g[1], atol=1e-6)
    assert float(jnp.abs(got_g[0][~counted]).max()) == 0.0
    np.testing.assert_allclose(got_g[0][keep], ref_g[0][keep], atol=1e-6)
    assert float(head_cross_entropy(hid, w, y, 16, counted)[1]) == float(
        jnp.sum((jnp.argmax(hid @ w, -1) == y) & counted))


def test_bfloat16_forward_is_near_the_reference_and_fp8_inputs_are_not():
    """The measure the chip's check uses (``agreement``'s rms) tells the
    stated precision from the next one down, at the tiny size too; the
    control can be confined to one family of products."""
    cfg = LM_PRESETS["mla-tiny"]()
    model = ViT(cfg)
    fields = dataclasses.asdict(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, T), 0, cfg.vocab_size)
    params = model.init(jax.random.key(4), ids)["params"]
    want = reference_mla.forward(params, ids, fields)
    got = model.apply({"params": params}, ids, False)
    low = reference_mla.forward(params, ids, fields,
                                dtype=jnp.float8_e4m3fn)
    near = reference_mla.agreement(got, want)["rms"]
    far = reference_mla.agreement(low, want)["rms"]
    assert near < 0.02 < far, (near, far)
    main, module = reference_mla.hidden(params, ids, ids, fields)
    for family in reference_mla.FAMILIES[:-1]:
        part, _ = reference_mla.hidden(params, ids, ids, fields,
                                       dtype=jnp.float8_e4m3fn, only=family)
        assert 0 < float(jnp.abs(part - main).max()), family
    with pytest.raises(AssertionError):
        reference_mla.hidden(params, ids, ids, fields, only="experts2")


def test_train_step_learns_and_counts(tiny):
    cfg, model, params, tokens, labels = tiny
    tx = make_optimizer(TrainConfig(batch_size=2), 100)
    state = engine.TrainState.create(apply_fn=model.apply, params=params,
                                     tx=tx, rng=jax.random.key(2))
    step = jax.jit(engine.make_train_step())
    batch = {"tokens": tokens, "label": labels}
    bias = lambda s: s.params["backbone"]["encoder_block_1"]["mlp"][
        "router_bias"]
    before = bias(state)
    seen = []
    for _ in range(6):
        state, m = step(state, batch)
        seen.append(m)
    first, m = seen[0], seen[-1]
    assert float(m["loss_sum"]) < float(first["loss_sum"])
    np.testing.assert_allclose(
        float(m["loss_sum"]) / 2,
        float(m["main_loss"]) + cfg.mtp_loss_weight * float(m["mtp_loss"]),
        rtol=1e-5)
    assert 0.0 <= float(m["mtp_top1_share"]) <= 1.0
    # top 2 sigmoid scores, each in (0, 1)
    assert 0.0 < float(m["moe_score_sum_mean"]) < 2.0
    assert float(m["moe_dropped_pairs"]) == 0.0
    assert float(m["moe_pairs_kept_share"]) == 1.0
    # 4 of 8 experts held, top 2, over the three routed blocks (the
    # module's with them): about half of 2 x T x 2 pairs a block
    assert 0.25 * T < float(m["moe_pairs_per_expert_mean"]) < 0.75 * T
    # no gradient, no decay (it has one dimension): the bias stays
    np.testing.assert_array_equal(bias(state), before)
    ev = jax.jit(engine.make_eval_step())(state, batch)
    assert float(ev["count"]) == 2.0 and np.isfinite(float(ev["loss_sum"]))


def test_counters_reach_step_telemetry_and_the_registry():
    from pytorch_vit_paper_replication_tpu.telemetry import (
        HELP_TEXT, INSTRUMENTS, StepTelemetry, TelemetryRegistry)

    reg = TelemetryRegistry()
    tel = StepTelemetry(None, registry=reg, sample_every=1)
    tel.step(data_wait_s=0.0, exec_s=0.1, images=1, step=1, blocked=True,
             counters={"main_loss": 9.5, "mtp_loss": 9.75,
                       "mtp_top1_share": 0.125,
                       "moe_score_sum_mean": 2.25})
    gauges = reg.snapshot()["gauges"]
    assert (gauges["tel_main_loss"], gauges["tel_mtp_loss"],
            gauges["tel_mtp_top1_share"],
            gauges["tel_moe_score_sum_mean"]) == (9.5, 9.75, 0.125, 2.25)
    for name in engine.LM_COUNTERS + ("moe_score_sum_mean",):
        assert f"tel_{name}" in INSTRUMENTS and f"tel_{name}" in HELP_TEXT


def test_flop_count_against_a_hand_count():
    """GLM-4.7-Flash's cut by hand (ISSUE 32's arithmetic), forward
    MFLOP a token at 16,384 tokens."""
    from pytorch_vit_paper_replication_tpu.telemetry import flops

    cfg = LM_PRESETS["glm-4.7-flash-ep8"]()
    t = cfg.max_seq_len
    blocks = 6                                   # 5 layers + the module
    latent = 2 * (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960)
    core = 2 * 2 * (t + 1) / 2 * 20 * 256
    out = 2 * 5120 * 2048
    dense = 3 * 2 * 2048 * 10240
    expert = 3 * 2 * 2048 * 1536
    routed = 5 * (2 * 2048 * 64 + expert * 4 * 8 / 64 + expert)
    merge = 2 * 4096 * 2048
    head = 2 * 2 * 2048 * 19360
    by_hand = blocks * (latent + core + out) + dense + routed + merge + head
    per_token = flops.forward_flops_per_sequence(cfg) / t
    assert per_token == pytest.approx(by_hand, rel=1e-12)
    assert per_token / 1e6 == pytest.approx(1711.9, abs=0.1)
    assert blocks * core / per_token == pytest.approx(0.588, abs=0.001)
    assert flops.train_step_flops_per_sequence(cfg) / 1e12 == pytest.approx(
        84.14, abs=0.01)
    # a model without the module: one block, the merge and one head less
    plain = flops.forward_flops_per_sequence(cfg.replace(mtp_modules=0)) / t
    assert per_token - plain == pytest.approx(
        latent + core + out + routed / 5 + merge + head / 2, rel=1e-12)
    # SmallThinker's count reads layer kinds and is what it was
    assert flops.forward_flops_per_sequence(
        LM_PRESETS["smallthinker-21b-a3b-ep4"]()) / t / 1e6 == pytest.approx(
            705.9, abs=0.1)


BLOCK = "jit(train_step)/jvp(ViT)/backbone/encoder_block_2"
MODULE = "jit(train_step)/transpose(jvp(ViT))/backbone/mtp"


@pytest.mark.parametrize("path,row,frozen", [
    (f"{BLOCK}/msa/qkv/q_down/q_down/dot_general", "mla_q", "msa_qkv"),
    (f"{BLOCK}/msa/qkv/q_down/q_norm/mul", "mla_q", "msa_qkv"),
    (f"{BLOCK}/msa/qkv/q_up/dot_general", "mla_q", "msa_qkv"),
    (f"{BLOCK}/msa/qkv/kv_down/kv_norm/mul", "mla_kv", "msa_qkv"),
    (f"{BLOCK}/msa/qkv/kv_up/dot_general", "mla_kv", "msa_qkv"),
    # taken again in the backward pass, under the block's checkpoint
    ("jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_2/jvp(ViT)/"
     "backbone/encoder_block_2/checkpoint/rematted_computation/msa/qkv/"
     "kv_up/dot_general", "mla_kv", "msa_qkv"),
    (f"{BLOCK}/msa/rope/concatenate", "rope", "msa_glue"),
    (f"{BLOCK}/msa/attn_core/flash_fwd/pallas_call", "attn_core",
     "attn_core"),
    (f"{BLOCK}/msa/out/dot_general", "msa_out", "msa_out"),
    (f"{BLOCK}/mlp/moe_shared/shared/gate/dot_general", "moe_shared",
     "mlp_xla"),
    (f"{BLOCK}/mlp/moe_router/router/dot_general", "moe_router", "mlp_xla"),
    ("jit(train_step)/jvp(ViT)/backbone/encoder_block_0/mlp/dense/up/"
     "dot_general", "mlp_xla", "mlp_xla"),
    (f"{MODULE}/patch_embedding/mtp_merge/eh_proj/dot_general", "mtp_merge",
     "patch_embed"),
    (f"{MODULE}/patch_embedding/mtp_merge/token_embedding/gather",
     "mtp_merge", "patch_embed"),
    (f"{MODULE}/encoder_block_3/msa/qkv/q_up/dot_general", "mtp_block",
     "msa_qkv"),
    (f"{MODULE}/encoder_block_3/mlp/moe_shared/shared/down/dot_general",
     "mtp_block", "mlp_xla"),
    (f"{MODULE}/encoder_norm/norm_s/mul", "mtp_head", "final_norm_head"),
    ("jit(train_step)/jvp(ViT)/mtp/head/head/dot_general", "mtp_head",
     "final_norm_head"),
    ("jit(train_step)/jvp(ViT)/mtp/head/loss/exp", "mtp_head",
     "final_norm_head"),
    ("jit(train_step)/jvp(ViT)/head/loss/exp", "head_loss",
     "final_norm_head"),
    ("jit(train_step)/jvp()/metrics/reduce_max", "metrics", "metrics"),
])
def test_device_trace_rows_of_the_new_scopes(path, row, frozen):
    """The trainer's table has a row for each new scope, and the
    benchmark's frozen table reads the same op under the row a reader
    expects, never ``other``."""
    from benchmark.lib import scopes
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    assert device_trace.classify(path)[0] == row
    assert scopes.classify(path)[0] == frozen


def test_the_lowered_step_names_every_new_scope(tiny):
    cfg, model, params, tokens, labels = tiny
    tx = make_optimizer(TrainConfig(batch_size=2), 100)
    state = engine.TrainState.create(apply_fn=model.apply, params=params,
                                     tx=tx, rng=jax.random.key(2))
    text = jax.jit(engine.make_train_step()).lower(
        state, {"tokens": tokens, "label": labels}).as_text(debug_info=True)
    for scope in ("/msa/qkv/q_down/", "/msa/qkv/q_up/", "/msa/qkv/kv_down/",
                  "/msa/qkv/kv_up/", "/msa/rope/", "/msa/attn_core/",
                  "/msa/out/", "/mlp/moe_shared/shared/", "/mlp/dense/",
                  "/mtp/patch_embedding/mtp_merge/eh_proj/",
                  "/mtp/patch_embedding/mtp_merge/token_embedding/",
                  "/mtp/encoder_block_3/msa/", "/mtp/encoder_norm/norm_s/",
                  "/mtp/head/head/", "/mtp/head/loss/",
                  "checkpoint/rematted_computation/msa/qkv/q_up/"):
        assert scope in text, scope


def test_presets_state_every_published_width():
    cfg = LM_PRESETS["glm-4.7-flash-ep8"]()
    assert (cfg.embedding_dim, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim,
            cfg.num_heads, cfg.kv_heads, cfg.head_dim) == (
        2048, 768, 512, 192, 64, 256, 20, 20, 256)
    assert (cfg.dense_width, cfg.expert_width, cfg.num_experts,
            cfg.experts_per_token, cfg.shared_experts, cfg.router_scale,
            cfg.rope_theta, cfg.ln_epsilon) == (
        10240, 1536, 64, 4, 1, 1.8, 1e6, 1e-5)
    assert (cfg.num_layers, cfg.dense_layers, cfg.num_experts_held,
            cfg.vocab_size, cfg.mtp_modules, cfg.mtp_loss_weight) == (
        5, 1, 8, 19360, 1, 0.3)
    assert [cfg.layer_routed(i) for i in range(6)] == [False] + [True] * 5
    assert all(cfg.layer_rope(i) and cfg.attention_kind(i) == ("causal", 0)
               for i in range(6))
    with pytest.raises(ValueError, match="latent attention"):
        cfg.replace(v_head_dim=128)
    with pytest.raises(ValueError, match="dense_layers needs dense_width"):
        cfg.replace(dense_width=0)
    with pytest.raises(ValueError, match="router_scoring"):
        cfg.replace(router_scoring="softmax2")
    with pytest.raises(ValueError, match="mtp_modules"):
        cfg.replace(mtp_modules=2)


def test_parameters_of_the_cut():
    """706.5 M parameters = 11.30 GB at 16 bytes each (ISSUE 32)."""
    model = ViT(LM_PRESETS["glm-4.7-flash-ep8"]())
    shapes = jax.eval_shape(model.init, jax.random.key(0),
                            jnp.zeros((1, 8), jnp.int32))["params"]
    count = lambda tree: sum(int(np.prod(a.shape))
                             for a in jax.tree.leaves(tree))
    back = shapes["backbone"]
    attention = (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960
                 + 5120 * 2048 + 768 + 512 + 2048)     # and its three norms
    assert count(back["encoder_block_1"]["msa"]) == attention == 21_761_280
    gated = lambda width: 3 * 2048 * width
    assert count(back["encoder_block_0"]) == attention + gated(10240) + 2048
    routed = attention + 2048 + 2048 * 64 + 64 + 9 * gated(1536)
    assert count(back["encoder_block_1"]) == routed
    assert count(back["mtp"]) == routed + 2 * 2048 * 2048 + 3 * 2048
    total = (attention + gated(10240) + 2048 + 5 * routed
             + 2 * 2048 * 2048 + 3 * 2048 + 2048 + 2 * 19360 * 2048)
    assert count(shapes) == total
    assert total / 1e6 == pytest.approx(706.5, abs=0.1)
    assert total * 16 / 2**30 == pytest.approx(10.53, abs=0.01)


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


def test_defaults_lower_to_the_parents_text():
    """A SmallThinker and a ViT configuration lower to the text they
    lowered to before this model's options existed (sha256 of
    ``lower(avals).as_text()`` at a small size on the CPU, recorded on
    the parent commit 6feb9ba, where no Mosaic payload carries a source
    line): the new fields' defaults leave both programs as they were.

    The two constants hold for PR 32's parent only. A later PR that
    changes either program on purpose, or an upgrade of jax, deletes
    them with this test: ``test_vit_presets_take_none_of_this_models_
    options`` is the check that stays."""
    ids = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    lm = _lowered_sha(LM_PRESETS["lm-tiny"](),
                      {"x": jnp.zeros((1, 8), jnp.int32), "tokens": ids,
                       "label": ids})
    vit = _lowered_sha(
        PRESETS["ViT-Ti/16"](image_size=32, num_classes=3),
        {"x": jnp.zeros((1, 32, 32, 3)),
         "image": jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32),
         "label": jax.ShapeDtypeStruct((2,), jnp.int32)})
    assert (lm, vit) == (PARENT_LM_SHA, PARENT_VIT_SHA)


PARENT_LM_SHA = "5e7593dcfdb455564d7e653b4bd3d4f11dcb19c029f9f7b9df75e5b60bcf32c1"
PARENT_VIT_SHA = "6236f5cff96a4a20cf24ada6b7d8fcd32bb75b504379e552841584e995a65a99"


def test_vit_presets_take_none_of_this_models_options():
    fields = ("kv_lora_rank", "q_lora_rank", "shared_experts",
              "dense_layers", "mtp_modules")
    for name, make in PRESETS.items():
        assert not any(getattr(make(), f) for f in fields), name
    st = LM_PRESETS["smallthinker-21b-a3b-ep4"]()
    assert not any(getattr(st, f) for f in fields)
    assert (st.router_scoring, st.router_input, st.expert_activation,
            st.router_scale) == ("softmax", "attention", "relu", 1.0)
    assert all(st.layer_routed(i) for i in range(4))
    assert ViTConfig().layer_routed(0) is False


def test_entry_point_trains_the_tiny_preset(tmp_path, capsys):
    """``train --model lm --preset mla-tiny --synthetic`` through the
    trainer's own loop: mesh, compile cache, checkpoint, telemetry."""
    from pytorch_vit_paper_replication_tpu.train import main

    results = main([
        "--model", "lm", "--preset", "mla-tiny", "--synthetic",
        "--batch-size", "8", "--epochs", "2", "--steps-per-epoch", "4",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--compile-cache-dir", str(tmp_path / "cache"),
        "--telemetry-jsonl", str(tmp_path / "tel.jsonl"),
        "--telemetry-every", "1"])
    # the objective is main + 0.3 x module: from about 1.3 log 256
    assert results["train_loss"][1] < results["train_loss"][0] < 8.5
    assert (tmp_path / "ckpt" / "final").is_dir()
    assert "model: mla-tiny | params: 195,000" in capsys.readouterr().out
    rows = [json.loads(l) for l in (tmp_path / "tel.jsonl").read_text()
            .splitlines()]
    sampled = [r for r in rows if "tel_mtp_loss" in r]
    assert sampled and all(
        r["tel_moe_pairs_kept_share"] == 1.0 and r["tel_main_loss"] > 0
        and 0.0 <= r["tel_mtp_top1_share"] <= 1.0
        and 0.0 < r["tel_moe_score_sum_mean"] < 2.0 for r in sampled)
