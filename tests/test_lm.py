"""The token model (``--model lm``) against its plain reference
(``benchmark/lib/reference_lm.py``) at the tiny preset, on the CPU: the
block options of the one encoder (grouped-query projections, rotary
positions and the attention kind per layer, routed experts of which a
share is held) with the chunked head + loss, the train step's counters
and their way to the telemetry, the FLOP count, the device trace's rows
and the entry point. The operators alone: ``tests/test_lm_ops.py``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import reference_lm
from pytorch_vit_paper_replication_tpu import engine
from pytorch_vit_paper_replication_tpu.configs import (LM_PRESETS, PRESETS,
                                                       TrainConfig)
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.optim import make_optimizer

T = 48      # not a multiple of the reference's or the kernels' blocks


def _tiny(**kw):
    # float32 compute: the comparison is of the mathematics
    return LM_PRESETS["lm-tiny"](dtype="float32", **kw)


def _model_dict(cfg):
    return dataclasses.asdict(cfg)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny()
    model = ViT(cfg)
    ids = jax.random.randint(jax.random.key(0), (2, T + 1), 0,
                             cfg.vocab_size)
    params = model.init(jax.random.key(1), ids[:, :-1])["params"]
    # a scale and a table that are not their initial ones
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.key(a.size),
                                               a.shape), params)
    return cfg, model, params, ids[:, :-1], ids[:, 1:]


def test_logits_equal_the_reference(tiny):
    cfg, model, params, tokens, _ = tiny
    got = model.apply({"params": params}, tokens, False)
    want = reference_lm.forward(params, tokens, _model_dict(cfg))
    assert got.shape == (2, T, cfg.vocab_size) and got.dtype == jnp.float32
    assert reference_lm.agreement(got, want)["max"] < 1e-4


def test_loss_and_every_gradient_leaf_equal_the_reference(tiny):
    cfg, model, params, tokens, labels = tiny

    def program(p):
        loss, _ = model.apply({"params": p}, tokens, True, labels=labels)
        return loss

    want_fn = lambda p: reference_lm.loss(p, tokens, labels,
                                          _model_dict(cfg))
    got, got_g = jax.value_and_grad(program)(params)
    want, want_g = jax.value_and_grad(want_fn)(params)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want_g))
    assert len(flat_got) == len(flat_want) == 2 + 1 + 4 * 8
    for path, g in flat_got:
        w = flat_want[path]
        assert float(jnp.abs(w).max()) > 0, path
        np.testing.assert_allclose(
            g, w, atol=2e-5 * float(jnp.abs(w).max()) + 1e-8,
            err_msg=jax.tree_util.keystr(path))


def test_bfloat16_forward_is_near_the_reference_and_fp8_inputs_are_not():
    """The measure the chip's check uses (``agreement``'s rms) tells the
    stated precision from the next one down, at the tiny size too."""
    cfg = LM_PRESETS["lm-tiny"]()
    model = ViT(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, T), 0, cfg.vocab_size)
    params = model.init(jax.random.key(4), ids)["params"]
    want = reference_lm.forward(params, ids, _model_dict(cfg))
    got = model.apply({"params": params}, ids, False)
    low = reference_lm.forward(params, ids, _model_dict(cfg),
                               dtype=jnp.float8_e4m3fn)
    near = reference_lm.agreement(got, want)["rms"]
    far = reference_lm.agreement(low, want)["rms"]
    assert near < 0.03 < far, (near, far)


def test_train_step_learns_and_counts(tiny):
    cfg, model, params, tokens, labels = tiny
    tx = make_optimizer(TrainConfig(batch_size=2), 100)
    state = engine.TrainState.create(apply_fn=model.apply, params=params,
                                     tx=tx, rng=jax.random.key(2))
    step = jax.jit(engine.make_train_step())
    batch = {"tokens": tokens, "label": labels}
    losses = []
    for _ in range(6):
        state, m = step(state, batch)
        losses.append(float(m["loss_sum"]) / 2)
    assert losses[-1] < losses[0]
    assert float(m["moe_dropped_pairs"]) == 0.0
    assert float(m["moe_pairs_kept_share"]) == 1.0
    # 4 of 8 experts held, top 2: about half of 2 x T x 2 pairs a layer
    mean = float(m["moe_pairs_per_expert_mean"])
    assert float(m["moe_pairs_per_expert_min"]) <= mean \
        <= float(m["moe_pairs_per_expert_max"])
    assert 0.25 * T < mean < 0.75 * T
    # half the experts held: a buffer of 2/3 of the pairs and a tile a
    # group, one pass at an even router's load
    assert float(m["moe_passes_max"]) >= 1.0
    assert 0.0 <= float(m["moe_one_pass_share"]) <= 1.0
    ev = jax.jit(engine.make_eval_step())(state, batch)
    assert float(ev["count"]) == 2.0 and np.isfinite(float(ev["loss_sum"]))


@pytest.mark.parametrize("passes,most,share", [
    ([[1, 1], [1, 1]], 1.0, 1.0), ([[1, 2], [3, 1]], 3.0, 0.5)])
def test_step_metrics_count_the_passes(passes, most, share):
    """``moe_passes_max`` / ``moe_one_pass_share`` over every chunk of
    every routed layer, from what the blocks sowed."""
    sown = {f"encoder_block_{i}": {"mlp": {
        "counts": (jnp.array([3, 5]),), "kept": (jnp.int32(8),),
        "routed": (jnp.int32(8),), "passes": (jnp.array(p),)}}
        for i, p in enumerate(passes)}
    m = engine._moe_metrics(sown)
    assert float(m["moe_passes_max"]) == most
    assert float(m["moe_one_pass_share"]) == share
    assert float(m["moe_dropped_pairs"]) == 0.0


def test_vit_presets_take_none_of_the_token_models_options():
    """The ViT presets are what they were: every block option at its
    default, so their blocks trace the code they traced before."""
    for name, make in PRESETS.items():
        cfg = make()
        assert (cfg.vocab_size, cfg.num_experts, cfg.norm) == (
            0, 0, "layernorm"), name
        assert cfg.kv_heads == cfg.num_heads and cfg.attn_bias
        assert cfg.attention_kind(3) == ("full", 0)
        assert not cfg.layer_rope(3)


def test_counters_reach_step_telemetry_and_the_registry():
    from pytorch_vit_paper_replication_tpu.telemetry import (
        HELP_TEXT, INSTRUMENTS, StepTelemetry, TelemetryRegistry)

    reg = TelemetryRegistry()
    tel = StepTelemetry(None, registry=reg, sample_every=1)
    tel.step(data_wait_s=0.0, exec_s=0.1, images=1, step=1, blocked=True,
             counters={"moe_pairs_per_expert_min": 3.0,
                       "moe_pairs_per_expert_mean": 24.0,
                       "moe_pairs_per_expert_max": 61.0,
                       "moe_pairs_kept_share": 1.0,
                       "moe_dropped_pairs": 0.0,
                       "moe_passes_max": 2.0,
                       "moe_one_pass_share": 0.875})
    snap = reg.snapshot()
    assert snap["gauges"]["tel_moe_pairs_per_expert_max"] == 61.0
    assert snap["gauges"]["tel_moe_pairs_kept_share"] == 1.0
    assert snap["counters"].get("tel_moe_dropped_pairs_total", 0) == 0
    assert snap["gauges"]["tel_moe_passes_max"] == 2.0
    assert snap["gauges"]["tel_moe_one_pass_share"] == 0.875
    for name in ("tel_moe_passes_max", "tel_moe_one_pass_share",
                 "tel_moe_pairs_per_expert_min",
                 "tel_moe_pairs_per_expert_mean",
                 "tel_moe_pairs_per_expert_max", "tel_moe_pairs_kept_share",
                 "tel_moe_dropped_pairs_total"):
        assert name in INSTRUMENTS and name in HELP_TEXT


def test_flop_count_knows_visibility_and_the_experts_held():
    from pytorch_vit_paper_replication_tpu.telemetry import flops

    cfg = LM_PRESETS["smallthinker-21b-a3b-ep4"]()
    t = cfg.max_seq_len
    assert flops.visible_pairs(t) == t * (t + 1) // 2
    assert flops.visible_pairs(t, 4096) == 4096 * 4097 // 2 + (t - 4096) * 4096
    assert flops.visible_pairs(100, 4096) == 100 * 101 // 2
    per_token = flops.forward_flops_per_sequence(cfg) / t
    assert per_token / 1e6 == pytest.approx(705.9, abs=0.1)
    assert flops.train_step_flops_per_sequence(cfg) / 1e12 == pytest.approx(
        34.70, abs=0.01)
    # all 64 experts held: 4 x the experts' part, nothing else moves
    whole = flops.forward_flops_per_sequence(
        cfg.replace(experts_held=64)) / t
    assert (whole - per_token) / 1e6 == pytest.approx(3 * 70.8, abs=0.2)


@pytest.mark.parametrize("path,row", [
    ("jit(train_step)/jvp(ViT)/backbone/encoder_block_1/mlp/moe_router/"
     "router/dot_general", ("moe_router", "forward")),
    ("jit(train_step)/jvp(ViT)/backbone/encoder_block_1/mlp/moe_dispatch/"
     "gather", ("moe_dispatch", "forward")),
    ("jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_2/mlp/"
     "moe_experts/mul", ("moe_experts", "backward")),
    ("jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_2/mlp/"
     "moe_combine/add", ("moe_combine", "backward")),
    ("jit(train_step)/jvp(ViT)/backbone/encoder_block_1/msa/rope/mul",
     ("rope", "forward")),
    ("jit(train_step)/jvp(ViT)/backbone/patch_embedding/token_embedding/"
     "gather", ("token_embedding", "forward")),
    ("jit(train_step)/jvp(ViT)/head/head/dot_general", ("head", "forward")),
    ("jit(train_step)/jvp(ViT)/head/loss/exp", ("head_loss", "forward")),
    # what the frozen table already had is read as before
    ("jit(train_step)/jvp(ViT)/backbone/encoder_block_1/mlp/norm/mul",
     ("mlp_xla", "forward")),
    ("jit(train_step)/jvp(ViT)/backbone/encoder_block_1/msa/qkv/"
     "dot_general", ("msa_qkv", "forward")),
])
def test_device_trace_rows_of_the_token_model(path, row):
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    assert device_trace.classify(path) == row
    assert device_trace.classify(path, kernel="moe_gmm_fwd")[0] == \
        "moe_gmm_fwd"


def test_entry_point_trains_the_tiny_preset(tmp_path, capsys):
    """``train --model lm --preset lm-tiny --synthetic`` through the
    trainer's own loop: mesh, compile cache, checkpoint, telemetry."""
    import json

    from pytorch_vit_paper_replication_tpu.train import main

    results = main([
        "--model", "lm", "--preset", "lm-tiny", "--synthetic",
        "--batch-size", "8", "--epochs", "2", "--steps-per-epoch", "4",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
        "--compile-cache-dir", str(tmp_path / "cache"),
        "--telemetry-jsonl", str(tmp_path / "tel.jsonl"),
        "--telemetry-every", "1"])
    assert results["train_loss"][1] < results["train_loss"][0] < 6.5
    assert (tmp_path / "ckpt" / "final").is_dir()
    assert "model: lm-tiny | params: 182,848" in capsys.readouterr().out
    rows = [json.loads(l) for l in (tmp_path / "tel.jsonl").read_text()
            .splitlines()]
    sampled = [r for r in rows if "tel_moe_pairs_kept_share" in r]
    assert sampled and all(r["tel_moe_pairs_kept_share"] == 1.0
                           and r["tel_moe_dropped_pairs"] == 0.0
                           and r["tel_moe_passes_max"] >= 1.0
                           and 0.0 <= r["tel_moe_one_pass_share"] <= 1.0
                           for r in sampled)
    with pytest.raises(SystemExit, match="token model's preset"):
        main(["--model", "lm", "--preset", "ViT-B/16", "--synthetic"])


def test_token_source_is_a_pure_function_of_its_key():
    from pytorch_vit_paper_replication_tpu.data.tokens import (TokenLoader,
                                                               TokenSource)

    src = TokenSource(2**31 + 5, 256, 32)
    a, b = src.batch(4, 0, 1, 2), src.batch(4, 0, 1, 2)
    assert (a["tokens"] == b["tokens"]).all() and a["tokens"].shape == (4, 32)
    assert (a["tokens"][:, 1:] == a["label"][:, :-1]).all()
    assert not (a["tokens"] == src.batch(4, 0, 1, 3)["tokens"]).all()
    loader = TokenLoader(src, 4, 3)
    first = [b["tokens"] for b in loader]
    loader.epoch, loader.skip_next_batches = 0, 1
    resumed = [b["tokens"] for b in loader]
    assert len(first) == 3 and len(resumed) == 2
    assert (first[1] == resumed[0]).all()
    # a few rows take most of the draws (Zipf), every id inside the slice
    ids = src.batch(64, 9)["tokens"]
    assert ids.max() < 256 and np.bincount(ids.ravel()).max() > 0.1 * ids.size
