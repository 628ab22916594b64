"""What ``test_dsa_files.py`` holds for its configuration's files, held
for the configuration whose layers mix gated short convolutions with
attention (``lfm2-24b-a2b-ep8``, cell ``lfm2_train_8k``): the file
builds the program's preset and keeps every number of the source's
configuration unless it is reduced, the copied FLOP count and the copied
finer table of scopes equal the program's, the mix's least bytes by
hand, each limit lies between its two readings, the cell's file names
what the driver reads, and both rehearsals of the cell pass at 64
tokens. (The copies are also held in tier-1 by
``tests/test_copies.py``.)"""

import json
import subprocess
import sys

import pytest

from benchmark.drivers import train_conv
from benchmark.lib import flops_conv, harness, kernels, kernels_conv, \
    kernels_lm, scopes_conv

# The source's ``config.json`` (huggingface.co/LiquidAI/LFM2-24B-A2B),
# every key that gives the model's shape: what the configuration file
# has to hold unless a key is reduced.
SOURCE = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"]
    + ["full_attention", "conv", "conv", "conv"] * 9
    + ["full_attention", "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
CONFIG = harness.BENCH / "configs" / "lfm2-24b-a2b-ep8.json"
CELL = "lfm2_train_8k"


def test_config_file_builds_the_programs_preset():
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    config = harness.load_json(CONFIG)
    cfg, _ = harness.build_model(config)
    assert cfg == LM_PRESETS[config["program_preset"]]()
    tiny = harness.load_cell(CELL, rehearsal=True)[1]
    assert harness.build_model(tiny)[0] == LM_PRESETS["conv-tiny"]()
    assert len(config["source"]) <= 200
    assert {"published", "assumed", "deployment", "reduced",
            "reduced_in_model", "memory_analysis"} <= set(config)
    assert "8 chips share each layer" in config["deployment"]
    assert "chip 0 of stage 0" in config["deployment"]
    assert {"tied_head", "embedding_init", "initialiser", "rotary",
            "expert_bias", "aux_loss", "dense", "packing",
            "dtype"} <= set(config["assumed"])
    assert config["param_dtype"] == "float32"


def test_every_published_number_is_in_the_file_unless_reduced():
    """The file holds every number of the source's configuration under
    the same key, nested groups whole, and only the keys in ``reduced``
    differ; no width is among them."""
    config = harness.load_json(CONFIG)
    src = SOURCE
    assert config["source"].startswith(
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/")
    differ = {k for k, v in src.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    m = config["model"]
    assert config["rope_parameters"] == src["rope_parameters"]
    assert (m["embedding_dim"], m["num_heads"], m["num_kv_heads"],
            m["dense_width"], m["expert_width"], m["experts_per_token"],
            m["num_experts"], m["conv_kernel"], m["rope_theta"],
            m["ln_epsilon"], m["router_scale"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["intermediate_size"],
        src["moe_intermediate_size"], src["num_experts_per_tok"],
        config["published"]["num_experts"], src["conv_L_cache"],
        src["rope_parameters"]["rope_theta"], src["norm_eps"],
        src["routed_scaling_factor"])
    assert src["use_expert_bias"] and src["norm_topk_prob"] \
        and m["router_scoring"] == "sigmoid"
    assert src["conv_bias"] is False and m["attn_bias"] is False
    # the layers kept are published layers 0 and 2-5, in their kinds
    kept = [src["layer_types"][i] for i in (0, 2, 3, 4, 5)]
    assert config["layer_types"] == kept
    assert [1 if t == "conv" else 0 for t in kept] == m["mixer_layout"]
    assert [0 if t == "conv" else 1 for t in kept] == m["rope_layout"]
    assert src["layer_types"].count("conv") == 30 \
        and src["layer_types"].count("full_attention") == 10
    assert (m["num_layers"], m["dense_layers"], m["experts_held"],
            m["vocab_size"]) == (
        config["num_hidden_layers"], config["num_dense_layers"],
        config["num_experts"], config["vocab_size"])
    for key in ("num_hidden_layers", "num_dense_layers", "num_experts",
                "vocab_size"):
        assert config["published"][key] == src[key], key
    # the floors of a model_config cut: a whole period and four layers
    # after the leading dense one, 8 experts, an eighth of the vocabulary
    assert m["experts_held"] >= 8 and m["num_layers"] - m["dense_layers"] >= 4
    assert m["vocab_size"] * 8 >= src["vocab_size"]


def test_flop_count_equals_the_programs():
    from pytorch_vit_paper_replication_tpu.telemetry import flops as theirs

    config = harness.load_json(CONFIG)
    cfg, _ = harness.build_model(config)
    for t in (8192, 2048, 1000, 64):
        assert flops_conv.train_step_flops_per_sequence(config["model"], t) \
            == theirs.train_step_flops_per_sequence(cfg, t)
    # 29.9 TFLOP a step of three sequences
    assert 3 * flops_conv.train_step_flops_per_sequence(
        config["model"], 8192) / 1e12 == pytest.approx(29.93, abs=0.01)


def test_the_mixs_least_bytes_by_hand():
    """Four conv layers, N = 3 x 8,192 tokens of width 2,048 in bf16:
    forward [N, 3D] in and [N, D] out, backward [N, 3D] and the
    cotangent in and [N, 3D] and the taps' float32 gradient out."""
    model = harness.load_json(CONFIG)["model"]
    n, d = 3 * 8192, 2048
    cost = kernels_conv.conv_mix_cost(model, 8192, 3)
    assert cost["bytes"] == 4 * ((4 * n * d + 7 * n * d) * 2 + 3 * d * 4)
    assert cost["flops"] == 4 * 3 * 2 * 3 * n * d
    least = kernels.roofline_seconds(
        cost, {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0})
    assert least["bound"] == "memory"
    assert least["seconds"] * 1e3 == pytest.approx(5.41, abs=0.01)


def test_the_core_and_the_grouped_products_count_their_own_layers():
    """The attention core's least work over the ONE attention layer (at
    head size 64, which the file does not state), the grouped products'
    over the FOUR routed blocks: what ``kernels_lm`` counts over all five
    layers, cut to them."""
    from benchmark.metrics import (conv_attn_core_roofline_pct,
                                   conv_moe_gmm_roofline_pct)

    model = harness.load_json(CONFIG)["model"]
    assert "head_dim_override" not in model
    t = 8192
    core = kernels_conv.attention_core_cost(model, t, 3)
    assert core["flops"] == 3 * 6 * 2.0 * (t * (t + 1) // 2) * 32 * 64
    assert core["bytes"] == 3 * 6 * t * (32 + 8) * 64 * 2
    pairs = 8 * 1536.0
    every = kernels_lm.moe_gmm_cost(model, pairs)
    routed = kernels_conv.moe_gmm_cost(model, pairs)
    for key in ("flops", "bytes"):
        assert routed[key] == pytest.approx(every[key] * 4 / 5, rel=1e-12)
    for mod in (conv_attn_core_roofline_pct, conv_moe_gmm_roofline_pct):
        assert mod.read({}) is None


def test_finer_table_equals_the_programs_new_rows():
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    theirs = dict((n, p.pattern) for n, p in device_trace.TOKEN_LAYERS)
    for name, pat in scopes_conv.ROWS:
        assert theirs[name] == pat.pattern
    block = "jit(train_step)/jvp(ViT)/backbone/encoder_block_2/msa"
    for path, row in [(f"{block}/conv/in_proj/dot_general", "conv_proj"),
                      (f"{block}/conv/out_proj/dot_general", "conv_proj"),
                      (f"{block}/conv/mix/mul", "conv_mix"),
                      (f"{block}/norm/mul", None), ("", None)]:
        assert scopes_conv.row_of(path) == row
        assert row is None or device_trace.classify(path)[0] == row


def test_fine_rows_by_hand_and_the_metrics_that_read_them():
    scope = "jit(train_step)/jvp(ViT)/backbone/encoder_block_0/msa/conv/"
    steps, ops = [], []
    for i in range(5):
        t0 = i * 1000
        steps.append({"name": "jit_train_step(1)", "start_ns": t0,
                      "dur_ns": 900})
        ops += [
            {"name": f"fusion.{i}", "op": "fusion", "start_ns": t0 + 10,
             "dur_ns": 100 + i, "scope": scope + "mix/mul"},
            {"name": "fusion.9", "op": "fusion", "start_ns": t0 + 210,
             "dur_ns": 50, "scope": scope + "in_proj/dot_general"},
            {"name": "fusion.7", "op": "fusion", "start_ns": t0 + 600,
             "dur_ns": 70, "scope": "jit(train_step)/optimizer/add"}]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": steps},
        {"name": "XLA Ops", "events": ops}]}]}
    rows = scopes_conv.fine_rows_ms(trace, "jit_train_step")
    assert rows == {"conv_mix": 102e-6, "conv_proj": 50e-6}
    assert scopes_conv.fine_rows_ms({"planes": []}, "jit_train_step") == {}
    from benchmark.metrics import (conv_mix_ms, conv_mix_roofline_pct,
                                   conv_mixer_ms, conv_step_mfu_pct)
    model = harness.load_json(CONFIG)["model"]
    peak = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    obs = {"conv": {"fine_rows_ms": {"conv_mix": 10.82, "conv_proj": 60.0},
                    "seq_len": 8192},
           "model": model, "train": {"batch_per_chip": 3}, "peak": peak,
           "trace": {"step_ms": 300.0}}
    assert conv_mix_ms.read(obs) == 10.82
    assert conv_mixer_ms.read(obs) == 70.82
    assert conv_mix_roofline_pct.read(obs) == pytest.approx(50.0, abs=0.1)
    assert conv_step_mfu_pct.read(obs) == pytest.approx(
        100 * 29.93e12 / 0.3 / 197e12, rel=1e-3)
    # a program without the scopes: left out, not raised
    for mod in (conv_mix_ms, conv_mixer_ms, conv_mix_roofline_pct,
                conv_step_mfu_pct):
        assert mod.read({}) is None
        assert mod.read({"conv": {"fine_rows_ms": {}}}) is None


def test_cell_file_names_what_the_driver_reads():
    cell, config = harness.load_cell(CELL)
    assert cell["driver"] == "train_conv" and cell["chips"] == 1
    p = cell["train_conv"]
    assert set(p) >= {"batch_per_chip", "seq_len", "recipe", "rng_impl",
                      "pool_batches", "successors", "remat",
                      "expect_kernels", "work_seeds"}
    assert p["batch_per_chip"] == 3 and p["seq_len"] == 8192 \
        == config["model"]["max_seq_len"]
    # four DISTINCT draws of one load
    assert p["remat"] is False and len(set(p["work_seeds"])) == 4
    assert p["expect_kernels"] == {
        "flash_fwd": 1, "flash_bwd*": [1, 2], "moe_gmm_fwd": 24,
        "moe_gmm_dx": 16, "moe_gmm_dw": 16}
    assert {"remat", "expect_kernels", "work_seeds", "limits"} \
        <= set(cell["notes"])
    assert len(cell["why"]) <= 200
    for seed in (0, 7, 2**31 + 7):
        work, order = train_conv.work_of(p, seed)
        assert work == p["work_seeds"][seed % 4]
        assert sorted(order) == list(range(p["pool_batches"]))


def test_limits_lie_between_their_two_readings():
    """``notes.limits`` of the cell's file gives, for each limit, the
    program's worst reading over its runs and the fp8 control's best
    over the probe's seeds: the limit lies between them with room on
    both sides."""
    limits = harness.load_cell(CELL)[0]["notes"]["limits"]
    assert 2 * limits["logits_rms_program_max"] \
        < train_conv.LOGITS_RMS_TOLERANCE \
        < limits["logits_rms_fp8_control_min"] / 2
    assert 2 * limits["conv_rms_program_max"] \
        < train_conv.CONV_RMS_TOLERANCE \
        < limits["conv_rms_fp8_conv_min"] / 2
    assert 2 * limits["conv_start_rms_program_max"] \
        < train_conv.CONV_RMS_TOLERANCE \
        < limits["conv_start_rms_fp8_conv_min"] / 2
    # the step: the gradient tells the step over part of its batch, the
    # parameters' change fp8 everywhere, the loss keeps the accepted value
    assert 2 * limits["step_grad_rms_program_max"] \
        < train_conv.GRAD_RMS_TOLERANCE \
        < limits["step_grad_rms_partial_batch_min"] / 2
    assert limits["step_grad_rms_partial_batch_min"] \
        < limits["step_grad_rms_fp8_conv_min"]
    assert 2 * limits["step_update_rms_program_max"] \
        < train_conv.UPDATE_RMS_TOLERANCE \
        < limits["step_update_rms_fp8_control_min"] / 2
    assert 3 * limits["step_loss_rel_program_max"] \
        < train_conv.LOSS_TOLERANCE \
        < limits["step_loss_rel_fp8_control_min"]


def test_benchmark_json_lists_the_cell_and_its_metrics():
    b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert b["workloads"][-1] == {
        "name": CELL, "config": "lfm2-24b-a2b-ep8",
        "traffic": "packed_8k_bs3", "chips": 1,
        "why": harness.load_cell(CELL)[0]["why"]}
    assert b["configs"][-1]["reduced"] == harness.load_json(CONFIG)[
        "reduced"]
    mine = {m["name"]: m for m in b["per_layer"]
            if m.get("workloads") == [CELL]}
    assert list(mine) == ["conv_mixer_ms", "conv_mix_ms",
                          "conv_mix_roofline_pct", "conv_step_mfu_pct",
                          "conv_attn_core_roofline_pct",
                          "conv_moe_gmm_roofline_pct"]
    assert [m["name"] for m in b["per_layer"]][-6:] == list(mine)
    for name, m in mine.items():
        mod = __import__(f"benchmark.metrics.{name}", fromlist=["x"])
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER,
                                mod.MOVES)
    listed = {m["name"] for m in b["per_layer"] + b["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert {"train_img_s", "attn_core_ms", "other_ms", "msa_glue_ms",
            "mlp_glue_ms", "moe_load_max_over_mean", "step_hbm_gib",
            "setup_first_step_s"} <= listed
    # their costs count every layer as routed, or as attention: this
    # cell's own files count the four routed blocks and the one attention
    # layer
    assert not {"moe_gmm_roofline_pct", "lm_attn_core_roofline_pct"} \
        & listed


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    """``run.py --rehearsal`` walks the driver at the tiny sizes on the
    CPU (64 tokens): the result line says correct, and names every
    metric of the kind that finds something to read."""
    done = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", str(trace),
         "--rehearsal"], capture_output=True, text=True, timeout=900,
        cwd=harness.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert {"logits_rms_err", "conv_mixer_rms_err", "step_loss_rel_err",
            "step_grad_rms_err", "step_update_rms_err"} \
        <= set(result["compared"])
    want = {"train_img_s", "setup_s"} if not trace else {
        "moe_load_max_over_mean", "step_hbm_gib"}
    assert want <= set(result["metrics"])


@pytest.mark.parametrize("control", sorted(train_conv.CONTROLS))
def test_each_control_fails_the_cells_own_check(control):
    """``TRAIN_CONV_CONTROL`` puts a faulty reference in the program's
    place (fp8 inputs everywhere, fp8 in the conv mixers' products, the
    step over two of the three sequences): the rehearsal of the cell
    reads ``correct`` false."""
    import os

    done = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", "0",
         "--rehearsal"], capture_output=True, text=True, timeout=900,
        cwd=harness.ROOT, env={**os.environ, "TRAIN_CONV_CONTROL": control})
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
