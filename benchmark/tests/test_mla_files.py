"""What ``test_lm_files.py`` holds for SmallThinker's files, held for the
latent-attention configuration's (``glm-4.7-flash-ep8``, cell
``glm47f_train_16k``): the file builds the program's preset and keeps
every published number, the copied FLOP count, the copied cost of the
attention core and the copied finer table of scopes equal the program's,
the logits' limit lies between its two readings (the losses' has no
upper one), and the cell's file names
what the driver reads. (The rehearsal of the cell walks the driver in
``test_rehearsal.py``, which takes every cell file it finds.)"""

import json

import numpy as np
import pytest

from benchmark.drivers import train_mla
from benchmark.lib import flops_mla, harness, kernels, kernels_mla, \
    scopes_mla

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = harness.BENCH / "configs" / "glm-4.7-flash-ep8.json"
CELL = "glm47f_train_16k"


def test_config_file_builds_the_programs_preset():
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    config = harness.load_json(CONFIG)
    cfg, _ = harness.build_model(config)
    assert cfg == LM_PRESETS[config["program_preset"]]()
    tiny = harness.load_cell(CELL, rehearsal=True)[1]
    assert harness.build_model(tiny)[0] == LM_PRESETS["mla-tiny"]()
    assert len(config["source"]) <= 200
    assert {"published", "assumed", "deployment", "reduced",
            "reduced_in_model"} <= set(config)
    assert "8 chips share each layer" in config["deployment"]
    assert {"rotary", "mtp_concat_order", "mtp_hidden", "mtp_loss_weight",
            "biases", "correction_bias", "aux_loss", "initialiser",
            "packing", "dtype"} <= set(config["assumed"])
    assert config["param_dtype"] == "float32"


def test_every_published_number_is_in_the_file_unless_reduced():
    """The contract's rule for a model of the catalog: the file holds
    every number of the catalog entry's ``config`` under the same key,
    and only the keys in ``reduced`` differ; no width is among them."""
    config = harness.load_json(CONFIG)
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    row = next(r for r in rows if r["source_url"] == config["source"])
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    m, src = config["model"], row["config"]
    assert (m["embedding_dim"], m["num_heads"], m["q_lora_rank"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"], m["dense_width"], m["expert_width"],
            m["experts_per_token"], m["shared_experts"], m["router_scale"],
            m["rope_theta"], m["ln_epsilon"], m["dense_layers"],
            m["mtp_modules"], m["attn_bias"]) == (
        src["hidden_size"], src["num_attention_heads"], src["q_lora_rank"],
        src["kv_lora_rank"], src["qk_nope_head_dim"],
        src["qk_rope_head_dim"], src["v_head_dim"], src["intermediate_size"],
        src["moe_intermediate_size"], src["num_experts_per_tok"],
        src["n_shared_experts"], src["routed_scaling_factor"],
        src["rope_theta"], src["rms_norm_eps"], src["first_k_dense_replace"],
        src["num_nextn_predict_layers"], src["attention_bias"])
    assert src["num_key_value_heads"] == src["num_attention_heads"]
    assert "head_dim_override" not in m     # the two parts' sum
    assert (m["num_experts"], m["router_scoring"], m["expert_activation"]
            ) == (config["published"]["n_routed_experts"], "sigmoid",
                  src["hidden_act"])
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) == (
        config["num_hidden_layers"], config["n_routed_experts"],
        config["vocab_size"])
    assert config["published"]["vocab_size"] == src["vocab_size"]
    assert config["published"]["num_hidden_layers"] \
        == src["num_hidden_layers"]
    # the floors of a model_config cut
    assert m["experts_held"] >= 8
    assert m["num_layers"] - m["dense_layers"] >= 4
    assert m["vocab_size"] * 8 >= src["vocab_size"]


def test_flop_count_equals_the_programs():
    from pytorch_vit_paper_replication_tpu.telemetry import flops as theirs

    config = harness.load_json(CONFIG)
    cfg, _ = harness.build_model(config)
    for t in (16384, 8192, 1000):
        assert flops_mla.train_step_flops_per_sequence(config["model"], t) \
            == theirs.train_step_flops_per_sequence(cfg, t)
    tiny = harness.load_cell(CELL, rehearsal=True)[1]
    assert flops_mla.train_step_flops_per_sequence(tiny["model"], 64) \
        == theirs.train_step_flops_per_sequence(
            harness.build_model(tiny)[0], 64)
    per_token = flops_mla.forward_flops_per_sequence(
        config["model"], 16384) / 16384
    assert per_token / 1e6 == pytest.approx(1711.9, abs=0.1)
    assert flops_mla.blocks(config["model"]) == 6


def test_attention_core_cost_by_hand():
    """Six GEMMs of 2 x pairs x 20 x 256 a block over six blocks; q, o
    and v and their cotangents at 20 x 256 columns, k and dk at 20 x 192
    + the ONE shared head's 64."""
    model = harness.load_json(CONFIG)["model"]
    t = 16384
    cost = kernels_mla.attention_core_cost(model, t, 1)
    pairs = t * (t + 1) // 2
    assert cost["flops"] == 6 * 6 * 2 * pairs * 20 * 256
    assert cost["flops"] / 3 / t / 1e6 == pytest.approx(1006.7, abs=0.1)
    wide, key = 20 * 256, 20 * 192 + 64
    assert cost["bytes"] == 6 * t * 2 * (9 * wide + 3 * key)
    least = kernels.roofline_seconds(cost, {"bf16_tflops": 197.0,
                                            "hbm_gb_per_s": 819.0})
    assert least["bound"] == "compute"
    assert least["seconds"] * 1e3 == pytest.approx(251.2, abs=0.1)
    # the accepted routed-layer cost counts num_layers blocks: five, as
    # many as the routed blocks that run (4 layers + the module's), so
    # the cell can be listed under moe_gmm_roofline_pct
    assert model["num_layers"] == flops_mla.blocks(model) \
        - model["dense_layers"] == 5


def test_finer_table_equals_the_programs_new_rows():
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    theirs = [(n, p.pattern) for n, p in device_trace.TOKEN_LAYERS]
    mine = [(n, p.pattern) for n, p in scopes_mla.ROWS]
    assert mine == theirs[:len(mine)]
    for path, row in [
            ("jit(train_step)/jvp(ViT)/mtp/head/loss/exp", "mtp_head"),
            ("jit(train_step)/transpose(jvp(ViT))/backbone/mtp/"
             "encoder_block_5/mlp/moe_shared/shared/up/dot_general",
             "mtp_block"),
            ("jit(train_step)/jvp(ViT)/backbone/encoder_block_2/mlp/"
             "moe_shared/shared/up/dot_general", "moe_shared"),
            ("jit(train_step)/jvp(ViT)/backbone/encoder_block_2/mlp/"
             "moe_router/router/dot_general", None),
            ("", None)]:
        assert scopes_mla.row_of(path) == row
        assert row is None or device_trace.classify(path)[0] == row


def test_fine_rows_by_hand():
    """Two chips' worth of one: five steps, the first and the last left
    out; a loop's body counted once; the median over the steps."""
    scope = "jit(train_step)/jvp(ViT)/backbone/encoder_block_1/mlp/" \
        "moe_shared/shared/up/dot_general"
    steps, ops = [], []
    for i in range(5):
        t0 = i * 1000
        steps.append({"name": "jit_train_step(1)", "start_ns": t0,
                      "dur_ns": 900})
        ops += [
            {"name": f"fusion.{i}", "op": "fusion", "start_ns": t0 + 10,
             "dur_ns": 100 + i, "scope": scope},
            {"name": "while.1", "op": "while", "start_ns": t0 + 200,
             "dur_ns": 300, "scope": scope},
            {"name": "fusion.9", "op": "fusion", "start_ns": t0 + 210,
             "dur_ns": 50, "scope": "jit(train_step)/jvp(ViT)/mtp/head/"
             "head/dot_general"},
            {"name": "fusion.7", "op": "fusion", "start_ns": t0 + 600,
             "dur_ns": 70, "scope": "jit(train_step)/optimizer/add"}]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": steps},
        {"name": "XLA Ops", "events": ops}]}]}
    rows = scopes_mla.fine_rows_ms(trace, "jit_train_step")
    assert rows == {"moe_shared": 102e-6, "mtp_head": 50e-6}
    assert scopes_mla.fine_rows_ms({"planes": []}, "jit_train_step") == {}
    assert scopes_mla.fine_rows_ms(trace, "jit_other") == {}
    # the metrics read the driver's table, and leave themselves out
    # where the program has no such scope
    from benchmark.metrics import moe_shared_ms, mtp_ms
    obs = {"mla": {"fine_rows_ms": rows}}
    assert mtp_ms.read(obs) == 50e-6 and moe_shared_ms.read(obs) == 102e-6
    assert mtp_ms.read({"mla": {"fine_rows_ms": {}}}) is None
    assert moe_shared_ms.read({"mla": {"fine_rows_ms": {}}}) is None


def test_cell_file_names_what_the_driver_reads():
    cell, config = harness.load_cell(CELL)
    assert cell["driver"] == "train_mla" and cell["chips"] == 1
    p = cell["train_mla"]
    assert set(p) >= {"batch_per_chip", "seq_len", "recipe", "rng_impl",
                      "pool_batches", "successors", "remat",
                      "expect_kernels", "work_seeds"}
    assert p["batch_per_chip"] == 1 and p["seq_len"] == 16384 \
        == config["model"]["max_seq_len"]
    assert p["remat"] is False and len(p["work_seeds"]) == 4
    # the flash backward as a family of one or two calls a block; the
    # grouped products of the five routed blocks by their two chunks of
    # tokens (2 x (2 + 1) forward calls, 2 x 2 and 2 x 2 backward)
    assert p["expect_kernels"] == {
        "flash_fwd": 6, "flash_bwd*": [6, 12], "moe_gmm_fwd": 30,
        "moe_gmm_dx": 20, "moe_gmm_dw": 20}
    assert {"remat", "expect_kernels", "work_seeds", "limits"} \
        <= set(cell["notes"])
    # every seed takes one of the four draws and orders its batches
    for seed in (0, 7, 2**31 + 7):
        work, order = train_mla.work_of(p, seed)
        assert work == p["work_seeds"][seed % 4]
        assert sorted(order) == list(range(p["pool_batches"]))


def test_limits_lie_between_their_two_readings():
    """``notes.limits`` of the cell's file gives, for the logits' limit,
    the largest reading of the program over its runs and the smallest of
    the fp8 control over the probe's seeds: the limit lies between them
    with twice of room on both sides."""
    limits = harness.load_cell(CELL)[0]["notes"]["limits"]
    program, control = limits["logits_rms_program_max"], \
        limits["logits_rms_fp8_control_min"]
    assert 2 * program < train_mla.LOGITS_RMS_TOLERANCE < control / 2


@pytest.mark.parametrize("name", ["loss_rel", "mtp_loss_rel"])
def test_the_losses_limit_has_no_upper_reading(name):
    """Why ``LOSS_TOLERANCE`` is not between two readings: the fp8
    control's smallest loss error over its seeds is not even twice the
    program's largest (the error crosses zero from seed to seed). The
    limit is the accepted token cell's, with three times of room over
    the program's largest; a later reading that opens a gap fails here
    and asks for a limit inside it."""
    from benchmark.drivers import train_lm

    limits = harness.load_cell(CELL)[0]["notes"]["limits"]
    program, control = limits[f"{name}_program_max"], \
        limits[f"{name}_fp8_control_min"]
    assert control < 2 * program
    assert 3 * program < train_mla.LOSS_TOLERANCE == train_lm.LOSS_TOLERANCE


def test_benchmark_json_lists_the_cell_and_its_metrics():
    b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert b["workloads"][-1] == {
        "name": CELL, "config": "glm-4.7-flash-ep8",
        "traffic": "packed_16k_bs1", "chips": 1,
        "why": harness.load_cell(CELL)[0]["why"]}
    assert b["configs"][-1]["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    mine = {m["name"]: m for m in b["per_layer"]
            if m.get("workloads") == [CELL]}
    assert set(mine) == {"mla_step_mfu_pct", "mla_attn_core_roofline_pct",
                         "mtp_ms", "moe_shared_ms"}
    assert [m["name"] for m in b["per_layer"]][-4:] == list(mine)
    listed = {m["name"] for m in b["per_layer"] + b["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert {"train_img_s", "attn_core_ms", "other_ms",
            "moe_load_max_over_mean", "moe_gmm_roofline_pct"} <= listed
    # their cost functions read SmallThinker's shape
    assert not {"lm_step_mfu_pct", "lm_attn_core_roofline_pct"} & listed


def test_pool_is_the_accepted_token_cells():
    pool = train_mla.make_pool(2**31 + 7, 2, 1, 64, 256, 4)
    assert (pool[0]["tokens"][:, 1:] == pool[0]["label"][:, :-1]).all()
    assert np.asarray(pool[0]["tokens"]).max() < 256
