"""What ``test_lm_files.py`` and ``test_mla_files.py`` hold for their
configurations' files, held for the sparse-attention configuration's
(``keye-vl-2.0-30b-a3b-ep8``, cell ``keye2_train_16k``): the file builds
the program's preset and keeps every published number, the copied FLOP
count, the copied costs of the sparse core and of the indexer and the
copied finer table of scopes equal the program's, each limit lies
between its two readings, the cell's file names what the driver reads,
and both rehearsals of the cell pass. (The copies are also held in
tier-1 by ``tests/test_copies.py``.)"""

import json
import subprocess
import sys

import numpy as np
import pytest

from benchmark.drivers import train_dsa
from benchmark.lib import flops_dsa, harness, kernels, kernels_dsa, \
    scopes_dsa

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CONFIG = harness.BENCH / "configs" / "keye-vl-2.0-30b-a3b-ep8.json"
CELL = "keye2_train_16k"


def test_config_file_builds_the_programs_preset():
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    config = harness.load_json(CONFIG)
    cfg, _ = harness.build_model(config)
    assert cfg == LM_PRESETS[config["program_preset"]]()
    tiny = harness.load_cell(CELL, rehearsal=True)[1]
    assert harness.build_model(tiny)[0] == LM_PRESETS["dsa-tiny"]()
    assert len(config["source"]) <= 200
    assert {"published", "assumed", "deployment", "reduced",
            "reduced_in_model", "memory_analysis"} <= set(config)
    assert "8 chips share each layer" in config["deployment"]
    assert {"qk_norm", "rotary", "indexer", "chunks", "selection",
            "alignment_loss", "router", "expert", "aux_loss", "initialiser",
            "packing", "dtype", "tower"} <= set(config["assumed"])
    assert config["param_dtype"] == "float32"


def test_every_published_number_is_in_the_file_unless_reduced():
    """The contract's rule for a model of the catalog: the file holds
    every number of the catalog entry's ``config`` under the same key,
    nested groups whole, and only the keys in ``reduced`` differ; no
    width is among them."""
    config = harness.load_json(CONFIG)
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("no catalog here")
    row = next(r for r in rows if r["source_url"] == config["source"])
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"}
    m, src = config["model"], row["config"]
    sa = src["sa_config"]
    assert config["sa_config"] == sa and config["rope_scaling"] \
        == src["rope_scaling"]
    assert (m["embedding_dim"], m["num_heads"], m["num_kv_heads"],
            m["head_dim_override"], m["expert_width"],
            m["experts_per_token"], m["rope_theta"], m["ln_epsilon"],
            m["attn_bias"], m["expert_activation"]) == (
        src["hidden_size"], src["num_attention_heads"],
        src["num_key_value_heads"], src["head_dim"],
        src["moe_intermediate_size"], src["num_experts_per_tok"],
        src["rope_theta"], src["rms_norm_eps"], src["attention_bias"],
        src["hidden_act"])
    assert (m["sa_topk"], m["sa_index_heads"], m["sa_index_head_dim"],
            m["sa_chunk"]) == (sa["topk"], sa["indexer_num_heads"],
                               sa["indexer_head_dim"], sa["q_chunk_size"])
    assert sa["indexer_num_kv_heads"] == 1 and sa["kv_chunk_size"] == 512
    assert sum(src["rope_scaling"]["mrope_section"]) * 2 == src["head_dim"]
    assert src["norm_topk_prob"] and m["router_scoring"] == "softmax"
    assert src["decoder_sparse_step"] == 1 and src["mlp_only_layers"] == []
    assert m["num_experts"] == config["published"]["num_experts"] \
        == src["num_experts"]
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) == (
        config["num_hidden_layers"], config["num_experts"],
        config["vocab_size"])
    assert config["num_local_experts"] == config["num_experts"]
    assert config["published"]["vocab_size"] == src["vocab_size"]
    assert config["published"]["num_hidden_layers"] \
        == src["num_hidden_layers"]
    # the floors of a model_config cut
    assert m["experts_held"] >= 8 and m["num_layers"] >= 4
    assert m["vocab_size"] * 8 >= src["vocab_size"]


def test_flop_count_equals_the_programs():
    from pytorch_vit_paper_replication_tpu.telemetry import flops as theirs

    config = harness.load_json(CONFIG)
    cfg, _ = harness.build_model(config)
    for t in (16384, 8192, 1000):
        assert flops_dsa.train_step_flops_per_sequence(config["model"], t) \
            == theirs.train_step_flops_per_sequence(cfg, t)
    assert flops_dsa.train_step_flops_per_sequence(
        config["model"], 16384) / 1e12 == pytest.approx(32.47, abs=0.01)


def test_costs_by_hand():
    """The sparse core: six GEMMs of 2 x selected pairs x 32 x 128 a
    layer over six layers; q, o and their cotangents at 32 heads, k, v
    and theirs at 4. The indexer: 2 x 16 x 64 a causal pair, its q, k
    and head weights read once."""
    model = harness.load_json(CONFIG)["model"]
    t = 16384
    selected, causal = 31_458_304, 134_225_920
    assert (flops_dsa.selected_pairs(t, 2048), flops_dsa.causal_pairs(t)) \
        == (selected, causal)
    peak = {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}
    core = kernels_dsa.sparse_core_cost(model, t, 1)
    assert core["flops"] == 6 * 6 * 2 * selected * 32 * 128
    assert core["bytes"] == 6 * 6 * t * (32 + 4) * 128 * 2
    least = kernels.roofline_seconds(core, peak)
    assert least["bound"] == "compute"
    assert least["seconds"] * 1e3 == pytest.approx(47.09, abs=0.01)
    indexer = kernels_dsa.indexer_cost(model, t, 1)
    assert indexer["flops"] == 6 * 2 * causal * 16 * 64
    assert indexer["bytes"] == 6 * t * ((16 * 64 + 64) * 2 + 16 * 4)
    least = kernels.roofline_seconds(indexer, peak)
    assert least["bound"] == "compute"
    assert least["seconds"] * 1e3 == pytest.approx(8.37, abs=0.01)
    # all six layers are routed: the accepted routed-layer cost counts
    # num_layers blocks, as many as run
    assert model["num_layers"] == 6 and "dense_layers" not in model


def test_finer_table_equals_the_programs_new_rows():
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    theirs = dict((n, p.pattern) for n, p in device_trace.TOKEN_LAYERS)
    for name, pat in scopes_dsa.ROWS:
        assert theirs[name] == pat.pattern
    block = "jit(train_step)/jvp(ViT)/backbone/encoder_block_2/checkpoint/msa"
    for path, row in [
            (f"{block}/indexer/proj/index_q/dot_general", "indexer/proj"),
            (f"{block}/while/body/indexer/scores/dot_general",
             "indexer/scores"),
            (f"{block}/indexer_loss/while/body/indexer/scores/dot_general",
             "indexer/scores"),
            (f"{block}/while/body/indexer/select/while/body/reduce_sum",
             "indexer/select"),
            (f"{block}/indexer_loss/while/body/exp", "indexer_loss"),
            (f"{block}/attn_core/flash_fwd/pallas_call", None),
            ("", None)]:
        assert scopes_dsa.row_of(path) == row
        assert row is None or device_trace.classify(path)[0] == row


def test_fine_rows_by_hand_and_the_metrics_that_read_them():
    scope = "jit(train_step)/jvp(ViT)/backbone/encoder_block_1/checkpoint/" \
        "msa/while/body/indexer/select/while/body/reduce_sum"
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
             "dur_ns": 50, "scope": scope.replace(
                 "indexer/select", "indexer/scores")},
            {"name": "fusion.7", "op": "fusion", "start_ns": t0 + 600,
             "dur_ns": 70, "scope": "jit(train_step)/optimizer/add"}]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": steps},
        {"name": "XLA Ops", "events": ops}]}]}
    rows = scopes_dsa.fine_rows_ms(trace, "jit_train_step")
    assert rows == {"indexer/scores": 50e-6, "indexer/select": 102e-6}
    assert scopes_dsa.fine_rows_ms({"planes": []}, "jit_train_step") == {}
    from benchmark.metrics import (dsa_indexer_ms, dsa_indexer_roofline_pct,
                                   dsa_select_ms, dsa_selected_share)
    model = harness.load_json(CONFIG)["model"]
    obs = {"dsa": {"fine_rows_ms": rows, "seq_len": 16384,
                   "selected_pairs": 31458304.0,
                   "causal_pairs": 134225920.0},
           "model": model, "train": {"batch_per_chip": 1},
           "peak": {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0}}
    assert dsa_select_ms.read(obs) == 102e-6
    assert dsa_indexer_ms.read(obs) == 50e-6
    assert dsa_selected_share.read(obs) == pytest.approx(0.23437, abs=1e-5)
    assert dsa_indexer_roofline_pct.read(obs) > 0
    # a program without the scopes or the counters: left out, not raised
    for mod in (dsa_indexer_ms, dsa_select_ms, dsa_indexer_roofline_pct,
                dsa_selected_share):
        assert mod.read({}) is None
        assert mod.read({"dsa": {"fine_rows_ms": {}}}) is None


def test_cell_file_names_what_the_driver_reads():
    cell, config = harness.load_cell(CELL)
    assert cell["driver"] == "train_dsa" and cell["chips"] == 1
    p = cell["train_dsa"]
    assert set(p) >= {"batch_per_chip", "seq_len", "recipe", "rng_impl",
                      "pool_batches", "successors", "remat",
                      "expect_kernels", "work_seeds"}
    assert p["batch_per_chip"] == 1 and p["seq_len"] == 16384 \
        == config["model"]["max_seq_len"]
    # four DISTINCT draws of one load, as the issue asks
    assert p["remat"] is False and len(set(p["work_seeds"])) == 4
    assert p["expect_kernels"] == {
        "flash_fwd": 6, "flash_bwd*": [6, 12], "moe_gmm_fwd": 18,
        "moe_gmm_dx": 12, "moe_gmm_dw": 12}
    assert {"remat", "expect_kernels", "work_seeds", "limits"} \
        <= set(cell["notes"])
    assert len(cell["why"]) <= 200
    for seed in (0, 7, 2**31 + 7):
        work, order = train_dsa.work_of(p, seed)
        assert work == p["work_seeds"][seed % 4]
        assert sorted(order) == list(range(p["pool_batches"]))


def test_limits_lie_between_their_two_readings():
    """``notes.limits`` of the cell's file gives, for each limit, the
    program's worst reading over its runs and the fp8 control's best
    over the probe's seeds: the limit lies between them with room on
    both sides."""
    limits = harness.load_cell(CELL)[0]["notes"]["limits"]
    assert 2 * limits["logits_rms_program_max"] \
        < train_dsa.LOGITS_RMS_TOLERANCE \
        < limits["logits_rms_fp8_control_min"] / 2
    assert limits["selection_agreement_fp8_control_max"] \
        < train_dsa.SELECTION_AGREEMENT_MIN \
        < limits["selection_agreement_program_min"]
    assert 3 * limits["loss_rel_program_max"] < train_dsa.LOSS_TOLERANCE
    assert 2 * limits["indexer_loss_rel_program_max"] \
        < train_dsa.INDEXER_LOSS_TOLERANCE
    # the timed step's own number: the fall of the alignment loss a round
    # of the pool, between the program's and a step without the update
    assert limits["indexer_fall_program_max"] \
        < train_dsa.INDEXER_FALL_MAX \
        < limits["indexer_fall_frozen_indexer_min"]


def test_benchmark_json_lists_the_cell_and_its_metrics():
    b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert b["workloads"][-1] == {
        "name": CELL, "config": "keye-vl-2.0-30b-a3b-ep8",
        "traffic": "packed_16k_bs1", "chips": 1,
        "why": harness.load_cell(CELL)[0]["why"]}
    assert b["configs"][-1]["reduced"] == [
        "num_hidden_layers", "num_experts", "num_local_experts",
        "vocab_size"]
    mine = {m["name"]: m for m in b["per_layer"]
            if m.get("workloads") == [CELL]}
    assert list(mine) == [
        "dsa_step_mfu_pct", "dsa_attn_core_roofline_pct",
        "dsa_indexer_roofline_pct", "dsa_indexer_ms", "dsa_select_ms",
        "dsa_selected_share"]
    assert [m["name"] for m in b["per_layer"]][-6:] == list(mine)
    for name, m in mine.items():
        mod = __import__(f"benchmark.metrics.{name}", fromlist=["x"])
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER,
                                mod.MOVES)
    listed = {m["name"] for m in b["per_layer"] + b["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert {"train_img_s", "attn_core_ms", "other_ms", "msa_glue_ms",
            "moe_load_max_over_mean", "moe_gmm_roofline_pct"} <= listed
    # their cost functions read other models' shapes
    assert not {"lm_step_mfu_pct", "lm_attn_core_roofline_pct",
                "mla_step_mfu_pct", "mtp_ms"} & listed


def test_pool_is_the_accepted_token_cells():
    pool = train_dsa.make_pool(2**31 + 7, 2, 1, 64, 256, 4)
    assert (pool[0]["tokens"][:, 1:] == pool[0]["label"][:, :-1]).all()
    assert np.asarray(pool[0]["tokens"]).max() < 256


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    """``run.py --rehearsal`` walks the driver at the tiny sizes on the
    CPU: the result line says correct, and names every metric of the
    kind that finds something to read."""
    done = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", str(trace),
         "--rehearsal"], capture_output=True, text=True, timeout=900,
        cwd=harness.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert {"logits_rms_err", "loss_rel_err", "indexer_loss_rel_err",
            "dsa_selection_agreement"} <= set(result["compared"])
    want = {"train_img_s", "setup_s"} if not trace else {
        "dsa_selected_share", "moe_load_max_over_mean", "step_hbm_gib"}
    assert want <= set(result["metrics"])
