"""What ``test_conv_files.py`` holds for its configuration's files, held
for the configuration whose layers mix Mamba-2 state-space layers with
attention (``granite-4.0-h-micro-pp4``, cell ``granite4h_train_16k``):
the file builds the program's preset and keeps every number of the
source's configuration unless it is reduced, the scan's least bytes by
hand, the finer rows and the metrics that read them, each limit lies
between its two readings, the cell's file names what the driver reads,
and the rehearsals of the cell and of its controls pass at 64 tokens.
(The copies are held in tier-1 by ``tests/test_copies.py``.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.drivers import train_ssm
from benchmark.lib import harness, kernels_ssm, scopes_ssm

# The source's ``config.json`` (huggingface.co/ibm-granite/
# granite-4.0-h-micro), every key that gives the model's shape: what the
# configuration file has to hold unless a key is reduced.
SOURCE = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352}
CONFIG = harness.BENCH / "configs" / "granite-4.0-h-micro-pp4.json"
CELL = "granite4h_train_16k"
METRICS = ["ssm_mixer_ms", "ssm_scan_ms", "ssm_scan_roofline_pct",
           "ssm_step_mfu_pct", "ssm_state_carry"]


def test_every_published_number_is_in_the_file_unless_reduced():
    """The file holds every number of the source's configuration under
    the same key, and only the keys in ``reduced`` differ; no width is
    among them. The model block is the program's preset."""
    from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS

    config = harness.load_json(CONFIG)
    assert harness.build_model(config)[0] == LM_PRESETS[
        config["program_preset"]]()
    differ = {k for k, v in SOURCE.items() if config.get(k) != v}
    assert differ == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    m = config["model"]
    assert (m["embedding_dim"], m["num_heads"], m["num_kv_heads"],
            m["dense_width"], m["ssm_heads"], m["ssm_head_dim"],
            m["ssm_state"], m["ssm_groups"], m["ssm_conv_kernel"],
            m["ssm_chunk"], m["ln_epsilon"], m["embedding_multiplier"],
            m["residual_multiplier"], m["logits_scaling"],
            m["attn_scale"]) == (
        SOURCE["hidden_size"], SOURCE["num_attention_heads"],
        SOURCE["num_key_value_heads"], SOURCE["shared_intermediate_size"],
        SOURCE["mamba_n_heads"], SOURCE["mamba_d_head"],
        SOURCE["mamba_d_state"], SOURCE["mamba_n_groups"],
        SOURCE["mamba_d_conv"], SOURCE["mamba_chunk_size"],
        SOURCE["rms_norm_eps"], SOURCE["embedding_multiplier"],
        SOURCE["residual_multiplier"], SOURCE["logits_scaling"],
        SOURCE["attention_multiplier"])
    assert m["ssm_heads"] * m["ssm_head_dim"] \
        == SOURCE["mamba_expand"] * SOURCE["hidden_size"]
    kept = SOURCE["layer_types"][:10]
    assert config["layer_types"] == kept
    assert [2 if t == "mamba" else 0 for t in kept] == m["mixer_layout"]
    assert not m.get("rope_layout") and m["tie_embedding"]
    assert (m["num_layers"], m["vocab_size"]) == (
        config["num_hidden_layers"], config["vocab_size"])
    # the floors of a model_config cut: a whole period, an eighth of the
    # vocabulary
    assert m["num_layers"] % 10 == 0 and m["vocab_size"] * 8 \
        == SOURCE["vocab_size"]
    assert {"initialiser", "tied_head", "multipliers", "packing", "eps",
            "dtype"} <= set(config["assumed"])
    assert "chip 0 of stage 0" in config["deployment"]


def test_the_scans_least_bytes_by_hand():
    """x, B, C read and y written in bf16 and dt read in float32 forward;
    those read again, y's cotangent read and their cotangents written
    backward: 43,264 bytes a token a layer, nine layers."""
    model = harness.load_json(CONFIG)["model"]
    cost = kernels_ssm.ssm_scan_cost(model, 16384, 1)
    forward = (4096 + 256) * 2 + 64 * 4 + 4096 * 2
    backward = 2 * ((4096 + 256) * 2 + 64 * 4) + 4096 * 2
    assert forward + backward == 43_264
    assert cost["bytes"] == 9 * 16384 * 43_264
    assert cost["bytes"] / 819e9 * 1e3 == pytest.approx(7.789, abs=0.001)
    assert cost["flops"] / 197e12 * 1e3 == pytest.approx(7.147, abs=0.001)


def test_fine_rows_and_the_metrics_that_read_them():
    scope = "jit(train_step)/jvp(ViT)/backbone/encoder_block_0/msa/ssm/"
    steps, ops = [], []
    for i in range(5):
        t0 = i * 1000
        steps.append({"name": "jit_train_step(1)", "start_ns": t0,
                      "dur_ns": 900})
        ops += [
            {"name": f"fusion.{i}", "op": "fusion", "start_ns": t0 + 10,
             "dur_ns": 100 + i, "scope": scope + "scan/dot_general"},
            {"name": "fusion.9", "op": "fusion", "start_ns": t0 + 210,
             "dur_ns": 50, "scope": scope + "out_proj/dot_general"},
            {"name": "fusion.8", "op": "fusion", "start_ns": t0 + 300,
             "dur_ns": 20, "scope": scope + "gate_norm/mul"},
            {"name": "fusion.7", "op": "fusion", "start_ns": t0 + 600,
             "dur_ns": 70, "scope": "jit(train_step)/optimizer/add"}]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": steps},
        {"name": "XLA Ops", "events": ops}]}]}
    rows = scopes_ssm.fine_rows_ms(trace, "jit_train_step")
    assert rows == {"ssm_scan": 102e-6, "ssm_proj": 50e-6,
                    "ssm_norm": 20e-6}
    from benchmark.metrics import (ssm_mixer_ms, ssm_scan_ms,
                                   ssm_scan_roofline_pct, ssm_state_carry,
                                   ssm_step_mfu_pct)
    model = harness.load_json(CONFIG)["model"]
    obs = {"ssm": {"fine_rows_ms": {"ssm_scan": 77.89, "ssm_proj": 100.0,
                                    "ssm_conv": 10.0},
                   "seq_len": 16384, "state_carry": 0.019},
           "model": model, "train": {"batch_per_chip": 1},
           "peak": {"bf16_tflops": 197.0, "hbm_gb_per_s": 819.0},
           "trace": {"step_ms": 1000.0}}
    assert ssm_scan_ms.read(obs) == 77.89
    assert ssm_mixer_ms.read(obs) == pytest.approx(187.89)
    assert ssm_scan_roofline_pct.read(obs) == pytest.approx(10.0, abs=0.01)
    assert ssm_step_mfu_pct.read(obs) == pytest.approx(
        100 * 80.6e12 / 197e12, abs=0.05)
    assert ssm_state_carry.read(obs) == 0.019
    # a program without the scopes or the counter: left out, not raised
    for mod in (ssm_mixer_ms, ssm_scan_ms, ssm_scan_roofline_pct,
                ssm_step_mfu_pct, ssm_state_carry):
        assert mod.read({}) is None


def test_cell_file_names_what_the_driver_reads():
    cell, config = harness.load_cell(CELL)
    assert cell["driver"] == "train_ssm" and cell["chips"] == 1
    p = cell["train_ssm"]
    assert p["batch_per_chip"] == 1 and p["seq_len"] == 16384 \
        == config["model"]["max_seq_len"]
    assert p["remat"] is True and "work_seeds" not in p
    assert p["expect_kernels"] == {"flash_fwd": 1, "flash_bwd*": [1, 2]}
    assert {"remat", "expect_kernels", "work_seeds", "limits"} \
        <= set(cell["notes"])
    assert len(cell["why"]) <= 200


def test_limits_lie_between_their_two_readings():
    """``notes.limits`` gives, for each limit, the program's worst reading
    over its runs and the best of the controls it tells apart: the limit
    lies between them with half as much again on each side."""
    limits = harness.load_cell(CELL)[0]["notes"]["limits"]
    d = train_ssm
    for name, limit in (("logits_rms", d.LOGITS_RMS_TOLERANCE),
                        ("ssm_rms", d.SSM_RMS_TOLERANCE),
                        ("ssm_start_rms", d.SSM_RMS_TOLERANCE),
                        ("step_grad_rms", d.GRAD_RMS_TOLERANCE),
                        ("step_update_rms", d.UPDATE_RMS_TOLERANCE)):
        assert 1.5 * limits[f"{name}_program_max"] < limit \
            < limits[f"{name}_control_min"] / 1.5, name
    assert 3 * limits["step_loss_rel_program_max"] < d.LOSS_TOLERANCE
    assert limits["ssm_start_rms_no_carry_min"] > 4 * d.SSM_RMS_TOLERANCE


def test_benchmark_json_lists_the_cell_and_its_metrics():
    # found by name: a later PR appends after them
    b = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    assert {
        "name": CELL, "config": "granite-4.0-h-micro-pp4",
        "traffic": "packed_16k_bs1", "chips": 1,
        "why": harness.load_cell(CELL)[0]["why"]} in b["workloads"]
    assert [c["reduced"] for c in b["configs"]
            if c["name"] == "granite-4.0-h-micro-pp4"] == [
        harness.load_json(CONFIG)["reduced"]]
    mine = [m for m in b["per_layer"] if m["name"] in METRICS]
    assert [m["name"] for m in mine] == METRICS
    for m in mine:
        mod = __import__(f"benchmark.metrics.{m['name']}", fromlist=["x"])
        assert m["workloads"] == [CELL]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (mod.UNIT, mod.BETTER, mod.SOURCE, mod.LAYER,
                                mod.MOVES)
    listed = {m["name"] for m in b["per_layer"] + b["end_to_end"]
              if CELL in m.get("workloads", ())}
    assert {"train_img_s", "attn_core_ms", "other_ms", "msa_glue_ms",
            "mlp_glue_ms", "step_hbm_gib", "setup_first_step_s"} <= listed
    # their costs count every layer as attention or as routed
    assert not {"moe_gmm_roofline_pct", "lm_attn_core_roofline_pct",
                "moe_load_max_over_mean"} & listed


def _rehearse(trace: int, control=None):
    env = dict(os.environ)
    env.pop("TRAIN_SSM_CONTROL", None)
    if control:
        env["TRAIN_SSM_CONTROL"] = control
    done = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 11), "--seconds", "1", "--trace", str(trace),
         "--rehearsal"], capture_output=True, text=True, timeout=900,
        cwd=harness.ROOT, env=env)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_the_cell_rehearses(trace):
    """``run.py --rehearsal`` walks the driver at the tiny sizes on the
    CPU (64 tokens): the result line says correct, and names every
    metric of the kind that finds something to read."""
    result = _rehearse(trace)
    assert result["correct"] is True and result["failed"] == 0
    assert {"logits_rms_err", "ssm_mixer_rms_err", "ssm_chunk_start_rms_err",
            "step_loss_rel_err", "step_grad_rms_err",
            "step_update_rms_err"} <= set(result["compared"])
    want = {"train_img_s", "setup_s"} if not trace else {
        "ssm_state_carry", "step_hbm_gib"}
    assert want <= set(result["metrics"])


@pytest.mark.parametrize("control", sorted(train_ssm.CONTROLS))
def test_each_control_fails_the_cells_own_check(control):
    """``TRAIN_SSM_CONTROL`` puts a faulty reference in the program's
    place (fp8 inputs everywhere, fp8 in the scan's inputs, no state
    carried between chunks, the loss over half the positions): the
    rehearsal of the cell, in float32 at 64 tokens, reads ``correct``
    false. The chip's readings are in the cell's ``notes.limits``."""
    assert _rehearse(0, control)["correct"] is False
