"""The copies this repo keeps of the program inside the benchmark (a
yardstick that neither side of a comparison can edit) for the token model
whose attention an indexer selects, for the one whose layers mix gated
short convolutions with attention and for the one whose layers mix
Mamba-2 state-space layers with attention, held equal to the program:
the FLOP counts (``benchmark/lib/flops_dsa.py``, ``flops_conv.py``,
``flops_ssm.py``), the finer tables of scopes
(``benchmark/lib/scopes_dsa.py``, ``scopes_conv.py``, ``scopes_ssm.py``)
and the presets against their configuration files. (The older copies are
held by ``benchmark/tests/test_copies.py``, ``test_lm_files.py`` and
``test_mla_files.py``.)"""

import json

import pytest

from benchmark.lib import flops_conv, flops_dsa, flops_ssm, harness, \
    scopes_conv, scopes_dsa, scopes_ssm
from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS
from pytorch_vit_paper_replication_tpu.telemetry import device_trace, flops

CONFIG = harness.BENCH / "configs" / "keye-vl-2.0-30b-a3b-ep8.json"
CELL = "keye2_train_16k"
CONV_CONFIG = harness.BENCH / "configs" / "lfm2-24b-a2b-ep8.json"
CONV_CELL = "lfm2_train_8k"
SSM_CONFIG = harness.BENCH / "configs" / "granite-4.0-h-micro-pp4.json"
SSM_CELL = "granite4h_train_16k"


@pytest.mark.parametrize("tokens", [16384, 8192, 2048, 1000, 64])
def test_flop_count_equals_the_programs(tokens):
    config = harness.load_json(CONFIG)
    cfg, _ = harness.build_model(config)
    assert flops_dsa.train_step_flops_per_sequence(config["model"], tokens) \
        == flops.train_step_flops_per_sequence(cfg, tokens)
    assert flops_dsa.forward_flops_per_sequence(config["model"], tokens) \
        == flops.forward_flops_per_sequence(cfg, tokens)
    assert flops_dsa.selected_pairs(tokens, cfg.sa_topk) \
        == flops.visible_pairs(tokens, cfg.sa_topk)
    assert flops_dsa.causal_pairs(tokens) == flops.visible_pairs(tokens)


def test_flop_count_of_the_rehearsal_model_equals_the_programs():
    tiny = harness.load_cell(CELL, rehearsal=True)[1]
    assert flops_dsa.train_step_flops_per_sequence(tiny["model"], 64) \
        == flops.train_step_flops_per_sequence(
            harness.build_model(tiny)[0], 64)


def test_finer_table_equals_the_programs_new_rows():
    theirs = {n: p.pattern for n, p in device_trace.TOKEN_LAYERS}
    mine = [(n, p.pattern) for n, p in scopes_dsa.ROWS]
    assert [n for n, _ in mine] == [
        n for n, _ in device_trace.TOKEN_LAYERS if "indexer" in n]
    assert all(theirs[n] == pattern for n, pattern in mine)
    assert set(scopes_dsa.INDEXER_ROWS) | {"indexer/select"} \
        == {n for n, _ in mine}


@pytest.mark.parametrize("preset,rehearsal", [
    ("keye-vl-2.0-30b-a3b-ep8", False), ("dsa-tiny", True)])
def test_config_file_is_the_programs_preset(preset, rehearsal):
    config = harness.load_cell(CELL, rehearsal=rehearsal)[1]
    assert harness.build_model(config)[0] == LM_PRESETS[preset]()
    assert config["program_preset"] == "keye-vl-2.0-30b-a3b-ep8"
    assert json.loads(CONFIG.read_text())["name"] == config["name"]


@pytest.mark.parametrize("tokens", [8192, 2048, 1000, 64])
def test_conv_flop_count_equals_the_programs(tokens):
    config = harness.load_json(CONV_CONFIG)
    cfg, _ = harness.build_model(config)
    assert flops_conv.train_step_flops_per_sequence(config["model"], tokens) \
        == flops.train_step_flops_per_sequence(cfg, tokens)
    assert flops_conv.forward_flops_per_sequence(config["model"], tokens) \
        == flops.forward_flops_per_sequence(cfg, tokens)
    tiny = harness.load_cell(CONV_CELL, rehearsal=True)[1]
    assert flops_conv.train_step_flops_per_sequence(tiny["model"], tokens) \
        == flops.train_step_flops_per_sequence(
            harness.build_model(tiny)[0], tokens)


def test_conv_finer_table_equals_the_programs_new_rows():
    theirs = {n: p.pattern for n, p in device_trace.TOKEN_LAYERS}
    mine = [(n, p.pattern) for n, p in scopes_conv.ROWS]
    assert [n for n, _ in mine] == [
        n for n, _ in device_trace.TOKEN_LAYERS if n.startswith("conv_")]
    assert all(theirs[n] == pattern for n, pattern in mine)


@pytest.mark.parametrize("preset,rehearsal", [
    ("lfm2-24b-a2b-ep8", False), ("conv-tiny", True)])
def test_conv_config_file_is_the_programs_preset(preset, rehearsal):
    config = harness.load_cell(CONV_CELL, rehearsal=rehearsal)[1]
    assert harness.build_model(config)[0] == LM_PRESETS[preset]()
    assert config["program_preset"] == "lfm2-24b-a2b-ep8"
    assert json.loads(CONV_CONFIG.read_text())["name"] == config["name"]


@pytest.mark.parametrize("tokens", [16384, 8192, 1000, 256, 64, 17])
def test_ssm_flop_count_equals_the_programs(tokens):
    config = harness.load_json(SSM_CONFIG)
    cfg, _ = harness.build_model(config)
    assert flops_ssm.train_step_flops_per_sequence(config["model"], tokens) \
        == flops.train_step_flops_per_sequence(cfg, tokens)
    assert flops_ssm.forward_flops_per_sequence(config["model"], tokens) \
        == flops.forward_flops_per_sequence(cfg, tokens)
    assert flops_ssm.chunk_pairs(tokens, 256) == flops.chunk_pairs(
        tokens, 256)
    tiny = harness.load_cell(SSM_CELL, rehearsal=True)[1]
    assert flops_ssm.train_step_flops_per_sequence(tiny["model"], tokens) \
        == flops.train_step_flops_per_sequence(
            harness.build_model(tiny)[0], tokens)


def test_ssm_finer_table_equals_the_programs_new_rows():
    theirs = {n: p.pattern for n, p in device_trace.TOKEN_LAYERS}
    mine = [(n, p.pattern) for n, p in scopes_ssm.ROWS]
    assert [n for n, _ in mine] == [
        n for n, _ in device_trace.TOKEN_LAYERS if n.startswith("ssm_")]
    assert all(theirs[n] == pattern for n, pattern in mine)


@pytest.mark.parametrize("preset,rehearsal", [
    ("granite-4.0-h-micro-pp4", False), ("ssm-tiny", True)])
def test_ssm_config_file_is_the_programs_preset(preset, rehearsal):
    config = harness.load_cell(SSM_CELL, rehearsal=rehearsal)[1]
    assert harness.build_model(config)[0] == LM_PRESETS[preset]()
    assert config["program_preset"] == "granite-4.0-h-micro-pp4"
    assert json.loads(SSM_CONFIG.read_text())["name"] == config["name"]


def _window_of(driver: str) -> tuple:
    """The part of a token driver's ``run`` that IS the yardstick of
    ``train_img_s``: from the window's constants to the end of the
    training loop (the feed, where the window opens and closes, the
    barriers on the steps in flight, the capture's start and stop), and
    the ``train`` entry that hands steps and seconds to ``run.py``."""
    text = (harness.BENCH / "drivers" / f"{driver}.py").read_text()
    body = text[text.index("    warm, in_flight = WARMUP_STEPS, "):
                text.index("    # ---- after the window")]
    result = text[text.index('        "train": {"steps": w["steps"]'):
                  text.index('"feed_ms": fed')]
    return body, result


@pytest.mark.parametrize("copy", ["train_mla", "train_dsa", "train_conv",
                                  "train_ssm"])
def test_the_timed_window_is_one_text_in_every_token_driver(copy):
    """``drivers/train_mla.py::run``, ``drivers/train_dsa.py::run``,
    ``drivers/train_conv.py::run`` and ``drivers/train_ssm.py::run`` are
    ``drivers/train_lm.py::run`` written again (a ``model_config`` PR may
    edit no accepted benchmark file; PERF.md section 7 queues the one
    ``run`` that takes the cell's key, comparison and checks). Until
    then an edit to where the window opens or closes in one of them and
    not the others fails here."""
    body, result = _window_of("train_lm")
    assert "def stop_check" in body and "engine.train(" in body
    assert "t_close" in result
    assert _window_of(copy) == (body, result)
