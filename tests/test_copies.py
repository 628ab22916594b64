"""The copies this repo keeps of the program inside the benchmark (a
yardstick that neither side of a comparison can edit) for the token model
whose attention an indexer selects, held equal to the program: the FLOP
count (``benchmark/lib/flops_dsa.py``), the finer table of scopes
(``benchmark/lib/scopes_dsa.py``) and the preset against its
configuration file. (The older copies are held by
``benchmark/tests/test_copies.py``, ``test_lm_files.py`` and
``test_mla_files.py``.)"""

import json

import pytest

from benchmark.lib import flops_dsa, harness, scopes_dsa
from pytorch_vit_paper_replication_tpu.configs import LM_PRESETS
from pytorch_vit_paper_replication_tpu.telemetry import device_trace, flops

CONFIG = harness.BENCH / "configs" / "keye-vl-2.0-30b-a3b-ep8.json"
CELL = "keye2_train_16k"


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


@pytest.mark.parametrize("copy", ["train_mla", "train_dsa"])
def test_the_timed_window_is_one_text_in_every_token_driver(copy):
    """``drivers/train_mla.py::run`` and ``drivers/train_dsa.py::run`` are
    ``drivers/train_lm.py::run`` written again (a ``model_config`` PR may
    edit no accepted benchmark file; PERF.md section 7 queues the one
    ``run`` that takes the cell's key, comparison and checks). Until
    then an edit to where the window opens or closes in one of them and
    not the others fails here."""
    body, result = _window_of("train_lm")
    assert "def stop_check" in body and "engine.train(" in body
    assert "t_close" in result
    assert _window_of(copy) == (body, result)
