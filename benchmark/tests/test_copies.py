"""The copied arithmetic equals the program's today, and the schedule is
a pure function of its parameters and seed."""

import numpy as np
import pytest

from benchmark.lib import flops, harness, kernels, schedule


@pytest.mark.parametrize("name,preset,gflop", [
    ("vit-b16-224", "ViT-B/16", 105.4), ("vit-l16-224", "ViT-L/16", 369.3)])
def test_flop_count_equals_the_programs(name, preset, gflop):
    from pytorch_vit_paper_replication_tpu.configs import PRESETS
    from pytorch_vit_paper_replication_tpu.telemetry import flops as theirs

    model = harness.load_json(
        harness.BENCH / "configs" / f"{name}.json")["model"]
    mine = flops.train_step_flops_per_image(model)
    assert mine == theirs.train_step_flops_per_image(PRESETS[preset]())
    assert mine / 1e9 == pytest.approx(gflop, abs=0.05)


def test_peaks_equal_the_programs_and_unknown_kind_is_an_error():
    from pytorch_vit_paper_replication_tpu.telemetry import flops as theirs

    for kind, row in theirs.CHIP_PEAKS.items():
        assert {k: flops.peaks(kind)[k] for k in row} == row
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")


def test_config_file_is_the_programs_preset():
    from pytorch_vit_paper_replication_tpu.configs import PRESETS

    for path in sorted((harness.BENCH / "configs").glob("*.json")):
        config = harness.load_json(path)
        cfg, _ = harness.build_model(config)
        assert cfg == PRESETS[config["program_preset"]]()


def test_schedule_is_a_pure_function_of_the_seed():
    traffic = {"rate_rps": 500.0,
               "segments": [{"t0": 1.0, "t1": 2.0, "rate_mult": 4.0}],
               "tier_mix": {"interactive": 3, "batch": 1}}
    a = schedule.build_schedule(traffic, seed=7, duration_s=4.0)
    b = schedule.build_schedule(traffic, seed=7, duration_s=4.0)
    c = schedule.build_schedule(traffic, seed=8, duration_s=4.0)
    assert np.array_equal(a["t"], b["t"]) and a["tier"] == b["tier"]
    assert not np.array_equal(a["t"][:50], c["t"][:50])
    assert np.all(np.diff(a["t"]) > 0) and a["t"][-1] < 4.0
    # 3 s at 500 rps and 1 s at 2000 rps: 3500 expected, sd ~59.
    assert abs(len(a["t"]) - 3500) < 300
    burst = ((a["t"] >= 1.0) & (a["t"] < 2.0)).sum()
    assert abs(burst - 2000) < 230
    assert 0.15 < a["tier"].count("batch") / len(a["tier"]) < 0.35


def test_offset_moves_the_segments_with_the_window():
    traffic = {"rate_rps": 200.0,
               "segments": [{"t0": 0.0, "t1": 1.0, "rate_mult": 0.0}]}
    s = schedule.build_schedule(traffic, seed=1, duration_s=3.0,
                                offset_s=1.0)
    assert ((s["t"] >= 1.0) & (s["t"] < 2.0)).sum() == 0
    assert (s["t"] < 1.0).sum() > 100


def test_mlp_kernel_cost_by_hand():
    # B/16, batch 256: N = 256 * 197 rows, D 768, M 3072, 12 layers.
    n, d, m = 256 * 197, 768, 3072
    cost = kernels.mlp_half_block_cost(n, d, m, layers=12)
    assert cost["flops"] == 12 * 6 * 2 * n * d * m
    assert cost["bytes"] == 12 * (5 * n * d * 2 + 6 * d * m * 2)
    least = kernels.roofline_seconds(cost, flops.peaks("TPU v5 lite"))
    assert least["bound"] == "compute"
    assert least["seconds"] == pytest.approx(cost["flops"] / 197e12)
    fwd = kernels.mlp_half_block_cost(n, d, m, layers=12, backward=False)
    assert fwd["flops"] * 3 == cost["flops"]
