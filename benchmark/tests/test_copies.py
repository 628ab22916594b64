"""The copied arithmetic and the copied reading of a scope path equal
the program's today, and the schedule is a pure function of its
parameters and seed."""

import numpy as np
import pytest

from benchmark.lib import flops, harness, kernels, schedule, scopes, xplane

FIXTURES = harness.BENCH / "fixtures"


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


@pytest.mark.parametrize("path", sorted(
    (harness.BENCH / "configs").glob("*.json")), ids=lambda p: p.stem)
def test_config_file_is_the_programs_preset(path):
    """A ViT's file is one of ``configs.PRESETS``, a token model's (it
    has a ``vocab_size``) one of ``configs.LM_PRESETS``."""
    from pytorch_vit_paper_replication_tpu.configs import (LM_PRESETS,
                                                           PRESETS)

    config = harness.load_json(path)
    cfg, _ = harness.build_model(config)
    presets = LM_PRESETS if config["model"].get("vocab_size") else PRESETS
    assert cfg == presets[config["program_preset"]]()


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


# ------------------------------------- lib/scopes.py = telemetry/device_trace
BLOCK = "jit(train_step)/jvp(ViT)/backbone/encoder_block_3"
BACK = "jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_3"
PATHS = [
    "jit(train_step)/jvp(ViT)/backbone/patch_embedding/patch_conv/conv",
    f"{BLOCK}/msa/norm/reduce_sum", f"{BACK}/msa/qkv/dot_general",
    f"{BLOCK}/msa/attn_core/bqhd,bkhd->bhqk/dot_general",
    f"{BACK}/msa/attn_core/flash_bwd_dq/pallas_call",
    f"{BACK}/msa/out/dot_general", f"{BLOCK}/msa/squeeze", f"{BLOCK}/add",
    f"{BLOCK}/mlp/norm/reduce_sum", f"{BACK}/mlp/lnmlp_bwd/pallas_call",
    "jit(train_step)/jvp(ViT)/backbone/encoder_norm/mul",
    "jit(train_step)/transpose(jvp(ViT))/head/dot_general",
    "jit(train_step)/jvp(ViT)/slice",
    "jit(train_step)/jvp(loss)/jit(take_along_axis)/gather",
    "jit(train_step)/metrics/reduce_sum",
    "jit(train_step)/optimizer/jit(clip)/max",
    "jit(train_step)/transpose(jvp(ViT))/backbone/jvp(ViT)/backbone/"
    "checkpoint/rematted_computation/encoder_block_1/msa/attn_core/exp",
    f"{BACK}/msa/out/reshape;{BACK}/mlp/reshape",
    "jit(train_step)/jit(_threefry_fold_in)/slice", ""]


def test_layers_and_the_phase_rule_equal_the_programs():
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    assert [(n, p.pattern) for n, p in scopes.LAYERS] == [
        (n, p.pattern) for n, p in device_trace.LAYERS]
    assert xplane.PHASES == device_trace.PHASES
    for path in PATHS:
        for extra in ({}, {"kernel": "lnmlp_bwd"}, {"op": "all-reduce-start"},
                      {"name": "fusion.84.remat"}, {"by_block": True}):
            assert scopes.classify(path, **extra) == \
                device_trace.classify(path, **extra), (path, extra)
        row = {"name": "lnmlp_fwd.2", "scope": path}
        assert scopes.kernel_name(row) == device_trace.kernel_name(row)
    # one example of each, so that two equal wrongs do not pass
    assert scopes.classify(PATHS[4]) == ("attn_core", "backward")
    assert scopes.classify(PATHS[9], kernel="lnmlp_bwd") == (
        "lnmlp_bwd", "backward")
    assert scopes.classify(PATHS[-4]) == ("attn_core", "recompute")


def test_parse_scopes_equals_the_programs():
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    mlp = "jit(train_step)/jvp(ViT)/backbone/encoder_block_0/mlp/" \
        "lnmlp_fwd/pallas_call"
    hlo = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "ENTRY %main {",
        "  %copy-start.1 = (bf16[8]{0}, bf16[8]{0}) copy-start(%p.0)",
        "  %copy-done.1 = bf16[8]{0} copy-done(%copy-start.1)",
        "  %lnmlp_fwd.2 = bf16[8]{0} custom-call(%copy-done.1), "
        'custom_call_target="tpu_custom_call", frontend_attributes='
        '{kernel_metadata={}}, metadata={op_name="' + mlp + '" '
        "stack_frame_id=7}",
        "  %slice-start.3 = bf16[4]{0} slice-start(%lnmlp_fwd.2)",
        "  ROOT %fusion.3 = f32[] fusion(%lnmlp_fwd.2), kind=kLoop, "
        'metadata={op_name="jit(train_step)/optimizer/add"}',
        "}"])
    got = scopes.parse_scopes(hlo)
    assert got == device_trace.parse_scopes(hlo)
    assert got["module"] == "jit_train_step"
    # an instruction of the compiler's own takes its consumer's path,
    # else (nothing uses it) its operand's
    assert got["scopes"] == {
        "lnmlp_fwd.2": mlp, "fusion.3": "jit(train_step)/optimizer/add",
        "copy-done.1": mlp, "copy-start.1": mlp, "slice-start.3": mlp}


def test_leaves_equals_the_programs():
    """``xplane.leaves`` is ``telemetry/device_trace.py::leaves``: on
    loops over ops, a loop with no body event, nested control flow, an
    op that outlasts the loop it began in, events in any order, and an
    empty line. (What the reducer makes of them is ``test_xplane.py``.)"""
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    def e(name, op, start, dur):
        return {"name": name, "op": op, "start_ns": start, "dur_ns": dur}
    events = [
        e("while.1", "while", 0, 100), e("fusion.1", "fusion", 0, 40),
        e("lnmlp_fwd.2", "custom-call", 40, 60),
        e("while.2", "while", 100, 50),                 # no body event
        e("conditional.3", "conditional", 150, 100),
        e("while.4", "while", 160, 80), e("fusion.5", "fusion", 170, 70),
        e("call.6", "call", 250, 50), e("copy.7", "copy", 290, 30),
        e("fusion.8", "fusion", 320, 10), e("while.9", "while", 330, 5)]
    want = ["fusion.1", "lnmlp_fwd.2", "while.2", "fusion.5", "call.6",
            "copy.7", "fusion.8", "while.9"]
    assert [x["name"] for x in xplane.leaves(events)] == want
    rng = np.random.default_rng(30)
    for _ in range(40):
        order = [events[i] for i in rng.permutation(len(events))]
        assert xplane.leaves(order) == device_trace.leaves(order)
        assert [x["name"] for x in xplane.leaves(order)] == want
    assert xplane.leaves([]) == device_trace.leaves([]) == []
    assert xplane.CONTROL_FLOW == ("while", "conditional", "call")


@pytest.mark.parametrize("fixture", [
    "train_step_b16_scoped.events.json.gz",
    "train_step_b16_dp4_scoped.events.json.gz"])
def test_the_two_readers_agree_row_by_row_on_the_recorded_steps(fixture):
    """The benchmark's ``rows_ms`` is the trainer's table on the same
    recorded steps, row by row. The trainer's reader makes a row of every
    Mosaic kernel; the benchmark's only of the MLP kernels, which are the
    only kernels of these steps."""
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    trace = xplane.load_events_json(FIXTURES / fixture)
    mine = xplane.reduce_trace(trace, module_prefix="jit_train_step")
    theirs = device_trace.reduce(trace)
    assert theirs["steps"] == mine["steps"] >= 3
    table = {(r["layer"], r["phase"]): r["ms"] for r in theirs["rows"]}
    rows = {(layer, phase): ms for layer, by in mine["rows_ms"].items()
            for phase, ms in by.items()}
    assert set(rows) == set(table)
    for key, ms in table.items():
        assert rows[key] == pytest.approx(ms, rel=1e-9, abs=1e-9), key
    for key in ("step_ms", "busy_ms", "mosaic_ms", "xla_ms",
                "collective_ms", "collective_exposed_ms"):
        assert mine[key] == pytest.approx(theirs[key], rel=1e-6), key
