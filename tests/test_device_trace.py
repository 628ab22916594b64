"""telemetry/device_trace.py: the classifier on hand-made paths, the
reduction on hand-made events and on three recorded steps of the real
B/16 train step (from the chip, PR 24), and the capture path of
``ProfileController`` on the CPU, where a trace has no device plane."""

import gzip
import json
from pathlib import Path

import pytest

from pytorch_vit_paper_replication_tpu.telemetry import (
    ProfileController, StepTelemetry, TelemetryRegistry, device_trace)

FIXTURE = Path(__file__).parent / "fixtures" / \
    "train_step_b16_scoped.events.json.gz"
BLOCK = "jit(train_step)/jvp(ViT)/backbone/encoder_block_3"
BACK = "jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_3"


@pytest.mark.parametrize("scope, extra, want", [
    ("jit(train_step)/jvp(ViT)/backbone/patch_embedding/patch_conv/conv",
     {}, ("patch_embed", "forward")),
    (f"{BLOCK}/msa/norm/reduce_sum", {}, ("msa_norm", "forward")),
    (f"{BACK}/msa/qkv/dot_general", {}, ("msa_qkv", "backward")),
    # innermost wins: attn_core under msa, not msa_glue
    (f"{BLOCK}/msa/attn_core/bqhd,bkhd->bhqk/dot_general", {},
     ("attn_core", "forward")),
    (f"{BACK}/msa/out/dot_general", {}, ("msa_out", "backward")),
    (f"{BLOCK}/msa/squeeze", {}, ("msa_glue", "forward")),
    (f"{BLOCK}/add", {}, ("block_glue", "forward")),
    # the MLP's own LayerNorm is the MLP's, not the attention's
    (f"{BLOCK}/mlp/norm/reduce_sum", {}, ("mlp_xla", "forward")),
    (f"{BACK}/mlp/lnmlp_bwd/pallas_call", {"kernel": "lnmlp_bwd"},
     ("lnmlp_bwd", "backward")),
    ("jit(train_step)/jvp(ViT)/backbone/encoder_norm/mul", {},
     ("final_norm_head", "forward")),
    ("jit(train_step)/transpose(jvp(ViT))/head/dot_general", {},
     ("final_norm_head", "backward")),
    ("jit(train_step)/jvp(ViT)/slice", {}, ("final_norm_head", "forward")),
    ("jit(train_step)/jvp(loss)/jit(take_along_axis)/gather", {},
     ("loss", "forward")),
    ("jit(train_step)/transpose(jvp(loss))/mul", {}, ("loss", "backward")),
    ("jit(train_step)/metrics/reduce_sum", {}, ("metrics", "forward")),
    ("jit(train_step)/optimizer/jit(clip)/max", {},
     ("optimizer", "optimizer")),
    ("jit(train_step)/transpose(jvp(ViT))/backbone/jvp(ViT)/backbone/"
     "checkpoint/rematted_computation/encoder_block_1/msa/attn_core/exp",
     {}, ("attn_core", "recompute")),
    (f"{BLOCK}/msa/attn_core/dot_general", {"name": "fusion.84.remat"},
     ("attn_core", "recompute")),
    (f"{BACK}/msa/out/reshape;{BACK}/mlp/reshape", {},
     ("msa_out", "backward")),          # several paths: the first
    ("jit(train_step)/jvp(ViT)/backbone/encoder_block_3/msa/qkv/dot",
     {"op": "all-reduce-start"}, ("collective", "forward")),
    ("jit(train_step)/jit(_threefry_fold_in)/slice", {},
     ("other", "forward")),
    ("", {}, ("other", "forward")),
    (f"{BLOCK}/msa/attn_core/exp", {"by_block": True},
     ("attn_core@3", "forward")),
])
def test_classify(scope, extra, want):
    assert device_trace.classify(scope, **extra) == want


def test_parse_scopes_joins_by_instruction_and_inherits_from_the_user():
    hlo = "\n".join([
        "HloModule jit_train_step, is_scheduled=true",
        "ENTRY %main {",
        '  %copy-start.1 = (bf16[8]{0}, bf16[8]{0}) copy-start(%p.0)',
        '  %copy-done.1 = bf16[8]{0} copy-done(%copy-start.1)',
        '  %lnmlp_fwd.2 = bf16[8]{0} custom-call(%copy-done.1), '
        'custom_call_target="tpu_custom_call", frontend_attributes='
        '{kernel_metadata={}}, metadata={op_name="jit(train_step)/jvp(ViT)'
        '/backbone/encoder_block_0/mlp/lnmlp_fwd/pallas_call" '
        'stack_frame_id=7}',
        '  ROOT %fusion.3 = f32[] fusion(%lnmlp_fwd.2), kind=kLoop, '
        'metadata={op_name="jit(train_step)/optimizer/add"}',
        "}"])
    got = device_trace.parse_scopes(hlo)
    assert got["module"] == "jit_train_step"
    mlp = "jit(train_step)/jvp(ViT)/backbone/encoder_block_0/mlp/" \
        "lnmlp_fwd/pallas_call"
    assert got["scopes"] == {
        "lnmlp_fwd.2": mlp, "fusion.3": "jit(train_step)/optimizer/add",
        "copy-done.1": mlp, "copy-start.1": mlp}
    row = {"name": "lnmlp_fwd.2", "scope": mlp}
    assert device_trace.kernel_name(row) == "lnmlp_fwd"
    assert device_trace.kernel_name({"name": "mlp.36", "scope": ""}) == "mlp"


# ------------------------------------------------------------ hand-made
def _op(name, start, dur, scope, **kw):
    return {"name": name, "start_ns": start, "dur_ns": dur, "op": "fusion",
            "out": "", "mosaic": False, "scope": scope, **kw}


def _plane(chip, step_ns, scale=1.0, runs=5, anchor_end=None):
    """``runs`` executions of a step that holds a qkv op, a gap, a kernel
    and an optimizer op; the op times of execution ``i`` grow with ``i``
    so that a median differs from a mean."""
    mods, ops = [], []
    if anchor_end is not None:
        mods.append({"name": "jit_profiler_clock_anchor(1)",
                     "start_ns": anchor_end - 10, "dur_ns": 10})
    for i in range(runs):
        t0 = 1000 + i * step_ns
        mods.append({"name": "jit_train_step(42)", "start_ns": t0,
                     "dur_ns": step_ns - 100})
        grow = 1 + (10 if i == runs - 1 else 0)     # one slow execution
        a, b, c = (int(x * scale * grow) for x in (1000, 3000, 500))
        ops += [
            _op(f"fusion.{i}", t0, a, f"{BACK}/msa/qkv/dot_general"),
            # 2000 ns of nothing, then the kernel
            _op("lnmlp_fwd.7", t0 + a + 2000, b,
                f"{BLOCK}/mlp/lnmlp_fwd/pallas_call", mosaic=True,
                op="custom-call", kernel="lnmlp_fwd"),
            _op("fusion.9", t0 + a + 2000 + b, c,
                "jit(train_step)/optimizer/add")]
    return {"name": f"/device:TPU:{chip}", "lines": [
        {"name": device_trace.MODULE_LINE, "events": mods},
        {"name": device_trace.OPS_LINE, "events": ops}]}


def test_reduce_rows_sum_to_busy_median_over_steps_mean_over_chips():
    trace = {"planes": [_plane(0, 100_000), _plane(1, 100_000, scale=2.0),
                        {"name": "/host:CPU", "lines": []}]}
    got = device_trace.reduce(trace)
    assert (got["chips"], got["steps"]) == (2, 4)    # the first is dropped
    rows = {(r["layer"], r["phase"]): r for r in got["rows"]}
    # per chip the median over steps (the slow execution does not move
    # it), then the mean over the two chips (x1 and x2)
    assert rows[("msa_qkv", "backward")]["ms"] == pytest.approx(1.5e-3)
    assert rows[("lnmlp_fwd", "forward")]["ms"] == pytest.approx(4.5e-3)
    assert rows[("optimizer", "optimizer")]["ms"] == pytest.approx(0.75e-3)
    assert rows[("lnmlp_fwd", "forward")]["calls"] == 1
    assert sum(r["ms"] for r in got["rows"]) == pytest.approx(got["busy_ms"])
    assert got["mosaic_ms"] + got["xla_ms"] == pytest.approx(got["busy_ms"])
    assert got["step_ms"] == pytest.approx(99_900 / 1e6)
    assert got["other_pct"] == 0 and 0 < got["idle_pct"] < 100
    assert "lnmlp_fwd" in device_trace.format_table(got)
    json.dumps(got)                                  # plain Python


def test_reduce_reports_other_and_a_collectives_exposed_part():
    plane = _plane(0, 100_000)
    ops = plane["lines"][1]["events"]
    for i in range(5):
        t0 = 1000 + i * 100_000
        ops.append(_op(f"copy.{i}", t0 + 20_000, 700, "no/such/module"))
        # an all-reduce half under the kernel (which runs from t0 + 3000
        # to t0 + 6000), half after the optimizer op has ended
        ops.append(_op("all-reduce.1", t0 + 5000, 3000, f"{BACK}/msa/qkv",
                       op="all-reduce"))
    got = device_trace.reduce({"planes": [plane]})
    rows = {(r["layer"], r["phase"]): r["ms"] for r in got["rows"]}
    assert rows[("other", "forward")] == pytest.approx(0.7e-3)
    assert got["other_pct"] == pytest.approx(100 * 700 / 99_900)
    assert got["collective_ms"] == pytest.approx(3e-3)
    # exposed: from 6500 (the optimizer op's end) to 8000
    assert rows[("collective", "forward")] == pytest.approx(1.5e-3)
    assert got["collective_exposed_ms"] == pytest.approx(1.5e-3)
    assert sum(rows.values()) == pytest.approx(got["busy_ms"])


def test_reduce_counts_a_loops_body_and_not_the_while_laid_over_it():
    """The ``XLA Ops`` line lays a ``while`` event over its body's ops
    (the passes of the routed layer): the body's ops are counted under
    their own scopes, the enclosing event under none, and a loop whose
    body left no event stays a row of its own."""
    plane = _plane(0, 100_000)
    ops = plane["lines"][1]["events"]
    mlp = f"{BLOCK}/mlp"
    for i in range(5):
        t0 = 1000 + i * 100_000 + 20_000
        ops += [
            _op("while.3", t0, 5000, f"{mlp}/while", op="while"),
            _op("fusion.30", t0 + 100, 1500,
                f"{mlp}/while/body/moe_dispatch/gather"),
            _op("moe_gmm_fwd.4", t0 + 1700, 3000,
                f"{mlp}/while/body/moe_experts/moe_gmm_fwd/pallas_call",
                mosaic=True, op="custom-call", kernel="moe_gmm_fwd"),
            _op("while.5", t0 + 6000, 40, f"{mlp}/moe_dispatch/while",
                op="while")]
    got = device_trace.reduce({"planes": [plane]})
    rows = {(r["layer"], r["phase"]): r["ms"] for r in got["rows"]}
    assert rows[("moe_dispatch", "forward")] == pytest.approx(1.54e-3)
    assert rows[("moe_gmm_fwd", "forward")] == pytest.approx(3e-3)
    assert ("mlp_xla", "forward") not in rows
    assert sum(rows.values()) == pytest.approx(got["busy_ms"])


def test_reduce_needs_three_complete_steps_and_says_why():
    got = device_trace.reduce({"planes": [_plane(0, 100_000, runs=3)]})
    assert "rows" not in got and "2 complete executions" in got["reason"]
    # a window closes when its last step is dispatched: the execution
    # that was running then is cut by the capture's end, and is no step
    plane = _plane(0, 100_000, runs=5)
    whole = device_trace.reduce({"planes": [plane]})
    plane["lines"][0]["events"][-1]["dur_ns"] = 2100
    plane["lines"][1]["events"][-2:] = []      # its later ops never ran
    cut = device_trace.reduce({"planes": [plane]})
    assert (whole["steps"], cut["steps"]) == (4, 3)
    assert cut["step_ms"] == whole["step_ms"]
    none = device_trace.reduce({"planes": [{"name": "/host:CPU",
                                            "lines": []}]})
    assert none["chips"] == 0 and "no device plane" in none["reason"]
    assert "no table" in device_trace.format_table(none)


def test_idle_gap_is_named_by_the_host_span_through_the_anchor_shift():
    # The anchor ended at 500 on the trace's clock and at 1_000_500 on
    # the host's. On the host's clock the loader was waited for during
    # the gap inside the third execution, and a checkpoint elsewhere.
    trace = {"planes": [_plane(0, 100_000, anchor_end=500)]}
    anchor = device_trace.module_end_ns(trace, "jit_profiler_clock_anchor")
    assert anchor == 500
    gap_lo = 1000 + 2 * 100_000 + 1000
    host = [("data_wait", 1_000_000 + gap_lo + 100, 1_000_000 + gap_lo + 1900),
            ("checkpoint", 1_000_000 + 50_000, 1_000_000 + 60_000)]
    spans = device_trace.shift_spans(host, 1_000_500, anchor)
    assert spans[0] == ("data_wait", gap_lo + 100, gap_lo + 1900)
    got = device_trace.reduce(trace, host_spans=spans)
    named = [(round(g["ms"] * 1e6), g["span"]) for g in got["idle_gaps"]]
    assert (2000, "data_wait") in named
    # the long gaps between an execution's last op and the next one's
    # first are covered by no span of the program
    assert named[0][1] == "(no span)" and named[0][0] > 90_000


# ------------------------------------------------- the recorded B/16 step
@pytest.fixture(scope="module")
def recorded():
    with gzip.open(FIXTURE, "rt") as f:
        return json.load(f)


def test_recorded_b16_step_names_its_kernels_and_leaves_nothing_over(
        recorded):
    got = device_trace.reduce(recorded)
    assert (got["chips"], got["steps"]) == (1, 3)
    rows = {(r["layer"], r["phase"]): r for r in got["rows"]}
    assert rows[("lnmlp_fwd", "forward")]["calls"] == 12
    assert rows[("lnmlp_bwd", "backward")]["calls"] == 12
    assert got["other_pct"] < 1.0
    assert sum(r["ms"] for r in got["rows"]) == pytest.approx(
        got["busy_ms"], rel=1e-3)
    assert got["idle_pct"] < 1.0
    # every layer of the table occurs in the real step, in the phases
    # it can have
    layers = {layer for layer, _ in rows}
    assert layers >= {"msa_norm", "msa_qkv", "attn_core", "msa_out",
                      "msa_glue", "mlp_xla", "block_glue", "patch_embed",
                      "final_norm_head", "loss", "metrics", "optimizer"}
    assert ("attn_core", "backward") in rows and \
        rows[("attn_core", "backward")]["ms"] > \
        rows[("attn_core", "forward")]["ms"]


def test_the_two_readers_agree_on_the_recorded_step(recorded):
    """The same file through the benchmark's reader: the same step, the
    same time in Mosaic calls and in XLA ops."""
    from benchmark.lib import xplane

    theirs = xplane.reduce_trace(recorded, module_prefix="jit_train_step")
    mine = device_trace.reduce(recorded)
    assert mine["steps"] == theirs["steps"]
    assert mine["step_ms"] == pytest.approx(theirs["step_ms"], rel=1e-6)
    assert mine["mosaic_ms"] == pytest.approx(theirs["mosaic_ms"], rel=1e-6)
    assert mine["xla_ms"] == pytest.approx(theirs["xla_ms"], rel=1e-6)
    assert mine["busy_ms"] == pytest.approx(theirs["busy_ms"], rel=1e-6)
    assert theirs["mosaic_calls"] == 24
    assert mine["idle_pct"] == pytest.approx(
        100 * (1 - theirs["busy_s"] / theirs["window_s"]), abs=1e-3)


def test_cli_prints_the_table_of_a_capture_directory(
        recorded, tmp_path, monkeypatch, capsys):
    """``python -m ...device_trace <dir>``: the scope map and the spans
    come from ``program.json.gz`` beside the capture."""
    (tmp_path / "plugins").mkdir()
    (tmp_path / "plugins" / "x.xplane.pb").write_bytes(b"")
    with gzip.open(tmp_path / device_trace.PROGRAM_FILE, "wt") as f:
        json.dump({"module": "jit_train_step", "scopes": {"a": "b"},
                   "host_spans": []}, f)
    seen = {}

    def load(path, scopes=None):
        seen["scopes"] = scopes
        return recorded
    monkeypatch.setattr(device_trace, "load", load)
    assert device_trace.main([str(tmp_path), "--by-block"]) == 0
    assert seen["scopes"] == {"a": "b"}
    out = capsys.readouterr().out
    assert "attn_core@11" in out and "% of step" in out
    table = json.loads((tmp_path / device_trace.TABLE_FILE).read_text())
    assert table["steps"] == 3 and table["rows"]


# ----------------------------------------- the controller, on the CPU
def test_capture_on_the_cpu_closes_with_a_reason_and_raises_nothing(
        tmp_path):
    import jax
    import jax.numpy as jnp

    reg = TelemetryRegistry()
    pc = ProfileController(tmp_path / "prof", registry=reg, steps=(2, 3))
    tel = StepTelemetry(registry=reg, profiler=pc, sample_memory=False)
    asked = []
    pc.set_program(lambda: asked.append(1) or "HloModule jit_f, x")
    f = jax.jit(lambda x: x * 2)
    for step in range(1, 5):
        tel.step_begin(step)
        jax.block_until_ready(f(jnp.ones(4)))
        if step == 1:                    # not active: nothing is kept
            tel.span("checkpoint", 0.01)
            assert pc._spans == []
        if step == 2:
            assert pc.active
            tel.span("eval", 0.02)
        tel.step(data_wait_s=0.001, exec_s=0.002, images=4, step=step)
    assert not pc.active and pc._spans == []
    events = [e for e in reg.last_events()
              if e["event"] == "profiler_device_time"]
    assert len(events) == 1 and "no device plane" in events[0]["reason"]
    assert not asked, "no device plane: the step is not compiled again"
    assert reg.snapshot()["counters"].get(
        "profiler_capture_errors_total", 0) == 0
    capture = Path(pc.last_capture_path)
    table = json.loads((capture / device_trace.TABLE_FILE).read_text())
    assert table["chips"] == 0
    with gzip.open(capture / device_trace.PROGRAM_FILE, "rt") as f:
        program = json.load(f)
    # the spans of steps 2 and 3, on the trace's clock only through an
    # anchor, which a trace without device planes does not hold
    assert program["host_spans"] == []
    tel.close()
    pc.close()


def test_spans_are_kept_only_while_a_capture_is_active(tmp_path):
    pc = ProfileController(tmp_path / "prof", registry=TelemetryRegistry())
    pc.add_span("data_wait", 1000, 0.5)
    assert pc._spans == []
    pc._active = (9, tmp_path)           # as maybe_start leaves it
    pc.add_span("data_wait", 2_000_000_000, 0.5)
    assert pc._spans == [("data_wait", 1_500_000_000, 2_000_000_000)]
    pc._active = None


def test_first_step_hands_the_profiler_the_step_programs_hlo(tmp_path):
    import jax
    import jax.numpy as jnp

    pc = ProfileController(tmp_path / "prof", registry=TelemetryRegistry())
    tel = StepTelemetry(registry=pc.registry, profiler=pc,
                        sample_memory=False)

    @jax.jit
    def train_step(state, batch):
        with jax.named_scope("optimizer"):
            return state + batch["x"].sum(), {}
    tel.first_step(train_step, jnp.zeros(()), {"x": jnp.ones(3)})
    program = device_trace.parse_scopes(pc._program_text())
    assert program["module"] == "jit_train_step"
    assert any("optimizer" in s for s in program["scopes"].values())
    tel.first_step(lambda s, b: (s, {}), None, None)   # no .lower: kept
    assert pc._program_text is not None
