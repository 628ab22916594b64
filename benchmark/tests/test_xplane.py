"""The reducer's arithmetic, against values worked out by hand on a
made-up two-chip trace, and on the trace recorded on the chip."""

from pathlib import Path

import pytest

from benchmark.lib import flops, harness, scopes, xplane
from benchmark.metrics import (attn_core_bwd_ms, attn_core_fwd_ms,
                               attn_core_ms, attn_core_roofline_pct,
                               lm_attn_core_roofline_pct, lm_step_mfu_pct,
                               ln_ms, mlp_glue_ms, mlp_kernel_bwd_ms,
                               mlp_kernel_fwd_ms, mlp_kernel_ms,
                               mlp_kernel_roofline_pct, msa_glue_ms,
                               msa_proj_ms, optimizer_ms, other_ms,
                               xla_backward_ms, xla_ops_ms)

FIXTURE = Path(__file__).resolve().parent.parent / "fixtures" / \
    "train_step_b16_scoped.events.json.gz"
BLOCK = "jit(train_step)/jvp(ViT)/backbone/encoder_block_3"
BACK = "jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_3"


def ev(name, start, dur, scope="", **extra):
    """An event as ``load`` keeps it: without the program's scope map an
    op has no scope, and a kernel is named by its instruction."""
    row = {"name": name, "start_ns": start, "dur_ns": dur,
           **xplane.parse_hlo(name), "scope": scope, **extra}
    if row["mosaic"]:
        row["kernel"] = scopes.kernel_name(row)
    return row


MOSAIC = ('%mlp.7 = (bf16[8,4]{1,0:T(8,128)(2,1)}, f32[1,4]{1,0}) '
          'custom-call(s32[3]{0} %pad.1, bf16[8,4]{1,0} %custom-call.2), '
          'custom_call_target="tpu_custom_call", frontend_attributes={}')


def made_up_trace():
    """Chip 0: two steps of 1000 ns at 0 and 1200 (a 200 ns gap). In
    each: an XLA fusion 0-400, a Mosaic call 400-700, an all-reduce
    650-950 (50 ns under the Mosaic call, 250 ns exposed... of which
    900-950 overlaps a second fusion 900-1000). Chip 1: the same, 100 ns
    later, without the gap (second step at 1100)."""
    def step(t0):
        return [ev("%fusion.1", t0, 400),
                ev(MOSAIC, t0 + 400, 300),
                ev("%all-reduce.3 = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} "
                   "%fusion.1), replica_groups={{0,1}}", t0 + 650, 300),
                ev("%fusion.2", t0 + 900, 100)]
    chip0 = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ev("jit_train_step(1)", 0, 1000),
            ev("jit_train_step(1)", 1200, 1000),
            ev("jit_add(2)", 2200, 10)]},
        {"name": "XLA Ops", "events": step(0) + step(1200)}]}
    chip1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [
            ev("jit_train_step(1)", 100, 1000),
            ev("jit_train_step(1)", 1100, 1000)]},
        {"name": "XLA Ops", "events": step(100) + step(1100)}]}
    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ev("bench.feed", 990, 220), ev("bench.wait_step", 0, 900)]}]}
    return {"planes": [chip0, chip1, host]}


def test_interval_arithmetic():
    assert xplane.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert xplane.overlap_ns([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert xplane.gaps([(5, 10), (20, 30)], 0, 40) == [
        (0, 5), (10, 20), (30, 40)]
    assert xplane.union_ns([]) == 0


@pytest.mark.parametrize("name,cls", [
    ("all-reduce.3", "collective"), ("all-reduce-start.1", "collective"),
    ("all-gather.12", "collective"), ("reduce-scatter.2", "collective"),
    ("%ar = f32[4]{0} all-reduce-done(f32[4]{0} %x)", "collective"),
    (MOSAIC, "mosaic"), ("fusion.1", "xla"), ("copy.3", "xla"),
    # XLA's own custom calls, and a fusion that reads one, are not kernels.
    ('%custom-call.2 = bf16[8,4]{1,0} custom-call(f32[8,4]{1,0} %p), '
     'custom_call_target="AllocateBuffer"', "xla"),
    ("%fusion.9 = bf16[8,4]{1,0} fusion(bf16[8,4]{1,0} %custom-call.2), "
     "kind=kLoop", "xla")])
def test_op_classes(name, cls):
    assert xplane.op_class(ev(name, 0, 1)) == cls


def test_parse_hlo_keeps_name_opcode_and_shape():
    p = xplane.parse_hlo(MOSAIC)
    assert p == {"name": "mlp.7", "op": "custom-call", "mosaic": True,
                 "out": "(bf16[8,4], f32[1,4])"}
    assert xplane.op_label(ev(MOSAIC, 0, 1)) == \
        "mlp custom-call -> (bf16[8,4], f32[1,4])"
    assert xplane.op_label(ev("fusion.12", 0, 1)) == "fusion"


def test_reduce_by_hand():
    r = xplane.reduce_trace(made_up_trace(), module_prefix="jit_train_step",
                            window_ns=(0, 2200))
    assert r["chips"] == 2 and r["steps"] == 2
    c0, c1 = r["per_chip"]
    # Busy: each step's ops cover its whole 1000 ns; chip 0 idles 200 ns
    # between steps, chip 1 idles 100 ns at the start and 100 at the end.
    assert c0["busy_s"] == pytest.approx(2000e-9)
    assert c1["busy_s"] == pytest.approx(2000e-9)
    assert r["busy_s"] == pytest.approx(2000e-9)
    assert r["window_s"] == pytest.approx(2200e-9)
    for c in (c0, c1):
        assert c["step_ms"] == pytest.approx(1000e-6)
        assert c["mosaic_ms"] == pytest.approx(300e-6)
        assert c["mosaic_calls"] == 1
        # a kernel without a scope is named by its instruction
        assert c["mosaic_by_kernel_ms"] == {"mlp": pytest.approx(300e-6)}
        assert c["xla_ms"] == pytest.approx(500e-6)
        assert c["collective_ms"] == pytest.approx(300e-6)
        # 650-950, less 650-700 under the kernel and 900-950 under the
        # second fusion: 200 ns exposed.
        assert c["collective_exposed_ms"] == pytest.approx(200e-6)
        # mosaic + xla + exposed collective = the step's busy time.
        assert c["busy_ms"] == pytest.approx(1000e-6)
        # No op of this trace has a scope: all of it is ``other``, the
        # kernel too (``mlp`` is not a name the program gives), and the
        # collectives' exposed part is a row, so the rows sum to busy.
        assert c["rows_ms"] == {
            ("other", "forward"): pytest.approx(800e-6),
            ("collective", "forward"): pytest.approx(200e-6)}
    assert r["rows_ms"] == {"other": {"forward": pytest.approx(800e-6)},
                            "collective": {"forward": pytest.approx(200e-6)}}
    assert r["xla_by_phase_ms"] == {"forward": pytest.approx(500e-6)}
    assert xplane.layer_ms(r["rows_ms"], *r["rows_ms"]) == pytest.approx(
        r["busy_ms"])
    assert r["device_ops"][0][0] == "fusion"       # 4 x 500 ns
    named = dict(xplane.attribute_gaps(r["gaps"], xplane.host_spans(
        made_up_trace())))
    # Chip 0's 1000-1200 gap lies under bench.feed (990-1210).
    assert named["bench.feed"] == pytest.approx(200e-9)
    assert sum(named.values()) == pytest.approx(400e-9)


def kernel_call(name, scope_tail):
    return (f'%{name} = (bf16[8,4]{{1,0}}, f32[1,4]{{1,0}}) custom-call('
            'bf16[8,4]{1,0} %p), custom_call_target="tpu_custom_call"',
            scope_tail)


def test_rows_by_layer_and_phase_by_hand(capsys):
    """One chip, three steps of 2000 ns, each: a qkv backward fusion, the
    attention core's forward fusion, a flash kernel under ``attn_core``
    (a Mosaic call that is no MLP kernel: it counts under its scope's
    layer), the MLP kernel (a row of its own, by name), a rematerialised
    fusion, an optimizer op, a copy of no scope, and 350 ns idle. The
    third step's core forward is 10x as long: medians do not move."""
    flash, flash_scope = kernel_call(
        "flash_bwd_dq.4", f"{BACK}/msa/attn_core/flash_bwd_dq/pallas_call")
    mlp, mlp_scope = kernel_call(
        "lnmlp_fwd.2", f"{BLOCK}/mlp/lnmlp_fwd/pallas_call")
    mods, ops = [], []
    for i in range(3):
        t0 = 5000 * i
        slow = 1800 if i == 2 else 0
        mods.append(ev("jit_train_step(1)", t0, 2000 + slow))
        ops += [
            ev("%fusion.1", t0, 300, f"{BACK}/msa/qkv/dot_general"),
            ev("%fusion.2", t0 + 300, 200 + slow,
               f"{BLOCK}/msa/attn_core/bqhd,bkhd->bhqk/dot_general")]
        t0 += slow
        ops += [
            ev(flash, t0 + 500, 400, flash_scope),
            ev(mlp, t0 + 900, 500, mlp_scope),
            ev("%fusion.84.remat", t0 + 1400, 100,
               f"{BLOCK}/msa/attn_core/exp"),
            ev("%fusion.9", t0 + 1500, 100, "jit(train_step)/optimizer/add"),
            ev("%copy.3", t0 + 1600, 50, "")]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": mods},
        {"name": "XLA Ops", "events": ops}]}]}
    r = xplane.reduce_trace(trace, module_prefix="jit_train_step",
                            window_ns=(0, 14000))
    assert r["steps"] == 3 and r["mosaic_calls"] == 2
    ns = lambda x: pytest.approx(x * 1e-6)
    assert r["rows_ms"] == {
        "msa_qkv": {"backward": ns(300)},
        "attn_core": {"forward": ns(200), "backward": ns(400),
                      "recompute": ns(100)},
        "lnmlp_fwd": {"forward": ns(500)},
        "optimizer": {"optimizer": ns(100)},
        "other": {"forward": ns(50)}}
    assert r["mosaic_by_kernel_ms"] == {"flash_bwd_dq": ns(400),
                                        "lnmlp_fwd": ns(500)}
    assert r["xla_by_phase_ms"] == {"forward": ns(250), "backward": ns(300),
                                    "recompute": ns(100),
                                    "optimizer": ns(100)}
    assert (r["mosaic_ms"], r["xla_ms"], r["busy_ms"]) == (
        ns(900), ns(750), ns(1650))
    assert xplane.layer_ms(r["rows_ms"], *r["rows_ms"]) == ns(1650)
    assert "attn_core 0.001 (for 0.000 bac 0.000 rec 0.000)" in \
        xplane.format_rows(r)
    # The metric files are lookups in that table. Parent and change are
    # read by one definition: the flash kernel's time is the attention
    # core's, in no XLA metric and not the MLP kernel's.
    obs = {"trace": r, "train": {"batch_per_chip": 256}}
    assert attn_core_ms.read(obs) == ns(700)
    assert mlp_kernel_ms.read(obs) == ns(500)
    assert "flash_bwd_dq" in capsys.readouterr().out     # named, not counted
    assert mlp_kernel_fwd_ms.read(obs) == ns(500)
    assert mlp_kernel_bwd_ms.read(obs) == 0.0
    assert xla_ops_ms.read(obs) == ns(750)
    assert xla_backward_ms.read(obs) == ns(400)
    assert msa_proj_ms.read(obs) == ns(300)
    assert optimizer_ms.read(obs) == ns(100)
    assert other_ms.read(obs) == ns(50)
    # a layer that no op ran under reads 0, not nothing
    assert msa_glue_ms.read(obs) == ln_ms.read(obs) == \
        mlp_glue_ms.read(obs) == 0.0
    # ... and a trace without steps has no table: nothing is reported
    none = {"trace": xplane.reduce_trace(trace), "train": obs["train"]}
    assert none["trace"]["rows_ms"] == {}
    assert attn_core_ms.read(none) is None and other_ms.read(none) is None


def control(op, n, start, dur, scope=""):
    """A ``while`` / ``conditional`` / ``call`` as the op line names it:
    by its whole instruction, a tuple shape first."""
    return ev(f"%{op}.{n} = (s32[], f32[8,4]{{1,0}}) {op}((s32[], "
              f"f32[8,4]{{1,0}}) %tuple.{n}), condition=%cond.{n}, "
              f"body=%body.{n}", start, dur, scope)


def looped_trace(steps=4, chips=1):
    """Steps of 2000 ns (one every 2500) with control flow laid over
    ops, as the token cell's step has it since PR 28. In each step:

    * 0-100 a qkv fusion;
    * 100-700 a ``while`` under ``mlp`` over two XLA ops (100-300,
      500-690) and the MLP kernel (300-500): 10 ns of the loop's own at
      its end;
    * 700-800 a ``while`` whose body left no event: the device's work;
    * 800-1200 a ``conditional`` over a ``while`` (820-1180) over two
      backward fusions of the attention core (830-1000, 1000-1180);
    * 1200-1300 a ``call`` over one optimizer op (1205-1295);
    * 1300-1400 an all-reduce, 1350-1500 a fusion of no scope.

    Added up with the control flow the step reads 600 + 400 + 360 + 100
    = 1,460 ns too long; its busy time is 1,500 ns."""
    mlp, mlp_scope = kernel_call(
        "lnmlp_fwd.2", f"{BLOCK}/mlp/while/body/lnmlp_fwd/pallas_call")
    planes = []
    for chip in range(chips):
        mods, ops = [], []
        for i in range(steps):
            t = 2500 * i + 7 * chip
            mods.append(ev("jit_train_step(1)", t, 2000))
            ops += [
                ev("%fusion.1", t, 100, f"{BACK}/msa/qkv/dot_general"),
                control("while", 1, t + 100, 600, f"{BLOCK}/mlp/while"),
                ev("%fusion.2", t + 100, 200, f"{BLOCK}/mlp/while/body/add"),
                ev(mlp, t + 300, 200, mlp_scope),
                ev("%fusion.3", t + 500, 190, f"{BACK}/mlp/while/body/mul"),
                control("while", 2, t + 700, 100, f"{BLOCK}/mlp/while"),
                control("conditional", 3, t + 800, 400,
                        f"{BACK}/msa/attn_core/cond"),
                control("while", 4, t + 820, 360,
                        f"{BACK}/msa/attn_core/cond/branch_1_fun/while"),
                ev("%fusion.4", t + 830, 170, f"{BACK}/msa/attn_core/mul"),
                ev("%fusion.5", t + 1000, 180, f"{BACK}/msa/attn_core/dot"),
                control("call", 5, t + 1200, 100,
                        "jit(train_step)/optimizer/call"),
                ev("%fusion.6", t + 1205, 90,
                   "jit(train_step)/optimizer/add"),
                ev("%all-reduce.7 = f32[4,4]{1,0} all-reduce(f32[4,4]{1,0} "
                   "%fusion.1), replica_groups={{0,1}}", t + 1300, 100),
                ev("%fusion.8", t + 1350, 150, "")]
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops[::-1]}]})   # in any order
    return {"planes": planes}


def test_a_step_with_loops_is_read_once():
    trace = looped_trace()
    ops = trace["planes"][0]["lines"][1]["events"]
    kept = xplane.leaves(ops)
    names = lambda evs: {e["name"] for e in evs}
    # the control flow that encloses an op goes; the loop without a body
    # event stays, and so does every op
    assert names(ops) - names(kept) == {"call.5", "conditional.3",
                                        "while.1", "while.4"}
    assert len(kept) == len(ops) - 4 * 4
    r = xplane.reduce_trace(trace, module_prefix="jit_train_step")
    ns = lambda x: pytest.approx(x * 1e-6)
    assert r["steps"] == 3
    assert r["rows_ms"] == {
        "msa_qkv": {"backward": ns(100)},
        "mlp_xla": {"forward": ns(200 + 100), "backward": ns(190)},
        "lnmlp_fwd": {"forward": ns(200)},
        "attn_core": {"backward": ns(170 + 180)},
        "optimizer": {"optimizer": ns(90)},
        "other": {"forward": ns(150)},
        "collective": {"forward": ns(50)}}
    assert r["mosaic_by_kernel_ms"] == {"lnmlp_fwd": ns(200)}
    assert r["xla_by_phase_ms"] == {
        "forward": ns(200 + 100 + 150), "backward": ns(100 + 190 + 350),
        "optimizer": ns(90)}
    # the XLA ops' union holds no kernel under a loop any more
    assert (r["mosaic_ms"], r["xla_ms"]) == (ns(200), ns(1180))
    assert r["mosaic_calls"] == 1
    # the rows sum to the ops' time: 1,430 ns. The step's busy time is a
    # union, and keeps what is the loops' own (10 + 30 + 10 + 20 ns
    # under a loop and under no op of its body)
    assert xplane.layer_ms(r["rows_ms"], *r["rows_ms"]) == ns(1430)
    assert r["busy_ms"] == ns(1500)
    assert r["busy_s"] == pytest.approx(3 * 1500e-9)
    # ... and the list the driver's breakdown is made from names no loop
    # that has a body
    labels = [label for label, _ in r["device_ops"]]
    assert not any(label.startswith(("conditional", "call"))
                   for label in labels)
    assert [s for label, s in r["device_ops"]
            if label.startswith("while")] == [pytest.approx(3 * 100e-9)]
    obs = {"trace": r, "train": {"batch_per_chip": 1}}
    assert mlp_glue_ms.read(obs) == ns(490)
    assert xla_backward_ms.read(obs) == ns(640)
    assert xla_ops_ms.read(obs) == ns(1180)
    assert attn_core_ms.read(obs) == ns(350)
    assert attn_core_fwd_ms.read(obs) == 0.0
    assert attn_core_bwd_ms.read(obs) == ns(350)


def test_the_attention_core_by_direction_sums_to_the_core():
    """``attn_core_fwd_ms`` + ``attn_core_bwd_ms`` = ``attn_core_ms``,
    by the phase of the op's scope: on the by-hand step above (forward
    200, backward 400, recompute 100) and on the recorded one."""
    rows = {"attn_core": {"forward": 0.2, "backward": 0.4, "recompute": 0.1},
            "msa_qkv": {"backward": 0.3}}
    obs = {"trace": {"rows_ms": rows}, "train": {"batch_per_chip": 256}}
    assert attn_core_fwd_ms.read(obs) == pytest.approx(0.2)
    assert attn_core_bwd_ms.read(obs) == pytest.approx(0.5)
    # a step with no attention core reads 0, not nothing; no table,
    # nothing
    none = {"trace": {"rows_ms": {"msa_qkv": {"backward": 0.3}}},
            "train": obs["train"]}
    assert attn_core_fwd_ms.read(none) == attn_core_bwd_ms.read(none) == 0.0
    assert attn_core_bwd_ms.read({"trace": {"rows_ms": {}},
                                  "train": obs["train"]}) is None


@pytest.mark.parametrize("chips", [1, 2])
def test_the_two_readers_agree_row_by_row_on_a_step_with_loops(chips):
    """The trainer's reader (``telemetry/device_trace.py``, repaired in
    PR 28) and the benchmark's on the same synthetic events: the same
    rows, none of them holding a loop laid over its body."""
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    trace = looped_trace(steps=5, chips=chips)
    mine = xplane.reduce_trace(trace, module_prefix="jit_train_step")
    theirs = device_trace.reduce(trace)
    assert theirs["steps"] == mine["steps"] == 4
    table = {(r["layer"], r["phase"]): r["ms"] for r in theirs["rows"]}
    rows = {(layer, phase): ms for layer, by in mine["rows_ms"].items()
            for phase, ms in by.items()}
    assert set(rows) == set(table)
    for key, ms in table.items():
        assert rows[key] == pytest.approx(ms, rel=1e-9), key
    for key in ("step_ms", "mosaic_ms", "xla_ms", "collective_ms",
                "collective_exposed_ms"):
        assert mine[key] == pytest.approx(theirs[key], rel=1e-9), key
    # the trainer's busy time is the union of the ops it kept; the
    # benchmark's keeps the loops' own 70 ns
    assert mine["busy_ms"] - theirs["busy_ms"] == pytest.approx(70e-6)


def test_window_defaults_to_the_device_events_or_the_steps():
    r = xplane.reduce_trace(made_up_trace())
    assert r["window_s"] == pytest.approx(2200e-9)
    trace = made_up_trace()
    mods = trace["planes"][0]["lines"][0]["events"]
    mods.append(ev("jit_train_step(1)", 2400, 1000))
    r = xplane.reduce_trace(trace, module_prefix="jit_train_step")
    # Chip 0 now has three steps: its second one's start to its last
    # one's end (chip 1, with two, does not set the window).
    assert r["window_s"] == pytest.approx((3400 - 1200) * 1e-9)


def test_no_device_plane_reads_as_nothing():
    r = xplane.reduce_trace({"planes": [{"name": "/host:CPU", "lines": []}]})
    assert r["chips"] == 0 and r["busy_s"] == 0.0


@pytest.fixture(scope="module")
def recorded():
    """Four steps of the real ViT-B/16 bs 256 step on a v5e, recorded by
    this harness (PR 25: ``run.py --workload b16_train --trace 1
    --dump-events ... --dump-steps 4``): every op with the scope joined
    in from the step's optimized HLO, every Mosaic call with its
    kernel's name, and the harness's own host spans on the trace's
    clock."""
    return xplane.load_events_json(FIXTURE)


def b16_obs(trace):
    return {"train": {"batch_per_chip": 256},
            "model": harness.load_cell("b16_train")[1]["model"],
            "peak": flops.peaks("TPU v5 lite"),
            "trace": xplane.reduce_trace(trace,
                                         module_prefix="jit_train_step")}


def test_recorded_trace_from_the_chip(recorded):
    mods = [m for m in recorded["planes"][0]["lines"][0]["events"]
            if m["name"].startswith("jit_train_step")]
    assert len(mods) == 4
    # By default the window starts at the second step seen: three steps
    # back to back, the chip never idle for more than 0.1% of it.
    r = xplane.reduce_trace(recorded, module_prefix="jit_train_step")
    assert r["chips"] == 1 and r["steps"] == 3
    assert r["busy_s"] / r["window_s"] > 0.999
    assert r["mosaic_calls"] == 24          # 12 layers, forward + backward
    assert r["step_ms"] == pytest.approx(STEP_MS, abs=0.01)
    assert r["mosaic_ms"] == pytest.approx(110.74, abs=0.01)
    assert r["collective_ms"] == 0.0
    # Kernels + XLA ops account for the step's device duration: the
    # remainder (gaps between ops inside the program) is under 0.1%.
    assert (r["mosaic_ms"] + r["xla_ms"]) / r["step_ms"] == \
        pytest.approx(1.0, abs=1e-3)
    assert r["device_ops"][0][0].startswith(
        "lnmlp_bwd custom-call -> (bf16[50432,768], f32[1,768]")
    assert sorted(r["mosaic_by_kernel_ms"]) == ["lnmlp_bwd", "lnmlp_fwd"]
    assert {"bench.feed", "bench.wait_step"} <= {
        n for n, _, _ in xplane.host_spans(recorded)}
    # From the first step's start the window holds all four.
    lo = mods[0]["start_ns"]
    hi = mods[-1]["start_ns"] + mods[-1]["dur_ns"]
    assert xplane.reduce_trace(recorded, module_prefix="jit_train_step",
                               window_ns=(lo, hi))["steps"] == 4


# What the recorded step reads, metric by metric (ms per step; the two
# shares in %). PERF.md section 5 has the same table from the trainer's
# own capture of the same program.
STEP_MS = 296.77
TABLE = {
    attn_core_ms: 76.74, attn_core_roofline_pct: 17.75, msa_glue_ms: 20.69,
    msa_proj_ms: 58.41, ln_ms: 6.76, mlp_kernel_fwd_ms: 36.45,
    mlp_kernel_bwd_ms: 74.29, mlp_glue_ms: 14.38, optimizer_ms: 3.74,
    xla_backward_ms: 115.66, other_ms: 0.003, mlp_kernel_ms: 110.74,
    mlp_kernel_roofline_pct: 78.54, xla_ops_ms: 185.90}


@pytest.mark.parametrize("metric", TABLE, ids=lambda m: m.__name__.split(
    ".")[-1])
def test_recorded_step_reduced_to_the_table(recorded, metric):
    assert metric.read(b16_obs(recorded)) == pytest.approx(
        TABLE[metric], abs=0.01)


def test_recorded_steps_rows_sum_to_its_busy_time(recorded):
    obs = b16_obs(recorded)
    r = obs["trace"]
    rows = r["rows_ms"]
    assert xplane.layer_ms(rows, *rows) == pytest.approx(
        r["busy_ms"], rel=1e-3)
    assert mlp_kernel_fwd_ms.read(obs) + mlp_kernel_bwd_ms.read(obs) == \
        pytest.approx(mlp_kernel_ms.read(obs), rel=1e-4)
    # every layer of the table occurs in the real step; the backward of
    # the attention core is the larger part
    assert set(rows) >= {
        "msa_norm", "msa_qkv", "attn_core", "msa_out", "msa_glue",
        "mlp_xla", "block_glue", "patch_embed", "final_norm_head", "loss",
        "metrics", "optimizer", "lnmlp_fwd", "lnmlp_bwd"}
    assert rows["attn_core"]["backward"] > 2 * rows["attn_core"]["forward"]
    assert sum(r["xla_by_phase_ms"].values()) == pytest.approx(
        r["xla_ms"], rel=1e-3)


@pytest.fixture(scope="module")
def token_recorded():
    """Four steps of ``st21b_train_16k``'s step on one v5e chip (PR 30,
    recorded as the ViT fixtures were: ``run.py --workload
    st21b_train_16k --seed 3001 --trace 1 --dump-events ... --dump-steps
    4``): the first recorded step with loops that have work in them (the
    passes of ``ops/moe.py``, 32 ``while`` events a step) and with
    kernels outside the MLP (flash attention, the grouped products)."""
    return xplane.load_events_json(FIXTURE.with_name(
        "train_step_st21b_scoped.events.json.gz"))


def token_obs(trace):
    cell, config = harness.load_cell("st21b_train_16k")
    return {"train": {"batch_per_chip": 1}, "model": config["model"],
            "lm": {"seq_len": 16384}, "peak": flops.peaks("TPU v5 lite"),
            "trace": xplane.reduce_trace(trace,
                                         module_prefix="jit_train_step")}


def test_recorded_token_step_is_read_once(token_recorded):
    ops = token_recorded["planes"][0]["lines"][1]["events"]
    kept = xplane.leaves(ops)
    gone = [e for e in ops if e["op"] in xplane.CONTROL_FLOW]
    # every loop of this step has work in it: all 32 a step go
    assert len(gone) == 4 * 32 and len(kept) == len(ops) - len(gone)
    assert {e["op"] for e in gone} == {"while"}
    obs = token_obs(token_recorded)
    r = obs["trace"]
    assert r["steps"] == 3 and r["step_ms"] == pytest.approx(481.80, abs=.01)
    rows = r["rows_ms"]
    # what the loops laid over their bodies, a step: the 85.9 ms that
    # ``mlp_glue_ms`` and the rows' sum held twice from PR 28 to PR 29
    twice = sum(e["dur_ns"] for e in gone) / 4 / 1e6
    assert twice == pytest.approx(85.86, abs=0.01)
    assert xplane.layer_ms(rows, *rows) == pytest.approx(481.53, abs=0.01)
    assert xplane.layer_ms(rows, *rows) == pytest.approx(
        r["busy_ms"], rel=1e-3)
    assert r["busy_ms"] / r["step_ms"] > 0.999
    assert mlp_glue_ms.read(obs) == pytest.approx(126.48, abs=0.01)
    assert xla_ops_ms.read(obs) == pytest.approx(279.39, abs=0.01)
    assert xla_backward_ms.read(obs) == pytest.approx(127.66, abs=0.01)
    assert r["mosaic_ms"] + r["xla_ms"] == pytest.approx(r["busy_ms"],
                                                         rel=1e-3)
    assert other_ms.read(obs) < 0.01
    assert not any(label.startswith("while") for label, _ in
                   r["device_ops"])
    # the attention core by direction, whatever implements it: here the
    # three flash kernels and 16 ms of XLA
    by_kernel = r["mosaic_by_kernel_ms"]
    assert [round(by_kernel[k], 1) for k in (
        "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")] == [44.6, 46.6, 71.3]
    assert attn_core_fwd_ms.read(obs) == pytest.approx(47.52, abs=0.01)
    assert attn_core_bwd_ms.read(obs) == pytest.approx(130.94, abs=0.01)
    assert attn_core_fwd_ms.read(obs) + attn_core_bwd_ms.read(obs) == \
        pytest.approx(attn_core_ms.read(obs), rel=1e-9)
    assert lm_attn_core_roofline_pct.read(obs) == pytest.approx(37.97,
                                                                abs=0.01)
    assert lm_step_mfu_pct.read(obs) == pytest.approx(36.55, abs=0.01)


def test_the_two_readers_agree_on_the_recorded_token_step(token_recorded):
    """The trainer's table has a row for every kernel and for the routed
    layer's scopes (``TOKEN_LAYERS``); folded into the benchmark's
    layers it is the benchmark's table, phase by phase."""
    from pytorch_vit_paper_replication_tpu.telemetry import device_trace

    mine = xplane.reduce_trace(token_recorded,
                               module_prefix="jit_train_step")
    theirs = device_trace.reduce(token_recorded)
    assert theirs["steps"] == mine["steps"] == 3
    for key in ("step_ms", "mosaic_ms", "xla_ms"):
        assert mine[key] == pytest.approx(theirs[key], rel=1e-9), key
    # the benchmark's busy time keeps the loops' own (0.15 ms a step)
    assert 0 <= mine["busy_ms"] - theirs["busy_ms"] < 0.2
    fold = {"moe_router": "mlp_xla", "moe_dispatch": "mlp_xla",
            "moe_experts": "mlp_xla", "moe_combine": "mlp_xla",
            "moe_gmm_fwd": "mlp_xla", "moe_gmm_dx": "mlp_xla",
            "moe_gmm_dw": "mlp_xla", "flash_fwd": "attn_core",
            "flash_bwd_dq": "attn_core", "flash_bwd_dkv": "attn_core",
            "rope": "msa_glue", "token_embedding": "patch_embed",
            "head": "final_norm_head", "head_loss": "final_norm_head"}
    table = {}
    for row in theirs["rows"]:
        key = (fold.get(row["layer"], row["layer"]), row["phase"])
        table[key] = table.get(key, 0.0) + row["ms"]
    rows = {(layer, phase): ms for layer, by in mine["rows_ms"].items()
            for phase, ms in by.items()}
    assert set(rows) == set(table)
    for key, ms in table.items():
        # (a sum of medians against the median of a sum)
        assert rows[key] == pytest.approx(ms, rel=2e-3, abs=2e-3), key


def test_async_collective_span_counts_from_start_to_done():
    """An all-reduce in flight 100-700 (async line) under a fusion
    0-500, then its ``-done`` op waiting 500-700 with nothing else
    running: 600 ns of collective, 200 ns exposed."""
    ar = "%all-reduce-start.1 = f32[4]{0} all-reduce-start(f32[4]{0} %g)"
    done = "%all-reduce-done.1 = f32[4]{0} all-reduce-done(f32[4]{0} %s)"
    chip = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [ev("jit_train_step(1)", 0, 800)]},
        {"name": "XLA Ops", "events": [ev("fusion.1", 0, 500),
                                       ev(done, 500, 200),
                                       ev("fusion.2", 700, 100)]},
        {"name": "Async XLA Ops", "events": [ev(ar, 100, 600)]}]}
    r = xplane.reduce_trace({"planes": [chip]},
                            module_prefix="jit_train_step",
                            window_ns=(0, 800))
    assert r["collective_ms"] == pytest.approx(600e-6)
    assert r["collective_exposed_ms"] == pytest.approx(200e-6)
    assert r["xla_ms"] == pytest.approx(600e-6)
    assert r["busy_s"] == pytest.approx(800e-9)


def test_recorded_trace_of_four_chips():
    """Four steps of ``b16_train_dp4`` on a 2x2 v5e host (PR 25, recorded
    as the one-chip fixture was). The gradients are reduced by four
    synchronous all-reduces per step (bf16, three of them tuples), under
    which nothing else runs: all of their time is exposed, and it is the
    row ``collective``."""
    trace = xplane.load_events_json(FIXTURE.with_name(
        "train_step_b16_dp4_scoped.events.json.gz"))
    r = xplane.reduce_trace(trace, module_prefix="jit_train_step")
    assert r["chips"] == 4 and r["steps"] == 3
    assert r["mosaic_calls"] == 24
    assert r["step_ms"] == pytest.approx(298.73, abs=0.01)
    assert r["collective_ms"] == pytest.approx(3.02, abs=0.01)
    assert r["collective_exposed_ms"] == pytest.approx(r["collective_ms"])
    assert (r["mosaic_ms"] + r["xla_ms"] + r["collective_exposed_ms"]) \
        / r["step_ms"] == pytest.approx(1.0, abs=1e-3)
    assert r["busy_s"] / r["window_s"] > 0.999
    assert any(n == "bench.wait_step" for n, _, _ in
               xplane.host_spans(trace))
    rows = r["rows_ms"]
    assert rows["collective"] == {
        "forward": pytest.approx(r["collective_exposed_ms"])}
    assert xplane.layer_ms(rows, *rows) == pytest.approx(
        r["busy_ms"], rel=1e-3)
    # Under the mesh the kernel's calls are per shard (50,432 rows a
    # chip) and keep their names.
    obs = {"trace": r, "train": {"batch_per_chip": 256},
           "model": harness.load_cell("b16_train_dp4")[1]["model"],
           "peak": flops.peaks("TPU v5 lite")}
    # (a sum of per-kernel medians against the median of the sums)
    assert mlp_kernel_ms.read(obs) == pytest.approx(r["mosaic_ms"], rel=1e-4)
    assert r["mosaic_ms"] == pytest.approx(110.74, abs=0.01)
    assert attn_core_ms.read(obs) == pytest.approx(76.6, abs=0.1)
    assert attn_core_roofline_pct.read(obs) == pytest.approx(17.8, abs=0.05)
    assert other_ms.read(obs) < 0.1


def test_a_kernel_outside_the_mlp_counts_under_its_scopes_layer(recorded):
    """On the recorded B/16 step every Mosaic call is the MLP kernel. A
    flash-attention kernel put into each step (1 ms, under the scope
    ``attn_core``, as ``ops/flash_attention.py`` names it) is a Mosaic
    call and not this kernel: it stays out of the MLP layer's metrics
    and out of ``xla_ops_ms``, and is the attention core's time."""
    before = b16_obs(recorded)
    share = mlp_kernel_roofline_pct.read(before)
    flash = ('%flash_fwd.1 = (bf16[3072,200,64]{2,1,0}, f32[3072,1,200]'
             '{2,1,0}) custom-call(bf16[3072,200,64]{2,1,0} %q), '
             'custom_call_target="tpu_custom_call"')
    scope = f"{BLOCK}/msa/attn_core/flash_fwd/pallas_call"
    trace = {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": list(ln["events"])}
            for ln in p["lines"]]} for p in recorded["planes"]]}
    lines = {ln["name"]: ln["events"] for ln in trace["planes"][0]["lines"]}
    for m in lines["XLA Modules"]:
        if m["name"].startswith("jit_train_step"):
            lines["XLA Ops"].append(ev(flash, m["start_ns"] + 10, 1_000_000,
                                       scope))
    after = b16_obs(trace)
    assert after["trace"]["mosaic_calls"] == 25
    assert after["trace"]["mosaic_ms"] == pytest.approx(
        before["trace"]["mosaic_ms"] + 1.0, abs=1e-6)
    assert after["trace"]["mosaic_by_kernel_ms"]["flash_fwd"] == \
        pytest.approx(1.0)
    assert mlp_kernel_ms.read(after) == mlp_kernel_ms.read(before)
    assert mlp_kernel_roofline_pct.read(after) == pytest.approx(share)
    assert attn_core_ms.read(after) == pytest.approx(
        attn_core_ms.read(before) + 1.0)
    assert xla_backward_ms.read(after) == xla_backward_ms.read(before)
    assert after["trace"]["xla_by_phase_ms"] == \
        before["trace"]["xla_by_phase_ms"]


def test_trim_and_dump_round_trip(recorded, tmp_path):
    """How the fixtures were recorded (``run.py --dump-events
    --dump-steps``): ``trim`` keeps whole steps from the second one
    seen, and a dumped trace loads back as it was."""
    one = xplane.trim(recorded, module_prefix="jit_train_step", steps=1)
    mods = [m for m in one["planes"][0]["lines"][0]["events"]
            if m["name"].startswith("jit_train_step")]
    assert len(mods) == 1
    r = xplane.reduce_trace(one, module_prefix="jit_train_step",
                            window_ns=xplane._iv(mods[0]))
    assert r["steps"] == 1 and r["mosaic_calls"] == 24
    xplane.dump_events_json(one, tmp_path / "one.events.json.gz")
    assert xplane.load_events_json(tmp_path / "one.events.json.gz") == one
