"""The reducer's arithmetic, against values worked out by hand on a
made-up two-chip trace, and on the trace recorded on the chip."""

from pathlib import Path

import pytest

from benchmark.lib import flops, harness, kernels, xplane
from benchmark.metrics import mlp_kernel_ms, mlp_kernel_roofline_pct

FIXTURE = Path(__file__).resolve().parent.parent / "fixtures" / \
    "train_step_b16.events.json.gz"


def ev(name, start, dur):
    return {"name": name, "start_ns": start, "dur_ns": dur,
            **xplane.parse_hlo(name)}


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
        assert c["mosaic_by_out_ms"] == {
            "(bf16[8,4], f32[1,4])": pytest.approx(300e-6)}
        assert c["xla_ms"] == pytest.approx(500e-6)
        assert c["collective_ms"] == pytest.approx(300e-6)
        # 650-950, less 650-700 under the kernel and 900-950 under the
        # second fusion: 200 ns exposed.
        assert c["collective_exposed_ms"] == pytest.approx(200e-6)
        # mosaic + xla + exposed collective = the step's busy time.
        assert c["busy_ms"] == pytest.approx(1000e-6)
    assert r["device_ops"][0][0] == "fusion"       # 4 x 500 ns
    named = dict(xplane.attribute_gaps(r["gaps"], xplane.host_spans(
        made_up_trace())))
    # Chip 0's 1000-1200 gap lies under bench.feed (990-1210).
    assert named["bench.feed"] == pytest.approx(200e-9)
    assert sum(named.values()) == pytest.approx(400e-9)


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


def test_recorded_trace_from_the_chip():
    """Three steps of the real ViT-B/16 bs 256 step on a v5e (PR 22,
    ``--dump-events --dump-steps 3``; ops parsed, other lines dropped).
    The profiler slowed the host's feed in that run: between the first
    and the second step the chip waited 455.76 ms for its batch."""
    trace = xplane.load_events_json(FIXTURE)
    mods = [m for m in trace["planes"][0]["lines"][0]["events"]
            if m["name"].startswith("jit_train_step")]
    assert len(mods) == 3
    lo = mods[0]["start_ns"]
    hi = mods[-1]["start_ns"] + mods[-1]["dur_ns"]
    r = xplane.reduce_trace(trace, module_prefix="jit_train_step",
                            window_ns=(lo, hi))
    assert r["chips"] == 1 and r["steps"] == 3
    assert r["mosaic_calls"] == 24          # 12 layers, forward + backward
    assert r["step_ms"] == pytest.approx(296.554, abs=0.01)
    assert r["mosaic_ms"] == pytest.approx(110.74, abs=0.01)
    assert r["collective_ms"] == 0.0
    # Kernels + XLA ops account for the step's device duration: the
    # remainder (gaps between ops inside the program) is under 0.1%.
    assert (r["mosaic_ms"] + r["xla_ms"]) / r["step_ms"] == \
        pytest.approx(1.0, abs=1e-3)
    gap = mods[1]["start_ns"] - (mods[0]["start_ns"] + mods[0]["dur_ns"])
    assert gap == pytest.approx(455.76e6, abs=0.01e6)
    assert r["window_s"] - r["busy_s"] == pytest.approx(gap / 1e9, abs=2e-3)
    assert r["device_ops"][0][0].startswith("mlp custom-call -> (bf16[50432")
    named = dict(xplane.attribute_gaps(r["gaps"], xplane.host_spans(trace)))
    assert named["bench.wait_step"] == pytest.approx(gap / 1e9, abs=2e-3)
    # By default the window starts at the second step seen: two steps
    # back to back, the chip never idle for more than 0.1% of it.
    d = xplane.reduce_trace(trace, module_prefix="jit_train_step")
    assert d["steps"] == 2 and d["busy_s"] / d["window_s"] > 0.999


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
    """Two steps of ``b16_train_dp4`` on a 2x2 v5e host (PR 22). The
    gradients are reduced by four synchronous all-reduces per step (bf16,
    three of them tuples), under which nothing else runs: all of their
    time is exposed."""
    trace = xplane.load_events_json(FIXTURE.with_name(
        "train_step_b16_dp4.events.json.gz"))
    steps = [xplane._iv(m) for p in trace["planes"][:4]
             for m in p["lines"][0]["events"]
             if m["name"].startswith("jit_train_step")]
    r = xplane.reduce_trace(
        trace, module_prefix="jit_train_step",
        window_ns=(min(s for s, _ in steps), max(e for _, e in steps)))
    assert r["chips"] == 4 and r["steps"] == 2
    assert r["mosaic_calls"] == 24
    assert r["step_ms"] == pytest.approx(298.75, abs=0.05)
    assert r["collective_ms"] == pytest.approx(3.08, abs=0.05)
    assert r["collective_exposed_ms"] == pytest.approx(r["collective_ms"])
    assert (r["mosaic_ms"] + r["xla_ms"] + r["collective_exposed_ms"]) \
        / r["step_ms"] == pytest.approx(1.0, abs=1e-3)
    assert r["busy_s"] / r["window_s"] > 0.999
    assert any(n == "bench.wait_step" for n, _, _ in
               xplane.host_spans(trace))
    # Under the mesh the kernel's calls are per shard (``shard_map``
    # in their names, 50,432 rows a chip): still the MLP kernel's.
    obs = {"trace": r, "train": {"batch_per_chip": 256},
           "model": harness.load_cell("b16_train_dp4")[1]["model"]}
    assert mlp_kernel_ms.read(obs) == pytest.approx(r["mosaic_ms"])
    assert r["mosaic_ms"] == pytest.approx(110.74, abs=0.01)


def test_mlp_kernel_metric_counts_only_the_mlp_kernel():
    """On the recorded B/16 step every Mosaic call is the MLP kernel
    (forward returns ``[rows, 3072]``, backward ``[768, 3072]``). A
    flash-attention kernel put into each step is a Mosaic call and not
    this kernel: it stays out of the MLP layer's metrics."""
    trace = xplane.load_events_json(FIXTURE)
    obs = {"train": {"batch_per_chip": 256},
           "model": harness.load_cell("b16_train")[1]["model"],
           "peak": flops.peaks("TPU v5 lite")}
    obs["trace"] = xplane.reduce_trace(trace, module_prefix="jit_train_step")
    by_out = obs["trace"]["mosaic_by_out_ms"]
    assert sorted(by_out) == [
        "(bf16[50432,768], bf16[50432,3072])",
        "(bf16[50432,768], f32[1,768], f32[1,768], f32[768,3072], "
        "f32[1,3072], f32[3072,768], f32[1,768])"]
    assert sum(by_out.values()) == pytest.approx(obs["trace"]["mosaic_ms"])
    assert mlp_kernel_ms.read(obs) == pytest.approx(110.74, abs=0.01)
    share = mlp_kernel_roofline_pct.read(obs)
    assert share == pytest.approx(78.5, abs=0.1)

    flash = ('%flash.1 = (bf16[256,12,197,64]{3,2,1,0}, f32[3072,197]{1,0}) '
             'custom-call(bf16[256,12,197,64]{3,2,1,0} %q), '
             'custom_call_target="tpu_custom_call"')
    lines = {ln["name"]: ln["events"] for ln in trace["planes"][0]["lines"]}
    for m in lines["XLA Modules"]:
        if m["name"].startswith("jit_train_step"):
            lines["XLA Ops"].append(ev(flash, m["start_ns"] + 10, 1_000_000))
    obs["trace"] = xplane.reduce_trace(trace, module_prefix="jit_train_step")
    assert obs["trace"]["mosaic_calls"] == 25
    assert obs["trace"]["mosaic_ms"] == pytest.approx(111.74, abs=0.01)
    assert mlp_kernel_ms.read(obs) == pytest.approx(110.74, abs=0.01)
    assert mlp_kernel_roofline_pct.read(obs) == pytest.approx(share)


def test_trim_and_dump_round_trip(tmp_path):
    """How the fixtures were recorded (``run.py --dump-events
    --dump-steps``): ``trim`` keeps whole steps from the second one
    seen, and a dumped trace loads back as it was."""
    trace = xplane.load_events_json(FIXTURE)
    one = xplane.trim(trace, module_prefix="jit_train_step", steps=1)
    mods = [m for m in one["planes"][0]["lines"][0]["events"]
            if m["name"].startswith("jit_train_step")]
    assert len(mods) == 1
    r = xplane.reduce_trace(one, module_prefix="jit_train_step",
                            window_ns=xplane._iv(mods[0]))
    assert r["steps"] == 1 and r["mosaic_calls"] == 24
    xplane.dump_events_json(one, tmp_path / "one.events.json.gz")
    assert xplane.load_events_json(tmp_path / "one.events.json.gz") == one


@pytest.mark.parametrize("out,m,mine", [
    # As the chip's traces name them (PR 22): B/16, then L/16, whose
    # 96 x 197 = 18,912 rows the kernel pads to 18,944.
    ("(bf16[50432,768], bf16[50432,3072])", 3072, True),
    ("(bf16[50432,768], f32[1,768], f32[1,768], f32[768,3072], "
     "f32[1,3072], f32[3072,768], f32[1,768])", 3072, True),
    ("(bf16[18944,1024], bf16[18944,4096])", 4096, True),
    ("(bf16[18944,1024], f32[1,1024], f32[1,1024], f32[1024,4096], "
     "f32[1,4096], f32[4096,1024], f32[1,", 4096, True),
    # Flash attention at B/16: blocks and per-row statistics, and
    # batch x heads = 3072 rows is not a matrix of the hidden width.
    ("(bf16[256,12,197,64], f32[3072,197])", 3072, False),
    ("bf16[3072,577,64]", 3072, False)])
def test_which_mosaic_call_is_the_mlp_kernel(out, m, mine):
    assert kernels.is_mlp_half_block(out, m) is mine
