"""Cold-start subsystem tests (ISSUE 4, placement rule ISSUE 21): where
the persistent compile cache lives (jax's variable, else the flag, else
the checkout), cache-hit INSTRUMENTATION across fresh subprocesses (no
wall clocks), config fingerprints, warmup-manifest contracts, AOT warmup
observability, the cached-restart bit-identity extension of the serve
round trip, the coldstart bench harness, and the bench compact-gates
line-length bound."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from pytorch_vit_paper_replication_tpu import compile_cache
from pytorch_vit_paper_replication_tpu.serve.engine import (
    load_warmup_manifest, validate_warmup_manifest, write_warmup_manifest)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module", autouse=True)
def _cache_config_guard():
    """Leave the process cache-less after this module: later test files
    must not keep writing entries into this module's tmp dirs."""
    yield
    import jax

    jax.config.update("jax_compilation_cache_dir", None)
    try:
        from jax.experimental.compilation_cache import compilation_cache
        compilation_cache.reset_cache()
    except Exception:  # noqa: BLE001
        pass


# ----------------------------------------------------- fingerprint/salt
def test_config_fingerprint_stable_and_order_insensitive(tiny_config):
    a = compile_cache.config_fingerprint(tiny_config, x=1, y="b")
    b = compile_cache.config_fingerprint(tiny_config, y="b", x=1)
    assert a == b and len(a) == 64


def test_config_fingerprint_sensitive_to_config(tiny_config):
    base = compile_cache.config_fingerprint(tiny_config)
    assert base != compile_cache.config_fingerprint(
        tiny_config.replace(dtype="bfloat16"))
    assert base != compile_cache.config_fingerprint(
        tiny_config.replace(num_layers=3))


# ------------------------------------------------- where the cache lives
_WHERE = """
import json, sys
from pytorch_vit_paper_replication_tpu import compile_cache as C
returned = C.configure(sys.argv[1] if len(sys.argv) > 1 else None)
import jax
print(json.dumps({"jax": jax.config.jax_compilation_cache_dir,
                  "stats": C.STATS.cache_dir, "returned": str(returned)}))
"""


def _where(tmp_path, *flag, env_dir=None) -> dict:
    """configure() in a fresh process started from an unrelated cwd."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("XLA_FLAGS", None)
    env.pop(compile_cache.ENV_CACHE_DIR, None)
    if env_dir is not None:
        env[compile_cache.ENV_CACHE_DIR] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _WHERE, *flag],
                         capture_output=True, text=True, timeout=120,
                         env=env, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_jax_variable_places_the_cache_whatever_the_flag(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: the cache is exactly there and no
    code sets another directory — flag or no flag."""
    outside = tmp_path / "placed_from_outside"
    for flag in ([], [str(tmp_path / "from_flag")]):
        got = _where(tmp_path, *flag, env_dir=outside)
        assert got == {"jax": str(outside), "stats": str(outside),
                       "returned": str(outside)}
    assert not (tmp_path / "from_flag").exists()


def test_default_cache_is_the_checkout_from_any_cwd(tmp_path):
    """Variable unset, no flag: <checkout>/.jax_compile_cache, anchored
    to the package and not the cwd — every component of the path is the
    checkout's own, none a temp name, a pid or the time."""
    got = _where(tmp_path)
    want = REPO / ".jax_compile_cache"
    assert got == {"jax": str(want), "stats": str(want),
                   "returned": str(want)}
    assert compile_cache.DEFAULT_CACHE_DIR == want
    assert Path(got["jax"]).parts == REPO.parts + (".jax_compile_cache",)
    assert not (tmp_path / ".jax_compile_cache").exists()


def test_flag_places_the_cache_when_the_variable_is_unset(tmp_path):
    got = _where(tmp_path, str(tmp_path / "cc"))
    assert got["jax"] == got["stats"] == str(tmp_path / "cc")
    assert (tmp_path / "cc").is_dir()
    assert not list((tmp_path / "cc").iterdir())  # no salted subdir


def test_only_configure_sets_a_cache_directory():
    """train/serve/predict/probe/batch_infer (and everything else) go
    through configure(): no other source line touches jax's setting."""
    offenders = [
        str(f.relative_to(REPO))
        for root in ("pytorch_vit_paper_replication_tpu", "tools")
        for f in (REPO / root).rglob("*.py")
        if "jax_compilation_cache_dir" in f.read_text()
        and f.name != "compile_cache.py"]
    offenders += [f for f in ("bench.py", "chip_smoke.py")
                  if "jax_compilation_cache_dir" in (REPO / f).read_text()]
    assert offenders == []
    assert "VIT_COMPILE_CACHE_DIR" not in (
        REPO / "pytorch_vit_paper_replication_tpu" / "compile_cache.py"
    ).read_text()


def test_seconds_since_process_start_positive_and_monotonic():
    a = compile_cache.seconds_since_process_start()
    b = compile_cache.seconds_since_process_start()
    assert 0 < a <= b


# ------------------------------ what a program's first call cost (PR 24)
@pytest.fixture
def no_persistent_cache():
    """No cache directory for this test, whatever an earlier test of the
    same process (or the checkout's own ``.jax_compile_cache/``) left in
    force: a first call here is a compile, never a read."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_first_call_leaves_its_stages_under_the_programs_name(
        no_persistent_cache):
    import jax
    import jax.numpy as jnp

    compile_cache._install_listeners()

    def pr24_stage_probe(x):
        return jnp.tanh(x) * 3

    before = compile_cache.seconds_since_process_start()
    jax.block_until_ready(jax.jit(pr24_stage_probe)(jnp.ones(5)))
    row = compile_cache.STATS.snapshot()["programs"]["pr24_stage_probe"]
    assert row["count"] == 1
    assert row["trace"] > 0 and row["lower"] > 0 and row["backend"] > 0
    mine = [e for e in compile_cache.STATS._kept(None)
            if e[1] == "pr24_stage_probe"]
    assert [e[0] for e in mine][:3] == ["trace", "lower", "backend"]
    assert all(e[3] >= before for e in mine)
    assert "pr24_stage_probe x1 trace" in \
        compile_cache.STATS.programs_line(top=10_000)
    from pytorch_vit_paper_replication_tpu.telemetry import get_registry
    counters = get_registry().snapshot()["counters"]
    assert counters["compile_trace_seconds_total"] > 0
    assert counters["compile_backend_seconds_total"] > 0


def _stats_with(monkeypatch, events):
    """A CacheStats fed ``(stage, fun_name, seconds, end)`` by hand."""
    stats = compile_cache.CacheStats()
    for stage, fun_name, seconds, end in events:
        monkeypatch.setattr(compile_cache, "seconds_since_process_start",
                            lambda end=end: end)
        stats._on_stage(stage, fun_name, seconds)
    return stats


def test_stage_seconds_counts_nested_traces_once_and_stops_at_until_s(
        monkeypatch):
    stats = _stats_with(monkeypatch, [
        ("trace", "multiply", 0.5, 11.0),      # traced inside train_step's
        ("trace", "train_step", 3.0, 12.0),    # trace: 9.0 .. 12.0
        ("lower", "jit(train_step)", 1.0, 13.0),
        ("cache_read", None, 1.5, 14.5),       # named by what follows
        ("backend", "jit(train_step)", 2.0, 15.0),
        ("trace", "eval_step", 4.0, 40.0),     # after the window opened
        ("lower", "jit(eval_step)", 1.0, 41.0),
        ("backend", "jit(eval_step)", 9.0, 50.0)])
    assert stats.stage_seconds(until_s=20.0) == {
        "trace": 3.0, "lower": 1.0, "backend": 2.0, "cache_read": 1.5}
    everything = stats.stage_seconds()
    assert everything["trace"] == 7.0 and everything["backend"] == 11.0
    # the helper is a count on the row of the program whose trace it ran
    # inside, and no row: its seconds are part of that trace's
    programs = stats.programs(until_s=20.0)
    assert set(programs) == {"train_step"}
    assert programs["train_step"] == {"count": 1, "trace": 3.0,
                                      "lower": 1.0, "backend": 2.0,
                                      "cache_read": 1.5, "folded": 1}
    assert stats.programs()["eval_step"]["backend"] == 9.0
    assert len(stats._kept(None)) == 7, "one event a stage a program"
    line = stats.programs_line(until_s=20.0, top=1)
    assert line == ("train_step x1 trace 3.00 lower 1.00 backend 2.00 "
                    "(cache read 1.50; 1 folded)")
    assert stats.programs_line(top=1).endswith(   # costliest first
        "; 1 others trace 3.00 lower 1.00 backend 2.00")


def _a_program(name, begin, helpers=0):
    """The events of one program's first call from ``begin`` on: a trace
    of 4 s with ``helpers`` helper traces inside it, each reported
    before the trace that encloses it as jax reports them, then 1 s of
    lowering and 2 s in the backend."""
    inside = [("trace", "multiply", 1e-4, begin + (i + 1) * 1e-4)
              for i in range(helpers)]
    return inside + [("trace", name, 4.0, begin + 4.0),
                     ("lower", f"jit({name})", 1.0, begin + 5.0),
                     ("backend", f"jit({name})", 2.0, begin + 7.0)]


def test_a_long_run_does_not_push_the_set_ups_stages_out(monkeypatch):
    """PR 36: the ring of 8,192 events turned over on the helpers of the
    later traces (4,955 in one gradient of a token model) and the
    set-up's events, the oldest, were the ones dropped; the three set-up
    metrics then read 0.0 (ledger, PR 35, ``keye2_train_16k``)."""
    stats = _stats_with(monkeypatch, [
        *_a_program("make_state", 10.0, helpers=300),
        *_a_program("train_step", 20.0, helpers=20_000),
        *_a_program("reference", 100.0, helpers=20_000),     # after the
        *_a_program("eval_forward", 200.0, helpers=20_000)])  # window
    assert stats.dropped == 0
    assert len(stats._kept(None)) == 12
    assert stats.stage_seconds(until_s=30.0) == {
        "trace": 8.0, "lower": 2.0, "backend": 4.0, "cache_read": 0.0}
    rows = stats.programs(until_s=30.0)
    assert set(rows) == {"make_state", "train_step"}
    assert rows["make_state"]["folded"] == 300
    assert rows["train_step"] == {"count": 1, "trace": 4.0, "lower": 1.0,
                                  "backend": 2.0, "cache_read": 0.0,
                                  "folded": 20_000}
    assert "train_step x1 trace 4.00 lower 1.00 backend 2.00" in \
        stats.programs_line(until_s=30.0)
    assert "dropped" not in stats.programs_line()


def test_a_helper_of_a_helper_is_folded_with_it(monkeypatch):
    stats = _stats_with(monkeypatch, [
        ("trace", "square", 0.1, 10.3),        # inside norm's trace
        ("trace", "norm", 0.5, 10.6),          # inside step's trace
        ("trace", "add", 0.1, 10.9),
        ("trace", "step", 2.0, 12.0)])
    assert stats.programs() == {"step": {
        "count": 0, "trace": 2.0, "lower": 0.0, "backend": 0.0,
        "cache_read": 0.0, "folded": 3}}


def test_two_threads_events_are_not_folded_together(monkeypatch):
    """A warm-up thread's program that ends inside the interval of the
    main thread's trace did not run inside that trace: both stay, and
    the seconds they share count once."""
    import threading

    stats = compile_cache.CacheStats()

    def feed(stage, name, seconds, end):
        monkeypatch.setattr(compile_cache, "seconds_since_process_start",
                            lambda: end)
        stats._on_stage(stage, name, seconds)

    feed("trace", "multiply", 0.5, 11.0)
    other = threading.Thread(
        target=feed, args=("trace", "rung_8", 1.0, 11.5))
    other.start()
    other.join()
    feed("trace", "train_step", 3.0, 12.0)        # 9.0 .. 12.0
    rows = stats.programs()
    assert set(rows) == {"rung_8", "train_step"}
    assert rows["train_step"]["folded"] == 1 and rows["rung_8"]["folded"] == 0
    assert stats.stage_seconds()["trace"] == 3.0


def test_kept_stage_events_are_bounded(monkeypatch):
    monkeypatch.setattr(compile_cache, "MAX_STAGE_EVENTS", 16)
    stats = _stats_with(monkeypatch, [
        ("backend", f"jit(f{i})", 1.0, float(i)) for i in range(40)])
    assert len(stats._kept(None)) == 16
    assert stats.stage_seconds()["backend"] == 16.0
    assert set(stats.programs()) == {f"f{i}" for i in range(24, 40)}


def test_a_drop_past_the_bound_is_counted_and_shown(monkeypatch):
    from pytorch_vit_paper_replication_tpu.telemetry import (HELP_TEXT,
                                                             INSTRUMENTS,
                                                             get_registry)

    name = "compile_stage_events_dropped_total"
    assert INSTRUMENTS[name] == "counter" and name in HELP_TEXT
    before = get_registry().snapshot()["counters"].get(name, 0)
    monkeypatch.setattr(compile_cache, "MAX_STAGE_EVENTS", 16)
    stats = _stats_with(monkeypatch, [
        ("backend", f"jit(f{i})", 1.0, float(i)) for i in range(16)])
    assert stats.dropped == 0 and stats.snapshot()["dropped"] == 0
    assert "dropped" not in stats.programs_line()
    for i in range(16, 40):
        monkeypatch.setattr(compile_cache, "seconds_since_process_start",
                            lambda i=i: float(i))
        stats._on_stage("backend", f"jit(f{i})", 1.0)
    assert stats.dropped == 24 and stats.snapshot()["dropped"] == 24
    assert stats.programs_line().endswith(" (24 earlier events dropped)")
    assert get_registry().snapshot()["counters"][name] == before + 24
    # the oldest go first, whichever thread reported them
    assert min(e[3] for e in stats._kept(None)) == 24.0


# ------------------------------- the start-up's four stages (PR 36)
def test_the_programs_clock_is_the_benchmarks_to_the_tick():
    """Both count ``CLOCK_BOOTTIME`` from the process's start ticks: a
    stage's end and the benchmark's ``setup_s`` are instants of one
    clock (it was ``btime``, in whole seconds, before PR 36)."""
    from benchmark.lib import clock

    a = compile_cache.seconds_since_process_start()
    b = clock.since_process_start()
    c = compile_cache.seconds_since_process_start()
    assert a <= b <= c and c - a < 0.05


def test_startup_stages_close_once_and_in_order(monkeypatch):
    from pytorch_vit_paper_replication_tpu.telemetry import (HELP_TEXT,
                                                             INSTRUMENTS,
                                                             get_registry)

    assert list(compile_cache.STARTUP_STAGES) == [
        "imports", "mesh", "state", "first_step"]
    for gauge in compile_cache.STARTUP_STAGES.values():
        assert INSTRUMENTS[gauge] == "gauge" and gauge in HELP_TEXT
    stats = compile_cache.CacheStats()
    monkeypatch.setattr(compile_cache, "STATS", stats)
    now = [0.0]
    monkeypatch.setattr(compile_cache, "seconds_since_process_start",
                        lambda: now[0])

    def close(name, at, entered):
        now[0] = at
        return stats.close_stage(name, entered)

    assert stats.startup() == {} and stats.startup_line() == ""
    assert close("imports", 6.0, 5.9) == 6.0
    assert close("imports", 7.0, 6.5) == 7.0, "now, and nothing kept"
    assert close("state", 20.0, 19.0) == 20.0     # no mesh: a stage a
    assert close("mesh", 21.0, 20.5) == 21.0      # process never closed
    assert close("first_step", 50.0, 22.0) == 50.0   # is absent
    assert close("first_step", 90.0, 60.0) == 90.0
    startup = stats.startup()
    assert list(startup) == ["imports", "state", "first_step"]
    assert startup["imports"] == {"begin_s": 0.0, "end_s": 6.0,
                                  "seconds": 6.0,
                                  "own_s": pytest.approx(0.1)}
    assert startup["state"] == {"begin_s": 6.0, "end_s": 20.0,
                                "seconds": 14.0, "own_s": 1.0}
    assert startup["first_step"] == {"begin_s": 20.0, "end_s": 50.0,
                                     "seconds": 30.0, "own_s": 28.0}
    assert all(s["own_s"] <= s["seconds"] for s in startup.values())
    assert stats.snapshot()["startup"] == startup
    assert stats.startup_line() == ("imports 6.00 (own 0.10), state 14.00 "
                                    "(own 1.00), first_step 30.00 (own "
                                    "28.00)")
    gauges = get_registry().snapshot()["gauges"]
    assert gauges["startup_state_seconds"] == 14.0
    assert gauges["startup_first_step_seconds"] == 30.0

    # the decorator: the function's return closes the stage, a raise
    # closes nothing, and the function stays what it was
    fresh = compile_cache.CacheStats()
    monkeypatch.setattr(compile_cache, "STATS", fresh)

    @compile_cache.closes_startup_stage("mesh")
    def make(fail=False):
        """doc"""
        now[0] += 2.0
        if fail:
            raise ValueError("no mesh")
        return "made"

    now[0] = 10.0
    with pytest.raises(ValueError):
        make(fail=True)
    assert fresh.startup() == {}
    assert make() == "made" and make.__name__ == "make"
    assert fresh.startup() == {"mesh": {"begin_s": 0.0, "end_s": 14.0,
                                        "seconds": 14.0, "own_s": 2.0}}


_STARTUP = """
import json, sys
import jax, jax.numpy as jnp
from pytorch_vit_paper_replication_tpu import compile_cache, engine, parallel
from pytorch_vit_paper_replication_tpu.configs import (
    MeshConfig, TrainConfig, ViTConfig)
from pytorch_vit_paper_replication_tpu.metrics import MetricsLogger
from pytorch_vit_paper_replication_tpu.models import ViT
from pytorch_vit_paper_replication_tpu.optim import make_optimizer

compile_cache.configure(sys.argv[1])
mesh = parallel.make_mesh(MeshConfig())
model = ViT(ViTConfig(image_size=32, patch_size=8, num_layers=2, num_heads=2,
                      embedding_dim=32, mlp_size=64, num_classes=3,
                      dtype="float32", attention_impl="xla"))
tx = make_optimizer(TrainConfig(batch_size=8), 10)
make_state = lambda key, rng: engine.TrainState.create(
    apply_fn=model.apply, tx=tx, rng=rng,
    params=model.init(key, jnp.zeros((1, 32, 32, 3)))["params"])
state = jax.jit(make_state)(jax.random.key(0), jax.random.key(1))
state = parallel.shard_train_state(state, mesh)
step = parallel.make_parallel_train_step(state, mesh)
batch = {"image": jnp.ones((8, 32, 32, 3)), "label": jnp.zeros(8, jnp.int32)}
feed = lambda: (parallel.shard_batch(batch, mesh) for _ in range(2))
with MetricsLogger(sys.argv[2]) as logger:
    engine.train(state, feed, lambda: (), epochs=1, train_step=step,
                 eval_step=lambda *a: None, verbose=True, logger=logger)
from pytorch_vit_paper_replication_tpu.telemetry import get_registry
print("SNAPSHOT " + json.dumps({
    "cache": compile_cache.STATS.snapshot(),
    "registry": get_registry().snapshot()}))
"""


def test_a_trainers_start_leaves_its_four_stages(tmp_path):
    """``configure``, ``make_mesh``, ``make_parallel_train_step`` and two
    steps of ``engine.train`` in a fresh process: the ``[startup]`` line
    beside ``[programs]``, the four stages in ``snapshot()["startup"]``,
    as gauges and in the first-epoch row, ``time_to_first_step`` the end
    of ``first_step``, and no row of ``snapshot()["programs"]`` for a
    helper traced inside a program."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO))
    env.pop("XLA_FLAGS", None)
    env.pop(compile_cache.ENV_CACHE_DIR, None)
    jsonl = tmp_path / "m.jsonl"
    out = subprocess.run(
        [sys.executable, "-c", _STARTUP, str(tmp_path / "cc"), str(jsonl)],
        capture_output=True, text=True, timeout=600, env=env, cwd=tmp_path)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.splitlines()
    shown = next(ln for ln in lines if ln.startswith("[startup] "))
    assert lines.index(shown) + 1 == next(
        i for i, ln in enumerate(lines) if ln.startswith("[programs] "))
    assert [w for w in shown.replace(",", "").split()
            if w in compile_cache.STARTUP_STAGES] == list(
                compile_cache.STARTUP_STAGES)
    snap = json.loads(next(ln for ln in lines if ln.startswith(
        "SNAPSHOT ")).split(" ", 1)[1])
    startup = snap["cache"]["startup"]
    assert list(startup) == list(compile_cache.STARTUP_STAGES)
    ends = [0.0] + [s["end_s"] for s in startup.values()]
    assert [s["begin_s"] for s in startup.values()] == ends[:-1]
    assert all(0 <= s["own_s"] <= s["seconds"] for s in startup.values())
    # the step is traced, lowered and compiled inside engine.train
    assert startup["first_step"]["own_s"] > 0.5 * \
        startup["first_step"]["seconds"]
    for name, gauge in compile_cache.STARTUP_STAGES.items():
        assert snap["registry"]["gauges"][gauge] == pytest.approx(
            startup[name]["seconds"], abs=1e-3)
    row = json.loads(jsonl.read_text().splitlines()[0])
    assert row["time_to_first_step"] == pytest.approx(
        startup["first_step"]["end_s"], abs=1e-3)
    assert f"time_to_first_step: {row['time_to_first_step']:.2f}s" in \
        out.stdout
    assert sum(row[f"startup_{name}_s"] for name in startup) == \
        pytest.approx(row["time_to_first_step"], abs=5e-3)
    # one row a top-level program: the helpers a trace called (jitted
    # jnp functions, thousands in a step) are its ``folded`` count
    programs = snap["cache"]["programs"]
    assert programs["train_step"]["count"] == 1
    assert programs["train_step"]["folded"] > 100
    assert not {"multiply", "subtract", "_where", "true_divide"} \
        & set(programs)
    # (``add`` stays: ``engine._accumulate`` adds the steps' metrics
    # eagerly, a program of its own.) A helper has a trace and no more.
    assert all(r["lower"] > 0 and r["count"] >= 1 for r in programs.values())
    assert snap["cache"]["dropped"] == 0
    # (the costliest program comes first there: at this size the
    # initialiser or the step)
    assert " x1 trace " in next(
        ln for ln in lines if ln.startswith("[programs] "))


def test_setup_metrics_read_the_stages_up_to_the_window_with_a_second_of_slack(
        monkeypatch, capsys):
    """The benchmark's clock and the program's both count from process
    start but may differ by up to a second (``btime`` is whole seconds):
    the three set-up metrics allow that second and no more."""
    from benchmark.metrics import (setup_backend_s, setup_cache_read_s,
                                   setup_trace_lower_s)

    stats = _stats_with(monkeypatch, [
        ("trace", "train_step", 3.0, 20.0),
        ("lower", "jit(train_step)", 1.0, 21.0),
        ("cache_read", None, 0.25, 21.5),
        ("backend", "jit(train_step)", 2.0, 30.9),   # 0.9 s "late"
        ("backend", "jit(eval_step)", 0.5, 31.5)])   # after the window
    monkeypatch.setattr(compile_cache, "STATS", stats)
    assert setup_trace_lower_s.read({"setup_s": 30.0}) == 4.0
    assert capsys.readouterr().out == "", "a rehearsal prints no time"
    obs = {"setup_s": 30.0, "peak": {"bf16_tflops": 197.0}}
    assert setup_trace_lower_s.read(obs) == 4.0
    assert setup_backend_s.read(obs) == 2.0
    assert setup_cache_read_s.read(obs) == 0.25
    out = capsys.readouterr().out
    assert out.startswith("[programs] ") and "train_step x1" in out \
        and "eval_step" not in out
    # a program from before the counters reports nothing, and raises
    # nothing (the benchmark's files are laid over the parent commit too)
    monkeypatch.setattr(compile_cache, "STATS", object())
    assert setup_trace_lower_s.read(obs) is None
    assert setup_backend_s.read(obs) is None
    assert setup_cache_read_s.read(obs) is None


def test_warn_if_uncached_fires_once_on_tpu(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(compile_cache, "_warned_uncached", False)
    # No cache configured at all for this check.
    old = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    try:
        with pytest.warns(UserWarning, match="JAX_COMPILATION_CACHE_DIR"):
            compile_cache.warn_if_uncached("test")
        # second call: silent (warn ONCE per process)
        compile_cache.warn_if_uncached("test")
    finally:
        jax.config.update("jax_compilation_cache_dir", old)


def test_no_warn_on_cpu_backend(monkeypatch, recwarn):
    monkeypatch.setattr(compile_cache, "_warned_uncached", False)
    compile_cache.warn_if_uncached("test")  # backend here IS cpu
    assert not [w for w in recwarn.list
                if "JAX_COMPILATION_CACHE_DIR" in str(w.message)]


# --------------------------------------- cross-process hit instrumentation
_CHILD = """
import json, sys
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from pytorch_vit_paper_replication_tpu import compile_cache as C
C.configure(sys.argv[1])
f = jax.jit(lambda x: (x @ x.T).sum())
f(jnp.ones((128, 128))).block_until_ready()
print(json.dumps(C.STATS.snapshot()))
"""


def _run_child(script_path, cache_dir) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, str(script_path), str(cache_dir)],
        capture_output=True, text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_second_process_hits_cache(tmp_path):
    """The satellite's contract, asserted via instrumentation (hit/miss
    counters), not wall clock: the same program in a FRESH process hits
    every entry; another directory starts cold."""
    script = tmp_path / "child.py"
    script.write_text(_CHILD.format(repo=str(REPO)))
    cold = _run_child(script, tmp_path / "cc")
    assert cold["hits"] == 0 and cold["requests"] >= 1
    assert cold["cache_dir"] == str(tmp_path / "cc")
    warm = _run_child(script, tmp_path / "cc")
    assert warm["requests"] >= 1
    assert warm["hits"] == warm["requests"] and warm["misses"] == 0
    # saved = stored compile time - retrieval time: can be slightly
    # NEGATIVE for sub-ms modules, so only assert it was recorded.
    assert isinstance(warm["compile_time_saved_s"], float)
    elsewhere = _run_child(script, tmp_path / "cc2")
    assert elsewhere["hits"] == 0


# ------------------------------------------------------ warmup manifest
def test_warmup_manifest_round_trip(tmp_path):
    p = write_warmup_manifest(tmp_path, fingerprint="abc",
                              buckets=(8, 1, 32), image_size=224,
                              dtype="bfloat16")
    assert p.name == "warmup.json"
    m = load_warmup_manifest(tmp_path)
    assert m["buckets"] == [1, 8, 32] and m["fingerprint"] == "abc"
    assert validate_warmup_manifest(
        m, fingerprint="abc", buckets=(1, 8, 32),
        image_size=224) == [1, 8, 32]
    assert load_warmup_manifest(tmp_path / "nope") is None


def test_warmup_manifest_rejects_fingerprint_mismatch(tmp_path):
    write_warmup_manifest(tmp_path, fingerprint="abc", buckets=(1, 8),
                          image_size=224, dtype="bfloat16")
    m = load_warmup_manifest(tmp_path)
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        validate_warmup_manifest(m, fingerprint="OTHER", buckets=(1, 8),
                                 image_size=224)
    with pytest.raises(ValueError, match="image_size"):
        validate_warmup_manifest(m, fingerprint="abc", buckets=(1, 8),
                                 image_size=384)


def test_warmup_manifest_refuses_ladder_disagreeing_with_plan_buckets(
        tmp_path):
    """A manifest rung plan_buckets would never dispatch on this ladder
    (5 pads to 8; 64 exceeds the top rung) is refused, not warmed."""
    write_warmup_manifest(tmp_path, fingerprint="abc", buckets=(1, 5),
                          image_size=224, dtype="bfloat16")
    with pytest.raises(ValueError, match="plan_buckets"):
        validate_warmup_manifest(load_warmup_manifest(tmp_path),
                                 fingerprint="abc", buckets=(1, 8),
                                 image_size=224)
    write_warmup_manifest(tmp_path, fingerprint="abc", buckets=(64,),
                          image_size=224, dtype="bfloat16")
    with pytest.raises(ValueError, match="plan_buckets"):
        validate_warmup_manifest(load_warmup_manifest(tmp_path),
                                 fingerprint="abc", buckets=(1, 8, 32),
                                 image_size=224)


def test_corrupt_manifest_guided_refusal_and_atomic_write(tmp_path):
    """A tampered/torn warmup.json refuses with delete-it guidance, not
    a raw JSONDecodeError traceback; our own writer can't produce one
    (temp-file + atomic replace, no .tmp debris left behind)."""
    (tmp_path / "warmup.json").write_text('{"fingerprint": "abc", "buck')
    with pytest.raises(ValueError, match="delete"):
        load_warmup_manifest(tmp_path)
    (tmp_path / "warmup.json").write_text("[1, 2]")
    with pytest.raises(ValueError, match="JSON object"):
        load_warmup_manifest(tmp_path)
    write_warmup_manifest(tmp_path, fingerprint="abc", buckets=(1,),
                          image_size=224, dtype="bfloat16")
    assert load_warmup_manifest(tmp_path)["buckets"] == [1]
    assert list(tmp_path.glob("*.tmp*")) == []


def test_configure_refuses_file_as_cache_dir(tmp_path):
    """The misparse symptom — a positional swallowed into
    --compile-cache-dir — dies with a diagnosis, not NotADirectoryError."""
    img = tmp_path / "img.jpg"
    img.write_bytes(b"\xff\xd8")
    with pytest.raises(ValueError, match="swallowed"):
        compile_cache.configure(str(img))


# ------------------------------------- engine: AOT warmup + cached restart
@pytest.fixture(scope="module")
def tiny_ckpt(tmp_path_factory):
    """A ViT-Ti/16@32 params export + transform.json, the from_checkpoint
    contract without the cost of a CLI training run."""
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu.checkpoint import save_model
    from pytorch_vit_paper_replication_tpu.configs import PRESETS
    from pytorch_vit_paper_replication_tpu.models import ViT

    cfg = PRESETS["ViT-Ti/16"](num_classes=3, image_size=32)
    model = ViT(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 32, 32, 3)))["params"]
    root = tmp_path_factory.mktemp("cs_ckpt")
    save_model(params, root, "final")
    (root / "transform.json").write_text(json.dumps(
        {"image_size": 32, "pretrained": False, "normalize": False}))
    return root, model, params


def test_cached_restart_engine_bit_identical_and_observable(
        tiny_ckpt, tmp_path):
    """The acceptance-criteria extension of the serve round trip: a
    SECOND engine built from the same checkpoint with the persistent
    cache enabled (a) really deserializes its rung executables from the
    cache (hit counters — not wall clock), (b) consumes the warmup
    manifest the first serve wrote, and (c) serves probs bit-identical
    to predict_image."""
    from pytorch_vit_paper_replication_tpu.predictions import predict_image
    from pytorch_vit_paper_replication_tpu.serve import InferenceEngine

    ckpt, model, params = tiny_ckpt
    compile_cache.configure(str(tmp_path / "cache"))
    assert load_warmup_manifest(ckpt) is None
    with InferenceEngine.from_checkpoint(
            ckpt, preset="ViT-Ti/16", num_classes=3, buckets=(1, 2),
            max_wait_us=500) as e1:
        snap = e1.snapshot()
    # first serve wrote the manifest; per-rung timings are observable
    manifest = load_warmup_manifest(ckpt)
    assert manifest["buckets"] == [1, 2]
    assert set(snap["warmup"]["rungs"]) == {"1", "2"}
    assert snap["warmup"]["done"] and snap["warmup"]["cumulative_s"] > 0
    assert snap["compile_cache"]["requests"] >= 2
    assert snap["warm_rungs"] == [1, 2]

    hits_before = compile_cache.STATS.hits
    with InferenceEngine.from_checkpoint(
            ckpt, preset="ViT-Ti/16", num_classes=3, buckets=(1, 2),
            max_wait_us=500) as e2:
        # the restart consumed the manifest's rung set from disk...
        assert e2._warmup_rungs == (1, 2)
        # ...its executables came from the persistent cache...
        assert compile_cache.STATS.hits - hits_before >= 2
        # ...and the numerics are untouched: bit-identical probs.
        import jax
        img = np.asarray(jax.random.uniform(jax.random.key(1), (32, 32, 3)),
                         np.float32)
        _, _, probs_ref = predict_image(model, params, img,
                                        ["a", "b", "c"], image_size=32)
        result = e2.submit(img).result(timeout=60)
        np.testing.assert_array_equal(result.probs, probs_ref)
        assert e2.snapshot()["time_to_first_batch_s"] > 0


def test_engine_refuses_manifest_from_other_model(tiny_ckpt, tmp_path):
    """from_checkpoint validates the on-disk manifest against THIS
    engine's fingerprint/ladder before warming anything."""
    import shutil

    from pytorch_vit_paper_replication_tpu.serve import InferenceEngine

    ckpt, _, _ = tiny_ckpt
    clone = tmp_path / "ckpt_clone"
    shutil.copytree(ckpt, clone)
    m = load_warmup_manifest(clone) or {}
    # write_warmup_manifest resolves the final/ subdir exactly like the
    # engine's read path, so the tampered file is the one it loads
    write_warmup_manifest(clone, fingerprint="someone-elses-model",
                          buckets=m.get("buckets", [1, 2]),
                          image_size=32, dtype="bfloat16")
    with pytest.raises(ValueError, match="fingerprint mismatch"):
        InferenceEngine.from_checkpoint(clone, preset="ViT-Ti/16",
                                        num_classes=3, buckets=(1, 2),
                                        warmup=False)


def test_manifest_extends_with_dispatched_rungs(tiny_ckpt, tmp_path):
    """close() unions traffic-dispatched rungs into the manifest, so a
    widened ladder converges to warm on the next restart instead of
    fossilizing on the first serve's shape set — and the manifest is
    one file whether the checkpoint is addressed as the run dir or its
    final/ export."""
    import shutil

    from pytorch_vit_paper_replication_tpu.serve import InferenceEngine

    src, _, _ = tiny_ckpt
    ckpt = tmp_path / "ckpt"
    shutil.copytree(src, ckpt)
    for d in (ckpt, ckpt / "final"):
        (d / "warmup.json").unlink(missing_ok=True)
    eng = InferenceEngine.from_checkpoint(
        ckpt, preset="ViT-Ti/16", num_classes=3, buckets=(1, 2),
        warmup=False)
    assert load_warmup_manifest(ckpt) is None  # warmup=False: no write
    eng.stats.observe_batch(2, 2)  # traffic rides rung 2
    eng.close()
    m = load_warmup_manifest(ckpt)
    assert m["buckets"] == [2]
    # run-dir and final/ spellings resolve to the SAME manifest file
    assert load_warmup_manifest(ckpt / "final") == m
    assert not (ckpt / "warmup.json").exists()
    # a corrupt manifest doesn't crash manifest upkeep — it is repaired
    # from the dispatched set instead
    (ckpt / "final" / "warmup.json").write_text("{torn")
    eng._extend_manifest()
    assert load_warmup_manifest(ckpt)["buckets"] == [2]


def test_background_warmup_serves_before_ladder_finishes(tiny_ckpt):
    """warmup="async": submit() is servable immediately (jit fallback /
    early rungs) and the ladder converges to fully warm."""
    from pytorch_vit_paper_replication_tpu.serve import InferenceEngine as Eng

    ckpt, model, params = tiny_ckpt
    eng = Eng(model, params, image_size=32, class_names=["a", "b", "c"],
              buckets=(1, 2), warmup="async", max_wait_us=500)
    try:
        img = np.zeros((32, 32, 3), np.float32)
        r = eng.submit(img).result(timeout=60)
        assert r.probs.shape == (3,)
        assert eng.wait_warm(60)
        assert sorted(eng._compiled) == [1, 2]
        assert eng._warmup_error is None
    finally:
        eng.close()


# -------------------------------------------------- coldstart harness
def test_coldstart_serve_child_cold_then_warm(tiny_ckpt, tmp_path):
    """The tools/coldstart_bench.py serve leg end to end at smoke scale
    (two fresh subprocesses, one rung): run 1 misses and compiles, run 2
    hits — asserted on the children's own instrumentation."""
    import importlib.util
    import shutil

    spec = importlib.util.spec_from_file_location(
        "coldstart_bench", REPO / "tools" / "coldstart_bench.py")
    cb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cb)

    # Own manifest-free checkpoint copy: the module fixture's manifest
    # records a (1, 2) ladder, this smoke leg serves ladder (1,).
    src, _, _ = tiny_ckpt
    ckpt = tmp_path / "ckpt"
    shutil.copytree(src, ckpt)
    for d in (ckpt, ckpt / "final"):  # either manifest spelling
        (d / "warmup.json").unlink(missing_ok=True)
    cold = cb._run_serve_child(ckpt, tmp_path / "cc", buckets="1",
                               num_classes=3, timeout_s=300)
    warm = cb._run_serve_child(ckpt, tmp_path / "cc", buckets="1",
                               num_classes=3, timeout_s=300)
    assert cold["compile_cache"]["hits"] == 0
    assert cold["compile_cache"]["misses"] >= 1
    assert warm["compile_cache"]["hits"] >= 1
    assert warm["compile_cache"]["misses"] == 0
    for leg in (cold, warm):
        assert leg["time_to_all_buckets_warm_s"] > 0
        assert leg["time_to_first_batch_s"] > 0
        assert leg["warmup"]["done"] and leg["warm_rungs"] == [1]


@pytest.mark.slow
def test_coldstart_full_harness(tmp_path):
    """The full train+serve A/B at artifact scale (minutes of fresh
    subprocesses) — the committed evidence path, excluded from tier-1."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "coldstart_bench", REPO / "tools" / "coldstart_bench.py")
    cb = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cb)
    result = cb.run_coldstart(workdir=tmp_path)
    assert result["cs_train_cold_s"] > 0 and result["cs_serve_cold_s"] > 0
    assert result["serve"]["warm"]["compile_cache"]["hits"] >= 3


# ------------------------------------------------ bench compact line
def test_compact_gates_line_stays_bounded():
    """The r8 satellite: the final compact line — headline + EVERY gate
    key bench.py can emit (scraped from its source, so a future gate
    can't silently outgrow the bound) + the cs_*/telemetry/bi_*
    extras — fits the driver's tail-capture budget (<=900 chars since
    r18; the capture is 2000, the bound protects >2x headroom)."""
    import importlib.util
    import re

    spec = importlib.util.spec_from_file_location("bench_mod",
                                                  REPO / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    src = (REPO / "bench.py").read_text()
    gate_keys = set(re.findall(r'"([a-z0-9_]+_ok)"', src))
    assert "cold_start_ok" in gate_keys  # the r8 gate rides the line
    assert "telemetry_overhead_ok" in gate_keys  # the r9 gate rides too
    assert "batch_infer_ok" in gate_keys  # the r11 gate rides too
    assert "fleet_serve_ok" in gate_keys  # the r13 gate rides too
    assert "elastic_ok" in gate_keys  # the r14 gate rides too
    assert "multihead_ok" in gate_keys  # the r14 multihead gate too
    assert "search_ok" in gate_keys  # the r15 search gate rides too
    assert "autoscale_ok" in gate_keys  # the r16 autoscale gate too
    assert "deploy_ok" in gate_keys  # the r17 flywheel gate rides too
    assert "cascade_ok" in gate_keys  # the r18 cascade gate rides too
    payload = {"value": 8857.13, "mfu": 0.4693, "tflops": 92.45}
    for k in gate_keys:
        payload[k] = False
    for k in bench.COMPACT_EXTRA_KEYS:
        payload[k] = 8888.888  # worst-case width for the seconds fields
    line = bench.compact_gates_line(payload)
    assert len(line) <= 900
    parsed = json.loads(line)
    assert parsed["cold_start_ok"] is False
    assert parsed["cs_serve_cold_s"] == 8888.888
    assert parsed["telemetry_overhead_pct"] == 8888.888
    assert parsed["bi_vs_train"] == 8888.888
    assert parsed["cascade_speedup"] == 8888.888  # r18 evidence rides too
    assert parsed["cascade_agreement"] == 8888.888

    # r9 satellite: the telemetry subsystem's instrument/row names must
    # never collide with the JSONL vocabulary the repo already emits
    # (engine.train metric rows, ServeStats.emit rows) — a merged
    # stream must stay attributable by key alone. The row spine
    # (time/step/epoch) is deliberately shared.
    from pytorch_vit_paper_replication_tpu.telemetry import (INSTRUMENTS,
                                                             ROW_KEYS)
    existing_jsonl_keys = {
        # engine.train -> MetricsLogger rows
        "time", "step", "epoch", "train_loss", "train_acc", "test_loss",
        "test_acc", "images_per_sec", "grad_norm", "skipped_steps", "lr",
        "time_to_first_step", "compile_cache_hits",
        "compile_cache_misses", "startup_imports_s", "startup_mesh_s",
        "startup_state_s", "startup_first_step_s",
        # ServeStats.emit flattened rows
        "submitted", "completed", "rejected_queue_full", "expired",
        "batches", "padded_rows", "degraded_batches", "warmup_total_s",
        "time_to_first_batch_s",
    } | {f"lat_{leg}_{q}" for leg in ("queue", "device", "total")
         for q in ("p50", "p95", "p99", "count")}
    telemetry_keys = set(INSTRUMENTS) | set(ROW_KEYS)
    shared_spine = {"time", "step", "epoch"}
    collisions = telemetry_keys & (existing_jsonl_keys - shared_spine)
    assert not collisions, (
        f"telemetry names collide with existing JSONL keys: {collisions}")

    # r10 satellite: the Prometheus renderer grew # HELP metadata — the
    # SAMPLE names must stay exactly the r9 ones (dashboards/scrape
    # configs key on them). Render a representative registry and assert
    # the name grammar byte-for-byte.
    from pytorch_vit_paper_replication_tpu.telemetry import (
        TelemetryRegistry)
    reg = TelemetryRegistry()
    reg.count("tel_steps_total", 3)
    reg.set_counter("serve_completed_total", 7)
    reg.gauge("serve_latency_total_p99_s", 0.078)
    for v in (0.1, 0.2, 0.3):
        reg.observe("tel_step_s", v)
    text = reg.to_prometheus()
    stable_samples = (
        "vit_tel_steps_total 3",
        "vit_serve_completed_total 7",
        "vit_serve_latency_total_p99_s 0.078",
        'vit_tel_step_s{quantile="0.5"} 0.2',
        'vit_tel_step_s{quantile="0.95"} ',
        'vit_tel_step_s{quantile="0.99"} ',
        "vit_tel_step_s_count 3",
        "vit_tel_step_s_sum ",
    )
    for sample in stable_samples:
        assert sample in text, f"stable sample name lost: {sample!r}"
    # And every metric now carries HELP + TYPE metadata.
    for name in ("vit_tel_steps_total", "vit_serve_completed_total",
                 "vit_tel_step_s"):
        assert f"# HELP {name} " in text
        assert f"# TYPE {name} " in text


def test_train_cli_logs_time_to_first_step(tmp_path):
    """The run-log field the coldstart bench consumes: a real (tiny)
    train run writes time_to_first_step to its metrics JSONL exactly
    once, on the first epoch record."""
    from pytorch_vit_paper_replication_tpu.train import main as train_main

    jsonl = tmp_path / "m.jsonl"
    train_main([
        "--synthetic", "--preset", "ViT-Ti/16", "--image-size", "32",
        "--patch-size", "16", "--dtype", "float32", "--attention", "xla",
        "--epochs", "2", "--batch-size", "8", "--synthetic-per-class", "4",
        "--num-workers", "1", "--metrics-jsonl", str(jsonl),
        "--compile-cache-dir", str(tmp_path / "cache")])
    records = [json.loads(line) for line in
               jsonl.read_text().splitlines() if line.strip()]
    ttfs = [r for r in records if "time_to_first_step" in r]
    assert len(ttfs) == 1 and ttfs[0]["epoch"] == 1
    assert ttfs[0]["time_to_first_step"] > 0
    # the cache dir named by the flag received the entries, unsalted
    assert list((tmp_path / "cache").glob("*-cache"))
