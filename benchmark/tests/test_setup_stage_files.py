"""The four metrics that read the program's start-up stages
(``setup_imports_s``, ``setup_mesh_s``, ``setup_state_s``,
``setup_first_step_s``; PR 36): each file passes what ``test_files.py``
asks of a metric file, ``BENCHMARK.json`` lists each for every train
cell under ``setup_s``, each counts only a stage that ended before the
window opened and reads nothing from a program without the record (the
files are laid over the parent commit too), and a traced rehearsal of
``b16_train`` reports the four."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import harness
from benchmark.tests import test_files

STAGES = {"setup_imports_s": "imports", "setup_mesh_s": "mesh",
          "setup_state_s": "state", "setup_first_step_s": "first_step"}


@pytest.mark.parametrize("name", sorted(STAGES))
def test_the_file_and_its_entry(name):
    test_files.test_metric_file(name)
    mod = importlib.import_module(f"benchmark.metrics.{name}")
    assert (mod.KIND, mod.SOURCE, mod.MOVES) == (
        "per_layer", "program_span", "setup_s")
    entry = next(m for m in test_files.BENCHMARK["per_layer"]
                 if m["name"] == name)
    assert entry["workloads"] == [
        w["name"] for w in test_files.BENCHMARK["workloads"]]


def test_benchmark_json_still_agrees_with_the_files():
    test_files.test_benchmark_json_agrees_with_the_files()
    # appended: nothing that was there moved
    assert [m["name"] for m in test_files.BENCHMARK["per_layer"]][-4:] == [
        "setup_imports_s", "setup_mesh_s", "setup_state_s",
        "setup_first_step_s"]


@pytest.mark.parametrize("name", sorted(STAGES))
def test_a_stage_counts_if_it_ended_before_the_window_opened(name,
                                                             monkeypatch):
    from pytorch_vit_paper_replication_tpu import compile_cache

    mod = importlib.import_module(f"benchmark.metrics.{name}")
    stats = compile_cache.CacheStats()
    monkeypatch.setattr(compile_cache, "STATS", stats)
    assert mod.read({"setup_s": 30.0}) is None, "the stage never closed"
    now = [0.0]
    monkeypatch.setattr(compile_cache, "seconds_since_process_start",
                        lambda: now[0])
    for stage, at in (("imports", 6.0), ("mesh", 14.5), ("state", 17.0),
                      ("first_step", 29.0)):
        now[0] = at
        stats.close_stage(stage, at - 0.5)
    want = {"imports": 6.0, "mesh": 8.5, "state": 2.5, "first_step": 12.0}
    assert mod.read({"setup_s": 30.0}) == want[STAGES[name]]
    assert mod.read({}) is None and mod.read({"setup_s": None}) is None
    # a stage that ended after the window opened is not set-up's
    late = mod.read({"setup_s": 10.0})
    assert late == (6.0 if name == "setup_imports_s" else None)
    # a program from before the stages reports nothing and raises nothing
    monkeypatch.setattr(compile_cache, "STATS", object())
    assert mod.read({"setup_s": 30.0}) is None


def test_a_traced_rehearsal_of_b16_train_reports_the_four(tmp_path):
    done = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "b16_train", "--seed", str(2**31 + 36), "--seconds", "1",
         "--trace", "1", "--rehearsal"], capture_output=True, text=True,
        timeout=900, cwd=harness.ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert set(STAGES) <= set(result["metrics"])
    assert all(result["metrics"][name] == {"value": None, "unit": "s"}
               for name in STAGES), "a CPU run prints no time"
