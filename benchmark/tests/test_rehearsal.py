"""``run.py --rehearsal`` walks every driver end to end on the CPU (the
four-chip cell on four virtual devices); the last line has the
contract's keys and no others, and no time or rate. And a new cell and a
new metric need only new files and new ``BENCHMARK.json`` entries, and
change no cell they do not name: shown on a throw-away copy."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import harness

KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
CELLS = sorted(p.stem for p in (harness.BENCH / "workloads").glob("*.json"))


@pytest.fixture(scope="session")
def cache_dir(tmp_path_factory):
    """The rehearsals' compile cache: placed from outside, as a harness
    may, so that the checkout's own cache stays as it was."""
    return str(tmp_path_factory.mktemp("compile_cache"))


def rehearse(root, cell, trace, *extra, cache_dir):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=cache_dir)
    proc = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         cell, "--seed", "3", "--seconds", "1.5", "--trace", str(trace),
         *extra], capture_output=True, text=True, env=env, cwd=root,
        timeout=600)
    return proc


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_walks_the_driver(cell, trace, cache_dir):
    proc = rehearse(harness.ROOT, cell, trace, "--rehearsal",
                    cache_dir=cache_dir)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result) == KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    chips = harness.load_cell(cell)[0]["chips"]
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    assert result["metrics"], "at least one metric of this kind"
    # each number that ``correct`` compared, beside its limit: the
    # result's last key, and the last lines on standard error
    assert list(result)[-1] == "compared"
    errs = [ln for ln in proc.stderr.splitlines() if ln.strip()]
    if result["compared"]:             # (the serve driver names none yet)
        assert all(set(c) == {"value", "limit"}
                   for c in result["compared"].values())
        assert [ln.split()[1] for ln in errs[-len(result["compared"]):]
                if ln.startswith("[compared] ")] == list(result["compared"])
        assert result["compared"]["losses_not_finite"] == {
            "value": 0, "limit": 0}
    if not trace:
        assert "setup_s" in result["metrics"]
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
        assert m["value"] is None, "a CPU run prints no time and no rate"


def test_the_gc_watch_times_the_collections_of_a_window():
    """What the ``[window]`` line says of the interpreter's collections:
    those that began inside the window, and the longest."""
    import gc
    import time

    watch = harness.GcWatch()
    t0 = time.perf_counter()
    gc.collect()
    t1 = time.perf_counter()
    gc.collect(0)
    t2 = time.perf_counter()
    assert [g for _, _, g in watch.pauses][-2:] == [2, 0]
    assert all(0 <= s < t2 - t0 for _, s, _ in watch.pauses[-2:])
    assert watch.report(t2, t2 + 1) == "gc in the window: none"
    assert watch._on not in gc.callbacks
    assert watch.pauses[-2][0] >= t0
    again = harness.GcWatch()
    gc.collect()
    line = again.report(t1, time.perf_counter())
    assert line.startswith("gc in the window: 1 collections, ")
    assert "(generation 2, " in line


def test_no_tpu_means_no_result(cache_dir):
    proc = rehearse(harness.ROOT, "b16_train", 0, cache_dir=cache_dir)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(ln.startswith("{") for ln in proc.stdout.splitlines())


def test_alone_in_a_directory_means_no_result(tmp_path, cache_dir):
    shutil.copytree(harness.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    proc = rehearse(tmp_path, "b16_train", 0, "--rehearsal",
                    cache_dir=cache_dir)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def listed(cell, kind):
    b = harness.load_json(harness.ROOT / "BENCHMARK.json")
    return {m["name"] for m in b[kind] if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("trace,kind", [(0, "end_to_end"),
                                        (1, "per_layer")])
def test_a_listed_cell_reports_what_benchmark_json_lists(trace, kind,
                                                         cache_dir):
    proc = rehearse(harness.ROOT, "b16_train_dp4", trace, "--rehearsal",
                    cache_dir=cache_dir)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    got = set(result["metrics"])
    # The CPU's trace has no device plane: the readers of the device
    # trace find nothing there and leave themselves out.
    b = harness.load_json(harness.ROOT / "BENCHMARK.json")
    unread = {m["name"] for m in b[kind] if m["source"] == "device_trace"}
    assert got == listed("b16_train_dp4", kind) - unread and got


def test_a_new_cell_and_metric_are_only_new_files_and_entries(tmp_path,
                                                              cache_dir):
    shutil.copytree(harness.BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(harness.ROOT / "pytorch_vit_paper_replication_tpu",
               tmp_path / "pytorch_vit_paper_replication_tpu")
    before = {p: p.read_bytes() for p in (tmp_path / "benchmark").rglob("*")
              if p.is_file()}
    cell = harness.load_json(harness.BENCH / "workloads" / "l16_train.json")
    cell.update(name="throwaway_cell", why="a cell added as data")
    cell["rehearsal"]["batch_per_chip"] = 2
    (tmp_path / "benchmark" / "workloads" / "throwaway_cell.json"
     ).write_text(json.dumps(cell))
    (tmp_path / "benchmark" / "metrics" / "throwaway_steps.py").write_text(
        '"""Steps in the window."""\n'
        'UNIT, KIND, SOURCE, BETTER = "steps", "end_to_end", '
        '"host_clock", "higher"\n\n\n'
        'def read(obs):\n'
        '    return obs["train"]["steps"] if obs.get("train") else None\n')
    b = harness.load_json(harness.ROOT / "BENCHMARK.json")
    b["workloads"].append({"name": "throwaway_cell",
                           "config": cell["config"], "traffic": "throwaway",
                           "chips": 1, "why": cell["why"]})
    b["end_to_end"].append({"name": "throwaway_steps", "unit": "steps",
                            "better": "higher", "bound": 0.05,
                            "source": "host_clock",
                            "workloads": ["throwaway_cell"]})
    next(m for m in b["end_to_end"] if m["name"] == "train_img_s")[
        "workloads"].append("throwaway_cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))

    proc = rehearse(tmp_path, "throwaway_cell", 0, "--rehearsal",
                    cache_dir=cache_dir)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result["metrics"]) == {"setup_s", "train_img_s",
                                      "throwaway_steps"}
    assert result["metrics"]["throwaway_steps"]["unit"] == "steps"
    # The new metric's file is there for every cell, and a cell that its
    # entry does not name reports what it reported before.
    proc = rehearse(tmp_path, "l16_train", 0, "--rehearsal",
                    cache_dir=cache_dir)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(result["metrics"]) == listed("l16_train", "end_to_end")
    assert all(p.read_bytes() == was for p, was in before.items())
