"""One run of one cell: ``python benchmark/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

Everything that belongs to one cell, configuration, driver or metric is
a file found by name: ``workloads/<cell>.json``, ``configs/<name>.json``,
``drivers/<driver>.py``, ``metrics/<metric>.py``. Which metrics a cell
reports is what ``BENCHMARK.json`` lists for it (an entry without
``workloads`` holds for every cell), so a new metric changes no cell it
does not name. This file has no table of any of them. The last line of
standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and last
``compared``: each number that ``correct`` compared beside its limit
(also the last lines on standard error).

``--rehearsal`` is the harness's own switch for the CPU: the cell's and
the configuration's ``rehearsal`` sizes, virtual CPU devices, and a
result line whose metric values are ``null``. It walks the same code
and never prints a time or a rate.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on virtual CPU devices; counts only")
    ap.add_argument("--dump-events", metavar="FILE.json.gz",
                    help="with --trace 1: save the loaded trace events "
                         "(how benchmark/fixtures/ was recorded)")
    ap.add_argument("--dump-steps", type=int, default=0,
                    help="trim the dumped events to this many steps")
    return ap.parse_args(argv)


def cell_metrics(cell: str, kind: str) -> dict:
    """``{name: module}`` of the metrics of this kind that the cell
    reports: those ``BENCHMARK.json`` lists for it, each read by
    ``metrics/<name>.py``. A cell that ``BENCHMARK.json`` does not list
    yet (one being built, or kept for later) has no contract to keep:
    it gets every metric file of the kind, and each leaves itself out
    where it finds nothing to read."""
    listed = json.loads((ROOT / "BENCHMARK.json").read_text())
    if cell in {w["name"] for w in listed["workloads"]}:
        names = [m["name"] for m in listed[kind]
                 if cell in m.get("workloads", (cell,))]
    else:
        names = sorted(p.stem for p in (HERE / "metrics").glob("*.py")
                       if not p.stem.startswith("_"))
    found = {}
    for name in names:
        mod = importlib.import_module(f"benchmark.metrics.{name}")
        if mod.KIND == kind:
            found[name] = mod
    return found


def reduce_capture(obs: dict, dump_to=None, dump_steps=0) -> None:
    """Trace directory -> ``obs["trace"]`` (numbers) and the breakdown;
    the trace itself is deleted. ``obs["hlo_text"]``, where a driver
    gives it, is the optimized HLO of the traced program: the profile
    keeps no scope path, so each op's is joined in from there."""
    from benchmark.lib import scopes, xplane

    capture = obs.pop("capture", None)
    hlo_text = obs.pop("hlo_text", None)
    if capture is None or not capture.started:
        return
    t0 = time.perf_counter()
    try:
        by_name = scopes.parse_scopes(hlo_text)["scopes"] if hlo_text \
            else None
        trace = xplane.load(xplane.find_xplane(capture.dir), by_name)
        anchor = xplane.module_end_ns(trace, f"jit_{capture.ANCHOR}")
        if anchor is not None:
            trace["planes"].append(capture.host_plane(anchor))
        if dump_to:
            xplane.dump_events_json(
                xplane.trim(trace, module_prefix=obs["module_prefix"],
                            steps=dump_steps) if dump_steps else trace,
                dump_to)
        obs["trace"] = xplane.reduce_trace(
            trace, module_prefix=obs.get("module_prefix", ""))
        obs["trace"]["idle_gaps"] = xplane.attribute_gaps(
            obs["trace"].pop("gaps"), xplane.host_spans(trace))
        if obs["trace"].get("rows_ms"):
            print("[rows] " + xplane.format_rows(obs["trace"]), flush=True)
        if obs["trace"]["chips"]:       # a device's trace: not on the CPU
            print(f"[trace] read in {time.perf_counter() - t0:.1f} s after "
                  f"the window, scopes of {len(by_name or ())} instructions "
                  "joined in", flush=True)
    finally:
        capture.cleanup()


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, str(ROOT))
    from benchmark.lib import harness

    if not (ROOT / "pytorch_vit_paper_replication_tpu").is_dir():
        raise harness.Refused("the program is not in this checkout; the "
                              "benchmark measures nothing by itself")
    cell, config = harness.load_cell(args.workload,
                                     rehearsal=args.rehearsal)
    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell['chips']}").strip()
    driver = importlib.import_module(f"benchmark.drivers.{cell['driver']}")
    obs = driver.run(cell, config, args)
    obs["cell"], obs["config"] = cell, config
    reduce_capture(obs, args.dump_events, args.dump_steps)

    from benchmark.lib import flops
    devices = obs.pop("devices")
    device = harness.device_report(devices, obs.get("program_bytes", 0))
    obs["peak"] = None if args.rehearsal else flops.peaks(device["kind"])
    trace = obs.get("trace")
    if args.trace:
        seen = trace and trace.get("chips") and trace["busy_s"] > 0
        if seen:
            device["busy_s"] = trace["busy_s"]
            device["window_s"] = trace["window_s"]
        elif not args.rehearsal:
            raise harness.Refused("the traced run saw no device operation")

    kind = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for name, mod in cell_metrics(cell["name"], kind).items():
        value = mod.read(obs)
        if value is None:
            continue           # nothing to read in this cell: left out
        metrics[name] = {"value": None if args.rehearsal else float(value),
                         "unit": mod.UNIT}
    checks = obs["checks"]
    bad = [k for k, ok in checks.items() if not ok]
    if bad:
        print(f"[correct] failed checks: {bad}", flush=True)
    result = {"correct": not bad, "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": metrics,
              "device": device}
    if args.trace and not args.rehearsal:
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    # Each number that ``correct`` compared, beside its limit: the
    # result's last key, and the last lines on standard error.
    result["compared"] = {
        name: {"value": value, "limit": limit}
        for name, (value, limit) in obs.get("compared", {}).items()}
    for name, c in result["compared"].items():
        print(f"[compared] {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
