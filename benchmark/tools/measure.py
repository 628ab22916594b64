"""Run cells several times, one process per run, and report spreads.

    python benchmark/tools/measure.py --out chiprun_out/m1 \
        --seconds 10 b16_train:6 b16_train:6 l16_train:t

Each positional is ``<cell>:<n>`` (a set of n untraced runs, each with
another seed: ``--first-seed`` + 1 ... + n, the same seeds in every set,
as the driver measures two sets) or ``<cell>:t`` (one traced run). Sets
of one cell are reported apart. Per set and metric:
median, quartiles (``statistics.quantiles(values, n=4)``, as the
contract takes them) and the spread (distance between the quartiles over
the median). This process never touches jax, so each child gets the
chip. Every result line and the tail of each child's output land in
``--out``. It is no part of a run.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent


def quartiles(xs):
    xs = sorted(xs)
    if len(xs) < 2:
        return xs[0], xs[0], xs[0]
    q = statistics.quantiles(xs, n=4)    # as the contract's spread is taken
    return q[0], q[1], q[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("sets", nargs="+")
    ap.add_argument("--out", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=100)
    ap.add_argument("--extra", default="", help="appended to traced runs")
    args = ap.parse_args()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    log = open(out / "results.jsonl", "a")
    for k, spec in enumerate(args.sets):
        cell, n = spec.split(":")
        traced = n == "t"
        rows = []
        seed = args.first_seed
        for _ in range(1 if traced else int(n)):
            seed += 1
            cmd = [sys.executable, "benchmark/run.py", "--workload", cell,
                   "--seed", str(seed), "--seconds", str(args.seconds),
                   "--trace", "1" if traced else "0"]
            if traced and args.extra:
                cmd += args.extra.split()
            load = Path("/proc/loadavg").read_text().split()[0]
            t0 = time.time()
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True)
            wall = time.time() - t0
            lines = [ln for ln in proc.stdout.splitlines()
                     if not ln.startswith(("E0", "W0", "I0"))]
            (out / f"{cell}.set{k}.{seed}.out").write_text(
                "\n".join(lines[-40:]) + "\n--- stderr tail ---\n"
                + proc.stderr[-4000:])
            row = {"cell": cell, "set": k, "seed": seed, "traced": traced,
                   "rc": proc.returncode, "wall_s": round(wall, 2),
                   "host_load_before": float(load)}
            if proc.returncode == 0 and lines:
                row["result"] = json.loads(lines[-1])
            log.write(json.dumps(row) + "\n")
            log.flush()
            for ln in lines[-7:-1]:
                if ln.startswith("["):
                    print("   ", ln[:900])
            print(json.dumps(row)[:3000], flush=True)
            rows.append(row)
        good = [r["result"] for r in rows if "result" in r]
        if traced or not good:
            continue
        print(f"== set {k}: {cell}, {len(good)} of {len(rows)} runs ok, "
              f"correct {sum(r['correct'] for r in good)}")
        for name in good[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in good
                    if name in r["metrics"]]
            q1, med, q3 = quartiles(vals)
            print(f"   {name:16s} median {med:.4f} q1 {q1:.4f} q3 {q3:.4f} "
                  f"spread {(q3 - q1) / med * 100:.3f}% "
                  f"first {vals[0]:.4f} min {min(vals):.4f} "
                  f"max {max(vals):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
