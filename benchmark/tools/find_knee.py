"""Sweep the offered rate of a serve cell, once, when the cell is
defined: the highest rate the system sustains is the knee, and the cell
then fixes its rate relative to it (0.8x below, 1.5x above).

    python benchmark/tools/find_knee.py --workload b16_serve_steady \
        --rates 400,800,1200,1600,2000 --seconds 8 [--seed 0]

One process, one engine, one replay per rate (the cell's own driver
code: ``drivers/serve.py::replay``). Per rate: completed/s, p50, p99,
``gen_late_ms_p99``, failures, and whether the backlog grew (the median
latency of the window's last quarter against its first). The cell's
file records the knee, the sweep and the date. It is no part of a run.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent.parent


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    from benchmark.drivers import serve
    from benchmark.lib import harness, schedule

    cell, config = harness.load_cell(args.workload,
                                     rehearsal=args.rehearsal)
    if args.rehearsal:
        import os
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    p = cell["serve"]
    harness.configure_cache()
    devices = harness.claim_devices(cell["chips"], rehearsal=args.rehearsal)
    cfg, _, _, engine = serve.build_engine(cell, config, args)
    images = serve.make_images(args.seed, p["pool_images"], cfg.image_size)
    pre = serve.PRE_ROLL_S
    print(f"device {devices[0].device_kind} x{len(devices)}; rungs "
          f"{list(engine.buckets)}; window {args.seconds} s after "
          f"{pre} s pre-roll", flush=True)
    try:
        for rate in [float(r) for r in args.rates.split(",")]:
            arrivals = schedule.build_schedule(
                dict(p["traffic"], rate_rps=rate), seed=args.seed,
                duration_s=pre + args.seconds, offset_s=pre)
            before = dict(engine.stats.counters)
            r = serve.replay(engine, arrivals, images,
                             timeout_s=serve.REQUEST_TIMEOUT_S)
            after = dict(engine.stats.counters)
            in_win = r["due"] >= pre
            ok = in_win & r["ok"]
            lat = (r["done"] - r["due"])[ok] * 1e3
            late = (r["sent"] - r["due"])[in_win] * 1e3
            order = np.argsort(r["due"][ok])
            q = max(1, len(order) // 4)
            first, last = lat[order[:q]], lat[order[-q:]]
            t_last = float(np.nanmax(r["done"][ok])) if ok.any() else 0.0
            row = {
                "rate_rps": rate, "scheduled": int(in_win.sum()),
                "answered": int(ok.sum()),
                "failed": int((in_win & ~r["ok"]).sum()),
                "completed_per_s": ok.sum() / max(args.seconds,
                                                  t_last - pre),
                "p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
                "p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
                "gen_late_ms_p99": float(np.percentile(late, 99)),
                "first_quarter_p50_ms": float(np.median(first))
                if len(lat) else None,
                "last_quarter_p50_ms": float(np.median(last))
                if len(lat) else None,
                "batches": after["batches"] - before["batches"],
                "padded_rows": after["padded_rows"] - before["padded_rows"],
            }
            row["backlog_grew"] = bool(
                row["failed"] or (len(lat) and np.median(last)
                                  > 1.5 * np.median(first) + 5.0))
            if args.rehearsal:   # a CPU run prints counts, never a time
                row = {k: v for k, v in row.items() if k in (
                    "rate_rps", "scheduled", "answered", "failed",
                    "batches", "padded_rows")}
            print(json.dumps(row), flush=True)
            time.sleep(1.0)         # let the queue empty between rates
    finally:
        engine.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
