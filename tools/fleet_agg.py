"""fleet_agg — merge N telemetry shippers into ONE fleet view.

N workers (train hosts, serve replicas) each run a
``TelemetryShipper`` (``train.py --ship-to`` / ``serve --ship-to``)
pushing length-prefixed JSON frames here. The aggregator keeps the
latest snapshot per worker and answers the fleet questions a router or
an operator actually asks:

* **liveness/staleness** — which workers are alive, how long since
  each last shipped (a killed replica goes ``alive: false`` after
  ``--stale-after-s``; the serve-fleet router drains traffic off it),
* **fleet-wide percentiles** — per-worker histogram snapshots merged
  count-weighted (each worker's p50/p95/p99 weighted by its window
  count: an approximation — true fleet quantiles need the raw
  samples — but a traffic-weighted one, so an idle replica can't drag
  the fleet p99; the merged ``count_total``/``sum_total`` are exact).
  Only ALIVE workers merge: a dead replica's frozen last window is
  history, not fleet state, and must not skew the p99 the router
  steers by (counters, being lifetime totals, stay summed across all
  workers ever seen),
* **fleet counters** — exact sums across workers
  (``tel_steps_total``, ``serve_completed_total``, frames shipped...),
* **one Prometheus endpoint** (``--http-port``) rendering all of the
  above through the same renderer as every other surface in the repo,
  plus ``/fleet.json`` for programmatic consumers.

Usage::

    python tools/fleet_agg.py --port 9000 --http-port 9001
    # elsewhere: train.py --ship-to HOST:9000 ... / serve --ship-to ...
    curl http://localhost:9001/metrics     # fleet Prometheus text
    curl http://localhost:9001/fleet.json  # full merged snapshot

``run_fleet_demo`` is the committed-evidence harness (bench.py's
``fleet_obs_ok`` gate and the tier-1 two-subprocess test both run
it): one REAL train process and one REAL serve process, both shipping
into an in-process aggregator, merged into a single fleet snapshot
with both workers alive at once, plus a Perfetto-loadable chrome
trace exported from the same run's telemetry JSONL
(``runs/fleet_r10/``).
"""

from __future__ import annotations

import argparse
import json
import socketserver
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO) not in sys.path:  # runnable without an installed package
    sys.path.insert(0, str(_REPO))

from pytorch_vit_paper_replication_tpu.telemetry.registry import (  # noqa: E402
    render_prometheus)
from pytorch_vit_paper_replication_tpu.telemetry.shipper import (  # noqa: E402
    read_frame)

DEFAULT_STALE_AFTER_S = 10.0
FLEET_HELP = {
    "fleet_workers": "Workers that ever shipped a frame",
    "fleet_workers_alive": "Workers inside the staleness deadline",
    "fleet_frames_total": "Frames received across all workers",
}


def merge_histograms(snaps: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Count-weighted merge of per-worker histogram snapshots (the
    ``{p50,p95,p99,count,count_total,sum_total}`` registry shape).
    Quantiles are weighted means over workers' window counts — an
    approximation (see module docstring); counts/sums are exact."""
    merged: Dict[str, Any] = {"count": 0, "count_total": 0,
                              "sum_total": 0.0}
    acc = {q: [0.0, 0] for q in ("p50", "p95", "p99")}  # [weighted, n]
    for h in snaps:
        n = int(h.get("count") or 0)
        merged["count"] += n
        merged["count_total"] += int(h.get("count_total") or 0)
        merged["sum_total"] += float(h.get("sum_total") or 0.0)
        for q in acc:
            if h.get(q) is not None and n > 0:
                acc[q][0] += float(h[q]) * n
                acc[q][1] += n
    for q, (weighted, n) in acc.items():
        merged[q] = round(weighted / n, 6) if n else None
    merged["sum_total"] = round(merged["sum_total"], 6)
    merged["workers"] = len(snaps)
    return merged


class FleetAggregator:
    """TCP frame receiver + merged fleet view (see module docstring).

    Library API (the tests, the bench gate, and the router-to-come use
    it in-process): ``start()``/``close()``, ``fleet_snapshot()``,
    ``to_prometheus()``; the CLI ``main`` wraps it with an optional
    HTTP endpoint and a periodic status line.
    """

    def __init__(self, host: str = "127.0.0.1", port: int = 0, *,
                 stale_after_s: float = DEFAULT_STALE_AFTER_S,
                 evict_after_s: float = 600.0,
                 events_per_worker: int = 256):
        self.stale_after_s = float(stale_after_s)
        # Dead workers are kept (stale, with their last snapshot — the
        # forensic view) until evict_after_s, then dropped entirely:
        # pid-keyed default worker ids mean a crash-looping replica
        # registers a NEW id per restart, and without eviction the
        # worker dict / fleet.json / per-worker Prometheus series grow
        # without bound. 0 disables eviction (debug forensics).
        self.evict_after_s = float(evict_after_s)
        self.events_per_worker = int(events_per_worker)
        self._lock = threading.Lock()
        self._workers: Dict[str, Dict[str, Any]] = {}
        self._frames_total = 0
        agg = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                while True:
                    try:
                        frame = read_frame(self.rfile)
                    except (ValueError, OSError):
                        # Torn/oversized frame or an abruptly-dead
                        # shipper (SIGKILLed worker, TCP reset) — both
                        # are routine fleet churn, not tracebacks.
                        return
                    if frame is None:
                        return
                    agg._ingest(frame, self.client_address)

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.address = self._server.server_address[:2]
        self._thread: Optional[threading.Thread] = None

    # ----------------------------------------------------------- lifecycle
    @property
    def port(self) -> int:
        return self.address[1]

    def start(self) -> "FleetAggregator":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._server.serve_forever, name="fleet-agg",
                daemon=True)
            self._thread.start()
        return self

    def close(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        self._thread = None

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------- ingest
    def _ingest(self, frame: Dict[str, Any], addr) -> None:
        if not isinstance(frame, dict) or "worker_id" not in frame:
            return
        wid = str(frame["worker_id"])
        with self._lock:
            w = self._workers.setdefault(wid, {
                "role": str(frame.get("role", "worker")),
                "frames": 0, "events": [], "first_seen": time.time()})
            w["frames"] += 1
            w["seq"] = frame.get("seq")
            w["pid"] = frame.get("pid")
            w["address"] = f"{addr[0]}:{addr[1]}"
            w["worker_time"] = frame.get("time")
            w["last_seen"] = time.time()
            w["last_seen_mono"] = time.monotonic()
            w["snapshot"] = frame.get("snapshot") or {}
            events = frame.get("events") or []
            # Dedup on the events' own (time, event) identity: shippers
            # resend the ring tail every frame.
            seen = {(e.get("time"), e.get("event"))
                    for e in w["events"]}
            w["events"].extend(
                e for e in events if isinstance(e, dict)
                and (e.get("time"), e.get("event")) not in seen)
            w["events"] = w["events"][-self.events_per_worker:]
            self._frames_total += 1

    # -------------------------------------------------------------- views
    def worker_events(self, worker_id: str) -> List[dict]:
        with self._lock:
            w = self._workers.get(worker_id)
            return list(w["events"]) if w else []

    def fleet_snapshot(self) -> Dict[str, Any]:
        """The merged fleet view: per-worker liveness + merged
        counters/histograms (JSON-serializable)."""
        now_mono = time.monotonic()
        with self._lock:
            if self.evict_after_s > 0:
                for wid in [w for w, v in self._workers.items()
                            if now_mono - v["last_seen_mono"]
                            > self.evict_after_s]:
                    del self._workers[wid]
            workers: Dict[str, Any] = {}
            counters: Dict[str, float] = {}
            hists: Dict[str, List[dict]] = {}
            alive = 0
            for wid, w in sorted(self._workers.items()):
                staleness = now_mono - w["last_seen_mono"]
                is_alive = staleness <= self.stale_after_s
                alive += is_alive
                snap = w.get("snapshot") or {}
                workers[wid] = {
                    "role": w["role"],
                    "alive": bool(is_alive),
                    "staleness_s": round(staleness, 3),
                    "frames": w["frames"],
                    "seq": w.get("seq"),
                    "pid": w.get("pid"),
                    "address": w.get("address"),
                    "last_seen": w.get("last_seen"),
                    "gauges": dict(snap.get("gauges", {})),
                }
                for name, v in snap.get("counters", {}).items():
                    if isinstance(v, (int, float)):
                        counters[name] = counters.get(name, 0) + v
                # Histograms merge from ALIVE workers only: a killed
                # replica's frozen last latency window must not skew
                # the fleet p99 the router steers by — after the
                # staleness deadline its traffic is gone, so its
                # window is history, not state. (Counters stay summed
                # across all workers: lifetime totals remain true
                # after death.)
                if is_alive:
                    for name, h in snap.get("histograms", {}).items():
                        if isinstance(h, dict):
                            hists.setdefault(name, []).append(h)
            return {
                "time": time.time(),
                "workers_total": len(workers),
                "workers_alive": alive,
                "stale_after_s": self.stale_after_s,
                "frames_total": self._frames_total,
                "workers": workers,
                "merged": {
                    "counters": counters,
                    "histograms": {name: merge_histograms(snaps)
                                   for name, snaps in sorted(
                                       hists.items())},
                },
            }

    def to_prometheus(self, prefix: str = "vit_") -> str:
        """The fleet as ONE Prometheus endpoint: merged counters and
        histograms under the shared renderer, plus fleet_* liveness
        gauges and per-worker up/staleness gauges (worker ids are
        folded into the metric name — the renderer is label-free by
        design and sanitizes them)."""
        fleet = self.fleet_snapshot()
        gauges: Dict[str, Any] = {
            "fleet_workers": fleet["workers_total"],
            "fleet_workers_alive": fleet["workers_alive"],
        }
        help_text = dict(FLEET_HELP)
        for wid, w in fleet["workers"].items():
            up = f"fleet_worker_up_{wid}"
            stale = f"fleet_worker_staleness_s_{wid}"
            gauges[up] = int(w["alive"])
            gauges[stale] = w["staleness_s"]
            help_text[up] = f"1 while {wid} ({w['role']}) ships inside " \
                            "the staleness deadline"
            help_text[stale] = f"Seconds since {wid} last shipped"
        snap = {
            "counters": dict(fleet["merged"]["counters"],
                             fleet_frames_total=fleet["frames_total"]),
            "gauges": gauges,
            "histograms": fleet["merged"]["histograms"],
        }
        return render_prometheus(snap, prefix=prefix,
                                 help_text=help_text)

    def start_http(self, port: int, host: str = "127.0.0.1"):
        """``/metrics`` (Prometheus) + ``/fleet.json`` (full view) —
        the shared stdlib server (ONE implementation,
        :func:`..telemetry.shipper.start_metrics_http`) with this
        aggregator's render callbacks."""
        from pytorch_vit_paper_replication_tpu.telemetry.shipper import (
            start_metrics_http)

        return start_metrics_http(
            port=port, host=host, render_text=self.to_prometheus,
            render_json=self.fleet_snapshot, json_path="/fleet.json",
            thread_name="fleet-http")


# --------------------------------------------------------------- demo
def _child_env() -> dict:
    from tools._common import cpu_child_env  # ONE copy of the recipe
    return cpu_child_env()


def _serve_child_main(args) -> None:
    """Runs INSIDE the demo's serve subprocess: a real
    ``InferenceEngine`` (ViT-Ti, fresh params — the fleet gate measures
    telemetry merging, not checkpoint loading; coldstart_bench owns
    that) serving synthetic requests while shipping frames."""
    import numpy as np

    from pytorch_vit_paper_replication_tpu.configs import PRESETS
    from pytorch_vit_paper_replication_tpu.models import ViT
    from pytorch_vit_paper_replication_tpu.serve.engine import (
        InferenceEngine)
    from pytorch_vit_paper_replication_tpu.telemetry.shipper import (
        TelemetryShipper)

    import jax
    import jax.numpy as jnp

    cfg = PRESETS["ViT-Ti/16"](num_classes=3, image_size=args.image_size,
                               patch_size=16, dtype="float32")
    model = ViT(cfg)
    params = model.init(jax.random.key(0), jnp.zeros(
        (1, args.image_size, args.image_size, 3)))["params"]
    eng = InferenceEngine(model, params, image_size=args.image_size,
                          class_names=["a", "b", "c"],
                          buckets=(1, 2), warmup=True)
    shipper = TelemetryShipper(
        args.ship_to, worker_id=args.worker_id, role="serve",
        interval_s=args.ship_interval_s,
        pre_ship=eng.publish_telemetry).start()
    rng = np.random.default_rng(0)
    # Serve until the parent signals (stop file: the aggregator saw the
    # fleet state it needed) or the duration cap — whichever first, so
    # the demo is deterministic about worker overlap without dragging
    # a fixed sleep through every CI run.
    stop_file = Path(args.stop_file) if args.stop_file else None
    t_end = time.monotonic() + args.duration_s
    served = 0
    while time.monotonic() < t_end:
        # Honor the parent's stop only after at least ONE completed
        # request: the parent signals on both-workers-ALIVE, which can
        # land while this child is still warming up — exiting with
        # zero served would flunk the serve_traffic_merged check the
        # demo exists to prove (a real, if rare, race on a loaded
        # host).
        if served > 0 and stop_file is not None and stop_file.exists():
            break
        img = rng.random((args.image_size, args.image_size, 3),
                         np.float32)
        eng.submit(img).result(timeout=60)
        served += 1
    shipper.close()
    eng.close()
    print(json.dumps({"served": served}))


def run_fleet_demo(workdir: str | Path, *, image_size: int = 32,
                   per_class: int = 6, batch_size: int = 8,
                   serve_duration_s: float = 180.0,
                   ship_interval_s: float = 0.5,
                   stale_after_s: float = 6.0,
                   child_timeout_s: float = 420.0) -> dict:
    """One train + one serve subprocess, both shipping into an
    in-process aggregator; returns the gate fields bench.py publishes
    and writes the committed-evidence artifacts into ``workdir``:

    * ``fleet_snapshot.json`` — the merged view captured while BOTH
      workers were alive, plus the final view,
    * ``train_trace.json`` — the train child's telemetry JSONL as a
      Perfetto-loadable chrome trace (validated before writing).
    """
    from pytorch_vit_paper_replication_tpu.telemetry.chrome_trace import (
        to_chrome_trace, validate_chrome_trace)

    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    tel_jsonl = workdir / "train_telemetry.jsonl"
    agg = FleetAggregator(stale_after_s=stale_after_s).start()
    live_snapshot = None
    train_p = serve_p = None
    try:
        ship = f"127.0.0.1:{agg.port}"
        train_cmd = [
            sys.executable, "-m",
            "pytorch_vit_paper_replication_tpu.train",
            "--synthetic", "--preset", "ViT-Ti/16",
            "--image-size", str(image_size), "--patch-size", "16",
            "--dtype", "float32", "--attention", "xla",
            "--epochs", "1", "--batch-size", str(batch_size),
            "--synthetic-per-class", str(per_class),
            "--num-workers", "1",
            "--telemetry-jsonl", str(tel_jsonl),
            "--telemetry-every", "4",
            "--ship-to", ship, "--ship-interval-s",
            str(ship_interval_s), "--worker-id", "train-0"]
        stop_file = workdir / "serve_stop"
        serve_cmd = [
            sys.executable, str(Path(__file__).resolve()),
            "--serve-child", "--ship-to", ship,
            "--worker-id", "serve-0",
            "--ship-interval-s", str(ship_interval_s),
            "--image-size", str(image_size),
            "--duration-s", str(serve_duration_s),
            "--stop-file", str(stop_file)]
        t0 = time.perf_counter()
        # The children write to files: nobody reads a pipe while the
        # loop below polls, and a child that fills one (a populated
        # compile cache logs a line per hit) would wait on it for ever.
        logs = {name: workdir / f"{name}_child.log"
                for name in ("train", "serve")}

        def spawn(name, cmd):
            with open(logs[name], "w") as out:
                return subprocess.Popen(cmd, env=_child_env(), stdout=out,
                                        stderr=subprocess.STDOUT)

        train_p = spawn("train", train_cmd)
        serve_p = spawn("serve", serve_cmd)
        # Poll for the both-alive moment — the fleet claim the
        # artifact exists to prove: two REAL processes, one merged
        # view, both inside the staleness deadline at once.
        deadline = time.monotonic() + child_timeout_s
        while time.monotonic() < deadline:
            snap = agg.fleet_snapshot()
            if (snap["workers_total"] >= 2
                    and snap["workers_alive"] >= 2):
                live_snapshot = snap
                break
            if (train_p.poll() is not None
                    and serve_p.poll() is not None):
                break
            time.sleep(0.25)
        # Release the serve child: the overlap (or the children's own
        # exit) has been observed; it ships a final frame and leaves.
        stop_file.touch()
        for name, proc in (("train", train_p), ("serve", serve_p)):
            proc.wait(timeout=max(1.0, deadline - time.monotonic()))
            if proc.returncode != 0:
                raise RuntimeError(
                    f"{name} child failed rc={proc.returncode}:\n"
                    f"{logs[name].read_text()[-2000:]}")
        wall_s = time.perf_counter() - t0
        final_snapshot = agg.fleet_snapshot()
        prometheus = agg.to_prometheus()
        stop_file.unlink(missing_ok=True)  # coordination, not evidence
    finally:
        # Reap the children on EVERY exit path: a timeout/raise above
        # must not orphan a CPU-burning train process whose workdir
        # (bench runs it in a TemporaryDirectory) is about to vanish.
        for proc in (train_p, serve_p):
            if proc is not None and proc.poll() is None:
                proc.kill()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    pass
        agg.close()

    # Chrome trace from the same run (Perfetto-loadable, validated).
    rows = [json.loads(line) for line in
            tel_jsonl.read_text().splitlines() if line.strip()]
    trace = to_chrome_trace(rows, pid=1, process_name="train-0")
    trace_events = validate_chrome_trace(trace)
    (workdir / "train_trace.json").write_text(json.dumps(trace) + "\n")

    workers = final_snapshot["workers"]
    merged = final_snapshot["merged"]["counters"]
    checks = {
        "both_workers_seen": final_snapshot["workers_total"] == 2,
        "both_alive_at_once": bool(
            live_snapshot is not None
            and live_snapshot["workers_alive"] == 2),
        "roles_correct": sorted(
            w["role"] for w in workers.values()) == ["serve", "train"],
        "train_steps_merged": merged.get("tel_steps_total", 0) > 0,
        "serve_traffic_merged": merged.get(
            "serve_completed_total", 0) > 0,
        "frames_from_both": all(
            w["frames"] >= 2 for w in workers.values()),
        "chrome_trace_valid": trace_events > 0,
        "fleet_prometheus_renders": "vit_fleet_workers 2" in prometheus,
    }
    result = {
        "fleet_workers": final_snapshot["workers_total"],
        "fleet_frames_total": final_snapshot["frames_total"],
        "fleet_train_steps": merged.get("tel_steps_total"),
        "fleet_serve_completed": merged.get("serve_completed_total"),
        "fleet_chrome_trace_events": trace_events,
        "fleet_demo_wall_s": round(wall_s, 2),
        "fleet_checks": checks,
        "fleet_obs_ok": all(checks.values()),
    }
    (workdir / "fleet_snapshot.json").write_text(json.dumps({
        "live_both_alive": live_snapshot,
        "final": final_snapshot,
        "result": result}, indent=2, default=str) + "\n")
    return result


# ----------------------------------------------------------------- CLI
def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--port", type=int, default=9000,
                   help="TCP port shippers push frames to (0 = pick)")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--http-port", type=int, default=None,
                   help="also serve /metrics + /fleet.json here")
    p.add_argument("--stale-after-s", type=float,
                   default=DEFAULT_STALE_AFTER_S,
                   help="a worker silent longer than this is reported "
                        "alive=false")
    p.add_argument("--evict-after-s", type=float, default=600.0,
                   help="a worker silent longer than this is dropped "
                        "from the view entirely (bounds the worker "
                        "set under pid-keyed ids + restart churn; "
                        "0 = never evict)")
    p.add_argument("--status-interval-s", type=float, default=10.0,
                   help="print a one-line fleet status this often "
                        "(0 = quiet)")
    p.add_argument("--snapshot-out", default=None,
                   help="write the final fleet snapshot JSON here on "
                        "exit")
    p.add_argument("--demo", metavar="WORKDIR", default=None,
                   help="run the two-subprocess committed-evidence "
                        "demo into WORKDIR and exit (see "
                        "run_fleet_demo)")
    # Internal: the demo's serve-subprocess entry point.
    p.add_argument("--serve-child", action="store_true",
                   help=argparse.SUPPRESS)
    p.add_argument("--ship-to", default=None, help=argparse.SUPPRESS)
    p.add_argument("--worker-id", default="serve-0",
                   help=argparse.SUPPRESS)
    p.add_argument("--ship-interval-s", type=float, default=0.5,
                   help=argparse.SUPPRESS)
    p.add_argument("--image-size", type=int, default=32,
                   help=argparse.SUPPRESS)
    p.add_argument("--duration-s", type=float, default=180.0,
                   help=argparse.SUPPRESS)
    p.add_argument("--stop-file", default=None, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.serve_child:
        _serve_child_main(args)
        return 0
    if args.demo:
        result = run_fleet_demo(args.demo)
        print(json.dumps(result, indent=2))
        return 0 if result["fleet_obs_ok"] else 1

    agg = FleetAggregator(args.host, args.port,
                          stale_after_s=args.stale_after_s,
                          evict_after_s=args.evict_after_s).start()
    # SIGTERM (systemd/k8s stop) must reach the finally below — the
    # --snapshot-out promise is "on exit", not "on Ctrl-C only".
    import signal as _signal

    def _on_term(signum, frame):
        raise KeyboardInterrupt

    _signal.signal(_signal.SIGTERM, _on_term)
    print(f"[fleet_agg] listening on {args.host}:{agg.port} "
          f"(stale after {args.stale_after_s:g}s)")
    http_srv = None
    if args.http_port is not None:
        http_srv = agg.start_http(args.http_port, args.host)
        print(f"[fleet_agg] http://{args.host}:"
              f"{http_srv.server_address[1]}/metrics | /fleet.json")
    try:
        while True:
            time.sleep(args.status_interval_s or 1.0)
            if args.status_interval_s:
                s = agg.fleet_snapshot()
                print(f"[fleet_agg] workers {s['workers_alive']}/"
                      f"{s['workers_total']} alive, "
                      f"{s['frames_total']} frames")
    except KeyboardInterrupt:
        pass
    finally:
        if args.snapshot_out:
            Path(args.snapshot_out).write_text(json.dumps(
                agg.fleet_snapshot(), indent=2, default=str) + "\n")
        if http_srv is not None:
            http_srv.shutdown()
            http_srv.server_close()
        agg.close()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
