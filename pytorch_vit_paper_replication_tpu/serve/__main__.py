"""Serving CLI: stdin/stdout pipe mode and a TCP socket mode.

Pipe mode (default) — newline-delimited image paths in, TSV out::

    printf '%s\n' img1.jpg img2.jpg | \\
        python -m pytorch_vit_paper_replication_tpu.serve \\
            --checkpoint runs/ckpt --classes-file classes.txt

    img1.jpg<TAB>pizza<TAB>0.912

Socket mode — concurrent clients' requests coalesce into shared device
batches (the micro-batching win; one connection per client, one image
path per line)::

    python -m ...serve --checkpoint runs/ckpt --classes-file classes.txt \\
        --port 7878
    # elsewhere:  printf 'img1.jpg\n' | nc localhost 7878

The magic line ``::stats`` (either mode) returns the live
``ServeStats`` snapshot as one JSON line instead of a prediction;
``::metrics`` returns the shared telemetry registry (serve stats +
compile-cache + data-pipeline counters) as a Prometheus text block
terminated by one blank line (the frame marker for pipelining
clients) — point any Prometheus-speaking scraper at the socket.
``--stats-jsonl`` additionally appends a snapshot there every
``--stats-interval-s`` seconds, in the same JSONL shape train runs use.

Multi-head + SLO-tier commands (ISSUE 12; both modes):

* ``::head probs|features|tokens`` — this connection's (or the stdin
  stream's) default head. ``probs`` answers the classic TSV; a
  ``features`` request answers ``path<TAB>features<TAB>[D floats]``
  (full-precision float32 JSON — the bit-identity-probe-able form) and
  ``tokens`` answers the full ``[T, D]`` nested JSON row.
* ``::tier interactive|batch`` — this connection's SLO class
  (interactive caps the batch-fill wait; batch rides until the bucket
  fills, bounded by ``--batch-max-wait-us``).
* ``::req [head=H] [tier=T] [k=K] <path>`` — one-shot explicit form
  carrying head/tier (and the search K) inline; the reply echoes the
  bare path. This is what the fleet router relays, so pooled
  router↔replica connections never depend on per-connection state.

Embedding search (ISSUE 13; both modes): with ``--search-index DIR``
(an index built by ``tools/build_index.py``), ``::search K <path>``
embeds the image through the features head — coalescing with every
other request in the micro-batcher — scans the memory-mapped index
sharded over the local devices, and answers
``path<TAB>search<TAB>{"k": K, "ids": [...], "scores": [...]}`` (ids
are index row numbers, scores full-precision float32 — the
bit-consistency-probe-able form). The fleet router relays it as
``::req k=K ...``.
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

from ..telemetry import tracing as _tracing
from .batching import (DEFAULT_HEAD, DEFAULT_TIER, TIERS,
                       parse_req_line, parse_search_line)
from .bucketing import DEFAULT_BUCKETS
from .engine import InferenceEngine

# Line shapes that are REQUESTS (an ingress may mint a trace for them);
# every other ::command is control traffic and is never traced.
_REQUEST_CMDS = ("::req", "::probs", "::search")


def add_engine_args(p: argparse.ArgumentParser) -> None:
    """Engine/SLO knobs (tools/serve_bench.py keeps its own parser —
    its defaults are harness-sized, not serving-sized)."""
    p.add_argument("--buckets", type=str,
                   default=",".join(str(b) for b in DEFAULT_BUCKETS),
                   help="comma-separated batch bucket ladder")
    p.add_argument("--max-wait-us", type=int, default=2000,
                   help="micro-batch coalescing window for interactive-"
                        "tier requests (latency knob)")
    p.add_argument("--batch-max-wait-us", type=int, default=50_000,
                   help="batch-tier fill window: how long a batch-tier "
                        "request rides the queue hoping for a full "
                        "bucket — also its anti-starvation bound")
    p.add_argument("--max-queue", type=int, default=1024,
                   help="admission bound; beyond it submits are rejected "
                        "with a retry-after hint")
    p.add_argument("--timeout-s", type=float, default=None,
                   help="per-request deadline; expired requests are "
                        "dropped before they occupy a device batch")


def parse_buckets(spec: str):
    return tuple(int(b) for b in spec.split(",") if b.strip())


class ConnState:
    """Per-connection protocol state: the default head/tier a bare
    request line rides (set by ``::head`` / ``::tier``). One instance
    per socket connection; one for the whole stdin stream."""

    __slots__ = ("head", "tier")

    def __init__(self, head: str = DEFAULT_HEAD,
                 tier: str = DEFAULT_TIER):
        self.head = head
        self.tier = tier


def _answer(line: str, engine: InferenceEngine,
            timeout: float | None,
            state: ConnState | None = None) -> str:
    """One request line -> one response (shared by both modes).

    ``::stats`` answers one JSON line; ``::metrics`` answers the shared
    telemetry registry as a Prometheus text block, terminated by one
    BLANK line — the frame marker on this otherwise line-per-response
    protocol, so a pipelining client knows where the block ends (blank
    request lines are ignored, so the sentinel can't collide).

    Fleet-control commands (the router/rollout substrate, ISSUE 10):
    ``::drain [timeout_s]`` quiesces the engine's micro-batcher (new
    submits refused with ``DrainingError`` backpressure, in-flight
    work flushed) and answers ``{"draining": true, "unfinished": N}``;
    ``::probs <path>`` answers one request as a JSON line carrying the
    FULL float32 softmax row (the bit-identity probe the rolling
    checkpoint swap verifies a restarted replica with — the TSV
    response's 4-decimal prob can't prove bit-exactness).

    ISSUE 20 tracing: an inbound ``trace=`` token (the router's relay)
    is stripped before any grammar below sees it and its context
    adopted; a request line WITHOUT one makes this process the ingress
    (the serve CLI is a front door in its own right) and may mint a
    sampled trace. Either way a ``serve.request`` span brackets the
    handling and the context rides into the micro-batcher."""
    line = line.strip()
    state = state if state is not None else ConnState()
    hdr, line = _tracing.extract_wire_context(line)
    tracer = _tracing.get_tracer()
    ctx = tracer.accept(hdr)
    if ctx is None and hdr is None and (
            not line.startswith("::") or
            line.startswith(_REQUEST_CMDS)):
        ctx = tracer.ingress(line)
    if ctx is None:
        return _answer_line(line, engine, timeout, state, None)
    t0 = time.monotonic()
    reply = _answer_line(line, engine, timeout, state, ctx)
    tracer.record(ctx, "serve.request",
                  _tracing.wall_from_monotonic(t0),
                  _tracing.wall_from_monotonic(time.monotonic()))
    return reply


def _answer_line(line: str, engine: InferenceEngine,
                 timeout: float | None, state: ConnState,
                 ctx) -> str:
    if line == "::stats":
        return json.dumps(engine.snapshot())
    if line == "::metrics":
        return engine.prometheus_metrics().rstrip("\n") + "\n"
    if line.startswith("::head"):
        parts = line.split()
        if len(parts) == 2 and parts[1] in engine.heads:
            state.head = parts[1]
            return f"::head\tok\t{state.head}"
        return (f"{line}\tERROR\tValueError: expected '::head H' with "
                f"H in {list(engine.heads)}")
    if line.startswith("::tier"):
        parts = line.split()
        if len(parts) == 2 and parts[1] in TIERS:
            state.tier = parts[1]
            return f"::tier\tok\t{state.tier}"
        return (f"{line}\tERROR\tValueError: expected '::tier T' with "
                f"T in {list(TIERS)}")
    if line == "::drain" or line.startswith("::drain "):
        parts = line.split()
        try:
            drain_s = float(parts[1]) if len(parts) > 1 else 10.0
        except ValueError:
            return json.dumps({"error": f"bad ::drain timeout {parts[1]!r}"})
        return json.dumps({"draining": True,
                           "unfinished": engine.drain(drain_s)})
    if line.startswith("::probs "):
        path = line[len("::probs "):].strip()
        try:
            r = engine.submit(path, timeout=timeout, ctx=ctx).result()
        except Exception as e:  # noqa: BLE001 — one bad probe answers
            # THAT probe; serving goes on.
            return json.dumps({"error": f"{type(e).__name__}: {e}"})
        return json.dumps({"label": r.label, "prob": r.prob,
                           "probs": [float(p) for p in r.probs]})
    if line.startswith("::search"):
        try:
            k, path = parse_search_line(line)
        except ValueError as e:
            return f"{line}\tERROR\tValueError: {e}"
        return _search_reply(path, k, engine, timeout, state.tier)
    head, tier = state.head, state.tier
    if line.startswith("::req"):
        # One-shot inline head/tier (what the fleet router relays);
        # absent fields fall back to the connection defaults, and the
        # reply echoes the BARE path — same shape either spelling.
        # A k= pair marks a SEARCH request (the router's relay form
        # of ::search).
        try:
            req_head, req_tier, req_k, _model, path = parse_req_line(line)
        except ValueError as e:
            return f"{line}\tERROR\tValueError: {e}"
        head = req_head if req_head is not None else head
        tier = req_tier if req_tier is not None else tier
        if req_k is not None:
            return _search_reply(path, req_k, engine, timeout, tier)
        line = path
    try:
        fut = engine.submit(line, timeout=timeout, head=head, tier=tier,
                            ctx=ctx)
    except Exception as e:  # noqa: BLE001 — admission errors
        # (backpressure, shutdown, an unknown head) answer THAT
        # request; serving goes on.
        return f"{line}\tERROR\t{type(e).__name__}: {e}"
    return _finish(line, fut, head)


def _search_reply(path: str, k: int, engine: InferenceEngine,
                  timeout: float | None, tier: str) -> str:
    """One ``::search`` request -> one reply line (both modes, and the
    ``::req k=`` relay form): ``path\\tsearch\\t{json}`` with index
    row ids and full-precision float32 scores, best first."""
    try:
        ids, scores = engine.search(path, k, tier=tier, timeout=timeout)
    except Exception as e:  # noqa: BLE001 — a bad request (no index,
        # k out of bounds, unreadable image, backpressure) answers
        # THAT request; serving goes on.
        return f"{path}\tERROR\t{type(e).__name__}: {e}"
    return f"{path}\tsearch\t" + json.dumps(
        {"k": k, "ids": ids, "scores": scores})


def _serve_stdin(engine: InferenceEngine, timeout: float | None) -> None:
    # Submit-ahead pipeline: keep a bounded window of futures in flight
    # so piped batch traffic actually coalesces instead of serializing
    # batch-of-1 — and so a million-line stdin neither exhausts memory
    # nor trips the engine's own admission bound.
    window = max(1, engine._batcher.max_queue // 2)
    state = ConnState()
    pending = []
    tracer = _tracing.get_tracer()

    def drain(n):
        while len(pending) > n:
            p_line, fut, p_head, p_ctx, p_t0 = pending.pop(0)
            print(_finish(p_line, fut, p_head), flush=True)
            if p_ctx is not None:
                # The pipelined root span closes when the reply is out,
                # not at submit — queue time is the whole point.
                tracer.record(p_ctx, "serve.request",
                              _tracing.wall_from_monotonic(p_t0),
                              _tracing.wall_from_monotonic(
                                  time.monotonic()))

    for line in sys.stdin:
        line = line.strip()
        if not line:
            continue
        hdr, line = _tracing.extract_wire_context(line)
        ctx = tracer.accept(hdr)
        if ctx is None and hdr is None and (
                not line.startswith("::") or
                line.startswith("::req")):
            ctx = tracer.ingress(line)
        if line.startswith("::") and not line.startswith("::req"):
            # Control commands answer in submission order relative to
            # the pipeline: flush the window first (::drain especially
            # must not race the requests already accepted ahead of it;
            # ::head/::tier must not retag them). ::req lines are
            # REQUESTS and ride the pipeline below.
            drain(0)
            print(_answer(line, engine, timeout, state), flush=True)
            continue
        head, tier = state.head, state.tier
        if line.startswith("::req"):
            try:
                req_head, req_tier, req_k, _model, path = \
                    parse_req_line(line)
            except ValueError as e:
                print(f"{line}\tERROR\tValueError: {e}", flush=True)
                continue
            head = req_head if req_head is not None else head
            tier = req_tier if req_tier is not None else tier
            if req_k is not None:
                # A search request: the embed+scan is synchronous, so
                # it answers in submission order like a control line.
                drain(0)
                t0 = time.monotonic()
                reply = _search_reply(path, req_k, engine, timeout,
                                      tier)
                if ctx is not None:
                    tracer.record(
                        ctx, "serve.request",
                        _tracing.wall_from_monotonic(t0),
                        _tracing.wall_from_monotonic(time.monotonic()))
                print(reply, flush=True)
                continue
            line = path
        try:
            t0 = time.monotonic()
            pending.append((line, engine.submit(
                line, timeout=timeout, head=head, tier=tier,
                ctx=ctx), head, ctx, t0))
        except Exception as e:  # noqa: BLE001
            print(f"{line}\tERROR\t{type(e).__name__}: {e}", flush=True)
        drain(window)
    drain(0)


def _format_row(values) -> str:
    """A features/tokens row as full-precision float32 JSON (float ->
    repr round-trips exactly, so a parsed reply reconstructs the row
    bit-for-bit — what the multi-head bit-identity probes rest on)."""
    import numpy as np

    arr = np.asarray(values, np.float32)
    return json.dumps(arr.tolist())


def _finish(line: str, fut, head: str = DEFAULT_HEAD) -> str:
    try:
        result = fut.result()
        if head == "probs":
            return f"{line}\t{result.label}\t{result.prob:.4f}"
        return f"{line}\t{head}\t{_format_row(result)}"
    except Exception as e:  # noqa: BLE001
        return f"{line}\tERROR\t{type(e).__name__}: {e}"


def _serve_socket(engine: InferenceEngine, host: str, port: int,
                  timeout: float | None, on_ready=None) -> None:
    import socketserver

    class Handler(socketserver.StreamRequestHandler):
        def handle(self):
            state = ConnState()  # per-connection head/tier defaults
            for raw in self.rfile:
                line = raw.decode("utf-8", "replace").strip()
                if not line:
                    continue
                reply = _answer(line, engine, timeout, state)
                self.wfile.write((reply + "\n").encode())
                self.wfile.flush()

    class Server(socketserver.ThreadingTCPServer):
        allow_reuse_address = True
        daemon_threads = True

    with Server((host, port), Handler) as srv:
        print(f"[serve] listening on {host}:{srv.server_address[1]} "
              f"(line protocol: one image path per line; '::stats' for "
              f"a JSON snapshot, '::metrics' for Prometheus text)",
              file=sys.stderr)
        if on_ready is not None:
            on_ready(srv)  # tests: grab the bound port / call shutdown()
        try:
            srv.serve_forever()
        except KeyboardInterrupt:
            pass


def main(argv=None):
    p = argparse.ArgumentParser(
        description="TPU ViT online serving (dynamic micro-batching)")
    p.add_argument("--checkpoint", required=True,
                   help="params export or training --checkpoint-dir "
                        "(its transform.json is honored)")
    cls_group = p.add_mutually_exclusive_group(required=True)
    cls_group.add_argument("--classes", nargs="+",
                           help="class names, in training order")
    cls_group.add_argument("--classes-file",
                           help="file with one class name per line")
    p.add_argument("--preset", default="ViT-B/16")
    p.add_argument("--model-tier", default=None, metavar="TIER",
                   help="declared deployment tier this replica plays "
                        "(e.g. student|teacher in a cascade fleet); "
                        "reported as model_tier in ::stats, overriding "
                        "the arch-derived label — fleet model= routing "
                        "keys on the deployment spec, this is the "
                        "replica's own self-report")
    p.add_argument("--image-size", type=int, default=None,
                   help="override the checkpoint's transform.json size")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=None,
                   help="serve a TCP socket instead of stdin/stdout")
    p.add_argument("--stats-jsonl", default=None,
                   help="append periodic ServeStats snapshots here")
    p.add_argument("--stats-interval-s", type=float, default=10.0)
    p.add_argument("--ship-to", default=None, metavar="HOST:PORT",
                   help="push telemetry snapshots to a "
                        "tools/fleet_agg.py aggregator (the fleet "
                        "router's health substrate); drop-don't-block "
                        "— a dead aggregator never stalls serving")
    p.add_argument("--ship-interval-s", type=float, default=2.0,
                   help="shipper cadence for --ship-to")
    p.add_argument("--worker-id", default=None,
                   help="identity in the fleet view (default "
                        "serve-<host>-<pid>)")
    p.add_argument("--search-index", default=None, metavar="DIR",
                   help="a tools/build_index.py index directory; "
                        "enables '::search K <path>' — embed via the "
                        "features head, scan the memory-mapped index "
                        "across the local devices, answer the K "
                        "nearest rows")
    p.add_argument("--search-k-max", type=int, default=100,
                   help="largest K a ::search may ask for (bounds the "
                        "compiled scan programs' candidate widths)")
    p.add_argument("--trace-jsonl", default=None, metavar="PATH",
                   help="append request-trace spans here (ISSUE 20); "
                        "inbound trace= tokens are honored regardless "
                        "of --trace-sample, which gates only traces "
                        "MINTED at this ingress")
    p.add_argument("--trace-sample", type=float, default=0.0,
                   help="deterministic head-sampling rate in [0,1] for "
                        "traces minted here (seeded hash of trace_id — "
                        "no wall clock, no PRNG)")
    p.add_argument("--trace-role", default="replica",
                   help="process-role label on recorded spans (the "
                        "merged Perfetto lane name)")
    p.add_argument("--trace-seed", type=int, default=0,
                   help="sampling-hash seed (shift it to rotate WHICH "
                        "traces the rate selects)")
    p.add_argument("--no-manifest", action="store_true",
                   help="ignore any warmup.json next to the checkpoint "
                        "and don't write one — required when serving "
                        "with a --buckets ladder that disagrees with "
                        "the recorded shape set")
    p.add_argument("--sync-warmup", action="store_true",
                   help="block until the whole bucket ladder is compiled "
                        "before accepting traffic (default: warm in the "
                        "background, smallest rung first — requests for "
                        "already-warm rungs are servable immediately)")
    add_engine_args(p)
    from ..compile_cache import add_cache_cli, configure
    add_cache_cli(p)
    args = p.parse_args(argv)
    if args.ship_to:
        # Pure CLI precondition: a typo'd address must fail before the
        # checkpoint load + bucket-ladder warmup, not after.
        from ..telemetry.shipper import parse_address
        try:
            parse_address(args.ship_to)
        except ValueError as e:
            raise SystemExit(f"--ship-to: {e}")

    if args.trace_jsonl:
        from ..telemetry.registry import get_registry
        _tracing.configure_tracer(
            args.trace_jsonl, role=args.trace_role,
            sample_rate=args.trace_sample, seed=args.trace_seed,
            registry=get_registry())
        print(f"[serve] tracing: role={args.trace_role} "
              f"sample={args.trace_sample:g} -> {args.trace_jsonl}",
              file=sys.stderr)

    from ..predictions import load_class_names
    class_names = (load_class_names(args.classes_file)
                   if args.classes_file else args.classes)

    # Cache before the first compile: every replica of a checkpoint, and
    # every restart, then shares the rung executables.
    print(f"[serve] compile cache: {configure(args.compile_cache_dir)}",
          file=sys.stderr)

    def log_rung(bucket, seconds):
        print(f"[serve] warmup: bucket {bucket} compiled in "
              f"{seconds:.2f}s", file=sys.stderr)

    search_index = None
    if args.search_index:
        # Load (and shape-check) the index BEFORE the checkpoint load:
        # a bad --search-index path must fail in milliseconds, not
        # after a multi-second warmup.
        from ..search.index import EmbeddingIndex
        search_index = EmbeddingIndex(args.search_index)
        print(f"[serve] search index: "
              f"{json.dumps(search_index.describe())}", file=sys.stderr)

    # Background warmup overlaps rung compilation with socket accept /
    # stdin reads: a restarted server answers already-warm rungs while
    # the rest of the ladder is still compiling.
    engine = InferenceEngine.from_checkpoint(
        args.checkpoint, preset=args.preset, class_names=class_names,
        image_size=args.image_size, buckets=parse_buckets(args.buckets),
        max_wait_us=args.max_wait_us,
        batch_max_wait_us=args.batch_max_wait_us,
        max_queue=args.max_queue,
        warmup=(True if args.sync_warmup else "async"),
        use_manifest=not args.no_manifest,
        warmup_callback=log_rung,
        search_index=search_index,
        search_k_max=args.search_k_max,
        model_tier=args.model_tier)
    print(f"[serve] warming {len(engine._warmup_rungs)} bucket shapes "
          f"{list(engine._warmup_rungs)} at {engine.image_size}px"
          + ("" if args.sync_warmup else " (background)")
          + f"; heads: {','.join(engine.heads)}",
          file=sys.stderr)

    shipper = None
    if args.ship_to:
        from ..telemetry.shipper import TelemetryShipper
        # pre_ship syncs live engine state into the registry right
        # before each frame, so the fleet view's serve_* numbers are
        # current, not last-scrape-old.
        shipper = TelemetryShipper(
            args.ship_to, worker_id=args.worker_id, role="serve",
            interval_s=args.ship_interval_s,
            pre_ship=engine.publish_telemetry)
        shipper.start()
        print(f"[serve] telemetry shipper: {shipper.worker_id} -> "
              f"{args.ship_to} every {args.ship_interval_s:g}s",
              file=sys.stderr)

    emitter = None
    if args.stats_jsonl:
        from ..metrics import MetricsLogger
        logger = MetricsLogger(jsonl_path=args.stats_jsonl)
        stop = threading.Event()

        def emit_loop():
            while not stop.wait(args.stats_interval_s):
                engine.stats.emit(logger)

        emitter = (threading.Thread(target=emit_loop, daemon=True), stop,
                   logger)
        emitter[0].start()

    try:
        if args.port is not None:
            _serve_socket(engine, args.host, args.port, args.timeout_s)
        else:
            _serve_stdin(engine, args.timeout_s)
    finally:
        if emitter is not None:
            emitter[1].set()
            engine.stats.emit(emitter[2])  # final snapshot
            emitter[2].close()
        if shipper is not None:
            shipper.close()  # one final frame: the shutdown state
            # reaches the fleet view before the worker goes stale
        print(json.dumps(engine.snapshot()), file=sys.stderr)
        engine.close()


if __name__ == "__main__":
    main()
