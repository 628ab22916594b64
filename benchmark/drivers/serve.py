"""Serve driver: the program's in-process ``InferenceEngine`` under an
open-loop replay.

The engine, its batcher and its warm-up are the program's. The replay
loop is the benchmark's own: one thread sleeps to each arrival's due
time and submits; a request is timed from when it was **due** to when
its future resolved, and how late the generator ran is kept beside it.
``PRE_ROLL_S`` of traffic at the cell's rate runs before the window
opens, uncounted, so that each rung has executed once.

How the window is marked and checked is the yardstick and not a cell's
data: the constants below hold for every serve cell. A cell's file gives
the engine's settings and the traffic, never how they are timed.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..lib import clock, harness, kernels, reference_vit, schedule

PRE_ROLL_S = 1.0            # uncounted traffic before the window opens
TRACE_SECONDS = 3.0         # what a ``--trace 1`` run captures
REQUEST_TIMEOUT_S = 30.0    # a request older than this counts as failed
CHECK_ANSWERS = 32          # sampled answers held to the reference


def make_images(seed: int, n: int, image_size: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return rng.random((n, image_size, image_size, 3), dtype=np.float32)


def build_engine(cell: dict, config: dict, args):
    """Model, weights from the seed in one jitted call, and the engine
    with a synchronous warm-up of the cell's rungs."""
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu.serve.engine import (
        InferenceEngine)

    p = cell["serve"]
    cfg, model = harness.build_model(config)
    dummy = jnp.zeros((1, cfg.image_size, cfg.image_size, 3))
    params = jax.jit(lambda k: model.init(k, dummy)["params"])(
        jax.random.key(args.seed))
    engine = InferenceEngine(
        model, params, image_size=cfg.image_size, buckets=p["buckets"],
        max_wait_us=p["max_wait_us"], max_queue=p["max_queue"],
        warmup=True)
    return cfg, model, params, engine


def replay(engine, arrivals: dict, images: np.ndarray, *, timeout_s: float,
           tracer=None, on_time=(), keep=frozenset()) -> dict:
    """Submit every arrival at its due time; returns per-request arrays
    (seconds from the replay's start): ``due``, ``sent``, ``done`` (NaN
    where refused or failed), ``ok``, and the answers of the requests
    listed in ``keep``. ``on_time``: ``[(t, fn)]`` called once when the
    replay clock passes ``t`` (window marks, profiler start/stop)."""
    t_due = arrivals["t"]
    n = len(t_due)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    ok = np.zeros(n, bool)
    answers = {}
    marks = sorted(on_time, key=lambda m: m[0])
    pending = []
    t0 = time.perf_counter()

    def on_done(fut, i):
        done[i] = time.perf_counter() - t0
        if fut.exception() is None:
            ok[i] = True
            if i in keep:
                answers[i] = fut.result()

    for i in range(n):
        while marks and marks[0][0] <= t_due[i]:
            marks.pop(0)[1]()
        wait = t0 + t_due[i] - time.perf_counter()
        if wait > 0:
            with harness.annotate("bench.sleep_until_due"):
                time.sleep(wait)
        ctx = tracer.ingress(str(i)) if tracer is not None else None
        sent[i] = time.perf_counter() - t0
        try:
            with harness.annotate("bench.submit"):
                fut = engine.submit(images[i % len(images)],
                                    timeout=timeout_s,
                                    head=arrivals["head"][i],
                                    tier=arrivals["tier"][i], ctx=ctx)
        except Exception:  # noqa: BLE001 - refused at admission: counted
            continue
        fut.add_done_callback(lambda f, i=i: on_done(f, i))
        pending.append(fut)
    for _, fn in marks:
        fn()
    deadline = time.perf_counter() + timeout_s + 30.0
    with harness.annotate("bench.drain"):
        for fut in pending:
            try:
                fut.result(timeout=max(0.1, deadline - time.perf_counter()))
            except Exception:  # noqa: BLE001 - expired or failed: counted
                pass
    # ``result()`` can return before the done-callback has run on the
    # batcher's thread: let the last ones land.
    time.sleep(0.05)
    return {"due": t_due, "sent": sent, "done": done, "ok": ok,
            "answers": answers, "t0": t0}


def run(cell: dict, config: dict, args) -> dict:
    import jax

    from pytorch_vit_paper_replication_tpu.telemetry import tracing

    p = cell["serve"]
    phases = [("imports", clock.since_process_start())]
    mark = lambda name: phases.append((name, clock.since_process_start()))
    cache = harness.configure_cache()
    devices = harness.claim_devices(cell["chips"], rehearsal=args.rehearsal)
    mark("chip")
    cfg, model, params, engine = build_engine(cell, config, args)
    mark("engine_warm")
    images = make_images(args.seed, p["pool_images"], cfg.image_size)
    pre = PRE_ROLL_S
    arrivals = schedule.build_schedule(
        p["traffic"], seed=args.seed, duration_s=pre + args.seconds,
        offset_s=pre)

    tracer, sink, capture = None, None, None
    if args.trace:
        harness.WORK.mkdir(parents=True, exist_ok=True)
        sink = harness.WORK / f"{cell['name']}.spans.jsonl"
        sink.unlink(missing_ok=True)
        tracer = tracing.configure_tracer(str(sink), role="bench",
                                          sample_rate=1.0, seed=args.seed)
        capture = harness.Capture(cell["name"])
    w = {}

    def open_window():
        w["setup_s"] = clock.since_process_start()
        phases.append(("window_open", w["setup_s"]))
        w["misses_open"] = cache.misses
        w["stats_open"] = dict(engine.stats.counters)

    threads = []

    def in_thread(fn):
        def go():
            threads.append(threading.Thread(target=fn, daemon=True))
            threads[-1].start()
        return go

    marks = [(pre, open_window)]
    if capture is not None:
        t_a = pre + min(1.0, args.seconds / 4)
        marks += [(t_a, in_thread(capture.start)),
                  (t_a + TRACE_SECONDS, in_thread(capture.stop))]
    # Candidates for the answers checked against the reference: drawn
    # before the replay, so that only their rows are kept.
    rng = np.random.default_rng(args.seed)
    in_win = arrivals["t"] >= pre
    keep = rng.choice(np.flatnonzero(in_win),
                      size=min(4 * CHECK_ANSWERS, int(in_win.sum())),
                      replace=False)
    try:
        r = replay(engine, arrivals, images,
                   timeout_s=REQUEST_TIMEOUT_S, tracer=tracer,
                   on_time=marks, keep=frozenset(keep.tolist()))
        w["misses_close"] = cache.misses
        stats_close = dict(engine.stats.counters)
        for t in threads:
            t.join()
        if capture is not None:
            capture.stop()

        # ---- after the window -----------------------------------------
        answered = in_win & r["ok"]
        failed = int((in_win & ~r["ok"]).sum())
        lat = (r["done"] - r["due"])[answered]
        late = (r["sent"] - r["due"])[in_win]
        t_last = float(np.nanmax(r["done"][answered])) if answered.any() \
            else pre + args.seconds
        spans = []
        if sink is not None:
            tracer.close()
            tracing.configure_tracer(None)
            t_open_wall = tracing.wall_from_perf_counter(r["t0"] + pre)
            spans = [s for s in tracing.read_trace_sink(str(sink))
                     if s["t0"] >= t_open_wall]
            sink.unlink(missing_ok=True)

        # Mosaic calls per rung, read from each rung's lowered program.
        x_s = lambda b: jax.ShapeDtypeStruct(
            (b, cfg.image_size, cfg.image_size, 3), np.float32)
        per_rung = {b: kernels.kernel_counts(
            engine._fwd.lower(params, x_s(b)).as_text())
            for b in engine.buckets}
        expect = p["expect_kernels"]
        rung_bytes = {b: harness.program_bytes(c)
                      for b, c in engine._compiled.items()}

        # Sampled answers against the reference's softmax rows.
        sample = [i for i in keep if i in r["answers"]][:CHECK_ANSWERS]
        err = float("inf")
        if len(sample):
            which = np.asarray([i % len(images) for i in sample])
            got = np.stack([r["answers"][i].probs for i in sample])
            params_host = jax.device_get(params)
            want = np.asarray(jax.jit(
                lambda prm, x: reference_vit.forward(
                    prm, x, patch_size=cfg.patch_size,
                    ln_epsilon=cfg.ln_epsilon, pool=cfg.pool))(
                params_host, images[which]))
            want = want - want.mean(-1, keepdims=True)
            err = reference_vit.agreement(reference_vit.log_rows(got), want)
    finally:
        engine.close()
        if capture is not None:
            capture.stop()

    checks = {
        "every_request_accounted": int(in_win.sum())
        == int(answered.sum()) + failed,
        "mosaic_calls": args.rehearsal or all(
            kernels.check_kernels(found, expect)[0]
            for found in per_rung.values()),
        "reference": err <= reference_vit.TOLERANCE,
        "no_compile_in_window": w["misses_close"] == w["misses_open"],
    }
    delta = {k: stats_close.get(k, 0) - w["stats_open"].get(k, 0)
             for k in stats_close}
    if not args.rehearsal:
        print("[setup] seconds since process start: " + ", ".join(
            f"{k} {v:.1f}" for k, v in phases), flush=True)
    print(f"[serve] offered {p['traffic']['rate_rps']} rps | in window "
          f"{int(in_win.sum())} scheduled, {int(answered.sum())} answered "
          f"(latency samples), {failed} failed | mosaic kernels per "
          f"rung {per_rung} (the cell names {expect}; others are not "
          f"judged) | reference error {err:.4f} "
          f"(tolerance {reference_vit.TOLERANCE}) | cache misses at open "
          f"{w['misses_open']} at close {w['misses_close']} | rung "
          f"programs {({b: round(v / 2**30, 3) for b, v in rung_bytes.items()})}"
          f" GiB | batcher counters in window {delta}", flush=True)
    params_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(params))
    return {
        "setup_s": w["setup_s"],
        "attempted": int(in_win.sum()), "failed": failed, "checks": checks,
        "devices": devices,
        # The largest rung's program counts the weights among its
        # arguments; the other rungs are not resident at the same time.
        "program_bytes": max(list(rung_bytes.values()) + [params_bytes]),
        "serve": {"latency_s": lat, "late_s": late,
                  "answered": int(answered.sum()),
                  "window_s": float(args.seconds),
                  "elapsed_s": max(float(args.seconds), t_last - pre),
                  "counters": delta, "spans": spans},
        "model": config["model"],
        "capture": capture, "module_prefix": "jit_fused",
    }
