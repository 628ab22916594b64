"""Serving subsystem tests: bucket ladder, micro-batcher semantics
(deterministic — manual dispatch drive, no sleeps-as-sync), pad+mask
correctness, the checkpoint->serve round trip (bit-exact vs
``predict_image``, ``transform.json`` honored), bucketed directory
prediction, and the socket CLI."""

import json
import socket
import threading
import time

import numpy as np
import pytest

from pytorch_vit_paper_replication_tpu.serve import (
    DrainingError, InferenceEngine, MicroBatcher, QueueFullError,
    RequestExpired, ShutdownError, pad_rows_to_bucket, pick_bucket,
    plan_buckets)


# --------------------------------------------------------------- ladder
def test_pick_bucket_smallest_rung():
    assert pick_bucket(1) == 1
    assert pick_bucket(2) == 8
    assert pick_bucket(9, (1, 8, 32)) == 32
    with pytest.raises(ValueError, match="top bucket"):
        pick_bucket(257)


def test_plan_buckets_bounded_shapes_and_waste():
    """A 1000-image directory compiles <= 5 shapes (the satellite's
    done-criterion) and chunks cover every image exactly once."""
    plan = plan_buckets(1000)
    assert len(set(plan)) <= 5
    assert sum(plan) >= 1000
    assert sum(plan) - 1000 < plan[-1]  # waste < one final chunk
    # Sub-rung remainders pad up instead of spraying batch-of-1s...
    assert plan_buckets(7, (1, 8)) == [8]
    # ...but decompose when that wastes less total compute.
    assert plan_buckets(104) == [32, 32, 32, 8]
    assert plan_buckets(0) == []


def test_pad_rows_to_bucket_mask():
    rows = np.arange(6, dtype=np.float32).reshape(3, 2)
    padded, mask = pad_rows_to_bucket(rows, 8)
    assert padded.shape == (8, 2)
    np.testing.assert_array_equal(mask, [1, 1, 1, 0, 0, 0, 0, 0])
    np.testing.assert_array_equal(padded[:3], rows)
    full, mask_full = pad_rows_to_bucket(rows, 3)
    assert full is rows and mask_full.sum() == 3


# ---------------------------------------------------------- micro-batcher
def _echo_forward(log):
    def fwd(x, mask, heads):
        log.append((x.shape[0], int(mask.sum())))
        return x * 2.0
    return fwd


def _multihead_echo(log):
    """Head-splitting callback: the fused-forward output contract —
    a {head: per_row_outputs} dict covering every tagged head."""
    def fwd(x, mask, heads):
        log.append((x.shape[0], tuple(heads)))
        return {"probs": x * 2.0, "features": x * 3.0,
                "tokens": x * 5.0}
    return fwd


def test_batcher_coalesces_concurrent_submits():
    """Six submits inside one max-wait window ride ONE device batch
    (bucket 8), not six batch-of-1 dispatches."""
    log = []
    with MicroBatcher(_echo_forward(log), buckets=(1, 8, 32),
                      max_wait_us=300_000) as mb:
        futs = [mb.submit(np.full(4, i, np.float32)) for i in range(6)]
        outs = [f.result(timeout=10) for f in futs]
    for i, out in enumerate(outs):
        np.testing.assert_array_equal(out, np.full(4, 2.0 * i))
    assert log == [(8, 6)]  # one padded bucket-8 batch, 6 real rows
    snap = mb.stats.snapshot()
    assert snap["counters"]["batches"] == 1
    assert snap["counters"]["padded_rows"] == 2
    assert snap["batch_occupancy"]["8"]["mean_occupancy"] == 0.75


def test_batcher_bucket_selection_deterministic():
    """Manual drive: batch size picks the smallest covering rung."""
    log = []
    mb = MicroBatcher(_echo_forward(log), buckets=(1, 4, 8),
                      max_wait_us=0, start_thread=False)
    mb.submit(np.zeros(2, np.float32))
    assert mb.run_once() == 1
    for _ in range(3):
        mb.submit(np.zeros(2, np.float32))
    assert mb.run_once() == 3
    assert [b for b, _ in log] == [1, 4]


def test_batcher_deadline_expiry_skips_device_batch():
    """An expired request is dropped at batch formation — the forward
    never sees its row — and its future fails with RequestExpired."""
    log = []
    mb = MicroBatcher(_echo_forward(log), buckets=(1, 4),
                      max_wait_us=0, start_thread=False)
    dead = mb.submit(np.full(2, 7.0, np.float32), timeout=0.0)
    time.sleep(0.002)  # guarantee monotonic() passes the deadline
    live = mb.submit(np.full(2, 1.0, np.float32))
    assert mb.run_once() == 1
    with pytest.raises(RequestExpired):
        dead.result(timeout=0)
    np.testing.assert_array_equal(live.result(timeout=0), np.full(2, 2.0))
    assert log == [(1, 1)]  # the expired row never occupied a batch
    assert mb.stats.snapshot()["counters"]["expired"] == 1


def test_batcher_degrades_and_recovers_bucket_cap():
    """Expiries step the bucket cap down a rung (drain faster); clean
    dispatches step it back up after `recover_after`."""
    mb = MicroBatcher(_echo_forward([]), buckets=(1, 4, 8),
                      max_wait_us=0, recover_after=2, start_thread=False)
    assert mb.effective_bucket_cap == 8
    mb.submit(np.zeros(2, np.float32), timeout=0.0)
    time.sleep(0.002)
    mb.submit(np.zeros(2, np.float32))
    mb.run_once()
    assert mb.effective_bucket_cap == 4  # degraded one rung
    for _ in range(2):  # two clean dispatches -> recover
        mb.submit(np.zeros(2, np.float32))
        mb.run_once()
    assert mb.effective_bucket_cap == 8


def test_batcher_full_queue_rejects_not_grows():
    mb = MicroBatcher(_echo_forward([]), buckets=(1,), max_queue=3,
                      start_thread=False)
    for _ in range(3):
        mb.submit(np.zeros(2, np.float32))
    with pytest.raises(QueueFullError) as exc:
        mb.submit(np.zeros(2, np.float32))
    assert exc.value.retry_after_s > 0
    assert mb.queue_depth() == 3  # rejected, not enqueued
    assert mb.stats.snapshot()["counters"]["rejected_queue_full"] == 1


def test_batcher_close_fails_pending_and_refuses_new():
    mb = MicroBatcher(_echo_forward([]), buckets=(4,), start_thread=False)
    fut = mb.submit(np.zeros(2, np.float32))
    mb.close()
    with pytest.raises(ShutdownError):
        fut.result(timeout=0)
    with pytest.raises(ShutdownError):
        mb.submit(np.zeros(2, np.float32))


def test_batcher_malformed_rows_fail_batch_not_batcher():
    """Mismatched row shapes break np.stack at batch FORMATION — that
    must fail the batch's futures, not kill the worker loop."""
    log = []
    mb = MicroBatcher(_echo_forward(log), buckets=(1, 4),
                      max_wait_us=0, start_thread=False)
    a = mb.submit(np.zeros(2, np.float32))
    b = mb.submit(np.zeros(3, np.float32))  # incompatible shape
    assert mb.run_once() == 2
    for fut in (a, b):
        with pytest.raises(ValueError):
            fut.result(timeout=0)
    assert log == []  # the forward never ran
    ok = mb.submit(np.ones(2, np.float32))  # batcher still serves
    mb.run_once()
    np.testing.assert_array_equal(ok.result(timeout=0), np.full(2, 2.0))


def test_batcher_cancelled_requests_do_not_break_dispatch():
    """A caller-cancelled future must not blow up resolution — neither
    at expiry (_collect), at close(), nor on a served batch."""
    mb = MicroBatcher(_echo_forward([]), buckets=(1, 4),
                      max_wait_us=0, start_thread=False)
    expired = mb.submit(np.zeros(2, np.float32), timeout=0.0)
    assert expired.cancel()
    time.sleep(0.002)
    served = mb.submit(np.zeros(2, np.float32))
    assert served.cancel()
    live = mb.submit(np.ones(2, np.float32))
    assert mb.run_once() == 2  # cancelled-but-live `served` + `live`
    np.testing.assert_array_equal(live.result(timeout=0), np.full(2, 2.0))
    closing = mb.submit(np.ones(2, np.float32))
    assert closing.cancel()
    mb.close()  # must not raise InvalidStateError


def test_engine_wrap_callback_error_fails_future_not_hangs():
    """An exception inside the result-wrapping callback (e.g. class_names
    shorter than the model's output row) must land on the returned
    future — cf swallows callback exceptions, which would otherwise
    leave the caller blocked forever."""
    import concurrent.futures as cf

    eng = InferenceEngine.__new__(InferenceEngine)  # no device needed
    eng.class_names = ["only"]
    raw: cf.Future = cf.Future()
    out = eng._wrap(raw)
    raw.set_result(np.array([0.1, 0.2, 0.7], np.float32))  # argmax = 2
    with pytest.raises(IndexError):
        out.result(timeout=1)


def test_batcher_forward_error_fails_batch_not_batcher():
    calls = {"n": 0}

    def fwd(x, mask, heads):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("device fell over")
        return x

    mb = MicroBatcher(fwd, buckets=(1, 4), max_wait_us=0,
                      start_thread=False)
    bad = mb.submit(np.zeros(2, np.float32))
    mb.run_once()
    with pytest.raises(RuntimeError, match="fell over"):
        bad.result(timeout=0)
    ok = mb.submit(np.ones(2, np.float32))
    mb.run_once()
    np.testing.assert_array_equal(ok.result(timeout=0), np.ones(2))


def test_batcher_drain_rejects_flushes_and_reports():
    """The first-class quiesce contract (ISSUE 10 satellite): drain
    refuses new submits with DrainingError (a QueueFullError carrying
    retry_after_s — existing backpressure handling applies), reports
    the unfinished count, and in-flight work keeps flushing."""
    mb = MicroBatcher(_echo_forward([]), buckets=(1, 4),
                      max_wait_us=0, start_thread=False)
    queued = [mb.submit(np.zeros(2, np.float32)) for _ in range(3)]
    # Manual-drive batcher: nothing consumes the queue, so a 0-budget
    # drain reports exactly the queued requests as unfinished.
    assert mb.drain(timeout_s=0.0) == 3
    assert mb.draining
    with pytest.raises(DrainingError) as exc:
        mb.submit(np.zeros(2, np.float32))
    assert exc.value.retry_after_s > 0
    assert isinstance(exc.value, QueueFullError)  # one backpressure
    #                                               set of classes fleet-wide
    assert mb.stats.snapshot()["counters"]["rejected_draining"] == 1
    # Draining gates ADMISSION, not dispatch: the queue still flushes.
    assert mb.run_once() == 3
    for f in queued:
        np.testing.assert_array_equal(f.result(timeout=0), np.zeros(2))
    assert mb.drain(timeout_s=0.0) == 0   # now fully drained
    mb.resume()
    ok = mb.submit(np.ones(2, np.float32))
    mb.run_once()
    np.testing.assert_array_equal(ok.result(timeout=0), np.full(2, 2.0))


def test_batcher_drain_waits_for_worker_flush():
    """With the worker thread running, drain blocks until queued work
    lands (returns 0) instead of failing it like close() would."""
    with MicroBatcher(_echo_forward([]), buckets=(1, 8),
                      max_wait_us=100) as mb:
        futs = [mb.submit(np.full(2, i, np.float32)) for i in range(5)]
        assert mb.drain(timeout_s=10.0) == 0
        for i, f in enumerate(futs):
            np.testing.assert_array_equal(
                f.result(timeout=0), np.full(2, 2.0 * i))


# --------------------------------------- multi-head + SLO tiers (ISSUE 12)
def test_batcher_coalesces_across_heads_one_dispatch():
    """Classifier and embedding requests inside one window ride ONE
    device batch; each future resolves to ITS head's row."""
    log = []
    mb = MicroBatcher(_multihead_echo(log), buckets=(1, 8),
                      max_wait_us=0, start_thread=False)
    futs = [mb.submit(np.full(2, i, np.float32), head=h)
            for i, h in enumerate(("probs", "features", "tokens",
                                   "probs"))]
    assert mb.run_once() == 4
    assert len(log) == 1   # ONE fused dispatch for the mixed batch
    assert log[0] == (8, ("probs", "features", "tokens", "probs"))
    scale = {"probs": 2.0, "features": 3.0, "tokens": 5.0}
    for i, (f, h) in enumerate(zip(futs, ("probs", "features",
                                          "tokens", "probs"))):
        np.testing.assert_array_equal(
            f.result(timeout=0), np.full(2, scale[h] * i))
    snap = mb.stats.snapshot()
    assert snap["counters"]["batches"] == 1
    assert snap["heads"]["probs"]["completed"] == 2
    assert snap["heads"]["features"]["completed"] == 1
    assert snap["heads"]["tokens"]["completed"] == 1


def test_batcher_missing_head_fails_request_not_batch():
    """A head the forward does not produce fails ITS future; siblings
    in the same batch still resolve — and the failure counts as
    head_errors, never as a completion in the per-head tables."""
    def fwd(x, mask, heads):
        return {"probs": x * 2.0}

    mb = MicroBatcher(fwd, buckets=(1, 4), max_wait_us=0,
                      start_thread=False)
    ok = mb.submit(np.ones(2, np.float32), head="probs")
    bad = mb.submit(np.ones(2, np.float32), head="features")
    assert mb.run_once() == 2
    np.testing.assert_array_equal(ok.result(timeout=0), np.full(2, 2.0))
    with pytest.raises(ValueError, match="no 'features' head"):
        bad.result(timeout=0)
    snap = mb.stats.snapshot()
    assert snap["counters"]["completed"] == 1
    assert snap["counters"]["head_errors"] == 1
    assert "features" not in {
        h for h, row in snap["heads"].items() if row["completed"]}


def test_batcher_deadline_shorter_than_fill_window_still_served():
    """A lone batch-tier request whose expiry deadline is SHORTER than
    the batch fill window must be dispatched off an idle device before
    it expires, not held for the fill window and then dropped."""
    log = []
    # max_wait is also the margin before the expiry at which the lone
    # request is dispatched: at 2 ms a loaded host woke too late (the
    # one tier-1 test that failed now and then, PR 30 and PR 32).
    mb = MicroBatcher(_echo_forward(log), buckets=(1, 8),
                      max_wait_us=20_000, batch_max_wait_us=300_000,
                      start_thread=False)
    fut = mb.submit(np.ones(2, np.float32), timeout=0.05, tier="batch")
    t0 = time.monotonic()
    assert mb.run_once() == 1
    assert time.monotonic() - t0 < 0.06   # not the 300 ms fill window
    np.testing.assert_array_equal(fut.result(timeout=0), np.full(2, 2.0))
    assert mb.stats.snapshot()["counters"]["expired"] == 0


def test_batcher_rejects_unknown_tier():
    mb = MicroBatcher(_echo_forward([]), buckets=(1,),
                      start_thread=False)
    with pytest.raises(ValueError, match="unknown tier"):
        mb.submit(np.zeros(2, np.float32), tier="bulk")


def test_batcher_batch_tier_waits_interactive_forces_dispatch():
    """Tiered batch-fill deadlines: a lone batch-tier request rides
    the queue for its (long) fill window; an interactive arrival caps
    the wait at max_wait — run_once returns as soon as the earliest
    fill deadline passes."""
    log = []
    mb = MicroBatcher(_echo_forward(log), buckets=(1, 8),
                      max_wait_us=0, batch_max_wait_us=60_000,
                      start_thread=False)
    t0 = time.monotonic()
    mb.submit(np.zeros(2, np.float32), tier="batch")
    assert mb.run_once() == 1
    waited = time.monotonic() - t0
    assert waited >= 0.05   # rode the 60 ms batch window (minus jitter)
    # Interactive company collapses the wait to max_wait (~0 here).
    mb.submit(np.zeros(2, np.float32), tier="batch")
    mb.submit(np.zeros(2, np.float32), tier="interactive")
    t0 = time.monotonic()
    assert mb.run_once() == 2   # one batch, both tiers coalesced
    assert time.monotonic() - t0 < 0.05


def test_batcher_interactive_wins_slots_batch_never_starves():
    """Priority at batch formation: interactive requests take the
    bucket slots first; a batch-tier request older than its fill
    window ESCALATES and can no longer be displaced."""
    log = []
    mb = MicroBatcher(_echo_forward(log), buckets=(1, 2),
                      max_wait_us=0, batch_max_wait_us=30_000,
                      start_thread=False)
    slow = mb.submit(np.zeros(2, np.float32), tier="batch")
    fast = [mb.submit(np.ones(2, np.float32)) for _ in range(2)]
    assert mb.run_once() == 2          # cap 2: both interactive win
    assert all(f.done() for f in fast)
    assert not slow.done()             # batch-tier displaced, queued
    time.sleep(0.04)                   # its 30 ms fill window passes
    more = [mb.submit(np.ones(2, np.float32)) for _ in range(2)]
    assert mb.run_once() == 2
    assert slow.done()                 # escalated: dispatched FIRST
    assert sum(f.done() for f in more) == 1   # one slot left
    mb.run_once()
    assert all(f.done() for f in more)


def test_batcher_tier_expiry_still_degrades():
    """The tier machinery composes with the existing degradation path:
    an expired batch-tier request sheds before occupying a batch AND
    steps the bucket cap down a rung, exactly like interactive expiry."""
    mb = MicroBatcher(_echo_forward([]), buckets=(1, 4, 8),
                      max_wait_us=0, recover_after=2,
                      start_thread=False)
    assert mb.effective_bucket_cap == 8
    dead = mb.submit(np.zeros(2, np.float32), timeout=0.0, tier="batch")
    time.sleep(0.002)
    live = mb.submit(np.zeros(2, np.float32))
    assert mb.run_once() == 1
    with pytest.raises(RequestExpired):
        dead.result(timeout=0)
    assert live.done()
    assert mb.effective_bucket_cap == 4   # degraded one rung
    snap = mb.stats.snapshot()
    assert snap["tiers"]["batch"]["expired"] == 1
    assert snap["counters"]["expired"] == 1


def test_batcher_segregated_mode_splits_heads():
    """The A/B baseline: segregate_heads=True runs the backbone once
    PER HEAD — the same admitted batch splits into per-head padded
    forwards (two fleets, same cadence) where the fused path runs one."""
    log = []
    mb = MicroBatcher(_multihead_echo(log), buckets=(1, 8),
                      max_wait_us=0, segregate_heads=True,
                      start_thread=False)
    p = [mb.submit(np.full(2, i, np.float32), head="probs")
         for i in range(2)]
    f = [mb.submit(np.full(2, i, np.float32), head="features")
         for i in range(2)]
    assert mb.run_once() == 4
    # TWO device dispatches for the mixed batch (vs the fused path's
    # one), each padded to its own bucket, each single-head.
    assert [entry[1] for entry in log] == [("probs", "probs"),
                                           ("features", "features")]
    for i, x in enumerate(p):
        np.testing.assert_array_equal(x.result(timeout=0),
                                      np.full(2, 2.0 * i))
    for i, x in enumerate(f):
        np.testing.assert_array_equal(x.result(timeout=0),
                                      np.full(2, 3.0 * i))
    snap = mb.stats.snapshot()
    assert snap["counters"]["batches"] == 2   # one per head


def test_engine_drain_cli_command(served_checkpoint, served_engine):
    """::drain quiesces through the engine and answers JSON; requests
    after it get DrainingError backpressure; resume() reopens."""
    from pytorch_vit_paper_replication_tpu.serve.__main__ import _answer

    _, train_dir, _ = served_checkpoint
    image = str(next(p for p in sorted(train_dir.rglob("*.jpg"))))
    try:
        reply = json.loads(_answer("::drain 5", served_engine, None))
        assert reply == {"draining": True, "unfinished": 0}
        with pytest.raises(DrainingError):
            served_engine.submit(np.zeros((32, 32, 3), np.float32))
        err = _answer(image, served_engine, None)
        assert "\tERROR\tDrainingError" in err
    finally:
        served_engine.resume()   # module-scoped engine: leave it open
    results = served_engine.predict(
        [np.zeros((32, 32, 3), np.float32)])
    assert len(results) == 1


def test_probs_cli_command_bit_identical(served_checkpoint,
                                         served_engine):
    """::probs answers the FULL softmax row, bit-identical to
    predict_image (what the fleet rollout's re-admission probe and
    fleet_bench's swapped-replica assert both rest on)."""
    from pytorch_vit_paper_replication_tpu.predictions import predict_image
    from pytorch_vit_paper_replication_tpu.serve.__main__ import _answer

    _, train_dir, classes = served_checkpoint
    image = next(p for p in sorted(train_dir.rglob("*.jpg")))
    _, _, probs_ref = predict_image(
        served_engine.model, served_engine._params, image, classes,
        transform=served_engine.transform)
    reply = json.loads(_answer(f"::probs {image}", served_engine, None))
    assert reply["label"] in classes
    got = np.asarray(reply["probs"], np.float32)
    np.testing.assert_array_equal(got, probs_ref)
    bad = json.loads(_answer("::probs /no/such/file.jpg",
                             served_engine, None))
    assert "error" in bad


def test_engine_fused_heads_bit_identity(served_checkpoint,
                                         served_engine):
    """ISSUE 12 parity satellite: the online pooled [D] embedding is
    bit-identical to (a) the OfflineEngine features head and (b) a
    direct ViTFeatureExtractor apply on the same checkpoint; the
    tokens head matches the raw backbone output; probs bit-identity
    vs predict_image is asserted by the existing round-trip test."""
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu.models import (
        ViTFeatureExtractor)
    from pytorch_vit_paper_replication_tpu.serve import OfflineEngine

    assert served_engine.heads == ("probs", "features", "tokens")
    _, train_dir, _ = served_checkpoint
    images = sorted(train_dir.rglob("*.jpg"))[:3]
    rows = np.stack([served_engine._to_row(p) for p in images])

    # Bit-identity is a SAME-SHAPE contract (a different batch shape
    # is a different XLA program whose reductions may round
    # differently — the predict_batch test documents the same): each
    # online request below dispatches as a bucket-1 batch, so every
    # reference runs its program at batch shape 1 too.
    # (a) offline features head: the SAME checkpoint params through
    # OfflineEngine's own compiled program on a 1-device mesh.
    import jax as _jax
    off = OfflineEngine(served_engine.model, served_engine._params,
                        head="features",
                        image_size=served_engine.image_size,
                        buckets=(1,), devices=_jax.devices()[:1])
    assert off.ladder == (1,)

    # (b) direct backbone apply (pool + float32, the offline
    # expression, hand-rolled — proves both engines, not one vs other).
    cfg = served_engine.model.config
    backbone = ViTFeatureExtractor(cfg)

    def feat(p, x):
        tokens = backbone.apply({"params": p}, x)
        pooled = tokens[:, 0] if cfg.pool == "cls" else \
            tokens.mean(axis=1)
        return pooled.astype(jnp.float32)

    feat_fn = jax.jit(feat)
    tok_fn = jax.jit(
        lambda p, x: backbone.apply({"params": p}, x).astype(
            jnp.float32))

    for i, img in enumerate(images):
        online = served_engine.submit(img, head="features").result(
            timeout=30)
        off_row = np.asarray(off.dispatch(rows[i:i + 1]))[0]
        direct = np.asarray(feat_fn(
            served_engine._params["backbone"],
            jnp.asarray(rows[i:i + 1])))[0]
        np.testing.assert_array_equal(online, off_row)
        np.testing.assert_array_equal(online, direct)
        tokens = served_engine.submit(img, head="tokens",
                                      tier="batch").result(timeout=30)
        tok_direct = np.asarray(tok_fn(
            served_engine._params["backbone"],
            jnp.asarray(rows[i:i + 1])))[0]
        np.testing.assert_array_equal(tokens, tok_direct)


def test_engine_rejects_unknown_head(served_engine):
    with pytest.raises(ValueError, match="unknown head"):
        served_engine.submit(np.zeros((32, 32, 3), np.float32),
                             head="logits")


def test_cli_head_tier_protocol(served_checkpoint, served_engine):
    """The line protocol's multi-head surface: ::head/::tier set
    connection state, a features request answers full-precision JSON
    that reconstructs the served row bit-for-bit, and the one-shot
    ::req inline form needs no state."""
    from pytorch_vit_paper_replication_tpu.serve.__main__ import (
        ConnState, _answer)

    _, train_dir, _ = served_checkpoint
    image = str(next(p for p in sorted(train_dir.rglob("*.jpg"))))
    ref = served_engine.submit(image, head="features").result(timeout=30)

    state = ConnState()
    assert _answer("::head features", served_engine, None,
                   state) == "::head\tok\tfeatures"
    assert _answer("::tier batch", served_engine, None,
                   state) == "::tier\tok\tbatch"
    reply = _answer(image, served_engine, None, state)
    path, head, payload = reply.split("\t", 2)
    assert path == image and head == "features"
    got = np.asarray(json.loads(payload), np.float32)
    np.testing.assert_array_equal(got, ref)

    # Bad values keep the state and answer the ERROR shape.
    bad = _answer("::head logits", served_engine, None, state)
    assert "\tERROR\tValueError" in bad and state.head == "features"
    bad = _answer("::tier bulk", served_engine, None, state)
    assert "\tERROR\tValueError" in bad and state.tier == "batch"

    # One-shot ::req overrides a fresh connection's defaults; the
    # reply echoes the BARE path.
    fresh = ConnState()
    reply = _answer(f"::req head=tokens tier=batch {image}",
                    served_engine, None, fresh)
    path, head, payload = reply.split("\t", 2)
    assert path == image and head == "tokens"
    tok = np.asarray(json.loads(payload), np.float32)
    ref_tok = served_engine.submit(image, head="tokens").result(
        timeout=30)
    np.testing.assert_array_equal(tok, ref_tok)
    assert fresh.head == "probs"    # one-shot: state untouched
    bad = _answer("::req head=tokens", served_engine, None, fresh)
    assert "\tERROR\tValueError" in bad   # no path


def test_pipe_mode_head_tier_and_req(served_checkpoint, served_engine,
                                     monkeypatch, capsys):
    """The stdin/stdout pipe mode speaks the same multi-head surface:
    ::head/::tier flush the submit-ahead window and retag the stream;
    ::req rides the pipeline as a request."""
    import io

    from pytorch_vit_paper_replication_tpu.serve.__main__ import (
        _serve_stdin)

    _, train_dir, classes = served_checkpoint
    image = str(next(p for p in sorted(train_dir.rglob("*.jpg"))))
    ref = served_engine.submit(image, head="features").result(timeout=30)
    monkeypatch.setattr("sys.stdin", io.StringIO(
        f"{image}\n::head features\n{image}\n"
        f"::req head=probs tier=batch {image}\n"))
    _serve_stdin(served_engine, None)
    out = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(out) == 4
    assert out[0].split("\t")[1] in classes          # default: probs TSV
    assert out[1] == "::head\tok\tfeatures"
    path, head, payload = out[2].split("\t", 2)
    assert path == image and head == "features"
    got = np.asarray(json.loads(payload), np.float32)
    # Protocol test, not bit-identity (that's pinned at controlled
    # shapes elsewhere): the pipelined features request may coalesce
    # with the ::req one into a different bucket shape = a different
    # XLA program (the predict_batch cross-shape caveat).
    assert got.shape == ref.shape
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.05)
    assert out[3].split("\t")[1] in classes          # ::req probs TSV


def test_pipe_mode_records_serve_request_root_span(
        served_checkpoint, served_engine, monkeypatch, capsys,
        tmp_path):
    """Pipelined stdin requests close a ``serve.request`` ROOT span
    (regression: the submit-ahead path minted the ingress context and
    the batcher wrote its children, but the root itself was never
    recorded — the merged tree held orphans)."""
    import io

    from pytorch_vit_paper_replication_tpu.serve.__main__ import (
        _serve_stdin)
    from pytorch_vit_paper_replication_tpu.telemetry.tracing import (
        configure_tracer)

    _, train_dir, _classes = served_checkpoint
    image = str(next(p for p in sorted(train_dir.rglob("*.jpg"))))
    sink = tmp_path / "sink_stdin.jsonl"
    configure_tracer(str(sink), role="replica", sample_rate=1.0)
    try:
        monkeypatch.setattr("sys.stdin", io.StringIO(f"{image}\n"))
        _serve_stdin(served_engine, None)
    finally:
        configure_tracer(None)
    out = [ln for ln in capsys.readouterr().out.splitlines() if ln]
    assert len(out) == 1 and "ERROR" not in out[0]
    rows = [json.loads(ln) for ln in
            sink.read_text().splitlines() if ln]
    roots = [r for r in rows if r["name"] == "serve.request"]
    assert len(roots) == 1
    root = roots[0]
    assert root["parent_id"] is None
    assert root["t1"] >= root["t0"]
    children = [r for r in rows if r["name"].startswith("batch.")]
    assert children, "batcher children missing from the sink"
    for ch in children:
        assert ch["trace_id"] == root["trace_id"]
        assert ch["parent_id"] == root["span_id"]
        # children nest inside the root's wall window (1 ms slack: the
        # monotonic/perf_counter epoch anchors are captured µs apart)
        assert root["t0"] <= ch["t0"] + 1e-3
        assert ch["t1"] <= root["t1"] + 1e-3


def test_stats_publish_head_tier_instruments(served_engine):
    """The serve_head_*/serve_tier_* instruments (ISSUE 12 satellite)
    ride ::metrics after mixed traffic."""
    from pytorch_vit_paper_replication_tpu.serve.__main__ import _answer

    row = np.zeros((32, 32, 3), np.float32)
    served_engine.submit(row, head="features",
                         tier="batch").result(timeout=30)
    served_engine.predict([row])
    text = _answer("::metrics", served_engine, None)
    assert "# TYPE vit_serve_head_features_total counter" in text
    assert "# TYPE vit_serve_tier_batch_total counter" in text
    assert "vit_serve_tier_batch_p99_s " in text
    snap = served_engine.snapshot()
    assert snap["heads"]["features"]["completed"] >= 1
    assert snap["tiers"]["batch"]["completed"] >= 1


def test_snapshot_model_tier_declared_overrides_arch(served_checkpoint,
                                                     served_engine):
    """``--model-tier``: an operator-declared deployment role wins
    over the arch-derived label in ::stats (a cascade's student
    replica reports "student", not just "ViT-Ti/16"); an undeclared
    engine keeps self-reporting its architecture."""
    ckpt, _, classes = served_checkpoint
    assert served_engine.snapshot()["model_tier"] == "ViT-Ti/16"
    eng = InferenceEngine.from_checkpoint(
        ckpt, preset="ViT-Ti/16", class_names=classes,
        buckets=(1,), warmup=False, use_manifest=False,
        model_tier="student")
    try:
        assert eng.snapshot()["model_tier"] == "student"
    finally:
        eng.close()


# ------------------------------------------------- pad+mask correctness
def test_pad_rows_never_change_real_logits(tiny_config):
    """Same real rows, same bucket shape, DIFFERENT pad contents ->
    bit-identical real-row outputs (rows of a ViT forward are
    independent; this is the property the mask contract rests on)."""
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu.models import ViT

    model = ViT(tiny_config)
    rng = jax.random.key(0)
    s = tiny_config.image_size
    params = model.init(rng, jnp.zeros((1, s, s, 3)))["params"]
    fwd = jax.jit(lambda x: model.apply({"params": params}, x))

    real = np.asarray(
        jax.random.uniform(jax.random.key(1), (3, s, s, 3)), np.float32)
    pad_a, _ = pad_rows_to_bucket(real, 8)                 # row-0 pad
    pad_b = np.concatenate(
        [real, np.asarray(jax.random.uniform(jax.random.key(2),
                                             (5, s, s, 3)), np.float32)])
    out_a = np.asarray(fwd(jnp.asarray(pad_a)))[:3]
    out_b = np.asarray(fwd(jnp.asarray(pad_b)))[:3]
    np.testing.assert_array_equal(out_a, out_b)


# ---------------------------------------------- checkpoint -> serve trip
@pytest.fixture(scope="module")
def served_checkpoint(tmp_path_factory):
    """Train a tiny ViT 1 epoch through the real CLI (writes the final
    export + transform.json exactly like production) and return
    (checkpoint_dir, train_dir, class_names)."""
    from pytorch_vit_paper_replication_tpu.data import (
        make_synthetic_image_folder)
    from pytorch_vit_paper_replication_tpu.train import main as train_main

    root = tmp_path_factory.mktemp("serve_ckpt")
    train_dir, test_dir = make_synthetic_image_folder(
        root / "ds", train_per_class=4, test_per_class=2, image_size=32)
    train_main([
        "--train-dir", str(train_dir), "--test-dir", str(test_dir),
        "--preset", "ViT-Ti/16", "--image-size", "32", "--patch-size",
        "16", "--dtype", "float32", "--attention", "xla", "--epochs", "1",
        "--batch-size", "8", "--mesh-data", "8", "--num-workers", "1",
        "--checkpoint-dir", str(root / "ckpt"),
    ])
    classes = sorted(d.name for d in train_dir.iterdir() if d.is_dir())
    return root / "ckpt", train_dir, classes


@pytest.fixture(scope="module")
def served_engine(served_checkpoint):
    ckpt, _, classes = served_checkpoint
    eng = InferenceEngine.from_checkpoint(
        ckpt, preset="ViT-Ti/16", class_names=classes,
        buckets=(1, 4, 8), max_wait_us=1000)
    yield eng
    eng.close()


def test_roundtrip_bit_exact_vs_predict_image(served_checkpoint,
                                              served_engine):
    """Engine probs == predict_image probs bit-for-bit on the same
    image (same params, same transform, same jitted expression)."""
    from pytorch_vit_paper_replication_tpu.predictions import predict_image

    _, train_dir, classes = served_checkpoint
    image = next(p for p in sorted(train_dir.rglob("*.jpg")))
    label_ref, prob_ref, probs_ref = predict_image(
        served_engine.model, served_engine._params, image, classes,
        transform=served_engine.transform)
    result = served_engine.submit(image).result(timeout=30)
    np.testing.assert_array_equal(result.probs, probs_ref)
    assert result.label == label_ref
    assert result.prob == prob_ref


def test_roundtrip_honors_transform_json(served_checkpoint, served_engine):
    """The engine preprocesses with the checkpoint's recorded transform
    (32px, scratch run => NO ImageNet normalize), not the predict
    default (224px, normalize ON)."""
    from pytorch_vit_paper_replication_tpu.data.transforms import (
        make_transform)

    ckpt, train_dir, _ = served_checkpoint
    spec = json.loads((ckpt / "transform.json").read_text())
    assert served_engine.image_size == spec["image_size"] == 32
    image = next(p for p in sorted(train_dir.rglob("*.jpg")))
    from PIL import Image
    with Image.open(image) as img:
        expect = np.asarray(make_transform(**spec)(img))
    got = served_engine._to_row(image)
    np.testing.assert_array_equal(got, expect)
    assert got.shape == (32, 32, 3)
    assert got.min() >= 0.0 and got.max() <= 1.0  # un-normalized [0,1]


def test_engine_warmup_then_no_new_shapes(served_engine):
    """Every dispatch after warmup hits a warmed bucket shape."""
    shapes = set()
    orig = served_engine._fwd

    def counting(p, x):
        shapes.add(x.shape[0])
        return orig(p, x)

    served_engine._fwd = counting
    try:
        results = served_engine.predict(
            [np.zeros((32, 32, 3), np.float32)] * 3)
    finally:
        served_engine._fwd = orig
    assert len(results) == 3
    assert shapes <= set(served_engine.buckets)


def test_predict_batch_uses_bucket_ladder(served_checkpoint, monkeypatch):
    """Directory prediction chunks onto the ladder (6 images on a
    (1, 4, 8) ladder dispatch exactly plan_buckets(6) shapes) and every
    result matches the single-image path."""
    import pytorch_vit_paper_replication_tpu.predictions as predictions

    ckpt, train_dir, classes = served_checkpoint
    images = sorted(train_dir.rglob("*.jpg"))[:6]
    eng = InferenceEngine.from_checkpoint(
        ckpt, preset="ViT-Ti/16", class_names=classes, warmup=False,
        use_manifest=False)  # ad-hoc ladder test; skip the shared manifest

    shapes = []
    real_jf = predictions._jitted_forward

    def spying_jf(model):
        fwd = real_jf(model)

        def wrapped(params, x):
            shapes.append(int(x.shape[0]))
            return fwd(params, x)
        return wrapped

    monkeypatch.setattr(predictions, "_jitted_forward", spying_jf)
    batched = predictions.predict_batch(
        eng.model, eng._params, images, classes,
        transform=eng.transform, buckets=(1, 4, 8))
    assert shapes == plan_buckets(6, (1, 4, 8))
    singles = [predictions.predict_image(
        eng.model, eng._params, p, classes,
        transform=eng.transform)[:2] for p in images]
    for (bl, bp), (sl, sp) in zip(batched, singles):
        assert bl == sl
        # Different batch shapes are different XLA programs; CPU
        # vectorization reorders float reductions at ~1e-5.
        assert bp == pytest.approx(sp, abs=1e-4)
    eng.close()


# ------------------------------------------------------------------ CLI
def test_socket_cli_serves_and_reports_stats(served_checkpoint):
    """End-to-end socket mode: concurrent clients get answers, ::stats
    returns a JSON snapshot."""
    from pytorch_vit_paper_replication_tpu.serve.__main__ import (
        _serve_socket)

    ckpt, train_dir, classes = served_checkpoint
    eng = InferenceEngine.from_checkpoint(
        ckpt, preset="ViT-Ti/16", class_names=classes, buckets=(1, 4),
        max_wait_us=5000,
        use_manifest=False)  # ad-hoc ladder test; skip the shared manifest
    image = str(next(p for p in sorted(train_dir.rglob("*.jpg"))))
    holder = {}
    ready = threading.Event()

    def on_ready(srv):
        holder["srv"] = srv
        ready.set()

    t = threading.Thread(target=_serve_socket,
                         args=(eng, "127.0.0.1", 0, None, on_ready),
                         daemon=True)
    t.start()
    assert ready.wait(30)
    port = holder["srv"].server_address[1]

    def ask(line):
        with socket.create_connection(("127.0.0.1", port), timeout=30) as s:
            s.sendall((line + "\n").encode())
            return s.makefile().readline().strip()

    replies = []
    threads = [threading.Thread(
        target=lambda: replies.append(ask(image))) for _ in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(30)
    assert len(replies) == 3
    for r in replies:
        path, label, prob = r.split("\t")
        assert path == image and label in classes
        assert 0.0 <= float(prob) <= 1.0
    stats = json.loads(ask("::stats"))
    assert stats["counters"]["completed"] >= 3
    assert "latency_s" in stats and "buckets" in stats
    holder["srv"].shutdown()
    t.join(10)
    eng.close()


def test_predict_cli_classes_file(served_checkpoint, tmp_path, capsys):
    """--classes-file replaces greedy-nargs --classes and classifies."""
    from pytorch_vit_paper_replication_tpu.predict import main as predict_main

    ckpt, train_dir, classes = served_checkpoint
    cls_file = tmp_path / "classes.txt"
    cls_file.write_text("\n".join(classes) + "\n")
    image = str(next(p for p in sorted(train_dir.rglob("*.jpg"))))
    # Image path LAST — the arrangement greedy --classes silently eats.
    predict_main(["--checkpoint", str(ckpt), "--preset", "ViT-Ti/16",
                  "--classes-file", str(cls_file), image])
    out = capsys.readouterr().out
    assert image in out
    assert any(c in out for c in classes)


def test_cli_metrics_prometheus(served_engine):
    """The ::metrics command answers the shared telemetry registry as
    Prometheus text exposition — serve counters synced in, engine
    gauges included, TYPE headers well-formed (ISSUE 5)."""
    from pytorch_vit_paper_replication_tpu.serve.__main__ import _answer

    served_engine.predict([np.zeros((32, 32, 3), np.float32)] * 2)
    text = _answer("::metrics", served_engine, None)
    # The multi-line block is framed by a trailing blank line (after
    # the transport's own newline) so pipelining clients can find the
    # end of the response on this line-per-response protocol.
    assert text.endswith("\n") and not text.endswith("\n\n")
    assert "# TYPE vit_serve_submitted_total counter" in text
    assert "# TYPE vit_serve_completed_total counter" in text
    assert "# TYPE vit_serve_queue_depth gauge" in text
    assert "# TYPE vit_serve_latency_total_p50_s gauge" in text
    # Counters carry the real totals (>= the two requests just served).
    submitted = next(
        line for line in text.splitlines()
        if line.startswith("vit_serve_submitted_total "))
    assert float(submitted.split()[1]) >= 2
    # Every sample line is "name[{labels}] value" — scrapeable shape.
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        assert name.startswith("vit_")
        float(value)


def test_serve_stats_emit_jsonl(tmp_path):
    """ServeStats.emit writes MetricsLogger-compatible JSONL."""
    from pytorch_vit_paper_replication_tpu.metrics import MetricsLogger
    from pytorch_vit_paper_replication_tpu.serve import ServeStats

    stats = ServeStats()
    stats.observe_latency("total", 0.01)
    stats.observe_batch(8, 6)
    logger = MetricsLogger(jsonl_path=tmp_path / "serve.jsonl")
    stats.emit(logger, phase="test")
    logger.close()
    rec = json.loads((tmp_path / "serve.jsonl").read_text().splitlines()[0])
    assert rec["lat_total_p50"] == pytest.approx(0.01)
    assert rec["occupancy_b8"] == 0.75
    assert rec["batches"] == 1 and rec["phase"] == "test"
