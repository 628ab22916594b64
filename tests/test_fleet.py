"""Serving-fleet tests (ISSUE 10): device partitioning, routing
policies, the router's exactly-once re-dispatch under replica SIGKILL,
health-gated membership + supervised restart, the rolling checkpoint
hot-swap with rollback, phase-tagged bench windows, and one REAL
serve-CLI replica behind the router proving cross-process bit-identity.

Most process tests ride ``tests/data/fake_replica.py`` — a jax-free
stand-in speaking the exact protocol slice the fleet layer touches —
so supervision semantics run in tier-1 time; the real-replica test and
``tools/fleet_bench.py`` (bench gate + committed run) cover the true
serve CLI.
"""

import importlib.util
import json
import os
import signal
import socket
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from pytorch_vit_paper_replication_tpu.serve.fleet import (
    FleetRouter, LeastLoadedAffinity, ReplicaManager, ReplicaSpec,
    ReplicaView, RoundRobin, build_serve_command, is_backpressure,
    make_policy, partition_devices, replica_env, rolling_swap)
from pytorch_vit_paper_replication_tpu.telemetry.registry import (
    HELP_TEXT, INSTRUMENTS, TelemetryRegistry)

REPO = Path(__file__).resolve().parent.parent
FAKE = REPO / "tests" / "data" / "fake_replica.py"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_fake_module():
    spec = importlib.util.spec_from_file_location("fake_replica", FAKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------- partitioning
def test_partition_devices_even_and_wrapped():
    assert partition_devices(8, 2) == [[0, 1, 2, 3], [4, 5, 6, 7]]
    assert partition_devices(8, 3) == [[0, 1, 2], [3, 4, 5], [6, 7]]
    assert partition_devices(2, 4) == [[0], [1], [0], [1]]
    assert partition_devices(1, 1) == [[0]]
    with pytest.raises(ValueError):
        partition_devices(0, 1)
    with pytest.raises(ValueError):
        partition_devices(4, 0)


def test_replica_env_exports_partition():
    env = replica_env([2, 3], base={"KEEP": "1"})
    assert env["KEEP"] == "1"
    assert env["TPU_VISIBLE_DEVICES"] == "2,3"
    assert env["TPU_VISIBLE_CHIPS"] == "2,3"
    assert env["VIT_REPLICA_DEVICES"] == "2,3"


# ------------------------------------------------------------ policy
def _view(rid, *, up=True, draining=False, inflight=0, queue=0,
          warm=(1, 8), addr=("127.0.0.1", 1)):
    return ReplicaView(rid=rid, address=addr, up=up, draining=draining,
                       inflight=inflight, queue_depth=queue,
                       warm_rungs=tuple(warm), restarts=0)


def test_affinity_prefers_warm_rung_then_least_loaded():
    pol = LeastLoadedAffinity()
    views = [_view("r0", warm=(1,), inflight=0),
             _view("r1", warm=(8,), inflight=5)]
    # Affinity wins over load: r1 is busier but warm for rung 8.
    assert pol.choose(views, rung=8) == "r1"
    # No rung hint: pure least-loaded.
    assert pol.choose(views) == "r0"
    # Nobody warm for the rung: least-loaded fallback, not a refusal.
    assert pol.choose(views, rung=32) == "r0"
    # Load ties break by rid (deterministic).
    tied = [_view("rb"), _view("ra")]
    assert pol.choose(tied) == "ra"


def test_policy_filters_down_draining_excluded():
    pol = LeastLoadedAffinity()
    views = [_view("r0", up=False), _view("r1", draining=True),
             _view("r2", addr=None), _view("r3", inflight=9)]
    assert pol.choose(views) == "r3"
    assert pol.choose(views, exclude=frozenset({"r3"})) is None
    assert pol.choose([]) is None


def test_round_robin_cycles():
    pol = RoundRobin()
    views = [_view("r0"), _view("r1")]
    picks = [pol.choose(views) for _ in range(4)]
    assert picks == ["r0", "r1", "r0", "r1"]


def test_make_policy_names():
    assert make_policy("affinity").name == "affinity"
    assert make_policy("round-robin").name == "round-robin"
    with pytest.raises(ValueError, match="unknown routing policy"):
        make_policy("nope")


def test_fleet_instruments_declared_with_help():
    """Every fleet_route_*/fleet_swap_*/replica_* instrument the
    subsystem publishes is declared with HELP_TEXT (vitlint's
    instrument rules enforce the publish sites; this pins the names)."""
    for name in ("fleet_route_requests_total", "fleet_route_retries_total",
                 "fleet_route_rejected_total", "fleet_route_errors_total",
                 "fleet_route_inflight", "fleet_route_lat_s",
                 "fleet_replicas_up", "fleet_swaps_total",
                 "fleet_swap_failures_total",
                 "fleet_swap_rollbacks_total", "fleet_swap_active",
                 "fleet_swap_last_s", "replica_restarts_total"):
        assert name in INSTRUMENTS, name
        assert name in HELP_TEXT, name


# ----------------------------------------------------- phase windows
def test_phase_report_splits_on_marks():
    sb = _load_tool("serve_bench")
    marks = sb.parse_marks(["3=during", "8=post"])
    assert marks == [(3.0, "during"), (8.0, "post")]
    samples = [(1.0, 0.010, True), (4.0, 0.050, True),
               (4.5, 0.2, False), (9.0, 0.020, True)]
    rep = sb.phase_report(samples, marks, first_label="pre")
    assert list(rep) == ["pre", "during", "post"]
    assert rep["pre"]["count"] == 1 and rep["pre"]["p99_ms"] == 10.0
    assert rep["during"]["count"] == 1 and rep["during"]["errors"] == 1
    assert rep["during"]["p99_ms"] == 50.0   # errors never pollute p99
    assert rep["post"]["p50_ms"] == 20.0
    empty = sb.phase_report([], marks, first_label="pre")
    assert empty["pre"]["p99_ms"] is None
    with pytest.raises(ValueError):
        sb.parse_marks(["nolabel"])


def test_serve_bench_open_loop_carries_phases():
    """An open-loop serve_bench run with marks reports per-phase
    percentiles (the --mark satellite, engine-level)."""
    sb = _load_tool("serve_bench")
    engine = sb.make_engine("ViT-Ti/16", 32, 3, (1, 4), 1000, 256)
    try:
        out = sb.run_open_loop(engine, rate_rps=40.0, duration_s=1.2,
                               timeout_s=10.0,
                               marks=[(0.6, "late")])
    finally:
        engine.close()
    assert set(out["phases"]) == {"start", "late"}
    assert (out["phases"]["start"]["count"]
            + out["phases"]["late"]["count"]) == out["completed"]


# ------------------------------------------------------ fake fleet
def _fake_factory(warm_by_rid=None, delay_s=0.0):
    def factory(spec):
        cmd = [sys.executable, str(FAKE), "--ckpt", spec.checkpoint]
        warm = (warm_by_rid or {}).get(spec.rid)
        if warm:
            cmd += ["--warm", warm]
        if delay_s:
            cmd += ["--delay-s", str(delay_s)]
        return cmd
    return factory


def _mk_fleet(tmp_path, *, warm_by_rid=None, delay_s=0.0, n=2,
              ckpt="ckA", auto_restart=True, expected_rungs=None,
              max_retries=2, max_inflight=1024):
    registry = TelemetryRegistry()
    specs = [ReplicaSpec(rid=f"r{i}", checkpoint=str(tmp_path / ckpt))
             for i in range(n)]
    manager = ReplicaManager(
        specs, command_factory=_fake_factory(warm_by_rid, delay_s),
        env_factory=lambda spec: dict(os.environ),
        health_interval_s=0.05, stale_after_s=1.0,
        restart_backoff_s=(0.1, 0.5), auto_restart=auto_restart,
        expected_rungs=expected_rungs, registry=registry)
    router = FleetRouter(manager, registry=registry,
                         max_retries=max_retries,
                         max_inflight=max_inflight,
                         request_timeout_s=30.0)
    return manager, router, registry


def _ask(address, lines, timeout=30.0):
    """Open one connection, send the lines, read one reply each."""
    with socket.create_connection(address, timeout=timeout) as sock:
        sock.settimeout(timeout)
        rfile = sock.makefile("r", encoding="utf-8")
        replies = []
        for line in lines:
            sock.sendall((line + "\n").encode())
            replies.append(rfile.readline().rstrip("\n"))
        rfile.close()
        return replies


def _ask_block(address, line, timeout=30.0):
    """One command whose reply is a blank-line-framed multi-line block
    (::metrics)."""
    with socket.create_connection(address, timeout=timeout) as sock:
        sock.settimeout(timeout)
        rfile = sock.makefile("r", encoding="utf-8")
        sock.sendall((line + "\n").encode())
        lines = []
        for reply in rfile:
            if reply == "\n":
                break
            lines.append(reply)
        rfile.close()
        return "".join(lines)


def test_router_routes_and_answers_stats_metrics(tmp_path):
    manager, router, registry = _mk_fleet(tmp_path)
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()
        (reply,) = _ask(router.address, ["img1.jpg"])
        path, tag, prob = reply.split("\t")
        assert path == "img1.jpg" and tag == "ckA"
        assert float(prob) == pytest.approx(0.9)
        (stats,) = _ask(router.address, ["::stats"])
        snap = json.loads(stats)
        assert snap["policy"] == "affinity"
        assert set(snap["replicas"]) == {"r0", "r1"}
        assert all(r["up"] for r in snap["replicas"].values())
        assert snap["counters"]["fleet_route_requests_total"] >= 1
        metrics = _ask_block(router.address, "::metrics")
        assert "# TYPE vit_fleet_route_requests_total counter" in metrics
        assert "vit_fleet_replicas_up 2" in metrics
        assert "vit_replica_up_r0 1" in metrics


def test_replica_with_failed_warmup_is_not_up(tmp_path, monkeypatch):
    """A serve replica whose rung compile failed keeps answering ::stats
    from the jit path; its snapshot carries warmup.error, and the health
    round takes it out of the fleet at once instead of calling a server
    that cannot run its program healthy."""
    manager, _, _ = _mk_fleet(tmp_path)
    with manager:
        manager.start()
        assert manager.wait_ready(20.0)
        real_poll = manager._poll_stats
        sick = manager.address_of("r0")

        def poll(addr):
            snap = real_poll(addr)
            if snap is not None and addr == sick:
                snap["warmup"] = {"done": False,
                                  "error": "XlaRuntimeError: boom"}
            return snap

        monkeypatch.setattr(manager, "_poll_stats", poll)
        # The health thread may be mid-round on a snapshot taken before
        # the patch: two rounds later every verdict is the patched one.
        time.sleep(3 * manager.health_interval_s)
        manager.poll_once()
        assert not manager.view("r0").up
        assert manager.view("r1").up


def test_router_rung_affinity_steers_to_warm_replica(tmp_path):
    manager, router, _ = _mk_fleet(
        tmp_path, warm_by_rid={"r0": "1", "r1": "8"})
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()
        replies = _ask(router.address,
                       ["::rung 8"] + ["x.jpg"] * 4)
        assert replies[0] == "::rung\tok\t8"
        # Every request from this rung-8 connection rode r1 — but the
        # fake's tag is the ckpt basename (same for both), so assert
        # through the replicas' own served counters instead.
        s0 = json.loads(manager.request("r0", "::stats"))
        s1 = json.loads(manager.request("r1", "::stats"))
        assert s1["counters"]["completed"] == 4
        assert s0["counters"]["completed"] == 0


def test_router_head_tier_relay_stateless(tmp_path):
    """ISSUE 12: ::head/::tier are CLIENT-connection state at the
    router; non-default traffic relays as the inline ::req form (the
    pooled replica connections are shared, so replica-side state can
    never be trusted), the reply echoes the bare path, and the fake
    replica's tag echo proves which head/tier actually arrived."""
    manager, router, _ = _mk_fleet(tmp_path, n=1)
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()
        replies = _ask(router.address, [
            "::head features", "::tier batch", "img1.jpg",
            "::head probs", "::tier interactive", "img2.jpg",
            "::req head=tokens img3.jpg",
            "::head logits",
        ])
        assert replies[0] == "::head\tok\tfeatures"
        assert replies[1] == "::tier\tok\tbatch"
        path, tag, _prob = replies[2].split("\t")
        assert path == "img1.jpg" and tag == "ckA:features:batch"
        # Back to defaults: the relayed line is the BARE path again
        # (byte-identical to the pre-multi-head protocol).
        assert replies[3] == "::head\tok\tprobs"
        assert replies[4] == "::tier\tok\tinteractive"
        assert replies[5].split("\t")[1] == "ckA"
        # One-shot ::req: overrides without touching connection state.
        path, tag, _prob = replies[6].split("\t")
        assert path == "img3.jpg" and tag == "ckA:tokens:interactive"
        assert "\tERROR\tValueError" in replies[7]   # bad head value


def test_router_refuses_unknown_control_commands(tmp_path):
    """Control lines are router-owned: ::drain must NOT relay to a
    replica (any client could permanently quiesce it through the
    front door) — it answers an error, and the replicas never see it."""
    manager, router, _ = _mk_fleet(tmp_path)
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()
        (reply,) = _ask(router.address, ["::drain 5"])
        assert "\tERROR\t" in reply and "unknown" in reply
        # The replicas still admit traffic (nothing was drained).
        (ok,) = _ask(router.address, ["still.jpg"])
        assert "\tERROR\t" not in ok


def test_router_admission_bounds_inflight(tmp_path):
    manager, router, registry = _mk_fleet(tmp_path, max_inflight=0)
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()
        (reply,) = _ask(router.address, ["x.jpg"])
        assert "\tERROR\tQueueFullError" in reply
        assert "retry after" in reply
        assert is_backpressure(reply)
        counters = registry.snapshot()["counters"]
        assert counters["fleet_route_rejected_total"] == 1


def test_router_no_replica_available_is_explicit_backpressure(tmp_path):
    manager, router, registry = _mk_fleet(
        tmp_path, ckpt="ckbad", auto_restart=False)
    with manager, router:
        manager.start()   # fakes exit(3) before listening
        router.start()
        time.sleep(0.3)
        (reply,) = _ask(router.address, ["x.jpg"])
        assert "\tERROR\tNoReplicaAvailable" in reply
        assert "retry after" in reply
        counters = registry.snapshot()["counters"]
        assert counters["fleet_route_errors_total"] == 1


def test_replica_sigkill_mid_load_redispatch_exactly_once(tmp_path):
    """THE replica-death satellite: SIGKILL a replica under live load;
    every request is answered exactly once (the router re-dispatches
    the failed ones to the survivor), the dead replica goes down
    within stale_after_s, and the supervised restart re-admits it."""
    manager, router, registry = _mk_fleet(
        tmp_path, warm_by_rid={"r0": "1", "r1": "8"}, delay_s=0.25)
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()

        n_clients = 12
        replies: list = [None] * n_clients
        barrier = threading.Barrier(n_clients + 1)

        def client(i):
            barrier.wait(timeout=20)
            # No rung hint: least-loaded spreads load over BOTH
            # replicas, so some requests are mid-flight on the victim.
            (replies[i],) = _ask(router.address, [f"img{i}.jpg"],
                                 timeout=60.0)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        barrier.wait(timeout=20)
        time.sleep(0.1)   # let requests land on both replicas
        victim_pid = manager.pid_of("r1")
        down_at = [None]
        watch_stop = threading.Event()

        def watch_down():
            while not watch_stop.is_set():
                if not manager.view("r1").up:
                    down_at[0] = time.monotonic()
                    return
                time.sleep(0.01)

        # The supervised restart re-admits r1 within ~100 ms, so the
        # down transition must be observed CONCURRENTLY, not after the
        # load joins.
        watcher = threading.Thread(target=watch_down, daemon=True)
        watcher.start()
        t_kill = time.monotonic()
        os.kill(victim_pid, signal.SIGKILL)
        for t in threads:
            t.join(90)

        # Exactly once: every client got exactly one non-error reply.
        assert all(r is not None for r in replies)
        assert all("\tERROR\t" not in r for r in replies), replies
        counters = registry.snapshot()["counters"]
        assert counters["fleet_route_requests_total"] == n_clients
        assert counters.get("fleet_route_retries_total", 0) >= 1

        # Down within stale_after_s of the kill (process death is
        # detected by poll(), faster than the staleness deadline).
        watcher.join(manager.stale_after_s + 2.0)
        watch_stop.set()
        assert down_at[0] is not None
        assert down_at[0] <= t_kill + manager.stale_after_s

        # Supervised restart re-admits it...
        assert manager.wait_healthy("r1", 20.0)
        assert counters_after_restart(registry) >= 1
        # ...and rung-8 traffic steers to it again (it is routable,
        # not just alive).
        before = json.loads(
            manager.request("r1", "::stats"))["counters"]["completed"]
        _ask(router.address, ["::rung 8", "again.jpg"])
        after = json.loads(
            manager.request("r1", "::stats"))["counters"]["completed"]
        assert after == before + 1


def counters_after_restart(registry) -> int:
    return registry.snapshot()["counters"].get(
        "replica_restarts_total", 0)


def test_rolling_swap_fakes_zero_downtime(tmp_path):
    """Rolling swap over fakes: replicas move to the new checkpoint
    one at a time (never both unroutable), requests keep being
    answered throughout, ::probs flips to the new checkpoint's row."""
    fake = _load_fake_module()
    manager, router, registry = _mk_fleet(
        tmp_path, warm_by_rid={"r0": "1,8", "r1": "1,8"},
        expected_rungs=(1, 8))
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()

        stop = threading.Event()
        errors: list = []
        answered = [0]
        overlap = [0]

        def background_load():
            while not stop.is_set():
                (r,) = _ask(router.address, ["bg.jpg"], timeout=30.0)
                answered[0] += 1
                if "\tERROR\t" in r:
                    errors.append(r)
                time.sleep(0.01)

        def watch_membership():
            while not stop.is_set():
                views = manager.views()
                if sum(1 for v in views if not v.routable) > 1:
                    overlap[0] += 1
                time.sleep(0.01)

        lt = threading.Thread(target=background_load, daemon=True)
        wt = threading.Thread(target=watch_membership, daemon=True)
        lt.start()
        wt.start()
        new_ckpt = str(tmp_path / "ckB")
        expect = np.asarray(fake.probs_for_ckpt(new_ckpt), np.float32)
        report = rolling_swap(
            manager, router, new_ckpt, drain_timeout_s=5.0,
            warm_timeout_s=20.0, probe="probe.jpg",
            expect_probs=expect, registry=registry)
        stop.set()
        lt.join(30)
        wt.join(30)

        assert report["ok"] and not report["rolled_back"]
        assert report["swapped"] == ["r0", "r1"]
        assert all(r["probe"]["matched"]
                   for r in report["replicas"])
        assert not errors and answered[0] > 0
        assert overlap[0] == 0   # never more than one replica out
        counters = registry.snapshot()["counters"]
        assert counters["fleet_swaps_total"] == 1
        # The swap is visible on the router protocol too.
        (status,) = _ask(router.address, ["::swap-status"])
        assert json.loads(status)["ok"] is True
        # And membership stayed healthy: both replicas now report the
        # new checkpoint.
        for rid in ("r0", "r1"):
            snap = json.loads(manager.request(rid, "::stats"))
            assert snap["ckpt"] == new_ckpt


def test_rolling_swap_rolls_back_on_bad_checkpoint(tmp_path):
    """A checkpoint whose replica never comes up triggers rollback:
    the failed replica restarts onto its OLD checkpoint, the fleet
    converges back to fully-up, and the report says so."""
    manager, router, registry = _mk_fleet(
        tmp_path, warm_by_rid={"r0": "1,8", "r1": "1,8"},
        expected_rungs=(1, 8))
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()
        old = manager.checkpoint_of("r0")
        report = rolling_swap(
            manager, router, str(tmp_path / "ckbad"),
            drain_timeout_s=2.0, warm_timeout_s=2.5,
            registry=registry)
        assert not report["ok"] and report["rolled_back"]
        assert report["swapped"] == []
        assert report["restores"] and all(
            r["healthy"] for r in report["restores"])
        counters = registry.snapshot()["counters"]
        assert counters["fleet_swap_failures_total"] == 1
        assert counters["fleet_swap_rollbacks_total"] == 1
        assert manager.wait_ready(20.0)
        for rid in ("r0", "r1"):
            assert manager.checkpoint_of(rid) == old
            assert not manager.view(rid).draining
        (reply,) = _ask(router.address, ["still.jpg"])
        assert "\tERROR\t" not in reply


def test_rollback_readmits_even_when_restore_is_unhealthy(tmp_path):
    """A rollback whose restore ALSO misses the warm gate must still
    clear `draining` — otherwise a replica the supervisor later heals
    stays silently unroutable forever (review finding)."""
    # expected_rungs demands rung 8 the fakes never report, so every
    # wait_healthy gate fails: the first swap fails, and the restore
    # comes back "unhealthy" too.
    manager, router, registry = _mk_fleet(
        tmp_path, warm_by_rid={"r0": "1", "r1": "1"},
        expected_rungs=(1, 8))
    with manager, router:
        manager.start()
        assert manager.wait_ready(20.0)
        router.start()
        report = rolling_swap(
            manager, router, str(tmp_path / "ckB"),
            drain_timeout_s=1.0, warm_timeout_s=1.5,
            registry=registry)
        assert not report["ok"] and report["rolled_back"]
        assert report["restores"] and not report["restores"][0]["healthy"]
        # The deliberate exclusion is lifted even though the restore
        # missed the gate: up-ness alone governs routability now.
        for rid in ("r0", "r1"):
            assert not manager.view(rid).draining
        (reply,) = _ask(router.address, ["alive.jpg"])
        assert "\tERROR\t" not in reply


def test_router_ships_frames_as_role_router(tmp_path):
    """Router telemetry frames merge in tools/fleet_agg.py under role
    'router' (the satellite: the fleet view shows the front door next
    to its replicas)."""
    from pytorch_vit_paper_replication_tpu.telemetry.shipper import (
        TelemetryShipper)

    fa = _load_tool("fleet_agg")
    manager, router, registry = _mk_fleet(tmp_path)
    agg = fa.FleetAggregator(stale_after_s=5.0).start()
    try:
        with manager, router:
            manager.start()
            assert manager.wait_ready(20.0)
            router.start()
            _ask(router.address, ["ship.jpg"])
            shipper = TelemetryShipper(
                ("127.0.0.1", agg.port), worker_id="router-0",
                role="router", registry=registry,
                pre_ship=router.publish_telemetry)
            assert shipper.ship_now()
            shipper.close()
            # The aggregator ingests frames on its own thread —
            # poll for arrival (same idiom as test_fleet_obs).
            deadline = time.time() + 10.0
            while time.time() < deadline:
                snap = agg.fleet_snapshot()
                if "router-0" in snap["workers"]:
                    break
                time.sleep(0.05)
            w = snap["workers"]["router-0"]
            assert w["role"] == "router" and w["alive"]
            assert w["gauges"]["fleet_replicas_up"] == 2
            merged = snap["merged"]["counters"]
            assert merged["fleet_route_requests_total"] >= 1
    finally:
        agg.close()


# --------------------------------------------------- one REAL replica
def test_real_replica_behind_router_bit_identical(tmp_path):
    """One REAL serve-CLI replica supervised by the manager, fronted
    by the router: the routed TSV answer and the ::probs row match
    predict_image through the shared inference-load contract —
    cross-process bit-identity, the property the rolling swap's
    re-admission probe rests on."""
    import functools

    from pytorch_vit_paper_replication_tpu.predictions import (
        load_inference_checkpoint, predict_image)

    fb = _load_tool("fleet_bench")
    ckpt, _, _ = fb.make_checkpoint(tmp_path / "ckpt", seed=0)
    classes_file = tmp_path / "classes.txt"
    classes_file.write_text("\n".join(fb.CLASSES) + "\n")
    probe = fb.make_probe_image(tmp_path / "probe.png", 32)

    model, params, transform, _spec = load_inference_checkpoint(
        ckpt, "ViT-Ti/16", len(fb.CLASSES))
    ref_label, ref_prob, ref_probs = predict_image(
        model, params, probe, list(fb.CLASSES), transform=transform)

    from tools._common import cpu_child_env
    registry = TelemetryRegistry()
    manager = ReplicaManager(
        [ReplicaSpec(rid="r0", checkpoint=str(ckpt))],
        command_factory=functools.partial(
            build_serve_command, classes_file=str(classes_file),
            preset="ViT-Ti/16", buckets="1,4"),
        env_factory=lambda spec: replica_env(spec.devices,
                                             base=cpu_child_env()),
        health_interval_s=0.25, stale_after_s=5.0,
        expected_rungs=(1, 4), registry=registry)
    router = FleetRouter(manager, registry=registry)
    with manager, router:
        manager.start()
        assert manager.wait_ready(180.0), manager.stderr_tail("r0")
        assert manager.wait_healthy("r0", 180.0, require_rungs=(1, 4))
        router.start()
        (reply,) = _ask(router.address, [str(probe)], timeout=120.0)
        path, label, prob = reply.split("\t")
        assert path == str(probe) and label == ref_label
        assert float(prob) == pytest.approx(ref_prob, abs=1e-4)
        probs_reply = json.loads(
            manager.request("r0", f"::probs {probe}", timeout_s=120.0))
        got = np.asarray(probs_reply["probs"], np.float32)
        np.testing.assert_array_equal(got, ref_probs)
