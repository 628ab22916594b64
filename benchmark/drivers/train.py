"""Train driver: the program's own loop, step, mesh and feed.

``engine.train`` runs; this file writes no loop. It passes the program's
step (``make_parallel_train_step``) behind ``_Remember``, which keeps
each step's ``loss_sum`` handle and adds no device work. With those
handles the batch iterator and ``stop_check`` mark the window: block
once after the warm-up steps (window opens), block on the step before
last at every step (at most ``MAX_IN_FLIGHT`` steps queued, so the
device queue never drains and the window closes within a step of
``--seconds``), block on the last step (window closes).

How the window is marked is the yardstick and not a cell's data: the
constants below hold for every train cell. A cell's file gives
what is trained (batch, recipe, pool), never how it is timed.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..lib import clock, harness, kernels, reference_vit

WARMUP_STEPS = 3      # steps before the window opens (the first compiles)
MAX_IN_FLIGHT = 2     # steps queued on the device while the host feeds
TRACE_STEPS = 10      # steps a ``--trace 1`` run captures, from the
#                       second step after the window opens
SCHEDULE_STEPS = 10_000   # the run the warm-up/decay schedule is built
#                           for: a window is its first few dozen steps


class _Remember:
    """The step, remembering each call's ``loss_sum`` handle."""

    def __init__(self, step):
        self._step = step
        self.handles = []
        if hasattr(step, "lower"):
            self.lower = step.lower

    def __call__(self, state, batch):
        state, metrics = self._step(state, batch)
        self.handles.append(metrics["loss_sum"])
        return state, metrics


def make_pool(seed: int, n_batches: int, batch: int, image_size: int,
              n_classes: int, pool_classes: int):
    """``n_batches`` seeded float32 host batches that can be learned:
    labels from ``pool_classes`` classes, each image uniform noise plus
    its class's fixed low-resolution pattern."""
    rng = np.random.default_rng(seed)
    classes = rng.choice(n_classes, size=min(pool_classes, n_classes),
                         replace=False)
    cells = max(1, image_size // 16)
    coarse = rng.random((len(classes), cells, cells, 3), dtype=np.float32)
    patterns = coarse.repeat(image_size // cells, 1).repeat(
        image_size // cells, 2)
    pool = []
    for _ in range(n_batches):
        which = rng.integers(0, len(classes), size=batch)
        image = rng.random((batch, image_size, image_size, 3),
                           dtype=np.float32)
        image *= 0.5
        image += patterns[which]
        pool.append({"image": image,
                     "label": classes[which].astype(np.int32)})
    return pool


def run(cell: dict, config: dict, args) -> dict:
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu import engine, parallel
    from pytorch_vit_paper_replication_tpu.configs import (MeshConfig,
                                                           TrainConfig)
    from pytorch_vit_paper_replication_tpu.ops.partition import \
        traced_on_mesh
    from pytorch_vit_paper_replication_tpu.optim import make_optimizer

    p = cell["train"]
    phases = [("imports", clock.since_process_start())]
    mark = lambda name: phases.append((name, clock.since_process_start()))
    cache = harness.configure_cache()
    cfg, model = harness.build_model(config)
    chips = cell["chips"]
    batch = p["batch_per_chip"] * chips
    # The host batches are numpy's work: made while jax reaches the chip
    # and builds the weights.
    pool = []
    pool_thread = threading.Thread(target=lambda: pool.extend(make_pool(
        args.seed, p["pool_batches"], batch, cfg.image_size,
        cfg.num_classes, p["pool_classes"])))
    pool_thread.start()
    devices = harness.claim_devices(chips, rehearsal=args.rehearsal)
    mark("chip")
    mesh = parallel.make_mesh(MeshConfig(), devices=devices)
    assert mesh.shape["data"] == chips, "the trainer's default mesh"
    tx = make_optimizer(TrainConfig(batch_size=batch, seed=args.seed,
                                    **p.get("recipe", {})),
                        SCHEDULE_STEPS)

    # Weights, optimizer state and the dropout key: one jitted call from
    # the seed, laid out on the mesh as the program lays them out. The
    # keys are arguments: a seed closed over would be a constant of the
    # program, and every seed would compile (and miss the cache) anew.
    def make_state(key, dropout_key):
        dummy = jnp.zeros((1, cfg.image_size, cfg.image_size, 3))
        return engine.TrainState.create(
            apply_fn=model.apply, params=model.init(key, dummy)["params"],
            tx=tx, rng=dropout_key)

    keys = (jax.random.key(args.seed),
            jax.random.key(args.seed, impl=p["rng_impl"]))
    shardings = parallel.state_shardings(
        jax.eval_shape(make_state, *keys), mesh)
    state = jax.jit(make_state, out_shardings=shardings)(*keys)
    state = parallel.shard_train_state(state, mesh)
    step = _Remember(parallel.make_parallel_train_step(state, mesh))
    jax.block_until_ready(state.params)
    mark("weights")
    pool_thread.join()
    mark("pool")
    warm, in_flight = WARMUP_STEPS, MAX_IN_FLIGHT
    capture = harness.Capture(cell["name"]) if args.trace else None
    trace_first = warm + 2
    trace_last = trace_first + TRACE_STEPS
    w = {"steps": 0}
    # The host's side of every step, on the host's clock (two reads
    # each): when the feed began and how long it took, how long the host
    # then waited for the device, and when the step before last ended.
    feeds, waits, ticks = [], [], []
    collections = harness.GcWatch()

    def feed():
        i = 0
        while True:
            t0 = time.perf_counter()
            with harness.annotate("bench.feed"):
                batch_i = parallel.shard_batch(pool[i % len(pool)], mesh)
            feeds.append((t0, time.perf_counter() - t0))
            yield batch_i
            i += 1

    def stop_check(global_step: int) -> bool:
        handles = step.handles
        if global_step == 1:
            mark("first_step")      # engine.train has just blocked on it
        if global_step < warm:
            return False
        if global_step == warm:
            jax.block_until_ready(handles[-1])
            w["setup_s"] = clock.since_process_start()
            phases.append(("window_open", w["setup_s"]))
            w["misses_open"] = cache.misses
            w["t_open"] = time.perf_counter()
            ticks.append(w["t_open"])
            return False
        if capture is not None:
            if global_step == trace_first:
                jax.block_until_ready(handles[-1])
                capture.start()
            elif global_step == trace_last:
                jax.block_until_ready(handles[-1])
                capture.stop()
        t0 = time.perf_counter()
        with harness.annotate("bench.wait_step"):
            jax.block_until_ready(handles[-in_flight])
        ticks.append(time.perf_counter())
        waits.append(ticks[-1] - t0)
        if ticks[-1] - w["t_open"] < args.seconds:
            return False
        jax.block_until_ready(handles[-1])
        w["t_close"] = time.perf_counter()
        w["steps"] = global_step - warm
        w["misses_close"] = cache.misses
        return True

    state, _ = engine.train(
        state, feed, lambda: (), epochs=1, train_step=step,
        eval_step=lambda *a: None, verbose=False, stop_check=stop_check)
    if capture is not None:
        capture.stop()

    # ---- after the window: what need not be paid as set-up ----------
    # The window from the host's side: a window that the host bounded
    # (little waiting for the device, or a few long intervals) is told
    # apart here from one in which every step took longer on the chip.
    walls = np.diff(ticks + [w["t_close"]]) * 1e3
    fed = np.array([d for t0, d in feeds
                    if w["t_open"] <= t0 < w["t_close"]]) * 1e3
    elapsed = w["t_close"] - w["t_open"]
    collected = collections.report(w["t_open"], w["t_close"])
    if not args.rehearsal:
        print(f"[window] {elapsed:.3f} s, steps {w['steps']} | step wall "
              f"ms p50 {np.median(walls):.1f} max {walls.max():.1f} "
              f"(interval {int(walls.argmax())}) | host: feed ms p50 "
              f"{np.median(fed):.1f} max {fed.max():.1f} sum "
              f"{fed.sum() / 1e3:.2f} s, waited for the device "
              f"{sum(waits):.2f} s = {100 * sum(waits) / elapsed:.1f}% of "
              "the window | intervals ms (the first has no step before it "
              "to wait for): " + " ".join(f"{x:.0f}" for x in walls)
              + " | " + collected,
              flush=True)
    losses = [float(h) / batch for h in step.handles]
    window_losses = losses[warm:]
    q = max(1, len(window_losses) // 4)
    example = parallel.shard_batch(pool[0], mesh)
    lowered = step.lower(state, example)
    found = kernels.kernel_counts(lowered.as_text())
    compiled = lowered.compile()
    step_bytes = harness.program_bytes(compiled)

    # Eval-mode logits of the program's model against the plain float32
    # reference, on 4 seeded images, under the cell's mesh.
    images = pool[1]["image"][:4]
    fwd = traced_on_mesh(
        jax.jit(lambda prm, x: model.apply({"params": prm}, x, False)),
        mesh)
    got = np.asarray(fwd(state.params,
                         parallel.shard_batch({"image": images},
                                              mesh)["image"]), np.float32)
    params_host = jax.device_get(state.params)
    want = np.asarray(jax.jit(
        lambda prm, x: reference_vit.forward(
            prm, x, patch_size=cfg.patch_size, ln_epsilon=cfg.ln_epsilon,
            pool=cfg.pool))(params_host, images))
    err = reference_vit.agreement(got, want)

    # A rehearsal takes what it finds (the interpreter leaves no call).
    expect = found if args.rehearsal else p["expect_kernels"]
    kernels_ok, unnamed = kernels.check_kernels(found, expect)
    checks = {
        "loss_finite": bool(np.all(np.isfinite(losses))),
        "loss_fell": bool(np.mean(window_losses[-q:])
                          < np.mean(window_losses[:q])),
        "mosaic_calls": kernels_ok,
        "reference": err <= reference_vit.TOLERANCE,
        "no_compile_in_window": w["misses_close"] == w["misses_open"],
    }
    # Each number compared, beside its limit (the result's last key).
    compared = {
        "reference_err": (float(err), reference_vit.TOLERANCE),
        "loss_last_quarter": (float(np.mean(window_losses[-q:])),
                              float(np.mean(window_losses[:q]))),
        "losses_not_finite": (int(np.sum(~np.isfinite(losses))), 0),
        "compiles_in_window": (w["misses_close"] - w["misses_open"], 0),
        **kernels.compared_calls(found, expect),
    }
    if not args.rehearsal:
        print("[setup] seconds since process start: " + ", ".join(
            f"{k} {v:.1f}" for k, v in phases), flush=True)
    print(f"[train] steps {w['steps']} batch {batch} chips {chips} | loss "
          f"first-quarter {np.mean(window_losses[:q]):.4f} last-quarter "
          f"{np.mean(window_losses[-q:]):.4f} final {losses[-1]:.6f} | "
          f"mosaic kernels {found} (the cell names {expect}; not named by "
          f"it, not judged: {unnamed}) | reference error "
          f"{err:.4f} of its std (tolerance {reference_vit.TOLERANCE}) | "
          f"cache misses at open {w['misses_open']} at close "
          f"{w['misses_close']} hits {cache.snapshot()['hits']} | step "
          f"program {step_bytes / 2**30:.2f} GiB per chip "
          f"(memory_analysis)", flush=True)
    return {
        "setup_s": w["setup_s"],
        "attempted": w["steps"], "failed": 0, "checks": checks,
        "compared": compared, "devices": devices, "program_bytes": step_bytes,
        "train": {"steps": w["steps"], "images": w["steps"] * batch,
                  "elapsed_s": w["t_close"] - w["t_open"], "chips": chips,
                  "batch_per_chip": p["batch_per_chip"],
                  "step_hbm_bytes": step_bytes, "final_loss": losses[-1],
                  "feed_ms": fed, "wait_s": waits, "step_wall_ms": walls},
        "model": config["model"],
        "capture": capture, "module_prefix": "jit_train_step",
        # The traced run's reader joins each device op to its scope path
        # through the optimized HLO of the step (made above, after the
        # window: a cache hit, as ``step_hbm_gib`` already needed).
        "hlo_text": compiled.as_text() if capture is not None else None,
    }
