"""Train driver for a token model: the program's own loop, step, mesh
and feed, as ``drivers/train.py`` has them for the ViT.

``engine.train`` runs; this file writes no loop. The window, its
constants and the ``[window]`` / ``[setup]`` / ``[train]`` lines are
``drivers/train.py``'s (imported, not restated): block once after the
warm-up steps, at most ``MAX_IN_FLIGHT`` steps queued, block on the last.
What differs is what is trained and how it is checked: a pool of seeded
packed token sequences, and after the window the logits of the program's
model on one pool sequence at the timed shape against the plain float32
reference (``lib/reference_lm.py``), its loss against the reference's,
and the routed layers' counters of every step.

The first import below is of a module that only a program with the
token model has: on an older program the run ends there, non-zero,
before any device is claimed.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..lib import clock, harness, kernels, reference_lm
from .train import (MAX_IN_FLIGHT, SCHEDULE_STEPS, TRACE_STEPS,
                    WARMUP_STEPS)

# Limits of the comparison with the reference, set from the chip's
# readings (PERF.md section 5 gives both of each). The rms difference of
# the logits in units of the reference's standard deviation: the
# program's bf16 forward read 0.0065-0.0108 in 60 runs, the reference
# with fp8 e4m3 matmul inputs 0.523 and 0.545 (0.523 with the rounding
# confined to the attention core's two products); the limit is their
# geometric middle. The relative difference of the loss (at most 1.6e-4
# in 52 runs) does not tell fp8 from bf16 and only guards the chunked
# loss against the reference's.
LOGITS_RMS_TOLERANCE = 0.07
LOSS_TOLERANCE = 2e-3
LOGIT_CHUNK = 2048


class _Remember:
    """The step, remembering each call's metrics (device scalars)."""

    def __init__(self, step):
        self._step = step
        self.metrics = []
        if hasattr(step, "lower"):
            self.lower = step.lower

    def __call__(self, state, batch):
        state, metrics = self._step(state, batch)
        self.metrics.append(metrics)
        return state, metrics

    @property
    def handles(self):
        return [m["loss_sum"] for m in self.metrics]


def make_pool(seed: int, n_batches: int, batch: int, seq_len: int,
              vocab: int, fanout: int):
    """``n_batches`` seeded host batches of packed sequences that can be
    learned, as the trainer's own ``--synthetic`` stream makes them
    (``data/tokens.py``): ranks Zipf(1.0) over the ``vocab`` rows held
    (which row has which rank is one seeded permutation), each rank's
    successor one of ``fanout`` fixed candidates (a first-order successor
    table, drawn Zipf(1.0) too), so the next-token loss falls from ``log
    vocab``. ``label[t]`` is the token after ``tokens[t]``."""
    rng = np.random.default_rng([seed, 0x10C])
    weights = 1.0 / np.arange(1, vocab + 1)
    cdf = np.cumsum(weights / weights.sum())
    draw = lambda shape: np.minimum(
        np.searchsorted(cdf, rng.random(shape)), vocab - 1)
    rows = rng.permutation(vocab).astype(np.int32)
    table = draw((vocab, fanout))
    pool = []
    for _ in range(n_batches):
        rank = np.empty((batch, seq_len + 1), np.int64)
        rank[:, 0] = draw(batch)
        pick = rng.integers(0, fanout, size=(batch, seq_len))
        for t in range(seq_len):
            rank[:, t + 1] = table[rank[:, t], pick[:, t]]
        seq = rows[rank]
        pool.append({"tokens": np.ascontiguousarray(seq[:, :-1]),
                     "label": np.ascontiguousarray(seq[:, 1:])})
    return pool


def work_of(p: dict, seed: int) -> tuple[int, list]:
    """``(work seed, order)`` of a run: the seed that the weights and
    the pool are drawn from, and the order in which the pool's batches
    are fed. The step's time follows the pairs that the router sends to
    the held experts (0.19 us a pair), and which share of them a random
    router sends there is the draw's: 1,275 to 1,705 pairs a held expert
    over 23 seeds where a balanced router sends 1,536 (PERF.md, PR 30).
    So a cell lists ``work_seeds``, draws whose load was measured at
    1,536 +- 8, ``--seed`` takes one of them and orders its batches,
    and every seed does the same work in another order. A cell without
    the list draws everything from ``--seed``."""
    seeds = p.get("work_seeds")
    work = seeds[seed % len(seeds)] if seeds else seed
    order = np.random.default_rng([seed, 0x0DE]).permutation(
        p["pool_batches"]).tolist()
    return work, order


def compare_with_reference(model, model_fields: dict, params, batch, mesh,
                           *, dtype=None, only=None) -> dict:
    """The program's eval-mode logits and loss on ``batch`` (one
    sequence at the timed shape, under the cell's mesh) against the
    reference's, the reference's ``[T, V]`` logits taken ``LOGIT_CHUNK``
    positions at a time. ``dtype`` rounds the REFERENCE's matmul inputs
    (and compares that with the true reference): the reading a forward
    in that precision gives; ``only`` confines the rounding to one
    kernel's products (``reference_lm.block``)."""
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu import parallel
    from pytorch_vit_paper_replication_tpu.ops.partition import \
        traced_on_mesh

    tokens, labels = batch["tokens"], batch["label"]
    placed = parallel.shard_batch(batch, mesh)
    hidden = jax.jit(lambda prm, x: reference_lm.hidden(
        prm, x, model_fields))(params, tokens)[0]
    if dtype is None:
        fwd = traced_on_mesh(jax.jit(
            lambda prm, b: (model.apply({"params": prm}, b["tokens"], False),
                            model.apply({"params": prm}, b["tokens"], False,
                                        labels=b["label"])[0])), mesh)
        got, got_loss = fwd(params, placed)
        got = got[0]
    else:
        low = jax.jit(lambda prm, x: reference_lm.hidden(
            prm, x, model_fields, dtype=dtype, only=only))(
                params, tokens)[0]
        got, got_loss = None, None

    @jax.jit
    def chunk(prm, hid, got_rows, y, low_rows):
        want = reference_lm.logits(prm, hid)
        if low_rows is not None:
            got_rows = reference_lm.logits(
                prm, low_rows, dtype=None if only else dtype)
        diff = got_rows - want
        nll = jax.nn.logsumexp(want, -1) - jnp.take_along_axis(
            want, y[:, None], 1)[:, 0]
        low_nll = jax.nn.logsumexp(got_rows, -1) - jnp.take_along_axis(
            got_rows, y[:, None], 1)[:, 0]
        return (jnp.sum(diff * diff), jnp.max(jnp.abs(diff)),
                jnp.sum(want), jnp.sum(want * want), jnp.sum(nll),
                jnp.sum(low_nll))

    sums = np.zeros(6, np.float64)
    t = hidden.shape[0]
    for lo in range(0, t, LOGIT_CHUNK):
        hi = min(t, lo + LOGIT_CHUNK)
        part = chunk(params, hidden[lo:hi],
                     None if got is None else got[lo:hi],
                     labels[0, lo:hi],
                     None if dtype is None else low[lo:hi])
        part = np.asarray(jax.device_get(part), np.float64)
        sums[[0, 2, 3, 4, 5]] += part[[0, 2, 3, 4, 5]]
        sums[1] = max(sums[1], part[1])
    n = t * int(model_fields["vocab_size"])
    std = max(np.sqrt(max(sums[3] / n - (sums[2] / n) ** 2, 0.0)), 1e-12)
    want_loss = sums[4] / t
    got_loss = sums[5] / t if got_loss is None else float(got_loss)
    return {"rms": float(np.sqrt(sums[0] / n) / std),
            "max": float(sums[1] / std), "loss": got_loss,
            "reference_loss": float(want_loss),
            "loss_error": float(abs(got_loss - want_loss)
                                / max(abs(want_loss), 1e-12))}


def run(cell: dict, config: dict, args) -> dict:
    # Only a program with the token model has this module (see above).
    from pytorch_vit_paper_replication_tpu.ops import moe  # noqa: F401

    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu import engine, parallel
    from pytorch_vit_paper_replication_tpu.configs import (MeshConfig,
                                                           TrainConfig)
    from pytorch_vit_paper_replication_tpu.optim import make_optimizer

    p = cell["train_lm"]
    phases = [("imports", clock.since_process_start())]
    mark = lambda name: phases.append((name, clock.since_process_start()))
    cache = harness.configure_cache()
    cfg, model = harness.build_model(
        {"model": {**config["model"], "remat": p["remat"]}})
    chips = cell["chips"]
    batch = p["batch_per_chip"] * chips
    seq_len = min(p["seq_len"], cfg.max_seq_len)
    work, order = work_of(p, args.seed)
    pool = []
    pool_thread = threading.Thread(target=lambda: pool.extend(make_pool(
        work, p["pool_batches"], batch, seq_len, cfg.vocab_size,
        p["successors"])))
    pool_thread.start()
    devices = harness.claim_devices(chips, rehearsal=args.rehearsal)
    mark("chip")
    mesh = parallel.make_mesh(MeshConfig(), devices=devices)
    assert mesh.shape["data"] == chips, "the trainer's default mesh"
    tx = make_optimizer(TrainConfig(batch_size=batch, seed=work,
                                    **p.get("recipe", {})),
                        SCHEDULE_STEPS)

    # Weights, optimizer state and the dropout key: one jitted call from
    # the seed (keys as arguments, so that every seed is one program).
    def make_state(key, dropout_key):
        return engine.TrainState.create(
            apply_fn=model.apply,
            params=model.init(key, jnp.zeros((1, 8), jnp.int32))["params"],
            tx=tx, rng=dropout_key)

    keys = (jax.random.key(work),
            jax.random.key(work, impl=p["rng_impl"]))
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
    feeds, waits, ticks = [], [], []
    collections = harness.GcWatch()

    def feed():
        i = 0
        while True:
            t0 = time.perf_counter()
            with harness.annotate("bench.feed"):
                batch_i = parallel.shard_batch(
                    pool[order[i % len(pool)]], mesh)
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
    seen = jax.device_get(step.metrics)
    losses = [float(m["loss_sum"]) / batch for m in seen]
    window_losses = losses[warm:]
    q = max(1, len(window_losses) // 4)
    counters = {k: np.array([float(m[k]) for m in seen[warm:]])
                for k in seen[0] if k.startswith("moe_")}
    example = parallel.shard_batch(pool[0], mesh)
    lowered = step.lower(state, example)
    found = kernels.kernel_counts(lowered.as_text())
    compiled = lowered.compile()
    step_bytes = harness.program_bytes(compiled)
    hlo_text = compiled.as_text() if capture is not None else None
    del lowered, compiled, example

    # Logits and loss of the program's model on one pool sequence of the
    # timed length against the plain float32 reference.
    one = {k: v[:chips] for k, v in pool[order[1]].items()}
    ref = compare_with_reference(model, config["model"], state.params, one,
                                 mesh)

    # A rehearsal takes what it finds (the interpreter leaves no call).
    expect = found if args.rehearsal else p["expect_kernels"]
    kernels_ok, unnamed = kernels.check_kernels(found, expect)
    checks = {
        "loss_finite": bool(np.all(np.isfinite(losses))),
        "loss_fell": bool(np.mean(window_losses[-q:])
                          < np.mean(window_losses[:q])),
        "mosaic_calls": kernels_ok,
        "reference": ref["rms"] <= LOGITS_RMS_TOLERANCE,
        "reference_loss": ref["loss_error"] <= LOSS_TOLERANCE,
        "no_compile_in_window": w["misses_close"] == w["misses_open"],
        # no capacity: every pair routed to a held expert is computed
        "no_dropped_pairs": bool(
            np.all(counters["moe_dropped_pairs"] == 0)
            and np.all(counters["moe_pairs_kept_share"] == 1.0)),
    }
    # Each number compared, beside its limit (the result's last key).
    compared = {
        "logits_rms_err": (ref["rms"], LOGITS_RMS_TOLERANCE),
        "loss_rel_err": (ref["loss_error"], LOSS_TOLERANCE),
        "loss_last_quarter": (float(np.mean(window_losses[-q:])),
                              float(np.mean(window_losses[:q]))),
        "losses_not_finite": (int(np.sum(~np.isfinite(losses))), 0),
        "compiles_in_window": (w["misses_close"] - w["misses_open"], 0),
        "dropped_pairs": (float(counters["moe_dropped_pairs"].sum()), 0),
        "pairs_kept_share_min": (
            float(counters["moe_pairs_kept_share"].min()), 1.0),
        **kernels.compared_calls(found, expect),
    }
    load = counters["moe_pairs_per_expert_max"] / np.maximum(
        counters["moe_pairs_per_expert_mean"], 1e-9)
    if not args.rehearsal:
        print("[setup] seconds since process start: " + ", ".join(
            f"{k} {v:.1f}" for k, v in phases), flush=True)
    print(f"[train] steps {w['steps']} sequences {batch} x {seq_len} "
          f"tokens, chips {chips} | weights and pool of work seed {work}, "
          f"batches fed in the order {order} | loss first-quarter "
          f"{np.mean(window_losses[:q]):.4f} last-quarter "
          f"{np.mean(window_losses[-q:]):.4f} final {losses[-1]:.6f} | "
          f"mosaic kernels {found} (the cell names {expect}; not named by "
          f"it, not judged: {unnamed}) | reference: logits rms error "
          f"{ref['rms']:.5f} of its std (tolerance {LOGITS_RMS_TOLERANCE}"
          f"; max {ref['max']:.3f}), loss {ref['loss']:.5f} against "
          f"{ref['reference_loss']:.5f} (relative {ref['loss_error']:.2e}, "
          f"tolerance {LOSS_TOLERANCE}) | pairs per held expert mean "
          f"{counters['moe_pairs_per_expert_mean'].mean():.0f} max "
          f"{counters['moe_pairs_per_expert_max'].max():.0f}, dropped "
          f"{counters['moe_dropped_pairs'].sum():.0f} | cache misses at "
          f"open {w['misses_open']} at close {w['misses_close']} hits "
          f"{cache.snapshot()['hits']} | step program "
          f"{step_bytes / 2**30:.2f} GiB per chip (memory_analysis)",
          flush=True)
    return {
        "setup_s": w["setup_s"],
        "attempted": w["steps"], "failed": 0, "checks": checks,
        "compared": compared, "devices": devices, "program_bytes": step_bytes,
        "train": {"steps": w["steps"], "images": w["steps"] * batch,
                  "elapsed_s": w["t_close"] - w["t_open"], "chips": chips,
                  "batch_per_chip": p["batch_per_chip"],
                  "step_hbm_bytes": step_bytes, "final_loss": losses[-1],
                  "feed_ms": fed, "wait_s": waits, "step_wall_ms": walls},
        "lm": {"tokens_per_step_per_chip": p["batch_per_chip"] * seq_len,
               "seq_len": seq_len, "load_max_over_mean": load,
               "pairs_per_expert_mean":
                   counters["moe_pairs_per_expert_mean"]},
        "model": config["model"],
        "capture": capture, "module_prefix": "jit_train_step",
        "hlo_text": hlo_text,
    }
