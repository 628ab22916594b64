"""Train driver for a token model whose attention an indexer selects
(DeepSeek sparse attention): ``drivers/train_lm.py``'s run with this
model's reference, its two-term objective and its selection.

``engine.train`` runs; this file writes no loop. Imported, not restated:
the window's constants (``drivers/train.py``) and the pool, the order of
its batches (``work_of``) and the step that remembers its metrics
(``drivers/train_lm.py``). ``run`` itself, the window included
(``stop_check``, where the span opens and closes, the barrier on the
steps in flight, the capture's start and stop), IS ``train_lm.run``'s
text a third time, as ``train_mla.run`` is its second: that function
takes neither the cell's key, nor a comparison, nor further checks, and
a PR that adds a configuration may edit no benchmark file (PERF.md
section 7 queues the repair: one ``run`` with those as parameters). What
differs from it:

* ``correct`` compares, on one pool sequence at the timed shape, the
  program's eval-mode logits with ``lib/reference_dsa.py`` (rms in units
  of the reference's spread), the main loss and the indexer's alignment
  loss EACH with the reference's, and **the selection itself**:
  ``dsa_selection_agreement``, the share of (query, selected key) pairs
  on which the program's sets (scored from bf16 products) and the
  reference's (float32) agree, in the first and the last layer;
* the step's counters of the selection (``dsa_selected_pairs`` /
  ``dsa_causal_pairs``, ``dsa_pbar_mass_min``) are checked and handed to
  the metrics;
* a traced run's capture is read here once more by the finer table
  ``lib/scopes_dsa.py`` (the indexer's rows) before ``run.py`` reduces it
  by the frozen one: ``dsa_indexer_ms``, ``dsa_select_ms`` and the two
  rooflines read that.

The program's preset is asked for first: on a program without it the run
ends there, non-zero, before jax is imported or a device is claimed.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..lib import (clock, harness, kernels, reference_dsa, scopes,
                   scopes_dsa, xplane)
from .train import (MAX_IN_FLIGHT, SCHEDULE_STEPS, TRACE_STEPS,
                    WARMUP_STEPS)
from .train_lm import LOGIT_CHUNK, _Remember, make_pool, work_of

# Limits of the comparison with the reference, from readings on the chip
# at the cell's sizes (PERF.md section 6 and the cell's ``notes.limits``
# give them; ``tools/dsa_reference_probe.py`` takes them).
# The rms difference of the logits in units of the reference's standard
# deviation: the program's bf16 forward (largest 0.0104) against the
# reference with fp8 e4m3 matmul inputs (smallest 0.517; 0.516 with the
# rounding confined to the indexer's and the core's products, which is
# what this limit has to refuse): their geometric middle is 0.073.
LOGITS_RMS_TOLERANCE = 0.07
# The share of (query, selected key) pairs on which the program's and
# the reference's selections agree, the smaller of layer 0's and the
# last layer's. The program (scores from bf16 products) reads 0.9899 at
# least; the control whose INDEXER products alone are rounded to fp8
# reads 0.962 at most, and passes the logits' limit (0.021): this limit
# is what refuses it. In disagreement, 0.0101 against 0.038: the
# geometric middle is 0.0196.
SELECTION_AGREEMENT_MIN = 0.98
# The relative difference of the main loss: the accepted token cells'
# value, 18 times the program's largest reading (1.1e-4) and under the
# fp8 control's smallest (3.9e-3): here it has both readings.
LOSS_TOLERANCE = 2e-3
# The relative difference of the alignment loss summed over layers: the
# program's largest 3.2e-4, the fp8 control's smallest 3.2e-2 (2.0e-3
# with the rounding confined to the indexer, which the selection's
# limit refuses): the geometric middle of the first two.
INDEXER_LOSS_TOLERANCE = 2.5e-3
# A rehearsal's sizes (64 tokens, 8 keys a query, an indexer of 2 heads
# of 8): one pair is 0.2% of the selection and the alignment loss a mean
# over 484 pairs, so the two limits that count pairs are the tiny size's
# own (readings 0.988-0.994 and 6e-3 to 1.3e-2 on the CPU).
REHEARSAL_LIMITS = {"selection": 0.9, "indexer_loss": 0.05,
                    "indexer_fall": 1.05}
# The one number of the TIMED step's own backward pass: the alignment
# loss's hand-taken gradients, the core's backward through the selection
# strip and the kept bits train the indexer, or they do not. Over the
# run's first steps (3 of warm-up and the window's first 9, the learning
# rate under 3e-5) the loss on the SAME pool batch, one round of the pool
# later, is INDEXER_FALL_MAX of what it was or less: the program reads
# 0.9854-0.9908 over 8 runs on 4 draws and one of those runs with the
# indexer's update left out 1.0001 (my chip runs, PR 34:
# ``notes.limits``); the limit halves the distance. Later the loss rises by itself
# (its target moves: PERF.md section 6), so only these steps are read. A
# rehearsal's 12 steps move nothing either way (0.996-1.003 with the
# update, without it and with its sign turned): its limit only asks for a
# number.
INDEXER_FALL_MAX = 0.996
INDEXER_FALL_STEPS = 12
MODULE_PREFIX = "jit_train_step"


def compare_with_reference(model, model_fields: dict, params, batch, mesh,
                           *, dtype=None, only=None) -> dict:
    """The program's eval-mode logits, its two losses and its selections
    on ``batch`` (one sequence at the timed shape, under the cell's
    mesh) against the reference's, the ``[T, V]`` logits taken
    ``LOGIT_CHUNK`` positions at a time. ``dtype`` rounds the
    REFERENCE's matmul inputs (and compares that with the true
    reference): the reading a forward in that precision gives; ``only``
    confines the rounding to families of products
    (``reference_dsa.FAMILIES``)."""
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu import parallel
    from pytorch_vit_paper_replication_tpu.ops.partition import \
        traced_on_mesh

    tokens, labels = batch["tokens"], batch["label"]
    last = int(model_fields["num_layers"]) - 1
    layers = sorted({0, last})
    agree = jax.jit(lambda got, want: (
        jnp.sum((got != 0) & (want != 0), dtype=jnp.int32),
        jnp.sum(want != 0, dtype=jnp.int32)))

    def reference(**kw):
        # not under one ``jax.jit``: every layer runs the one compiled
        # block (``reference_dsa._block_program``)
        hid, loss, chosen = reference_dsa.hidden(
            params, tokens, model_fields, selections=tuple(layers), **kw)
        return hid[0], float(loss), chosen

    hid, want_indexer, want_sets = reference()
    if dtype is None:
        def program(prm, b):
            _, sown = model.apply(
                {"params": prm}, b["tokens"], False, labels=b["label"],
                mutable=["lm_stats", "dsa_stats", "dsa_probe"])
            probe = sown["dsa_probe"]["backbone"]
            return (model.apply({"params": prm}, b["tokens"], False),
                    sown["lm_stats"]["main_loss"][0],
                    sown["lm_stats"]["indexer_loss"][0],
                    [probe[f"encoder_block_{i}"]["msa"]["mask"][0]
                     for i in layers])
        got, got_main, got_indexer, got_sets = traced_on_mesh(
            jax.jit(program), mesh)(params, parallel.shard_batch(batch, mesh))
        # the program's counter is the layers' mean, the reference's their
        # sum
        got, got_indexer, low = got[0], float(got_indexer) * (last + 1), None
    else:
        low, got_indexer, got_sets = reference(dtype=dtype, only=only)
        got_sets = [got_sets[i] for i in layers]
        got = got_main = None
    shares = []
    for layer, got_set in zip(layers, got_sets):
        both, all_ = jax.device_get(agree(got_set, want_sets[layer]))
        shares.append(float(both) / max(float(all_), 1.0))
    del got_sets, want_sets
    d_head = reference_dsa._low(dtype, only, "head")

    @jax.jit
    def chunk(prm, hid, got_rows, y, low_rows):
        """Sums over one chunk of positions: squared and largest logit
        difference, the reference's logits and their squares, its cross
        entropies, and the compared side's where that is a rounded
        reference."""
        want = reference_dsa.logits(prm, hid)
        if low_rows is not None:
            got_rows = reference_dsa.logits(prm, low_rows, dtype=d_head)
        nll = lambda lg: jnp.sum(jax.nn.logsumexp(lg, -1)
                                 - jnp.take_along_axis(lg, y[:, None], 1)[:, 0])
        diff = got_rows - want
        return (jnp.sum(diff * diff), jnp.max(jnp.abs(diff)),
                jnp.sum(want), jnp.sum(want * want), nll(want),
                nll(got_rows))

    sums = np.zeros(6, np.float64)
    for lo in range(0, hid.shape[0], LOGIT_CHUNK):
        hi = min(hid.shape[0], lo + LOGIT_CHUNK)
        part = np.asarray(jax.device_get(chunk(
            params, hid[lo:hi], None if got is None else got[lo:hi],
            labels[0, lo:hi], None if low is None else low[lo:hi])),
            np.float64)
        sums[[0, 2, 3, 4, 5]] += part[[0, 2, 3, 4, 5]]
        sums[1] = max(sums[1], part[1])
    t = hid.shape[0]
    n = t * int(model_fields["vocab_size"])
    std = max(np.sqrt(max(sums[3] / n - (sums[2] / n) ** 2, 0.0)), 1e-12)
    want_main = sums[4] / t
    if dtype is not None:
        got_main = sums[5] / t
    rel = lambda a, b: float(abs(float(a) - b) / max(abs(b), 1e-12))
    return {"rms": float(np.sqrt(sums[0] / n) / std),
            "max": float(sums[1] / std),
            "loss": float(got_main), "reference_loss": float(want_main),
            "loss_error": rel(got_main, want_main),
            "indexer_loss": float(got_indexer),
            "reference_indexer_loss": float(want_indexer),
            "indexer_loss_error": rel(got_indexer, want_indexer),
            "selection_agreement": dict(zip(layers, shares)),
            "selection_agreement_min": min(shares)}


def fine_rows(capture, hlo_text) -> dict:
    """The traced steps by the finer table, read from the capture's
    directory while it is still there; empty where there is none."""
    if capture is None or not capture.started or not hlo_text:
        return {}
    by_name = scopes.parse_scopes(hlo_text)["scopes"]
    trace = xplane.load(xplane.find_xplane(capture.dir), by_name)
    rows = scopes_dsa.fine_rows_ms(trace, MODULE_PREFIX)
    if rows:
        print("[fine rows] device ms per step: " + " | ".join(
            f"{k} {v:.3f}" for k, v in rows.items()), flush=True)
    return rows


# pbar is normed over the selection by the core's own row statistic: its
# mass departs from 1 by the rounding of bf16 products alone.
PBAR_MASS_TOLERANCE = 0.02


def same_batch_ratio(values, period: int, steps: int) -> float:
    """Mean of ``values[i + period] / values[i]`` over the first ``steps``
    steps: the pool comes round every ``period`` steps, so each ratio is
    of one batch with itself."""
    v = np.asarray(values[:steps], np.float64)
    return float(np.mean(v[period:] / v[:-period]))


def reference_dsa_pairs(seq_len: int, topk: int) -> tuple:
    """``(selected, causal)`` query-key pairs of one sequence a layer:
    every query selects ``min(t + 1, topk)`` of its ``t + 1`` causal
    keys."""
    k = min(topk, seq_len)
    return (float(k * (k + 1) // 2 + (seq_len - k) * k),
            float(seq_len * (seq_len + 1) // 2))


def run(cell: dict, config: dict, args) -> dict:
    # Only a program with this model has the preset (see above).
    from pytorch_vit_paper_replication_tpu import configs
    if config["program_preset"] not in configs.LM_PRESETS:
        raise harness.Refused(
            f"the program has no preset {config['program_preset']!r}")

    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu import engine, parallel
    from pytorch_vit_paper_replication_tpu.configs import (MeshConfig,
                                                           TrainConfig)
    from pytorch_vit_paper_replication_tpu.optim import make_optimizer

    p = cell["train_dsa"]
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
                for k in seen[0]
                if k.startswith("moe_") or k in engine.LM_COUNTERS}
    example = parallel.shard_batch(pool[0], mesh)
    lowered = step.lower(state, example)
    found = kernels.kernel_counts(lowered.as_text())
    compiled = lowered.compile()
    step_bytes = harness.program_bytes(compiled)
    hlo_text = compiled.as_text() if capture is not None else None
    del lowered, compiled, example
    fine = fine_rows(capture, hlo_text)

    # Logits, both losses and the selections of the program's model on
    # one pool sequence of the timed length against the plain float32
    # reference.
    one = {k: v[:chips] for k, v in pool[order[1]].items()}
    ref = compare_with_reference(model, config["model"], state.params, one,
                                 mesh)
    selection_min, indexer_tolerance, fall_max = (
        (REHEARSAL_LIMITS["selection"], REHEARSAL_LIMITS["indexer_loss"],
         REHEARSAL_LIMITS["indexer_fall"])
        if args.rehearsal
        else (SELECTION_AGREEMENT_MIN, INDEXER_LOSS_TOLERANCE,
              INDEXER_FALL_MAX))
    selected = counters["dsa_selected_pairs"]
    causal = counters["dsa_causal_pairs"]
    want_selected = reference_dsa_pairs(seq_len, cfg.sa_topk)
    indexer_ratio = same_batch_ratio(
        [float(m["indexer_loss"]) for m in seen], len(pool),
        INDEXER_FALL_STEPS)

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
        "reference_indexer_loss":
            ref["indexer_loss_error"] <= indexer_tolerance,
        "selection": ref["selection_agreement_min"] >= selection_min,
        "indexer_loss_fell": indexer_ratio <= fall_max,
        # every query selects min(t + 1, topk) keys, and pbar is normed
        # over them
        "selected_pairs": bool(np.all(selected == want_selected[0])
                               and np.all(causal == want_selected[1])),
        "pbar_mass": bool(np.all(np.abs(
            counters["dsa_pbar_mass_min"] - 1.0) <= PBAR_MASS_TOLERANCE)),
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
        "indexer_loss_rel_err": (ref["indexer_loss_error"],
                                 indexer_tolerance),
        "dsa_selection_agreement": (ref["selection_agreement_min"],
                                    selection_min),
        "indexer_loss_same_batch_ratio": (indexer_ratio, fall_max),
        "dsa_selected_pairs": (float(selected[-1]), want_selected[0]),
        "dsa_pbar_mass_err": (float(np.max(np.abs(
            counters["dsa_pbar_mass_min"] - 1.0))), PBAR_MASS_TOLERANCE),
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
          f"batches fed in the order {order} | objective first-quarter "
          f"{np.mean(window_losses[:q]):.4f} last-quarter "
          f"{np.mean(window_losses[-q:]):.4f} final {losses[-1]:.6f} "
          f"(main {counters['main_loss'][-1]:.4f}, indexer_loss "
          f"{counters['indexer_loss'][-1]:.4f} a layer: first-quarter "
          f"{np.mean(counters['indexer_loss'][:q]):.4f} last-quarter "
          f"{np.mean(counters['indexer_loss'][-q:]):.4f}; on the same "
          f"batch a round of the pool later, first {INDEXER_FALL_STEPS} "
          f"steps: x {indexer_ratio:.4f}) | selected "
          f"{selected[-1]:.0f} of {causal[-1]:.0f} causal pairs a layer = "
          f"share {selected[-1] / causal[-1]:.4f}, pbar mass min "
          f"{counters['dsa_pbar_mass_min'].min():.6f} | "
          f"mosaic kernels {found} (the cell names {expect}; not named by "
          f"it, not judged: {unnamed}) | reference: logits rms error "
          f"{ref['rms']:.5f} of its std (tolerance {LOGITS_RMS_TOLERANCE}"
          f"; max {ref['max']:.3f}), main loss {ref['loss']:.5f} against "
          f"{ref['reference_loss']:.5f} (relative {ref['loss_error']:.2e}; "
          f"tolerance {LOSS_TOLERANCE}), indexer loss "
          f"{ref['indexer_loss']:.5f} against "
          f"{ref['reference_indexer_loss']:.5f} summed over layers "
          f"(relative {ref['indexer_loss_error']:.2e}; tolerance "
          f"{indexer_tolerance}), selection agreement by layer "
          f"{ref['selection_agreement']} (at least {selection_min}) | "
          f"pairs per held expert mean "
          f"{counters['moe_pairs_per_expert_mean'].mean():.0f} max "
          f"{counters['moe_pairs_per_expert_max'].max():.0f}, "
          f"dropped {counters['moe_dropped_pairs'].sum():.0f} | cache "
          f"misses at open {w['misses_open']} at close "
          f"{w['misses_close']} hits {cache.snapshot()['hits']} | step "
          f"program {step_bytes / 2**30:.2f} GiB per chip "
          "(memory_analysis)", flush=True)
    lm = {"tokens_per_step_per_chip": p["batch_per_chip"] * seq_len,
          "seq_len": seq_len, "load_max_over_mean": load,
          "pairs_per_expert_mean": counters["moe_pairs_per_expert_mean"]}
    return {
        "setup_s": w["setup_s"],
        "attempted": w["steps"], "failed": 0, "checks": checks,
        "compared": compared, "devices": devices, "program_bytes": step_bytes,
        "train": {"steps": w["steps"], "images": w["steps"] * batch,
                  "elapsed_s": w["t_close"] - w["t_open"], "chips": chips,
                  "batch_per_chip": p["batch_per_chip"],
                  "step_hbm_bytes": step_bytes, "final_loss": losses[-1],
                  "feed_ms": fed, "wait_s": waits, "step_wall_ms": walls},
        # ``lm``: what the accepted routed-layer metrics read; ``dsa``:
        # what this model's own metrics read.
        "lm": lm, "dsa": {"seq_len": seq_len, "fine_rows_ms": fine,
                          "selected_pairs": float(np.median(selected)),
                          "causal_pairs": float(np.median(causal))},
        "model": config["model"],
        "capture": capture, "module_prefix": MODULE_PREFIX,
        "hlo_text": hlo_text,
    }
