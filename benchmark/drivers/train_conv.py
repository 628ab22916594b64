"""Train driver for a token model whose layers mix gated short
convolutions with attention (LFM2): ``drivers/train_lm.py``'s run with
this model's reference and a check of the conv mixer itself.

``engine.train`` runs; this file writes no loop. Imported, not restated:
the window's constants (``drivers/train.py``) and the pool, the order of
its batches (``work_of``) and the step that remembers its metrics
(``drivers/train_lm.py``). ``run`` itself, the window included
(``stop_check``, where the span opens and closes, the barrier on the
steps in flight, the capture's start and stop), IS ``train_lm.run``'s
text a fourth time, as ``train_mla.run`` and ``train_dsa.run`` are its
second and third: that function takes neither the cell's key, nor a
comparison, nor further checks, and a PR that adds a configuration may
edit no benchmark file (PERF.md section 7 queues the repair: one ``run``
with those as parameters; ``tests/test_copies.py`` holds the window's
text equal in all four). What differs from it:

* ``correct`` compares, after the window, **one more call of the timed
  step** on a pool batch (the cell's sequences) with the reference's
  step on the same sequences from the same state
  (:func:`compare_step`): the loss, the step's gradient as its Adam
  first moment carries it, and the parameters' change. On that pool
  batch it also compares the program's eval-mode logits with
  ``lib/reference_conv.py`` (rms in units of the reference's spread)
  and **the conv mixer itself**: layer 0's mixer output (what the
  program sows into ``conv_probe``) against the reference's, over every
  position and over the first ``conv_kernel - 1`` of each sequence;
* with ``TRAIN_CONV_CONTROL`` set in the environment (:data:`CONTROLS`)
  the compared side of both comparisons is a faulty reference instead
  of the program: a check that the limits tell the fault, through this
  cell's own ``correct``. The benchmark never sets it;
* a traced run's capture is read here once more by the finer table
  ``lib/scopes_conv.py`` (the mixer's projections and its mix) before
  ``run.py`` reduces it by the frozen one, under which the mixer is
  ``msa_glue``: ``conv_mixer_ms``, ``conv_mix_ms`` and
  ``conv_mix_roofline_pct`` read that.

The program's preset is asked for first: on a program without it the run
ends there, non-zero, before jax is imported or a device is claimed.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..lib import clock, harness, kernels, reference_conv, scopes, \
    scopes_conv, xplane
from .train import (MAX_IN_FLIGHT, SCHEDULE_STEPS, TRACE_STEPS,
                    WARMUP_STEPS)
from .train_lm import LOGIT_CHUNK, _Remember, make_pool, work_of

# Limits of the comparison with the reference, from readings on the chip
# at the cell's sizes (PERF.md section 6 and the cell's ``notes.limits``
# give them; ``tools/conv_reference_probe.py`` takes them).
# The rms difference of the logits in units of the reference's standard
# deviation: the program's bf16 forward (largest 0.0216) against the
# reference with fp8 e4m3 matmul inputs everywhere (smallest 0.120); the
# limit is their geometric middle. (With the rounding confined to the
# conv mixers' two products the logits read 0.073 and 0.075: over it.)
LOGITS_RMS_TOLERANCE = 0.05
# The rms difference of layer 0's mixer output in units of the
# reference's standard deviation: the conv mixer's own number, read where
# the stream is the embedding's rows alone. The program reads 0.0064
# (bf16 products, float32 mix), the reference with fp8 inputs confined
# to the mixers' two products 0.0564; the geometric middle is 0.019. The
# same limit holds at each sequence's first two positions (the program
# 0.0040-0.0058, that control 0.041-0.043).
CONV_RMS_TOLERANCE = 0.02
# The timed step's loss on the cell's sequences against the reference's,
# relative: the accepted token cells' value, 16 times the program's
# largest reading (1.24e-4; fp8 everywhere read 3.0e-3 and 1.05e-2, the
# step over 2 of the 3 sequences 6.3e-4 and 9.9e-4: the gradient tells
# that one).
LOSS_TOLERANCE = 2e-3
# The timed step's gradient against the reference's, as the step's Adam
# first moment carries it: ``|mu - mu_ref| / ((1 - b1) |clip(g_ref)|)``
# over every leaf together (the moment's decay and the decayed weights
# are the same on both sides and cancel). The program reads 0.0124-0.0173
# (ten seeds), the step over 2 of the 3 sequences 0.072 and 0.089, fp8
# confined to the conv mixers' products 0.447, fp8 everywhere 1.19 (two
# seeds each): the geometric middle of the program's largest and the
# smallest fault's is 0.035.
GRAD_RMS_TOLERANCE = 0.035
# The parameters' change of the timed step against the reference step's,
# ``|d - d_ref| / |d_ref|`` (a state left unchanged reads 1). The
# program reads 0.00090-0.00106, fp8 everywhere 0.037: their geometric
# middle is 0.0063. (The decayed weights outweigh a clipped gradient in
# Adam's moments, so a gradient's fault moves the change little: the
# step over 2 of 3 sequences reads 0.0049-0.0057 here.)
UPDATE_RMS_TOLERANCE = 0.006
MODULE_PREFIX = "jit_train_step"

# What ``TRAIN_CONV_CONTROL`` puts in the program's place: the reference
# with fp8 e4m3 matmul inputs everywhere, or confined to the conv mixers'
# two products, or the reference's step over the first 2 of the cell's 3
# sequences (a step that trains on part of its batch).
CONTROLS = {"fp8": {"dtype": "float8_e4m3fn", "only": None},
            "fp8_conv": {"dtype": "float8_e4m3fn", "only": "conv"},
            "partial_batch": {"sequences": 2}}


def compare_with_reference(model, model_fields: dict, params, batch, mesh,
                           *, dtype=None, only=None) -> dict:
    """The program's eval-mode logits and layer 0's mixer output on
    ``batch`` (sequences at the timed shape, under the cell's mesh)
    against the reference's, which takes each sequence alone, the
    ``[B x T, V]`` logits taken ``LOGIT_CHUNK`` positions at a time;
    ``conv_start_rms`` reads the mixer at the first ``conv_kernel - 1``
    positions of every sequence, where a tap that read the sequence
    before it in the batch would show. ``dtype`` rounds the
    REFERENCE's matmul inputs (and compares that with the true
    reference): the reading a forward in that precision gives; ``only``
    confines the rounding to families of products
    (``reference_conv.FAMILIES``)."""
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu import parallel
    from pytorch_vit_paper_replication_tpu.ops.partition import \
        traced_on_mesh

    tokens = batch["tokens"]
    # not under one ``jax.jit``: every layer of a kind runs one compiled
    # block (``reference_conv._block_program``)
    hid, want_mixed = reference_conv.hidden(params, tokens, model_fields,
                                            mixers=(0,))
    want_mixed = want_mixed[0]
    if dtype is None:
        def program(prm, b):
            got, sown = model.apply({"params": prm}, b["tokens"], False,
                                    mutable=["conv_probe"])
            return got, sown["conv_probe"]["backbone"]["encoder_block_0"][
                "msa"]["out"][0]
        got, got_mixed = traced_on_mesh(jax.jit(program), mesh)(
            params, parallel.shard_batch(batch, mesh))
        low = None
    else:
        low, got_mixed = reference_conv.hidden(
            params, tokens, model_fields, dtype=dtype, only=only,
            mixers=(0,))
        got_mixed, got = got_mixed[0], None
    hid, got, low = (None if x is None else x.reshape(-1, x.shape[-1])
                     for x in (hid, got, low))
    d_head = reference_conv._low(dtype, only, "head")
    start = int(model_fields["conv_kernel"]) - 1

    @jax.jit
    def mixer(got_mixed, want):
        # in units of the largest entry first: squares of a mixer that
        # has learned to be small would leave float32's range
        scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-30)
        diff = (got_mixed.astype(jnp.float32) - want) / scale
        rms = lambda d: jnp.sqrt(jnp.mean(d * d))
        return (rms(diff), jnp.max(jnp.abs(diff)), jnp.std(want / scale),
                rms(diff[:, :start]))

    conv_rms, conv_max, conv_std, conv_start = (
        float(x) for x in jax.device_get(mixer(got_mixed, want_mixed)))
    del got_mixed, want_mixed

    @jax.jit
    def chunk(prm, hid, got_rows, low_rows):
        """Sums over one chunk of positions: squared and largest logit
        difference, the reference's logits and their squares (the
        compared side a rounded reference where ``low_rows`` is given)."""
        want = reference_conv.logits(prm, hid)
        if low_rows is not None:
            got_rows = reference_conv.logits(prm, low_rows, dtype=d_head)
        diff = got_rows - want
        return (jnp.sum(diff * diff), jnp.max(jnp.abs(diff)),
                jnp.sum(want), jnp.sum(want * want))

    sums = np.zeros(4, np.float64)
    for lo in range(0, hid.shape[0], LOGIT_CHUNK):
        hi = min(hid.shape[0], lo + LOGIT_CHUNK)
        part = np.asarray(jax.device_get(chunk(
            params, hid[lo:hi], None if got is None else got[lo:hi],
            None if low is None else low[lo:hi])), np.float64)
        sums[[0, 2, 3]] += part[[0, 2, 3]]
        sums[1] = max(sums[1], part[1])
    n = hid.shape[0] * int(model_fields["vocab_size"])
    std = max(np.sqrt(max(sums[3] / n - (sums[2] / n) ** 2, 0.0)), 1e-12)
    return {"rms": float(np.sqrt(sums[0] / n) / std),
            "max": float(sums[1] / std),
            "conv_rms": conv_rms / max(conv_std, 1e-12),
            "conv_start_rms": conv_start / max(conv_std, 1e-12),
            "conv_max": conv_max / max(conv_std, 1e-12)}


def _adam(opt_state):
    """The Adam state inside the recipe's chain."""
    import jax
    import optax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: isinstance(s, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)]
    assert len(found) == 1, opt_state
    return found[0]


def _free(tree):
    import jax

    for a in jax.tree.leaves(tree):
        if isinstance(a, jax.Array) and not a.is_deleted():
            a.delete()


def compare_step(step, state, batch, mesh, tx, recipe, model_fields, *,
                 control=None) -> dict:
    """One call of the timed ``step`` on ``batch`` (a pool batch: the
    cell's sequences) from ``state`` against the reference's step on
    the same sequences from the same state: ``reference_conv.gradients``
    and then ``tx``, the recipe's optimizer. It consumes ``state`` (the
    step donates it; the old state waits on the host).

    Compared, each relative to the reference's: the step's loss; its
    gradient, read from the Adam first moment it leaves (``mu_new = b1
    mu + (1 - b1)(clip(g) + decay)``, so the two sides' moments differ by
    ``(1 - b1)(clip(g) - clip(g_ref))``), over every leaf together and
    the worst leaf; and the parameters' change. ``control`` (a key of
    :data:`CONTROLS`) puts a faulty reference step in the program's
    place."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_vit_paper_replication_tpu import parallel

    tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["label"])
    old_params, old_opt = jax.device_get((state.params, state.opt_state))
    update = jax.jit(tx.update, donate_argnums=(0, 1))
    if control is None:
        new, metrics = step(state, parallel.shard_batch(batch, mesh))
        got_loss = float(jax.device_get(metrics["loss_sum"])) / len(tokens)
        got_params, got_mu = jax.device_get((new.params,
                                             _adam(new.opt_state).mu))
        _free(new)
    else:
        _free(state)
        c = dict(CONTROLS[control])
        k = c.pop("sequences", len(tokens))
        if c.get("dtype"):
            c["dtype"] = getattr(jnp, c["dtype"])
        params = jax.device_put(old_params)
        got_loss, g = reference_conv.gradients(params, tokens[:k],
                                               labels[:k], model_fields, **c)
        u, opt = update(g, jax.device_put(old_opt), params)
        got_params, got_mu = jax.device_get((optax.apply_updates(params, u),
                                             _adam(opt).mu))
        got_loss = float(got_loss)
        _free((params, u, opt))
    params = jax.device_put(old_params)
    want_loss, g = reference_conv.gradients(params, tokens, labels,
                                            model_fields)
    want_loss = float(want_loss)
    norms = [float(x) for x in jax.device_get(
        [jnp.linalg.norm(a.ravel()) for a in jax.tree.leaves(g)])]
    total = float(np.sqrt(np.sum(np.square(norms))))
    clip = min(1.0, recipe.grad_clip_norm / max(total, 1e-30))
    u, opt = update(g, jax.device_put(old_opt), params)
    want_mu = _adam(opt).mu

    @jax.jit
    def sums(p_old, p_new, mu, u_ref, mu_ref):
        d_mu, d = mu - mu_ref, (p_new - p_old) - u_ref
        return jnp.sum(d_mu * d_mu), jnp.sum(d * d), jnp.sum(u_ref * u_ref)

    per_leaf = []
    for args in zip(*(jax.tree.leaves(t) for t in (
            params, got_params, got_mu, u, want_mu))):
        per_leaf.append([float(x) for x in jax.device_get(sums(*args))])
    _free((params, u, opt))
    per_leaf = np.asarray(per_leaf, np.float64)
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(old_params)]
    scale = (1.0 - recipe.beta1) * clip
    leaf = [(np.sqrt(s) / (scale * n), name)
            for (s, _, _), n, name in zip(per_leaf, norms, names) if n > 0]
    worst = max(leaf)
    return {"loss": got_loss, "reference_loss": want_loss,
            "loss_error": abs(got_loss - want_loss) / max(abs(want_loss),
                                                          1e-12),
            "grad_rms": float(np.sqrt(per_leaf[:, 0].sum())
                              / (scale * total)),
            "grad_rms_leaf_max": float(worst[0]), "grad_worst_leaf": worst[1],
            "update_rms": float(np.sqrt(per_leaf[:, 1].sum()
                                        / max(per_leaf[:, 2].sum(), 1e-30))),
            "grad_norm": total, "clip": clip}


def fine_rows(capture, hlo_text) -> dict:
    """The traced steps by the finer table, read from the capture's
    directory while it is still there; empty where there is none."""
    if capture is None or not capture.started or not hlo_text:
        return {}
    by_name = scopes.parse_scopes(hlo_text)["scopes"]
    trace = xplane.load(xplane.find_xplane(capture.dir), by_name)
    rows = scopes_conv.fine_rows_ms(trace, MODULE_PREFIX)
    if rows:
        print("[fine rows] device ms per step: " + " | ".join(
            f"{k} {v:.3f}" for k, v in rows.items()), flush=True)
    return rows


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

    p = cell["train_conv"]
    control = os.environ.get("TRAIN_CONV_CONTROL") or None
    if control is not None and control not in CONTROLS:
        raise harness.Refused(f"TRAIN_CONV_CONTROL {control!r}: one of "
                              f"{sorted(CONTROLS)}")
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
    recipe = TrainConfig(batch_size=batch, seed=work, **p.get("recipe", {}))
    tx = make_optimizer(recipe, SCHEDULE_STEPS)

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
    fine = fine_rows(capture, hlo_text)

    # Logits and layer 0's mixer of the program's model on a pool batch
    # against the plain float32 reference; then one more timed step on
    # that batch against the reference's step (it consumes the state).
    low = dict(CONTROLS.get(control, {}))
    low.pop("sequences", None)
    if low.get("dtype"):
        low["dtype"] = getattr(jnp, low["dtype"])
    ref = compare_with_reference(model, config["model"], state.params,
                                 pool[order[1]], mesh, **low)
    st = compare_step(step, state, pool[order[1]], mesh, tx, recipe,
                      config["model"], control=control)
    del state

    # A rehearsal takes what it finds (the interpreter leaves no call).
    expect = found if args.rehearsal else p["expect_kernels"]
    kernels_ok, unnamed = kernels.check_kernels(found, expect)
    checks = {
        "loss_finite": bool(np.all(np.isfinite(losses))),
        "loss_fell": bool(np.mean(window_losses[-q:])
                          < np.mean(window_losses[:q])),
        "mosaic_calls": kernels_ok,
        "reference": ref["rms"] <= LOGITS_RMS_TOLERANCE,
        "reference_conv_mixer": ref["conv_rms"] <= CONV_RMS_TOLERANCE,
        "reference_conv_start": ref["conv_start_rms"] <= CONV_RMS_TOLERANCE,
        "reference_step_loss": st["loss_error"] <= LOSS_TOLERANCE,
        "reference_step_gradient": st["grad_rms"] <= GRAD_RMS_TOLERANCE,
        "reference_step_update": st["update_rms"] <= UPDATE_RMS_TOLERANCE,
        "no_compile_in_window": w["misses_close"] == w["misses_open"],
        # no capacity: every pair routed to a held expert is computed
        "no_dropped_pairs": bool(
            np.all(counters["moe_dropped_pairs"] == 0)
            and np.all(counters["moe_pairs_kept_share"] == 1.0)),
    }
    # Each number compared, beside its limit (the result's last key).
    compared = {
        "logits_rms_err": (ref["rms"], LOGITS_RMS_TOLERANCE),
        "conv_mixer_rms_err": (ref["conv_rms"], CONV_RMS_TOLERANCE),
        "conv_start_rms_err": (ref["conv_start_rms"], CONV_RMS_TOLERANCE),
        "step_loss_rel_err": (st["loss_error"], LOSS_TOLERANCE),
        "step_grad_rms_err": (st["grad_rms"], GRAD_RMS_TOLERANCE),
        "step_update_rms_err": (st["update_rms"], UPDATE_RMS_TOLERANCE),
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
          f"; max {ref['max']:.3f}), layer 0's conv mixer rms error "
          f"{ref['conv_rms']:.5f} of its std (tolerance "
          f"{CONV_RMS_TOLERANCE}; max {ref['conv_max']:.3f}; at each "
          f"sequence's first positions {ref['conv_start_rms']:.5f}) | "
          f"{'control ' + control if control else 'timed step'} against "
          f"the reference's step on {batch} x {seq_len} tokens: loss "
          f"{st['loss']:.6f} against {st['reference_loss']:.6f} (relative "
          f"{st['loss_error']:.3e}, tolerance {LOSS_TOLERANCE}), gradient "
          f"rms error {st['grad_rms']:.5f} (tolerance {GRAD_RMS_TOLERANCE}"
          f"; worst leaf {st['grad_rms_leaf_max']:.5f} "
          f"{st['grad_worst_leaf']}; reference norm {st['grad_norm']:.4f}, "
          f"clipped by {st['clip']:.4f}), parameters' change rms error "
          f"{st['update_rms']:.5f} (tolerance {UPDATE_RMS_TOLERANCE}) | "
          f"pairs per "
          f"held expert mean "
          f"{counters['moe_pairs_per_expert_mean'].mean():.0f} max "
          f"{counters['moe_pairs_per_expert_max'].max():.0f}, dropped "
          f"{counters['moe_dropped_pairs'].sum():.0f} | cache misses at "
          f"open {w['misses_open']} at close {w['misses_close']} hits "
          f"{cache.snapshot()['hits']} | step program "
          f"{step_bytes / 2**30:.2f} GiB per chip (memory_analysis)",
          flush=True)
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
        # ``lm``: what the accepted routed-layer metrics read; ``conv``:
        # what this model's own metrics read.
        "lm": lm, "conv": {"seq_len": seq_len, "fine_rows_ms": fine},
        "model": config["model"],
        "capture": capture, "module_prefix": MODULE_PREFIX,
        "hlo_text": hlo_text,
    }
