"""Train driver for a token model whose layers mix Mamba-2 state-space
layers with attention (granite-4.0-h): ``drivers/train_lm.py``'s run
with this model's reference and a check of the Mamba mixer itself.

``engine.train`` runs; this file writes no loop. Imported, not restated:
the window's constants (``drivers/train.py``) and the pool, the order of
its batches (``work_of``) and the step that remembers its metrics
(``drivers/train_lm.py``). ``run`` itself, the window included
(``stop_check``, where the span opens and closes, the barrier on the
steps in flight, the capture's start and stop), IS ``train_lm.run``'s
text a fifth time, as ``train_mla.run``, ``train_dsa.run`` and
``train_conv.run`` are its second to fourth: a PR that adds a
configuration may edit no benchmark file (PERF.md section 7 queues the
repair: one ``run`` with the cell's key, comparison and checks as
parameters; ``tests/test_copies.py`` holds the window's text equal in
all five). What differs from it:

* ``correct`` compares, after the window, **one more call of the timed
  step** on a pool batch (the cell's sequence) with the reference's step
  on the same sequence from the same state (:func:`compare_step`): the
  loss, the step's gradient as its Adam first moment carries it, and the
  parameters' change. On that pool batch it also compares the program's
  eval-mode logits with ``lib/reference_ssm.py`` (rms in units of the
  reference's spread) and **the Mamba mixer itself**: the first
  state-space layer's mixer output (what the program sows into
  ``ssm_probe``) against the reference's, over every position and over
  the first ``START_POSITIONS`` positions of each chunk after the first,
  where a state that is not carried, or carried wrong, shows first (the
  reference has no chunks: it takes the scan in its quadratic form);
* with ``TRAIN_SSM_CONTROL`` set in the environment (:data:`CONTROLS`)
  the compared side of both comparisons is a faulty reference instead
  of the program: a check that the limits tell the fault, through this
  cell's own ``correct``. The benchmark never sets it;
* a traced run's capture is read here once more by the finer table
  ``lib/scopes_ssm.py`` (the mixer's projections, convolution, scan and
  gated norm) before ``run.py`` reduces it by the frozen one, under which
  the mixer is ``msa_glue``: ``ssm_mixer_ms``, ``ssm_scan_ms`` and
  ``ssm_scan_roofline_pct`` read that, and ``ssm_state_carry`` the
  program's counter over the window's steps.

The program's preset is asked for first: on a program without it the run
ends there, non-zero, before a device is claimed.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..lib import clock, harness, kernels, reference_ssm, scopes, \
    scopes_ssm, xplane
from .train import (MAX_IN_FLIGHT, SCHEDULE_STEPS, TRACE_STEPS,
                    WARMUP_STEPS)
from .train_conv import _adam, _free
from .train_lm import LOGIT_CHUNK, _Remember, make_pool, work_of

# Limits of the comparison with the reference, from readings on one TPU
# v5e at the cell's sizes: thirteen program runs on thirteen seeds and
# each control on two (PERF.md section 6 and the cell's ``notes.limits`` give
# every reading). Each limit is the geometric middle of the program's
# largest reading and the smallest of the controls it tells apart, as the
# first call read them; the second call's readings lie inside.
# The rms difference of the logits in units of the reference's standard
# deviation: the program 0.0171-0.0174, the scan's x, B and C in fp8
# 0.0609-0.0612 (no state carried 0.109-0.121, fp8 everywhere 0.148).
LOGITS_RMS_TOLERANCE = 0.032
# The rms difference of the first state-space layer's mixer output in
# units of the reference's standard deviation, over every position and
# over the first START_POSITIONS of each chunk after the first: the
# program 0.0052-0.0053 at both, the scan's inputs in fp8 0.0251-0.0258
# at both, no state carried 0.038-0.049 and, at the chunks' first
# positions, 0.088-0.110.
SSM_RMS_TOLERANCE = 0.012
# The timed step's loss against the reference's, relative: the accepted
# token cells' value, 136 times the program's largest reading (1.47e-5);
# no control moves the loss of a sequence near initialisation beyond
# 4.1e-5 (the gradient and the change tell them).
LOSS_TOLERANCE = 2e-3
# The timed step's gradient against the reference's, as the step's Adam
# first moment carries it: ``|mu - mu_ref| / ((1 - b1) |clip(g_ref)|)``
# over every leaf together. The program 0.0098-0.0122, no state carried
# 0.087-0.100, the loss over half the positions 0.368-0.399, the scan's
# inputs in fp8 0.504-0.509, fp8 everywhere 0.983-0.984.
GRAD_RMS_TOLERANCE = 0.034
# The parameters' change of the timed step against the reference step's,
# ``|d - d_ref| / |d_ref|`` (a state left unchanged reads 1): the program
# 0.00045-0.00050, no state carried 0.0040-0.0049, the loss over half the
# positions 0.0166-0.0170, the scan's inputs in fp8 0.0174-0.0186, fp8
# everywhere 0.035-0.036.
UPDATE_RMS_TOLERANCE = 0.0016
# Positions at the start of each chunk after the first that the mixer's
# second comparison reads.
START_POSITIONS = 16
MODULE_PREFIX = "jit_train_step"

# What ``TRAIN_SSM_CONTROL`` puts in the program's place: the reference
# with fp8 e4m3 matmul inputs everywhere (the scan's x, B and C too), or
# with the scan's x, B and C alone rounded to fp8, or with no state
# carried from one chunk of ``ssm_chunk`` positions to the next, or the
# reference's step with the loss over the first half of the sequence's
# positions (a step that trains on part of its batch).
CONTROLS = {"fp8": {"dtype": "float8_e4m3fn", "only": None},
            "fp8_scan": {"dtype": "float8_e4m3fn", "only": "scan"},
            "no_carry": {"carry": False},
            "half_positions": {"positions": 0.5}}


def _fault(control, seq_len: int) -> dict:
    """The faulty reference's keyword arguments for ``control`` (empty
    for the program)."""
    import jax.numpy as jnp

    fault = dict(CONTROLS.get(control, {}))
    if fault.get("dtype"):
        fault["dtype"] = getattr(jnp, fault["dtype"])
    share = fault.pop("positions", None)
    if share is not None:
        fault["counted"] = np.arange(seq_len) < int(share * seq_len)
    return fault


def _first_ssm_layer(model: dict) -> int:
    return next(i for i in range(model["num_layers"])
                if reference_ssm.is_ssm(model, i))


def compare_with_reference(model, model_fields: dict, params, batch, mesh,
                           *, dtype=None, only=None, carry=True) -> dict:
    """The program's eval-mode logits and the first state-space layer's
    mixer output on ``batch`` (sequences at the timed shape, under the
    cell's mesh) against the reference's, which takes each sequence
    alone, the ``[B x T, V]`` logits taken ``LOGIT_CHUNK`` positions at a
    time; ``ssm_start_rms`` reads the mixer at the first
    ``START_POSITIONS`` positions of every chunk after the first. With
    ``dtype`` or ``carry=False`` the compared side is the reference so
    faulted (``only`` confines the rounding to families of
    ``reference_ssm.FAMILIES``)."""
    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu import parallel
    from pytorch_vit_paper_replication_tpu.ops.partition import \
        traced_on_mesh

    tokens = batch["tokens"]
    layer = _first_ssm_layer(model_fields)
    name = f"encoder_block_{layer}"
    hid, want_mixed = reference_ssm.hidden(params, tokens, model_fields,
                                           mixers=(layer,))
    want_mixed = want_mixed[layer]
    if dtype is None and carry:
        def program(prm, b):
            got, sown = model.apply({"params": prm}, b["tokens"], False,
                                    mutable=["ssm_probe"])
            return got, sown["ssm_probe"]["backbone"][name]["msa"]["out"][0]
        got, got_mixed = traced_on_mesh(jax.jit(program), mesh)(
            params, parallel.shard_batch(batch, mesh))
        low = None
    else:
        low, got_mixed = reference_ssm.hidden(
            params, tokens, model_fields, dtype=dtype, only=only,
            carry=carry, mixers=(layer,))
        got_mixed, got = got_mixed[layer], None
    hid, got, low = (None if x is None else x.reshape(-1, x.shape[-1])
                     for x in (hid, got, low))
    d_head = reference_ssm._low(dtype, only, "head")
    chunk = int(model_fields["ssm_chunk"])
    pos = np.arange(tokens.shape[1])
    starts = np.flatnonzero((pos >= chunk) & (pos % chunk < START_POSITIONS))

    @jax.jit
    def mixer(got_mixed, want):
        # in units of the largest entry first: squares of a mixer that
        # has learned to be small would leave float32's range
        scale = jnp.maximum(jnp.max(jnp.abs(want)), 1e-30)
        diff = (got_mixed.astype(jnp.float32) - want) / scale
        rms = lambda d: jnp.sqrt(jnp.mean(d * d))
        return (rms(diff), jnp.max(jnp.abs(diff)), jnp.std(want / scale),
                rms(diff[:, starts]))

    ssm_rms, ssm_max, ssm_std, ssm_start = (
        float(x) for x in jax.device_get(mixer(got_mixed, want_mixed)))
    del got_mixed, want_mixed

    @jax.jit
    def chunk_sums(prm, hid, got_rows, low_rows):
        """Sums over one chunk of positions: squared and largest logit
        difference, the reference's logits and their squares (the
        compared side a faulty reference where ``low_rows`` is given)."""
        want = reference_ssm.logits(prm, hid, model_fields)
        if low_rows is not None:
            got_rows = reference_ssm.logits(prm, low_rows, model_fields,
                                            dtype=d_head)
        diff = got_rows - want
        return (jnp.sum(diff * diff), jnp.max(jnp.abs(diff)),
                jnp.sum(want), jnp.sum(want * want))

    sums = np.zeros(4, np.float64)
    for lo in range(0, hid.shape[0], LOGIT_CHUNK):
        hi = min(hid.shape[0], lo + LOGIT_CHUNK)
        part = np.asarray(jax.device_get(chunk_sums(
            params, hid[lo:hi], None if got is None else got[lo:hi],
            None if low is None else low[lo:hi])), np.float64)
        sums[[0, 2, 3]] += part[[0, 2, 3]]
        sums[1] = max(sums[1], part[1])
    n = hid.shape[0] * int(model_fields["vocab_size"])
    std = max(np.sqrt(max(sums[3] / n - (sums[2] / n) ** 2, 0.0)), 1e-12)
    return {"rms": float(np.sqrt(sums[0] / n) / std),
            "max": float(sums[1] / std), "layer": layer,
            "ssm_rms": ssm_rms / max(ssm_std, 1e-12),
            "ssm_start_rms": ssm_start / max(ssm_std, 1e-12),
            "ssm_max": ssm_max / max(ssm_std, 1e-12)}


def compare_step(step, state, batch, mesh, tx, recipe, model_fields, *,
                 fault=None) -> dict:
    """One call of the timed ``step`` on ``batch`` (a pool batch: the
    cell's sequences) from ``state`` against the reference's step on
    the same sequences from the same state: ``reference_ssm.gradients``
    and then ``tx``, the recipe's optimizer. It consumes ``state`` (the
    step donates it; the old state waits on the host).

    Compared, each relative to the reference's: the step's loss; its
    gradient, read from the Adam first moment it leaves (``mu_new = b1
    mu + (1 - b1)(clip(g) + decay)``, so the two sides' moments differ by
    ``(1 - b1)(clip(g) - clip(g_ref))``), over every leaf together and
    the worst leaf; and the parameters' change. ``fault`` (from
    :func:`_fault`; empty for the program) puts a faulty reference step
    in the program's place."""
    import jax
    import jax.numpy as jnp
    import optax

    from pytorch_vit_paper_replication_tpu import parallel

    tokens, labels = np.asarray(batch["tokens"]), np.asarray(batch["label"])
    old_params, old_opt = jax.device_get((state.params, state.opt_state))
    update = jax.jit(tx.update, donate_argnums=(0, 1))
    if not fault:
        new, metrics = step(state, parallel.shard_batch(batch, mesh))
        got_loss = float(jax.device_get(metrics["loss_sum"])) / len(tokens)
        got_params, got_mu = jax.device_get((new.params,
                                             _adam(new.opt_state).mu))
        _free(new)
    else:
        _free(state)
        params = jax.device_put(old_params)
        got_loss, g = reference_ssm.gradients(params, tokens, labels,
                                              model_fields, **fault)
        u, opt = update(g, jax.device_put(old_opt), params)
        got_params, got_mu = jax.device_get((optax.apply_updates(params, u),
                                             _adam(opt).mu))
        got_loss = float(got_loss)
        _free((params, u, opt))
    params = jax.device_put(old_params)
    want_loss, g = reference_ssm.gradients(params, tokens, labels,
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
    rows = scopes_ssm.fine_rows_ms(trace, MODULE_PREFIX)
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

    p = cell["train_ssm"]
    control = os.environ.get("TRAIN_SSM_CONTROL") or None
    if control is not None and control not in CONTROLS:
        raise harness.Refused(f"TRAIN_SSM_CONTROL {control!r}: one of "
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
    carry = np.array([float(m["ssm_state_carry"]) for m in seen[warm:]])
    example = parallel.shard_batch(pool[0], mesh)
    lowered = step.lower(state, example)
    found = kernels.kernel_counts(lowered.as_text())
    compiled = lowered.compile()
    step_bytes = harness.program_bytes(compiled)
    hlo_text = compiled.as_text() if capture is not None else None
    del lowered, compiled, example
    fine = fine_rows(capture, hlo_text)

    # Logits and the first state-space layer's mixer of the program's
    # model on a pool batch against the plain float32 reference; then one
    # more timed step on that batch against the reference's step (it
    # consumes the state).
    fault = _fault(control, seq_len)
    ref = compare_with_reference(model, config["model"], state.params,
                                 pool[order[1]], mesh,
                                 **{k: v for k, v in fault.items()
                                    if k != "counted"})
    st = compare_step(step, state, pool[order[1]], mesh, tx, recipe,
                      config["model"], fault=fault)
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
        "reference_ssm_mixer": ref["ssm_rms"] <= SSM_RMS_TOLERANCE,
        "reference_ssm_chunk_starts": ref["ssm_start_rms"]
        <= SSM_RMS_TOLERANCE,
        "reference_step_loss": st["loss_error"] <= LOSS_TOLERANCE,
        "reference_step_gradient": st["grad_rms"] <= GRAD_RMS_TOLERANCE,
        "reference_step_update": st["update_rms"] <= UPDATE_RMS_TOLERANCE,
        "no_compile_in_window": w["misses_close"] == w["misses_open"],
    }
    # Each number compared, beside its limit (the result's last key).
    compared = {
        "logits_rms_err": (ref["rms"], LOGITS_RMS_TOLERANCE),
        "ssm_mixer_rms_err": (ref["ssm_rms"], SSM_RMS_TOLERANCE),
        "ssm_chunk_start_rms_err": (ref["ssm_start_rms"],
                                    SSM_RMS_TOLERANCE),
        "step_loss_rel_err": (st["loss_error"], LOSS_TOLERANCE),
        "step_grad_rms_err": (st["grad_rms"], GRAD_RMS_TOLERANCE),
        "step_update_rms_err": (st["update_rms"], UPDATE_RMS_TOLERANCE),
        "loss_last_quarter": (float(np.mean(window_losses[-q:])),
                              float(np.mean(window_losses[:q]))),
        "losses_not_finite": (int(np.sum(~np.isfinite(losses))), 0),
        "compiles_in_window": (w["misses_close"] - w["misses_open"], 0),
        **kernels.compared_calls(found, expect),
    }
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
          f"; max {ref['max']:.3f}), layer {ref['layer']}'s Mamba mixer "
          f"rms error {ref['ssm_rms']:.5f} of its std (tolerance "
          f"{SSM_RMS_TOLERANCE}; max {ref['ssm_max']:.3f}; at the first "
          f"{START_POSITIONS} positions of each chunk after the first "
          f"{ref['ssm_start_rms']:.5f}) | "
          f"{'control ' + control if control else 'timed step'} against "
          f"the reference's step on {batch} x {seq_len} tokens: loss "
          f"{st['loss']:.6f} against {st['reference_loss']:.6f} (relative "
          f"{st['loss_error']:.3e}, tolerance {LOSS_TOLERANCE}), gradient "
          f"rms error {st['grad_rms']:.5f} (tolerance {GRAD_RMS_TOLERANCE}"
          f"; worst leaf {st['grad_rms_leaf_max']:.5f} "
          f"{st['grad_worst_leaf']}; reference norm {st['grad_norm']:.4f}, "
          f"clipped by {st['clip']:.4f}), parameters' change rms error "
          f"{st['update_rms']:.5f} (tolerance {UPDATE_RMS_TOLERANCE}) | "
          f"ssm_state_carry {carry.mean():.4f} (window's steps: min "
          f"{carry.min():.4f} max {carry.max():.4f}) | cache misses at "
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
        # ``ssm``: what this model's own metrics read
        "ssm": {"seq_len": seq_len, "fine_rows_ms": fine,
                "state_carry": float(carry.mean())},
        "model": config["model"],
        "capture": capture, "module_prefix": MODULE_PREFIX,
        "hlo_text": hlo_text,
    }
