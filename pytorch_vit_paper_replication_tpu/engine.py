"""Training engine: pure, jittable step functions + the epoch loop.

TPU-native redesign of the reference's ``going_modular/engine.py``:

* ``train_step``/``test_step`` (reference :9 / :81) become **pure functions**
  ``(state, batch) -> (state, metrics)`` under ``jax.jit`` with the state
  donated — params update in-place in HBM, no host round-trips.
* The reference calls ``.item()`` on loss/accuracy every batch
  (engine.py:54,74,121,125), forcing a device→host sync per step. Here
  metrics stay on-device as running **sums** (loss·n, correct, n) and are
  fetched once per log interval.
* Accuracy is example-weighted (correct/total), not the reference's
  mean-of-batch-means (engine.py:77-78) which over-weights a ragged last
  batch; SURVEY.md §5 flags this as a deliberate, documented replacement.
* Gradient clipping / Adam / weight decay / LR schedule all live inside the
  optax chain (:mod:`.optim`), so a step is exactly: forward, backward,
  update — one fused XLA program.

The :func:`train` orchestrator reproduces the reference ``engine.train``
contract (:132-211): per-epoch train+eval metrics, printed per epoch,
returned as the same ``{"train_loss": [...], ...}`` dict shape.
"""

from __future__ import annotations

import time
from collections import Counter
from typing import Any, Callable, Dict, Iterable, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

Batch = Dict[str, jax.Array]  # {"image": [B,H,W,C] float, "label": [B] int32}
# or, for a token model, {"tokens": [B,T] int32, "label": [B,T] int32}:
# each position's next token.


@flax.struct.dataclass
class TrainState:
    """Model + optimizer state carried through the jitted step.

    ``apply_fn``/``tx`` are static (pytree-excluded); ``rng`` seeds dropout
    and is folded with the step counter so every step gets fresh noise.
    """

    step: jax.Array
    params: Any
    opt_state: Any
    rng: jax.Array
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)

    @classmethod
    def create(cls, *, apply_fn, params, tx, rng):
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=tx.init(params), rng=rng, apply_fn=apply_fn,
                   tx=tx)


def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       label_smoothing: float = 0.0) -> jax.Array:
    """Mean softmax cross-entropy in float32 (reference: nn.CrossEntropyLoss,
    main notebook cell 91)."""
    logits = logits.astype(jnp.float32)
    if label_smoothing > 0.0:
        num_classes = logits.shape[-1]
        onehot = optax.smooth_labels(
            jax.nn.one_hot(labels, num_classes), label_smoothing)
        losses = optax.softmax_cross_entropy(logits, onehot)
    else:
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels)
    return losses.mean()


def distill_loss(student_logits: jax.Array, teacher_logits: jax.Array,
                 labels: jax.Array, *, t: float = 1.0,
                 alpha: float = 0.5,
                 label_smoothing: float = 0.0) -> jax.Array:
    """Hinton knowledge-distillation loss in float32:

    ``(1-alpha) * CE(student, labels) + alpha * t^2 *
    KL(softmax(teacher/t) || softmax(student/t))``

    The ``t^2`` factor keeps the soft-target gradient magnitude
    comparable across temperatures (Hinton et al. 2015 §2). ``alpha``
    weights the SOFT term: ``alpha=0`` reduces bit-exactly to the
    plain (optionally label-smoothed) CE — a static Python branch, the
    identical traced graph, not a numerical approximation — so a
    distillation run degenerates gracefully to ordinary training;
    ``alpha=1`` is pure teacher mimicry (the cascade student's
    objective: gated agreement with the teacher is what serve-time
    escalation prices). KL is computed from log-softmaxes
    (``sum p_t * (log p_t - log p_s)``) — no raw
    ``log(softmax(...))``, which underflows for confident teachers."""
    t = float(t)
    alpha = float(alpha)
    if alpha == 0.0:
        return cross_entropy_loss(student_logits, labels,
                                  label_smoothing)
    log_s = jax.nn.log_softmax(
        student_logits.astype(jnp.float32) / t, axis=-1)
    log_t = jax.nn.log_softmax(
        teacher_logits.astype(jnp.float32) / t, axis=-1)
    kl = jnp.sum(jnp.exp(log_t) * (log_t - log_s), axis=-1).mean()
    soft = (t * t) * kl
    if alpha == 1.0:
        return soft
    hard = cross_entropy_loss(student_logits, labels, label_smoothing)
    return (1.0 - alpha) * hard + alpha * soft


def _metrics(loss, logits, labels) -> Dict[str, jax.Array]:
    pred = jnp.argmax(logits, axis=-1)
    n = jnp.asarray(labels.shape[0], jnp.float32)
    return {
        "loss_sum": loss * n,
        "correct": jnp.sum(pred == labels).astype(jnp.float32),
        "count": n,
    }


def _moe_metrics(moe_stats) -> Dict[str, jax.Array]:
    """The routed layers' counters of one step, from what the blocks
    sowed (``moe_stats``: per layer ``counts [E_held]``, ``kept``,
    ``routed``, ``passes [chunks]``): pairs on the emptiest, the mean and
    the fullest held expert over all layers, the share of the routed
    pairs that were computed (1.0: there is no capacity), the pairs
    dropped, the most passes over its row buffer that any chunk of
    tokens of any layer took, and the share of the chunks served in one
    (``ops.moe.buffer_rows``: a load above 4/3 of an even router's costs
    a pass more, never a pair)."""
    from flax.traverse_util import flatten_dict
    sown = flatten_dict(moe_stats)     # (..., block, "mlp", key) -> (v,)
    leaves = lambda key: [v for path, vs in sown.items()
                          if path[-1] == key for v in vs]
    counts = jnp.stack(leaves("counts")).astype(jnp.float32)
    kept = sum(leaves("kept")).astype(jnp.float32)
    routed = sum(leaves("routed")).astype(jnp.float32)
    passes = jnp.concatenate(leaves("passes"))
    scores = leaves("score_sum")    # a sigmoid router's normaliser
    return {
        **({"moe_score_sum_mean": jnp.mean(jnp.stack(scores))}
           if scores else {}),
        "moe_pairs_per_expert_min": counts.min(),
        "moe_pairs_per_expert_mean": counts.mean(),
        "moe_pairs_per_expert_max": counts.max(),
        # (a TPU's a / a need not be 1.0: the equal case is spelled out)
        "moe_pairs_kept_share": jnp.where(
            kept == routed, 1.0, kept / jnp.maximum(routed, 1.0)),
        "moe_dropped_pairs": routed - kept,
        "moe_passes_max": passes.max().astype(jnp.float32),
        "moe_one_pass_share": jnp.mean((passes == 1).astype(jnp.float32)),
    }


def _dsa_metrics(dsa_stats) -> Dict[str, jax.Array]:
    """The counters of one step's indexed attention, from what the
    blocks sowed (``dsa_stats``, per layer): the query-key pairs the
    selection kept and the causal pairs it chose from, a sequence a
    layer (the layers' mean), and the smallest over layers of the mean
    over queries of ``pbar``'s mass on the selection (1 by
    construction: it guards the normalisation)."""
    from flax.traverse_util import flatten_dict
    sown = flatten_dict(dsa_stats)
    leaves = lambda key: jnp.stack([v for path, vs in sown.items()
                                    if path[-1] == key for v in vs])
    return {"dsa_selected_pairs": jnp.mean(leaves("selected_pairs")),
            "dsa_causal_pairs": jnp.mean(leaves("causal_pairs")),
            "dsa_pbar_mass_min": jnp.min(leaves("pbar_mass"))}


def _token_loss(state, params, batch, dropout_rng):
    """A token model's objective: mean next-token cross entropy over
    every position of ``batch["tokens"]`` against ``batch["label"]``,
    taken by the model's head in chunks (no ``[B, T, V]`` logits), and
    the metrics of the step: ``correct`` counts sequences' worth of
    right positions, so that ``correct / count`` is the token accuracy.
    A model with a multi-token-prediction module returns its two-term
    objective and sows the terms (``lm_stats``: ``main_loss``,
    ``mtp_loss``, ``mtp_top1_share``), which ride along as counters; so
    does a model whose attention an indexer selects (``main_loss``,
    ``indexer_loss``, and :func:`_dsa_metrics`)."""
    (loss, right), sown = state.apply_fn(
        {"params": params}, batch["tokens"], True, labels=batch["label"],
        rngs={"dropout": dropout_rng},
        mutable=["moe_stats", "lm_stats", "dsa_stats"])
    n, t = batch["label"].shape
    with jax.named_scope("metrics"):
        metrics = {"loss_sum": loss * n, "correct": right / t,
                   "count": jnp.asarray(n, jnp.float32)}
        if sown.get("moe_stats"):
            metrics.update(_moe_metrics(sown["moe_stats"]))
        if sown.get("dsa_stats"):
            metrics.update(_dsa_metrics(sown["dsa_stats"]))
        metrics.update({key: value[0] for key, value in
                        sown.get("lm_stats", {}).items()})
    return loss, metrics


# A token model's step counters (beside ``moe_*``) that the loop hands to
# the telemetry on barriered steps.
LM_COUNTERS = ("main_loss", "mtp_loss", "mtp_top1_share", "indexer_loss",
               "dsa_selected_pairs", "dsa_causal_pairs", "dsa_pbar_mass_min")


def _masked_metrics(losses, logits, labels, mask) -> Dict[str, jax.Array]:
    """Example-weighted sums over the valid (mask=1) rows only — used by
    eval, where ragged final batches are padded up to the data-parallel
    divisor (see data.pad_batch)."""
    pred = jnp.argmax(logits, axis=-1)
    mask = mask.astype(jnp.float32)
    return {
        "loss_sum": jnp.sum(losses * mask),
        "correct": jnp.sum((pred == labels) * mask),
        "count": jnp.sum(mask),
    }


def make_train_step(label_smoothing: float = 0.0, nan_guard: bool = False,
                    distill_alpha: Optional[float] = None,
                    distill_t: float = 1.0):
    """Build the pure train step ``(state, batch) -> (state, metrics)``.

    Jit it yourself (or via :mod:`.parallel.api` for meshes):
    ``jax.jit(step, donate_argnums=0)``.

    ``distill_alpha`` (non-None) switches the objective to
    :func:`distill_loss` against per-example ``batch["teacher_logits"]``
    (``[B, C]`` float32 rows the train loop gathers from a ``--head
    logits`` offline sink by record ordinal) at temperature
    ``distill_t`` — everything else (grads, optimizer, nan-guard,
    metrics, checkpoints) is the ordinary step, so a distilled student
    is a completely ordinary checkpoint. Distill metrics add
    ``teacher_agree`` — the count of rows where student and teacher
    argmax already match, the live view of the agreement the cascade
    gate later prices.

    ``nan_guard=True`` adds failure detection the reference lacks entirely
    (SURVEY.md §5): when the loss or gradient norm is nonfinite (a bad
    batch, an LR spike), the step applies **no** parameter/optimizer
    update, contributes nothing to the epoch's loss/accuracy sums, and
    reports ``metrics["skipped"] = 1`` — the run survives instead of
    poisoning every weight with NaNs. ``state.step`` still advances (fresh
    dropout noise next batch); the optimizer's internal count — and with
    it the LR-schedule position — reverts along with ``opt_state``, so
    warmup/decay track *applied* updates, one schedule step behind
    ``state.step`` per skip. Costs one ``where`` per parameter leaf
    (<1% step time).
    """

    def train_step(state: TrainState, batch: Batch
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        dropout_rng = jax.random.fold_in(state.rng, state.step)
        # What is trained is read off the batch: token ids in, each
        # position's next token as the label.
        tokens = "tokens" in batch
        if tokens and (distill_alpha is not None or label_smoothing):
            raise ValueError("a token model trains on plain cross entropy")

        def loss_fn(params):
            if tokens:
                return _token_loss(state, params, batch, dropout_rng)
            logits = state.apply_fn(
                {"params": params}, batch["image"], True,
                rngs={"dropout": dropout_rng})
            with jax.named_scope("loss"):
                if distill_alpha is not None:
                    loss = distill_loss(
                        logits, batch["teacher_logits"], batch["label"],
                        t=distill_t, alpha=distill_alpha,
                        label_smoothing=label_smoothing)
                else:
                    loss = cross_entropy_loss(logits, batch["label"],
                                              label_smoothing)
            return loss, logits

        # The scopes below and the modules' own names are what
        # telemetry/device_trace.py sums a captured step by.
        # aux: the logits, or a token model's metrics (it has no logits)
        (loss, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(state.params)
        with jax.named_scope("optimizer"):
            updates, opt_state = state.tx.update(grads, state.opt_state,
                                                 state.params)
            params = optax.apply_updates(state.params, updates)
        with jax.named_scope("metrics"):
            metrics = aux if tokens else _metrics(loss, aux, batch["label"])
            if distill_alpha is not None:
                metrics["teacher_agree"] = jnp.sum(
                    jnp.argmax(aux, axis=-1) ==
                    jnp.argmax(batch["teacher_logits"], axis=-1)
                ).astype(jnp.float32)
            metrics["grad_norm"] = optax.global_norm(grads)
            if nan_guard:
                # A single scalar catches every nonfinite leaf: any NaN/inf
                # gradient makes the global norm nonfinite.
                ok = jnp.isfinite(loss) & jnp.isfinite(metrics["grad_norm"])
                keep = lambda new, old: jax.tree.map(
                    lambda n, o: jnp.where(ok, n, o), new, old)
                params = keep(params, state.params)
                opt_state = keep(opt_state, state.opt_state)
                # where(), not multiply: loss_sum/grad_norm are NaN on a
                # skipped step and NaN * 0 = NaN would poison the epoch sums.
                metrics = {k: jnp.where(ok, v, jnp.zeros_like(v))
                           for k, v in metrics.items()}
                metrics["skipped"] = 1.0 - ok.astype(jnp.float32)
        new_state = state.replace(step=state.step + 1, params=params,
                                  opt_state=opt_state)
        return new_state, metrics

    return train_step


def make_eval_step():
    """Build the pure eval step ``(state, batch) -> metrics``
    (reference ``test_step``, engine.py:81-129, minus the host syncs).
    Eval loss is plain cross-entropy (no label smoothing), matching the
    reference's test_step."""

    def eval_step(state: TrainState, batch: Batch) -> Dict[str, jax.Array]:
        if "tokens" in batch:
            loss, right = state.apply_fn(
                {"params": state.params}, batch["tokens"], False,
                labels=batch["label"])
            n, t = batch["label"].shape
            return {"loss_sum": loss * n, "correct": right / t,
                    "count": jnp.asarray(n, jnp.float32)}
        logits = state.apply_fn({"params": state.params}, batch["image"],
                                False)
        labels = batch["label"]
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels)
        mask = batch.get("mask")
        if mask is None:
            mask = jnp.ones_like(labels, jnp.float32)
        return _masked_metrics(losses, logits, labels, mask)

    return eval_step


def _accumulate(total: Optional[Dict], m: Dict) -> Dict:
    """Running on-device sums of whatever keys the step reports."""
    if total is None:
        return dict(m)
    return jax.tree.map(lambda a, b: a + b, total, m)


def _finalize(total: Dict[str, jax.Array],
              steps: int = 0) -> Dict[str, float]:
    """One device fetch, then example-weighted means; with ``steps``, a
    summed ``grad_norm`` becomes a mean over *applied* (non-skipped)
    updates — skipped steps contribute zeros to the sum and must not
    dilute it."""
    total = jax.device_get(total)
    n = max(float(total["count"]), 1.0)
    out = {"loss": float(total["loss_sum"]) / n,
           "acc": float(total["correct"]) / n,
           "count": n,
           "skipped": float(total.get("skipped", 0.0))}
    if steps and "grad_norm" in total:
        applied = max(steps - out["skipped"], 1.0)
        out["grad_norm"] = float(total["grad_norm"]) / applied
    if "teacher_agree" in total:
        # Example-weighted student/teacher argmax agreement — the live
        # view of the fidelity the cascade gate will measure.
        out["teacher_agree"] = float(total["teacher_agree"]) / n
    return out


def evaluate(
    state: TrainState,
    eval_batches: Callable[[], Iterable[Batch]],
    *,
    eval_step: Optional[Callable] = None,
    on_batch: Optional[Callable[[], None]] = None,
) -> Dict[str, float]:
    """One full pass over ``eval_batches``: example-weighted loss/accuracy.

    The eval half of the reference's ``engine.train`` epoch (test_step loop,
    engine.py:81-129), exposed standalone so a saved model can be scored
    without training (the reference does this only ad hoc in-notebook,
    main nb cells 125-134; here it backs ``train.py --eval-only``).

    ``on_batch`` is called after each batch — the telemetry watchdog's
    heartbeat, so a long eval over a big test set reads as progress,
    not a stall.
    """
    if eval_step is None:
        eval_step = jax.jit(make_eval_step())
    total = None
    for batch in eval_batches():
        total = _accumulate(total, eval_step(state, batch))
        if on_batch is not None:
            on_batch()
    return _finalize(total) if total else {"loss": 0., "acc": 0.,
                                           "count": 0., "skipped": 0.}


def _report_first_step(train_step, state, batch, stats) -> None:
    """Once per process, after the first step's barrier: what the device
    boundary actually did, printed instead of trusted. The persistent
    cache's hit/miss counts, the start-up's stages and what the first
    calls cost by stage (two splits of ``time_to_first_step``: by where
    the process was, and by program); on a TPU the Mosaic calls found in the
    lowered step, by kernel name with the per-shard operand's shape (the
    MLP kernels' rows, the attention pair's packed qkv projection; the
    kernel dispatch reads ``jax.default_backend()`` and the call's shapes
    and falls back silently), and for a model whose layers mix
    convolutions and attention each layer's mixer beside them — costs
    one more lowering, so it is skipped
    elsewhere, where there is no Mosaic to find; and the memory each
    local device holds, where the backend reports it."""
    from .ops.partition import mosaic_calls

    cache = stats.snapshot()
    lines = [f"compile cache: {cache['hits']} hits, {cache['misses']} "
             f"misses ({cache['cache_dir']})"]
    # Process start -> here, by stage: each stage's seconds, and of them
    # the seconds inside the call that closed it.
    lines.append(f"[startup] seconds by stage (own: inside the call "
                 f"that closed it): {stats.startup_line()}")
    # What the first calls cost, by stage, costliest program first (the
    # train step), then every other program's together.
    lines.append("[programs] seconds in first calls (backend holds the "
                 f"cache read): {stats.programs_line(top=1)}")
    if jax.default_backend() == "tpu" and hasattr(train_step, "lower"):
        calls = mosaic_calls(train_step.lower(state, batch).as_text())
        by_kernel = ", ".join(
            f"{name} x{n} {list(shape)}"
            for (name, shape), n in sorted(Counter(calls).items()))
        lines.append(f"train step: {len(calls)} Mosaic kernel calls: "
                     f"{by_kernel}")
        # which mixer each layer has, where the model mixes two kinds
        cfg = getattr(getattr(state.apply_fn, "__self__", None), "config",
                      None)
        if getattr(cfg, "mixer_layout", ()):
            lines[-1] += " | mixers by layer: " + ", ".join(
                f"{i} {cfg.layer_mixer(i)}" for i in range(cfg.num_layers))
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    if all("bytes_in_use" in s for s in stats):
        # (peak_bytes_in_use adds nothing here: on the v5e it reads the
        # same as bytes_in_use — the step's temporaries are not in it.)
        gib = " ".join(f"{s['bytes_in_use'] / 2**30:.2f}" for s in stats)
        lines.append(f"device memory in use: {gib} GiB (limit "
                     f"{stats[0].get('bytes_limit', 0) / 2**30:.2f} GiB "
                     "per device)")
    # vitlint: hot-path-ok(once per process, with the first-step barrier)
    print("\n".join(lines))


def train(
    state: TrainState,
    train_batches: Callable[[], Iterable[Batch]],
    eval_batches: Callable[[], Iterable[Batch]],
    *,
    epochs: int,
    train_step: Optional[Callable] = None,
    eval_step: Optional[Callable] = None,
    logger=None,
    checkpointer=None,
    verbose: bool = True,
    start_epoch: int = 0,
    checkpoint_every_steps: int = 0,
    checkpoint_every_epochs: int = 1,
    lr_schedule: Optional[Callable[[int], float]] = None,
    telemetry=None,
    stop_check: Optional[Callable[[int], bool]] = None,
) -> Tuple[TrainState, Dict[str, list]]:
    """Epoch-granularity loop, the reference ``engine.train`` equivalent.

    Args:
      state: initial :class:`TrainState`.
      train_batches / eval_batches: zero-arg callables returning a fresh
        iterator of batches for one epoch (epoch-level reshuffling lives in
        the data pipeline).
      epochs: number of epochs (reference signature, engine.py:132).
      train_step / eval_step: already-jitted step functions; defaults build
        and jit the standard ones.
      logger: optional :class:`.metrics.MetricsLogger`.
      checkpointer: optional :class:`.checkpoint.Checkpointer`; saved each
        epoch (a capability the reference lacks — utils.py only saves once,
        manually, and has no restore).
      start_epoch: epochs already completed before this call (resume);
        printed/logged epoch numbers continue from it, so run history stays
        unambiguous across restarts.
      checkpoint_every_steps: with a checkpointer, also save every N train
        steps (not just per epoch) — preemption tolerance for long epochs
        (ImageNet-scale); 0 disables. The unit is *micro*-steps (one
        ``train_step`` call): under gradient accumulation, N counts
        micro-batches, not optimizer updates — resume math is in the same
        unit, so the pair stays self-consistent.
      checkpoint_every_epochs: epoch-granularity save cadence (default 1 =
        every epoch, the historical behavior). Long cheap-epoch runs can
        raise it — per-epoch saves of a large state can dominate wall
        time on slow storage. The FINAL epoch always saves, so resume
        never loses more than the interval.
      lr_schedule: optional ``micro_step -> lr`` callable; when given, the
        end-of-epoch learning rate is logged (JSONL/TensorBoard ``lr``) so
        the warmup/decay trajectory is auditable from the run artifacts.
        Callers under gradient accumulation map micro-steps to optimizer
        updates themselves (train.py passes ``s -> sched(s // accum)``).
      telemetry: optional :class:`..telemetry.StepTelemetry`. When given,
        every step's wall time is split into data-wait (blocked on the
        batch iterator) and dispatch/device seconds, with a sampled
        ``block_until_ready`` barrier every ``telemetry.block_every``
        steps so async dispatch can't skew the split; checkpoint saves
        and the eval pass record as spans, the watchdog (if wired) is
        beaten on every one of them, and each epoch closes with a
        goodput summary row. None = no telemetry work beyond the loop's
        two unconditional perf_counter reads per step (~100 ns, the
        cost of keeping one loop shape for both modes).

      stop_check: optional ``global_step -> bool`` hook called after
        every applied step — the **resumable epoch boundary** the
        elastic layer (``parallel.elastic``) yields through. Returning
        True stops the loop cleanly AT that step: the partial epoch's
        eval/logging is skipped (its metrics would be a lie), the state
        carries the exact step count, and the caller owns the follow-up
        (the elastic worker force-saves a checkpoint and exits with
        ``EXIT_YIELD`` so a re-formed cluster resumes from here via the
        loader's epoch/skip math). The hook also doubles as per-step
        progress for heartbeats, so it is called even when False.

    Mid-epoch resume is the **loader's** job, not this loop's: set
    ``DataLoader.epoch``/``DataLoader.skip_next_batches`` before calling
    (as ``train.py`` does) so the already-trained prefix is sliced off at
    the index level and never decoded. The loop itself never skips batches
    — a second, engine-level skip stacked on the loader's caused a resumed
    run to silently drop data (round-2 VERDICT bug).

    Returns:
      ``(final_state, results)`` where results matches the reference's dict
      shape: ``{"train_loss": [...], "train_acc": [...], "test_loss": [...],
      "test_acc": [...]}`` (engine.py:173).
    """
    if train_step is None:
        train_step = jax.jit(make_train_step(), donate_argnums=0)
    if eval_step is None:
        eval_step = jax.jit(make_eval_step())

    results = {"train_loss": [], "train_acc": [],
               "test_loss": [], "test_acc": []}

    from .compile_cache import STATS as cache_stats
    from .compile_cache import seconds_since_process_start

    entered = seconds_since_process_start()
    global_step = int(jax.device_get(state.step))
    time_to_first_step = None

    stop_requested = False
    for epoch in range(epochs):
        t0 = time.perf_counter()
        total = None
        steps = 0
        epoch_no = start_epoch + epoch + 1
        batches = iter(train_batches())
        while True:
            # Data-wait span: host time blocked on the batch
            # iterator — the loader's share of the step, separated
            # from the device's (the clock calls cost ~100 ns; the
            # telemetry overhead gate holds the whole path < 2%).
            t_wait = time.perf_counter()
            try:
                batch = next(batches)
            except StopIteration:
                break
            t_step = time.perf_counter()
            data_wait = t_step - t_wait
            if telemetry is not None:
                # Pre-step hook: opens an armed profiler capture
                # window BEFORE dispatch (after it, the window
                # would miss this step's XLA ops). A None-check
                # when no profiler is wired.
                telemetry.step_begin(global_step + 1)
            state, metrics = train_step(state, batch)
            blocked = False
            if telemetry is not None and telemetry.should_block():
                # Sampled honesty barrier: async dispatch returns
                # before the device finishes, so unsampled step
                # walls measure dispatch; barriering every N-th
                # step re-pins the host timeline to the device at
                # amortized-negligible cost.
                # vitlint: hot-path-ok(sampled honesty barrier, every telemetry.block_every steps)
                jax.block_until_ready(metrics["loss_sum"])
                blocked = True
            if time_to_first_step is None:
                # The cold-start headline: process start -> first
                # optimizer update applied. The one-off barrier makes
                # it honest (async dispatch would otherwise report
                # trace time, not compile+execute time); on a resume
                # it measures THIS restart's latency — exactly the
                # number preemption recovery pays on top of the
                # checkpoint gap.
                # vitlint: hot-path-ok(one-off time-to-first-step barrier, first step only)
                jax.block_until_ready(metrics["loss_sum"])
                blocked = True
                time_to_first_step = cache_stats.close_stage(
                    "first_step", entered)
                if telemetry is not None:
                    telemetry.first_step(train_step, state, batch)
                if verbose:
                    # vitlint: hot-path-ok(once per process, with the first-step barrier)
                    print(f"time_to_first_step: "
                          f"{time_to_first_step:.2f}s (process start "
                          f"-> first train step applied)")
                    _report_first_step(train_step, state, batch,
                                       cache_stats)
            total = _accumulate(total, metrics)
            steps += 1
            global_step += 1
            if telemetry is not None:
                # A token model's routing counters ride the barriered
                # steps only: the step has finished there, so the fetch
                # waits for nothing.
                counters = None
                if blocked and "moe_dropped_pairs" in metrics:
                    # vitlint: hot-path-ok(sampled, on steps already barriered)
                    counters = {k: float(v) for k, v in jax.device_get(
                        {k: v for k, v in metrics.items()
                         if k.startswith("moe_") or k in LM_COUNTERS}
                    ).items()}
                telemetry.step(
                    data_wait_s=data_wait,
                    exec_s=time.perf_counter() - t_step,
                    images=int(batch["label"].shape[0]),
                    step=global_step, epoch=epoch_no, blocked=blocked,
                    counters=counters)
            if (checkpoint_every_steps and checkpointer is not None
                    and global_step % checkpoint_every_steps == 0):
                t_ck = time.perf_counter()
                checkpointer.save(state)
                if telemetry is not None:
                    telemetry.span("checkpoint",
                                   time.perf_counter() - t_ck)
            if stop_check is not None and stop_check(global_step):
                stop_requested = True
                break
        if stop_requested:
            # Clean mid-epoch yield (elastic re-formation): no partial-
            # epoch eval/log rows, no epoch-end save — the caller
            # checkpoints the returned state itself.
            break
        train_m = _finalize(total, steps) if total else {
            "loss": 0., "acc": 0., "count": 0., "skipped": 0.}
        train_time = time.perf_counter() - t0
        if train_m["skipped"] and verbose:
            print(f"[warn] nan-guard skipped {int(train_m['skipped'])} "
                  f"nonfinite update(s) this epoch")

        t_ev = time.perf_counter()
        eval_m = evaluate(
            state, eval_batches, eval_step=eval_step,
            on_batch=telemetry.heartbeat if telemetry is not None else None)
        if telemetry is not None:
            telemetry.span("eval", time.perf_counter() - t_ev)

        results["train_loss"].append(train_m["loss"])
        results["train_acc"].append(train_m["acc"])
        results["test_loss"].append(eval_m["loss"])
        results["test_acc"].append(eval_m["acc"])

        img_per_sec = train_m["count"] / max(train_time, 1e-9)
        if "teacher_agree" in train_m:
            # Distillation observability (ISSUE 19): the blended loss
            # and live teacher-agreement ride the process registry so
            # ::metrics / the shipper expose the same fidelity signal
            # the cascade gate will measure at serve time.
            from .telemetry import get_registry
            reg = get_registry()
            reg.gauge("distill_loss", round(train_m["loss"], 6))
            reg.gauge("distill_teacher_agree_frac",
                      round(train_m["teacher_agree"], 6))
        if verbose:
            # Same per-epoch readout as reference engine.py:196-202
            # (+ the KD agreement leg when distilling).
            agree = (f" | teacher_agree: {train_m['teacher_agree']:.4f}"
                     if "teacher_agree" in train_m else "")
            print(f"Epoch: {epoch_no} | "
                  f"train_loss: {train_m['loss']:.4f} | "
                  f"train_acc: {train_m['acc']:.4f} | "
                  f"test_loss: {eval_m['loss']:.4f} | "
                  f"test_acc: {eval_m['acc']:.4f} | "
                  f"img/s: {img_per_sec:.1f}{agree}")
        if logger is not None:
            # ONE device fetch of the step scalar per log line (it used
            # to be read back once for the LR and again for the step
            # field — each a blocking device->host round-trip).
            cur_step = int(jax.device_get(state.step))
            extra = {}
            if "grad_norm" in train_m:
                extra["grad_norm"] = train_m["grad_norm"]
            if train_m["skipped"]:
                extra["skipped_steps"] = train_m["skipped"]
            if lr_schedule is not None:
                # End-of-epoch LR: makes the warmup->decay trajectory
                # auditable from the JSONL (callers map micro-steps to
                # optimizer updates before passing the schedule).
                extra["lr"] = float(lr_schedule(cur_step))
            if epoch == 0 and time_to_first_step is not None:
                # Restart-latency leg in the run log, once per process,
                # with the persistent-cache counters that explain it
                # (keys match ServeStats.emit so dashboards share one
                # vocabulary).
                extra["time_to_first_step"] = round(time_to_first_step, 3)
                cache = cache_stats.snapshot()
                for name, stage in cache["startup"].items():
                    extra[f"startup_{name}_s"] = round(stage["seconds"], 3)
                if cache["requests"]:
                    extra["compile_cache_hits"] = cache["hits"]
                    extra["compile_cache_misses"] = cache["misses"]
            logger.log(step=cur_step, epoch=epoch_no,
                       train_loss=train_m["loss"], train_acc=train_m["acc"],
                       test_loss=eval_m["loss"], test_acc=eval_m["acc"],
                       images_per_sec=img_per_sec, **extra)
        if checkpointer is not None and (
                epoch_no % max(1, checkpoint_every_epochs) == 0
                or epoch == epochs - 1):
            t_ck = time.perf_counter()
            checkpointer.save(state)
            if telemetry is not None:
                telemetry.span("checkpoint", time.perf_counter() - t_ck)
        if telemetry is not None:
            # Epoch goodput summary row (step p50/p95/p99, data-wait
            # fraction, goodput %) — trace_report's per-epoch table.
            telemetry.epoch_end(epoch=epoch_no, step=global_step)

    if checkpointer is not None:
        checkpointer.wait()
    return state, results
