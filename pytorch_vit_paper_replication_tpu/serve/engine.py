"""The online inference engine: checkpoint -> warmed, micro-batched model.

Wraps (model, params) behind a :class:`.batching.MicroBatcher` whose
device callback is ONE **fused multi-head forward** (ISSUE 12): the
backbone runs once per device batch and splits at the heads —

* ``probs`` — the jitted ``softmax(head(pool(backbone(x))))``, the SAME
  expression :mod:`..predictions` jits, so a served classifier request
  is bit-identical to ``predict_image`` (the round-trip test asserts
  it);
* ``features`` — the pooled ``[D]`` embedding, the SAME
  backbone-apply + pool + float32 expression
  :class:`.offline.OfflineEngine`'s features head runs (the parity
  test asserts bit-identity);
* ``tokens`` — the full final-LN ``[T, D]`` token sequence
  (:class:`..models.ViTFeatureExtractor`'s output), the remaining half
  of ROADMAP item 4(a).

The backbone is >99% of the FLOPs (telemetry/flops.py), so computing
every head for every row costs ~nothing extra — and it buys the thing
that matters: the compiled shape universe is ONE program per bucket
rung regardless of the head mix, so classifier and embedding traffic
coalesce into the SAME device batches instead of running two
backbone passes (or two fleets). Host transfer stays per-need: only
the heads some request in the batch actually asked for are fetched.
Models without the ViT ``{"backbone", "head"}`` param split serve
``probs`` only (``engine.heads`` says which heads are live).

Startup **warmup** is ahead-of-time: every bucket rung is explicitly
``jit(...).lower(shape).compile()``d (no throwaway execute-to-warm
forwards), each compiled executable kept and dispatched directly, with
per-rung compile seconds recorded in :class:`.stats.ServeStats` — so a
slow restart is diagnosable from ``::stats`` alone, and with a
persistent compilation cache (:mod:`..compile_cache`) a restarted
server deserializes instead of recompiling. ``warmup="async"`` runs
the ladder in a background thread, smallest rung first: the server can
accept traffic immediately, requests for already-warm rungs are
servable before the ladder finishes, and a not-yet-warm rung falls
back to the ordinary jit path (compile-on-demand, usually a cache
hit).

The **warmup manifest** (``warmup.json`` next to the checkpoint —
model-config fingerprint, bucket ladder, image size, dtype) is written
at first serve, extended at shutdown with any rungs traffic dispatched
beyond the recorded set, and consumed on restart, so a restarted
server compiles exactly the recorded, traffic-extended shape set — a
ladder widened later can't leave its new rungs permanently cold. A
manifest whose fingerprint or ladder disagrees with this engine's is
refused (ValueError) instead of silently warming the wrong programs
(the CLI's ``--no-manifest`` opts out for a deliberate ladder change).

``InferenceEngine.from_checkpoint`` loads exactly the way ``predict.py``
does: a training ``--checkpoint-dir`` is resolved to its ``final``
params-only export, and the run's recorded ``transform.json`` (image
size, pretrained-crop geometry, normalize) is honored so the serving
path preprocesses pixels identically to training eval.
"""

from __future__ import annotations

import concurrent.futures as cf
import json
import threading
import time
import warnings
from pathlib import Path
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple, Union)

import numpy as np

from .. import compile_cache
from ..utils.atomic import atomic_write_json
from .batching import DEFAULT_TIER, MicroBatcher
from .bucketing import DEFAULT_BUCKETS, plan_buckets
from .stats import ServeStats

WARMUP_MANIFEST = "warmup.json"
# The fused forward's head set, in output order. Requests tag one.
HEADS: Tuple[str, ...] = ("probs", "features", "tokens")


def _manifest_dir(directory: str | Path) -> Path:
    """A training ``--checkpoint-dir`` and its ``final`` params export
    must share ONE manifest, whichever spelling the operator used —
    the same resolution checkpoint loading (and the deploy
    controller's fingerprinting) applies: ``utils.digest
    .resolve_export_dir``, the one copy."""
    from ..utils.digest import resolve_export_dir
    return resolve_export_dir(directory)


def model_fingerprint(model, image_size: int) -> str:
    """Identity of the compiled-program universe: the model's config
    dataclass (architecture, dtype, attention/mlp impls — everything
    that changes the HLO) plus the serving image size."""
    ident = getattr(model, "config", None)
    if ident is None:  # non-ViT modules: class name is the best we have
        ident = type(model).__name__
    return compile_cache.config_fingerprint(ident, image_size=image_size)


def write_warmup_manifest(directory: str | Path, *, fingerprint: str,
                          buckets: Sequence[int], image_size: int,
                          dtype: str,
                          heads: Optional[Sequence[str]] = None) -> Path:
    """Record the traffic-proven shape set next to the checkpoint.

    Written via :func:`..utils.atomic.atomic_write_json` (temp-file +
    atomic replace): a replica (or restart) reading concurrently never
    observes a torn file, and a process killed mid-write leaves the
    previous manifest intact. Concurrent writers — replicas sharing
    one checkpoint dir — are last-writer-wins; a rung union lost to
    the race self-heals at that replica's next
    :meth:`InferenceEngine.close`.
    """
    payload = {
        "fingerprint": fingerprint,
        "buckets": sorted(int(b) for b in buckets),
        "image_size": int(image_size),
        "dtype": str(dtype),
    }
    if heads is not None:
        # Informational (the rung set is the warm contract; the fused
        # program serves every head from one executable per rung) —
        # recorded so an operator reading warmup.json can see which
        # heads this checkpoint's serving program answers.
        payload["heads"] = [str(h) for h in heads]
    return atomic_write_json(
        _manifest_dir(directory) / WARMUP_MANIFEST, payload, indent=2)


def load_warmup_manifest(directory: str | Path) -> Optional[dict]:
    """None when no manifest exists; ValueError (with delete-it
    guidance, not a raw JSON traceback) when one exists but cannot be
    parsed — external tampering or a non-atomic third-party write."""
    path = _manifest_dir(directory) / WARMUP_MANIFEST
    if not path.is_file():
        return None
    try:
        manifest = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(
            f"corrupt warmup manifest {path}: {e}; delete it and the "
            "next serve will rebuild the shape set") from e
    if not isinstance(manifest, dict):
        raise ValueError(
            f"corrupt warmup manifest {path}: expected a JSON object, "
            f"got {type(manifest).__name__}; delete it and the next "
            "serve will rebuild the shape set")
    return manifest


def validate_warmup_manifest(manifest: dict, *, fingerprint: str,
                             buckets: Sequence[int],
                             image_size: int) -> List[int]:
    """Returns the manifest's rung set, or raises ValueError when the
    manifest belongs to a different program universe — a mismatched
    model-config fingerprint / image size, or a ladder ``plan_buckets``
    on THIS engine's ladder would never dispatch (warming those shapes
    would compile programs no request can ever ride)."""
    if manifest.get("fingerprint") != fingerprint:
        raise ValueError(
            "warmup manifest fingerprint mismatch: the manifest was "
            "written for a different model config/dtype/image size; "
            f"delete {WARMUP_MANIFEST} or serve the matching checkpoint")
    # A missing image_size key is a mismatch, not a pass — defaulting to
    # the engine's own value would make this check vacuous.
    if int(manifest.get("image_size", -1)) != int(image_size):
        raise ValueError(
            f"warmup manifest image_size {manifest.get('image_size')} != "
            f"engine image_size {image_size}")
    rungs = sorted(int(b) for b in manifest.get("buckets", []))
    if not rungs:
        raise ValueError("warmup manifest has no bucket ladder")
    ladder = tuple(sorted(set(int(b) for b in buckets)))
    for r in rungs:
        if plan_buckets(r, ladder) != [r]:
            raise ValueError(
                f"warmup manifest rung {r} disagrees with plan_buckets "
                f"on this engine's ladder {list(ladder)}: no request "
                f"would ever dispatch that shape; delete the manifest "
                f"or serve with the original --buckets")
    return rungs


class ServeResult(NamedTuple):
    label: Any            # class name when known, else the class index
    prob: float
    probs: np.ndarray     # full softmax row, float32 [num_classes]


class InferenceEngine:
    """See module docstring.

    ``max_wait_us`` is the latency/occupancy knob: how long the batcher
    holds the oldest queued request hoping for company. ``max_queue``
    bounds admission (beyond it, ``submit`` raises
    :class:`.batching.QueueFullError` with a retry-after hint).
    """

    def __init__(self, model, params: Any, *,
                 image_size: int = 224,
                 transform=None,
                 class_names: Optional[Sequence[str]] = None,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 max_wait_us: int = 2000,
                 batch_max_wait_us: int = 50_000,
                 max_queue: int = 1024,
                 stats: Optional[ServeStats] = None,
                 segregate_heads: bool = False,
                 warmup: Union[bool, str] = True,
                 warmup_rungs: Optional[Sequence[int]] = None,
                 warmup_callback: Optional[Callable[[int, float],
                                                    None]] = None,
                 search_index=None,
                 search_k_max: int = 100,
                 model_tier: Optional[str] = None):
        from ..data.transforms import eval_transform

        self.model = model
        self.image_size = int(image_size)
        self.transform = transform or eval_transform(self.image_size)
        self.class_names = (list(class_names)
                            if class_names is not None else None)
        # Operator-declared deployment tier (serve --model-tier,
        # e.g. "student"/"teacher" in a cascade fleet). When set it
        # wins over the arch-derived label in ::stats — the operator
        # is stating which ROLE this replica plays, not which
        # architecture it happens to be.
        self.declared_model_tier = (str(model_tier)
                                    if model_tier else None)
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.stats = stats if stats is not None else ServeStats()
        self._params = params
        # The fused multi-head forward (see module docstring): ONE
        # program per rung serving every head a request may tag.
        self._fwd, self.heads = self._make_forward(model, params)
        # AOT-compiled executables per rung (written by warmup, read by
        # the single batcher worker thread; dict writes are atomic).
        self._compiled: Dict[int, Any] = {}
        self._warmup_callback = warmup_callback
        self._warmup_rungs = tuple(sorted(set(
            int(b) for b in (warmup_rungs
                             if warmup_rungs is not None else self.buckets))))
        self._warmup_thread: Optional[threading.Thread] = None
        self._warmup_error: Optional[str] = None
        # (directory, fingerprint, dtype) set by from_checkpoint when
        # manifest upkeep is on; close() extends the recorded rung set
        # with what traffic actually dispatched.
        self._manifest_target: Optional[Tuple[Path, str, str]] = None
        # Content identity of the checkpoint this engine is ANSWERING
        # FROM (sha256 over the resolved params export's payload bytes,
        # set by from_checkpoint; None for in-memory-constructed
        # engines). Distinct from model_fingerprint — that identifies
        # the compiled-program universe, identical across two
        # checkpoints of one config; this identifies the params. The
        # fleet health poll reads it out of ::stats so the deploy
        # canary judge can PROVE which model answered which window (a
        # half-completed rollout is otherwise indistinguishable from a
        # healthy mixed fleet).
        self.checkpoint_fingerprint: Optional[str] = None
        self.checkpoint_path: Optional[str] = None
        # Embedding search (ISSUE 13): a built search/ index this
        # engine answers ``::search K <path>`` against — the query is
        # embedded through the fused features head (bit-identical to
        # the offline embedder that filled the index), then the
        # device-sharded scanner finds its neighbors. The scanner's
        # per-device shards are placed ONCE here, like params.
        self._search_index = None
        self._scanner = None
        if search_index is not None:
            from ..search.index import EmbeddingIndex
            from ..search.scan import ShardedScanner

            idx = (search_index if isinstance(search_index,
                                              EmbeddingIndex)
                   else EmbeddingIndex(search_index))
            if "features" not in self.heads:
                raise ValueError(
                    "search_index needs the features head; this "
                    f"model serves only {list(self.heads)}")
            fp = model_fingerprint(model, self.image_size)
            if idx.fingerprint is not None and idx.fingerprint != fp:
                # The index was embedded by a different program
                # universe (model config / dtype / image size):
                # neighbors would be computed in a foreign embedding
                # space. Warn, don't die — an operator may serve a
                # numerically-identical re-export whose config
                # fingerprint legitimately moved.
                warnings.warn(
                    f"search index {idx.path} was built from "
                    f"fingerprint {idx.fingerprint}, this engine is "
                    f"{fp}: queries and index rows may live in "
                    "different embedding spaces", stacklevel=2)
            if int(idx.dim) != self._feature_dim():
                raise ValueError(
                    f"search index dim {idx.dim} != this model's "
                    f"pooled embedding dim {self._feature_dim()}")
            self._search_index = idx
            self._scanner = ShardedScanner(
                idx.embeddings, k_max=int(search_k_max),
                metric=idx.metric, norms=idx.norms,
                registry=self.stats.registry)
        self._batcher = MicroBatcher(
            self._device_forward, buckets=self.buckets,
            max_wait_us=max_wait_us, batch_max_wait_us=batch_max_wait_us,
            max_queue=max_queue, stats=self.stats,
            segregate_heads=segregate_heads)
        if warmup == "async":
            self._warmup_thread = threading.Thread(
                target=self._warmup_guarded, name="serve-warmup",
                daemon=True)
            self._warmup_thread.start()
        elif warmup:
            self.warmup()

    # ---------------------------------------------------------- device
    @staticmethod
    def _make_forward(model, params):
        """Build the fused multi-head jitted forward. Nothing is donated:
        params are shared across batches, and no head's output has the
        request batch's shape for XLA to alias it to.

        For a ViT-shaped (model, params) — a ``.config`` plus the
        ``{"backbone", "head"}`` param split — the program runs the
        backbone ONCE and emits every head:

        * ``probs`` is EXACTLY the ``predictions._jitted_forward``
          expression (backbone -> pool -> float32 head -> softmax, the
          ops :class:`..models.ViT`'s compact body runs), so served
          classifier rows stay bit-identical to ``predict_image``;
        * ``features`` is EXACTLY the offline features-head expression
          (backbone tokens -> pool -> float32), so online embeddings
          stay bit-identical to :class:`.offline.OfflineEngine`;
        * ``tokens`` is the float32 final-LN token sequence.

        Anything else (a custom module without the split) serves the
        classic softmax as a ``probs``-only dict — one output contract
        for the batcher either way.
        """
        import jax
        import jax.numpy as jnp

        cfg = getattr(model, "config", None)
        multihead = (cfg is not None and isinstance(params, dict)
                     and "backbone" in params and "head" in params)
        if not multihead:
            def fwd_probs(p, x):
                return {"probs": jax.nn.softmax(
                    model.apply({"params": p}, x).astype(jnp.float32),
                    axis=-1)}
            return jax.jit(fwd_probs), ("probs",)

        import flax.linen as nn

        from ..models import ViTFeatureExtractor

        backbone = ViTFeatureExtractor(cfg)
        pool = cfg.pool
        n_classes = cfg.num_classes

        def fused(p, x):
            tokens = backbone.apply({"params": p["backbone"]}, x)
            pooled = tokens[:, 0] if pool == "cls" else \
                tokens.mean(axis=1)
            # The float32 head Dense is ViT's own (models.apply_tail
            # runs the same standalone apply; pinned equal by tests).
            logits = nn.Dense(
                n_classes, dtype=jnp.float32,
                param_dtype=jnp.float32).apply(
                {"params": p["head"]}, pooled.astype(jnp.float32))
            return {"probs": jax.nn.softmax(
                        logits.astype(jnp.float32), axis=-1),
                    "features": pooled.astype(jnp.float32),
                    "tokens": tokens.astype(jnp.float32)}
        return jax.jit(fused), HEADS

    def _device_forward(self, padded: np.ndarray, mask: np.ndarray,
                        heads: Optional[Sequence[str]] = None
                        ) -> Dict[str, np.ndarray]:
        import jax.numpy as jnp

        # mask rides the eval pad+mask contract: rows of a ViT forward
        # are independent, so correctness needs only that callers never
        # READ pad rows — the batcher slices real rows by construction.
        del mask
        # AOT-warmed rungs dispatch their compiled executable directly;
        # anything else (background warmup still running, a rung the
        # manifest skipped) rides the jit path — compile-on-demand,
        # usually a persistent-cache hit when one is configured.
        fwd = self._compiled.get(int(padded.shape[0]), self._fwd)
        out = fwd(self._params, jnp.asarray(padded))
        # THE response drain: served rows must land on host to resolve
        # the per-request futures — one fetch per NEEDED head per
        # batch (the fused program computes every head — backbone
        # cost — but only heads some request tagged pay host
        # transfer; tokens rows are T x D, not worth shipping unasked).
        need = set(heads) if heads is not None else {"probs"}
        # vitlint: hot-path-ok(request/response boundary, one drain per needed head per batch)
        host = {h: np.asarray(v) for h, v in out.items() if h in need}
        self.stats.observe_first_batch(
            compile_cache.seconds_since_process_start())
        return host

    def _aot_compile_rung(self, b: int) -> float:
        """``jit(...).lower(shape).compile()`` one rung; returns seconds."""
        import jax

        t0 = time.perf_counter()
        x_s = jax.ShapeDtypeStruct(
            (b, self.image_size, self.image_size, 3), np.float32)
        p_s = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self._params)
        compiled = self._fwd.lower(p_s, x_s).compile()
        dt = time.perf_counter() - t0
        self._compiled[b] = compiled
        self.stats.observe_warmup_rung(b, dt)
        if self._warmup_callback is not None:
            self._warmup_callback(b, dt)
        return dt

    def _warmup_guarded(self) -> None:
        try:
            self.warmup()
        except Exception as e:  # noqa: BLE001 — background thread: the
            # engine stays up on the jit fallback; ::stats carries the
            # diagnosis instead of a dead thread's lost traceback.
            self._warmup_error = f"{type(e).__name__}: {e}"

    def warmup(self, rungs: Optional[Sequence[int]] = None) -> List[int]:
        """AOT-compile the rung set (default: the warmup ladder) before
        serving, smallest first so single-request traffic is servable
        earliest; returns the compiled rungs."""
        t0 = time.perf_counter()
        todo = sorted(set(int(b) for b in (
            rungs if rungs is not None else self._warmup_rungs)))
        for b in todo:
            self._aot_compile_rung(b)
        self.stats.warmup_finished(time.perf_counter() - t0)
        return todo

    def wait_warm(self, timeout: Optional[float] = None) -> bool:
        """Block until a background (``warmup="async"``) ladder finishes;
        True when every requested rung is compiled."""
        if self._warmup_thread is not None:
            self._warmup_thread.join(timeout)
        return all(b in self._compiled for b in self._warmup_rungs)

    # ------------------------------------------------------------- API
    def _to_row(self, image) -> np.ndarray:
        from PIL import Image

        if isinstance(image, (str, Path)):
            with Image.open(image) as img:
                return np.asarray(self.transform(img))
        if isinstance(image, Image.Image):
            return np.asarray(self.transform(image))
        return np.asarray(image, np.float32)

    def _wrap(self, raw: cf.Future) -> cf.Future:
        out: cf.Future = cf.Future()

        def done(f: cf.Future):
            # Anything raised here is swallowed by cf's callback
            # machinery (logged, not raised), which would leave `out`
            # unresolved and the caller blocked forever — so every
            # failure mode must land on the future instead.
            try:
                err = f.exception()
                if err is not None:
                    out.set_exception(err)
                    return
                probs = np.asarray(f.result())
                idx = int(probs.argmax())
                label = (self.class_names[idx]
                         if self.class_names is not None else idx)
                out.set_result(ServeResult(label, float(probs[idx]), probs))
            except Exception as e:  # noqa: BLE001
                if not out.done():
                    out.set_exception(e)

        raw.add_done_callback(done)
        return out

    def submit(self, image, timeout: Optional[float] = None,
               head: str = "probs",
               tier: str = DEFAULT_TIER, ctx=None) -> cf.Future:
        """Enqueue one image (path / PIL / preprocessed array); returns
        a Future of :class:`ServeResult` (``head="probs"``) or of the
        raw float32 row — ``[D]`` for ``features``, ``[T, D]`` for
        ``tokens``. ``tier`` picks the SLO class (``interactive`` |
        ``batch`` — see :mod:`.batching`). ``ctx`` (ISSUE 20) is the
        request's sampled TraceContext (or None): the batcher records
        its queue-wait/device spans under it. Raises
        :class:`.batching.QueueFullError` under backpressure and
        ValueError for a head this engine's model cannot serve."""
        if head not in self.heads:
            raise ValueError(
                f"unknown head {head!r}; this engine serves "
                f"{list(self.heads)}")
        raw = self._batcher.submit(self._to_row(image), timeout=timeout,
                                   head=head, tier=tier, ctx=ctx)
        return self._wrap(raw) if head == "probs" else raw

    def predict(self, images: Sequence,
                timeout: Optional[float] = None) -> List[ServeResult]:
        """Synchronous convenience: submit all, wait for all."""
        futures = [self.submit(img, timeout=timeout) for img in images]
        return [f.result() for f in futures]

    def _feature_dim(self) -> int:
        cfg = getattr(self.model, "config", None)
        return int(getattr(cfg, "embedding_dim", -1))

    @property
    def search_index(self):
        """The attached :class:`..search.index.EmbeddingIndex`, or
        None when this engine serves no ``::search`` traffic."""
        return self._search_index

    def search(self, image, k: int, *,
               tier: str = DEFAULT_TIER,
               timeout: Optional[float] = None
               ) -> Tuple[List[int], List[float]]:
        """Embed ``image`` through the features head (coalescing with
        every other head's traffic in the micro-batcher) and scan the
        attached index; returns ``(row_ids, scores)`` of the K nearest
        index rows, best first. Bit-consistent with embedding the same
        image offline and scanning the same index (the features head
        is pinned bit-identical to the offline embedder, and the scan
        is deterministic) — the search bench gates exactly that."""
        if self._scanner is None:
            raise ValueError(
                "no search index attached (serve --search-index DIR "
                "after building one with tools/build_index.py)")
        if not 1 <= int(k) <= self._scanner.k_max:
            raise ValueError(
                f"k={k} outside [1, {self._scanner.k_max}] (bound at "
                "engine construction by search_k_max and the index "
                "size)")
        emb = self._batcher.submit(
            self._to_row(image), timeout=timeout, head="features",
            tier=tier).result()
        scores, ids = self._scanner.scan(
            np.asarray(emb, np.float32)[None, :], int(k))
        return [int(i) for i in ids[0]], [float(s) for s in scores[0]]

    def publish_telemetry(self, registry=None):
        """Sync this engine's live state into the telemetry registry
        (``serve_*`` names) and return it — ONE publish path shared by
        the ``::metrics`` command and the fleet shipper's per-frame
        ``pre_ship`` callback, so a scraped endpoint and a shipped
        frame can never disagree about what "current" means. Defaults
        to the stats' BOUND registry (where the ``serve_lat_*_s``
        histogram samples already stream) — see
        :meth:`..serve.stats.ServeStats.publish` for the explicit-
        registry caveat."""
        reg = registry if registry is not None else self.stats.registry
        self.stats.publish(reg)
        reg.gauge("serve_queue_depth", self._batcher.queue_depth())
        reg.gauge("serve_warm_rungs", len(self._compiled))
        return reg

    def prometheus_metrics(self) -> str:
        """The live registry as Prometheus text exposition — serving
        stats synced in (``serve_*``), plus whatever else this process
        published (compile-cache counters, data-pipeline counters). The
        socket CLI's ``::metrics`` command returns exactly this."""
        return self.publish_telemetry().to_prometheus()

    def snapshot(self) -> dict:
        """Serving stats + engine config, JSON-serializable."""
        snap = self.stats.snapshot()
        snap["served_heads"] = list(self.heads)
        snap["buckets"] = list(self.buckets)
        snap["effective_bucket_cap"] = self._batcher.effective_bucket_cap
        snap["queue_depth"] = self._batcher.queue_depth()
        snap["warm_rungs"] = sorted(self._compiled)
        snap["search_index"] = (self._search_index.describe()
                                if self._search_index is not None
                                else None)
        snap["checkpoint_fingerprint"] = self.checkpoint_fingerprint
        snap["checkpoint_path"] = self.checkpoint_path
        # Reported model tier: the operator's --model-tier declaration
        # when given (deployment ROLE — "student"/"teacher"), else the
        # arch-derived label ("ViT-Ti/16" …, informational). Fleet
        # model= routing keys on the deployment spec's declared name,
        # never on this self-report.
        if self.declared_model_tier is not None:
            snap["model_tier"] = self.declared_model_tier
        else:
            cfg = getattr(self.model, "config", None)
            if cfg is not None:
                from ..configs import model_tier
                snap["model_tier"] = model_tier(cfg)
            else:
                snap["model_tier"] = None
        if self._warmup_error is not None:
            snap["warmup"]["error"] = self._warmup_error
        return snap

    def _extend_manifest(self) -> None:
        """Union the rungs traffic actually dispatched into the manifest
        (best-effort), so a ladder widened after the first serve gets its
        new, now traffic-proven rungs AOT-warmed on the next restart
        instead of staying permanently on the jit fallback."""
        if self._manifest_target is None:
            return
        dispatched = set(self.stats.dispatched_buckets())
        directory, fp, dtype = self._manifest_target
        try:
            existing = load_warmup_manifest(directory)
        except ValueError:
            existing = None  # corrupt: the rewrite below repairs it
        recorded = set(existing.get("buckets", [])) if existing else set()
        if not dispatched - recorded:
            return
        try:
            write_warmup_manifest(
                directory, fingerprint=fp,
                buckets=sorted(recorded | dispatched),
                image_size=self.image_size, dtype=dtype,
                heads=self.heads)
        except OSError:
            pass  # read-only checkpoint dir: startup already warned

    def drain(self, timeout_s: float = 10.0) -> int:
        """Quiesce the micro-batcher (:meth:`.batching.MicroBatcher.
        drain`): new submits fail with ``DrainingError``, in-flight
        work flushes, returns the unfinished count. The fleet rollout
        path calls this (via the CLI's ``::drain`` command) before
        restarting a replica onto a new checkpoint."""
        return self._batcher.drain(timeout_s)

    def resume(self) -> None:
        """Lift a :meth:`drain` — admissions open again."""
        self._batcher.resume()

    def close(self) -> None:
        self._batcher.close()
        self._extend_manifest()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------ constructors
    @classmethod
    def from_checkpoint(cls, checkpoint: str | Path, *,
                        preset: str = "ViT-B/16",
                        class_names: Optional[Sequence[str]] = None,
                        num_classes: Optional[int] = None,
                        image_size: Optional[int] = None,
                        normalize: Optional[bool] = None,
                        use_manifest: bool = True,
                        **engine_kwargs) -> "InferenceEngine":
        """Load a params export (or a training --checkpoint-dir) and
        build a warmed engine, honoring ``transform.json`` exactly as
        ``predict.py`` does — the SAME
        :func:`..predictions.load_inference_checkpoint` call, so serving
        preprocessing cannot drift from offline prediction.

        With ``use_manifest`` (default), an existing ``warmup.json``
        next to the checkpoint narrows warmup to exactly the
        traffic-proven rung set (validated against this engine's model
        fingerprint and ladder — see :func:`validate_warmup_manifest`;
        an explicit ``warmup_rungs`` kwarg wins over the manifest);
        when absent and warmup is enabled, one is written at first
        serve so the NEXT restart warms the proven set (best-effort:
        a read-only checkpoint directory warns instead of failing).
        At :meth:`close`, rungs traffic dispatched beyond the recorded
        set are unioned in, so a later ladder widening converges to
        warm instead of fossilizing on the first serve's shape set.
        """
        from ..predictions import load_inference_checkpoint

        if class_names is None and num_classes is None:
            raise ValueError("pass class_names or num_classes")
        n_classes = (len(class_names) if class_names is not None
                     else int(num_classes))
        model, params, transform, spec = load_inference_checkpoint(
            checkpoint, preset, n_classes,
            image_size=image_size, normalize=normalize)
        ladder = engine_kwargs.get("buckets", DEFAULT_BUCKETS)
        fp = model_fingerprint(model, spec["image_size"])
        manifest = load_warmup_manifest(checkpoint) if use_manifest else None
        if manifest is not None and "warmup_rungs" not in engine_kwargs:
            engine_kwargs["warmup_rungs"] = validate_warmup_manifest(
                manifest, fingerprint=fp, buckets=ladder,
                image_size=spec["image_size"])
        eng = cls(model, params, image_size=spec["image_size"],
                  transform=transform, class_names=class_names,
                  **engine_kwargs)
        # Content fingerprint of the export actually served: the SAME
        # digest walk deploy/ uses to fingerprint candidate exports, so
        # "which model is this replica answering from" is provable by
        # comparing ::stats against the export on disk.
        from ..utils.digest import (cached_checkpoint_fingerprint,
                                    resolve_export_dir)
        resolved = resolve_export_dir(checkpoint)
        eng.checkpoint_fingerprint = cached_checkpoint_fingerprint(
            resolved)
        eng.checkpoint_path = str(resolved)
        dtype = str(getattr(getattr(model, "config", None), "dtype",
                            "unknown"))
        if use_manifest:
            eng._manifest_target = (Path(checkpoint), fp, dtype)
        # First serve writes the manifest — but only when warmup is on
        # (a warmup=False engine proved nothing), and best-effort: a
        # checkpoint on a read-only mount must not kill the server.
        if (use_manifest and manifest is None
                and engine_kwargs.get("warmup", True)):
            try:
                write_warmup_manifest(
                    checkpoint, fingerprint=fp, buckets=eng.buckets,
                    image_size=eng.image_size, dtype=dtype,
                    heads=eng.heads)
            except OSError as e:
                warnings.warn(
                    f"could not write {WARMUP_MANIFEST} next to the "
                    f"checkpoint ({e}); restarts will warm the full "
                    f"ladder instead of the traffic-proven set",
                    stacklevel=2)
        return eng
