"""Offline batch inference: every local device, resumable, streaming.

The online engine (:mod:`.engine`) optimizes *latency* — coalesce
concurrent requests, dispatch small batches fast. This module is the
*throughput* half of ROADMAP item 4: sweep an entire packed-shard
dataset ("embed 10⁶ images overnight") through the same bucketed
jitted forward, but

* **sharded data-parallel over every local device** — one
  ``Mesh(jax.devices(), ("batch",))``, inputs ``device_put`` with a
  ``NamedSharding(P("batch"))``, params replicated once at
  construction (the SNIPPETS §1–3 pjit partitioning pattern). The
  bucket ladder is rounded up to device-count multiples
  (:func:`shard_ladder`) so every compiled shape splits evenly;
* **double-buffered**: dispatch is async — batch N+1's host→device
  copy and forward are issued while batch N still computes, with a
  bounded in-flight window (``prefetch``) so host memory stays O(few
  batches);
* **resumable**: an atomic progress manifest (``progress.json``,
  temp-file + ``os.replace`` — the PR 4 warmup-manifest discipline)
  records the record offset + output-row count after every flushed
  checkpoint. A SIGKILL'd run restarted with the same config resumes
  at the last durable offset and produces a final sink byte-identical
  to an unkilled run (manifest writes happen only at loader-batch
  boundaries, so the resumed chunking replays the original plan).
  COMPLETION seals the sink: the final manifest additionally records
  ``sink_sha256``, so a consumer (``tools/build_index.py``) can prove
  the matrix it memory-maps is the exact bytes this job finished;
* outputs append to a pre-sized ``.npy`` sink (:class:`NpySink` —
  rows written in place through a memmap, so "resume" is just "keep
  writing at the recorded row"), optionally mirrored as a predictions
  JSONL for the classifier head.

Heads: ``probs`` runs the exact :func:`..predictions.predict_image`
softmax expression (bit-identical rows — the test asserts it);
``features`` runs the :class:`..models.ViTFeatureExtractor` backbone
behind the same ladder and emits pooled ``[D]`` embeddings — the
minimal slice of ROADMAP 4(a); ``logits`` emits the pre-softmax
classifier activations (the probs expression minus the softmax,
bit-exact — softmax(logits row) == probs row), the distillation
dataset for ``train.py --distill-from`` and the calibration feed.

Telemetry rides the shared registry (``bi_*`` instruments): live
img/s gauge, data-wait vs device-drain histograms, progress gauge —
so ``tools/fleet_agg.py`` sees batch jobs next to train and serve.
"""

from __future__ import annotations

import json
import os
import time
from collections import deque
from pathlib import Path
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..utils.atomic import atomic_write_json
from .bucketing import DEFAULT_BUCKETS, pad_rows_to_bucket, plan_buckets
from .engine import model_fingerprint

PROGRESS_MANIFEST = "progress.json"
SINK_NAME = "outputs.npy"
PREDS_NAME = "preds.jsonl"
PROGRESS_VERSION = 1

# The one offline head registry: name -> what the sink rows are. Both
# the engine's validation and the batch_infer CLI (--head choices AND
# its error text) derive from this dict, so the two can never drift.
OFFLINE_HEADS = {
    "probs": "softmax class probabilities [C] (predict_image program)",
    "features": "pooled backbone embeddings [D]",
    "logits": "pre-softmax class scores [C] (the distillation dataset)",
}


def shard_ladder(buckets: Sequence[int], ndev: int) -> Tuple[int, ...]:
    """The bucket ladder rounded up to device-count multiples.

    ``NamedSharding(P("batch"))`` needs the batch dimension to split
    evenly over the mesh, so every rung becomes the next multiple of
    ``ndev`` (duplicates collapse: ``(1, 8)`` on 8 devices is just
    ``(8,)``). On one device this is the identity."""
    nd = max(1, int(ndev))
    rungs = {-(-int(b) // nd) * nd for b in buckets if int(b) >= 1}
    if not rungs:
        raise ValueError(f"bucket ladder must be positive ints: {buckets}")
    return tuple(sorted(rungs))


# --------------------------------------------------------------- manifest
def write_progress(out_dir: str | Path, payload: dict) -> Path:
    """Atomically persist the progress manifest (temp-file +
    ``os.replace`` via :func:`..utils.atomic.atomic_write_json`, the
    PR 4 warmup-manifest discipline): a reader — or a resume after
    SIGKILL — never observes a torn file, and a process killed
    mid-write leaves the previous manifest intact. The caller flushes
    the sink FIRST, so the manifest never claims rows that are not
    durably in the sink."""
    return atomic_write_json(
        Path(out_dir) / PROGRESS_MANIFEST,
        {"version": PROGRESS_VERSION, **payload}, indent=2)


def load_progress(out_dir: str | Path) -> Optional[dict]:
    """None when no manifest exists; ValueError (with delete-it
    guidance) when one exists but cannot be parsed."""
    path = Path(out_dir) / PROGRESS_MANIFEST
    if not path.is_file():
        return None
    try:
        manifest = json.loads(path.read_text())
    except (OSError, json.JSONDecodeError) as e:
        raise ValueError(
            f"corrupt progress manifest {path}: {e}; delete it (or the "
            "whole output dir) to restart the job from record 0") from e
    if not isinstance(manifest, dict):
        raise ValueError(
            f"corrupt progress manifest {path}: expected a JSON object, "
            f"got {type(manifest).__name__}; delete it to restart")
    return manifest


def validate_progress(manifest: dict, *, fingerprint: str, head: str,
                      total_records: int, out_dim: int, batch_size: int,
                      ladder: Sequence[int],
                      row_shape: Sequence[int] = ()) -> int:
    """Returns the resume offset (records_done), or raises ValueError
    when the manifest belongs to a different job: resuming under a
    different model/head/dataset-length/batching would silently mix
    two incompatible output streams in one sink. Batch size and
    ladder are part of the identity because bit-identical resume
    replays the original chunk plan — a different plan would still be
    *correct*, but the byte-identity contract is the stronger, more
    testable guarantee."""
    checks = (("fingerprint", fingerprint), ("head", head),
              ("total_records", int(total_records)),
              ("out_dim", int(out_dim)), ("batch_size", int(batch_size)),
              ("ladder", [int(b) for b in ladder]))
    if len(row_shape) > 1:
        # Tensor-row jobs additionally pin the full per-row shape —
        # out_dim (the trailing axis) is ambiguous between a [D]
        # vector sink and a [T, D] token sink with the same D.
        checks += (("row_shape", [int(d) for d in row_shape]),)
    for key, want in checks:
        got = manifest.get(key)
        if got != want:
            raise ValueError(
                f"progress manifest {key} mismatch: manifest has "
                f"{got!r}, this job wants {want!r} — the output dir "
                "belongs to a different job; point --out elsewhere, or "
                "delete it (or pass --fresh) to restart")
    done = int(manifest.get("records_done", -1))
    if not 0 <= done <= int(total_records):
        raise ValueError(
            f"progress manifest records_done={done} outside "
            f"[0, {total_records}]; delete the output dir to restart")
    return done


# ------------------------------------------------------------------ sinks
class NpySink:
    """A pre-sized float32 ``.npy`` written in place through a memmap.

    The total row count is known up front (the dataset length), so the
    file is created at final size immediately and rows land at their
    absolute offset — resuming is just reopening ``r+`` and continuing
    at the manifest's row. Rows beyond the last flushed checkpoint may
    hold partial data after a SIGKILL; the resumed run rewrites them
    with identical bytes, which is what makes the final file
    byte-identical to an unkilled run's."""

    def __init__(self, path: str | Path, *, rows: int,
                 dim: int | Sequence[int], resume: bool = False):
        # ``dim`` is the PER-ROW shape: an int for vector rows
        # ([C] probs/logits, [D] features) or a shape tuple for
        # tensor rows (e.g. unpooled [T, D] token grids) — the file
        # is always one contiguous float32 array of (rows, *dim).
        dims = ((int(dim),) if isinstance(dim, int)
                else tuple(int(d) for d in dim))
        shape = (int(rows),) + dims
        self.path = Path(path)
        if resume:
            self._map = np.lib.format.open_memmap(self.path, mode="r+")
            if self._map.shape != shape or \
                    self._map.dtype != np.float32:
                raise ValueError(
                    f"existing sink {self.path} is "
                    f"{self._map.dtype}{self._map.shape}, this job "
                    f"needs float32{shape}; delete the output "
                    "dir to restart")
        else:
            self._map = np.lib.format.open_memmap(
                self.path, mode="w+", dtype=np.float32, shape=shape)

    def write(self, row: int, values: np.ndarray) -> None:
        self._map[row:row + len(values)] = values

    def flush(self) -> None:
        self._map.flush()

    def close(self) -> None:
        self.flush()
        # Release the mapping promptly (Windows-style lingering handles
        # don't matter on Linux, but tests reopen the file immediately).
        del self._map


class PredsJsonl:
    """Optional classifier-predictions mirror: one
    ``{"index", "label", "prob"}`` line per record. Resume truncates
    to the manifest's recorded byte offset — rows written past the
    last checkpoint are cut and rewritten, keeping the file
    byte-identical to an unkilled run's."""

    def __init__(self, path: str | Path, *,
                 class_names: Optional[Sequence[str]] = None,
                 resume_bytes: Optional[int] = None):
        self.path = Path(path)
        self._classes = list(class_names) if class_names else None
        if resume_bytes is not None and int(resume_bytes) > 0:
            if not self.path.exists():
                # Same refusal discipline as the sink/manifest: silently
                # restarting the mirror here would produce a file that
                # starts mid-dataset while the run reports success.
                raise ValueError(
                    f"manifest records {resume_bytes} preds bytes but "
                    f"{self.path} is missing — the mirror cannot resume; "
                    "rerun with --fresh to rebuild the whole job")
            with open(self.path, "r+b") as f:
                f.truncate(int(resume_bytes))
            self._fh = open(self.path, "ab")
        else:
            # Streaming sink, not a manifest: durability comes from the
            # flush/fsync + manifest-records-the-offset contract, and
            # resume truncates to the recorded byte — temp+replace
            # doesn't apply to an append stream.
            # vitlint: disable=atomic-manifest(streaming sink; resume truncates to the manifest's recorded offset)
            self._fh = open(self.path, "wb")

    def write(self, start_index: int, probs: np.ndarray) -> None:
        lines = []
        for i, row in enumerate(probs):
            idx = int(row.argmax())
            label = self._classes[idx] if self._classes else idx
            lines.append(json.dumps(
                {"index": start_index + i, "label": label,
                 "prob": round(float(row[idx]), 6)}))
        self._fh.write(("\n".join(lines) + "\n").encode())

    def flush(self) -> int:
        """Durable byte offset (what the manifest records)."""
        self._fh.flush()
        os.fsync(self._fh.fileno())
        return self._fh.tell()

    def close(self) -> None:
        self._fh.close()


class _RecordRange:
    """Records ``[start, stop)`` of a dataset — the resume window.

    Forwards the page-cache hint hooks with the offset applied, so
    block readahead / evict-behind keep working on a resumed run."""

    def __init__(self, ds, start: int, stop: int):
        self._ds = ds
        self._start = int(start)
        self._n = int(stop) - int(start)
        self.classes = getattr(ds, "classes", None)

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, idx: int):
        if not 0 <= idx < self._n:
            raise IndexError(idx)
        return self._ds[self._start + idx]

    def willneed_records(self, lo: int, hi: int) -> None:
        if hasattr(self._ds, "willneed_records"):
            self._ds.willneed_records(lo + self._start, hi + self._start)

    def evict_records(self, lo: int, hi: int) -> None:
        if hasattr(self._ds, "evict_records"):
            self._ds.evict_records(lo + self._start, hi + self._start)


# ----------------------------------------------------------------- engine
class OfflineEngine:
    """All-device sharded batch-inference engine (see module docstring).

    ``prefetch`` bounds the in-flight dispatch window: each chunk's
    ``device_put`` + forward are issued asynchronously and the host
    only blocks fetching the OLDEST chunk once more than ``prefetch``
    are outstanding — at the default depth 2, batch N+1's host→device
    transfer overlaps batch N's compute (classic double buffering).
    """

    def __init__(self, model, params: Any, *, head: str = "probs",
                 image_size: int = 224,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 prefetch: int = 2,
                 class_names: Optional[Sequence[str]] = None,
                 devices: Optional[Sequence] = None,
                 registry=None):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from ..telemetry.registry import get_registry

        if head not in ("probs", "features", "logits"):
            raise ValueError(
                f"unknown head {head!r} (probs|features|logits)")
        self.model = model
        self.head = head
        self.image_size = int(image_size)
        self.prefetch = max(1, int(prefetch))
        self.class_names = (list(class_names)
                            if class_names is not None else None)
        self._registry = registry if registry is not None else get_registry()

        devs = list(devices) if devices is not None else jax.devices()
        self.mesh = Mesh(np.asarray(devs), ("batch",))
        self.ladder = shard_ladder(buckets, len(devs))
        self._data_sharding = NamedSharding(self.mesh, P("batch"))
        replicated = NamedSharding(self.mesh, P())

        if head == "features":
            from ..models import ViTFeatureExtractor
            cfg = getattr(model, "config", None)
            if cfg is None:
                raise ValueError(
                    "head='features' needs a ViT model (a .config with "
                    "pool/embedding_dim); got "
                    f"{type(model).__name__}")
            backbone = ViTFeatureExtractor(cfg)
            pool = cfg.pool
            apply_params = params["backbone"]

            def fn(p, x):
                tokens = backbone.apply({"params": p}, x)
                pooled = tokens[:, 0] if pool == "cls" else \
                    tokens.mean(axis=1)
                return pooled.astype(jnp.float32)
        elif head == "logits":
            apply_params = params

            # The probs program with the final softmax dropped: the
            # float32 cast happens BEFORE softmax in the probs fn, so
            # these rows are bit-identical to the tensor the probs
            # head softmaxes (test-asserted) — one teacher dump serves
            # both distillation (logits) and audit (probs) consumers.
            def fn(p, x):
                return model.apply({"params": p}, x).astype(jnp.float32)
        else:
            apply_params = params

            # The exact predictions._jitted_forward expression — offline
            # rows stay bit-identical to predict_image (test-asserted).
            def fn(p, x):
                return jax.nn.softmax(
                    model.apply({"params": p}, x).astype(jnp.float32),
                    axis=-1)

        out = jax.eval_shape(
            fn,
            jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                         apply_params),
            jax.ShapeDtypeStruct(
                (1, self.image_size, self.image_size, 3), np.float32))
        self.out_dim = int(out.shape[-1])
        # Full per-row shape (batch axis dropped). Vector heads keep
        # rank-1 rows, so existing sinks/manifests are unchanged; a
        # future tensor head (unpooled tokens) flows through NpySink's
        # N-D path and gets its row_shape pinned in the manifest.
        self.out_shape = tuple(int(d) for d in out.shape[1:])

        # No donation: no output has the input batch's shape, so XLA
        # has nothing to alias it to ("Some donated buffers were not
        # usable" on every rung when it was asked). Traced on the mesh
        # so the Pallas kernels run per shard of the batch.
        from ..ops.partition import traced_on_mesh
        self._fwd = traced_on_mesh(jax.jit(fn), self.mesh,
                                   data_axis="batch")
        # Params placed ONCE, replicated over the mesh — every per-chunk
        # dispatch reuses the same committed buffers.
        self._params = jax.device_put(apply_params, replicated)
        self._jax = jax

    # ----------------------------------------------------------- identity
    def fingerprint(self) -> str:
        """Identity of the compiled-program universe (model config +
        image size — :func:`.engine.model_fingerprint`); the progress
        manifest additionally pins head/ladder/batch."""
        return model_fingerprint(self.model, self.image_size)

    # ----------------------------------------------------------- dispatch
    def put(self, padded: np.ndarray):
        """``device_put`` one padded chunk with the batch-axis sharding
        (async; rows land round-robin across every mesh device)."""
        return self._jax.device_put(padded, self._data_sharding)

    def dispatch(self, padded: np.ndarray):
        """Async: transfer one padded chunk and issue its forward;
        returns the (not yet materialized) device output."""
        return self._fwd(self._params, self.put(padded))

    # ---------------------------------------------------------------- run
    def run(self, dataset, out_dir: str | Path, *,
            batch_size: Optional[int] = None,
            resume: bool = True,
            limit: Optional[int] = None,
            num_workers: int = 1,
            worker_type: str = "thread",
            readahead: int = 2,
            evict_behind: bool = True,
            checkpoint_every_records: Optional[int] = None,
            checkpoint_every_s: float = 30.0,
            preds_jsonl: bool = False,
            log_every_s: float = 30.0,
            throttle_s: float = 0.0) -> dict:
        """Sweep ``dataset`` into ``out_dir`` (see module docstring);
        returns the run summary dict.

        ``readahead``/``evict_behind`` give the sweep the PR 1
        page-cache discipline (sequential scan, O(readahead) resident
        blocks) — the defaults are the sane always-on values for an
        unshuffled full-dataset pass. ``throttle_s`` sleeps after each
        loader batch (kill/resume tests pace the run with it; keep 0
        in production)."""
        from ..data.image_folder import DataLoader

        out = Path(out_dir)
        out.mkdir(parents=True, exist_ok=True)
        n_total = len(dataset)
        if limit is not None:
            n_total = min(int(limit), n_total)
        if n_total <= 0:
            raise ValueError(f"nothing to do: dataset has {n_total} records")
        bs = int(batch_size) if batch_size else self.ladder[-1]
        fp = self.fingerprint()
        ladder = [int(b) for b in self.ladder]

        manifest = load_progress(out) if resume else None
        start = 0
        if manifest is not None:
            start = validate_progress(
                manifest, fingerprint=fp, head=self.head,
                total_records=n_total, out_dim=self.out_dim,
                batch_size=bs, ladder=ladder, row_shape=self.out_shape)
        base = {"fingerprint": fp, "head": self.head,
                "total_records": n_total, "out_dim": self.out_dim,
                "batch_size": bs, "ladder": ladder, "sink": SINK_NAME}
        if len(self.out_shape) > 1:
            # Tensor rows only: out_dim alone (the trailing axis) no
            # longer identifies the row — pin the full shape so a
            # [T, D] sink can never resume (or be consumed) as a [D]
            # one. Vector heads omit the key, keeping their manifests
            # byte-compatible with pre-tensor-row jobs.
            base["row_shape"] = [int(d) for d in self.out_shape]

        sink = NpySink(out / SINK_NAME, rows=n_total, dim=self.out_shape,
                       resume=manifest is not None)
        preds = None
        if preds_jsonl and self.head == "probs":
            if manifest is not None and \
                    manifest.get("preds_bytes") is None and start > 0:
                raise ValueError(
                    "resuming with --preds-jsonl but the manifest has no "
                    "preds offset (the original run didn't write the "
                    "mirror) — the file would start mid-dataset; rerun "
                    "with --fresh")
            preds = PredsJsonl(
                out / PREDS_NAME, class_names=self.class_names,
                resume_bytes=(manifest or {}).get("preds_bytes")
                if manifest is not None else None)
        if manifest is None:
            # Claim the directory up front: a concurrent/later resume
            # validates against THIS job's identity, and a kill before
            # the first checkpoint restarts cleanly from record 0.
            write_progress(out, {**base, "records_done": 0,
                                 "rows_written": 0,
                                 "preds_bytes": 0 if preds else None})

        if start >= n_total:
            sink.close()
            if preds:
                preds.close()
            return {"records": n_total, "resumed_from": start,
                    "processed": 0, "already_complete": True,
                    "images_per_sec": 0.0, "wall_s": 0.0,
                    "devices": int(self.mesh.devices.size),
                    "head": self.head, "out_dim": self.out_dim,
                    "sink": str(out / SINK_NAME)}

        loader = DataLoader(
            _RecordRange(dataset, start, n_total), bs, shuffle=False,
            num_workers=max(1, int(num_workers)), worker_type=worker_type,
            readahead=max(0, int(readahead)),
            evict_behind=bool(evict_behind))
        ckpt_records = int(checkpoint_every_records or 32 * bs)

        reg = self._registry
        reg.gauge("bi_devices", int(self.mesh.devices.size))
        inflight: deque = deque()   # (device_out, n_real, abs_row)
        stats = {"data_wait_s": 0.0, "drain_s": 0.0, "checkpoints": 0,
                 "drained": start, "t_first_done": None}

        def drain_one() -> None:
            y, n_real, row = inflight.popleft()
            t0 = time.perf_counter()
            # THE drain: the oldest in-flight chunk is fetched to host
            # for the sink; the prefetch window keeps it off the
            # dispatch critical path.
            # vitlint: hot-path-ok(bounded-window drain to the sink)
            rows = np.asarray(y)[:n_real]
            dt = time.perf_counter() - t0
            stats["drain_s"] += dt
            reg.observe("bi_drain_s", dt)
            sink.write(row, rows)
            if preds is not None:
                preds.write(row, rows)
            stats["drained"] += n_real
            if stats["t_first_done"] is None:
                # First completed chunk: everything before this point is
                # compile + pipeline fill; steady rate excludes it.
                stats["t_first_done"] = time.perf_counter()
                stats["first_images"] = stats["drained"]

        def write_checkpoint(done: int) -> None:
            while inflight:
                drain_one()
            sink.flush()
            pb = preds.flush() if preds is not None else None
            payload = {**base, "records_done": done,
                       "rows_written": done, "preds_bytes": pb}
            if done >= n_total:
                # Completion seals the sink: its sha256 lands in the
                # manifest so a consumer (tools/build_index.py) can
                # prove the matrix it memory-maps is the exact bytes
                # this job finished — a torn copy, a partial rsync, or
                # a sink from a different run refuses loudly instead
                # of silently indexing garbage. Sink flushed above, so
                # the digest hashes durable bytes.
                payload["sink_sha256"] = sink_sha256(sink.path)
            write_progress(out, payload)
            stats["checkpoints"] += 1
            reg.count("bi_checkpoints_total")

        t_run0 = time.perf_counter()
        abs_row = start
        done = start
        since_ckpt = 0
        last_ckpt_t = last_log_t = t_run0
        it = iter(loader)
        try:
            while True:
                t0 = time.perf_counter()
                batch = next(it, None)
                wait = time.perf_counter() - t0
                if batch is None:
                    break
                stats["data_wait_s"] += wait
                reg.observe("bi_data_wait_s", wait)
                images = batch["image"]
                pos = 0
                for bucket in plan_buckets(len(images), self.ladder):
                    take = min(bucket, len(images) - pos)
                    padded, _ = pad_rows_to_bucket(
                        images[pos:pos + take], bucket)
                    pos += take
                    # Async: the H2D copy + forward of THIS chunk are
                    # issued while earlier chunks still compute; the
                    # host only blocks on the oldest once the window
                    # exceeds `prefetch`.
                    inflight.append(
                        (self.dispatch(padded), take, abs_row))
                    abs_row += take
                    while len(inflight) > self.prefetch:
                        drain_one()
                done += len(images)
                since_ckpt += len(images)
                reg.count("bi_records_total", len(images))
                reg.count("bi_batches_total")
                now = time.perf_counter()
                elapsed = now - t_run0
                reg.gauge("bi_images_per_sec",
                          round((done - start) / max(elapsed, 1e-9), 2))
                reg.gauge("bi_progress_pct",
                          round(100.0 * done / n_total, 2))
                if since_ckpt >= ckpt_records or \
                        now - last_ckpt_t >= checkpoint_every_s:
                    write_checkpoint(done)
                    since_ckpt = 0
                    last_ckpt_t = time.perf_counter()
                if log_every_s and now - last_log_t >= log_every_s:
                    rate = (done - start) / max(elapsed, 1e-9)
                    eta = (n_total - done) / max(rate, 1e-9)
                    # vitlint: hot-path-ok(rate-limited progress log, default 30s cadence)
                    print(f"[batch_infer] {done}/{n_total} records "
                          f"({100.0 * done / n_total:.1f}%), "
                          f"{rate:.1f} img/s, eta {eta:.0f}s")
                    last_log_t = now
                if throttle_s:
                    # vitlint: hot-path-ok(test pacing knob, 0 in production)
                    time.sleep(throttle_s)
            write_checkpoint(done)
        finally:
            loader.close()
            sink.close()
            if preds is not None:
                preds.close()

        wall = time.perf_counter() - t_run0
        processed = done - start
        steady = None
        t_first = stats["t_first_done"]
        first_images = stats.get("first_images", start)
        if t_first is not None and done > first_images:
            span = time.perf_counter() - t_first
            steady = round((done - first_images) / max(span, 1e-9), 2)
        return {
            "records": n_total,
            "resumed_from": start,
            "processed": processed,
            "wall_s": round(wall, 3),
            "images_per_sec": round(processed / max(wall, 1e-9), 2),
            "steady_images_per_sec": steady,
            "data_wait_s": round(stats["data_wait_s"], 3),
            "drain_s": round(stats["drain_s"], 3),
            "checkpoints": stats["checkpoints"],
            "devices": int(self.mesh.devices.size),
            "ladder": ladder,
            "batch_size": bs,
            "head": self.head,
            "out_dim": self.out_dim,
            "sink": str(out / SINK_NAME),
            "preds": str(out / PREDS_NAME) if preds_jsonl
            and self.head == "probs" else None,
        }


def sink_sha256(path: str | Path) -> str:
    """Streaming sha256 of a sink file — the kill+resume evidence
    hash (byte-identity proven by digest, not a 2xN-GB comparison)."""
    import hashlib

    h = hashlib.sha256()
    # vitlint: hot-path-ok(completion-time digest: reached from run() only once, at the final manifest after the last row drained)
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()
