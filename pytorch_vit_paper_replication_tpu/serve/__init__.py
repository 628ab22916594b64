"""Online inference engine: dynamic micro-batching over a bucket ladder.

The training side of this repo is component-complete; this package opens
the workload the north star actually names — serving. The pieces:

* :mod:`.bucketing` — the fixed **bucket ladder** (pad every device batch
  up to one of a handful of sizes so the jitted forward compiles once per
  bucket, never once per ragged batch). Shared with
  :func:`..predictions.predict_batch`.
* :mod:`.batching` — :class:`MicroBatcher`: a thread-safe request queue
  that coalesces concurrent ``submit()`` calls into device batches under
  a max-batch-size / max-wait policy, with bounded-queue admission
  control (reject-with-retry-after), per-request deadlines (expired work
  is dropped *before* it occupies a device batch), graceful
  degradation to smaller buckets when deadlines start missing,
  **cross-head coalescing** (every request carries a ``head`` tag;
  classifier + embedding traffic share one device batch split at the
  heads) and **SLO tiers** (``interactive`` caps the batch-fill wait;
  ``batch`` rides until the bucket fills, bounded by its
  anti-starvation window; priority ordering at batch formation).
* :mod:`.engine` — :class:`InferenceEngine`: checkpoint→model→params load
  (honoring ``transform.json`` exactly as ``predict.py`` does), ONE
  **fused multi-head forward** per bucket rung (backbone once →
  ``probs`` bit-identical to ``predict_image``, pooled ``features``
  bit-identical to the offline head, full ``[T, D]`` ``tokens``), AOT
  (``lower().compile()``) warmup of the bucket ladder at startup —
  optionally in the background, overlapping socket accept — driven by a
  **warmup manifest** written next to the checkpoint, with per-rung
  compile timings and persistent-compile-cache hit/miss counters in
  ``::stats`` (see :mod:`..compile_cache`), per-request futures.
* :mod:`.stats` — :class:`ServeStats`: rolling p50/p95/p99 for queue /
  device / total latency, batch-occupancy histogram, rejected/expired
  counters; ``snapshot()`` plus a JSONL emitter consistent with
  :mod:`..metrics`.
* :mod:`.offline` — :class:`OfflineEngine`: the *throughput* half
  (ROADMAP 4b) — sweep a whole packed dataset through the same
  bucketed forward sharded over every local device, double-buffered
  prefetch, an atomic resumable progress
  manifest, and ``.npy``/JSONL sinks ("embed 10⁶ images overnight";
  CLI: ``tools/batch_infer.py``, gate: ``batch_infer_ok``).
* :mod:`.fleet` — the multi-replica serving fleet (ISSUE 10): a
  :class:`ReplicaManager` supervising N engine subprocesses, a
  :class:`FleetRouter` front door (least-loaded + bucket-affinity
  routing, exactly-once re-dispatch on replica death, fleet-level
  ``QueueFullError`` backpressure), and ``rolling_swap`` —
  zero-downtime checkpoint hot-swap with automatic rollback
  (CLI: ``python -m …serve.fleet``; harness: ``tools/fleet_bench.py``,
  gate: ``fleet_serve_ok``).
* ``python -m pytorch_vit_paper_replication_tpu.serve`` — stdin/stdout
  and TCP socket CLI (see ``__main__.py``).

Load harness: ``tools/serve_bench.py`` (closed/open-loop arrival,
offered-load sweep, CPU-runnable); ``bench.py`` publishes its gates.
"""

from .batching import (DEFAULT_HEAD, DEFAULT_TIER, TIERS, DrainingError,
                       MicroBatcher, QueueFullError, RequestExpired,
                       ShutdownError)
from .bucketing import (DEFAULT_BUCKETS, pad_rows_to_bucket, pick_bucket,
                        plan_buckets)
from .engine import (HEADS, InferenceEngine, load_warmup_manifest,
                     validate_warmup_manifest, write_warmup_manifest)
from .offline import (NpySink, OfflineEngine, load_progress,
                      shard_ladder, validate_progress, write_progress)
from .stats import ServeStats

__all__ = [
    "DEFAULT_BUCKETS", "pick_bucket", "plan_buckets", "pad_rows_to_bucket",
    "DEFAULT_HEAD", "DEFAULT_TIER", "HEADS", "TIERS",
    "DrainingError", "MicroBatcher", "QueueFullError", "RequestExpired",
    "ShutdownError",
    "InferenceEngine", "NpySink", "OfflineEngine", "ServeStats",
    "load_progress", "load_warmup_manifest", "shard_ladder",
    "validate_progress", "validate_warmup_manifest",
    "write_progress", "write_warmup_manifest",
]
