"""Per-step span telemetry for the training hot loop.

The engine loop was a black box: one wall-clock number per epoch, with
data-wait, device compute, checkpoint saves, and eval all smeared
together. :class:`StepTelemetry` splits every step into spans the way
production-scale trainers attribute goodput (MegaScale, arXiv:
2402.15627 — per-phase attribution is where the MFU recovery lives):

* **data-wait** — seconds blocked on the batch iterator (`next()`),
* **step-exec** — dispatch + device seconds. Async dispatch makes the
  per-step host wall a lie, so every ``block_every``-th step the engine
  barriers on the step's metrics (``block_until_ready``) before
  stamping the clock — the sampled barrier re-synchronizes the
  host-side timeline at amortized-negligible cost. Between barriers
  the unbarriered walls measure dispatch, and the barriered step
  absorbs the window's backlog, so the step-wall/step-exec
  **histograms are fed barrier-window amortized values** (window wall
  / steps in window) instead of the raw mix — honest per-step numbers
  on every backend. On a synchronous backend a one-step window (the
  barriered step flushes alone) keeps true stragglers like the
  first-step compile at full magnitude; data-wait is host-side and
  always recorded raw,
* **checkpoint** / **eval** — the epoch's non-step spans.

Everything publishes through the shared
:class:`.registry.TelemetryRegistry` (histograms + counters + gauges +
the postmortem event ring) and — sampled, every ``sample_every`` steps
— as JSONL rows through :class:`..metrics.MetricsLogger`, so telemetry
streams are machine-readable with the exact same row grammar as train
metrics. ``tools/trace_report.py`` turns the stream into the
phase-breakdown report; ``epoch_end`` emits the per-epoch summary row
(step p50/p95/p99, data-wait fraction, goodput %).

Live gauges: ``tel_images_per_sec`` over the sampling window and
``tel_mfu`` (analytic model FLOPs vs the chip's peak — the same
arithmetic as bench.py's self-audit, via :mod:`.flops`).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from .flops import analytic_mfu
from .registry import TelemetryRegistry, get_registry

# Every key a telemetry JSONL row may carry beyond the declared
# INSTRUMENTS — the collision test (tests/test_compile_cache.py) holds
# INSTRUMENTS + ROW_KEYS disjoint from the pre-existing MetricsLogger
# vocabulary, minus the deliberately shared row spine (time/step/epoch).
ROW_KEYS = (
    "event", "tel_block_sampled", "tel_step_amortized_s", "tel_steps",
    "tel_images", "tel_epoch_wall_s", "tel_step_p50_s", "tel_step_p95_s",
    "tel_step_p99_s", "tel_data_wait_s_sum", "tel_step_exec_s_sum",
    "tel_ckpt_s_sum", "tel_eval_s_sum",
    # event="span" rows (r10): checkpoint/eval spans now also ride the
    # JSONL so the chrome-trace exporter can place them on the
    # timeline (they were registry-ring-only before).
    "span", "seconds",
)


class StepTelemetry:
    """Publish per-step spans to the registry + sampled JSONL rows.

    Args:
      jsonl_path: telemetry event stream destination (None = registry
        and watchdog only — the watchdog-without-tracing configuration).
      registry: defaults to the process-global registry.
      sample_every: emit one ``event="step"`` JSONL row every N steps
        (the first step of each window), so long runs trace at bounded
        volume. 1 = every step.
      block_every: how often the engine should barrier for honest
        timing (defaults to ``sample_every``); the engine asks via
        :meth:`should_block`.
      flops_per_image: analytic train-step FLOPs (``telemetry.flops``);
        with ``peak_tflops`` enables the ``tel_mfu`` gauge. None = gauge
        omitted (TinyVGG).
      peak_tflops: one chip's peak (``flops.peak_bf16_tflops`` of the
        mesh's ``device_kind``); None — a kind with no published peak,
        the CPU included — omits the gauge too.
      n_chips: MFU/per-chip denominator: the size of the mesh the step
        runs on (not the devices the process can see).
      watchdog: optional :class:`.watchdog.Watchdog`; every recorded
        step and span beats it (progress of ANY kind resets the stall
        deadline — a long eval pass is not a hang).
      profiler: optional :class:`.profiling.ProfileController`; the
        engine's pre-step hook (:meth:`step_begin`) opens capture
        windows through it, and each recorded step feeds its window
        close + anomaly baseline.
      sample_memory: publish device-memory watermark gauges
        (:func:`.profiling.sample_device_memory`) on the honesty-
        barrier cadence — the barriered step is the only moment the
        host-side live-array view is settled. Default on; each sample
        is fenced and amortized over ``block_every`` steps.
    """

    def __init__(self, jsonl_path=None, *,
                 registry: Optional[TelemetryRegistry] = None,
                 sample_every: int = 32,
                 block_every: Optional[int] = None,
                 flops_per_image: Optional[float] = None,
                 peak_tflops: Optional[float] = None,
                 n_chips: int = 1,
                 watchdog=None,
                 profiler=None,
                 sample_memory: bool = True):
        self.registry = registry if registry is not None else get_registry()
        self.sample_every = max(1, int(sample_every))
        self.block_every = max(1, int(block_every if block_every is not None
                                      else self.sample_every))
        self.flops_per_image = flops_per_image
        self.peak_tflops = peak_tflops
        self.watchdog = watchdog
        self.profiler = profiler
        self.sample_memory = bool(sample_memory)
        self._logger = None
        if jsonl_path is not None:
            from ..metrics import MetricsLogger
            self._logger = MetricsLogger(jsonl_path)
        self.n_chips = max(1, int(n_chips))
        self._total_steps = 0
        # Live-throughput window: images/time since the last sampled row.
        self._win_t0 = time.perf_counter()
        self._win_images = 0
        # Walls buffered since the last honesty barrier (flushed
        # window-amortized into the histograms — module docstring).
        self._blk_wall: list = []
        self._blk_exec: list = []
        self._last_amortized: Optional[float] = None
        self._epoch_reset()

    # ------------------------------------------------------------ engine
    def should_block(self) -> bool:
        """True when the UPCOMING step should barrier on its metrics
        before the engine stamps its clock (honest sampled timing).

        Aligned with the emit cadence: the upcoming step is number
        ``_total_steps + 1``, and a row is emitted for steps 1, N+1,
        2N+1, ... — so with ``block_every == sample_every`` (the
        default) every SAMPLED row carries a barrier-honest timing
        (review r9: the two cadences were off by one and sampled rows
        never recorded a barriered step)."""
        return self._total_steps % self.block_every == 0

    def step_begin(self, step: Optional[int] = None) -> None:
        """Pre-step hook (the engine calls it just before dispatching
        the step): opens a profiler capture window when one is armed
        for this step — the capture must start BEFORE dispatch or the
        window misses the step's XLA ops. A None-check when no
        profiler is wired."""
        if self.profiler is not None:
            self.profiler.maybe_start(
                step if step is not None else self._total_steps + 1)

    def first_step(self, train_step, state, batch) -> None:
        """Once, after the first applied step: tell the profiler how to
        get the step program's optimized HLO (from shapes and layouts
        alone — no array is kept), which is where a capture's ops get
        their module paths (:mod:`.device_trace`)."""
        if self.profiler is None or not hasattr(train_step, "lower"):
            return
        import jax

        try:
            avals = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype,
                    sharding=getattr(a, "sharding", None)),
                (state, batch))
        except (AttributeError, TypeError):
            return      # a leaf that is no array: captures go unnamed
        self.profiler.set_program(
            lambda: train_step.lower(*avals).compile().as_text())

    def step(self, *, data_wait_s: float, exec_s: float, images: int,
             step: Optional[int] = None, epoch: Optional[int] = None,
             blocked: bool = False,
             counters: Optional[dict] = None) -> None:
        """Record one completed train step's spans. ``counters``: the
        step's own counters as host floats (a token model's ``moe_*``
        metrics, which the loop fetches on barriered steps only):
        published as ``tel_<name>`` gauges — ``moe_dropped_pairs`` as the
        counter ``tel_moe_dropped_pairs_total`` — and on the step's
        row."""
        reg = self.registry
        if counters:
            for name, value in counters.items():
                if name == "moe_dropped_pairs":
                    reg.count("tel_moe_dropped_pairs_total", int(value))
                else:
                    reg.gauge(f"tel_{name}", round(float(value), 4))
        if self.profiler is not None and self.profiler.active:
            # The same two intervals, on the host's clock, for the open
            # capture to set beside the device's (before on_step_end,
            # which may close it).
            now = time.perf_counter_ns()
            self.profiler.add_span("step_exec", now, exec_s)
            self.profiler.add_span("data_wait", now - int(exec_s * 1e9),
                                   data_wait_s)
        total = data_wait_s + exec_s
        self._total_steps += 1
        self._ep_steps += 1
        self._ep_images += images
        self._ep_wait += data_wait_s
        self._ep_exec += exec_s
        self._win_images += images
        # Step-wall/step-exec buffer until the next barrier: unbarriered
        # walls are dispatch times under async execution and the
        # barriered step absorbs the backlog, so the histograms get the
        # window-amortized per-step value (see module docstring).
        self._blk_wall.append(total)
        self._blk_exec.append(exec_s)
        if blocked:
            self._flush_block_window()
            if self.sample_memory:
                # Device-memory watermarks ride the honesty-barrier
                # cadence: the barrier just settled the backlog, so the
                # live-array census is a real point-in-time figure, and
                # the cost amortizes over block_every steps.
                from .profiling import sample_device_memory
                sample_device_memory(reg)
        if self.profiler is not None:
            # The anomaly baseline is fed ONLY barrier-amortized walls
            # (unbarriered walls are dispatch times under async — a
            # device slowdown would be invisible in them); unbarriered
            # steps still tick the window-close logic.
            self.profiler.on_step_end(
                step if step is not None else self._total_steps,
                self._last_amortized if blocked else None)
        reg.observe("tel_data_wait_s", data_wait_s)
        reg.count("tel_steps_total")
        reg.count("tel_images_total", images)
        if self.watchdog is not None:
            self.watchdog.beat()
        if (self._total_steps - 1) % self.sample_every == 0:
            now = time.perf_counter()
            dt = max(now - self._win_t0, 1e-9)
            ips = self._win_images / dt
            self._win_t0, self._win_images = now, 0
            reg.gauge("tel_images_per_sec", round(ips, 2))
            row = {"event": "step",
                   "tel_data_wait_s": round(data_wait_s, 6),
                   "tel_step_exec_s": round(exec_s, 6),
                   "tel_step_s": round(total, 6),
                   "tel_images_per_sec": round(ips, 2),
                   "tel_block_sampled": int(bool(blocked))}
            if blocked and self._last_amortized is not None:
                # The raw wall above absorbs the window's async backlog;
                # this is the honest per-step figure (window wall /
                # steps) dashboards should plot.
                row["tel_step_amortized_s"] = round(self._last_amortized, 6)
            if self.flops_per_image and self.peak_tflops:
                mfu = analytic_mfu(ips / self.n_chips,
                                   self.flops_per_image, self.peak_tflops)
                reg.gauge("tel_mfu", round(mfu, 4))
                row["tel_mfu"] = round(mfu, 4)
            if counters:
                row.update({f"tel_{k}": round(float(v), 4)
                            for k, v in counters.items()})
            if step is not None:
                row["step"] = int(step)
            if epoch is not None:
                row["epoch"] = int(epoch)
            reg.event("step", **{k: v for k, v in row.items()
                                 if k != "event"})
            if self._logger is not None:
                self._logger.log(**row)

    def heartbeat(self) -> None:
        """Beat the watchdog without recording anything — for
        fine-grained progress inside long phases (per eval batch), so a
        big test set can't outlive the stall deadline on a healthy
        run."""
        if self.watchdog is not None:
            self.watchdog.beat()

    def span(self, name: str, seconds: float) -> None:
        """Record a non-step span (``"checkpoint"`` or ``"eval"``)."""
        key = {"checkpoint": "tel_ckpt_s", "eval": "tel_eval_s"}.get(name)
        if key is None:
            raise ValueError(f"unknown span {name!r} "
                             "(expected 'checkpoint' or 'eval')")
        if name == "checkpoint":
            self._ep_ckpt += seconds
        else:
            self._ep_eval += seconds
        if self.profiler is not None and self.profiler.active:
            self.profiler.add_span(name, time.perf_counter_ns(), seconds)
        self.registry.observe(key, seconds)
        self.registry.event("span", span=name,
                            seconds=round(seconds, 6))
        if self._logger is not None:
            # Spans ride the JSONL too (r10): the chrome-trace exporter
            # places checkpoint/eval slices on the same timeline as the
            # step lanes — ring-only spans died with the process.
            self._logger.log(event="span", span=name,
                             seconds=round(seconds, 6))
        if self.watchdog is not None:
            self.watchdog.beat()

    def epoch_end(self, *, epoch: Optional[int] = None,
                  step: Optional[int] = None) -> Dict[str, Any]:
        """Summarize the finished epoch, emit its JSONL row, reset.

        Goodput is step-exec's share of the epoch wall (what MegaScale
        calls effective-compute share); data-wait fraction is the input
        pipeline's share — together they tell you whether to buy
        loader workers or kernel time (SCALING.md reads them).
        """
        self._flush_block_window()
        wall = max(time.perf_counter() - self._ep_t0, 1e-9)
        if self._ep_step_wall:
            p50, p95, p99 = np.percentile(
                np.asarray(self._ep_step_wall), [50.0, 95.0, 99.0])
        else:
            p50 = p95 = p99 = None
        goodput = 100.0 * self._ep_exec / wall
        wait_frac = self._ep_wait / wall
        ips = self._ep_images / wall
        summary: Dict[str, Any] = {
            "event": "epoch_summary",
            "tel_steps": self._ep_steps,
            "tel_images": self._ep_images,
            "tel_epoch_wall_s": round(wall, 3),
            "tel_step_p50_s": _r6(p50),
            "tel_step_p95_s": _r6(p95),
            "tel_step_p99_s": _r6(p99),
            "tel_data_wait_frac": round(wait_frac, 4),
            "tel_goodput_pct": round(goodput, 2),
            "tel_images_per_sec": round(ips, 2),
            "tel_data_wait_s_sum": round(self._ep_wait, 3),
            "tel_step_exec_s_sum": round(self._ep_exec, 3),
            "tel_ckpt_s_sum": round(self._ep_ckpt, 3),
            "tel_eval_s_sum": round(self._ep_eval, 3),
        }
        if self.flops_per_image and self.peak_tflops:
            summary["tel_mfu"] = round(
                analytic_mfu(ips / self.n_chips, self.flops_per_image,
                             self.peak_tflops), 4)
        if epoch is not None:
            summary["epoch"] = int(epoch)
        if step is not None:
            summary["step"] = int(step)
        self.registry.gauge("tel_goodput_pct", summary["tel_goodput_pct"])
        self.registry.gauge("tel_data_wait_frac",
                            summary["tel_data_wait_frac"])
        self.registry.event("epoch_summary",
                            **{k: v for k, v in summary.items()
                               if k != "event"})
        if self._logger is not None:
            self._logger.log(**summary)
        if self.watchdog is not None:
            self.watchdog.beat()
        self._epoch_reset()
        return summary

    # ------------------------------------------------------------- misc
    def _flush_block_window(self) -> None:
        """Fold the buffered walls since the last barrier into the
        histograms/percentile list as the window-amortized per-step
        value, one observation per step so weighting stays per-step
        (module docstring: the async-dispatch honesty rule)."""
        n = len(self._blk_wall)
        if not n:
            return
        aw = sum(self._blk_wall) / n
        ae = sum(self._blk_exec) / n
        for _ in range(n):
            self.registry.observe("tel_step_s", aw)
            self.registry.observe("tel_step_exec_s", ae)
            self._ep_step_wall.append(aw)
        self._last_amortized = aw
        self._blk_wall.clear()
        self._blk_exec.clear()

    def _epoch_reset(self) -> None:
        self._ep_t0 = time.perf_counter()
        self._ep_steps = 0
        self._ep_images = 0
        self._ep_wait = 0.0
        self._ep_exec = 0.0
        self._ep_ckpt = 0.0
        self._ep_eval = 0.0
        self._ep_step_wall = []

    def close(self) -> None:
        if self._logger is not None:
            self._logger.close()
            self._logger = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def _r6(v):
    return None if v is None else round(float(v), 6)
