"""Structured metrics/observability.

The reference's observability is print statements + tqdm + an in-memory
results dict (SURVEY.md §5 'metrics'). This module upgrades that to:

* JSONL event stream (one object per log call) — machine-readable run
  history,
* TensorBoard scalars (``tensorboardX``) when a ``tb_dir`` is given,
* throughput (images/sec and per-chip), step timing,
* a :class:`Timer` for images/sec accounting that excludes compilation.

Profiler captures are :mod:`.telemetry.profiling`'s (``--profile-steps``).
"""

from __future__ import annotations

import json
import math
import time
from pathlib import Path
from typing import Any, Dict, Optional

import jax


def _json_safe(v: Any) -> Any:
    """Non-finite floats break the JSONL contract: ``json.dumps`` emits
    bare ``NaN``/``Infinity`` (valid Python, INVALID JSON) and strict
    consumers (trace_report, dashboards, jq) choke on the whole line.
    NaN — "no value" — becomes null; infinities keep their sign as
    strings so the information survives round-tripping."""
    if isinstance(v, float) and not math.isfinite(v):
        if math.isnan(v):
            return None
        return "Infinity" if v > 0 else "-Infinity"
    return v


class MetricsLogger:
    """Write metrics to stdout, a JSONL file, and/or TensorBoard.

    TensorBoard scalars are written per ``log(step=..., ...)`` call for
    every numeric metric; view with ``tensorboard --logdir <tb_dir>``.
    Rows without a ``step`` key inherit the last-seen step (snapshot
    emitters like ServeStats carry no step of their own; collapsing
    them all onto global_step=0 made their scalar history a single
    overwritten point).

    Also a context manager: ``with MetricsLogger(...) as logger`` closes
    the JSONL handle and flushes the TensorBoard writer on ANY exit path
    — a run that raises mid-epoch must not lose its buffered scalars.
    """

    def __init__(self, jsonl_path: Optional[str | Path] = None,
                 stdout: bool = False,
                 tb_dir: Optional[str | Path] = None):
        self.jsonl_path = Path(jsonl_path) if jsonl_path else None
        self.stdout = stdout
        self._fh = None
        self._tb = None
        self._last_step = 0
        if self.jsonl_path:
            self.jsonl_path.parent.mkdir(parents=True, exist_ok=True)
            self._fh = open(self.jsonl_path, "a")
        if tb_dir:
            from tensorboardX import SummaryWriter

            self._tb = SummaryWriter(str(tb_dir))

    def log(self, **metrics: Any) -> None:
        record = {"time": time.time()}
        for k, v in metrics.items():
            if hasattr(v, "item"):
                v = v.item()
            record[k] = _json_safe(v)
        if self._fh:
            self._fh.write(json.dumps(record, allow_nan=False) + "\n")
            self._fh.flush()
        if self.stdout:
            print(json.dumps(record, allow_nan=False))
        if self._tb is not None:
            if record.get("step") is not None:
                self._last_step = int(record["step"])
            step = self._last_step
            for k, v in record.items():
                if k in ("time", "step", "epoch"):
                    continue
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    self._tb.add_scalar(k, v, global_step=step)
            self._tb.flush()

    def close(self):
        if self._fh:
            self._fh.close()
            self._fh = None
        if self._tb is not None:
            self._tb.close()
            self._tb = None

    def __enter__(self) -> "MetricsLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class Timer:
    """Wall-clock throughput meter that can exclude warmup/compile steps."""

    def __init__(self):
        self.reset()

    def reset(self):
        self._t0 = None
        self._images = 0

    def start(self):
        self._t0 = time.perf_counter()
        self._images = 0

    def tick(self, batch_size: int):
        self._images += batch_size

    @property
    def elapsed(self) -> float:
        return 0.0 if self._t0 is None else time.perf_counter() - self._t0

    @property
    def images_per_sec(self) -> float:
        dt = self.elapsed
        return self._images / dt if dt > 0 else 0.0

    def images_per_sec_per_chip(self,
                                n_chips: Optional[int] = None) -> float:
        n = n_chips or jax.device_count()
        return self.images_per_sec / max(1, n)


def block_until_ready(tree: Any) -> Any:
    """Barrier for honest step timing (async dispatch otherwise lies)."""
    return jax.block_until_ready(tree)
