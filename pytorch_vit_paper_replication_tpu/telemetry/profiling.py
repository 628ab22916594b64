"""On-demand XLA profiler capture windows + device-memory watermarks.

PR 5's spans say *which phase* a slow step spent its time in; they
cannot say *which compiled op* or *how many HBM bytes*. This module
drills below the span level, without the cost of always-on tracing:

* :class:`ProfileController` — bounded ``jax.profiler`` capture
  windows over the training loop, armed three ways:

  - **explicitly**: ``train.py --profile-steps A:B`` captures global
    steps A..B (inclusive) into the run's trace dir,
  - **by signal**: ``SIGUSR2`` to a running trainer captures the next
    ``signal_steps`` steps — attach-a-profiler-without-restarting,
    the remote-TPU-host workflow,
  - **automatically**: a rolling step-time baseline; when the current
    window's p50 regresses more than ``auto_pct`` % over the anchored
    baseline, the controller arms a capture of the next window — the
    trace of the regression IS the forensic artifact, captured while
    the anomaly is still happening.

  Every capture publishes through the registry
  (``profiler_captures_total``, ``profiler_capture_active``,
  ``profiler_last_capture_path``) and the event ring, so the watchdog
  postmortem names the most recent capture — a stall bundle points at
  the trace that explains it. All ``jax.profiler`` calls are fenced:
  a profiling failure degrades to a counted error, never a dead run.

  A capture is of the **device alone** (host and Python tracers off):
  on the v5e the host tracer records every chunk of the runtime's
  host-side layout change of an input batch, the feed then outlasts
  the step, and the chip reads 27-41% idle where it is 0.04% idle
  (PERF.md, PR 22) — a capture that misreports the run it captures.
  The host's side comes from the program's own spans instead: while a
  capture is active :class:`..spans.StepTelemetry` hands over the
  intervals it already measures (``data_wait``, ``step_exec``,
  ``checkpoint``, ``eval``), and an anchor — a tiny named program run
  right after the capture starts, whose end both clocks see — sets
  them on the trace's clock. When the window closes the capture is
  read back (:mod:`.device_trace`): ``<capture>/device_time.json``,
  one ``profiler_device_time`` event, the gauges
  ``profiler_last_step_device_ms`` / ``profiler_last_idle_pct`` and,
  for a verbose trainer, the layer x phase table on standard output.

* :func:`sample_device_memory` — peak/live device-byte watermarks:
  live bytes via ``jax.live_arrays()`` (every backend) plus per-device
  ``memory_stats()`` (``bytes_in_use`` / ``peak_bytes_in_use`` /
  ``bytes_limit`` where the backend reports them, i.e. TPU/GPU).
  :class:`..spans.StepTelemetry` samples it on the existing
  honesty-barrier cadence — the barriered step is the only moment the
  host-side view of live arrays is settled — so OOM-adjacent drift is
  visible in the gauges long before the allocator kills the run.

Both stay inside the telemetry overhead budget: the per-step hooks are
a None-check when disarmed, the anomaly check runs every
``check_every`` steps, and watermark sampling rides the (already
amortized) barrier cadence. ``tools/telemetry_overhead.py`` measures
the whole instrumented path — watermarks and shipper ON, capture
windows disarmed — under the same <2% gate.
"""

from __future__ import annotations

import gzip
import json
import signal
import statistics
import time
from collections import deque
from pathlib import Path
from typing import Callable, Optional, Tuple

from .registry import TelemetryRegistry, get_registry


def sample_device_memory(registry: Optional[TelemetryRegistry] = None
                         ) -> dict:
    """Publish device-memory watermark gauges; returns what it saw.

    ``mem_live_bytes``/``mem_live_arrays`` come from
    ``jax.live_arrays()`` (works on every backend, CPU included);
    ``mem_devN_*`` gauges come from ``Device.memory_stats()`` where the
    backend implements it. Peaks (``*_peak``) are tracked monotonically
    via :meth:`..registry.TelemetryRegistry.gauge_max` — the watermark
    survives the sample that follows a big free. Every probe is fenced:
    telemetry must never take the step down.
    """
    reg = registry if registry is not None else get_registry()
    seen: dict = {}
    try:
        import jax
        arrs = jax.live_arrays()
        live = int(sum(getattr(a, "nbytes", 0) or 0 for a in arrs))
        seen["mem_live_bytes"] = live
        seen["mem_live_arrays"] = len(arrs)
        reg.gauge("mem_live_bytes", live)
        reg.gauge("mem_live_arrays", len(arrs))
        reg.gauge_max("mem_live_bytes_peak", live)
        for i, d in enumerate(jax.local_devices()):
            try:
                ms = d.memory_stats()
            except Exception:  # noqa: BLE001 — CPU devices: no stats
                ms = None
            if not ms:
                continue
            if "bytes_in_use" in ms:
                reg.gauge(f"mem_dev{i}_bytes_in_use", ms["bytes_in_use"])
                seen[f"mem_dev{i}_bytes_in_use"] = ms["bytes_in_use"]
            if "peak_bytes_in_use" in ms:
                reg.gauge_max(f"mem_dev{i}_bytes_peak",
                              ms["peak_bytes_in_use"])
            if "bytes_limit" in ms:
                reg.gauge(f"mem_dev{i}_bytes_limit", ms["bytes_limit"])
    except Exception:  # noqa: BLE001 — jax absent/uninitialized
        pass
    return seen


def parse_profile_steps(spec: str) -> Tuple[int, int]:
    """``"A:B"`` -> (A, B), global train steps, inclusive window."""
    try:
        a_s, b_s = spec.split(":")
        a, b = int(a_s), int(b_s)
    except ValueError:
        raise ValueError(
            f"--profile-steps expects START:END (e.g. 100:110), got "
            f"{spec!r}") from None
    if a < 1 or b < a:
        raise ValueError(
            f"--profile-steps window {a}:{b} must satisfy 1 <= START <= END")
    return a, b


class ProfileController:
    """Arm/disarm ``jax.profiler`` capture windows over the step loop.

    The engine's pre-step hook calls :meth:`maybe_start` (capture must
    open BEFORE dispatch so the window holds the step's XLA ops) and
    :class:`..spans.StepTelemetry` calls :meth:`on_step_end` after each
    recorded step (closes the window, feeds the anomaly baseline).

    Args:
      trace_dir: capture destination; each window writes its own
        ``capture_NNN_stepA`` subdirectory (TensorBoard/xprof layout).
      steps: optional explicit (start, end) global-step window
        (``--profile-steps``).
      auto: arm a capture automatically when the rolling step-time p50
        regresses more than ``auto_pct`` % over the anchored baseline.
      auto_pct / auto_window: anomaly threshold and rolling-window
        length, counted in fed samples — one barrier-amortized wall
        per honesty barrier (``StepTelemetry.block_every`` steps
        each); the baseline anchors to the first full window after
        ``warmup_steps`` samples and re-anchors after every fired
        capture so one long regression can't fire forever.
      signal_steps: capture length for SIGUSR2- and anomaly-armed
        windows.
      max_captures: hard bound on windows per process — profiling disk
        is bounded no matter how flappy the anomaly signal gets.
      check_every: anomaly-check cadence in steps (keeps the median
        computation off the per-step path).
      verbose: print the device-time table when a capture closes.
    """

    ANCHOR = "profiler_clock_anchor"

    def __init__(self, trace_dir: str | Path, *,
                 registry: Optional[TelemetryRegistry] = None,
                 steps: Optional[Tuple[int, int]] = None,
                 auto: bool = False,
                 auto_pct: float = 25.0,
                 auto_window: int = 64,
                 warmup_steps: int = 3,
                 signal_steps: int = 16,
                 max_captures: int = 8,
                 check_every: int = 16,
                 verbose: bool = False):
        self.trace_dir = Path(trace_dir)
        self.registry = registry if registry is not None else get_registry()
        self.auto = bool(auto)
        self.auto_pct = float(auto_pct)
        self.auto_window = max(4, int(auto_window))
        self.warmup_steps = max(0, int(warmup_steps))
        self.signal_steps = max(1, int(signal_steps))
        self.max_captures = max(1, int(max_captures))
        self.check_every = max(1, int(check_every))
        # One pending window at a time: (start_step, end_step, reason).
        self._window: Optional[Tuple[int, int, str]] = steps and (
            int(steps[0]), int(steps[1]), "flag")
        self._active: Optional[Tuple[int, Path]] = None  # (end, dir)
        self._captures = 0
        self._signal_request = False
        self._sigusr2_installed = False
        self._prev_sigusr2 = None
        self._recent: deque = deque(maxlen=self.auto_window)
        self._baseline_p50: Optional[float] = None
        self._steps_seen = 0
        self.last_capture_path: Optional[str] = None
        self.verbose = bool(verbose)
        # Host spans of the active capture, (name, start_ns, end_ns) on
        # time.perf_counter_ns, and the anchor's end on that clock.
        self._spans: list = []
        self._anchor_host_ns: Optional[int] = None
        self._anchor = self._make_anchor()
        # () -> optimized HLO text of the step program, and what
        # device_trace.parse_scopes made of it (once per process).
        self._program_text: Optional[Callable[[], str]] = None
        self._program: Optional[dict] = None
        self.last_device_time: Optional[dict] = None
        self.registry.gauge("profiler_capture_active", 0)

    def _make_anchor(self):
        """The clock anchor, compiled now and not inside a window."""
        try:
            import jax
            import jax.numpy as jnp

            def anchor(x):
                return x + 1
            anchor.__name__ = self.ANCHOR
            fn, x = jax.jit(anchor), jnp.zeros((), jnp.int32)
            jax.block_until_ready(fn(x))
            return lambda: jax.block_until_ready(fn(x))
        except Exception as e:  # noqa: BLE001 — jax absent/uninitialized
            self.registry.event("profiler_error", error=f"anchor: {e}")
            return None

    @property
    def active(self) -> bool:
        """True while a capture window is open."""
        return self._active is not None

    def add_span(self, name: str, end_ns: int, seconds: float) -> None:
        """A host interval that ended at ``end_ns`` (perf_counter_ns)
        and lasted ``seconds``; kept only while a capture is active."""
        if self._active is not None:
            self._spans.append((name, end_ns - int(seconds * 1e9), end_ns))

    def set_program(self, hlo_text: Callable[[], str]) -> None:
        """How to get the step program's optimized HLO text, from which
        a capture's ops get their module paths. Called (one cache hit)
        when the first capture closes, never on the step path."""
        self._program_text = hlo_text

    # ------------------------------------------------------------ arming
    def arm(self, start_step: int, n_steps: Optional[int] = None,
            reason: str = "manual") -> bool:
        """Request a capture of ``n_steps`` starting at ``start_step``;
        False when refused (already active/armed, or budget spent).
        Refusals are counted and ring-evented — an operator whose
        SIGUSR2 lost to a pending ``--profile-steps`` window (or to a
        spent ``max_captures`` budget) must see WHY no trace appears,
        not wait forever."""
        if self._active is not None or self._window is not None:
            self._refuse(reason, "capture already active or armed")
            return False
        if self._captures >= self.max_captures:
            self._refuse(reason,
                         f"max_captures={self.max_captures} spent")
            return False
        n = self.signal_steps if n_steps is None else max(1, int(n_steps))
        self._window = (int(start_step), int(start_step) + n - 1, reason)
        self.registry.event("profiler_armed", start=self._window[0],
                            end=self._window[1], reason=reason)
        return True

    def _refuse(self, reason: str, why: str) -> None:
        self.registry.count("profiler_arms_refused_total")
        self.registry.event("profiler_arm_refused", reason=reason,
                            why=why)

    def install_sigusr2(self) -> None:
        """SIGUSR2 -> capture the next ``signal_steps`` steps. Main
        thread only (CPython rule); the handler just sets a flag — the
        step loop does the actual arming, so a signal landing mid-jit
        can't re-enter the profiler."""
        self._prev_sigusr2 = signal.getsignal(signal.SIGUSR2)
        self._sigusr2_handler = self._on_sigusr2
        signal.signal(signal.SIGUSR2, self._sigusr2_handler)
        self._sigusr2_installed = True

    def uninstall_sigusr2(self) -> None:
        if not self._sigusr2_installed:
            return
        try:
            if signal.getsignal(signal.SIGUSR2) == self._sigusr2_handler:
                signal.signal(signal.SIGUSR2, self._prev_sigusr2)
        except ValueError:  # not the main thread
            return
        self._sigusr2_installed = False

    def _on_sigusr2(self, signum, frame) -> None:
        self._signal_request = True

    # --------------------------------------------------------- step hooks
    def maybe_start(self, step: int) -> bool:
        """Pre-step hook: open the capture window when ``step`` enters
        an armed one. Returns True while a capture is active."""
        if self._signal_request:
            self._signal_request = False
            self.arm(step, self.signal_steps, reason="sigusr2")
        if self._active is not None:
            return True
        if self._window is None or step < self._window[0]:
            return False
        start, end, reason = self._window
        self._window = None
        if step > end:  # the window was missed entirely (resume skipped
            return False  # past it); drop it rather than capture garbage
        path = (self.trace_dir
                / f"capture_{self._captures:03d}_step{step}_{reason}")
        try:
            import jax
            path.mkdir(parents=True, exist_ok=True)
            options = jax.profiler.ProfileOptions()
            options.host_tracer_level = 0      # the device alone: see
            options.python_tracer_level = 0    # the module docstring
            jax.profiler.start_trace(str(path), profiler_options=options)
        except Exception as e:  # noqa: BLE001 — profiling must never
            # take the training step down with it.
            self.registry.count("profiler_capture_errors_total")
            self.registry.event("profiler_error", error=f"{e}")
            return False
        self._active = (end, path)
        self._spans, self._anchor_host_ns = [], None
        try:
            if self._anchor is not None:
                self._anchor()
                self._anchor_host_ns = time.perf_counter_ns()
        except Exception as e:  # noqa: BLE001 — spans go unplaced
            self.registry.event("profiler_error", error=f"anchor: {e}")
        self._captures += 1
        self.registry.count("profiler_captures_total")
        self.registry.gauge("profiler_capture_active", 1)
        self.registry.event("profiler_capture_start", step=step,
                            end=end, reason=reason, path=str(path))
        return True

    def on_step_end(self, step: int,
                    step_s: Optional[float] = None) -> None:
        """Post-step hook: close an elapsed window; when ``step_s`` is
        given (the caller passes barrier-amortized walls only — raw
        walls under async dispatch are dispatch times and would hide a
        device slowdown), feed the anomaly baseline."""
        if self._active is not None and step >= self._active[0]:
            self._stop(step)
        # No anomaly work while a capture is active or a window is
        # already pending (re-arming would only rack up refusals).
        if (not self.auto or self._active is not None
                or self._window is not None or step_s is None):
            return
        self._steps_seen += 1
        if self._steps_seen <= self.warmup_steps:
            return  # compile steps would poison the baseline
        self._recent.append(float(step_s))
        if (len(self._recent) < self.auto_window
                or self._steps_seen % self.check_every):
            return
        p50 = statistics.median(self._recent)
        if self._baseline_p50 is None:
            self._baseline_p50 = p50
            return
        if p50 > self._baseline_p50 * (1.0 + self.auto_pct / 100.0):
            armed = self.arm(step + 1, self.signal_steps, reason="anomaly")
            if armed:
                self.registry.event(
                    "profiler_anomaly", step=step,
                    p50_s=round(p50, 6),
                    baseline_p50_s=round(self._baseline_p50, 6),
                    regression_pct=round(
                        100.0 * (p50 / self._baseline_p50 - 1.0), 2))
                # Re-anchor: the regressed regime is the new normal
                # until something changes again — one sustained
                # regression fires one capture, not max_captures.
                self._baseline_p50 = p50

    def _stop(self, step: int) -> None:
        end, path = self._active
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001
            self.registry.count("profiler_capture_errors_total")
            self.registry.event("profiler_error", error=f"{e}")
        self._active = None
        self.last_capture_path = str(path)
        self.registry.gauge("profiler_capture_active", 0)
        self.registry.gauge("profiler_last_capture_path", str(path))
        self.registry.event("profiler_capture_stop", step=step,
                            path=str(path))
        try:
            self._read_back(path)
        except Exception as e:  # noqa: BLE001 — as every profiler call
            self.registry.count("profiler_capture_errors_total")
            self.registry.event("profiler_error", error=f"read back: {e}")

    def _read_back(self, path: Path) -> None:
        """The closed capture as device time by layer and phase."""
        from . import device_trace

        def scopes():
            # One compile of the step (a cache hit), once per process,
            # and only for a capture that has a device plane.
            if self._program is None and self._program_text is not None:
                self._program = device_trace.parse_scopes(
                    self._program_text())
            return (self._program or {}).get("scopes")

        trace = device_trace.load(path, scopes)
        program = dict(self._program or {"module": "", "scopes": {}})
        anchor_ns = device_trace.module_end_ns(trace, f"jit_{self.ANCHOR}")
        program["host_spans"] = [] if None in (
            anchor_ns, self._anchor_host_ns) else device_trace.shift_spans(
            self._spans, self._anchor_host_ns, anchor_ns)
        self._spans = []
        result = device_trace.reduce(
            trace, module_prefix=program["module"] or "jit_train_step",
            host_spans=program["host_spans"])
        with gzip.open(path / device_trace.PROGRAM_FILE, "wt") as f:
            json.dump(program, f, separators=(",", ":"))
        (path / device_trace.TABLE_FILE).write_text(
            json.dumps(result, indent=1))
        self.last_device_time = result
        if "rows" in result:
            self.registry.gauge("profiler_last_step_device_ms",
                                round(result["step_ms"], 4))
            self.registry.gauge("profiler_last_idle_pct",
                                round(result["idle_pct"], 4))
        self.registry.event(
            "profiler_device_time", path=str(path),
            **{k: result[k] for k in ("reason", "steps", "chips", "step_ms",
                                      "busy_ms", "idle_pct", "rows",
                                      "idle_gaps") if k in result})
        if self.verbose:
            print(device_trace.format_table(result), flush=True)

    # ------------------------------------------------------------ cleanup
    def close(self) -> None:
        """Stop any active capture and release the signal handler —
        wired into train.py's observability ExitStack so a run that
        raises mid-capture still finalizes its trace files."""
        if self._active is not None:
            self._stop(-1)
        self.uninstall_sigusr2()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
