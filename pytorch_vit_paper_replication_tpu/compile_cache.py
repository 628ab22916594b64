"""Cold-start subsystem: persistent XLA compilation cache + instrumentation.

Every process start pays the full XLA compile bill — the train step on a
launch (or a preemption restart, where compile time is pure lost work on
top of the checkpoint gap), every bucket rung of the serve ladder, each
``predict_batch``/probe forward. jax ships a persistent compilation
cache (``jax_compilation_cache_dir``) that converts all of those
recompiles into a disk read; this module is the ONE place that owns
wiring it:

* :func:`configure` — the one rule for where the cache lives, called by
  every entry point before its first jit. If ``JAX_COMPILATION_CACHE_DIR``
  is set the cache is exactly there and this code sets no directory at
  all (whoever runs the program — a launcher, a benchmark harness —
  places the cache from outside and finds it again next time). If it is
  unset the cache is ON at ``--compile-cache-dir``, or by default at
  ``<checkout>/.jax_compile_cache``: a fixed path next to the package,
  never the cwd, a temp name, a pid or the time, because the path is
  what the next process must find. jax's own cache key already hashes
  the program, the compile options, the jax/jaxlib/libtpu versions and
  the device kind, so entries need no further salting.
* :data:`STATS` — hit/miss/saved-seconds counters fed by
  ``jax.monitoring`` events, so "did the cache actually work" is
  assertable from instrumentation instead of wall clocks, and surfaced
  through the train run's :class:`..metrics.MetricsLogger` JSONL
  (first-epoch line) and the serve ``::stats`` line protocol.
  Since PR 24 it also keeps what each program's first call cost by
  stage — ``trace`` (Python -> jaxpr), ``lower`` (jaxpr -> MLIR module),
  ``backend`` (compile on a miss; read + deserialise on a hit) and
  ``cache_read`` (the read alone, part of ``backend``) — under the
  program's ``fun_name`` and on the process's clock, so that
  ``time_to_first_step`` comes with its split
  (:meth:`CacheStats.stage_seconds`, ``snapshot()["programs"]``).
  Since PR 36 a function traced inside a program's trace (a jitted
  ``jnp`` helper: thousands in one token-model step) is a ``folded``
  count on that program's row and no event of its own, so what is kept
  is one event a stage for each top-level program and a long run cannot
  push the set-up's rows out; the bound that remains counts what it
  drops (``dropped``, ``compile_stage_events_dropped_total``).
* :func:`seconds_since_process_start` — the denominator for the
  ``time_to_first_step`` / ``time_to_first_batch`` run-log fields
  (honest restart latency includes interpreter + import + backend init,
  not just the compile the caller happens to time): ``CLOCK_BOOTTIME``
  less the process's start ticks, so it counts from the same instant,
  to the tick, as ``benchmark/lib/clock.py``.
* :data:`STARTUP_STAGES` — ``imports``, ``mesh``, ``state``,
  ``first_step``: the four stages of a trainer's start on that clock,
  each closed once a process by the function where its work ends
  (:func:`closes_startup_stage`, ``engine.train``'s first-step barrier)
  and beginning where the one before it ended
  (``snapshot()["startup"]``, gauges ``startup_<stage>_seconds``, the
  trainer's ``[startup]`` line). ``time_to_first_step`` is the end of
  ``first_step``.
* :func:`warn_if_uncached` — one warning per process when a library
  caller reaches inference on a non-CPU backend without having called
  :func:`configure`; silent multi-minute warmups were the failure mode.

``tools/coldstart_bench.py`` measures the end-to-end effect in fresh
subprocesses; ``runs/coldstart_r8/`` carries the committed numbers and
``bench.py`` gates them (``cold_start_ok``).
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import hashlib
import json
import os
import threading
import time
import warnings
from pathlib import Path
from typing import Any, Dict, Optional

# jax's own variable: when set, it alone decides where the cache lives.
ENV_CACHE_DIR = "JAX_COMPILATION_CACHE_DIR"
# Where the cache lives otherwise: next to the package (the checkout
# root for a source tree), whatever the cwd. .gitignore'd.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parent.parent / \
    ".jax_compile_cache"

# jax.monitoring event names the persistent cache emits (jax/_src/
# compiler.py). One *request* per XLA module that consults the cache;
# a *hit* per module deserialized instead of compiled.
_EVENT_REQUESTS = "/jax/compilation_cache/compile_requests_use_cache"
_EVENT_HITS = "/jax/compilation_cache/cache_hits"
_EVENT_SAVED_SECS = "/jax/compilation_cache/compile_time_saved_sec"
# Duration events of a program's first call (jax/_src/dispatch.py,
# pxla.py, compiler.py), event -> (stage, registry counter). The first
# three carry the program's ``fun_name``; the cache read does not, and
# is emitted just before the ``backend`` event of the program it read.
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("trace", "compile_trace_seconds_total"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower", "compile_lower_seconds_total"),
    "/jax/core/compile/backend_compile_duration":
        ("backend", "compile_backend_seconds_total"),
    "/jax/compilation_cache/cache_retrieval_time_sec":
        ("cache_read", "compile_cache_read_seconds_total"),
}
STAGE_NAMES = tuple(stage for stage, _ in _STAGES.values())
# Bounds: a serve process compiles for as long as it lives. What is kept
# is one event a stage for each top-level program (tens in a training
# run); the bound also has to hold the helpers of the one trace that is
# open, which are kept until the event that encloses them arrives (some
# thousands in a token model's step).
MAX_STAGE_EVENTS = 65536
# The stages of a trainer's start, in order, each with its registry
# gauge: who closes each is in the module docstring.
STARTUP_STAGES = {
    "imports": "startup_imports_seconds",
    "mesh": "startup_mesh_seconds",
    "state": "startup_state_seconds",
    "first_step": "startup_first_step_seconds",
}


def _process_clock():
    """``(clock, its reading when this PROCESS started)``.

    Linux: field 22 of /proc/self/stat is the start time in clock ticks
    since boot, which is where ``CLOCK_BOOTTIME`` counts from. Elsewhere
    the monotonic clock from this module's import — a lower bound,
    clearly documented.
    """
    try:
        stat = Path("/proc/self/stat").read_text()
        # comm (field 2) may contain spaces/parens; split after the
        # closing paren. starttime is field 22 → index 19 post-comm.
        ticks = float(stat.rsplit(")", 1)[1].split()[19])
        clock = functools.partial(time.clock_gettime, time.CLOCK_BOOTTIME)
        clock()
        return clock, ticks / os.sysconf("SC_CLK_TCK")
    except Exception:  # noqa: BLE001 — non-Linux / hardened /proc
        return time.monotonic, time.monotonic()


_CLOCK, _PROCESS_START = _process_clock()


def seconds_since_process_start() -> float:
    """Seconds since the interpreter started — the time-to-first-X base."""
    return _CLOCK() - _PROCESS_START


class CacheStats:
    """Thread-safe persistent-cache counters (fed by jax.monitoring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.requests = 0
        self.hits = 0
        self.saved_secs = 0.0
        self.cache_dir: Optional[str] = None
        # Per thread, in order of arrival: (stage, fun_name, seconds,
        # end: seconds since process start, folded: events that ended
        # inside this one's interval and were dropped for it).
        self._events: Dict[int, collections.deque] = \
            collections.defaultdict(collections.deque)
        self._kept_count = 0
        self.dropped = 0
        self._unnamed_read = threading.local()
        # (name, begin_s, end_s, own_s) of the start-up stages closed.
        self._startup: list = []

    @property
    def misses(self) -> int:
        return self.requests - self.hits

    def _on_event(self, event: str, **kw) -> None:
        with self._lock:
            if event == _EVENT_REQUESTS:
                self.requests += 1
            elif event == _EVENT_HITS:
                self.hits += 1
            else:
                return
        # Mirror into the shared telemetry registry (telemetry/): the
        # serve ::metrics Prometheus text and watchdog postmortems see
        # cache behavior without asking this module for a snapshot.
        # jax emits a SEPARATE event per kind (a request event AND, on
        # a hit, a hit event) — count each into its own counter only.
        from .telemetry.registry import get_registry
        get_registry().count(
            "compile_cache_requests_total" if event == _EVENT_REQUESTS
            else "compile_cache_hits_total")

    def _on_duration(self, event: str, duration: float, **kw) -> None:
        if event == _EVENT_SAVED_SECS:
            with self._lock:
                self.saved_secs += float(duration)
            from .telemetry.registry import get_registry
            get_registry().count("compile_cache_saved_seconds_total",
                                 float(duration))
        elif event in _STAGES:
            stage, counter = _STAGES[event]
            self._on_stage(stage, kw.get("fun_name"), float(duration))
            from .telemetry.registry import get_registry
            get_registry().count(counter, float(duration))

    def _on_stage(self, stage: str, fun_name: Optional[str],
                  seconds: float) -> None:
        """Keep one stage of one program's first call. ``trace`` names
        the function bare and ``lower``/``backend`` as ``jit(<name>)``:
        one key for both. A cache read has no name until the
        ``backend`` event that follows it on the same thread.

        jax reports a stage when it ends, so what ran inside it (the
        jitted helpers a trace calls, each with a trace event of its
        own) is already kept when the event arrives: this thread's
        newest events that began after this one did. They are popped
        and become its ``folded`` count, their seconds being part of
        its own. Each event is popped at most once, and two threads'
        events never meet."""
        name = fun_name or "(unnamed)"
        if name.startswith("jit(") and name.endswith(")"):
            name = name[4:-1]
        end = seconds_since_process_start()
        if stage == "cache_read":
            self._unnamed_read.event = (seconds, end)
            return
        read = getattr(self._unnamed_read, "event", None)
        begin, folded, dropped = end - seconds, 0, 0
        with self._lock:
            mine = self._events[threading.get_ident()]
            before = len(mine)
            while mine and mine[-1][3] - mine[-1][2] >= begin:
                folded += 1 + mine.pop()[4]
            if stage == "backend" and read is not None:
                self._unnamed_read.event = None
                mine.append(("cache_read", name, *read, 0))
            mine.append((stage, name, seconds, end, folded))
            self._kept_count += len(mine) - before
            while self._kept_count > MAX_STAGE_EVENTS:
                tid = min((t for t, d in self._events.items() if d),
                          key=lambda t: self._events[t][0][3])
                self._events[tid].popleft()
                if not self._events[tid]:
                    del self._events[tid]
                self._kept_count -= 1
                dropped += 1
            self.dropped += dropped
        if dropped:
            from .telemetry.registry import get_registry
            get_registry().count("compile_stage_events_dropped_total",
                                 dropped)

    def _kept(self, until_s: Optional[float]) -> list:
        with self._lock:
            events = [e for d in self._events.values() for e in d]
        return sorted((e for e in events
                       if until_s is None or e[3] <= until_s),
                      key=lambda e: e[3])

    def stage_seconds(self, until_s: Optional[float] = None
                      ) -> Dict[str, float]:
        """Seconds this process has spent in each stage, over the kept
        events that ended before ``until_s`` (seconds since process
        start; None = all). The union of the events' intervals, not
        their sum: a function traced while another is being traced is
        folded into it, and where two threads trace at once each
        instant still counts once."""
        from .telemetry.device_trace import covered

        spans: Dict[str, list] = {stage: [] for stage in STAGE_NAMES}
        for stage, _, seconds, end, _ in self._kept(until_s):
            spans[stage].append((end - seconds, end))
        return {stage: covered(ivs) for stage, ivs in spans.items()}

    def programs(self, until_s: Optional[float] = None
                 ) -> Dict[str, Dict[str, float]]:
        """Per ``fun_name`` of a top-level program: ``count`` (first
        calls: backend events), the seconds of each stage, over the
        same events as :meth:`stage_seconds` (here plain sums: a
        program's own cost), and ``folded``: the events of what ran
        inside its stages (a trace's jitted helpers), which have no row
        of their own."""
        out: Dict[str, Dict[str, float]] = {}
        for stage, name, seconds, _, folded in self._kept(until_s):
            row = out.setdefault(name, {
                "count": 0, **dict.fromkeys(STAGE_NAMES, 0.0), "folded": 0})
            row[stage] += seconds
            row["count"] += stage == "backend"
            row["folded"] += folded
        return out

    def programs_line(self, until_s: Optional[float] = None,
                      top: int = 6) -> str:
        """:meth:`programs` on one line, costliest first: what
        ``engine._report_first_step`` prints beside the hits/misses and
        the benchmark as its ``[programs]`` line."""
        cost = ("trace", "lower", "backend")
        rows = sorted(self.programs(until_s).items(),
                      key=lambda kv: -sum(kv[1][s] for s in cost))
        shown = [f"{name} x{r['count']} trace {r['trace']:.2f} lower "
                 f"{r['lower']:.2f} backend {r['backend']:.2f} (cache "
                 f"read {r['cache_read']:.2f}; {r['folded']} folded)"
                 for name, r in rows[:top]]
        if rows[top:]:
            shown.append(f"{len(rows) - top} others " + " ".join(
                f"{s} {sum(r[s] for _, r in rows[top:]):.2f}"
                for s in cost))
        line = "; ".join(shown)
        if self.dropped:
            line += f" ({self.dropped} earlier events dropped)"
        return line

    def close_stage(self, name: str, entered_s: float) -> float:
        """Close start-up stage ``name`` now, and return now. It began
        where the last closed stage ended (0 for the first), and
        ``entered_s`` is when the function that closes it was entered:
        ``own_s`` tells the time inside that function from the time
        before it (a caller's: the benchmark's harness). Once a process
        and in the order of :data:`STARTUP_STAGES`: a stage that is
        closed, or comes before one that is, stays as it is."""
        order = list(STARTUP_STAGES)
        end = seconds_since_process_start()
        with self._lock:
            last = self._startup[-1] if self._startup else None
            if last and order.index(name) <= order.index(last[0]):
                return end
            begin = last[2] if last else 0.0
            self._startup.append((name, begin, end, end - entered_s))
        from .telemetry.registry import get_registry
        get_registry().gauge(STARTUP_STAGES[name], round(end - begin, 3))
        return end

    def startup(self) -> Dict[str, Dict[str, float]]:
        """The start-up stages closed so far, in order."""
        with self._lock:
            stages = list(self._startup)
        return {name: {"begin_s": begin, "end_s": end,
                       "seconds": end - begin, "own_s": own}
                for name, begin, end, own in stages}

    def startup_line(self) -> str:
        """:meth:`startup` on one line: the trainer's ``[startup]``."""
        return ", ".join(f"{name} {s['seconds']:.2f} (own {s['own_s']:.2f})"
                         for name, s in self.startup().items())

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            snap = {
                "cache_dir": self.cache_dir,
                "requests": self.requests,
                "hits": self.hits,
                "misses": self.requests - self.hits,
                "compile_time_saved_s": round(self.saved_secs, 3),
                "dropped": self.dropped,
            }
        snap["programs"] = self.programs()
        snap["startup"] = self.startup()
        return snap


STATS = CacheStats()
_listeners_installed = False
_warned_uncached = False


def closes_startup_stage(name: str):
    """Decorator: the function's return closes start-up stage ``name``
    (:meth:`CacheStats.close_stage`; a call that raises closes nothing)."""
    def decorate(fn):
        @functools.wraps(fn)
        def closing(*args, **kwargs):
            entered = seconds_since_process_start()
            out = fn(*args, **kwargs)
            STATS.close_stage(name, entered)
            return out
        return closing
    return decorate


def _install_listeners() -> None:
    """Register the monitoring listeners once per process (idempotent)."""
    global _listeners_installed
    if _listeners_installed:
        return
    from jax import monitoring

    monitoring.register_event_listener(STATS._on_event)
    monitoring.register_event_duration_secs_listener(STATS._on_duration)
    _listeners_installed = True


def config_fingerprint(*objs: Any, **parts: Any) -> str:
    """Stable hex digest of arbitrary config state.

    Dataclasses (e.g. :class:`..configs.ViTConfig`) are serialized via
    ``asdict``; everything else must be JSON-serializable. Keyword parts
    are sorted, so call-site ordering cannot change the digest. Used
    for the warmup-manifest and model-meta identity checks.
    """
    def canon(o):
        if dataclasses.is_dataclass(o) and not isinstance(o, type):
            return {"__dc__": type(o).__name__,
                    **dataclasses.asdict(o)}
        return o

    payload = {"args": [canon(o) for o in objs],
               "kwargs": {k: canon(v) for k, v in sorted(parts.items())}}
    blob = json.dumps(payload, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()


_ATOMIC_PUT_LOCK = threading.Lock()
_atomic_put_installed = False


def _install_atomic_cache_writes() -> None:
    """Harden jax's persistent-cache writes to temp + ``os.replace``.

    jax's ``LRUCache.put`` writes the serialized executable with a bare
    ``write_bytes`` — a worker SIGKILLed mid-write (preemption,
    the elastic fault-injection harness, an OOM kill) leaves a
    TRUNCATED ``-cache`` file at the final path, and the next process
    to hit that key feeds torn bytes into XLA executable
    deserialization, which segfaults. With a shared cache the poison
    then kills every subsequent recovery of every worker: one
    preemption becomes a permanent crash loop (found by
    tools/elastic_bench.py's SIGKILL runs; the elastic supervisor's
    cache quarantine is the second line of defense for caches poisoned
    before this guard existed).

    The patch preserves put()'s semantics (same lock window, same
    no-overwrite early return) and changes only the write: same-dir
    temp file carrying the pid, then an atomic rename — the
    ``utils.atomic`` manifest discipline applied to jax's files.
    Guarded by duck-type checks so a jax that has fixed (or moved)
    this internally degrades to a no-op with a warning, never a crash.
    """
    global _atomic_put_installed
    with _ATOMIC_PUT_LOCK:
        if _atomic_put_installed:
            return
        _atomic_put_installed = True
        try:
            from jax._src import lru_cache as _lru
            LRUCache = _lru.LRUCache
            cache_suffix = _lru._CACHE_SUFFIX
            atime_suffix = _lru._ATIME_SUFFIX
        except (ImportError, AttributeError):
            warnings.warn(
                "compile_cache: jax's LRUCache internals moved; "
                "persistent-cache writes stay non-atomic (a killed "
                "worker can leave a torn cache entry)", RuntimeWarning)
            return
        original_put = LRUCache.put

        def atomic_put(self, key, val):
            raw = getattr(self, "path", None)
            eviction = getattr(self, "eviction_enabled", None)
            try:
                # jax wraps the dir in etils epath (possibly a remote
                # bucket); the atomic dance needs a local filesystem.
                local = os.fspath(raw) if raw is not None else None
            except TypeError:
                local = None
            if (not key or local is None or "://" in local or eviction):
                # Unknown shape, remote storage, or eviction mode (its
                # size accounting needs the lock-file dance): keep
                # jax's own put.
                return original_put(self, key, val)
            path = Path(local)
            cache_path = path / f"{key}{cache_suffix}"
            if cache_path.exists():
                return  # same no-overwrite contract as jax's put
            tmp = cache_path.with_name(
                cache_path.name + f".tmp.{os.getpid()}")
            try:
                tmp.write_bytes(val)
                os.replace(tmp, cache_path)
                (path / f"{key}{atime_suffix}").write_bytes(
                    time.time_ns().to_bytes(8, "little"))
            except OSError:
                # Best-effort cleanup; a failed put is a cache miss
                # next time, never a torn entry.
                try:
                    tmp.unlink(missing_ok=True)
                except OSError:
                    pass
                raise

        LRUCache.put = atomic_put


@closes_startup_stage("imports")
def configure(cache_dir: Optional[str] = None) -> Optional[Path]:
    """Turn jax's persistent compilation cache on and say where it is.

    ``JAX_COMPILATION_CACHE_DIR`` set: the cache is where jax put it when
    it read the variable at import, and ``cache_dir`` is ignored; no
    code path sets another directory. Unset: ``cache_dir`` (the
    ``--compile-cache-dir`` flag) or :data:`DEFAULT_CACHE_DIR`.

    Returns the directory in force (also ``STATS.cache_dir``). Every
    entry point's first call into the program: its return closes the
    start-up stage ``imports``.

    The min-compile-time/entry-size thresholds are zeroed: jax's default
    of 1 s would silently skip every sub-second compile — exactly the
    entries the CPU tests and the cold-start harness look for.
    """
    import jax

    _install_listeners()
    _install_atomic_cache_writes()
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if not os.environ.get(ENV_CACHE_DIR):
        resolved = Path(cache_dir).expanduser() if cache_dir \
            else DEFAULT_CACHE_DIR
        if resolved.exists() and not resolved.is_dir():
            # Catch the misparse symptom early with a diagnosis, not a
            # NotADirectoryError from mkdir: the classic cause is a
            # positional (an image path) landing in --compile-cache-dir.
            raise ValueError(
                f"compile cache dir {str(resolved)!r} is an existing "
                "file, not a directory — was a positional argument (e.g. "
                "an image path) swallowed by --compile-cache-dir?")
        resolved.mkdir(parents=True, exist_ok=True)
        if jax.config.jax_compilation_cache_dir != str(resolved):
            jax.config.update("jax_compilation_cache_dir", str(resolved))
            # A cache already initialized (an earlier compile in this
            # process) holds the OLD dir; reset so the new one takes.
            from jax.experimental.compilation_cache import (
                compilation_cache)
            compilation_cache.reset_cache()
    in_force = jax.config.jax_compilation_cache_dir
    with STATS._lock:
        STATS.cache_dir = in_force
    return Path(in_force) if in_force else None


def add_cache_cli(parser) -> None:
    """The shared ``--compile-cache-dir`` axis (train/serve/predict/
    probe/batch_infer). The value is REQUIRED — an optional-value flag
    placed ahead of a positional (predict's image paths) silently
    swallows one, the same greedy-nargs footgun ``--classes-file``
    exists to kill."""
    parser.add_argument(
        "--compile-cache-dir", default=None, metavar="DIR",
        help="persistent XLA compilation cache directory (restarts skip "
             "recompiles: preemption recovery becomes checkpoint gap + "
             f"cache hit). Ignored when ${ENV_CACHE_DIR} is set — the "
             "cache is then exactly there; default "
             f"{DEFAULT_CACHE_DIR}")


def warn_if_uncached(context: str) -> None:
    """Warn ONCE per process when a non-CPU backend runs without a
    persistent compilation cache — a library caller that never called
    :func:`configure` — the silent multi-minute-warmup failure mode this
    subsystem exists to kill."""
    global _warned_uncached
    if _warned_uncached:
        return
    import jax

    if jax.config.jax_compilation_cache_dir:
        return
    backend = jax.default_backend()
    if backend == "cpu":
        return
    _warned_uncached = True
    warnings.warn(
        f"[{context}] no persistent compilation cache is configured on "
        f"the '{backend}' backend: every process start re-pays full XLA "
        f"compilation (multi-second stalls per shape). Call "
        f"compile_cache.configure() or set ${ENV_CACHE_DIR}.",
        stacklevel=2)
