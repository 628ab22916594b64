"""What every driver needs from the machine and the program: the chips,
the compile cache, the model from a configuration file, device memory,
and a profiler capture. Imports jax lazily, after ``run.py`` has fixed
the environment."""

from __future__ import annotations

import contextlib
import gc
import json
import shutil
import threading
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent.parent   # the checkout
BENCH = ROOT / "benchmark"
WORK = ROOT / ".benchmark_work"          # traces and span sinks; ignored


class Refused(SystemExit):
    """The run cannot produce a result (no TPU, too few chips, a cell
    that does not exist): exit non-zero, print no result line."""

    def __init__(self, why: str):
        super().__init__(f"benchmark: {why}")


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, *, rehearsal: bool = False) -> tuple[dict, dict]:
    """A cell's file and its configuration's, found by name. In a
    rehearsal the cell's and the configuration's ``rehearsal`` blocks
    replace the sizes, and nothing else changes."""
    path = BENCH / "workloads" / f"{name}.json"
    if not path.is_file():
        raise Refused(f"no cell file {path}")
    cell = load_json(path)
    config = load_json(BENCH / "configs" / f"{cell['config']}.json")
    if rehearsal:
        block = cell["driver"]
        cell[block] = {**cell[block], **cell.get("rehearsal", {})}
        config["model"] = {**config["model"],
                           **config.get("rehearsal_model", {})}
    return cell, config


def claim_devices(chips: int, *, rehearsal: bool):
    """The first ``chips`` devices. Outside a rehearsal they must be
    TPUs whose kind is in the table of peaks."""
    import jax

    from . import flops

    devices = jax.devices()
    if not rehearsal:
        if devices[0].platform != "tpu":
            raise Refused(f"no TPU: jax found {devices[0].platform!r}; a "
                          "device metric is never measured elsewhere")
        flops.peaks(devices[0].device_kind)
    if len(devices) < chips:
        raise Refused(f"cell needs {chips} chips, jax found {len(devices)}")
    return devices[:chips]


def configure_cache():
    """The program's own rule: ``JAX_COMPILATION_CACHE_DIR`` if set, else
    ``<checkout>/.jax_compile_cache``. Returns its hit/miss counters."""
    from pytorch_vit_paper_replication_tpu import compile_cache

    compile_cache.configure()
    return compile_cache.STATS


def build_model(config: dict):
    """The program's model for a configuration file; every kernel-path
    option of ``ViTConfig`` stays at its default."""
    from pytorch_vit_paper_replication_tpu.configs import ViTConfig
    from pytorch_vit_paper_replication_tpu.models import ViT

    cfg = ViTConfig(**config["model"])
    return cfg, ViT(cfg)


def device_report(devices, program_bytes: int = 0) -> dict:
    """The contract's ``device`` key. ``memory_peak_bytes`` is the larger
    of the allocator's peak on the fullest chip and ``program_bytes``
    (what the compiled programs hold per chip by ``memory_analysis()``):
    on the v5e the allocator's peak leaves out a program's temporaries.
    Both readings are printed on a line of their own."""
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    print(f"[device] allocator peak {peak} B, compiled programs "
          f"{int(program_bytes)} B per chip; memory_peak_bytes is the "
          "larger", flush=True)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": max(peak, int(program_bytes))}


def program_bytes(compiled) -> int:
    """Bytes one device holds while ``compiled`` runs: arguments +
    temporaries + outputs - aliased (``memory_analysis()``)."""
    m = compiled.memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes)


class GcWatch:
    """Every collection of Python's garbage collector, timed on the
    host's clock (two reads a collection, nothing between them): a run
    that reads far off because one step's wait returned seconds late
    (PERF.md section 7) can then be told apart: a collection that long
    is the interpreter's, none is the machine's."""

    def __init__(self):
        self.pauses = []            # (start, seconds, generation)
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info["generation"]))
            self._t0 = None

    def report(self, lo: float, hi: float) -> str:
        """The collections that began in ``[lo, hi)``, in a few words;
        the watch ends here."""
        if self._on in gc.callbacks:
            gc.callbacks.remove(self._on)
        seen = [p for p in self.pauses if lo <= p[0] < hi]
        if not seen:
            return "gc in the window: none"
        t0, longest, gen = max(seen, key=lambda p: p[1])
        return (f"gc in the window: {len(seen)} collections, "
                f"{sum(p[1] for p in seen) * 1e3:.1f} ms together, longest "
                f"{longest * 1e3:.1f} ms (generation {gen}, "
                f"{t0 - lo:.2f} s after it opened)")


class Capture:
    """One profiler capture into ``WORK/<tag>``, of the device alone.

    The host tracer stays off: at any level the runtime records every
    chunk of its host-side layout change of an input batch (a million
    events a thread), the 154 MB feed of a B/16 step then takes longer
    than the step, and the chip reads 27-41% idle where it is 0.04%
    (PERF.md, PR 22). The benchmark's own host spans (``annotate``) are
    kept here instead, on the host's clock, and set on the trace's clock
    by an anchor: a tiny named program run right after the capture
    starts, whose end the host sees when ``block_until_ready`` returns
    and the trace has as a module event. ``start``/``stop`` may be
    called from another thread than the one that does the work."""

    ANCHOR = "bench_clock_anchor"

    def __init__(self, tag: str):
        import jax
        import jax.numpy as jnp

        self.dir = WORK / tag
        self.started = False
        self.stopped = False
        self.spans = []                  # (name, t0_ns, t1_ns), host clock
        self.anchor_host_ns = None
        self._lock = threading.Lock()
        anchor = lambda x: x + 1
        anchor.__name__ = self.ANCHOR
        self._anchor = jax.jit(anchor)
        self._x = jnp.zeros((), jnp.int32)
        jax.block_until_ready(self._anchor(self._x))     # compiled in set-up

    def start(self):
        import jax

        global _ACTIVE
        with self._lock:
            if self.started:
                return
            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(str(self.dir), profiler_options=opts)
            jax.block_until_ready(self._anchor(self._x))
            self.anchor_host_ns = time.perf_counter_ns()
            self.started = True
            _ACTIVE = self

    def stop(self):
        import jax

        global _ACTIVE
        with self._lock:
            if self.started and not self.stopped:
                jax.profiler.stop_trace()
                self.stopped = True
                _ACTIVE = None

    def host_plane(self, anchor_trace_end_ns: int) -> dict:
        """The recorded spans as a ``/host:CPU`` plane on the trace's
        clock, given where the trace has the anchor's end."""
        shift = anchor_trace_end_ns - self.anchor_host_ns
        return {"name": "/host:CPU", "lines": [{"name": "bench", "events": [
            {"name": n, "start_ns": t0 + shift, "dur_ns": t1 - t0}
            for n, t0, t1 in self.spans]}]}

    def cleanup(self):
        shutil.rmtree(self.dir, ignore_errors=True)


_ACTIVE = None       # the capture that is recording host spans, if any


@contextlib.contextmanager
def annotate(name: str):
    """A host span around the benchmark's call into a layer, kept while
    a capture runs (two clock reads) and free otherwise."""
    cap = _ACTIVE
    if cap is None:
        yield
        return
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        cap.spans.append((name, t0, time.perf_counter_ns()))
