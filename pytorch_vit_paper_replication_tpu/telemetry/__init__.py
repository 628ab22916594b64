"""Unified telemetry: one event schema, one registry, every subsystem.

The cross-cutting observability layer (ISSUEs 5 + 7): train's hot
loop, serve, the data pipeline, and the compile cache all publish
through one thread-safe :class:`.registry.TelemetryRegistry` —

* :mod:`.registry` — counters / gauges / rolling histograms, the
  postmortem event ring, and the ONE Prometheus text renderer
  (``# HELP``/``# TYPE`` + summary ``_count``/``_sum``) behind serve's
  ``::metrics``, ``train.py --metrics-port``, and the fleet
  aggregator's endpoint,
* :mod:`.spans` — :class:`StepTelemetry`, the engine loop's per-step
  span tracker (data-wait / step-exec / checkpoint / eval seconds,
  sampled honest-timing barriers, live images/sec + analytic-MFU
  gauges, per-epoch goodput summaries) emitting MetricsLogger-
  compatible JSONL that ``tools/trace_report.py`` renders,
* :mod:`.watchdog` — :class:`Watchdog`, the stall heartbeat that dumps
  all-thread stacks + memory + the last-N events instead of freezing
  silently (and the same dump on SIGTERM for preemption forensics),
* :mod:`.profiling` — :class:`ProfileController`, on-demand
  ``jax.profiler`` capture windows (``--profile-steps A:B``, SIGUSR2,
  or a step-time anomaly) plus device-memory watermark gauges sampled
  on the honesty-barrier cadence. A capture is of the device alone
  (the host tracer slows the feed it records: PERF.md, PR 22); the
  host's side is :class:`StepTelemetry` 's own spans, handed over
  while a capture is active and set on the trace's clock by an anchor
  program,
* :mod:`.device_trace` — the reader: when a window closes (and as
  ``python -m ...telemetry.device_trace <capture>``) the captured
  step's device time by layer (``patch_embed``, ``msa_norm``,
  ``msa_qkv``, ``attn_core``, ``msa_out``, ``msa_glue``,
  ``block_glue``, the Pallas kernels by their ``name=``, ``mlp_xla``,
  ``final_norm_head``, ``loss``, ``metrics``, ``optimizer``,
  ``collective``, ``other``) and by phase (forward / backward /
  recompute / optimizer), printed, written to
  ``<capture>/device_time.json`` and published as one
  ``profiler_device_time`` event; an op's layer is its jax name stack
  (module names and ``named_scope`` s), joined in from the step
  program's optimized HLO because the v5e profile keeps no path,
* :mod:`.chrome_trace` — the span/event stream as Chrome trace-event
  JSON, so engine spans render in Perfetto next to XLA captures,
* :mod:`.tracing` — request-scoped DISTRIBUTED tracing (ISSUE 20):
  W3C-traceparent-style :class:`.tracing.TraceContext` carried across
  loadgen -> router -> batcher -> replica (+ the cascade teacher hop)
  as a ``trace=`` wire token, per-process crash-tolerant JSONL span
  sinks, and deterministic seeded-hash head sampling (no wall clock,
  no PRNG — every process decides a trace_id identically),
* :mod:`.shipper` — :class:`TelemetryShipper`, the drop-don't-block
  TCP push of registry snapshots into ``tools/fleet_agg.py``'s merged
  fleet view, and the stdlib ``/metrics`` HTTP endpoint,
* :mod:`.flops` — the analytic ViT FLOP math shared with bench.py's
  MFU self-audit.

``tools/telemetry_overhead.py`` A/Bs the whole instrumented path —
including watermark sampling and a live shipper — against bare loops;
bench.py gates it (< 2% step-throughput cost,
``telemetry_overhead_ok``; request tracing rides the same harness and
the same budget, ``tracing_overhead_ok``).

Tracing a request end-to-end
----------------------------

Every serving process appends spans to its OWN sink; the join is a
post-hoc merge keyed on trace_id::

    # 1. replicas: span sink + role per process
    python -m pytorch_vit_paper_replication_tpu.serve CKPT \\
        --serve --trace-jsonl sink_replica.jsonl --trace-role replica

    # 2. client ingress: loadgen samples 1% of requests (seeded hash
    #    of the trace_id — deterministic, replayable) and stamps a
    #    trace= token on the wire; the router and every hop after it
    #    adopt the token, so ONE decision covers the whole chain
    python tools/loadgen.py --profile P.json --target H:P --image I \\
        --trace-jsonl sink_client.jsonl --trace-sample 0.01

    # 3. join the sinks: causal tree, Perfetto trace with one lane
    #    group per process role, SLO attribution naming the dominant
    #    hop per latency-percentile bucket + exemplar trace_ids
    python tools/trace_merge.py sink_*.jsonl \\
        --out-trace trace.json --out-report slo.json --tree

An untraced request's wire bytes are byte-identical to a pre-tracing
build's, and a tracer configured with ``--trace-sample 0`` allocates
ZERO span objects (tools/telemetry_overhead.py raises if it ever
does). ``runs/trace_r20/`` carries a committed merged trace of an
escalated cascade request — client.request -> router.request ->
cascade.student -> cascade.decide -> cascade.teacher -> the teacher
replica's serve.request — plus the SLO report and the <=2%-overhead
serve_bench A/B; ``tools/trace_demo.py`` regenerates it.
"""

from .chrome_trace import (to_chrome_trace, validate_chrome_trace,
                           write_chrome_trace)
from .flops import (CHIP_PEAKS, analytic_mfu, peak_bf16_tflops,
                    train_step_flops_per_image,
                    train_step_flops_per_sequence)
from .profiling import (ProfileController, parse_profile_steps,
                        sample_device_memory)
from .registry import (HELP_TEXT, INSTRUMENTS, TelemetryRegistry,
                       get_registry, render_prometheus)
from .shipper import FrameSink, TelemetryShipper, start_metrics_http
from .spans import ROW_KEYS, StepTelemetry
from .tracing import (TraceContext, Tracer, configure_tracer,
                      get_tracer, trace_sample)
from .watchdog import Watchdog, memory_report

__all__ = [
    "CHIP_PEAKS", "FrameSink", "HELP_TEXT", "INSTRUMENTS",
    "ProfileController", "ROW_KEYS", "StepTelemetry", "TelemetryRegistry",
    "TelemetryShipper", "TraceContext", "Tracer", "Watchdog",
    "analytic_mfu", "configure_tracer", "get_registry", "get_tracer",
    "memory_report", "parse_profile_steps", "peak_bf16_tflops",
    "render_prometheus", "sample_device_memory", "start_metrics_http",
    "to_chrome_trace", "trace_sample", "train_step_flops_per_image",
    "train_step_flops_per_sequence",
    "validate_chrome_trace", "write_chrome_trace",
]
