"""The ONE telemetry registry: counters, gauges, rolling histograms.

The repo grew three disjoint metric systems — per-epoch
:class:`..metrics.MetricsLogger` rows in train, :class:`..serve.stats.
ServeStats` percentiles in serve, and :data:`..compile_cache.STATS`
counters — each with its own locking, snapshot shape, and vocabulary.
This module is the shared substrate they all publish through:

* **counters** — monotonic totals (``tel_steps_total``, cache hits),
* **gauges** — last-value instruments (``tel_images_per_sec``,
  ``tel_goodput_pct``),
* **histograms** — bounded rolling sample windows with p50/p95/p99
  snapshots (step seconds, data-wait seconds) — same reservoir design
  as ServeStats' latency legs, so percentiles mean the same thing in
  train and serve,
* an **event ring** — the last N emitted telemetry events, kept so a
  watchdog postmortem (:mod:`.watchdog`) can show what the run was
  doing right before it stalled,
* :meth:`TelemetryRegistry.to_prometheus` — the registry rendered as
  Prometheus text exposition format (the serve CLI's ``::metrics``
  command), so any scraper that speaks Prometheus can watch a run.

Instrument names are namespaced by publisher (``tel_`` for the train
hot-loop spans, ``serve_``/``data_``/``compile_cache_``/``watchdog_``
for theirs) and the train-side names are declared in
:data:`INSTRUMENTS` — tests assert they can NEVER collide with the
existing MetricsLogger JSONL vocabulary (``images_per_sec``,
``lat_total_p99``, ...), so dashboards reading a merged stream always
know which subsystem a key came from.
"""

from __future__ import annotations

import json
import re
import threading
import time
from collections import deque
from typing import Any, Dict, Iterable, List, Optional

import numpy as np

# Rolling-histogram window: big enough that p99 has tail samples over an
# epoch of steps, bounded so sustained runs can't grow memory.
DEFAULT_HIST_WINDOW = 4096
# Event ring depth — what a postmortem shows as "the last things done".
DEFAULT_EVENT_RING = 256

# The train-side telemetry schema: every instrument the engine-loop
# spans (:mod:`.spans`) and watchdog publish, name -> kind. The names
# are deliberately tel_/watchdog_-prefixed: tests/test_compile_cache.py
# asserts this set stays disjoint from the MetricsLogger JSONL keys the
# repo already emits (engine.train rows, ServeStats.emit rows), so a
# merged JSONL stream can always be attributed by key alone.
INSTRUMENTS: Dict[str, str] = {
    "tel_step_s": "histogram",          # full step wall (wait+exec)
    "tel_data_wait_s": "histogram",     # blocked on the batch iterator
    "tel_step_exec_s": "histogram",     # dispatch+device (step minus wait)
    "tel_ckpt_s": "histogram",          # checkpoint-save span
    "tel_eval_s": "histogram",          # eval-pass span
    "tel_images_per_sec": "gauge",      # live window throughput (global)
    "tel_mfu": "gauge",                 # analytic-FLOPs MFU (per chip)
    # routed experts (a token model's step counters, engine._moe_metrics)
    "tel_moe_pairs_per_expert_min": "gauge",
    "tel_moe_pairs_per_expert_mean": "gauge",
    "tel_moe_pairs_per_expert_max": "gauge",
    "tel_moe_pairs_kept_share": "gauge",
    "tel_moe_dropped_pairs_total": "counter",
    "tel_moe_passes_max": "gauge",
    "tel_moe_one_pass_share": "gauge",
    "tel_moe_score_sum_mean": "gauge",   # a sigmoid router's normaliser
    # a model with a multi-token-prediction module: its objective's terms
    "tel_main_loss": "gauge",
    "tel_mtp_loss": "gauge",
    "tel_mtp_top1_share": "gauge",
    # a model whose attention an indexer selects (engine._dsa_metrics)
    "tel_indexer_loss": "gauge",
    "tel_dsa_selected_pairs": "gauge",
    "tel_dsa_causal_pairs": "gauge",
    "tel_dsa_pbar_mass_min": "gauge",
    "tel_dsa_select_served": "gauge",
    "tel_dsa_select_tie_rows": "gauge",
    # a model with state-space layers (engine._token_loss)
    "tel_ssm_state_carry": "gauge",
    "tel_goodput_pct": "gauge",         # step-exec share of wall time
    "tel_data_wait_frac": "gauge",      # data-wait share of wall time
    "tel_steps_total": "counter",
    "tel_images_total": "counter",
    "watchdog_beats_total": "counter",
    "watchdog_stalls_total": "counter",
    "watchdog_postmortems_total": "counter",
    # Deep-profiling instruments (telemetry/profiling.py): capture
    # windows + device-memory watermarks. Per-device mem_devN_* gauges
    # are published dynamically alongside these (same mem_ prefix).
    "profiler_captures_total": "counter",
    "profiler_capture_errors_total": "counter",
    "profiler_arms_refused_total": "counter",
    "profiler_capture_active": "gauge",
    "profiler_last_capture_path": "gauge",   # string gauge: snapshot/
    # postmortem only — the Prometheus renderer skips non-numerics
    # The last closed capture, read back (telemetry/device_trace.py).
    "profiler_last_step_device_ms": "gauge",
    "profiler_last_idle_pct": "gauge",
    "mem_live_bytes": "gauge",
    "mem_live_bytes_peak": "gauge",
    "mem_live_arrays": "gauge",
    # Fleet shipper (telemetry/shipper.py) delivery counters.
    "shipper_frames_total": "counter",
    "shipper_dropped_total": "counter",
    "shipper_reconnects_total": "counter",
    # Offline batch inference (serve/offline.py, tools/batch_infer.py):
    # the bi_ namespace, so a fleet view shows batch jobs next to train
    # (tel_) and serve (serve_) workers.
    "bi_records_total": "counter",
    "bi_batches_total": "counter",
    "bi_checkpoints_total": "counter",
    "bi_images_per_sec": "gauge",
    "bi_progress_pct": "gauge",
    "bi_devices": "gauge",
    "bi_data_wait_s": "histogram",
    "bi_drain_s": "histogram",
    # Data-pipeline counters (data/image_folder.py DataLoader).
    "data_batches_total": "counter",
    "data_epochs_total": "counter",
    "data_last_epoch_s": "gauge",
    # Persistent compile-cache mirror (compile_cache.CacheStats): jax
    # monitoring events counted into the shared registry so ::metrics
    # and postmortems see cache behavior without a CacheStats snapshot.
    "compile_cache_requests_total": "counter",
    "compile_cache_hits_total": "counter",
    "compile_cache_saved_seconds_total": "counter",
    # What programs' first calls cost, by stage (CacheStats._on_stage).
    "compile_trace_seconds_total": "counter",
    "compile_lower_seconds_total": "counter",
    "compile_backend_seconds_total": "counter",
    "compile_cache_read_seconds_total": "counter",
    # Kept stage events the bound pushed out (0 in a training run: what
    # is kept is one event a stage for each top-level program).
    "compile_stage_events_dropped_total": "counter",
    # The stages of a trainer's start, seconds each (CacheStats.
    # close_stage): process start -> configure() -> make_mesh() ->
    # make_parallel_train_step() -> the first step applied.
    "startup_imports_seconds": "gauge",
    "startup_mesh_seconds": "gauge",
    "startup_state_seconds": "gauge",
    "startup_first_step_seconds": "gauge",
    # Serving fleet (serve/fleet/): the router's routing/admission
    # instruments, the rolling checkpoint hot-swap, and replica
    # membership. Per-replica replica_up_<rid> gauges are published
    # dynamically alongside these (same replica_ prefix).
    "fleet_route_requests_total": "counter",
    "fleet_route_retries_total": "counter",
    "fleet_route_rejected_total": "counter",
    "fleet_route_errors_total": "counter",
    "fleet_route_inflight": "gauge",
    "fleet_route_lat_s": "histogram",
    "fleet_route_lat_ema_s": "gauge",
    "fleet_replicas_up": "gauge",
    "fleet_swaps_total": "counter",
    "fleet_swap_failures_total": "counter",
    "fleet_swap_rollbacks_total": "counter",
    "fleet_swap_active": "gauge",
    "fleet_swap_last_s": "gauge",
    "replica_restarts_total": "counter",
    # Telemetry-driven autoscaling (serve/fleet/autoscale.py, ISSUE
    # 14): the control loop's decisions, its view of the signals it
    # steered by (so a timeline explains itself), and the two costs a
    # scaling action pays — warm spin-up and drain-out seconds.
    "autoscale_decisions_total": "counter",
    "autoscale_up_total": "counter",
    "autoscale_down_total": "counter",
    "autoscale_aborts_total": "counter",
    "autoscale_replicas_target": "gauge",
    "autoscale_signal_load": "gauge",
    "autoscale_signal_lat_s": "gauge",
    "autoscale_warm_coverage": "gauge",
    "autoscale_spinup_s": "histogram",
    "autoscale_drain_s": "histogram",
    # Elastic preemption-tolerant training (parallel/elastic.py): the
    # supervisor's membership/recovery instruments plus worker-side
    # heartbeat/collective counters — one elastic_ namespace so a fleet
    # view shows cluster churn next to the training rows it explains.
    "elastic_heartbeats_total": "counter",
    "elastic_heartbeat_misses_total": "counter",
    "elastic_reforms_total": "counter",
    "elastic_recoveries_total": "counter",
    "elastic_lost_steps_total": "counter",
    "elastic_collective_failures_total": "counter",
    "elastic_yields_total": "counter",
    "elastic_init_retries_total": "counter",
    "elastic_cache_quarantines_total": "counter",
    "elastic_workers": "gauge",
    "elastic_generation": "gauge",
    "elastic_last_recovery_s": "gauge",
    # Embedding search (ISSUE 13, search/scan.py): the device-sharded
    # top-k scanner's instruments — one search_ namespace whether the
    # scan runs under an online ::search request or an offline sweep.
    "search_queries_total": "counter",
    "search_scans_total": "counter",
    "search_qps": "gauge",
    "search_index_rows": "gauge",
    "search_devices": "gauge",
    "search_scan_s": "histogram",
    "search_merge_s": "histogram",
    # Continuous deployment (deploy/, ISSUE 15): the train→serve
    # flywheel's phase machine, gate verdicts, canary shadow mirror,
    # and the promote/rollback outcomes — one deploy_ namespace so a
    # fleet view shows the rollout state next to the serving rows it
    # governs.
    "deploy_candidates_total": "counter",
    "deploy_gate_passed_total": "counter",
    "deploy_gate_refused_total": "counter",
    "deploy_canaries_total": "counter",
    "deploy_promotions_total": "counter",
    "deploy_rollbacks_total": "counter",
    "deploy_quarantined_total": "counter",
    "deploy_shadow_compared_total": "counter",
    "deploy_shadow_exceeded_total": "counter",
    "deploy_shadow_canary_errors_total": "counter",
    "deploy_phase": "gauge",
    "deploy_incumbent_step": "gauge",
    "deploy_candidate_step": "gauge",
    "deploy_gate_s": "histogram",
    "deploy_canary_s": "histogram",
    "deploy_promote_s": "histogram",
    # Serve-engine point gauges published by engine.publish_telemetry /
    # ServeStats.publish with static names (the serve_lat_*/
    # serve_latency_*/serve_*_total families are dynamic, riding the
    # serve_ namespace prefix).
    "serve_queue_depth": "gauge",
    "serve_warm_rungs": "gauge",
    "serve_warmup_cumulative_s": "gauge",
    "serve_time_to_first_batch_s": "gauge",
    # Fused multi-head serving (ISSUE 12): per-head and per-SLO-tier
    # request counters + rolling-p99 gauges published by
    # ServeStats.publish; the matching serve_lat_head_<head>_s /
    # serve_lat_tier_<tier>_s histograms are dynamic names on the
    # serve_ namespace prefix.
    "serve_head_probs_total": "counter",
    "serve_head_features_total": "counter",
    "serve_head_tokens_total": "counter",
    "serve_head_probs_p99_s": "gauge",
    "serve_head_features_p99_s": "gauge",
    "serve_head_tokens_p99_s": "gauge",
    "serve_tier_interactive_total": "counter",
    "serve_tier_batch_total": "counter",
    "serve_tier_interactive_p99_s": "gauge",
    "serve_tier_batch_p99_s": "gauge",
    # Speculative two-tier cascade (serve/cascade.py, ISSUE 19):
    # the student-answers/teacher-escalates accounting — per-tier
    # served counters, the margin histogram the threshold sweep
    # prices, the live escalation rate the capacity math keys on, and
    # the calibration-predicted agreement floor live agreement is
    # judged against.
    "cascade_requests_total": "counter",
    "cascade_escalated_total": "counter",
    "cascade_served_student_total": "counter",
    "cascade_served_teacher_total": "counter",
    "cascade_student_failover_total": "counter",
    "cascade_teacher_fallback_total": "counter",
    "cascade_escalation_rate": "gauge",
    "cascade_threshold": "gauge",
    "cascade_predicted_agreement": "gauge",
    "cascade_margin": "histogram",
    # Escalation-drift alarm (serve/cascade.py EscalationDriftAlarm,
    # ISSUE 20, ROADMAP 3(b)): rolling-window escalation rate vs the
    # calibration's prediction, alarm state + fire count.
    "cascade_drift_window_rate": "gauge",
    "cascade_drift_expected_rate": "gauge",
    "cascade_drift_alarm_active": "gauge",
    "cascade_drift_alarms_total": "counter",
    # Request-scoped distributed tracing (telemetry/tracing.py +
    # tools/trace_merge.py, ISSUE 20): spans recorded by this process,
    # and the merged view's root-latency percentiles — the SLO gauges
    # the exemplar trace_ids are registered next to (as
    # trace_slo_exemplar ring events carrying the hex ids).
    "trace_spans_total": "counter",
    "trace_traces_total": "gauge",
    "trace_p50_s": "gauge",
    "trace_p90_s": "gauge",
    "trace_p99_s": "gauge",
    # Knowledge distillation (distill/ + train.py --distill-from,
    # ISSUE 19): the KD mix in force and the per-epoch student/teacher
    # argmax agreement — the fidelity number the cascade's calibration
    # will re-measure offline.
    "distill_alpha": "gauge",
    "distill_t": "gauge",
    "distill_loss": "gauge",
    "distill_teacher_agree_frac": "gauge",
}

# Prometheus # HELP text for the declared instruments (the renderer
# emits a generic fallback for dynamically-named ones). Keep these one
# line each — exposition-format HELP is single-line by grammar.
HELP_TEXT: Dict[str, str] = {
    "tel_step_s": "Train step wall seconds (barrier-window amortized)",
    "tel_data_wait_s": "Seconds blocked on the batch iterator",
    "tel_step_exec_s": "Step dispatch+device seconds (amortized)",
    "tel_ckpt_s": "Checkpoint-save span seconds",
    "tel_eval_s": "Eval-pass span seconds",
    "tel_images_per_sec": "Live window throughput, global images/sec",
    "tel_mfu": "Analytic model-FLOPs utilization per chip",
    "tel_moe_pairs_per_expert_min":
        "Token-expert pairs on the emptiest held expert, last sampled step",
    "tel_moe_pairs_per_expert_mean":
        "Token-expert pairs per held expert, mean, last sampled step",
    "tel_moe_pairs_per_expert_max":
        "Token-expert pairs on the fullest held expert, last sampled step",
    "tel_moe_pairs_kept_share":
        "Share of the pairs routed to held experts that were computed",
    "tel_moe_dropped_pairs_total":
        "Pairs routed to a held expert and not computed (sampled steps)",
    "tel_moe_passes_max":
        "Most passes over the routed row buffer by one chunk of tokens, "
        "last sampled step",
    "tel_moe_one_pass_share":
        "Share of the routed layers' token chunks served in one pass",
    "tel_moe_score_sum_mean":
        "Sum of a token's selected sigmoid router scores before they are "
        "normalised, mean over tokens and routed blocks",
    "tel_main_loss": "Next-token loss of the main head, last sampled step",
    "tel_mtp_loss":
        "Loss of the multi-token-prediction module, last sampled step",
    "tel_mtp_top1_share":
        "Share of the module's positions whose largest logit is the "
        "target (how often a drafted token would be accepted)",
    "tel_indexer_loss":
        "The sparse-attention indexer's alignment loss, mean over layers, "
        "last sampled step",
    "tel_dsa_selected_pairs":
        "Query-key pairs the indexer's selection kept, a sequence a layer",
    "tel_dsa_causal_pairs":
        "Causal query-key pairs the selection chose from, a sequence a "
        "layer",
    "tel_dsa_pbar_mass_min":
        "Smallest over layers of the mean over queries of the head-mean "
        "attention probabilities' mass on the selection (1 by construction)",
    "tel_dsa_select_served":
        "Share of the indexed layers whose selection the dsa_select kernel "
        "searched (0: the XLA bisection)",
    "tel_dsa_select_tie_rows":
        "Query rows whose threshold score was tied beyond what they take, "
        "a sequence, summed over layers",
    "tel_ssm_state_carry":
        "Share of the state entering a chunk of the scan that reaches its "
        "end, mean over state-space layers, heads and chunks",
    "tel_goodput_pct": "Step-exec share of epoch wall time, percent",
    "tel_data_wait_frac": "Data-wait share of epoch wall time",
    "tel_steps_total": "Train steps recorded",
    "tel_images_total": "Train images recorded",
    "watchdog_beats_total": "Watchdog heartbeats received",
    "watchdog_stalls_total": "Stall deadlines missed",
    "watchdog_postmortems_total": "Postmortem dumps written",
    "profiler_captures_total": "XLA profiler capture windows opened",
    "profiler_capture_errors_total": "Profiler start/stop failures",
    "profiler_arms_refused_total": "Capture requests refused (window "
                                   "already armed/active or budget "
                                   "spent)",
    "profiler_capture_active": "1 while a capture window is open",
    "mem_live_bytes": "Sum of live jax array bytes at last sample",
    "mem_live_bytes_peak": "Peak of mem_live_bytes over the run",
    "mem_live_arrays": "Count of live jax arrays at last sample",
    "shipper_frames_total": "Telemetry frames delivered to the "
                            "aggregator",
    "shipper_dropped_total": "Telemetry frames dropped (aggregator "
                             "unreachable)",
    "shipper_reconnects_total": "Aggregator (re)connections",
    "bi_records_total": "Batch-inference records completed",
    "bi_batches_total": "Batch-inference loader batches consumed",
    "bi_checkpoints_total": "Batch-inference progress manifests written",
    "bi_images_per_sec": "Batch-inference live sweep throughput",
    "bi_progress_pct": "Batch-inference dataset progress, percent",
    "bi_devices": "Devices the batch-inference mesh shards over",
    "bi_data_wait_s": "Seconds blocked on the batch-inference loader",
    "bi_drain_s": "Seconds blocked fetching batch-inference outputs",
    "profiler_last_capture_path": "Most recent capture directory "
                                  "(string gauge: snapshot/postmortem "
                                  "only)",
    "data_batches_total": "Data-loader batches yielded",
    "data_epochs_total": "Data-loader epochs completed",
    "data_last_epoch_s": "Wall seconds of the last completed "
                         "data-loader epoch",
    "compile_cache_requests_total": "XLA modules that consulted the "
                                    "persistent compile cache",
    "compile_cache_hits_total": "XLA modules deserialized from the "
                                "persistent compile cache",
    "compile_cache_saved_seconds_total": "Compile seconds saved by "
                                         "persistent-cache hits",
    "compile_trace_seconds_total": "Seconds tracing Python to jaxprs "
                                   "(programs' first calls)",
    "compile_lower_seconds_total": "Seconds lowering jaxprs to MLIR "
                                   "modules",
    "compile_backend_seconds_total": "Seconds in the backend: compile "
                                     "on a cache miss, read + "
                                     "deserialise on a hit",
    "compile_cache_read_seconds_total": "Seconds reading persistent-"
                                        "cache entries (part of backend)",
    "compile_stage_events_dropped_total": "First-call stage events the "
                                          "record's bound pushed out "
                                          "(oldest first)",
    "startup_imports_seconds": "Process start to compile_cache."
                               "configure() returning: interpreter, "
                               "imports, argument parsing",
    "startup_mesh_seconds": "From there to make_mesh() returning: the "
                            "backend's initialisation, the device mesh",
    "startup_state_seconds": "From there to make_parallel_train_step() "
                             "returning: state made and laid out, step "
                             "built",
    "startup_first_step_seconds": "From there to the first train step "
                                  "applied: first batch, trace, lower, "
                                  "compile or cache read, execution",
    "profiler_last_step_device_ms": "Device ms per step in the last "
                                    "closed capture",
    "profiler_last_idle_pct": "Device idle share of the last closed "
                              "capture's window, percent",
    "fleet_route_requests_total": "Client request lines the fleet "
                                  "router dispatched",
    "fleet_route_retries_total": "Re-dispatches after a replica died "
                                 "or pushed back mid-request",
    "fleet_route_rejected_total": "Requests refused with fleet-level "
                                  "backpressure",
    "fleet_route_errors_total": "Requests that exhausted every "
                                "routable replica",
    "fleet_route_inflight": "Requests in flight through the router",
    "fleet_route_lat_s": "Client-observed request seconds through "
                         "the router",
    "fleet_replicas_up": "Replicas inside the health deadline",
    "fleet_swaps_total": "Rolling checkpoint swaps completed",
    "fleet_swap_failures_total": "Replica swaps that failed the "
                                 "health/warm/probe gate",
    "fleet_swap_rollbacks_total": "Rolling swaps rolled back to the "
                                  "old checkpoint",
    "fleet_swap_active": "1 while a rolling swap is in progress",
    "fleet_swap_last_s": "Seconds the last completed replica swap "
                         "took",
    "fleet_route_lat_ema_s": "EMA of client-observed request seconds "
                             "through the router",
    "replica_restarts_total": "Supervised replica restarts",
    "autoscale_decisions_total": "Autoscaler observe/decide ticks",
    "autoscale_up_total": "Replicas scaled up (warm gate passed)",
    "autoscale_down_total": "Replicas drained out by scale-down",
    "autoscale_aborts_total": "Scale-ups aborted at the warm gate",
    "autoscale_replicas_target": "Replica count the last decision "
                                 "asked for",
    "autoscale_signal_load": "Queue pressure per up-replica the "
                             "decider last saw",
    "autoscale_signal_lat_s": "Router latency EMA the decider last "
                              "saw, seconds",
    "autoscale_warm_coverage": "Fraction of up replicas warm for the "
                               "expected ladder",
    "autoscale_spinup_s": "Scale-up spawn-to-warm-admitted seconds",
    "autoscale_drain_s": "Scale-down quiesce-to-removed seconds",
    "elastic_heartbeats_total": "Elastic worker heartbeats written",
    "elastic_heartbeat_misses_total": "Workers declared lost on a stale "
                                      "heartbeat",
    "elastic_reforms_total": "Cluster membership re-formations "
                             "completed",
    "elastic_recoveries_total": "Re-formations caused by a lost worker",
    "elastic_lost_steps_total": "Train steps redone after a recovery "
                                "restore",
    "elastic_collective_failures_total": "Host-collective ops failed "
                                         "under a worker",
    "elastic_yields_total": "Clean checkpoint-and-step-aside worker "
                            "yields",
    "elastic_init_retries_total": "jax.distributed coordinator connect "
                                  "retries",
    "elastic_cache_quarantines_total": "Compile caches quarantined by "
                                       "the crash-loop breaker",
    "elastic_workers": "Live workers in the current generation",
    "elastic_generation": "Current elastic membership generation",
    "elastic_last_recovery_s": "Detect-to-respawn seconds of the last "
                               "recovery",
    "search_queries_total": "Query rows answered by the top-k scanner",
    "search_scans_total": "Query chunks dispatched across the scan "
                          "mesh",
    "search_qps": "Queries per second of the last scan call",
    "search_index_rows": "Rows of the attached embedding index",
    "search_devices": "Devices the index shards scan across",
    "search_scan_s": "Seconds blocked draining one query chunk's "
                     "merged top-k",
    "search_merge_s": "Host dispatch seconds of one chunk's fan-out + "
                      "device-side merge",
    "serve_queue_depth": "Serve micro-batcher queue depth at last "
                         "publish",
    "serve_warm_rungs": "Bucket rungs with AOT-compiled executables",
    "serve_warmup_cumulative_s": "Cumulative AOT warmup compile "
                                 "seconds",
    "serve_time_to_first_batch_s": "Process start to first completed "
                                   "device batch, seconds",
    "serve_head_probs_total": "Classifier-head requests completed",
    "serve_head_features_total": "Pooled-embedding-head requests "
                                 "completed",
    "serve_head_tokens_total": "Token-sequence-head requests completed",
    "serve_head_probs_p99_s": "Rolling p99 total latency, probs head",
    "serve_head_features_p99_s": "Rolling p99 total latency, features "
                                 "head",
    "serve_head_tokens_p99_s": "Rolling p99 total latency, tokens head",
    "serve_tier_interactive_total": "Interactive-tier requests "
                                    "completed",
    "serve_tier_batch_total": "Batch-tier requests completed",
    "serve_tier_interactive_p99_s": "Rolling p99 total latency, "
                                    "interactive tier",
    "serve_tier_batch_p99_s": "Rolling p99 total latency, batch tier",
    "deploy_candidates_total": "Verified trainer steps picked up as "
                               "deploy candidates",
    "deploy_gate_passed_total": "Candidates that passed the offline "
                                "gate",
    "deploy_gate_refused_total": "Candidates the offline gate refused "
                                 "(corrupt/unloadable/eval)",
    "deploy_canaries_total": "Canary replica swaps started",
    "deploy_promotions_total": "Candidates promoted fleet-wide",
    "deploy_rollbacks_total": "Canary/promote cycles rolled back to "
                              "the incumbent",
    "deploy_quarantined_total": "Candidates quarantined with a reason "
                                "file",
    "deploy_shadow_compared_total": "Shadow requests compared canary "
                                    "vs incumbent",
    "deploy_shadow_exceeded_total": "Shadow comparisons past the "
                                    "probs-shift tolerance",
    "deploy_shadow_canary_errors_total": "Shadow probes the canary "
                                         "failed to answer",
    "deploy_phase": "Controller phase (0 idle, 1 gating, 2 canary, "
                    "3 promoting)",
    "deploy_incumbent_step": "Trainer step the incumbent was exported "
                             "from",
    "deploy_candidate_step": "Trainer step of the candidate in flight",
    "deploy_gate_s": "Offline gate seconds (verify+export+eval)",
    "deploy_canary_s": "Canary window seconds, swap to verdict",
    "deploy_promote_s": "Promote seconds, verdict to fleet-wide",
    "cascade_requests_total": "Requests admitted to the cascade",
    "cascade_escalated_total": "Low-margin rows escalated to the "
                               "teacher",
    "cascade_served_student_total": "Requests answered by the student "
                                    "tier",
    "cascade_served_teacher_total": "Requests answered by the teacher "
                                    "tier",
    "cascade_student_failover_total": "Student failures escalated to "
                                      "the teacher unconditionally",
    "cascade_teacher_fallback_total": "Teacher failures answered with "
                                      "the student's low-margin result",
    "cascade_escalation_rate": "Escalated / admitted, running fraction",
    "cascade_threshold": "Softmax-margin escalation threshold in force",
    "cascade_predicted_agreement": "Calibration-predicted top-1 "
                                   "agreement floor at the threshold "
                                   "in force",
    "cascade_margin": "Student softmax margin (top1 - top2) per row",
    "cascade_drift_window_rate": "Rolling-window escalation fraction "
                                 "the drift alarm watches",
    "cascade_drift_expected_rate": "Calibrated escalation-rate "
                                   "expectation the window is judged "
                                   "against",
    "cascade_drift_alarm_active": "1 while the window sits outside the "
                                  "drift band, else 0",
    "cascade_drift_alarms_total": "Drift-alarm firings (band exits, "
                                  "with hysteresis)",
    "trace_spans_total": "Request-trace spans recorded by this process",
    "trace_traces_total": "Complete request traces in the merged view",
    "trace_p50_s": "Merged-trace root-span latency p50 seconds",
    "trace_p90_s": "Merged-trace root-span latency p90 seconds",
    "trace_p99_s": "Merged-trace root-span latency p99 seconds",
    "distill_alpha": "KD soft-target weight in force (0 = plain CE)",
    "distill_t": "KD softmax temperature in force",
    "distill_loss": "Latest KD train loss (blended hard+soft)",
    "distill_teacher_agree_frac": "Per-epoch student/teacher argmax "
                                  "agreement over train batches",
}


class _RollingHistogram:
    """Fixed-window sample reservoir with percentile snapshots (the
    ServeStats reservoir, generalized). NOT thread-safe on its own —
    the registry's lock serializes access."""

    def __init__(self, window: int = DEFAULT_HIST_WINDOW):
        self._samples: deque = deque(maxlen=window)
        self.count_total = 0          # lifetime observations, not window
        self.sum_total = 0.0

    def observe(self, value: float) -> None:
        v = float(value)
        self._samples.append(v)
        self.count_total += 1
        self.sum_total += v

    def snapshot(self) -> Dict[str, Optional[float]]:
        if not self._samples:
            return {"p50": None, "p95": None, "p99": None, "count": 0,
                    "count_total": self.count_total,
                    "sum_total": round(self.sum_total, 6)}
        arr = np.fromiter(self._samples, float)
        p50, p95, p99 = np.percentile(arr, [50.0, 95.0, 99.0])
        return {"p50": round(float(p50), 6), "p95": round(float(p95), 6),
                "p99": round(float(p99), 6), "count": int(arr.size),
                "count_total": self.count_total,
                "sum_total": round(self.sum_total, 6)}


class TelemetryRegistry:
    """Thread-safe shared metrics registry (see module docstring).

    One lock guards everything: every operation is a dict lookup plus a
    scalar update or deque append, so contention is nanoseconds even
    from the training hot loop — the overhead A/B
    (``tools/telemetry_overhead.py``) holds the whole instrumented path
    under the 2% budget.
    """

    def __init__(self, *, hist_window: int = DEFAULT_HIST_WINDOW,
                 event_ring: int = DEFAULT_EVENT_RING):
        # RLock, not Lock: the watchdog's SIGTERM handler snapshots the
        # registry from whatever the interrupted (main) thread was
        # doing — possibly mid-``count()`` with this lock held. A plain
        # Lock would deadlock the handler against its own thread.
        self._lock = threading.RLock()
        self._hist_window = hist_window
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, Any] = {}
        self._hists: Dict[str, _RollingHistogram] = {}
        self._events: deque = deque(maxlen=event_ring)

    # ------------------------------------------------------- instruments
    def count(self, name: str, n: float = 1) -> None:
        """Increment a monotonic counter."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def set_counter(self, name: str, value: float) -> None:
        """Set a counter to an absolute value — the bridge for
        subsystems that keep their own totals (ServeStats, CacheStats)
        and publish point-in-time syncs instead of deltas."""
        with self._lock:
            self._counters[name] = value

    def gauge(self, name: str, value: Any) -> None:
        with self._lock:
            self._gauges[name] = value

    def gauge_max(self, name: str, value: float) -> None:
        """Monotonic high-water gauge: keep the max of the existing
        value and this one — device-memory watermarks
        (:mod:`.profiling`) must survive the sample after a big free."""
        with self._lock:
            prev = self._gauges.get(name)
            if not isinstance(prev, (int, float)) or isinstance(
                    prev, bool) or value > prev:
                self._gauges[name] = value

    def observe(self, name: str, value: float) -> None:
        """Add one sample to a rolling histogram."""
        with self._lock:
            hist = self._hists.get(name)
            if hist is None:
                hist = self._hists[name] = _RollingHistogram(
                    self._hist_window)
            hist.observe(value)

    def event(self, name: str, **fields: Any) -> Dict[str, Any]:
        """Append one event to the ring buffer (the postmortem's
        "what was happening" record); returns the stored dict."""
        record = {"time": time.time(), "event": name, **fields}
        with self._lock:
            self._events.append(record)
        return record

    # --------------------------------------------------------- read side
    def last_events(self, n: int = DEFAULT_EVENT_RING) -> List[Dict]:
        with self._lock:
            return list(self._events)[-n:]

    def snapshot(self) -> Dict[str, Any]:
        """Point-in-time plain-dict view (JSON-serializable)."""
        with self._lock:
            return {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {name: h.snapshot()
                               for name, h in self._hists.items()},
            }

    def to_prometheus(self, prefix: str = "vit_") -> str:
        """Render the registry as Prometheus text exposition format —
        :func:`render_prometheus` over :meth:`snapshot` (ONE renderer
        behind serve's ``::metrics``, ``train.py --metrics-port``, and
        the fleet aggregator's endpoint)."""
        return render_prometheus(self.snapshot(), prefix=prefix)

    def reset(self) -> None:
        """Forget everything — tests only (the process-global registry
        would otherwise leak state between cases)."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._events.clear()


def _fmt(v: float) -> str:
    """Prometheus sample values: integers stay integral, floats use
    repr (full precision, no scientific-notation surprises for the
    magnitudes metrics take)."""
    if isinstance(v, float) and not v.is_integer():
        return repr(v)
    return str(int(v))


def render_prometheus(snap: Dict[str, Any], prefix: str = "vit_",
                      help_text: Optional[Dict[str, str]] = None) -> str:
    """Registry-snapshot-shaped dict -> Prometheus text exposition.

    The ONE renderer (serve ``::metrics``, train ``--metrics-port``,
    ``tools/fleet_agg.py``'s fleet endpoint all call it). Per metric:
    a ``# HELP`` line (from :data:`HELP_TEXT` merged with
    ``help_text``, generic fallback otherwise), a ``# TYPE`` line, then
    samples. Counters/gauges map directly; histograms render as
    summaries — quantile-labeled samples over the rolling window plus
    the lifetime ``_count``/``_sum`` pair. Sample names are EXACTLY
    the pre-HELP-era ones (prefix + sanitized raw name) — dashboards
    keyed on r9 names keep working, asserted by the name-stability
    test. Non-numeric gauges are skipped (they stay visible in the
    JSON snapshot/postmortem)."""
    helps = dict(HELP_TEXT)
    if help_text:
        helps.update(help_text)

    def name_of(raw: str) -> str:
        return prefix + re.sub(r"[^a-zA-Z0-9_:]", "_", raw)

    def header(raw: str, n: str, kind: str) -> List[str]:
        text = helps.get(raw, f"{kind} {raw} (no help registered)")
        return [f"# HELP {n} {text}", f"# TYPE {n} {kind}"]

    lines: List[str] = []
    for raw, v in sorted(snap.get("counters", {}).items()):
        n = name_of(raw)
        lines += header(raw, n, "counter") + [f"{n} {_fmt(v)}"]
    for raw, v in sorted(snap.get("gauges", {}).items()):
        if not isinstance(v, (int, float)) or isinstance(v, bool):
            continue
        n = name_of(raw)
        lines += header(raw, n, "gauge") + [f"{n} {_fmt(v)}"]
    for raw, h in sorted(snap.get("histograms", {}).items()):
        n = name_of(raw)
        lines += header(raw, n, "summary")
        for q, key in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
            if h.get(key) is not None:
                lines.append(f'{n}{{quantile="{q}"}} {_fmt(h[key])}')
        lines.append(f"{n}_count {h['count_total']}")
        lines.append(f"{n}_sum {_fmt(h['sum_total'])}")
    return "\n".join(lines) + "\n"


# The process-global registry every subsystem publishes through by
# default. Constructed eagerly: it is cheap (three dicts and a deque)
# and having exactly one removes every "did you pass the registry"
# wiring question between train/serve/data/compile_cache.
_REGISTRY = TelemetryRegistry()


def get_registry() -> TelemetryRegistry:
    """The process-global :class:`TelemetryRegistry`."""
    return _REGISTRY


def dump_events_jsonl(events: Iterable[Dict], fh) -> int:
    """Write events as JSONL (postmortem tail section); returns count.
    Non-finite floats get the same treatment as MetricsLogger rows
    (NaN -> null, infinities -> signed strings) — a postmortem tail
    must never contain a line strict JSON consumers reject."""
    from ..metrics import _json_safe   # lazy: registry stays jax-free
    n = 0
    for ev in events:
        row = {k: _json_safe(v) for k, v in ev.items()}
        try:
            line = json.dumps(row, default=str, allow_nan=False)
        except ValueError:   # non-finite buried in a nested value: a
            # postmortem must never crash the dump — degrade to repr.
            line = json.dumps({"event": "unserializable", "repr": repr(ev)})
        fh.write(line + "\n")
        n += 1
    return n
