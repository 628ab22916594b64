"""CLI training entry point.

Replaces the reference's notebooks and its broken ``going_modular/train.py``
(which forgets the positional ``lr_scheduler`` arg and raises TypeError —
SURVEY.md §2.1 'Script entry point'). One command trains any preset on an
image-folder dataset, from scratch or from a pretrained backbone, on any
mesh shape, with checkpoints and JSONL metrics:

    python -m pytorch_vit_paper_replication_tpu.train \\
        --train-dir data/pizza_steak_sushi/train \\
        --test-dir data/pizza_steak_sushi/test \\
        --preset ViT-B/16 --epochs 10 --batch-size 32

    # no dataset handy (or offline): --synthetic generates one
    python -m pytorch_vit_paper_replication_tpu.train --synthetic \\
        --preset ViT-Ti/16 --image-size 64 --epochs 2

Multi-host: run the same command per host; per-host data sharding and the
jax.distributed handshake are automatic (--multihost).
"""

from __future__ import annotations

import argparse
import contextlib
import json
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp

from . import engine, parallel
from .checkpoint import Checkpointer
from .configs import LM_PRESETS, MeshConfig, PRESETS, TrainConfig
from .data import create_dataloaders, make_synthetic_image_folder
from .data.transforms import make_transform
from .metrics import MetricsLogger
from .models import ViT
from .optim import head_only_label_fn, make_lr_schedule, make_optimizer
from .transfer import init_from_pretrained
from .utils import (atomic_write_json, count_params, plot_loss_curves,
                    set_seeds)


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        description="TPU-native ViT training",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    data = p.add_argument_group("data")
    data.add_argument("--dataset",
                      choices=["imagefolder", "cifar10", "packed"],
                      default="imagefolder")
    data.add_argument("--train-dir", type=str, default=None,
                      help="train split: image folder, or for --dataset "
                           "packed a data.pack output dir")
    data.add_argument("--test-dir", type=str, default=None)
    data.add_argument("--data-root", type=str, default=None,
                      help="for --dataset cifar10: the cifar-10-batches-py "
                           "dir or the .tar.gz archive")
    data.add_argument("--augment", action="store_true",
                      help="RandomResizedCrop + horizontal-flip train "
                           "augmentation (the standard ImageNet recipe) "
                           "for --dataset imagefolder; eval keeps the "
                           "deterministic transform")
    data.add_argument("--no-augment", action="store_true",
                      help="disable the same augmentation where it is on "
                           "by default (--dataset packed)")
    data.add_argument("--synthetic", action="store_true",
                      help="generate a tiny synthetic dataset (offline demo)")
    data.add_argument("--synthetic-per-class", type=int, default=32,
                      help="train images per class for --synthetic (test "
                      "split gets a quarter); 75 reproduces the reference "
                      "dataset's 225-train-image scale")
    data.add_argument("--synthetic-noise", type=float, default=40.0,
                      help="per-pixel noise sigma for --synthetic; higher "
                      "makes the classes harder (multi-epoch learning "
                      "curves instead of instant separability)")
    data.add_argument("--image-size", type=int, default=224)
    data.add_argument("--num-workers", type=int, default=None)
    data.add_argument("--worker-type", choices=["thread", "process"],
                      default="thread",
                      help="decode-pool flavor: threads (default; PIL/"
                           "libjpeg release the GIL) or forked processes "
                           "(the reference torch DataLoader's num_workers "
                           "semantics — wins on multi-core hosts where "
                           "the transform's numpy stages serialize on "
                           "the GIL)")
    data.add_argument("--shuffle-window", type=int, default=0,
                      help="streaming windowed shuffle: visit shard "
                           "blocks in a seeded shuffled order and mix "
                           "records through an N-record window instead "
                           "of a global permutation — sequential I/O "
                           "and an O(window) record working set, for "
                           "packs much larger than RAM (0 = global "
                           "shuffle). 64k records is a good ImageNet "
                           "value; see SCALING.md for the memory "
                           "budget formula")
    data.add_argument("--readahead", type=int, default=0,
                      help="stream N upcoming shard blocks into the "
                           "page cache ahead of the consumer (packed "
                           "datasets; 2 = double-buffered). 0 = off")
    data.add_argument("--evict-behind", action="store_true",
                      help="drop fully-consumed shard blocks from the "
                           "page cache behind the consumer (with "
                           "--readahead: bounds the resident set to "
                           "O(window + readahead blocks) for packs "
                           "much larger than RAM)")
    data.add_argument("--cache-dataset", action="store_true",
                      help="decode each image once and serve later epochs "
                           "from RAM (tf.data cache() semantics; use when "
                           "the decoded dataset fits host memory)")
    data.add_argument("--no-normalize", action="store_true",
                      help="disable ImageNet normalization (it defaults ON "
                           "for --pretrained runs — the weights' own input "
                           "distribution — and OFF for scratch runs)")

    model = p.add_argument_group("model")
    model.add_argument("--model", choices=["vit", "tinyvgg", "lm"],
                       default="vit",
                       help="tinyvgg = the reference script entry point's "
                            "baseline CNN (going_modular train.py:39-43)")
    model.add_argument("--hidden-units", type=int, default=10,
                       help="TinyVGG conv width (reference train.py:14)")
    model.add_argument("--preset", default="ViT-B/16",
                       choices=sorted(PRESETS) + sorted(LM_PRESETS),
                       help="a ViT preset, or with --model lm a token "
                            "model's (" + ", ".join(sorted(LM_PRESETS))
                            + ")")
    model.add_argument("--steps-per-epoch", type=int, default=8,
                       help="--model lm --synthetic: batches an epoch of "
                            "the seeded token stream")
    model.add_argument("--patch-size", type=int, default=None)
    model.add_argument("--dtype", default="bfloat16",
                       choices=["bfloat16", "float32"])
    model.add_argument("--ln-eps", type=float, default=None,
                       help="LayerNorm epsilon override (default 1e-6; use "
                            "1e-5 for weights ported from torch.nn."
                            "LayerNorm-default models)")
    model.add_argument("--attention", default="auto",
                       choices=["auto", "xla", "flash"])
    model.add_argument("--attention-softmax", default="saturating",
                       choices=["saturating", "exact"],
                       help="XLA-path softmax: 'saturating' skips the "
                            "row-max read (+1.7%% step; exact for logits "
                            "<= ~96, saturates beyond); 'exact' = "
                            "max-subtracted at any magnitude (use under "
                            "attention-logit growth, the ViT-22B/QK-norm "
                            "regime)")
    model.add_argument("--sp-impl", default="ring",
                       choices=["ring", "ulysses"],
                       help="sequence-parallel strategy for --mesh-seq>1: "
                            "'ring' rotates K/V over neighbor ICI (O(T* "
                            "T/K) memory); 'ulysses' re-shards tokens-> "
                            "heads with two all_to_alls (needs heads %% "
                            "seq == 0)")
    model.add_argument("--mlp-impl", default="auto",
                       choices=["auto", "fused", "xla"],
                       help="MLP half-block execution: 'fused' = the "
                            "Pallas LN+MLP+residual kernel (~15%% faster "
                            "steps on v5e), 'auto' = fused on TPU")
    model.add_argument("--pool", default="cls", choices=["cls", "gap"],
                       help="classifier pooling; 'gap' drops the CLS token "
                            "(even token count — required for --mesh-seq "
                            "ring attention on typical shapes)")
    model.add_argument("--dropout", type=float, default=None,
                       help="override ALL three dropout rates (attention/"
                            "MLP/embedding) with one value; 0 makes the "
                            "step fully deterministic given (seed, step) "
                            "— what the elastic trajectory-equivalence "
                            "gate runs with, since dropout noise is "
                            "assigned by position within the LOCAL batch "
                            "and therefore re-draws when the dp "
                            "topology changes. Default: preset rates")
    model.add_argument("--remat", action="store_true")

    train = p.add_argument_group("training (reference recipe defaults)")
    train.add_argument("--epochs", type=int, default=10)
    train.add_argument("--batch-size", type=int, default=32,
                       help="GLOBAL batch size across all devices")
    train.add_argument("--lr", type=float, default=1e-3)
    train.add_argument("--weight-decay", type=float, default=0.03)
    train.add_argument("--warmup-fraction", type=float, default=0.05)
    train.add_argument("--grad-clip", type=float, default=1.0)
    train.add_argument("--label-smoothing", type=float, default=0.0)
    train.add_argument("--seed", type=int, default=42)
    train.add_argument("--grad-accum", type=int, default=1,
                       help="average gradients over N micro-batches per "
                            "optimizer update (effective batch = N x "
                            "--batch-size) — the paper's batch-4096 recipe "
                            "on few chips")
    train.add_argument("--nan-guard", action="store_true",
                       help="skip (don't apply) any update whose loss or "
                            "gradient norm is nonfinite instead of letting "
                            "one bad step poison the weights; skipped "
                            "steps are counted and excluded from metrics")
    train.add_argument("--distill-from", type=str, default=None,
                       metavar="SINK_DIR",
                       help="knowledge distillation: a COMPLETED tools/"
                            "batch_infer.py --head logits output dir, "
                            "dumped by the teacher over this exact train "
                            "split. Teacher rows are gathered per batch "
                            "by record ordinal, so any shuffle/resume "
                            "order stays aligned; the manifest's rows/"
                            "classes/sha256 are verified against the "
                            "split and the sink bytes before the first "
                            "step. The objective becomes engine."
                            "distill_loss (temperature --distill-t KL "
                            "mixed with hard CE at --distill-alpha); "
                            "the emitted checkpoint stays completely "
                            "ordinary")
    train.add_argument("--distill-t", type=float, default=2.0,
                       help="distillation temperature T (the KL term "
                            "compares softmax(logits/T) and is scaled "
                            "by T^2, Hinton et al. 2015)")
    train.add_argument("--distill-alpha", type=float, default=0.5,
                       help="soft-target weight in the KD mix; 0.0 "
                            "reduces bit-exactly to ordinary training, "
                            "1.0 is pure teacher mimicry (the cascade "
                            "student's objective)")
    train.add_argument("--eval-only", action="store_true",
                       help="score a saved model instead of training: load "
                            "the latest checkpoint (or the final/ params "
                            "export) from --checkpoint-dir, run one eval "
                            "pass over the test split, print/log metrics, "
                            "exit. --train-dir becomes optional")
    train.add_argument("--rng-impl", default="unsafe_rbg",
                       choices=["threefry2x32", "rbg", "unsafe_rbg"],
                       help="PRNG for dropout masks; unsafe_rbg is ~18%% "
                            "faster per step on TPU")
    train.add_argument("--extend-schedule", action="store_true",
                       help="allow resuming with a different --epochs than "
                            "the checkpoint was written for: the warmup+"
                            "decay LR schedule is re-scaled to the NEW "
                            "horizon, which re-opens decay — a converged "
                            "model restored mid/post-decay suddenly sees a "
                            "mid-schedule LR (the measured 3.05 loss spike "
                            "at epoch 31 of runs/longrun_r4). Without this "
                            "flag a horizon change on resume is an error")

    transfer = p.add_argument_group("transfer learning")
    transfer.add_argument("--pretrained", type=str, default=None,
                          help="torch .pth state_dict to initialize the "
                               "backbone from")
    transfer.add_argument("--freeze-backbone", action="store_true",
                          help="train the classifier head only")

    elastic = p.add_argument_group("elastic (parallel/elastic.py)")
    elastic.add_argument("--elastic", type=int, default=0, metavar="N",
                         help="supervise N elastic workers of this exact "
                              "command instead of training directly: "
                              "heartbeat-monitored worker processes, "
                              "automatic mesh re-formation on a lost "
                              "worker (dp axis shrinks to the "
                              "survivors, restore from the last "
                              "verified rotating checkpoint through "
                              "the compile cache), and scale-back-up "
                              "when the host rejoins. Requires "
                              "--checkpoint-dir; pair with "
                              "--checkpoint-every-steps to bound "
                              "redone work. 0 = off")
    elastic.add_argument("--elastic-backend", default="host",
                         choices=["host", "jax"],
                         help="worker cluster flavor: 'host' = "
                              "independent single-process JAX workers "
                              "with gradients summed across processes "
                              "through the supervisor's TCP allreduce "
                              "(runs anywhere); 'jax' = a real "
                              "jax.distributed cluster re-initialized "
                              "per generation (TPU pods)")
    elastic.add_argument("--elastic-heartbeat-s", type=float, default=1.0,
                         help="worker heartbeat cadence into the "
                              "rendezvous directory")
    elastic.add_argument("--elastic-timeout-s", type=float, default=15.0,
                         help="supervisor declares a worker lost when "
                              "its heartbeat is older than this (a "
                              "hung-but-alive process counts as lost "
                              "and is killed)")
    elastic.add_argument("--elastic-rejoin-s", type=float, default=0.0,
                         help="scale back up to the full worker count "
                              "this many seconds after a loss (a "
                              "graceful checkpoint-handoff "
                              "re-formation: zero lost steps). "
                              "0 = stay on the survivors")
    elastic.add_argument("--elastic-local-devices", type=int, default=0,
                         help="give each worker its own K-virtual-"
                              "device CPU split (the 2-process CPU "
                              "cluster recipe; sets JAX_PLATFORMS=cpu "
                              "for the workers). 0 = inherit the "
                              "environment untouched")
    elastic.add_argument("--elastic-rendezvous", type=str, default=None,
                         help="shared rendezvous directory for "
                              "heartbeats/membership (default: "
                              "<checkpoint-dir>/elastic)")
    # Internal per-worker wiring, set by the supervisor when it spawns:
    elastic.add_argument("--elastic-worker-id", type=int, default=None,
                         help=argparse.SUPPRESS)
    elastic.add_argument("--elastic-process-count", type=int, default=1,
                         help=argparse.SUPPRESS)
    elastic.add_argument("--elastic-generation", type=int, default=0,
                         help=argparse.SUPPRESS)
    elastic.add_argument("--elastic-collective", type=str, default=None,
                         help=argparse.SUPPRESS)

    dist = p.add_argument_group("distributed")
    dist.add_argument("--mesh-data", type=int, default=-1,
                      help="-1 = all remaining devices")
    dist.add_argument("--mesh-model", type=int, default=1,
                      help="tensor parallelism (attention heads / MLP "
                           "hidden sharded)")
    dist.add_argument("--mesh-seq", type=int, default=1,
                      help="sequence parallelism (ring attention over the "
                           "token axis)")
    dist.add_argument("--mesh-pipe", type=int, default=1,
                      help="pipeline parallelism (encoder layers staged "
                           "over the axis, GPipe microbatching; composes "
                           "with --mesh-data)")
    dist.add_argument("--pipe-microbatches", type=int, default=0,
                      help="GPipe microbatches per step (default: the "
                           "pipe axis size); must divide the per-data-"
                           "shard batch")
    dist.add_argument("--multihost", action="store_true")

    out = p.add_argument_group("output")
    out.add_argument("--checkpoint-dir", type=str, default=None)
    out.add_argument("--keep-checkpoints", type=int, default=3)
    out.add_argument("--checkpoint-every-steps", type=int, default=0,
                     help="also checkpoint every N train steps (not just "
                          "per epoch); resume continues mid-epoch, skipping "
                          "the already-trained batches of the interrupted "
                          "epoch's deterministic order. The unit is micro-"
                          "steps: under --grad-accum K this fires every N "
                          "micro-batches, i.e. every N/K optimizer updates")
    out.add_argument("--sync-checkpoints", action="store_true",
                     help="synchronous (blocking) checkpoint saves "
                     "instead of Orbax's background writer")
    out.add_argument("--checkpoint-every-epochs", type=int, default=1,
                     help="save cadence in epochs (final epoch always "
                     "saves); raise for long cheap-epoch runs where "
                     "per-epoch saves dominate wall time")
    out.add_argument("--metrics-jsonl", type=str, default=None)
    out.add_argument("--tensorboard-dir", type=str, default=None,
                     help="write TensorBoard scalars here")
    out.add_argument("--plot", type=str, default=None,
                     help="save loss curves PNG here")

    obs = p.add_argument_group("observability (telemetry/)")
    obs.add_argument("--telemetry-jsonl", type=str, default=None,
                     help="per-step span telemetry stream (sampled "
                          "'step' rows + per-epoch goodput summaries: "
                          "data-wait vs device seconds, step p50/p95/"
                          "p99, goodput %%, live img/s + analytic MFU); "
                          "render with tools/trace_report.py")
    obs.add_argument("--telemetry-every", type=int, default=32,
                     help="telemetry sampling cadence: one JSONL step "
                          "row and one block_until_ready honesty "
                          "barrier per N steps (the barrier keeps async "
                          "dispatch from skewing the data-wait/device "
                          "split; overhead is gated < 2%% by bench.py's "
                          "telemetry_overhead_ok)")
    obs.add_argument("--watchdog-s", type=float, default=0.0,
                     help="stall watchdog deadline: if no train step/"
                          "span completes for this many seconds, dump "
                          "all-thread stacks + memory + the last "
                          "telemetry events to the postmortem file "
                          "instead of freezing silently; the same dump "
                          "fires on SIGTERM (preemption forensics). "
                          "0 = off")
    obs.add_argument("--postmortem", type=str, default=None,
                     help="watchdog postmortem path (default: "
                          "postmortem.txt next to --checkpoint-dir or "
                          "--telemetry-jsonl, else ./postmortem.txt)")
    obs.add_argument("--profile-steps", type=str, default=None,
                     metavar="A:B",
                     help="capture the device trace of global steps "
                          "A..B (inclusive) into the run's profile "
                          "dir, and when the window closes print and "
                          "write (device_time.json) the step's device "
                          "time by module and by forward / backward / "
                          "optimizer (telemetry/device_trace.py). The "
                          "capture is of the device alone: the host "
                          "tracer slows the feed it records. "
                          "A running trainer can also be captured "
                          "without flags: SIGUSR2 arms a window over "
                          "the next steps")
    obs.add_argument("--profile-auto", action="store_true",
                     help="auto-capture on step-time anomalies: when "
                          "the rolling p50 of barrier-amortized step "
                          "walls regresses more than "
                          "--profile-auto-pct over the anchored "
                          "baseline, a capture window over the next "
                          "steps is armed automatically — the trace "
                          "of the regression is taken WHILE it is "
                          "happening")
    obs.add_argument("--profile-auto-pct", type=float, default=25.0,
                     help="anomaly threshold for --profile-auto "
                          "(percent p50 regression)")
    obs.add_argument("--profile-trace-dir", type=str, default=None,
                     help="capture destination (default: profiles/ "
                          "next to --checkpoint-dir or "
                          "--telemetry-jsonl)")
    obs.add_argument("--metrics-port", type=int, default=None,
                     help="serve the telemetry registry as Prometheus "
                          "text on http://127.0.0.1:PORT/metrics "
                          "(stdlib HTTP; 0 = pick a free port) — "
                          "train becomes scrapeable/health-checkable "
                          "like serve's ::metrics. Default: off")
    obs.add_argument("--ship-to", type=str, default=None,
                     metavar="HOST:PORT",
                     help="push registry snapshots to a "
                          "tools/fleet_agg.py aggregator every "
                          "--ship-interval-s (drop-don't-block: a "
                          "dead aggregator costs dropped frames, "
                          "never a stalled step)")
    obs.add_argument("--ship-interval-s", type=float, default=2.0,
                     help="shipper cadence for --ship-to")
    obs.add_argument("--worker-id", type=str, default=None,
                     help="identity in the fleet view (default "
                          "train-<host>-<pid>)")
    from .compile_cache import add_cache_cli
    add_cache_cli(p)
    return p


# The canonical loader lives in the distill/ package (ISSUE 19); the
# re-export keeps `from ...train import load_distill_sink` — the import
# path the refusal tests and older scripts pin — stable.
from .distill.sink import load_distill_sink  # noqa: E402,F401


def _run_elastic_supervisor(args, argv) -> dict:
    """``--elastic N`` without worker wiring: this process supervises N
    spawned copies of the same command (parallel/elastic.py owns the
    loop); training happens only in the workers."""
    import sys

    from .parallel.elastic import ElasticSupervisor

    if not args.checkpoint_dir:
        raise SystemExit(
            "--elastic requires --checkpoint-dir: recovery re-forms the "
            "cluster FROM the rotating checkpoint")
    if args.multihost:
        raise SystemExit("--elastic and --multihost are exclusive (the "
                         "elastic supervisor owns cluster formation)")
    if not args.checkpoint_every_steps:
        print("[elastic] note: no --checkpoint-every-steps — a lost "
              "worker redoes everything since the last EPOCH save; a "
              "step cadence bounds redone work to ~cadence/2")
    rendezvous = args.elastic_rendezvous or str(
        Path(args.checkpoint_dir) / "elastic")
    sup = ElasticSupervisor(
        argv if argv is not None else sys.argv[1:],
        num_workers=args.elastic, rendezvous=rendezvous,
        checkpoint_dir=args.checkpoint_dir,
        backend=args.elastic_backend,
        heartbeat_s=args.elastic_heartbeat_s,
        timeout_s=args.elastic_timeout_s,
        rejoin_s=args.elastic_rejoin_s,
        local_devices=args.elastic_local_devices)
    summary = sup.run()
    if summary["result"] != "completed":
        raise SystemExit(1)
    return {"elastic_supervisor": summary}


def main(argv=None) -> dict:
    args = build_parser().parse_args(argv)
    if args.elastic and args.elastic_worker_id is None:
        return _run_elastic_supervisor(args, argv)
    # Pure CLI preconditions: a typo'd window/address must fail before
    # the minutes of data/model/jit setup, not after.
    profile_window = None
    if args.profile_steps:
        from .telemetry import parse_profile_steps
        try:
            profile_window = parse_profile_steps(args.profile_steps)
        except ValueError as e:
            raise SystemExit(str(e))
    if args.ship_to:
        from .telemetry.shipper import parse_address
        try:
            parse_address(args.ship_to)
        except ValueError as e:
            raise SystemExit(f"--ship-to: {e}")
    if args.multihost:
        parallel.initialize_multi_host()
    elastic_ctx = None
    if args.elastic_worker_id is not None:
        # Supervised elastic worker: heartbeats + membership watch +
        # (host backend) the cross-process gradient collective. Started
        # BEFORE data/model setup so a slow pack open never reads as a
        # dead worker.
        from .parallel.elastic import ElasticWorkerContext
        if args.multihost:
            raise SystemExit("--elastic-worker-id and --multihost are "
                             "exclusive")
        rendezvous = args.elastic_rendezvous or (
            str(Path(args.checkpoint_dir) / "elastic")
            if args.checkpoint_dir else None)
        if rendezvous is None:
            raise SystemExit("elastic worker needs --elastic-rendezvous "
                             "or --checkpoint-dir")
        if args.elastic_backend == "jax":
            # Real pod: (re-)join the jax.distributed cluster of this
            # generation, with retry/backoff — the coordinator of a
            # freshly re-formed cluster comes up concurrently.
            if not args.elastic_collective:
                raise SystemExit(
                    "elastic jax backend needs --elastic-collective "
                    "HOST:PORT (the generation's jax.distributed "
                    "coordinator; the supervisor assigns one per "
                    "generation)")
            parallel.initialize_multi_host(
                coordinator_address=args.elastic_collective,
                num_processes=args.elastic_process_count,
                process_id=args.elastic_worker_id,
                retries=5, backoff_s=1.0,
                reinitialize=args.elastic_generation > 0)
        elastic_ctx = ElasticWorkerContext(
            rendezvous, worker_id=args.elastic_worker_id,
            process_count=args.elastic_process_count,
            generation=args.elastic_generation,
            backend=args.elastic_backend,
            collective_address=(args.elastic_collective
                                if args.elastic_backend == "host"
                                else None),
            heartbeat_s=args.elastic_heartbeat_s).start()
        print(f"elastic worker {args.elastic_worker_id}/"
              f"{args.elastic_process_count} gen "
              f"{args.elastic_generation} ({args.elastic_backend} "
              f"backend), rendezvous {rendezvous}")
    if elastic_ctx is not None and args.elastic_backend == "host":
        # Host-backend data sharding is supervisor-assigned, not
        # jax-derived: each worker is a single-process JAX instance.
        proc_idx, proc_cnt = elastic_ctx.process_info()
    else:
        proc_idx, proc_cnt = parallel.process_info()

    cfg_kwargs = dict(image_size=args.image_size, dtype=args.dtype,
                      attention_impl=args.attention,
                      attention_softmax=args.attention_softmax,
                      mlp_impl=args.mlp_impl, remat=args.remat,
                      pool=args.pool)
    if args.patch_size:
        cfg_kwargs["patch_size"] = args.patch_size
    if args.ln_eps is not None:
        cfg_kwargs["ln_epsilon"] = args.ln_eps
    if args.dropout is not None:
        cfg_kwargs.update(attn_dropout=args.dropout,
                          mlp_dropout=args.dropout,
                          embedding_dropout=args.dropout)

    # Persistent compile cache BEFORE the first jit: a restart (e.g.
    # preemption recovery) then pays a cache read instead of the full
    # XLA compile — time_to_first_step in the run log is the receipt.
    from .compile_cache import configure
    print(f"compile cache: {configure(args.compile_cache_dir)}")

    rng = set_seeds(args.seed)

    if args.eval_only:
        if not args.checkpoint_dir:
            # Pure CLI precondition: fail before any data/model/jit setup.
            raise SystemExit("--eval-only requires --checkpoint-dir")
        if not args.train_dir and args.test_dir:
            # Eval needs no train split; reuse the test dir so the loader
            # plumbing (class names, transform decisions) works unchanged.
            args.train_dir = args.test_dir

    # Data -----------------------------------------------------------------
    assert args.batch_size % proc_cnt == 0, "global batch % hosts != 0"
    loader_kwargs = dict(
        batch_size=args.batch_size // proc_cnt,
        seed=args.seed, process_index=proc_idx, process_count=proc_cnt,
        worker_type=args.worker_type,
        shuffle_window=args.shuffle_window, readahead=args.readahead,
        evict_behind=args.evict_behind)
    if args.num_workers is not None:
        loader_kwargs["num_workers"] = args.num_workers
    # ONE transform decision, shared with predict via transform.json below:
    # pretrained runs get the weights' own eval transform (resize-shorter +
    # center-crop + ImageNet normalize, reference main nb cell 117).
    transform_spec = dict(
        image_size=args.image_size, pretrained=bool(args.pretrained),
        normalize=False if args.no_normalize else bool(args.pretrained))

    if args.augment and args.dataset == "cifar10":
        raise SystemExit(
            "--augment (RandomResizedCrop) is for --dataset imagefolder; "
            "the cifar10 path has no augmentation support")
    if args.augment and args.dataset == "packed":
        print("[info] --augment is already the default for --dataset packed")

    if args.model == "lm":
        # A token model trains on packed token sequences; the only
        # source here is the seeded stream (data/tokens.py).
        if args.preset not in LM_PRESETS:
            raise SystemExit(f"--model lm takes a token model's preset "
                             f"({', '.join(sorted(LM_PRESETS))}), not "
                             f"{args.preset!r}")
        if not args.synthetic:
            raise SystemExit("--model lm reads no corpus yet: pass "
                             "--synthetic (a seeded, predictable token "
                             "stream)")
        if (args.pretrained or args.freeze_backbone or args.distill_from
                or args.mesh_pipe != 1 or args.elastic_worker_id is not None
                or args.label_smoothing):
            raise SystemExit(
                "--model lm trains from scratch on plain cross entropy, "
                "data-parallel: --pretrained/--freeze-backbone/"
                "--distill-from/--label-smoothing/--mesh-pipe/--elastic "
                "do not apply")
        from .data.tokens import TokenLoader, TokenSource
        # (--remat adds recomputation; a preset that has it keeps it)
        cfg = LM_PRESETS[args.preset](
            dtype=args.dtype, attention_impl=args.attention,
            attention_softmax=args.attention_softmax,
            **({"remat": True} if args.remat else {}))
        source = TokenSource(args.seed, cfg.vocab_size, cfg.seq_len)
        train_dl = TokenLoader(source, loader_kwargs["batch_size"],
                               args.steps_per_epoch,
                               stream=2 * proc_idx)
        test_dl = TokenLoader(source, loader_kwargs["batch_size"],
                              max(1, args.steps_per_epoch // 4),
                              stream=2 * proc_idx + 1)
        class_names = [f"{cfg.vocab_size} vocabulary rows x {cfg.seq_len} "
                       "tokens"]
    elif args.dataset == "cifar10":
        from .data import DataLoader, ResizedArrayDataset, load_cifar10, \
            make_fake_cifar10
        # CIFAR preprocessing is a plain square resize (+ optional
        # normalize) — record THAT in transform.json, not the pretrained
        # resize-shorter+crop pipeline, or predict would preprocess
        # differently than training did.
        transform_spec["pretrained"] = False
        if args.synthetic:
            root = make_fake_cifar10(
                Path(tempfile.mkdtemp(prefix="cifar_fake_")))
        elif args.data_root:
            root = args.data_root
        else:
            raise SystemExit(
                "--data-root required for --dataset cifar10 (or pass "
                "--synthetic)")
        train_ds, test_ds = load_cifar10(root)
        train_ds = ResizedArrayDataset(train_ds, args.image_size,
                                       normalize=transform_spec["normalize"])
        test_ds = ResizedArrayDataset(test_ds, args.image_size,
                                      normalize=transform_spec["normalize"])
        if args.cache_dataset:
            # Deliberately ignored: real CIFAR-10 resized to 224px is ~45 GB
            # of float32 — caching it would OOM typical hosts, and at the
            # native 32px the resize being skipped is trivially cheap.
            print("[warn] --cache-dataset has no effect with "
                  "--dataset cifar10 (resized CIFAR would not fit host RAM)")
        train_dl = DataLoader(train_ds, shuffle=True, drop_last=True,
                              **loader_kwargs)
        test_dl = DataLoader(test_ds, shuffle=False, pad_shards=True,
                             **loader_kwargs)
        class_names = list(train_ds.classes)
    elif args.dataset == "packed":
        from .data import create_packed_dataloaders
        if not args.train_dir or not args.test_dir:
            raise SystemExit(
                "--train-dir/--test-dir (pack_image_folder outputs) "
                "required for --dataset packed; build them with "
                "python -m pytorch_vit_paper_replication_tpu.data.pack")
        augment = not args.no_augment  # ImageNet recipe default: on
        train_dl, test_dl, class_names = create_packed_dataloaders(
            args.train_dir, args.test_dir, image_size=args.image_size,
            normalize=transform_spec["normalize"], augment=augment,
            num_workers=args.num_workers,
            worker_type=args.worker_type,
            batch_size=loader_kwargs["batch_size"], seed=args.seed,
            process_index=proc_idx, process_count=proc_cnt,
            shuffle_window=args.shuffle_window, readahead=args.readahead,
            evict_behind=args.evict_behind)
        # Packed eval sees ResizeShorter(pack_size) + CenterCrop(image_size)
        # of the original image; record exactly that in transform.json so
        # predict.py crops the identical region (the "pretrained" pipeline
        # with the pack size as the shorter-side target).
        pack_size = train_dl.dataset.pack_size
        if args.image_size > pack_size:
            # Training would crop pack_size then bilinearly upscale, while
            # predict.py (via transform.json) would resize the ORIGINAL to
            # image_size — different pixels (ADVICE r2). No silent
            # divergence: the shards simply lack the resolution asked for.
            raise SystemExit(
                f"--image-size {args.image_size} exceeds the shards' pack "
                f"size {pack_size}: packed records have no more resolution "
                f"to offer, and eval/predict geometry would diverge. "
                f"Re-pack with pack_size >= {args.image_size} "
                f"(python -m pytorch_vit_paper_replication_tpu.data.pack "
                f"--pack-size {args.image_size} ...)")
        transform_spec["pretrained"] = True
        transform_spec["resize_size"] = pack_size
        if args.cache_dataset:
            print("[warn] --cache-dataset has no effect with --dataset "
                  "packed (shards are already decode-free via memmap)")
    else:
        if args.synthetic:
            tmp = Path(tempfile.mkdtemp(prefix="vit_synth_"))
            train_dir, test_dir = make_synthetic_image_folder(
                tmp, train_per_class=args.synthetic_per_class,
                test_per_class=max(1, args.synthetic_per_class // 4),
                image_size=args.image_size,
                noise_sigma=args.synthetic_noise)
        else:
            if not args.train_dir or not args.test_dir:
                raise SystemExit(
                    "--train-dir/--test-dir required (or pass --synthetic)")
            train_dir, test_dir = args.train_dir, args.test_dir
        transform = make_transform(**transform_spec)
        if args.augment:
            # Augment the train split only; eval (and predict, via
            # transform.json) keeps the deterministic pipeline. cache=True
            # warn-and-skips the stochastic train side automatically.
            # Seeded like the packed path: statistically reproducible
            # from --seed (thread scheduling permutes the draws).
            from .data.transforms import ThreadLocalRng, augment_transform
            train_transform = augment_transform(
                args.image_size, normalize=transform_spec["normalize"],
                rng=ThreadLocalRng(args.seed))
        else:
            train_transform = transform
        train_dl, test_dl, class_names = create_dataloaders(
            train_dir, test_dir, train_transform, eval_transform=transform,
            drop_last_train=True, cache=args.cache_dataset, **loader_kwargs)
        # The native decoder falls back to PIL without a word when g++
        # or libjpeg is missing; say which one feeds this run.
        from . import native
        print("jpeg decoder: "
              + ("native" if native.available() else "PIL"))
    print(f"classes: {class_names} | train batches/epoch: {len(train_dl)}")

    distill_rows = None
    if args.distill_from:
        if args.eval_only:
            raise SystemExit("--distill-from does nothing under "
                             "--eval-only; drop one of the two")
        if args.elastic or args.elastic_worker_id is not None:
            raise SystemExit(
                "--distill-from is not supported under --elastic (the "
                "host-collective step has no KD objective); distill on "
                "one worker, then serve/deploy the checkpoint elastically")
        distill_rows, distill_manifest = load_distill_sink(
            args.distill_from, n_records=len(train_dl.dataset),
            n_classes=len(class_names))
        # The loader tags each batch with its rows' dataset ordinals so
        # the gather below survives shuffling and mid-epoch resume.
        train_dl.emit_indices = True
        print(f"distillation: teacher sink {args.distill_from} "
              f"({distill_manifest['total_records']} records x "
              f"{distill_manifest['out_dim']} classes, teacher "
              f"fingerprint {distill_manifest['fingerprint']}) | "
              f"t={args.distill_t:g} alpha={args.distill_alpha:g}")
        # The KD hyperparameters in force, on the process registry: a
        # scraped/shipped run is attributable as a distillation run
        # without reading its argv (engine.train publishes the moving
        # distill_loss / distill_teacher_agree_frac pair per epoch).
        from .telemetry import get_registry
        get_registry().gauge("distill_alpha", args.distill_alpha)
        get_registry().gauge("distill_t", args.distill_t)

    if args.model == "lm":
        model = ViT(cfg)
        model_name = args.preset
    elif args.model == "tinyvgg":
        # Reference script-entry parity (going_modular train.py:39-43).
        if args.pretrained or args.freeze_backbone:
            raise SystemExit(
                "--pretrained/--freeze-backbone apply to ViT only")
        if args.mesh_model != 1 or args.mesh_seq != 1:
            raise SystemExit("--model tinyvgg supports data parallelism "
                             "only (no TP/SP shardings for a 2-block CNN)")
        from .models import TinyVGG
        cfg = None
        model = TinyVGG(hidden_units=args.hidden_units,
                        num_classes=len(class_names), dtype=args.dtype)
        model_name = f"TinyVGG({args.hidden_units})"
    else:
        cfg = PRESETS[args.preset](num_classes=len(class_names), **cfg_kwargs)
        model = ViT(cfg)
        model_name = args.preset

    # Mesh + state ---------------------------------------------------------
    mesh = parallel.make_mesh(
        MeshConfig(data=args.mesh_data, model=args.mesh_model,
                   seq=args.mesh_seq, pipe=args.mesh_pipe))
    if args.batch_size % mesh.shape["data"] != 0:
        raise SystemExit(
            f"--batch-size {args.batch_size} not divisible by the mesh "
            f"'data' axis size {mesh.shape['data']}")
    if cfg is not None:
        parallel.validate_mesh_for_config(cfg, mesh)
    pipe_stages = mesh.shape.get("pipe", 1)
    microbatches = args.pipe_microbatches or pipe_stages
    if pipe_stages > 1:
        if cfg is None:
            raise SystemExit("--mesh-pipe applies to --model vit only")
        try:
            parallel.validate_pipeline(cfg, mesh, microbatches,
                                       args.batch_size)
        except ValueError as e:
            raise SystemExit(str(e))
    train_cfg = TrainConfig(
        batch_size=args.batch_size, epochs=args.epochs,
        learning_rate=args.lr, weight_decay=args.weight_decay,
        warmup_fraction=args.warmup_fraction, grad_clip_norm=args.grad_clip,
        label_smoothing=args.label_smoothing, seed=args.seed,
        freeze_backbone=args.freeze_backbone)

    steps_per_epoch = len(train_dl)
    total_steps = steps_per_epoch * args.epochs
    accum = max(1, args.grad_accum)
    if args.eval_only:
        # --eval-only never trains, so a tiny/absent train split is fine —
        # and the checkpoint's own grad_accum must win: the restore
        # template's opt_state structure (MultiSteps vs plain) has to
        # match what was saved, without the user re-passing --grad-accum.
        meta_p = Path(args.checkpoint_dir) / "run_meta.json"
        if meta_p.is_file():
            accum = max(1, json.loads(meta_p.read_text()).get("grad_accum",
                                                              accum))
    elif accum > total_steps:
        raise SystemExit(
            f"--grad-accum {accum} exceeds the run's {total_steps} total "
            "micro-steps: no optimizer update would ever be applied")
    tx = make_optimizer(
        train_cfg, max(1, total_steps // accum),
        trainable_label_fn=head_only_label_fn if train_cfg.freeze_backbone
        else None, grad_accum_steps=accum,
        # Stacked [L,...] blocks need the layout-aware ndim rule or 2-D
        # stacked biases/LN params would wrongly receive weight decay.
        decay_mask_fn=parallel.pipeline_decay_mask if pipe_stages > 1
        else None)
    if accum > 1:
        print(f"gradient accumulation: {accum} micro-batches/update "
              f"(effective batch {args.batch_size * accum})")
        if getattr(args, "checkpoint_every_steps", 0):
            # The unit changed from optimizer steps to micro-steps when
            # grad accumulation landed (ADVICE r3): make the cadence
            # explicit so unchanged invocations aren't surprised.
            print(f"note: --checkpoint-every-steps counts MICRO-steps — "
                  f"{args.checkpoint_every_steps} micro-steps = "
                  f"{args.checkpoint_every_steps / accum:g} optimizer "
                  f"updates at this accumulation")

    if args.pretrained:
        params = init_from_pretrained(model, cfg, args.pretrained, rng=rng)
        print(f"initialized backbone from {args.pretrained}")
    elif args.model == "lm":
        # Jitted, so that only the initialisers run: the shapes do not
        # depend on the sequence, and the forward pass is dead code.
        params = jax.jit(model.init)(
            rng, jnp.zeros((1, 8), jnp.int32))["params"]
    else:
        dummy = jnp.zeros((1, args.image_size, args.image_size, 3))
        params = model.init(rng, dummy)["params"]
    dev0 = mesh.devices.flat[0]
    print(f"model: {model_name} | params: {count_params(params):,} | "
          f"mesh: {dict(mesh.shape)} | devices: {jax.device_count()} | "
          f"platform: {dev0.platform} | device_kind: {dev0.device_kind}")

    dropout_rng = jax.random.key(args.seed, impl=args.rng_impl)
    apply_fn = model.apply
    std_params_template = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if pipe_stages > 1:
        # Pipeline layout: blocks stacked [L, ...] and sharded over
        # 'pipe'; the apply_fn swap is the ONLY change — engine and the
        # step builders are layout-agnostic (pure steps pay off again).
        params = parallel.stack_block_params(params, cfg.num_layers)
        apply_fn = parallel.make_pipeline_apply(
            cfg, mesh, num_microbatches=microbatches)
        print(f"pipeline: {pipe_stages} stages x "
              f"{cfg.num_layers // pipe_stages} layers, "
              f"{microbatches} microbatches")
    state = engine.TrainState.create(
        apply_fn=apply_fn, params=params, tx=tx, rng=dropout_rng)
    state = parallel.shard_train_state(state, mesh)
    train_step = parallel.make_parallel_train_step(
        state, mesh, label_smoothing=args.label_smoothing,
        nan_guard=args.nan_guard, sp_impl=args.sp_impl,
        distill_alpha=(args.distill_alpha if args.distill_from
                       else None),
        distill_t=args.distill_t)
    eval_step = parallel.make_parallel_eval_step(state, mesh,
                                                 sp_impl=args.sp_impl)
    if elastic_ctx is not None and args.elastic_backend == "host":
        # dp across worker PROCESSES rides the supervisor's TCP
        # allreduce: local gradient sums out, one global optimizer
        # update in — the same math as a pod's psum, host-side because
        # these workers are independent JAX instances. The local mesh
        # (dp over this worker's own devices) stays as built above.
        from .parallel.elastic import (make_host_collective_eval_step,
                                       make_host_collective_train_step)
        train_step = make_host_collective_train_step(
            state, collective=elastic_ctx.collective,
            label_smoothing=args.label_smoothing,
            nan_guard=args.nan_guard, on_step=elastic_ctx.record_loss)
        eval_step = make_host_collective_eval_step(
            eval_step, elastic_ctx.collective)

    checkpointer = (Checkpointer(args.checkpoint_dir,
                                 max_to_keep=args.keep_checkpoints,
                                 async_save=not args.sync_checkpoints)
                    if args.checkpoint_dir else None)
    epochs_to_run = args.epochs
    done_epochs = 0
    skip_batches = 0
    meta_path = (Path(args.checkpoint_dir) / "run_meta.json"
                 if args.checkpoint_dir else None)
    if (not args.eval_only and checkpointer is not None
            and checkpointer.latest_step() is not None):
        if elastic_ctx is not None:
            # Recovery restore: a torn/corrupt newest step (the save a
            # preemption interrupted) falls back to the previous good
            # one instead of killing the re-formed cluster.
            state = checkpointer.restore_latest_verified(state)
        else:
            state = checkpointer.restore(state)
        done_steps = int(jax.device_get(state.step))
        done_epochs = done_steps // max(1, steps_per_epoch)
        skip_batches = done_steps % max(1, steps_per_epoch)
        epochs_to_run = max(0, args.epochs - done_epochs)
        # done_epochs/skip_batches are derived from steps_per_epoch, which
        # must match the interrupted run's — a different batch size or
        # dataset would silently mis-slice the resumed epoch.
        if meta_path.is_file():
            meta = json.loads(meta_path.read_text())
            # Schedule-horizon guard (r4 VERDICT #6): resuming with a
            # different schedule length — a different --epochs, OR the
            # same epochs over a changed steps_per_epoch (batch size /
            # dataset change at an epoch boundary) — silently re-scales
            # the warmup+decay schedule: a converged model restored
            # after full decay lands back at a mid-schedule LR (the
            # epoch-31 3.05 loss spike in runs/longrun_r4). Make that an
            # explicit choice.
            meta_epochs = meta.get("epochs")
            old_spe = meta.get("steps_per_epoch", steps_per_epoch)
            if (meta_epochs is not None
                    and meta_epochs * old_spe != total_steps):
                msg = (f"schedule horizon change on resume: checkpoint "
                       f"was written for --epochs {meta_epochs} x "
                       f"{old_spe} steps/epoch (LR schedule over "
                       f"{meta_epochs * old_spe} micro-steps), this run "
                       f"schedules over {total_steps} ({args.epochs} x "
                       f"{steps_per_epoch}); re-scaling re-opens "
                       f"warmup/decay at the restored step")
                if not args.extend_schedule:
                    raise SystemExit(
                        msg + " — pass --extend-schedule to accept the "
                        "re-scaled schedule (reference-notebook-style "
                        "manual continuation, main nb cell 98), or rerun "
                        f"with --epochs {meta_epochs} and the original "
                        "batch size/dataset")
                print(f"[extend-schedule] {msg}")
            if meta.get("steps_per_epoch") != steps_per_epoch:
                msg = (f"resume mismatch: checkpoint was written with "
                       f"steps_per_epoch={meta.get('steps_per_epoch')} "
                       f"(batch {meta.get('global_batch_size')}), this run "
                       f"has {steps_per_epoch} (batch {args.batch_size})")
                if skip_batches:
                    raise SystemExit(
                        msg + " — mid-epoch resume would skip a wrong-"
                        "sized prefix; rerun with the original batch "
                        "size/dataset")
                print(f"[warn] {msg}; epoch accounting and the LR "
                      "schedule's remaining length shift accordingly")
            if meta.get("grad_accum", 1) != accum:
                # Same-k MultiSteps state restores silently for any k, so
                # this is the only guard against resuming with a different
                # effective batch + LR schedule (accum=1 vs >1 would fail
                # later, but only as a cryptic orbax structure error).
                raise SystemExit(
                    f"resume mismatch: checkpoint used "
                    f"--grad-accum {meta.get('grad_accum', 1)}, this run "
                    f"uses {accum}; rerun with the original value")
        # Continue the per-epoch shuffle sequence where the run left off
        # (the loader derives order from (seed, epoch)); a mid-epoch
        # checkpoint additionally skips the interrupted epoch's
        # already-trained batch prefix — index-level in the loader, so
        # skipped batches never touch the decode pipeline.
        train_dl.epoch = done_epochs
        train_dl.skip_next_batches = skip_batches
        print(f"resumed from step {done_steps} "
              f"({done_epochs}/{args.epochs} epochs done"
              + (f" + {skip_batches} steps" if skip_batches else "")
              + f"; {epochs_to_run} to run)")
    if (meta_path is not None and not args.eval_only
            and (elastic_ctx is None or elastic_ctx.is_primary)):
        meta_path.parent.mkdir(parents=True, exist_ok=True)
        # Atomic (temp+os.replace): a preemption landing mid-write must
        # not tear the resume-contract file the NEXT restart validates
        # against (vitlint atomic-manifest).
        atomic_write_json(meta_path, {
            "steps_per_epoch": steps_per_epoch,
            "global_batch_size": args.batch_size,
            "grad_accum": accum,
            # Schedule horizon — the --epochs the LR schedule was sized
            # for; a resume with a different value must opt in via
            # --extend-schedule (r4 VERDICT #6).
            "epochs": args.epochs})
    # Context-managed observability: the JSONL handle / TensorBoard
    # writer / telemetry stream / watchdog all close on EVERY exit path
    # — logger.close() used to run only on success, leaking the handle
    # and unflushed TB scalars whenever training raised.
    with contextlib.ExitStack() as obs_stack:
        logger = (obs_stack.enter_context(
            MetricsLogger(args.metrics_jsonl, tb_dir=args.tensorboard_dir))
            if args.metrics_jsonl or args.tensorboard_dir else None)
        telemetry = None
        run_dir = (Path(args.checkpoint_dir) if args.checkpoint_dir
                   else Path(args.telemetry_jsonl).parent
                   if args.telemetry_jsonl else Path("."))
        if (args.telemetry_jsonl or args.watchdog_s > 0
                or args.profile_steps or args.profile_auto
                or args.ship_to or args.metrics_port is not None):
            from .telemetry import (ProfileController, StepTelemetry,
                                    Watchdog, peak_bf16_tflops,
                                    train_step_flops_per_image,
                                    train_step_flops_per_sequence)
            watchdog = None
            if args.watchdog_s > 0:
                pm = args.postmortem or str(run_dir / "postmortem.txt")
                watchdog = Watchdog(args.watchdog_s, postmortem_path=pm)
                watchdog.install_sigterm()
                obs_stack.callback(watchdog.stop)
                watchdog.start()
                print(f"watchdog: deadline {args.watchdog_s:g}s, "
                      f"postmortem -> {pm}")
            # The capture controller exists whenever telemetry does:
            # even with no profiling flags, SIGUSR2 can arm a window on
            # a live run (attach-a-profiler-without-restarting).
            trace_dir = args.profile_trace_dir or str(run_dir / "profiles")
            profiler = ProfileController(
                trace_dir, steps=profile_window,
                auto=args.profile_auto, auto_pct=args.profile_auto_pct,
                verbose=True)
            profiler.install_sigusr2()
            obs_stack.callback(profiler.close)
            if args.profile_steps or args.profile_auto:
                print(f"profiler: captures -> {trace_dir}"
                      + (f", steps {args.profile_steps}"
                         if args.profile_steps else "")
                      + (f", auto-arm on p50 +{args.profile_auto_pct:g}%"
                         if args.profile_auto else ""))
            telemetry = obs_stack.enter_context(StepTelemetry(
                args.telemetry_jsonl,
                sample_every=args.telemetry_every,
                flops_per_image=(
                    None if cfg is None
                    else train_step_flops_per_sequence(cfg)
                    if cfg.vocab_size
                    else train_step_flops_per_image(cfg)),
                peak_tflops=peak_bf16_tflops(dev0.device_kind),
                n_chips=mesh.size,
                watchdog=watchdog, profiler=profiler))
        if args.metrics_port is not None:
            from .telemetry import start_metrics_http
            http_srv = start_metrics_http(port=args.metrics_port)
            obs_stack.callback(http_srv.server_close)
            obs_stack.callback(http_srv.shutdown)
            print(f"metrics: http://127.0.0.1:"
                  f"{http_srv.server_address[1]}/metrics")
        if args.ship_to:
            from .telemetry import TelemetryShipper
            shipper = TelemetryShipper(
                args.ship_to, worker_id=args.worker_id, role="train",
                interval_s=args.ship_interval_s)
            obs_stack.callback(shipper.close)
            shipper.start()
            print(f"telemetry shipper: {shipper.worker_id} -> "
                  f"{args.ship_to} every {args.ship_interval_s:g}s")

        dp_size = mesh.shape["data"]

        def train_batches():
            for b in train_dl:
                if distill_rows is not None:
                    # Gather this batch's teacher rows by dataset
                    # ordinal — a [B, C] float32 fancy-index copy out
                    # of the read-only sink memmap; shard_batch places
                    # it over 'data' like any other batch key.
                    b["teacher_logits"] = distill_rows[b.pop("index")]
                yield parallel.shard_batch(b, mesh)

        # Ragged final eval batches pad up to the data-axis divisor —
        # times the microbatch count on pipeline meshes, whose per-shard
        # batch must split into M microbatches. The mask keeps metrics
        # example-exact.
        eval_pad = dp_size * (microbatches if pipe_stages > 1 else 1)

        def eval_batches():
            from .data import pad_batch
            for b in test_dl:
                yield parallel.shard_batch(pad_batch(b, eval_pad), mesh)

        if args.eval_only:
            # Score-a-saved-model workflow (reference does this ad hoc
            # in-notebook, main nb cells 125-134): load, one eval pass,
            # exit.
            if (checkpointer is not None
                    and checkpointer.latest_step() is not None):
                try:
                    state = checkpointer.restore(state)
                except ValueError as e:
                    # Pre-run_meta checkpoints (or a deleted
                    # run_meta.json) can leave the restore template's
                    # opt_state structure (MultiSteps vs plain chain)
                    # mismatched with what was saved — orbax then raises
                    # a structure error that says nothing about the
                    # cause (ADVICE r3).
                    raise SystemExit(
                        "--eval-only: checkpoint restore failed with a "
                        "structure mismatch — if this checkpoint predates "
                        "run_meta.json (or the file was deleted), pass "
                        "--grad-accum matching the original run.\n"
                        f"original error: {e}")
                src = f"checkpoint step {int(jax.device_get(state.step))}"
            else:
                final = Path(args.checkpoint_dir) / "final"
                if not final.is_dir():
                    raise SystemExit(
                        f"--eval-only: no checkpoints and no final/ "
                        f"export under {args.checkpoint_dir}")
                from .checkpoint import load_model
                from .parallel.sharding import shard_tree
                # The final/ export is always STANDARD layout (abstract
                # template — no device_get: sharded leaves may span
                # non-addressable devices on multi-host meshes). Pipeline
                # runs re-stack after loading. Only params are
                # (re)placed; opt_state stays put.
                loaded = load_model(final, std_params_template)
                if pipe_stages > 1:
                    loaded = parallel.stack_block_params(loaded,
                                                         cfg.num_layers)
                state = state.replace(params=shard_tree(loaded, mesh))
                src = "final/ params export"
            m = engine.evaluate(
                state, eval_batches, eval_step=eval_step,
                # A long scoring pass must read as progress, not a
                # stall, when --watchdog-s is set.
                on_batch=(telemetry.heartbeat if telemetry is not None
                          else None))
            print(f"eval ({src}) | test_loss: {m['loss']:.4f} | "
                  f"test_acc: {m['acc']:.4f} | examples: {int(m['count'])}")
            if logger:
                logger.log(step=int(jax.device_get(state.step)), epoch=0,
                           test_loss=m["loss"], test_acc=m["acc"])
            return {"train_loss": [], "train_acc": [],
                    "test_loss": [m["loss"]], "test_acc": [m["acc"]]}

        # End-of-epoch LR into the JSONL: the schedule spans optimizer
        # updates, state.step counts micro-steps — divide by accum.
        lr_sched = make_lr_schedule(train_cfg, max(1, total_steps // accum))

        def run_train():
            return engine.train(
                state, train_batches, eval_batches, epochs=epochs_to_run,
                train_step=train_step, eval_step=eval_step, logger=logger,
                # Host backend: non-primary workers never write the
                # shared rotating checkpoint (state is replicated; one
                # writer). jax backend: every process keeps it — orbax
                # multi-process saves are COLLECTIVE.
                checkpointer=(checkpointer if elastic_ctx is None
                              or elastic_ctx.is_primary
                              or args.elastic_backend == "jax"
                              else None),
                start_epoch=done_epochs,
                checkpoint_every_steps=args.checkpoint_every_steps,
                checkpoint_every_epochs=args.checkpoint_every_epochs,
                lr_schedule=lambda s: lr_sched(s // accum),
                telemetry=telemetry,
                stop_check=(elastic_ctx.stop_check
                            if elastic_ctx is not None else None))

        if elastic_ctx is not None:
            from .parallel.elastic import (EXIT_COLLECTIVE, EXIT_YIELD,
                                           CollectiveFailure)

            def _yield_save(save_state):
                # The state at the last APPLIED step is globally
                # consistent on every worker (lockstep collectives), so
                # the primary can hand it to the next generation (jax
                # backend: every process joins — orbax saves are
                # collective). The span beats the watchdog: a drain
                # must not read as a stall (telemetry/watchdog
                # interplay).
                if not checkpointer or not (
                        elastic_ctx.is_primary
                        or args.elastic_backend == "jax"):
                    return
                step_now = int(jax.device_get(save_state.step))
                if checkpointer.latest_step() == step_now:
                    return
                import time as _time
                t_ck = _time.perf_counter()
                checkpointer.save(save_state, force=True)
                checkpointer.wait()
                if telemetry is not None:
                    telemetry.span("checkpoint",
                                   _time.perf_counter() - t_ck)

            try:
                state, results = run_train()
            except CollectiveFailure as e:
                elastic_ctx.count_collective_failure()
                print(f"[elastic] collective failed: {e} — exiting for "
                      f"re-formation")
                try:
                    # The loop never returned: the last applied state
                    # rides on the step function itself.
                    last = getattr(train_step, "last_state", None)
                    _yield_save(last if last is not None else state)
                except Exception as se:  # noqa: BLE001 — a failed
                    # best-effort save must not mask the exit protocol;
                    # recovery falls back to the last rotating save.
                    print(f"[elastic] yield save failed: {se}")
                elastic_ctx.close()
                raise SystemExit(EXIT_COLLECTIVE)
            if elastic_ctx.reform_pending:
                print("[elastic] yielding for re-formation at step "
                      f"{int(jax.device_get(state.step))}")
                _yield_save(state)
                elastic_ctx.count_yield()
                elastic_ctx.close()
                raise SystemExit(EXIT_YIELD)
            elastic_ctx.write_result({
                "worker_id": elastic_ctx.worker_id,
                "process_count": elastic_ctx.process_count,
                "generation": elastic_ctx.generation,
                "final_step": int(jax.device_get(state.step)),
                "results": results})
        else:
            state, results = run_train()

        if args.checkpoint_dir and (elastic_ctx is None
                                    or elastic_ctx.is_primary):
            # Params-only export in save_model format — what predict.py
            # loads. Pipeline runs export the STANDARD layout so
            # predict/transfer never see the stacked tree.
            from .checkpoint import save_model
            export = jax.device_get(state.params)
            if pipe_stages > 1:
                export = parallel.unstack_block_params(export)
            save_model(export, Path(args.checkpoint_dir), "final")
            # Record the transform decision so predict applies the same
            # one — atomically, so a concurrent predict/serve reading
            # the fresh checkpoint can't see a torn spec. (A token model
            # has no image transform, and no serving path reads it yet.)
            if args.model != "lm":
                atomic_write_json(
                    Path(args.checkpoint_dir) / "transform.json",
                    transform_spec)
            if cfg is not None and args.model != "lm":
                # Pin the model identity next to the transform: the
                # inference loaders refuse a tier-mismatched restore
                # loudly instead of shape-erroring mid-warmup.
                from .predictions import write_model_meta
                write_model_meta(Path(args.checkpoint_dir), cfg,
                                 extra={"preset": args.preset})

        if args.plot:
            plot_loss_curves(results, save_path=args.plot)
        if elastic_ctx is not None:
            elastic_ctx.close()
        return results


def cli() -> None:
    """Console-script entry point: discard main()'s results dict so the
    pip-generated ``sys.exit(cli())`` wrapper exits 0 on success."""
    main()


if __name__ == "__main__":
    main()
