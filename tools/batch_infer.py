"""Offline batch inference over a packed-shard dataset, all devices.

The throughput half of ROADMAP item 4 ("embed 10⁶ images overnight"):
stream a ``pack_image_folder`` output through the bucketed jitted
forward sharded data-parallel over every local device, with
double-buffered host→device prefetch, the PR 1 page-cache discipline
(readahead + evict-behind, no shuffle), and an atomic progress
manifest so a killed run resumes where it durably left off — the
final sink is byte-identical to an unkilled run's. Outputs land in a
pre-sized ``outputs.npy`` (softmax probs; pooled ``[D]`` embeddings
with ``--head features``; pre-softmax classifier activations with
``--head logits`` — the distillation dataset ``train.py
--distill-from`` trains a student against); ``--preds-jsonl``
mirrors classifier
predictions one JSON line per record.

Usage::

    python tools/batch_infer.py PACK_DIR --checkpoint runs/ckpt \\
        --classes-file labels.txt --out runs/embed --head features

Re-running the same command against the same ``--out`` resumes from
the manifest; ``--fresh`` restarts from record 0. ``--ship-to
HOST:PORT`` ships ``bi_*`` telemetry frames so ``tools/fleet_agg.py``
shows the batch job next to train and serve workers.

``run_bench`` (imported by ``bench.py``) publishes the
``batch_infer_ok`` gate: offline img/s ≥ 1.0× the train-step img/s on
the same host — there is no backward pass, so slower-than-training
means the sweep path is broken. ``run_kill_resume`` is the committed-
evidence harness: SIGKILL a real subprocess mid-run, resume, and
prove the final sink's sha256 equals an unkilled run's.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from pathlib import Path
from typing import Optional

_REPO = Path(__file__).resolve().parent.parent
if str(_REPO) not in sys.path:  # runnable without an installed package
    sys.path.insert(0, str(_REPO))


def _build_engine(args, n_classes: int, class_names):
    """checkpoint -> (OfflineEngine, transform spec) via the ONE shared
    inference-load contract (``load_inference_checkpoint``), so batch
    inference preprocesses pixels exactly like predict/serve."""
    from pytorch_vit_paper_replication_tpu.predictions import (
        load_inference_checkpoint)
    from pytorch_vit_paper_replication_tpu.serve.bucketing import (
        DEFAULT_BUCKETS)
    from pytorch_vit_paper_replication_tpu.serve.offline import (
        OfflineEngine)

    model, params, _, spec = load_inference_checkpoint(
        args.checkpoint, args.preset, n_classes,
        image_size=args.image_size,
        normalize=False if args.no_normalize else None)
    buckets = tuple(args.buckets) if args.buckets else DEFAULT_BUCKETS
    engine = OfflineEngine(
        model, params, head=args.head, image_size=spec["image_size"],
        buckets=buckets, prefetch=args.prefetch, class_names=class_names)
    return engine, spec


def run_job(args) -> dict:
    """The real job: pack -> engine.run -> summary (printed + saved)."""
    from pytorch_vit_paper_replication_tpu.data.imagenet import (
        PackedShardDataset, eval_center_transform)
    from pytorch_vit_paper_replication_tpu.predictions import (
        load_class_names)

    class_names = (load_class_names(args.classes_file)
                   if args.classes_file else None)
    n_classes = (len(class_names) if class_names is not None
                 else args.num_classes)
    if n_classes is None:
        raise SystemExit("pass --classes-file or --num-classes (the "
                         "checkpoint's head size is needed to restore "
                         "params, even for --head features)")

    engine, spec = _build_engine(args, n_classes, class_names)
    # Array-space eval transform — the packed-eval path (records are
    # already resize-shorter'd + center-cropped at pack time); the
    # whole-pack startup WILLNEED hint is skipped because the streaming
    # readahead below pages blocks in (and out) incrementally.
    dataset = PackedShardDataset(
        args.pack, eval_center_transform(spec["image_size"],
                                         normalize=spec["normalize"]),
        startup_readahead=False)

    shipper = None
    if args.ship_to:
        from pytorch_vit_paper_replication_tpu.telemetry.shipper import (
            TelemetryShipper)
        shipper = TelemetryShipper(
            args.ship_to, worker_id=args.worker_id, role="batch_infer",
            interval_s=args.ship_interval_s).start()
        print(f"[batch_infer] telemetry shipper: {shipper.worker_id} -> "
              f"{args.ship_to} every {args.ship_interval_s:g}s")
    try:
        summary = engine.run(
            dataset, args.out,
            batch_size=args.batch_size,
            resume=not args.fresh,
            limit=args.limit,
            num_workers=args.num_workers,
            worker_type=args.worker_type,
            readahead=args.readahead,
            evict_behind=not args.no_evict_behind,
            checkpoint_every_records=args.checkpoint_every_records,
            checkpoint_every_s=args.checkpoint_every_s,
            preds_jsonl=args.preds_jsonl,
            throttle_s=args.throttle_s)
    finally:
        if shipper is not None:
            shipper.close()
    if args.sha256:
        from pytorch_vit_paper_replication_tpu.serve.offline import (
            sink_sha256)
        summary["sink_sha256"] = sink_sha256(summary["sink"])
    line = json.dumps({"metric": "batch_infer", **summary})
    print(line)
    (Path(args.out) / "summary.json").write_text(line + "\n")
    return summary


# ------------------------------------------------------------- bench gate
def run_bench(cfg=None, train_images_per_sec: Optional[float] = None,
              batch_size: int = 8, records: Optional[int] = None,
              workdir: Optional[Path] = None) -> dict:
    """The ``batch_infer_ok`` harness (bench.py imports this): sweep a
    synthetic pack through the real :class:`OfflineEngine` with the
    bench's model config and compare img/s against the full train step
    on the same host. Forward-only over all local devices must beat
    one chip's fwd+bwd+Adam — the gate is ≥ 1.0×. Two passes: the
    first compiles (and is discarded), the second measures."""
    import importlib.util
    import tempfile

    from pytorch_vit_paper_replication_tpu import configs
    from pytorch_vit_paper_replication_tpu.data.imagenet import (
        PackedShardDataset, eval_center_transform)
    from pytorch_vit_paper_replication_tpu.models import ViT
    from pytorch_vit_paper_replication_tpu.serve.offline import (
        OfflineEngine)
    import jax
    import jax.numpy as jnp

    def _load(name, fname):
        spec = importlib.util.spec_from_file_location(name, _REPO / fname)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    on_tpu = jax.default_backend() == "tpu"
    if cfg is None:
        cfg = configs.vit_b16(
            num_classes=1000, dtype="bfloat16" if on_tpu else "float32")
    bench = _load("bench_mod_for_bi", "bench.py")
    if train_images_per_sec is None:
        train_images_per_sec = bench.bench_train_step(
            cfg, batch_size=batch_size, steps=10 if on_tpu else 3)
    n = int(records or 8 * batch_size)

    model = ViT(cfg)
    params = model.init(jax.random.key(0), jnp.zeros(
        (1, cfg.image_size, cfg.image_size, 3)))["params"]
    engine = OfflineEngine(model, params, head="probs",
                           image_size=cfg.image_size,
                           buckets=(batch_size,))
    sc = _load("scale_epoch_for_bi", "tools/scale_epoch.py")
    import contextlib
    with contextlib.ExitStack() as stack:
        tmp = Path(workdir) if workdir is not None else Path(
            stack.enter_context(
                tempfile.TemporaryDirectory(prefix="bench_bi_")))
        pack = sc.make_synthetic_pack(
            tmp / "pack", records=n, pack_size=cfg.image_size,
            records_per_shard=max(batch_size, n // 2), seed=0)
        ds = PackedShardDataset(
            pack, eval_center_transform(cfg.image_size, normalize=True),
            startup_readahead=False)
        engine.run(ds, tmp / "warm", batch_size=batch_size, resume=False,
                   log_every_s=0.0)          # compile pass, discarded
        summary = engine.run(ds, tmp / "timed", batch_size=batch_size,
                             resume=False, log_every_s=0.0)
    bi_img_s = summary["images_per_sec"]
    vs = (round(bi_img_s / train_images_per_sec, 3)
          if train_images_per_sec else None)
    return {
        "bi_images_per_sec": bi_img_s,
        "bi_steady_images_per_sec": summary["steady_images_per_sec"],
        "bi_train_ref_images_per_sec": round(train_images_per_sec, 2)
        if train_images_per_sec else None,
        "bi_vs_train": vs,
        "bi_records": summary["records"],
        "bi_devices": summary["devices"],
        "bi_batch_size": summary["batch_size"],
        "batch_infer_ok": bool(vs is not None and vs >= 1.0),
    }


# ---------------------------------------------------- kill+resume evidence
def _make_tiny_job(workdir: Path, *, records: int = 768,
                   image_size: int = 32, num_classes: int = 3) -> dict:
    """A self-contained tiny job for the kill/resume proof: a ViT-Ti
    params export (+ transform.json, exactly what training writes) and
    a synthetic pack."""
    import importlib.util

    import jax
    import jax.numpy as jnp

    from pytorch_vit_paper_replication_tpu import configs
    from pytorch_vit_paper_replication_tpu.checkpoint import save_model
    from pytorch_vit_paper_replication_tpu.models import ViT

    spec = importlib.util.spec_from_file_location(
        "scale_epoch_for_bi", _REPO / "tools" / "scale_epoch.py")
    sc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sc)

    cfg = configs.vit_ti16(num_classes=num_classes, image_size=image_size,
                           dtype="float32", attention_impl="xla")
    model = ViT(cfg)
    params = model.init(jax.random.key(0), jnp.zeros(
        (1, image_size, image_size, 3)))["params"]
    ckpt = workdir / "ckpt"
    save_model(params, ckpt, "final")
    from pytorch_vit_paper_replication_tpu.utils.atomic import (
        atomic_write_json)
    # transform.json is a checkpoint manifest the inference loaders
    # validate — atomic like every other manifest (vitlint).
    atomic_write_json(ckpt / "transform.json",
                      {"image_size": image_size, "pretrained": False,
                       "normalize": False})
    pack = sc.make_synthetic_pack(
        workdir / "pack", records=records, pack_size=image_size,
        num_classes=num_classes, records_per_shard=256, seed=0)
    return {"checkpoint": ckpt, "pack": pack, "records": records,
            "num_classes": num_classes}


def run_kill_resume(workdir: Path, *, records: int = 768,
                    batch_size: int = 64, throttle_s: float = 0.05,
                    kill_after_records: int = 128,
                    timeout_s: float = 300.0) -> dict:
    """SIGKILL a real batch-infer subprocess mid-run, resume it, and
    compare the final sink's sha256 against an unkilled run's. The
    children run CPU-pinned (``tools/_common.cpu_child_env`` — one
    copy of the recipe); ``throttle_s`` paces the victim so the kill
    reliably lands mid-run."""
    from pytorch_vit_paper_replication_tpu.serve.offline import (
        PROGRESS_MANIFEST, sink_sha256)
    from tools._common import cpu_child_env

    workdir = Path(workdir)
    workdir.mkdir(parents=True, exist_ok=True)
    job = _make_tiny_job(workdir, records=records)

    def cmd(out: Path, throttle: float) -> list:
        return [sys.executable, str(_REPO / "tools" / "batch_infer.py"),
                str(job["pack"]), "--checkpoint", str(job["checkpoint"]),
                "--num-classes", str(job["num_classes"]),
                "--preset", "ViT-Ti/16", "--out", str(out),
                "--batch-size", str(batch_size),
                "--checkpoint-every-records", str(batch_size),
                "--checkpoint-every-s", "0.01",
                "--throttle-s", str(throttle)]

    env = cpu_child_env()
    clean_out = workdir / "clean"
    t0 = time.perf_counter()
    subprocess.run(cmd(clean_out, 0.0), env=env, check=True,
                   capture_output=True, timeout=timeout_s)
    clean_s = time.perf_counter() - t0

    killed_out = workdir / "killed"
    victim = subprocess.Popen(cmd(killed_out, throttle_s), env=env,
                              stdout=subprocess.DEVNULL,
                              stderr=subprocess.DEVNULL)
    manifest = killed_out / PROGRESS_MANIFEST
    killed_at = None
    deadline = time.monotonic() + timeout_s
    try:
        while time.monotonic() < deadline:
            if victim.poll() is not None:
                raise RuntimeError(
                    f"victim finished (rc={victim.returncode}) before the "
                    "kill landed; raise --throttle-s or records")
            if manifest.is_file():
                try:
                    done = json.loads(manifest.read_text()).get(
                        "records_done", 0)
                except (json.JSONDecodeError, OSError):
                    done = 0   # racing the atomic replace: retry
                if done >= kill_after_records:
                    killed_at = done
                    break
            time.sleep(0.02)
        if killed_at is None:
            raise RuntimeError("timed out waiting for progress to kill at")
        victim.send_signal(signal.SIGKILL)
        victim.wait(timeout=30)
    finally:
        if victim.poll() is None:
            victim.kill()
            victim.wait(timeout=30)

    # Resume: the SAME command (no throttle needed now) picks up at the
    # manifest's offset and finishes the sweep.
    t0 = time.perf_counter()
    resumed = subprocess.run(cmd(killed_out, 0.0), env=env, check=True,
                             capture_output=True, text=True,
                             timeout=timeout_s)
    resume_s = time.perf_counter() - t0
    resumed_summary = json.loads(
        [ln for ln in resumed.stdout.splitlines()
         if ln.startswith('{"metric": "batch_infer"')][-1])

    sha_clean = sink_sha256(clean_out / "outputs.npy")
    sha_resumed = sink_sha256(killed_out / "outputs.npy")
    return {
        "records": records,
        "batch_size": batch_size,
        "killed_at_records": killed_at,
        "resumed_from": resumed_summary["resumed_from"],
        "clean_wall_s": round(clean_s, 2),
        "resume_wall_s": round(resume_s, 2),
        "sink_sha256_clean": sha_clean,
        "sink_sha256_resumed": sha_resumed,
        "identical": sha_clean == sha_resumed,
    }


# -------------------------------------------------------------------- CLI
def main(argv=None) -> dict:
    # The head registry is the single source for --head: a head added
    # to serve/offline.py reaches this CLI (and its refusal messages)
    # with no second list to forget. Costs a package import at parse
    # time; check_cli's --help budget absorbs it.
    from pytorch_vit_paper_replication_tpu.serve.offline import (
        OFFLINE_HEADS)

    p = argparse.ArgumentParser(
        description="Offline batch inference: sweep a packed-shard "
                    "dataset through every local device, resumably",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("pack", nargs="?", default=None,
                   help="pack_image_folder output directory")
    p.add_argument("--checkpoint",
                   help="params export or training --checkpoint-dir")
    p.add_argument("--out", help="output directory (outputs.npy + "
                                 "progress.json land here; re-running "
                                 "resumes from the manifest)")
    cls = p.add_mutually_exclusive_group()
    cls.add_argument("--classes-file",
                     help="one class name per line (training order)")
    cls.add_argument("--num-classes", type=int, default=None,
                     help="head size when names don't matter")
    p.add_argument("--preset", default="ViT-B/16")
    p.add_argument("--head", choices=sorted(OFFLINE_HEADS),
                   default="probs",
                   help="probs = softmax rows (predict_image-identical); "
                        "features = pooled [D] backbone embeddings; "
                        "logits = pre-softmax classifier activations "
                        "(the distillation dataset for train.py "
                        "--distill-from)")
    p.add_argument("--image-size", type=int, default=None,
                   help="defaults to the checkpoint's transform.json")
    p.add_argument("--no-normalize", action="store_true")
    p.add_argument("--batch-size", type=int, default=None,
                   help="loader batch (default: top ladder rung)")
    p.add_argument("--buckets", type=int, nargs="+", default=None,
                   help="bucket ladder (default: the serve ladder, "
                        "rounded up to device-count multiples)")
    p.add_argument("--prefetch", type=int, default=2,
                   help="in-flight dispatch window (2 = double-buffered)")
    p.add_argument("--readahead", type=int, default=2,
                   help="shard blocks to page in ahead of the sweep "
                        "(the PR 1 page-cache discipline; 0 = off)")
    p.add_argument("--no-evict-behind", action="store_true",
                   help="keep swept blocks in the page cache (default "
                        "evicts behind the sweep — a full-dataset pass "
                        "should not churn the whole cache)")
    p.add_argument("--num-workers", type=int, default=1)
    p.add_argument("--worker-type", choices=["thread", "process"],
                   default="thread")
    p.add_argument("--fresh", action="store_true",
                   help="ignore an existing progress manifest and "
                        "restart from record 0")
    p.add_argument("--limit", type=int, default=None,
                   help="stop after N records (smoke runs)")
    p.add_argument("--checkpoint-every-records", type=int, default=None,
                   help="manifest cadence in records (default 32 "
                        "batches)")
    p.add_argument("--checkpoint-every-s", type=float, default=30.0)
    p.add_argument("--preds-jsonl", action="store_true",
                   help="also write preds.jsonl (probs head only)")
    p.add_argument("--sha256", action="store_true",
                   help="hash the final sink into the printed summary "
                        "(the completed job's progress.json always "
                        "records sink_sha256 — what build_index "
                        "verifies; this flag just surfaces it)")
    p.add_argument("--throttle-s", type=float, default=0.0,
                   help="sleep per loader batch (kill/resume tests "
                        "pace the run with this; keep 0 in production)")
    p.add_argument("--ship-to", default=None, metavar="HOST:PORT",
                   help="ship bi_* telemetry frames to a fleet "
                        "aggregator (tools/fleet_agg.py)")
    p.add_argument("--ship-interval-s", type=float, default=2.0)
    p.add_argument("--worker-id", default=None)
    p.add_argument("--demo-kill-resume", action="store_true",
                   help="run the committed-evidence kill+resume proof "
                        "into --out instead of a real job")
    from pytorch_vit_paper_replication_tpu.compile_cache import (
        add_cache_cli, configure)
    add_cache_cli(p)
    args = p.parse_args(argv)

    if args.ship_to:
        from pytorch_vit_paper_replication_tpu.telemetry.shipper import (
            parse_address)
        try:
            parse_address(args.ship_to)
        except ValueError as e:
            raise SystemExit(f"--ship-to: {e}")
    if not args.out:
        raise SystemExit("--out is required")

    if args.demo_kill_resume:
        result = run_kill_resume(Path(args.out))
        line = json.dumps({"metric": "batch_infer_kill_resume", **result})
        print(line)
        # vitlint: disable=atomic-manifest(single-writer bench artifact, read only after exit)
        (Path(args.out) / "kill_resume.json").write_text(line + "\n")
        if not result["identical"]:
            raise SystemExit("kill+resume sink differs from the clean run")
        return result

    if not args.pack or not args.checkpoint:
        raise SystemExit("PACK_DIR and --checkpoint are required")
    configure(args.compile_cache_dir)  # before the first jit
    return run_job(args)


if __name__ == "__main__":
    main()
