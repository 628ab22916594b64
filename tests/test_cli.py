"""CLI entry-point tests: drive ``train.main`` exactly as a user would
(reference entry points are notebooks + a broken ``train.py``; SURVEY.md
§2.1 — ours must actually work, on any mesh)."""

import math

import pytest

# The package exports engine.train as `train`, so import the CLI module's
# main explicitly.
from pytorch_vit_paper_replication_tpu.train import main as train_main


def test_cli_synthetic_seq_parallel(devices, tmp_path):
    """--mesh-seq 2: the whole CLI path trains with ring attention (gap
    pooling for an even token count) on a data=4 x seq=2 mesh."""
    results = train_main([
        "--synthetic", "--preset", "ViT-Ti/16", "--image-size", "32",
        "--patch-size", "16", "--pool", "gap", "--dtype", "float32",
        "--attention", "xla", "--epochs", "1", "--batch-size", "8",
        "--mesh-data", "4", "--mesh-seq", "2",
        "--metrics-jsonl", str(tmp_path / "m.jsonl"),
    ])
    assert len(results["train_loss"]) == 1
    assert math.isfinite(results["train_loss"][0])
    assert (tmp_path / "m.jsonl").exists()


def test_cli_rejects_indivisible_batch(devices):
    """ADVICE r1: --batch-size not divisible by the data axis must be a
    clear CLI error, not an obscure sharding failure."""
    with pytest.raises(SystemExit, match="data"):
        train_main([
            "--synthetic", "--preset", "ViT-Ti/16", "--image-size", "32",
            "--epochs", "1", "--batch-size", "6", "--mesh-data", "4",
            "--mesh-model", "2",
        ])


def test_cli_rejects_cls_pool_on_seq_mesh(devices):
    """CLS pooling gives an odd token count; --mesh-seq must fail fast
    with the pool='gap' hint."""
    with pytest.raises(ValueError, match="gap"):
        train_main([
            "--synthetic", "--preset", "ViT-Ti/16", "--image-size", "32",
            "--patch-size", "16", "--epochs", "1", "--batch-size", "8",
            "--mesh-data", "4", "--mesh-seq", "2",
        ])


def test_cli_cifar10_synthetic(devices, tmp_path):
    """VERDICT r1 #4 done-criterion: the CLI trains on (fake) CIFAR-10
    end-to-end — BASELINE.json benchmark config #2's pipeline. Also
    rides the r5 ``--attention-softmax exact`` flag through the full
    stack (config plumb-through; the flavor itself is contract-tested
    in test_ops.py)."""
    results = train_main([
        "--dataset", "cifar10", "--synthetic", "--preset", "ViT-Ti/16",
        "--image-size", "32", "--patch-size", "16", "--dtype", "float32",
        "--attention-softmax", "exact",
        "--epochs", "1", "--batch-size", "8", "--mesh-data", "8",
        "--checkpoint-dir", str(tmp_path / "ckpt"),
    ])
    assert len(results["train_loss"]) == 1
    assert math.isfinite(results["train_loss"][0])
    assert (tmp_path / "ckpt" / "final").is_dir()


def test_cli_mid_epoch_resume_matches_uninterrupted(devices, tmp_path):
    """VERDICT r2 #1: resume through ``train.main`` itself.

    Round 2 shipped a double-skip — train.py wired BOTH the loader-level
    index skip and engine.train's (since-removed) ``skip_train_batches``,
    so a resumed run silently dropped up to a full epoch. This test drives
    the CLI exactly as a preempted user would: train with step-interval
    checkpoints, delete everything after a mid-epoch save to simulate the
    preemption, rerun the same command, and require the resumed run to
    reach the full step count with params bit-identical to an
    uninterrupted run. Under the round-2 bug the resumed run trains 1
    batch instead of 2 and this fails on both assertions.
    """
    import shutil

    import jax
    import numpy as np
    import orbax.checkpoint as ocp

    from pytorch_vit_paper_replication_tpu.checkpoint import Checkpointer
    from pytorch_vit_paper_replication_tpu.data import (
        make_synthetic_image_folder)

    train_dir, test_dir = make_synthetic_image_folder(
        tmp_path / "ds", train_per_class=8, test_per_class=2, image_size=32)
    # 24 train images, batch 8, drop_last -> 3 steps/epoch, 6 steps total.
    common = [
        "--train-dir", str(train_dir), "--test-dir", str(test_dir),
        "--preset", "ViT-Ti/16", "--image-size", "32", "--patch-size", "16",
        "--dtype", "float32", "--attention", "xla", "--epochs", "2",
        "--batch-size", "8", "--mesh-data", "8", "--seed", "7",
        "--num-workers", "1",
    ]
    ck_a, ck_b = tmp_path / "ckA", tmp_path / "ckB"
    train_main(common + ["--checkpoint-dir", str(ck_a)])

    interval = ["--checkpoint-dir", str(ck_b),
                "--checkpoint-every-steps", "2", "--keep-checkpoints", "20"]
    train_main(common + interval)
    # Preemption right after the step-4 save (mid-epoch 2: 1 of 3 batches
    # of that epoch trained): drop every later checkpoint + the final
    # export, leaving step 4 as latest.
    for d in ck_b.iterdir():
        if d.is_dir() and (d.name.isdigit() or d.name == "final"):
            if d.name == "final" or int(d.name) > 4:
                shutil.rmtree(d)
    ck = Checkpointer(ck_b)
    assert ck.latest_step() == 4
    ck.close()

    train_main(common + interval)  # resume

    ck = Checkpointer(ck_b)
    assert ck.latest_step() == 6, "resumed run must finish all 6 steps"
    ck.close()

    ckptr = ocp.StandardCheckpointer()
    try:
        params_a = ckptr.restore(ck_a / "final")
        params_b = ckptr.restore(ck_b / "final")
    finally:
        ckptr.close()
    leaves_a, leaves_b = (jax.tree.leaves(t) for t in (params_a, params_b))
    assert len(leaves_a) == len(leaves_b)
    for a, b in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_cli_eval_only_matches_training_final_metrics(devices, tmp_path):
    """VERDICT r2 missing #2: score a saved model without training.

    ``--eval-only`` (no --train-dir needed) must reproduce the training
    run's final test metrics exactly — same checkpoint, same eval split,
    deterministic eval pass.
    """
    import numpy as np

    from pytorch_vit_paper_replication_tpu.data import (
        make_synthetic_image_folder)

    train_dir, test_dir = make_synthetic_image_folder(
        tmp_path / "ds", train_per_class=8, test_per_class=3, image_size=32)
    model_args = [
        "--preset", "ViT-Ti/16", "--image-size", "32", "--patch-size", "16",
        "--dtype", "float32", "--attention", "xla", "--batch-size", "8",
        "--mesh-data", "8", "--seed", "5", "--num-workers", "1",
    ]
    ck = tmp_path / "ckpt"
    results = train_main(model_args + [
        "--train-dir", str(train_dir), "--test-dir", str(test_dir),
        "--epochs", "1", "--checkpoint-dir", str(ck)])

    ev = train_main(model_args + [
        "--test-dir", str(test_dir), "--eval-only",
        "--checkpoint-dir", str(ck)])
    assert ev["train_loss"] == []
    np.testing.assert_allclose(ev["test_loss"][0], results["test_loss"][-1],
                               rtol=1e-6)
    assert ev["test_acc"][0] == results["test_acc"][-1]

    # The params-only final/ export path: remove the step checkpoints so
    # eval-only falls back to final/ — same params, same metrics.
    import shutil
    for d in ck.iterdir():
        if d.is_dir() and d.name.isdigit():
            shutil.rmtree(d)
    ev2 = train_main(model_args + [
        "--test-dir", str(test_dir), "--eval-only",
        "--checkpoint-dir", str(ck)])
    np.testing.assert_allclose(ev2["test_loss"][0], results["test_loss"][-1],
                               rtol=1e-6)

    with pytest.raises(SystemExit, match="checkpoint-dir"):
        train_main(model_args + ["--test-dir", str(test_dir), "--eval-only"])


def test_cli_resume_schedule_horizon_guard(devices, tmp_path):
    """VERDICT r4 #6: extending a run past its recorded --epochs horizon
    re-scales the LR schedule (re-opening decay on a converged model —
    the epoch-31 loss spike of runs/longrun_r4) and must be an explicit
    choice, while a same-epochs resume must leave the LR trajectory
    bit-identical to the uninterrupted run."""
    import json
    import shutil

    from pytorch_vit_paper_replication_tpu.checkpoint import Checkpointer
    from pytorch_vit_paper_replication_tpu.data import (
        make_synthetic_image_folder)

    train_dir, test_dir = make_synthetic_image_folder(
        tmp_path / "ds", train_per_class=8, test_per_class=2, image_size=32)
    # 24 train images, batch 8, drop_last -> 3 steps/epoch.
    common = [
        "--train-dir", str(train_dir), "--test-dir", str(test_dir),
        "--preset", "ViT-Ti/16", "--image-size", "32", "--patch-size", "16",
        "--dtype", "float32", "--attention", "xla", "--batch-size", "8",
        "--mesh-data", "8", "--seed", "7", "--num-workers", "1",
    ]
    ck_a, ck_b = tmp_path / "ckA", tmp_path / "ckB"

    # Uninterrupted 2-epoch run: the reference LR trajectory.
    train_main(common + ["--epochs", "2", "--checkpoint-dir", str(ck_a),
                         "--metrics-jsonl", str(tmp_path / "a.jsonl")])
    lr_a = [json.loads(l)["lr"]
            for l in (tmp_path / "a.jsonl").read_text().splitlines()]

    # Same command, preempted after the step-4 mid-epoch save, resumed
    # with the SAME --epochs: the logged LR of the resumed epochs must
    # equal the uninterrupted run's exactly (no silent re-scaling).
    interval = ["--epochs", "2", "--checkpoint-dir", str(ck_b),
                "--checkpoint-every-steps", "2", "--keep-checkpoints", "20"]
    train_main(common + interval)
    for d in ck_b.iterdir():
        if d.is_dir() and (d.name.isdigit() or d.name == "final"):
            if d.name == "final" or int(d.name) > 4:
                shutil.rmtree(d)
    ck = Checkpointer(ck_b)
    assert ck.latest_step() == 4
    ck.close()
    train_main(common + interval
               + ["--metrics-jsonl", str(tmp_path / "b.jsonl")])
    lr_b = [json.loads(l)["lr"]
            for l in (tmp_path / "b.jsonl").read_text().splitlines()]
    # The resumed run logs epoch 2 only; it must match run A's epoch 2.
    assert lr_b[-1] == lr_a[-1]

    # Extending the finished run: --epochs 4 re-scales the schedule and
    # must be rejected without the explicit flag...
    with pytest.raises(SystemExit, match="extend-schedule"):
        train_main(common + ["--epochs", "4",
                             "--checkpoint-dir", str(ck_a)])
    # ...and accepted with it (reference main nb cell 98's manual
    # continuation), running the 2 additional epochs to the new horizon.
    results = train_main(common + ["--epochs", "4", "--extend-schedule",
                                   "--checkpoint-dir", str(ck_a),
                                   "--metrics-jsonl",
                                   str(tmp_path / "c.jsonl")])
    assert len(results["train_loss"]) == 2
    rec = json.loads((tmp_path / "c.jsonl").read_text().splitlines()[-1])
    # End of the re-scaled schedule -> LR decayed to 0 at the NEW horizon.
    assert rec["lr"] == pytest.approx(0.0, abs=1e-6)
    # The extended horizon is re-recorded: a further same-epochs resume
    # compares against 4, not 2.
    assert json.loads((ck_a / "run_meta.json").read_text())["epochs"] == 4


def test_cli_tinyvgg(devices):
    """Reference script-entry parity: the CLI can train the TinyVGG
    baseline (going_modular train.py:39-43 — which crashes upstream).
    Runs with ``--worker-type process`` so the forked-decode-worker path
    (reference DataLoader num_workers semantics, r5) is exercised through
    the full CLI stack in a live-JAX parent process."""
    results = train_main([
        "--synthetic", "--model", "tinyvgg", "--hidden-units", "8",
        "--image-size", "64", "--dtype", "float32",
        "--epochs", "1", "--batch-size", "8", "--mesh-data", "8",
        "--worker-type", "process", "--num-workers", "2",
    ])
    assert len(results["train_loss"]) == 1
    assert math.isfinite(results["train_loss"][0])


def test_cli_pretrained_resolution_change(devices, tmp_path):
    """VERDICT r4 #5 (CLI-level piece): the 384px/577-token transfer
    workflow's mechanics at test scale — torch-layout weights written for
    32px are fine-tuned through the CLI at 64px, so pos-embedding
    interpolation (2x2 -> 4x4 grid), frozen-backbone optimization, and
    the final export all execute via ``--pretrained``. The committed
    full-scale run is runs/transfer384_r5/ (B/16, 224->384, flash)."""
    import importlib.util
    from pathlib import Path as P

    import numpy as np
    import orbax.checkpoint as ocp

    torch = pytest.importorskip("torch")
    spec = importlib.util.spec_from_file_location(
        "make_torch_vit",
        P(__file__).resolve().parent.parent / "tools" / "make_torch_vit.py")
    mtv = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mtv)

    from pytorch_vit_paper_replication_tpu.configs import PRESETS

    cfg32 = PRESETS["ViT-Ti/16"](num_classes=3, image_size=32)
    torch.manual_seed(0)
    pth = tmp_path / "ti_32.pth"
    torch.save(mtv.TorchViT(cfg32).state_dict(), pth)

    ck = tmp_path / "ckpt"
    results = train_main([
        "--synthetic", "--preset", "ViT-Ti/16", "--image-size", "64",
        "--dtype", "float32", "--attention", "xla", "--ln-eps", "1e-5",
        "--epochs", "1", "--batch-size", "8", "--mesh-data", "8",
        "--num-workers", "1", "--pretrained", str(pth),
        "--freeze-backbone", "--checkpoint-dir", str(ck),
    ])
    assert math.isfinite(results["train_loss"][0])

    # The backbone really stayed frozen AND really came from the torch
    # weights: the exported conv kernel equals the converted torch one.
    ckptr = ocp.StandardCheckpointer()
    try:
        final = ckptr.restore(ck / "final")
    finally:
        ckptr.close()
    torch.manual_seed(0)  # reconstruct the identical source model
    want = mtv.TorchViT(cfg32)
    np.testing.assert_allclose(
        np.asarray(final["backbone"]["patch_embedding"]["patch_conv"]
                   ["kernel"]),
        want.state_dict()["conv_proj.weight"].numpy().transpose(2, 3, 1, 0),
        rtol=1e-6)
    # 64px config: pos table interpolated to 17 tokens (4x4 grid + CLS).
    assert final["backbone"]["patch_embedding"]["pos_embedding"].shape \
        == (1, 17, cfg32.embedding_dim)


def test_cli_synthetic_scale_and_noise_flags(devices, tmp_path):
    """--synthetic-per-class / --synthetic-noise (the knobs behind the
    committed runs/dynamics_r4 artifact) reach the generator: more images
    per class -> more steps per epoch, and the logger records the LR
    schedule for auditability."""
    import json

    results = train_main([
        "--synthetic", "--synthetic-per-class", "16",
        "--synthetic-noise", "120", "--preset", "ViT-Ti/16",
        "--image-size", "32", "--patch-size", "16", "--dtype", "float32",
        "--epochs", "1", "--batch-size", "8",
        "--metrics-jsonl", str(tmp_path / "m.jsonl"),
    ])
    assert len(results["train_loss"]) == 1
    # 3 classes x 16/class = 48 train images -> 6 batches of 8.
    rec = json.loads((tmp_path / "m.jsonl").read_text().splitlines()[-1])
    assert rec["step"] == 6
    # LR logged from the real schedule (end of the only epoch = end of
    # decay -> 0).
    assert rec["lr"] == pytest.approx(0.0, abs=1e-6)
