#!/usr/bin/env python3
"""The quickest proof that the trainer and the server still start on the TPU.

    python chip_smoke.py

drives the program's own entry points once, at the full width of the
headline model (ViT-B/16, 224 px, bf16, random weights from a seed):

  probe       what JAX sees: platform, device_kind, device count
  train       `python -m ...train --synthetic --preset ViT-B/16
              --batch-size 256`: 8 optimizer steps, an eval pass per
              epoch, a checkpoint and the final/ export
  train-lm    `python -m ...train --model lm --preset lm-tiny --synthetic`,
              then `--preset mla-tiny`: 3 steps of each tiny token model
              (routed experts, causal / window attention; latent
              attention, shared expert, multi-token prediction) and its
              eval pass
  serve       `python -m ...serve --checkpoint <that run> --sync-warmup
              --buckets 1,8`, fed image paths and ::stats on stdin
  train-dp4   the same trainer with its default mesh over four chips
              (global batch 1024)
  loss-dp1 /  the first-step loss at one global batch, dropout off, on
  loss-dp4    one chip and on four: equal but for the reduction order
  offline     tools/batch_infer.py (OfflineEngine) sweeping a pack over
              four chips

The last three run only when the machine shows four chips or more; fewer
is the one reason a phase may be skipped, and it is printed.

A chip belongs to one process at a time, so this parent never initialises
a JAX backend: every phase is a child process that takes the chip, is
checked by what it printed and wrote, and has exited (its whole process
group killed on a timeout) before the next starts. On a host with several
chips the one-chip phases see exactly one, through the same variables the
serving fleet hands its replicas (`serve.fleet.replica.replica_env`).

Nothing here trusts a dispatch to have picked the device path: the train
phases read the Mosaic custom calls out of the lowered step (the trainer
prints them), the platform must be `tpu`, and any phase that fails makes
the exit code non-zero. The last line of stdout is one JSON object,
`{"ok": true, "device": {...}}`, printed only when every phase passed.

The compile cache is wherever `JAX_COMPILATION_CACHE_DIR` says, else
`<checkout>/.jax_compile_cache` — so a second run starts from cache hits,
and says so (`compile cache: N hits`).
"""

from __future__ import annotations

import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
PKG = "pytorch_vit_paper_replication_tpu"
PY = sys.executable

PRESET = "ViT-B/16"
LAYERS = 12              # B/16: per layer a fwd + a bwd Mosaic call of the
#                          MLP half-block and of the attention core
WIDTH = 768
TOKENS = 197             # 224 px / 16 + CLS
PER_CHIP = 256           # images per chip per step, the headline batch
# 342 x 3 classes = 1026 train images: 4 steps an epoch at 256, exactly
# one at the four-chip global batch of 1024 (the loader drops the rest).
PER_CLASS = 342
CLASSES = ["pizza", "steak", "sushi"]
# lr 1e-4: the recipe's 1e-3 is tuned for a schedule of thousands of
# steps; eight steps from random weights want the loss to fall, not to
# show the first spike of a warm-up that never happens.
TRAIN_COMMON = ["--preset", PRESET, "--lr", "1e-4", "--seed", "42"]
# dp=4 against one chip, same global batch, dropout off, identical
# weights and images (both from the seed). The two programs differ only
# in the order partial sums are added — the batch mean over 4x64 rows
# against 256, GEMMs tiled over 12,608 rows against 50,432 — and an f32
# sum taken in another order moves a bf16 activation by an ulp here and
# there, so the losses agree closely but not to the bit: 6.2e-6 apart
# on the v5e (PERF.md, PR 21). The bound leaves that gap 16x of room; a
# shard that saw the wrong rows or a dW summed twice moves the loss by
# orders of magnitude more.
LOSS_RTOL = 1e-4

PROBE = (
    "import json, jax\n"
    "d = jax.devices()\n"
    "print('DEVICE ' + json.dumps({'platform': d[0].platform, "
    "'kind': d[0].device_kind, 'count': len(d)}))\n")


class PhaseFailed(Exception):
    pass


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def child_env(chips=None) -> dict:
    """The environment of a phase's child: the package importable, and —
    on a host with several chips — only `chips` visible."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    if chips is not None:
        from pytorch_vit_paper_replication_tpu.serve.fleet.replica import (
            replica_env)
        env = replica_env(chips, base=env)
    return env


def run_child(name: str, cmd, *, env, log_dir: Path, timeout: float,
              stdin_text: str = "") -> str:
    """Run one phase's process to its end; return its stdout. Output is
    kept in `log_dir`; a non-zero exit or a timeout fails the phase, and
    the child's whole process group is gone either way."""
    t0 = time.time()
    out_path, err_path = log_dir / f"{name}.out", log_dir / f"{name}.err"
    with open(out_path, "w") as out, open(err_path, "w") as err:
        proc = subprocess.Popen(
            cmd, cwd=REPO, env=env, stdout=out, stderr=err,
            stdin=subprocess.PIPE, text=True, start_new_session=True)
        try:
            proc.communicate(stdin_text, timeout=timeout)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"timed out after {timeout:.0f}s")
        finally:
            try:
                os.killpg(proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            proc.wait()
    stdout = out_path.read_text()
    say(f"{name}: exit {proc.returncode} in {time.time() - t0:.0f}s")
    if proc.returncode != 0:
        tail = "\n".join(
            (stdout + "\n" + err_path.read_text()).splitlines()[-40:])
        raise PhaseFailed(f"exit code {proc.returncode}\n{tail}")
    return stdout


def find(pattern: str, text: str, what: str):
    m = re.search(pattern, text, re.M)
    if m is None:
        raise PhaseFailed(f"the output has no {what} (/{pattern}/)")
    return m


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


# ------------------------------------------------------------------ phases
def probe(ctx) -> None:
    out = run_child("probe", [PY, "-c", PROBE], env=child_env(),
                    log_dir=ctx["logs"], timeout=300)
    dev = json.loads(find(r"^DEVICE (.*)$", out, "DEVICE line").group(1))
    say(f"probe: platform={dev['platform']} device_kind={dev['kind']!r} "
        f"count={dev['count']}")
    check(dev["platform"] == "tpu",
          f"no TPU: jax reports platform {dev['platform']!r}")
    ctx["device"] = dev
    ctx["one_chip"] = [0] if dev["count"] > 1 else None


def train_rows(jsonl: Path):
    rows = [json.loads(line) for line in jsonl.read_text().splitlines()]
    return [r for r in rows if "train_loss" in r]


def check_train_output(out: str, *, chips: int, per_chip: int) -> None:
    """What every train phase must have printed about its device path."""
    m = find(r"mesh: \{'data': (\d+), .*platform: (\w+) \| "
             r"device_kind: (.+)$", out, "model/mesh line")
    check(int(m.group(1)) == chips and m.group(2) == "tpu",
          f"trained on data={m.group(1)} platform={m.group(2)}, wanted "
          f"data={chips} on tpu")
    m = find(r"^train step: (\d+) Mosaic kernel calls: (.*)$", out,
             "Mosaic call report")
    rows = [per_chip * TOKENS, WIDTH]
    packed = [per_chip, TOKENS, 3 * WIDTH]
    wanted = ", ".join(f"{name} x{LAYERS} {shape}" for name, shape in [
        ("attn_short_bwd", packed), ("attn_short_fwd", packed),
        ("lnmlp_bwd", rows), ("lnmlp_fwd", rows)])
    check(int(m.group(1)) == 4 * LAYERS and m.group(2) == wanted,
          f"Mosaic calls in the lowered step: {m.group(1)}: {m.group(2)}; "
          f"wanted {4 * LAYERS}: {wanted} (per layer the fused MLP "
          f"half-block forward and backward over the per-shard {rows[0]} "
          f"= {per_chip} images x {TOKENS} tokens, and the attention "
          "pair on the per-shard packed qkv projection)")
    mem = find(r"^device memory in use: ([\d. ]+) GiB", out,
               "device memory report").group(1).split()
    check(len(mem) == chips and all(float(g) > 0.5 for g in mem),
          f"memory in use per device {mem} GiB: wanted {chips} devices, "
          "each holding the state")
    ttfs = find(r"^time_to_first_step: ([\d.]+)s", out,
                "time_to_first_step").group(1)
    cache = find(r"^compile cache: (\d+) hits, (\d+) misses \((.*)\)", out,
                 "compile-cache counts")
    say(f"  {m.group(0)}")
    say(f"  time_to_first_step {ttfs}s | compile cache {cache.group(1)} "
        f"hits, {cache.group(2)} misses ({cache.group(3)}) | memory in "
        f"use {' '.join(mem)} GiB")


def train(ctx) -> None:
    ckpt, jsonl = ctx["work"] / "ckpt", ctx["work"] / "train.jsonl"
    env = child_env(ctx["one_chip"])
    env["TMPDIR"] = str(ctx["synth_tmp"])  # where --synthetic writes
    out = run_child(
        "train",
        [PY, "-m", f"{PKG}.train", "--synthetic", "--synthetic-per-class",
         str(PER_CLASS), "--batch-size", str(PER_CHIP), "--epochs", "2",
         "--checkpoint-dir", str(ckpt), "--checkpoint-every-epochs", "2",
         "--metrics-jsonl", str(jsonl), *TRAIN_COMMON],
        env=env, log_dir=ctx["logs"], timeout=900)
    check_train_output(out, chips=1, per_chip=PER_CHIP)
    say("  " + find(r"jpeg decoder: \w+", out, "decoder report").group(0))
    rows = train_rows(jsonl)
    losses = [r["train_loss"] for r in rows]
    steps = rows[-1]["step"] if rows else 0
    say(f"  {steps} steps, train_loss per epoch {losses}, test_acc "
        f"{[r['test_acc'] for r in rows]}")
    check(steps >= 6, f"{steps} optimizer steps, wanted >= 6")
    check(all(l == l and abs(l) != float("inf") for r in rows
              for l in (r["train_loss"], r["test_loss"])),
          f"non-finite loss in {rows}")
    check(losses[-1] < losses[0],
          f"train loss did not fall: {losses}")
    # Class k is noise around its own mean colour: anything that trains
    # at all separates the held-out split.
    check(rows[-1]["test_acc"] >= 0.95,
          f"eval accuracy {rows[-1]['test_acc']} on a separable test split")
    check((ckpt / str(steps)).is_dir(), f"no checkpoint step {steps}")
    check((ckpt / "final").is_dir() and (ckpt / "transform.json").is_file(),
          "no final/ export")
    ctx["ckpt"] = ckpt
    (synth,) = ctx["synth_tmp"].glob("vit_synth_*")
    ctx["synth"] = synth


def train_lm(ctx) -> None:
    """The token models through the same entry point: 3 steps of each
    tiny preset (SmallThinker's blocks, then GLM-4.7-Flash's: latent
    attention, a dense layer under routed ones with a shared expert, the
    multi-token-prediction module), the routed experts' Mosaic kernels at
    their smallest, XLA attention at T = 64, then the eval pass."""
    # (preset, routed blocks, the objective's ceiling: log 256 = 5.5, and
    # 1.3 x that with the module's term)
    for preset, routed, ceiling in (("lm-tiny", 4, 6.0),
                                    ("mla-tiny", 3, 8.0)):
        jsonl = ctx["work"] / f"train_{preset}.jsonl"
        out = run_child(
            "train-lm",
            [PY, "-m", f"{PKG}.train", "--model", "lm", "--preset", preset,
             "--synthetic", "--batch-size", "8", "--epochs", "1",
             "--steps-per-epoch", "3", "--metrics-jsonl", str(jsonl)],
            env=child_env(ctx["one_chip"]), log_dir=ctx["logs"], timeout=600)
        m = find(r"mesh: \{'data': (\d+), .*platform: (\w+) \|", out,
                 "model/mesh line")
        check(int(m.group(1)) == 1 and m.group(2) == "tpu",
              f"{preset} trained on data={m.group(1)} {m.group(2)}")
        calls = find(r"^train step: (\d+) Mosaic kernel calls: (.*)$", out,
                     "Mosaic call report").group(2)
        check(all(k in calls for k in (f"moe_gmm_fwd x{3 * routed}",
                                       f"moe_gmm_dx x{2 * routed}",
                                       f"moe_gmm_dw x{2 * routed}")),
              f"{preset}: the routed experts' kernels in the lowered step: "
              f"{calls}")
        (row,) = train_rows(jsonl)
        say(f"  {preset}: {row['step']} steps, train_loss "
            f"{row['train_loss']:.4f}, test_loss {row['test_loss']:.4f} | "
            f"{calls}")
        check(row["step"] == 3 and all(
            v == v and abs(v) != float("inf") and 0 < v < ceiling
            for v in (row["train_loss"], row["test_loss"])),
            f"{preset}: 3 steps with finite losses under {ceiling} wanted, "
            f"got {row}")


def serve(ctx) -> None:
    images = sorted((ctx["synth"] / "test").glob("*/*.jpg"))
    requests = [str(p) for p in images[::max(1, len(images) // 6)][:6]]
    check(len(requests) >= 4, f"only {len(requests)} test images to serve")
    out = run_child(
        "serve",
        [PY, "-m", f"{PKG}.serve", "--checkpoint", str(ctx["ckpt"]),
         "--preset", PRESET, "--sync-warmup", "--buckets", "1,8",
         "--classes", *CLASSES],
        env=child_env(ctx["one_chip"]), log_dir=ctx["logs"], timeout=600,
        stdin_text="\n".join(requests + ["::stats"]) + "\n")
    lines = out.splitlines()
    check(len(lines) == len(requests) + 1,
          f"{len(lines)} reply lines for {len(requests)} requests + "
          f"::stats:\n{out[-2000:]}")
    for path, line in zip(requests, lines):
        got = line.split("\t")
        check(len(got) == 3 and got[0] == path and got[1] in CLASSES
              and 0.0 <= float(got[2]) <= 1.0,
              f"reply {line!r} is not '{path}<TAB>label<TAB>prob' with a "
              f"label from {CLASSES}")
        # The checkpoint went trainer -> Orbax export -> server: the
        # answer is the class whose folder the image sits in.
        check(got[1] == Path(path).parent.name,
              f"served {got[1]!r} for {path}")
    stats = json.loads(lines[-1])
    warm = stats["warmup"]
    check("error" not in warm, f"warm-up failed: {warm.get('error')}")
    check(sorted(stats["warm_rungs"]) == [1, 8],
          f"warm rungs {stats['warm_rungs']}, wanted [1, 8]")
    say(f"  {len(requests)} replies, labels "
        f"{[l.split(chr(9))[1] for l in lines[:-1]]}; warm-up "
        f"{warm.get('total_s')}s, compile cache "
        f"{stats['compile_cache']['hits']} hits, "
        f"{stats['compile_cache']['misses']} misses")


def train_dp4(ctx) -> None:
    ckpt, jsonl = ctx["work"] / "ckpt_dp4", ctx["work"] / "train_dp4.jsonl"
    # The default mesh: every chip on the data axis. Six epochs of one
    # 1024-image step each, over the images the first phase wrote.
    out = run_child(
        "train-dp4",
        [PY, "-m", f"{PKG}.train", "--train-dir", str(ctx["synth"] / "train"),
         "--test-dir", str(ctx["synth"] / "test"), "--batch-size",
         str(4 * PER_CHIP), "--epochs", "6", "--checkpoint-dir", str(ckpt),
         "--checkpoint-every-epochs", "6", "--metrics-jsonl", str(jsonl),
         *TRAIN_COMMON],
        env=child_env(), log_dir=ctx["logs"], timeout=900)
    check_train_output(out, chips=4, per_chip=PER_CHIP)
    rows = train_rows(jsonl)
    losses = [r["train_loss"] for r in rows]
    say(f"  {rows[-1]['step']} steps, train_loss per epoch {losses}")
    check(rows[-1]["step"] == 6 and all(l == l for l in losses)
          and losses[-1] < losses[0],
          f"dp=4 losses not finite and falling over 6 steps: {losses}")
    check((ckpt / "final").is_dir(), "no final/ export from the dp=4 run")


def first_step_loss(ctx, name: str, chips) -> float:
    """One step at the one-chip batch, dropout off: a third of it per
    class, rounded up (86 x 3 = 258 images), is one batch an epoch, so
    the epoch's train_loss IS the first step's."""
    jsonl = ctx["work"] / f"{name}.jsonl"
    env = child_env(chips)
    env["TMPDIR"] = tempfile.mkdtemp(dir=ctx["work"])
    run_child(
        name,
        [PY, "-m", f"{PKG}.train", "--synthetic", "--synthetic-per-class",
         str(-(-PER_CHIP // 3)), "--batch-size", str(PER_CHIP), "--epochs",
         "1", "--dropout", "0", "--metrics-jsonl", str(jsonl),
         *TRAIN_COMMON],
        env=env, log_dir=ctx["logs"], timeout=900)
    (row,) = train_rows(jsonl)
    check(row["step"] == 1, f"{name} took {row['step']} steps, wanted 1")
    return row["train_loss"]


def loss_dp1_dp4(ctx) -> None:
    one = first_step_loss(ctx, "loss-dp1", [0])
    four = first_step_loss(ctx, "loss-dp4", None)
    gap = abs(one - four) / abs(one)
    say(f"  first-step loss, dropout off, global batch {PER_CHIP}: one "
        f"chip {one!r}, dp=4 {four!r}, relative gap {gap:.2e} "
        f"(tolerance {LOSS_RTOL:g})")
    check(gap <= LOSS_RTOL,
          f"dp=4 first-step loss {four} differs from the one-chip {one} "
          f"by {gap:.2e} > {LOSS_RTOL:g}")


def offline(ctx) -> None:
    pack, out_dir = ctx["work"] / "pack", ctx["work"] / "offline"
    run_child("pack", [PY, "-m", f"{PKG}.data.pack",
                       str(ctx["synth"] / "test"), str(pack)],
              env={**child_env(), "JAX_PLATFORMS": "cpu"},
              log_dir=ctx["logs"], timeout=300)
    out = run_child(
        "offline",
        [PY, str(REPO / "tools" / "batch_infer.py"), str(pack),
         "--checkpoint", str(ctx["ckpt"]), "--num-classes", "3", "--preset",
         PRESET, "--out", str(out_dir), "--batch-size", "128"],
        env=child_env(), log_dir=ctx["logs"], timeout=600)
    summary = json.loads(find(r'^(\{"metric": "batch_infer".*)$', out,
                              "batch_infer summary").group(1))
    n = len(list((ctx["synth"] / "test").glob("*/*.jpg")))
    say(f"  {summary['records']} records over {summary['devices']} "
        f"devices, ladder {summary.get('ladder')}")
    check(summary["records"] == n and summary["devices"] == 4,
          f"swept {summary['records']} of {n} records over "
          f"{summary['devices']} devices")
    import numpy as np
    probs = np.load(out_dir / "outputs.npy")
    check(probs.shape == (n, 3) and bool(np.isfinite(probs).all())
          and bool(np.allclose(probs.sum(axis=1), 1.0, atol=1e-3)),
          f"outputs.npy {probs.shape} is not {n} finite softmax rows")


FOUR_CHIP = [("train-dp4", train_dp4), ("loss-dp1-dp4", loss_dp1_dp4),
             ("offline", offline)]


def main() -> int:
    if not (REPO / PKG).is_dir():
        say(f"FAILED: {PKG}/ is not next to this script; nothing to run")
        return 1
    t0 = time.time()
    work = Path(tempfile.mkdtemp(prefix="chip_smoke_"))
    logs = REPO / "chiprun_out" / "chip_smoke"
    shutil.rmtree(logs, ignore_errors=True)
    logs.mkdir(parents=True)
    ctx = {"work": work, "logs": logs, "synth_tmp": work / "synth"}
    ctx["synth_tmp"].mkdir()
    passed, failed = [], []

    def run(name, fn) -> bool:
        say(f"--- {name}")
        try:
            fn(ctx)
        except PhaseFailed as e:
            say(f"{name}: FAILED: {e}")
            failed.append(name)
            return False
        except Exception:  # noqa: BLE001 — a check that could not even
            # read the child's output is a failed phase like any other
            say(f"{name}: FAILED:\n{traceback.format_exc()}")
            failed.append(name)
            return False
        passed.append(name)
        return True

    try:
        # Every later phase needs the device and the trained checkpoint.
        if run("probe", probe) and run("train", train):
            run("train-lm", train_lm)
            run("serve", serve)
            if ctx["device"]["count"] >= 4:
                for name, fn in FOUR_CHIP:
                    run(name, fn)
            else:
                say(f"skipped {[n for n, _ in FOUR_CHIP]}: this machine "
                    f"shows {ctx['device']['count']} < 4 chips")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    say(f"passed {passed}, failed {failed}, {time.time() - t0:.0f}s; child "
        f"logs in {logs}")
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": ctx["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
