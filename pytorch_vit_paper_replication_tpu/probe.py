"""Linear-probe workflow: extract frozen features once, fit a linear head.

The reference ships a headless ViT for exactly this
(``models/vit_no_classifier.py`` — returns the final-LN token sequence) but
never wires a probe; BASELINE.json config #4 (Food-101 linear probe) makes
it a first-class workflow here. Differs from ``--freeze-backbone``
fine-tuning in cost: the backbone forward runs ONCE per example, features
are cached host-side, and the head trains on them full-batch — thousands of
head epochs cost less than one backbone epoch.

API: :func:`extract_features` → :func:`train_linear_probe` →
:func:`evaluate_probe`. CLI::

    python -m pytorch_vit_paper_replication_tpu.probe \\
        --train-dir data/train --test-dir data/test \\
        --checkpoint runs/ckpt --preset ViT-B/16
"""

from __future__ import annotations

import argparse
from pathlib import Path
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .configs import ViTConfig
from .models import ViTFeatureExtractor


def extract_features(
    model: ViTFeatureExtractor,
    params,
    batches: Iterable[Dict[str, np.ndarray]],
    *,
    pool: Optional[str] = None,
) -> Tuple[np.ndarray, np.ndarray]:
    """Run the frozen backbone over `batches`, returning pooled features.

    Args:
      model: a :class:`ViTFeatureExtractor` (backbone-only module).
      params: its params — ``full_vit_params["backbone"]`` works directly.
      batches: iterable of ``{"image": [B,H,W,C], "label": [B]}``.
      pool: "cls" or "gap"; defaults to the model config's pooling.

    Returns:
      ``(features [N, D] float32, labels [N] int32)`` on host.
    """
    pool = pool or model.config.pool

    @jax.jit
    def fwd(p, x):
        tokens = model.apply({"params": p}, x)       # [B, T, D]
        pooled = tokens[:, 0] if pool == "cls" else tokens.mean(axis=1)
        return pooled.astype(jnp.float32)

    feats, labels = [], []
    for b in batches:
        feats.append(np.asarray(fwd(params, jnp.asarray(b["image"]))))
        labels.append(np.asarray(b["label"], np.int32))
    return np.concatenate(feats), np.concatenate(labels)


def train_linear_probe(
    features: np.ndarray,
    labels: np.ndarray,
    num_classes: int,
    *,
    epochs: int = 200,
    learning_rate: float = 1e-2,
    weight_decay: float = 0.0,
    seed: int = 0,
) -> Dict[str, jnp.ndarray]:
    """Fit ``softmax(W f + b)`` on cached features by full-batch Adam.

    The whole optimization is one ``lax.scan`` — a single XLA program, no
    per-epoch host round-trips. Returns ``{"kernel": [D, C], "bias": [C]}``.
    """
    x = jnp.asarray(features, jnp.float32)
    y = jnp.asarray(labels, jnp.int32)
    d = x.shape[-1]
    rng = jax.random.key(seed)
    head = {
        "kernel": jax.random.normal(rng, (d, num_classes), jnp.float32) * 0.01,
        "bias": jnp.zeros((num_classes,), jnp.float32),
    }
    tx = optax.adamw(learning_rate, weight_decay=weight_decay)
    opt_state = tx.init(head)

    def loss_fn(h):
        logits = x @ h["kernel"] + h["bias"]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, y).mean()

    def step(carry, _):
        h, o = carry
        grads = jax.grad(loss_fn)(h)
        updates, o = tx.update(grads, o, h)
        return (optax.apply_updates(h, updates), o), None

    (head, _), _ = jax.lax.scan(step, (head, opt_state), None, length=epochs)
    return jax.device_get(head)


def evaluate_probe(head, features: np.ndarray,
                   labels: np.ndarray) -> Dict[str, float]:
    """Accuracy/loss of a trained probe head on (features, labels)."""
    x = jnp.asarray(features, jnp.float32)
    y = jnp.asarray(labels, jnp.int32)
    logits = x @ jnp.asarray(head["kernel"]) + jnp.asarray(head["bias"])
    loss = optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()
    acc = (jnp.argmax(logits, -1) == y).mean()
    return {"loss": float(loss), "acc": float(acc)}


def _backbone_params(args, cfg: ViTConfig, model: ViTFeatureExtractor):
    """Backbone params from --checkpoint (this package's export) or
    --pretrained (torch .pth), else random init."""
    if args.checkpoint:
        from .checkpoint import load_model
        from .models import ViT

        # The Orbax restore template must match the SAVED tree, including
        # the head the probe discards — hence --num-classes.
        full = ViT(cfg.replace(num_classes=args.num_classes))
        template = jax.eval_shape(
            lambda: full.init(jax.random.key(0), jnp.zeros(
                (1, cfg.image_size, cfg.image_size, 3))))["params"]
        ckpt = Path(args.checkpoint)
        if (ckpt / "final").is_dir():
            ckpt = ckpt / "final"
        return load_model(ckpt, template)["backbone"]
    if args.pretrained:
        from .transfer import convert_torch_vit_state_dict, load_torch_file

        sd = load_torch_file(args.pretrained)
        return convert_torch_vit_state_dict(sd, cfg)["backbone"]
    print("[WARN] no --checkpoint/--pretrained: probing a RANDOM backbone")
    return model.init(jax.random.key(0), jnp.zeros(
        (1, cfg.image_size, cfg.image_size, 3)))["params"]


def main(argv=None) -> Dict[str, float]:
    from .configs import PRESETS
    from .data import create_dataloaders
    from .data.transforms import make_transform

    p = argparse.ArgumentParser(description="ViT linear probe")
    p.add_argument("--train-dir", required=True)
    p.add_argument("--test-dir", required=True)
    p.add_argument("--preset", choices=sorted(PRESETS), default="ViT-B/16")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--checkpoint", default=None,
                   help="trained checkpoint dir (this package's format)")
    p.add_argument("--num-classes", type=int, default=None,
                   help="class count the --checkpoint was trained with "
                        "(sizes the restore template's head)")
    p.add_argument("--pretrained", default=None,
                   help="torch .pth state_dict for the backbone")
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--probe-epochs", type=int, default=200)
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--weight-decay", type=float, default=0.0)
    p.add_argument("--no-normalize", action="store_true")
    from .compile_cache import add_cache_cli, configure
    add_cache_cli(p)
    args = p.parse_args(argv)
    # The probe re-pays the frozen-backbone forward compile every
    # invocation; with a cache, only the first run compiles.
    configure(args.compile_cache_dir)
    if args.checkpoint and not args.num_classes:
        p.error("--num-classes is required with --checkpoint (it sizes the "
                "saved head in the restore template)")

    cfg = PRESETS[args.preset](num_classes=1, image_size=args.image_size)
    model = ViTFeatureExtractor(cfg)
    params = _backbone_params(args, cfg, model)

    transform = make_transform(
        args.image_size, pretrained=bool(args.pretrained),
        normalize=not args.no_normalize)
    train_dl, test_dl, classes = create_dataloaders(
        args.train_dir, args.test_dir, transform,
        batch_size=args.batch_size)
    print(f"extracting features for {len(classes)} classes...")
    train_f, train_y = extract_features(model, params, train_dl)
    test_f, test_y = extract_features(model, params, test_dl)

    head = train_linear_probe(
        train_f, train_y, len(classes), epochs=args.probe_epochs,
        learning_rate=args.lr, weight_decay=args.weight_decay)
    train_m = evaluate_probe(head, train_f, train_y)
    test_m = evaluate_probe(head, test_f, test_y)
    print(f"probe: train_acc {train_m['acc']:.4f} | "
          f"test_acc {test_m['acc']:.4f} | test_loss {test_m['loss']:.4f}")
    return {"train_acc": train_m["acc"], "test_acc": test_m["acc"],
            "test_loss": test_m["loss"]}


if __name__ == "__main__":
    main()
