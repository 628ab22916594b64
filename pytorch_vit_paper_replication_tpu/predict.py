"""CLI single-image / directory prediction.

The scriptable face of :mod:`.predictions` (reference
``pred_and_plot_image``):

    python -m pytorch_vit_paper_replication_tpu.predict \\
        image1.jpg image2.jpg \\
        --checkpoint runs/ckpt --classes pizza steak sushi \\
        --preset ViT-B/16 --plot-dir preds/

(Images are positional; keep them before ``--classes``, whose greedy
nargs would otherwise swallow them — or sidestep the footgun entirely
with ``--classes-file labels.txt``, one class name per line.)
"""

from __future__ import annotations

import argparse
from pathlib import Path

from .configs import PRESETS
from .predictions import pred_and_plot_image, predict_batch


def main(argv=None):
    p = argparse.ArgumentParser(description="TPU ViT prediction")
    p.add_argument("images", nargs="+", help="image files to classify")
    p.add_argument("--checkpoint", required=True,
                   help="params checkpoint dir (from save_model/Checkpointer)")
    cls_group = p.add_mutually_exclusive_group(required=True)
    cls_group.add_argument("--classes", nargs="+",
                           help="class names in training order (greedy "
                                "nargs: keep image paths BEFORE this "
                                "flag, or use --classes-file)")
    cls_group.add_argument("--classes-file",
                           help="file with one class name per line — "
                                "immune to the --classes greedy-nargs "
                                "footgun that swallows trailing image "
                                "paths")
    p.add_argument("--preset", choices=sorted(PRESETS), default="ViT-B/16")
    p.add_argument("--image-size", type=int, default=None,
                   help="defaults to the checkpoint's recorded "
                        "transform.json image size, else 224")
    p.add_argument("--no-normalize", action="store_true",
                   help="disable ImageNet normalization (default follows "
                        "the checkpoint's transform.json when present, "
                        "else the reference predict default: normalized)")
    p.add_argument("--plot-dir", type=str, default=None)
    from .compile_cache import add_cache_cli, configure
    add_cache_cli(p)
    args = p.parse_args(argv)

    # Before the first jit: directory prediction compiles one forward
    # per bucket rung — all cache hits on the second invocation.
    configure(args.compile_cache_dir)

    from .predictions import load_class_names
    classes = (load_class_names(args.classes_file) if args.classes_file
               else args.classes)

    # One shared load contract with serve/: the checkpoint's recorded
    # transform.json wins (so a 384px checkpoint predicts at 384 with no
    # flags); explicit flags override.
    from .predictions import load_inference_checkpoint
    model, params, transform, _ = load_inference_checkpoint(
        args.checkpoint, args.preset, len(classes),
        image_size=args.image_size,
        normalize=False if args.no_normalize else None)

    if args.plot_dir:
        Path(args.plot_dir).mkdir(parents=True, exist_ok=True)
        for img in args.images:
            out = Path(args.plot_dir) / (Path(img).stem + "_pred.png")
            label, prob = pred_and_plot_image(
                model, params, classes, img, transform=transform,
                image_size=args.image_size, save_path=out)
            print(f"{img}: {label} ({prob:.3f}) -> {out}")
    else:
        for img, (label, prob) in zip(args.images, predict_batch(
                model, params, args.images, classes,
                transform=transform, image_size=args.image_size)):
            print(f"{img}: {label} ({prob:.3f})")


if __name__ == "__main__":
    main()
