"""Single-image inference + visualization.

Port of the reference's ``going_modular/predictions.py``
(``pred_and_plot_image``, :20-83): open an image, apply the eval transform
(Resize + [0,1] + ImageNet normalize by default, its :46-54), run a
batch-of-1 forward, softmax→argmax, and optionally plot the image titled
with the predicted class and probability.

TPU notes: the forward is jit-cached per (model, image size); prediction
over a *directory* batches images together instead of looping batch-of-1 —
single-image inference underutilizes an MXU badly.
"""

from __future__ import annotations

import functools
from pathlib import Path
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from PIL import Image

from .data.transforms import Transform, eval_transform


@functools.lru_cache(maxsize=8)
def _jitted_forward(model):
    # Keyed on the module itself (flax modules hash by config), not the
    # bound ``model.apply`` — bound methods of *equal* models compare
    # equal, which would silently share one cache slot (and its jit traces)
    # across models whose behavior-relevant config differs.
    return jax.jit(lambda params, x: jax.nn.softmax(
        model.apply({"params": params}, x).astype(jnp.float32), axis=-1))


def predict_image(
    model,
    params: Any,
    image: str | Path | Image.Image | np.ndarray,
    class_names: Optional[Sequence[str]] = None,
    transform: Optional[Transform] = None,
    image_size: int = 224,
) -> Tuple[str | int, float, np.ndarray]:
    """Classify one image; returns (predicted label, probability, probs).

    ``image`` may be a path, a PIL image, or an already-transformed NHWC
    array.
    """
    if transform is None:
        transform = eval_transform(image_size)
    if isinstance(image, (str, Path)):
        with Image.open(image) as img:
            # vitlint: hot-path-ok(host-side input prep, before dispatch)
            arr = np.asarray(transform(img))
    elif isinstance(image, Image.Image):
        # vitlint: hot-path-ok(host-side input prep, before dispatch)
        arr = np.asarray(transform(image))
    else:
        # vitlint: hot-path-ok(host-side input prep, before dispatch)
        arr = np.asarray(image, np.float32)
    x = jnp.asarray(arr)[None]
    # Batch-of-1 drain: the caller wants host-side probs.
    # vitlint: hot-path-ok(single-request response drain)
    probs = np.asarray(_jitted_forward(model)(params, x)[0])
    idx = int(probs.argmax())
    label = class_names[idx] if class_names is not None else idx
    return label, float(probs[idx]), probs


def predict_batch(
    model,
    params: Any,
    images: Sequence[str | Path],
    class_names: Optional[Sequence[str]] = None,
    transform: Optional[Transform] = None,
    image_size: int = 224,
    buckets: Optional[Sequence[int]] = None,
) -> List[Tuple[str | int, float]]:
    """Classify many images in device batches (the TPU-friendly path).

    Batches are chunked onto the serve **bucket ladder**
    (``serve.bucketing``, shared with the online engine) — full top-rung
    chunks plus one padded-and-masked tail — so a 1000-image directory
    compiles at most ``len(ladder)`` forward shapes instead of one per
    residual batch size. Pad rows are masked out of the results; rows of
    a ViT forward are independent, so they cannot perturb real rows.
    ``buckets=None`` uses the serve default ladder. Dispatch is
    pipelined: buckets are issued asynchronously (bounded in-flight
    window) and results fetched with one ``device_get`` per directory
    up to 8 chunks, so host→device copies overlap device compute
    instead of serializing behind it.
    """
    from .serve.bucketing import (DEFAULT_BUCKETS, pad_rows_to_bucket,
                                  plan_buckets)

    if transform is None:
        transform = eval_transform(image_size)
    ladder = tuple(buckets) if buckets is not None else DEFAULT_BUCKETS
    arrs = []
    for p in images:
        with Image.open(p) as img:
            # vitlint: hot-path-ok(host-side input prep, before dispatch)
            arrs.append(np.asarray(transform(img)))
    fwd = _jitted_forward(model)
    # Dispatch buckets asynchronously — jnp.asarray starts the next
    # chunk's host→device copy while the previous chunk's forward still
    # computes (jax's async dispatch), instead of the old per-bucket
    # np.asarray sync that serialized transfer behind compute. Results
    # come back in ONE device_get per directory for any directory up to
    # `window` chunks (2048 images at the default ladder); beyond that
    # the oldest chunk is fetched early so queued executions can't pin
    # unbounded input HBM.
    window = 8
    pending: List[Any] = []
    fetched: List[np.ndarray] = []
    masks: List[np.ndarray] = []
    done = 0
    for bucket in plan_buckets(len(arrs), ladder):
        take = min(bucket, len(arrs) - done)
        chunk = np.stack(arrs[done:done + take])
        done += take
        padded, mask = pad_rows_to_bucket(chunk, bucket)
        masks.append(mask)
        pending.append(fwd(params, jnp.asarray(padded)))
        if len(pending) >= window:
            # vitlint: hot-path-ok(bounded-window drain: oldest chunk only, caps queued input HBM)
            fetched.append(jax.device_get(pending.pop(0)))
    # vitlint: hot-path-ok(ONE final drain per directory, r11 contract)
    fetched.extend(jax.device_get(pending))
    out: List[Tuple[str | int, float]] = []
    for probs, mask in zip(fetched, masks):
        for row in probs[mask.astype(bool)]:
            idx = int(row.argmax())
            label = class_names[idx] if class_names is not None else idx
            out.append((label, float(row[idx])))
    return out


def load_class_names(path: str | Path) -> List[str]:
    """Read class names from a file, one label per line (blank lines and
    ``#`` comments skipped) — the ``--classes-file`` format shared by
    ``predict.py`` and the serve CLI."""
    names = []
    for line in Path(path).read_text().splitlines():
        line = line.strip()
        if line and not line.startswith("#"):
            names.append(line)
    if not names:
        raise ValueError(f"no class names in {path}")
    return names


MODEL_META = "model_meta.json"


def write_model_meta(checkpoint_dir: str | Path, cfg, *,
                     extra: Optional[dict] = None) -> Path:
    """Record the export's model identity (``model_meta.json`` next to
    ``transform.json``): the tier label, the architecture-identity
    slice, and the full config fingerprint. Written at export time by
    train.py (and copied forward by the deploy
    gate), read back by :func:`load_inference_checkpoint` so restoring
    a Ti student into a B/16 entry point refuses loudly with which-tier
    guidance instead of shape-erroring mid-warmup."""
    from .compile_cache import config_fingerprint
    from .configs import arch_of, model_tier
    from .utils.atomic import atomic_write_json

    meta = {
        "model_tier": model_tier(cfg),
        "arch": arch_of(cfg),
        "num_classes": int(cfg.num_classes),
        "config_fingerprint": config_fingerprint(cfg),
    }
    if extra:
        meta.update(extra)
    return atomic_write_json(Path(checkpoint_dir) / MODEL_META, meta)


def load_model_meta(checkpoint: str | Path) -> Optional[dict]:
    """The recorded ``model_meta.json`` (next to the export, or its
    parent run dir — the ``transform.json`` resolution order), or None
    for pre-meta checkpoints (they keep loading exactly as before)."""
    import json

    ckpt = Path(checkpoint)
    if (ckpt / "final").is_dir():
        ckpt = ckpt / "final"
    for d in (ckpt, ckpt.parent):
        meta_file = d / MODEL_META
        if meta_file.is_file():
            meta = json.loads(meta_file.read_text())
            if isinstance(meta, dict):
                return meta
    return None


def check_model_meta(checkpoint: str | Path, preset: str, cfg) -> None:
    """Refuse a checkpoint whose recorded architecture does not match
    the requested preset's — loudly, naming the tier that WOULD load,
    before any params restore or warmup compile spends minutes on a
    guaranteed shape error."""
    from .configs import arch_of

    meta = load_model_meta(checkpoint)
    if not meta or not isinstance(meta.get("arch"), dict):
        return  # pre-meta checkpoint: nothing recorded to compare
    if meta["arch"] == arch_of(cfg):
        return
    recorded = meta.get("model_tier", "<unrecorded tier>")
    diffs = ", ".join(
        f"{k}={meta['arch'].get(k)}!={v}"
        for k, v in arch_of(cfg).items() if meta["arch"].get(k) != v)
    raise ValueError(
        f"checkpoint {checkpoint} was exported from a {recorded} model "
        f"but is being restored as preset {preset!r} ({diffs}) — the "
        "params tree cannot fit this architecture and would shape-error "
        f"mid-warmup. Pass --preset {recorded} (or point at a {preset} "
        "checkpoint).")


def resolve_transform_spec(checkpoint: str | Path, *,
                           image_size: Optional[int] = None,
                           normalize: Optional[bool] = None) -> dict:
    """The checkpoint's preprocessing identity WITHOUT loading params:
    the recorded ``transform.json`` (next to the export, or its parent
    run dir) over the reference predict defaults (224px, normalize ON),
    explicit overrides last."""
    import json

    ckpt = Path(checkpoint)
    if (ckpt / "final").is_dir():
        ckpt = ckpt / "final"  # a training --checkpoint-dir
    spec = dict(image_size=224, pretrained=False, normalize=True)
    for d in (ckpt, ckpt.parent):
        tf_file = d / "transform.json"
        if tf_file.is_file():
            spec.update(json.loads(tf_file.read_text()))
            break
    if image_size is not None:
        spec["image_size"] = int(image_size)
    if normalize is not None:
        spec["normalize"] = bool(normalize)
    return spec


def load_inference_checkpoint(checkpoint: str | Path, preset: str,
                              num_classes: int, *,
                              image_size: Optional[int] = None,
                              normalize: Optional[bool] = None):
    """Resolve a params export (or a training ``--checkpoint-dir``) into
    ``(model, params, transform, spec)``.

    The ONE copy of the inference-load contract, shared by ``predict.py``
    and ``serve.InferenceEngine.from_checkpoint`` so serving
    preprocessing can never drift from offline prediction: a training
    ``--checkpoint-dir`` resolves to its ``final`` params-only export,
    and the run's recorded ``transform.json`` (image size,
    pretrained-crop geometry, normalize) wins over the reference predict
    default (224px, normalize ON) unless explicitly overridden here
    (``normalize=None`` / ``image_size=None`` mean "no override").
    """
    from .checkpoint import load_model
    from .compile_cache import warn_if_uncached
    from .configs import PRESETS
    from .data.transforms import make_transform
    from .models import ViT

    # Silent multi-minute warmups are the cold-start failure mode: on a
    # real accelerator with no persistent compile cache, every predict/
    # serve/probe process start re-compiles the full forward set. Once
    # per process, point at the flag.
    warn_if_uncached("inference")

    ckpt = Path(checkpoint)
    if (ckpt / "final").is_dir():
        ckpt = ckpt / "final"  # a training --checkpoint-dir
    spec = resolve_transform_spec(
        checkpoint, image_size=image_size, normalize=normalize)
    transform = make_transform(**spec)

    cfg = PRESETS[preset](num_classes=int(num_classes),
                          image_size=spec["image_size"])
    # Tier guard BEFORE any restore/compile: a Ti student restored into
    # a B/16 entry point refuses with which-tier guidance here instead
    # of shape-erroring minutes later mid-warmup.
    check_model_meta(checkpoint, preset, cfg)
    model = ViT(cfg)
    template = jax.eval_shape(
        lambda: model.init(jax.random.key(0), jnp.zeros(
            (1, cfg.image_size, cfg.image_size, 3))))["params"]
    params = load_model(ckpt, template)
    return model, params, transform, spec


def pred_and_plot_image(
    model,
    params: Any,
    class_names: Sequence[str],
    image_path: str | Path,
    transform: Optional[Transform] = None,
    image_size: int = 224,
    save_path: Optional[str | Path] = None,
):
    """API-parity port of reference ``pred_and_plot_image``
    (predictions.py:20-83): predict + matplotlib figure titled
    ``Pred: <class> | Prob: <p>``."""
    label, prob, _ = predict_image(
        model, params, image_path, class_names, transform, image_size)
    try:
        import matplotlib
        if save_path is not None:
            matplotlib.use("Agg")
        import matplotlib.pyplot as plt
    except Exception:  # pragma: no cover
        print(f"Pred: {label} | Prob: {prob:.3f} (matplotlib unavailable)")
        return label, prob
    with Image.open(image_path) as img:
        fig, ax = plt.subplots()
        ax.imshow(img)
        ax.set_title(f"Pred: {label} | Prob: {prob:.3f}")
        ax.axis("off")
    if save_path is not None:
        fig.savefig(save_path, dpi=120)
    return label, prob
