"""Attention dispatch: one entry point, multiple TPU execution paths.

The reference funnels attention through ``torch.nn.MultiheadAttention``
(``models/vit.py:86-98``). Here the projection layers live in the model
(``models/vit.py`` in this package) and the scaled-dot-product core is a free
function so the execution path can be swapped without touching model code:

* ``"xla"``    — hand-rolled einsum attention with compute-dtype logits
                 storage and an in-fusion f32 softmax. Measured fastest-
                 or-equal on v5e at every length that fits in HBM (within
                 ~5-10% of the 256-block Pallas kernel from 577 to 4096
                 tokens), because the MXU eats the materialized matmuls
                 and the bf16 logits halve the HBM bill that used to make
                 materialization expensive.
* ``"flash"``  — the Pallas flash-attention kernel
                 (:mod:`..ops.flash_attention`), tiled for VMEM with an
                 online-softmax accumulator. O(T) memory: the only path
                 that runs when the ``[B,H,T,T]`` logits cannot fit
                 (t=8192 at B=8,H=12 OOMs the XLA path on 16 GB).
* ``"auto"``   — from what the call can observe. A self-attention
                 call on the packed qkv projection
                 (:func:`self_attention`) on a TPU with no mask, no
                 active attention dropout, bf16 probability storage, a
                 head size of 64 or 128 and a ``[T, T]`` tile that fits
                 VMEM takes the short-sequence kernel pair
                 (:mod:`.short_attention`: the projection read where it
                 lies, ``[T, T]`` never in HBM). Since PR 26 speed does
                 favour a kernel at T = 197: the XLA core ran at 5.6x
                 its roofline there. Anything else is xla, unless the
                 materialized logits would eat a large fraction of HBM
                 (``_FLASH_MEMORY_BYTES``), then flash — between the two
                 of them memory decides, not speed.
* ``"short"`` is not a value: the kernel has no option of its own. Force
  ``"xla"`` or ``"flash"`` to keep it out.

Sequence parallelism rides on top of the dispatch rather than on ``impl``:
tracing under :func:`.partition.on_mesh` (done by ``parallel.api``'s step
builders) with a mesh whose 'seq' axis is >1 makes every eligible attention
call route through ring attention (:mod:`..parallel.ring_attention`) via
``jax.shard_map`` — tokens stay sharded over the ring, K/V rotate over ICI.
Model code never changes; that is the point. The same context makes the
flash kernel run per shard on a data/model mesh (XLA will not partition a
Mosaic call by itself).

**Structure, not a mask.** ``kind`` says which keys a query sees:
``"full"`` (every key: the ViT), ``"causal"`` (key j <= query i) or
``"causal_window"`` (also ``i - j < window``). The flash kernels compute
it from block positions and skip the blocks outside it; the XLA path —
short sequences, and everything off the TPU — builds the ``[T, T]``
boolean it stands for. k and v may have fewer heads than q
(grouped-query attention): flash reads each key/value head where it
lies, the XLA path repeats it. The short-sequence kernel and the
sequence-parallel paths serve ``"full"`` with equal head counts only.

Masks run natively on both single-device paths (in-kernel on flash since
round 4 — broadcast dims stream unmaterialized). The one remaining
fallback is explicit: an active sequence-parallel mesh that
cannot be honored (mask or non-divisible shapes) warns once and uses the
XLA path, which is always numerically correct (under GSPMD it simply
all-gathers K/V). Attention
dropout is first-class on BOTH accelerated paths — in-kernel on flash
(:mod:`.flash_attention`), in-ring on sequence parallel
(:mod:`..parallel.ring_attention`) — via the same positional-hash mask
scheme, so ``attn_dropout > 0`` long-sequence configs keep O(T) /
sharded memory.

All paths compute in the input dtype (bfloat16 recommended) with float32
softmax accumulation.
"""

from __future__ import annotations

import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from . import partition, short_attention
from .quant import PROBS_DTYPES, dequantize_probs, quantize_probs

# auto-dispatch: switch to the Pallas kernel when the XLA path would
# materialize this much for attention logits (+probs +backward residual,
# estimated 3x the logits tensor). 4 GiB leaves the rest of a 16 GB chip
# for params/activations. Below it, the XLA path measures equal-or-
# slightly-faster at every length on v5e (see module docstring), so only
# memory — never speed — selects the kernel.
_FLASH_MEMORY_BYTES = 4 * 1024**3
_FLASH_MIN_SEQ = 512  # Pallas kernel's own tiling floor

# Saturating-softmax constants (see _xla_attention): weights are exact
# for logits <= SHIFT + CLAMP; above that exp saturates (uniform over
# saturated entries) instead of overflowing to NaN. exp(CLAMP) = 5.5e34
# leaves f32 headroom for a ~6000-term saturated row sum.
_SOFTMAX_SHIFT = 16.0
_SOFTMAX_CLAMP = 80.0

def _sp_partition():
    """The active :class:`.partition.Partition` when its seq axis is >1
    (attention must then run sequence-parallel), else None."""
    part = partition.current()
    if part is None or part.size(part.seq_axis) <= 1:
        return None
    return part


@functools.lru_cache(maxsize=None)
def _warn_once(msg: str) -> None:
    warnings.warn(msg, stacklevel=3)


def _sp_attention(q, k, v, part, *, dropout_rate=0.0, dropout_rng=None,
                  deterministic=True):
    """Dispatch to ring or Ulysses attention over the seq axis
    (shard_map'd, per the partition's sp_impl).

    Batch is sharded over the data axis and heads over the model axis (a
    size-1 axis is a no-op), so the same call serves dp x tp x sp meshes.
    Attention dropout runs in-collective (positional hash masks shared
    with the flash kernel), so long sequences keep their sharded memory
    footprint with ``attn_dropout > 0`` on either impl.
    """
    from ..parallel.ring_attention import make_ring_attention
    from ..parallel.ulysses import make_ulysses_attention

    mesh = part.mesh
    make = (make_ulysses_attention if part.sp_impl == "ulysses"
            else make_ring_attention)
    head_axis = (part.model_axis if part.model_axis in mesh.axis_names
                 else None)
    fn = make(mesh, part.seq_axis, data_axis=part.data_axis,
              head_axis=head_axis,
              dropout_rate=dropout_rate,
              dropout_rng=dropout_rng,
              deterministic=deterministic)
    return fn(q, k, v)


def _softmax32(logits32, softmax: str):
    """The XLA path's f32 softmax over [B, H, T, Tk] logits — factored so
    the plain path and the quantized-storage custom_vjp share one
    definition. See ``_xla_attention`` for the saturating/exact trade."""
    if softmax == "exact":
        m = jax.lax.stop_gradient(jnp.max(logits32, axis=-1,
                                          keepdims=True))
        e = jnp.exp(logits32 - m)
        return e / jnp.sum(e, axis=-1, keepdims=True)
    e = jnp.exp(jnp.minimum(logits32 - _SOFTMAX_SHIFT, _SOFTMAX_CLAMP))
    return e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-35)


# --- low-precision materialized-probs storage (the bytes-side attack) -----
#
# PERF.md r5 priced the residual 25 MFU points at T=197 as ~98 ms of pure
# HBM traffic on the materialized [B,H,T,T] softmax tensors, and measured
# every graph-RESTRUCTURING attack (flash kernel, remat, deferred
# normalization, ...) negative at these shapes. The one untried mechanism
# class is shrinking the BYTES: probs live in [0,1], so 8-bit storage
# (fp8 or fixed-point u8, ops/quant.py) halves the largest tensor's
# traffic without touching the graph shape. The custom_vjp below is what
# makes that real on the backward side too: jax's AD would save the bf16
# weights as the PV-matmul residual regardless of what the forward
# stored, so the narrow tensor must be the residual BY CONSTRUCTION, with
# the backward dequantizing in-register.
#
# Backward math: with w = e/(s+eps) (either softmax flavor), the exact
# vjp is dl_k = w_k * (dw_k - sum_j dw_j w_j) — the epsilon and any
# constant shift cancel. One approximation, documented: the saturating
# flavor's clamp gate (zero grad through entries with logit-shift > 80)
# is not reproducible from the saved probs alone and is treated as
# pass-through; the saturated regime is a documented pathology
# (attention-logit growth) where quantized storage should not be used
# anyway — config validation is the guard rail, this comment is the
# record.


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _quantized_softmax_pv(logits32, v, softmax: str, probs_dtype: str,
                          residual_dtype: str, out_dtype: str):
    """softmax(logits) @ v with the materialized probs stored in
    ``probs_dtype`` and the backward residual stored in
    ``residual_dtype`` (ops/quant.py formats; "bf16" = compute dtype).

    ``logits32``: f32 [B,H,T,Tk], already scaled/masked. ``v``:
    [B,Tk,H,Dh]. Returns [B,T,H,Dh] in ``out_dtype``.
    """
    out, _ = _quantized_softmax_pv_fwd(logits32, v, softmax, probs_dtype,
                                       residual_dtype, out_dtype)
    return out


def _quantized_softmax_pv_fwd(logits32, v, softmax, probs_dtype,
                              residual_dtype, out_dtype):
    w32 = _softmax32(logits32, softmax)
    if probs_dtype == "bf16":
        # Forward-exact storage; only the backward residual is narrow.
        w_pv = w32.astype(out_dtype)
        wq = (w_pv if residual_dtype == "bf16"
              else quantize_probs(w32, residual_dtype))
    else:
        wq_fwd = quantize_probs(w32, probs_dtype)
        w_pv = dequantize_probs(wq_fwd, probs_dtype, out_dtype)
        if residual_dtype == probs_dtype:
            wq = wq_fwd
        elif residual_dtype == "bf16":
            # "bf16" means COMPUTE dtype everywhere in this subsystem
            # (ops/quant.py docstring) — for f32-compute models the
            # residual stays f32, matching the probs_dtype=="bf16"
            # branch above.
            wq = w32.astype(out_dtype)
        else:
            wq = quantize_probs(w32, residual_dtype)
    out = jnp.einsum("bhqk,bkhd->bqhd", w_pv, v)
    return out, (wq, v)


def _quantized_softmax_pv_bwd(softmax, probs_dtype, residual_dtype,
                              out_dtype, res, g):
    wq, v = res
    w = (wq if residual_dtype == "bf16"
         else dequantize_probs(wq, residual_dtype, out_dtype))
    # Mirror the AD path's matmul dtypes: operands in the compute dtype
    # (the MXU accumulates f32 internally either way).
    dv = jnp.einsum("bhqk,bqhd->bkhd", w, g)
    dw = jnp.einsum("bqhd,bkhd->bhqk", g, v)
    w32 = w.astype(jnp.float32)
    dw32 = dw.astype(jnp.float32)
    dl = w32 * (dw32 - jnp.sum(dw32 * w32, axis=-1, keepdims=True))
    return dl, dv


_quantized_softmax_pv.defvjp(_quantized_softmax_pv_fwd,
                             _quantized_softmax_pv_bwd)


def _xla_attention(q, k, v, *, dropout_rate: float, dropout_rng,
                   deterministic: bool, mask=None,
                   softmax: str = "saturating",
                   probs_dtype: str = "bf16",
                   residual_dtype: Optional[str] = None):
    """Reference-semantics attention via XLA, shapes [B, T, H, Dh].

    Hand-rolled einsum rather than ``jax.nn.dot_product_attention`` — the
    explicit form measures ~13% faster on the target TPU (the library
    path's vmap-of-dot_general lowers less cleanly) and shares one code
    path with the dropout branch.

    Precision: the MXU always accumulates QK^T in float32, but the
    *stored* ``[B, H, T, T]`` logits tensor is kept in the compute dtype —
    for bfloat16 models that halves the largest HBM tensor in the step and
    measures ~30% faster end-to-end on v5e (the f32 logits round-trip is
    the single biggest HBM consumer in a ViT train step). The softmax
    itself is still computed in float32: the upcast lives inside the XLA
    softmax fusion (VMEM-resident), so it costs no HBM traffic. (r5
    negative result, PERF.md: computing exp in bf16 with an f32 sum wins
    20% on the ISOLATED core vjp but regresses the FULL step 304 -> 318
    ms — the bf16 ``e``/f32 ``s`` pair changes which residuals XLA
    saves; kept f32.)

    ``probs_dtype`` / ``residual_dtype`` (r6, the bytes-side attack):
    storage format of the materialized softmax weights and of the
    backward residual respectively (``ops/quant.py`` formats —
    ``"bf16"``/``"fp8_e4m3"``/``"fp8_e5m2"``/``"u8"``).
    ``residual_dtype=None`` follows ``probs_dtype``. The default
    ``("bf16", None)`` is BIT-IDENTICAL to the pre-r6 path (same jaxpr);
    anything narrower routes through :func:`_quantized_softmax_pv`, whose
    custom_vjp saves the narrow tensor and dequantizes in-register in the
    backward. Quantized storage does not compose with attention dropout
    (the 1/keep rescale pushes weights above the [0,1] packing range):
    such calls warn once and use bf16 storage.
    """
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=q.dtype)
    logits = logits * jnp.asarray(scale, logits.dtype)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    # Hand-rolled softmax rather than jax.nn.softmax: its custom JVP saves
    # the float32 probabilities as a backward residual, which at [B,H,T,T]
    # is the step's largest HBM tensor; the plain-op form lets XLA keep the
    # f32 intermediates inside fusions (measured +16% step throughput).
    #
    # SATURATING softmax (r5 default): the classic row-max subtraction
    # costs a full extra read of the [B,H,T,T] tensor purely for range
    # safety (softmax is shift-invariant, and float rounding is
    # relative, so any in-range shift gives bit-comparable weights). A
    # constant shift with an upper clamp provides the overflow half of
    # that safety cheaper. The EXACT region is row-max logits in
    # roughly [-60, 96]: above 96 entries saturate to uniform with zero
    # gradient through the clamp (rather than NaN); below that,
    # exp(logit - 16) underflows f32 — a whole row under ~-71 collapses
    # to a defined ZERO output/zero grad (epsilon-guarded 0/eps, not
    # 0/0), with a smooth shrink region in between. Both edges are far
    # outside healthy attention scores at scale 1/sqrt(dh) (|logits|
    # <~ 30), but both ARE reachable in pathologies (attention-logit
    # growth in very large ViTs — the ViT-22B/QK-norm regime), so
    # config.attention_softmax="exact" keeps the max-subtracted form,
    # correct at any magnitude. (A two-sided clamp would fix the
    # negative edge gracefully but measures +7 ms/step — it blocks the
    # exp's fusion into the GEMM epilogue; documented trade instead.)
    # The epsilon also gives fully-MASKED rows the same zero-output
    # semantics as the flash kernel. Measured on the B/16 step: 304.6
    # -> 299.5 ms (+1.7%), the row-max read was the last avoidable
    # full-tensor pass. (The softmax itself lives in _softmax32, shared
    # with the quantized-storage custom_vjp.)
    logits32 = logits.astype(jnp.float32)
    rd = residual_dtype if residual_dtype is not None else probs_dtype
    quantized = probs_dtype != "bf16" or rd != "bf16"
    if quantized and not deterministic and dropout_rate > 0.0:
        _warn_once(
            "attention probs quantization (attention_probs_dtype/"
            "attention_probs_residual_dtype) does not compose with "
            "attention dropout — the 1/keep rescale exceeds the [0,1] "
            "packing range; using bf16 storage for dropout calls")
        quantized = False
    if quantized:
        return _quantized_softmax_pv(logits32, v, softmax, probs_dtype,
                                     rd, jnp.dtype(q.dtype).name)
    weights = _softmax32(logits32, softmax)
    if not deterministic and dropout_rate > 0.0:
        from .dropout import dropout as _u8_dropout
        weights = _u8_dropout(weights, dropout_rate, dropout_rng)
    weights = weights.astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", weights, v)


def structure_mask(kind: str, window: int, t: int):
    """The ``[1, 1, T, T]`` boolean (True = attend) that ``kind`` stands
    for, or None for ``"full"``."""
    if kind == "full":
        return None
    i = jnp.arange(t)[:, None]
    j = jnp.arange(t)[None, :]
    vis = j <= i
    if kind == "causal_window":
        vis = vis & (i - j < window)
    return vis[None, None]


def _flash_ok(q) -> bool:
    """auto-mode: use the Pallas kernel only when the XLA path's
    materialized logits would not fit comfortably (and shapes qualify).
    Under a mesh the logits are split over batch and heads, so it is the
    per-device share that is weighed."""
    if jax.default_backend() != "tpu":
        return False
    b, t, h, dh = q.shape
    part = partition.current()
    if part is not None:
        b = -(-b // part.size(part.data_axis))
        h = -(-h // part.size(part.model_axis))
    if t < _FLASH_MIN_SEQ or dh not in (32, 64, 128, 256):
        return False
    logits_bytes = b * h * t * t * jnp.dtype(q.dtype).itemsize
    return 3 * logits_bytes > _FLASH_MEMORY_BYTES


# One scope for every implementation (XLA, flash, ring, ulysses), so that a
# device trace splits `msa` into norm / qkv / attn_core / out and what is
# left: the slices and transposes between them (telemetry/device_trace.py).
@jax.named_scope("attn_core")
def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    mask: Optional[jax.Array] = None,
    heads_already_local: bool = False,
    softmax: str = "saturating",
    probs_dtype: str = "bf16",
    residual_dtype: Optional[str] = None,
    kind: str = "full",
    window: int = 0,
) -> jax.Array:
    """Multi-head scaled dot-product attention.

    Args:
      q, k, v: ``[batch, seq, heads, head_dim]``; k and v may have a
        divisor of q's heads (grouped-query attention).
      impl: ``"xla"``, ``"flash"``, or ``"auto"``.
      kind / window: the attention's structure (module docstring):
        ``"full"``, ``"causal"`` or ``"causal_window"`` over ``window``
        keys.
      dropout_rate / dropout_rng / deterministic: attention-weight dropout
        (reference ``attn_dropout``, models/vit.py:75).
      mask: optional boolean ``[batch, heads, q, k]`` mask (True = attend).
      heads_already_local: set by manual-TP callers (inside ``shard_map``,
        e.g. the pipeline's head-sliced MSA) whose ``q`` already carries
        per-shard heads — the Ulysses divisibility pre-check then uses
        ``heads`` as-is instead of dividing by the model-axis size
        (ADVICE r4: guessing from the mesh under-counted and could
        spuriously route to the gathered XLA fallback).
      softmax: XLA-path softmax flavor — ``"saturating"`` (default,
        +1.7% step: no row-max read; exact for logits <= ~96, saturates
        beyond) or ``"exact"`` (max-subtracted, any magnitude). See
        ``configs.ViTConfig.attention_softmax``. Ignored by the
        flash/ring/ulysses paths, which carry their own exact online
        softmax.
      probs_dtype: storage format for the XLA path's materialized softmax
        weights (``ops/quant.py``: ``"bf16"`` = compute dtype /
        ``"fp8_e4m3"`` / ``"fp8_e5m2"`` / ``"u8"`` fixed-point — probs
        are in [0,1], so u8 quantizes exactly that range in 256 levels).
        The bytes-side attack on the [B,H,T,T] HBM tax (PERF.md r6).
        Irrelevant to — and ignored by — the flash/ring/ulysses paths:
        they never materialize the probs at all.
      residual_dtype: storage format for the backward residual alone
        (``None`` = follow ``probs_dtype``). ``"bf16"`` probs + a narrow
        residual keeps the forward exact and shrinks only the saved
        tensor the backward re-reads.

    Returns:
      ``[batch, seq, heads, head_dim]`` attention output (pre out-projection).

    Masks run natively on BOTH single-device paths (in-kernel on flash
    since round 4 — broadcast dims stream unmaterialized, see
    :func:`..ops.flash_attention.flash_attention`), so a masked call
    keeps flash's O(T) memory class. Degenerate fully-masked rows yield
    a defined ZERO output on flash (zero grads too, ADVICE r4) and on
    the DEFAULT xla path (the saturating softmax's epsilon turns the
    all-zero row into 0/eps = 0); the ``softmax="exact"`` escape hatch
    retains the classic ``finfo.min``-fill behavior there — a uniform
    softmax with nonzero grads — so don't combine "exact" with
    fully-masked rows expecting zeros. The one remaining
    fallback (warns once per process): an active sequence-parallel
    mesh with a mask or shapes not divisible by the mesh axes uses the
    XLA path, which GSPMD keeps correct by gathering K/V instead of
    ring-rotating them. Attention dropout rides the ring natively.
    """
    if impl not in ("xla", "flash", "auto"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if probs_dtype not in PROBS_DTYPES:
        raise ValueError(f"unknown probs_dtype {probs_dtype!r}; "
                         f"expected one of {PROBS_DTYPES}")
    if residual_dtype is not None and residual_dtype not in PROBS_DTYPES:
        raise ValueError(f"unknown residual_dtype {residual_dtype!r}; "
                         f"expected one of {PROBS_DTYPES}")

    if kind not in ("full", "causal", "causal_window"):
        raise ValueError(f"unknown attention kind {kind!r}")
    structured = kind != "full" or k.shape[2] != q.shape[2]

    def xla(q, k, v, mask):
        """The XLA path, given what ``kind`` and the head counts stand
        for: the visibility matrix and repeated key/value heads."""
        if structured:
            group = q.shape[2] // k.shape[2]
            if group > 1:
                k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
            vis = structure_mask(kind, window, q.shape[1])
            if vis is not None:
                mask = vis if mask is None else jnp.logical_and(mask, vis)
        return _xla_attention(q, k, v, dropout_rate=dropout_rate,
                              dropout_rng=dropout_rng,
                              deterministic=deterministic, mask=mask,
                              softmax=softmax, probs_dtype=probs_dtype,
                              residual_dtype=residual_dtype)

    sp = _sp_partition()
    if sp is not None and structured:
        _warn_once(
            "sequence_parallel: ring/ulysses attention serve bidirectional "
            "attention with equal head counts only; using the (gathered) "
            "XLA path instead")
        return xla(q, k, v, mask)
    if sp is not None:
        b, t, h = q.shape[0], q.shape[1], q.shape[2]
        seq_size = sp.size(sp.seq_axis)
        if not heads_already_local:
            # Under GSPMD-TP the traced h is global and must be divided
            # down to the per-shard head count; manual-TP callers hold
            # local heads already and say so via heads_already_local.
            h = max(1, h // sp.size(sp.model_axis))
        if mask is not None:
            _warn_once(
                "sequence_parallel: attention masks are not supported by "
                "ring/ulysses attention; using the (gathered) XLA path "
                "instead")
        elif t % seq_size or b % sp.size(sp.data_axis):
            _warn_once(
                f"sequence_parallel: shape (batch={b}, tokens={t}) not "
                f"divisible by mesh axes {dict(sp.mesh.shape)}; using the "
                "(gathered) XLA path instead. Hint: pool='gap' removes the "
                "odd CLS token from the sequence length")
        elif sp.sp_impl == "ulysses" and h % seq_size:
            _warn_once(
                f"sequence_parallel: sp_impl='ulysses' needs heads ({h}) "
                f"divisible by the seq axis ({seq_size}); using the "
                "(gathered) XLA path instead — or use sp_impl='ring'")
        else:
            return _sp_attention(q, k, v, sp, dropout_rate=dropout_rate,
                                 dropout_rng=dropout_rng,
                                 deterministic=deterministic)
        # Honor the fallback message: never hand seq-sharded operands to
        # the Pallas kernel — GSPMD only guarantees the gathered semantics
        # for the plain XLA ops.
        return xla(q, k, v, mask)

    use_flash = impl == "flash" or (impl == "auto" and _flash_ok(q))
    if use_flash:
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, kind=kind, window=window,
                               mask=mask, dropout_rate=dropout_rate,
                               dropout_rng=dropout_rng,
                               deterministic=deterministic)
    return xla(q, k, v, mask)


def short_attention_ok(qkv_shape, dtype, *, impl, dropout_rate,
                       deterministic, mask, probs_dtype,
                       residual_dtype, kind: str = "full") -> bool:
    """auto-mode: whether the short-sequence kernel pair serves a
    self-attention call on a packed projection of this shape
    ``[B, T, 3, H, Dh]``. Decided from the call alone, before the
    projection exists (the model asks, to lay the projection out for the
    kernel): the backend, the absence of what the kernel does not do
    (mask, causal or windowed structure, active attention dropout,
    quantised probability storage, a sequence-parallel mesh) and the
    (per-shard) shapes
    (:func:`.short_attention.supported`: head size, whole slabs of
    heads, the ``[T, T]`` working set against VMEM). The softmax flavour
    is not asked: the kernel's exact softmax serves either."""
    if impl != "auto" or jax.default_backend() != "tpu":
        return False
    if mask is not None or (not deterministic and dropout_rate > 0.0):
        return False
    if kind != "full":
        return False
    if probs_dtype != "bf16" or residual_dtype not in (None, "bf16"):
        return False
    if _sp_partition() is not None:
        return False
    return short_attention.supported(qkv_shape, dtype)


def self_attention(qkv: jax.Array, *, impl: str = "auto",
                   dropout_rate: float = 0.0,
                   dropout_rng: Optional[jax.Array] = None,
                   deterministic: bool = True,
                   mask: Optional[jax.Array] = None,
                   heads_already_local: bool = False,
                   softmax: str = "saturating",
                   probs_dtype: str = "bf16",
                   residual_dtype: Optional[str] = None,
                   kind: str = "full", window: int = 0) -> jax.Array:
    """Self-attention from the packed qkv projection
    ``[batch, seq, 3, heads, head_dim]`` -> ``[batch, seq, heads,
    head_dim]``; the keywords are :func:`dot_product_attention`'s.
    (A grouped-query projection is not packed this way: its block calls
    :func:`dot_product_attention` with q, k and v.)

    Where ``impl="auto"`` finds the call one the short-sequence kernel
    serves (:func:`short_attention_ok`), the projection goes to it as it
    lies and no q, k or v is ever cut out of it. The kernel's softmax is the
    exact, max-subtracted one, which equals the ``"saturating"`` flavour
    over that flavour's whole exact range. Every other call gets q, k, v
    sliced from the projection and :func:`dot_product_attention`; the
    slices stay outside the ``attn_core`` scope, where a device trace
    has always counted them (``msa_glue``).
    """
    if impl not in ("xla", "flash", "auto"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if short_attention_ok(
            qkv.shape, qkv.dtype, impl=impl, dropout_rate=dropout_rate,
            deterministic=deterministic, mask=mask,
            probs_dtype=probs_dtype, residual_dtype=residual_dtype,
            kind=kind):
        with jax.named_scope("attn_core"):
            return short_attention.short_attention(qkv)
    return dot_product_attention(
        qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], impl=impl,
        dropout_rate=dropout_rate, dropout_rng=dropout_rng,
        deterministic=deterministic, mask=mask,
        heads_already_local=heads_already_local, softmax=softmax,
        probs_dtype=probs_dtype, residual_dtype=residual_dtype,
        kind=kind, window=window)
