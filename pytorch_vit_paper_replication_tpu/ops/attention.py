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
* ``"auto"``   — from what the call can observe (:func:`choose`, the
                 one place that decides). A self-attention call on the
                 packed qkv projection (:func:`self_attention`) on a
                 TPU with no mask, no active attention dropout, a head
                 size of 64 or 128 and a ``[T, T]`` tile that fits
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
``"full"`` (every key: the ViT), ``"causal"`` (key j <= query i),
``"causal_window"`` (also ``i - j < window``) or ``"causal_topk"`` (the
causal keys an indexer selects for each query: a set that differs per
row, which :mod:`.sparse_attention` makes and hands to the flash kernels
as a mask, or to XLA; it asks :func:`choose` which). The flash kernels compute
the first three from block positions and skip the blocks outside them; the XLA path —
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
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import partition, short_attention

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


@functools.lru_cache(maxsize=None)
def _warn_once(msg: str) -> None:
    warnings.warn(msg, stacklevel=3)


def _sp_attention(q, k, v, served, *, dropout_rate=0.0, dropout_rng=None,
                  deterministic=True):
    """``"ring"`` or ``"ulysses"`` attention over the active partition's
    seq axis (shard_map'd).

    Batch is sharded over the data axis and heads over the model axis (a
    size-1 axis is a no-op), so the same call serves dp x tp x sp meshes.
    Attention dropout runs in-collective (positional hash masks shared
    with the flash kernel), so long sequences keep their sharded memory
    footprint with ``attn_dropout > 0`` on either impl.
    """
    from ..parallel.ring_attention import make_ring_attention
    from ..parallel.ulysses import make_ulysses_attention

    part = partition.current()
    mesh = part.mesh
    make = (make_ulysses_attention if served == "ulysses"
            else make_ring_attention)
    head_axis = (part.model_axis if part.model_axis in mesh.axis_names
                 else None)
    fn = make(mesh, part.seq_axis, data_axis=part.data_axis,
              head_axis=head_axis,
              dropout_rate=dropout_rate,
              dropout_rng=dropout_rng,
              deterministic=deterministic)
    return fn(q, k, v)


def _xla_attention(q, k, v, *, dropout_rate: float, dropout_rng,
                   deterministic: bool, mask=None,
                   softmax: str = "saturating"):
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
    # full-tensor pass.
    logits32 = logits.astype(jnp.float32)
    if softmax == "exact":
        m = jax.lax.stop_gradient(jnp.max(logits32, axis=-1,
                                          keepdims=True))
        e = jnp.exp(logits32 - m)
        weights = e / jnp.sum(e, axis=-1, keepdims=True)
    else:
        e = jnp.exp(jnp.minimum(logits32 - _SOFTMAX_SHIFT, _SOFTMAX_CLAMP))
        weights = e / (jnp.sum(e, axis=-1, keepdims=True) + 1e-35)
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


def choose(shape, dtype, k_shape=None, *, impl: str = "auto",
           kind: str = "full", mask=None, dropout_rate: float = 0.0,
           deterministic: bool = True, heads_already_local: bool = False,
           backend: Optional[str] = None) -> Tuple[str, Optional[str]]:
    """Which implementation serves an attention call, from what the call
    can observe: ``(name, reason)``, ``name`` one of ``"short"``,
    ``"flash"``, ``"ring"``, ``"ulysses"``, ``"xla"``. Every other
    function here, and the model where it lays its projections out for
    the kernel before they exist, asks this one; no other place holds a
    rule.

    ``shape`` is the packed projection's ``[B, T, 3, H, Dh]``
    (:func:`self_attention`) or q's ``[B, T, H, Dh]`` with ``k_shape``
    where k has heads of its own; ``backend`` defaults to
    ``jax.default_backend()``; the mesh is the active
    :class:`.partition.Partition`'s. The keywords are the call's
    (:func:`dot_product_attention`). ``reason`` is set where a ``seq``
    mesh could not be honoured: the caller warns with it, once.

    The rules, in order:

    1. A ``seq`` axis > 1 decides alone. Structure (``kind`` or fewer
       key heads), a mask, a batch or token count the mesh does not
       divide, or Ulysses with heads the axis does not divide take the
       XLA path, which GSPMD keeps correct by gathering K/V; never a
       Pallas kernel on seq-sharded operands. Else ``sp_impl``'s ring
       or Ulysses.
    2. A packed call under ``"auto"`` on a TPU with nothing the
       short-sequence kernel does not do (mask, structure, active
       attention dropout) and (per-shard) shapes it serves
       (:func:`.short_attention.supported`: head size, whole slabs of
       heads, the ``[T, T]`` working set against VMEM) takes that
       kernel. The softmax flavour is not asked: the kernel's exact
       softmax serves either.
    3. Flash where forced, or under ``"auto"`` on a TPU where the XLA
       path's logits, probabilities and backward residual (3x the
       per-shard logits) pass ``_FLASH_MEMORY_BYTES`` at a length and
       head size the kernel tiles. Memory decides, not speed.
    4. XLA.
    """
    if impl not in ("xla", "flash", "auto"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if kind not in ("full", "causal", "causal_window", "causal_topk"):
        raise ValueError(f"unknown attention kind {kind!r}")
    packed = len(shape) == 5
    (b, t), (h, dh) = shape[:2], shape[-2:]
    structured = kind != "full" or (k_shape is not None
                                    and k_shape[2] != h)
    part = partition.current()

    if part is not None and part.size(part.seq_axis) > 1:
        seq_size = part.size(part.seq_axis)
        if structured:
            return "xla", (
                "sequence_parallel: ring/ulysses attention serve "
                "bidirectional attention with equal head counts only; "
                "using the (gathered) XLA path instead")
        if mask is not None:
            return "xla", (
                "sequence_parallel: attention masks are not supported by "
                "ring/ulysses attention; using the (gathered) XLA path "
                "instead")
        if t % seq_size or b % part.size(part.data_axis):
            return "xla", (
                f"sequence_parallel: shape (batch={b}, tokens={t}) not "
                f"divisible by mesh axes {dict(part.mesh.shape)}; using the "
                "(gathered) XLA path instead. Hint: pool='gap' removes the "
                "odd CLS token from the sequence length")
        if part.sp_impl == "ring":
            return "ring", None
        if not heads_already_local:
            # Under GSPMD-TP the traced h is global and must be divided
            # down to the per-shard head count; manual-TP callers hold
            # local heads already and say so via heads_already_local.
            h = max(1, h // part.size(part.model_axis))
        if h % seq_size:
            return "xla", (
                f"sequence_parallel: sp_impl='ulysses' needs heads ({h}) "
                f"divisible by the seq axis ({seq_size}); using the "
                "(gathered) XLA path instead — or use sp_impl='ring'")
        return "ulysses", None

    auto_on_tpu = (impl == "auto"
                   and (backend or jax.default_backend()) == "tpu")
    if (auto_on_tpu and packed and not structured and mask is None
            and (deterministic or dropout_rate <= 0.0)
            and short_attention.supported(shape, dtype)):
        return "short", None
    if impl == "flash":
        return "flash", None
    if auto_on_tpu and t >= _FLASH_MIN_SEQ and dh in (32, 64, 128, 256):
        # Under a mesh the logits are split over batch and heads: it is
        # the per-device share that is weighed.
        if part is not None:
            b = -(-b // part.size(part.data_axis))
            h = -(-h // part.size(part.model_axis))
        logits_bytes = b * h * t * t * jnp.dtype(dtype).itemsize
        if 3 * logits_bytes > _FLASH_MEMORY_BYTES:
            return "flash", None
    return "xla", None


# One scope for every implementation (short, XLA, flash, ring, ulysses), so
# that a device trace splits `msa` into norm / qkv / attn_core / out and what
# is left: the slices and transposes between them (telemetry/device_trace.py).
@jax.named_scope("attn_core")
def dot_product_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    impl: str = "auto",
    kind: str = "full",
    window: int = 0,
    mask: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    deterministic: bool = True,
    softmax: str = "saturating",
    heads_already_local: bool = False,
) -> jax.Array:
    """Multi-head scaled dot-product attention, by the implementation
    :func:`choose` names for the call.

    Args:
      q, k, v: ``[batch, seq, heads, head_dim]``; k and v may have a
        divisor of q's heads (grouped-query attention).
      impl: ``"xla"``, ``"flash"``, or ``"auto"``.
      kind / window: the attention's structure (module docstring):
        ``"full"``, ``"causal"`` or ``"causal_window"`` over ``window``
        keys.
      mask: optional boolean ``[batch, heads, q, k]`` mask (True = attend).
      dropout_rate / dropout_rng / deterministic: attention-weight dropout
        (reference ``attn_dropout``, models/vit.py:75).
      softmax: XLA-path softmax flavor — ``"saturating"`` (default,
        +1.7% step: no row-max read; exact for logits <= ~96, saturates
        beyond) or ``"exact"`` (max-subtracted, any magnitude). See
        ``configs.ViTConfig.attention_softmax``. Ignored by the
        short/flash/ring/ulysses paths, which carry their own exact
        softmax.
      heads_already_local: set by manual-TP callers (inside ``shard_map``,
        e.g. the pipeline's head-sliced MSA) whose ``q`` already carries
        per-shard heads — the Ulysses divisibility pre-check then uses
        ``heads`` as-is instead of dividing by the model-axis size
        (ADVICE r4: guessing from the mesh under-counted and could
        spuriously route to the gathered XLA fallback).

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
    mesh with structure, a mask or shapes not divisible by the mesh
    axes uses the XLA path (:func:`choose`, rule 1). Attention dropout
    rides the ring natively.
    """
    if kind == "causal_topk":
        raise ValueError("unknown attention kind 'causal_topk' here: the "
                         "keys are an indexer's to select "
                         "(ops.sparse_attention.sparse_attention)")
    served, reason = choose(
        q.shape, q.dtype, k.shape, impl=impl, kind=kind, mask=mask,
        dropout_rate=dropout_rate, deterministic=deterministic,
        heads_already_local=heads_already_local)
    if reason is not None:
        _warn_once(reason)
    dropout = dict(dropout_rate=dropout_rate, dropout_rng=dropout_rng,
                   deterministic=deterministic)
    if served in ("ring", "ulysses"):
        return _sp_attention(q, k, v, served, **dropout)
    if served == "flash":
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, kind=kind, window=window,
                               mask=mask, **dropout)
    # The XLA path, given what ``kind`` and the head counts stand for: the
    # visibility matrix and repeated key/value heads.
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    vis = structure_mask(kind, window, q.shape[1])
    if vis is not None:
        mask = vis if mask is None else jnp.logical_and(mask, vis)
    return _xla_attention(q, k, v, mask=mask, softmax=softmax, **dropout)


def self_attention(qkv: jax.Array, *, window: int = 0,
                   dropout_rng: Optional[jax.Array] = None,
                   softmax: str = "saturating", **call) -> jax.Array:
    """:func:`dot_product_attention` from the packed qkv projection
    ``[batch, seq, 3, heads, head_dim]``, with its keywords (named here:
    the three :func:`choose` does not read). A grouped-query projection
    is not packed this way: its block calls
    :func:`dot_product_attention` with q, k and v.

    Where :func:`choose` names the short-sequence kernel, the projection
    goes to it as it lies and no q, k or v is ever cut out of it. The
    kernel's softmax is the exact, max-subtracted one, which equals the
    ``"saturating"`` flavour over that flavour's whole exact range.
    Every other call gets q, k, v sliced from the projection; the
    slices stay outside the ``attn_core`` scope, where a device trace
    has always counted them (``msa_glue``).
    """
    if choose(qkv.shape, qkv.dtype, **call)[0] == "short":
        with jax.named_scope("attn_core"):
            return short_attention.short_attention(qkv)
    return dot_product_attention(
        qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], window=window,
        dropout_rng=dropout_rng, softmax=softmax, **call)
