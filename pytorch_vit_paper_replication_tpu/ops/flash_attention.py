"""Pallas TPU flash attention (forward + backward, optional dropout).

The reference's attention is ``torch.nn.MultiheadAttention``
(``models/vit.py:86-98``) — a library call that materializes the full
``[B, H, T, T]`` attention matrix in HBM. This kernel is the TPU-native
replacement for long sequences: softmax(QK^T)V is computed blockwise in VMEM
with an online-softmax accumulator, so HBM traffic stays O(T·D) instead of
O(T²), and every matmul lands on the MXU with float32 accumulation.

Layout: inputs are ``[B, T, H, Dh]``; internally folded to ``[B·H, T, Dh]``.
The forward's grid walks (key/value head, query block, query head of the
group); each program streams the visible K/V blocks with ``lax.fori_loop``,
three interior blocks a step. Sequence lengths that are not block-aligned are
padded by the wrapper and masked inside the kernel, so 577-token (384px) ViT
sequences work.

Both kernels hold a block transposed, ``[Bk, Bq]`` (``s^T = k q^T``: keys on
sublanes, queries on lanes). The forward's statistics (running maximum, sum,
correction) are then lane-dense ``[1, Bq]`` rows and its reductions over keys
sums of vector registers; its accumulator is ``o^T`` and is turned once a
program. A query row that no key attends is told apart in the forward's
epilogue, not inside its loop (``_fwd_kernel``): zero output, ``lse =
_NEG_INF``.

The backward pass is the flash recomputation in ONE kernel
(``flash_bwd``) that visits every visible (query block, key block) pair
once: from the saved row logsumexp it takes ``s``, ``p = exp(s - lse)``,
``dp = do v^T`` and ``ds = p (dp - delta)`` once a pair and all three
gradients from them (five products a pair; a ``dq`` kernel and a
``dk/dv`` kernel, as the file had until PR 31, take seven and the
softmax twice). Its grid walks (key/value head, query head of the group,
query block) and each program streams the visible key blocks: ``dq`` of
the query block is carried over them, and ``dk`` / ``dv`` are added into
two float32 ``[T, Dh]`` slabs of the key/value head that stay in VMEM
over its query blocks AND over the query heads of its group, zeroed at
the head's first program and cast and written once after its last: the
group's sum is taken there, and no per-query-head ``dk`` / ``dv`` ever
reaches HBM. ``delta_i = rowsum(dO_i * O_i)`` is taken in the kernel
from the block of ``o`` (XLA laid a float32 transposed copy of ``o``
through HBM for it). The kernel holds a pair's block transposed,
``[Bk, Bq]`` (``s^T = k q^T``), so that ``dv += p^T do`` and ``dk +=
ds^T q`` are plain products, only ``dq`` contracts rows, and ``lse`` /
``delta`` broadcast along sublanes as they lie.

**Attention dropout** (reference ``attn_dropout``, models/vit.py:75) runs
in-kernel so long-sequence configs keep the O(T) memory property: the
``[T, T]`` drop mask is never materialized. Each element's keep/drop bit is
a pure counter-based hash of ``(seed, batch·head, row, column)`` — an
integer avalanche mix (xor-shift-multiply, murmur3-finalizer family)
evaluated with plain vector ops, so the forward and the backward kernel
regenerate bit-identical masks independent of block iteration order, and
the same code path runs under the Pallas CPU interpreter (the pltpu
hardware PRNG has no interpret-mode lowering). Like :mod:`.dropout`, the
drop probability is quantized to ``round(rate*256)/256`` and survivors are
rescaled by the quantized keep probability, so the output is exactly
unbiased. The softmax normalizer uses the *undropped* probabilities
(dropout applies to the normalized attention weights, matching
``torch.nn.MultiheadAttention``/the XLA path's semantics).

**Causal and windowed attention are structure, not a mask array**
(``kind`` = ``"causal"`` / ``"causal_window"``): key j is visible to
query i iff ``j <= i`` and, with a window w, ``i - j < w``. Each kernel
computes that from the positions of the block it holds and visits only
the blocks that hold a visible pair — the loop over key blocks (forward
and backward alike) runs from the first such block to the last, so a
window layer at T = 16,384, w = 4,096 touches 22% of the square and
a causal layer 50%.

**Grouped-query attention**: q may have ``G`` times the heads of k and v.
Query head h reads key/value head ``h // G`` through the block index (k
and v are never repeated in HBM, and consecutive query heads of a group
find the block already in VMEM). dk and dv come out once a key/value
head, summed over its group inside the backward kernel in float32.

The matrix products take their operands in the input dtype (bfloat16 on
the MXU) and accumulate in float32; the softmax statistics are float32.

Use :func:`..ops.attention.dot_product_attention` with ``impl="flash"``/
``"auto"`` rather than calling this directly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import partition
from .dropout import positional_dropout_seed, positional_meta

# Blocks of 256 up to 2,047 tokens (T = 577 pads to 768, not 1,024),
# of 512 from there. Measured on the v5e with bf16 operands at T =
# 16,384, Dh = 128, 28 query heads over 4, forward + backward of a
# causal / a 4,096-window layer (PR 27, my chip run): 128x128 265.7 /
# 124.3 ms, 256x256 130.1 / 64.6, 512x512 67.4 / 37.3, 1024x1024 63.1 /
# 38.2, and 512x256 100.3 / 52.8, 256x512 80.5 / 43.4: the per-block
# softmax (VPU) and loop overheads are paid per block, the MXU work is
# not. (With float32 operands, an earlier installation found 256 best.)
# The one-pass backward alone at the same shapes, a causal / a window
# layer (PR 31, my chip run; the pair it replaced 50.3 / 27.6 at 512x512):
# 512x512 28.4 / 14.5 ms, 512x1024 28.2 / 15.8, 1024x512 28.5 / 15.9,
# 256x256 52.1 / 24.7. The transposed forward alone, a causal / a window
# layer (PR 37, my chip runs, device time; the parent's forward 16.75 /
# 9.38): a block a step 17.07 / 8.66, three interior blocks a step
# 14.23 / 7.85 (two 14.73 / 8.04, four 14.62 / 8.24); a block a step at
# 1024x512 15.48 / 8.77, 512x256 pairs 15.41 / 8.47, 256x512 pairs
# 18.27 / 10.77.
# Key blocks a step over the forward's interior (``_fwd_kernel``).
FWD_STEP_BLOCKS = 3
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 256
LONG_SEQUENCE = 2048
LONG_BLOCK = 512
_NEG_INF = float(-1e30)
_LOG2E = float(np.log2(np.e))
_LN2 = float(np.log(2.0))
_MIB = 1024 * 1024


def _vmem_limit(*resident, scratch=0):
    """Scoped-VMEM limit for a call that keeps ``resident`` whole
    operands or results (bytes each, double-buffered) and ``scratch``
    bytes of its own beside its blocks: the compiler's default (16 MiB
    on the v5e) stops at T ~ 8k of bf16 k and v at head size 128. The
    cap is what the backward asks at T = 16,384 and head size 256 (k, v,
    dk and dv slabs of 8 MiB twice each, two float32 slabs of 16 MiB:
    the compiler counts 100.3 MiB of the v5e's 128)."""
    need = 2 * sum(resident) + scratch + 16 * _MIB
    return int(min(max(need, 32 * _MIB), 112 * _MIB))


def _lanes(width: int) -> int:
    """Columns a row of ``width`` takes in VMEM: whole 128-lane tiles."""
    return -(-width // 128) * 128


def _slab_bytes(x, head_dim):
    """Bytes of one head's ``[T, Dh]`` slab of a ``[.., T, ..]`` array as
    VMEM holds it (a head of 64 columns takes 128 lanes: at T = 16,384
    the backward's four slabs and two float32 slabs take 49 MiB, which a
    count of 64 columns puts at 40)."""
    return x.shape[1] * _lanes(head_dim) * x.dtype.itemsize


def _layout(structure, x):
    """``(programs, head_dim, q_at, kv_at)`` of the arrays' layout.

    Folded (``structure[3] == 0``): q ``[B*H, T, Dh]``, k / v ``[B*Hkv,
    T, Dh]``. Flat (``structure[3] == H``, taken where Dh is a multiple
    of the 128 lanes): q ``[B, T, H*Dh]``, k / v ``[B, T, Hkv*Dh]`` — the
    projection's own layout, a head being a column block, so no
    transposed copy of q, k, v, o or their gradients is ever made.
    ``q_at(b)`` / ``kv_at(b)`` give program ``b``'s ``(leading index,
    column block)`` in a q-like and a k-like array."""
    _, _, group, heads = structure
    if not heads:
        return (x.shape[0], x.shape[2], lambda b: (b, 0),
                lambda b: (b // group, 0))
    return (x.shape[0] * heads, x.shape[2] // heads,
            lambda b: (b // heads, b % heads),
            lambda b: (b // heads, (b % heads) // group))


def _visible(structure, row, col):
    """Whether key ``col`` is visible to query ``row`` (arrays of global
    positions) under ``structure = (causal, window, query heads a
    key/value head, query heads of the flat layout or 0)``."""
    causal, window = structure[:2]
    ok = col <= row
    if window:
        ok = jnp.logical_and(ok, row - col < window)
    return ok


def _kv_block_range(structure, qi, block_q, block_k, num_kv):
    """Key blocks ``[lo, hi)`` that hold a key visible to some query of
    query block ``qi``."""
    causal, window = structure[:2]
    if not causal:
        return 0, num_kv
    hi = jnp.minimum(num_kv, ((qi + 1) * block_q + block_k - 1) // block_k)
    lo = 0
    if window:
        lo = jnp.maximum(0, (qi * block_q - window + 1) // block_k)
    return lo, hi


def _kv_full_range(structure, qi, block_q, block_k, lo, hi):
    """Of ``[lo, hi)``, the key blocks ``[full_lo, full_hi)`` whose every
    key is visible to every query of block ``qi``: no mask is needed
    there (the blocks before and after it are the window's and the
    diagonal's edges)."""
    causal, window = structure[:2]
    if not causal:
        return lo, hi
    full_hi = jnp.clip((qi * block_q + 1) // block_k, lo, hi)
    full_lo = lo
    if window:
        full_lo = jnp.clip(
            (qi * block_q + block_q - window + block_k - 1) // block_k,
            lo, full_hi)
    return full_lo, full_hi


def _edges_and_interior(body, lo, full_lo, full_hi, hi, carry,
                        steps=None):
    """``body(i, carry, masked)`` over ``[lo, hi)``: with the structure's
    mask on the edge blocks, without it on ``[full_lo, full_hi)``. Given
    ``steps = (n, body_n)``, ``body_n(i, carry)`` takes the interior's
    blocks ``i .. i + n - 1`` a step and ``body`` the ones left over."""
    edge = functools.partial(body, masked=True)
    carry = jax.lax.fori_loop(lo, full_lo, edge, carry)
    if steps is not None:
        n, body_n = steps
        whole = (full_hi - full_lo) // n
        carry = jax.lax.fori_loop(
            0, whole, lambda j, c: body_n(full_lo + n * j, c), carry)
        full_lo = full_lo + n * whole
    carry = jax.lax.fori_loop(full_lo, full_hi,
                              functools.partial(body, masked=False), carry)
    return jax.lax.fori_loop(full_hi, hi, edge, carry)


def _fold_heads(x):
    """[B, T, H, Dh] -> [B*H, T, Dh]."""
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _unfold_heads(x, b, h):
    """[B*H, T, Dh] -> [B, T, H, Dh]."""
    bh, t, d = x.shape
    return x.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _keep_mask(seed, bh, row0, col0, shape, threshold, query_dim=0):
    """Keep/drop mask for one attention block: the shared positional hash
    (:func:`..ops.dropout.positional_keep_u8`) on the block's global
    coordinates (queries along ``query_dim`` of ``shape``, keys along the
    other). Deterministic per element, so both kernels regenerate the
    identical mask regardless of grid/loop order and of how they hold
    the block."""
    from .dropout import positional_keep_u8

    row = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, query_dim)
    col = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - query_dim)
    return positional_keep_u8(seed, bh, row, col, threshold)


def _global_bh(meta_ref, heads, bh=None):
    """The GLOBAL batch·head index of this program (``bh``: its local
    one, ``program_id(0)`` unless given), the ``bh`` the mask hash is
    keyed on. ``meta_ref`` is the scalar-prefetch triple
    ``[seed, batch0, head0]`` and ``heads`` the static ``(local,
    global)`` head counts: under a mesh this shard holds batch rows from
    ``batch0`` and heads from ``head0``, so shards never share a mask
    and an element's mask is the one ring attention gives it. On one
    device (offsets 0, local == global) this is ``program_id(0)``."""
    h_local, h_total = heads
    if bh is None:
        bh = pl.program_id(0)
    return ((meta_ref[1] + jax.lax.div(bh, jnp.int32(h_local))) * h_total
            + meta_ref[2] + jax.lax.rem(bh, jnp.int32(h_local)))


# --------------------------------------------------------------------------
# Attention-mask plumbing (True = attend). The caller's mask broadcasts to
# [B, H, Tq, Tk]; it is folded to 3-D [G, Tq|1, Tk] WITHOUT materializing
# broadcast batch/head/query dims, so a key-padding mask [B,1,1,Tk] streams
# O(B·T) while only a caller-materialized full mask is O(T²) input. The
# static descriptor (bh_mode, q_bcast) tells the kernels how to index it.
# --------------------------------------------------------------------------

def _normalize_mask(mask, b, h, q_len, kv_len):
    """-> (mask3 [G, Tq|1, Tk] bool, (bh_mode, q_bcast)) or (None, None)."""
    if mask is None:
        return None, None
    while mask.ndim < 4:
        mask = mask[None]
    mb, mh, mq, mk = mask.shape
    if mk == 1 and kv_len > 1:
        # A key-broadcast mask (e.g. query-row padding [B,1,Tq,1]) cannot
        # stream column-wise; materialize the Tk axis so it keeps working
        # like the old XLA-fallback semantics (the cost is the mask the
        # caller's shape implies anyway).
        mask = jnp.broadcast_to(mask, (mb, mh, mq, kv_len))
        mk = kv_len
    if mk != kv_len or mq not in (1, q_len) or mb not in (1, b) \
            or mh not in (1, h):
        raise ValueError(
            f"mask shape {mask.shape} does not broadcast to "
            f"[{b}, {h}, {q_len}, {kv_len}]")
    q_bcast = mq == 1
    if mb > 1 and mh > 1:
        bh_mode = "full"
        m3 = mask.reshape(mb * mh, mq, mk)
    elif mb > 1:
        bh_mode = "batch"          # kernel program bh -> bh // H
        m3 = mask.reshape(mb, mq, mk)
    elif mh > 1:
        bh_mode = "head"           # kernel program bh -> bh % H
        m3 = mask.reshape(mh, mq, mk)
    else:
        bh_mode = "one"
        m3 = mask.reshape(1, mq, mk)
    return m3, (bh_mode, q_bcast)


def _mask_bh_index(bh_mode, h):
    return {
        "full": lambda b: b,
        "batch": lambda b: b // h,
        "head": lambda b: b % h,
        "one": lambda b: 0,
    }[bh_mode]


def _transposed_mask(mask3, mask_info, block_q, block_k):
    """The mask as both kernels read it, their block being held
    transposed: ``[G, Tk, Tq|1]`` padded with False, and the bytes of
    the strip one program holds. The expression is the one
    :func:`..indexer_loss.loss_pass` builds its selection with, so that
    XLA takes the ``[T, T]`` transpose once for the two."""
    padded = _pad_mask(mask3, mask_info, block_q, block_k)
    return (jnp.swapaxes(padded, 1, 2),
            _strip_bytes(padded, mask_info, block_q))


def _mask_spec_cols(mask_info, h, padded_kv, block_q, head_and_block):
    """BlockSpec of the transposed mask's strip ``[1, padded_kv,
    block_q|1]`` for a grid whose step ``head_and_block(*step)`` gives
    ``(batch·head, query block)``."""
    bh_mode, q_bcast = mask_info
    bhi = _mask_bh_index(bh_mode, h)

    def index(*step):
        b, i = head_and_block(*step[:3])
        return bhi(b), 0, 0 if q_bcast else i

    return pl.BlockSpec((1, padded_kv, 1 if q_bcast else block_q), index)


def _attend(tile):
    """A mask tile as booleans. A caller's mask may be int8 (non-zero =
    attend): a boolean array crosses a Mosaic kernel's boundary as 32-bit
    words, an int8 one as bytes."""
    if tile.dtype == jnp.bool_:
        return tile
    return tile.astype(jnp.int32) != 0


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(meta_ref, q_ref, k_ref, v_ref, *rest, scale,
                block_k, kv_len, threshold, mask_info, heads, structure):
    """One (key/value head, query block, query head of the group)
    program: the online softmax over the visible key blocks.

    The block is held transposed, ``[Bk, Bq]`` (``s^T = k q^T``), as
    ``flash_bwd`` holds it: the running maximum ``m``, the sum ``l`` and
    the correction are lane-dense ``[1, Bq]`` rows, the maximum and the
    sum over keys are reductions over sublanes, and the accumulator is
    ``o^T`` ``[Dh, Bq]`` (``v^T p^T``), which every correction scales
    along sublanes as it lies; it is turned once, after the last block.
    A logit is scaled by ``scale * log2 e`` as it leaves the MXU, so the
    loop takes ``exp2`` of base-2 logits (the same numbers as ``exp`` of
    the natural ones, to float32 rounding; folding the scale into a
    bfloat16 query instead rounds the query's elements up by 3e-4 of
    their size on average, a bias on every logit).

    Over the interior (``_kv_full_range``) the loop takes
    :data:`FWD_STEP_BLOCKS` key blocks a step, their logits first: the
    MXU takes the next blocks' products while the vector unit takes the
    softmax of one, where a block a step runs the two products and the
    softmax between them one after the other.

    A row that no key attends (a caller's mask; padded query rows)
    needs no guard inside the loop: its ``m`` stays ``_NEG_INF``, where
    ``exp2(s - m) = 1`` for each of its masked keys, so ``l`` and the
    accumulator gather a finite sum of ones and of rows of ``v``. If a
    key it attends arrives in a later block, ``m`` becomes finite and
    that block's ``correction = exp2(_NEG_INF - m) = 0`` wipes both
    exactly, and no masked key contributes again (``exp2(_NEG_INF -
    m)`` is 0). If none arrives, ``m`` is still ``_NEG_INF`` after the
    last block, and the epilogue writes a zero output and ``lse =
    _NEG_INF``, as the backward reads it (every ``p`` of the row
    masked, so zero gradients)."""
    group = structure[2]
    mask_ref = None
    if mask_info is not None:
        mask_ref, *rest = rest
    o_ref, lse_ref = rest
    causal = structure[0]
    q = q_ref[0]                       # [Bq, Dh]
    block_q = q.shape[0]
    num_kv = k_ref.shape[1] // block_k
    qi = pl.program_id(1)
    bh = _global_bh(meta_ref, heads, pl.program_id(0) * group
                    + pl.program_id(2))
    shape = (block_k, block_q)
    padded = kv_len != k_ref.shape[1]  # static: a last block of padding

    def logits(ki):
        """``s^T`` of key block ``ki``, ``[Bk, Bq]``, base 2."""
        k = k_ref[0, pl.ds(ki * block_k, block_k), :]
        return jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * (scale * _LOG2E)

    def update(ki, carry, s, masked):
        m, l, acc = carry              # [1, Bq], [1, Bq], [Dh, Bq]
        keys = pl.ds(ki * block_k, block_k)
        if padded or (causal and masked):
            col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            keep_s = col < kv_len
            if causal and masked:
                row = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, shape, 1)
                keep_s = jnp.logical_and(keep_s,
                                         _visible(structure, row, col))
            s = jnp.where(keep_s, s, _NEG_INF)
        if mask_info is not None:
            s = jnp.where(_attend(mask_ref[0, keys, :]), s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp2(s - m_new)
        correction = jnp.exp2(m - m_new)
        # The normalizer sums the UNDROPPED probabilities: dropout applies
        # to softmax(S), not to exp(S) pre-normalization.
        l_new = l * correction + jnp.sum(p, axis=0, keepdims=True)
        if threshold:
            keep = _keep_mask(meta_ref[0], bh, qi * block_q, ki * block_k,
                              shape, threshold, query_dim=1)
            p = jnp.where(keep, p, 0.0)
        v = v_ref[0, keys, :]
        acc_new = acc * correction + jax.lax.dot_general(
            v, p.astype(v.dtype), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def body(ki, carry, masked=True):
        return update(ki, carry, logits(ki), masked)

    def interior(ki, carry):
        s = [logits(ki + u) for u in range(FWD_STEP_BLOCKS)]
        for u in range(FWD_STEP_BLOCKS):
            carry = update(ki + u, carry, s[u], False)
        return carry

    lo, hi = _kv_block_range(structure, qi, block_q, block_k, num_kv)
    full = _kv_full_range(structure, qi, block_q, block_k, lo, hi)
    row = lambda fill: jnp.full((1, block_q), fill, jnp.float32)
    m, l, acc = _edges_and_interior(
        body, lo, *full, hi,
        (row(_NEG_INF), row(0.0), jnp.zeros((q.shape[1], block_q),
                                            jnp.float32)),
        steps=(FWD_STEP_BLOCKS, interior))
    dead = m == _NEG_INF               # no key attended: zero output
    keep_prob = 1.0 - threshold / 256.0  # quantized, like ops.dropout
    inv = jnp.where(dead, 0.0, 1.0 / (l * keep_prob))
    o_ref[0] = (acc * inv).T.astype(o_ref.dtype)
    # lse is carried as [bh, 1, T] so its (sublane, lane) block dims satisfy
    # the TPU (8, 128) tiling rule (sublane dim == full array dim 1).
    lse_ref[0] = jnp.where(dead, _NEG_INF, m * _LN2 + jnp.log(l))


def _pad_mask(mask3, mask_info, block_q, block_k):
    """Pad the folded mask's real (non-broadcast) q/k dims with False."""
    _, q_bcast = mask_info
    m = mask3 if q_bcast else _pad_to_false(mask3, 1, block_q)
    return _pad_to_false(m, 2, block_k)


def _strip_bytes(mask3, mask_info, block_q):
    """Bytes of the mask strip one program holds: a query block's rows
    (one where the mask broadcasts over queries) by every key."""
    rows = 1 if mask_info[1] else block_q
    return rows * mask3.shape[2] * mask3.dtype.itemsize


def _pad_to_false(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=False)


def _fwd(q, k, v, seed, mask3, mask_info, *, heads, scale, block_q,
         block_k, threshold, interpret, structure):
    q_len, kv_len = q.shape[1], k.shape[1]
    group = structure[2]
    bh, head_dim, q_at, kv_at = _layout(structure, q)
    qp = _pad_to(q, 1, block_q)
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)

    # Grid (key/value head n, query block i, query head g of its group):
    # query head n * group + g. The group's heads are walked innermost,
    # so k, v and a strip of the mask are fetched once for all of them.
    def q_rows(n, i, g, *_):
        at = q_at(n * group + g)
        return at[0], i, at[1]

    def kv_whole(n, i, g, *_):
        at = kv_at(n * group)
        return at[0], 0, at[1]

    q_spec = pl.BlockSpec((1, block_q, head_dim), q_rows)
    kv_spec = pl.BlockSpec((1, kp.shape[1], head_dim), kv_whole)
    in_specs = [q_spec, kv_spec, kv_spec]
    operands = [qp, kp, vp]
    strip = ()
    if mask_info is not None:
        mask_t, strip_bytes = _transposed_mask(mask3, mask_info, block_q,
                                               block_k)
        in_specs.append(_mask_spec_cols(
            mask_info, heads[0], kp.shape[1], block_q,
            lambda n, i, g: (n * group + g, i)))
        operands.append(mask_t)
        strip = (strip_bytes,)
    kernel = functools.partial(_fwd_kernel, scale=scale, block_k=block_k,
                               kv_len=kv_len, threshold=threshold,
                               mask_info=mask_info, heads=heads,
                               structure=structure)
    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh // group, qp.shape[1] // block_q, group),
            in_specs=in_specs,
            out_specs=[
                q_spec,
                pl.BlockSpec((1, 1, block_q),
                             lambda n, i, g, *_: (n * group + g, 0, i)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, qp.shape[1]), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit(_slab_bytes(kp, head_dim),
                                         _slab_bytes(vp, head_dim),
                                         *strip)),
        interpret=interpret,
    )(seed, *operands)
    return out[:, :q_len], lse[:, 0, :q_len]


# --------------------------------------------------------------------------
# Backward
# --------------------------------------------------------------------------

def _bwd_kernel(meta_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                *rest, scale, block_k, kv_len, threshold, mask_info, heads,
                structure):
    """One (key/value head, query head of its group, query block)
    program: every visible key block once, ``s``, ``p``, ``dp`` and
    ``ds`` taken once a pair of blocks and all three gradients from
    them. ``dq`` of the query block is carried over the key blocks;
    ``dk`` and ``dv`` are added into the key/value head's two float32
    ``[T, Dh]`` slabs, which stay in VMEM over the query blocks and over
    the group's query heads and leave once, after the last.

    The block is held transposed, ``[Bk, Bq]`` (``s^T = k q^T``): ``dv
    += p^T do`` and ``dk += ds^T q`` are then plain products and only
    ``dq`` contracts rows, and the row statistics broadcast along
    sublanes as they lie."""
    group = structure[2]
    mask_ref = None
    if mask_info is not None:
        mask_ref, *rest = rest
    dq_ref, dk_ref, dv_ref, dk_acc, dv_acc = rest
    q = q_ref[0]                       # [Bq, Dh]
    do = do_ref[0]
    causal = structure[0]
    block_q = q.shape[0]
    num_kv = k_ref.shape[1] // block_k
    g, qi = pl.program_id(1), pl.program_id(2)
    bh = _global_bh(meta_ref, heads, pl.program_id(0) * group + g)
    inv_keep = 256.0 / (256.0 - threshold)
    shape = (block_k, block_q)

    padded = kv_len != k_ref.shape[1]
    lse = lse_ref[0]                   # [1, Bq]
    # delta_i = rowsum(dO_i * O_i), turned to lie along the lanes as lse
    # does (it already carries the forward's dropout).
    delta = jnp.sum(do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta, (block_q, 128)).T[:1]

    @pl.when(jnp.logical_and(g == 0, qi == 0))
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def body(ki, dq, masked=True):
        keys = pl.ds(ki * block_k, block_k)
        k = k_ref[0, keys, :]
        v = v_ref[0, keys, :]
        s = jax.lax.dot_general(
            k, q, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [Bk, Bq]
        p = jnp.exp(s - lse)
        if padded or (causal and masked):
            col = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
            keep_s = col < kv_len
            if causal and masked:
                row = qi * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, shape, 1)
                keep_s = jnp.logical_and(keep_s,
                                         _visible(structure, row, col))
            p = jnp.where(keep_s, p, 0.0)
        if mask_info is not None:
            p = jnp.where(_attend(mask_ref[0, keys, :]), p, 0.0)  # [Bk, Bq|1]
        if threshold:
            keep = _keep_mask(meta_ref[0], bh, qi * block_q, ki * block_k,
                              shape, threshold, query_dim=1)
            p_dropped = jnp.where(keep, p * inv_keep, 0.0)
        else:
            p_dropped = p
        dv_acc[keys, :] += jnp.dot(p_dropped.astype(do.dtype), do,
                                   preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            v, do, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        if threshold:
            # dS = P * (M/keep * dP - delta): the mask enters through dP.
            dp = jnp.where(keep, dp * inv_keep, 0.0)
        ds = (p * (dp - delta) * scale).astype(q.dtype)  # [Bk, Bq]
        dk_acc[keys, :] += jnp.dot(ds, q,
                                   preferred_element_type=jnp.float32)
        return dq + jax.lax.dot_general(
            ds, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    lo, hi = _kv_block_range(structure, qi, block_q, block_k, num_kv)
    full = _kv_full_range(structure, qi, block_q, block_k, lo, hi)
    dq = _edges_and_interior(body, lo, *full, hi,
                             jnp.zeros(q.shape, jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)

    @pl.when(jnp.logical_and(g == group - 1,
                             qi == pl.num_programs(2) - 1))
    def _():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# custom_vjp wiring
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, seed, mask3, threshold, block_q, block_k, interpret,
           mask_info, heads, structure, with_lse=False):
    """``out``, or ``(out, lse)`` with the row statistic ``[bh, T]`` (a
    constant under the gradient: its cotangent is dropped)."""
    scale = _layout(structure, q)[1] ** -0.5
    out, lse = _fwd(q, k, v, seed, mask3, mask_info, heads=heads,
                    scale=scale,
                    block_q=block_q, block_k=block_k, threshold=threshold,
                    interpret=interpret, structure=structure)
    return (out, lse) if with_lse else out


def _flash_fwd(q, k, v, seed, mask3, threshold, block_q, block_k,
               interpret, mask_info, heads, structure, with_lse=False):
    scale = _layout(structure, q)[1] ** -0.5
    out, lse = _fwd(q, k, v, seed, mask3, mask_info, heads=heads,
                    scale=scale,
                    block_q=block_q, block_k=block_k, threshold=threshold,
                    interpret=interpret, structure=structure)
    # Named, so that a caller which takes q, k and v again in the
    # backward pass (``jax.checkpoint`` with these names kept: the latent
    # attention of ``models/vit.py``) does not take the core again too.
    out = checkpoint_name(out, "attn_core_out")
    lse = checkpoint_name(lse, "attn_core_lse")
    return (out, lse) if with_lse else out, (q, k, v, seed, mask3, out, lse)


def _flash_bwd(threshold, block_q, block_k, interpret, mask_info, heads,
               structure, with_lse, res, do):
    q, k, v, seed, mask3, out, lse = res
    if with_lse:
        do = do[0]
    q_len, kv_len = q.shape[1], k.shape[1]
    group = structure[2]
    bh, head_dim, q_at, kv_at = _layout(structure, q)

    qp = _pad_to(q, 1, block_q)
    outp = _pad_to(out, 1, block_q)
    dop = _pad_to(do, 1, block_q)
    # The row statistic rides as [bh, 1, T] (TPU tiling: sublane dim == 1
    # == full array dim is legal; a bare [bh, T] with 1-row blocks is
    # not).
    lsep = _pad_to(lse, 1, block_q)[:, None, :]
    kp = _pad_to(k, 1, block_k)
    vp = _pad_to(v, 1, block_k)
    padded_kv = kp.shape[1]

    # Grid (key/value head n, query head g of its group, query block i):
    # query head n * group + g.
    def q_rows(n, g, i, *_):
        at = q_at(n * group + g)
        return at[0], i, at[1]

    def kv_whole(n, g, i, *_):
        at = kv_at(n * group)
        return at[0], 0, at[1]

    q_spec = pl.BlockSpec((1, block_q, head_dim), q_rows)
    kv_spec = pl.BlockSpec((1, padded_kv, head_dim), kv_whole)
    in_specs = [q_spec, kv_spec, kv_spec, q_spec, q_spec,
                pl.BlockSpec((1, 1, block_q),
                             lambda n, g, i, *_: (n * group + g, 0, i))]
    operands = [qp, kp, vp, outp, dop, lsep]
    strip = ()
    if mask_info is not None:
        mask_t, strip_bytes = _transposed_mask(mask3, mask_info, block_q,
                                               block_k)
        in_specs.append(_mask_spec_cols(
            mask_info, heads[0], padded_kv, block_q,
            lambda n, g, i: (n * group + g, i)))
        operands.append(mask_t)
        strip = (strip_bytes,)
    slab = _slab_bytes(kp, head_dim)

    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=head_dim ** -0.5,
                          block_k=block_k,
                          kv_len=kv_len, threshold=threshold,
                          mask_info=mask_info, heads=heads,
                          structure=structure),
        name="flash_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bh // group, group, qp.shape[1] // block_q),
            in_specs=in_specs,
            out_specs=[q_spec, kv_spec, kv_spec],
            scratch_shapes=[
                pltpu.VMEM((padded_kv, head_dim), jnp.float32)] * 2,
        ),
        out_shape=[jax.ShapeDtypeStruct(qp.shape, q.dtype),
                   jax.ShapeDtypeStruct(kp.shape, k.dtype),
                   jax.ShapeDtypeStruct(vp.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                slab, slab, slab, slab, *strip,
                scratch=2 * 4 * padded_kv * _lanes(head_dim))),
        interpret=interpret,
    )(seed, *operands)
    seed_zero = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    mask_zero = (None if mask3 is None
                 else np.zeros(mask3.shape, dtype=jax.dtypes.float0))
    return (dq[:, :q_len], dk[:, :kv_len], dv[:, :kv_len], seed_zero,
            mask_zero)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, kind: str = "full", window: int = 0,
                    mask=None, dropout_rate: float = 0.0,
                    dropout_rng=None, deterministic: bool = True,
                    block_q: int | None = None,
                    block_k: int | None = None,
                    interpret=None, return_lse: bool = False):
    """Flash attention over ``[B, T, H, Dh]`` inputs, optional mask+dropout.

    ``dropout_rate``/``dropout_rng``/``deterministic`` follow the
    :func:`..ops.attention.dot_product_attention` contract; the drop mask
    is generated in-kernel (module docstring), so the O(T) memory property
    holds with dropout active.

    ``mask``: optional boolean (True = attend) or int8 (non-zero = attend:
    it crosses the kernels' boundary as bytes, a boolean one as 32-bit
    words) array broadcastable to ``[B, H, Tq, Tk]``, applied IN-KERNEL (round 4 — previously a silent XLA
    fallback): broadcast batch/head/query dims are never materialized, so
    a key-padding mask ``[B, 1, 1, Tk]`` streams O(B·T); only a mask the
    caller already materialized at ``[B, H, Tq, Tk]`` costs O(T²) input —
    activation memory stays O(T) either way. A query row whose mask
    attends to NO key yields a defined result: zero output and zero
    gradient (forward and backward agree — ADVICE r4; previously the
    forward degenerated to uniform attention while the backward zeroed
    it). Since r5 the XLA path's DEFAULT saturating softmax gives such
    rows the same zero output (its epsilon-guarded normalizer); only
    the ``softmax="exact"`` escape hatch keeps the old uniform-fill
    artifact there.

    ``kind`` / ``window``: the attention's structure (module docstring):
    ``"full"`` bidirectional, ``"causal"``, or ``"causal_window"`` over
    the last ``window`` keys. k and v may have fewer heads than q (a
    divisor of q's): grouped-query attention.

    ``return_lse``: also return the row statistic, ``(out, lse [B, H,
    Tq])``: the log of each query's sum of ``exp(q . k / sqrt(Dh))`` over
    the keys it attends to, a constant under the gradient (what reads it,
    the head-mean probabilities an indexer is aligned to, is one by
    definition: :mod:`.sparse_attention`).

    ``interpret``: run the Pallas interpreter instead of Mosaic (default:
    auto — True off-TPU, so a forced ``impl="flash"`` works everywhere
    and the CPU suite exercises the identical kernel code).

    Traced under a mesh (:func:`.partition.on_mesh`) the call runs per
    shard: batch split over the data axis, heads over the model axis,
    each shard attending over the full sequence (a sequence-sharded mesh
    never reaches this function — the dispatcher routes it through
    ring/Ulysses).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    long = k.shape[1] >= LONG_SEQUENCE
    if block_q is None:
        block_q = LONG_BLOCK if long else DEFAULT_BLOCK_Q
    if block_k is None:
        block_k = LONG_BLOCK if long else DEFAULT_BLOCK_K
    threshold, seed = positional_dropout_seed(
        "flash_attention", dropout_rate, dropout_rng, deterministic)
    h_total = q.shape[2]
    if kind not in ("full", "causal", "causal_window"):
        raise ValueError(f"unknown attention kind {kind!r}")
    if (kind == "causal_window") != bool(window):
        raise ValueError(f"kind {kind!r} with window {window}")
    if h_total % k.shape[2] or k.shape[2] != v.shape[2]:
        raise ValueError(f"{h_total} query heads over {k.shape[2]} / "
                         f"{v.shape[2]} key / value heads")
    if kind != "full" and q.shape[1] != k.shape[1]:
        raise ValueError("causal attention is self-attention: q and k "
                         "have the same positions")
    group = h_total // k.shape[2]

    def local(q, k, v, seed, mask, batch0=0, head0=0):
        b, t, h, _ = q.shape
        meta = positional_meta(seed, b, batch0, h, head0)
        mask3, mask_info = _normalize_mask(mask, b, h, t, k.shape[1])
        # Round clamped block sizes up to a multiple of 8 — Mosaic rejects
        # non-tile-aligned blocks for f32/bf16 on real TPUs (reachable when
        # impl="flash" is forced at short unaligned sequence lengths).
        bq = min(block_q, max(8, -(-t // 8) * 8))
        bk = min(block_k, max(8, -(-k.shape[1] // 8) * 8))
        dh = q.shape[-1]
        if dh % 128 == 0:
            # A head is a whole-lane column block of the projection: the
            # kernels read [B, T, H*Dh] where it lies (``_layout``).
            flat = lambda x: x.reshape(x.shape[:2] + (-1,))
            out = _flash(flat(q), flat(k), flat(v), meta, mask3, threshold,
                         bq, bk, interpret, mask_info, (h, h_total),
                         (kind != "full", int(window), group, h),
                         return_lse)
            if return_lse:
                return out[0].reshape(b, t, h, dh), out[1].reshape(b, h, t)
            return out.reshape(b, t, h, dh)
        out = _flash(_fold_heads(q), _fold_heads(k), _fold_heads(v), meta,
                     mask3, threshold, bq, bk, interpret, mask_info,
                     (h, h_total), (kind != "full", int(window), group, 0),
                     return_lse)
        if return_lse:
            return _unfold_heads(out[0], b, h), out[1].reshape(b, h, t)
        return _unfold_heads(out, b, h)

    part = partition.current()
    if part is None:
        return local(q, k, v, seed, mask)
    data, model = part.axis(part.data_axis), part.axis(part.model_axis)
    if q.shape[0] % part.size(data) or h_total % part.size(model):
        raise ValueError(
            f"flash_attention: batch {q.shape[0]} / heads {h_total} not "
            f"divisible by the mesh's {part.data_axis!r}/"
            f"{part.model_axis!r} axes ({part.size(data)}/"
            f"{part.size(model)})")
    spec = P(data, None, model, None)
    if mask is not None:
        while mask.ndim < 4:
            mask = mask[None]
    # A broadcast (size-1) batch/head dim of the mask stays replicated.
    mask_spec = None if mask is None else P(
        data if mask.shape[0] > 1 else None,
        model if mask.shape[1] > 1 else None, None, None)
    return part.shard_map(
        lambda q, k, v, seed, mask: local(
            q, k, v, seed, mask, part.index((data,)), part.index((model,))),
        in_specs=(spec, spec, spec, P(), mask_spec),
        out_specs=(spec, P(data, model, None)) if return_lse else spec,
    )(q, k, v, seed, mask)
