"""Sparse attention chosen by an indexer (DeepSeek sparse attention,
arXiv:2512.02556): every query attends to the ``topk`` causal keys that a
small learned indexer scores highest, and the indexer learns from its own
alignment loss alone.

With ``qI [B, T, J, Di]`` the indexer's query heads, ``kI [B, T, Di]`` its
ONE key head and ``w [B, T, J]`` its head weights (the two scale factors
folded in), for a query t and a key s <= t:

* score ``I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])``
  (:func:`scores`);
* selection ``S_t``: the ``min(t + 1, topk)`` causal keys of the largest
  score, ties to the lower position (:func:`select`). It passes no
  gradient;
* core ``o[t, h] = sum_{s in S_t} softmax_{S_t}(q[t, h] . k[s, g(h)] /
  sqrt(Dh)) v[s, g(h)]`` (:func:`core`);
* loss ``L_I = mean_t KL(pbar_t || softmax_{S_t} I[t, .])`` with ``pbar``
  the core's own probabilities averaged over the query heads, a constant
  (:func:`indexer_loss`).

**What this version does with unselected keys: it visits them, a block
pair at a time.** The ``[T, T]`` scores of the SELECTION are taken
``chunk`` query rows at a time over every key (a loop of XLA products; at
T = 16,384 the whole matrix would be 1 GiB a layer), each chunk searched
by one more kernel over its causal keys where the flash kernels serve the
core (:mod:`.indexer_select`) and by XLA over every key everywhere else
(:func:`select_rows`), the selection leaves as one int8 ``[B, T, T]``
array, and the core is the causal flash kernel
pair reading a strip of that array a query block
(:func:`..flash_attention.flash_attention` with ``mask=`` and
``return_lse``): every causal block is computed and the unselected pairs
are masked inside it. The indexer's loss and its gradient are taken
together in the forward pass (the gradient reaches the indexer's
parameters only, so nothing of it waits for the backward pass). Where the
flash kernels serve the core (:func:`..attention.choose`, asked as
:func:`core` asks it) that pass is two more kernels over the same causal
blocks (:mod:`.indexer_loss`, since PR 35): the head-mean probabilities,
the indexer's scores once more, the KL and the three gradients a (query
block, key block) pair at a time in VMEM, nothing above the diagonal
touched and no ``[C, T]`` array in HBM. Everywhere else (the CPU, the
tiny preset, a mesh) XLA takes it a chunk of query rows at a time over
every key (:func:`_loss_pass`), with one more ``q k^T`` over every key
for ``pbar`` and the scores through :func:`scores` again. What is counted
as the mechanism's work (``telemetry/flops.py``) is the selected pairs;
the rest is this version's overhead, and a version that gathers or skips
by block is read by the same count.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from . import indexer_loss as indexer_loss_kernels
from . import indexer_select
from .attention import choose

_F32 = jnp.float32


def _chunks(t: int, chunk: int) -> int:
    """Query rows a pass of the loops takes: ``chunk``, or the largest
    divisor of ``t`` under it."""
    rows = min(chunk, t)
    return rows if t % rows == 0 else math.gcd(t, rows)


def scores(q_idx, k_idx, w):
    """``(I [B, C, T] float32, relu(qI . kI) [B, J, C, T])`` of the query
    rows ``q_idx [B, C, J, Di]``, ``w [B, C, J]`` against every key
    ``k_idx [B, T, Di]``. The products leave the MXU in the inputs'
    dtype, the weighted sum over heads is float32. Scope
    ``indexer/scores``, wherever it is called from (the selection, and
    the loss's pass, which takes the scores again)."""
    with jax.named_scope("indexer/scores"):
        act = jax.nn.relu(jnp.einsum("bcjd,bsd->bjcs", q_idx, k_idx,
                                     preferred_element_type=q_idx.dtype))
        total = jnp.einsum("bcj,bjcs->bcs", w.astype(_F32),
                           act.astype(_F32))
    return total, act


def _sortable(x):
    """float32 -> uint32 whose unsigned order is the floats' (-0 as +0)."""
    bits = jax.lax.bitcast_convert_type(x + 0.0, jnp.uint32)
    return jnp.where(bits >> 31 == 0, bits | jnp.uint32(1 << 31), ~bits)


def select_rows(total, rows, topk: int):
    """The selection of query rows ``rows [C]`` (positions) from their
    scores ``total [B, C, T]``: int8 ``[B, C, T]``, 1 at the ``min(row +
    1, topk)`` causal keys of the largest score, ties to the lower
    position — what ``lax.top_k`` over the causal scores selects, by
    bisection on the scores' bit patterns (32 counts over the row, then
    14 over positions only where the threshold value is tied: a sort of
    16,384 scores a row is what no TPU does fast). The reference of the
    kernel :mod:`.indexer_select`."""
    return _select_rows(total, rows, topk)[0]


def _select_rows(total, rows, topk: int):
    """:func:`select_rows`, and ``[B, C]`` True where a row had more keys
    at its threshold than it takes."""
    t = total.shape[-1]
    cols = jnp.arange(t, dtype=jnp.int32)
    causal = cols[None, :] <= rows[:, None]                     # [C, T]
    want = jnp.minimum(rows + 1, topk)[None, :]                  # [1, C]
    keys = jnp.where(causal[None], _sortable(total), jnp.uint32(0))
    count = lambda hit: jnp.sum(hit, axis=-1, dtype=jnp.int32)

    def value_bit(i, found):
        trial = found | jax.lax.shift_left(jnp.uint32(1),
                                           (31 - i).astype(jnp.uint32))
        return jnp.where(count(keys >= trial[..., None]) >= want,
                         trial, found)

    # the want-th largest key of each row
    threshold = jax.lax.fori_loop(
        0, 32, value_bit, jnp.zeros(total.shape[:2], jnp.uint32))
    above = keys > threshold[..., None]
    tied = keys == threshold[..., None]
    short = want - count(above)          # ties to take, >= 1

    def lowest(tied):
        bits = max(1, (t - 1).bit_length())

        def position_bit(i, found):
            trial = found | jax.lax.shift_left(jnp.int32(1), bits - 1 - i)
            inside = count(tied & (cols < trial[..., None])) < short
            return jnp.where(inside, trial, found)

        # the position of the last tie taken: the largest with fewer than
        # ``short`` ties before it
        last = jax.lax.fori_loop(0, bits, position_bit,
                                 jnp.zeros(total.shape[:2], jnp.int32))
        return tied & (cols <= last[..., None])

    more = count(tied) > short
    tied = jax.lax.cond(jnp.any(more), lowest, lambda tied: tied, tied)
    return ((above | tied) & causal[None]).astype(jnp.int8), more


def _select(q_idx, k_idx, w, topk, chunk, served):
    """``(selection, rows tied beyond what they take, whether the kernel
    searched)``: ``chunk`` query rows at a time, each chunk's scores by
    :func:`scores` and its search by the kernel :mod:`.indexer_select`
    where it serves (where the flash kernels serve the core: ``served``),
    else by :func:`select_rows`."""
    q_idx, k_idx, w = (jax.lax.stop_gradient(x) for x in (q_idx, k_idx, w))
    b, t = q_idx.shape[:2]
    c = _chunks(t, chunk)

    def chunk_scores(i):
        take = lambda x: jax.lax.dynamic_slice_in_dim(x, i * c, c, axis=1)
        return scores(take(q_idx), k_idx, take(w))[0]

    if indexer_select.serves(served, t, c):
        def into(i, carry):
            picked, tied = carry
            total = chunk_scores(i)
            with jax.named_scope("indexer/select"):
                picked, more = indexer_select.select(picked, total, i * c,
                                                     topk)
            return picked, tied + jnp.sum(more)

        with jax.named_scope("indexer/select"):
            picked = indexer_select.empty(b, t)
        # each chunk's queries written in place (a stacked result would
        # be written by the kernel fused with the stacking, which the
        # compiler cannot give its VMEM)
        picked, tied = jax.lax.fori_loop(0, t // c, into,
                                         (picked, jnp.int32(0)))
        with jax.named_scope("indexer/select"):
            return jnp.swapaxes(picked[:, :t], 1, 2), tied, True

    def rows_of(i):
        total = chunk_scores(i)
        with jax.named_scope("indexer/select"):
            picked, more = _select_rows(
                total, i * c + jnp.arange(c, dtype=jnp.int32), topk)
            return picked, jnp.sum(more, dtype=jnp.int32)

    picked, tied = jax.lax.map(rows_of, jnp.arange(t // c))  # [n, B, C, T]
    return jnp.moveaxis(picked, 0, 1).reshape(b, t, t), jnp.sum(tied), False


def select(q_idx, k_idx, w, *, topk: int, chunk: int = 512):
    """The selection of every query: int8 ``[B, T, T]`` (1 = attend),
    ``chunk`` query rows at a time by :func:`select_rows`. No gradient
    passes. (:func:`sparse_attention` searches by the kernel
    ``dsa_select`` where the flash kernels serve the core: the same
    selection.)"""
    return _select(q_idx, k_idx, w, topk, chunk, "xla")[0]


_BITS = 8


def pack(mask):
    """int8 ``[B, T, T]`` of 0 / 1 -> uint8 ``[B, T, T / 8]``, a key a
    bit: what a block keeps of its selection for the backward pass (32
    MiB a layer at T = 16,384 where the bytes are 256)."""
    b, t, keys = mask.shape
    place = jnp.left_shift(jnp.uint8(1), jnp.arange(_BITS, dtype=jnp.uint8))
    return jnp.sum(mask.reshape(b, t, keys // _BITS, _BITS).astype(jnp.uint8)
                   * place, axis=-1, dtype=jnp.uint8)


def unpack(packed):
    """:func:`pack`'s inverse."""
    b, t, words = packed.shape
    place = jnp.arange(_BITS, dtype=jnp.uint8)
    bits = jnp.right_shift(packed[..., None], place) & jnp.uint8(1)
    return bits.astype(jnp.int8).reshape(b, t, words * _BITS)


def core(q, k, v, mask, *, impl: str = "auto"):
    """Attention of ``q [B, T, H, Dh]`` over the keys ``mask [B, T, T]``
    selects of ``k``, ``v [B, T, Hkv, Dh]``: ``(o [B, T, H, Dh], lse [B,
    H, T])``, ``lse`` a constant under the gradient. By
    :func:`..attention.choose`: the flash kernels with the mask, or XLA
    on the ``[T, T]`` logits."""
    served, _ = choose(q.shape, q.dtype, k.shape, impl=impl,
                       kind="causal_topk")
    if served == "flash":
        from .flash_attention import flash_attention
        return flash_attention(q, k, v, kind="causal", mask=mask[:, None],
                               return_lse=True)
    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=_F32) * q.shape[-1] ** -0.5
    logits = jnp.where(mask[:, None] != 0, logits, -jnp.inf)
    lse = jax.nn.logsumexp(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd",
                     jnp.exp(logits - lse[..., None]).astype(q.dtype), v)
    return out, jax.lax.stop_gradient(lse)


def _loss_pass(q_idx, k_idx, w, mask, q, k, lse, chunk, with_gradients):
    """The alignment loss, ``chunk`` query rows at a time: ``(loss, mean
    over queries of pbar's mass on the selection)`` and, asked, the
    loss's gradients by ``q_idx``, ``k_idx`` and ``w``."""
    b, t, heads, dh = q.shape
    group = heads // k.shape[2]
    c = _chunks(t, chunk)
    scale = dh ** -0.5
    take = lambda x, i, axis=1: jax.lax.dynamic_slice_in_dim(
        x, i * c, c, axis=axis)

    def rows_of(carry, i):
        loss, mass_sum, g_k = carry
        q_i, w_i = take(q_idx, i), take(w, i).astype(_F32)
        total, act = scores(q_i, k_idx, w_i)
        chosen = take(mask, i) != 0                              # [B, C, T]
        q_rows, lse_rows = take(q, i), take(lse, i, 2)

        def heads_of(g, summed):
            q_g = jax.lax.dynamic_slice_in_dim(q_rows, g * group, group,
                                               axis=2)
            k_g = jax.lax.dynamic_index_in_dim(k, g, axis=2, keepdims=False)
            lse_g = jax.lax.dynamic_slice_in_dim(lse_rows, g * group, group,
                                                 axis=1)
            s = jnp.einsum("bchd,bsd->bhcs", q_g, k_g,
                           preferred_element_type=_F32) * scale
            return summed + jnp.sum(jnp.exp(s - lse_g[..., None]), axis=1)

        pbar = jax.lax.fori_loop(0, k.shape[2], heads_of,
                                 jnp.zeros(total.shape, _F32)) / heads
        pbar = jnp.where(chosen, pbar, 0.0)
        log_soft = jax.nn.log_softmax(jnp.where(chosen, total, -jnp.inf),
                                      axis=-1)
        log_soft = jnp.where(chosen, log_soft, 0.0)
        mass = jnp.sum(pbar, axis=-1)                            # [B, C]
        cross = pbar * (jnp.log(jnp.where(pbar > 0, pbar, 1.0)) - log_soft)
        carry = (loss + jnp.sum(cross) / (b * t), mass_sum + jnp.sum(mass))
        if not with_gradients:
            return carry + (g_k,), None
        # d loss / d I over the selection: mass x softmax - pbar
        d_total = (jnp.where(chosen, jnp.exp(log_soft), 0.0)
                   * mass[..., None] - pbar) / (b * t)
        d_act = jnp.where(act > 0, d_total[:, None]
                          * jnp.moveaxis(w_i, -1, 1)[..., None], 0.0
                          ).astype(q_idx.dtype)                 # [B,J,C,T]
        g_q = jnp.einsum("bjcs,bsd->bcjd", d_act, k_idx,
                         preferred_element_type=_F32)
        g_k = g_k + jnp.einsum("bjcs,bcjd->bsd", d_act, q_i,
                               preferred_element_type=_F32)
        g_w = jnp.einsum("bcs,bjcs->bcj", d_total, act.astype(_F32))
        return carry + (g_k,), (g_q, g_w)

    zero = jnp.zeros((), _F32)
    (loss, mass_sum, g_k), per_row = jax.lax.scan(
        rows_of, (zero, zero, jnp.zeros(k_idx.shape, _F32)),
        jnp.arange(t // c))
    out = (loss, mass_sum / (b * t))
    if not with_gradients:
        return out
    whole = lambda x: jnp.moveaxis(x, 0, 1).reshape((b, t) + x.shape[3:])
    g_q, g_w = (whole(x) for x in per_row)
    return out, (g_q.astype(q_idx.dtype), g_k.astype(k_idx.dtype),
                 g_w.astype(w.dtype))


def _served_loss_pass(q_idx, k_idx, w, mask, q, k, lse, chunk, impl,
                      with_gradients):
    """The pass where the core goes: the kernel pair of
    :mod:`.indexer_loss` where :func:`..attention.choose` gives the core
    to the flash kernels, :func:`_loss_pass` everywhere else."""
    served, _ = choose(q.shape, q.dtype, k.shape, impl=impl,
                       kind="causal_topk")
    if indexer_loss_kernels.serves(served, q.shape, q_idx.shape):
        return indexer_loss_kernels.loss_pass(q_idx, k_idx, w, mask, q, k,
                                              lse, with_gradients)
    return _loss_pass(q_idx, k_idx, w, mask, q, k, lse, chunk,
                      with_gradients)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def indexer_loss(q_idx, k_idx, w, mask, q, k, lse, chunk=512, impl="auto"):
    """``(L_I, pbar's mass)``: the mean over queries of ``KL(pbar_t ||
    softmax_{S_t} I[t, .])``, ``pbar[t, s] = mean_h exp(q[t, h] . k[s,
    g(h)] / sqrt(Dh) - lse[h, t])`` on the selection ``mask`` (the core's
    own probabilities: their mass is 1), and that mass's mean. Its
    gradient reaches ``q_idx``, ``k_idx`` and ``w`` only — ``mask``,
    ``q``, ``k`` and ``lse`` are constants — and is taken in the forward
    pass with the loss, so the backward pass scales three small arrays.

    ``impl`` is the core's (:func:`core`): where :func:`..attention.choose`
    gives the core to the flash kernels, the pass is two Mosaic kernels
    that take ``pbar``, the indexer's scores, the KL
    (``indexer_loss_fwd``) and the three gradients (``indexer_loss_bwd``)
    a (query block, key block) pair at a time in VMEM and visit no block
    above the diagonal (:mod:`.indexer_loss`); everywhere else XLA takes
    it ``chunk`` query rows at a time over every key, the scores through
    :func:`scores` once more."""
    return _served_loss_pass(q_idx, k_idx, w, mask, q, k, lse, chunk, impl,
                             False)


def _indexer_loss_fwd(q_idx, k_idx, w, mask, q, k, lse, chunk, impl):
    out, gradients = _served_loss_pass(q_idx, k_idx, w, mask, q, k, lse,
                                       chunk, impl, True)
    # The loss leaves with its gradients. Only the backward pass reads
    # them, so nothing else keeps the compiler from taking every layer's
    # gradients after the last layer's core, the pass's operands (the
    # selection as bytes, 256 MiB a layer at T = 16,384, q, k) held until
    # then.
    out, gradients = jax.lax.optimization_barrier((out, gradients))
    # Named: a caller that takes the block again in the backward pass
    # (``jax.checkpoint`` keeping these names) does not take this pass
    # again.
    gradients = tuple(checkpoint_name(g, "indexer_loss_grad")
                      for g in gradients)
    return out, (gradients, mask, q, k, lse)


def _indexer_loss_bwd(chunk, impl, res, cotangents):
    del chunk, impl
    gradients, mask, q, k, lse = res
    scaled = tuple((cotangents[0] * g.astype(_F32)).astype(g.dtype)
                   for g in gradients)
    zeros = tuple(jnp.zeros_like(x) if jnp.issubdtype(x.dtype, jnp.floating)
                  else jnp.zeros(x.shape, jax.dtypes.float0)
                  for x in (mask, q, k, lse))
    return scaled + zeros


indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


def sparse_attention(q, k, v, q_idx, k_idx, w, *, topk: int, chunk: int = 512,
                     impl: str = "auto"):
    """The whole mechanism: ``(o [B, T, H, Dh], L_I, stats)``. ``stats``:
    ``mask`` (int8 ``[B, T, T]``), ``selected_pairs`` and
    ``causal_pairs`` (a sequence), ``pbar_mass`` (1 by construction),
    ``select_served`` (1 where the kernel ``dsa_select`` took the
    selection, 0 where :func:`select_rows` did) and ``select_tie_rows``
    (rows with more keys at their threshold than they take, a sequence).
    Scopes: ``indexer/scores``, ``indexer/select``, ``attn_core``,
    ``indexer_loss``."""
    b, t = q.shape[:2]
    served, _ = choose(q.shape, q.dtype, k.shape, impl=impl,
                       kind="causal_topk")
    mask, tie_rows, searched = _select(q_idx, k_idx, w, topk, chunk, served)
    if t % _BITS == 0:
        # Named a bit a pair: a caller that takes the block again in the
        # backward pass keeps that, and the bytes the kernels read are
        # spread from it again.
        with jax.named_scope("indexer/select"):
            mask = unpack(checkpoint_name(pack(mask), "indexer_mask"))
    else:
        mask = checkpoint_name(mask, "indexer_mask")
    with jax.named_scope("attn_core"):
        out, lse = core(q, k, v, mask, impl=impl)
    with jax.named_scope("indexer_loss"):
        loss, mass = indexer_loss(
            q_idx, k_idx, w, mask, *(jax.lax.stop_gradient(x)
                                     for x in (q, k, lse)), chunk, impl)
    with jax.named_scope("indexer/select"):
        selected = jnp.sum(mask, dtype=jnp.int32).astype(_F32) / b
    return out, loss, {
        "mask": mask, "selected_pairs": selected,
        "causal_pairs": jnp.asarray(t * (t + 1) // 2, _F32),
        "pbar_mass": jax.lax.stop_gradient(mass),
        "select_served": jnp.asarray(float(searched), _F32),
        "select_tie_rows": tie_rows.astype(_F32) / b}
