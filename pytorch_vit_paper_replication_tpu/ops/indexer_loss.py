"""The indexer's alignment loss and its three gradients as two Pallas
kernels over the causal blocks (:func:`..sparse_attention.indexer_loss`
is the mathematics; this file is the pass where the flash kernels serve
the core).

Both kernels walk a grid (batch, query block, key block) and do nothing
at a key block no query of the block may see (``_kv_block_range`` of the
flash kernels; its operands' block indices stop at the last visible one,
so nothing is fetched for it either). A (query block, key block) pair is
held transposed, ``[Bk, Bq]`` (keys on sublanes, queries on lanes, as
``flash_bwd`` holds it): a query's statistics (``lse`` of the core, the
indexer's head weights, the running maximum and sums) are rows that
broadcast along sublanes as they lie and sums over keys are sums of
vector registers. What a pair computes, all of it in VMEM:

* ``pbar^T = (1 / H) sum_h exp(k_g(h) q_h^T / sqrt(Dh) - lse_h)``, one
  key/value head after another into one float32 accumulator, zeroed
  where the selection's block (int8, transposed) says unselected;
* ``I^T = sum_j w_j * relu(kI qI_j^T)``: bf16 operands, the products
  LEAVE THE MXU IN FLOAT32 and stay so (``scores`` rounds them to the
  compute dtype before the ReLU; this is at least that precision). Two
  indexer heads of 64 columns share a 128-lane block of the projection:
  each is taken by a product over all 128 columns against the key
  padded with zeros under the other head's columns (``_key_parts``), so
  no half-lane slice is ever cut and a product is as deep as the MXU;
* ``indexer_loss_fwd``: the online log-sum-exp of ``I`` over a row's
  selected keys, ``mass = sum pbar`` and ``sum pbar (log pbar - I)``;
  after the last visible key block ``L_t = sum pbar (log pbar - I) +
  mass * lse_I``, the same KL with ``log_soft = I - lse_I`` expanded;
* ``indexer_loss_bwd``: ``pbar`` and ``I`` again, ``d_total =
  (exp(I - lse_I) * mass - pbar) / (B T)`` on the selection, and a head
  at a time the product again, ``d_act_j = where(act_j > 0, d_total *
  w_j)`` in the compute dtype, ``g_w_j = sum_s d_total * relu(act_j)``,
  ``g_q += d_act^T kI`` carried over the key blocks in float32 and
  ``g_k += d_act qI_j`` added into the float32 ``[T, 128]`` result,
  which stays in VMEM over the query blocks and leaves once (its two
  column halves, one a head of the pair, are added outside).

Every loop over heads is a Python loop: unrolled, a head's product
overlaps the vector unit's pass over the head before it (as
``lax.fori_loop``s the pair took 50.4 ms a layer of the v5e at T =
16,384, unrolled 30.7: PERF.md section 6, PR 35). No ``[.., C, T]`` array of the pass exists outside VMEM. The selection
already holds the causal structure (a key past the query is never
selected) and padded rows and keys are padded unselected, so no position
is compared inside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import partition
from .flash_attention import (DEFAULT_BLOCK_Q, LONG_BLOCK, LONG_SEQUENCE,
                              _attend, _kv_block_range, _pad_to, _vmem_limit)

_F32 = jnp.float32
_NEG = float(-1e30)
_LANES = 128
_CAUSAL = (True, 0)
_NT = (((1,), (1,)), ((), ()))        # [M, K] x [N, K] -> [M, N]
_TN = (((0,), (0,)), ((), ()))        # [K, M] x [K, N] -> [M, N]


def _pack(heads: int, width: int) -> int:
    """Indexer heads a lane block of the projection holds: the largest
    divisor of ``heads`` whose columns fit 128 lanes (2 at 16 heads of
    64)."""
    return max(p for p in range(1, heads + 1)
               if heads % p == 0 and (p == 1 or p * width <= _LANES))


def serves(served: str, q_shape, idx_shape) -> bool:
    """Whether the kernels take the loss's pass of a call whose core
    :func:`..attention.choose` gave to ``served``: where flash serves the
    core, on one device (a mesh's shards keep the XLA pass, which GSPMD
    partitions), and, compiled for the chip (off it the interpreter takes
    any shape), at whole lane blocks: a head size of a multiple of 128
    and indexer heads that fill 128 lanes in packs."""
    if served != "flash" or partition.current() is not None:
        return False
    if jax.default_backend() != "tpu":
        return True
    heads, width = idx_shape[2:]
    return (q_shape[-1] % _LANES == 0 and
            (_pack(heads, width) * width) % _LANES == 0)


def _key_parts(k_idx, pack: int):
    """``[B, T, pack * pack * Di]``: the indexer's key ``pack`` times,
    copy ``p`` under the columns of head ``p`` of a pack and zeros under
    the others', so that a product over a whole lane block of the
    projection is one head's."""
    if pack == 1:
        return k_idx
    eye = jnp.eye(pack, dtype=k_idx.dtype)
    parts = eye[None, None, :, :, None] * k_idx[:, :, None, None, :]
    return parts.reshape(k_idx.shape[:2] + (-1,))


def _pair(q_ref, k_ref, lse_ref, qi_ref, kp_ref, w_ref, mask_ref, *, dims):
    """``(pbar^T, I^T, attend)`` of the pair of blocks the program
    holds, each ``[Bk, Bq]``."""
    heads, group, dh, idx_heads, pack, width = dims
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    scale = dh ** -0.5
    zero = jnp.zeros((block_k, block_q), _F32)
    attend = _attend(mask_ref[0])

    summed = zero
    for h in range(heads):
        g = h // group
        s = jax.lax.dot_general(k_ref[0, :, g * dh:(g + 1) * dh],
                                q_ref[0, :, h * dh:(h + 1) * dh], _NT,
                                preferred_element_type=_F32)
        summed += jnp.exp(s * scale - lse_ref[0, h:h + 1, :])
    pbar = jnp.where(attend, summed * (1.0 / heads), 0.0)

    total = zero
    for j in range(idx_heads):
        act = jax.lax.dot_general(*_key_and_queries(kp_ref, qi_ref, j, dims),
                                  _NT, preferred_element_type=_F32)
        total += w_ref[0, j:j + 1, :] * jnp.maximum(act, 0.0)
    return pbar, total, attend


def _key_and_queries(kp_ref, qi_ref, j, dims):
    """Indexer head ``j``'s operands: the key's copy under the head's
    columns of its pack ``[Bk, W]`` and the pack's queries ``[Bq, W]``."""
    pack, width = dims[4:]
    return (kp_ref[0, :, j % pack * width:(j % pack + 1) * width],
            qi_ref[0, :, j // pack * width:(j // pack + 1) * width])


def _visible(block_q, block_k):
    """Whether the program's key block holds a key some query of its
    query block may see."""
    _, hi = _kv_block_range(_CAUSAL, pl.program_id(1), block_q, block_k,
                            pl.num_programs(2))
    return pl.program_id(2) < hi


def _loss_kernel(q_ref, k_ref, lse_ref, qi_ref, kp_ref, w_ref, mask_ref,
                 loss_ref, mass_ref, lse_i_ref, m_acc, l_acc, mass_acc,
                 cross_acc, *, dims):
    """``indexer_loss_fwd``: a row's loss, ``pbar``'s mass and the
    log-sum-exp of its selected scores, carried over the key blocks."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_acc[...] = jnp.full_like(m_acc, _NEG)
        for acc in (l_acc, mass_acc, cross_acc):
            acc[...] = jnp.zeros_like(acc)

    @pl.when(_visible(q_ref.shape[1], k_ref.shape[1]))
    def _():
        pbar, total, attend = _pair(q_ref, k_ref, lse_ref, qi_ref, kp_ref,
                                    w_ref, mask_ref, dims=dims)
        rows = functools.partial(jnp.sum, axis=0, keepdims=True)
        m_old = m_acc[...]
        m_new = jnp.maximum(m_old, jnp.max(
            jnp.where(attend, total, _NEG), axis=0, keepdims=True))
        l_acc[...] = l_acc[...] * jnp.exp(m_old - m_new) + rows(
            jnp.where(attend, jnp.exp(total - m_new), 0.0))
        m_acc[...] = m_new
        mass_acc[...] += rows(pbar)
        cross_acc[...] += rows(pbar * (jnp.log(
            jnp.where(pbar > 0, pbar, 1.0)) - total))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        # a row that selects nothing (padding) has mass 0 and loss 0
        l = l_acc[...]
        lse_i = m_acc[...] + jnp.log(jnp.where(l == 0.0, 1.0, l))
        lse_i_ref[0] = lse_i
        mass_ref[0] = mass_acc[...]
        loss_ref[0] = cross_acc[...] + mass_acc[...] * lse_i


def _grad_kernel(q_ref, k_ref, lse_ref, qi_ref, kp_ref, w_ref, mask_ref,
                 lse_i_ref, mass_ref, gq_ref, gk_ref, gw_ref, gq_acc,
                 gw_acc, *, dims, inv_rows):
    """``indexer_loss_bwd``: the loss's gradients by the indexer's
    queries (carried over the key blocks), its key (added into the
    result, which stays in VMEM over the query blocks) and its head
    weights."""
    _, _, _, idx_heads, pack, width = dims
    qi, ki = pl.program_id(1), pl.program_id(2)
    block_k = k_ref.shape[1]

    @pl.when(ki == 0)
    def _():
        gq_acc[...] = jnp.zeros_like(gq_acc)
        gw_acc[...] = jnp.zeros_like(gw_acc)

    @pl.when(jnp.logical_and(qi == 0, ki == 0))
    def _():
        gk_ref[...] = jnp.zeros_like(gk_ref)

    @pl.when(_visible(q_ref.shape[1], block_k))
    def _():
        pbar, total, attend = _pair(q_ref, k_ref, lse_ref, qi_ref, kp_ref,
                                    w_ref, mask_ref, dims=dims)
        # d loss / d I over the selection: mass x softmax - pbar
        d_total = jnp.where(
            attend, jnp.exp(total - lse_i_ref[0]) * mass_ref[0] - pbar,
            0.0) * inv_rows
        keys = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)
        head_of = jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[1], width), 1) // (width // pack)
        for jj in range(idx_heads // pack):
            g_q = jnp.zeros((q_ref.shape[1], width), _F32)
            g_k = jnp.zeros((block_k, width), _F32)
            for j in range(jj * pack, (jj + 1) * pack):
                k_p, q_p = _key_and_queries(kp_ref, qi_ref, j, dims)
                act = jax.lax.dot_general(k_p, q_p, _NT,
                                          preferred_element_type=_F32)
                live = act > 0
                gw_acc[j:j + 1, :] += jnp.sum(
                    jnp.where(live, act * d_total, 0.0), axis=0,
                    keepdims=True)
                d_act = jnp.where(live, d_total * w_ref[0, j:j + 1, :],
                                  0.0).astype(q_p.dtype)        # [Bk, Bq]
                g_q += jax.lax.dot_general(d_act, k_p, _TN,
                                           preferred_element_type=_F32)
                q_j = q_p if pack == 1 else jnp.where(
                    head_of == j % pack, q_p, jnp.zeros_like(q_p))
                g_k += jnp.dot(d_act, q_j, preferred_element_type=_F32)
            gq_acc[jj] += g_q
            gk_ref[0, keys, :] += g_k

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        for jj in range(idx_heads // pack):
            gq_ref[0, :, jj * width:(jj + 1) * width] = gq_acc[jj].astype(
                gq_ref.dtype)
        gw_ref[0] = gw_acc[...].astype(gw_ref.dtype)


def loss_pass(q_idx, k_idx, w, mask, q, k, lse, with_gradients, *,
              block_q=None, block_k=None, interpret=None):
    """:func:`..sparse_attention._loss_pass` by the two kernels: ``(loss,
    mean mass)`` and, asked, ``(g_q, g_k, g_w)``. ``q_idx [B, T, J, Di]``,
    ``k_idx [B, T, Di]``, ``w [B, T, J]``, ``mask`` int8 ``[B, T, T]``,
    ``q [B, T, H, Dh]``, ``k [B, T, Hkv, Dh]``, ``lse [B, H, T]``."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, heads, dh = q.shape
    idx_heads, di = q_idx.shape[2:]
    pack = _pack(idx_heads, di)
    width = pack * di
    block = LONG_BLOCK if t >= LONG_SEQUENCE else DEFAULT_BLOCK_Q
    block = min(block, -(-t // _LANES) * _LANES)
    block_q, block_k = block_q or block, block_k or block
    dims = (heads, heads // k.shape[2], dh, idx_heads, pack, width)

    flat = lambda x: x.reshape(x.shape[:2] + (-1,))
    rows = lambda x: _pad_to(x, 1, block_q)                 # [B, T, ..]
    lanes = lambda x: _pad_to(x, 2, block_q)                # [B, .., T]
    qp, qip = rows(flat(q)), rows(flat(q_idx))
    kp, parts = (_pad_to(x, 1, block_k)
                 for x in (flat(k), _key_parts(k_idx, pack)))
    lsep = lanes(lse.astype(_F32))
    w_t = lanes(jnp.swapaxes(w, 1, 2).astype(_F32))         # [B, J, T]
    # the block is held transposed: so is the selection (padded 0)
    mask_t = jnp.swapaxes(_pad_to(_pad_to(mask, 1, block_q), 2, block_k),
                          1, 2)
    tq, tk = qp.shape[1], kp.shape[1]
    grid = (b, tq // block_q, tk // block_k)

    def last(i, j):
        _, hi = _kv_block_range(_CAUSAL, i, block_q, block_k, grid[2])
        return jnp.minimum(j, hi - 1)

    q_rows = lambda n, i, j: (n, i, 0)
    k_rows = lambda n, i, j: (n, last(i, j), 0)
    q_lanes = lambda n, i, j: (n, 0, i)
    row = pl.BlockSpec((1, 1, block_q), q_lanes)
    idx_rows = pl.BlockSpec((1, block_q, idx_heads * di), q_rows)
    head_weights = pl.BlockSpec((1, idx_heads, block_q), q_lanes)
    in_specs = [
        pl.BlockSpec((1, block_q, heads * dh), q_rows),
        pl.BlockSpec((1, block_k, kp.shape[2]), k_rows),
        pl.BlockSpec((1, heads, block_q), q_lanes),
        idx_rows,
        pl.BlockSpec((1, block_k, parts.shape[2]), k_rows),
        head_weights,
        pl.BlockSpec((1, block_k, block_q),
                     lambda n, i, j: (n, last(i, j), i)),
    ]
    operands = (qp, kp, lsep, qip, parts, w_t, mask_t)
    a_row = jax.ShapeDtypeStruct((b, 1, tq), _F32)
    semantics = ("arbitrary",) * 3
    blocks = (block_q * heads * dh * q.dtype.itemsize,
              block_q * idx_heads * di * q_idx.dtype.itemsize)

    loss_rows, mass, lse_i = pl.pallas_call(
        functools.partial(_loss_kernel, dims=dims),
        name="indexer_loss_fwd",
        grid=grid, in_specs=in_specs, out_specs=[row] * 3,
        out_shape=[a_row] * 3,
        scratch_shapes=[pltpu.VMEM((1, block_q), _F32)] * 4,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_vmem_limit(*blocks)),
        interpret=interpret,
    )(*operands)
    out = (jnp.sum(loss_rows) / (b * t), jnp.sum(mass) / (b * t))
    if not with_gradients:
        return out

    g_q, g_k, g_w = pl.pallas_call(
        functools.partial(_grad_kernel, dims=dims, inv_rows=1.0 / (b * t)),
        name="indexer_loss_bwd",
        grid=grid, in_specs=in_specs + [row, row],
        out_specs=[
            idx_rows,
            pl.BlockSpec((1, tk, width), lambda n, i, j: (n, 0, 0)),
            head_weights,
        ],
        out_shape=[jax.ShapeDtypeStruct(qip.shape, q_idx.dtype),
                   jax.ShapeDtypeStruct((b, tk, width), _F32),
                   jax.ShapeDtypeStruct(w_t.shape, w.dtype)],
        scratch_shapes=[
            pltpu.VMEM((idx_heads // pack, block_q, width), _F32),
            pltpu.VMEM((idx_heads, block_q), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=_vmem_limit(
                *blocks, 4 * tk * width,
                scratch=4 * idx_heads * block_q * di)),
        interpret=interpret,
    )(*operands, lse_i, mass)
    # a pack's heads lie side by side: head p's share under its columns
    g_k = jnp.sum(g_k[:, :t].reshape(b, t, pack, di), axis=2)
    return out, (g_q[:, :t].reshape(q_idx.shape), g_k.astype(k_idx.dtype),
                 jnp.swapaxes(g_w[:, :, :t], 1, 2))
