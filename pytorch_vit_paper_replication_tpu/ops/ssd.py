"""The chunked scan of a Mamba-2 mixer (state-space duality) as a pair
of Mosaic kernels, ``ssd_fwd`` and ``ssd_bwd``, and the mixer's causal
convolution.

A Mamba-2 mixer (``models/vit.py::MambaBlock``) hands the scan, for
every sequence of a batch, head h (of H, each P columns wide) and
position t,

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T,    S_{-1} = 0,
    y_t = S_t C_t + D_h x_t,

a ``[P, N]`` state a head, ``B_t`` and ``C_t`` the ``N`` columns of the
head's group (H / G heads share one). Unrolled, ``y_t = sum_{s <= t}
(C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t``.

:func:`ssd` takes that in chunks of ``chunk`` positions (Mamba-2's
``chunk_size``): within a chunk the quadratic form, ``(L o C B^T)(dt
x)`` with ``L[t, s] = exp(acs_t - acs_s)`` on and below the diagonal,
``acs`` the chunk's cumulative sum of ``dt A``; between chunks the
state, carried from chunk to chunk (it decays by ``exp(sum over the
chunk of dt A)`` across one) and read back as ``exp(acs_t) C_t . S``.
Every decay is a difference of sums taken inside one chunk, so no two
long cumulative sums are subtracted. Elementwise arithmetic and every
accumulation are in float32; a product's operands go to the MXU in the
input's dtype (bf16 in the model), as Mamba-2's own kernels feed their
dots. Positions past a sequence's end (the last chunk padded) carry
``dt = 0`` and ``x = 0``: they add nothing and decay nothing.

The kernels. Both walk the grid (sequence, chunk, head block), the
chunks in order (``ssd_bwd`` from the last), a block's ``hb`` heads
(:func:`head_block`: 8 of Granite's heads of 64, 512 lane-dense
columns) unrolled one after another. They read ``x`` and ``y``'s
cotangent and write ``y`` and ``x``'s cotangent as column blocks of
``[batch, T, H P]`` (the mixer's own layout), ``B`` and ``C`` as the
group's column block of ``[batch, T, G N]``, and ``dt`` and ``acs``
(the chunk's cumulative sums of ``dt A``, an XLA product with a triangle
of ones at float32 precision) as rows ``[hb, chunk]``. A program holds
its block turned, positions on lanes: ``x``, ``y`` and every ``[P,
chunk]`` quantity of a head are lane-dense, and a sum over a head's P
columns is a sum over sublanes that lands as a row. What a chunk needs
stays in VMEM: ``C B^T`` (once a chunk and group), each head's in-chunk
block ``L o C B^T`` (turned, only its tiles of 128 x 128 on and above
the diagonal built), and the state of every head, ``f32[H P, N]`` in
scratch, carried from chunk to chunk and zeroed at a sequence's first.
``ssd_fwd`` writes ``y`` and, where a gradient is asked for, the states
entering each chunk (``f32[chunks, batch, H P, N]``), the one residual
beside the inputs. ``ssd_bwd`` carries the state's cotangent the same
way backwards, sums ``d(C B^T)`` over a group's heads in VMEM, writes
``x``'s, ``B``'s and ``C``'s cotangents once each and, a head and
position at a time, three float32 rows: the cotangent of ``acs``
(``dy_t . (y_t - D x_t) - u_t . du_t``, ``u = dt x``, and the chunk's
total decay at its end: no ``[chunk, chunk]`` sum is taken for it),
``du . x`` and ``dy . x``. From them small XLA passes over ``[batch, H,
T]`` take ``dt``'s, ``A``'s and ``D``'s (``acs``'s adjoint is the sum
from each position to its chunk's end, a product again). Nothing of the
size ``[chunk, chunk]`` and no state but that residual reaches HBM. Off
the chip the same kernels run under the Pallas interpreter; traced
under a mesh they run per shard.

:func:`causal_conv` is the mixer's depthwise causal convolution over
``[x | B | C]``, with its bias and the SiLU after it, as shifted
products along T (:func:`..ops.short_conv.depthwise_causal_conv`), its
gradient written out too.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import partition
from .flash_attention import _vmem_limit
from .short_conv import depthwise_causal_conv

_LANES = 128
BLOCK_COLUMNS = 512     # x's columns a head block takes at most
BLOCK_HEADS = 8         # and its heads
_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _earlier(x: jax.Array, by: int) -> jax.Array:
    """``x [batch, T, C]`` moved ``by`` positions earlier, zeros after."""
    if by == 0:
        return x
    return jnp.pad(x[:, by:], ((0, 0), (0, by), (0, 0)))


def _pre(v, taps, bias):
    return depthwise_causal_conv(v.astype(jnp.float32),
                                 taps.astype(jnp.float32)) \
        + bias.astype(jnp.float32)


@jax.custom_vjp
def causal_conv(v: jax.Array, taps: jax.Array, bias: jax.Array) -> jax.Array:
    """``silu(conv(v) + bias)`` of ``v [batch, T, C]`` with ``taps [K, C]``
    (tap K - 1 reads the position itself), float32 arithmetic, the result
    in ``v``'s dtype. Its gradient is written out: the backward pass
    keeps ``v`` and takes the convolution again."""
    return jax.nn.silu(_pre(v, taps, bias)).astype(v.dtype)


def _conv_fwd(v, taps, bias):
    return causal_conv(v, taps, bias), (v, taps, bias)


def _conv_bwd(res, dy):
    v, taps, bias = res
    pre = _pre(v, taps, bias)
    sig = jax.nn.sigmoid(pre)
    d_pre = dy.astype(jnp.float32) * sig * (1.0 + pre * (1.0 - sig))
    w = taps.astype(jnp.float32)
    k, t = w.shape[0], v.shape[1]
    v32 = v.astype(jnp.float32)
    # tap i reads the position K - 1 - i before: its adjoint reads as many
    # after
    dv = sum(w[i] * _earlier(d_pre, k - 1 - i) for i in range(k))
    d_taps = jnp.stack([
        jnp.sum(d_pre[:, k - 1 - i:] * v32[:, :t - (k - 1 - i)], axis=(0, 1))
        for i in range(k)])
    return (dv.astype(v.dtype), d_taps.astype(taps.dtype),
            jnp.sum(d_pre, axis=(0, 1)).astype(bias.dtype))


causal_conv.defvjp(_conv_fwd, _conv_bwd)


def state_carry(dt: jax.Array, a: jax.Array, chunk: int) -> jax.Array:
    """The mean over sequences, heads and chunks of ``exp(sum over the
    chunk of dt A)``: the share of the state entering a chunk that reaches
    its end (``dt [batch, T, H]``, ``a [H]``). A partial last chunk sums
    the positions it has."""
    q, (b, t, h) = min(chunk, dt.shape[1]), dt.shape
    c = -(-t // q)
    log = jnp.pad(dt.astype(jnp.float32) * a,
                  ((0, 0), (0, c * q - t), (0, 0)))
    return jnp.mean(jnp.exp(jnp.sum(log.reshape(b, c, q, h), axis=2)))


def head_block(h: int, p: int, g: int) -> int:
    """Heads a kernel program takes: the most heads of one group, at most
    :data:`BLOCK_HEADS` (a block's heads are unrolled), whose columns of
    ``x`` fit :data:`BLOCK_COLUMNS`, a whole number of lanes where one
    can be (``hb P`` a multiple of 128: two heads at P = 64)."""
    group = h // g
    fits = [k for k in range(1, min(group, BLOCK_HEADS) + 1)
            if group % k == 0 and k * p <= max(BLOCK_COLUMNS, p)]
    dense = [k for k in fits if k * p % _LANES == 0]
    return max(dense or fits)


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _last(row):
    """``[r, 1]``: the last entry of each row of ``[r, Q]``."""
    at = jax.lax.broadcasted_iota(jnp.int32, row.shape, 1)
    return jnp.sum(jnp.where(at == row.shape[1] - 1, row, 0.0), axis=1,
                   keepdims=True)


def _columns(rows):
    """``rows [hb, Q]`` turned once: ``[Q, 128]``, lane ``j`` row ``j``."""
    hb, q = rows.shape
    pad = jnp.zeros((_LANES - hb, q), jnp.float32)
    return jnp.concatenate([rows, pad], axis=0).T


def _span(q):
    """Rows of the in-chunk block taken together: 128 (the block is
    triangular, so a span of rows reaches only the columns on one side of
    its start), or the whole chunk where it is no multiple of 128."""
    return _LANES if q % _LANES == 0 else q


def _zero_below(wt_ref):
    """The blocks of ``wt_ref [Q, Q]`` that no span reaches, zeroed."""
    q = wt_ref.shape[0]
    span = _span(q)
    for g in range(1, q // span):
        wt_ref[g * span:(g + 1) * span, :g * span] = jnp.zeros(
            (span, g * span), wt_ref.dtype)


def _turned_block(wt_ref, cbt_ref, acs_ref, cols_ref, j):
    """Head ``j``'s in-chunk block turned, ``wt[s, t] = L[t, s] (C
    B^T)[t, s]`` with ``L[t, s] = exp(acs_t - acs_s)`` for ``t >= s``,
    else 0, into ``wt_ref`` in the MXU's dtype, a span of 128 rows at a
    time over the columns it reaches; ``acs`` as a row from ``acs_ref``
    and as a column from ``cols_ref``. Returns each span's decays."""
    q = wt_ref.shape[0]
    span = _span(q)
    decays = []
    for g in range(q // span):
        rs, reach = slice(g * span, (g + 1) * span), slice(g * span, q)
        s_ = jax.lax.broadcasted_iota(jnp.int32, (span, q - g * span), 0)
        t_ = jax.lax.broadcasted_iota(jnp.int32, (span, q - g * span), 1)
        decay = jnp.exp(jnp.where(
            t_ >= s_, acs_ref[0, 0, j:j + 1, reach] - cols_ref[rs, j:j + 1],
            -jnp.inf))
        decays.append(decay)
        wt_ref[rs, reach] = (decay * cbt_ref[rs, reach]).astype(wt_ref.dtype)
    return decays


def _fwd_kernel(d_ref, x_ref, acs_ref, dt_ref, b_ref, c_ref, y_ref, *rest,
                heads_per_group):
    """One (sequence, chunk, head block) program of ``ssd_fwd``. Held
    turned, positions on lanes: ``x``, ``y`` and every ``[P, Q]``
    quantity of a head, and the in-chunk block. ``rest``: the entering
    states' block (where a gradient is asked for), then the scratch, the
    state of every head ``[H P, N]`` last."""
    *ent, cbt_ref, ct_ref, cols_ref, xt_ref, zt_ref, yt_ref, upt_ref, \
        wt_ref, s_ref = rest
    ci, k = pl.program_id(1), pl.program_id(2)
    hb = acs_ref.shape[2]
    w = x_ref.shape[2]
    p = w // hb
    mxu = x_ref.dtype
    f32 = jnp.float32
    blk = pl.ds(pl.multiple_of(k * w, w), w)

    @pl.when(k % (heads_per_group // hb) == 0)
    def _():
        cbt_ref[...] = _dot(b_ref[0].astype(mxu), c_ref[0].astype(mxu), _NT)
        ct_ref[...] = c_ref[0].astype(f32).T.astype(mxu)

    @pl.when(ci == 0)
    def _():
        s_ref[blk, :] = jnp.zeros((w, s_ref.shape[1]), f32)

    s_in = s_ref[blk, :]                                     # [W, N]
    if ent:
        ent[0][0, 0] = s_in
    zt_ref[...] = _dot(s_in.astype(mxu), ct_ref[...])        # (C S_in)^T
    xt_ref[...] = x_ref[0].astype(f32).T
    cols_ref[...] = _columns(acs_ref[0, 0])
    _zero_below(wt_ref)
    for j in range(hb):
        hs = slice(j * p, (j + 1) * p)
        acs_r, dt_r = acs_ref[0, 0, j:j + 1, :], dt_ref[0, 0, j:j + 1, :]
        tot = _last(acs_r)
        xt = xt_ref[hs, :]
        ut = xt * dt_r                                       # (dt x)^T
        _turned_block(wt_ref, cbt_ref, acs_ref, cols_ref, j)
        # y_t = sum_s w[t, s] u_s + exp(acs_t) C_t . S_in + D x_t
        yt_ref[hs, :] = (_dot(ut.astype(mxu), wt_ref[...])
                         + jnp.exp(acs_r) * zt_ref[hs, :]
                         + d_ref[k * hb + j] * xt)
        upt_ref[hs, :] = jnp.exp(tot - acs_r) * ut
        s_ref[pl.ds(pl.multiple_of(k * w, w) + j * p, p), :] = \
            jnp.exp(tot) * s_in[hs]
    y_ref[0] = yt_ref[...].T.astype(y_ref.dtype)
    # S_out = exp(tot) S_in + sum_s exp(tot - acs_s) u_s B_s^T
    s_ref[blk, :] += _dot(upt_ref[...].astype(mxu), b_ref[0].astype(mxu))


def _bwd_kernel(d_ref, x_ref, acs_ref, dt_ref, b_ref, c_ref, ent_ref, dy_ref,
                dx_ref, db_ref, dc_ref, rows_ref, cbt_ref, bt_ref, ct_ref,
                dcbt_ref, dbt_acc, dct_acc, cols_ref, xt_ref, gyt_ref, zt_ref,
                vt_ref, gzt_ref, upt_ref, dxt_ref, wt_ref, ds_ref, *,
                heads_per_group):
    """One (sequence, chunk from the last, head block) program of
    ``ssd_bwd``. Held turned, positions on lanes: ``x``, ``y``'s
    cotangent and every ``[P, Q]`` quantity of a head (so that a sum over
    a head's columns is a sum over sublanes and lands as a row), and the
    in-chunk block (``wt[s, t] = L[t, s] (C B^T)[t, s]``). ``ds_ref [H P,
    N]`` carries each head's cotangent of the state leaving the chunk;
    ``dcbt_ref``, ``dbt_acc`` and ``dct_acc`` sum a group's heads."""
    ci, k = pl.program_id(1), pl.program_id(2)
    hb, q = acs_ref.shape[2], acs_ref.shape[3]
    w = x_ref.shape[2]
    p = w // hb
    mxu = x_ref.dtype
    f32 = jnp.float32
    blocks = heads_per_group // hb
    blk = pl.ds(pl.multiple_of(k * w, w), w)

    @pl.when(k % blocks == 0)
    def _():
        bm, cm = b_ref[0].astype(mxu), c_ref[0].astype(mxu)
        cbt_ref[...] = _dot(bm, cm, _NT)                     # (C B^T)^T
        bt_ref[...] = b_ref[0].astype(f32).T.astype(mxu)
        ct_ref[...] = c_ref[0].astype(f32).T.astype(mxu)
        dcbt_ref[...] = jnp.zeros_like(dcbt_ref)
        dbt_acc[...] = jnp.zeros_like(dbt_acc)
        dct_acc[...] = jnp.zeros_like(dct_acc)

    @pl.when(ci == 0)
    def _():
        ds_ref[blk, :] = jnp.zeros((w, ds_ref.shape[1]), f32)

    s_in, grad = ent_ref[0, 0], ds_ref[blk, :]               # [W, N]
    zt_ref[...] = _dot(s_in.astype(mxu), ct_ref[...])        # (C S_in)^T
    vt_ref[...] = _dot(grad.astype(mxu), bt_ref[...])        # (B dS_out)^T
    xt_ref[...] = x_ref[0].astype(f32).T
    gyt_ref[...] = dy_ref[0].astype(f32).T
    cols_ref[...] = _columns(acs_ref[0, 0])
    span = _span(q)
    _zero_below(wt_ref)
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    for j in range(hb):
        hs = slice(j * p, (j + 1) * p)
        acs_r, dt_r = acs_ref[0, 0, j:j + 1, :], dt_ref[0, 0, j:j + 1, :]
        tot = _last(acs_r)
        into, out = jnp.exp(acs_r), jnp.exp(tot - acs_r)     # [1, Q]
        xt, gyt = xt_ref[hs, :], gyt_ref[hs, :]              # [P, Q]
        ut = xt * dt_r
        utm, gytm = ut.astype(mxu), gyt.astype(mxu)
        decays = _turned_block(wt_ref, cbt_ref, acs_ref, cols_ref, j)
        y_in = _dot(utm, wt_ref[...])             # the block's y, turned
        du_in = _dot(gytm, wt_ref[...], _NT)
        up = out * ut
        du = du_in + out * vt_ref[hs, :]
        dwt = _dot(utm, gytm, _TN)                # [s, t]: u_s . dy_t
        for g, decay in enumerate(decays):
            rs = slice(g * span, (g + 1) * span)
            dcbt_ref[rs, g * span:] += dwt[rs, g * span:] * decay
        # d acs_t = dy_t . (y_t - D x_t) - u_t . du_t, and at the chunk's
        # end the cotangent of its total decay. The block's two terms
        # read the same rounded u, so that their sum over the chunk is 0
        # as the exact one is
        d_tot = (jnp.sum(jnp.sum(up * vt_ref[hs, :], axis=0, keepdims=True),
                         axis=1, keepdims=True)
                 + jnp.exp(tot) * jnp.sum(jnp.sum(
                     grad[hs] * s_in[hs], axis=0, keepdims=True), axis=1,
                     keepdims=True))
        rows_ref[0, 0, 0, j:j + 1, :] = (
            jnp.sum(gyt * (y_in + into * zt_ref[hs, :])
                    - utm.astype(f32) * du_in - up * vt_ref[hs, :], axis=0,
                    keepdims=True) + jnp.where(last, d_tot, 0.0))
        rows_ref[1, 0, 0, j:j + 1, :] = jnp.sum(du * xt, axis=0,
                                                keepdims=True)
        rows_ref[2, 0, 0, j:j + 1, :] = jnp.sum(gyt * xt, axis=0,
                                                keepdims=True)
        dxt_ref[hs, :] = du * dt_r + d_ref[k * hb + j] * gyt
        gzt_ref[hs, :] = gyt * into
        upt_ref[hs, :] = up
        ds_ref[pl.ds(pl.multiple_of(k * w, w) + j * p, p), :] = \
            jnp.exp(tot) * grad[hs]

    dx_ref[0] = dxt_ref[...].T.astype(dx_ref.dtype)
    gzm = gzt_ref[...].astype(mxu)
    ds_ref[blk, :] += _dot(gzm, c_ref[0].astype(mxu))
    dct_acc[...] += _dot(s_in.astype(mxu), gzm, _TN)         # dC^T
    dbt_acc[...] += _dot(grad.astype(mxu), upt_ref[...].astype(mxu), _TN)

    @pl.when(k % blocks == blocks - 1)
    def _():
        dcbt = dcbt_ref[...].astype(mxu)
        dc_ref[0] = (dct_acc[...] + _dot(bt_ref[...], dcbt)).T.astype(
            dc_ref.dtype)
        db_ref[0] = (dbt_acc[...].T + _dot(dcbt, c_ref[0].astype(mxu))
                     ).astype(db_ref.dtype)


def _rows(v, hb, tp):
    """``v [b, T, H]`` as float32 rows ``[b, H / hb, hb, tp]``, padded
    with zeros to ``tp`` positions."""
    b, t, h = v.shape
    v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, tp - t), (0, 0)))
    return v.transpose(0, 2, 1).reshape(b, h // hb, hb, tp)


def _padded(v, tp):
    """``v [b, T, ...]`` flattened to ``[b, tp, -1]``, zeros past T."""
    v = v.reshape(v.shape[:2] + (-1,))
    if v.shape[1] == tp:
        return v
    return jnp.pad(v, ((0, 0), (0, tp - v.shape[1]), (0, 0)))


def _in_chunks(v, q, reverse=False):
    """Cumulative sums of ``v [..., T]`` inside each chunk of ``q``
    positions (from each position to the chunk's end if ``reverse``), as
    a product with a triangle of ones at float32 precision: the sums keep
    the scope they are taken in (``jnp.cumsum`` lowers to a window that
    loses it)."""
    pos = jnp.arange(q)
    ones = (pos[:, None] >= pos[None, :] if reverse
            else pos[:, None] <= pos[None, :]).astype(jnp.float32)
    chunks = v.reshape(v.shape[:-1] + (-1, q))
    return jnp.matmul(chunks, ones, precision=jax.lax.Precision.HIGHEST
                      ).reshape(v.shape)


def _operands(x, dt, a, bb, cc, chunk):
    """The shapes a call takes, and its operands as the kernels read
    them: ``x``, ``B``, ``C`` flat and padded to whole chunks, ``acs``
    and ``dt`` as rows."""
    b, t, h, p = x.shape
    g, n = bb.shape[2:]
    q = min(chunk, t)
    c = -(-t // q)
    hb = head_block(h, p, g)
    shape = dict(b=b, t=t, h=h, p=p, g=g, n=n, q=q, c=c, hb=hb)
    dts = _rows(dt, hb, c * q)
    log = dts * a.astype(jnp.float32).reshape(h // hb, hb, 1)
    acs = _in_chunks(log, q)
    return shape, (_padded(x, c * q), acs, dts, _padded(bb, c * q),
                   _padded(cc, c * q))


def _specs(s, backward):
    """BlockSpecs over the grid (sequence, chunk, head block): ``x``-like,
    rows, ``B``-like, the entering states, and the chunk each visits."""
    q, hb, p, n, c = s["q"], s["hb"], s["p"], s["n"], s["c"]
    at = (lambda i: c - 1 - i) if backward else (lambda i: i)
    group = s["h"] // s["g"] // hb
    x = pl.BlockSpec((1, q, hb * p), lambda z, i, k: (z, at(i), k))
    rows = pl.BlockSpec((1, 1, hb, q), lambda z, i, k: (z, k, 0, at(i)))
    bc = pl.BlockSpec((1, q, n), lambda z, i, k: (z, at(i), k // group))
    ent = pl.BlockSpec((1, 1, hb * p, n), lambda z, i, k: (at(i), z, k, 0))
    return x, rows, bc, ent, pl.BlockSpec(memory_space=pltpu.SMEM)


def _params(s, scratch, interpret):
    """Compiler parameters: a program's blocks (at most four ``[Q, hb P]``
    and one ``[N, hb P]`` in float32, double-buffered) beside its
    ``scratch``."""
    q, w, n = s["q"], s["hb"] * s["p"], s["n"]
    held = sum(math.prod(v.shape) * jnp.dtype(v.dtype).itemsize
               for v in scratch)
    return dict(
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(4 * q * w * 4, n * w * 4,
                                         scratch=held)),
        interpret=interpret)


def _interpret():
    """The Pallas interpreter off the chip."""
    return jax.default_backend() != "tpu"


# ``_forward`` and ``_backward`` are traced once a shape and inlined where
# they are called: a model of many mixers traces the kernels' bodies once,
# not once a layer, and lowers each call as before.
@functools.partial(jax.jit, static_argnums=(6, 7, 8), inline=True)
def _forward(x, dt, a, bb, cc, d, chunk, keep, interpret):
    """``y`` and, if ``keep``, the state entering each chunk."""
    s, (xf, acs, dts, bf, cf) = _operands(x, dt, a, bb, cc, chunk)
    b, t, h, p, n, c = s["b"], s["t"], s["h"], s["p"], s["n"], s["c"]
    xs, rows, bc, ent, smem = _specs(s, backward=False)
    q, w, f32 = s["q"], s["hb"] * p, jnp.float32
    out_shape = [jax.ShapeDtypeStruct(xf.shape, x.dtype)]
    out_specs = [xs]
    if keep:
        out_shape.append(jax.ShapeDtypeStruct((c, b, h * p, n), f32))
        out_specs.append(ent)
    scratch = ([pltpu.VMEM((q, q), f32), pltpu.VMEM((n, q), x.dtype),
                pltpu.VMEM((q, _LANES), f32)] + [pltpu.VMEM((w, q), f32)] * 4
               + [pltpu.VMEM((q, q), x.dtype), pltpu.VMEM((h * p, n), f32)])
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, heads_per_group=h // s["g"]),
        name="ssd_fwd", grid=(b, c, h // s["hb"]),
        in_specs=[smem, xs, rows, rows, bc, bc], out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        **_params(s, scratch, interpret),
    )(d.astype(jnp.float32), xf, acs, dts, bf, cf)
    y = outs[0][:, :t].reshape(x.shape)
    return y, (outs[1] if keep else None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, bb, cc, d, chunk):
    return _forward(x, dt, a, bb, cc, d, chunk, False, _interpret())[0]


def _fwd(x, dt, a, bb, cc, d, chunk):
    y, entering = _forward(x, dt, a, bb, cc, d, chunk, True, _interpret())
    return y, (x, dt, a, bb, cc, d, entering)


def _bwd(chunk, res, dy):
    return _backward(*res, dy, chunk, _interpret())


@functools.partial(jax.jit, static_argnums=(8, 9), inline=True)
def _backward(x, dt, a, bb, cc, d, entering, dy, chunk, interpret):
    """The six cotangents from ``y``'s, ``dy``, and the residual."""
    s, (xf, acs, dts, bf, cf) = _operands(x, dt, a, bb, cc, chunk)
    b, t, h, p, n, q, c, hb = (s[k] for k in ("b", "t", "h", "p", "n", "q",
                                              "c", "hb"))
    w, f32 = hb * p, jnp.float32
    xs, rows, bc, ent, smem = _specs(s, backward=True)
    out_rows = pl.BlockSpec((3,) + rows.block_shape,
                            lambda z, i, k: (0,) + rows.index_map(z, i, k))
    scratch = ([pltpu.VMEM((q, q), f32)] + [pltpu.VMEM((n, q), x.dtype)] * 2
               + [pltpu.VMEM((q, q), f32)] + [pltpu.VMEM((n, q), f32)] * 2
               + [pltpu.VMEM((q, _LANES), f32)]
               + [pltpu.VMEM((w, q), f32)] * 7
               + [pltpu.VMEM((q, q), x.dtype), pltpu.VMEM((h * p, n), f32)])
    dx, db, dc, out = pl.pallas_call(
        functools.partial(_bwd_kernel, heads_per_group=h // s["g"]),
        name="ssd_bwd", grid=(b, c, h // hb),
        in_specs=[smem, xs, rows, rows, bc, bc, ent, xs],
        out_specs=[xs, bc, bc, out_rows],
        out_shape=[jax.ShapeDtypeStruct(xf.shape, x.dtype),
                   jax.ShapeDtypeStruct(bf.shape, bb.dtype),
                   jax.ShapeDtypeStruct(cf.shape, cc.dtype),
                   jax.ShapeDtypeStruct((3,) + acs.shape, f32)],
        scratch_shapes=scratch, **_params(s, scratch, interpret),
    )(d.astype(jnp.float32), xf, acs, dts, bf, cf, entering,
      _padded(dy, c * q))
    # the cotangents of acs, of each u's dt, of D; a position's dt A
    # reaches acs at it and at every later position of its chunk
    d_acs, du_x, dy_x = (v.reshape(b, h, c * q) for v in out)
    d_log = _in_chunks(d_acs, q, reverse=True)
    d_dt = d_log * a.astype(jnp.float32)[:, None] + du_x
    d_a = jnp.sum(d_log * dts.reshape(d_log.shape), axis=(0, 2))
    d_d = jnp.sum(dy_x, axis=(0, 2))
    back_t = lambda v, like: v[:, :t].reshape(like.shape)
    return (back_t(dx, x), d_dt.transpose(0, 2, 1)[:, :t].astype(dt.dtype),
            d_a.astype(a.dtype), back_t(db, bb), back_t(dc, cc),
            d_d.astype(d.dtype))


_scan.defvjp(_fwd, _bwd)


def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, bb: jax.Array,
        cc: jax.Array, d: jax.Array, chunk: int) -> jax.Array:
    """``y [batch, T, H, P]`` in ``x``'s dtype from ``x [batch, T, H,
    P]``, ``dt [batch, T, H]`` (after the softplus), ``a [H]`` (``A``,
    negative), ``bb``, ``cc [batch, T, G, N]`` and ``d [H]``, in chunks
    of ``chunk`` positions (module docstring). Sequences of the batch
    share no state. Traced under a mesh (:func:`.partition.on_mesh`) the
    kernels run per shard, the batch split over the data axis."""
    part = partition.current()
    if part is None:
        return _scan(x, dt, a, bb, cc, d, chunk)
    data = part.axis(part.data_axis)
    if x.shape[0] % part.size(data):
        raise ValueError(f"ssd: batch {x.shape[0]} not divisible by the "
                         f"mesh's {part.data_axis!r} axis "
                         f"({part.size(data)})")
    rows = P(data)
    return part.shard_map(
        lambda *v: _scan(*v, chunk), in_specs=(rows, rows, P(), rows, rows,
                                               P()),
        out_specs=rows)(x, dt, a, bb, cc, d)
