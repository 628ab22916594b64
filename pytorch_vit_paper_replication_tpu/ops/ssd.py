"""The chunked scan of a Mamba-2 mixer (state-space duality), as one
function, and the mixer's causal convolution.

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
x)`` with ``L[t, s] = exp(sum_{r=s+1..t} dt_r A)`` below the diagonal;
between chunks the state at each chunk's end, carried from chunk to
chunk by a scan (a state decays by ``exp(sum over the chunk of dt A)``
across it) and read back as ``exp(sum_{r<=t} dt_r A) C_t . S``. Every
decay is a sum taken inside one chunk, so no two long cumulative sums
are subtracted. Elementwise arithmetic and every accumulation are in
float32; a product's operands go to the MXU at the default precision
(bf16 passes on a TPU), as Mamba-2's own kernels feed their dots in the
input's dtype. The ``[chunks, heads, chunk, chunk]`` blocks are taken
``HEADS_AT_ONCE`` heads at a time, never for every head together.
Positions past a sequence's end (the last chunk padded) carry ``dt = 0``
and ``x = 0``: they add nothing and decay nothing.

Its gradient is written out (``jax.custom_vjp``): the backward pass keeps
the inputs and the state entering each chunk (``[chunks, batch, H, P,
N]`` float32) and takes each chunk's blocks again. A kernel that does
the same in fewer passes over HBM replaces it at this one call site.

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

from .short_conv import depthwise_causal_conv

HEADS_AT_ONCE = 8


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


def _layout(x, dt, a, bb, cc, chunk):
    """Inputs padded to whole chunks, float32: ``u = dt x [b, c, Q, H,
    P]``, ``x`` alike, ``B``, ``C`` ``[b, c, Q, G, N]``, ``dt`` and the
    in-chunk cumulative log-decay ``acs [b, c, Q, H]``."""
    b, t, h, p = x.shape
    q = min(chunk, t)
    c = -(-t // q)
    pad = lambda v: jnp.pad(v.astype(jnp.float32), ((0, 0), (0, c * q - t))
                            + ((0, 0),) * (v.ndim - 2))
    xs = pad(x).reshape(b, c, q, h, p)
    dts = pad(dt).reshape(b, c, q, h)
    g, n = bb.shape[2:]
    bs = pad(bb).reshape(b, c, q, g, n)
    cs = pad(cc).reshape(b, c, q, g, n)
    acs = jnp.cumsum(dts * a.astype(jnp.float32), axis=2)
    return xs, dts, bs, cs, acs


def _blocks(acs_k, b_g, c_g):
    """A group of heads' in-chunk blocks: ``L [b, c, hg, Q, Q]`` (the
    decays, 0 above the diagonal) and ``C B^T [b, c, Q, Q]``."""
    seg = acs_k.transpose(0, 1, 3, 2)                       # [b, c, hg, Q]
    q = seg.shape[-1]
    below = jnp.arange(q)[:, None] >= jnp.arange(q)[None, :]
    diff = seg[..., :, None] - seg[..., None, :]
    decay = jnp.exp(jnp.where(below, diff, -jnp.inf))
    return decay, jnp.einsum("bctn,bcsn->bcts", c_g, b_g)


def _heads(h: int, g: int) -> int:
    """Heads taken at a time: a divisor of the heads of one group."""
    return math.gcd(HEADS_AT_ONCE, h // g)


def _group_of(k, hg, h, g):
    """The B / C group that head block ``k`` (of ``hg`` heads) reads."""
    return (k * hg) // (h // g)


def _forward(x, dt, a, bb, cc, d, chunk):
    xs, dts, bs, cs, acs = _layout(x, dt, a, bb, cc, chunk)
    b, c, q, h, p = xs.shape
    g, n = bs.shape[3:]
    hg = _heads(h, g)
    u = xs * dts[..., None]
    total = acs[:, :, -1]                                    # [b, c, H]

    def one(k, bufs):
        y_buf, s_buf = bufs
        lo = k * hg
        grp = _group_of(k, hg, h, g)
        u_k = jax.lax.dynamic_slice_in_dim(u, lo, hg, axis=3)
        acs_k = jax.lax.dynamic_slice_in_dim(acs, lo, hg, axis=3)
        tot_k = jax.lax.dynamic_slice_in_dim(total, lo, hg, axis=2)
        b_g = jax.lax.dynamic_index_in_dim(bs, grp, axis=3, keepdims=False)
        c_g = jax.lax.dynamic_index_in_dim(cs, grp, axis=3, keepdims=False)
        decay, cb = _blocks(acs_k, b_g, c_g)
        w = decay * cb[:, :, None]
        y_k = jnp.einsum("bchts,bcshp->bcthp", w, u_k)
        out = jnp.exp(tot_k[:, :, None] - acs_k)             # [b, c, Q, hg]
        s_k = jnp.einsum("bcsh,bcshp,bcsn->cbhpn", out, u_k, b_g)
        return (jax.lax.dynamic_update_slice_in_dim(y_buf, y_k, lo, axis=3),
                jax.lax.dynamic_update_slice_in_dim(s_buf, s_k, lo, axis=2))

    y_diag, local = jax.lax.fori_loop(
        0, h // hg, one, (jnp.zeros_like(u),
                          jnp.zeros((c, b, h, p, n), jnp.float32)))

    def carry(state, inputs):
        local_c, decay_c = inputs
        return decay_c[..., None, None] * state + local_c, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((b, h, p, n), jnp.float32),
        (local, jnp.exp(total).transpose(1, 0, 2)))          # [c, b, H, P, N]
    y = y_diag + _from_states(cs, entering, acs, h) \
        + d.astype(jnp.float32)[:, None] * xs
    t = x.shape[1]
    return y.reshape(b, c * q, h, p)[:, :t].astype(x.dtype), entering


def _from_states(cs, entering, acs, h):
    """``exp(acs_t) C_t . S_in`` for every position: ``[b, c, Q, H, P]``."""
    b, c, q, g, n = cs.shape
    s = entering.reshape(c, b, g, h // g, -1, n)
    z = jnp.einsum("bctgn,cbgkpn->bctgkp", cs, s).reshape(b, c, q, h, -1)
    return jnp.exp(acs)[..., None] * z


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def ssd(x: jax.Array, dt: jax.Array, a: jax.Array, bb: jax.Array,
        cc: jax.Array, d: jax.Array, chunk: int) -> jax.Array:
    """``y [batch, T, H, P]`` in ``x``'s dtype from ``x [batch, T, H,
    P]``, ``dt [batch, T, H]`` (after the softplus), ``a [H]`` (``A``,
    negative), ``bb``, ``cc [batch, T, G, N]`` and ``d [H]``, in chunks
    of ``chunk`` positions (module docstring). Sequences of the batch
    share no state."""
    return _forward(x, dt, a, bb, cc, d, chunk)[0]


def _fwd(x, dt, a, bb, cc, d, chunk):
    y, entering = _forward(x, dt, a, bb, cc, d, chunk)
    return y, (x, dt, a, bb, cc, d, entering)


def _bwd(chunk, res, dy):
    x, dt, a, bb, cc, d, entering = res
    xs, dts, bs, cs, acs = _layout(x, dt, a, bb, cc, chunk)
    b, c, q, h, p = xs.shape
    g, n = bs.shape[3:]
    hg = _heads(h, g)
    t = x.shape[1]
    a32, d32 = a.astype(jnp.float32), d.astype(jnp.float32)
    gy = jnp.pad(dy.astype(jnp.float32),
                 ((0, 0), (0, c * q - t), (0, 0), (0, 0))).reshape(xs.shape)
    u = xs * dts[..., None]
    total = acs[:, :, -1]

    # y = y_diag + exp(acs) C . S_in + D x
    d_d = jnp.sum(gy * xs, axis=(0, 1, 2, 4))
    into = jnp.exp(acs)                                      # [b, c, Q, H]
    s = entering.reshape(c, b, g, h // g, p, n)
    gz = (gy * into[..., None]).reshape(b, c, q, g, h // g, p)
    z = jnp.einsum("bctgn,cbgkpn->bctgkp", cs, s)
    d_acs = into * jnp.sum((gy.reshape(z.shape) * z), axis=-1).reshape(
        b, c, q, h)
    d_entering = jnp.einsum("bctgkp,bctgn->cbgkpn", gz, cs).reshape(
        c, b, h, p, n)
    d_c = jnp.einsum("bctgkp,cbgkpn->bctgn", gz, s)

    # the carry, backwards: the state leaving chunk c is the one entering
    # chunk c + 1 and, decayed, part of the one leaving it
    decay = jnp.exp(total).transpose(1, 0, 2)                # [c, b, H]

    def back(grad, inputs):
        d_in, decay_c, entering_c = inputs
        d_decay = jnp.sum(grad * entering_c, axis=(-2, -1))
        return d_in + decay_c[..., None, None] * grad, (grad, d_decay)

    _, (d_local, d_decay) = jax.lax.scan(
        back, jnp.zeros((b, h, p, n), jnp.float32),
        (d_entering, decay, entering), reverse=True)
    d_total = (d_decay * decay).transpose(1, 0, 2)           # [b, c, H]

    def one(k, bufs):
        du_buf, dacs_buf, dtot_buf, db_buf, dc_buf = bufs
        lo = k * hg
        grp = _group_of(k, hg, h, g)
        cut = lambda v, axis: jax.lax.dynamic_slice_in_dim(v, lo, hg, axis)
        u_k, gy_k, acs_k, tot_k = (cut(u, 3), cut(gy, 3), cut(acs, 3),
                                   cut(total, 2))
        dl_k = cut(d_local, 2)                               # [c, b, hg, P, N]
        b_g = jax.lax.dynamic_index_in_dim(bs, grp, axis=3, keepdims=False)
        c_g = jax.lax.dynamic_index_in_dim(cs, grp, axis=3, keepdims=False)
        decay_k, cb = _blocks(acs_k, b_g, c_g)
        w = decay_k * cb[:, :, None]                         # [b, c, hg, t, s]
        # y_diag[t] = sum_s w[t, s] u[s]
        dw = jnp.einsum("bcthp,bcshp->bchts", gy_k, u_k)
        du = jnp.einsum("bchts,bcthp->bcshp", w, gy_k)
        d_cb = jnp.sum(dw * decay_k, axis=2)
        r = dw * w                                           # d decay * decay
        dacs = (jnp.sum(r, axis=-1) - jnp.sum(r, axis=-2)).transpose(
            0, 1, 3, 2)                                      # [b, c, Q, hg]
        dc = jnp.einsum("bcts,bcsn->bctn", d_cb, b_g)
        db = jnp.einsum("bcts,bctn->bcsn", d_cb, c_g)
        # local[c] = sum_s exp(total - acs_s) u_s B_s^T
        out = jnp.exp(tot_k[:, :, None] - acs_k)             # [b, c, Q, hg]
        v = jnp.einsum("cbhpn,bcsn->bcshp", dl_k, b_g)
        r_out = jnp.sum(v * u_k, axis=-1) * out
        du = du + out[..., None] * v
        db = db + jnp.einsum("bcsh,bcshp,cbhpn->bcsn", out, u_k, dl_k)
        dacs = dacs - r_out
        dtot = jnp.sum(r_out, axis=2)                        # [b, c, hg]
        add = lambda buf, part: jax.lax.dynamic_update_index_in_dim(
            buf, jax.lax.dynamic_index_in_dim(buf, grp, 3, False) + part,
            grp, axis=3)
        return (jax.lax.dynamic_update_slice_in_dim(du_buf, du, lo, axis=3),
                jax.lax.dynamic_update_slice_in_dim(dacs_buf, dacs, lo, 3),
                jax.lax.dynamic_update_slice_in_dim(dtot_buf, dtot, lo, 2),
                add(db_buf, db), add(dc_buf, dc))

    du, dacs_k, dtot_k, d_b, d_c_k = jax.lax.fori_loop(
        0, h // hg, one, (jnp.zeros_like(u), jnp.zeros_like(acs),
                          jnp.zeros_like(total), jnp.zeros_like(bs),
                          jnp.zeros_like(cs)))
    d_c = d_c + d_c_k
    d_acs = d_acs + dacs_k
    d_acs = d_acs.at[:, :, -1].add(d_total + dtot_k)
    # acs = cumsum(dt a) inside the chunk: a position's log-decay reaches
    # every later position of its chunk
    d_log = jnp.flip(jnp.cumsum(jnp.flip(d_acs, 2), axis=2), 2)
    d_dt = d_log * a32 + jnp.sum(du * xs, axis=-1)
    d_a = jnp.sum(d_log * dts, axis=(0, 1, 2))
    d_x = du * dts[..., None] + d32[:, None] * gy
    back_t = lambda v: v.reshape((b, c * q) + v.shape[3:])[:, :t]
    return (back_t(d_x).astype(x.dtype), back_t(d_dt).astype(dt.dtype),
            d_a.astype(a.dtype), back_t(d_b).astype(bb.dtype),
            back_t(d_c).astype(cc.dtype), d_d.astype(d.dtype))


ssd.defvjp(_fwd, _bwd)
