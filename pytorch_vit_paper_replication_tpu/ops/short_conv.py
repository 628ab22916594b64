"""The gated short convolution of an LFM2 mixer, as one function.

A conv layer's mixer (``models/vit.py::ShortConvBlock``) projects its
normed input to ``[B | C | u]`` (three column blocks of width D) and
takes, for every channel and every position t of a sequence,

    c_t = w_0 * v_{t-K+1} + ... + w_{K-1} * v_t,   v = B * u,
    y_t = C_t * c_t,

a depthwise causal convolution over the last K positions (``v`` is 0
before a sequence's first position). That is torch's ``Conv1d(groups=D,
padding=K-1)`` sliced to the first T outputs, ``weight[:, 0, k] =
taps[k]``.

:func:`short_conv` is the gate-conv-gate from the projection's output to
what the out projection reads: plain XLA, the K taps as products of
``v`` shifted along the time axis of ``[batch, T, D]`` (so that no tap
reads across the sequences of a batch), arithmetic in float32, the
result in the input's dtype. Its gradient is written out
(``jax.custom_vjp``): the backward pass reads the projection's output
and the result's cotangent and writes the projection's cotangent and the
taps', taking ``v`` and the convolution again, so that nothing but the
projection's output is kept. A kernel that does the same in one pass
over HBM replaces it at this one call site.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _shift(x: jax.Array, by: int) -> jax.Array:
    """``x [batch, T, D]`` moved ``by`` positions later along T (``by``
    < 0: earlier), zeros where nothing moved in."""
    if by == 0:
        return x
    t = x.shape[1]
    if by > 0:
        return jnp.pad(x[:, :t - by], ((0, 0), (by, 0), (0, 0)))
    return jnp.pad(x[:, -by:], ((0, 0), (0, -by), (0, 0)))


def _parts(bcu: jax.Array):
    d = bcu.shape[-1] // 3
    f32 = lambda a: a.astype(jnp.float32)
    return f32(bcu[..., :d]), f32(bcu[..., d:2 * d]), f32(bcu[..., 2 * d:])


def depthwise_causal_conv(v: jax.Array, taps: jax.Array) -> jax.Array:
    """``sum_k taps[k] * v`` moved ``K - 1 - k`` positions later, for
    ``v [batch, T, C]`` and ``taps [K, C]``: each channel convolved over
    its own last K positions, 0 before a sequence's first."""
    k = taps.shape[0]
    return sum(taps[i] * _shift(v, k - 1 - i) for i in range(k))


@jax.custom_vjp
def short_conv(bcu: jax.Array, taps: jax.Array) -> jax.Array:
    """``C * conv(B * u)`` of ``bcu [batch, T, 3D]`` (``[B | C | u]``)
    with ``taps [K, D]`` (float32): ``[batch, T, D]`` in ``bcu``'s
    dtype (module docstring)."""
    b, c, u = _parts(bcu)
    return (c * depthwise_causal_conv(
        b * u, taps.astype(jnp.float32))).astype(bcu.dtype)


def _fwd(bcu, taps):
    return short_conv(bcu, taps), (bcu, taps)


def _bwd(res, dy):
    bcu, taps = res
    b, c, u = _parts(bcu)
    w = taps.astype(jnp.float32)
    k = w.shape[0]
    v = b * u
    g = dy.astype(jnp.float32)
    d_conv = g * c
    # the adjoint of a shift later is the same shift earlier
    dv = sum(w[i] * _shift(d_conv, -(k - 1 - i)) for i in range(k))
    d_taps = jnp.stack([jnp.sum(d_conv * _shift(v, k - 1 - i), axis=(0, 1))
                        for i in range(k)])
    d_bcu = jnp.concatenate(
        [dv * u, g * depthwise_causal_conv(v, w), dv * b], axis=-1)
    return d_bcu.astype(bcu.dtype), d_taps.astype(taps.dtype)


short_conv.defvjp(_fwd, _bwd)
