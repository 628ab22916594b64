"""Next-token cross entropy through an untied head, without the logits.

A language model's head over ``N`` positions and ``V`` vocabulary rows
makes ``[N, V]`` logits: 2.5 GB in float32 at 16,384 x 37,984, once for
the loss and once more for its gradient. :func:`head_cross_entropy`
walks the positions in chunks instead. Each chunk's float32 logits live
only while its loss, its share of ``d loss / d hidden`` and its share of
``d loss / d W`` are taken — in the forward pass, since the loss is a
scalar and its gradients are known as soon as the logits are. The
backward pass only scales them by the incoming cotangent. Nothing is
computed twice and no ``[N, V]`` array is kept: what is saved is the
hidden gradient ``[N, D]`` and the weight gradient ``[D, V]`` that the
step needs anyway.

The matrix products take their operands in the hidden states' dtype
(bfloat16) and accumulate in float32; the log-sum-exp and the loss are
float32. Scopes: ``head`` around the products, ``loss`` around the rest.

A position can be left out (``counted``): it adds nothing to the loss,
to the count or to either gradient, and the mean is over the positions
that count. Two calls on one head matrix (a model with a second
objective through the same head) give it the sum of both gradients, as
any function of a shared argument does; so does a head tied to the
token embedding (:func:`tied_head_cross_entropy`, which reads the
embedding's ``[V, D]`` table as it lies), whose table gets the lookup's
gradient and the head's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

CHUNK = 2048     # positions whose float32 logits are alive at a time


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def head_cross_entropy(hidden, kernel, labels, chunk: int = CHUNK,
                       counted=None):
    """``(mean cross entropy, count of positions whose largest logit is
    the label)`` of ``hidden [N, D] @ kernel [D, V]`` against ``labels
    [N]``, over the positions where ``counted [N]`` (bool) holds: all of
    them where it is None. Differentiable in ``hidden`` and ``kernel``
    (the count is not)."""
    return _forward(hidden, kernel, labels, chunk, counted)[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def tied_head_cross_entropy(hidden, table, labels, chunk: int = CHUNK,
                            counted=None):
    """:func:`head_cross_entropy` through a head tied to the token
    embedding: ``table [V, D]`` as the embedding holds it (logits
    ``hidden @ table^T``, the gradient ``[V, D]`` as well): no transposed
    copy of it is made."""
    return _forward(hidden, table, labels, chunk, counted, rows=True)[0]


def _forward(hidden, kernel, labels, chunk, counted, rows=False):
    # ``keep`` weights a chunk's terms where positions are left out; the
    # mean is then over the positions that count. ``rows``: ``kernel`` is
    # a ``[V, D]`` table, not a ``[D, V]`` matrix.
    n = hidden.shape[0] if counted is None else jnp.maximum(
        jnp.sum(counted.astype(jnp.float32)), 1.0)
    w = kernel.astype(hidden.dtype)
    by = lambda a, b, lhs, rhs: jax.lax.dot_general(
        a, b, (((lhs,), (rhs,)), ((), ())),
        preferred_element_type=jnp.float32)
    loss_sum = jnp.zeros((), jnp.float32)
    correct = jnp.zeros((), jnp.float32)
    d_kernel = jnp.zeros(kernel.shape, jnp.float32)
    d_hidden = []
    for lo in range(0, hidden.shape[0], chunk):
        h, y = hidden[lo:lo + chunk], labels[lo:lo + chunk]
        keep = None if counted is None else \
            counted[lo:lo + chunk].astype(jnp.float32)
        kept = lambda terms: terms if keep is None else keep * terms
        with jax.named_scope("head"):
            logits = by(h, w, 1, 1) if rows else jnp.dot(
                h, w, preferred_element_type=jnp.float32)
        with jax.named_scope("loss"):
            top = jnp.max(logits, axis=-1, keepdims=True)
            e = jnp.exp(logits - top)
            z = jnp.sum(e, axis=-1, keepdims=True)
            hit = jax.lax.broadcasted_iota(
                jnp.int32, logits.shape, 1) == y[:, None]
            picked = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
            loss_sum += jnp.sum(kept(top[:, 0] + jnp.log(z[:, 0]) - picked))
            correct += jnp.sum(kept(
                jnp.where(hit, logits, -jnp.inf).max(-1) >= top[:, 0]))
            d_logits = e / z - hit
            d_logits = (d_logits / n if keep is None
                        else d_logits * (keep / n)[:, None]
                        ).astype(hidden.dtype)
        with jax.named_scope("head"):
            d_hidden.append(by(d_logits, w, 1, 0 if rows else 1).astype(
                hidden.dtype))
            d_kernel += by(d_logits, h, 0, 0) if rows else by(h, d_logits,
                                                               0, 0)
    return (loss_sum / n, correct), (jnp.concatenate(d_hidden), d_kernel)


def _fwd(hidden, kernel, labels, chunk, counted):
    return _forward(hidden, kernel, labels, chunk, counted)


def _fwd_rows(hidden, table, labels, chunk, counted):
    return _forward(hidden, table, labels, chunk, counted, rows=True)


def _bwd(chunk, res, g):
    d_hidden, d_kernel = res
    scale = g[0]
    return ((scale * d_hidden).astype(d_hidden.dtype), scale * d_kernel,
            None, None)


head_cross_entropy.defvjp(_fwd, _bwd)
tied_head_cross_entropy.defvjp(_fwd_rows, _bwd)
