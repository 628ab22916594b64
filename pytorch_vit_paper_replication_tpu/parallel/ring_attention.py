"""Ring attention — sequence/context parallelism over the token axis.

For sequences too long for one chip's HBM, Q/K/V are sharded over the 'seq'
mesh axis. Each device computes attention of its local queries against the
K/V block it currently holds, then rotates K/V one step around the ring with
``jax.lax.ppermute`` (XLA lowers this to neighbor ICI transfers that overlap
with the next block's compute). Softmax is accumulated online — the same
(m, l, acc) recurrence as the Pallas flash kernel — so the result is exact,
not an approximation.

The reference has no long-context story at all (fixed 197-token sequences,
SURVEY.md §5); this module is what makes long-context a first-class
capability of the TPU build. Three ways in: (1) training — build the step
via ``parallel.api.make_parallel_train_step`` on a mesh whose 'seq' axis is
>1 and every model attention call routes here automatically
(``ops.partition.on_mesh``); (2) :func:`make_ring_attention` for
a standalone global-array op; (3) :func:`ring_self_attention` inside your
own ``shard_map``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..ops.dropout import derive_positional_seed, positional_keep_u8

_NEG_INF = float(-1e30)


def _block_update(q, k, v, m, l, acc, scale, keep=None):
    """One online-softmax accumulation step against a K/V block.

    q: [B, Tq, H, Dh]; k/v: [B, Tk, H, Dh]; m/l: [B, H, Tq, 1];
    acc: [B, Tq, H, Dh] (f32); keep: optional [B, H, Tq, Tk] dropout keep
    mask — applied to the value accumulation only (dropout acts on the
    normalized softmax weights, so the normalizer ``l`` sums UNDROPPED
    probabilities; the survivor rescale happens once at the end).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)                         # [B, H, Tq, Tk]
    correction = jnp.exp(m - m_new)                # [B, H, Tq, 1]
    l_new = l * correction + jnp.sum(p, axis=-1, keepdims=True)
    if keep is not None:
        p = jnp.where(keep, p, 0.0)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * jnp.moveaxis(correction, 1, 2) + pv
    return m_new, l_new, acc_new


def ring_self_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        axis_name: str = "seq", *,
                        dropout_threshold: int = 0,
                        dropout_seed: Optional[jax.Array] = None,
                        data_axis: Optional[str] = None,
                        head_axis: Optional[str] = None) -> jax.Array:
    """Exact self-attention with K/V rotating around the `axis_name` ring.

    Args:
      q, k, v: the **local token shard** ``[B, T_local, H, Dh]``. Must be
        called inside ``shard_map``/``pmap`` with ``axis_name`` bound.
      dropout_threshold: uint8 threshold (``ops.dropout._threshold``) for
        attention-weight dropout; 0 disables. The keep/drop bit of every
        (example, head, query, key) element is a positional hash
        (``ops.dropout.avalanche_u32``) of its GLOBAL coordinates — the
        same scheme as the flash kernel — so the mask is identical
        whichever ring step (or mesh layout) visits the element, and the
        backward pass through this very code regenerates it for free.
      dropout_seed: int32 ``[1]`` seed (required when threshold > 0).
      data_axis / head_axis: mesh axes the batch / heads are sharded over
        (when bound) — used to derive global batch·head indices so
        dropout masks differ across shards.

    Returns:
      Local attention output ``[B, T_local, H, Dh]`` — the same values full
      attention over the gathered sequence would produce for these queries
      (with dropout: the same masked-softmax values, exactly unbiased via
      the quantized-keep rescale).
    """
    axis_size = jax.lax.axis_size(axis_name)
    scale = q.shape[-1] ** -0.5
    b, t, h, d = q.shape
    qf = q.astype(jnp.float32)

    if dropout_threshold:
        if dropout_seed is None:
            raise ValueError("ring attention dropout needs dropout_seed")
        seq_idx = jax.lax.axis_index(axis_name)
        b_off = (jax.lax.axis_index(data_axis) * b
                 if data_axis is not None else 0)
        h_off = (jax.lax.axis_index(head_axis) * h
                 if head_axis is not None else 0)
        h_total = h * (jax.lax.axis_size(head_axis)
                       if head_axis is not None else 1)
        bh_ids = ((b_off + jnp.arange(b))[:, None] * h_total
                  + (h_off + jnp.arange(h))[None, :])        # [B, H]
        row_ids = seq_idx * t + jnp.arange(t)                # global rows

        def keep_mask(r):
            # Ring step r holds the K/V block that started on device
            # (seq_idx - r) mod n -> its global column offset.
            col0 = ((seq_idx - r) % axis_size) * t
            return positional_keep_u8(
                dropout_seed[0], bh_ids[:, :, None, None],
                row_ids[None, None, :, None],
                (col0 + jnp.arange(t))[None, None, None, :],
                dropout_threshold)
    else:
        keep_mask = None

    m0 = jnp.full((b, h, t, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, t, 1), jnp.float32)
    acc0 = jnp.zeros((b, t, h, d), jnp.float32)

    def body(carry, r):
        m, l, acc, k_cur, v_cur = carry
        keep = keep_mask(r) if keep_mask is not None else None
        m, l, acc = _block_update(qf, k_cur.astype(jnp.float32),
                                  v_cur.astype(jnp.float32), m, l, acc,
                                  scale, keep=keep)
        # Rotate K/V to the next device; the last rotation is wasted but
        # keeps the loop shape static (XLA overlaps it with the epilogue).
        perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (m, l, acc, k_nxt, v_nxt), None

    (m, l, acc, _, _), _ = jax.lax.scan(
        body, (m0, l0, acc0, k, v), jnp.arange(axis_size))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    keep_prob = 1.0 - dropout_threshold / 256.0
    out = acc / (jnp.moveaxis(l_safe, 1, 2) * keep_prob)
    return out.astype(q.dtype)


def make_sp_attention(self_attention_fn, mesh, axis_name: str = "seq", *,
                      data_axis: str = "data",
                      head_axis: Optional[str] = None,
                      dropout_rate: float = 0.0,
                      dropout_rng: Optional[jax.Array] = None,
                      deterministic: bool = True):
    """Shared shard_map factory for sequence-parallel self-attention
    (ring and Ulysses): one place for the dropout-threshold derivation,
    the axis mesh-membership filters, the sharding specs, and the
    dropout-seed closure — so the two strategies cannot drift apart.

    ``self_attention_fn`` is the inside-shard_map attention
    (:func:`ring_self_attention` or
    :func:`.ulysses.ulysses_self_attention`); both share the same
    keyword contract.
    """
    from jax.sharding import PartitionSpec as P

    threshold = 0
    if not deterministic and dropout_rate > 0.0:
        from ..ops.dropout import _threshold

        threshold = _threshold(dropout_rate)
    # Same mesh-membership filter data_axis gets below: a head_axis absent
    # from the mesh should mean "no head sharding", not an opaque
    # axis-name error inside shard_map (ADVICE r3).
    if head_axis is not None and head_axis not in mesh.axis_names:
        head_axis = None
    spec = P(data_axis, axis_name, head_axis, None)
    inner = functools.partial(
        self_attention_fn, axis_name=axis_name,
        dropout_threshold=threshold,
        data_axis=data_axis if data_axis in mesh.axis_names else None,
        head_axis=head_axis)
    if not threshold:
        return jax.shard_map(inner, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)
    if dropout_rng is None:
        raise ValueError("sequence-parallel attention dropout needs "
                         "dropout_rng")
    seed = derive_positional_seed(dropout_rng)
    fn = jax.shard_map(
        lambda q, k, v, s: inner(q, k, v, dropout_seed=s),
        mesh=mesh, in_specs=(spec, spec, spec, P(None)), out_specs=spec,
        check_vma=False)
    return lambda q, k, v: fn(q, k, v, seed)


def make_ring_attention(mesh, axis_name: str = "seq", **kw):
    """Wrap :func:`ring_self_attention` in a ``shard_map`` over `mesh`.

    Returns a function of global ``[B, T, H, Dh]`` arrays with the token
    axis sharded over `axis_name`, batch over ``data_axis``, and (when
    ``head_axis`` is given — tensor parallelism) heads over that axis.
    ``dropout_rate``/``dropout_rng``/``deterministic`` follow the
    :func:`..ops.attention.dot_product_attention` contract (attention-
    weight dropout, in-ring, O(T_local²) extra memory only per block).
    """
    return make_sp_attention(ring_self_attention, mesh, axis_name, **kw)
