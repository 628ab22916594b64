"""Short-sequence self-attention on the packed qkv projection (Pallas).

The flash kernel (:mod:`.flash_attention`) is built for sequences whose
``[T, T]`` logits cannot exist anywhere: one (image, head) a grid step,
an online softmax over K blocks, operands folded to ``[B*H, T, Dh]`` and
padded in HBM. At ViT's pre-training length (T = 197) none of that is
needed and all of it costs: the whole ``[T, T]`` tile of a head is 256 KB
of VMEM. This pair of kernels is written for that case.

* **Operands where they lie.** Input is the qkv projection's output
  ``[B, T, 3, H, Dh]`` seen as ``[B, T, 3*D]`` (a free view): three
  ``BlockSpec`` s on the one array pick the 128-lane slab of q, of k and
  of v that holds ``128 // Dh`` heads (a pair at Dh = 64). Output is
  ``[B, T, D]``, what the out-projection contracts over. The backward
  writes ONE packed ``dqkv [B, T, 3*D]``, the cotangent the projection's
  backward GEMMs want. Nothing is sliced, transposed or padded in HBM:
  the token dimension is one overhanging block (T = 197 in a block of
  256), and rows past T are zeroed in VMEM.
* **``[T, T]`` never in HBM.** One grid step holds a few images x one
  slab of heads; logits, exponentials and probabilities of a head live
  in VMEM. One K block, one pass, an exact max-subtracted softmax. The
  backward rebuilds the logits from q and k and saves only the f32
  log-sum-exp ``[B, H, T]``.
* **The transposed domain.** Logits are computed as ``k q^T``
  (``[Tk, Tq]``): keys on sublanes, queries on lanes. Row statistics
  (max, sum, the backward's ``delta``) are then reductions over
  sublanes - elementwise VPU work, not cross-lane shuffles - and lie as
  lane-dense ``[1, Tq]`` rows, which is how ``[B, H, T]`` stores them
  and how they broadcast back. ``p^T do`` and ``ds^T q`` (dv, dk) are
  plain matmuls; ``o`` and ``dq`` come out transposed (``[Dh, Tq]``)
  and one ``[128, Tp]`` transpose per slab puts them back.
* **bf16 into the MXU, f32 out of it**: every ``dot`` takes operands in
  the compute dtype with ``preferred_element_type=float32``; the softmax
  is f32.

Selected by :func:`.attention.choose` for :func:`.attention.self_attention`
(``attention_impl="auto"`` on a TPU: no mask, no active attention
dropout, Dh 64 or 128, and :func:`plan` finding the working set inside
the VMEM budget). Under a mesh the call runs per shard inside
``shard_map`` (batch over the data axis, heads over the model axis), as
the MLP kernels do (:mod:`.partition`).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import partition

LANES = 128
HEAD_DIMS = (64, 128)
# What a grid step may hold: the v5e compiler's default scoped-VMEM limit
# (of 128 MiB a core). The kernels are given exactly this limit, and
# :func:`plan` keeps the working set inside it.
VMEM_BUDGET = 16 * 1024 * 1024
# Images a grid step at most, and how many of them the body of the loop
# over them holds (:func:`_for_each_image`). Measured on the v5e at
# B/16's shape, forward + backward a layer: 8 images rolled 3.59 ms, 2 a
# body 3.36, 4 a body 3.15, all 8 3.10; 16 images, 4 a body, 3.06
# (PERF.md, PR 26).
MAX_IMAGES = 16
UNROLL = 4
_NEG = -1e30
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _padded(tokens: int, itemsize: int):
    """``(Tp, Tk)``: tokens as lanes (queries; whole 128-lane tiles) and
    as sublanes (keys; whole packed sublane tiles of the compute dtype:
    16 rows of bf16, 8 of f32)."""
    return _round_up(tokens, LANES), _round_up(tokens, 32 // itemsize)


def plan(batch: int, tokens: int, head_dim: int,
         itemsize: int) -> Optional[int]:
    """Images per grid step for these (per-shard) shapes, or None when
    not even one image's working set fits :data:`VMEM_BUDGET`.

    Counted for the backward kernel, the larger of the two: five slabs
    (q, k, v, do in; a third of dqkv out) double-buffered by the
    pipeline and two more as scratch (dk, dv), the log-sum-exp rows,
    and per head in flight four f32 ``[Tk, Tp]`` temporaries (logits,
    probabilities, dp, ds) and two in the compute dtype (p and ds as
    MXU operands). At T = 197, Dh = 64 in bf16 that is 2.1 MiB of
    temporaries + 0.8 MiB an image; T = 577 (17 MiB) does not fit."""
    heads = LANES // head_dim
    tp, tk = _padded(tokens, itemsize)
    temporaries = heads * tk * tp * (4 * 4 + 2 * itemsize)
    per_image = 12 * tp * LANES * itemsize + 2 * heads * tp * 4
    images = (VMEM_BUDGET - temporaries) // per_image
    if images < 1:
        return None
    images = int(min(images, MAX_IMAGES, batch))
    # whole bodies of the image loop, where there is more than one
    return images - images % UNROLL if images > UNROLL else images


def _masks(tp: int, tk: int, tokens: int, head_dim: int):
    """Loop-invariant masks: rows of a ``[Tp, 128]`` slab that are
    tokens, the head each lane of a ``[Tk, 128]`` slab belongs to, and
    the key rows of a ``[Tk, Tp]`` tile that are tokens."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (tp, LANES), 0) < tokens
    # (a shift, not //: head sizes are powers of two, and floor division
    # lowers through sign(), a sixth of the kernels' lowering time)
    head = jax.lax.shift_right_logical(
        jax.lax.broadcasted_iota(jnp.int32, (tk, LANES), 1),
        head_dim.bit_length() - 1)
    keys = jax.lax.broadcasted_iota(jnp.int32, (tk, tp), 0) < tokens
    return rows, head, keys


def _scale(head_dim: int):
    """``(1/sqrt(Dh), fold)``: the scale is folded into q in the compute
    dtype when that is exact (a power of two: 1/8 at Dh = 64), which
    saves one pass over the ``[Tk, Tp]`` logits; else the logits are
    scaled in f32."""
    scale = head_dim ** -0.5
    return scale, math.log2(scale).is_integer()


def _scaled(q2, scale: float, fold: bool):
    if not fold:
        return q2
    return (q2.astype(jnp.float32) * scale).astype(q2.dtype)


def _pad_keys(x, tp: int):
    """``[Tk, Tp]`` -> ``[Tp, Tp]`` with zero rows: a contraction over
    keys wants whole 128-deep tiles."""
    tk = x.shape[0]
    if tk == tp:
        return x
    return jnp.concatenate([x, jnp.zeros((tp - tk, tp), x.dtype)], axis=0)


def _for_each_image(images: int, image) -> None:
    """``image(i)`` for the images of a block: a loop whose body holds
    :data:`UNROLL` of them (the scheduler overlaps one image's matmuls
    with the next one's softmax), not all: the body is emitted once per
    image it holds, in each of a step's 24 or 48 kernels."""
    unroll = UNROLL if images % UNROLL == 0 else 1

    def body(g, carry):
        for u in range(unroll):
            image(g * unroll + u)
        return carry

    jax.lax.fori_loop(0, images // unroll, body, 0)


def _per_head_nt(x, y, head, heads: int):
    """``x_a @ y.T`` for every head ``a`` of the slab, stacked on rows:
    ``[heads*Tk, Tp]`` from ``x [Tk, 128]`` and ``y [Tp, 128]``, where
    ``x_a`` is ``x`` with the other heads' lanes zeroed (a 128-deep
    contraction half zero costs this MXU what a 64-deep one costs). One
    matmul for the slab: ``y`` is loaded into the MXU once, not once a
    head (measured 8-20% of the kernels' time)."""
    if heads == 1:
        stacked = x
    else:
        stacked = jnp.concatenate(
            [jnp.where(head == a, x, 0) for a in range(heads)], axis=0)
    return jax.lax.dot_general(stacked, y, _NT,
                               preferred_element_type=jnp.float32)


def _own_lanes(stacked, head, heads: int):
    """``[heads*Tk, 128]`` (head ``a``'s product in rows ``a*Tk`` on,
    right in its own lanes only) -> ``[Tk, 128]`` with every head's
    lanes from its own rows."""
    tk = stacked.shape[0] // heads
    out = stacked[:tk]
    for a in range(1, heads):
        out = jnp.where(head == a, stacked[a * tk:(a + 1) * tk], out)
    return out


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, tokens, head_dim,
                tk):
    images, tp, _ = q_ref.shape
    heads = LANES // head_dim
    scale, fold = _scale(head_dim)
    rows, head, keys = _masks(tp, tk, tokens, head_dim)

    def image(i):
        # The overhang of the token block is whatever the buffer held:
        # zero it (0 x garbage may be NaN).
        q2 = _scaled(jnp.where(rows, q_ref[i], 0), scale, fold)
        k2 = jnp.where(rows, k_ref[i], 0)[:tk]
        v2t = jnp.where(rows, v_ref[i], 0).T                # [128, Tp]
        st_all = _per_head_nt(k2, q2, head, heads)          # [heads*Tk, Tp]
        outs = []
        for a in range(heads):
            st = st_all[a * tk:(a + 1) * tk]
            if not fold:
                st = st * scale
            st = jnp.where(keys, st, _NEG)                  # [Tk, Tp]
            m = jnp.max(st, axis=0, keepdims=True)          # [1, Tp]
            e = jnp.exp(st - m)
            l = jnp.sum(e, axis=0, keepdims=True)
            ot = jnp.dot(v2t[a * head_dim:(a + 1) * head_dim],
                         _pad_keys(e.astype(v2t.dtype), tp),
                         preferred_element_type=jnp.float32)  # [Dh, Tp]
            outs.append(ot * (1.0 / l))
            lse_ref[i, a:a + 1, :] = m + jnp.log(l)
        ot = jnp.concatenate(outs, axis=0) if heads > 1 else outs[0]
        o_ref[i] = ot.T.astype(o_ref.dtype)

    _for_each_image(images, image)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dqkv_ref, dk_ref,
                dv_ref, *, tokens, head_dim, tk):
    """Grid ``(image block, head slab, 3)``: the last axis walks the
    q, k and v thirds of the packed cotangent. Step 0 computes all three
    (dq to the output block, dk and dv to scratch); steps 1 and 2 copy
    dk and dv out - their input blocks are step 0's, so nothing is
    fetched again."""
    images, tp, _ = q_ref.shape
    heads = LANES // head_dim
    scale, fold = _scale(head_dim)
    third = pl.program_id(2)

    @pl.when(third == 0)
    def _():
        rows, head, keys = _masks(tp, tk, tokens, head_dim)
        queries = jax.lax.broadcasted_iota(jnp.int32, (1, tp), 1) < tokens

        def image(i):
            q2 = _scaled(jnp.where(rows, q_ref[i], 0), scale, fold)
            k2 = jnp.where(rows, k_ref[i], 0)
            v2 = jnp.where(rows, v_ref[i], 0)[:tk]
            do2 = jnp.where(rows, do_ref[i], 0)
            k2t = k2.T                                      # [128, Tp]
            st_all = _per_head_nt(k2[:tk], q2, head, heads)
            dp_all = _per_head_nt(v2, do2, head, heads)
            ps, dss, dqs = [], [], []
            for a in range(heads):
                st = st_all[a * tk:(a + 1) * tk]
                if not fold:
                    st = st * scale
                st = jnp.where(keys, st, _NEG)
                lse = jnp.where(queries, lse_ref[i, a:a + 1, :], 0.0)
                p = jnp.exp(st - lse)                       # [Tk, Tp]
                dp = dp_all[a * tk:(a + 1) * tk]
                # delta = rowsum(do * o) = sum over keys of p * dp
                delta = jnp.sum(p * dp, axis=0, keepdims=True)
                ds = (p * (dp - delta)).astype(q2.dtype)
                ps.append(p.astype(q2.dtype))
                dss.append(ds)
                dqs.append(jnp.dot(
                    k2t[a * head_dim:(a + 1) * head_dim],
                    _pad_keys(ds, tp), preferred_element_type=jnp.float32))
            dv = _own_lanes(jnp.dot(jnp.concatenate(ps, axis=0), do2,
                                    preferred_element_type=jnp.float32),
                            head, heads)
            dk = _own_lanes(jnp.dot(jnp.concatenate(dss, axis=0), q2,
                                    preferred_element_type=jnp.float32),
                            head, heads)
            if not fold:
                dk = dk * scale
            dqt = jnp.concatenate(dqs, axis=0) if heads > 1 else dqs[0]
            dqkv_ref[i] = (dqt * scale).T.astype(dqkv_ref.dtype)
            # Rows past Tk stay what they were: they are past T too, and
            # the write-back drops them.
            dk_ref[i, :tk] = dk.astype(dk_ref.dtype)
            dv_ref[i, :tk] = dv.astype(dv_ref.dtype)

        _for_each_image(images, image)

    @pl.when(third == 1)
    def _():
        dqkv_ref[...] = dk_ref[...]

    @pl.when(third == 2)
    def _():
        dqkv_ref[...] = dv_ref[...]


def _compiler_params(interpret, semantics):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=VMEM_BUDGET)


def _geometry(qkv, head_dim):
    b, t, d3 = qkv.shape
    slabs = d3 // 3 // LANES
    tp, tk = _padded(t, jnp.dtype(qkv.dtype).itemsize)
    return b, t, d3 // 3, slabs, LANES // head_dim, tp, tk


def _slab(images: int, tp: int, first):
    """Images ``n*images`` on, every token (one overhanging block), the
    128 lanes of slab ``first + j`` of a packed array; ``first`` may be
    a function of the grid indices past ``(n, j)``."""
    offset = first if callable(first) else lambda *_: first
    return pl.BlockSpec((images, tp, LANES),
                        lambda n, j, *rest: (n, 0, offset(*rest) + j))


def _stat_rows(images: int, heads: int, tp: int):
    """The slab's rows of ``lse [B, D/128, 128/Dh, T]``."""
    return pl.BlockSpec((images, None, heads, tp),
                        lambda n, j, *_: (n, j, 0, 0))


# jit(inline=True) on both calls: the kernel body is traced once per
# shape and dtype in a process, not once per layer and program (a B/16
# step calls the pair 12 times, its forward twice each), and every call
# site still gets a ``pallas_call`` of its own, under its own scope.
@functools.partial(jax.jit, static_argnums=(1, 2, 3), inline=True)
def _fwd_call(qkv, head_dim, images, interpret):
    """``[B, T, 3*D]`` -> ``(o [B, T, D], lse [B, D/128, 128/Dh, T])``."""
    b, t, d, slabs, heads, tp, tk = _geometry(qkv, head_dim)
    slab = functools.partial(_slab, images, tp)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tokens=t, head_dim=head_dim, tk=tk),
        name="attn_short_fwd",
        grid=(pl.cdiv(b, images), slabs),
        in_specs=[slab(0), slab(slabs), slab(2 * slabs)],
        out_specs=[slab(0), _stat_rows(images, heads, tp)],
        out_shape=[jax.ShapeDtypeStruct((b, t, d), qkv.dtype),
                   jax.ShapeDtypeStruct((b, slabs, heads, t), jnp.float32)],
        compiler_params=_compiler_params(interpret,
                                         ("parallel", "parallel")),
        interpret=interpret,
    )(qkv, qkv, qkv)


@functools.partial(jax.jit, static_argnums=(3, 4, 5), inline=True)
def _bwd_call(qkv, lse, do, head_dim, images, interpret):
    """-> the packed ``dqkv [B, T, 3*D]``."""
    b, t, _, slabs, heads, tp, tk = _geometry(qkv, head_dim)
    slab = functools.partial(_slab, images, tp)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, tokens=t, head_dim=head_dim, tk=tk),
        name="attn_short_bwd",
        grid=(pl.cdiv(b, images), slabs, 3),
        in_specs=[slab(0), slab(slabs), slab(2 * slabs), slab(0),
                  _stat_rows(images, heads, tp)],
        out_specs=slab(lambda third: third * slabs),
        out_shape=jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
        scratch_shapes=[pltpu.VMEM((images, tp, LANES), qkv.dtype)] * 2,
        compiler_params=_compiler_params(
            interpret, ("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(qkv, qkv, qkv, do, lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _attend(qkv, head_dim, images, interpret):
    return _fwd_call(qkv, head_dim, images, interpret)[0]


def _attend_fwd(qkv, head_dim, images, interpret):
    o, lse = _fwd_call(qkv, head_dim, images, interpret)
    return o, (qkv, lse)


def _attend_bwd(head_dim, images, interpret, res, do):
    qkv, lse = res
    return (_bwd_call(qkv, lse, do, head_dim, images, interpret),)


_attend.defvjp(_attend_fwd, _attend_bwd)


def _shards(part, batch: int, heads: int):
    """``(data axis, model axis)`` as a ``PartitionSpec`` wants them
    (None where the mesh does not split), or None when the mesh's axes
    do not divide the batch and the heads."""
    data, model = part.axis(part.data_axis), part.axis(part.model_axis)
    if batch % part.size(data) or heads % part.size(model):
        return None
    return data, model


def supported(qkv_shape, dtype) -> bool:
    """Whether the kernels serve a packed projection of this shape
    ``[B, T, 3, H, Dh]`` and dtype on the active mesh: a head size they
    are written for, whole 128-lane slabs of heads per shard, and a
    working set inside the VMEM budget."""
    b, t, three, h, dh = qkv_shape
    if three != 3 or dh not in HEAD_DIMS:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.bfloat16),
                                jnp.dtype(jnp.float32)):
        return False
    part = partition.current()
    if part is not None:
        shards = _shards(part, b, h)
        if shards is None:
            return False
        b //= part.size(shards[0])
        h //= part.size(shards[1])
    if (h * dh) % LANES:
        return False
    return plan(b, t, dh, jnp.dtype(dtype).itemsize) is not None


def short_attention(qkv: jax.Array, *,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Exact softmax self-attention ``[B, T, 3, H, Dh]`` (the packed qkv
    projection) -> ``[B, T, H, Dh]``; no mask, no dropout.

    The caller checks :func:`supported` first. ``interpret``: run the
    Pallas interpreter instead of Mosaic (default: off the TPU).

    Traced under a mesh (:func:`.partition.on_mesh`) the call runs per
    shard: batch over the data axis, heads over the model axis, each
    shard over the full sequence (XLA refuses to partition a Mosaic
    call).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    b, t, _, h, dh = qkv.shape
    itemsize = jnp.dtype(qkv.dtype).itemsize

    def local(qkv):
        """Packed ``[b, T, 3*d]`` -> ``[b, T, d]``."""
        images = plan(qkv.shape[0], t, dh, itemsize)
        return _attend(qkv, dh, images, interpret)

    part = partition.current()
    data, model = (None, None) if part is None else _shards(part, b, h)
    if model is not None:
        # Heads split over the model axis: only the 5-D projection can
        # say so.
        return part.shard_map(
            lambda qkv: local(qkv.reshape(qkv.shape[:2] + (-1,))).reshape(
                qkv.shape[:2] + qkv.shape[3:]),
            in_specs=(P(data, None, None, model, None),),
            out_specs=P(data, None, model, None))(qkv)
    if part is not None:
        # The packed view crosses the shard_map boundary, not the 5-D
        # one: the compiler lays out what it sees there, and a minor
        # dimension of Dh = 64 costs a transposing copy on each side.
        local = part.shard_map(local, in_specs=(P(data, None, None),),
                               out_specs=P(data, None, None))
    return local(qkv.reshape(b, t, 3 * h * dh)).reshape(b, t, h, dh)
