"""Routed feed-forward: top-k routing over all experts, and the part of
the result that the experts held on this chip give.

The layer is told which experts it holds (``expert_offset`` and how many
the weights have). It routes over every expert, keeps the token-expert
pairs whose expert is held, sorts them by expert, runs the three grouped
matrix products of a ReLU-gated expert over the ragged groups, and sums
each token's rows weighted by its router probabilities. What an absent
expert would add is left out — that is the exchange-free share of an
expert-parallel layer, and nothing here stands in for the other chips.
There is no capacity and no dropped pair: the row buffer is sized for
the case that every pair falls to a held expert.

**Layout of the rows.** The pairs of expert *e* are laid from a row that
is a multiple of the row tile, and a group takes at least one tile
(an empty group one tile of zero rows, so that its weight gradient is
written). A tile of rows then belongs to one expert, and a grouped
product is a tiled matmul whose weight block is chosen per tile from a
prefetched table — no tile straddles two experts, no group is padded to
another's length. Tiles after the last group are skipped and write
zeros.

**Kernels** (``pallas_call(name=)``): ``moe_gmm_fwd`` (rows @ W_e: gate
and up as one product over ``[D, 2F]``, then down), ``moe_gmm_dx``
(rows @ W_e^T: the two input gradients) and ``moe_gmm_dw`` (rows^T @
rows into ``[E, K, N]`` float32: the two weight gradients). The backward pass
takes the first product again rather than keep its result. Off the TPU they run in the Pallas interpreter.
Traced under a mesh the whole layer runs per shard of the batch
(:mod:`.partition`), each shard routing its own tokens.

Scopes, for the device trace: ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from . import partition

ROW_TILE = 256          # rows of a tile; a group starts on a multiple
# The row buffer holds every pair of the tokens it serves (no capacity),
# four times what a step at 16 of 64 experts uses, and the backward pass
# has five arrays of its length alive at once. Tokens are therefore
# served in equal chunks of at most this many pairs, one after the other:
# at 16,384 tokens x 6 that is two chunks, and a gigabyte less at the
# step's peak, for one more tile of padding a group.
MAX_PAIRS = 49152
_DW_COLS = 256          # output columns of one moe_gmm_dw program
_VMEM_LIMIT = 96 * 1024 * 1024


def route(router_logits: jax.Array, k: int):
    """The ``k`` largest of each row's router logits and the softmax over
    those ``k`` (taken after the selection, so the weights of a token sum
    to 1): ``(ids [N, k] int32, probs [N, k] float32)``."""
    with jax.named_scope("moe_router"):
        vals, ids = jax.lax.top_k(router_logits.astype(jnp.float32), k)
        return ids.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)


# ------------------------------------------------------------------ kernels
def _last_active(i, active_ref):
    """Tile ``i``, or the last active one: a skipped tile asks for the
    block that is already there, and no new one is fetched."""
    return jnp.minimum(i, active_ref[0] - 1)


def _gmm_kernel(group_ref, active_ref, lhs_ref, rhs_ref, out_ref, *,
                transpose_rhs):
    del group_ref

    @pl.when(pl.program_id(0) < active_ref[0])
    def _():
        dims = (((1,), (1 if transpose_rhs else 0,)), ((), ()))
        out_ref[...] = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[0], dims,
            preferred_element_type=jnp.float32).astype(out_ref.dtype)

    @pl.when(pl.program_id(0) >= active_ref[0])
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)


def _gmm(lhs, rhs, tile_group, active, *, transpose_rhs, tile, interpret):
    """``out[r] = lhs[r] @ rhs[group of r's tile]`` (``rhs[e]^T`` with
    ``transpose_rhs``) over the active tiles; zeros after them."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    last = _last_active
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="moe_gmm_dx" if transpose_rhs else "moe_gmm_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows // tile,),
            in_specs=[
                pl.BlockSpec((tile, k), lambda i, grp, act: (last(i, act), 0)),
                pl.BlockSpec((1,) + rhs.shape[1:],
                             lambda i, grp, act: (grp[last(i, act)], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile, n), lambda i, grp, act: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_group, active, lhs, rhs)


def _gmm_dw_kernel(group_ref, active_ref, lhs_ref, rhs_ref, out_ref):
    i = pl.program_id(1)
    first = jnp.logical_or(
        i == 0, group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])

    @pl.when(first)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < active_ref[0])
    def _():
        out_ref[0] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _gmm_dw(lhs, rhs, tile_group, active, groups, *, tile, interpret):
    """``out[e] = sum over the rows of group e of lhs[r]^T rhs[r]``,
    float32 ``[groups, K, N]``. Every group has a tile, so every block
    of the result is written."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    cols = _DW_COLS if n % _DW_COLS == 0 else n
    last = _last_active
    return pl.pallas_call(
        _gmm_dw_kernel,
        name="moe_gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // cols, rows // tile),
            in_specs=[
                pl.BlockSpec((tile, k),
                             lambda j, i, grp, act: (last(i, act), 0)),
                pl.BlockSpec((tile, cols),
                             lambda j, i, grp, act: (last(i, act), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, k, cols), lambda j, i, grp, act: (grp[i], 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_group, active, lhs, rhs)


# ----------------------------------------------------------------- dispatch
def dispatch(ids: jax.Array, *, experts_held: int, expert_offset: int,
             tile: int) -> dict:
    """Where every token-expert pair goes. ``ids`` ``[N, k]``: the
    experts each token was routed to, over all experts.

    * ``row_pair`` ``[R]``: the pair (index into the flattened ``N*k``)
      laid on each row of the buffer, ``N*k`` on a row of padding;
      ``R = ceil(N*k / tile) * tile + experts_held * tile`` holds every
      pair even if all fall to held experts.
    * ``pair_row`` ``[N*k]``: the row of each pair, ``R`` where its
      expert is not held. ``row_token`` ``[R]``: the token of each row's
      pair (clipped to a token on a row of padding).
    * ``tile_group`` ``[R / tile]``, ``active`` ``[1]``: the expert of
      each tile and how many tiles the groups take.
    * ``counts`` ``[experts_held]``: pairs on each held expert;
      ``kept``: rows laid (equals ``counts.sum()``: nothing is dropped).
    """
    n, k = ids.shape
    pairs = n * k
    local = ids.reshape(pairs) - expert_offset
    key = jnp.where((local >= 0) & (local < experts_held), local,
                    experts_held)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sorted_key = key[order]
    starts = jnp.searchsorted(
        sorted_key, jnp.arange(experts_held + 1, dtype=key.dtype)
    ).astype(jnp.int32)
    counts = starts[1:] - starts[:-1]
    tiles = jnp.maximum(1, -(-counts // tile))
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tile
    rows = -(-pairs // tile) * tile + experts_held * tile
    group = jnp.minimum(sorted_key, experts_held - 1)
    dest = jnp.where(
        sorted_key < experts_held,
        row_start[group] + jnp.arange(pairs, dtype=jnp.int32)
        - starts[group], rows)
    row_pair = jnp.full((rows,), pairs, jnp.int32).at[dest].set(
        order, mode="drop")
    pair_row = jnp.full((pairs,), rows, jnp.int32).at[order].set(dest)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(rows // tile), side="right"),
        experts_held - 1).astype(jnp.int32)
    return {"row_pair": row_pair, "pair_row": pair_row,
            "row_token": jnp.minimum(row_pair // k, n - 1),
            "tile_group": tile_group,
            "active": tile_end[-1:].astype(jnp.int32), "counts": counts,
            "kept": jnp.sum(row_pair < pairs).astype(jnp.int32)}


def _rows_of(x, row_token):
    """``x [N, D]`` of each row's token. A row of padding gets SOME
    token's (``row_token`` is clipped; a plain row gather is 2.5x the
    speed of one that fills, 0.55 against 1.38 ms for 53,248 rows of
    2,560 on the v5e): nothing reads what the kernels make of it — no
    pair points at it, and its router weight (``p_row``, gathered with a
    fill) is 0, so every gradient through it is 0 too."""
    return x.at[row_token].get(mode="promise_in_bounds")


def _sum_pairs(rows, pair_row, n, k, weights=None):
    """``out[t] = sum over t's k pairs of (weights *) rows[pair's row]``,
    in float32; a pair whose expert is not held (``pair_row`` = R) adds
    nothing: its weight is 0 and the row read in its place is finite
    (the kernels write zeros on a skipped tile). One row gather a slot,
    each made whole before the sum: a ``[N, k, D]`` view of one gather
    is a relayout of its own on the TPU (k is no multiple of the 8
    sublanes; 21 ms a step at 16,384 x 6 x 2,560), and a gather fused
    into the sum runs element by element."""
    last = rows.shape[0] - 1
    slots = pair_row.reshape(n, k)
    total = 0.0
    for s in range(k):
        got = jax.lax.optimization_barrier(
            rows.at[jnp.minimum(slots[:, s], last)].get(
                mode="promise_in_bounds"))
        w = (slots[:, s] <= last).astype(jnp.float32)
        if weights is not None:
            w = w * weights[:, s]
        total = total + got.astype(jnp.float32) * w[:, None]
    return total


# ------------------------------------------------------------- expert layer
@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _experts(u, probs, w_gate, w_up, w_down, plan, tile, interpret):
    return _experts_fwd(u, probs, w_gate, w_up, w_down, plan, tile,
                        interpret)[0]


def _hidden(gate_up):
    """``relu(gate) * up`` from the two halves of the first product."""
    f = gate_up.shape[-1] // 2
    return jax.nn.relu(gate_up[:, :f]) * gate_up[:, f:]


def _first_product(u, w_gate, w_up, plan, k, gmm):
    """The rows of the buffer, and gate and up as ONE product over
    ``[D, 2F]`` (the weights side by side, in the compute dtype)."""
    with jax.named_scope("moe_dispatch"):
        xs = _rows_of(u, plan["row_token"])
    with jax.named_scope("moe_experts"):
        w_in = jnp.concatenate([w_gate, w_up], axis=-1).astype(u.dtype)
        return xs, w_in, gmm(xs, w_in, transpose_rhs=False)


def _experts_fwd(u, probs, w_gate, w_up, w_down, plan, tile, interpret):
    n, k = probs.shape
    gmm = functools.partial(_gmm, tile_group=plan["tile_group"],
                            active=plan["active"], tile=tile,
                            interpret=interpret)
    _, _, gate_up = _first_product(u, w_gate, w_up, plan, k, gmm)
    with jax.named_scope("moe_experts"):
        out = gmm(_hidden(gate_up), w_down.astype(u.dtype),
                  transpose_rhs=False)
    with jax.named_scope("moe_combine"):
        y = _sum_pairs(out, plan["pair_row"], n, k, probs).astype(u.dtype)
    # Nothing of the buffer's size is kept for the backward pass: it is
    # sized for the worst case (every pair on a held expert), four times
    # what a step uses, and the first product is cheap to take again.
    return y, (u, probs, w_gate, w_up, w_down, plan)


def _experts_bwd(tile, interpret, res, dy):
    u, probs, w_gate, w_up, w_down, plan = res
    # Without the barrier the compiler sees the rows and the first
    # product below as the forward pass's (same operands), merges the
    # two, and keeps the forward's alive until here: every layer's
    # buffers at once. Tied to ``dy``, they are taken when it arrives.
    u, w_gate, w_up, w_down, dy = jax.lax.optimization_barrier(
        (u, w_gate, w_up, w_down, dy))
    n, k = probs.shape
    dt = u.dtype
    f32 = jnp.float32
    common = dict(tile_group=plan["tile_group"], active=plan["active"],
                  tile=tile, interpret=interpret)
    gmm = functools.partial(_gmm, **common)
    gmm_dw = functools.partial(_gmm_dw, groups=w_gate.shape[0], **common)
    xs, w_in, gate_up = _first_product(u, w_gate, w_up, plan, k, gmm)
    with jax.named_scope("moe_dispatch"):
        dy_rows = _rows_of(dy, plan["row_token"])
        p_row = jnp.take(probs.reshape(n * k), plan["row_pair"],
                         mode="fill", fill_value=0)[:, None]
    with jax.named_scope("moe_experts"):
        # y = sum p_row * (h @ W_down): with dyw = dy_row @ W_down^T,
        # dp_row = <dyw, h> and dh = p_row * dyw, so ``out`` is not
        # needed again.
        dyw = gmm(dy_rows, w_down.astype(dt), transpose_rhs=True
                  ).astype(f32)
        f = gate_up.shape[-1] // 2
        gate, up = gate_up[:, :f], gate_up[:, f:].astype(f32)
        h = jax.nn.relu(gate).astype(f32) * up
        dp_row = jnp.sum(dyw * h, axis=-1)
        dh = p_row * dyw
        d_gate_up = jnp.concatenate(
            [jnp.where(gate > 0, dh * up, 0.0),
             dh * jax.nn.relu(gate).astype(f32)], axis=-1).astype(dt)
        dw_down = gmm_dw((p_row * h).astype(dt), dy_rows)
        dw_in = gmm_dw(xs, d_gate_up)
        dxs = gmm(d_gate_up, w_in, transpose_rhs=True)
    with jax.named_scope("moe_combine"):
        du = _sum_pairs(dxs, plan["pair_row"], n, k).astype(dt)
        dprobs = jnp.take(dp_row, plan["pair_row"], mode="fill",
                          fill_value=0).reshape(n, k)
    zeros = jax.tree.map(
        lambda a: jnp.zeros(a.shape, jax.dtypes.float0), plan)
    return (du, dprobs.astype(probs.dtype),
            dw_in[..., :f].astype(w_gate.dtype),
            dw_in[..., f:].astype(w_up.dtype),
            dw_down.astype(w_down.dtype), zeros)


_experts.defvjp(_experts_fwd, _experts_bwd)


def moe_experts(u: jax.Array, ids: jax.Array, probs: jax.Array,
                w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, *,
                expert_offset: int = 0, tile: int | None = None,
                interpret=None):
    """The held experts' part of the routed feed-forward.

    ``u`` ``[B, T, D]`` (the normed block input), ``ids`` / ``probs``
    ``[B, T, k]`` from :func:`route` over all experts, ``w_gate`` /
    ``w_up`` ``[E_held, D, F]``, ``w_down`` ``[E_held, F, D]``: experts
    ``expert_offset .. + E_held``. Returns ``(y [B, T, D], stats)``:
    ``y = sum over a token's held experts e of p_e (relu(u W_gate,e) *
    (u W_up,e)) W_down,e`` and ``stats`` with ``counts`` (pairs on each
    held expert), ``kept`` (rows computed) and ``routed`` (pairs whose
    expert is held), all int32; ``routed - kept`` is what was dropped,
    0 by construction.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    held = w_gate.shape[0]

    def chunk(u, ids, probs, w_gate, w_up, w_down):
        n, k = ids.shape
        rows_tile = tile or min(ROW_TILE, max(8, -(-n * k // 8) * 8))
        with jax.named_scope("moe_dispatch"):
            plan = dispatch(ids, experts_held=held,
                            expert_offset=expert_offset, tile=rows_tile)
            counts, kept = plan.pop("counts"), plan.pop("kept")
            off = ids - expert_offset
            routed = jnp.sum((off >= 0) & (off < held)).astype(jnp.int32)
        y = _experts(u, probs, w_gate, w_up, w_down, plan, rows_tile,
                     interpret)
        return y, counts, kept, routed

    def local(u, ids, probs, w_gate, w_up, w_down):
        b, t, d = u.shape
        n, k = b * t, ids.shape[-1]
        chunks = -(-n * k // MAX_PAIRS)
        if n % chunks:
            chunks = 1
        step = n // chunks
        flat = (u.reshape(n, d), ids.reshape(n, k), probs.reshape(n, k))
        parts = [chunk(*(x[lo:lo + step] for x in flat), w_gate, w_up,
                       w_down) for lo in range(0, n, step)]
        y, counts, kept, routed = (list(p) for p in zip(*parts))
        return (jnp.concatenate(y).reshape(b, t, d), sum(counts)[None],
                sum(kept)[None], sum(routed)[None])

    part = partition.current()
    if part is None:
        y, counts, kept, routed = local(u, ids, probs, w_gate, w_up, w_down)
    else:
        data = part.axis(part.data_axis)
        rows = P(data)
        y, counts, kept, routed = part.shard_map(
            local, in_specs=(rows, rows, rows, P(), P(), P()),
            out_specs=(rows, rows, rows, rows),
        )(u, ids, probs, w_gate, w_up, w_down)
    return y, {"counts": counts.sum(0), "kept": kept.sum(),
               "routed": routed.sum()}
