"""Routed feed-forward: top-k routing over all experts, and the part of
the result that the experts held on this chip give.

The layer is told which experts it holds (``expert_offset`` and how many
the weights have). It routes over every expert, keeps the token-expert
pairs whose expert is held, sorts them by expert, runs the three grouped
matrix products of a gated expert over the ragged groups, and sums
each token's rows weighted by its router probabilities. What an absent
expert would add is left out — that is the exchange-free share of an
expert-parallel layer, and nothing here stands in for the other chips.
There is no capacity and no dropped pair.

**Layout of the rows.** The pairs of expert *e* are laid from a row that
is a multiple of the row tile, and a group takes at least one tile
(an empty group one tile of zero rows, so that its weight gradient is
written). A tile of rows then belongs to one expert, and a grouped
product is a tiled matmul whose weight block is chosen per tile from a
prefetched table — no tile straddles two experts, no group is padded to
another's length. The layout is a set of int32 tables long enough for
every pair to fall to a held expert (:func:`worst_case_rows`).

**Passes.** No activation array has that length. The rows are served
through a buffer of :func:`buffer_rows` rows — 4/3 of what falls to the
held experts when the router spreads the pairs evenly, and a tile a
group — one window of tiles of the layout after the other, in as many
passes as the step's load needs (``lax.while_loop``, one forward and one
backward; the backward is written by hand, nothing differentiates
through a loop). At 8,192 tokens x 6 and 16 of 64 experts that is
``256 x (ceil(49,152 x 16/64 x 4/3 / 256) + 16)`` = 20,480 rows of
53,248, and one pass while a chunk's held pairs and their padding fit
(16,384 pairs always do); a load above that costs another pass, never
a pair. Where every expert is held the buffer is the whole layout: one
pass by construction, and no loop is built. A pass gathers its rows,
runs the kernels over them (tiles after the window's last group are
skipped and write zeros), and adds to each token's sum the rows of the
pairs that lie in its window; the weight gradients go from pass to pass,
and from one chunk of the tokens to the next, in the buffer they are
written to (``moe_gmm_dw`` takes it as an aliased operand and reads only
the blocks of groups that go on).

**Kernels** (``pallas_call(name=)``): ``moe_gmm_fwd`` (rows @ W_e: gate
and up as one product over ``[D, 2F]``, then down), ``moe_gmm_dx``
(rows @ W_e^T: the two input gradients) and ``moe_gmm_dw`` (rows^T @
rows into ``[E, K, N]`` float32: the two weight gradients). The backward pass
takes the first product again rather than keep its result. Off the TPU they run in the Pallas interpreter.
Traced under a mesh the whole layer runs per shard of the batch
(:mod:`.partition`), each shard routing its own tokens and looping on
its own load.

Scopes, for the device trace: ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine`` (inside a loop's ``while/body`` too).
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
# Tokens are served in equal chunks of at most this many pairs, one after
# the other, each through its own buffer and its own passes: at 16,384
# tokens x 6 that is two chunks of 8,192 tokens. The backward pass has
# five row arrays alive at once (rows, their gradient, the first product,
# its gradient, the weighted hidden: 7,936 columns of bfloat16 together);
# at 20,480 rows a chunk that is 0.33 GB where one buffer for all the
# tokens' worst case would hold 1.7 GB, and a second pass is taken by the
# chunk whose load needs it, not by both.
MAX_PAIRS = 49152
_DW_COLS = 256          # output columns of one moe_gmm_dw program
_VMEM_LIMIT = 96 * 1024 * 1024
# What ``moe_gmm_dw`` makes of the result it is handed (``active[1]``).
_FROM_ZERO, _TILE_0_GOES_ON, _ALL_GO_ON = 0, 1, 2


def route(router_logits: jax.Array, k: int, *, scoring: str = "softmax",
          bias: jax.Array | None = None, scale: float = 1.0):
    """``(ids [N, k] int32, probs [N, k] float32)``: the ``k`` experts of
    each row and their weights.

    ``"softmax"``: the ``k`` largest router logits and the softmax over
    those ``k`` (taken after the selection, so the weights of a token
    sum to 1). ``"sigmoid"``: scores ``s = sigmoid(logits)``; the ``k``
    largest of ``s + bias`` (the correction bias moves the selection
    only, so no gradient reaches it); weights ``scale * s_e / (sum of
    the selected s + 1e-20)``."""
    with jax.named_scope("moe_router"):
        logits = router_logits.astype(jnp.float32)
        if scoring == "softmax":
            vals, ids = jax.lax.top_k(logits, k)
            return ids.astype(jnp.int32), jax.nn.softmax(vals, axis=-1)
        if scoring != "sigmoid":
            raise ValueError(f"unknown router scoring {scoring!r}")
        scores = jax.nn.sigmoid(logits)
        _, ids = jax.lax.top_k(
            scores if bias is None
            else scores + jax.lax.stop_gradient(bias), k)
        picked = jnp.take_along_axis(scores, ids, axis=-1)
        return ids.astype(jnp.int32), scale * picked / (
            jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)


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


# jit(inline=True) on both calls, as on ``ops/short_attention.py``'s: a
# kernel body is traced once per shape in a process, not once per layer,
# chunk and program, and every call site still gets a ``pallas_call`` of
# its own, under its own scope.
@functools.partial(jax.jit, inline=True,
                   static_argnames=("transpose_rhs", "tile", "interpret"))
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


def _gmm_dw_kernel(group_ref, active_ref, lhs_ref, rhs_ref, *refs):
    # refs: (out_ref,), or (acc_ref, out_ref) where the result is handed
    # what earlier calls gave; active_ref[1] then says which groups go on
    # from it (_FROM_ZERO / _TILE_0_GOES_ON / _ALL_GO_ON).
    out_ref = refs[-1]
    i = pl.program_id(1)
    first = jnp.logical_or(
        i == 0, group_ref[i] != group_ref[jnp.maximum(i - 1, 0)])
    goes_on = False
    if len(refs) == 2:
        goes_on = jnp.logical_and(first, jnp.logical_or(
            active_ref[1] == _ALL_GO_ON,
            jnp.logical_and(active_ref[1] == _TILE_0_GOES_ON, i == 0)))

        @pl.when(goes_on)
        def _():
            out_ref[...] = refs[0][...]

    @pl.when(jnp.logical_and(first, jnp.logical_not(goes_on)))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i < active_ref[0])
    def _():
        out_ref[0] += jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


@functools.partial(jax.jit, inline=True,
                   static_argnames=("groups", "tile", "interpret"))
def _gmm_dw(lhs, rhs, tile_group, active, groups, acc=None, *, tile,
            interpret):
    """``out[e] = sum over the rows of group e of lhs[r]^T rhs[r]``,
    float32 ``[groups, K, N]``. Without ``acc`` every group has a tile,
    so every block of the result is written. With ``acc`` (what earlier
    calls gave, same shape, given up to this call: the result is written
    where it lies) a group with no tile here keeps what it had, and
    ``active[1]`` says what a group with tiles here starts from: zero,
    or ``acc`` (every group: the tokens' chunks before this one have
    written them all; or only tile 0's, which an earlier pass over the
    same chunk began). Only the blocks that go on are read: a first pass
    reads one block a column of programs and uses none."""
    rows, k = lhs.shape
    n = rhs.shape[1]
    cols = _DW_COLS if n % _DW_COLS == 0 else n
    last = _last_active
    block = (1, k, cols)
    in_specs = [
        pl.BlockSpec((tile, k), lambda j, i, grp, act: (last(i, act), 0)),
        pl.BlockSpec((tile, cols), lambda j, i, grp, act: (last(i, act), j)),
    ]
    if acc is not None:
        in_specs.append(pl.BlockSpec(block, lambda j, i, grp, act: (
            grp[jnp.where(act[1] == _ALL_GO_ON, i, 0)], 0, j)))
    return pl.pallas_call(
        _gmm_dw_kernel,
        name="moe_gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // cols, rows // tile),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                block, lambda j, i, grp, act: (grp[i], 0, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), jnp.float32),
        input_output_aliases={} if acc is None else {4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(tile_group, active, lhs, rhs, *(() if acc is None else (acc,)))


# ----------------------------------------------------------------- dispatch
def worst_case_rows(pairs: int, experts_held: int, tile: int) -> int:
    """Rows that hold ``pairs`` pairs even if all fall to held experts:
    the pairs in whole tiles, and a tile a group for its padding."""
    return -(-pairs // tile) * tile + experts_held * tile


def dispatch(ids: jax.Array, *, experts_held: int, expert_offset: int,
             tile: int, window: int | None = None) -> dict:
    """Where every token-expert pair goes. ``ids`` ``[N, k]``: the
    experts each token was routed to, over all experts. These are int32
    tables over the rows of the worst case; no activation array of that
    length is made (:func:`moe_experts` serves them ``window`` rows at a
    time).

    * ``row_pair`` ``[R]``: the pair (index into the flattened ``N*k``)
      laid on each row, ``N*k`` on a row of padding; ``R`` is
      :func:`worst_case_rows`, which holds every pair even if all fall
      to held experts, rounded up to whole windows of ``window`` rows.
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
    rows = worst_case_rows(pairs, experts_held, tile)
    if window:
        rows = -(-rows // window) * window
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


@functools.partial(jax.jit, inline=True, static_argnames=("n", "k"))
def _sum_pairs(rows, pair_row, n, k, weights=None, start=None):
    """``out[t] = start[t] + sum over t's k pairs of (weights *)
    rows[pair's row]``, in float32; a pair whose row is not among
    ``rows`` (``pair_row`` = their number: its expert is not held, or
    another pass serves it) adds nothing: its weight is 0 and the row
    read in its place is finite (the kernels write zeros on a skipped
    tile). One row gather a slot, each made whole before the sum: a
    ``[N, k, D]`` view of one gather is a relayout of its own on the TPU
    (k is no multiple of the 8 sublanes; 21 ms a step at 16,384 x 6 x
    2,560), and a gather fused into the sum runs element by element.
    The sum starts from ``start`` (what earlier passes gave) rather than
    have it added afterwards: one more operand of the same fusion."""
    last = rows.shape[0] - 1
    slots = pair_row.reshape(n, k)
    total = 0.0 if start is None else start
    for s in range(k):
        got = jax.lax.optimization_barrier(
            rows.at[jnp.minimum(slots[:, s], last)].get(
                mode="promise_in_bounds"))
        w = (slots[:, s] <= last).astype(jnp.float32)
        if weights is not None:
            w = w * weights[:, s]
        total = total + got.astype(jnp.float32) * w[:, None]
    return total


# --------------------------------------------------------------- the passes
def buffer_rows(pairs: int, experts_held: int, num_experts: int,
                tile: int) -> int:
    """Rows of the buffer that a chunk of ``pairs`` pairs is served
    through: 4/3 of the pairs that fall to ``experts_held`` of
    ``num_experts`` when the router spreads them evenly, in whole tiles,
    and a tile a group for its padding; never more than the worst case.
    At 49,152 pairs and 16 of 64 experts: 64 + 16 tiles = 20,480 rows
    against 53,248."""
    share = -(-pairs * experts_held * 4 // (num_experts * 3 * tile))
    return min(tile * (share + experts_held),
               worst_case_rows(pairs, experts_held, tile))


def _window(plan, p, rows, tile, later):
    """Pass ``p``'s part of the layout: the tiles ``[p * rows / tile,
    (p + 1) * rows / tile)``, as a plan over ``rows`` rows. A pair laid
    outside it gets ``rows`` for its row, as one whose expert is not
    held. ``active`` becomes ``[tiles of the window that hold a group,
    which groups' weight gradients go on from what is there]``: all of
    them in a ``later`` chunk of the tokens (the chunks before it have
    written every group), else only a group that straddles this pass and
    the one before (a tile has one expert, so that is all a straddling
    group needs)."""
    tiles = rows // tile
    part = lambda a, size: jax.lax.dynamic_slice_in_dim(a, p * size, size)
    group, first = plan["tile_group"], p * tiles
    straddles = (p > 0) & (group[jnp.maximum(first - 1, 0)] == group[first])
    local = plan["pair_row"] - p * rows
    return {"row_token": part(plan["row_token"], rows),
            "row_pair": part(plan["row_pair"], rows),
            "tile_group": part(group, tiles),
            "active": jnp.stack([
                jnp.clip(plan["active"][0] - first, 0, tiles),
                jnp.int32(_ALL_GO_ON) if later else
                jnp.where(straddles, _TILE_0_GOES_ON, _FROM_ZERO)]),
            "pair_row": jnp.where((local >= 0) & (local < rows), local,
                                  rows)}


def passes_of(active, rows: int, tile: int):
    """Passes of ``rows`` rows that ``active[0]`` tiles take (int32)."""
    return jnp.maximum(1, -(-active[0] * tile // rows))


def _in_passes(one_pass, plan, rows, tile, start, later=False):
    """``one_pass(window, carried)`` over the layout, ``rows`` rows at a
    time, as often as the tiles that hold a group need; what a pass
    returns is handed to the next, and the first is handed ``start``: a
    tuple of arrays, with a ``ShapeDtypeStruct`` where a sum starts from
    nothing. Where the buffer is the whole layout there is one pass and
    no loop, and such a sum is handed ``None``; in a loop, zeros."""
    fresh = lambda s: isinstance(s, jax.ShapeDtypeStruct)
    if plan["row_token"].shape[0] == rows:
        return one_pass(_window(plan, 0, rows, tile, later),
                        tuple(None if fresh(s) else s for s in start))
    passes = passes_of(plan["active"], rows, tile)
    start = tuple(jnp.zeros(s.shape, s.dtype) if fresh(s) else s
                  for s in start)

    def step(carry):
        with jax.named_scope("moe_dispatch"):
            window = _window(plan, carry[0], rows, tile, later)
        return carry[0] + 1, one_pass(window, carry[1])

    return jax.lax.while_loop(lambda carry: carry[0] < passes, step,
                              (jnp.int32(0), start))[1]


# ------------------------------------------------------------- expert layer
def _silu_slope(x):
    s = jax.nn.sigmoid(x)
    return s * (1.0 + x * (1.0 - s))


# The gate's activation of a gated feed-forward ``act(gate) * up``,
# stated once: its value, and the gate's cotangent from the hidden
# state's ``dh`` (float32), which the hand-written backward pass needs.
ACTIVATIONS = {
    "relu": (jax.nn.relu,
             lambda gate, dh, up: jnp.where(gate > 0, dh * up, 0.0)),
    "silu": (jax.nn.silu, lambda gate, dh, up: dh * up * _silu_slope(
        gate.astype(jnp.float32))),
}


def gated(gate, up, activation: str):
    """``act(gate) * up``: the hidden state of a gated feed-forward."""
    return ACTIVATIONS[activation][0](gate) * up


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _experts(u, probs, w_gate, w_up, w_down, plans, tile, rows, interpret,
             activation):
    return _experts_fwd(u, probs, w_gate, w_up, w_down, plans, tile, rows,
                        interpret, activation)[0]


def _hidden(gate_up, activation):
    """``act(gate) * up`` from the two halves of the first product (the
    gate sliced and activated before ``up`` is sliced: the order the
    lowered step has had, kept so that its text stays what it was)."""
    f = gate_up.shape[-1] // 2
    return ACTIVATIONS[activation][0](gate_up[:, :f]) * gate_up[:, f:]


def _weights_in(w_gate, w_up, w_down, dt):
    """Gate and up side by side (ONE product over ``[D, 2F]``) and down,
    in the compute dtype; once a call, outside its passes."""
    with jax.named_scope("moe_experts"):
        return (jnp.concatenate([w_gate, w_up], axis=-1).astype(dt),
                w_down.astype(dt))


def _first_product(u, w_in, window, gmm):
    """The rows of the buffer and the first product over them."""
    with jax.named_scope("moe_dispatch"):
        xs = _rows_of(u, window["row_token"])
    with jax.named_scope("moe_experts"):
        return xs, gmm(xs, w_in, transpose_rhs=False)


def _chunks(plans, *arrays):
    """Each plan with its equal share of the tokens of ``arrays``."""
    step = arrays[0].shape[0] // len(plans)
    return [(plan, *(a[i * step:(i + 1) * step] for a in arrays))
            for i, plan in enumerate(plans)]


def _experts_fwd(u, probs, w_gate, w_up, w_down, plans, tile, rows,
                 interpret, activation):
    k = probs.shape[1]
    w_in, w_out = _weights_in(w_gate, w_up, w_down, u.dtype)
    ys = []
    for plan, u_c, probs_c in _chunks(plans, u, probs):
        def one_pass(window, carried):
            gmm = functools.partial(_gmm, tile_group=window["tile_group"],
                                    active=window["active"], tile=tile,
                                    interpret=interpret)
            _, gate_up = _first_product(u_c, w_in, window, gmm)
            with jax.named_scope("moe_experts"):
                out = gmm(_hidden(gate_up, activation), w_out,
                          transpose_rhs=False)
            with jax.named_scope("moe_combine"):
                return (_sum_pairs(out, window["pair_row"], u_c.shape[0],
                                   k, probs_c, start=carried[0]),)

        y, = _in_passes(one_pass, plan, rows, tile, (
            jax.ShapeDtypeStruct(u_c.shape, jnp.float32),))
        with jax.named_scope("moe_combine"):
            ys.append(y.astype(u.dtype))
    # Nothing of the buffer's size is kept for the backward pass: the
    # first product is cheap to take again.
    return jnp.concatenate(ys), (u, probs, w_gate, w_up, w_down, plans)


def _experts_bwd(tile, rows, interpret, activation, res, dy):
    u, probs, w_gate, w_up, w_down, plans = res
    act, through_act = ACTIVATIONS[activation]
    # Without the barrier the compiler sees the rows and the first
    # product below as the forward pass's (same operands), merges the
    # two, and keeps the forward's alive until here: every layer's
    # buffers at once. Tied to ``dy``, they are taken when it arrives.
    u, w_gate, w_up, w_down, dy = jax.lax.optimization_barrier(
        (u, w_gate, w_up, w_down, dy))
    k = probs.shape[1]
    dt = u.dtype
    f32 = jnp.float32
    f = w_gate.shape[-1]
    w_in, w_out = _weights_in(w_gate, w_up, w_down, dt)
    dus, dps = [], []
    # The weight gradients are summed over the chunks where they lie.
    dw_in = jax.ShapeDtypeStruct(w_in.shape, f32)
    dw_down = jax.ShapeDtypeStruct(w_down.shape, f32)
    for i, (plan, u_c, probs_c, dy_c) in enumerate(
            _chunks(plans, u, probs, dy)):
        n = u_c.shape[0]

        def one_pass(window, carried):
            common = dict(tile_group=window["tile_group"],
                          active=window["active"], tile=tile,
                          interpret=interpret)
            gmm = functools.partial(_gmm, **common)
            gmm_dw = functools.partial(_gmm_dw, groups=w_gate.shape[0],
                                       **common)
            du, dprobs, dw_in, dw_down = carried
            xs, gate_up = _first_product(u_c, w_in, window, gmm)
            with jax.named_scope("moe_dispatch"):
                dy_rows = _rows_of(dy_c, window["row_token"])
                p_row = jnp.take(probs_c.reshape(n * k), window["row_pair"],
                                 mode="fill", fill_value=0)[:, None]
            with jax.named_scope("moe_experts"):
                # y = sum p_row * (h @ W_down): with dyw = dy_row @
                # W_down^T, dp_row = <dyw, h> and dh = p_row * dyw, so
                # ``out`` is not needed again.
                dyw = gmm(dy_rows, w_out, transpose_rhs=True).astype(f32)
                gate, up = gate_up[:, :f], gate_up[:, f:].astype(f32)
                h = act(gate).astype(f32) * up
                dp_row = jnp.sum(dyw * h, axis=-1)
                dh = p_row * dyw
                d_gate_up = jnp.concatenate(
                    [through_act(gate, dh, up),
                     dh * act(gate).astype(f32)], axis=-1
                ).astype(dt)
                dw_down = gmm_dw((p_row * h).astype(dt), dy_rows,
                                 acc=dw_down)
                dw_in = gmm_dw(xs, d_gate_up, acc=dw_in)
                dxs = gmm(d_gate_up, w_in, transpose_rhs=True)
            with jax.named_scope("moe_combine"):
                du = _sum_pairs(dxs, window["pair_row"], n, k, start=du)
                here = jnp.take(dp_row, window["pair_row"], mode="fill",
                                fill_value=0).reshape(n, k)
                dprobs = here if dprobs is None else dprobs + here
            return du, dprobs, dw_in, dw_down

        du, dprobs, dw_in, dw_down = _in_passes(
            one_pass, plan, rows, tile, (
                jax.ShapeDtypeStruct(u_c.shape, f32),
                jax.ShapeDtypeStruct((n, k), f32), dw_in, dw_down),
            later=i > 0)
        with jax.named_scope("moe_combine"):
            dus.append(du.astype(dt))
        dps.append(dprobs.astype(probs.dtype))
    zeros = jax.tree.map(
        lambda a: jnp.zeros(a.shape, jax.dtypes.float0), plans)
    return (jnp.concatenate(dus), jnp.concatenate(dps),
            dw_in[..., :f].astype(w_gate.dtype),
            dw_in[..., f:].astype(w_up.dtype),
            dw_down.astype(w_down.dtype), zeros)


_experts.defvjp(_experts_fwd, _experts_bwd)


def moe_experts(u: jax.Array, ids: jax.Array, probs: jax.Array,
                w_gate: jax.Array, w_up: jax.Array, w_down: jax.Array, *,
                expert_offset: int = 0, num_experts: int | None = None,
                activation: str = "relu", tile: int | None = None,
                interpret=None):
    """The held experts' part of the routed feed-forward.

    ``u`` ``[B, T, D]`` (the normed block input), ``ids`` / ``probs``
    ``[B, T, k]`` from :func:`route` over all ``num_experts`` (the width
    of the router's output; taken as the experts held where it is not
    given), ``w_gate`` / ``w_up`` ``[E_held, D, F]``, ``w_down``
    ``[E_held, F, D]``: experts ``expert_offset .. + E_held``. Returns
    ``(y [B, T, D], stats)``: ``y = sum over a token's held experts e of
    p_e (act(u W_gate,e) * (u W_up,e)) W_down,e`` (``activation``: a key
    of :data:`ACTIVATIONS`) and ``stats`` with
    ``counts`` (pairs on each held expert), ``kept`` (rows computed),
    ``routed`` (pairs whose expert is held) and ``passes`` (of each
    chunk of tokens: :func:`buffer_rows`), all int32; ``routed - kept``
    is what was dropped, 0 by construction.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    held = w_gate.shape[0]

    def local(u, ids, probs, w_gate, w_up, w_down):
        b, t, d = u.shape
        n, k = b * t, ids.shape[-1]
        chunks = -(-n * k // MAX_PAIRS)
        if n % chunks:
            chunks = 1
        pairs = n // chunks * k
        rows_tile = tile or min(ROW_TILE, max(8, -(-pairs // 8) * 8))
        rows = buffer_rows(pairs, held, num_experts or held, rows_tile)
        with jax.named_scope("moe_dispatch"):
            plans = tuple(
                dispatch(part, experts_held=held,
                         expert_offset=expert_offset, tile=rows_tile,
                         window=rows)
                for part in ids.reshape(chunks, n // chunks, k))
            counts = sum(plan.pop("counts") for plan in plans)
            kept = sum(plan.pop("kept") for plan in plans)
            off = ids - expert_offset
            routed = jnp.sum((off >= 0) & (off < held)).astype(jnp.int32)
            passes = jnp.stack([passes_of(plan["active"], rows, rows_tile)
                                for plan in plans])
        y = _experts(u.reshape(n, d), probs.reshape(n, k), w_gate, w_up,
                     w_down, plans, rows_tile, rows, interpret, activation)
        return (y.reshape(b, t, d), counts[None], kept[None], routed[None],
                passes)

    part = partition.current()
    args = (u, ids, probs, w_gate, w_up, w_down)
    if part is None:
        y, counts, kept, routed, passes = local(*args)
    else:
        data = part.axis(part.data_axis)
        rows = P(data)
        y, counts, kept, routed, passes = part.shard_map(
            local, in_specs=(rows, rows, rows, P(), P(), P()),
            out_specs=(rows,) * 5)(*args)
    return y, {"counts": counts.sum(0), "kept": kept.sum(),
               "routed": routed.sum(), "passes": passes}
