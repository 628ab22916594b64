"""The indexer's selection as one Pallas kernel, ``dsa_select``, over each
query block's causal scores (:func:`..sparse_attention.select_rows` is
the mathematics and the reference; this file is the search where the
flash kernels serve the core).

A program holds ``Cq`` query rows of a chunk's scores as XLA lays them,
transposed, ``[keys, queries]`` (queries on lanes): a row's bound, its
count and what it wants are lane rows that broadcast along sublanes as
they lie, and a count over keys is a sum of vector registers. What it
does, all of it in VMEM:

* the scores of the keys up to the block's last row come in a key block
  at a time (the grid's last axis; its block index stops at the last
  causal block, so nothing past it is fetched) and are turned ONCE into
  int32 keys whose signed order is the floats' (``_sortable``; -0 as +0),
  a key past a row's diagonal the least key, as ``select_rows`` has it;
* the bisection of ``select_rows`` on those keys: 32 passes, each a count
  of the keys at or above a trial bound, over the block's causal keys
  only (whole tiles of 128), into four ``[8, Cq]`` int32 accumulators;
* the tie pass (14 more counts over positions), only in a block where
  some row has more keys at its threshold than it takes: ties to the
  lower position;
* the int8 selection of the block's queries, zeros past the diagonal
  included, written as it is held, ``[keys, queries]``, in place into
  the layer's one ``[B, T, T]`` buffer (aliased; :func:`empty` makes it):
  the layout XLA keeps the selection in (queries minor), so nothing is
  stacked, copied or transposed after the call.

A block whose last row is under ``topk`` takes every causal key and
searches nothing. Same scores in, the same selection out as
``select_rows``, bit for bit: nothing is approximated. At
``keye2_train_16k``'s shape the search is about 4.3 ms a layer where
XLA's bisection over every key took about 12 (TPU v5e; PERF.md).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import partition
from .flash_attention import _pad_to, _vmem_limit

_LANES = 128
_TILE = 128          # keys a step of a count
_ACCUMULATORS = 4    # independent sums a count carries
_INT_MIN = -2 ** 31
_INT_MAX = 2 ** 31 - 1
BLOCK_Q = 512        # query rows a program (lanes)
BLOCK_K = 2048       # keys a fetched block (sublanes)


def serves(served: str, t: int, chunk: int) -> bool:
    """Whether the kernel takes the selection of a call whose core
    :func:`..attention.choose` gave to ``served``: the rule of
    :func:`.indexer_loss.serves` (where flash serves the core, on one
    device), and, compiled for the chip (off it the interpreter takes any
    shape), at whole lane blocks of keys and of a chunk's rows."""
    if served != "flash" or partition.current() is not None:
        return False
    if jax.default_backend() != "tpu":
        return True
    return t % _LANES == 0 and chunk % _LANES == 0


def _sortable(x):
    """float32 -> int32 whose SIGNED order is the floats' (-0 as +0):
    :func:`..sparse_attention._sortable` with its top bit turned."""
    bits = jnp.where(x == 0.0, 0, jax.lax.bitcast_convert_type(x, jnp.int32))
    return jnp.where(bits < 0, bits ^ _INT_MAX, bits)


def _count(keys_ref, tiles, hit):
    """``[1, Cq]``: per query, the keys of the first ``tiles`` tiles for
    which ``hit(keys [8, Cq], position of the first)`` holds."""
    block_q = keys_ref.shape[1]

    def body(t, sums):
        start = pl.multiple_of(t * _TILE, _TILE)
        tile = keys_ref[pl.ds(start, _TILE), :]
        sums = list(sums)
        for s in range(_TILE // 8):
            a = s % _ACCUMULATORS
            sums[a] = sums[a] + hit(tile[s * 8:(s + 1) * 8],
                                    start + s * 8).astype(jnp.int32)
        return tuple(sums)

    zero = jnp.zeros((8, block_q), jnp.int32)
    sums = jax.lax.fori_loop(0, tiles, body, (zero,) * _ACCUMULATORS)
    return jnp.sum(functools.reduce(jnp.add, sums), axis=0, keepdims=True)


def _kernel(row0_ref, _, total_ref, sel_ref, ties_ref, keys_ref, thr_ref,
            cut_ref, *, topk, block_k):
    """One (sequence, query block, key block) program."""
    i, j = pl.program_id(1), pl.program_id(2)
    block_q = total_ref.shape[2]
    first = row0_ref[0] + i * block_q
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, block_q), 1)
    row = first + lane                                       # [1, Cq]
    # keys 0 .. the block's last row, in whole tiles
    width = jnp.minimum(first + block_q, keys_ref.shape[0])
    tiles = (width + _TILE - 1) // _TILE

    @pl.when(j * block_k < width)
    def _():
        def turn(t, _):
            start = pl.multiple_of(j * block_k + t * _TILE, _TILE)
            pos = start + jax.lax.broadcasted_iota(
                jnp.int32, (_TILE, block_q), 0)
            keys_ref[pl.ds(start, _TILE), :] = jnp.where(
                pos <= row, _sortable(total_ref[0, pl.ds(t * _TILE, _TILE),
                                                :]), _INT_MIN)
            return 0

        jax.lax.fori_loop(0, block_k // _TILE, turn, 0)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        # the bound a row takes keys at or above, and the last tied
        # position it takes: every causal key until searched
        thr_ref[...] = jnp.full_like(thr_ref, _INT_MIN)
        cut_ref[...] = jnp.full_like(cut_ref, _INT_MAX)
        ties_ref[0] = jnp.zeros((1, block_q), jnp.int32)

        @pl.when(first + block_q > topk)
        def _():
            want = jnp.minimum(row + 1, topk)

            def value_bit(p, carry):
                found, at_found = carry
                trial = found ^ jax.lax.shift_left(1, 31 - p)
                n = _count(keys_ref, tiles, lambda x, _: x >= trial)
                more = n >= want
                return (jnp.where(more, trial, found),
                        jnp.where(more, n, at_found))

            # the want-th largest key of each row, and how many are at or
            # above it
            thr, at_thr = jax.lax.fori_loop(
                0, 32, value_bit,
                (jnp.full((1, block_q), _INT_MIN, jnp.int32),
                 jnp.full((1, block_q), tiles * _TILE, jnp.int32)))
            thr_ref[...] = thr
            above = _count(keys_ref, tiles, lambda x, _: x > thr)
            short = want - above                   # ties to take, >= 1
            tied = (at_thr - above) > short
            ties_ref[0] = tied.astype(jnp.int32)

            @pl.when(jnp.max(ties_ref[0]) > 0)
            def _():
                bits = max(1, (keys_ref.shape[0] - 1).bit_length())
                sublane = jax.lax.broadcasted_iota(jnp.int32, (8, block_q), 0)

                def position_bit(p, last):
                    trial = last | jax.lax.shift_left(1, bits - 1 - p)
                    n = _count(keys_ref, tiles, lambda x, at: jnp.logical_and(
                        x == thr, at + sublane < trial))
                    return jnp.where(n < short, trial, last)

                # the position of the last tie taken: the largest with
                # fewer than ``short`` ties before it
                cut_ref[...] = jax.lax.fori_loop(
                    0, bits, position_bit, jnp.zeros((1, block_q), jnp.int32))

        thr, cut = thr_ref[...], cut_ref[...]

        def write(t, _):
            start = pl.multiple_of(t * _TILE, _TILE)
            x = keys_ref[pl.ds(start, _TILE), :]
            pos = start + jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            take = jnp.logical_and(
                jnp.logical_or(x > thr, jnp.logical_and(x == thr, pos <= cut)),
                pos <= row)
            sel_ref[0, pl.ds(start, _TILE), :] = take.astype(
                jnp.int32).astype(sel_ref.dtype)
            return 0

        def clear(t, _):
            start = pl.multiple_of(t * _TILE, _TILE)
            sel_ref[0, pl.ds(start, _TILE), :] = jnp.zeros(
                (_TILE, block_q), sel_ref.dtype)
            return 0

        jax.lax.fori_loop(0, tiles, write, 0)
        jax.lax.fori_loop(tiles, keys_ref.shape[0] // _TILE, clear, 0)


def empty(b: int, t: int, *, interpret=None):
    """The selection :func:`select` writes a chunk of queries at a time,
    held as the kernel holds it: int8 ``[B, keys, queries]``, the keys
    padded to whole lane blocks, as a kernel (``dsa_select_buffer``)
    that writes nothing leaves it. Every query's column is written once,
    by the chunk that holds it, so nothing is spent setting it first
    (zeros would be a broadcast and a copy into the loop's buffer, 256
    MiB each a layer at T = 16,384)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return pl.pallas_call(
        lambda _: None, name="dsa_select_buffer",
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((b, -(-t // _LANES) * _LANES, t),
                                       jnp.int8),
        interpret=interpret)()


def select(picked, total, row0, topk: int, *, block_q=None, block_k=None,
           interpret=None):
    """``(picked, tied [B, C] int32)``: the selection ``picked`` (from
    :func:`empty`, ``[B, keys, queries]``) with the queries ``row0 ..
    row0 + C - 1`` (``row0`` a traced multiple of C) written in place from
    their scores ``total [B, C, T]`` float32, as :func:`..sparse_attention.
    select_rows` selects them, and 1 where a row had more keys at its
    threshold than it takes (the rows the tie pass decides). Written in
    place, in the aliased buffer: no chunk's rows are stacked or copied
    after the call, and the selection leaves as the scores came,
    queries on lanes: the layout XLA keeps it in (``[B, T, T]`` with the
    queries minor), so no transposing copy is made of it."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, c, t = total.shape
    tp = picked.shape[1]
    block_q = block_q or math.gcd(c, BLOCK_Q)
    block_k = block_k or math.gcd(tp, BLOCK_K)
    # as XLA lays the scores: keys on sublanes, queries on lanes
    total_t = _pad_to(jnp.swapaxes(total, 1, 2), 1, tp)
    grid = (b, c // block_q, tp // block_k)

    def key_block(n, i, j, row0_ref):
        width = jnp.minimum(row0_ref[0] + (i + 1) * block_q, tp)
        return n, jnp.minimum(j, (width + block_k - 1) // block_k - 1), i

    picked, tied = pl.pallas_call(
        functools.partial(_kernel, topk=topk, block_k=block_k),
        name="dsa_select",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((1, block_k, block_q), key_block)],
            out_specs=[
                pl.BlockSpec((1, tp, block_q), lambda n, i, j, r: (
                    n, 0, r[0] // block_q + i)),
                pl.BlockSpec((1, 1, block_q), lambda n, i, j, r: (n, 0, i))],
            scratch_shapes=[pltpu.VMEM((tp, block_q), jnp.int32),
                            pltpu.VMEM((1, block_q), jnp.int32),
                            pltpu.VMEM((1, block_q), jnp.int32)]),
        out_shape=[jax.ShapeDtypeStruct(picked.shape, jnp.int8),
                   jax.ShapeDtypeStruct((b, 1, c), jnp.int32)],
        input_output_aliases={1: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(block_q * tp, 4 * block_k * block_q,
                                         scratch=4 * tp * block_q)),
        interpret=interpret,
    )(jnp.reshape(row0, (1,)).astype(jnp.int32), picked, total_t)
    return picked, tied[:, 0]
