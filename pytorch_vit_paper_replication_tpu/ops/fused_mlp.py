"""Pallas TPU fused MLP block: fc1 -> GELU -> dropout -> fc2 in VMEM.

The reference's MLP is two separate ``nn.Linear`` calls with GELU/Dropout
between them (``models/vit.py:100-131``). Under XLA those lower to two GEMM
custom-calls with the ``[B*T, mlp_size]`` hidden activation materialized in
HBM between them — for ViT-B/16 at batch 256 that is a ~310 MB bf16 tensor
written by fc1 and re-read by fc2 *per layer per direction*, and PERF.md's
round-3 breakdown identifies exactly this inter-GEMM elementwise traffic as
the step's binding constraint (fc1 moves ~0.7 GB of HBM for 0.24 TFLOP).

This kernel keeps the hidden activation in VMEM: the grid walks row blocks
of the flattened ``[N, D]`` input; each program computes
``gelu(x @ W1 + b1)``, applies the dropout mask, and immediately multiplies
by ``W2`` — the ``[block, mlp_size]`` hidden tile never touches HBM. The
weights use constant index maps, so Pallas DMAs them into VMEM once and
reuses them across the whole grid. HBM traffic per MLP drops from
``~2*N*mlp + 2*N*D`` elements to ``2*N*D`` (read x, write out) plus one
weight load.

The backward saves exactly ONE residual — the pre-activation ``h`` in the
compute dtype — instead of XLA's several (pre-activation for the GELU
derivative, post-dropout hidden for fc2's weight grad, plus the mask):
GELU and its derivative are re-evaluated from ``h`` on the VPU (cheap), so
a single kernel produces ``dx`` per block in 4 GEMMs while accumulating
``dW1/db1/dW2/db2`` in VMEM float32 across the sequential TPU grid
(constant output index maps -> one HBM writeback at grid end). A
flash-style full-recompute variant (save nothing, re-derive ``h`` via an
extra ``x @ W1`` GEMM) was measured SLOWER on v5e: these GEMMs are
MXU-shape-bound at ~71 TF/s, so +20% backward FLOPs cost more than the
one saved ``[N, F]`` round-trip — see PERF.md round 4.

**Hidden dropout** runs in-kernel with the same counter-based positional
hash the flash-attention kernel uses (:func:`.dropout.positional_keep_u8`,
keyed on the flattened ``(row, hidden-column)`` coordinates), so forward and
backward regenerate bit-identical masks with no stored randomness, and the
drop rate is quantized to ``round(rate*256)/256`` with survivors rescaled by
the quantized keep probability — exactly :mod:`.dropout`'s semantics. The
mask *bits* differ from the XLA path's ``jax.random.bits`` draw (same
statistics, different stream); parity tests compare the paths with dropout
off and validate the fused mask against a hand-evaluated positional mask.

GELU is exact (erf-based) to match ``torch.nn.GELU``/the model's
``nn.gelu(approximate=False)``; it and its derivative are evaluated in
float32 inside the kernel, with matmul operands cast back to the compute
dtype so every contraction runs native-rate on the MXU.

Use :class:`..models.vit.MLPBlock` with ``config.mlp_impl`` rather than
calling this directly.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from jax.sharding import PartitionSpec as P

from . import partition
from .dropout import (positional_dropout_seed, positional_keep_u8,
                      positional_meta)

DEFAULT_BLOCK_ROWS = 256
_SQRT_HALF = math.sqrt(0.5)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)


def _erf(x):
    """erf via Abramowitz & Stegun 7.1.26 (max abs error 1.5e-7 — below
    bf16/f32-accumulation noise). Mosaic has no lowering for the ``erf``
    primitive, so the kernel evaluates this polynomial form; it uses only
    mul/add/div/exp, all native VPU ops."""
    a = jnp.abs(x)
    t = 1.0 / (1.0 + 0.3275911 * a)
    poly = t * (0.254829592 + t * (-0.284496736 + t * (
        1.421413741 + t * (-1.453152027 + t * 1.061405429))))
    y = 1.0 - poly * jnp.exp(-a * a)
    return jnp.where(x < 0.0, -y, y)


def _gelu_exact(h):
    """Exact (erf-based) GELU, float32 in/out: ``h * Phi(h)``."""
    return h * 0.5 * (1.0 + _erf(h * _SQRT_HALF))


def _gelu_grad(h):
    """d/dh of exact GELU: ``Phi(h) + h * phi(h)``."""
    phi = jnp.exp(-0.5 * h * h) * _INV_SQRT_2PI
    cdf = 0.5 * (1.0 + _erf(h * _SQRT_HALF))
    return cdf + h * phi


def _keep_mask(meta_ref, block_row0, shape, threshold, *, tag=0):
    """Dropout keep mask for one [block_rows, F] tile, keyed on the
    GLOBAL (flattened-row, column) coordinates so every kernel (fwd,
    bwd) regenerates the identical mask. ``meta_ref`` is the scalar-
    prefetch triple ``[seed, row0, col0]``: under a mesh each shard's
    rows and hidden columns start at its own offset
    (:func:`.dropout.positional_meta`), so
    no two shards draw the same mask and the mask of an element does
    not depend on how the batch was split. ``tag`` decorrelates the two
    dropout sites that share one seed (0 = hidden, 1 = output; the
    output is never column-sharded, so its column offset is 0)."""
    row = (meta_ref[1] + block_row0
           + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    if tag == 0:
        col = col + meta_ref[2]
    return positional_keep_u8(meta_ref[0], jnp.int32(tag), row, col,
                              threshold)


# --------------------------------------------------------------------------
# Forward
# --------------------------------------------------------------------------

def _fwd_kernel(meta_ref, x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref,
                h_ref=None, *, threshold, block_rows):
    """Forward: hidden tile never leaves VMEM. With an ``h_ref`` output
    (training variant) the pre-activation is additionally written in the
    compute dtype as the backward's single residual; without one
    (primal-only) nothing is saved.

    Deliberate bf16 trade-off (ADVICE r4): in bf16 training the saved
    ``h`` is the ROUNDED pre-activation, so the backward re-derives
    GELU'(h)/dropout from a value that differs from the f32 ``h`` the
    forward used — a one-ulp-of-bf16 gradient mismatch invisible to the
    f32 parity tests. MEASURED r5 (PERF.md): saving
    h as f32 instead costs ~2.5% of the full B/16 step (848->827 img/s,
    the doubled [rows, mlp_size] residual round-trip) while moving no
    grad's error vs an f32 reference (both variants ~3-5e-3, dominated
    by bf16 compute everywhere else); the bf16 residual stays."""
    x = x_ref[...]
    h = jax.lax.dot(x, w1_ref[...], preferred_element_type=jnp.float32)
    h = h + b1_ref[...].astype(jnp.float32)
    if h_ref is not None:
        h_ref[...] = h.astype(h_ref.dtype)
    g = _gelu_exact(h)
    if threshold:
        keep = _keep_mask(meta_ref, pl.program_id(0) * block_rows,
                          g.shape, threshold)
        g = jnp.where(keep, g * (256.0 / (256.0 - threshold)), 0.0)
    out = jax.lax.dot(g.astype(x.dtype), w2_ref[...],
                      preferred_element_type=jnp.float32)
    out = out + b2_ref[...].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


# --------------------------------------------------------------------------
# Backward (saved-h residual; dW accumulated across the sequential grid)
# --------------------------------------------------------------------------

def _bwd_kernel(meta_ref, x_ref, h_ref, w1_ref, w2_ref, do_ref,
                dx_ref, dw1_ref, db1_ref, dw2_ref, db2_ref, *,
                threshold, block_rows):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw1_ref[...] = jnp.zeros_like(dw1_ref)
        db1_ref[...] = jnp.zeros_like(db1_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)
        db2_ref[...] = jnp.zeros_like(db2_ref)

    x = x_ref[...]
    do = do_ref[...]
    do32 = do.astype(jnp.float32)

    # GELU and its derivative re-evaluated from the saved pre-activation
    # (VPU work only — no recompute GEMM).
    h = h_ref[...].astype(jnp.float32)
    g = _gelu_exact(h)
    if threshold:
        keep = _keep_mask(meta_ref, i * block_rows, g.shape, threshold)
        inv_keep = 256.0 / (256.0 - threshold)
        g_drop = jnp.where(keep, g * inv_keep, 0.0)
    else:
        g_drop = g

    # dG = dOut @ W2^T   (contract the D dims: w2 is [F, D])
    dg = jax.lax.dot_general(do, w2_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if threshold:
        dg = jnp.where(keep, dg * inv_keep, 0.0)
    dh = dg * _gelu_grad(h)
    dh_c = dh.astype(x.dtype)

    # dX = dH @ W1^T     (contract the F dims: w1 is [D, F])
    dx = jax.lax.dot_general(dh_c, w1_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dx_ref[...] = dx.astype(dx_ref.dtype)

    # Weight/bias grads accumulate in VMEM f32; one HBM writeback at grid
    # end (constant output index maps; the TPU grid is sequential).
    dw1_ref[...] += jax.lax.dot_general(
        x, dh_c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # [D, F]
    db1_ref[...] += jnp.sum(dh, axis=0, keepdims=True)         # [1, F]
    dw2_ref[...] += jax.lax.dot_general(
        g_drop.astype(x.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                    # [F, D]
    db2_ref[...] += jnp.sum(do32, axis=0, keepdims=True)       # [1, D]


# --------------------------------------------------------------------------
# custom_vjp wiring
# --------------------------------------------------------------------------

def _compiler_params(interpret):
    if interpret:
        return None
    # The bwd kernel holds both weight matrices (bf16) plus their two f32
    # grad accumulators in VMEM — 27 MiB at ViT-B's 768x3072, 75 MiB at
    # ViT-H's 1280x5120, before the row blocks — so raise the compiler's
    # default cap. A v5e core has 128 MiB of VMEM (jax's own
    # pallas.tpu.get_tpu_info table, "TPU v5 lite"); the v5e compiler
    # accepts this limit for the B/16, L/16 and H/14 steps.
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=100 * 1024 * 1024,
    )


def _fused_call(x, w1, b1, w2, b2, seed, threshold, block_rows, interpret,
                *, save_h):
    """Shared forward pallas_call; ``save_h`` adds the residual output
    (same pattern as :func:`_lnmlp_call`, so the primal and vjp forward
    cannot diverge)."""
    n, d = x.shape
    f = w1.shape[1]
    kernel = functools.partial(_fwd_kernel, threshold=threshold,
                               block_rows=block_rows)
    const = lambda i, *_: (0, 0)  # noqa: E731
    row_spec = pl.BlockSpec((block_rows, d), lambda i, *_: (i, 0))
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((n, d), x.dtype)]
    if save_h:
        out_specs.append(pl.BlockSpec((block_rows, f), lambda i, *_: (i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((n, f), x.dtype))
    res = pl.pallas_call(
        kernel,
        name="mlp_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block_rows,),
            in_specs=[
                row_spec,
                pl.BlockSpec((d, f), const),
                pl.BlockSpec((1, f), const),
                pl.BlockSpec((f, d), const),
                pl.BlockSpec((1, d), const),
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(seed, x, w1, b1[None, :], w2, b2[None, :])
    return res if save_h else (res[0], None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _fused(x, w1, b1, w2, b2, seed, threshold, block_rows, interpret):
    out, _ = _fused_call(x, w1, b1, w2, b2, seed, threshold, block_rows,
                         interpret, save_h=False)
    return out


def _fused_fwd(x, w1, b1, w2, b2, seed, threshold, block_rows, interpret):
    out, h = _fused_call(x, w1, b1, w2, b2, seed, threshold, block_rows,
                         interpret, save_h=True)
    return out, (x, h, w1, b1, w2, seed)


def _fused_bwd(threshold, block_rows, interpret, res, do):
    x, h, w1, b1, w2, seed = res
    n, d = x.shape
    f = w1.shape[1]
    kernel = functools.partial(_bwd_kernel, threshold=threshold,
                               block_rows=block_rows)
    const = lambda i, *_: (0, 0)  # noqa: E731
    row_spec = pl.BlockSpec((block_rows, d), lambda i, *_: (i, 0))
    dx, dw1, db1, dw2, db2 = pl.pallas_call(
        kernel,
        name="mlp_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block_rows,),
            in_specs=[
                row_spec,
                pl.BlockSpec((block_rows, f), lambda i, *_: (i, 0)),
                pl.BlockSpec((d, f), const),
                pl.BlockSpec((f, d), const),
                row_spec,
            ],
            out_specs=[
                row_spec,
                pl.BlockSpec((d, f), const),
                pl.BlockSpec((1, f), const),
                pl.BlockSpec((f, d), const),
                pl.BlockSpec((1, d), const),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((d, f), jnp.float32),
            jax.ShapeDtypeStruct((1, f), jnp.float32),
            jax.ShapeDtypeStruct((f, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(seed, x, h, w1, w2, do)
    seed_zero = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return (dx, dw1.astype(w1.dtype), db1[0].astype(b1.dtype),
            dw2.astype(w2.dtype), db2[0].astype(do.dtype), seed_zero)


_fused.defvjp(_fused_fwd, _fused_bwd)


# --------------------------------------------------------------------------
# Full half-block kernel: x + drop(fc2(drop(gelu(fc1(LN(x))))))
# --------------------------------------------------------------------------
#
# The encoder block's entire MLP half — pre-norm LayerNorm, both GEMMs, the
# hidden and output dropouts, and the residual add (reference
# ``models/vit.py:115-126`` + the residual at ``:168``) — as ONE kernel.
# Beyond :func:`fused_mlp` this also keeps the LayerNorm output and the
# fc2 output in VMEM (each a [N, D] round trip per direction under XLA)
# and needs no LayerNorm residuals at all: row mean/rstd are recomputed
# from ``x`` in backward on the VPU. The two dropout masks share one seed,
# decorrelated by the hash's ``bh`` tag (0 = hidden, 1 = output).

def _ln(x32, gamma_ref, beta_ref, eps):
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    c = x32 - mu
    var = jnp.mean(c * c, axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = c * rstd
    y = xhat * gamma_ref[...].astype(jnp.float32) \
        + beta_ref[...].astype(jnp.float32)
    return xhat, rstd, y


def _lnmlp_fwd_kernel(meta_ref, x_ref, gamma_ref, beta_ref, w1_ref, b1_ref,
                      w2_ref, b2_ref, o_ref, h_ref=None, *, threshold,
                      block_rows, eps):
    x32 = x_ref[...].astype(jnp.float32)
    _, _, y = _ln(x32, gamma_ref, beta_ref, eps)
    h = jax.lax.dot(y.astype(x_ref.dtype), w1_ref[...],
                    preferred_element_type=jnp.float32)
    h = h + b1_ref[...].astype(jnp.float32)
    if h_ref is not None:
        h_ref[...] = h.astype(h_ref.dtype)
    g = _gelu_exact(h)
    row0 = pl.program_id(0) * block_rows
    if threshold:
        inv_keep = 256.0 / (256.0 - threshold)
        keep = _keep_mask(meta_ref, row0, g.shape, threshold)
        g = jnp.where(keep, g * inv_keep, 0.0)
    f = jax.lax.dot(g.astype(x_ref.dtype), w2_ref[...],
                    preferred_element_type=jnp.float32)
    f = f + b2_ref[...].astype(jnp.float32)
    if threshold:
        keep2 = _keep_mask(meta_ref, row0, f.shape, threshold, tag=1)
        f = jnp.where(keep2, f * inv_keep, 0.0)
    o_ref[...] = (x32 + f).astype(o_ref.dtype)


def _lnmlp_bwd_kernel(meta_ref, x_ref, h_ref, gamma_ref, beta_ref,
                      w1_ref, w2_ref, do_ref, dx_ref, dgamma_ref,
                      dbeta_ref, dw1_ref, db1_ref, dw2_ref, db2_ref, *,
                      threshold, block_rows, eps):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dgamma_ref[...] = jnp.zeros_like(dgamma_ref)
        dbeta_ref[...] = jnp.zeros_like(dbeta_ref)
        dw1_ref[...] = jnp.zeros_like(dw1_ref)
        db1_ref[...] = jnp.zeros_like(db1_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)
        db2_ref[...] = jnp.zeros_like(db2_ref)

    x32 = x_ref[...].astype(jnp.float32)
    xhat, rstd, y = _ln(x32, gamma_ref, beta_ref, eps)
    do32 = do_ref[...].astype(jnp.float32)
    row0 = i * block_rows

    # Output dropout enters through the fc2 cotangent.
    if threshold:
        inv_keep = 256.0 / (256.0 - threshold)
        keep2 = _keep_mask(meta_ref, row0, do32.shape, threshold, tag=1)
        df = jnp.where(keep2, do32 * inv_keep, 0.0)
    else:
        df = do32
    df_c = df.astype(x_ref.dtype)

    h = h_ref[...].astype(jnp.float32)
    g = _gelu_exact(h)
    if threshold:
        keep = _keep_mask(meta_ref, row0, g.shape, threshold)
        g_drop = jnp.where(keep, g * inv_keep, 0.0)
    else:
        g_drop = g

    dw2_ref[...] += jax.lax.dot_general(
        g_drop.astype(x_ref.dtype), df_c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db2_ref[...] += jnp.sum(df, axis=0, keepdims=True)

    dg = jax.lax.dot_general(df_c, w2_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    if threshold:
        dg = jnp.where(keep, dg * inv_keep, 0.0)
    dh = dg * _gelu_grad(h)
    dh_c = dh.astype(x_ref.dtype)

    dw1_ref[...] += jax.lax.dot_general(
        y.astype(x_ref.dtype), dh_c, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    db1_ref[...] += jnp.sum(dh, axis=0, keepdims=True)

    dy = jax.lax.dot_general(dh_c, w1_ref[...], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    dgamma_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)
    dbeta_ref[...] += jnp.sum(dy, axis=0, keepdims=True)

    dxhat = dy * gamma_ref[...].astype(jnp.float32)
    m1 = jnp.mean(dxhat, axis=-1, keepdims=True)
    m2 = jnp.mean(dxhat * xhat, axis=-1, keepdims=True)
    dx_ln = rstd * (dxhat - m1 - xhat * m2)
    dx_ref[...] = (do32 + dx_ln).astype(dx_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10, 11))
def _lnmlp(x, gamma, beta, w1, b1, w2, b2, seed, threshold, block_rows,
           eps, interpret):
    out, _ = _lnmlp_call(x, gamma, beta, w1, b1, w2, b2, seed, threshold,
                         block_rows, eps, interpret, save_h=False)
    return out


def _lnmlp_call(x, gamma, beta, w1, b1, w2, b2, seed, threshold, block_rows,
                eps, interpret, *, save_h):
    n, d = x.shape
    f = w1.shape[1]
    kernel = functools.partial(_lnmlp_fwd_kernel, threshold=threshold,
                               block_rows=block_rows, eps=eps)
    const = lambda i, *_: (0, 0)  # noqa: E731
    row_spec = pl.BlockSpec((block_rows, d), lambda i, *_: (i, 0))
    vec_d = pl.BlockSpec((1, d), const)
    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((n, d), x.dtype)]
    if save_h:
        out_specs.append(pl.BlockSpec((block_rows, f), lambda i, *_: (i, 0)))
        out_shape.append(
            jax.ShapeDtypeStruct((n, f), x.dtype))
    res = pl.pallas_call(
        kernel,
        name="lnmlp_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block_rows,),
            in_specs=[
                row_spec, vec_d, vec_d,
                pl.BlockSpec((d, f), const),
                pl.BlockSpec((1, f), const),
                pl.BlockSpec((f, d), const),
                vec_d,
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(seed, x, gamma[None, :], beta[None, :], w1, b1[None, :], w2,
      b2[None, :])
    if save_h:
        return res
    return res[0], None


def _lnmlp_fwd(x, gamma, beta, w1, b1, w2, b2, seed, threshold, block_rows,
               eps, interpret):
    out, h = _lnmlp_call(x, gamma, beta, w1, b1, w2, b2, seed, threshold,
                         block_rows, eps, interpret, save_h=True)
    return out, (x, h, gamma, beta, w1, w2, seed)


def _lnmlp_bwd(threshold, block_rows, eps, interpret, res, do):
    x, h, gamma, beta, w1, w2, seed = res
    n, d = x.shape
    f = w1.shape[1]
    kernel = functools.partial(_lnmlp_bwd_kernel, threshold=threshold,
                               block_rows=block_rows, eps=eps)
    const = lambda i, *_: (0, 0)  # noqa: E731
    row_spec = pl.BlockSpec((block_rows, d), lambda i, *_: (i, 0))
    vec_d = pl.BlockSpec((1, d), const)
    dx, dgamma, dbeta, dw1, db1, dw2, db2 = pl.pallas_call(
        kernel,
        name="lnmlp_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // block_rows,),
            in_specs=[
                row_spec,
                pl.BlockSpec((block_rows, f), lambda i, *_: (i, 0)),
                vec_d, vec_d,
                pl.BlockSpec((d, f), const),
                pl.BlockSpec((f, d), const),
                row_spec,
            ],
            out_specs=[
                row_spec, vec_d, vec_d,
                pl.BlockSpec((d, f), const),
                pl.BlockSpec((1, f), const),
                pl.BlockSpec((f, d), const),
                vec_d,
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((n, d), x.dtype),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
            jax.ShapeDtypeStruct((d, f), jnp.float32),
            jax.ShapeDtypeStruct((1, f), jnp.float32),
            jax.ShapeDtypeStruct((f, d), jnp.float32),
            jax.ShapeDtypeStruct((1, d), jnp.float32),
        ],
        compiler_params=_compiler_params(interpret),
        interpret=interpret,
    )(seed, x, h, gamma[None, :], beta[None, :], w1, w2, do)
    seed_zero = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return (dx, dgamma[0].astype(gamma.dtype), dbeta[0].astype(gamma.dtype),
            dw1.astype(w1.dtype), db1[0].astype(w1.dtype),
            dw2.astype(w2.dtype), db2[0].astype(w2.dtype), seed_zero)


_lnmlp.defvjp(_lnmlp_fwd, _lnmlp_bwd)


def _flat_rows(x, block_rows):
    """``[..., D]`` -> ``([N_padded, D], N, block)``: rows flattened and
    padded up to a whole number of row blocks."""
    x2 = x.reshape(-1, x.shape[-1])
    n = x2.shape[0]
    block = min(block_rows, max(16, -(-n // 16) * 16))
    pad = (-n) % block
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    return x2, n, block


def _row_sharding(part, x):
    """How the active mesh splits the rows of ``x``: batch over the data
    axis and — for ``[B, T, D]`` whose tokens divide — tokens over the
    seq axis (the MLP is per-token). -> ``(axes, PartitionSpec)``."""
    data = part.axis(part.data_axis)
    seq = part.axis(part.seq_axis)
    if x.ndim != 3 or x.shape[1] % part.size(seq):
        seq = None
    if x.shape[0] % part.size(data):
        raise ValueError(
            f"leading dim {x.shape[0]} of the fused-MLP input is not "
            f"divisible by the mesh's {part.data_axis!r} axis "
            f"({part.size(data)})")
    lead = (data, seq) if x.ndim == 3 else (data,) + (None,) * (x.ndim - 2)
    return (data, seq), P(*lead, None)


def fused_ln_mlp_residual(x: jax.Array, gamma: jax.Array, beta: jax.Array,
                          w1: jax.Array, b1: jax.Array, w2: jax.Array,
                          b2: jax.Array, *, eps: float = 1e-6,
                          dropout_rate: float = 0.0,
                          dropout_rng: Optional[jax.Array] = None,
                          deterministic: bool = True,
                          block_rows: int = DEFAULT_BLOCK_ROWS,
                          interpret: Optional[bool] = None) -> jax.Array:
    """The encoder block's full MLP half as one kernel:
    ``x + drop(fc2(drop(gelu(fc1(LN(x))))))``.

    Same contract as :func:`fused_mlp` plus the LayerNorm params
    (``gamma``/``beta``, shape ``[D]``) and ``eps``. ``dropout_rate``
    applies to BOTH dropout sites (hidden and output), matching the
    reference's single ``mlp_dropout`` rate (``models/vit.py:120-126``).
    Requires ``w2``'s output dim to equal ``x``'s feature dim (the
    residual add).

    Traced under a mesh (:func:`.partition.on_mesh`) the call runs per
    shard of the rows with every weight replicated; a mesh that slices
    the hidden dim (tensor parallelism) wants :func:`fused_mlp`, whose
    partial sums can be reduced before the residual.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if w2.shape[1] != x.shape[-1]:
        raise ValueError(
            f"residual form needs fc2 out dim == input dim, got "
            f"{w2.shape[1]} != {x.shape[-1]}")
    threshold, seed = positional_dropout_seed(
        "fused_ln_mlp_residual", dropout_rate, dropout_rng, deterministic)

    def local(x, gamma, beta, w1, b1, w2, b2, seed, row_shard=0):
        x2, n, block = _flat_rows(x, block_rows)
        meta = positional_meta(seed, n, row_shard)
        out = _lnmlp(x2, gamma, beta, w1, b1, w2, b2, meta, threshold,
                     block, eps, interpret)
        return out[:n].reshape(x.shape)

    part = partition.current()
    if part is None:
        return local(x, gamma, beta, w1, b1, w2, b2, seed)
    rows, x_spec = _row_sharding(part, x)
    return part.shard_map(
        lambda *a: local(*a, row_shard=part.index(rows)),
        in_specs=(x_spec,) + (P(),) * 7, out_specs=x_spec,
    )(x, gamma, beta, w1, b1, w2, b2, seed)


def fused_mlp(x: jax.Array, w1: jax.Array, b1: jax.Array, w2: jax.Array,
              b2: jax.Array, *, dropout_rate: float = 0.0,
              dropout_rng: Optional[jax.Array] = None,
              deterministic: bool = True,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              interpret: Optional[bool] = None) -> jax.Array:
    """Fused ``gelu(x @ w1 + b1) -> dropout -> @ w2 + b2`` (module docstring).

    Args:
      x: ``[..., D]`` input (any leading shape; flattened internally).
      w1, b1: fc1 params ``[D, F]`` / ``[F]``.
      w2, b2: fc2 params ``[F, D_out]`` / ``[D_out]``.
      dropout_rate / dropout_rng / deterministic: hidden-activation dropout
        (reference ``models/vit.py:122`` — the dropout between GELU and fc2);
        same contract as :func:`.attention.dot_product_attention`.
      block_rows: rows of the flattened input processed per grid step.
      interpret: run the Pallas interpreter (default: auto — True off-TPU,
        so the CPU test suite exercises the identical kernel code).

    Returns:
      ``[..., D_out]``, in ``x.dtype``.

    Traced under a mesh (:func:`.partition.on_mesh`) the call runs per
    shard: rows split like :func:`fused_ln_mlp_residual`'s, and when the
    mesh has a model axis the hidden dim is sliced over it (``w1``
    columns / ``w2`` rows, the layout ``parallel.sharding.TP_RULES``
    places them in) with fc2's partial sums ``psum``'d before ``b2`` is
    added once — Megatron wiring, the same form
    :class:`..models.vit.MLPBlock`'s manual ``tp_axis`` spells by hand.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    threshold, seed = positional_dropout_seed(
        "fused_mlp", dropout_rate, dropout_rng, deterministic)

    def local(x, w1, b1, w2, b2, seed, row_shard=0, col_shard=0):
        x2, n, block = _flat_rows(x, block_rows)
        meta = positional_meta(seed, n, row_shard, w1.shape[1], col_shard)
        out = _fused(x2, w1, b1, w2, b2, meta, threshold, block, interpret)
        return out[:n].reshape(*x.shape[:-1], w2.shape[1])

    part = partition.current()
    if part is None:
        return local(x, w1, b1, w2, b2, seed)
    rows, x_spec = _row_sharding(part, x)
    model = part.axis(part.model_axis)
    if w1.shape[1] % part.size(model):
        raise ValueError(
            f"mlp hidden dim {w1.shape[1]} is not divisible by the mesh's "
            f"{part.model_axis!r} axis ({part.size(model)})")

    def shard(x, w1, b1, w2, b2, seed):
        if model is None:
            return local(x, w1, b1, w2, b2, seed, part.index(rows))
        partial = local(x, w1, b1, w2, jnp.zeros_like(b2), seed,
                        part.index(rows), jax.lax.axis_index(model))
        return jax.lax.psum(partial, model) + b2

    return part.shard_map(
        shard,
        in_specs=(x_spec, P(None, model), P(model), P(model, None), P(),
                  P()),
        out_specs=x_spec)(x, w1, b1, w2, b2, seed)
