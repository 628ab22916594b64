"""Vision Transformer as Flax modules — the TPU-native core model library.

Mirrors the reference's module decomposition one-to-one so capability parity
is auditable (reference ``models/vit.py``):

=========================  =====================================
reference (torch)          here (Flax Linen)
=========================  =====================================
``PatchEmbedding`` (:5)    :class:`PatchEmbedding`
``MultiHeadSelfAttentionBlock`` (:69)  :class:`MultiHeadSelfAttentionBlock`
``MLPBlock`` (:100)        :class:`MLPBlock`
``TransformerEncoderBlock`` (:133)     :class:`TransformerEncoderBlock`
``ViT`` (:172)             :class:`ViT`
``models/vit_no_classifier.py``        :class:`ViTFeatureExtractor`
=========================  =====================================

Differences, all deliberate and TPU-motivated:

* Images are **NHWC** (TPU-native layout), not NCHW.
* Activations compute in ``config.dtype`` (bfloat16 by default) with float32
  parameters and float32 logits — the reference is float32 end-to-end.
* CLS token initializes to zeros and the position embedding to
  truncated-normal(0.02), following the original ViT JAX release. The
  reference uses ``torch.rand`` uniform-[0,1) for both
  (``models/vit.py:35-42``), a known deviation from the paper that SURVEY.md
  §2.2 flags as not worth copying.
* The attention core is :func:`..ops.attention.self_attention`, handed
  the packed qkv projection: the short-sequence Pallas kernel, XLA-fused
  or Pallas flash, by what the call allows.
* The encoder stack can be rematerialized (``config.remat``) to trade FLOPs
  for HBM on large configs.
* Dropout draws uint8 threshold masks (:mod:`..ops.dropout`) instead of
  float bernoulli — 4x fewer random bits, ~13% faster train steps on v5e;
  the drop rate is quantized to n/256 (see that module's docstring).

Parameter-count parity with the reference (85,800,963 for the 3-class
ViT-B/16, reference main notebook cell 80) is asserted in
``tests/test_models.py``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint

from ..configs import ViTConfig
from ..ops import partition
from ..ops.attention import choose, dot_product_attention, self_attention
from ..ops.dropout import Dropout


def _dtype(cfg: ViTConfig):
    return jnp.dtype(cfg.dtype)


def _norm(cfg: ViTConfig, name: str) -> nn.Module:
    """The block's normalisation: LayerNorm, or RMSNorm (scale only)."""
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.ln_epsilon, dtype=_dtype(cfg),
                          name=name)
    return nn.LayerNorm(epsilon=cfg.ln_epsilon, dtype=_dtype(cfg), name=name)


def rotary(x: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding of ``x [B, T, H, Dh]`` at positions
    ``0..T-1``: rotate-half over the whole head (pairs ``(i, i + Dh/2)``
    turned by ``t * theta^(-2i/Dh)``), computed in float32."""
    t, dh = x.shape[1], x.shape[-1]
    freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., dh // 2:], x32[..., :dh // 2]],
                           axis=-1)
    return (x32 * cos + half * sin).astype(x.dtype)


class TokenEmbedding(nn.Module):
    """Token ids ``[B, T]`` -> ``[B, T, D]``: a row of the table each. No
    position is added here: a token model's positions are its blocks'
    rotary embeddings (or none).

    Rows start at N(0, 1) (``torch.nn.Embedding``'s default; what rows
    of std 0.02 scaled by sqrt(D) are at D = 2560), not at ``init_std``:
    the residual stream a block's norms and routers read is then the
    token's own row. At 0.02 it is what the first position-free causal
    layer adds to every position alike, and an untrained router sends a
    whole sequence to the same experts. A head tied to the table reads
    these rows as its weights, and at N(0, 1) its initial logits would
    be sqrt(D) wide: a tied table's rows start at ``init_std``."""

    config: ViTConfig

    @nn.compact
    def __call__(self, ids: jax.Array) -> jax.Array:
        cfg = self.config
        if ids.shape[1] > cfg.max_seq_len:
            raise ValueError(f"{ids.shape[1]} tokens, max_seq_len "
                             f"{cfg.max_seq_len}")
        std = cfg.init_std if cfg.tie_embedding else 1.0
        table = self.param("embedding", nn.initializers.normal(std),
                           (cfg.vocab_size, cfg.embedding_dim), jnp.float32)
        return jnp.take(table, ids, axis=0).astype(_dtype(cfg))


class _PatchConv(nn.Module):
    """Patch projection with a conv-layout kernel, computed as one matmul.

    Params are identical to ``nn.Conv`` (kernel ``[P, P, C, D]`` + bias) so
    torch-weight conversion and sharding rules are unaffected, but the
    compute is an explicit unfold + ``[B·N, P·P·C] @ [P·P·C, D]`` matmul —
    ~2x faster than the strided-conv lowering on the target TPU.
    """

    config: ViTConfig

    @nn.compact
    def __call__(self, images: jax.Array) -> jax.Array:
        cfg = self.config
        p, c, d = cfg.patch_size, cfg.color_channels, cfg.embedding_dim
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (p, p, c, d), jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (d,), jnp.float32)
        b, h, w, _ = images.shape
        n = h // p
        x = images.reshape(b, n, p, n, p, c)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, n * n, p * p * c)
        x = x @ kernel.reshape(p * p * c, d).astype(x.dtype)
        return x + bias.astype(x.dtype)


class PatchEmbedding(nn.Module):
    """Patchify + embed + CLS + learned position embedding.

    Reference: ``models/vit.py:5-67``. Patchify is mathematically the
    reference's ``Conv2d(kernel_size=patch_size, stride=patch_size)``,
    executed as an unfolded matmul (see :class:`_PatchConv`).
    """

    config: ViTConfig

    @nn.compact
    def __call__(self, images: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        b, h, w, c = images.shape
        if h != cfg.image_size or w != cfg.image_size:
            raise ValueError(
                f"expected {cfg.image_size}x{cfg.image_size} images, got "
                f"{h}x{w}")
        x = _PatchConv(cfg, name="patch_conv")(images.astype(_dtype(cfg)))

        if cfg.pool == "cls":
            cls = self.param("cls_token", nn.initializers.zeros,
                             (1, 1, cfg.embedding_dim), jnp.float32)
            cls = jnp.broadcast_to(cls.astype(x.dtype),
                                   (b, 1, cfg.embedding_dim))
            x = jnp.concatenate([cls, x], axis=1)

        pos = self.param("pos_embedding",
                         nn.initializers.truncated_normal(stddev=0.02),
                         (1, cfg.seq_len, cfg.embedding_dim), jnp.float32)
        x = x + pos.astype(x.dtype)
        x = Dropout(rate=cfg.embedding_dropout,
                    deterministic=not train)(x)
        return x


class MultiHeadSelfAttentionBlock(nn.Module):
    """Pre-norm multi-head self-attention; returns attention output only.

    Reference: ``models/vit.py:69-98`` — LayerNorm then MHA with q=k=v; the
    residual add lives in :class:`TransformerEncoderBlock`, matching the
    reference's wiring. QKV is one fused projection so XLA issues a single
    [D, 3D] matmul on the MXU.

    ``tp_axis``: manual tensor parallelism for callers running inside
    ``shard_map`` (the pipeline, ``parallel/pipeline.py``), where GSPMD
    cannot insert collectives. Params arrive head-sliced, the module
    computes its local heads, and the out-projection's partial sum is
    ``psum``'d over the axis — Megatron wiring, explicit. ``None`` (the
    default, every non-pipeline path) changes nothing: GSPMD handles TP
    from sharding annotations alone.
    """

    config: ViTConfig
    tp_axis: Optional[str] = None
    layer: int = 0     # which block: picks the layer's rotary / window

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False,
                 with_normed: bool = False):
        cfg = self.config
        if cfg.vocab_size:
            attention = (_latent_attention if cfg.kv_lora_rank
                         else _token_attention)
            out, y = attention(self, x, train)
            return (out, y) if with_normed else out
        # Deliberately NOT Pallas-fused: a fused LN+QKV kernel (the
        # fused_mlp treatment applied here) measured a net LOSS — isolated
        # full-vjp 10.5 -> 11.5 ms, full step 306 -> 344 ms — because XLA's
        # single deep-contraction dW GEMM beats per-block VMEM
        # accumulation and there is no [N, mlp]-sized intermediate to
        # eliminate on this side. See PERF.md round-4 negative results.
        y = nn.LayerNorm(epsilon=cfg.ln_epsilon, dtype=_dtype(cfg), name="norm")(x)
        dropout_rng = None
        if train and cfg.attn_dropout > 0.0:
            dropout_rng = self.make_rng("dropout")
        heads = (cfg.num_heads, cfg.head_dim)
        # Where the short-sequence kernel will serve the call, the
        # projections are taken flat, so that the compiler lays their
        # results out as the kernel reads them (_FlatDenseGeneral).
        if _flat_projections(cfg, y.shape[:2] + (3,) + heads, train):
            dense = _FlatDenseGeneral
        else:
            dense = functools.partial(nn.DenseGeneral,
                                      param_dtype=jnp.float32)
        # Under manual TP the caller passes a head-LOCAL config (flax
        # validates stored params against the declared features, so
        # num_heads here must equal the params' local head count — see
        # parallel/pipeline.py's block_cfg).
        qkv = dense(features=(3,) + heads, axis=-1, dtype=_dtype(cfg),
                    name="qkv")(y)              # [B, T, 3, H(_local), Dh]
        # The projection goes to the dispatch packed: the short-sequence
        # kernel reads it where it lies; every other path slices q, k, v.
        attn = self_attention(
            qkv, impl=cfg.attention_impl, dropout_rate=cfg.attn_dropout,
            dropout_rng=dropout_rng, deterministic=not train,
            softmax=cfg.attention_softmax,
            # Manual TP hands this module a head-LOCAL config: tell the
            # dispatcher so its Ulysses divisibility pre-check doesn't
            # divide the already-local head count again (ADVICE r4).
            heads_already_local=self.tp_axis is not None,
        )                                        # [B, T, H(_local), Dh]
        out = dense(features=cfg.embedding_dim, axis=(-2, -1),
                    dtype=_dtype(cfg), name="out")(attn)
        if self.tp_axis is not None:
            out = jax.lax.psum(out, self.tp_axis)
        return (out, y) if with_normed else out


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _leave_together(lhs, rhs, cut_input):
    """A product's operands as they are; their GRADIENTS leave together
    (:func:`_tied_product`)."""
    return lhs, rhs


def _leave_together_bwd(cut_input, _, gradients):
    d_lhs, d_rhs = gradients
    if not cut_input:
        return jax.lax.optimization_barrier((d_lhs, d_rhs))
    # The input is a constant of this product: it gets zeros, one that
    # waits for the kernel's gradient spread over its shape.
    zero, d_rhs = jax.lax.optimization_barrier(
        (jnp.zeros((), d_lhs.dtype), d_rhs))
    return jnp.broadcast_to(zero, d_lhs.shape), d_rhs


_leave_together.defvjp(lambda lhs, rhs, cut_input: ((lhs, rhs), None),
                       _leave_together_bwd)


def _tied_product(lhs, rhs, dimension_numbers, precision=None,
                  preferred_element_type=None, *, cut_input=False):
    """``lax.dot_general`` for the projections of a block whose attention
    an indexer selects (``nn.DenseGeneral(dot_general=)``): in the
    backward pass the input's gradient, which the block before waits for,
    leaves with the kernel's, which only the optimizer reads (a barrier
    on the two; the forward pass is the plain product's). Nothing else
    orders the two, and the order the compiler takes for
    ``keye2_train_16k``'s step computes every block's weight gradients
    after the LAST block's backward pass, their operands held until then
    (the core's output, dq after the q norm, the normed input, the loss's
    gradients: 0.4 GiB a layer growing where this order frees 0.08;
    13.94 GiB for the step: PERF.md section 6, PR 35). ``cut_input``: the
    product reads its input as a constant (the indexer's projections),
    and the zeros the input gets wait for the kernel's gradient all the
    same."""
    lhs, rhs = _leave_together(lhs, rhs, cut_input)
    return jax.lax.dot_general(lhs, rhs, dimension_numbers,
                               precision=precision,
                               preferred_element_type=preferred_element_type)


def _token_attention(self: MultiHeadSelfAttentionBlock, x: jax.Array,
                     train: bool):
    """(Called from the block's compact ``__call__``; a free function and
    not a method, since flax names a scope after a method and the paths
    ``msa/norm``, ``msa/qkv``, ``msa/attn_core``, ``msa/out`` are what
    the device trace reads.)

    A token model's attention: one projection to ``H`` query and
    ``2 x Hkv`` key/value heads, rotary positions where the layer has
    them, causal (or causal-window) structure. Returns the output
    and the normed input (the router of a routed block reads it)."""
    cfg = self.config
    if self.tp_axis is not None:
        raise ValueError("a token model has no manual tensor "
                         "parallelism")
    dt = _dtype(cfg)
    hq, hkv = cfg.num_heads, cfg.kv_heads
    dense = functools.partial(
        nn.DenseGeneral, use_bias=cfg.attn_bias, dtype=dt,
        param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(cfg.init_std),
        # an indexed block's weight gradients are taken in place
        **({"dot_general": _tied_product} if cfg.sa_topk else {}))
    y = _norm(cfg, "norm")(x)
    qkv = dense(features=(hq + 2 * hkv, cfg.head_dim), axis=-1,
                name="qkv")(y)               # [B, T, H + 2 Hkv, Dh]
    q, k, v = (qkv[:, :, :hq], qkv[:, :, hq:hq + hkv],
               qkv[:, :, hq + hkv:])
    if cfg.qk_norm:
        # over the columns of each head, one learned scale a projection
        q = nn.RMSNorm(epsilon=cfg.ln_epsilon, dtype=dt, name="q_norm")(q)
        k = nn.RMSNorm(epsilon=cfg.ln_epsilon, dtype=dt, name="k_norm")(k)
    if cfg.layer_rope(self.layer):
        with jax.named_scope("rope"):
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
    kind, window = cfg.attention_kind(self.layer)
    dropout_rng = None
    if train and cfg.attn_dropout > 0.0:
        dropout_rng = self.make_rng("dropout")
    if kind == "causal_topk":
        if dropout_rng is not None:
            raise ValueError("sparse attention has no attention dropout")
        attn, indexer_loss = _indexed_attention(self, y, q, k, v, dense)
    else:
        attn = dot_product_attention(
            q, k, v, impl=cfg.attention_impl, kind=kind, window=window,
            dropout_rate=cfg.attn_dropout, dropout_rng=dropout_rng,
            deterministic=not train, softmax=cfg.attention_softmax)
    out = dense(features=cfg.embedding_dim, axis=(-2, -1),
                name="out")(attn)
    if kind == "causal_topk":
        # The rest of the model waits for this layer's alignment loss.
        # Nothing else orders the two, and the compiler puts what only
        # the objective reads as late as it can: every layer's pass after
        # the last layer's core, each layer's selection (256 MiB of
        # bytes), q and k held until then. On the block's result and not
        # inside ``sparse_attention`` on the core's, which the backward
        # pass reads: there a checkpoint takes the barrier again (the
        # loss has to be kept by name for it), and the out projection
        # fuses with the residual and its norm and keeps its result in
        # two layouts, 0.35 GiB more for ``keye2_train_16k``'s step
        # (PERF.md section 6, PR 35, After the review).
        out, _ = jax.lax.optimization_barrier((out, indexer_loss))
    return out, y


def _indexed_attention(self: MultiHeadSelfAttentionBlock, y, q, k, v,
                       dense):
    """(Called from :func:`_token_attention`.) Sparse attention chosen
    by an indexer (:mod:`..ops.sparse_attention`): ``index_q`` (``J``
    heads of ``Di``), ``index_k`` (ONE head, LayerNorm'd) and
    ``index_w`` (a weight a head, scaled ``J^-1/2 Di^-1/2``) read the
    block's normed input with the gradient cut, q and k of the indexer
    are turned by their positions over all ``Di`` columns, and every
    query attends to the ``sa_topk`` causal keys of the largest score.
    The indexer's alignment loss and the counters of the selection are
    sown into ``dsa_stats`` (kept when the caller makes it mutable: the
    objective adds the loss there, :func:`_with_indexer_loss`); the
    selection itself into ``dsa_probe``. Scopes: ``msa/indexer/proj``,
    ``msa/indexer/scores``, ``msa/indexer/select``, ``msa/attn_core``,
    ``msa/indexer_loss``."""
    from ..ops.sparse_attention import sparse_attention
    cfg = self.config
    heads, width = cfg.sa_index_heads, cfg.sa_index_head_dim
    # The indexer reads the block's normed input as a constant: the
    # product cuts the gradient itself.
    dense = functools.partial(
        dense, dot_general=functools.partial(_tied_product, cut_input=True))
    with jax.named_scope("indexer/proj"):
        q_idx = dense(features=(heads, width), name="index_q")(y)
        k_idx = nn.LayerNorm(epsilon=cfg.ln_epsilon, dtype=_dtype(cfg),
                             name="index_k_norm")(
            dense(features=width, name="index_k")(y))
        # float32: the weights order the scores
        w_idx = dense(features=heads, dtype=jnp.float32, name="index_w")(
            y.astype(jnp.float32)) * (heads ** -0.5 * width ** -0.5)
        q_idx = rotary(q_idx, cfg.rope_theta)
        k_idx = rotary(k_idx[:, :, None], cfg.rope_theta)[:, :, 0]
    attn, loss, stats = sparse_attention(
        q, k, v, q_idx, k_idx, w_idx, topk=cfg.sa_topk, chunk=cfg.sa_chunk,
        impl=cfg.attention_impl)
    self.sow("dsa_probe", "mask", stats.pop("mask"))
    self.sow("dsa_stats", "indexer_loss", loss)
    for key, value in stats.items():
        self.sow("dsa_stats", key, value)
    return attn, loss


def _latent_attention(self: MultiHeadSelfAttentionBlock, x: jax.Array,
                      train: bool):
    """(A free function for the reason :func:`_token_attention` is.)

    Latent attention: with ``a = norm(x)``, queries ``norm_q(a W_qa)
    W_qb`` and keys/values ``norm_kv((a W_kva)[:r]) W_kvb``, a head's
    query/key being ``qk_nope_head_dim`` columns of those and
    ``qk_rope_head_dim`` rotary columns — the key's rotary part the ONE
    head ``(a W_kva)[r:]`` that every query head reads. k and v are made
    whole per head (the absorbed form is for decoding and is not
    built), and every array between the latent products and the flash
    kernels lies as the kernels read it, a head a column block of
    ``[B, T, H x Dh]``: the up-projections and ``out`` are flat products
    (:class:`_FlatProduct`), the query's rotary part is turned in place
    (:func:`_flat_rotary`), the key is assembled by its own product
    (:class:`_LatentKeyValueUp`). As ``[B, T, H, Dh]`` arrays XLA:TPU
    lays the heads on sublanes and copies each for the kernels: ten
    passes over HBM a block (PERF.md, PR 33). Scopes:
    ``msa/qkv/{q_down,q_up,kv_down,kv_up}`` with their inner norms,
    ``msa/rope``, ``msa/attn_core``, ``msa/out``. Returns the output and
    the normed input, as :func:`_token_attention`."""
    cfg = self.config
    if self.tp_axis is not None:
        raise ValueError("a token model has no manual tensor "
                         "parallelism")
    if train and cfg.attn_dropout > 0.0:
        raise ValueError("latent attention has no attention dropout")
    rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    heads, dh = cfg.num_heads, cfg.head_dim
    dense = functools.partial(
        nn.DenseGeneral, use_bias=False, dtype=_dtype(cfg),
        param_dtype=jnp.float32,
        kernel_init=nn.initializers.normal(cfg.init_std))
    flat = functools.partial(_FlatProduct, init_std=cfg.init_std,
                             dtype=_dtype(cfg))
    y = _norm(cfg, "norm")(x)
    with jax.named_scope("qkv"):
        with jax.named_scope("q_down"):
            c_q = _norm(cfg, "q_norm")(
                dense(features=cfg.q_lora_rank, name="q_down")(y))
        with jax.named_scope("kv_down"):
            c = dense(features=rank + rope, name="kv_down")(y)
            c_kv, k_rope = _norm(cfg, "kv_norm")(c[..., :rank]), c[..., rank:]
        q = flat((cfg.q_lora_rank, heads, dh), name="q_up")(c_q)
    with jax.named_scope("rope"):
        q = _flat_rotary(q, heads, rope, cfg.rope_theta)
        k_rope = _flat_rotary(k_rope, 1, rope, cfg.rope_theta)
    with jax.named_scope("qkv"):
        k, v = _LatentKeyValueUp(cfg, name="kv_up")(c_kv, k_rope)
    kind, window = cfg.attention_kind(self.layer)
    # (the dispatch takes [B, T, H, Dh]; the flash path's own reshape to
    # [B, T, H x Dh] meets these and the pair cancels in the compiler)
    attn = dot_product_attention(
        *(a.reshape(a.shape[:2] + (heads, -1)) for a in (q, k, v)),
        impl=cfg.attention_impl, kind=kind, window=window,
        deterministic=True, softmax=cfg.attention_softmax)
    out = flat((heads, cfg.v_head_dim, cfg.embedding_dim), n_in=2,
               name="out")(attn.reshape(attn.shape[:2] + (-1,)))
    return out, y


class ShortConvBlock(nn.Module):
    """A conv layer's mixer (LFM2's gated short convolution), in the
    attention's place and with its wiring: pre-norm, the residual added
    by :class:`TransformerEncoderBlock`, the normed input handed on to a
    routed block's router. ``[B | C | u] = norm(x) W_in`` (``in_proj``,
    ``D -> 3D``), ``C * conv(B * u)`` over the last ``conv_kernel``
    positions with ``taps [K, D]`` (:func:`..ops.short_conv.short_conv`),
    ``W_out`` (``out_proj``, ``D -> D``); no bias. Scopes
    ``conv/in_proj``, ``conv/mix``, ``conv/out_proj``, inside the
    block's mixer scope (the module is named ``msa``, as the attention
    it replaces). The mixer's output is sown into ``conv_probe`` (kept
    where the caller makes it mutable: the benchmark compares layer 0's
    with its reference)."""

    config: ViTConfig
    tp_axis: Optional[str] = None
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False,
                 with_normed: bool = False):
        from ..ops.short_conv import short_conv
        if self.tp_axis is not None:
            raise ValueError("a token model has no manual tensor "
                             "parallelism")
        cfg = self.config
        d = cfg.embedding_dim
        init = nn.initializers.normal(cfg.init_std)
        dense = functools.partial(nn.Dense, use_bias=False, dtype=_dtype(cfg),
                                  param_dtype=jnp.float32, kernel_init=init)
        y = _norm(cfg, "norm")(x)
        with jax.named_scope("conv"):
            bcu = dense(3 * d, name="in_proj")(y)        # [B, T, 3D]
            taps = self.param("taps", init, (cfg.conv_kernel, d),
                              jnp.float32)
            with jax.named_scope("mix"):
                mixed = short_conv(bcu, taps)
            out = dense(d, name="out_proj")(mixed)
        self.sow("conv_probe", "out", out)
        return (out, y) if with_normed else out


def _turn_heads(x, heads, rope, theta, sign):
    """:func:`_flat_rotary`'s pass, by ``sign`` times the angle."""
    b, t, width = x.shape
    half = rope // 2
    freq = theta ** (-jnp.arange(0, rope, 2, dtype=jnp.float32) / rope)
    angle = freq[:, None] * jnp.arange(t, dtype=jnp.float32)[None, :]
    cos, sin = jnp.cos(angle), sign * jnp.sin(angle)        # [half, T]
    # tokens on the lanes: a head's columns are rows, ``half`` a group,
    # and the turned ones its last two groups
    groups = width // heads // half
    xt = jnp.swapaxes(x, 1, 2).reshape(b, heads, groups, half, t)
    x1, x2 = (xt[:, :, g].astype(jnp.float32)
              for g in (groups - 2, groups - 1))
    # ``rotary``'s ``x * cos + [-x2, x1] * sin``, half by half
    y = jnp.stack([x1 * cos + (-x2) * sin, x2 * cos + x1 * sin], axis=2)
    xt = jax.lax.dynamic_update_slice_in_dim(
        xt, y.astype(x.dtype), groups - 2, axis=2)
    return jnp.swapaxes(xt.reshape(b, width, t), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _flat_rotary(x: jax.Array, heads: int, rope: int,
                 theta: float) -> jax.Array:
    """:func:`rotary` of the last ``rope`` columns of every head of
    ``x [B, T, heads x Dh]`` (a head's other columns stay), with that
    function's arithmetic (float32, rounded once): bit-equal to
    ``concatenate([x[..., :nope], rotary(x[..., nope:])])`` on
    ``[B, T, heads, Dh]``, without that array.

    Taken with the tokens on the lanes, ``[B, heads x Dh, T]``: there the
    table is ``[rope / 2, T]`` and broadcasts over the heads as over any
    major dimension, a column's partner is ``rope / 2`` ROWS away, and
    the turned rows are updated where they lie. The product before it
    writes that layout at no cost, and one transposing pass brings the
    result to the kernels' (in the backward pass the cotangent takes the
    same way back). What was tried on ``[T, heads x Dh]`` itself: one
    elementwise pass with tables of period ``Dh`` has the compiler write
    those tables out at full size, 320 MiB each, and a partner 32 lanes
    away as shifted copies; head by head on ``[T, 64]`` windows it is 40
    small ops a pass, each moving four times its bytes in lane padding
    (PERF.md, PR 33). The transpose is the turn by the opposite angle:
    the same pass over the cotangent."""
    return _turn_heads(x, heads, rope, theta, 1.0)


_flat_rotary.defvjp(
    lambda x, *static: (_turn_heads(x, *static, 1.0), None),
    lambda heads, rope, theta, _, g: (
        _turn_heads(g, heads, rope, theta, -1.0),))


def _drawn_flat(initializer, rows: int):
    """``nn.DenseGeneral``'s way with a kernel's initialiser: drawn at
    the flat shape ``[rows, columns]`` (fan-in and fan-out are the
    GEMM's), then given the parameter's."""
    def init(rng, shape, dtype=jnp.float32):
        return initializer(
            rng, (rows, int(np.prod(shape)) // rows), dtype).reshape(shape)
    return init


class _FlatProduct(nn.Module):
    """A bias-free ``nn.DenseGeneral`` by its parameter (``kernel`` of
    ``shape``: names, shapes and initial values of the tree are that
    module's) whose product is taken flat and LEFT flat: ``[...,
    prod(in)] x [prod(in), prod(out)]``, the first ``n_in`` dims of
    ``shape`` contracted. Latent attention's heads are made and read
    this way, as column blocks of ``[B, T, H x Dh]``, which is how the
    flash kernels read them (:class:`_FlatDenseGeneral` has the why)."""

    shape: Tuple[int, ...]
    init_std: float
    dtype: jnp.dtype
    n_in: int = 1

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        rows = int(np.prod(self.shape[:self.n_in]))
        kernel = self.param("kernel", _drawn_flat(
            nn.initializers.normal(self.init_std), rows),
                            self.shape, jnp.float32)
        # Two things the compiler is told, both read from the step
        # compiled for the v5e (PERF.md, PR 33). Without the barrier it
        # folds the reshape into the weight-gradient product, whose other
        # operand is then the activation as [H, Dh, T]: a transposed copy
        # of 160 MiB a product. Without the layout it relays the float32
        # parameter for the reshape, and with it its moments and update
        # (270 MiB alive at the step's peak), where the converted kernel
        # is 8 MB.
        kernel = with_layout_constraint(
            kernel.astype(self.dtype),
            Layout(major_to_minor=tuple(range(kernel.ndim))))
        return jnp.dot(x.astype(self.dtype), jax.lax.optimization_barrier(
            kernel.reshape(rows, -1)))


class _LatentKeyValueUp(nn.Module):
    """``kv_up``: ``nn.DenseGeneral``'s kernel ``[rank, H, nope + v]``,
    and from it k and v as two flat products, each whole where the flash
    kernels read it. k's product places the ONE rotary head under every
    head's own columns itself: ``k_rope`` stands beside the latent, and
    under the kernel's k columns (zeros where the rotary part goes) stand
    rows of 0 / 1 that copy it to each head. ``k_nope + 0`` and ``0 +
    k_rope x 1`` are exact, so k is bit-equal to the concatenation; the
    rotary head's gradient, the sum over the heads, is the same product
    transposed."""

    config: ViTConfig

    @nn.compact
    def __call__(self, c_kv: jax.Array, k_rope: jax.Array):
        cfg, dt = self.config, _dtype(self.config)
        rank, nope, rope = (cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim)
        kernel = self.param(
            "kernel",
            _drawn_flat(nn.initializers.normal(cfg.init_std), rank),
            (rank, cfg.num_heads, nope + cfg.v_head_dim),
            jnp.float32).astype(dt)
        place = jnp.pad(jnp.eye(rope, dtype=dt), ((0, 0), (nope, 0)))
        # (the barriers: as in _FlatProduct)
        k = jnp.dot(
            jnp.concatenate([c_kv, k_rope], axis=-1).astype(dt),
            jax.lax.optimization_barrier(jnp.concatenate([
                jnp.pad(kernel[..., :nope], ((0, 0), (0, 0), (0, rope))
                        ).reshape(rank, -1),
                jnp.tile(place, (1, cfg.num_heads))])))
        v = jnp.dot(c_kv.astype(dt), jax.lax.optimization_barrier(
            kernel[..., nope:].reshape(rank, -1)))
        return k, v


# What a latent-attention block keeps of its attention for the backward
# pass: the core's output and row statistic (named where they are made,
# ``ops/attention.py`` / ``ops/flash_attention.py``), and not q, k and v
# — three ``[T, H x head]`` arrays a layer, where the latents they are
# taken from again are a sixth of one. The projections before the core
# are computed twice (2.6% of GLM-4.7-Flash's step FLOPs); the core is
# not.
_KEEP_OF_LATENT_ATTENTION = jax.checkpoint_policies.save_only_these_names(
    "attn_core_out", "attn_core_lse")


# What a block whose attention an indexer selects keeps: the core's
# output and row statistic as above, the selection (one int8 ``[T, T]`` a
# layer: taking it again is the bisection again) and the alignment loss's
# three gradients, which its forward pass already holds. The projections,
# the norms and the rotary embedding are computed twice.
_KEEP_OF_INDEXED_ATTENTION = jax.checkpoint_policies.save_only_these_names(
    "attn_core_out", "attn_core_lse", "indexer_mask", "indexer_loss_grad")


def _flat_projections(cfg: ViTConfig, qkv_shape, train: bool) -> bool:
    """Whether this block's projections are taken over flattened feature
    dims (:class:`_FlatDenseGeneral`): where the short-sequence kernel
    will serve the block's call (asked of the dispatch's own
    :func:`..ops.attention.choose`, before the projection exists), and
    no mesh axis splits the heads (a head-sharded ``[D, 3, H, Dh]``
    kernel has no flat ``[D, 3*D]`` sharding; there the kernel still
    runs, per shard, on the 5-D projection)."""
    served, _ = choose(qkv_shape, _dtype(cfg), impl=cfg.attention_impl,
                       dropout_rate=cfg.attn_dropout,
                       deterministic=not train)
    if served != "short":
        return False
    part = partition.current()
    return part is None or part.size(part.model_axis) == 1


class _FlatDenseGeneral(nn.Module):
    """``nn.DenseGeneral`` with the same parameters (names, shapes, the
    flat-shape initialisation) and the same product, taken as ONE 2-D
    GEMM over flattened input and output feature dims, the bias added to
    the flat result.

    Why it exists: with ``nn.DenseGeneral`` XLA:TPU sees the projection's
    result as ``[B, T, 3, H, Dh]`` — minor dimension 64, half a lane
    tile — and lays it out batch-minor (B/16) or token-minor (L/16). The
    attention kernel reads ``[B, T, 3*D]`` row-major, so every layer paid
    four transposing copies (qkv, o, do, dqkv: 1.5 GB a layer on B/16).
    Given a flat GEMM whose result goes straight to the kernel, the
    compiler emits the kernel's layout from the GEMM itself, and reads
    the kernel's results the same way (compiled for the v5e in
    ``tests/test_v5e_compile.py``; PERF.md, PR 26)."""

    features: Union[int, Tuple[int, ...]]
    axis: Union[int, Tuple[int, ...]] = -1    # trailing axes of the input
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        out_shape = tuple(np.atleast_1d(self.features).tolist())
        n_in = np.atleast_1d(self.axis).size
        lead, in_shape = x.shape[:x.ndim - n_in], x.shape[x.ndim - n_in:]
        flat = (int(np.prod(in_shape)), int(np.prod(out_shape)))

        kernel = self.param(
            "kernel", _drawn_flat(nn.initializers.lecun_normal(), flat[0]),
            in_shape + out_shape, jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, out_shape,
                          jnp.float32)
        y = jnp.dot(x.reshape(lead + flat[:1]).astype(self.dtype),
                    kernel.reshape(flat).astype(self.dtype))
        y = y + bias.reshape(flat[1:]).astype(self.dtype)
        return y.reshape(lead + out_shape)


class _DenseParams(nn.Module):
    """Declares ``kernel``/``bias`` params identical to ``nn.Dense``'s
    (same names, shapes, initializers) WITHOUT computing the matmul — the
    fused MLP path reads them and hands the compute to the Pallas kernel,
    so checkpoints and TP sharding rules are indifferent to ``mlp_impl``."""

    shape: tuple  # (features_in, features_out)

    @nn.compact
    def __call__(self):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            self.shape, jnp.float32)
        bias = self.param("bias", nn.initializers.zeros,
                          (self.shape[1],), jnp.float32)
        return kernel, bias


class _LnParams(nn.Module):
    """``scale``/``bias`` params identical to ``nn.LayerNorm``'s, compute
    delegated (to the fused LN+MLP kernel)."""

    dim: int

    @nn.compact
    def __call__(self):
        scale = self.param("scale", nn.initializers.ones, (self.dim,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (self.dim,),
                          jnp.float32)
        return scale, bias


def _mlp_fused(cfg: ViTConfig) -> bool:
    """Whether ``config.mlp_impl`` selects the Pallas path here."""
    impl = cfg.mlp_impl
    if cfg.norm != "layernorm":
        return False        # the kernel's fused norm is LayerNorm
    return impl == "fused" or (impl == "auto"
                               and jax.default_backend() == "tpu")


class MLPBlock(nn.Module):
    """Pre-norm MLP: LN → Linear(D→mlp) → GELU → Dropout → Linear(mlp→D) → Dropout.

    Reference: ``models/vit.py:100-131``. GELU is exact (erf-based) to match
    ``torch.nn.GELU``'s default.

    ``config.mlp_impl`` selects the execution path: ``"xla"`` is two
    ``nn.Dense`` GEMMs; ``"fused"``/``"auto"``-on-TPU routes fc1→GELU→
    hidden-dropout→fc2 through the Pallas kernel (:mod:`..ops.fused_mlp`)
    so the ``[B·T, mlp_size]`` hidden activation never round-trips HBM.
    Both paths declare IDENTICAL param trees (fc1/fc2 kernel+bias).

    ``include_residual``: the block OWNS the ``+ x`` residual add when
    True (set by :class:`TransformerEncoderBlock`, which then never adds
    it itself — one owner, no mode-dependent double-add). It also unlocks
    the deepest fusion: the whole half-block (LN through residual) as one
    kernel (:func:`..ops.fused_mlp.fused_ln_mlp_residual`). The DEFAULT
    False keeps the reference's standalone contract — this module returns
    the MLP output only (reference ``models/vit.py:128-131``) — on every
    backend and impl.

    ``tp_axis``: manual TP inside ``shard_map`` (see
    :class:`MultiHeadSelfAttentionBlock`): fc1/fc2 arrive hidden-sliced;
    fc2's partial sum is ``psum``'d BEFORE the final dropout so every
    shard applies the identical mask to the identical replicated tensor.
    The fused core kernel composes: it computes the hidden-sliced partial
    locally and the psum stays outside (full-block fusion is skipped —
    the residual must follow the psum).

    On a mesh (GSPMD, no ``tp_axis``) XLA cannot split the kernels, so
    they shard_map themselves (:mod:`..ops.partition`). A mesh with a
    model axis takes the same hidden-sliced core-kernel form, the psum
    then inside :func:`..ops.fused_mlp.fused_mlp`.
    """

    config: ViTConfig
    tp_axis: Optional[str] = None
    include_residual: bool = False

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        fused = _mlp_fused(cfg)
        dt = _dtype(cfg)
        part = partition.current()
        hidden_sliced = self.tp_axis is not None or (
            part is not None and part.size(part.model_axis) > 1)

        if fused and self.include_residual and not hidden_sliced:
            # One kernel for the whole half-block, INCLUDING the
            # residual add.
            from ..ops.fused_mlp import fused_ln_mlp_residual
            scale, bias = _LnParams(cfg.embedding_dim, name="norm")()
            w1, b1 = _DenseParams((cfg.embedding_dim, cfg.mlp_size),
                                  name="fc1")()
            w2, b2 = _DenseParams((cfg.mlp_size, cfg.embedding_dim),
                                  name="fc2")()
            dropout_rng = None
            if train and cfg.mlp_dropout > 0.0:
                dropout_rng = self.make_rng("dropout")
            return fused_ln_mlp_residual(
                x, scale, bias, w1.astype(dt), b1.astype(dt),
                w2.astype(dt), b2.astype(dt), eps=cfg.ln_epsilon,
                dropout_rate=cfg.mlp_dropout, dropout_rng=dropout_rng,
                deterministic=not train)

        y = _norm(cfg, "norm")(x)
        if fused:
            from ..ops.fused_mlp import fused_mlp
            w1, b1 = _DenseParams((cfg.embedding_dim, cfg.mlp_size),
                                  name="fc1")()
            w2, b2 = _DenseParams((cfg.mlp_size, cfg.embedding_dim),
                                  name="fc2")()
            dropout_rng = None
            if train and cfg.mlp_dropout > 0.0:
                dropout_rng = self.make_rng("dropout")
            y = fused_mlp(y, w1.astype(dt), b1.astype(dt), w2.astype(dt),
                          b2.astype(dt), dropout_rate=cfg.mlp_dropout,
                          dropout_rng=dropout_rng, deterministic=not train)
        else:
            y = nn.Dense(cfg.mlp_size, dtype=dt,
                         param_dtype=jnp.float32, name="fc1")(y)
            y = nn.gelu(y, approximate=False)
            y = Dropout(rate=cfg.mlp_dropout, deterministic=not train)(y)
            y = nn.Dense(cfg.embedding_dim, dtype=dt,
                         param_dtype=jnp.float32, name="fc2")(y)
        if self.tp_axis is not None:
            y = jax.lax.psum(y, self.tp_axis)
        y = Dropout(rate=cfg.mlp_dropout, deterministic=not train)(y)
        return y + x if self.include_residual else y


class _GatedMLP(nn.Module):
    """``(act(u W_gate) * (u W_up)) W_down``, bias-free, ``act`` the
    configuration's ``expert_activation``: a token model's dense
    feed-forward and the shared expert of a routed one (plain XLA GEMMs
    over every token)."""

    config: ViTConfig
    width: int

    @nn.compact
    def __call__(self, u: jax.Array) -> jax.Array:
        from ..ops import moe
        cfg = self.config
        dense = functools.partial(
            nn.Dense, use_bias=False, dtype=_dtype(cfg),
            param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(cfg.init_std))
        hidden = moe.gated(dense(self.width, name="gate")(u),
                           dense(self.width, name="up")(u),
                           cfg.expert_activation)
        return dense(cfg.embedding_dim, name="down")(hidden)


class GatedMLPBlock(nn.Module):
    """A token model's dense feed-forward, residual included: ``x +
    gated(norm(x))`` at ``dense_width`` (:class:`_GatedMLP`; the ViT's
    :class:`MLPBlock` is the GELU MLP with biases and its kernel)."""

    config: ViTConfig

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        cfg = self.config
        return x + _GatedMLP(cfg, cfg.dense_width, name="dense")(
            _norm(cfg, "norm")(x))


class RoutedMLPBlock(nn.Module):
    """Routed feed-forward in the MLP's place, residual included: ``x +
    shared(u) + sum over a token's held experts e of p_e (act(u
    W_gate,e) * (u W_up,e)) W_down,e`` with ``u = norm(x)``, ``act`` the
    configuration's ``expert_activation`` and ``shared`` one gated
    product of ``shared_experts`` widths over every token (absent at 0).

    The router reads what ``router_input`` names — ``routed_from``, the
    block's pre-attention normed input, or ``u`` itself — and routes
    over all ``num_experts`` by ``router_scoring``; this chip holds
    experts ``expert_offset .. + experts_held`` and adds their part only
    (:mod:`..ops.moe`). The counters of the routing are sown into the
    collection ``moe_stats`` (kept when the caller makes it mutable).
    """

    config: ViTConfig

    @nn.compact
    def __call__(self, x: jax.Array, routed_from: jax.Array,
                 train: bool = False) -> jax.Array:
        from ..ops import moe
        cfg = self.config
        d, f, held = (cfg.embedding_dim, cfg.expert_width,
                      cfg.num_experts_held)
        init = nn.initializers.normal(cfg.init_std)
        u = _norm(cfg, "norm")(x)
        if cfg.router_input == "block":
            routed_from = u
        with jax.named_scope("moe_router"):
            # float32 at full precision: the selection is a comparison,
            # and 64 outputs cost nothing.
            logits = nn.Dense(
                cfg.num_experts, use_bias=False, dtype=jnp.float32,
                param_dtype=jnp.float32, kernel_init=init,
                precision=jax.lax.Precision.HIGHEST, name="router")(
                routed_from.astype(jnp.float32))
        if cfg.router_scoring == "sigmoid":
            # Zero, and no gradient reaches it (it only moves the
            # selection); the balance rule that would update it between
            # steps is not built (ROADMAP R-A).
            bias = self.param("router_bias", nn.initializers.zeros,
                              (cfg.num_experts,), jnp.float32)
            ids, probs = moe.route(logits, cfg.experts_per_token,
                                   scoring="sigmoid", bias=bias,
                                   scale=cfg.router_scale)
            with jax.named_scope("moe_router"):
                # the normaliser: the selected scores' sum, a token
                self.sow("moe_stats", "score_sum", jnp.mean(jnp.sum(
                    jnp.take_along_axis(jax.nn.sigmoid(logits), ids, -1),
                    axis=-1)))
        else:
            ids, probs = moe.route(logits, cfg.experts_per_token)
        gate = self.param("gate", init, (held, d, f), jnp.float32)
        up = self.param("up", init, (held, d, f), jnp.float32)
        down = self.param("down", init, (held, f, d), jnp.float32)
        y, stats = moe.moe_experts(u, ids, probs, gate, up, down,
                                   expert_offset=cfg.expert_offset,
                                   num_experts=logits.shape[-1],
                                   activation=cfg.expert_activation)
        for key, value in stats.items():
            self.sow("moe_stats", key, value)
        if cfg.shared_experts:
            with jax.named_scope("moe_shared"):
                y = y + _GatedMLP(cfg, cfg.shared_experts * f,
                                  name="shared")(u)
        return x + y


class TransformerEncoderBlock(nn.Module):
    """Pre-norm residual encoder block: ``x = msa(x)+x; x = mlp(x)+x``.

    Reference: ``models/vit.py:133-169`` (residual wiring at :167-168).
    ``layer`` is the block's index: a token model's mixer (attention or a
    gated short convolution), rotary positions and attention kind are
    chosen per layer (``configs.ViTConfig``).
    """

    config: ViTConfig
    tp_axis: Optional[str] = None
    layer: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, train: bool = False) -> jax.Array:
        msa = MultiHeadSelfAttentionBlock
        if self.config.layer_mixer(self.layer) == "conv":
            msa = ShortConvBlock
        elif self.config.kv_lora_rank:
            msa = nn.remat(msa, static_argnums=(2, 3),
                           policy=_KEEP_OF_LATENT_ATTENTION)
        elif self.config.sa_topk:
            msa = nn.remat(msa, static_argnums=(2, 3),
                           policy=_KEEP_OF_INDEXED_ATTENTION)
        msa = msa(self.config, tp_axis=self.tp_axis, layer=self.layer,
                  name="msa")
        if self.config.layer_routed(self.layer):
            attn, normed = msa(x, train, True)
            return RoutedMLPBlock(self.config, name="mlp")(
                attn + x, normed, train)
        x = msa(x, train, False) + x
        if self.config.dense_layers:
            return GatedMLPBlock(self.config, name="mlp")(x, train)
        # The MLP half's residual is OWNED by MLPBlock (one owner on
        # every impl/backend; unlocks the full-half-block kernel).
        return MLPBlock(self.config, tp_axis=self.tp_axis,
                        include_residual=True, name="mlp")(x, train)


class ViTFeatureExtractor(nn.Module):
    """ViT backbone with no classifier: returns the final-LN token sequence.

    Reference: ``models/vit_no_classifier.py`` — byte-identical to the
    classifier model except the head is absent and ``forward`` returns the
    full LayerNorm'd ``[B, T, D]`` sequence (its :217-226). Used for
    linear-probe / transfer workloads.
    """

    config: ViTConfig

    @nn.compact
    def __call__(self, images: jax.Array, train: bool = False,
                 next_tokens: Optional[jax.Array] = None):
        """``next_tokens [B, T]`` (each position's next token) asks a
        model with a multi-token-prediction module for that module's
        hidden states too: ``(tokens, module's tokens)``."""
        cfg = self.config
        if cfg.vocab_size:
            # ``images`` are token ids [B, T]. The scope is the name the
            # device trace's table has for the input embedding.
            embed = TokenEmbedding(cfg, name="token_embedding")
            with jax.named_scope("patch_embedding"):
                x = embed(images)
        else:
            x = PatchEmbedding(cfg, name="patch_embedding")(images, train)
        block = TransformerEncoderBlock
        if cfg.remat:
            block = nn.remat(block, static_argnums=(2,))
        for i in range(cfg.num_layers):
            x = block(cfg, layer=i, name=f"encoder_block_{i}")(x, train)
        tokens = _norm(cfg, "encoder_norm")(x)
        # (initialising makes the module's parameters, asked for or not)
        if not cfg.mtp_modules or (next_tokens is None
                                   and not self.is_initializing()):
            return tokens
        with jax.named_scope("mtp"), jax.named_scope("patch_embedding"), \
                jax.named_scope("mtp_merge"):
            ahead = embed(images if next_tokens is None else next_tokens)
        drafted = MTPModule(cfg, name="mtp")(x, ahead, train)
        return tokens if next_tokens is None else (tokens, drafted)


class MTPModule(nn.Module):
    """One multi-token-prediction module (DeepSeek-V3, section 2.2): at
    position i the hidden state of the last block, before the final
    norm, and the shared embedding of token i + 1, each normed, side by
    side through ``eh_proj`` (``[embedding ; hidden]``, 2D -> D); one
    block of the last layer's kind with weights of its own (block
    ``num_layers``); a final norm of its own. The caller puts the
    model's head on the result, against token i + 2. Scopes:
    ``mtp/patch_embedding/mtp_merge`` (the module's input embedding: the
    lookup, ``norm_e``, ``norm_h``, ``eh_proj``),
    ``mtp/encoder_block_<n>``, ``mtp/norm_s``."""

    config: ViTConfig

    @nn.compact
    def __call__(self, hidden: jax.Array, ahead: jax.Array,
                 train: bool = False) -> jax.Array:
        """``hidden``: the last block's output; ``ahead``: the shared
        embedding's rows of each position's next token."""
        cfg = self.config
        with jax.named_scope("patch_embedding"), \
                jax.named_scope("mtp_merge"):
            merged = jnp.concatenate(
                [_norm(cfg, "norm_e")(ahead),
                 _norm(cfg, "norm_h")(hidden)], axis=-1)
            x = nn.Dense(cfg.embedding_dim, use_bias=False,
                         dtype=_dtype(cfg), param_dtype=jnp.float32,
                         kernel_init=nn.initializers.normal(cfg.init_std),
                         name="eh_proj")(merged)
        n = cfg.num_layers
        block = TransformerEncoderBlock
        if cfg.remat:
            block = nn.remat(block, static_argnums=(2,))
        x = block(cfg, layer=n, name=f"encoder_block_{n}")(x, train)
        # (named for the row of the device trace's table it belongs to)
        with jax.named_scope("encoder_norm"):
            return _norm(cfg, "norm_s")(x)


class ViT(nn.Module):
    """ViT classifier: backbone + Linear head on the pooled token.

    Reference: ``models/vit.py:172-236`` — classifier reads the CLS token
    only (``x[:, 0]``, its :235); ``config.pool="gap"`` additionally offers
    global-average-pool (no reference counterpart). Logits are float32.

    Params nest as ``{"backbone": ..., "head": ...}`` so transfer learning
    can swap/freeze the head without touching backbone paths
    (cf. reference main notebook cells 112-113).
    """

    config: ViTConfig

    @nn.compact
    def __call__(self, images: jax.Array, train: bool = False,
                 labels: Optional[jax.Array] = None):
        cfg = self.config
        if cfg.mtp_modules and labels is not None:
            return _two_term_loss(self, images, labels, train)
        backbone = ViTFeatureExtractor(cfg, name="backbone")
        tokens = backbone(images, train)
        # a tied head reads the embedding's table (one parameter, whose
        # gradient is the sum of both uses')
        table = (backbone.variables["params"]["token_embedding"]["embedding"]
                 if cfg.tie_embedding else None)
        if cfg.sa_topk and labels is not None:
            return _with_indexer_loss(
                self, LMHead(cfg, name="head")(tokens, labels, table=table))
        if cfg.vocab_size:
            return LMHead(cfg, name="head")(tokens, labels, table=table)
        if cfg.pool == "cls":
            pooled = tokens[:, 0]
        else:
            pooled = tokens.mean(axis=1)
        logits = nn.Dense(cfg.num_classes, dtype=jnp.float32,
                          param_dtype=jnp.float32, name="head")(
            pooled.astype(jnp.float32))
        return logits


def _two_term_loss(self: ViT, images: jax.Array, labels: jax.Array,
                   train: bool):
    """(Called from :class:`ViT`'s compact ``__call__``.) The objective
    of a model with a multi-token-prediction module: ``main +
    mtp_loss_weight x module``, both through the ONE head matrix (its
    gradient is the sum of both terms'). The module's target at position
    i is ``labels[i + 1]`` (token i + 2); a sequence's last position has
    none and is left out of the mean. Returns ``(objective, main's
    positions predicted right)`` as a one-term model does, and sows the
    terms into ``lm_stats``: ``main_loss``, ``mtp_loss``,
    ``mtp_top1_share`` (the module's positions whose largest logit is
    the target: how often a drafted token would be accepted)."""
    cfg = self.config
    tokens, drafted = ViTFeatureExtractor(cfg, name="backbone")(
        images, train, next_tokens=labels)
    head = LMHead(cfg, name="head")
    main, right = head(tokens, labels)
    further = jnp.roll(labels, -1, axis=1)    # (the last wraps: left out)
    counted = jnp.broadcast_to(
        jnp.arange(labels.shape[1]) < labels.shape[1] - 1, labels.shape)
    with jax.named_scope("mtp"):
        module, drafted_right = head(drafted, further, counted)
    self.sow("lm_stats", "main_loss", main)
    self.sow("lm_stats", "mtp_loss", module)
    self.sow("lm_stats", "mtp_top1_share",
             drafted_right / jnp.sum(counted))
    return main + cfg.mtp_loss_weight * module, right


def _with_indexer_loss(self: ViT, main_and_right):
    """(Called from :class:`ViT`'s compact ``__call__``.) The objective
    of a model whose attention an indexer selects: ``main +
    sum over layers of L_I`` (weight 1 a layer), the alignment losses the
    attention blocks sowed into ``dsa_stats``. The two terms' gradients
    fall on disjoint parameters: the selection passes none, and the
    indexer reads its input with the gradient cut. Where the caller did
    not make ``dsa_stats`` mutable (evaluation) nothing was sown and the
    objective is the main loss. Sows ``main_loss`` and ``indexer_loss``
    (the layers' mean) into ``lm_stats``."""
    from flax.traverse_util import flatten_dict
    main, right = main_and_right
    sown = flatten_dict(self.variables.get("dsa_stats", {}))
    terms = [v for path, vs in sorted(sown.items())
             if path[-1] == "indexer_loss" for v in vs]
    if not terms:
        return main, right
    self.sow("lm_stats", "main_loss", main)
    self.sow("lm_stats", "indexer_loss", sum(terms) / len(terms))
    return main + sum(terms), right


class LMHead(nn.Module):
    """A token model's head: float32 logits ``[B, T, V]`` at every
    position or, given ``labels [B, T]`` (each position's target),
    ``(mean cross entropy, positions predicted right)`` over the
    positions ``counted`` (all of them by default) without the logits
    ever being whole (:mod:`..ops.lm_loss`). Untied, a ``kernel [D, V]``
    of its own; tied (``tie_embedding``), no parameter: ``table`` is the
    token embedding's ``[V, D]``, read as it lies."""

    config: ViTConfig

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 labels: Optional[jax.Array] = None,
                 counted: Optional[jax.Array] = None,
                 table: Optional[jax.Array] = None):
        from ..ops import lm_loss
        cfg = self.config
        if cfg.tie_embedding:
            if labels is None:
                return jnp.einsum("btd,vd->btv", tokens,
                                  table.astype(tokens.dtype),
                                  preferred_element_type=jnp.float32)
            loss, kernel = lm_loss.tied_head_cross_entropy, table
        else:
            kernel = self.param(
                "kernel", nn.initializers.normal(cfg.init_std),
                (cfg.embedding_dim, cfg.vocab_size), jnp.float32)
            if labels is None:
                return jnp.dot(tokens, kernel.astype(tokens.dtype),
                               preferred_element_type=jnp.float32)
            loss = lm_loss.head_cross_entropy
        return loss(
            tokens.reshape(-1, cfg.embedding_dim), kernel,
            labels.reshape(-1),
            counted=None if counted is None else counted.reshape(-1))


def apply_tail(cfg: ViTConfig, params, tokens: jax.Array) -> jax.Array:
    """The model tail — final LayerNorm, cls/gap pooling, float32 head —
    applied with explicit params to encoder-output tokens.

    Mirrors :class:`ViT`'s compact tail (encoder_norm in
    :class:`ViTFeatureExtractor`, pool+head in :class:`ViT`) for callers
    that run the encoder outside the module — the pipeline-parallel apply
    (``parallel/pipeline.py``). Kept HERE, next to the modules it
    mirrors, and pinned equal to them by
    ``tests/test_pipeline.py::test_pipeline_forward_matches_standard``,
    so a tail change that misses one copy fails loudly.
    """
    x = nn.LayerNorm(epsilon=cfg.ln_epsilon, dtype=_dtype(cfg)).apply(
        {"params": params["backbone"]["encoder_norm"]}, tokens)
    pooled = x[:, 0] if cfg.pool == "cls" else x.mean(axis=1)
    return nn.Dense(cfg.num_classes, dtype=jnp.float32,
                    param_dtype=jnp.float32).apply(
        {"params": params["head"]}, pooled.astype(jnp.float32))


def create_model(config: ViTConfig, *, with_head: bool = True) -> nn.Module:
    """Factory matching the reference's two model files."""
    return ViT(config) if with_head else ViTFeatureExtractor(config)
