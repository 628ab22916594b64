"""Configuration system for the TPU-native ViT framework.

The reference keeps hyperparameters as notebook-cell literals and constructor
kwargs (reference ``models/vit.py:173-183``, ``going_modular/train.py:12-15``);
here they are frozen dataclasses so they can be hashed into ``jax.jit`` static
arguments, serialized into checkpoints, and driven from the CLI.

Presets follow Table 1 of the ViT paper (arXiv:2010.11929), which the reference
cites in its main notebook (cell 21).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    """Architecture hyperparameters for a Vision Transformer classifier.

    Mirrors the constructor surface of the reference ``ViT``
    (``models/vit.py:172-199``): image/patch geometry, depth, heads, widths,
    and the three dropout rates. Adds TPU-specific knobs (compute dtype,
    attention implementation, remat) that have no reference counterpart.
    """

    image_size: int = 224
    patch_size: int = 16
    color_channels: int = 3
    num_layers: int = 12
    num_heads: int = 12
    embedding_dim: int = 768
    mlp_size: int = 3072
    num_classes: int = 1000
    attn_dropout: float = 0.0
    mlp_dropout: float = 0.1
    embedding_dropout: float = 0.1
    # LayerNorm epsilon. 1e-6 is the ViT/torchvision convention; set 1e-5
    # when porting weights from models built on torch.nn.LayerNorm defaults
    # (like the reference's custom ViT) — the mismatch is visible on
    # low-variance rows (e.g. the CLS token early in training).
    ln_epsilon: float = 1e-6
    # --- TPU-native knobs (no reference counterpart) ---
    # Compute dtype for activations; params are kept in float32. bfloat16 is
    # native on the MXU and halves HBM traffic for activations.
    dtype: str = "bfloat16"
    # "xla" = the hand-rolled einsum attention of ops/attention.py, with
    # the [B,H,T,T] probabilities materialized;
    # "flash" = the Pallas flash-attention kernel in ops/flash_attention.py;
    # "auto" = on a TPU the short-sequence kernel pair of
    # ops/short_attention.py where the call allows it (no mask, no active
    # attention dropout, head size 64 or 128, a [T,T] tile that fits VMEM:
    # T = 197 does, T = 577 does not), flash where the materialized logits
    # would not fit HBM, xla for the rest and off the TPU
    # (ops/attention.py::choose).
    attention_impl: str = "auto"
    # MLP-block execution path: "xla" = two nn.Dense GEMMs with the hidden
    # activation materialized between them; "fused" = the Pallas fused
    # fc1->GELU->dropout->fc2 kernel (ops/fused_mlp.py — hidden tile stays
    # in VMEM, measured ~12% faster fwd+bwd on v5e at ViT-B shapes);
    # "auto" = fused on TPU, xla elsewhere. Param trees are identical
    # across paths; the hidden-dropout mask STREAM differs (positional
    # hash vs jax.random.bits — same statistics, see ops/fused_mlp.py).
    mlp_impl: str = "auto"
    # XLA-path softmax flavor: "saturating" (default) drops the row-max
    # read over the [B,H,T,T] logits — exact for logits <= ~96, saturates
    # (uniform over clamped entries, zero grad through them) beyond,
    # measured +1.7% step throughput (PERF.md r5); "exact" restores the
    # classic max-subtracted softmax, correct at ANY logit magnitude —
    # use it when training in regimes with documented attention-logit
    # growth (the ViT-22B/QK-norm failure mode). Flash/ring/ulysses
    # paths always carry their own exact online softmax.
    attention_softmax: str = "saturating"
    # Rematerialize encoder blocks to trade FLOPs for HBM (for huge configs).
    remat: bool = False
    # Pool strategy for classification: "cls" token (reference vit.py:235)
    # or "gap" (global average pool, used by some ViT variants).
    pool: str = "cls"
    # Explicit per-head dim. None (always, except inside the pipeline's
    # manual tensor parallelism) derives embedding_dim // num_heads; the
    # pipeline's head-LOCAL block config sets it so halving num_heads
    # keeps the true head width (parallel/pipeline.py).
    head_dim_override: int | None = None
    # --- block options of the one encoder (a decoder-only language model
    # is this encoder with them set; every default is the ViT's) ---
    # Rows of the token embedding and of the untied per-position head
    # held here. 0 = an image model (patches in, one pooled class out);
    # > 0 = tokens in, next-token logits out at every position, and
    # attention is causal. A vocabulary shared over chips is this chip's
    # slice: ids, logits and loss are over the slice.
    vocab_size: int = 0
    # Positions of a token sequence (the image geometry is unused then).
    max_seq_len: int = 0
    # "layernorm" (scale + bias) or "rmsnorm" (scale only), at
    # ``ln_epsilon``.
    norm: str = "layernorm"
    # Key/value heads; None = ``num_heads``. Fewer makes the projection
    # grouped-query: query head g reads key/value head g // (H // Hkv).
    num_kv_heads: int | None = None
    # Biases on the attention projections.
    attn_bias: bool = True
    # Per layer, 1 = rotary positions on q and k (rotate-half over the
    # whole head, ``rope_theta``), 0 = none. () = none anywhere. A layout
    # shorter than the depth repeats (one period is enough).
    rope_layout: Tuple[int, ...] = ()
    rope_theta: float = 10000.0
    # Per layer, 1 = causal attention over the last ``sliding_window``
    # keys only, 0 = over every earlier key. Token models only.
    sliding_window_layout: Tuple[int, ...] = ()
    sliding_window: int = 0
    # Routed feed-forward in place of the MLP: ``num_experts`` > 0 routes
    # every token to ``experts_per_token`` of ``num_experts`` by its
    # router scores (``router_scoring``), each expert a gated
    # ``embedding_dim -> expert_width -> embedding_dim``
    # (``expert_activation`` on the gate). This chip holds experts
    # ``expert_offset ..+ experts_held`` and computes their part of the
    # result (None = all of them).
    num_experts: int = 0
    experts_per_token: int = 0
    expert_width: int = 0
    experts_held: int | None = None
    expert_offset: int = 0
    # "softmax": the largest router logits, softmax over the selected.
    # "sigmoid": scores ``sigmoid(logits)``; selected by score + a
    # correction bias (a parameter that takes no gradient), weighted by
    # the scores alone, normalised over the selected and scaled by
    # ``router_scale``.
    router_scoring: str = "softmax"
    router_scale: float = 1.0
    # What the router reads: "attention" = the block's pre-attention
    # normed input, "block" = the feed-forward's own normed input.
    router_input: str = "attention"
    # The gate's activation in every gated feed-forward (routed, shared,
    # dense): "relu" or "silu".
    expert_activation: str = "relu"
    # Experts of ``expert_width`` that every token passes, beside the
    # routed ones (one gated product of that many widths, held whole).
    shared_experts: int = 0
    # The layer kind per layer: the first ``dense_layers`` blocks of a
    # routed model have a gated dense feed-forward of ``dense_width``
    # instead (bias-free, ``expert_activation``).
    dense_layers: int = 0
    dense_width: int = 0
    # Latent attention (``kv_lora_rank`` > 0): queries through a
    # ``q_lora_rank`` latent and keys/values through a ``kv_lora_rank``
    # latent, each with a norm inside; a head's query/key is
    # ``qk_nope_head_dim`` columns from the latent and
    # ``qk_rope_head_dim`` rotary columns, the key's rotary part ONE
    # head shared by all query heads; values ``v_head_dim`` a head.
    # ``head_dim`` is the two parts' sum (no ``head_dim_override``), and
    # ``v_head_dim`` must equal it until the core takes unequal sizes.
    # The heads are taken again from the latents in the backward pass.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Multi-token prediction: that many modules after the last block
    # (one is built), each the shared embedding of the next token merged
    # with the hidden state, one block of the last layer's kind, a norm
    # and the shared head, predicting one token further; the objective
    # is main + ``mtp_loss_weight`` x the modules' mean.
    mtp_modules: int = 0
    mtp_loss_weight: float = 0.3
    # Sparse attention chosen by an indexer (``sa_topk`` > 0; DeepSeek
    # sparse attention): every query attends to the ``sa_topk`` causal
    # keys (all of them while it has no more) that a learned indexer
    # scores highest: ``sa_index_heads`` query heads of
    # ``sa_index_head_dim`` over ONE shared key head, read off the
    # block's normed input with the gradient cut. The indexer learns
    # from its own alignment loss alone (the KL from the main
    # attention's head-mean probabilities over the selected keys), added
    # to the objective at weight 1 a layer. ``sa_chunk``
    # query rows are scored at a time (the ``[T, T]`` scores are never
    # whole); it changes no result.
    sa_topk: int = 0
    sa_index_heads: int = 0
    sa_index_head_dim: int = 0
    sa_chunk: int = 512
    # RMSNorm with a learned scale over the columns of each query and
    # key head, before the rotary embedding (token models).
    qk_norm: bool = False
    # Per layer, 1 = a gated short convolution takes the attention's place
    # (LFM2's mixer: ``[B | C | u] = norm(x) W_in``, ``C * conv(B * u)``,
    # a depthwise causal convolution over the last ``conv_kernel``
    # positions, ``W_out``; :mod:`..ops.short_conv`), 2 = a Mamba-2
    # state-space layer (``models/vit.py::MambaBlock``), 0 = attention.
    # () = attention everywhere. A layout shorter than the depth repeats.
    # Token models only; a conv or state-space layer has no rotary
    # positions or window.
    mixer_layout: Tuple[int, ...] = ()
    conv_kernel: int = 3
    # A Mamba-2 layer: ``ssm_heads`` heads of ``ssm_head_dim`` columns,
    # each with a ``[ssm_head_dim, ssm_state]`` state; ``ssm_groups``
    # groups of heads share their B and C; a depthwise causal convolution
    # of ``ssm_conv_kernel`` taps (with a bias) over ``[x | B | C]``; the
    # scan taken in chunks of ``ssm_chunk`` positions (:mod:`..ops.ssd`).
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 256
    # Granite's multipliers (token models): the embedding's rows times
    # ``embedding_multiplier``; each mixer's and feed-forward's output
    # times ``residual_multiplier`` before its residual add; the logits
    # divided by ``logits_scaling``. ``attn_scale``: the softmax scale of
    # attention (None = ``head_dim ** -0.5``), its ratio to the default
    # folded into q before the core.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    attn_scale: float | None = None
    # The head reads the token embedding's table (``[V, D]``, as it lies)
    # instead of a matrix of its own; the table's gradient is the sum of
    # the lookup's and the head's.
    tie_embedding: bool = False
    # Std of the normal initialiser of a token model's matrices (and of
    # a tied table's rows; an untied table's start at N(0, 1):
    # ``models/vit.py::TokenEmbedding``).
    init_std: float = 0.02

    def __post_init__(self):
        for name in ("rope_layout", "sliding_window_layout", "mixer_layout"):
            object.__setattr__(self, name, tuple(getattr(self, name)))
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"unknown norm {self.norm!r}")
        if self.num_heads % self.kv_heads != 0:
            raise ValueError(
                f"num_heads ({self.num_heads}) must be a multiple of "
                f"num_kv_heads ({self.kv_heads})")
        if self.vocab_size and self.max_seq_len <= 0:
            raise ValueError("a token model needs max_seq_len")
        if any(self.sliding_window_layout) and not (
                self.vocab_size and self.sliding_window > 0):
            raise ValueError("sliding_window_layout needs a token model "
                             "and sliding_window > 0")
        if self.num_experts:
            held = self.num_experts_held
            if not (0 < self.experts_per_token <= self.num_experts
                    and self.expert_width > 0 and held > 0
                    and 0 <= self.expert_offset
                    and self.expert_offset + held <= self.num_experts):
                raise ValueError(
                    f"routed feed-forward: {self.experts_per_token} of "
                    f"{self.num_experts} experts of width "
                    f"{self.expert_width}, {held} held from "
                    f"{self.expert_offset}")
        if self.router_scoring not in ("softmax", "sigmoid") \
                or self.router_input not in ("attention", "block") \
                or self.expert_activation not in ("relu", "silu"):
            raise ValueError(
                f"router_scoring {self.router_scoring!r}, router_input "
                f"{self.router_input!r}, expert_activation "
                f"{self.expert_activation!r}")
        if self.shared_experts and not self.num_experts:
            raise ValueError("shared_experts belong to a routed model")
        if self.dense_layers and not self.num_experts \
                and self.dense_layers != self.num_layers:
            raise ValueError(
                "dense_layers: the leading layers of a routed model, or "
                "every layer (num_layers) of one without experts")
        if self.dense_layers and self.dense_width <= 0:
            raise ValueError("dense_layers needs dense_width")
        if self.kv_lora_rank and not (
                self.vocab_size and self.q_lora_rank > 0
                and self.qk_rope_head_dim % 2 == 0
                and self.kv_heads == self.num_heads
                and self.head_dim_override is None
                and self.qk_nope_head_dim + self.qk_rope_head_dim
                == self.v_head_dim):
            raise ValueError(
                "latent attention: a token model with q_lora_rank, equal "
                "head counts, no head_dim_override (the head size is "
                "qk_nope_head_dim + qk_rope_head_dim) and v_head_dim equal "
                "to it (one head size for the attention core)")
        if self.sa_topk and not (
                self.vocab_size and not self.kv_lora_rank
                and not any(self.sliding_window_layout)
                and self.sa_index_heads > 0 and self.sa_chunk > 0
                and self.sa_index_head_dim > 0
                and self.sa_index_head_dim % 2 == 0):
            raise ValueError(
                "sparse attention: a token model without latent attention "
                "or windows, with sa_index_heads of an even "
                "sa_index_head_dim and sa_chunk > 0")
        if self.mtp_modules not in (0, 1) or (
                self.mtp_modules and not self.vocab_size):
            raise ValueError("mtp_modules: 0, or 1 on a token model")
        layers = range(self.num_layers + self.mtp_modules)
        if not set(self.mixer_layout) <= {0, 1, 2}:
            raise ValueError(f"mixer_layout {self.mixer_layout}: 0 "
                             "attention, 1 conv, 2 ssm")
        if any(self.mixer_layout) and not (
                self.vocab_size and self.conv_kernel > 0
                and not self.kv_lora_rank and not self.sa_topk
                and not any(self.layer_mixer(i) != "attention"
                            and (self.layer_rope(i)
                                 or self.attention_kind(i)[1])
                            for i in layers)):
            raise ValueError(
                "gated short convolutions and state-space layers: a token "
                "model with conv_kernel > 0, no latent attention and no "
                "indexer, and no rotary positions or window on such a layer")
        if any(self.layer_mixer(i) == "ssm" for i in layers) and not (
                self.ssm_heads > 0 and self.ssm_head_dim > 0
                and self.ssm_state > 0 and self.ssm_groups > 0
                and self.ssm_heads % self.ssm_groups == 0
                and self.ssm_conv_kernel > 0 and self.ssm_chunk > 0):
            raise ValueError(
                "state-space layers: ssm_heads, ssm_head_dim, ssm_state, "
                "ssm_conv_kernel and ssm_chunk > 0, ssm_heads a multiple "
                "of ssm_groups")
        if (self.embedding_multiplier, self.residual_multiplier,
                self.logits_scaling, self.attn_scale) \
                != (1.0, 1.0, 1.0, None) and not (
                    self.vocab_size and not self.kv_lora_rank
                    and self.logits_scaling > 0
                    and (self.attn_scale is None or self.attn_scale > 0)
                    and (self.residual_multiplier == 1.0
                         or self.dense_layers == self.num_layers)):
            raise ValueError(
                "multipliers: a token model without latent attention, "
                "logits_scaling and attn_scale > 0, and a residual_"
                "multiplier only where every feed-forward is dense")
        if self.tie_embedding and not (self.vocab_size
                                       and not self.mtp_modules):
            raise ValueError("tie_embedding: a token model without a "
                             "multi-token-prediction module")
        if self.image_size % self.patch_size != 0:
            # Reference asserts the same invariant at models/vit.py:25.
            raise ValueError(
                f"image_size ({self.image_size}) must be divisible by "
                f"patch_size ({self.patch_size})"
            )
        if (self.head_dim_override is None and not self.kv_lora_rank
                and self.embedding_dim % self.num_heads != 0):
            raise ValueError(
                f"embedding_dim ({self.embedding_dim}) must be divisible by "
                f"num_heads ({self.num_heads})"
            )
        if self.pool not in ("cls", "gap"):
            raise ValueError(f"pool must be 'cls' or 'gap', got {self.pool!r}")
        if self.attention_impl not in ("xla", "flash", "auto"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.mlp_impl not in ("xla", "fused", "auto"):
            raise ValueError(f"unknown mlp_impl {self.mlp_impl!r}")
        if self.attention_softmax not in ("saturating", "exact"):
            raise ValueError(
                f"unknown attention_softmax {self.attention_softmax!r}")

    @property
    def num_patches(self) -> int:
        # Reference computes the same at models/vit.py:26.
        return (self.image_size // self.patch_size) ** 2

    @property
    def seq_len(self) -> int:
        """Token count including the CLS token (197 for 224/16); a token
        model's ``max_seq_len``."""
        if self.vocab_size:
            return self.max_seq_len
        return self.num_patches + (1 if self.pool == "cls" else 0)

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def num_experts_held(self) -> int:
        return (self.num_experts if self.experts_held is None
                else self.experts_held)

    def layer_routed(self, layer: int) -> bool:
        """Whether block ``layer``'s feed-forward is the routed one (the
        multi-token-prediction module's block is block ``num_layers``)."""
        return bool(self.num_experts) and layer >= self.dense_layers

    def layer_rope(self, layer: int) -> bool:
        """Whether block ``layer`` turns q and k by their positions."""
        lay = self.rope_layout
        return bool(lay and lay[layer % len(lay)])

    def layer_mixer(self, layer: int) -> str:
        """Block ``layer``'s mixer: ``"conv"`` (a gated short
        convolution), ``"ssm"`` (a Mamba-2 state-space layer) or
        ``"attention"``."""
        lay = self.mixer_layout
        kind = lay[layer % len(lay)] if lay else 0
        return ("attention", "conv", "ssm")[kind]

    def attention_kind(self, layer: int):
        """Block ``layer``'s attention as structure: ``("full", 0)``
        bidirectional, ``("causal", 0)``, ``("causal_window", w)`` or
        ``("causal_topk", k)``: the k causal keys an indexer selects."""
        if not self.vocab_size:
            return ("full", 0)
        if self.sa_topk:
            return ("causal_topk", self.sa_topk)
        lay = self.sliding_window_layout
        if lay and lay[layer % len(lay)]:
            return ("causal_window", self.sliding_window)
        return ("causal", 0)

    @property
    def head_dim(self) -> int:
        if self.head_dim_override is not None:
            return self.head_dim_override
        if self.kv_lora_rank:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.embedding_dim // self.num_heads

    def replace(self, **kw) -> "ViTConfig":
        return dataclasses.replace(self, **kw)


# --- Table 1 presets (ViT paper) ------------------------------------------
# The reference only builds ViT-Base/16 (its defaults, models/vit.py:173-183);
# Large and Huge are listed in its notebook cell 21 and are BASELINE.json
# stretch configs.

def vit_ti16(**kw) -> ViTConfig:
    """ViT-Tiny/16 (DeiT-Ti) — handy for tests and laptops."""
    return ViTConfig(num_layers=12, num_heads=3, embedding_dim=192,
                     mlp_size=768, **kw)


def vit_s16(**kw) -> ViTConfig:
    """ViT-Small/16 (DeiT-S)."""
    return ViTConfig(num_layers=12, num_heads=6, embedding_dim=384,
                     mlp_size=1536, **kw)


def vit_b16(**kw) -> ViTConfig:
    """ViT-Base/16 — the reference's default architecture."""
    return ViTConfig(**kw)


def vit_l16(**kw) -> ViTConfig:
    """ViT-Large/16."""
    return ViTConfig(num_layers=24, num_heads=16, embedding_dim=1024,
                     mlp_size=4096, **kw)


def vit_h14(**kw) -> ViTConfig:
    """ViT-Huge/14 — the pjit model-parallel stretch config."""
    kw.setdefault("patch_size", 14)
    return ViTConfig(num_layers=32, num_heads=16, embedding_dim=1280,
                     mlp_size=5120, **kw)


def smallthinker_21b_a3b_ep4(**kw) -> ViTConfig:
    """SmallThinker-21BA3B-Instruct (huggingface.co/PowerInfer), one
    chip's share of a deployment in which 4 chips share each layer: every
    published width (2560, 28 query / 4 key-value heads of 128, experts
    of width 768, top 6 of 64 router outputs, window 4096, theta 1.5e6),
    one period of its 52 layers (full and position-free, then three
    windowed with rotary positions), experts 0-15 of 64 and rows
    0-37,983 of the 151,936-row vocabulary. What is assumed of the source
    is in ``benchmark/configs/smallthinker-21b-a3b-ep4.json``."""
    base = dict(
        vocab_size=37984, max_seq_len=16384, num_layers=4, num_heads=28,
        num_kv_heads=4, head_dim_override=128, embedding_dim=2560,
        norm="rmsnorm", ln_epsilon=1e-6, attn_bias=False,
        rope_layout=(0, 1, 1, 1), rope_theta=1.5e6,
        sliding_window_layout=(0, 1, 1, 1), sliding_window=4096,
        num_experts=64, experts_per_token=6, expert_width=768,
        experts_held=16, expert_offset=0, attn_dropout=0.0,
        mlp_dropout=0.0, embedding_dropout=0.0)
    return ViTConfig(**{**base, **kw})


def lm_tiny(**kw) -> ViTConfig:
    """The same block at a size for tests: 4 layers of one period, width
    64, 4 query / 2 key-value heads of 16, 8 experts of width 32 of which
    4 are held, top 2, window 16, 256 rows, 64 positions."""
    return smallthinker_21b_a3b_ep4(**{**dict(
        vocab_size=256, max_seq_len=64, num_heads=4, num_kv_heads=2,
        head_dim_override=16, embedding_dim=64, sliding_window=16,
        num_experts=8, experts_per_token=2, expert_width=32,
        experts_held=4), **kw})


def glm_47_flash_ep8(**kw) -> ViTConfig:
    """GLM-4.7-Flash (huggingface.co/zai-org, ``glm4_moe_lite``), one
    chip's share of a deployment in which 8 chips share each layer:
    every published width (2048; latent attention 768 / 512 with 20
    heads of 192 + 64 rotary and 256 value columns, theta 1e6; the
    leading dense layer of 10240; experts of 1536, 4 of 64 by sigmoid
    scores scaled 1.8, one shared), the leading dense layer and 4 of the
    46 routed layers, the one multi-token-prediction module, experts 0-7
    of 64 and rows 0-19,359 of the 154,880-row vocabulary, at 16,384 of
    its 202,752 positions. What is assumed of the source is in
    ``benchmark/configs/glm-4.7-flash-ep8.json``."""
    base = dict(
        vocab_size=19360, max_seq_len=16384, num_layers=5, num_heads=20,
        embedding_dim=2048, norm="rmsnorm",
        ln_epsilon=1e-5, attn_bias=False, rope_layout=(1,),
        rope_theta=1e6, q_lora_rank=768, kv_lora_rank=512,
        qk_nope_head_dim=192, qk_rope_head_dim=64, v_head_dim=256,
        num_experts=64, experts_per_token=4, expert_width=1536,
        experts_held=8, expert_offset=0, router_scoring="sigmoid",
        router_scale=1.8, router_input="block", expert_activation="silu",
        shared_experts=1, dense_layers=1, dense_width=10240,
        mtp_modules=1, mtp_loss_weight=0.3, attn_dropout=0.0,
        mlp_dropout=0.0, embedding_dropout=0.0)
    return ViTConfig(**{**base, **kw})


def mla_tiny(**kw) -> ViTConfig:
    """The same blocks at a size for tests: a dense layer and 2 routed
    ones with the module, width 64, latents 24 / 16, 4 heads of 8 + 8
    and 16, dense 96, 8 experts of 32 of which 4 are held, top 2, one
    shared, 256 rows, 64 positions."""
    return glm_47_flash_ep8(**{**dict(
        vocab_size=256, max_seq_len=64, num_layers=3, num_heads=4,
        embedding_dim=64, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8,
        v_head_dim=16, num_experts=8, experts_per_token=2,
        expert_width=32, experts_held=4, dense_width=96), **kw})


def keye_vl_20_30b_a3b_ep8(**kw) -> ViTConfig:
    """Keye-VL-2.0-30B-A3B's language model (huggingface.co/Kwai-Keye,
    ``KeyeVL2``), one chip's share of a deployment in which 8 chips share
    each layer: every published width (2048; 32 query / 4 key-value heads
    of 128 with per-head q / k norms, theta 1e7; the sparse-attention
    indexer of 16 heads of 64 over one key head selecting 2,048 keys a
    query, scored 512 query rows at a time; experts of 768, 8 of 128 by
    softmax over the selected, none shared), 6 of its 48 layers (every
    one routed), experts 0-15 of 128 and rows 0-18,991 of the
    151,936-row vocabulary, at 16,384 of its 262,144 positions. The
    vision tower is not built: on text the three ``mrope`` position
    streams are equal and the rotary embedding is the 1-D one. What is
    assumed of the source is in
    ``benchmark/configs/keye-vl-2.0-30b-a3b-ep8.json``."""
    base = dict(
        vocab_size=18992, max_seq_len=16384, num_layers=6, num_heads=32,
        num_kv_heads=4, head_dim_override=128, embedding_dim=2048,
        norm="rmsnorm", ln_epsilon=1e-6, attn_bias=False, qk_norm=True,
        rope_layout=(1,), rope_theta=1e7, sa_topk=2048, sa_index_heads=16,
        sa_index_head_dim=64, sa_chunk=512,
        num_experts=128, experts_per_token=8, expert_width=768,
        experts_held=16, expert_offset=0, router_input="block",
        expert_activation="silu", attn_dropout=0.0, mlp_dropout=0.0,
        embedding_dropout=0.0)
    return ViTConfig(**{**base, **kw})


def dsa_tiny(**kw) -> ViTConfig:
    """The same blocks at a size for tests: 2 layers, width 64, 4 query /
    2 key-value heads of 16, an indexer of 2 heads of 8 selecting 8 keys
    a query 16 rows at a time, 8 experts of 32 of which 4 are held, top
    2, 256 rows, 64 positions."""
    return keye_vl_20_30b_a3b_ep8(**{**dict(
        vocab_size=256, max_seq_len=64, num_layers=2, num_heads=4,
        num_kv_heads=2, head_dim_override=16, embedding_dim=64,
        sa_topk=8, sa_index_heads=2, sa_index_head_dim=8, sa_chunk=16,
        num_experts=8, experts_per_token=2, expert_width=32,
        experts_held=4), **kw})


def lfm2_24b_a2b_ep8(**kw) -> ViTConfig:
    """LFM2-24B-A2B (huggingface.co/LiquidAI, ``lfm2_moe``), one chip's
    share of a deployment in which 8 chips share each layer: every
    published width (2048; gated short convolutions of 3 taps beside
    grouped-query attention of 32 query / 8 key-value heads of 64 with
    per-head q / k norms, theta 1e6; the leading dense layer of 11,776;
    experts of 1,536, 4 of 64 by sigmoid scores and an expert bias, none
    shared; one table for the embedding and the head), the first leading
    dense layer (a conv layer) and published layers 2-5 (attention, then
    three conv layers: one period of the 38 routed layers), experts 0-7
    of 64 and rows 0-8,191 of the 65,536-row vocabulary, at 8,192 of its
    128,000 positions. What is assumed of the source is in
    ``benchmark/configs/lfm2-24b-a2b-ep8.json``."""
    base = dict(
        vocab_size=8192, max_seq_len=8192, num_layers=5, num_heads=32,
        num_kv_heads=8, embedding_dim=2048, norm="rmsnorm", ln_epsilon=1e-5,
        attn_bias=False, qk_norm=True, rope_layout=(0, 1, 0, 0, 0),
        rope_theta=1e6, mixer_layout=(1, 0, 1, 1, 1), conv_kernel=3,
        num_experts=64, experts_per_token=4, expert_width=1536,
        experts_held=8, expert_offset=0, router_scoring="sigmoid",
        router_scale=1.0, router_input="block", expert_activation="silu",
        dense_layers=1, dense_width=11776, tie_embedding=True,
        attn_dropout=0.0, mlp_dropout=0.0, embedding_dropout=0.0)
    return ViTConfig(**{**base, **kw})


def conv_tiny(**kw) -> ViTConfig:
    """The same blocks at a size for tests: a conv layer with the dense
    feed-forward, an attention layer and a conv layer routed, width 64,
    4 query / 2 key-value heads of 16, dense 96, 8 experts of 32 of which
    4 are held, top 2, 256 rows, 64 positions."""
    return lfm2_24b_a2b_ep8(**{**dict(
        vocab_size=256, max_seq_len=64, num_layers=3, num_heads=4,
        num_kv_heads=2, embedding_dim=64, num_experts=8,
        experts_per_token=2, expert_width=32, experts_held=4,
        dense_width=96), **kw})


def granite_40_h_micro_pp4(**kw) -> ViTConfig:
    """granite-4.0-h-micro (huggingface.co/ibm-granite,
    ``granitemoehybrid``), one chip's part of the first of 4 pipeline
    stages: every published width (2048; Mamba-2 layers of 64 heads of 64
    with a 128-wide state, one group, a 4-tap convolution with a bias,
    chunks of 256, expansion 2; grouped-query attention of 32 query / 8
    key-value heads of 64 with no positions, softmax scale 1/64; a SiLU-
    gated feed-forward of 8,192 in every layer; one table for the
    embedding and the head; multipliers 12 / 0.22 / 8), published
    layers 0-9 (one whole period: nine Mamba-2 layers, attention at layer
    5) and rows 0-12,543 of the 100,352-row vocabulary, at 16,384 of its
    131,072 positions; each block taken again in the backward pass.
    What is assumed of the source is in
    ``benchmark/configs/granite-4.0-h-micro-pp4.json``."""
    base = dict(
        vocab_size=12544, max_seq_len=16384, num_layers=10, num_heads=32,
        num_kv_heads=8, embedding_dim=2048, norm="rmsnorm", ln_epsilon=1e-5,
        attn_bias=False, mixer_layout=(2, 2, 2, 2, 2, 0, 2, 2, 2, 2),
        ssm_heads=64, ssm_head_dim=64, ssm_state=128, ssm_groups=1,
        ssm_conv_kernel=4, ssm_chunk=256, dense_layers=10, dense_width=8192,
        expert_activation="silu", tie_embedding=True,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0, attn_scale=0.015625, remat=True,
        attn_dropout=0.0, mlp_dropout=0.0, embedding_dropout=0.0)
    return ViTConfig(**{**base, **kw})


def ssm_tiny(**kw) -> ViTConfig:
    """The same blocks at a size for tests: a Mamba-2 layer, an attention
    layer and a Mamba-2 layer, width 64, 4 query / 2 key-value heads of
    16, 8 state-space heads of 16 with a 16-wide state, chunks of 16,
    dense 96, 256 rows, 64 positions; float32 compute (at this width
    bf16's rounding of a step's update, after the hundreds of steps a
    rehearsal takes, reaches the cell's limits, which are set for the
    chip's widths)."""
    return granite_40_h_micro_pp4(**{**dict(
        vocab_size=256, max_seq_len=64, num_layers=3, num_heads=4,
        num_kv_heads=2, embedding_dim=64, mixer_layout=(2, 0, 2),
        ssm_heads=8, ssm_head_dim=16, ssm_state=16, ssm_chunk=16,
        dense_layers=3, dense_width=96, attn_scale=1 / 32,
        dtype="float32"), **kw})


PRESETS = {
    "ViT-Ti/16": vit_ti16,
    "ViT-S/16": vit_s16,
    "ViT-B/16": vit_b16,
    "ViT-L/16": vit_l16,
    "ViT-H/14": vit_h14,
}

# Token models: ``--model lm --preset <name>``. Kept apart from PRESETS,
# whose factories all take image geometry (``model_tier``, the serving
# tiers and the CLI's image options walk that table).
LM_PRESETS = {
    "smallthinker-21b-a3b-ep4": smallthinker_21b_a3b_ep4,
    "lm-tiny": lm_tiny,
    "glm-4.7-flash-ep8": glm_47_flash_ep8,
    "mla-tiny": mla_tiny,
    "keye-vl-2.0-30b-a3b-ep8": keye_vl_20_30b_a3b_ep8,
    "dsa-tiny": dsa_tiny,
    "lfm2-24b-a2b-ep8": lfm2_24b_a2b_ep8,
    "conv-tiny": conv_tiny,
    "granite-4.0-h-micro-pp4": granite_40_h_micro_pp4,
    "ssm-tiny": ssm_tiny,
}

# The fields that make two configs the same *servable architecture*
# (same param-tree shapes at a given head size). num_classes /
# image_size / dtype / kernel-impl knobs legitimately vary per
# deployment and are NOT identity.
ARCH_FIELDS = ("patch_size", "num_layers", "num_heads",
               "embedding_dim", "mlp_size", "pool")


def arch_of(cfg: "ViTConfig") -> dict:
    """The architecture-identity slice of a config — what the
    checkpoint meta records and the tier-mismatch refusal compares."""
    return {f: getattr(cfg, f) for f in ARCH_FIELDS}


def model_tier(cfg: "ViTConfig") -> str:
    """Human-meaningful tier label for a config: the ``PRESETS`` key
    whose architecture matches (``"ViT-Ti/16"`` …), else a synthesized
    ``custom/<dim>x<layers>p<patch>`` spelling. This is the label a
    serve replica reports in ``::stats`` (``model_tier``,
    informational) and the checkpoint's ``model_meta.json`` records
    for the load-time tier-mismatch refusal. The fleet's ``model=``
    routing filter deliberately does NOT key on it — routing keys on
    the deployment spec's declared model name (operator config), this
    label just tells a human which architecture that name maps to."""
    want = arch_of(cfg)
    for name, factory in PRESETS.items():
        if arch_of(factory(num_classes=cfg.num_classes,
                           image_size=cfg.image_size,
                           patch_size=cfg.patch_size)) == want:
            return name
    return (f"custom/{cfg.embedding_dim}x{cfg.num_layers}"
            f"p{cfg.patch_size}")


@dataclasses.dataclass(frozen=True)
class TrainConfig:
    """Training-recipe hyperparameters.

    Defaults reproduce the reference recipe: Adam(1e-3, 0.9, 0.999) with
    weight decay 0.03 applied only to ndim>1 params (reference main notebook
    cells 84-85), linear warmup over 5% of steps then linear decay to 0
    (cells 87-88), global-norm-1 gradient clipping (engine.py:63), batch 32,
    10 epochs.
    """

    batch_size: int = 32
    epochs: int = 10
    learning_rate: float = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    weight_decay: float = 0.03
    warmup_fraction: float = 0.05
    grad_clip_norm: float = 1.0
    label_smoothing: float = 0.0
    seed: int = 42
    # Freeze everything except the classifier head (transfer learning;
    # reference main notebook cell 112 sets requires_grad=False on backbone).
    freeze_backbone: bool = False

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout for distributed training.

    Axis names follow the scaling-book convention:
      data  — data parallelism (batch sharded, gradients psum'd over ICI)
      model — tensor parallelism (attention heads / MLP hidden sharded)
      seq   — sequence/context parallelism (ring attention over tokens)
      pipe  — pipeline parallelism (encoder layers staged, GPipe
              microbatching — parallel/pipeline.py)
    A dimension of 1 disables that axis. The reference has no distributed
    code at all (SURVEY.md §2.4); this is a greenfield TPU-native component.
    """

    data: int = -1   # -1 = all remaining devices
    model: int = 1
    seq: int = 1
    pipe: int = 1

    def axis_sizes(self, n_devices: int) -> Tuple[int, int, int, int]:
        model = max(1, self.model)
        seq = max(1, self.seq)
        pipe = max(1, self.pipe)
        data = self.data
        rest = model * seq * pipe
        if data == -1:
            if n_devices % rest != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by model*seq*pipe="
                    f"{rest}")
            data = n_devices // rest
        if data * rest != n_devices:
            raise ValueError(
                f"mesh {data}x{model}x{seq}x{pipe} != {n_devices} devices")
        return data, model, seq, pipe
