"""Pipeline parallelism — GPipe microbatching of the ViT encoder stack.

The reference has no distributed code at all (SURVEY.md §2.4); this is the
last of the four classic parallelism axes, built the TPU-native way: the
``num_layers`` encoder blocks are stacked into one ``[L, ...]`` parameter
pytree, sharded over the mesh's ``pipe`` axis (``L/S`` contiguous layers
per stage), and a ``jax.shard_map``'d schedule pushes ``M`` microbatches
through the ``S`` stages. Every tick each stage runs its layer group on
its current microbatch, then hands the activation to the next stage with
``jax.lax.ppermute`` (neighbor ICI transfer, overlapped with the next
tick's compute by XLA); after ``M + S - 1`` ticks the last stage holds
every processed microbatch and broadcasts the result with one ``psum``.
Bubble fraction is the textbook ``(S-1)/(M+S-1)``.

Scope (validated): composes with data parallelism AND tensor parallelism
(``dp × tp × pp``). Inside ``shard_map`` every array is local, so GSPMD
cannot insert TP's collectives — instead pp×tp runs manual Megatron
wiring: stacked block leaves keep their TP rule one axis right
(``sharding.pspec_for_path``), blocks are built from a head-local config
and psum their out/fc2 partial sums over the model axis
(``models/vit.py`` ``tp_axis``), and the replicated out/fc2 biases are
fed as ``b/tp`` so the psum reconstructs them exactly once (see
``scale_replicated_biases``). Sequence parallelism does not compose
(the ring's collectives would nest inside the schedule — refused by
:func:`validate_pipeline`). Patch embedding, final LayerNorm, and the
classifier head are computed replicated on every stage (they are <1% of
step FLOPs; staging them would buy nothing and complicate the
schedule).

Numerics: deterministic pipeline output is identical to the standard
per-layer model (same modules, same params, just stacked). Dropout is
valid but draws DIFFERENT masks than the unpipelined model: each
(layer, microbatch) gets an independent key via ``fold_in`` instead of
flax's per-module path folding — documented, tested for independence.

Entry points: :func:`stack_block_params` / :func:`unstack_block_params`
convert between the standard and pipeline parameter layouts (checkpoints
export the standard layout, so predict/transfer are unaffected);
:func:`make_pipeline_apply` builds the drop-in ``apply_fn`` consumed by
``engine.TrainState`` — the train/eval step code does not change at all,
which is the payoff of keeping steps pure.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.partition import on_mesh

BLOCKS_KEY = "encoder_blocks"  # sharding rule lives in sharding.pspec_for_path


def stack_block_params(params: Dict[str, Any], num_layers: int
                       ) -> Dict[str, Any]:
    """Standard ViT params -> pipeline layout.

    ``{"backbone": {"encoder_block_i": ..., rest}, "head": ...}`` becomes
    ``{"backbone": {rest}, "head": ..., "encoder_blocks": stacked}`` where
    every leaf of ``stacked`` gains a leading ``[L]`` layer axis (sharded
    over 'pipe' by ``sharding.pspec_for_path``'s stacked-blocks rule).
    """
    backbone = dict(params["backbone"])
    blocks = [backbone.pop(f"encoder_block_{i}") for i in range(num_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *blocks)
    out = dict(params)
    out["backbone"] = backbone
    out[BLOCKS_KEY] = stacked
    return out


def unstack_block_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Inverse of :func:`stack_block_params` (used for the standard-layout
    checkpoint export, so predict/transfer never see the pipeline tree)."""
    out = dict(params)
    stacked = out.pop(BLOCKS_KEY)
    num_layers = jax.tree.leaves(stacked)[0].shape[0]
    backbone = dict(out["backbone"])
    for i in range(num_layers):
        backbone[f"encoder_block_{i}"] = jax.tree.map(
            lambda a, i=i: a[i], stacked)
    out["backbone"] = backbone
    return out


def pipeline_decay_mask(params: Dict[str, Any]) -> Dict[str, Any]:
    """Weight-decay mask for the pipeline layout: stacked block leaves
    carry a leading ``[L]`` axis, so the reference's ndim>1 rule
    (optim.decay_mask, main nb cell 84) becomes ndim>2 there — otherwise
    stacked biases/LayerNorm params ([L, d], 2-D) would silently start
    receiving decay the standard layout excludes."""

    def mask(path, leaf):
        stacked = any(getattr(k, "key", None) == BLOCKS_KEY for k in path)
        return jnp.ndim(leaf) > (2 if stacked else 1)

    return jax.tree_util.tree_map_with_path(mask, params)


def validate_pipeline(cfg, mesh: Mesh, num_microbatches: int,
                      batch_size: int) -> None:
    """Divisibility/compat checks, CLI-friendly messages."""
    stages = mesh.shape.get("pipe", 1)
    if stages <= 1:
        return
    if mesh.shape.get("seq", 1) != 1:
        raise ValueError(
            "pipeline parallelism does not compose with sequence "
            "parallelism (inside the pipeline's shard_map the ring's "
            "collectives would nest; shard long sequences with --mesh-seq "
            "without --mesh-pipe)")
    if mesh.shape.get("model", 1) > 1:
        # pp×tp runs manual Megatron wiring (models/vit.py tp_axis psums);
        # same divisibility rules as GSPMD TP.
        from .sharding import validate_tp_divisibility

        validate_tp_divisibility(cfg, mesh)
    if cfg.num_layers % stages != 0:
        raise ValueError(
            f"num_layers={cfg.num_layers} not divisible by the pipe axis "
            f"size {stages}")
    per_shard = batch_size // mesh.shape.get("data", 1)
    if num_microbatches < 1 or per_shard % num_microbatches != 0:
        raise ValueError(
            f"per-data-shard batch {per_shard} not divisible by "
            f"num_microbatches={num_microbatches}")


def make_pipeline_apply(cfg, mesh: Mesh, *, num_microbatches: int,
                        pipe_axis: str = "pipe", data_axis: str = "data",
                        model_axis: str = "model"):
    """Build the pipelined ``apply_fn(variables, images, train, rngs)``.

    Drop-in for ``ViT(cfg).apply`` over the pipeline parameter layout —
    same call signature, so ``engine.TrainState`` and the step builders
    work unchanged. ``num_microbatches`` is the GPipe M (>= pipe size for
    a small bubble; must divide the per-data-shard batch).

    pp×tp: when the mesh's model axis is >1, each stage's blocks run on
    head-/hidden-sliced params (stacked leaves carry their TP rule one
    axis right — ``sharding.pspec_for_path``) with explicit Megatron
    psums over the model axis (``models/vit.py`` ``tp_axis``); the block
    is built from a head-LOCAL config so flax's declared shapes match the
    local shards. Dropout keys are deliberately NOT folded by the model
    index: post-psum tensors are replicated across the tp group and must
    receive the identical mask on every shard (the price is mask reuse
    across head/hidden slices — the same correlation GSPMD-free Megatron
    TP has always had).
    """
    import flax.linen as nn

    from ..models.vit import (PatchEmbedding, TransformerEncoderBlock,
                              apply_tail)

    stages = mesh.shape[pipe_axis]
    tp = mesh.shape.get(model_axis, 1)
    layers_per_stage = cfg.num_layers // stages
    block_cfg = cfg
    if tp > 1:
        block_cfg = cfg.replace(num_heads=cfg.num_heads // tp,
                                mlp_size=cfg.mlp_size // tp,
                                head_dim_override=cfg.head_dim)
    block_cls = TransformerEncoderBlock
    if cfg.remat:
        # Same remat policy as the standard model (models/vit.py:212):
        # recompute block activations in the backward pass.
        block_cls = nn.remat(TransformerEncoderBlock, static_argnums=(2,))
    block = block_cls(block_cfg, tp_axis=model_axis if tp > 1 else None)
    dtype = jnp.dtype(cfg.dtype)

    def scale_replicated_biases(stacked_local):
        """Manual-TP bias correction: the out/fc2 biases are REPLICATED
        over the model axis while their matmul outputs are partial sums —
        adding b on every shard then psum'ing would contribute tp*b (a
        uniform-shift probe hides this behind LayerNorm's shift
        invariance; a per-channel one exposes it). Scaling to b/tp makes
        the psum reconstruct b exactly once, and the shard_map transpose's
        model-axis cotangent sum then yields exactly the true gradient:
        sum_shards(ct/tp) * tp = ct. The affected-leaf set is pinned next
        to TP_RULES (sharding.REPLICATED_PARTIAL_SUM_BIASES)."""
        from .sharding import REPLICATED_PARTIAL_SUM_BIASES, _path_names

        def f(path, leaf):
            if _path_names(path)[-2:] in REPLICATED_PARTIAL_SUM_BIASES:
                return leaf / tp
            return leaf

        return jax.tree_util.tree_map_with_path(f, stacked_local)

    def run_stage(stacked_local, x, train, rng, mb_index):
        """Apply this stage's layer group to one microbatch (params
        already bias-corrected by the caller when tp > 1)."""
        stage = jax.lax.axis_index(pipe_axis)
        for j in range(layers_per_stage):
            layer_params = jax.tree.map(lambda a, j=j: a[j], stacked_local)
            rngs = None
            if rng is not None:
                # Independent noise per (data shard, global layer,
                # microbatch): the rng enters shard_map replicated, so
                # without the data fold every dp shard would draw the
                # SAME masks; equal keys at equal shapes would likewise
                # repeat masks across microbatches/layers.
                shard_rng = jax.random.fold_in(
                    rng, jax.lax.axis_index(data_axis))
                global_layer = stage * layers_per_stage + j
                rngs = {"dropout": jax.random.fold_in(
                    shard_rng, global_layer * num_microbatches + mb_index)}
            x = block.apply({"params": layer_params}, x, train, rngs=rngs)
        return x

    def encoder(stacked_local, x_local, train, rng):
        """The shard_map body: GPipe schedule over M microbatches."""
        if tp > 1:
            # Once, outside the scan — loop-invariant.
            stacked_local = scale_replicated_biases(stacked_local)
        stage = jax.lax.axis_index(pipe_axis)
        b_local, t, d = x_local.shape
        mb = b_local // num_microbatches
        micro = x_local.reshape(num_microbatches, mb, t, d)
        ticks = num_microbatches + stages - 1

        def tick(carry, tk):
            incoming, acc = carry                  # acc: [M, mb, t, d]
            feed = micro[jnp.clip(tk, 0, num_microbatches - 1)]
            x_in = jnp.where(stage == 0, feed, incoming)
            # Microbatch index at this stage this tick (clipped ticks are
            # warmup/drain bubbles whose results are never selected).
            mb_index = jnp.clip(tk - stage, 0, num_microbatches - 1)
            out = run_stage(stacked_local, x_in, train, rng, mb_index)
            sent = jax.lax.ppermute(
                out, pipe_axis,
                [(i, i + 1) for i in range(stages - 1)])
            # Bounded output buffer (round-4; previously the scan STACKED
            # every tick's output into [M+S-1, mb, t, d] per stage):
            # microbatch m finishes on the last stage at tick S-1+m, so
            # write each tick's result into its clipped slot — warmup
            # ticks (< S-1) land on slot 0 and are overwritten by the
            # real microbatch 0 at tick S-1 (the scan is sequential
            # ascending). Slot writes are the scan's only output, so the
            # schedule's live buffer is exactly the [M, mb, t, d] layer
            # output the unpipelined model produces anyway.
            slot = jnp.clip(tk - (stages - 1), 0, num_microbatches - 1)
            acc = jax.lax.dynamic_update_slice_in_dim(
                acc, out[None], slot, axis=0)
            return (sent, acc), None

        (_, finished), _ = jax.lax.scan(
            tick,
            (jnp.zeros((mb, t, d), dtype),
             jnp.zeros((num_microbatches, mb, t, d), dtype)),
            jnp.arange(ticks))
        # Other stages' buffers hold garbage; one psum selects the last
        # stage's and broadcasts it everywhere (activations are tiny next
        # to weights).
        contrib = jnp.where(stage == stages - 1, finished,
                            jnp.zeros_like(finished))
        y = jax.lax.psum(contrib, pipe_axis)
        return y.reshape(b_local, t, d)

    # Params enter sharded ('pipe' on the stacked leading axis), batch
    # enters sharded over 'data', replicated over 'pipe'.
    x_spec = P(data_axis, None, None)

    def apply_fn(variables, images, train: bool = False,
                 rngs: Optional[dict] = None):
        params = variables["params"]
        dropout_rng = (rngs or {}).get("dropout")
        pe_rngs = None
        if dropout_rng is not None:
            # Large sentinel fold: disjoint from every (layer, microbatch)
            # fold used inside the pipeline (those are < L*M << 2^31).
            pe_rngs = {"dropout": jax.random.fold_in(dropout_rng,
                                                     2**31 - 1)}
        x = PatchEmbedding(cfg).apply(
            {"params": params["backbone"]["patch_embedding"]}, images,
            train, rngs=pe_rngs)

        stacked = params[BLOCKS_KEY]
        # Per-leaf specs from the central rule ('pipe' on the layer axis,
        # TP rule shifted right under pp×tp) so shard_map's view matches
        # how shard_train_state placed the arrays.
        from .sharding import pspec_for_path

        stacked_specs = jax.tree_util.tree_map_with_path(
            lambda p, leaf: pspec_for_path(p, leaf),
            {BLOCKS_KEY: stacked})[BLOCKS_KEY]
        # Inside this shard_map every array is already local: the Pallas
        # kernels must run as they are, not shard_map themselves again.
        with on_mesh(None):
            if dropout_rng is not None:
                fn = jax.shard_map(
                    lambda s, xx, r: encoder(s, xx, train, r),
                    mesh=mesh,
                    in_specs=(stacked_specs, x_spec, P()),
                    out_specs=x_spec, check_vma=False)
                x = fn(stacked, x, dropout_rng)
            else:
                fn = jax.shard_map(
                    lambda s, xx: encoder(s, xx, train, None),
                    mesh=mesh,
                    in_specs=(stacked_specs, x_spec),
                    out_specs=x_spec, check_vma=False)
                x = fn(stacked, x)

        return apply_tail(cfg, params, x)

    return apply_fn
