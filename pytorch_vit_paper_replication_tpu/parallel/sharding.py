"""Parameter/state sharding rules — Megatron-style tensor parallelism for
the ViT, expressed as path-pattern → PartitionSpec.

With these shardings on params and the batch sharded over 'data', GSPMD
inserts the collectives automatically (scaling-book recipe: pick a mesh,
annotate shardings, let XLA place psum/all-gather over ICI):

* qkv projection sharded over heads  → each model-shard computes its heads'
  attention locally,
* out projection sharded over heads  → partial sums reduced (psum) into the
  residual stream,
* MLP fc1 sharded over the hidden dim, fc2 over its input → one psum after
  fc2.

LayerNorms, embeddings, and the classifier head are replicated (they are
tiny and sit on the un-sharded residual stream).

Rules match on the **trailing name components** of a leaf's path, so they
apply equally to ``params`` and to structurally-congruent optimizer state
(Adam's mu/nu carry the same sub-paths).
"""

from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Biases that stay REPLICATED over 'model' while their matmul outputs are
# per-shard partial sums (their rules below are P()): under the pipeline's
# manual TP these must be fed as b/tp so the psum reconstructs them once
# (pipeline.scale_replicated_biases). Keep in lockstep with TP_RULES and
# with the psum placement in models/vit.py (tp_axis).
REPLICATED_PARTIAL_SUM_BIASES: Tuple[Tuple[str, ...], ...] = (
    ("out", "bias"), ("fc2", "bias"))

# (trailing path names) -> PartitionSpec. First match wins.
TP_RULES: Tuple[Tuple[Tuple[str, ...], P], ...] = (
    (("qkv", "kernel"), P(None, None, "model", None)),  # [D, 3, H, Dh]
    (("qkv", "bias"), P(None, "model", None)),          # [3, H, Dh]
    (("out", "kernel"), P("model", None, None)),        # [H, Dh, D]
    (("out", "bias"), P()),                             # [D]
    (("fc1", "kernel"), P(None, "model")),              # [D, mlp]
    (("fc1", "bias"), P("model")),                      # [mlp]
    (("fc2", "kernel"), P("model", None)),              # [mlp, D]
    (("fc2", "bias"), P()),                             # [D]
)


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for k in path:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "name"):
            names.append(str(k.name))
        # GetAttrKey/SequenceKey indices are structural, not names — skip.
    return tuple(names)


def pspec_for_path(path, leaf=None) -> P:
    """PartitionSpec for one leaf: pipeline-stacked blocks shard their
    leading layer axis over 'pipe'; otherwise TP rule if the trailing
    names match; replicated else."""
    names = _path_names(path)
    # Pipeline layout (parallel/pipeline.py): every leaf under the
    # stacked-blocks subtree has a leading [L] layer axis sharded over
    # 'pipe'; the per-layer dims keep their TP rule shifted one axis
    # right (pp×tp composition). Must match BEFORE the bare TP rules —
    # the trailing names (qkv/kernel etc.) are the same but the stacked
    # rank is +1.
    if "encoder_blocks" in names:
        for pattern, spec in TP_RULES:
            if names[-len(pattern):] == pattern:
                return P("pipe", *spec)
        return P("pipe")
    for pattern, spec in TP_RULES:
        if names[-len(pattern):] == pattern:
            # A token model's grouped-query projection has the names and
            # not the ranks ([D, H + 2 Hkv, Dh], no bias): it stays
            # replicated (validate_mesh_for_config refuses it a model
            # axis).
            if leaf is not None and len(spec) > len(leaf.shape):
                return P()
            return spec
    return P()


def tree_pspecs(tree: Any) -> Any:
    """Map every leaf of a pytree (params, opt state, TrainState...) to its
    PartitionSpec."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: pspec_for_path(path, leaf), tree)


def tree_shardings(tree: Any, mesh: Mesh) -> Any:
    return jax.tree.map(lambda spec: NamedSharding(mesh, spec),
                        tree_pspecs(tree),
                        is_leaf=lambda x: isinstance(x, P))


def shard_tree(tree: Any, mesh: Mesh) -> Any:
    """Place a host-side pytree onto the mesh per the rules.

    Single-process: a plain ``device_put``. Multi-process (mesh spanning
    hosts): ``device_put`` rejects shardings with non-addressable devices,
    so each host materializes its addressable shards from its own full
    copy via ``make_array_from_callback`` — every host computes the same
    initial state (same seed), so indexing the local copy yields globally
    consistent shards. Typed PRNG keys are placed via their raw key data
    (callbacks need indexable ndarrays) and re-wrapped.
    """
    multiprocess = jax.process_count() > 1

    def place(path, leaf):
        sharding = NamedSharding(mesh, pspec_for_path(path, leaf))
        if not multiprocess:
            return jax.device_put(leaf, sharding)
        if jax.dtypes.issubdtype(getattr(leaf, "dtype", None),
                                 jax.dtypes.prng_key):
            impl = str(jax.random.key_impl(leaf))
            import numpy as np
            data = np.asarray(jax.device_get(jax.random.key_data(leaf)))
            placed = jax.make_array_from_callback(
                data.shape, NamedSharding(mesh, P()),
                lambda idx, a=data: a[idx])
            return jax.random.wrap_key_data(placed, impl=impl)
        import numpy as np
        arr = np.asarray(jax.device_get(leaf))
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx, a=arr: a[idx])

    return jax.tree_util.tree_map_with_path(place, tree)


def validate_tp_divisibility(config, mesh: Mesh) -> None:
    """TP requires heads and mlp hidden divisible by the model-axis size."""
    tp = mesh.shape["model"]
    if tp == 1:
        return
    if config.num_heads % tp != 0:
        raise ValueError(
            f"num_heads={config.num_heads} not divisible by model-axis "
            f"size {tp}")
    if config.mlp_size % tp != 0:
        raise ValueError(
            f"mlp_size={config.mlp_size} not divisible by model-axis "
            f"size {tp}")


def validate_sp_divisibility(config, mesh: Mesh) -> None:
    """Ring attention shards the token axis: seq_len % seq-axis must be 0.

    ViT's CLS token makes the default sequence odd (197 for 224/16) — the
    error suggests ``pool="gap"`` which drops it (196 = 4·49 patches).
    """
    sp = mesh.shape.get("seq", 1)
    if sp == 1:
        return
    if config.seq_len % sp != 0:
        hint = (" (pool='gap' would drop the CLS token, giving "
                f"{config.num_patches} tokens)" if config.pool == "cls"
                else "")
        raise ValueError(
            f"seq_len={config.seq_len} not divisible by seq-axis size "
            f"{sp}{hint}")


def validate_mesh_for_config(config, mesh: Mesh) -> None:
    """All mesh-vs-architecture divisibility checks in one call."""
    if getattr(config, "vocab_size", 0):
        others = {a: n for a, n in mesh.shape.items()
                  if a != "data" and n > 1}
        if others:
            raise ValueError(
                "a token model trains data-parallel only: its grouped-"
                "query projection, routed experts and head have no "
                f"sharding over {sorted(others)} (experts spread over "
                "chips need an expert axis the mesh does not have)")
        return
    validate_tp_divisibility(config, mesh)
    validate_sp_divisibility(config, mesh)
