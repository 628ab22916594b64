"""High-level distributed API: shard a TrainState onto a mesh and build the
jitted SPMD train/eval steps.

Usage (the whole data+tensor-parallel story, scaling-book style)::

    mesh = make_mesh(MeshConfig(data=4, model=2))
    state = shard_train_state(state, mesh)          # params/opt-state placed
    step = make_parallel_train_step(state, mesh)    # jit with shardings
    for batch in loader:
        state, metrics = step(state, shard_batch(batch, mesh))

GSPMD inserts the gradient psum over 'data' and the TP collectives over
'model'; nothing in the model or engine code changes — the payoff of pure
step functions (SURVEY.md §7). The one thing GSPMD will not split is a
Mosaic (Pallas) custom call, so the steps are traced under
``ops.partition.on_mesh`` and the kernels shard_map themselves.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compile_cache import closes_startup_stage
from ..engine import TrainState, make_eval_step, make_train_step
from ..ops.partition import traced_on_mesh
from .sharding import pspec_for_path, shard_tree


def state_shardings(state: TrainState, mesh: Mesh) -> TrainState:
    """NamedSharding pytree congruent to the state (params + opt state via
    the TP rules; step/rng replicated)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, pspec_for_path(path, leaf)),
        state)


def shard_train_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place an (unsharded, host or single-device) TrainState onto `mesh`."""
    return shard_tree(state, mesh)


def batch_sharding_for(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("data"))


def shard_batch(batch: Dict[str, Any], mesh: Mesh) -> Dict[str, Any]:
    """Place a host batch with its leading dim sharded over 'data'.

    Works for any batch keys (image/label/mask/...). On multi-host, each
    process passes its local shard and this becomes a
    ``jax.make_array_from_process_local_data`` placement.
    """
    sh = batch_sharding_for(mesh)
    if jax.process_count() > 1:
        return {k: jax.make_array_from_process_local_data(sh, v)
                for k, v in batch.items()}
    return {k: jax.device_put(v, sh) for k, v in batch.items()}


@closes_startup_stage("state")
def make_parallel_train_step(state: TrainState, mesh: Mesh, *,
                             label_smoothing: float = 0.0,
                             nan_guard: bool = False,
                             sp_impl: str = "ring",
                             distill_alpha: Optional[float] = None,
                             distill_t: float = 1.0):
    """Jit the train step with explicit state shardings and donation.

    Batch shardings are inherited from the arrays themselves (place them
    with :func:`shard_batch`), so extra keys like eval masks — or the
    KD path's ``teacher_logits`` — need no special-casing. ``sp_impl``
    picks the sequence-parallel strategy on seq>1 meshes ("ring" or
    "ulysses" — parallel/ulysses.py's table). ``distill_alpha``/
    ``distill_t`` select the knowledge-distillation objective
    (:func:`..engine.distill_loss`). Its return closes the start-up
    stage ``state``: the state is made and laid out before this call,
    and the step is built here.
    """
    step = make_train_step(label_smoothing, nan_guard=nan_guard,
                           distill_alpha=distill_alpha,
                           distill_t=distill_t)
    st_sh = state_shardings(state, mesh)
    jitted = jax.jit(step,
                     in_shardings=(st_sh, None),
                     out_shardings=(st_sh, None),
                     donate_argnums=0)
    return traced_on_mesh(jitted, mesh, sp_impl=sp_impl)


def make_parallel_eval_step(state: TrainState, mesh: Mesh, *,
                            sp_impl: str = "ring"):
    step = make_eval_step()
    st_sh = state_shardings(state, mesh)
    jitted = jax.jit(step, in_shardings=(st_sh, None), out_shardings=None)
    return traced_on_mesh(jitted, mesh, sp_impl=sp_impl)
