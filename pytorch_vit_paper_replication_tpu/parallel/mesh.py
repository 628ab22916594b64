"""Device-mesh construction and multi-host initialization.

The reference is single-accelerator (`mps`→`cuda`→`cpu` selection, SURVEY.md
§2.4) — everything here is the greenfield TPU-native distributed layer. Axes:

  data  — batch sharding, gradient psum over ICI (DP)
  model — tensor parallelism over attention heads / MLP hidden (TP)
  seq   — sequence/context parallelism, ring attention over tokens (SP)
  pipe  — pipeline parallelism, encoder layers staged with GPipe
          microbatching (PP — parallel/pipeline.py)

Meshes are built with ``mesh_utils.create_device_mesh`` so the axis order
maps onto the physical ICI torus (fast axes innermost); within a slice every
collective rides ICI, across slices XLA routes over DCN.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compile_cache import closes_startup_stage
from ..configs import MeshConfig

AXES = ("data", "model", "seq", "pipe")


@closes_startup_stage("mesh")
def make_mesh(config: Optional[MeshConfig] = None,
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build the ('data','model','seq','pipe') mesh over the given devices.

    Its return closes the start-up stage ``mesh`` (reaching the chip:
    ``jax.devices()`` initialises the backend, here or in the caller
    just before)."""
    config = config or MeshConfig()
    devices = list(devices) if devices is not None else jax.devices()
    shape = config.axis_sizes(len(devices))
    # On a TPU this orders the devices along the physical torus (a 2x2
    # v5e host comes back 0,1,3,2); elsewhere it is a plain reshape. A
    # shape it cannot map is an error, not a silent loss of that order.
    dev_array = mesh_utils.create_device_mesh(
        shape, devices=np.asarray(devices))
    return Mesh(dev_array, AXES)


def single_device_mesh() -> Mesh:
    """A trivial 1x1x1x1 mesh — lets every code path be mesh-shaped even on
    one chip (the bench configuration)."""
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1, 1, 1), AXES)


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Batch dimension sharded over the data axis; everything else
    replicated."""
    return NamedSharding(mesh, P("data"))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def initialize_multi_host(coordinator_address: Optional[str] = None,
                          num_processes: Optional[int] = None,
                          process_id: Optional[int] = None, *,
                          retries: int = 0, backoff_s: float = 1.0,
                          max_backoff_s: float = 30.0,
                          reinitialize: bool = False) -> None:
    """``jax.distributed.initialize`` wrapper for multi-host pods.

    On TPU pods all arguments are auto-detected from the environment; args
    exist for manual DCN setups. No-op if already initialized. The
    reference's closest analog would be torch's ``init_process_group`` —
    which it never calls (SURVEY.md §2.4).

    ``retries`` > 0 retries a failed coordinator connect with exponential
    backoff (``backoff_s`` doubling up to ``max_backoff_s``) instead of
    hard-crashing the worker — on a pod the coordinator host routinely
    comes up seconds after its peers, and under elastic re-formation
    (``parallel.elastic``) a whole new coordinator is being stood up
    while survivors reconnect. Attempts beyond the first are counted on
    the ``elastic_init_retries_total`` telemetry instrument so flapping
    coordinators are diagnosable from the fleet view.

    ``reinitialize=True`` first tears down an existing
    ``jax.distributed`` client (ignored if none is live) so a surviving
    worker can join a NEW, differently-sized cluster in-process — the
    mesh-re-formation path.
    """
    import time as _time

    from ..telemetry import get_registry

    if reinitialize:
        try:
            jax.distributed.shutdown()
        except (RuntimeError, ValueError):
            pass  # not initialized (or already torn down): nothing to do
    delay = max(0.05, float(backoff_s))
    last: Optional[Exception] = None
    for attempt in range(max(0, int(retries)) + 1):
        if attempt:
            get_registry().count("elastic_init_retries_total")
            _time.sleep(delay)
            delay = min(delay * 2, float(max_backoff_s))
        try:
            jax.distributed.initialize(
                coordinator_address=coordinator_address,
                num_processes=num_processes,
                process_id=process_id)
            return
        except RuntimeError as e:
            if "already initialized" in str(e):
                return
            last = e  # coordinator not up yet (connect/deadline errors)
        except (ConnectionError, OSError) as e:
            last = e
    assert last is not None
    raise last


def process_info() -> tuple[int, int]:
    """(process_index, process_count) — feeds the data loader's per-host
    sharding."""
    return jax.process_index(), jax.process_count()
