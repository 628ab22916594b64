"""Trace-time mesh context: how the Pallas kernels survive a mesh.

Under ``jax.jit`` XLA partitions ordinary HLO from sharding annotations
alone, but it refuses a Mosaic custom call outright ("Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a
shard_map."). Off-TPU the kernels run in interpret mode, which lowers to
plain HLO and partitions happily — so only a real multi-chip TPU sees the
refusal. The program therefore partitions its own kernels: whoever jits a
step over a mesh traces it under :func:`on_mesh`, and each kernel's
public wrapper (:func:`.fused_mlp.fused_ln_mlp_residual`,
:func:`.fused_mlp.fused_mlp`, :func:`.flash_attention.flash_attention`,
:func:`.short_attention.short_attention`) asks :func:`current` and,
when a mesh of more than one device is active, runs its ``pallas_call``
per shard inside ``jax.shard_map``.

The same context carries the sequence-parallel choice: attention routes
through ring/Ulysses when the active mesh's ``seq`` axis is >1
(:func:`.attention.dot_product_attention`).

The context only needs to surround *tracing*: the traced program carries
the shard_map'd calls permanently.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import re
import threading
from typing import List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh

_ACTIVE = threading.local()


@dataclasses.dataclass(frozen=True)
class Partition:
    """The mesh a trace runs on, and which of its axes shard what."""

    mesh: Mesh
    data_axis: str = "data"
    model_axis: str = "model"
    seq_axis: str = "seq"
    sp_impl: str = "ring"

    def size(self, axis: Optional[str]) -> int:
        return self.mesh.shape.get(axis, 1) if axis is not None else 1

    def axis(self, axis: str) -> Optional[str]:
        """``axis`` when the mesh really splits over it, else None — the
        spelling a ``PartitionSpec`` entry wants."""
        return axis if self.size(axis) > 1 else None

    def shard_map(self, fn, in_specs, out_specs):
        """``jax.shard_map`` over every axis of the mesh. Replication is
        not tracked (``check_vma=False``, like the ring and the
        pipeline): the transpose sums the cotangent of every operand
        over the axes its spec leaves out — which is the once-only
        gradient psum over ``data`` for the replicated weights."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def index(self, axes: Sequence[Optional[str]]):
        """This shard's linear position over ``axes`` (row-major; None
        entries skipped). Only valid inside :meth:`shard_map`."""
        idx = 0
        for a in axes:
            if a is not None:
                idx = idx * self.size(a) + jax.lax.axis_index(a)
        return idx


@contextlib.contextmanager
def on_mesh(mesh: Optional[Mesh], *, data_axis: str = "data",
            model_axis: str = "model", seq_axis: str = "seq",
            sp_impl: str = "ring"):
    """Declare that the code traced inside runs on ``mesh``.

    Entered (through :func:`traced_on_mesh`) by ``parallel.api``'s step
    builders and by ``serve.offline.OfflineEngine`` around their jitted
    calls.
    ``mesh=None`` clears the context — for a caller that has already
    entered its own ``shard_map`` (the pipeline), inside which every
    array is local and the kernels must run unwrapped.

    ``sp_impl``: ``"ring"`` (K/V rotate over neighbor ICI, O(T·T_local)
    memory) or ``"ulysses"`` (two all_to_alls re-shard tokens→heads,
    local full-sequence attention — needs heads divisible by the seq
    axis; see ``parallel/ulysses.py`` for the trade-off table).
    """
    if sp_impl not in ("ring", "ulysses"):
        raise ValueError(f"unknown sp_impl {sp_impl!r}")
    prev = getattr(_ACTIVE, "partition", None)
    _ACTIVE.partition = None if mesh is None else Partition(
        mesh, data_axis, model_axis, seq_axis, sp_impl)
    try:
        yield
    finally:
        _ACTIVE.partition = prev


def traced_on_mesh(jitted, mesh: Mesh, **axes):
    """`jitted`, traced under :func:`on_mesh` whenever it is called or
    lowered — how a step builder hands its mesh to the kernels. A mesh
    of one device needs no context and gets `jitted` back."""
    if mesh.size <= 1:
        return jitted

    @functools.wraps(jitted)
    def call(*args, **kwargs):
        with on_mesh(mesh, **axes):
            return jitted(*args, **kwargs)

    def lower(*args, **kwargs):
        with on_mesh(mesh, **axes):
            return jitted.lower(*args, **kwargs)

    call.lower = lower
    return call


def current() -> Optional[Partition]:
    """The active partition, or None when tracing for a single device
    (no context, or a mesh of one)."""
    part = getattr(_ACTIVE, "partition", None)
    if part is None or part.mesh.size <= 1:
        return None
    return part


_MOSAIC_CALL = re.compile(
    r'stablehlo\.custom_call @tpu_custom_call\(.*kernel_name = "([^"]+)"'
    r'.*\} : \((.*)\) -> ')


def mosaic_calls(lowered_text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """``(kernel name, shape of its first array operand)`` for every
    Mosaic custom call in a lowered program's StableHLO text
    (``jitted.lower(...).as_text()``).

    The dispatch picks kernel or XLA path, Mosaic or interpreter, from
    ``jax.default_backend()`` and says nothing; this reads what actually
    went into the program. The interpreter's expansion leaves no
    ``tpu_custom_call``. Under a mesh the operand shape is the
    per-shard one (rows = images per chip x tokens for the MLP
    kernels). Operand 0 is the kernels' scalar-prefetch vector, so the
    shape reported is operand 1's; a call with no operand (one that only
    makes its result's buffer) reports ``()``."""
    calls = []
    for line in lowered_text.splitlines():
        m = _MOSAIC_CALL.search(line)
        if m is None:
            continue
        operands = re.findall(r"tensor<([^>]+)>", m.group(2)) or [""]
        dims = operands[min(1, len(operands) - 1)].split("x")[:-1]
        calls.append((m.group(1), tuple(int(d) for d in dims)))
    return calls
