"""What the benchmark knows about a Mamba-2 layer's scan, from shapes
alone: the least work of what the program's scope ``ssm/scan`` computes
(from ``x``, ``dt``, ``B`` and ``C`` to ``y``, forward and backward),
whatever computes it, so that a kernel that takes XLA's place is judged
on the same count.

* bytes: forward, ``x [N, H P]``, ``B`` and ``C [N, G N]`` read in the
  compute dtype (bf16), ``dt [N, H]`` in float32, ``y [N, H P]`` written
  in the compute dtype; backward, the same four read again, ``y``'s
  cotangent read, and the cotangents of ``x``, ``B``, ``C`` (bf16) and
  ``dt`` (float32) written. ``A`` and ``D`` (a number a head) are not
  counted. Nothing in between (the decays, the chunks' blocks, the
  states) need reach HBM: one pass each way can hold a chunk's work in
  VMEM and carry the state from chunk to chunk.
* FLOPs: ``flops_ssm.scan_flops`` forward and twice that backward, as
  the step's FLOP count has it.
"""

from __future__ import annotations

from . import flops_ssm


def _ssm_layers(model: dict) -> int:
    return sum(1 for layer in range(model["num_layers"])
               if flops_ssm._is_ssm(model, layer))


def ssm_scan_cost(model: dict, seq_len: int, sequences: int, *,
                  act_bytes: int = 2, dt_bytes: int = 4) -> dict:
    """FLOPs and HBM bytes of the scans of one train step on one chip
    (every state-space layer, forward and backward)."""
    n = sequences * seq_len
    hp = model["ssm_heads"] * model["ssm_head_dim"]
    gn = model.get("ssm_groups", 1) * model["ssm_state"]
    inputs = n * (hp + 2 * gn) * act_bytes + n * model["ssm_heads"] * dt_bytes
    y = n * hp * act_bytes
    forward = inputs + y
    backward = inputs + y + inputs
    flops = 3.0 * sequences * flops_ssm.scan_flops(model, seq_len)
    layers = _ssm_layers(model)
    return {"flops": layers * flops,
            "bytes": float(layers * (forward + backward))}
