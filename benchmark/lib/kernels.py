"""Least work of the fused MLP half-block kernel, from shapes alone.

The kernel (``ops/fused_mlp.py``) computes, for N = images x tokens rows
of width D and hidden width M, ``x + drop(fc2(drop(gelu(fc1(LN(x))))))``.
What the algorithm needs, not what the kernel happens to do:

* forward: 2 GEMMs (fc1, fc2), 2*N*D*M FLOPs each;
* backward: 4 GEMMs (dW2, dH, dW1, dX), 2*N*D*M each. The kernel also
  recomputes fc1 to rebuild the hidden tile; recomputation is not
  counted;
* bytes: activations in and out once in the compute dtype (forward: x
  in, y out; backward: x and dy in, dx out), both weight matrices once
  (forward: read; backward: read, and the two weight gradients written).
  LN parameters and biases are a few KiB and are left out.
"""

from __future__ import annotations

import re


def mlp_half_block_cost(rows: int, d: int, m: int, *, layers: int,
                        act_bytes: int = 2, w_bytes: int = 2,
                        backward: bool = True) -> dict:
    """FLOPs and HBM bytes of every MLP half-block call of one step on
    one chip: ``layers`` forward calls and, with ``backward``, as many
    backward calls."""
    gemm = 2.0 * rows * d * m
    flops = 2 * gemm
    bytes_ = 2 * rows * d * act_bytes + 2 * d * m * w_bytes
    if backward:
        flops += 4 * gemm
        bytes_ += 3 * rows * d * act_bytes + 4 * d * m * w_bytes
    return {"flops": layers * flops, "bytes": float(layers * bytes_)}


def roofline_seconds(cost: dict, peak: dict) -> dict:
    """Least time the chip could take for ``cost`` and which peak bounds
    it (``compute`` or ``memory``)."""
    t_flops = cost["flops"] / (peak["bf16_tflops"] * 1e12)
    t_bytes = cost["bytes"] / (peak["hbm_gb_per_s"] * 1e9)
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "compute_s": t_flops, "memory_s": t_bytes}


def is_mlp_half_block(out: str, m: int) -> bool:
    """Whether a Mosaic call whose result type is ``out`` (as the trace
    names it) is this kernel. Until the program names its kernels they
    are told apart by what they return: the forward returns the hidden
    activations ``[rows, m]`` (the rows padded to the kernel's block:
    18,944 for L/16's 18,912), the backward the weight gradient
    ``[d, m]``, and no other kernel of the program (flash attention
    returns ``[batch, heads, tokens, head_dim]`` blocks and per-row
    statistics) returns a matrix of the hidden width."""
    return re.search(rf"\[\d+,{m}\]", out) is not None
