"""What the benchmark knows about the program's kernels, from shapes and
text alone: the least work of the fused MLP half-block kernel and of the
attention core, and which Mosaic kernels a lowered program holds.

**The MLP half-block kernel** (``ops/fused_mlp.py``) computes, for N =
images x tokens rows of width D and hidden width M,
``x + drop(fc2(drop(gelu(fc1(LN(x))))))``. What the algorithm needs, not
what the kernel happens to do:

* forward: 2 GEMMs (fc1, fc2), 2*N*D*M FLOPs each;
* backward: 4 GEMMs (dW2, dH, dW1, dX), 2*N*D*M each. The kernel also
  recomputes fc1 to rebuild the hidden tile; recomputation is not
  counted;
* bytes: activations in and out once in the compute dtype (forward: x
  in, y out; backward: x and dy in, dx out), both weight matrices once
  (forward: read; backward: read, and the two weight gradients written).
  LN parameters and biases are a few KiB and are left out.

**The attention core** (``ops/attention.py::dot_product_attention``, the
scope ``attn_core``: from q, k, v ``[B, H, T, Dh]`` to o, whatever
computes it):

* forward: 2 GEMMs (q k^T, p v), 2*B*H*T*T*Dh FLOPs each;
* backward: 4 GEMMs (dv, dp, dq, dk) of the same size. Rebuilding the
  logits in the backward is recomputation and is not counted; the
  softmax's exponentials and sums are O(T*T), not O(T*T*Dh), and are
  left out;
* bytes: q, k, v read and o written forward; q, k, v, o, do read and dq,
  dk, dv written backward: 12 tensors of B*H*T*Dh in the compute dtype.
  The ``[T, T]`` logits and probabilities are not counted: the algorithm
  does not need them in HBM (a tile of them fits the chip's VMEM), so a
  program that writes them there is that much further from its roofline.
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


def attention_core_cost(batch: int, heads: int, tokens: int, head_dim: int,
                        *, layers: int, act_bytes: int = 2) -> dict:
    """FLOPs and HBM bytes of the attention core of one train step on one
    chip: ``layers`` forward and backward passes over ``batch`` images
    (module docstring)."""
    gemm = 2.0 * batch * heads * tokens * tokens * head_dim
    tensor = batch * heads * tokens * head_dim * act_bytes
    return {"flops": layers * 6 * gemm, "bytes": float(layers * 12 * tensor)}


def roofline_seconds(cost: dict, peak: dict) -> dict:
    """Least time the chip could take for ``cost`` and which peak bounds
    it (``compute`` or ``memory``)."""
    t_flops = cost["flops"] / (peak["bf16_tflops"] * 1e12)
    t_bytes = cost["bytes"] / (peak["hbm_gb_per_s"] * 1e9)
    return {"seconds": max(t_flops, t_bytes),
            "bound": "compute" if t_flops >= t_bytes else "memory",
            "compute_s": t_flops, "memory_s": t_bytes}


# The MLP half-block kernels by the names the program gives them
# (``pallas_call(name=)``, PR 24): LayerNorm fused in (the default path)
# and the hidden-sliced core under a model axis.
MLP_FORWARD = ("lnmlp_fwd", "mlp_fwd")
MLP_BACKWARD = ("lnmlp_bwd", "mlp_bwd")
MLP_KERNELS = MLP_FORWARD + MLP_BACKWARD

_MOSAIC_CALL = re.compile(
    r'stablehlo\.custom_call @tpu_custom_call\(.*kernel_name = "([^"]+)"')


def kernel_counts(lowered_text: str) -> dict:
    """``{kernel name: calls}`` of the Mosaic custom calls in a lowered
    program's StableHLO text (``jitted.lower(...).as_text()``): what went
    into the program, not what a dispatch says it chose. The Pallas
    interpreter's expansion (off the TPU) leaves none. The names are
    those of ``ops/partition.py::mosaic_calls`` (held equal by a test)."""
    counts = {}
    for name in _MOSAIC_CALL.findall(lowered_text):
        counts[name] = counts.get(name, 0) + 1
    return dict(sorted(counts.items()))


def check_kernels(found: dict, expect: dict) -> tuple:
    """``(ok, unnamed)``: whether every kernel a cell names occurs
    exactly as often as it says, and the kernels it does not name, with
    their counts. A kernel the cell's author did not foresee does not
    make a program incorrect (its numbers are held by the reference
    check); a named kernel that is missing, or a call short or over,
    does: the XLA fallback, or another kernel in its place, would pass
    every numeric check and be another program."""
    ok = all(found.get(name, 0) == n for name, n in expect.items())
    return ok, {k: n for k, n in found.items() if k not in expect}
