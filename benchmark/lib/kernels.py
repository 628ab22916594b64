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


def family_of(name: str, expect: dict):
    """The key of ``expect`` that holds the kernel ``name``: its own
    name, else the family (a key that ends in ``*``) whose text before
    the ``*`` it begins with, else None."""
    if name in expect:
        return name
    return next((key for key in expect
                 if key.endswith("*") and name.startswith(key[:-1])), None)


def kernel_calls(found: dict, expect: dict) -> tuple:
    """``(calls, unnamed)``: the calls found under each key of
    ``expect`` (a family's members together), and the kernels that no
    key holds, with their counts."""
    calls, unnamed = dict.fromkeys(expect, 0), {}
    for name, n in found.items():
        key = family_of(name, expect)
        if key is None:
            unnamed[name] = n
        else:
            calls[key] += n
    return calls, unnamed


def check_kernels(found: dict, expect: dict) -> tuple:
    """``(ok, unnamed)``: whether the step holds what a cell names, and
    the kernels it does not name, with their counts.

    A plain key of ``expect`` names one kernel, which has to occur
    exactly that often. A key that ends in ``*`` names a family, every
    kernel whose name begins with the text before the ``*``; its value
    is ``[least, most]`` calls of the family together (a kernel that has
    a plain key of its own counts there and not in a family). That is
    what the check guards: no fall-back to XLA and no layer without its
    kernel (none of the family, or too few), no other program in its
    place (too many), and not how a backward pass is cut into calls. A
    kernel the cell's author did not foresee does not make a program
    incorrect (its numbers are held by the reference check); a member
    of a named family is named, and is not listed among those."""
    calls, unnamed = kernel_calls(found, expect)
    bounds = {key: want if key.endswith("*") else (want, want)
              for key, want in expect.items()}
    return all(least <= calls[key] <= most
               for key, (least, most) in bounds.items()), unnamed


def compared_calls(found: dict, expect: dict) -> dict:
    """``{"calls.<key>": (calls found, what the cell's file says)}`` for
    every kernel or family a cell names: the numbers that
    ``check_kernels`` compared, as a run prints them."""
    calls, _ = kernel_calls(found, expect)
    return {f"calls.{key}": (calls[key], expect[key]) for key in expect}
