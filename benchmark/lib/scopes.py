"""From an op of the device trace to the layer and the phase it belongs
to: the scope map of a compiled program, the table of layers, and the
rule that reads a scope path.

Copied from ``pytorch_vit_paper_replication_tpu/telemetry/device_trace.py``
(PR 24) and held equal to it by ``tests/test_copies.py``, as ``flops.py``
is: the yardstick reads parent and change by one definition that neither
can edit. The profile keeps no scope path on this installation (jax
0.9.0, libtpu 0.0.34: an ``XLA Ops`` event has its device offset and
duration and nothing else), so the path is joined in: the event's
instruction name (``fusion.123``) looked up in ``{instruction: op_name}``
parsed from the optimized HLO of the step (``compiled.as_text()``),
whose ``op_name`` is the jax name stack — flax module names, the
program's ``named_scope`` s (``attn_core``, ``loss``, ``metrics``,
``optimizer``) and its kernels' ``pallas_call(name=)``.

An op counts once, with its own duration, under its own path. XLA fuses
across scopes and a fusion carries one path, its root's; the reader does
not split a fusion. An instruction the compiler made itself (an
asynchronous copy, a slice of a prefetched weight) has no path and takes
its consumer's. What matches nothing is the layer ``other``.
"""

from __future__ import annotations

import re

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|ragged-all-to-all)"
    r"(-start|-done)?$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'\bmetadata=\{op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPER = re.compile(r"\b(?:jit|jvp|transpose|vmap|pmap|shard_map)\(|\)")
_BLOCK = re.compile(r"(?:^|/)encoder_block_(\d+)(?:/|$)")


# The layer of an op: the first pattern that its scope path matches,
# innermost name first (``msa/norm`` before ``msa``, ``mlp`` before the
# block that holds it). The path is matched with the transform wrappers
# (``transpose(jvp(ViT))`` -> ``ViT``) taken off.
LAYERS = tuple((name, re.compile(rf"(?:^|/)(?:{pat})(?:/|$)"))
               for name, pat in (
    ("msa_norm", r"msa/norm"),
    ("msa_qkv", r"msa/qkv"),
    ("attn_core", r"attn_core"),
    ("msa_out", r"msa/out"),
    ("msa_glue", r"msa"),        # under msa, none of the four: the
    #                              slices of qkv, the transposes
    ("mlp_xla", r"mlp"),         # XLA ops around the MLP kernels
    ("block_glue", r"encoder_block_\d+"),     # the residual adds
    ("patch_embed", r"patch_embedding"),
    ("final_norm_head", r"encoder_norm|head|ViT/[^/]+$"),  # + pooling
    ("loss", r"loss"),
    ("metrics", r"metrics"),
    ("optimizer", r"optimizer"),
))


def parse_scopes(hlo_text: str) -> dict:
    """``{"module": name, "scopes": {instruction: op_name}}`` from the
    optimized HLO text of a program (``jitted.lower(...).compile()
    .as_text()``). An instruction without an ``op_name`` takes that of
    the nearest instruction that uses it, else of its nearest operand."""
    scopes, operands, users = {}, {}, {}
    for name, rest in _INSTRUCTION.findall(hlo_text):
        found = _OP_NAME.search(rest)
        if found and found.group(1):
            scopes[name] = found.group(1)
        operands[name] = _OPERAND.findall(rest)
        for operand in operands[name]:
            users.setdefault(operand, []).append(name)
    for name in [n for n in operands if n not in scopes]:
        for graph in (users, operands):
            seen, frontier = {name}, [name]
            for _ in range(4):
                frontier = [n for f in frontier for n in graph.get(f, ())
                            if n not in seen and not seen.add(n)]
                named = [scopes[n] for n in frontier
                         if n in scopes and n in operands]
                if named or not frontier:
                    break
            if named:
                scopes[name] = named[0]
                break
    module = re.match(r"HloModule (\S+?),", hlo_text)
    return {"module": module.group(1) if module else "", "scopes": scopes}


def classify(scope: str, *, op: str = "", name: str = "",
             kernel: str = "", by_block: bool = False) -> tuple:
    """``(layer, phase)`` of one op from its scope path, its opcode, its
    instruction name and (for a Mosaic call) its kernel's name."""
    if _COLLECTIVE.match(op or ""):
        return "collective", "forward"
    scope = (scope or "").split(";")[0]
    path = _WRAPPER.sub("", scope)
    layer = kernel or next(
        (layer for layer, pat in LAYERS if pat.search(path)), "other")
    if layer == "optimizer":
        phase = "optimizer"
    elif "rematted_computation" in scope or ".remat" in name:
        phase = "recompute"
    elif "transpose(" in scope:
        phase = "backward"
    else:
        phase = "forward"
    block = _BLOCK.search(path) if by_block else None
    return (f"{layer}@{block.group(1)}" if block else layer), phase


def kernel_name(row: dict) -> str:
    """A Mosaic call's kernel: the scope segment that ``pallas_call(name=)``
    adds in front of ``pallas_call``, else the instruction's own name."""
    parts = row["scope"].split("/")
    if "pallas_call" in parts[1:]:
        return parts[parts.index("pallas_call", 1) - 1]
    return re.sub(r"[.\d]+$", "", row["name"])
