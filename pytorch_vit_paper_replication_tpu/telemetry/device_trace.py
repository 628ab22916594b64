"""The program reads its own device trace: a captured train step's device
time by module and by forward / backward / optimizer.

Two stages, so that the arithmetic is tested without a chip: :func:`load`
turns a capture (``.xplane.pb``, read with ``jax.profiler.ProfileData``
and nothing else) into plain Python, :func:`reduce` turns that into the
table. ``python -m pytorch_vit_paper_replication_tpu.telemetry.device_trace
<capture dir or .xplane.pb>`` prints it; :class:`.profiling.ProfileController`
runs both when a capture window closes.

**Where an op's module comes from.** On the v5e (jax 0.9, libtpu 0.0.34)
a device plane ``/device:TPU:<n>`` has the line ``XLA Modules`` (one
event per execution of a jitted program) and ``XLA Ops`` (one event per
HLO instruction the core ran, named by the instruction's text). The
profile keeps **no** scope path: an op event's only stats are its device
offset and duration, and the instruction text carries no ``metadata=``
(checked on the chip, PR 24). So the path is joined in: the event's
instruction name (``fusion.123``) is looked up in ``{instruction:
op_name}`` parsed from the optimized HLO of the step program
(:func:`parse_scopes`), whose ``op_name`` is the jax name stack —
``jit(train_step)/transpose(jvp(ViT))/backbone/encoder_block_3/msa/qkv/
dot_general``: flax module names, the ``named_scope`` s of
``ops/attention.py`` (``attn_core``) and ``engine.make_train_step``
(``loss``, ``metrics``, ``optimizer``), and the Pallas kernels' ``name=``.
``load`` still asks the event first (a stat ``tf_op`` / ``name``, then
``metadata={op_name=...}`` in its text), for installations that keep it.

**What a row is.** An op counts once, with its own duration, under its
own path. XLA fuses across scopes and a fusion carries one path, its
root's — the softmax passes over the logits are rooted at the attention
core's ``dot_general`` s, an optimizer update fused into the weight
gradient counts with the gradient. That is what every profile viewer
shows; the reader does not split a fusion. An instruction the compiler
made itself (an asynchronous copy between memory spaces, a slice of a
prefetched weight) has no path and takes its consumer's. What matches
nothing is the row ``other``, reported, never dropped.
"""

from __future__ import annotations

import argparse
import bisect
import gzip
import json
import re
import statistics
import sys
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE, OPS_LINE, ASYNC_LINE = "XLA Modules", "XLA Ops", "Async XLA Ops"
# Written by ProfileController into a capture's directory: the scope map
# of the step program and the host's spans on the trace's clock.
PROGRAM_FILE = "program.json.gz"
TABLE_FILE = "device_time.json"
MIN_STEPS = 3
PHASES = ("forward", "backward", "recompute", "optimizer")

_COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast|ragged-all-to-all)"
    r"(-start|-done)?$")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")
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

# A token model's rows, asked BEFORE :data:`LAYERS` (which stays as the
# benchmark's frozen copy has it): the named scopes of the routed
# feed-forward inside ``mlp`` (``ops/moe.py``), the rotary embedding
# inside ``msa``, the token embedding and the head with its loss
# (``ops/lm_loss.py``). First a multi-token-prediction module's rows
# (its merge, its block whole, its norm and its pass through the head),
# then the latent attention's two paths and the shared expert, then the
# sparse-attention indexer's four (matched without ``msa/`` in front: an
# op inside the loops over chunks of query rows may carry the path from
# the loop's body on), then a conv layer's mixer (its two projections and
# the gate-conv-gate between them; the module keeps the name ``msa``, so
# the frozen table counts it under ``msa_glue`` and its norm under
# ``msa_norm``). A ViT's paths match none of them.
TOKEN_LAYERS = tuple((name, re.compile(rf"(?:^|/)(?:{pat})(?:/|$)"))
                     for name, pat in (
    ("mtp_merge", r"mtp/(?:.*/)?mtp_merge"),
    ("mtp_block", r"mtp/(?:.*/)?encoder_block_\d+"),
    ("mtp_head", r"mtp"),            # the norm, the second head + loss
    ("mla_q", r"msa/qkv/q_(?:down|up)"),
    ("mla_kv", r"msa/qkv/kv_(?:down|up)"),
    ("moe_shared", r"mlp/(?:.*/)?moe_shared"),
    ("moe_router", r"mlp/moe_router"),
    ("moe_dispatch", r"mlp/(?:.*/)?moe_dispatch"),
    ("moe_experts", r"mlp/(?:.*/)?moe_experts"),
    ("moe_combine", r"mlp/(?:.*/)?moe_combine"),
    ("indexer/proj", r"indexer/proj"),
    ("indexer/scores", r"indexer/scores"),
    ("indexer/select", r"indexer/select"),
    ("indexer_loss", r"indexer_loss"),
    ("conv_proj", r"msa/conv/(?:in|out)_proj"),
    ("conv_mix", r"msa/conv/mix"),
    ("rope", r"msa/rope"),
    ("token_embedding", r"token_embedding"),
    ("head_loss", r"head/(?:.*/)?loss"),
    ("head", r"head/(?:.*/)?head"),
))


# ------------------------------------------------------------------ scopes
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
        (layer for layer, pat in TOKEN_LAYERS + LAYERS if pat.search(path)),
        "other")
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


# -------------------------------------------------------------------- load
def parse_instruction(text: str) -> dict:
    """``name`` / ``op`` / ``out`` / ``mosaic`` of one ``XLA Ops`` event,
    whose name is the HLO instruction's text (the keys and values that
    ``benchmark/lib/xplane.py`` keeps, so one recorded file serves both
    readers). A bare name (``fusion.3``) reads as its own opcode."""
    if " = " not in text:
        name = text.lstrip("%")
        return {"name": name, "op": re.sub(r"[.\d]+$", "", name),
                "out": "", "mosaic": False}
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):                      # a tuple of results
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    return {"name": name.lstrip("%"), "op": rest.split("(", 1)[0],
            "out": _LAYOUT.sub("", shape)[:96],
            "mosaic": 'custom_call_target="tpu_custom_call"' in rest}


def _event_scope(ev, row: dict, scopes: dict) -> str:
    """The op's scope path: from the event if the profile keeps it, else
    joined in by instruction name (module docstring)."""
    for key, value in ev.stats:
        if key in ("tf_op", "name", "op_name") and isinstance(value, str) \
                and "/" in value:
            return value
    found = _OP_NAME.search(ev.name)
    return found.group(1) if found else scopes.get(row["name"], "")


def find_xplane(path) -> Path:
    """The newest ``.xplane.pb`` under a capture directory (or the file)."""
    path = Path(path)
    if path.is_file():
        return path
    files = sorted(path.rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return files[-1]


def load(path, scopes=None) -> dict:
    """Plain-Python view of a capture: of every ``/device:TPU:<n>`` plane
    the executions of the ``XLA Modules`` line, the ops of the ``XLA
    Ops`` line and the collectives in flight of ``Async XLA Ops``, each
    with ``start_ns`` / ``dur_ns`` on the trace's clock; an op also has
    its opcode, result type, ``mosaic`` with the ``kernel`` 's name, and
    ``scope``. ``scopes`` is :func:`parse_scopes` 's map, or a callable
    that makes it, called only if the capture has a device plane."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(str(find_xplane(path))).planes:
        if DEVICE_PLANE.match(plane.name) is None:
            continue
        scopes = (scopes() if callable(scopes) else scopes) or {}
        lines = []
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OPS_LINE, ASYNC_LINE):
                continue
            events = []
            for ev in line.events:
                row = {"name": ev.name, "start_ns": int(ev.start_ns),
                       "dur_ns": int(ev.duration_ns)}
                if line.name != MODULE_LINE:
                    row.update(parse_instruction(ev.name))
                    if line.name == ASYNC_LINE and \
                            not _COLLECTIVE.match(row["op"]):
                        continue         # copies and slices in flight
                    row["scope"] = _event_scope(ev, row, scopes)
                    if row["mosaic"]:
                        row["kernel"] = kernel_name(row)
                events.append(row)
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def kernel_name(row: dict) -> str:
    """A Mosaic call's kernel: the scope segment that ``pallas_call(name=)``
    adds in front of ``pallas_call``, else the instruction's own name."""
    parts = row["scope"].split("/")
    if "pallas_call" in parts[1:]:
        return parts[parts.index("pallas_call", 1) - 1]
    return re.sub(r"[.\d]+$", "", row["name"])


# ------------------------------------------------------------------ reduce
def _iv(ev):
    return ev["start_ns"], ev["start_ns"] + ev["dur_ns"]


def _union(intervals) -> list:
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def covered(intervals):
    """Total length that ``(start, end)`` intervals cover, each instant
    once."""
    return sum(hi - lo for lo, hi in _union(intervals))


def _events(plane, line_name):
    return next((ln["events"] for ln in plane["lines"]
                 if ln["name"] == line_name), [])


def leaves(events) -> list:
    """The ops of one ``XLA Ops`` line without the control flow that
    encloses others: the line lays a ``while`` over the ops of its body
    (the passes of ``ops/moe.py``: 85.8 ms a step counted twice in the
    first traced run of PR 28), and the body's ops are the device's
    work. A loop whose body left no event of its own stays."""
    events = sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    return [e for e, after in zip(events, events[1:] + [None])
            if e.get("op") not in ("while", "conditional", "call")
            or after is None or _iv(after)[1] > _iv(e)[1]]


def module_end_ns(trace: dict, prefix: str):
    """End of the first execution of the program named ``prefix...`` on
    the first chip that ran it (the clock anchor), or None."""
    for plane in trace["planes"]:
        ends = sorted(_iv(m)[1] for m in _events(plane, MODULE_LINE)
                      if m["name"].startswith(prefix))
        if ends:
            return ends[0]
    return None


def shift_spans(spans, host_ns: int, trace_ns: int) -> list:
    """Host spans ``(name, start_ns, end_ns)`` set on the trace's clock,
    given one instant (the anchor program's end) read on both."""
    shift = trace_ns - host_ns
    return [(name, lo + shift, hi + shift) for name, lo, hi in spans]


def _chip(plane, module_prefix, by_block):
    """One chip's share of :func:`reduce`, or the reason it has none."""
    ops = sorted(leaves(_events(plane, OPS_LINE))
                 + _events(plane, ASYNC_LINE), key=lambda e: e["start_ns"])
    runs = sorted((m for m in _events(plane, MODULE_LINE)
                   if m["name"].startswith(module_prefix)),
                  key=lambda m: m["start_ns"])
    # The first execution seen may have begun before the capture, and the
    # last is cut where the capture ended while it ran (a window closes
    # when its last step is dispatched, not when it has run): a step
    # program's device time is steady to 0.01%, so a last execution
    # shorter than the others by 1% is a cut one.
    steps = runs[1:]
    if len(steps) > 1 and steps[-1]["dur_ns"] < 0.99 * statistics.median(
            m["dur_ns"] for m in steps[:-1]):
        steps = steps[:-1]
    if len(steps) < MIN_STEPS:
        return None, (f"{len(steps)} complete executions of "
                      f"{module_prefix!r} on {plane['name']} (of "
                      f"{len(runs)} seen); a table needs {MIN_STEPS}")
    starts = [e["start_ns"] for e in ops]
    per_step, rows, calls = [], {}, {}
    for i, m in enumerate(steps):
        lo, hi = _iv(m)
        inside = ops[bisect.bisect_left(starts, lo):
                     bisect.bisect_left(starts, hi)]
        compute, coll, mosaic = [], [], 0
        for e in inside:
            key = classify(e.get("scope", ""), op=e.get("op", ""),
                           name=e["name"], kernel=e.get("kernel", ""),
                           by_block=by_block)
            if key[0] == "collective":
                coll.append(_iv(e))
                continue
            compute.append(_iv(e))
            mosaic += e["dur_ns"] if e.get("mosaic") else 0
            rows.setdefault(key, [0] * len(steps))[i] += e["dur_ns"]
            calls.setdefault(key, [0] * len(steps))[i] += 1
        busy, computing = covered(compute + coll), covered(compute)
        exposed = busy - computing
        if coll:
            key = ("collective", "forward")
            rows.setdefault(key, [0] * len(steps))[i] = exposed
            calls.setdefault(key, [0] * len(steps))[i] = len(coll)
        per_step.append({"step": hi - lo, "busy": busy, "mosaic": mosaic,
                         "xla": computing - mosaic,
                         "collective": covered(coll),
                         "collective_exposed": exposed})
    window = (steps[0]["start_ns"], _iv(steps[-1])[1])
    busy_iv = [[max(lo, window[0]), min(hi, window[1])]
               for lo, hi in _union(_iv(e) for e in ops)
               if min(hi, window[1]) > max(lo, window[0])]
    out = {f"{k}_ms": statistics.median(s[k] for s in per_step) / 1e6
           for k in per_step[0]}
    out.update(
        steps=len(steps), window=window, busy_iv=busy_iv,
        window_ms=(window[1] - window[0]) / 1e6,
        window_busy_ms=sum(hi - lo for lo, hi in busy_iv) / 1e6,
        rows={k: statistics.median(v) / 1e6 for k, v in rows.items()},
        calls={k: statistics.median(v) for k, v in calls.items()})
    return out, None


def reduce(trace: dict, module_prefix: str = "jit_train_step",
           host_spans=(), by_block: bool = False) -> dict:
    """The table of a loaded capture. Over the complete executions of the
    step program (``module_prefix`` in the ``XLA Modules`` line; at least
    3, else no table and a ``reason``), per chip the median over steps,
    then the mean over chips:

    * ``step_ms``; ``busy_ms`` (union of the op intervals inside a step);
      ``mosaic_ms`` / ``xla_ms`` / ``collective_ms`` /
      ``collective_exposed_ms`` as ``benchmark/lib/xplane.py`` has them;
      ``idle_pct`` of the window from the first complete step's start to
      the last one's end.
    * ``rows``: device milliseconds per step for each (layer, phase) that
      occurs — :data:`LAYERS`, a Mosaic call under its kernel's name,
      ``collective`` (the part under no compute op), ``other``. They sum
      to ``busy_ms`` to within overlapping ops. ``by_block`` keeps the
      encoder blocks apart (``attn_core@3``).
    * ``idle_gaps``: the ten longest gaps of the window, each named by the
      host span (``(name, start_ns, end_ns)`` on the trace's clock, see
      :func:`shift_spans`) that covers most of it, else ``(no span)``.
    """
    planes = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not planes:
        return {"chips": 0, "reason": "no device plane in the capture "
                "(a backend without one, such as the CPU)"}
    chips = []
    for plane in planes:
        chip, reason = _chip(plane, module_prefix, by_block)
        if chip is None:
            return {"chips": len(planes), "reason": reason}
        chips.append(chip)
    mean = lambda values: sum(values) / len(chips)
    out = {"chips": len(chips), "module": module_prefix,
           "steps": min(c["steps"] for c in chips)}
    for key in ("step_ms", "busy_ms", "mosaic_ms", "xla_ms",
                "collective_ms", "collective_exposed_ms", "window_ms"):
        out[key] = mean([c[key] for c in chips])
    out["idle_pct"] = 100.0 * mean(
        [1.0 - c["window_busy_ms"] / c["window_ms"] for c in chips])
    keys = sorted({k for c in chips for k in c["rows"]})
    out["rows"] = sorted(
        ({"layer": layer, "phase": phase,
          "ms": mean([c["rows"].get((layer, phase), 0.0) for c in chips]),
          "calls": mean([c["calls"].get((layer, phase), 0)
                         for c in chips])}
         for layer, phase in keys), key=lambda r: -r["ms"])
    for row in out["rows"]:
        row["pct_of_step"] = 100.0 * row["ms"] / out["step_ms"]
    out["other_pct"] = sum(r["pct_of_step"] for r in out["rows"]
                           if r["layer"].startswith("other"))
    gaps = []
    for c in chips:
        edges = [c["window"][0]] + [t for iv in c["busy_iv"] for t in iv] \
            + [c["window"][1]]
        gaps += [(lo, hi) for lo, hi in zip(edges[::2], edges[1::2])
                 if hi > lo]
    out["idle_gaps"] = []
    for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        cover = {}
        for name, s0, s1 in host_spans:
            cover[name] = cover.get(name, 0) + max(
                0, min(hi, s1) - max(lo, s0))
        best = max(cover, key=cover.get, default=None)
        out["idle_gaps"].append(
            {"ms": (hi - lo) / 1e6,
             "span": best if best and cover[best] > 0 else "(no span)"})
    return out


# ------------------------------------------------------------------- print
def format_table(result: dict) -> str:
    """Layer x phase, milliseconds per step and % of the step."""
    if "rows" not in result:
        return f"device time: no table ({result.get('reason')})"
    layers = {}
    for row in result["rows"]:
        layers.setdefault(row["layer"], {})[row["phase"]] = row["ms"]
    phases = [p for p in PHASES if any(p in v for v in layers.values())]
    head = f"{'layer':<18}" + "".join(f"{p:>11}" for p in phases) \
        + f"{'total ms':>11}{'% of step':>11}"
    lines = [
        f"device time per step: {result['step_ms']:.2f} ms, busy "
        f"{result['busy_ms']:.2f} ms, idle {result['idle_pct']:.2f}% of the "
        f"captured window ({result['steps']} steps, {result['chips']} "
        f"chip(s), {result['module']})", head]
    for layer, by in sorted(layers.items(),
                            key=lambda kv: -sum(kv[1].values())):
        total = sum(by.values())
        lines.append(
            f"{layer:<18}"
            + "".join(f"{by[p]:>11.3f}" if p in by else f"{'':>11}"
                      for p in phases)
            + f"{total:>11.3f}{100 * total / result['step_ms']:>11.2f}")
    totals = [sum(by.get(p, 0.0) for by in layers.values())
              for p in phases]
    lines.append(f"{'sum':<18}" + "".join(f"{t:>11.3f}" for t in totals)
                 + f"{sum(totals):>11.3f}"
                 f"{100 * sum(totals) / result['step_ms']:>11.2f}")
    if result["idle_gaps"]:
        lines.append("longest idle gaps: " + ", ".join(
            f"{g['ms']:.3f} ms {g['span']}"
            for g in result["idle_gaps"][:5]))
    return "\n".join(lines)


def read_program(capture_dir) -> dict:
    """What the controller left beside a capture (scope map, module name,
    host spans on the trace's clock), or empty defaults."""
    capture_dir = Path(capture_dir)
    for d in [capture_dir, *capture_dir.parents][:5]:
        if (d / PROGRAM_FILE).is_file():
            with gzip.open(d / PROGRAM_FILE, "rt") as f:
                return json.load(f)
    return {}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Device time of a captured train step by module and "
                    "by forward / backward / optimizer.")
    ap.add_argument("capture", help="capture directory or .xplane.pb")
    ap.add_argument("--by-block", action="store_true",
                    help="keep the encoder blocks apart")
    ap.add_argument("--json", metavar="FILE",
                    help=f"write the table here (default: {TABLE_FILE} "
                         "beside the capture)")
    args = ap.parse_args(argv)
    xplane = find_xplane(args.capture)
    program = read_program(xplane.parent)
    result = reduce(
        load(xplane, program.get("scopes")),
        module_prefix=program.get("module") or "jit_train_step",
        host_spans=[tuple(s) for s in program.get("host_spans", ())],
        by_block=args.by_block)
    print(format_table(result))
    target = Path(args.json) if args.json else (
        Path(args.capture) if Path(args.capture).is_dir()
        else xplane.parent) / TABLE_FILE
    target.write_text(json.dumps(result, indent=1))
    return 0 if "rows" in result else 1


if __name__ == "__main__":
    sys.exit(main())
