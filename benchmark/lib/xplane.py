"""From a profiler trace (``.xplane.pb``) to busy/idle, step, kernel and
collective times, and to the step's device time by layer and phase.

Two stages, so that the arithmetic can be checked without a chip:
``load`` turns the file into plain Python (planes -> lines -> events),
``reduce_trace`` turns that into numbers. ``ProfileData`` needs nothing
but jax. Times are nanoseconds on the trace's own clock.

What a TPU trace looks like (v5e, jax 0.9): one plane per chip named
``/device:TPU:<n>``; on it the line ``XLA Modules`` has one event per
execution of a jitted program (named ``jit_<fn>(<fingerprint>)``), and
``Async XLA Ops`` the start-to-done span of each asynchronous op (of
which ``load`` keeps the collectives), and
``XLA Ops`` one event per HLO op executed by the core, named by the
whole HLO instruction (``%mlp.36 = (bf16[50432,768]{...}, ...)
custom-call(...), custom_call_target="tpu_custom_call", ...``): ``load``
keeps its name, opcode and output shape, and joins in the scope path
that the profile does not keep (``lib/scopes.py``). A Pallas kernel is a
``custom-call`` whose target is ``tpu_custom_call`` (other custom calls
are XLA's own); its ``kernel`` is the name the program gave it. The
host's threads are not read: the benchmark captures
the device alone and adds its own host spans as a ``/host:CPU`` plane,
set on the trace's clock by an anchor program (``harness.Capture``).
"""

from __future__ import annotations

import bisect
import functools
import gzip
import json
import re
from pathlib import Path

from . import kernels, scopes

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"    # start-to-done spans of asynchronous ops
HOST_PLANE = "/host:CPU"

# HLO opcodes of cross-chip collectives (with their async halves).
_COLLECTIVE = scopes._COLLECTIVE
PHASES = ("forward", "backward", "recompute", "optimizer")
_LAYOUT = re.compile(r"\{[^{}]*\}|/\*.*?\*/")


def parse_hlo(text: str) -> dict:
    """``{"name", "op", "out", "mosaic"}`` of one ``XLA Ops`` event name.
    A bare name (``fusion.3``) reads as its own opcode."""
    if " = " not in text:
        name = text.lstrip("%")
        return {"name": name, "op": re.sub(r"[.\d]+$", "", name),
                "out": "", "mosaic": False}
    name, rest = text.split(" = ", 1)
    if rest.startswith("("):                 # a tuple shape: balanced
        depth = end = 0
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


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, scopes_by_name=None) -> dict:
    """Plain-Python view of an xplane file: of every device plane the
    module line, the op line (parsed) and the collectives in flight.
    ``scopes_by_name`` is ``{instruction: op_name}`` of the traced
    program (``scopes.parse_scopes(compiled.as_text())["scopes"]``):
    every op gets its ``scope`` from it, and a Mosaic call its
    ``kernel``. Without it an op has no scope (its layer is ``other``)
    and a kernel is named by its instruction."""
    from jax.profiler import ProfileData

    scopes_by_name = scopes_by_name or {}

    path = Path(path)
    if path.suffix == ".gz":
        with gzip.open(path, "rb") as f:
            raw = f.read()
        data = (ProfileData.from_text_proto(raw.decode())
                if ".textproto" in path.name
                else ProfileData.from_serialized_xspace(raw))
    else:
        data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name) is None:
            continue
        lines = []
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OPS_LINE, ASYNC_LINE):
                continue
            events = []
            for ev in line.events:
                row = {"name": ev.name, "start_ns": int(ev.start_ns),
                       "dur_ns": int(ev.duration_ns)}
                if line.name in (OPS_LINE, ASYNC_LINE):
                    row.update(parse_hlo(ev.name))
                    if line.name == ASYNC_LINE and \
                            op_class(row) != "collective":
                        continue        # copies and slices in flight
                    row["scope"] = scopes_by_name.get(row["name"], "")
                    if row["mosaic"]:
                        row["kernel"] = scopes.kernel_name(row)
                events.append(row)
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def load_events_json(path) -> dict:
    """A trace saved by ``dump_events_json`` (the recorded fixture)."""
    with gzip.open(path, "rt") as f:
        return json.load(f)


def dump_events_json(trace: dict, path) -> None:
    with gzip.open(path, "wt") as f:
        json.dump(trace, f, separators=(",", ":"))


def trim(trace: dict, *, module_prefix: str, steps: int) -> dict:
    """The part of a loaded trace that holds ``steps`` executions of the
    step program, from the start of the second one seen on the first
    chip (how ``fixtures/`` is kept small)."""
    first = next(p for p in trace["planes"] if DEVICE_PLANE.match(p["name"]))
    runs = sorted(_iv(m) for m in _line(first, MODULE_LINE)
                  if m["name"].startswith(module_prefix))
    lo = runs[1][0] - 1000
    hi = runs[min(steps + 1, len(runs) - 1)][0] - 1000
    planes = []
    for plane in trace["planes"]:
        lines = [{"name": ln["name"], "events": [
            e for e in ln["events"] if lo <= e["start_ns"] < hi]}
            for ln in plane["lines"]]
        planes.append({"name": plane["name"],
                       "lines": [ln for ln in lines if ln["events"]]})
    return {"planes": planes}


# ------------------------------------------------------------ arithmetic
def merged(intervals) -> list:
    """The intervals as a sorted list of disjoint ``[start, end]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals) -> int:
    """Total length covered by ``(start, end)`` intervals."""
    return sum(e - s for s, e in merged(intervals))


def overlap_ns(a, b) -> int:
    """Length of the intersection of two interval sets (each merged)."""
    a, b = merged(a), merged(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def gaps(intervals, lo: int, hi: int) -> list:
    """Idle intervals inside ``[lo, hi]`` left by ``intervals``."""
    out, cur = [], lo
    for s, e in merged(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def op_class(ev: dict) -> str:
    """``mosaic`` (a Pallas kernel), ``collective`` or ``xla`` for one
    parsed event of the ``XLA Ops`` line."""
    if ev.get("mosaic"):
        return "mosaic"
    if _COLLECTIVE.match(ev.get("op") or ""):
        return "collective"
    return "xla"


def op_label(ev: dict) -> str:
    """What the breakdown sums by: the op's name without its number, its
    opcode and its output shape, so that the twelve layers' copies of
    one fusion are one row."""
    base = re.sub(r"[.\d]+$", "", ev["name"])
    label = base if base == ev.get("op") else f"{base} {ev.get('op')}"
    return f"{label} -> {ev['out']}"[:160] if ev.get("out") else label


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    return (xs[n // 2] if n % 2 else 0.5 * (xs[n // 2 - 1] + xs[n // 2])) \
        if n else None


def _iv(ev):
    return (ev["start_ns"], ev["start_ns"] + ev["dur_ns"])


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


CONTROL_FLOW = ("while", "conditional", "call")


def leaves(events) -> list:
    """The ops of one ``XLA Ops`` line without the control flow that
    encloses others: the line lays a ``while`` (a ``conditional``, a
    ``call``) over the ops of its body, and the body's ops are the
    device's work; added together, a loop's time counts twice (87 ms a
    step in ``st21b_train_16k`` from PR 28 to PR 29: the passes of
    ``ops/moe.py``). A loop whose body left no event of its own stays.
    Copied from ``telemetry/device_trace.py::leaves`` and held equal to
    it by ``tests/test_copies.py``."""
    events = sorted(events, key=lambda e: (e["start_ns"], -e["dur_ns"]))
    return [e for e, after in zip(events, events[1:] + [None])
            if e.get("op") not in CONTROL_FLOW
            or after is None or _iv(after)[1] > _iv(e)[1]]


@functools.lru_cache(maxsize=None)
def _row_of(scope: str, op: str, name: str, kernel: str) -> tuple:
    """``(layer, phase)`` of an op: ``scopes.classify``, asked once for
    the instruction and not once for each step and chip it ran in. Only
    an MLP half-block kernel is a layer of its own."""
    return scopes.classify(
        scope, op=op, name=name,
        kernel=kernel if kernel in kernels.MLP_KERNELS else "")


def _add(sums: dict, key, i: int, n: int, ns: int) -> None:
    """``sums[key][i] += ns``, where ``sums[key]`` has one slot for each
    of the ``n`` steps (a step without the key counts as 0)."""
    sums.setdefault(key, [0] * n)[i] += ns


def _mean_of_dicts(dicts) -> dict:
    """Per key the mean over ``dicts`` (the chips), a chip without the
    key counting as 0."""
    return {k: sum(d.get(k, 0.0) for d in dicts) / len(dicts)
            for k in sorted(set().union(*dicts))}


def layer_ms(rows_ms: dict, *layers) -> float:
    """Milliseconds per step in ``layers``, every phase, of a ``rows_ms``
    table; a layer that no op ran under counts as 0."""
    return sum(sum(rows_ms.get(layer, {}).values()) for layer in layers)


def format_rows(reduced: dict) -> str:
    """One line: each layer's milliseconds per step by phase, largest
    first, and their sum against the step's busy time."""
    rows = reduced.get("rows_ms") or {}
    parts = []
    for layer in sorted(rows, key=lambda k: -layer_ms(rows, k)):
        by = " ".join(f"{p[:3]} {rows[layer][p]:.3f}" for p in PHASES
                      if p in rows[layer])
        parts.append(f"{layer} {layer_ms(rows, layer):.3f} ({by})")
    total = layer_ms(rows, *rows)
    return ("device ms per step by layer (phase): " + " | ".join(parts)
            + f" | rows' sum {total:.3f} of busy "
            f"{reduced.get('busy_ms', 0.0):.3f} ms")


def _default_window(per_chip, all_iv, module_prefix):
    """From the start of the second execution of the step program to the
    end of the last, on the chip where that is widest (the first may
    have begun before the capture, and what precedes it is the
    profiler's start); without steps, first to last device event."""
    spans = []
    for chip in per_chip:
        steps = sorted(_iv(m) for m in chip["mods"]
                       if module_prefix
                       and m["name"].startswith(module_prefix))
        if len(steps) >= 3:
            spans.append((steps[1][0], steps[-1][1]))
    if spans:
        return min(s for s, _ in spans), max(e for _, e in spans)
    if not all_iv:
        return 0, 0
    return min(s for s, _ in all_iv), max(e for _, e in all_iv)


def reduce_trace(trace: dict, *, module_prefix: str = "",
                 window_ns=None) -> dict:
    """Numbers from a loaded trace.

    ``module_prefix``: the step program's name in the ``XLA Modules``
    line (``jit_train_step``); its executions delimit the steps. Per
    chip, over the complete executions: the median duration, and per
    execution the op time inside it by class (Mosaic calls also by
    their kernel's name), the part of the collectives (their ops, and
    their start-to-done spans where the trace has them) during which no
    other op runs on that chip, and ``rows_ms[layer][phase]``: every
    op's own duration under the layer and phase of its scope
    (``scopes.classify``). One rule holds for every kernel, present or
    to come: an MLP half-block kernel (by name, ``kernels.MLP_KERNELS``)
    is a row of its own; **every other op, XLA or Mosaic, counts under
    the layer its scope names**, so ``attn_core`` is whatever ran under
    that scope. The collectives' exposed part is the row ``collective``,
    so that the rows of a step sum to its ``busy_ms`` (to within ops
    that overlap). ``xla_by_phase_ms`` is the same sum over the ops that
    are neither Mosaic calls nor collectives. **A step with loops is
    read once:** every sum (the rows, the XLA / Mosaic split, the time
    by kernel, ``device_ops``) is over ``leaves`` of the op line, so a
    ``while`` laid over its body's ops adds nothing to them. Busy time
    is the union of all op intervals, control flow with them (modules
    where a plane has no op line), inside the traced window, which is
    ``window_ns`` or else ``_default_window``: a union counts nothing
    twice. Values are means over chips of per-chip medians."""
    devs = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devs:
        return {"chips": 0, "busy_s": 0.0, "window_s": 0.0,
                "device_ops": [], "gaps": []}
    per_chip = []
    all_iv = []
    for plane in devs:
        ops = _line(plane, OPS_LINE)
        mods = _line(plane, MODULE_LINE)
        busy_iv = [_iv(e) for e in (ops or mods)]
        all_iv.extend(busy_iv)
        work = leaves(ops)
        kept = {id(e) for e in work}
        per_chip.append({"plane": plane["name"], "ops": work, "mods": mods,
                         "busy_iv": busy_iv,
                         # control flow laid over its body: busy, no work
                         "over": [_iv(e) for e in ops if id(e) not in kept],
                         "async": _line(plane, ASYNC_LINE)})
    if window_ns is None:
        window_ns = _default_window(per_chip, all_iv, module_prefix)
    lo, hi = window_ns
    out = {"chips": len(devs), "window_s": (hi - lo) / 1e9,
           "per_chip": []}
    op_totals, gap_list = {}, []
    for chip in per_chip:
        clipped = [(max(s, lo), min(e, hi)) for s, e in chip["busy_iv"]
                   if min(e, hi) > max(s, lo)]
        busy = union_ns(clipped)
        row = {"plane": chip["plane"], "busy_s": busy / 1e9}
        steps = [m for m in chip["mods"]
                 if module_prefix and m["name"].startswith(module_prefix)
                 and m["start_ns"] >= lo
                 and m["start_ns"] + m["dur_ns"] <= hi]
        row["steps"] = len(steps)
        if steps:
            row["step_ms"] = _median(m["dur_ns"] for m in steps) / 1e6
            acc = {"mosaic": [], "collective": [], "xla": [],
                   "collective_exposed": [], "busy": [], "mosaic_calls": []}
            # Per key a list with one sum (ns) per step.
            rows, by_kernel, xla_phase = {}, {}, {}
            ops_sorted = sorted(chip["ops"] + chip["async"],
                                key=lambda e: e["start_ns"])
            starts = [e["start_ns"] for e in ops_sorted]
            for i, m in enumerate(steps):
                s0, s1 = _iv(m)
                a = bisect.bisect_left(starts, s0)
                b = bisect.bisect_left(starts, s1)
                over = [iv for iv in chip["over"] if s0 <= iv[0] < s1]
                by = {"mosaic": [], "collective": [], "xla": []}
                for e in ops_sorted[a:b]:
                    cls = op_class(e)
                    by[cls].append(_iv(e))
                    if cls == "collective":
                        continue
                    kernel = e["kernel"] if cls == "mosaic" else ""
                    key = _row_of(e.get("scope", ""), e.get("op", ""),
                                  e["name"], kernel)
                    _add(rows, key, i, len(steps), e["dur_ns"])
                    if cls == "mosaic":
                        _add(by_kernel, kernel, i, len(steps), e["dur_ns"])
                    else:
                        _add(xla_phase, key[1], i, len(steps), e["dur_ns"])
                compute = by["mosaic"] + by["xla"]
                coll = union_ns(by["collective"])
                exposed = coll - overlap_ns(by["collective"], compute)
                if by["collective"]:
                    _add(rows, ("collective", "forward"), i, len(steps),
                         exposed)
                acc["mosaic"].append(sum(e - s for s, e in by["mosaic"]))
                acc["mosaic_calls"].append(len(by["mosaic"]))
                acc["xla"].append(union_ns(by["xla"]))
                acc["collective"].append(coll)
                acc["collective_exposed"].append(exposed)
                acc["busy"].append(
                    union_ns(compute + by["collective"] + over))
            for k, v in acc.items():
                key = k if k == "mosaic_calls" else f"{k}_ms"
                row[key] = _median(v) / (1 if k == "mosaic_calls" else 1e6)
            row["rows_ms"] = {k: _median(v) / 1e6 for k, v in rows.items()}
            row["mosaic_by_kernel_ms"] = {
                k: _median(v) / 1e6 for k, v in by_kernel.items()}
            row["xla_by_phase_ms"] = {
                k: _median(v) / 1e6 for k, v in xla_phase.items()}
        for e in chip["ops"]:
            if lo <= e["start_ns"] < hi:
                label = op_label(e)
                op_totals[label] = op_totals.get(label, 0) + e["dur_ns"]
        gap_list.extend(gaps(chip["busy_iv"], lo, hi))
        out["per_chip"].append(row)
    n = len(out["per_chip"])
    out["busy_s"] = sum(r["busy_s"] for r in out["per_chip"]) / n
    for key in ("step_ms", "mosaic_ms", "xla_ms", "collective_ms",
                "collective_exposed_ms", "busy_ms", "mosaic_calls"):
        vals = [r[key] for r in out["per_chip"] if key in r]
        if vals:
            out[key] = sum(vals) / len(vals)
    stepped = [r for r in out["per_chip"] if "rows_ms" in r]
    for key in ("mosaic_by_kernel_ms", "xla_by_phase_ms"):
        out[key] = _mean_of_dicts([r[key] for r in stepped])
    out["rows_ms"] = {}
    for (layer, phase), ms in _mean_of_dicts(
            [r["rows_ms"] for r in stepped]).items():
        out["rows_ms"].setdefault(layer, {})[phase] = ms
    out["steps"] = min((r["steps"] for r in out["per_chip"]), default=0)
    # Top device ops by total time, summed over chips then averaged.
    out["device_ops"] = [[k, v / 1e9 / n] for k, v in sorted(
        op_totals.items(), key=lambda kv: -kv[1])[:10]]
    out["gaps"] = sorted(gap_list, key=lambda g: g[0] - g[1])[:10]
    return out


def module_end_ns(trace: dict, prefix: str):
    """End of the first execution of the module named ``prefix...`` on
    the first chip that has one (the clock anchor), or None."""
    for plane in trace["planes"]:
        ends = sorted(_iv(m)[1] for m in _line(plane, MODULE_LINE)
                      if m["name"].startswith(prefix))
        if ends:
            return ends[0]
    return None


def host_spans(trace: dict) -> list:
    """The benchmark's own spans on the host plane, as ``(name,
    start_ns, end_ns)``."""
    out = []
    for plane in trace["planes"]:
        if plane["name"].startswith(HOST_PLANE):
            for line in plane["lines"]:
                out.extend((e["name"], *_iv(e)) for e in line["events"])
    return out


def attribute_gaps(gap_list, spans) -> list:
    """Name each idle gap by the host span that covers most of it
    (``(no bench span)`` where none does): ``[[name, seconds], ...]``,
    summed by name, longest first, at most 10."""
    by = {}
    for g0, g1 in gap_list:
        best, best_ov = "(no bench span)", 0
        for name, s0, s1 in spans:
            ov = min(g1, s1) - max(g0, s0)
            if ov > best_ov:
                best, best_ov = name, ov
        by[best] = by.get(best, 0) + (g1 - g0)
    return [[k, v / 1e9] for k, v in sorted(
        by.items(), key=lambda kv: -kv[1])[:10]]
