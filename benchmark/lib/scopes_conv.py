"""A finer table than ``lib/scopes.py::LAYERS`` for a token model whose
layers mix gated short convolutions with attention: the rows that
``telemetry/device_trace.py::TOKEN_LAYERS`` gained with it (copied, and
held equal to the program's by ``tests/test_copies.py``), and a reader
that sums a loaded trace's step by any such table. ``drivers/train_conv.py``
reads the capture with it before ``run.py`` reduces the same capture by
the frozen table, under which the mixer is ``msa_glue`` (the module
keeps the attention's name, ``msa``) and its norm ``msa_norm``; the
metrics ``conv_mixer_ms``, ``conv_mix_ms`` and ``conv_mix_roofline_pct``
read the result.

A program without these scopes (an older one, another model) matches
none of the rows and the reader returns an empty table.
"""

from __future__ import annotations

import re
import statistics

from . import scopes, xplane

# Asked in this order, first match wins (the program's rule).
ROWS = tuple((name, re.compile(rf"(?:^|/)(?:{pat})(?:/|$)"))
             for name, pat in (
    ("conv_proj", r"msa/conv/(?:in|out)_proj"),
    ("conv_mix", r"msa/conv/mix"),
))


def row_of(scope: str, rows=ROWS):
    """The row of ``rows`` an op's scope path falls under, or None."""
    path = scopes._WRAPPER.sub("", (scope or "").split(";")[0])
    return next((name for name, pat in rows if pat.search(path)), None)


def fine_rows_ms(trace: dict, module_prefix: str, rows=ROWS) -> dict:
    """``{row: ms a step}``: per chip, over the complete executions of
    the step program but the first and the last, the median over steps
    of the summed durations of the ops (``xplane.leaves``: a loop's body
    once) under each of ``rows``; the mean over chips. Empty where no op
    matches a row or the trace has fewer than three steps."""
    per_chip = []
    for plane in trace["planes"]:
        if not xplane.DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        steps = sorted(
            (m["start_ns"], m["start_ns"] + m["dur_ns"])
            for m in lines.get(xplane.MODULE_LINE, ())
            if m["name"].startswith(module_prefix))[1:-1]
        if not steps:
            continue
        sums = {}
        for e in xplane.leaves(lines.get(xplane.OPS_LINE, [])):
            row = row_of(e.get("scope", ""), rows)
            if row is None:
                continue
            for i, (lo, hi) in enumerate(steps):
                if lo <= e["start_ns"] < hi:
                    sums.setdefault(row, [0] * len(steps))[i] += e["dur_ns"]
                    break
        per_chip.append({row: statistics.median(v) / 1e6
                         for row, v in sums.items()})
    found = set().union(*per_chip) if per_chip else ()
    return {row: sum(c.get(row, 0.0) for c in per_chip) / len(per_chip)
            for row in sorted(found)}
