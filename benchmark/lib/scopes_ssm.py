"""A finer table than ``lib/scopes.py::LAYERS`` for a token model whose
layers mix Mamba-2 state-space layers with attention: the rows that
``telemetry/device_trace.py::TOKEN_LAYERS`` gained with it (copied, and
held equal to the program's by ``tests/test_copies.py``), read with
``lib/scopes_conv.py``'s reader. ``drivers/train_ssm.py`` reads the
capture with it before ``run.py`` reduces the same capture by the frozen
table, under which the mixer is ``msa_glue`` (the module keeps the
attention's name, ``msa``) and its norm ``msa_norm``; the metrics
``ssm_mixer_ms``, ``ssm_scan_ms`` and ``ssm_scan_roofline_pct`` read the
result.

A program without these scopes (an older one, another model) matches
none of the rows and the reader returns an empty table.
"""

from __future__ import annotations

import re

from . import scopes_conv

# Asked in this order, first match wins (the program's rule).
ROWS = tuple((name, re.compile(rf"(?:^|/)(?:{pat})(?:/|$)"))
             for name, pat in (
    ("ssm_proj", r"msa/ssm/(?:in|out)_proj"),
    ("ssm_conv", r"msa/ssm/conv"),
    ("ssm_scan", r"msa/ssm/scan"),
    ("ssm_norm", r"msa/ssm/gate_norm"),
))


def row_of(scope: str):
    """The row an op's scope path falls under, or None."""
    return scopes_conv.row_of(scope, ROWS)


def fine_rows_ms(trace: dict, module_prefix: str) -> dict:
    """``{row: ms a step}`` by :data:`ROWS` (``scopes_conv.fine_rows_ms``)."""
    return scopes_conv.fine_rows_ms(trace, module_prefix, ROWS)
