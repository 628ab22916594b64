"""Shared helpers for the tools/ harnesses (not a CLI itself —
``check_cli`` skips ``_``-prefixed files).

ONE copy of the subprocess-environment recipe: every harness that
spawns fresh children (coldstart A/B, fleet demo, --help smoke) needs
the same three lines, and three drifting copies is how "strip one more
env var" silently reaches only two of them.
"""

from __future__ import annotations

import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent


def cpu_child_env() -> dict:
    """Environment for fresh CPU-pinned child processes:

    * ``JAX_PLATFORMS=cpu`` — children must not wait on (or fight
      over) the parent's TPU,
    * the parent test harness's 8-virtual-device ``XLA_FLAGS`` is
      dropped — it slows children ~8x and measures a topology no
      deployment restarts into,
    * ``JAX_COMPILATION_CACHE_DIR`` is dropped — a cache placed from
      outside is for the chip's programs; the harnesses hand their CPU
      children a ``--compile-cache-dir`` of their own (cold/warm A/Bs
      need one nobody else has warmed), which the variable would
      override,
    * the repo root rides ``PYTHONPATH`` so children import the
      package without an install.
    """
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["PYTHONPATH"] = (str(REPO) + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else str(REPO))
    return env


def ensure_repo_on_path() -> None:
    """Make the package importable when a tool runs uninstalled."""
    if str(REPO) not in sys.path:
        sys.path.insert(0, str(REPO))
