"""Seconds since this process started, on the host's boot clock."""

from __future__ import annotations

import os
import time

_IMPORTED = time.clock_gettime(time.CLOCK_BOOTTIME)


def _start_boottime() -> float:
    """Field 22 of /proc/self/stat: start time in ticks since boot. Falls
    back to this module's import time (a lower bound on set-up)."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        return ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return _IMPORTED


_START = _start_boottime()


def since_process_start() -> float:
    return time.clock_gettime(time.CLOCK_BOOTTIME) - _START
