"""Open-loop arrival schedules from a cell's parameters and a seed.

The arithmetic is that of the program's ``serve/loadgen.py::
build_schedule``: a non-homogeneous Poisson process by Lewis-Shedler
thinning (candidates at the peak rate, each kept with probability
rate(t)/peak), every draw from one seeded generator in a fixed order, so
the schedule is a pure function of (parameters, seed). The draws are
made in bulk instead of one call per arrival (the original spends ~60 us
of ``rng.choice`` per request: seconds of set-up at chip rates), so the
two do not produce the same trace from the same seed.

Parameters (a cell's ``traffic`` block):
  rate_rps        carrier rate
  segments        optional [{"t0","t1","rate_mult"}], seconds from the
                  start of the measured window: bursts and lulls
  head_mix / tier_mix   optional {name: weight}
"""

from __future__ import annotations

import numpy as np


def _rate_at(t: np.ndarray, rate: float, segments) -> np.ndarray:
    out = np.full(t.shape, float(rate))
    for seg in segments:
        inside = (t >= seg["t0"]) & (t < seg["t1"])
        out[inside] = rate * float(seg["rate_mult"])
    return out


def _draw_tags(rng, mix, n: int):
    names = sorted(mix)
    w = np.asarray([float(mix[k]) for k in names])
    return [names[i] for i in rng.choice(len(names), size=n, p=w / w.sum())]


def build_schedule(traffic: dict, *, seed: int, duration_s: float,
                   offset_s: float = 0.0) -> dict:
    """Arrivals on ``[0, duration_s)``. ``offset_s`` shifts the segments
    (a pre-roll before the measured window keeps their times relative to
    the window). Returns ``{"t": float64[n], "head": [...], "tier":
    [...]}``."""
    rate = float(traffic["rate_rps"])
    segments = [dict(s, t0=s["t0"] + offset_s, t1=s["t1"] + offset_s)
                for s in traffic.get("segments", ())]
    peak = rate * max([1.0] + [float(s["rate_mult"]) for s in segments])
    rng = np.random.default_rng(seed)
    # Enough candidates for the window with overwhelming probability:
    # mean + 6 sigma + a constant.
    mean = peak * duration_s
    n_cand = int(mean + 6.0 * np.sqrt(mean) + 16)
    t = np.cumsum(rng.exponential(1.0 / peak, size=n_cand))
    coin = rng.random(n_cand) * peak
    keep = (t < duration_s) & (coin <= _rate_at(t, rate, segments))
    t = t[keep]
    return {
        "t": t,
        "head": _draw_tags(rng, traffic.get("head_mix") or {"probs": 1},
                           len(t)),
        "tier": _draw_tags(rng, traffic.get("tier_mix")
                           or {"interactive": 1}, len(t)),
    }
