"""Interval arithmetic of the trace reduction, apart from the reading of
the profiler's file so that it can be tested on hand-made events."""

from __future__ import annotations

import re

import numpy as np


def merge(starts, ends) -> list[tuple[float, float]]:
    """The intervals' union as sorted disjoint intervals."""
    order = np.argsort(np.asarray(starts, np.float64), kind="stable")
    out: list[list[float]] = []
    for i in order.tolist():
        s, e = float(starts[i]), float(ends[i])
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(starts, ends, lo: float, hi: float):
    s = np.clip(np.asarray(starts, np.float64), lo, hi)
    e = np.clip(np.asarray(ends, np.float64), lo, hi)
    return s, e


def gaps(busy: list[tuple[float, float]], lo: float, hi: float
         ) -> list[tuple[float, float]]:
    """What [lo, hi) has outside the disjoint sorted `busy` intervals."""
    out = []
    cur = lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def attribute(gap: tuple[float, float], names: list[str], starts, ends
              ) -> str:
    """The host span that covers most of the gap; among equals the
    shortest (the deepest call). `host__unattributed` if none overlaps."""
    starts = np.asarray(starts, np.float64)
    ends = np.asarray(ends, np.float64)
    if len(starts) == 0:
        return "host__unattributed"
    overlap = np.minimum(ends, gap[1]) - np.maximum(starts, gap[0])
    best = overlap.max()
    if best <= 0:
        return "host__unattributed"
    cand = np.flatnonzero(overlap >= best * 0.999)
    pick = cand[np.argmin((ends - starts)[cand])]
    return "host__" + safe_name(names[int(pick)])


def safe_name(name: str, limit: int = 96) -> str:
    return re.sub(r"[^A-Za-z0-9_.]+", "_", name).strip("_")[:limit]
