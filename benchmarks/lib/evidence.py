"""Arithmetic the per-layer readers share, over what a traced run
gathered: /stats.json before and after the window, and the reduced
profiler trace."""

from __future__ import annotations

import re


def dig(stats: dict, path: list):
    for key in path:
        stats = stats[key]
    return stats


def stats_delta(evidence: dict, num: list, den: list):
    """(num after - num before) / (den after - den before), a path being
    the keys from the top of /stats.json; None where nothing was counted.
    With a histogram's `sum` over its `count` this is the exact mean of
    what was recorded in the window."""
    before, after = evidence.get("stats_before"), evidence.get("stats_after")
    if before is None or after is None:
        return None
    d = dig(after, den) - dig(before, den)
    if d <= 0:
        return None
    return (dig(after, num) - dig(before, num)) / d


def trace_ops(evidence: dict, pattern: str) -> tuple[int, float]:
    """(calls, device seconds) of the traced operations whose name matches."""
    pat = re.compile(pattern)
    calls, seconds = 0, 0.0
    for name, count, total_s in (evidence.get("trace") or {"ops": []})["ops"]:
        if pat.search(name):
            calls += count
            seconds += total_s
    return calls, seconds
