"""Device seconds by named scope, from a reduced trace's operations and
the operation-to-scope map the program wrote beside its capture
(`pio_scopes.json`: the v5e profiler's operation lines carry no scope
name; the compiled program's text does). Pure arithmetic: no jax."""

from __future__ import annotations

import json
import re
from pathlib import Path

_KEY = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\(")


def operation_key(name: str) -> str | None:
    """`%name = type opcode` of a profiler event's name: what it shares
    with the line of the compiled program's text."""
    m = _KEY.match(name)
    return None if m is None else f"{m.group(1)} = {m.group(2)} {m.group(3)}"


def load_scopes(trace_dir: Path) -> dict:
    path = Path(trace_dir) / "pio_scopes.json"
    return json.loads(path.read_text()) if path.is_file() else {}


def by_scope(trace_ops: list, scopes: dict) -> dict:
    """{scope: [calls, seconds]} over the traced operations
    ([name, calls, seconds]) that the map names, and under "" what it
    does not name (containers such as `while` are never named: their
    time is their bodies')."""
    out: dict[str, list] = {}
    for name, calls, seconds in trace_ops:
        scope = scopes.get(operation_key(name) or "", "")
        if not scope and re.match(r"^%?(while|conditional|call)[.\d]* = ",
                                  name):
            continue
        rec = out.setdefault(scope, [0, 0.0])
        rec[0] += calls
        rec[1] += seconds
    return out


def seconds_of(scoped: dict, pattern: str) -> float:
    """Seconds of the scopes whose name matches (a scope and the scopes
    inside it: `pio.seq.experts` and `pio.seq.experts.matmul`)."""
    pat = re.compile(pattern)
    return sum(s for name, (_c, s) in scoped.items()
               if name and pat.search(name))
