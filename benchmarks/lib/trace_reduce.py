"""Child of a traced run: reduces the profiler's `.xplane.pb` to what the
per-layer readers and the result line need. Runs after the chip-holding
child has gone, with JAX held to the host (only `jax.profiler.ProfileData`
is used; the harness itself never imports jax).

    python benchmarks/lib/trace_reduce.py <trace dir> <out.json> \
        [--crop-event REGEX]

With `--crop-event` the window is cut to [end of the first host event
whose name matches, end of the last]: the benchmark engine's own
`observe` calls mark the ends of the iterations on the trace's clock.
Without it the window is the whole trace.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib import intervals  # noqa: E402

#: host events shorter than this are not candidates for a gap's name
MIN_HOST_SPAN_NS = 50_000


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise SystemExit(f"trace_reduce: no .xplane.pb under {trace_dir}")
    return found[-1]


def read_planes(path: Path, crop_event: str | None = None):
    """(device planes, host events, marks): each device plane as {name,
    ops: (names, starts, ends)} of its op line; host events as (names,
    starts, ends) over every host thread; marks as the ends of the host
    events, of any length, that match `crop_event`. Times in ns on the
    trace's clock."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, host, marks = [], ([], [], []), []
    pat = re.compile(crop_event) if crop_event else None
    for plane in data.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            by_name = {ln.name: ln for ln in lines}
            line = by_name.get("XLA Ops")
            if line is None:
                continue
            names, starts, ends = [], [], []
            for ev in line.events:
                names.append(ev.name)
                starts.append(ev.start_ns)
                ends.append(ev.start_ns + ev.duration_ns)
            devices.append({"name": plane.name, "ops": (names, starts, ends)})
        elif plane.name.startswith("/host:CPU"):
            for line in lines:
                for ev in line.events:
                    if pat is not None and pat.search(ev.name):
                        marks.append(ev.start_ns + ev.duration_ns)
                    if ev.duration_ns >= MIN_HOST_SPAN_NS:
                        host[0].append(ev.name)
                        host[1].append(ev.start_ns)
                        host[2].append(ev.start_ns + ev.duration_ns)
    return devices, host, sorted(marks)


def reduce(devices, host, marks=None) -> dict:
    """`marks`, when given, cut the window to [first mark, last mark]."""
    import numpy as np

    all_starts = [s for d in devices for s in d["ops"][1]] + list(host[1])
    all_ends = [e for d in devices for e in d["ops"][2]] + list(host[2])
    if not all_starts:
        raise SystemExit("trace_reduce: the trace holds no event")
    lo, hi = float(min(all_starts)), float(max(all_ends))
    if marks is not None:
        if len(marks) < 2:
            raise SystemExit("trace_reduce: fewer than two host events "
                             "match --crop-event")
        lo, hi = float(marks[0]), float(marks[-1])
    busy_total = 0.0
    ops: dict[str, list] = {}
    gap_list = []
    for d in devices:
        names, starts, ends = d["ops"]
        s, e = intervals.clip(starts, ends, lo, hi)
        busy = intervals.merge(s, e)
        busy_total += sum(b - a for a, b in busy)
        for n, a, b in zip(names, s.tolist(), e.tolist()):
            if b > a:
                rec = ops.setdefault(n, [0, 0.0])
                rec[0] += 1
                rec[1] += (b - a) / 1e9
        gap_list += intervals.gaps(busy, lo, hi)
    gap_list.sort(key=lambda g: g[0] - g[1])
    h_names, h_s, h_e = host[0], np.asarray(host[1]), np.asarray(host[2])
    idle = [[intervals.attribute(g, h_names, h_s, h_e), (g[1] - g[0]) / 1e9]
            for g in gap_list[:10]]
    ranked = sorted(ops.items(), key=lambda kv: -kv[1][1])
    return {
        "device_planes": [d["name"] for d in devices],
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / 1e9 / max(len(devices), 1),
        "marks_ns": marks or [],
        "ops": [[n, c, t] for n, (c, t) in ranked],
        "device_ops": [[intervals.safe_name(n), t]
                       for n, (_c, t) in ranked[:10]],
        "idle_gaps": idle,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("trace_dir")
    p.add_argument("out")
    p.add_argument("--crop-event", default=None)
    a = p.parse_args(argv)
    devices, host, marks = read_planes(find_xplane(Path(a.trace_dir)),
                                       a.crop_event)
    Path(a.out).write_text(json.dumps(
        reduce(devices, host, marks if a.crop_event else None)))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
