#!/usr/bin/env python3
"""The one sweep per serving configuration that finds its knee.

    python benchmarks/sweep.py --config als-amazon18 --traffic serve-steady \
        --seed N --rates 100:10,400,440,480 --seconds 27 [--callers 1024:60,1536]

Seeds and deploys the configuration once (the program's defaults), then
offers each rate in turn as an open loop of `--seconds` (or of the
seconds after its colon) after the mix's warm-up, and last one
closed-loop phase for each pool under `--callers`. For each phase one
line: what was offered, what completed, the latency's median and 99th
percentile, the median of the window's first and second half (a queue
that grows shows there), what was still unanswered when the last request
had been sent, and the answers completed per second in each 10 s of the
sending time. Answers are counted when well-formed; none is compared
with the reference here (every run of a cell does that).

The knee is the highest rate at which the backlog does not grow: nothing
failed, the second half's median within GROWTH of the first's over a
phase of at least MIN_LATENCIES median latencies (a shorter phase cannot
show a slow growth and is not eligible), and the 99th percentile under
TAIL_MULTIPLE times the lightest load's median. The line `knee:` is the
rule's own output; the cell's rate, four fifths of it, goes into
`benchmarks/cells/<config>.<traffic>.json` by hand, with this table,
which goes into PERF.md too.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lib import serve, spec  # noqa: E402
from lib.runctx import RunContext  # noqa: E402
from lib.stats import percentile  # noqa: E402

GROWTH = 1.03          # steady rates read within 1.01 (PERF.md, section 4)
MIN_LATENCIES = 10.0
TAIL_MULTIPLE = 3.0


def phase_row(label, seconds, verdict, outcome) -> dict:
    win = verdict["window"]
    idx, lat = win["idx"], verdict["latency_ms"]
    half = len(idx) // 2
    end_of_sending = float(np.nanmax(outcome.sent))
    unanswered = int((~(outcome.done <= end_of_sending)
                      & ~np.isnan(outcome.sent)).sum())
    return {
        "offered": label, "seconds": seconds,
        "attempted": verdict["attempted"], "failed": verdict["failed"],
        "completed_per_s": verdict["good"] / win["window_s"],
        "completed_per_s_by_10s": serve.completed_by_slices(verdict, outcome),
        "p50_ms": percentile(lat.tolist(), 50),
        "p99_ms": percentile(lat.tolist(), 99),
        "mean_ms": float(np.mean(lat)),
        "p50_first_half_ms": percentile(lat[:half].tolist(), 50),
        "p50_second_half_ms": percentile(lat[half:].tolist(), 50),
        "unanswered_at_last_send": unanswered,
        "lag_p99_ms": percentile(verdict["lag_ms"].tolist(), 99),
    }


def knee(rows: list[dict]) -> str | None:
    """The highest open-loop rate that meets the rule in the docstring."""
    open_rows = [r for r in rows if r["offered"].endswith("q/s")]
    if not open_rows:
        return None
    light = open_rows[0]["p50_ms"]
    held = [r for r in open_rows
            if r["failed"] == 0
            and r["seconds"] * 1e3 >= MIN_LATENCIES * r["p50_ms"]
            and r["p50_second_half_ms"] <= GROWTH * r["p50_first_half_ms"]
            and r["p99_ms"] <= TAIL_MULTIPLE * light]
    return held[-1]["offered"] if held else None


def phases_of(text: str, kind: str, seconds: float) -> list[tuple]:
    """`a[:s],b[:s]` -> [(kind, a, s), ...]."""
    out = []
    for item in filter(None, text.split(",")):
        value, _, own = item.partition(":")
        out.append((kind, float(value) if kind == "open" else int(value),
                    float(own) if own else seconds))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--traffic", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=27.0)
    p.add_argument("--callers", default="")
    p.add_argument("--rehearse", action="store_true")
    a = p.parse_args(argv)
    sys.path.append(str(spec.REPO))
    cell = {"name": f"{a.config}.sweep", "config_name": a.config, "chips": 1,
            "config": spec.load_json(spec.BENCH / "configs" / f"{a.config}.json"),
            "traffic": spec.load_json(spec.BENCH / "traffic" / f"{a.traffic}.json")}
    ctx = RunContext(workload=cell["name"], seed=a.seed, seconds=a.seconds,
                     trace=False, rehearse=a.rehearse, control=False)
    rows = []
    try:
        live = serve.start_server(ctx, cell)
        ctx.say("phases: " + json.dumps(live["spans"]))
        phases = (phases_of(a.rates, "open", a.seconds)
                  + phases_of(a.callers, "closed", a.seconds))
        for mode, offered, seconds in phases:
            phase = serve.drive(
                ctx, cell, live, mode, seconds=seconds,
                rate_qps=offered if mode == "open" else None,
                callers=offered if mode == "closed" else None)
            verdict = serve.judge(ctx, cell, live, phase, mode, check=False)
            label = (f"{offered:g} q/s" if mode == "open"
                     else f"{offered} callers")
            rows.append(phase_row(label, seconds, verdict, phase["outcome"]))
            ctx.say("SWEEP " + json.dumps(rows[-1]))
            time.sleep(2.0)  # let the queue drain between phases
        ctx.say(f"knee: {knee(rows)}")
        serve.stop_server(ctx, live)
        side = ctx.read_side()
        ctx.say(f"device: {json.dumps(live['device'])} memory_peak_bytes="
                f"{side.get('exit_memory_peak_bytes')} of "
                f"{side.get('exit_memory_limit_bytes')}")
    finally:
        ctx.cleanup()
    out = spec.BENCH / "out" / f"sweep.{a.config}.{a.traffic}.json"
    out.write_text(json.dumps({"rows": rows, "lines": ctx.lines}, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
