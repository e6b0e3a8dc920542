#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmarks/run.py --workload <config>.<traffic> --seed N \
        --seconds S --trace 0|1

drives one cell of BENCHMARK.json through the entry points a user calls
(`pio train`, `pio deploy`, POST /queries.json) with the program's default
options, and prints, as its last line, one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, and `breakdown` in a traced
run. With `--trace 0` the metrics are the cell's end-to-end metrics; with
`--trace 1` its per-layer metrics. No accelerator, or fewer chips than
the cell asks for, is exit code 1 and no result line.

This process never imports jax: a chip belongs to one process at a time,
so every chip-holding verb is its own child, one at a time.

    python benchmarks/run.py --rehearse --workload ... --seconds 4

runs the same control flow at toy sizes on the host, says so on every
line it prints, and prints no result line.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # the run's wall time starts here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from lib import spec  # noqa: E402
from lib.proc import RunFailed  # noqa: E402
from lib.runctx import RunContext  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse", action="store_true",
                   help="toy sizes on the host, labelled, no result line")
    p.add_argument("--control", action="store_true",
                   help="also print what the lower-precision control reads "
                        "(the builder's runs; never the driver's)")
    a = p.parse_args(argv)

    if not (spec.REPO / "predictionio_tpu" / "tools" / "cli.py").is_file():
        raise RunFailed(f"no predictionio_tpu checkout at {spec.REPO}")
    sys.path.append(str(spec.REPO))  # the harness reads the program's records
    cell = spec.load_cell(a.workload)
    ctx = RunContext(workload=a.workload, seed=a.seed, seconds=a.seconds,
                     trace=bool(a.trace), rehearse=a.rehearse,
                     control=a.control)
    try:
        kind = spec.kind_module(cell["traffic"]["kind"])
        run = kind.run(ctx, cell)
    finally:
        ctx.cleanup()
    if "jax" in sys.modules:
        raise RunFailed("the harness imported jax")

    wall = time.monotonic() - T0
    setup_s = wall - run["window_s"] - run["check_s"]
    ctx.say(f"run: wall={wall:.3f} s window={run['window_s']:.3f} s "
            f"check={run['check_s']:.3f} s setup_s={setup_s:.3f} s")
    device = dict(run["device"])
    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"], "metrics": {}, "device": device}
    if a.trace:
        evidence = run["evidence"]
        evidence["harness"]["setup_s"] = setup_s
        for m in spec.metrics_of(cell, "per_layer"):
            value = spec.read_layer_metric(m["name"], evidence)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        trace = evidence["trace"]
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    else:
        values = {**run["metrics"], "setup_s": setup_s}
        for m in spec.metrics_of(cell, "end_to_end"):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    out = spec.BENCH / "out"
    (out / f"{a.workload}.seed{a.seed}.trace{a.trace}.json").write_text(
        json.dumps({"lines": ctx.lines, "result": result,
                    "rehearsal": a.rehearse}, indent=1))
    if a.rehearse:
        print("REHEARSAL (host, toy sizes; says nothing about the chip): "
              + json.dumps(result["metrics"]))
        return 0
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
