"""What every traffic kind gets from run.py: where to work, how to start
children, and the run's arguments."""

from __future__ import annotations

import json
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from .proc import Children
from .spec import BENCH

# what the recommendation template's AlgorithmParams takes beside the
# iteration count
ENGINE_PARAMS = ("rank", "lambda_", "seed")


@dataclass
class RunContext:
    workload: str
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    control: bool
    work: Path = field(init=False)
    children: Children = field(init=False)
    lines: list[str] = field(default_factory=list)

    def __post_init__(self):
        # a fixed place inside the checkout; made anew for every run
        self.work = BENCH / "out" / "work" / self.workload
        shutil.rmtree(self.work, ignore_errors=True)
        self.work.mkdir(parents=True)
        self.side = self.work / "side.json"
        self.children = Children(self.work, self.work / "home", self.rehearse)
        self.children.env["PIO_BENCH_SIDE"] = str(self.side)
        self.children.host_env["PIO_BENCH_SIDE"] = str(self.side)

    def say(self, line: str) -> None:
        """A line worth reading that is not the result: printed at once
        and kept for benchmarks/out/."""
        if self.rehearse:
            line = "REHEARSAL " + line
        print(line, flush=True)
        self.lines.append(line)

    def make_engine(self, datasource_params: dict, algorithm: dict,
                    iterations: int) -> Path:
        """The benchmark's engine directory for this run: its engine.py
        and an engine.json with this run's sizes and the parameters of
        the configuration's `algorithm` block that the template's
        algorithm takes (the rest of the block states defaults of the
        program, which no run sets)."""
        engine = self.work / "engine"
        shutil.copytree(BENCH / "engine", engine)
        variant = json.loads((engine / "engine.json").read_text())
        variant["datasource"]["params"] = datasource_params
        variant["algorithms"][0]["name"] = algorithm["name"]
        variant["algorithms"][0]["params"] = {
            **{k: algorithm[k] for k in ENGINE_PARAMS},
            "num_iterations": iterations}
        (engine / "engine.json").write_text(json.dumps(variant, indent=2))
        return engine

    def reduce_trace(self, trace_dir: Path, crop_event: str | None = None
                     ) -> dict:
        """The profiler's trace reduced by a child that may import jax
        (held to the host), once the chip-holding child has gone."""
        out = self.work / "trace.json"
        argv = [sys.executable, str(BENCH / "lib" / "trace_reduce.py"),
                str(trace_dir), str(out)]
        if crop_event:
            argv += ["--crop-event", crop_event]
        self.children.run("trace_reduce", argv, timeout=600, host_only=True)
        return json.loads(out.read_text())

    def read_side(self) -> dict:
        return json.loads(self.side.read_text()) if self.side.exists() else {}

    def clock(self) -> float:
        return time.monotonic()

    def cleanup(self) -> None:
        """Whatever happened, no child outlives the run and the heavy
        files (model blobs, traces) go."""
        self.children.stop_all()
        for heavy in ("home", "trace"):
            shutil.rmtree(self.work / heavy, ignore_errors=True)
