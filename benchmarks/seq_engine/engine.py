"""The benchmark's engine for the sequence-serving cells: the stock
`seqrec` template (templates/seqrec/engine.py) as it is. A `pio deploy`
of this engine serves exactly as the template does; at exit the process
writes its device's peak memory to the file `PIO_BENCH_SIDE` names.

The model is seeded (benchmarks/lib/seq_seed_model.py), never trained
here: training at the published widths does not fit one chip.
"""

from __future__ import annotations

import atexit
import dataclasses
import importlib.util
import json
import os
import sys
from pathlib import Path

import predictionio_tpu

_REPO = Path(predictionio_tpu.__file__).resolve().parents[1]
_SIDE = os.environ.get("PIO_BENCH_SIDE")
#: benchmarks/tests/test_seq_cell.py alone sets this, to see `correct`
#: come out false when the timed path runs 3 passes for the model's 4
_BREAK = os.environ.get("PIO_BENCH_BREAK")


def _load(path: Path, name: str):
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_tpl = _load(_REPO / "templates" / "seqrec" / "engine.py",
             "pio_bench_stock_seqrec")


@atexit.register
def _at_exit() -> None:
    # registered after jax's own exit hook (the CLI imports jax before any
    # engine), so it runs before the backend is torn down
    if not _SIDE or "jax" not in sys.modules:
        return
    path = Path(_SIDE)
    facts = json.loads(path.read_text()) if path.exists() else {}
    try:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        facts["exit_memory_peak_bytes"] = max(
            (s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        facts["exit_memory_limit_bytes"] = max(
            (s.get("bytes_limit", 0) for s in stats), default=0)
    except Exception as e:  # noqa: BLE001 - exiting; say so in the file
        facts["exit_error"] = repr(e)
    tmp = path.with_suffix(".tmp")
    tmp.write_text(json.dumps(facts))
    tmp.replace(path)


if _BREAK == "passes":
    from predictionio_tpu.models import looped_lm as _lm

    _sound = _lm.LoopedLMModel.make_encoder

    def _one_pass_short(self):
        cfg = self.config
        self.config = dataclasses.replace(
            cfg, total_ut_steps=cfg.total_ut_steps - 1)
        try:
            return _sound(self)
        finally:
            self.config = cfg

    _lm.LoopedLMModel.make_encoder = _one_pass_short


def engine_factory():
    return _tpl.engine_factory()
