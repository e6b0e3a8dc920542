"""The benchmark's engine for the `hybrid_ssm` cells: the stock `seqrec`
template as it is, through benchmarks/seq_engine/engine.py (imported, not
copied: its exit hook writes the device's peak memory to the file
`PIO_BENCH_SIDE` names). The Algorithm is named in engine.json; the model
is seeded (benchmarks/lib/hybrid_ssm_seed_model.py), never trained here.
"""

from __future__ import annotations

import importlib.util
import os
import sys
from pathlib import Path

import predictionio_tpu

_SHARED = (Path(predictionio_tpu.__file__).resolve().parents[1]
           / "benchmarks" / "seq_engine" / "engine.py")


def _load():
    name = "pio_bench_seq_engine"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, _SHARED)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


#: a rehearsal alone sets this (lib/hybrid_ssm_serve.py): a serving step
#: of so many tokens (lattice from a quarter of it), so that the host
#: compiles small programs
_STEP_TOKENS = os.environ.get("PIO_BENCH_STEP_TOKENS")
if _STEP_TOKENS:
    from predictionio_tpu.models import hybrid_ssm_lm as _lm

    _lm.STEP_TOKEN_BUDGET = int(_STEP_TOKENS)
    _lm.STEP_TOKEN_MIN = int(_STEP_TOKENS) // 4


def engine_factory():
    return _load().engine_factory()
