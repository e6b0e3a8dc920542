"""The comparison that decides `correct` has been shown to fail.

`test_*_control_*`: the reference in the nearest lower precision (one
bfloat16 pass for float32), put in the program's place, comes out as not
correct, at a size a test run can hold. On the chip, at the cells' own
sizes, the same was read on three seeds or more (PERF.md, section 2).

`test_broken_*`: a whole rehearsal of a cell (the harness's look for a
chip skipped, as `--rehearse` does) with the timed path broken where the
answers are produced, and `correct` comes out false.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from lib import draw, reference, spec

SIZES = (2000, 60_000, 64, 10)    # users, items, rank, k


def served_by(precision):
    rows = np.arange(0, 2000, 50)
    q = draw.factor_rows(11, draw.USER_SIDE, rows, SIZES[0], SIZES[2])
    ids, scores, _ = reference.scan_catalog(11, q, SIZES[1], SIZES[2],
                                            SIZES[3], precision=precision)
    return rows, [[{"item": f"i{int(i)}", "score": float(s)}
                   for i, s in zip(ir, sr)] for ir, sr in zip(ids, scores)]


def test_serving_reference_passes_itself_and_the_bf16_control_fails():
    rows, sound = served_by("float32")
    got = reference.check_served(11, rows, sound, *SIZES)
    assert got["score_rel_err"] <= reference.SCORE_RTOL / 10
    assert got["wrong_ids"] == got["short"] == 0
    rows, control = served_by("bfloat16")
    assert control == reference.control_served(11, rows, *SIZES)
    got = reference.check_served(11, rows, control, *SIZES)
    assert got["score_rel_err"] > 3 * reference.SCORE_RTOL


def test_training_control_bf16_products_raise_the_heavy_rows_residual():
    """The normal equations of a heavily rated row solved from products
    rounded to bfloat16 leave a residual several times the float32
    solve's, which is what the chip's control showed (PERF.md)."""
    rng = np.random.default_rng(3)
    n, rank, lam = 60_000, 64, 0.01
    x = rng.standard_normal((n, rank)).astype(np.float32) * 0.3
    r = rng.integers(0, 101, n).astype(np.float32)

    def solve(xs):
        a = xs.astype(np.float64).T @ xs.astype(np.float64)
        a += lam * n * np.eye(rank)
        return np.linalg.solve(a, xs.astype(np.float64).T @ r)

    users = np.arange(n)
    for factors, worse in ((x, False), (reference.to_bf16(x), True)):
        v = solve(factors).astype(np.float32)[None]
        resid = reference.als_item_residuals(
            x, v, np.array([0]), np.array([n]), users, r, lam)[0]
        assert (resid > 1e-4) == worse, resid


def rehearse(workload, broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PIO_BENCH_BREAK", None)
    if broken:
        env["PIO_BENCH_BREAK"] = broken
    proc = subprocess.run(
        [sys.executable, str(spec.BENCH / "run.py"), "--rehearse",
         "--workload", workload, "--seed", "77", "--seconds", "3",
         "--trace", "0"], env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert proc.stdout.splitlines()[-1].startswith("REHEARSAL")
    out = json.loads((spec.BENCH / "out"
                      / f"{workload}.seed77.trace0.json").read_text())
    return out["result"]


@pytest.mark.parametrize("workload,broken", [
    ("als-amazon18.serve-steady", "answers"),
    ("als-amazon18.serve-closed", "answers"),
    ("als-kdd11.train", "train"),
])
def test_broken_timed_path_comes_out_not_correct(workload, broken):
    assert rehearse(workload, None)["correct"] is True
    result = rehearse(workload, broken)
    assert result["correct"] is False
    if broken == "answers":
        assert result["failed"] > 0
