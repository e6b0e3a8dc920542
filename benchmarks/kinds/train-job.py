"""Traffic kind `train-job`: one `pio train` job with the program's
defaults over ratings drawn from the seed.

The measured window is the job's ALS iterations after the first (which
traces and compiles, or reads the compile cache): it runs from the end
of the first iteration to the end of the last, on the benchmark's own
clock, and `train_iter_ms` is the whole window over the count of its
iterations, so that a stall in any one of them shows. The median of the
iterations, each from the end of one to the end of the next, stands
beside it as a per-layer metric. Everything else the job makes its user
wait for is `setup_s`.
Parameters: benchmarks/traffic/<mix>.json.
"""

import json
import os
import statistics

import numpy as np

from lib import reference
from lib.proc import RunFailed, pio_argv, require_chips

ITERATION_END_EVENT = r"^bench_iteration_end$"


def iterations_for(seconds: float, iter_estimate_s: float) -> int:
    """1 warm-up + as many as fill the window at the estimate, never
    fewer than 3; the estimate is a datum of the cell's own file, so parent
    and change run the same count."""
    return 1 + max(3, round(seconds / iter_estimate_s))


def run(ctx, cell):
    cfg, traffic = cell["config"], cell["traffic"]
    sizes = cfg["rehearsal"]["train"] if ctx.rehearse else cfg["train"]
    estimate = (traffic["rehearsal_iter_estimate_s"] if ctx.rehearse
                else traffic["iter_estimate_s"])
    iterations = iterations_for(ctx.seconds, estimate)
    engine = ctx.make_engine({
        "n_users": sizes["users"], "n_items": sizes["items"],
        "n_ratings": sizes["ratings"], "user_sigma": sizes["user_sigma"],
        "item_exponent": sizes["item_exponent"],
        "item_top_share": sizes["item_top_share"],
        "rating_max": sizes["rating_max"], "data_seed": ctx.seed,
        "structure_seed": sizes["structure_seed"],
        "check_rows": int(traffic["check_rows"]),
        "rehearse": int(ctx.rehearse)}, cfg["algorithm"], iterations)
    ctx.say(f"sizes: {json.dumps(sizes)} iterations={iterations} "
            f"(1 warm-up + {iterations - 1} measured)")

    argv = pio_argv("train", "--engine-dir", str(engine))
    trace_dir = ctx.work / "trace"
    if ctx.trace:
        argv += ["--profile-dir", str(trace_dir)]
    if ctx.control:
        # the program's own lower-precision path in the program's place
        ctx.children.env["PIO_BENCH_CONTROL_DTYPE"] = "bfloat16"
        ctx.say("CONTROL: training with compute_dtype bfloat16; the "
                "comparison below has to come out not correct")
    t = ctx.clock()
    out = ctx.children.run("train", argv, timeout=1150)
    train_wall = ctx.clock() - t
    iid = next((ln.rsplit(": ", 1)[1].strip() for ln in out.splitlines()
                if ln.startswith("Training completed. Engine instance:")),
               None)
    if iid is None:
        raise RunFailed(f"`pio train` named no engine instance\n{out[-2000:]}")
    side = ctx.read_side()
    device = side["device"]
    require_chips(device, cell["chips"], ctx.rehearse)

    # -- what the job recorded of itself ------------------------------------
    os.environ["PIO_HOME"] = ctx.children.env["PIO_HOME"]
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.workflow.serialization import deserialize_models

    inst = Storage.get_metadata().engine_instance_get(iid)
    blob = Storage.get_models().get(iid)
    (model,) = deserialize_models(blob.models, engine_dir=engine)
    Storage.reset()
    trained_on = inst.backend_conf
    if ((trained_on["platform"], trained_on["device_kind"],
         trained_on["device_count"])
            != (device["platform"], device["kind"], device["count"])):
        raise RunFailed(f"`pio train` stamped {trained_on}, its engine saw "
                        f"{device}")
    (attempt,) = json.loads(inst.convergence)
    phases = dict(json.loads(inst.phase_times))
    algo = json.loads(inst.algorithms_params)[0]["params"]
    ctx.say(f"trained with: {json.dumps(algo)} mesh={trained_on['mesh']} "
            f"native={trained_on['native']}")

    # -- the window ---------------------------------------------------------
    marks = side["marks"]
    if [m["iteration"] for m in marks] != list(range(iterations)):
        raise RunFailed(f"the trainer reported iterations "
                        f"{[m['iteration'] for m in marks]}, wanted "
                        f"0..{iterations - 1}")
    ends = [m["t"] for m in marks]
    iters_s = [b - a for a, b in zip(ends, ends[1:])]
    window_s = ends[-1] - ends[0]
    compiles = sum(1 for c in side["compile_times"] if ends[0] < c <= ends[-1])
    steps_s = [m["step_seconds"] for m in marks]
    ctx.say(f"window: {window_s:.3f} s, {len(iters_s)} iterations "
            f"{[round(x * 1e3, 1) for x in iters_s]} ms; first (warm-up) "
            f"step {steps_s[0]:.3f} s; compiles_in_window={compiles}")

    # -- the check ----------------------------------------------------------
    t = ctx.clock()
    verdict = check_model(ctx, cell, model, sizes, algo, attempt)
    check_s = ctx.clock() - t

    als_clock = side["train_als_t1"] - side["train_als_t0"]
    spans = {
        "pio_train_wall_s": train_wall,
        "data_draw_s": side["data_draw_s"],
        "data_frame_s": side["data_frame_s"],
        "read_training_s": phases.get("datasource.read_training"),
        "train_outside_als_s": train_wall - als_clock,
        "als_unaccounted_s": (als_clock - attempt["layoutSeconds"]
                              - attempt["uploadSeconds"] - sum(steps_s)),
        "train_iter_median_ms": statistics.median(iters_s) * 1e3,
        "als_step_ms": statistics.median(steps_s[1:]) * 1e3,
    }
    ctx.say("phases: " + json.dumps({k: round(v, 3) for k, v in spans.items()
                                     if v is not None}))
    trace = None
    if ctx.trace:
        trace = ctx.reduce_trace(trace_dir, crop_event=ITERATION_END_EVENT)
    return {
        "device": {**device, "memory_peak_bytes": side["memory_peak_bytes"]},
        "attempted": len(iters_s), "failed": 0,
        "correct": verdict, "window_s": window_s, "check_s": check_s,
        "metrics": {"train_iter_ms": window_s / len(iters_s) * 1e3},
        "evidence": {
            "harness": spans, "convergence": attempt, "trace": trace,
            "device_kind": device["kind"],
            "shapes": {"n_ratings": sizes["ratings"],
                       "n_users": sizes["users"], "n_items": sizes["items"],
                       "rank": algo["rank"],
                       "cg_iters": int(cell["traffic"]["cg_iters_counted"]),
                       "iterations_in_window": len(iters_s)},
        },
    }


def check_model(ctx, cell, model, sizes, algo, attempt) -> bool:
    """The persisted model against the float64 normal equations of a
    seeded sample of item rows, and the trainer's sampled RMSE falling."""
    u = np.asarray(model.user_factors, np.float32)
    v = np.asarray(model.item_factors, np.float32)
    rank = algo["rank"]
    if u.shape != (sizes["users"], rank) or v.shape != (sizes["items"], rank):
        raise RunFailed(f"factors are {u.shape} x {v.shape}, wanted "
                        f"{sizes['users']} x {sizes['items']} at rank {rank}")
    aside = np.load(ctx.side.with_suffix(".check.npz"))
    resid = reference.als_item_residuals(
        u, v, aside["rows"], aside["counts"], aside["users"],
        aside["ratings"], algo["lambda_"])
    counts = aside["counts"]
    detail = sorted(zip(counts.tolist(), resid.tolist()))
    (ctx.work / "residuals.json").write_text(json.dumps(detail))
    check = cell["traffic"]["check"]
    heavy = resid[counts >= check["rehearsal_heavy_min_ratings" if ctx.rehearse
                                  else "heavy_min_ratings"]]
    light = counts < check["light_max_ratings"]
    numbers = [
        # the precision of the gramian and of the solver's products shows
        # on the most rated rows, where the inexact solve leaves least
        ("als_resid_heavy_median",
         float(np.median(heavy)) if len(heavy) else 0.0,
         check["rehearsal_resid_heavy_median_limit" if ctx.rehearse
               else "resid_heavy_median_limit"]),
        # on lightly rated rows the residual is the inexact solve's own,
        # about 0.5 / ratings: held against a step that solves nothing
        ("als_resid_light_scaled",
         float(np.median(resid[light] * counts[light])) if light.any()
         else 0.0, check["resid_light_scaled_limit"]),
        ("rmse_final_over_first",
         attempt["finalLoss"] / attempt["firstLoss"],
         check["rmse_ratio_limit"]),
        ("nonfinite_factors",
         int((~np.isfinite(u)).sum() + (~np.isfinite(v)).sum()), 0),
    ]
    for name, value, limit in numbers:
        ctx.say(f"compared: {name}={value!r} limit={limit!r} "
                f"({'ok' if value <= limit else 'NOT OK'}) over "
                f"{len(resid)} item rows ({len(heavy)} heavy)")
    return bool(len(heavy)) and all(val <= lim for _n, val, lim in numbers)
