"""A sequence-serving run: the looped decoder and every user's history
from the seed -> `pio deploy` with its defaults -> warm-up -> the
measured window -> /stop -> a sample of the window's own answers held to
the plain reference, on the freed chip.

The load generator, the window's bounds, the server's stop and the late
failures are lib/serve.py's and lib/loadgen.py's, as they stand: the
query body {"user", "num"} is this engine's too. What differs is the
model that is seeded, the reference it is held to, and the plan: a row
costs its history's length here, so the plan keeps the mix's order in
every seed (lib/seq_draw.py says why), and `drive` is lib/serve.py's
closed loop over that plan.
"""

from __future__ import annotations

import asyncio
import json
import shutil
import sys
import urllib.request

import numpy as np

from . import loadgen, seq_draw, serve
from .proc import (PROBE, RunFailed, free_port, host_memory_bytes,
                   http_get_json, http_post_json, parse_probe, pio_argv,
                   require_chips, wait_ready)
from .runctx import RunContext
from .spec import BENCH

#: A served score may differ from the reference's logit of the same item
#: by this share of the row's logit spread (max - min over the items), and
#: a served item's reference logit may lie this far under the reference's
#: num-th best unseen logit. PERF.md, section 2, has the readings on the
#: chip the limits stand between: the served path at bfloat16 weights and
#: activations against the float32 reference, and the control at float8.
SCORE_ERR_LIMIT = 0.2
RANK_SLACK_LIMIT = 0.2
#: the published keys of the model block that become the engine's params
MODEL_KEYS = ("hidden_size", "intermediate_size", "num_hidden_layers",
              "num_attention_heads", "head_dim", "total_ut_steps",
              "early_exit_threshold", "rms_norm_eps", "rope_theta")


def sizes_of(cell: dict, rehearse: bool) -> tuple[dict, dict]:
    """(model block, serve sizes) of this run."""
    cfg = cell["config"]
    if rehearse:
        return cfg["rehearsal"]["model"], cfg["rehearsal"]["serve"]
    # the published keys stand at the top of the configuration's file
    return {k: cfg[k] for k in MODEL_KEYS}, cfg["serve"]


def make_engine(ctx: RunContext, model: dict, sizes: dict, cfg: dict):
    engine = ctx.work / "engine"
    shutil.copytree(BENCH / "seq_engine", engine)
    variant = json.loads((engine / "engine.json").read_text())
    variant["algorithms"][0]["params"] = {
        **{k: model[k] for k in MODEL_KEYS}, "max_len": sizes["max_len"],
        "compute_dtype": cfg["precision"]["compute_dtype"]}
    (engine / "engine.json").write_text(json.dumps(variant, indent=2))
    return engine


def start_server(ctx: RunContext, cell: dict) -> dict:
    model, sizes = sizes_of(cell, ctx.rehearse)
    spans: dict[str, float] = {}
    engine = make_engine(ctx, model, sizes, cell["config"])
    cell_json = ctx.work / "cell.json"
    cell_json.write_text(json.dumps(
        {"model": model, "sizes": sizes, "traffic": cell["traffic"]}))
    histories = ctx.work / "home" / "histories.npy"
    histories.parent.mkdir(parents=True, exist_ok=True)
    probe, probe_log = ctx.children.start(
        "probe", [sys.executable, "-c", PROBE])
    t = ctx.clock()
    seeder, seed_log = ctx.children.start("seed_model", [
        sys.executable, str(BENCH / "lib" / "seq_seed_model.py"),
        "--engine-dir", str(engine), "--seed", str(ctx.seed),
        "--cell-json", str(cell_json), "--seconds", str(ctx.seconds),
        "--histories-out", str(histories)], host_only=True)
    device = parse_probe(ctx.children.wait("probe", probe, probe_log, 120))
    require_chips(device, cell["chips"], ctx.rehearse)
    out = ctx.children.wait("seed_model", seeder, seed_log, 900)
    spans["seed_model_s"] = ctx.clock() - t
    seeded = json.loads(next(ln for ln in out.splitlines()
                             if ln.startswith("SEEDED "))[7:])
    ctx.say(f"seeded in {spans['seed_model_s']:.1f} s; children's peak RSS "
            f"so far {ctx.children.peak_rss_bytes()} B")

    port = free_port()
    url = f"http://127.0.0.1:{port}"
    t = ctx.clock()
    server, log = ctx.children.start("deploy", pio_argv(
        "deploy", "--engine-dir", str(engine), "--ip", "127.0.0.1",
        "--port", str(port)))
    host_peak = wait_ready(url, server, log, timeout=1200)
    spans["deploy_ready_s"] = ctx.clock() - t
    ctx.say(f"deployed in {spans['deploy_ready_s']:.1f} s; host memory in "
            f"use peaked at {host_peak} B of {host_memory_bytes()[1]} B")
    t = ctx.clock()
    num = int(cell["traffic"]["num"])
    for j in range(int(cell["traffic"]["warm_queries"])):
        status, body = http_post_json(
            url + "/queries.json", {"user": f"u{j}", "num": num}, 900)
        if status != 200 or len(body["itemScores"]) != num:
            raise RunFailed(f"warm-up query {j} answered {status}: {body}")
        if j == 0:
            spans["first_query_s"] = ctx.clock() - t
    spans["warm_queries_s"] = ctx.clock() - t
    stats = http_get_json(url + "/stats.json")
    served_on = stats["device"]
    found = (served_on["platform"], served_on["device_kind"],
             served_on["device_count"])
    if found != (device["platform"], device["kind"], device["count"]):
        raise RunFailed(f"`pio deploy` serves on {found}, the probe found "
                        f"{device}")
    if stats["model"]["engineInstanceId"] != seeded["engine_instance"]:
        raise RunFailed("`pio deploy` loaded another model than the seeded")
    ctx.say(f"sizes: {json.dumps(sizes)} model: "
            f"{json.dumps({k: model[k] for k in MODEL_KEYS})} "
            f"blob_bytes={seeded['blob_bytes']} "
            f"history_mean={seeded['history_mean']:.2f}")
    ctx.say("seed_model: " + json.dumps(
        {k: round(seeded[k], 3) for k in ("draw_s", "serialize_s",
                                          "persist_s")}))
    seq = stats.get("sequence") or {}
    ctx.say(f"serving: kernel={stats['retrieval']['kernel']} "
            f"pipeline={stats['pipeline']['mode']} "
            f"tokenBudget={seq.get('tokenBudget')} "
            f"tokenLattice={seq.get('tokenLattice')} "
            f"maxInflight={stats['batching']['maxInflight']} "
            f"prewarm_compiles={stats['execCache']['misses']} "
            f"first_query={spans['first_query_s']:.2f} s")
    return {"url": url, "server": server, "log": log, "device": device,
            "spans": spans, "sizes": sizes, "model": model,
            "stats_ready": stats, "cell_json": cell_json,
            "histories": histories}


def drive(ctx: RunContext, cell: dict, live: dict, *, seconds: float,
          callers: int, trace_dir=None) -> dict:
    """Warm-up and window against a live server: `serve.drive`'s closed
    loop (the same hooks at the same times), over `seq_draw.closed_plan`."""
    traffic = cell["traffic"]
    plan = seq_draw.closed_plan(traffic, ctx.seed, live["sizes"]["users"],
                                seconds)
    url = live["url"]
    grabbed: dict = {}

    async def grab_before():
        grabbed["before"] = await asyncio.to_thread(
            http_get_json, url + "/stats.json")

    async def profile():
        span = max(1.0, min(float(traffic["trace_seconds"]), seconds * 0.4))
        req = urllib.request.Request(
            f"{url}/debug/profile?seconds={span}&dir={trace_dir}",
            method="POST")

        def post():
            with urllib.request.urlopen(req, timeout=300) as r:
                return json.loads(r.read().decode())
        grabbed["profile"] = await asyncio.to_thread(post)

    hooks = [(plan.warmup_s, grab_before)]
    if trace_dir is not None:
        hooks.append((plan.warmup_s + seconds * 0.3, profile))
    outcome = asyncio.run(loadgen.run_closed_loop(
        url + "/queries.json", plan, callers, hooks=hooks))
    grabbed["after"] = http_get_json(url + "/stats.json")
    return {"plan": plan, "outcome": outcome, **grabbed}


def check_answers(ctx: RunContext, live: dict, sample: list[dict]) -> dict:
    """The reference over the sampled answers, in a child that may hold
    the chip (the server has gone); the harness stays off jax."""
    answers = ctx.work / "answers.json"
    answers.write_text(json.dumps(sample))
    out = ctx.work / "check.json"
    argv = [sys.executable, str(BENCH / "lib" / "seq_check.py"),
            "--seed", str(ctx.seed), "--cell-json", str(live["cell_json"]),
            "--answers", str(answers), "--histories", str(live["histories"]),
            "--out", str(out)]
    if ctx.control:
        argv.append("--control")
    ctx.children.run("seq_check", argv, timeout=1800)
    return json.loads(out.read_text())


def verdict_of(checked: dict, passes: int, steps: int, loop_passes: int
               ) -> list[tuple[str, float, float]]:
    """(name, value, limit) of every number that decides `correct`."""
    wrong_exit = sum(1 for s in checked["exit_steps"] if s != passes)
    if steps <= 0 or loop_passes != passes * steps:
        wrong_exit += 1   # the served path ran another number of passes
    return [("score_err", checked["score_err"], SCORE_ERR_LIMIT),
            ("rank_slack", checked["rank_slack"], RANK_SLACK_LIMIT),
            ("short_answers", checked["short"], 0),
            ("exit_step_mismatch", wrong_exit, 0)]


def judge(ctx: RunContext, cell: dict, live: dict, phase: dict) -> dict:
    plan, outcome = phase["plan"], phase["outcome"]
    win = serve.window_of(plan, outcome, "closed")
    idx = win["idx"]
    ok = np.zeros(len(plan.users), bool)
    for i in idx.tolist():
        ok[i] = (outcome.status[i] == 200 and i in outcome.answers
                 and serve.well_formed(outcome.answers[i], plan.num,
                                       plan.rows[i] >= 0))
    t = ctx.clock()
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    known = [i for i in idx.tolist() if ok[i] and plan.rows[i] >= 0]
    _, first = np.unique(plan.rows[known], return_index=True)
    distinct = [known[j] for j in sorted(first.tolist())]
    take = min(int(cell["traffic"]["check_answers"]), len(distinct))
    picked = sorted(rng.choice(len(distinct), take, replace=False).tolist())
    sample = [{"row": int(plan.rows[distinct[j]]),
               "served": outcome.answers[distinct[j]]} for j in picked]
    numbers: list = []
    if sample:
        checked = check_answers(ctx, live, sample)
        seq0 = phase["before"].get("sequence") or {}
        seq1 = phase["after"].get("sequence") or {}
        numbers = verdict_of(
            checked, int(live["model"]["total_ut_steps"]),
            seq1.get("steps", 0) - seq0.get("steps", 0),
            seq1.get("loopPasses", 0) - seq0.get("loopPasses", 0))
        ctx.say(f"check: reference on {checked['device']}, weights "
                f"{checked['weights_s']:.1f} s, forwards "
                f"{checked['forward_s']:.1f} s")
        if ctx.control:
            ctl = checked["control"]
            ctx.say("control (reference with float8 matrices, in the "
                    f"program's place): score_err={ctl['score_err']!r} "
                    f"rank_slack={ctl['rank_slack']!r} -> correct="
                    f"{ctl['score_err'] <= SCORE_ERR_LIMIT and ctl['rank_slack'] <= RANK_SLACK_LIMIT and ctl['short'] == 0}")
    correct = bool(sample) and all(v <= lim for _n, v, lim in numbers)
    check_s = ctx.clock() - t
    for name, value, limit in numbers:
        ctx.say(f"compared: {name}={value!r} limit={limit!r} "
                f"({'ok' if value <= limit else 'NOT OK'}) over "
                f"{len(sample)} answers")
    wrong = 0 if correct or not sample else max(1, int(numbers[2][1]))
    late = serve.late_failures(plan, outcome, "closed", win)
    failed = int((~ok[idx]).sum()) + wrong + late
    return {"window": win, "attempted": int(len(idx)) + late,
            "failed": failed, "correct": correct, "check_s": check_s,
            "good": int(ok[idx].sum()) - wrong,
            "latency_ms": (outcome.done[idx] - win["clock0"][idx]) * 1e3}


def run(ctx: RunContext, cell: dict) -> dict:
    traffic = cell["traffic"]
    callers = int(traffic["callers"])
    live = start_server(ctx, cell)
    trace_dir = ctx.work / "trace" if ctx.trace else None
    phase = drive(ctx, cell, live, seconds=ctx.seconds, callers=callers,
                  trace_dir=trace_dir)
    t = ctx.clock()
    serve.stop_server(ctx, live)
    live["spans"]["stop_s"] = ctx.clock() - t
    side = ctx.read_side()
    verdict = judge(ctx, cell, live, phase)
    win = verdict["window"]
    before, after = phase["before"], phase["after"]
    compiles = after["execCache"]["misses"] - before["execCache"]["misses"]
    seq0, seq1 = before.get("sequence") or {}, after.get("sequence") or {}
    gained = {k: seq1.get(k, 0) - seq0.get(k, 0)
              for k in ("steps", "rows", "tokensReal", "tokensComputed")}
    ctx.say(f"window: {win['window_s']:.3f} s; attempted="
            f"{verdict['attempted']} failed={verdict['failed']} offered="
            f"{callers} callers compiles_in_window={compiles} "
            f"sequence={json.dumps(gained)} "
            f"exitStepHistogram={seq1.get('exitStepHistogram')}")
    pipe0, pipe1 = before.get("pipeline") or {}, after.get("pipeline") or {}
    clock = pipe1.get("clockSeconds", 0) - pipe0.get("clockSeconds", 0)
    if clock > 0 and gained["rows"] > 0:
        # an answer is not a unit of work here: what a seed's window
        # asked for, beside what the server got through
        ctx.say(f"work: {gained['tokensReal'] / clock:.1f} tokens/s, "
                f"{gained['steps'] / clock:.3f} steps/s, "
                f"{gained['tokensReal'] / gained['rows']:.2f} tokens an "
                f"answered history, {gained['rows'] / gained['steps']:.2f} "
                "rows a step (the server's counters over its own clock)")
    if clock > 0:
        ctx.say("device with no step dispatched and unfinished: "
                f"{100 * (pipe1['deviceIdleSeconds'] - pipe0['deviceIdleSeconds']) / clock:.2f}% "
                "of the window (the program's own count)")
    ctx.say("completed per second in each 10 s of sending: "
            f"{serve.completed_by_slices(verdict, phase['outcome'])}")
    lat = verdict["latency_ms"]
    if len(lat):
        ctx.say(f"latency of the window's answers: p50="
                f"{float(np.percentile(lat, 50)):.1f} ms p99="
                f"{float(np.percentile(lat, 99)):.1f} ms")
    metrics = {"served_qps": verdict["good"] / win["window_s"]}
    spans = dict(live["spans"])
    ctx.say("phases: " + json.dumps({k: round(v, 3)
                                     for k, v in spans.items()}))
    trace = ctx.reduce_trace(trace_dir) if ctx.trace else None
    device = dict(live["device"])
    device["memory_peak_bytes"] = side.get("exit_memory_peak_bytes")
    if device["memory_peak_bytes"] is None:
        raise RunFailed(f"`pio deploy` left no peak memory: {side}")
    return {
        "device": device, "attempted": verdict["attempted"],
        "failed": verdict["failed"], "correct": verdict["correct"],
        "window_s": win["window_s"], "check_s": verdict["check_s"],
        "metrics": metrics,
        "evidence": {
            "harness": spans, "stats_before": before, "stats_after": after,
            "trace": trace, "device_kind": device["kind"],
            "shapes": {"n_items": live["sizes"]["items"],
                       "dim": live["model"]["hidden_size"],
                       "k": int(traffic["num"]), "model": live["model"]},
        },
    }
