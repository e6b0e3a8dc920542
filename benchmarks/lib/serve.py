"""A serving run: model from the seed -> `pio deploy` with its defaults ->
warm-up -> the measured window -> /stop -> the answers checked.

Shared by the `open-loop` and `closed-loop` traffic kinds."""

from __future__ import annotations

import asyncio
import json
import subprocess
import sys
import urllib.request

import numpy as np

from . import loadgen, reference
from .proc import (PROBE, RunFailed, STOP_TIMEOUT_S, free_port, host_memory_bytes,
                   host_memory_used_bytes, http_get,
                   http_get_json, http_post_json, parse_probe, pio_argv, require_chips, tail,
                   wait_ready)
from .runctx import RunContext
from .spec import BENCH
from .stats import percentile


def sizes_of(cell: dict, rehearse: bool) -> dict:
    cfg = cell["config"]
    return cfg["rehearsal"]["serve"] if rehearse else cfg["serve"]


def start_server(ctx: RunContext, cell: dict) -> dict:
    """Seed the model, deploy it with the program's defaults, wait until
    it is ready. Returns what the window needs."""
    sizes = sizes_of(cell, ctx.rehearse)
    spans: dict[str, float] = {}
    algorithm = cell["config"]["algorithm"]
    engine = ctx.make_engine({"rehearse": int(ctx.rehearse)}, algorithm,
                             algorithm["num_iterations"])
    # the probe touches the chip and lets go of it while the model is
    # being made on the host: no run starts serving on a device it has
    # not named, and a machine without a chip fails in seconds
    probe, probe_log = ctx.children.start(
        "probe", [sys.executable, "-c", PROBE])
    t = ctx.clock()
    seeder, seed_log = ctx.children.start("seed_model", [
        sys.executable, str(BENCH / "lib" / "seed_model.py"),
        "--engine-dir", str(engine), "--seed", str(ctx.seed),
        "--users", str(sizes["users"]), "--items", str(sizes["items"]),
        "--rank", str(sizes["rank"])], host_only=True)
    device = parse_probe(ctx.children.wait("probe", probe, probe_log, 120))
    require_chips(device, cell["chips"], ctx.rehearse)
    out = ctx.children.wait("seed_model", seeder, seed_log, 600)
    spans["seed_model_s"] = ctx.clock() - t
    ctx.say(f"seeded in {spans['seed_model_s']:.1f} s; children's peak RSS "
            f"so far {ctx.children.peak_rss_bytes()} B")
    seeded = json.loads(next(ln for ln in out.splitlines()
                             if ln.startswith("SEEDED "))[7:])

    port = free_port()
    url = f"http://127.0.0.1:{port}"
    t = ctx.clock()
    server, log = ctx.children.start("deploy", pio_argv(
        "deploy", "--engine-dir", str(engine), "--ip", "127.0.0.1",
        "--port", str(port)))
    host_peak = wait_ready(url, server, log, timeout=900)
    spans["deploy_ready_s"] = ctx.clock() - t
    ctx.say(f"deployed in {spans['deploy_ready_s']:.1f} s; host memory in "
            f"use peaked at {host_peak} B of {host_memory_bytes()[1]} B")
    # a few single queries, one after the other: whatever the server
    # builds at its first answer (the id maps' inverses) is set-up, not
    # the window's first requests' latency
    t = ctx.clock()
    for j in range(int(cell["traffic"]["warm_queries"])):
        status, body = http_post_json(
            url + "/queries.json",
            {"user": f"u{j}", "num": int(cell["traffic"]["num"])}, 900)
        if status != 200 or len(body["itemScores"]) != cell["traffic"]["num"]:
            raise RunFailed(f"warm-up query {j} answered {status}: {body}")
        if j == 0:
            spans["first_query_s"] = ctx.clock() - t
    spans["warm_queries_s"] = ctx.clock() - t
    ctx.say(f"first query {spans['first_query_s']:.1f} s, "
            f"{cell['traffic']['warm_queries']} warm-up queries "
            f"{spans['warm_queries_s']:.1f} s")
    stats = http_get_json(url + "/stats.json")
    served_on = stats["device"]
    found = (served_on["platform"], served_on["device_kind"],
             served_on["device_count"])
    if found != (device["platform"], device["kind"], device["count"]):
        raise RunFailed(f"`pio deploy` serves on {found}, the probe found "
                        f"{device}")
    if stats["model"]["engineInstanceId"] != seeded["engine_instance"]:
        raise RunFailed("`pio deploy` loaded another model than the seeded")
    ctx.say(f"sizes: {json.dumps(sizes)} blob_bytes={seeded['blob_bytes']}")
    ctx.say("seed_model: " + json.dumps(
        {k: round(seeded[k], 3) for k in ("draw_s", "serialize_s",
                                          "persist_s")}))
    ctx.say(f"serving: kernel={stats['retrieval']['kernel']} "
            f"pipeline={stats['pipeline']['mode']} "
            f"batchMax={stats['batching']['maxBatchSize']} "
            f"maxInflight={stats['batching']['maxInflight']} "
            f"prewarm_compiles={stats['execCache']['misses']}")
    return {"url": url, "server": server, "log": log, "device": device,
            "spans": spans, "sizes": sizes, "stats_ready": stats}


def stop_server(ctx: RunContext, live: dict) -> None:
    http_get(live["url"] + "/stop")
    try:
        rc = live["server"].wait(timeout=STOP_TIMEOUT_S)
    except subprocess.TimeoutExpired:
        raise RunFailed(f"`pio deploy` still running {STOP_TIMEOUT_S:.0f} s "
                        f"after /stop\n{tail(live['log'])}") from None
    ctx.children.live.remove(live["server"])
    if rc != 0:
        raise RunFailed(f"`pio deploy` exited {rc} after /stop\n"
                        f"{tail(live['log'])}")


def drive(ctx: RunContext, cell: dict, live: dict, mode: str, *,
          seconds: float, rate_qps: float | None, callers: int | None,
          trace_dir=None) -> dict:
    """Warm-up and window of one phase against a live server."""
    traffic = cell["traffic"]
    sizes = live["sizes"]
    plan = loadgen.make_plan(traffic, ctx.seed, sizes["users"], seconds,
                             rate_qps=rate_qps)
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
    query_url = url + "/queries.json"
    if mode == "open":
        outcome = asyncio.run(loadgen.run_open_loop(query_url, plan,
                                                    hooks=hooks))
    else:
        outcome = asyncio.run(loadgen.run_closed_loop(query_url, plan,
                                                      callers, hooks=hooks))
    grabbed["after"] = http_get_json(url + "/stats.json")
    ctx.say(f"host memory in use after the window: "
            f"{host_memory_used_bytes()} B")
    return {"plan": plan, "outcome": outcome, **grabbed}


def window_of(plan, outcome, mode: str) -> dict:
    """Which requests are the window's, and how long it was.

    Open loop: the requests due from the end of the warm-up on, until the
    last of them is answered; each is timed from when it was due.
    Closed loop: the requests that came to their end (answer or failure)
    between the end of the warm-up and the moment the callers stop
    sending, whenever they were sent, over exactly that time. The pool
    is saturated throughout, so this is the rate of answers; counting
    requests by when they were sent, over the time to the last answer,
    would take in the drain and leave out the answers that arrive at the
    window's start, and so read a shorter latency as more throughput."""
    sent, done = outcome.sent, outcome.done
    if mode == "open":
        inside = (plan.due >= plan.warmup_s) & ~np.isnan(sent)
        idx = np.flatnonzero(inside)
        t_first = plan.warmup_s
        t_last = float(np.nanmax(done[idx])) if len(idx) else t_first
        clock0 = plan.due          # latency from when a request was due
    else:
        t_first, t_last = plan.warmup_s, plan.warmup_s + plan.seconds
        idx = np.flatnonzero((done >= t_first) & (done <= t_last))
        clock0 = sent
    return {"idx": idx, "t_first": t_first, "t_last": t_last,
            "window_s": t_last - t_first, "clock0": clock0}


SLICE_S = 10.0


def completed_by_slices(verdict: dict, outcome) -> list[float]:
    """Answers (status 200, the warm-up's too) completed per second in
    each whole 10 s between the window's start and the last send: how
    steady a run is inside itself."""
    win = verdict["window"]
    end_of_sending = float(np.nanmax(outcome.sent))
    done = outcome.done[outcome.status == 200]
    edges = np.arange(win["t_first"], end_of_sending - SLICE_S + 1e-9,
                      SLICE_S)
    return [float(((done >= a) & (done < a + SLICE_S)).sum() / SLICE_S)
            for a in edges]


def late_failures(plan, outcome, mode: str, win: dict) -> int:
    """A closed loop's requests that were sent inside the window and came
    to no answer after it: outside the rate, but failures all the same."""
    if mode == "open":
        return 0
    sent, done = outcome.sent, outcome.done
    late = (sent < win["t_last"]) & ~(done <= win["t_last"])
    return int((late & (outcome.status != 200)).sum())


def well_formed(answer: list, num: int, known: bool) -> bool:
    if not known:
        return answer == []
    if len(answer) != num:
        return False
    scores = [it.get("score") for it in answer]
    return (all(isinstance(s, float) and np.isfinite(s) for s in scores)
            and all(a >= b for a, b in zip(scores, scores[1:])))


def judge(ctx: RunContext, cell: dict, live: dict, phase: dict, mode: str,
          check: bool = True) -> dict:
    """Failures, latencies and the comparison with the reference, once the
    server has gone. The sweep, between its phases, passes `check` False:
    it reads rates and latencies and claims no `correct`."""
    plan, outcome = phase["plan"], phase["outcome"]
    win = window_of(plan, outcome, mode)
    idx = win["idx"]
    ok = np.zeros(len(plan.users), bool)
    for i in idx.tolist():
        ok[i] = (outcome.status[i] == 200 and i in outcome.answers
                 and well_formed(outcome.answers[i], plan.num,
                                 plan.rows[i] >= 0))
    # -- the reference, on a seeded sample of the window's answers ----------
    t = ctx.clock()
    sizes = live["sizes"]
    rng = np.random.default_rng([ctx.seed, 0xC4EC])
    known = [i for i in idx.tolist() if ok[i] and plan.rows[i] >= 0]
    _, first = np.unique(plan.rows[known], return_index=True)
    distinct = [known[j] for j in sorted(first.tolist())]
    take = min(int(cell["traffic"]["check_answers"]) if check else 0,
               len(distinct))
    sample = sorted(rng.choice(len(distinct), take, replace=False).tolist())
    sample = [distinct[j] for j in sample]
    rows = plan.rows[sample]
    served = [outcome.answers[i] for i in sample]
    args = (sizes["users"], sizes["items"], sizes["rank"], plan.num)
    checked = (reference.check_served(ctx.seed, rows, served, *args)
               if sample else None)
    numbers = []
    if checked:
        numbers = [("score_rel_err", checked["score_rel_err"],
                    reference.SCORE_RTOL),
                   ("wrong_ids", checked["wrong_ids"], 0),
                   ("short_answers", checked["short"], 0)]
    correct = bool(sample) and all(v <= lim for _n, v, lim in numbers)
    if ctx.control and sample:
        ctl = reference.check_served(
            ctx.seed, rows, reference.control_served(ctx.seed, rows, *args),
            *args)
        ctx.say("control (reference at one bf16 pass, in the program's "
                f"place): score_rel_err={ctl['score_rel_err']!r} "
                f"wrong_ids={ctl['wrong_ids']} -> correct="
                f"{ctl['score_rel_err'] <= reference.SCORE_RTOL and ctl['wrong_ids'] == 0}")
    check_s = ctx.clock() - t
    wrong = 0
    if checked and not correct:
        wrong = max(1, checked["wrong_ids"] + checked["short"])
    late = late_failures(plan, outcome, mode, win)
    failed = int((~ok[idx]).sum()) + wrong + late
    for name, value, limit in numbers:
        ctx.say(f"compared: {name}={value!r} limit={limit!r} "
                f"({'ok' if value <= limit else 'NOT OK'}) over "
                f"{len(sample)} answers")
    lat = np.where(ok[idx], (outcome.done[idx] - win["clock0"][idx]) * 1e3,
                   loadgen.REQUEST_TIMEOUT_S * 1e3)
    lag = (outcome.sent[idx] - win["clock0"][idx]) * 1e3
    return {"window": win, "ok": ok,
            "attempted": int(len(idx)) + late, "failed": failed,
            "correct": correct, "check_s": check_s,
            "latency_ms": lat, "lag_ms": lag,
            "good": int(ok[idx].sum()) - wrong}


def run(ctx: RunContext, cell: dict, mode: str) -> dict:
    traffic = cell["traffic"]
    if mode == "open":
        rate = float(traffic["rate_qps"])
        callers = None
    else:
        rate, callers = None, int(traffic["callers"])
    live = start_server(ctx, cell)
    trace_dir = ctx.work / "trace" if ctx.trace else None
    phase = drive(ctx, cell, live, mode, seconds=ctx.seconds, rate_qps=rate,
                  callers=callers, trace_dir=trace_dir)
    t = ctx.clock()
    stop_server(ctx, live)
    live["spans"]["stop_s"] = ctx.clock() - t
    ctx.say(f"children's peak RSS {ctx.children.peak_rss_bytes()} B")
    side = ctx.read_side()
    verdict = judge(ctx, cell, live, phase, mode)
    win = verdict["window"]
    lat = verdict["latency_ms"]
    before, after = phase["before"], phase["after"]
    compiles = after["execCache"]["misses"] - before["execCache"]["misses"]
    ctx.say(f"window: {win['window_s']:.3f} s from {win['t_first']:.3f} s "
            f"after the warm-up began; attempted={verdict['attempted']} "
            f"failed={verdict['failed']} offered="
            f"{rate if rate else str(callers) + ' callers'} "
            f"compiles_in_window={compiles}")
    ctx.say("completed per second in each 10 s of sending: "
            f"{completed_by_slices(verdict, phase['outcome'])}")
    metrics = {}
    if mode == "open":
        metrics["query_p50_ms"] = percentile(lat.tolist(), 50)
        metrics["query_p99_ms"] = percentile(lat.tolist(), 99)
    else:
        metrics["served_qps"] = verdict["good"] / win["window_s"]
    spans = dict(live["spans"])
    spans["generator_lag_ms"] = percentile(verdict["lag_ms"].tolist(), 99)
    spans["client_mean_ms"] = float(np.mean(lat))
    ctx.say("phases: " + json.dumps({k: round(v, 3)
                                     for k, v in spans.items()}))
    trace = None
    if ctx.trace:
        trace = ctx.reduce_trace(trace_dir)
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
                       "dim": live["sizes"]["rank"], "k": int(traffic["num"])},
        },
    }
