"""A `granite-4.0-h-micro-seqrec` serving run: the hybrid state-space
decoder (whole) and every user's history from the seed -> `pio deploy`
with its defaults -> warm-up -> the measured window -> /stop -> a sample
of the window's own answers held to the plain reference, on the freed
chip.

The load generator, the window's bounds, the server's stop and the late
failures are lib/serve.py's and lib/loadgen.py's; the plan and the drive
of the closed loop are lib/seq_serve.py's and lib/seq_draw.py's, as they
stand (imported). What differs from lib/latent_moe_serve.py is the model
that is seeded, the reference it is held to, what the sample must hold
(long histories AND short ones), and the counts the device program hands
out; as there, every child has a time limit from the run's budget (the
driver stops a run at 360 s, mute).
"""

from __future__ import annotations

import json
import shutil
import sys

import numpy as np

from . import hybrid_ssm_counts as counts
from . import hybrid_ssm_reference as ref, scope_time, seq_serve, serve
from .latent_moe_serve import traffic_of
from .proc import (PROBE, RunFailed, free_port, host_memory_bytes,
                   http_get_json, http_post_json, parse_probe, pio_argv,
                   require_chips, wait_ready)
from .runctx import RunContext
from .spec import BENCH

#: What decides `correct`, each with its reason: every limit between
#: its two readings ON THE CHIP (PERF.md, sections 2 and 6; all PR 44's
#: chip runs, `TPU v5 lite`): sound, the program's bfloat16 matmul
#: inputs against this file's float32 reference through 40 layers,
#: twelve runs on twelve seeds; the control, the reference with its
#: bfloat16-stated matrices rounded to float8 (e5m2) in the program's
#: place, one run. The scores are gaps over the row's logit spread (max
#: - min over the items). (The values were first set from host readings
#: of the rounding at the published widths and 10 and 20 of the 40
#: layers, carried to 40: sound 0.005-0.009, control 0.1-0.25; the
#: chip's readings left them where they were.)
#: - the MEDIAN over the sampled answers of the worst gap of a served
#:   score to the reference's logit of the same item: sound
#:   0.0025-0.0035, the control 0.112: a lower precision moves every
#:   answer; 8.7 times the largest sound reading, 3.7 times under the
#:   control's;
SCORE_ERR_MEDIAN_LIMIT = 0.03
#: - the largest such gap over the sample: sound 0.0039-0.0060 (no
#:   router here, so no flipped near-tie: the largest stands near the
#:   median), the control 0.173;
SCORE_ERR_LIMIT = 0.06
#: - how far a served item's reference logit may lie under the
#:   reference's num-th best: sound 0.0021-0.0063, the control 0.188.
RANK_SLACK_LIMIT = 0.06
COUNTERS = ("ssmChunks", "ssmResetsInChunk", "pairsCausal")


def sizes_of(cell: dict, rehearse: bool) -> tuple[dict, dict]:
    """(model block as the reference and the program read it, serve
    sizes) of this run: the published keys as they are."""
    cfg = cell["config"]
    if rehearse:
        return cfg["rehearsal"]["model"], cfg["rehearsal"]["serve"]
    return {k: cfg[k] for k in ref.CONFIG_KEYS}, cfg["serve"]


def make_engine(ctx: RunContext, model: dict, sizes: dict, cfg: dict):
    engine = ctx.work / "engine"
    shutil.copytree(BENCH / "hybrid_ssm_engine", engine)
    variant = json.loads((engine / "engine.json").read_text())
    variant["algorithms"][0]["params"] = {
        **model, "max_len": sizes["max_len"], "exclude_seen": False,
        "compute_dtype": cfg["precision"]["compute_dtype"]}
    (engine / "engine.json").write_text(json.dumps(variant, indent=2))
    return engine


def start_server(ctx: RunContext, cell: dict) -> dict:
    model, sizes = sizes_of(cell, ctx.rehearse)
    traffic = traffic_of(cell, ctx.rehearse)
    if ctx.rehearse:
        traffic["check_short_upto"] = traffic["history"]["median"] // 2
    limits = traffic["limits_s"]
    spans: dict[str, float] = {}
    engine = make_engine(ctx, model, sizes, cell["config"])
    cell_json = ctx.work / "cell.json"
    cell_json.write_text(json.dumps(
        {"model": model, "sizes": sizes, "traffic": traffic}))
    home = ctx.work / "home"
    home.mkdir(parents=True, exist_ok=True)
    histories = home / "histories.npy"
    if ctx.rehearse:  # a step of 512 tokens: the host compiles small ones
        for env in (ctx.children.env, ctx.children.host_env):
            env["PIO_BENCH_STEP_TOKENS"] = "512"
    probe, probe_log = ctx.children.start(
        "probe", [sys.executable, "-c", PROBE])
    t = ctx.clock()
    seeder, seed_log = ctx.children.start("seed_model", [
        sys.executable, str(BENCH / "lib" / "hybrid_ssm_seed_model.py"),
        "--engine-dir", str(engine), "--seed", str(ctx.seed),
        "--cell-json", str(cell_json), "--seconds", str(ctx.seconds),
        "--histories-out", str(histories)], host_only=True)
    device = parse_probe(ctx.children.wait("probe", probe, probe_log, 120))
    require_chips(device, cell["chips"], ctx.rehearse)
    out = ctx.children.wait("seed_model", seeder, seed_log,
                            limits["seed_model"])
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
    host_peak = wait_ready(url, server, log, timeout=limits["deploy_ready"])
    spans["deploy_ready_s"] = ctx.clock() - t
    ctx.say(f"deployed in {spans['deploy_ready_s']:.1f} s; host memory in "
            f"use peaked at {host_peak} B of {host_memory_bytes()[1]} B")
    t = ctx.clock()
    num = int(traffic["num"])
    for j in range(int(traffic["warm_queries"])):
        status, body = http_post_json(
            url + "/queries.json", {"user": f"u{j}", "num": num}, 300)
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
    n_mamba, n_attention = counts.kinds(model)
    ctx.say(f"sizes: {json.dumps(sizes)} layers: {n_mamba} mamba + "
            f"{n_attention} attention, matrices "
            f"{counts.matrix_params(model) / 1e6:.1f} M parameters "
            f"blob_bytes={seeded['blob_bytes']} "
            f"history_mean={seeded['history_mean']:.2f}")
    ctx.say("seed_model: " + json.dumps(
        {k: round(seeded[k], 3) for k in (
            "draw_s", "histories_wait_s", "serialize_s", "persist_s")}))
    ctx.say("startup: " + json.dumps(
        [[name, round(seconds, 2)] for name, seconds, _b in
         (stats.get("startup") or {}).get("phases", [])
         if seconds >= 0.5]))
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
            "traffic": traffic, "stats_ready": stats, "cell_json": cell_json,
            "histories": histories}


def check_answers(ctx: RunContext, live: dict, sample: list[dict]) -> dict:
    """The reference over the sampled answers, in a child that may hold
    the chip (the server has gone); the harness stays off jax."""
    answers = ctx.work / "answers.json"
    answers.write_text(json.dumps(sample))
    out = ctx.work / "check.json"
    argv = [sys.executable, str(BENCH / "lib" / "hybrid_ssm_check.py"),
            "--seed", str(ctx.seed), "--cell-json", str(live["cell_json"]),
            "--answers", str(answers), "--histories", str(live["histories"]),
            "--out", str(out)]
    if ctx.control:
        argv.append("--control")
    limit = live["traffic"]["limits_s"]["check"] * (2 if ctx.control else 1)
    ctx.children.run("hybrid_ssm_check", argv, timeout=limit)
    return json.loads(out.read_text())


def pick_sample(seed: int, traffic: dict, candidates: list[int],
                lengths: list[int]) -> list[int]:
    """`check_answers` of ``candidates`` (the window's known-user
    answers, distinct users; ``lengths[j]`` the history of
    ``candidates[j]``), drawn from the seed: `check_long_answers` from
    histories over `check_long_over` events, `check_short_answers` from
    histories of at most `check_short_upto`, the rest from the ones
    between (a long one's reference costs tens of a short one's, and the
    run has a clock); where a class runs out, from whatever is left."""
    rng = np.random.default_rng([seed, 0xC4EC])
    take = min(int(traffic["check_answers"]), len(candidates))
    klass = [("long" if n > traffic["check_long_over"] else
              "short" if n <= traffic["check_short_upto"] else "between")
             for n in lengths]
    pools = {k: [j for j, c in enumerate(klass) if c == k]
             for k in ("long", "short", "between")}
    for pool in pools.values():
        rng.shuffle(pool)
    quota = {"long": int(traffic["check_long_answers"]),
             "short": int(traffic["check_short_answers"])}
    quota["between"] = max(0, take - sum(quota.values()))
    chosen: list[int] = []
    for k in ("long", "short", "between"):
        n = min(quota[k], len(pools[k]), take - len(chosen))
        chosen += pools[k][:n]
        pools[k] = pools[k][n:]
    left = pools["between"] + pools["short"] + pools["long"]
    chosen += left[:take - len(chosen)]
    return sorted(candidates[j] for j in chosen)


def count_mismatches(model: dict, gained: dict) -> list[tuple[str, int]]:
    """The device program's counts against what the window's answered
    histories must give: the attention layers' causal pairs exactly (the
    pipeline's host-side count of n (n + 1) / 2 a history); the scan's
    live chunks between what the real tokens need and one part-filled
    chunk a step more."""
    n_mamba, n_attention = counts.kinds(model)
    Q = model["mamba_chunk_size"]
    low = -(-gained["tokensReal"] // Q) * n_mamba
    high = low + gained["steps"] * n_mamba
    chunks = gained["ssmChunks"]
    return [("pairsCausal_mismatch",
             abs(gained["pairsCausal"]
                 - gained["attentionPairs"] * n_attention)),
            ("ssmChunks_outside", max(0, low - chunks, chunks - high)),
            ("ssmResets_outside", max(
                0, gained["ssmResetsInChunk"] - gained["rows"] * n_mamba))]


def judge(ctx: RunContext, live: dict, phase: dict, gained: dict) -> dict:
    plan, outcome = phase["plan"], phase["outcome"]
    traffic = live["traffic"]
    win = serve.window_of(plan, outcome, "closed")
    idx = win["idx"]
    ok = np.zeros(len(plan.users), bool)
    for i in idx.tolist():
        ok[i] = (outcome.status[i] == 200 and i in outcome.answers
                 and serve.well_formed(outcome.answers[i], plan.num,
                                       plan.rows[i] >= 0))
    t = ctx.clock()
    known = [i for i in idx.tolist() if ok[i] and plan.rows[i] >= 0]
    sample: list[dict] = []
    if known:
        hist = np.load(live["histories"], mmap_mode="r")
        _, first = np.unique(plan.rows[known], return_index=True)
        distinct = [known[j] for j in sorted(first.tolist())]
        lengths = [int((np.asarray(hist[plan.rows[i]]) > 0).sum())
                   for i in distinct]
        sample = [{"row": int(plan.rows[i]), "served": outcome.answers[i]}
                  for i in pick_sample(ctx.seed, traffic, distinct, lengths)]
    numbers: list = []
    if sample:
        checked = check_answers(ctx, live, sample)
        numbers = [
            ("score_err_median", checked["score_err_median"],
             SCORE_ERR_MEDIAN_LIMIT),
            ("score_err", checked["score_err"], SCORE_ERR_LIMIT),
            ("rank_slack", checked["rank_slack"], RANK_SLACK_LIMIT),
            ("short_answers", checked["short"], 0),
            *[(name, value, 0)
              for name, value in count_mismatches(live["model"], gained)]]
        got = [r["length"] for r in checked["per_answer"]]
        ctx.say(f"check: reference on {checked['device']}, weights "
                f"{checked['weights_s']:.1f} s, waited "
                f"{checked['compile_wait_s']:.1f} s more for its programs, "
                f"forwards {checked['forward_s']:.1f} s; of {len(sample)} "
                f"answers {sum(n > traffic['check_long_over'] for n in got)} "
                f"from histories over {traffic['check_long_over']} events, "
                f"{sum(n <= traffic['check_short_upto'] for n in got)} from "
                f"histories of at most {traffic['check_short_upto']}; "
                "score_err by answer "
                + json.dumps(sorted(round(r['score_err'], 5)
                                    for r in checked['per_answer']))
                + "; (events, seconds) by answer "
                + json.dumps([[r['length'], round(r['seconds'], 2)]
                              for r in checked['per_answer']]))
        if ctx.control:
            ctl = checked["control"]
            passes = (ctl["score_err_median"] <= SCORE_ERR_MEDIAN_LIMIT
                      and ctl["score_err"] <= SCORE_ERR_LIMIT
                      and ctl["rank_slack"] <= RANK_SLACK_LIMIT
                      and ctl["short"] == 0)
            ctx.say("control (reference with float8 matrices, in the "
                    f"program's place): {json.dumps(ctl)} -> correct="
                    f"{passes}")
    correct = bool(sample) and all(v <= lim for _n, v, lim in numbers)
    check_s = ctx.clock() - t
    for name, value, limit in numbers:
        ctx.say(f"compared: {name}={value!r} limit={limit!r} "
                f"({'ok' if value <= limit else 'NOT OK'}) over "
                f"{len(sample)} answers")
    wrong = 0 if correct or not sample else max(1, int(numbers[3][1]))
    late = serve.late_failures(plan, outcome, "closed", win)
    failed = int((~ok[idx]).sum()) + wrong + late
    return {"window": win, "attempted": int(len(idx)) + late,
            "failed": failed, "correct": correct, "check_s": check_s,
            "good": int(ok[idx].sum()) - wrong,
            "latency_ms": (outcome.done[idx] - win["clock0"][idx]) * 1e3}


def run(ctx: RunContext, cell: dict) -> dict:
    live = start_server(ctx, cell)
    traffic = live["traffic"]
    callers = int(traffic["callers"])
    trace_dir = ctx.work / "trace" if ctx.trace else None
    phase = seq_serve.drive(ctx, {**cell, "traffic": traffic}, live,
                            seconds=ctx.seconds, callers=callers,
                            trace_dir=trace_dir)
    t = ctx.clock()
    serve.stop_server(ctx, live)
    live["spans"]["stop_s"] = ctx.clock() - t
    side = ctx.read_side()
    before, after = phase["before"], phase["after"]
    seq0, seq1 = before.get("sequence") or {}, after.get("sequence") or {}
    gained = {k: seq1.get(k, 0) - seq0.get(k, 0)
              for k in ("steps", "rows", "tokensReal", "tokensComputed",
                        "attentionPairs", *COUNTERS)}
    verdict = judge(ctx, live, phase, gained)
    win = verdict["window"]
    compiles = after["execCache"]["misses"] - before["execCache"]["misses"]
    ctx.say(f"window: {win['window_s']:.3f} s; attempted="
            f"{verdict['attempted']} failed={verdict['failed']} offered="
            f"{callers} callers compiles_in_window={compiles} "
            f"sequence={json.dumps(gained)}")
    pipe0, pipe1 = before.get("pipeline") or {}, after.get("pipeline") or {}
    clock = pipe1.get("clockSeconds", 0) - pipe0.get("clockSeconds", 0)
    if clock > 0 and gained["rows"] > 0:
        ctx.say(f"work: {gained['tokensReal'] / clock:.1f} tokens/s, "
                f"{gained['steps'] / clock:.3f} steps/s, "
                f"{gained['tokensReal'] / gained['rows']:.2f} tokens an "
                f"answered history, {gained['rows'] / gained['steps']:.2f} "
                "rows a step (the server's counters over its own clock)")
        ctx.say("device with no step dispatched and unfinished: "
                f"{100 * (pipe1['deviceIdleSeconds'] - pipe0['deviceIdleSeconds']) / clock:.2f}% "
                "of the window (the program's own count)")
    if gained["ssmChunks"] > 0:
        ctx.say(f"scan: {gained['ssmChunks']} live chunks, "
                f"{gained['ssmResetsInChunk'] / gained['ssmChunks']:.3f} "
                "history starts inside a chunk a chunk")
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
    trace = scoped = None
    if ctx.trace:
        scopes = scope_time.load_scopes(trace_dir)
        trace = ctx.reduce_trace(trace_dir)
        scoped = scope_time.by_scope(trace["ops"], scopes)
        named = sum(c for s, (c, _t) in scoped.items() if s)
        ctx.say("device seconds by scope: " + json.dumps(
            {s: round(t, 4) for s, (_c, t) in sorted(scoped.items())})
            + f" ({named} of {sum(c for c, _t in scoped.values())} "
            "operations named)")
    device = dict(live["device"])
    device["memory_peak_bytes"] = side.get("exit_memory_peak_bytes")
    if device["memory_peak_bytes"] is None:
        raise RunFailed(f"`pio deploy` left no peak memory: {side}")
    model = live["model"]
    return {
        "device": device, "attempted": verdict["attempted"],
        "failed": verdict["failed"], "correct": verdict["correct"],
        "window_s": win["window_s"], "check_s": verdict["check_s"],
        "metrics": metrics,
        "evidence": {
            "harness": spans, "stats_before": before, "stats_after": after,
            "trace": trace, "scopes": scoped, "device_kind": device["kind"],
            "shapes": {"n_items": live["sizes"]["items"],
                       "dim": model["hidden_size"],
                       "k": int(traffic["num"]), "model": model},
        },
    }
