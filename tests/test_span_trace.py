"""One span primitive on the profiler's clock (ISSUE 24).

``obs/trace.py::span`` is the program's one way to time a block: both
clock reads go to the caller's sink, and the block is a named event of a
profiler capture. Pinned here, all on the CPU: what a capture taken
through ``maybe_profile`` holds (the serving stages with their facts,
the ALS phases, one ``pio.profile.window``, no Python frame), that a
process without jax stays without it, the pipeline's feed counters on an
injected clock, the start-up record in ``/stats.json``, and the ALS
phases as one chain with nothing between its links.
"""

from __future__ import annotations

import json
import re
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from predictionio_tpu.obs.startup import (STARTUP, StartupRecord,
                                          host_bytes_in_use,
                                          process_age_seconds)
from predictionio_tpu.obs.trace import span
from predictionio_tpu.obs.waterfall import (BatchClock, reset_stage_sink,
                                            set_stage_sink, stage_span)
from predictionio_tpu.ops.pipeline import ServingPipeline, _SharedState
from predictionio_tpu.ops.retrieval import DeviceRetriever

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# the primitive


def test_span_hands_its_two_clock_reads_to_the_sink_and_chains_on_t0():
    got = []
    with span("t.first", sink=lambda *a: got.append(a)) as first:
        time.sleep(0.001)
    with span("t.second", sink=lambda *a: got.append(a), t0=first.t1) as second:
        pass
    (n1, a0, a1), (n2, b0, b1) = got
    assert (n1, n2) == ("t.first", "t.second")
    assert (a0, a1) == (first.t0, first.t1) and a1 - a0 >= 0.001
    assert b0 == a1 and b1 == second.t1 >= b0  # no instant between the two


def test_span_without_jax_times_and_imports_nothing():
    """The event server and the benchmark's harness never import jax; a
    span there times its block and leaves jax alone."""
    code = (
        "import sys\n"
        "from predictionio_tpu.obs.startup import STARTUP\n"
        "from predictionio_tpu.obs.trace import span\n"
        "from predictionio_tpu.obs.waterfall import stage_span\n"
        "assert 'jax' not in sys.modules\n"
        "with span('t.block', sink=STARTUP.phase, rows=3) as s:\n"
        "    pass\n"
        "with stage_span('host_assembly', rows=3, b_pad=8):\n"
        "    pass\n"
        "assert s.t1 >= s.t0 and STARTUP.snapshot()['phases'][0][0] == "
        "'pio.t.block'\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n"
        "print('ok')\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60, cwd=REPO)
    assert p.returncode == 0 and p.stdout.strip() == "ok", p.stderr[-2000:]


def test_stage_span_end_is_the_stage_mark_on_the_ambient_clock():
    clock = BatchClock()
    token = set_stage_sink(clock)
    try:
        with stage_span("host_assembly", rows=2, b_pad=8) as s:
            time.sleep(0.001)
    finally:
        reset_stage_sink(token)
    # all the time since the clock began, up to the span's own end reading
    assert clock.stages == {"host_assembly": pytest.approx(s.t1 - clock.t0)}
    with stage_span("device_compute", rows=2, b_pad=8):
        pass  # no request attributed: a span still, no mark anywhere
    assert set(clock.stages) == {"host_assembly"}


def test_phase_timer_is_a_span_and_persist_joins_the_phases():
    from predictionio_tpu.workflow.context import Context
    from predictionio_tpu.workflow.tracing import phase_timer
    from tests.test_resilience import _trained

    ctx = Context()
    timer = phase_timer(ctx, "read")
    assert isinstance(timer, span) and timer.name == "train.read"
    with timer:
        pass
    assert [p for p, _ in ctx.phase_times] == ["read"]
    _engine, inst = _trained()
    phases = [p for p, _s in json.loads(inst.phase_times)]
    assert phases[-2:] == ["persist.serialize", "persist.put"]
    assert "datasource.read_training" in phases


# ---------------------------------------------------------------------------
# what a capture holds


def _tiny_ratings(rng, nu=40, ni=30, n=600):
    from predictionio_tpu.storage.bimap import BiMap
    from predictionio_tpu.storage.frame import Ratings

    return Ratings(
        user_indices=rng.integers(0, nu, n).astype(np.int32),
        item_indices=rng.integers(0, ni, n).astype(np.int32),
        ratings=rng.uniform(1, 5, n).astype(np.float32),
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}))


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = trace_dir.glob("plugins/profile/*/*.xplane.pb")
    data = ProfileData.from_file(str(path))
    return [(ev.name, dict(ev.stats), ev.start_ns, ev.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:CPU")
            for line in plane.lines for ev in line.events]


def test_capture_holds_the_program_spans_and_no_python_frame(tmp_path, rng):
    from predictionio_tpu.models.als import ALSConfig, train_als
    from predictionio_tpu.workflow.microbatch import MicroBatcher
    from predictionio_tpu.workflow.tracing import maybe_profile

    items = rng.standard_normal((300, 16)).astype(np.float32)
    users = rng.standard_normal((40, 16)).astype(np.float32)
    pipe = ServingPipeline(users, DeviceRetriever(items))
    batcher = MicroBatcher(
        lambda qs: [pipe.topk_rows(np.asarray(qs, np.int32), 5)])
    ratings = _tiny_ratings(rng)
    config = ALSConfig(rank=4, iterations=2, seed=1)
    batcher._call_batch_fn([1, 2, 3])  # compile outside the capture
    with maybe_profile(str(tmp_path / "trace")):
        batcher._call_batch_fn([1, 2, 3])
        train_als(ratings, config)
    events = _host_events(tmp_path / "trace")
    names = [n for n, *_ in events]
    assert names.count("pio.profile.window") == 1
    assert not [n for n in names
                if n.startswith("$") or re.search(r"\.py:\d+", n)], \
        "the Python tracer was on"
    for stage in ("host_assembly", "device_dispatch", "device_compute",
                  "result_scatter"):
        (stats,) = [s for n, s, *_ in events if n == f"pio.serve.{stage}"]
        assert stats["rows"] == 3 and stats["b_pad"] == 8
    (stats,) = [s for n, s, *_ in events if n == "pio.serve.batch_form"]
    assert stats["rows"] == 3
    for phase in ("layout", "layout.plan", "layout.user", "layout.item",
                  "upload", "init_factors", "first_step", "step", "observe",
                  "final_pull"):
        assert f"pio.train.als.{phase}" in names, phase
    steps = [s for n, s, *_ in events
             if n in ("pio.train.als.first_step", "pio.train.als.step")]
    assert sorted(s["step_num"] for s in steps) == [0, 1]
    # everything of the program lies inside the window
    (w0, w1), = [(t, t + d) for n, _s, t, d in events
                 if n == "pio.profile.window"]
    assert all(w0 <= t and t + d <= w1 for n, _s, t, d in events
               if n.startswith("pio.") and n != "pio.profile.window")


def test_maybe_profile_starts_the_capture_with_the_python_tracer_off(
        tmp_path, monkeypatch):
    import jax

    from predictionio_tpu.workflow.tracing import maybe_profile

    seen = {}

    def start_trace(log_dir, *, profiler_options=None, **_kw):
        seen.update(dir=log_dir, options=profiler_options, running=True)

    monkeypatch.setattr(jax.profiler, "start_trace", start_trace)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: seen.update(running=False))
    with pytest.raises(RuntimeError):
        with maybe_profile(str(tmp_path)):
            assert seen["running"]
            raise RuntimeError("inside the capture")
    assert seen["running"] is False  # stopped on the way out of an error
    assert seen["options"].python_tracer_level == 0
    assert seen["options"].host_tracer_level == 2  # jax's own default


# ---------------------------------------------------------------------------
# the pipeline's feed counters


def test_feed_counters_on_an_injected_clock():
    now = [100.0]
    st = _SharedState(clock=lambda: now[0])
    depth_dt = []  # (depth, seconds) as the schedule below spends them

    def spend(seconds, delta):
        depth_dt.append((st.in_device, seconds))
        now[0] += seconds
        with st.cond:
            st.advance(delta)

    spend(2.0, +1)    # idle 2 s, then one batch enters
    spend(0.5, +1)    # 0.5 s at depth 1
    spend(1.5, -1)    # 1.5 s at depth 2
    spend(0.25, -1)   # 0.25 s at depth 1
    spend(3.0, +1)    # idle 3 s
    spend(1.0, 0)     # 1 s at depth 1, read at a snapshot
    clock_s = now[0] - st.t_attach
    busy_s = sum(dt for depth, dt in depth_dt if depth >= 1)
    assert st.idle_s == pytest.approx(5.0)
    assert st.idle_s + busy_s == pytest.approx(clock_s)
    assert st.depth_s == pytest.approx(
        sum(depth * dt for depth, dt in depth_dt))
    assert st.in_device == 1


def test_pipeline_stats_carry_the_counters_and_dispatches_move_them(rng):
    items = rng.standard_normal((200, 16)).astype(np.float32)
    users = rng.standard_normal((20, 16)).astype(np.float32)
    pipe = ServingPipeline(users, DeviceRetriever(items))
    before = pipe.stats()
    time.sleep(0.01)
    pipe.topk_rows(np.arange(4, dtype=np.int32), 5)
    after = pipe.stats()
    for key in ("deviceIdleSeconds", "inDeviceSeconds", "clockSeconds"):
        assert after[key] >= before[key] >= 0.0
    assert after["clockSeconds"] - before["clockSeconds"] >= 0.01
    assert after["inDeviceSeconds"] > before["inDeviceSeconds"]
    # one batch at a time: depth is 0 or 1, so the two add up to the clock
    assert (after["deviceIdleSeconds"] + after["inDeviceSeconds"]
            == pytest.approx(after["clockSeconds"], abs=1e-6))
    assert pipe._state.in_device == 0


# ---------------------------------------------------------------------------
# start-up as phases


def test_process_age_and_host_memory_read_proc():
    age = process_age_seconds()
    assert 0.0 < age < 24 * 3600
    time.sleep(0.02)
    assert process_age_seconds() >= age + 0.01
    used = host_bytes_in_use()
    assert used is None or used > 0
    record = StartupRecord()
    seconds = record.process_to_device()
    # from the process's start, not from this call: at least the age
    # above (/proc/uptime counts in hundredths of a second)
    assert seconds >= age - 0.02
    (name, got, held), = record.snapshot()["phases"]
    assert (name, got, held is None or held > 0) == (
        "pio.process.to_device", seconds, True)
    assert record.snapshot()["readySeconds"] is None
    record.mark_ready()
    first = record.snapshot()["readySeconds"]
    record.mark_ready()  # the first call wins
    assert record.snapshot()["readySeconds"] == first >= seconds - 0.02


def test_stats_json_of_a_deploy_has_the_startup_phases_in_order(tmp_path, rng):
    import requests

    from predictionio_tpu.workflow.create_server import (
        EngineServer, create_engine_server_app)
    from tests.helpers import ServerThread
    from tests.test_capture_replay import _train_quickstart

    engine, inst = _train_quickstart(tmp_path, rng, "startuptest")
    STARTUP.reset()
    server = EngineServer(engine, inst)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        r = requests.post(st.url + "/queries.json",
                          json={"user": "u1", "num": 3})
        assert r.status_code == 200 and r.json()["itemScores"]
        startup = requests.get(st.url + "/stats.json").json()["startup"]
    finally:
        st.stop()
    names = [n for n, _s, _b in startup["phases"]]
    top = [n for n in names if n.count(".") == 2]
    # the blob is read ONCE: its checksum rides on prepare_deploy's result
    assert top == ["pio.deploy.blob_read", "pio.deploy.checksum",
                   "pio.deploy.deserialize",
                   "pio.deploy.attach_retriever", "pio.deploy.attach_pipeline",
                   "pio.deploy.prewarm", "pio.serve.id_map_inverse"]
    # a child ends, and so stands, before its parent
    assert names.index("pio.deploy.attach_retriever.catalog_pad") \
        < names.index("pio.deploy.attach_retriever.catalog_upload") \
        < names.index("pio.deploy.attach_retriever")
    programs = [i for i, n in enumerate(names)
                if n == "pio.deploy.prewarm.program"]
    assert programs and max(programs) < names.index("pio.deploy.prewarm")
    assert all(s > 0 for _n, s, _b in startup["phases"])
    assert all(b is None or b > 0 for _n, _s, b in startup["phases"])
    # a second answer builds nothing
    assert names.count("pio.serve.id_map_inverse") == 1


# ---------------------------------------------------------------------------
# ALS with nothing unaccounted


def test_als_phases_are_one_chain_and_their_keys_reach_the_record(
        rng, monkeypatch):
    from predictionio_tpu.models import als
    from predictionio_tpu.obs import trace
    from predictionio_tpu.obs.training import TRAINING

    chain = []
    stock_exit = trace.span.__exit__

    def recording_exit(self, *exc):
        out = stock_exit(self, *exc)
        if self.name.startswith("train.als."):
            chain.append((self.name, self.t0, self.t1))
        return out

    monkeypatch.setattr(trace.span, "__exit__", recording_exit)
    TRAINING.reset_source("train")
    ratings = _tiny_ratings(rng)  # made outside the timed call
    t_before = time.perf_counter()
    als.train_als(ratings, als.ALSConfig(rank=4, iterations=3, seed=2))
    t_after = time.perf_counter()
    TRAINING.finish("train")
    (attempt,) = TRAINING.summaries("train")
    top = [(n, a, b) for n, a, b in chain if n.count(".") == 2]
    assert [n.rsplit(".", 1)[1] for n, _a, _b in top] == [
        "layout", "upload", "init_factors", "first_step", "observe",
        "step", "observe", "step", "observe", "final_pull"]
    for (_n, _a, end), (_m, start, _b) in zip(top, top[1:]):
        assert start == end  # each starts on the reading that ended the last
    covered = top[-1][2] - top[0][1]
    assert covered == pytest.approx(sum(b - a for _n, a, b in top))
    assert covered >= 0.97 * (t_after - t_before) - 0.005
    inner = [(n, a, b) for n, a, b in chain
             if n in ("train.als.layout.plan", "train.als.layout.user",
                      "train.als.layout.item")]
    assert [a for _n, a, _b in inner[1:]] == [b for _n, _a, b in inner[:-1]]
    by_name = {n: b - a for n, a, b in chain}
    for key, phase in (("layoutSeconds", "layout"),
                       ("layoutUserSeconds", "layout.user"),
                       ("layoutItemSeconds", "layout.item"),
                       ("uploadSeconds", "upload"),
                       ("initSeconds", "init_factors"),
                       ("finalPullSeconds", "final_pull")):
        assert attempt[key] == by_name[f"train.als.{phase}"], key
    assert attempt["firstStepSeconds"] == by_name["train.als.first_step"]
    # `pio train` and `pio deploy` take the process's first look at the
    # device through STARTUP; a library caller leaves the key out
    if STARTUP.process_to_device_seconds is None:
        assert "processToDeviceSeconds" not in attempt
    else:
        assert attempt["processToDeviceSeconds"] > 0


def test_capture_shows_the_closed_gate_as_one_named_interval(tmp_path):
    """ISSUE 28: the time a formed batch's cut waits for a place ahead
    of the device is ``pio.serve.cut_held``, the one span that crosses an
    ``await``: in a capture it is one event as long as the wait, and a
    synchronous span taken on the loop's thread meanwhile lies inside
    it."""
    import asyncio
    import threading

    from predictionio_tpu.workflow.microbatch import MicroBatcher
    from predictionio_tpu.workflow.tracing import maybe_profile

    steps = [threading.Event() for _ in range(3)]
    calls = []

    def batch_fn(queries):
        calls.append(queries)
        assert steps[len(calls) - 1].wait(10)
        return [("ok", q) for q in queries]

    async def main():
        mb = MicroBatcher(batch_fn, window_s=0.0)
        tasks = []
        for q in (0, 1):  # two device steps outstanding: the gate closes
            tasks.append(asyncio.create_task(mb.submit(q)))
            while len(calls) <= q:
                await asyncio.sleep(0.002)
        tasks.append(asyncio.create_task(mb.submit(2)))
        await asyncio.sleep(0.03)
        with span("test.on_the_loop"):
            time.sleep(0.001)
        await asyncio.sleep(0.03)
        steps[0].set()
        while len(calls) < 3:
            await asyncio.sleep(0.002)
        for ev in steps:
            ev.set()
        out = await asyncio.gather(*tasks)
        held = mb.stats()["cutsHeld"]
        await mb.close()
        return out, held

    try:
        with maybe_profile(str(tmp_path / "trace")):
            out, held = asyncio.new_event_loop().run_until_complete(main())
    finally:
        for ev in steps:
            ev.set()
    assert out == [0, 1, 2] and held == 1
    events = _host_events(tmp_path / "trace")
    (gate,) = [(t, t + d) for n, _s, t, d in events
               if n == "pio.serve.cut_held"]
    assert gate[1] - gate[0] >= 0.05e9  # the whole wait, not a marker
    (inner,) = [(t, t + d) for n, _s, t, d in events
                if n == "pio.test.on_the_loop"]
    assert gate[0] <= inner[0] and inner[1] <= gate[1]
