"""Hot-path latency attribution (ISSUE 11): per-request stage
waterfalls, the always-on flight recorder, and the SLO burn-rate engine.

Covers the acceptance criteria:

- waterfall soundness: on the batched, fallback and brownout serve paths
  every recorded request's stage durations sum to its wall latency
  within 5%, and the device-compute stage is genuinely fenced
  (ms-order on a real retriever, not a trivially-zero timestamp delta);
- flight chaos: an injected ``microbatch.dispatch`` hang trips the
  watchdog and the incident dump written AT THAT MOMENT contains the
  hung request's waterfall with its stalled stage plus the mode
  transition — and the server keeps serving (no restart);
- SLO burn: a synthetic bad-fraction burst (injected clock) moves the
  ``pio_slo_*`` gauges and flips ``summary()`` to breaching;
- /stats.json: waterfall + SLO + flight blocks are present, the
  host/device share split is coherent, and the snapshot is taken under
  the reload lock (torn-snapshot regression pin);
- satellite 1: every event-server response carries X-PIO-Request-ID —
  including the admission-shed 429, the journal-full 503, the auth 401
  and the webhook 404, none of which stamped it before.
"""

from __future__ import annotations

import glob
import json
import threading
import time

import numpy as np
import pytest
import requests

from predictionio_tpu.obs.flight import FLIGHT
from predictionio_tpu.obs.metrics import METRICS
from predictionio_tpu.obs.trace import TRACE_HEADER
from predictionio_tpu.obs.waterfall import (
    DEVICE_STAGES,
    STAGES,
    BatchClock,
    Waterfall,
    mark_stage,
    reset_stage_sink,
    set_stage_sink,
)
from predictionio_tpu.faults import FAULTS
from tests.helpers import ServerThread


def _poll(cond, timeout_s: float = 15.0, interval_s: float = 0.05):
    t_end = time.monotonic() + timeout_s
    while time.monotonic() < t_end:
        if cond():
            return True
        time.sleep(interval_s)
    return cond()


def _served_server(**kw):
    from predictionio_tpu.workflow.create_server import EngineServer
    from tests.test_resilience import _trained

    engine, inst = _trained()
    return EngineServer(engine, inst, **kw)


def _assert_sound(rec: dict, max_err: float = 0.05):
    """One flight record's stages must sum to its wall within 5%."""
    stages = rec["stagesMs"]
    assert stages, f"no stages attributed: {rec}"
    assert set(stages) <= set(STAGES)
    total = sum(stages.values())
    wall = rec["wallMs"]
    assert wall > 0
    assert abs(total - wall) <= max_err * wall + 0.05, \
        f"stages {total:.3f}ms vs wall {wall:.3f}ms: {rec}"


# ---------------------------------------------------------------------------
# waterfall mechanics (unit)


def test_waterfall_residual_closes_sum_to_wall():
    wf = Waterfall(rid="r1")
    wf.mark("admission")
    time.sleep(0.002)
    wf.mark("host_assembly")
    wf.finish("ok", record=False)
    assert wf.finished
    assert sum(wf.stages.values()) == pytest.approx(wf.wall, rel=1e-6)
    assert "response_write" in wf.stages  # the residual stage
    wf2 = wf.finish("again")  # idempotent: first finish wins
    assert wf2.status == "ok"


def test_marks_are_additive_and_batch_merge_lands_in_full():
    wf = Waterfall()
    wf.add("device_compute", 0.001)
    wf.add("device_compute", 0.002)
    assert wf.stages["device_compute"] == pytest.approx(0.003)
    clock = BatchClock()
    clock.add("batch_form", 0.004)
    clock.add("device_compute", 0.005)
    wf.merge_batch(clock)
    assert wf.stages["batch_form"] == pytest.approx(0.004)
    assert wf.stages["device_compute"] == pytest.approx(0.008)


def test_batch_clock_reports_in_progress_successor():
    clock = BatchClock()
    assert clock.in_progress() == "batch_form"  # nothing marked yet
    clock.mark("batch_form")
    assert clock.in_progress() == "host_assembly"
    clock.mark("device_compute")
    assert clock.in_progress() == "result_scatter"


def test_mark_stage_is_noop_without_sink():
    mark_stage("device_compute")  # must not raise, must not record
    wf = Waterfall()
    token = set_stage_sink(wf)
    try:
        mark_stage("admission")
    finally:
        reset_stage_sink(token)
    mark_stage("queue_wait")  # after reset: back to no-op
    assert "queue_wait" not in wf.stages


def test_device_compute_is_fenced_ms_order():
    """The block_until_ready delta around the retrieval invoke must
    capture real device time: on a 65k x 64 catalog the scoring matmul
    costs whole milliseconds even on CPU, and JAX dispatches async — an
    unfenced measurement would book ~0 compute."""
    from predictionio_tpu.ops.retrieval import DeviceRetriever

    rng = np.random.default_rng(7)
    items = (rng.normal(size=(65_536, 64)) / 8.0).astype(np.float32)
    q = (rng.normal(size=(32, 64)) / 8.0).astype(np.float32)
    ret = DeviceRetriever(items)
    ret.topk(q, 10)  # warm: compile outside the attributed window

    wf = Waterfall(path="unit")
    token = set_stage_sink(wf)
    try:
        wf.cursor()
        ret.topk(q, 10)
    finally:
        reset_stage_sink(token)
    assert "device_compute" in wf.stages
    device = sum(wf.stages.get(s, 0.0) for s in DEVICE_STAGES)
    assert device >= 1e-4, f"device stages implausibly small: {wf.stages}"
    # the fence moved the wait out of result_scatter: the host pull
    # after a fenced result is cheap relative to the compute itself
    assert wf.stages.get("result_scatter", 0.0) < 10 * max(device, 1e-9)


# ---------------------------------------------------------------------------
# waterfall soundness through the server (batched / fallback / brownout)


def _drive(url: str, n: int, sess=None):
    sess = sess or requests
    codes = []
    for i in range(n):
        codes.append(sess.post(url + "/queries.json", json={"q": i},
                               timeout=10).status_code)
    return codes


def test_batched_path_waterfalls_sum_to_wall():
    from predictionio_tpu.workflow.create_server import (
        create_engine_server_app)

    server = _served_server(batch_window_ms=0.5, batch_max=8,
                            batch_inflight=2)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        assert all(c == 200 for c in _drive(st.url, 24))
        snap = FLIGHT.snapshot()
        recs = [r for r in snap["records"] if r["status"] == "ok"]
        assert len(recs) >= 24
        for rec in recs:
            _assert_sound(rec)
            # the batcher path attributes its own stages, not just the
            # structural residual
            assert "queue_wait" in rec["stagesMs"]
            assert "batch_form" in rec["stagesMs"]
            assert rec["context"]["http"] == 200
        # the response echoes the rid the flight record carries
        rid = "wf-join-0001"
        r = requests.post(st.url + "/queries.json", json={"q": 1},
                          headers={TRACE_HEADER: rid}, timeout=10)
        assert r.headers[TRACE_HEADER] == rid
        assert any(rec["requestId"] == rid
                   for rec in FLIGHT.snapshot()["records"])
    finally:
        st.stop()


def test_fallback_and_brownout_paths_sum_to_wall():
    from predictionio_tpu.workflow.create_server import (
        create_engine_server_app)

    # batch_window_ms=0: no micro-batcher, every query takes the
    # fallback (to_thread) path — the contextvar sink must follow it
    server = _served_server(batch_window_ms=0)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        assert all(c == 200 for c in _drive(st.url, 8))
        recs = [r for r in FLIGHT.snapshot()["records"]
                if r["status"] == "ok"]
        assert len(recs) >= 8
        for rec in recs:
            _assert_sound(rec)
            assert rec["context"]["mode"] == "normal"

        FLIGHT.reset()
        server._set_mode("brownout")
        assert all(c == 200 for c in _drive(st.url, 8))
        recs = [r for r in FLIGHT.snapshot()["records"]
                if r["status"] == "ok"]
        assert len(recs) >= 8
        for rec in recs:
            _assert_sound(rec)
            assert rec["context"]["mode"] == "brownout"
    finally:
        st.stop()


def test_stats_json_carries_waterfall_slo_flight_blocks():
    from predictionio_tpu.workflow.create_server import (
        create_engine_server_app)

    server = _served_server(batch_window_ms=0.5, batch_max=8)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        assert all(c == 200 for c in _drive(st.url, 12))
        stats = requests.get(st.url + "/stats.json", timeout=10).json()
        wfb = stats["waterfall"]
        assert wfb["wall"]["count"] >= 12
        recorded = [s for s in STAGES if wfb["stages"][s]["count"] > 0]
        assert len(recorded) >= 3
        assert wfb["hostShare"] is not None
        assert wfb["hostShare"] + wfb["deviceShare"] == pytest.approx(
            1.0, abs=1e-3)
        slo = stats["slo"]
        names = {o["name"] for o in slo["objectives"]}
        assert names == {"latency", "availability"}
        assert all(o["windows"]["5m"]["events"] >= 12
                   for o in slo["objectives"])
        assert stats["flight"]["records"] >= 12
        # /health.json summarizes the same SLO + flight state
        health = requests.get(st.url + "/health.json", timeout=10).json()
        assert health["slo"]["breaching"] is False
        assert health["flight"]["capacity"] == 256
    finally:
        st.stop()


def test_stats_snapshot_taken_under_reload_lock():
    """Torn-snapshot regression pin: serving_stats must read the
    deployed bundle and the patch epoch under ``_reload_lock`` — a
    concurrent reload can no longer interleave between the two reads."""
    server = _served_server(batch_window_ms=0)
    server.serve_query({"q": 0})

    done = threading.Event()
    out = {}

    def snap():
        out["stats"] = server.serving_stats()
        done.set()

    with server._reload_lock:
        t = threading.Thread(target=snap, daemon=True)
        t.start()
        # while a reload holds the lock the stats reader must block
        assert not done.wait(0.3), "serving_stats did not take the lock"
    assert done.wait(5.0)
    assert out["stats"]["model"] is not None


# ---------------------------------------------------------------------------
# flight recorder: ring, incidents, chaos


def test_flight_ring_is_bounded_and_dump_cooldown(tmp_path):
    FLIGHT.configure(capacity=4, dump_dir=str(tmp_path / "fl"),
                     cooldown_s=60.0)
    for i in range(9):
        FLIGHT.record({"requestId": f"r{i}", "wallMs": 1.0,
                       "stagesMs": {}, "status": "ok", "path": "serve",
                       "finished": True})
    snap = FLIGHT.snapshot()
    assert len(snap["records"]) == 4
    assert snap["records"][-1]["requestId"] == "r8"

    p1 = FLIGHT.incident("test_reason")
    assert p1 and json.load(open(p1))["reason"] == "test_reason"
    assert FLIGHT.incident("test_reason") is None  # cooldown suppresses
    assert METRICS.get("pio_flight_dumps_suppressed_total").value(
        "test_reason") == 1
    assert FLIGHT.incident("other_reason") is not None  # per-reason
    assert FLIGHT.incident("test_reason", force=True) is not None


@pytest.mark.chaos
def test_chaos_hang_dumps_flight_with_stalled_stage(tmp_path):
    """ISSUE 11 acceptance: inject a microbatch.dispatch hang -> the
    watchdog fires -> the incident file written at that moment contains
    the hung request's waterfall (stalled stage stamped) and the mode
    transition context — and the server answers queries afterwards
    without a restart."""
    from predictionio_tpu.workflow.create_server import (
        create_engine_server_app)

    dump_dir = str(tmp_path / "flight")  # conftest pointed FLIGHT here
    server = _served_server(batch_window_ms=0.5, batch_max=8,
                            batch_inflight=2, dispatch_timeout_s=0.3,
                            degraded_cooldown_s=60.0)
    FAULTS.inject("microbatch.dispatch", "hang", times=1, max_hang_s=20)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        r = requests.post(st.url + "/queries.json", json={"q": 0},
                          timeout=30)
        assert r.status_code == 504  # watchdog reclaimed the dispatch
        assert _poll(lambda: server.degraded)

        wd_dumps = glob.glob(f"{dump_dir}/flight-watchdog-*.json")
        assert wd_dumps, "watchdog fired but no incident dump written"
        payload = json.load(open(wd_dumps[0]))
        assert payload["reason"] == "watchdog"
        hung = [rec for rec in payload["records"] if rec.get("hung")]
        assert hung, "dump does not contain the hung request"
        assert hung[0]["stalledStage"] in STAGES
        assert hung[0]["requestId"] == r.headers[TRACE_HEADER]
        # the mode transition is dumped too (degraded entry)
        mode_dumps = glob.glob(f"{dump_dir}/flight-mode_degraded-*.json")
        assert mode_dumps
        assert json.load(open(mode_dumps[0]))["context"]["mode"] == \
            "degraded"

        # no restart: the degraded server still answers
        r = requests.post(st.url + "/queries.json", json={"q": 1},
                          timeout=10)
        assert r.status_code == 200
        assert METRICS.get("pio_flight_dumps_total").value("watchdog") >= 1
    finally:
        FAULTS.clear()
        _poll(lambda: server.batcher.stats()["zombieDispatches"] == 0,
              timeout_s=5)
        st.stop()


# ---------------------------------------------------------------------------
# SLO burn-rate engine


def test_slo_synthetic_burn_moves_gauges_and_breaches():
    from predictionio_tpu.obs.slo import SloTracker, default_objectives

    clock = {"t": 1000.0}
    tr = SloTracker(default_objectives(deadline_s=0.25),
                    now_fn=lambda: clock["t"])
    # 5 minutes of clean traffic: nothing burns
    for _ in range(300):
        tr.observe(0.01, ok=True)
        clock["t"] += 1.0
    tr.refresh_gauges()
    burn = METRICS.get("pio_slo_burn_rate")
    assert burn.value("availability", "5m") == 0.0
    assert tr.summary()["breaching"] is False

    # a 50% failure burst: availability budget is 0.1%, so the 5m burn
    # rockets past 1.0 and the summary flips to breaching
    for _ in range(120):
        tr.observe(0.01, ok=False)
        tr.observe(0.01, ok=True)
        clock["t"] += 1.0
    tr.refresh_gauges()
    assert burn.value("availability", "5m") > 100.0
    assert METRICS.get("pio_slo_bad_fraction").value(
        "availability", "5m") > 0.2
    # the 1h window dilutes the same burst: multi-window separation
    assert burn.value("availability", "1h") < burn.value(
        "availability", "5m")
    s = tr.summary()
    assert s["breaching"] is True
    avail = next(o for o in s["objectives"] if o["name"] == "availability")
    assert avail["breaching"] is True
    assert METRICS.get("pio_slo_events_total").value(
        "availability", "bad") == 120


def test_slo_latency_objective_burns_on_slow_requests():
    from predictionio_tpu.obs.slo import Objective, SloTracker

    clock = {"t": 0.0}
    tr = SloTracker([Objective("latency", "latency", 0.99,
                               threshold_s=0.1)],
                    now_fn=lambda: clock["t"])
    for _ in range(100):
        tr.observe(0.5, ok=True)  # slow but "successful"
        clock["t"] += 0.5
    rates = tr.burn_rates()
    assert rates["latency"]["5m"] == pytest.approx(100.0)  # 1.0 / 0.01


def test_event_server_books_ingest_availability_slo():
    from predictionio_tpu.api import create_event_app
    from predictionio_tpu.storage import Storage

    meta = Storage.get_metadata()
    app = meta.app_insert("sloapp")
    key = meta.access_key_insert(app.id).key
    Storage.get_events().init_app(app.id)
    st = ServerThread(lambda: create_event_app(stats=True))
    try:
        ev = {"event": "rate", "entityType": "user", "entityId": "u1",
              "properties": {"rating": 4}}
        assert requests.post(f"{st.url}/events.json?accessKey={key}",
                             json=ev, timeout=10).status_code == 201
        stats = requests.get(f"{st.url}/stats.json?accessKey={key}",
                             timeout=10).json()
        slo = stats["slo"]
        assert slo["objectives"][0]["name"] == "ingest-availability"
        assert slo["objectives"][0]["windows"]["5m"]["events"] >= 1
        assert slo["breaching"] is False
    finally:
        st.stop()


# ---------------------------------------------------------------------------
# satellite 1: request-id stamping on every event-server response


def test_event_server_stamps_request_id_on_shed_401_404_and_503(tmp_path):
    from predictionio_tpu.api import DurableIngestor, create_event_app
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.workflow.admission import AdmissionController

    meta = Storage.get_metadata()
    app = meta.app_insert("stampapp")
    key = meta.access_key_insert(app.id).key
    Storage.get_events().init_app(app.id)
    adm = AdmissionController("ingest", rate_limit_qps=0.001,
                              rate_limit_burst=2.0)
    adm.sample_interval_s = 0.0
    # a 1-byte journal: the first durable append answers 503
    ingestor = DurableIngestor(str(tmp_path / "wal"), fsync="never",
                               max_bytes=1)
    st = ServerThread(lambda: create_event_app(
        stats=True, ingestor=ingestor, admission=adm))
    ev = {"event": "rate", "entityType": "user", "entityId": "u1",
          "properties": {"rating": 4}}
    try:
        url = f"{st.url}/events.json?accessKey={key}"
        # journal-full 503: stamped, adopting the client's id
        r = requests.post(url, json=ev,
                          headers={TRACE_HEADER: "stamp-503"}, timeout=10)
        assert r.status_code == 503
        assert r.headers[TRACE_HEADER] == "stamp-503"
        # webhook 404 (unknown connector): stamped
        r = requests.post(f"{st.url}/webhooks/nope.json?accessKey={key}",
                          json={}, timeout=10)
        assert r.status_code == 404
        assert r.headers[TRACE_HEADER]
        # burst (2 tokens) spent -> rate-limit shed 429: stamped
        r = requests.post(url, json=ev, timeout=10)
        assert r.status_code == 429
        assert r.headers[TRACE_HEADER]
        # auth 401 (separate rate bucket per key): stamped
        r = requests.post(f"{st.url}/events.json?accessKey=wrong",
                          json=ev, timeout=10)
        assert r.status_code == 401
        assert r.headers[TRACE_HEADER]
        # aiohttp-raised 404 (unknown route): the middleware catches
        # HTTPException and stamps it too
        r = requests.get(f"{st.url}/no/such/route", timeout=10)
        assert r.status_code == 404
        assert r.headers[TRACE_HEADER]
    finally:
        st.stop()
