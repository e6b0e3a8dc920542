"""Overload control (ISSUE 6): adaptive admission, backpressure pacing,
brownout/degraded mode unification, CoDel enqueue drops, and the
end-to-end overload chaos acceptance scenario.

Covers `workflow/admission.py` (token buckets, rate limiter, the
controller's signal math and fail-open contract), the engine server's
shed/brownout surfaces, the event server's ingest 429 path, the
feedback publisher's Retry-After honoring, and the ingest journal's
dynamic Retry-After — all CPU-fast and deterministic (faults armed via
`predictionio_tpu/faults.py`, clocks injected where timing matters).
"""

from __future__ import annotations

import asyncio
import threading
import time

import pytest
import requests

from predictionio_tpu.obs.metrics import METRICS
from predictionio_tpu.workflow.admission import (
    AdmissionController,
    RateLimiter,
    TokenBucket,
    backpressure_retry_after_s,
)
from predictionio_tpu.workflow.create_server import (
    EngineServer,
    create_engine_server_app,
)
from predictionio_tpu.faults import FAULTS
from predictionio_tpu.workflow.microbatch import DeadlineExceeded, MicroBatcher
from tests.helpers import ServerThread
from tests.test_resilience import _poll, _trained

pytestmark = pytest.mark.overload

_HALF = lambda: 0.5  # rng stub: kills jitter (factor becomes exactly 1)


# ---------------------------------------------------------------------------
# backpressure_retry_after_s — the shared pacing helper


def test_retry_after_proportional_to_backlog():
    # 100 queued / 10 per sec = 10 s to drain; jitter pinned to zero
    assert backpressure_retry_after_s(100, 10.0, rng=_HALF) == pytest.approx(10.0)


def test_retry_after_clamped_to_base_and_cap():
    # tiny backlog: clamps up to base_s
    assert backpressure_retry_after_s(1, 1000.0, rng=_HALF) == pytest.approx(1.0)
    # monster backlog: clamps down to cap_s
    assert backpressure_retry_after_s(10_000, 1.0, rng=_HALF) == pytest.approx(30.0)
    # unknown drain rate: base_s
    assert backpressure_retry_after_s(500, None, rng=_HALF) == pytest.approx(1.0)
    assert backpressure_retry_after_s(500, 0.0, rng=_HALF) == pytest.approx(1.0)


def test_retry_after_jitter_bounds():
    lo = backpressure_retry_after_s(100, 10.0, rng=lambda: 0.0)
    hi = backpressure_retry_after_s(100, 10.0, rng=lambda: 1.0)
    assert lo == pytest.approx(10.0 * 0.75)
    assert hi == pytest.approx(10.0 * 1.25)
    for _ in range(20):
        v = backpressure_retry_after_s(100, 10.0)
        assert 7.5 <= v <= 12.5


# ---------------------------------------------------------------------------
# TokenBucket / RateLimiter


def test_token_bucket_burst_then_refill():
    b = TokenBucket(rate_per_s=1.0, burst=3.0)
    t = 100.0
    # full burst up front, then deny
    assert [b.allow(now=t) for _ in range(4)] == [True, True, True, False]
    assert b.retry_after_s() == pytest.approx(1.0)
    # 2 s later: 2 tokens refilled
    assert b.allow(now=t + 2.0)
    assert b.allow(now=t + 2.0)
    assert not b.allow(now=t + 2.0)
    # refill caps at burst, not unbounded
    assert [b.allow(now=t + 1000.0) for _ in range(4)] == [
        True, True, True, False]


def test_token_bucket_clock_monotonicity():
    """A clock that stands still or steps BACKWARD neither refills nor
    penalizes — suspend/resume and test-clock jumps stay safe."""
    b = TokenBucket(rate_per_s=100.0, burst=1.0)
    t = 50.0
    assert b.allow(now=t)
    assert not b.allow(now=t)       # same instant: no refill
    assert not b.allow(now=t - 10)  # backwards: no refill, no crash
    assert b.tokens == pytest.approx(0.0)
    assert b.allow(now=t + 0.02)    # forward again: refills normally


def test_token_bucket_default_burst_and_validation():
    assert TokenBucket(10.0).burst == pytest.approx(20.0)
    assert TokenBucket(0.1).burst == pytest.approx(1.0)  # at least one
    with pytest.raises(ValueError):
        TokenBucket(0.0)


def test_rate_limiter_per_key_independence_and_lru():
    rl = RateLimiter(rate_per_s=1.0, burst=1.0, max_keys=2)
    t = 10.0
    ok_a, _ = rl.allow("a", now=t)
    ok_a2, ra = rl.allow("a", now=t)
    ok_b, _ = rl.allow("b", now=t)
    assert ok_a and not ok_a2 and ok_b  # b unaffected by a's exhaustion
    assert ra > 0
    # third key evicts the least-recently-used ("a", exhausted); a
    # re-seen "a" restarts with a full burst
    rl.allow("c", now=t)
    assert len(rl) == 2
    ok_a3, _ = rl.allow("a", now=t)
    assert ok_a3


# ---------------------------------------------------------------------------
# AdmissionController — signal math, class priority, fail-open


def _queue_controller(depth_box: dict, queue_high: int) -> AdmissionController:
    c = AdmissionController(
        "serve", queue_depth=lambda: depth_box["v"], queue_high=queue_high,
        backlog=lambda: depth_box["v"], drain_per_s=lambda: 10.0)
    c.sample_interval_s = 0.0  # resample on every decide (tests drive time)
    return c


def test_admission_sheds_classes_in_priority_order():
    depth = {"v": 0}
    c = _queue_controller(depth, queue_high=20)
    for k in ("serve", "feedback", "ingest"):
        assert c.decide(k).admitted

    depth["v"] = 16  # pressure 0.8: feedback sheds first
    assert c.decide("serve").admitted
    assert c.decide("ingest").admitted
    d = c.decide("feedback")
    assert not d.admitted and "overloaded" in d.reason

    depth["v"] = 19  # pressure 0.95: ingest joins
    assert c.decide("serve").admitted
    assert not c.decide("ingest").admitted

    depth["v"] = 20  # pressure 1.0: serve sheds too
    d = c.decide("serve")
    assert not d.admitted
    # Retry-After is lag-proportional with jitter: 20/10 = 2 s +/- 25 %
    assert 1.5 <= d.retry_after_s <= 2.5

    depth["v"] = 0  # queue drained: everything admits again
    for k in ("serve", "feedback", "ingest"):
        assert c.decide(k).admitted


def test_admission_inflight_is_brownout_only_never_sheds():
    """A busy pipeline alone (100 % slot occupancy, empty queue) must
    degrade gracefully, not refuse work."""
    c = AdmissionController("serve", queue_depth=lambda: 0, queue_high=8,
                            inflight=lambda: 1.0)
    c.sample_interval_s = 0.0
    assert c.decide("serve").admitted
    assert c.decide("feedback").admitted
    assert c.shed_pressure == pytest.approx(0.0)
    assert c.brownout_pressure == pytest.approx(1.0)
    assert c.overloaded


def test_admission_brownout_hysteresis():
    depth = {"v": 0}
    c = _queue_controller(depth, queue_high=10)
    c.pressure()
    assert not c.overloaded and c.recovered
    depth["v"] = 8  # 0.8 >= enter 0.75
    c.pressure()
    assert c.overloaded
    depth["v"] = 6  # 0.6: between exit (0.5) and enter — neither
    c.pressure()
    assert not c.overloaded and not c.recovered
    depth["v"] = 4  # 0.4 <= exit 0.5
    c.pressure()
    assert c.recovered


def test_admission_expiry_rate_is_windowed_and_recovers():
    """The deadline-expiry signal is a RATE over a sliding window, so
    it falls back to zero after the burst — a lifetime quantile/count
    would wedge the server shedding forever."""
    ctr = METRICS.get("pio_deadline_expired_total")
    c = AdmissionController("serve", expiry_counter_name=
                            "pio_deadline_expired_total",
                            expiry_rate_high=10.0, window_s=0.25)
    c.sample_interval_s = 0.0
    t0 = 1000.0
    assert c.pressure(now=t0) == pytest.approx(0.0)  # first sample arms prev
    ctr.inc(5)
    p = c.pressure(now=t0 + 0.3)  # 5 expiries / 0.3 s = 16.7/s -> 1.67
    assert p == pytest.approx(5 / 0.3 / 10.0, rel=1e-3)
    assert not c.decide("serve", now=t0 + 0.3).admitted
    # the burst stops: the next window reads a zero delta
    p = c.pressure(now=t0 + 0.6)
    assert p == pytest.approx(0.0)
    assert c.decide("serve", now=t0 + 0.6).admitted


def test_admission_rate_limit_throttles_per_key():
    c = AdmissionController("serve", rate_limit_qps=1.0, rate_limit_burst=1.0)
    c.sample_interval_s = 0.0
    t = 10.0
    assert c.decide("serve", key="k1", now=t).admitted
    d = c.decide("serve", key="k1", now=t)
    assert not d.admitted
    assert "rate limit" in d.reason
    assert d.retry_after_s > 0
    assert c.decide("serve", key="k2", now=t).admitted  # other keys fine
    assert c.decide("serve", now=t).admitted  # keyless requests skip it
    assert c.stats()["classes"]["serve"]["throttled"] == 1


@pytest.mark.chaos
def test_admission_fails_open_on_controller_error():
    """The armed ``admission.decide`` fault proves the fail-OPEN path:
    overload control must never be the outage."""
    depth = {"v": 100}
    c = _queue_controller(depth, queue_high=10)  # pressure 10: would shed
    FAULTS.inject("admission.decide", "error", times=2)
    for klass in ("serve", "ingest"):
        d = c.decide(klass)
        assert d.admitted  # admitted despite crushing pressure
        assert "failing open" in d.reason
    assert FAULTS.fired("admission.decide") == 2
    s = c.stats()
    assert s["classes"]["serve"]["errorOpen"] == 1
    assert s["classes"]["serve"]["admitRate"] == 1.0
    # fault budget spent: the controller sheds normally again
    assert not c.decide("serve").admitted


def test_admission_stats_and_metrics():
    depth = {"v": 20}
    c = _queue_controller(depth, queue_high=10)
    c.decide("serve")
    s = c.stats()
    assert s["pressure"] == pytest.approx(2.0)
    assert s["signals"]["queue"] == pytest.approx(2.0)
    assert s["classes"]["serve"]["shed"] == 1
    assert s["rateLimit"] is None
    assert METRICS.get("pio_admission_total").value("serve", "shed") == 1
    assert METRICS.get("pio_admission_pressure").value("serve") == \
        pytest.approx(2.0)


# ---------------------------------------------------------------------------
# CoDel: drop at enqueue when the queue ahead cannot drain in time


def test_codel_drops_doomed_query_at_enqueue():
    gate = threading.Event()
    gate.set()

    def batch_fn(qs):
        if not gate.is_set():
            gate.wait(10)
        time.sleep(0.02)
        return [("ok", q) for q in qs]

    async def drive():
        mb = MicroBatcher(batch_fn, max_batch=1, window_s=0.0005,
                          max_pending=64, max_inflight=1)
        # prime the dispatch-time EWMA (~20 ms) with two clean batches
        assert await mb.submit("a") == "a"
        await mb.submit("b")
        assert mb.stats()["ewmaDispatchMs"] >= 10
        # no dispatch history + shallow queue never pre-drops: a fresh
        # tight-deadline submit on an EMPTY queue serves normally
        assert await mb.submit("ok", deadline=time.monotonic() + 5) == "ok"

        gate.clear()
        t_hold = asyncio.create_task(mb.submit("hold"))  # occupies the slot
        assert await asyncio.to_thread(
            _poll, lambda: mb.stats()["inflight"] == 1)
        t_q = asyncio.create_task(mb.submit("queued"))   # builds the queue
        assert await asyncio.to_thread(
            _poll, lambda: len(mb._pending) >= 1)
        expired_before = mb.deadline_expired
        # ~40+ ms of queue ahead vs a 5 ms budget: dropped at ENQUEUE
        with pytest.raises(DeadlineExceeded, match="sojourn"):
            await mb.submit("victim", deadline=time.monotonic() + 0.005)
        assert mb.codel_dropped == 1
        assert METRICS.get("pio_codel_dropped_total").value() == 1
        # a CoDel drop is its own counter, NOT a deadline expiry
        assert mb.deadline_expired == expired_before
        gate.set()
        assert await t_hold == "hold"
        assert await t_q == "queued"
        await mb.close()

    asyncio.run(drive())


# ---------------------------------------------------------------------------
# unified mode state machine (brownout vs watchdog degraded)


def _admission_server(**kw) -> EngineServer:
    engine, inst = _trained()
    kw.setdefault("batch_window_ms", 0.5)
    kw.setdefault("batch_max", 1)
    kw.setdefault("admission", True)
    return EngineServer(engine, inst, **kw)


def test_two_batches_ahead_of_the_device_is_not_overload():
    """ISSUE 28: at a light Poisson load whose batches overlap two deep
    (the gate's normal working state: it holds most cuts) an
    ``--admission`` server stays in ``normal``: queries stay on the
    batcher and keep their full ``num``. The controller's ``inflight``
    signal is the thread bound's reading, not the gate's fill."""
    import random

    server = _admission_server(batch_max=8)
    assert server.batcher.max_inflight == 8
    server.admission.sample_interval_s = 0.0  # judge on every request
    modes: list[str] = []
    set_mode = server._set_mode
    server._set_mode = lambda m: (modes.append(m), set_mode(m))[1]
    ahead: list[int] = []
    FAULTS.inject("microbatch.dispatch", "slow", delay_s=0.03)
    st = ServerThread(lambda: create_engine_server_app(server))
    rnd = random.Random(28)

    def caller():
        for _ in range(25):
            time.sleep(rnd.expovariate(1 / 0.02))  # ~50/s a caller
            r = requests.post(st.url + "/queries.json", json={"q": 2},
                              timeout=10)
            assert r.status_code == 200
            ahead.append(server.batcher.stats()["aheadOfDevice"])

    try:
        threads = [threading.Thread(target=caller) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        s = server.batcher.stats()
        assert s["batchedQueries"] == 100  # none left for the fallback
        assert max(ahead) == 2 and s["cutsHeld"] > 0  # they did overlap
        assert modes == [] and server.mode == "normal"
        assert server.admission.stats()["signals"]["inflight"] <= 0.5
        assert server.brownout_degrade({"q": 2, "num": 50})["num"] == 50
    finally:
        FAULTS.clear()
        st.stop()


def test_mode_state_machine_unifies_brownout_and_degraded():
    server = _admission_server()
    adm = server.admission
    assert server.mode == "normal" and not server.degraded

    # overload pressure -> brownout
    adm.brownout_pressure = 0.9
    server._update_brownout()
    assert server.mode == "brownout"
    assert server.brownout_since is not None
    assert METRICS.get("pio_server_mode").value() == 1

    # watchdog trip OUTRANKS brownout -> degraded; brownout updates
    # must not pull the server out of degraded even when recovered
    server._on_watchdog_trip()
    assert server.mode == "degraded" and server.degraded
    assert METRICS.get("pio_server_mode").value() == 2
    assert METRICS.get("pio_degraded_mode").value() == 1
    adm.brownout_pressure = 0.0
    server._update_brownout()
    assert server.mode == "degraded"

    # probe success with pressure still high drops to brownout, not
    # straight to normal (the probe proved the device, not the queue)
    adm.brownout_pressure = 0.9
    server._exit_degraded()
    assert server.mode == "brownout"
    assert METRICS.get("pio_degraded_mode").value() == 0

    # pressure falls under the exit threshold -> normal
    adm.brownout_pressure = 0.1
    server._update_brownout()
    assert server.mode == "normal"
    assert server.brownout_since is None
    assert METRICS.get("pio_server_mode").value() == 0

    # probe success with pressure recovered goes straight to normal
    server._on_watchdog_trip()
    server._exit_degraded()
    assert server.mode == "normal"


def test_health_reports_mode_and_admission():
    server = _admission_server()
    h = server.health()
    assert h["mode"] == "normal"
    assert h["brownout"] == {"active": False, "since": None, "topk": 10}
    assert h["admission"]["pressure"] == pytest.approx(0.0)
    server.admission.brownout_pressure = 0.9
    server._update_brownout()
    h = server.health()
    assert h["status"] == "brownout" and h["mode"] == "brownout"
    assert h["brownout"]["active"] and h["brownout"]["since"]


def test_brownout_degrade_clamps_topk_fields():
    server = _admission_server(brownout_topk=10)
    q = {"user": "u1", "num": 100, "k": 3, "limit": True, "topK": 50}
    assert server.brownout_degrade(q) is q  # normal mode: untouched
    server._set_mode("brownout")
    out = server.brownout_degrade(q)
    assert out == {"user": "u1", "num": 10, "k": 3, "limit": True, "topK": 10}
    assert q["num"] == 100  # original never mutated
    assert server.brownout_degrade({"user": "u1"}) == {"user": "u1"}
    server._set_mode("degraded")
    assert server.brownout_degrade(q)["num"] == 10  # degraded clamps too


# ---------------------------------------------------------------------------
# FeedbackPublisher honors server-provided Retry-After on 429/503


def _backpressure_stub(status: int, retry_after: str | None):
    from aiohttp import web

    def app():
        async def events(request):
            headers = {}
            if retry_after is not None:
                headers["Retry-After"] = retry_after
            return web.json_response({}, status=status, headers=headers)

        a = web.Application()
        a.router.add_post("/events.json", events)
        return a

    return ServerThread(app)


@pytest.mark.parametrize("status", [429, 503])
def test_feedback_honors_retry_after(status):
    from predictionio_tpu.workflow.feedback import FeedbackPublisher

    stub = _backpressure_stub(status, "7.5")
    try:
        async def drive():
            pub = FeedbackPublisher(stub.url, "key", breaker_threshold=1)
            await pub._post({"event": "predict"}, attempt=0)
            assert pub.failed == 1
            event, attempt, not_before = pub._retry[0]
            delay = not_before - time.monotonic()
            # server said 7.5 s; client adds up to +10 % jitter — never
            # its own (much shorter) exponential guess
            assert 7.0 <= delay <= 8.5
            assert attempt == 1
            # a shedding server is ALIVE: even with breaker_threshold=1
            # the breaker must NOT open on backpressure
            assert pub._state == "closed"
            assert pub._consecutive_failures == 0
            await pub.aclose()

        asyncio.run(drive())
    finally:
        stub.stop()


def test_feedback_unparseable_retry_after_uses_backoff():
    from predictionio_tpu.workflow.feedback import FeedbackPublisher

    stub = _backpressure_stub(429, "soon")
    try:
        async def drive():
            pub = FeedbackPublisher(stub.url, "key")
            await pub._post({"event": "predict"}, attempt=0)
            _, _, not_before = pub._retry[0]
            # falls back to the local exponential schedule (base 0.25 s)
            assert not_before - time.monotonic() <= 0.3
            await pub.aclose()

        asyncio.run(drive())
    finally:
        stub.stop()


# ---------------------------------------------------------------------------
# event server: ingest 429 + Retry-After


def _event_app_key():
    from predictionio_tpu.storage import Storage

    meta = Storage.get_metadata()
    app = meta.app_insert("overloadapp")
    Storage.get_events().init_app(app.id)
    return meta.access_key_insert(app.id).key


_EV = {"event": "rate", "entityType": "user", "entityId": "u1",
       "targetEntityType": "item", "targetEntityId": "i1",
       "properties": {"rating": 4.0},
       "eventTime": "2020-01-01T00:00:00.000Z"}


def test_event_server_sheds_ingest_with_retry_after():
    from predictionio_tpu.api.event_server import create_event_app

    fill = {"v": 0.0}
    adm = AdmissionController("ingest", journal_fill=lambda: fill["v"],
                              backlog=lambda: 500,
                              drain_per_s=lambda: 100.0)
    adm.sample_interval_s = 0.0
    key = _event_app_key()
    st = ServerThread(lambda: create_event_app(stats=True, admission=adm))
    try:
        url = f"{st.url}/events.json?accessKey={key}"
        assert requests.post(url, json=_EV, timeout=10).status_code == 201
        fill["v"] = 0.89  # 0.89/0.9 = 0.988 >= ingest threshold 0.95
        r = requests.post(url, json=_EV, timeout=10)
        assert r.status_code == 429
        assert "overloaded" in r.json()["message"]
        ra = float(r.headers["Retry-After"])
        assert 1.0 * 0.75 <= ra <= 30.0 * 1.25  # jittered 500/100 = 5 s
        # stats surface both the shed count and the admission block
        stats = requests.get(f"{st.url}/stats.json?accessKey={key}",
                             timeout=10).json()
        assert stats["admission"]["classes"]["ingest"]["shed"] >= 1
        assert stats["statusCount"].get("429", 0) >= 1
        fill["v"] = 0.0  # pressure gone: admits again
        assert requests.post(url, json=_EV, timeout=10).status_code == 201
    finally:
        st.stop()


def test_event_server_rate_limits_per_access_key():
    from predictionio_tpu.api.event_server import create_event_app

    adm = AdmissionController("ingest", rate_limit_qps=0.001,
                              rate_limit_burst=2.0)
    adm.sample_interval_s = 0.0
    key = _event_app_key()
    st = ServerThread(lambda: create_event_app(admission=adm))
    try:
        url = f"{st.url}/events.json?accessKey={key}"
        assert requests.post(url, json=_EV, timeout=10).status_code == 201
        assert requests.post(url, json=_EV, timeout=10).status_code == 201
        r = requests.post(url, json=_EV, timeout=10)  # burst spent
        assert r.status_code == 429
        assert float(r.headers["Retry-After"]) > 0
    finally:
        st.stop()


def test_ingestor_dynamic_retry_after(tmp_path):
    """The journal-full Retry-After is computed from live lag / drain
    rate through the shared helper, not a fixed constant."""
    from predictionio_tpu.api.ingest import DurableIngestor

    ing = DurableIngestor(str(tmp_path / "j"), drain_batch=64)
    try:
        assert ing.fill_fraction() == pytest.approx(0.0, abs=1e-3)
        assert ing.drain_rate_per_s() is None
        # no history: base retry (1 s +/- 25 %)
        assert 0.75 <= ing.retry_after_s() <= 1.25
        # 640 records of lag at a measured 640/s drain -> ~1 s; 6400 -> ~10 s
        ing._ewma_drain_s = 0.1
        assert ing.drain_rate_per_s() == pytest.approx(640.0)
        for _ in range(100):
            ing.journal.append(b"x" * 64)
        lag = ing.journal.lag
        assert lag == 100
        expect = max(1.0, lag / 640.0)
        assert expect * 0.75 <= ing.retry_after_s() <= expect * 1.25
    finally:
        ing.journal.close()


# ---------------------------------------------------------------------------
# acceptance: overload chaos — shed at ingress, bounded p99, full recovery


def _p99(metrics_text: str) -> float:
    for line in metrics_text.splitlines():
        if line.startswith('pio_serving_latency_seconds_summary'
                           '{quantile="0.99"}'):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError("serving p99 not in /metrics")


def _shed_count(metrics_text: str) -> float:
    for line in metrics_text.splitlines():
        if line.startswith('pio_admission_total'
                           '{klass="serve",decision="shed"}'):
            return float(line.rsplit(" ", 1)[1])
    return 0.0


@pytest.mark.chaos
def test_overload_chaos_sheds_bounded_and_recovers():
    """The ISSUE 6 acceptance scenario, read entirely off /metrics:
    saturate the batcher with a hung device call, assert ingress sheds
    429 + Retry-After while the p99 of answered requests stays bounded
    and zero requests hang, then full recovery (shed rate -> 0, mode ->
    normal) after the fault releases."""
    engine, inst = _trained()
    server = EngineServer(engine, inst, batch_window_ms=0.5, batch_max=1,
                          batch_inflight=1, admission=True,
                          admission_queue_high=2)
    server.admission.sample_interval_s = 0.01  # tight loop for the test
    st = ServerThread(lambda: create_engine_server_app(server))
    q = {"q": 1}
    try:
        # ---- phase A: unloaded baseline p99
        for _ in range(20):
            assert requests.post(st.url + "/queries.json", json=q,
                                 timeout=10).status_code == 200
        m = requests.get(st.url + "/metrics", timeout=10).text
        p99_unloaded = _p99(m)
        assert _shed_count(m) == 0

        # ---- phase B: hang the device; queue builds behind the slot
        METRICS.reset()  # phase-B-only histogram (handles stay valid)
        FAULTS.inject("microbatch.dispatch", "hang", times=1, max_hang_s=60)
        held: dict[int, requests.Response] = {}

        def post_held(i):
            held[i] = requests.post(st.url + "/queries.json", json=q,
                                    timeout=60)

        # With one pipeline slot, the very first hung dispatch drives the
        # inflight signal to 1.0 and brownout reroutes everything after
        # it to the (fast) fallback path — so the queue can only be
        # stuffed by requests admitted off a still-stale pressure sample.
        # Widen the cache window, prime it at pressure 0, then land the
        # burst inside the window: one request hangs in the slot, two
        # queue behind it -> queue depth >= admission_queue_high.
        server.admission.sample_interval_s = 5.0
        server.admission.pressure()  # prime: queue 0, inflight 0
        threads = [threading.Thread(target=post_held, args=(i,), daemon=True)
                   for i in range(3)]
        for t in threads:
            t.start()
        assert _poll(lambda: len(server.batcher._pending) >= 2, timeout_s=4)
        # tighten the window again: the next decide() resamples and sees
        # queue pressure 2/2 = 1.0 -> serve sheds
        server.admission.sample_interval_s = 0.01

        def sheds():
            r = requests.post(st.url + "/queries.json", json=q, timeout=10)
            return r if r.status_code == 429 else None

        shed_resp = None

        def try_shed():
            nonlocal shed_resp
            shed_resp = sheds()
            return shed_resp is not None

        assert _poll(try_shed, timeout_s=10), "ingress never shed 429"
        assert float(shed_resp.headers["Retry-After"]) > 0
        assert "overloaded" in shed_resp.json()["message"]
        # overload pressure also means brownout (or it would, were the
        # watchdog not involved): mode is no longer normal
        assert server.mode == "brownout"

        # every request answered during the overload was answered FAST
        # (sheds + fallback serves) — the hung ones have not resolved
        # yet, so the phase-B histogram holds only live answers
        m = requests.get(st.url + "/metrics", timeout=10).text
        assert _shed_count(m) >= 1
        p99_overload = _p99(m)
        assert p99_overload <= max(2 * p99_unloaded, 0.1), \
            f"admitted p99 {p99_overload}s blew past the unloaded " \
            f"baseline {p99_unloaded}s under overload"

        # ---- phase C: release the fault; ZERO requests hang
        FAULTS.clear()
        for t in threads:
            t.join(30)
            assert not t.is_alive(), "a request hung past fault release"
        assert len(held) == 3  # all held requests got SOME answer
        for r in held.values():
            assert r.status_code in (200, 504)

        # recovery: pressure decays, mode returns to normal, fresh
        # queries admit, and the shed counter stops moving
        def recovered():
            r = requests.post(st.url + "/queries.json", json=q, timeout=10)
            return r.status_code == 200 and server.mode == "normal"

        assert _poll(recovered, timeout_s=15), "server never recovered"
        m = requests.get(st.url + "/metrics", timeout=10).text
        shed_after_release = _shed_count(m)
        for _ in range(10):
            assert requests.post(st.url + "/queries.json", json=q,
                                 timeout=10).status_code == 200
        m = requests.get(st.url + "/metrics", timeout=10).text
        assert _shed_count(m) == shed_after_release, \
            "still shedding after the overload passed"
        h = requests.get(st.url + "/health.json", timeout=10).json()
        assert h["status"] == "ok" and h["mode"] == "normal"
    finally:
        FAULTS.clear()
        st.stop()
