"""Serving resilience layer: deadlines, stuck-dispatch watchdog, degraded
mode, graceful drain, feedback circuit breaker — proven via the
deterministic fault-injection harness (predictionio_tpu/faults.py).

The acceptance scenario (ISSUE 2): with ``max_inflight`` batches hung via
injected faults, the watchdog reclaims all pipeline slots, /health.json
reports degraded, subsequent queries still answer on the per-query
fallback path, and a drain finishes cleanly — where the pre-PR code
wedged its pipeline forever.

All chaos-marked tests run under conftest's SIGALRM guard and get every
armed fault cleared on teardown.
"""

from __future__ import annotations

import asyncio
import threading
import time

import pytest
import requests

from predictionio_tpu.controller import Engine, EngineParams
from predictionio_tpu.storage import Storage
from predictionio_tpu.storage.events_base import StorageError
from predictionio_tpu.testing.sample_engine import (
    SampleAlgoParams,
    SampleAlgorithm,
    SampleDataSource,
    SampleDataSourceParams,
    SamplePreparator,
    SampleQuery,
    SampleServing,
)
from predictionio_tpu.workflow import Context, run_train
from predictionio_tpu.workflow.create_server import (
    EngineServer,
    create_engine_server_app,
)
from predictionio_tpu.faults import FAULTS, FaultInjected
from predictionio_tpu.workflow.feedback import FeedbackPublisher
from predictionio_tpu.workflow.microbatch import (
    DeadlineExceeded,
    DispatchTimeout,
    MicroBatcher,
    ServerBusy,
)
from tests.helpers import ServerThread


class EchoAlgorithm(SampleAlgorithm):
    """SampleAlgorithm that declares its query dataclass, so raw-dict
    queries off the wire decode before predict (SampleAlgorithm itself
    leaves queries as dicts, which its predict cannot serve)."""

    query_class = SampleQuery


def make_resilience_engine() -> Engine:
    return Engine(
        data_source_classes=SampleDataSource,
        preparator_classes=SamplePreparator,
        algorithm_classes={"echo": EchoAlgorithm},
        serving_classes=SampleServing,
    )


def _trained():
    engine = make_resilience_engine()
    ep = EngineParams(
        data_source_params=("", SampleDataSourceParams(id=0)),
        algorithm_params_list=(("echo", SampleAlgoParams(id=1)),),
    )
    iid = run_train(engine, ep, Context(),
                    engine_factory="tests.test_resilience:"
                                   "make_resilience_engine")
    return engine, Storage.get_metadata().engine_instance_get(iid)


def _poll(cond, timeout_s: float = 10.0, interval_s: float = 0.02) -> bool:
    t_end = time.monotonic() + timeout_s
    while time.monotonic() < t_end:
        if cond():
            return True
        time.sleep(interval_s)
    return cond()


# ---------------------------------------------------------------------------
# fault-injection harness


@pytest.mark.chaos
def test_fault_error_budget_and_disarm():
    """An error fault fires exactly `times` then disarms itself."""
    FAULTS.inject("t.site", "error", times=2)
    with pytest.raises(FaultInjected):
        FAULTS.fire("t.site")
    with pytest.raises(FaultInjected):
        FAULTS.fire("t.site")
    FAULTS.fire("t.site")  # budget spent: no-op
    assert FAULTS.fired("t.site") == 2


@pytest.mark.chaos
def test_fault_custom_exception_and_clear():
    FAULTS.inject("t.exc", "error", exc=StorageError("injected"))
    with pytest.raises(StorageError, match="injected"):
        FAULTS.fire("t.exc")
    FAULTS.clear("t.exc")
    FAULTS.fire("t.exc")  # disarmed


@pytest.mark.chaos
def test_fault_hang_blocks_until_released():
    FAULTS.inject("t.hang", "hang", max_hang_s=10)
    done = threading.Event()

    def worker():
        FAULTS.fire("t.hang")
        done.set()

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    assert not done.wait(0.2), "hang fault did not block"
    FAULTS.release("t.hang")
    assert done.wait(5), "release did not unblock the hung thread"
    t.join(5)


@pytest.mark.chaos
def test_fault_slow_delays_then_continues():
    FAULTS.inject("t.slow", "slow", delay_s=0.05, times=1)
    t0 = time.monotonic()
    FAULTS.fire("t.slow")
    assert time.monotonic() - t0 >= 0.05
    assert FAULTS.fired("t.slow") == 1


def test_unarmed_sites_are_noops():
    FAULTS.fire("never.armed")
    asyncio.run(FAULTS.afire("never.armed"))


# ---------------------------------------------------------------------------
# request deadlines (MicroBatcher.submit)


def test_submit_expired_deadline_raises_504_without_slot():
    async def main():
        mb = MicroBatcher(lambda qs: [("ok", q) for q in qs], window_s=0)
        with pytest.raises(DeadlineExceeded):
            await mb.submit("q", deadline=time.monotonic() - 0.01)
        assert mb.deadline_expired == 1
        assert mb.batches == 0  # never consumed a batch slot
        await mb.close()

    asyncio.run(main())


def test_deadline_expires_while_queued():
    async def main():
        served = []

        def bf(qs):
            served.append(list(qs))
            return [("ok", q) for q in qs]

        # fixed 80 ms window >> 20 ms deadline: the query expires in the
        # queue and must be swept at batch formation, not dispatched
        mb = MicroBatcher(bf, window_s=0.08)
        task = asyncio.create_task(
            mb.submit("q", deadline=time.monotonic() + 0.02))
        with pytest.raises(DeadlineExceeded):
            await task
        assert mb.deadline_expired == 1
        assert served == [] and mb.batches == 0
        await mb.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# stuck-dispatch watchdog


@pytest.mark.chaos
def test_watchdog_reclaims_slot_and_tracks_zombie():
    def bf(qs):
        return [("ok", ("served", q)) for q in qs]

    async def main():
        FAULTS.inject("microbatch.dispatch", "hang", times=1, max_hang_s=10)
        trips = []
        mb = MicroBatcher(bf, window_s=0, max_inflight=1,
                          dispatch_timeout_s=0.2,
                          on_watchdog=lambda: trips.append(1))
        with pytest.raises(DispatchTimeout):
            await mb.submit("q1")
        assert mb.watchdog_trips == 1
        assert trips == [1]
        assert mb.stats()["zombieDispatches"] == 1
        # the ONLY pipeline slot was held by the hung batch; this submit
        # completing proves the watchdog reclaimed it (pre-PR: wedged
        # forever)
        out = await asyncio.wait_for(mb.submit("q2"), 5)
        assert out == ("served", "q2")
        # releasing the hang lets the zombie thread finish and unregister
        FAULTS.clear()
        for _ in range(200):
            if mb.stats()["zombieDispatches"] == 0:
                break
            await asyncio.sleep(0.02)
        assert mb.stats()["zombieDispatches"] == 0
        await mb.close()

    asyncio.run(main())


@pytest.mark.chaos
def test_watchdog_disabled_by_default():
    """Without dispatch_timeout_s a slow batch is just slow — no trip."""
    async def main():
        FAULTS.inject("microbatch.dispatch", "slow", delay_s=0.1, times=1)
        mb = MicroBatcher(lambda qs: [("ok", q) for q in qs], window_s=0)
        assert await mb.submit("q") == "q"
        assert mb.watchdog_trips == 0
        await mb.close()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# close()/submit() race + graceful drain (MicroBatcher)


def test_close_racing_submit_sheds_with_server_busy():
    """A submit landing while close() is draining must shed (503), not
    start a worker generation close() would leak or cancel."""
    release = threading.Event()

    def bf(qs):
        release.wait(5)
        return [("ok", q) for q in qs]

    async def main():
        mb = MicroBatcher(bf, window_s=0, max_inflight=1)
        t1 = asyncio.create_task(mb.submit("a"))
        await asyncio.sleep(0.05)  # dispatched; bf blocked on the latch
        closer = asyncio.create_task(mb.close())
        await asyncio.sleep(0.01)  # close() set _closing, awaits in-flight
        with pytest.raises(ServerBusy):
            await mb.submit("b")
        release.set()
        await closer
        assert await t1 == "a"  # in-flight batch still answered
        # close() resets the shed flag: the batcher restarts cleanly
        assert await mb.submit("c") == "c"
        await mb.close()

    asyncio.run(main())


def test_drain_flushes_queued_queries():
    """drain() answers queued queries (no window) instead of cancelling
    them like close(); expired ones still 504."""
    async def main():
        served = []

        def bf(qs):
            served.append(list(qs))
            return [("ok", q) for q in qs]

        # 5 s window: submissions sit queued while the worker sleeps
        mb = MicroBatcher(bf, window_s=5.0, max_batch=4)
        t1 = asyncio.create_task(mb.submit("a"))
        t2 = asyncio.create_task(mb.submit("b"))
        t3 = asyncio.create_task(
            mb.submit("c", deadline=time.monotonic() + 0.01))
        await asyncio.sleep(0.05)  # enqueue all three; t3's deadline passes
        await mb.drain()
        assert await t1 == "a"
        assert await t2 == "b"
        with pytest.raises(DeadlineExceeded):
            await t3
        assert sorted(q for b in served for q in b) == ["a", "b"]

    asyncio.run(main())


# ---------------------------------------------------------------------------
# ServerBusy -> 503 under a saturated pipeline (HTTP level)


@pytest.mark.chaos
def test_http_503_when_pipeline_saturated():
    engine, inst = _trained()
    server = EngineServer(engine, inst, batch_window_ms=0.5, batch_max=1,
                          batch_inflight=1)
    server.batcher.max_pending = 1  # tiny queue: saturation in 2 queries
    FAULTS.inject("microbatch.dispatch", "hang", max_hang_s=20)
    st = ServerThread(lambda: create_engine_server_app(server))
    results: dict[str, requests.Response] = {}

    def post(key, q):
        results[key] = requests.post(
            st.url + "/queries.json", json={"q": q}, timeout=30)

    t1 = threading.Thread(target=post, args=("q1", 1), daemon=True)
    t2 = threading.Thread(target=post, args=("q2", 2), daemon=True)
    try:
        t1.start()
        # q1 holds the only dispatch slot (hung in the fault)
        assert _poll(lambda: server.batcher.stats()["inflight"] == 1)
        t2.start()
        # q2 fills the pending queue behind the held slot
        assert _poll(lambda: len(server.batcher._pending) == 1)
        r3 = requests.post(st.url + "/queries.json", json={"q": 3},
                           timeout=10)
        assert r3.status_code == 503
        assert "full" in r3.json()["message"]
        # free the pipeline: both held queries answer normally
        FAULTS.clear()
        t1.join(15)
        t2.join(15)
        assert results["q1"].status_code == 200
        assert results["q2"].status_code == 200
        assert results["q1"].json()["value"] == 1
        assert results["q2"].json()["value"] == 2
    finally:
        FAULTS.clear()
        t1.join(5)
        t2.join(5)
        st.stop()


# ---------------------------------------------------------------------------
# acceptance: hung pipeline -> watchdog -> degraded -> fallback -> drain


@pytest.mark.chaos
def test_hung_pipeline_degrades_falls_back_and_drains():
    """ISSUE 2 acceptance: ALL max_inflight slots hang; the watchdog
    reclaims every one (each hung query answers 504, not never), the
    server flips degraded and /health.json says so, the next query still
    answers on the per-query fallback path, and drain completes."""
    engine, inst = _trained()
    server = EngineServer(
        engine, inst,
        batch_window_ms=0.5, batch_max=1, batch_inflight=2,
        dispatch_timeout_s=0.3,
        degraded_cooldown_s=60.0,  # no half-open probe during this test
    )
    n_slots = server.batcher.max_inflight
    FAULTS.inject("microbatch.dispatch", "hang", times=n_slots,
                  max_hang_s=20)
    st = ServerThread(lambda: create_engine_server_app(server))
    codes: list[int] = []

    def post(q):
        codes.append(requests.post(
            st.url + "/queries.json", json={"q": q}, timeout=30).status_code)

    threads = [threading.Thread(target=post, args=(i,), daemon=True)
               for i in range(n_slots)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(15)
        # every hung batch answered 504 — the watchdog failed them
        # instead of wedging their slots (pre-PR behavior: no answer ever)
        assert codes == [504] * n_slots
        assert server.batcher.watchdog_trips == n_slots
        assert server.degraded

        h = requests.get(st.url + "/health.json", timeout=10)
        assert h.status_code == 200  # degraded still serves -> still ready
        body = h.json()
        assert body["status"] == "degraded"
        assert body["degraded"]["active"] is True
        assert body["degraded"]["watchdogTrips"] == n_slots
        assert body["degraded"]["zombieDispatches"] == n_slots
        # degraded mode shrank the pipeline
        assert body["degraded"]["maxInflight"] == max(1, n_slots // 2)

        # subsequent queries still answer: per-query fallback, no batcher
        batches_before = server.batcher.batches
        r = requests.post(st.url + "/queries.json", json={"q": 5},
                          timeout=10)
        assert r.status_code == 200
        assert r.json()["value"] == 5
        assert server.batcher.batches == batches_before  # bypassed
        assert server.degraded  # cooldown (60 s) far away: still degraded

        # degraded/watchdog counters surface in /stats.json too
        stats = requests.get(st.url + "/stats.json", timeout=10).json()
        assert stats["resilience"]["degraded"] is True
        assert stats["resilience"]["watchdogTrips"] == n_slots

        # graceful drain (the SIGTERM/on_shutdown path): completes even
        # with zombie threads still hung, then the server refuses queries
        asyncio.run_coroutine_threadsafe(
            server.drain(), st._loop).result(15)
        assert server._drained
        h = requests.get(st.url + "/health.json", timeout=10)
        assert h.status_code == 503
        assert h.json()["status"] == "draining"
        assert h.json()["ready"] is False
        r = requests.post(st.url + "/queries.json", json={"q": 6},
                          timeout=10)
        assert r.status_code == 503
    finally:
        FAULTS.clear()  # release the zombie threads
        _poll(lambda: server.batcher.stats()["zombieDispatches"] == 0,
              timeout_s=5)
        st.stop()


@pytest.mark.chaos
def test_degraded_half_open_probe_recovers():
    """After the cooldown, ONE query probes the batched path; success
    exits degraded mode and restores the configured pipeline width."""
    engine, inst = _trained()
    server = EngineServer(
        engine, inst,
        batch_window_ms=0.5, batch_max=1, batch_inflight=2,
        dispatch_timeout_s=0.3, degraded_cooldown_s=0.2,
    )
    FAULTS.inject("microbatch.dispatch", "hang", times=1, max_hang_s=20)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        r = requests.post(st.url + "/queries.json", json={"q": 1},
                          timeout=30)
        assert r.status_code == 504
        assert server.degraded
        assert server.batcher.max_inflight == 1
        time.sleep(0.25)  # past the cooldown: next query is the probe
        r = requests.post(st.url + "/queries.json", json={"q": 2},
                          timeout=10)
        assert r.status_code == 200  # fault budget spent: probe succeeds
        assert not server.degraded
        assert server.batcher.max_inflight == 2  # restored
    finally:
        FAULTS.clear()
        st.stop()


@pytest.mark.chaos
def test_deadline_header_maps_to_504():
    engine, inst = _trained()
    server = EngineServer(engine, inst, batch_window_ms=0.5)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        r = requests.post(st.url + "/queries.json", json={"q": 1},
                          headers={"X-PIO-Deadline-Ms": "0.001"},
                          timeout=10)
        assert r.status_code == 504
        assert "deadline" in r.json()["message"]
        # malformed header falls back to the (unset) server default
        r = requests.post(st.url + "/queries.json", json={"q": 2},
                          headers={"X-PIO-Deadline-Ms": "soon"},
                          timeout=10)
        assert r.status_code == 200
    finally:
        st.stop()


# ---------------------------------------------------------------------------
# feedback loop: one session, tracked tasks, breaker, bounded retries


@pytest.mark.chaos
def test_feedback_uses_one_session_and_threads_prid():
    received: list[dict] = []

    def stub_app():
        from aiohttp import web

        async def events(request):
            received.append(await request.json())
            return web.json_response({"eventId": "e"}, status=201)

        app = web.Application()
        app.router.add_post("/events.json", events)
        return app

    stub = ServerThread(stub_app)
    engine, inst = _trained()
    server = EngineServer(engine, inst, batch_window_ms=0.5,
                          feedback_url=stub.url, access_key="k")
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        r1 = requests.post(st.url + "/queries.json", json={"q": 1},
                           timeout=10)
        assert r1.status_code == 200 and r1.json()["prId"]
        assert _poll(lambda: server.feedback.stats()["sent"] == 1)
        session = server.feedback._session
        assert session is not None
        r2 = requests.post(st.url + "/queries.json", json={"q": 2},
                           timeout=10)
        assert r2.status_code == 200
        assert _poll(lambda: server.feedback.stats()["sent"] == 2)
        assert server.feedback._session is session  # ONE session reused
        assert len(received) == 2
        assert received[0]["prId"] == r1.json()["prId"]
        assert received[0]["properties"]["query"] == {"q": 1}
        # drain closes the session and leaves no tracked task behind
        asyncio.run_coroutine_threadsafe(
            server.drain(), st._loop).result(15)
        fs = server.feedback.stats()
        assert fs["inflightTasks"] == 0
        assert server.feedback._session is None
    finally:
        st.stop()
        stub.stop()


def test_feedback_breaker_opens_then_drops_fast():
    async def main():
        # nothing listens on port 9: every POST fails fast
        pub = FeedbackPublisher("http://127.0.0.1:9", "k",
                                timeout_s=0.5, breaker_threshold=2,
                                retry_max=0, breaker_reset_s=60.0)
        pub.publish({"q": 1}, {"v": 1}, "pr1")
        pub.publish({"q": 2}, {"v": 2}, "pr2")
        for _ in range(200):
            if not pub._tasks:
                break
            await asyncio.sleep(0.02)
        s = pub.stats()
        assert s["failed"] == 2
        assert s["breakerState"] == "open"
        assert s["breakerOpens"] == 1
        dropped_before = s["dropped"]
        pub.publish({"q": 3}, {"v": 3}, "pr3")  # breaker open: no task
        assert pub.stats()["dropped"] == dropped_before + 1
        assert not pub._tasks
        await pub.aclose()

    asyncio.run(main())


def test_feedback_breaker_half_open_cycle():
    pub = FeedbackPublisher("http://x", "k", breaker_threshold=1,
                            breaker_reset_s=0.0)
    pub._on_failure(RuntimeError("boom"))
    assert pub._state == "open"
    # reset elapsed: ONE probe admitted, state half-open
    assert pub._breaker_allows(time.monotonic()) is True
    assert pub._state == "half_open"
    assert pub._breaker_allows(time.monotonic()) is False  # probe in air
    pub._on_failure(RuntimeError("probe failed"))
    assert pub._state == "open"
    assert pub.breaker_opens == 2
    assert pub._breaker_allows(time.monotonic()) is True
    pub._on_success()
    assert pub._state == "closed"
    assert pub._consecutive_failures == 0


def test_feedback_retry_queue_is_bounded():
    async def main():
        pub = FeedbackPublisher("http://127.0.0.1:9", "k",
                                queue_max=4, retry_max=10)
        for i in range(10):
            pub._enqueue_retry({"i": i}, attempt=1)
        assert len(pub._retry) == 4  # oldest 6 dropped, not hoarded
        assert pub.stats()["dropped"] == 6
        # past retry_max the event drops instead of retrying forever
        pub._enqueue_retry({"i": 99}, attempt=11)
        assert pub.stats()["dropped"] == 7
        await pub.aclose()

    asyncio.run(main())


# ---------------------------------------------------------------------------
# event-store write faults exercise the real 500 path


@pytest.mark.chaos
def test_event_server_write_fault_answers_500_then_recovers():
    from predictionio_tpu.api import create_event_app

    meta = Storage.get_metadata()
    app = meta.app_insert("chaosapp")
    ak = meta.access_key_insert(app.id)
    Storage.get_events().init_app(app.id)
    FAULTS.inject("eventserver.insert", "error",
                  exc=StorageError("injected write failure"), times=1)
    st = ServerThread(lambda: create_event_app(stats=True))
    ev = {"event": "rate", "entityType": "user", "entityId": "u0"}
    try:
        r = requests.post(st.url + "/events.json",
                          params={"accessKey": ak.key}, json=ev, timeout=10)
        assert r.status_code == 500
        assert "injected write failure" in r.json()["message"]
        # fault budget spent: the store works again, no restart needed
        r = requests.post(st.url + "/events.json",
                          params={"accessKey": ak.key}, json=ev, timeout=10)
        assert r.status_code == 201
    finally:
        st.stop()
