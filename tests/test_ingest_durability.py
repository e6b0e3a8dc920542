"""Durable ingestion over real HTTP — the WAL-backed event server
(api/ingest.py + storage/journal.py wired through api/event_server.py).

The contract under test is the one the reference got from HBase's WAL:
a 201 means the event is durably journaled and WILL reach the backend —
through a storage outage, a process kill, and a restart — exactly once
and in order. Deterministic outages come from predictionio_tpu/faults.py
(``eventserver.drain`` / ``journal.append``); the chaos marker's
conftest guard clears armed faults and bounds each test.
"""

import threading
import time

import pytest
import requests

from predictionio_tpu.api import DurableIngestor, create_event_app
from predictionio_tpu.storage import Storage
from predictionio_tpu.storage.events_base import EventQuery
from predictionio_tpu.faults import FAULTS

pytestmark = pytest.mark.ingest

EV = {
    "event": "rate",
    "entityType": "user",
    "entityId": "u0",
    "targetEntityType": "item",
    "targetEntityId": "i0",
    "properties": {"rating": 5},
    "eventTime": "2020-01-01T00:00:00.000Z",
}


def _fast_ingestor(journal_dir, **kw):
    """Small breaker/backoff knobs so outage->recovery cycles fit a test."""
    kw.setdefault("fsync", "batch")
    kw.setdefault("breaker_threshold", 2)
    kw.setdefault("breaker_reset_s", 0.2)
    kw.setdefault("backoff_base_s", 0.02)
    kw.setdefault("backoff_cap_s", 0.1)
    return DurableIngestor(str(journal_dir), **kw)


class _DurableServer:
    """The test_event_server.py server thread, plus an ingestor and a
    ``kill()`` that stops the loop WITHOUT cleanup — a faithful crash
    (no drain, no journal close, no final fsync beyond policy)."""

    def __init__(self, ingestor=None, stats=True):
        import asyncio

        from aiohttp import web

        self._loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self.port = None

        async def _start():
            runner = web.AppRunner(
                create_event_app(stats=stats, ingestor=ingestor))
            await runner.setup()  # runs startup replay before the listener
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            self.port = runner.addresses[0][1]
            self._runner = runner
            self._ready.set()

        def _run():
            asyncio.set_event_loop(self._loop)
            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()
        assert self._ready.wait(15)

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}"

    def stop(self):
        import asyncio

        async def _stop():
            await self._runner.cleanup()
            self._loop.stop()

        asyncio.run_coroutine_threadsafe(_stop(), self._loop)
        self._thread.join(timeout=10)

    def kill(self):
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10)
        assert not self._thread.is_alive()


def _mk_app_key():
    meta = Storage.get_metadata()
    app = meta.app_insert("durapp")
    key = meta.access_key_insert(app.id).key
    Storage.get_events().init_app(app.id)
    return app, key


def _poll(predicate, timeout=30.0, interval=0.05, what="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(interval)
    pytest.fail(f"timed out waiting for {what}")


def test_durable_ack_drain_health_and_stats(tmp_path):
    app, key = _mk_app_key()
    s = _DurableServer(_fast_ingestor(tmp_path / "wal"))
    try:
        for i in range(3):
            r = requests.post(
                f"{s.url}/events.json?accessKey={key}",
                json=dict(EV, entityId=f"d{i}"))
            assert r.status_code == 201 and r.json()["eventId"]

        # acks are journal-acks; the drainer lands them in the backend
        _poll(lambda: len(list(Storage.get_events().find(
            EventQuery(app.id, limit=-1)))) == 3, what="drain to backend")

        h = requests.get(f"{s.url}/health.json").json()  # no auth needed
        assert h["status"] == "ok"
        assert h["journal"]["fsyncPolicy"] == "batch"
        _poll(lambda: requests.get(
            f"{s.url}/health.json").json()["journal"]["lag"] == 0,
            what="lag drop in health")

        st = requests.get(f"{s.url}/stats.json?accessKey={key}").json()
        assert st["statusCount"] == {"201": 3}
        assert st["ingest"]["journal"]["appended"] == 3
        assert st["ingest"]["drain"]["breakerState"] == "closed"
    finally:
        s.stop()


def test_durable_batch_acks_per_row(tmp_path):
    app, key = _mk_app_key()
    s = _DurableServer(_fast_ingestor(tmp_path / "wal"))
    try:
        batch = [dict(EV, entityId=f"b{i}") for i in range(4)]
        batch.insert(2, dict(EV, event="$badreserved"))
        r = requests.post(
            f"{s.url}/batch/events.json?accessKey={key}", json=batch)
        assert r.status_code == 200
        assert [x["status"] for x in r.json()] == [201, 201, 400, 201, 201]
        _poll(lambda: len(list(Storage.get_events().find(
            EventQuery(app.id, limit=-1)))) == 4, what="batch drain")
    finally:
        s.stop()


@pytest.mark.chaos
def test_journal_append_fault_is_a_500(tmp_path):
    _, key = _mk_app_key()
    s = _DurableServer(_fast_ingestor(tmp_path / "wal"))
    try:
        FAULTS.inject("journal.append", "error", times=1)
        r = requests.post(f"{s.url}/events.json?accessKey={key}", json=EV)
        assert r.status_code == 500
        assert "journal" in r.json()["message"]
        # a failing disk is not sticky state: the next append acks
        r = requests.post(f"{s.url}/events.json?accessKey={key}", json=EV)
        assert r.status_code == 201
    finally:
        s.stop()


@pytest.mark.chaos
def test_journal_full_is_503_with_retry_after_and_no_loss(tmp_path):
    """Past the journal cap the server sheds load loudly (503 +
    Retry-After) — and every 201 it DID hand out still lands after the
    outage clears. No silent loss on either side of the cap."""
    app, key = _mk_app_key()
    ing = _fast_ingestor(tmp_path / "wal", max_bytes=2048,
                         segment_max_bytes=256)
    s = _DurableServer(ing)
    try:
        FAULTS.inject("eventserver.drain", "error")  # hard outage
        url = f"{s.url}/events.json?accessKey={key}"
        acked = 0
        saw_503 = None
        for i in range(40):
            r = requests.post(url, json=dict(EV, entityId=f"f{i}"))
            if r.status_code == 201:
                acked += 1
            else:
                saw_503 = r
                break
        assert saw_503 is not None and 0 < acked < 40
        assert saw_503.status_code == 503
        # dynamic backpressure (ISSUE 6): lag-proportional + jittered,
        # never below 75 % of the 1 s base
        assert float(saw_503.headers["Retry-After"]) >= 0.75
        assert "capacity" in saw_503.json()["message"]

        # a batch against a full journal: per-row 503s, header on wrapper
        rb = requests.post(
            f"{s.url}/batch/events.json?accessKey={key}",
            json=[dict(EV, entityId=f"fb{i}") for i in range(3)])
        assert rb.status_code == 200
        assert float(rb.headers["Retry-After"]) >= 0.75
        rows = rb.json()
        acked += sum(1 for x in rows if x["status"] == 201)
        assert {x["status"] for x in rows} <= {201, 503}
        assert 503 in {x["status"] for x in rows}

        FAULTS.clear()  # backend heals
        _poll(lambda: len(list(Storage.get_events().find(
            EventQuery(app.id, limit=-1)))) == acked,
            what="all acked events to land")
        got = list(Storage.get_events().find(EventQuery(app.id, limit=-1)))
        assert len({e.entity_id for e in got}) == acked  # exactly once
    finally:
        s.stop()


@pytest.mark.chaos
def test_outage_kill_restart_heal_exactly_once_in_order(tmp_path):
    """The acceptance scenario: hard storage outage -> 500 events all ack
    201 -> process killed cold -> restart on the same journal -> backend
    heals -> every event lands exactly once, in order, and /health.json
    walks degraded -> ok."""
    app, key = _mk_app_key()
    total, per_batch = 500, 50
    wal = tmp_path / "wal"

    FAULTS.inject("eventserver.drain", "error")  # outage from the start
    s = _DurableServer(_fast_ingestor(wal, max_bytes=64 * 1024 * 1024))
    killed = False
    try:
        sess = requests.Session()
        for b in range(total // per_batch):
            batch = [
                dict(EV, entityId=f"n{b * per_batch + j:04d}",
                     eventTime=(f"2020-01-01T00:"
                                f"{(b * per_batch + j) // 60:02d}:"
                                f"{(b * per_batch + j) % 60:02d}Z"))
                for j in range(per_batch)
            ]
            r = sess.post(f"{s.url}/batch/events.json?accessKey={key}",
                          json=batch, timeout=30)
            assert r.status_code == 200
            assert all(x["status"] == 201 for x in r.json()), r.text[:300]

        # the backend saw NOTHING, yet the breaker says so out loud
        assert list(Storage.get_events().find(EventQuery(app.id))) == []
        _poll(lambda: requests.get(
            f"{s.url}/health.json").json()["status"] == "degraded",
            what="degraded health during outage")

        s.kill()  # cold crash: no drain, no graceful close
        killed = True
    finally:
        if not killed:
            s.stop()

    # restart on the same journal; the outage is still on, so startup
    # replay defers — the server must come up and keep acking anyway
    s2 = _DurableServer(_fast_ingestor(wal, max_bytes=64 * 1024 * 1024))
    try:
        _poll(lambda: requests.get(
            f"{s2.url}/health.json").json()["status"] == "degraded",
            what="degraded health after restart")
        assert requests.get(
            f"{s2.url}/health.json").json()["journal"]["lag"] == total

        FAULTS.clear()  # storage recovers

        def _recovered():
            h = requests.get(f"{s2.url}/health.json").json()
            return h["status"] == "ok" and h["journal"]["lag"] == 0

        _poll(_recovered, timeout=60, what="recovery to ok with zero lag")

        got = list(Storage.get_events().find(EventQuery(app.id, limit=-1)))
        assert len(got) == total
        ids = [e.entity_id for e in got]
        assert len(set(ids)) == total            # exactly once
        assert ids == [f"n{i:04d}" for i in range(total)]  # in order
        st = requests.get(f"{s2.url}/stats.json?accessKey={key}").json()
        assert st["ingest"]["drain"]["breakerState"] == "closed"
        assert st["ingest"]["drain"]["breakerOpens"] >= 1
    finally:
        s2.stop()


@pytest.mark.chaos
def test_kill_mid_append_truncates_torn_tail(tmp_path):
    """A crash mid-frame leaves a torn tail; the restarted journal keeps
    the longest valid prefix and replays exactly the acked events."""
    app, key = _mk_app_key()
    wal = tmp_path / "wal"
    FAULTS.inject("eventserver.drain", "error")
    s = _DurableServer(_fast_ingestor(wal))
    try:
        for i in range(5):
            assert requests.post(
                f"{s.url}/events.json?accessKey={key}",
                json=dict(EV, entityId=f"t{i}")).status_code == 201
        s.kill()
    except BaseException:
        s.stop()
        raise
    # simulate the torn in-flight frame the kill interrupted
    seg = sorted(wal.glob("journal-*.log"))[-1]
    with open(seg, "ab") as fh:
        fh.write(b"\x80\x00\x00\x00\x99\x99halfwritten")

    FAULTS.clear()
    s2 = _DurableServer(_fast_ingestor(wal))
    try:
        _poll(lambda: len(list(Storage.get_events().find(
            EventQuery(app.id, limit=-1)))) == 5, what="replay of 5 acks")
        got = list(Storage.get_events().find(EventQuery(app.id, limit=-1)))
        assert {e.entity_id for e in got} == {f"t{i}" for i in range(5)}
        h = requests.get(f"{s2.url}/health.json").json()
        assert h["status"] == "ok" and h["journal"]["lag"] == 0
    finally:
        s2.stop()


# ---------------------------------------------------------------------------
# Partitioned ingest (ISSUE 9): N journals, N drainers, per-entity order


def _entities_by_partition(n, per, entity_type="user"):
    """Deterministic entity ids grouped by their journal partition."""
    from predictionio_tpu.storage.partition import shard_of

    out = {k: [] for k in range(n)}
    i = 0
    while any(len(v) < per for v in out.values()):
        eid = f"e{i:04d}"
        k = shard_of(entity_type, eid, n)
        if len(out[k]) < per:
            out[k].append(eid)
        i += 1
    return out


@pytest.mark.chaos
def test_partitioned_outage_kill_restart_heal_exactly_once(tmp_path):
    """The PR-3 acceptance scenario, per partition: a full outage wedges
    all 8 drainers, then exactly 3 drain batches are let through — 3 of
    8 partition cursors advance — and the process is killed cold
    mid-drain. After restart + heal every event lands exactly once and
    in per-entity order (the partitioned ordering contract)."""
    app, key = _mk_app_key()
    n_entities, per_entity = 40, 5
    total = n_entities * per_entity
    wal = tmp_path / "wal"

    FAULTS.inject("eventserver.drain", "error")  # outage from the start
    s = _DurableServer(_fast_ingestor(wal, partitions=8, drain_batch=128))
    killed = False
    try:
        sess = requests.Session()
        evs = [
            dict(EV, entityId=f"g{e:02d}",
                 properties={"seq": q},
                 eventTime=f"2020-01-01T00:{q:02d}:{e % 60:02d}Z")
            for q in range(per_entity) for e in range(n_entities)
        ]
        for b in range(0, total, 50):
            r = sess.post(f"{s.url}/batch/events.json?accessKey={key}",
                          json=evs[b:b + 50], timeout=30)
            assert r.status_code == 200
            assert all(x["status"] == 201 for x in r.json()), r.text[:300]

        assert list(Storage.get_events().find(EventQuery(app.id))) == []
        _poll(lambda: requests.get(
            f"{s.url}/health.json").json()["status"] == "degraded",
            what="degraded health during outage")
        h = requests.get(f"{s.url}/health.json").json()
        assert h["journal"]["lag"] == total
        assert len(h["partitions"]) == 8
        assert all(p["lag"] > 0 for p in h["partitions"])

        # let exactly 3 drain batches through (drain_batch=128 >= any
        # partition's lag, so one batch fully drains one partition),
        # then the outage resumes: 3 of 8 cursors advanced, 5 pending
        FAULTS.inject("eventserver.drain", "error", after=3)

        def _three_drained():
            st = requests.get(
                f"{s.url}/stats.json?accessKey={key}").json()["ingest"]
            return st["drain"]["drainedBatches"] == 3
        _poll(_three_drained, what="exactly 3 partition batches to drain")

        h = requests.get(f"{s.url}/health.json").json()
        drained_parts = [p for p in h["partitions"] if p["lag"] == 0]
        assert len(drained_parts) == 3
        assert 0 < h["journal"]["lag"] < total

        s.kill()  # cold crash mid-drain
        killed = True
    finally:
        if not killed:
            s.stop()

    FAULTS.clear()  # storage recovers before the restart
    s2 = _DurableServer(_fast_ingestor(wal, partitions=8, drain_batch=128))
    try:
        def _recovered():
            h = requests.get(f"{s2.url}/health.json").json()
            return h["status"] == "ok" and h["journal"]["lag"] == 0
        _poll(_recovered, timeout=60, what="recovery to ok with zero lag")

        got = list(Storage.get_events().find(EventQuery(app.id, limit=-1)))
        assert len(got) == total  # exactly once, nothing lost
        by_entity = {}
        for e in got:
            by_entity.setdefault(e.entity_id, []).append(e)
        assert len(by_entity) == n_entities
        for eid, entity_events in by_entity.items():
            seqs = [e.properties["seq"] for e in sorted(
                entity_events, key=lambda e: e.event_time)]
            assert seqs == list(range(per_entity)), (eid, seqs)
    finally:
        s2.stop()


@pytest.mark.chaos
def test_poison_partition_browns_out_alone(tmp_path):
    """One wedged partition must not stall the other N-1: its breaker
    opens and /health.json degrades, but sibling partitions keep
    draining to the backend the whole time."""
    ents = _entities_by_partition(4, 3)
    poison = 2
    app, key = _mk_app_key()
    FAULTS.inject(f"eventserver.drain_partition.p{poison}", "error")
    s = _DurableServer(_fast_ingestor(tmp_path / "wal", partitions=4))
    try:
        for k in range(4):
            for eid in ents[k]:
                assert requests.post(
                    f"{s.url}/events.json?accessKey={key}",
                    json=dict(EV, entityId=eid)).status_code == 201

        healthy_ids = {eid for k, v in ents.items() if k != poison
                       for eid in v}
        _poll(lambda: {e.entity_id for e in Storage.get_events().find(
            EventQuery(app.id, limit=-1))} == healthy_ids,
            what="healthy partitions to drain around the poison one")

        def _poison_open():
            h = requests.get(f"{s.url}/health.json").json()
            return (h["status"] == "degraded"
                    and h["partitions"][poison]["breakerState"] == "open")
        _poll(_poison_open, what="poison partition breaker to open")
        h = requests.get(f"{s.url}/health.json").json()
        assert h["partitions"][poison]["lag"] == 3
        for k in range(4):
            if k != poison:
                assert h["partitions"][k]["breakerState"] == "closed"
                assert h["partitions"][k]["lag"] == 0

        st = requests.get(
            f"{s.url}/stats.json?accessKey={key}").json()["ingest"]
        per = st["drain"]["partitions"]
        assert per[poison]["breakerState"] == "open"
        assert per[poison]["breakerOpens"] >= 1
        assert st["drain"]["breakerState"] == "open"  # aggregate = worst
        assert {d["partition"] for d in st["journal"]["perPartition"]} \
            == set(range(4))

        # per-partition observability rides the metrics registry too
        from predictionio_tpu.obs.metrics import METRICS

        text = METRICS.render_prometheus()
        assert f'pio_journal_partition_lag{{partition="{poison}"}} 3' in text
        assert 'pio_ingest_drain_failures_total{partition="%d"}' % poison \
            in text

        FAULTS.clear()  # the poison clears; the partition heals alone

        def _healed():
            h = requests.get(f"{s.url}/health.json").json()
            return h["status"] == "ok" and h["journal"]["lag"] == 0
        _poll(_healed, what="poison partition to heal")
        got = {e.entity_id for e in Storage.get_events().find(
            EventQuery(app.id, limit=-1))}
        assert got == {eid for v in ents.values() for eid in v}
    finally:
        s.stop()


def test_batch_full_partition_503s_only_its_events(tmp_path):
    """A batch spanning partitions where ONE is at capacity: that
    partition's events answer 503 (+Retry-After on the wrapper), the
    siblings' events still ack 201 — per-partition backpressure at the
    HTTP surface."""
    import asyncio

    ents = _entities_by_partition(2, 1)
    hot, cold = ents[0][0], ents[1][0]
    app, key = _mk_app_key()
    # tiny cap: each partition takes ~2 small events, then JournalFull
    ing = _fast_ingestor(tmp_path / "wal", partitions=2, max_bytes=1200,
                         fsync="never")
    FAULTS.inject("eventserver.drain", "error")  # keep records queued
    s = _DurableServer(ing)
    try:
        # fill the hot partition via singles until it 503s
        saw_503 = False
        for i in range(40):
            r = requests.post(f"{s.url}/events.json?accessKey={key}",
                              json=dict(EV, entityId=hot))
            if r.status_code == 503:
                saw_503 = True
                break
        assert saw_503
        # mixed batch: hot-partition events 503, cold-partition event 201
        rb = requests.post(
            f"{s.url}/batch/events.json?accessKey={key}",
            json=[dict(EV, entityId=hot), dict(EV, entityId=cold),
                  dict(EV, entityId=hot)])
        assert rb.status_code == 200
        assert float(rb.headers["Retry-After"]) > 0
        assert [x["status"] for x in rb.json()] == [503, 201, 503]
    finally:
        FAULTS.clear()
        s.stop()
