"""Streaming online learning (ISSUE 10): the journal-tailing fold-in
updater, /reload/delta hot-patching and eval-gated promotion.

Three layers under test, bottom-up:

- ``storage/journal.py JournalFollower`` — the independent read-only
  follow cursor (never the drainer's ``cursor.json``), its restart
  resume, GC clamp and torn-tail hold;
- ``workflow/streaming.py StreamingUpdater`` — tail -> group -> batched
  fold-in -> gate -> publish, with the drainer's exactly-once cursor
  discipline and breaker (chaos via the ``stream.*`` fault sites);
- ``workflow/create_server.py`` ``/reload/delta`` — copy-on-write
  user-factor patching, bounded patch table, reload reconciliation —
  capped by the ISSUE 10 acceptance e2e: a user unseen at train time
  becomes personalized within ONE updater cycle, bitwise-matching the
  host ``fold_in_user`` reference, with the whole event -> patch path
  joinable by one request id.
"""

import json
import logging
import threading
import time
import urllib.error
import zlib

import numpy as np
import pytest
import requests

from predictionio_tpu.obs.metrics import METRICS
from predictionio_tpu.storage.journal import (
    _HEADER,
    EventJournal,
    JournalFollower,
    PartitionedJournal,
)
from predictionio_tpu.faults import FAULTS
from predictionio_tpu.workflow.streaming import StreamingUpdater
from tests.helpers import ServerThread

pytestmark = pytest.mark.streaming


# ---------------------------------------------------------------------------
# harness


def _rec(uid, iid, rating=None, trace=None, event="rate") -> bytes:
    """One WAL payload in the drainer's frame (api/ingest.py encode)."""
    e = {"event": event, "entityType": "user", "entityId": uid,
         "targetEntityType": "item", "targetEntityId": iid,
         "eventTime": "2020-01-01T00:00:00Z"}
    if rating is not None:
        e["properties"] = {"rating": rating}
    d = {"e": e, "a": 1, "c": None}
    if trace:
        d["t"] = trace
    return json.dumps(d, separators=(",", ":")).encode()


def _als(rng, nu=4, ni=40, rank=6, implicit=False):
    from predictionio_tpu.models.als import ALSConfig, ALSModel
    from predictionio_tpu.storage.bimap import BiMap

    return ALSModel(
        user_factors=rng.standard_normal((nu, rank)).astype(np.float32),
        item_factors=rng.standard_normal((ni, rank)).astype(np.float32),
        user_ids=BiMap({f"u{i}": i for i in range(nu)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
        config=ALSConfig(rank=rank, lambda_=0.1, alpha=2.0,
                         implicit_prefs=implicit),
    )


def _eye_model(ni=6, user0_row=None):
    """Orthogonal item factors make the gate's top-k deterministic:
    a factor c*e_j ranks item j first. ``user0_row``: pin u0's serving
    factor (the gate baseline) to a chosen basis vector."""
    from predictionio_tpu.models.als import ALSConfig, ALSModel
    from predictionio_tpu.storage.bimap import BiMap

    item_factors = np.eye(ni, dtype=np.float32)
    uf = np.zeros((1, ni), np.float32)
    if user0_row is not None:
        uf[0] = item_factors[user0_row]
    return ALSModel(
        user_factors=uf,
        item_factors=item_factors,
        user_ids=BiMap({"u0": 0}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
        config=ALSConfig(rank=ni, lambda_=0.1, alpha=2.0,
                         implicit_prefs=False),
    )


class _DeltaSink:
    """A stand-in engine server exposing only POST /reload/delta —
    records every applied patch request (body + trace header) and can
    fail the next N requests with a chosen status."""

    def __init__(self):
        self.requests: list[tuple[dict, str | None]] = []
        self.hits = 0          # every handler invocation, incl. failures
        self.fail_next = 0
        self.fail_status = 503
        self.epoch = 0

        from aiohttp import web

        async def handler(request):
            self.hits += 1
            body = await request.json()
            if self.fail_next > 0:
                self.fail_next -= 1
                return web.json_response({"message": "down"},
                                         status=self.fail_status)
            users = body.get("users", {})
            self.epoch += 1
            self.requests.append(
                (users, request.headers.get("X-PIO-Request-ID")))
            return web.json_response(
                {"message": "Patched", "appliedCount": len(users),
                 "epoch": self.epoch})

        def factory():
            app = web.Application()
            app.router.add_post("/reload/delta", handler)
            return app

        self.server = ServerThread(factory)

    @property
    def url(self):
        return self.server.url

    def users_published(self) -> list[str]:
        return [u for users, _ in self.requests for u in users]

    def stop(self):
        self.server.stop()


def _updater(model, journal_dir, url, **kw):
    """Test-speed knobs: no batch window, instant backoff."""
    kw.setdefault("batch_window_ms", 0.0)
    kw.setdefault("backoff_base_s", 0.0)
    kw.setdefault("backoff_cap_s", 0.01)
    kw.setdefault("publish_timeout_s", 5.0)
    return StreamingUpdater(model, journal_dir, url, **kw)


def _poll(cond, timeout_s=15.0, interval_s=0.02):
    t_end = time.monotonic() + timeout_s
    while time.monotonic() < t_end:
        if cond():
            return True
        time.sleep(interval_s)
    return cond()


# ---------------------------------------------------------------------------
# JournalFollower: the independent read-only cursor


def test_follower_is_independent_of_the_drain_cursor(tmp_path):
    """Draining past records must not move the follower, and committing
    the follower must not move the drain cursor — two consumers, one
    log (the consumer-group analog)."""
    j = EventJournal(tmp_path, fsync="never")
    for i in range(3):
        j.append(_rec(f"u{i}", "i0"))

    # drainer consumes everything first
    payloads, pos = j.peek_batch(10)
    assert len(payloads) == 3
    j.advance(pos)
    assert j.lag == 0

    # the follower still sees all three records
    f = JournalFollower(tmp_path)
    records, fpos = f.poll(0, 10)
    assert len(records) == 3
    assert f.lag(0) == 3
    f.commit(0, fpos)
    assert f.lag(0) == 0

    # follower commit wrote its OWN cursor file, not the drainer's
    assert (tmp_path / "follow-stream.json").exists()
    assert j.lag == 0 and j.drained == 3

    # a differently-named consumer starts from the oldest record
    other = JournalFollower(tmp_path, name="audit")
    records, _ = other.poll(0, 10)
    assert len(records) == 3


def test_follower_infers_partitioned_layout_and_resumes_exactly(tmp_path):
    pj = PartitionedJournal(tmp_path, partitions=3, fsync="never")
    for i in range(4):
        pj.append(_rec(f"a{i}", "i0"), partition=0)
    pj.append(_rec("b0", "i1"), partition=2)

    f = JournalFollower(tmp_path)  # layout.json says 3
    assert f.num_partitions == 3
    records, pos0 = f.poll(0, 2)  # partial batch
    assert [json.loads(r)["e"]["entityId"] for r in records] == ["a0", "a1"]
    f.commit(0, pos0)

    # restart: a fresh follower resumes at the committed position
    f2 = JournalFollower(tmp_path)
    records, pos0b = f2.poll(0, 10)
    assert [json.loads(r)["e"]["entityId"] for r in records] == ["a2", "a3"]
    records, _ = f2.poll(1, 10)
    assert records == []
    records, pos2 = f2.poll(2, 10)
    assert [json.loads(r)["e"]["entityId"] for r in records] == ["b0"]
    # idx in the returned position is cumulative across commits
    assert pos0b[2] == 4 and pos2[2] == 1


def test_follower_clamps_to_oldest_surviving_segment(tmp_path):
    """GC behind the drainer can collect the follower's cursored segment;
    the follower clamps to the oldest surviving record (replay is safe —
    fold-in is idempotent)."""
    j = EventJournal(tmp_path, fsync="never", segment_max_bytes=1)
    for i in range(3):  # 1-byte segments: one record per segment
        j.append(_rec(f"u{i}", "i0"))

    f = JournalFollower(tmp_path)
    records, pos = f.poll(0, 1)
    assert len(records) == 1
    f.commit(0, pos)

    # "GC" collects the cursored segment out from under the follower
    segs = sorted(tmp_path.glob("journal-*.log"))
    assert len(segs) >= 3
    segs[0].unlink()
    segs[1].unlink()

    records, pos = f.poll(0, 10)
    assert [json.loads(r)["e"]["entityId"] for r in records] == ["u2"]
    f.commit(0, pos)
    assert f.lag(0) == 0


def test_follower_holds_position_at_a_torn_frame(tmp_path):
    """A corrupt/partial frame stops the poll AT the frame without
    advancing past it — the writer's recovery (or next flush) resolves
    it; the follower must never skip records."""
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("u0", "i0"))
    j.append(_rec("u1", "i0"))
    seg = next(iter(sorted(tmp_path.glob("journal-*.log"))))
    with open(seg, "ab") as fh:  # frame with a wrong CRC after the tail
        fh.write(_HEADER.pack(4, zlib.crc32(b"good") ^ 0xFF) + b"evil")

    f = JournalFollower(tmp_path)
    records, pos = f.poll(0, 10)
    assert [json.loads(r)["e"]["entityId"] for r in records] == ["u0", "u1"]
    f.commit(0, pos)
    records, pos2 = f.poll(0, 10)
    assert records == [] and pos2 == pos  # held, not skipped


# ---------------------------------------------------------------------------
# StreamingUpdater: tail -> fold -> publish


def test_cycle_publishes_bitwise_foldin_and_commits(tmp_path, rng):
    """The published patch is BITWISE the host ``fold_in_user`` factor
    (after the JSON round trip), tagged with the WAL trace id; the
    cursor commits so the next cycle is a no-op."""
    m = _als(rng)
    pj = PartitionedJournal(tmp_path, partitions=2, fsync="never")
    pj.append(_rec("newu", "i1", 4.0, trace="rid-1"), partition=0)
    pj.append(_rec("newu", "i2", 5.0, trace="rid-1"), partition=0)
    pj.append(_rec("u0", "i7", 3.0, trace="rid-2"), partition=1)

    sink = _DeltaSink()
    try:
        up = _updater(m, tmp_path, sink.url)
        summary = up.run_cycle()
        assert summary["polled"] == 3 and summary["published"] == 2

        assert len(sink.requests) == 2  # one publish per partition
        by_user = {u: (np.asarray(vec, np.float32), trace)
                   for users, trace in sink.requests
                   for u, vec in users.items()}
        ref_new = m.fold_in_user(["i1", "i2"], [4.0, 5.0])
        ref_u0 = m.fold_in_user(["i7"], [3.0])
        assert np.array_equal(by_user["newu"][0], ref_new)
        assert by_user["newu"][1] == "rid-1"
        assert np.array_equal(by_user["u0"][0], ref_u0)
        assert by_user["u0"][1] == "rid-2"

        # counters + metrics
        assert up.users_patched == 2 and up.last_epoch == sink.epoch
        assert METRICS.get("pio_stream_users_patched_total").value() == 2
        assert METRICS.get("pio_stream_gate_decisions_total"
                           ).value("ungated") == 2
        assert METRICS.get("pio_stream_fold_in_seconds"
                           ).snapshot()["count"] == 2
        # the lag gauge samples at poll time (cursor not yet committed)
        assert METRICS.get("pio_stream_tail_lag").value("0") == 2.0

        # committed: replaying the cycle publishes nothing
        hits = sink.hits
        assert up.run_cycle()["published"] == 0
        assert sink.hits == hits
        assert up.stats()["lag"] == {"0": 0, "1": 0}
    finally:
        sink.stop()


def test_cycle_consumes_unfoldable_records_without_publishing(tmp_path, rng):
    """$set traffic, unknown-item events and malformed frames are
    consumed (cursor advances) but never published — and a keep-last
    duplicate collapses to the latest rating."""
    m = _als(rng)
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("u0", "p", event="$set"))       # reserved: skipped
    j.append(b"this is not json")                  # malformed: skipped
    j.append(_rec("ghost", "nosuchitem", 2.0))     # unknown item: dropped

    sink = _DeltaSink()
    try:
        up = _updater(m, tmp_path, sink.url)
        assert up.run_cycle()["published"] == 0
        assert sink.hits == 0
        assert up.events_seen == 3 and up.events_skipped == 2
        assert up.stats()["lag"] == {"0": 0}  # consumed, not wedged

        # keep-last: two ratings for the same (user, item) fold once
        j.append(_rec("newu", "i3", 1.0))
        j.append(_rec("newu", "i3", 5.0))
        assert up.run_cycle()["published"] == 1
        got = np.asarray(sink.requests[0][0]["newu"], np.float32)
        assert np.array_equal(got, m.fold_in_user(["i3"], [5.0]))
    finally:
        sink.stop()


# ---------------------------------------------------------------------------
# eval-gated promotion


def test_gate_skips_regression_and_still_commits(tmp_path):
    """u0's serving factor already ranks the held-out item first; the
    fold-in candidate (from the OTHER item only) misses it — a hit@1
    regression past the gate. The publish is skipped, the decision is
    counted, and the cursor still advances (a deliberate skip must not
    wedge the partition on replay)."""
    m = _eye_model(user0_row=3)  # baseline factor = e3 -> top-1 = i3
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("u0", "i0", 4.0))
    j.append(_rec("u0", "i3", 3.0))  # held out (last known item)

    sink = _DeltaSink()
    try:
        up = _updater(m, tmp_path, sink.url, eval_gate=0.5, eval_k=1)
        summary = up.run_cycle()
        assert summary["gateSkipped"] == 1 and summary["published"] == 0
        assert sink.hits == 0
        assert up.gate_skips == 1
        assert up.last_gate["folded"] == 0.0
        assert up.last_gate["baseline"] == 1.0
        assert METRICS.get("pio_stream_gate_decisions_total"
                           ).value("skip") == 1
        assert up.stats()["lag"] == {"0": 0}  # committed despite the skip
    finally:
        sink.stop()


def test_gate_publishes_improvement_and_unevaluated_batches(tmp_path):
    """An unknown user's baseline is a guaranteed miss, so a fold-in
    that ranks the held-out item publishes; a batch with no >=2-item
    holdout user is 'unevaluated' and publishes too (the gate never
    blocks what it cannot measure)."""
    m = _eye_model()
    # duplicate factor rows: rating i0 also ranks i5 (same vector)
    m.item_factors = np.vstack([m.item_factors[:5],
                                m.item_factors[0][None, :]])
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("fresh", "i0", 4.0))
    j.append(_rec("fresh", "i5", 3.0))  # held; shares i0's factor -> hit

    sink = _DeltaSink()
    try:
        up = _updater(m, tmp_path, sink.url, eval_gate=0.5, eval_k=2)
        assert up.run_cycle()["published"] == 1
        assert up.last_gate["folded"] == 1.0
        assert up.last_gate["baseline"] == 0.0
        assert METRICS.get("pio_stream_gate_decisions_total"
                           ).value("publish") == 1

        # single-event user: nothing to hold out -> unevaluated, published
        j.append(_rec("solo", "i1", 2.0))
        assert up.run_cycle()["published"] == 1
        assert METRICS.get("pio_stream_gate_decisions_total"
                           ).value("unevaluated") == 1
        assert sorted(sink.users_published()) == ["fresh", "solo"]
    finally:
        sink.stop()


# ---------------------------------------------------------------------------
# publish failures: cursor discipline, breaker, fatal classification


def test_transient_publish_holds_cursor_then_replays_once(tmp_path, rng):
    m = _als(rng)
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("newu", "i1", 4.0))

    sink = _DeltaSink()
    sink.fail_next = 1  # one 503, then healthy
    try:
        up = _updater(m, tmp_path, sink.url)
        assert up.run_cycle()["published"] == 0
        assert up.publish_failures == 1 and up.users_patched == 0
        assert up.stats()["lag"] == {"0": 1}  # cursor HELD

        assert up.run_cycle()["published"] == 1  # same batch, replayed
        assert sink.users_published() == ["newu"]  # exactly once
        assert up.stats()["lag"] == {"0": 0}
    finally:
        sink.stop()


def test_publish_breaker_opens_paces_and_recovers(tmp_path, rng):
    m = _als(rng)
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("newu", "i1", 4.0))

    sink = _DeltaSink()
    sink.fail_next = 2
    try:
        up = _updater(m, tmp_path, sink.url,
                      breaker_threshold=2, breaker_reset_s=0.15)
        up.run_cycle()
        up.run_cycle()
        assert up.breaker.state == "open" and up.breaker.opens == 1

        # while open, cycles hold the cursor WITHOUT hitting the server
        hits = sink.hits
        up.run_cycle()
        assert sink.hits == hits and up.stats()["lag"] == {"0": 1}

        time.sleep(0.2)  # past reset: half-open probe succeeds -> closed
        assert up.run_cycle()["published"] == 1
        assert up.breaker.state == "closed"
        assert sink.users_published() == ["newu"]
    finally:
        sink.stop()


def test_fatal_publish_raises_to_the_operator(tmp_path, rng):
    """A 400 means the patch itself is malformed — replaying it forever
    would wedge the partition, so it must raise, not retry."""
    m = _als(rng)
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("newu", "i1", 4.0))

    sink = _DeltaSink()
    sink.fail_next, sink.fail_status = 1, 400
    try:
        up = _updater(m, tmp_path, sink.url)
        with pytest.raises(urllib.error.HTTPError):
            up.run_cycle()
    finally:
        sink.stop()


# ---------------------------------------------------------------------------
# chaos: kill mid-batch, restart, exactly-once (the PR-3 discipline)


def test_chaos_publish_fault_kill_restart_no_double_apply(tmp_path, rng):
    """Batch 1 publishes; batch 2's publish is FAULTED mid-batch and the
    updater dies there. A fresh updater (same follow-cursor name) must
    resume at the exact committed position: batch 2 publishes exactly
    once, batch 1 is never re-published."""
    m = _als(rng)
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("ua", "i1", 4.0))
    j.append(_rec("ua", "i2", 5.0))

    sink = _DeltaSink()
    try:
        up1 = _updater(m, tmp_path, sink.url)
        assert up1.run_cycle()["published"] == 1

        j.append(_rec("ub", "i3", 2.0))
        FAULTS.inject("stream.publish", "error", times=1)
        assert up1.run_cycle()["published"] == 0  # fault -> cursor held
        assert FAULTS.fired("stream.publish") == 1
        assert sink.hits == 1  # the fault fired BEFORE any request
        up1.stop()  # "kill": no cleanup commit happens after this
        del up1

        FAULTS.clear()
        up2 = _updater(m, tmp_path, sink.url)  # restart, fresh follower
        assert up2.run_cycle()["published"] == 1
        # exactly-once across the crash: each user published exactly once
        assert sorted(sink.users_published()) == ["ua", "ub"]
        got = np.asarray(sink.requests[1][0]["ub"], np.float32)
        assert np.array_equal(got, m.fold_in_user(["i3"], [2.0]))
        # exact cursor resume: nothing left behind, nothing re-read
        assert up2.run_cycle()["published"] == 0
        assert up2.stats()["lag"] == {"0": 0}
    finally:
        sink.stop()


def test_run_forever_retries_transient_cycle_faults(tmp_path, rng):
    """The daemon loop treats an injected ``stream.tail`` fault as
    transient (classify_error) and keeps cycling until the batch lands."""
    m = _als(rng)
    j = EventJournal(tmp_path, fsync="never")
    j.append(_rec("newu", "i1", 4.0))

    sink = _DeltaSink()
    FAULTS.inject("stream.tail", "error", times=2)
    try:
        up = _updater(m, tmp_path, sink.url, batch_window_ms=1.0,
                      backoff_base_s=0.001)
        t = threading.Thread(target=up.run_forever, daemon=True)
        t.start()
        assert _poll(lambda: up.users_patched == 1)
        up.stop()
        t.join(timeout=10)
        assert not t.is_alive()
        assert FAULTS.fired("stream.tail") == 2
        assert sink.users_published() == ["newu"]
    finally:
        sink.stop()


# ---------------------------------------------------------------------------
# /reload/delta: copy-on-write patching on the engine server


def _mini_server(model, patch_table_max=100):
    """An EngineServer skeleton carrying just the delta-patch state —
    the full HTTP route is covered by the e2e below."""
    from predictionio_tpu.controller.engine import TrainResult
    from predictionio_tpu.workflow.create_server import Deployed, EngineServer

    srv = object.__new__(EngineServer)
    srv._reload_lock = threading.Lock()
    srv.patch_epoch = 0
    srv.patch_table = {}
    srv.patch_table_max = patch_table_max
    srv.patch_discarded = 0
    dep = object.__new__(Deployed)
    dep.instance = None
    dep.result = TrainResult(models=[model], algorithms=[], serving=None,
                             algorithm_names=["als"])
    srv.deployed = dep
    return srv


def test_apply_delta_copy_on_write_update_and_append(rng):
    m = _als(rng)
    srv = _mini_server(m)
    old_dep, old_uf = srv.deployed, m.user_factors
    vec_known = rng.standard_normal(6).astype(np.float32)
    vec_fresh = rng.standard_normal(6).astype(np.float32)

    out = srv.apply_delta({"u1": vec_known.tolist(),
                           "fresh": vec_fresh.tolist()})
    assert out["appliedCount"] == 2 and out["epoch"] == 1
    assert out["applied"] == ["fresh", "u1"]

    patched = srv.deployed.result.models[0]
    assert np.array_equal(patched.user_factors[1], vec_known)
    row = patched.user_ids.get("fresh")
    assert row == 4  # appended past the trained rows
    assert np.array_equal(patched.user_factors[row], vec_fresh)
    # recommend_products serves the patched user through the normal path
    assert patched.recommend_products("fresh", 3)

    # copy-on-write: the ORIGINAL bundle and arrays are untouched
    assert srv.deployed is not old_dep
    assert m.user_factors is old_uf
    assert not np.array_equal(old_uf[1], vec_known)
    assert m.user_ids.get("fresh") is None  # original map never extended


def test_apply_delta_validates_and_bounds_the_table(rng):
    m = _als(rng)
    srv = _mini_server(m, patch_table_max=2)

    out = srv.apply_delta({
        "u0": [float("nan")] * 6,          # non-finite
        "u1": [[1.0, 2.0]],                # wrong ndim
        "u2": list(range(9)),              # rank mismatch (9 != 6)
        "a": np.arange(6, dtype=float).tolist(),
        "b": np.arange(6, dtype=float).tolist(),
        "c": np.arange(6, dtype=float).tolist(),  # table full (max 2)
    })
    assert sorted(out["dropped"]["invalid"]) == ["u0", "u1"]
    assert out["dropped"]["rankMismatch"] == ["u2"]
    assert out["dropped"]["tableFull"] == ["c"]  # deterministic order
    assert out["applied"] == ["a", "b"]
    assert out["patchedUsers"] == 2

    # users already tracked always re-patch, even at the cap
    out2 = srv.apply_delta({"a": np.ones(6).tolist()})
    assert out2["applied"] == ["a"] and out2["epoch"] == 2


def test_apply_delta_with_nothing_applicable_keeps_the_bundle(rng):
    m = _als(rng)
    srv = _mini_server(m)
    dep = srv.deployed
    out = srv.apply_delta({"u0": ["oops", "not", "numbers"]})
    assert out["appliedCount"] == 0 and out["epoch"] == 0
    assert srv.deployed is dep  # no pointless swap


# ---------------------------------------------------------------------------
# the ISSUE 10 acceptance e2e: unseen user -> personalized in one cycle


def test_e2e_unseen_user_personalized_within_one_cycle(
        tmp_path, rng, caplog):
    """The full loop on real HTTP: quickstart train + deploy, a durable
    event server journaling to a WAL, one StreamingUpdater cycle — and
    the unseen user's recommendations go from fallback-empty to
    personalized, bitwise-matching the host fold-in reference, with the
    whole event -> patch path joinable by one request id; outstanding
    deltas survive a concurrent full /reload."""
    import shutil
    from pathlib import Path

    from predictionio_tpu.api import DurableIngestor, create_event_app
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.tools.cli import main as pio
    from predictionio_tpu.workflow import resolve_engine_factory
    from predictionio_tpu.workflow.create_server import (
        EngineServer,
        create_engine_server_app,
    )
    from tests.test_quickstart_e2e import REPO, make_events_file

    caplog.set_level(logging.INFO, logger="pio.trace")

    # -- train + deploy (the quickstart slice) -----------------------------
    engine_dir = tmp_path / "myrec"
    shutil.copytree(REPO / "templates" / "recommendation", engine_dir)
    variant = json.loads((engine_dir / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "stest"
    (engine_dir / "engine.json").write_text(json.dumps(variant))

    assert pio(["app", "new", "stest"]) == 0
    app = Storage.get_metadata().app_get_by_name("stest")
    events_file = tmp_path / "events.jsonl"
    make_events_file(events_file, rng)
    assert pio(["import", "--appid", str(app.id), "--input",
                str(events_file)]) == 0
    assert pio(["train", "--engine-dir", str(engine_dir)]) == 0
    insts = Storage.get_metadata().engine_instance_get_completed(
        "default", "1", "default")

    engine = resolve_engine_factory("engine:engine_factory",
                                    engine_dir=engine_dir)
    server = EngineServer(engine, insts[0])
    st = ServerThread(lambda: create_engine_server_app(server))

    # -- durable event server over the WAL the updater will tail -----------
    from tests.test_ingest_durability import _DurableServer

    key = Storage.get_metadata().access_key_insert(app.id).key
    wal = tmp_path / "wal"
    es = _DurableServer(DurableIngestor(str(wal), fsync="batch"))
    try:
        # before: the unseen user gets the empty fallback
        r = requests.post(st.url + "/queries.json",
                          json={"user": "fresh1", "num": 4})
        assert r.status_code == 200 and r.json()["itemScores"] == []

        # the user's first events, all under ONE request id
        rid = "e2e-fresh1-rid"
        folded_items = [("i2", 5.0), ("i7", 4.0), ("i11", 3.0)]
        for iid, rating in folded_items:
            r = requests.post(
                f"{es.url}/events.json?accessKey={key}",
                json={"event": "rate", "entityType": "user",
                      "entityId": "fresh1", "targetEntityType": "item",
                      "targetEntityId": iid,
                      "properties": {"rating": rating},
                      "eventTime": "2020-02-01T00:00:00Z"},
                headers={"X-PIO-Request-ID": rid})
            assert r.status_code == 201

        # -- ONE updater cycle folds + publishes ---------------------------
        model = next(mm for mm in server.deployed.result.models
                     if hasattr(mm, "fold_in_users"))
        up = _updater(model, wal, st.url)
        summary = up.run_cycle()
        assert summary["published"] == 1 and up.users_patched == 1

        # after: personalized, non-fallback recommendations
        r = requests.post(st.url + "/queries.json",
                          json={"user": "fresh1", "num": 4})
        scores = r.json()["itemScores"]
        assert len(scores) == 4
        assert scores[0]["score"] > 0

        # bitwise: the serving factor IS the host fold_in_user reference
        ref = model.fold_in_user([i for i, _ in folded_items],
                                 [v for _, v in folded_items])
        srv_model = next(mm for mm in server.deployed.result.models
                         if getattr(mm, "user_ids", None) is not None
                         and mm.user_ids.get("fresh1") is not None)
        row = srv_model.user_ids.get("fresh1")
        assert np.array_equal(srv_model.user_factors[row], ref)
        assert server.patch_epoch == 1

        # health + stats surfaces expose the patch posture
        h = requests.get(st.url + "/health.json").json()
        assert h["model"]["patchEpoch"] == 1
        assert h["model"]["patchedUsers"] == 1
        stats = requests.get(st.url + "/stats.json").json()
        assert stats["patches"]["epoch"] == 1

        # malformed delta bodies are rejected, not applied
        r = requests.post(st.url + "/reload/delta", data=b"{nope")
        assert r.status_code == 400
        r = requests.post(st.url + "/reload/delta", json={"users": "x"})
        assert r.status_code == 400

        # -- the trace join: one grep over the event->patch path -----------
        lines = [json.loads(rec.message) for rec in caplog.records
                 if rec.name == "pio.trace"]
        evts = {ln["evt"] for ln in lines if ln.get("trace") == rid}
        assert {"ingest.ingress", "stream.tail", "stream.fold_in",
                "stream.publish", "serve.delta"} <= evts

        # -- deltas survive a concurrent full /reload ----------------------
        stop = threading.Event()
        failures: list[str] = []

        def hammer():
            while not stop.is_set():
                rr = requests.post(st.url + "/queries.json",
                                   json={"user": "fresh1", "num": 2})
                if rr.status_code != 200 or not rr.json()["itemScores"]:
                    failures.append(f"{rr.status_code}: {rr.text[:100]}")
                    return

        t = threading.Thread(target=hammer, daemon=True)
        t.start()
        try:
            rr = requests.get(st.url + "/reload")
            assert rr.status_code == 200
        finally:
            stop.set()
            t.join(timeout=10)
        assert not failures  # never a torn bundle, never de-personalized

        # reconciliation re-applied the still-unseen user's delta onto
        # the fresh bundle (training never saw fresh1's events)
        r = requests.post(st.url + "/queries.json",
                          json={"user": "fresh1", "num": 4})
        assert len(r.json()["itemScores"]) == 4
        srv_model = next(mm for mm in server.deployed.result.models
                         if getattr(mm, "user_ids", None) is not None
                         and mm.user_ids.get("fresh1") is not None)
        assert np.array_equal(
            srv_model.user_factors[srv_model.user_ids.get("fresh1")], ref)
        assert requests.get(st.url + "/stats.json"
                            ).json()["patches"]["epoch"] == 2
    finally:
        es.kill()
        st.stop()
