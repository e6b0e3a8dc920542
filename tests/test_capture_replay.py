"""Provenance + capture/replay (ISSUE 13): the golden-traffic harness.

Layers under test, bottom-up:

- ``obs/capture.py CaptureRing`` — hot-path recording, ring flush to the
  CRC-framed capture journal, drop-oldest disk bound, offline iteration;
- ``obs/replay.py`` — the three-tier differ (bitwise / topk_set /
  score_tol), the provenance field differ, and the replay report;
- ``obs/flight.py`` incident listeners — an incident flushes the ring
  so the requests that LED INTO it are on disk;
- satellite 1 — every app (engine incl. /reload/delta, /debug/*,
  /metrics; event server; dashboard; admin) stamps X-PIO-Request-ID on
  every response;
- the ISSUE 13 acceptance e2e — capture >= 200 live requests across the
  exact, brownout-clamped and ANN full-cover-delegate paths, replay
  against the same model -> 100% bitwise parity; apply a streaming
  delta patch and replay again -> the diff names exactly the patched
  users, keyed by a provenance delta whose patchEpoch moved.
"""

import json
import shutil
import time

import numpy as np
import pytest
import requests

from predictionio_tpu.obs.capture import CaptureRing, iter_capture
from predictionio_tpu.obs.flight import FlightRecorder
from predictionio_tpu.obs.metrics import METRICS
from predictionio_tpu.obs.replay import (
    PROVENANCE_HEADER,
    ShadowMirror,
    diff_tier,
    replay_records,
)
from predictionio_tpu.obs.trace import TRACE_HEADER
from tests.helpers import ServerThread

pytestmark = pytest.mark.replay


# ---------------------------------------------------------------------------
# capture ring (unit)


def _rec_args(i: int, user: str = "u0") -> dict:
    return {"rid": f"r{i}", "request": {"user": user, "num": 3},
            "response": {"itemScores": [{"item": "i1", "score": 1.0 + i}]},
            "status": 200, "latency_ms": 1.25,
            "provenance": {"patchEpoch": 0}}


def test_capture_ring_persists_and_iterates(tmp_path):
    cap = CaptureRing(str(tmp_path / "cap"), ring_capacity=4)
    for i in range(10):  # 4-record ring: flushes ride record()
        cap.record(**_rec_args(i))
    cap.close()  # final flush picks up the partial ring

    got = list(iter_capture(tmp_path / "cap"))
    assert [r["rid"] for r in got] == [f"r{i}" for i in range(10)]
    assert got[0]["request"] == {"user": "u0", "num": 3}
    assert got[0]["provenance"] == {"patchEpoch": 0}
    assert got[3]["response"]["itemScores"][0]["score"] == 4.0
    assert METRICS.get("pio_capture_records_total").value("captured") == 10
    assert METRICS.get("pio_capture_flushes_total").value("ring_full") >= 2
    # close() is idempotent and records after close are ignored
    cap.close()
    cap.record(**_rec_args(99))
    assert len(list(iter_capture(tmp_path / "cap"))) == 10


def test_capture_sampling_and_stop_flush(tmp_path):
    cap = CaptureRing(str(tmp_path / "cap"), sample=0.0, ring_capacity=64)
    cap.record(**_rec_args(0))
    assert cap.sampled_out == 1 and cap.captured == 0
    cap.start()
    cap.sample = 1.0
    cap.record(**_rec_args(1))
    cap.stop()  # must flush the partial ring to disk
    assert cap.enabled is False
    assert [r["rid"] for r in iter_capture(tmp_path / "cap")] == ["r1"]
    # disabled: recording is a no-op until start()
    cap.record(**_rec_args(2))
    assert cap.captured == 1
    st = cap.stats()
    assert st["journalRecords"] == 1 and st["sampledOut"] == 1
    cap.close()


def test_capture_disk_ring_drops_oldest(tmp_path):
    """Past max_bytes the OLDEST captured segments are released — the
    journal bounds disk without ever refusing new golden traffic."""
    cap = CaptureRing(str(tmp_path / "cap"), ring_capacity=1,
                      max_bytes=16 * 1024, segment_max_bytes=1024)
    for i in range(200):  # ~200 * ~150B >> 16 KiB
        cap.record(**_rec_args(i))
    cap.close()
    got = [r["rid"] for r in iter_capture(tmp_path / "cap")]
    assert got, "everything was dropped"
    assert got[-1] == "r199", "newest records must survive"
    assert got[0] != "r0", "oldest records must have been released"
    assert got == [f"r{i}" for i in range(200 - len(got), 200)]
    assert cap.stats()["journalBytes"] <= 16 * 1024


def test_incident_listener_flushes_capture(tmp_path):
    """The EngineServer wiring contract: a flight-recorder incident
    flushes the capture ring, so the requests that led into the
    incident are on disk even mid-ring; listener exceptions and the
    dump-failure path (path=None) must not break the recorder."""
    cap = CaptureRing(str(tmp_path / "cap"), ring_capacity=1024)
    fr = FlightRecorder(capacity=8, dump_dir=str(tmp_path / "flight"),
                        cooldown_s=0.0)
    seen = []
    fr.add_incident_listener(lambda reason, path: 1 / 0)  # swallowed
    fr.add_incident_listener(
        lambda reason, path: seen.append((reason, cap.flush("incident"))))
    cap.record(**_rec_args(0))
    path = fr.incident("test_reason")
    assert path is not None
    assert seen == [("test_reason", 1)]
    assert [r["rid"] for r in iter_capture(tmp_path / "cap")] == ["r0"]
    assert METRICS.get("pio_capture_flushes_total").value("incident") == 1
    # reset() clears listeners (test isolation for the server wiring)
    fr.reset()
    fr.incident("test_reason", force=True)
    assert len(seen) == 1
    cap.close()


# ---------------------------------------------------------------------------
# the differ (unit)


def _scores(*pairs):
    return {"itemScores": [{"item": i, "score": s} for i, s in pairs]}


def test_diff_tiers():
    a = _scores(("i1", 2.0), ("i2", 1.0))
    assert diff_tier(a, _scores(("i1", 2.0), ("i2", 1.0))) == "bitwise"
    # same set, different order/scores -> topk_set
    assert diff_tier(a, _scores(("i2", 2.0), ("i1", 1.0))) == "topk_set"
    # same score ladder within tolerance, different items -> score_tol
    assert diff_tier(a, _scores(("i9", 2.0 + 1e-9), ("i8", 1.0))) == "score_tol"
    assert diff_tier(a, _scores(("i9", 5.0), ("i8", 1.0))) == "mismatch"
    assert diff_tier(a, _scores(("i1", 2.0))) == "mismatch"
    # non-ranking payloads fall back to whole-payload equality
    assert diff_tier({"x": 1}, {"x": 1}) == "bitwise"
    assert diff_tier({"x": 1}, {"x": 2}) == "mismatch"
    # a decorated-but-equal ranking (extra field) is still bitwise
    assert diff_tier({**a, "note": 1}, {**a, "note": 2}) == "bitwise"


def test_replay_report_shape_and_skips():
    class _Stub:
        def serve_query(self, q):
            if q["user"] == "boom":
                raise RuntimeError("dead user")
            return _scores(("i1", 2.0), ("i2", 1.0))

        def provenance(self):
            return {"patchEpoch": 3, "mode": "normal"}

    records = [
        {"rid": "a", "request": {"user": "u"}, "status": 200,
         "response": _scores(("i1", 2.0), ("i2", 1.0)),
         "latencyMs": 1.0, "provenance": {"patchEpoch": 0, "mode": "normal"}},
        # prId decoration (feedback path) must not break bitwise
        {"rid": "b", "request": {"user": "u"}, "status": 200,
         "response": {**_scores(("i1", 2.0), ("i2", 1.0)), "prId": "x"}},
        {"rid": "c", "request": {"user": "u"}, "status": 200,
         "response": _scores(("i9", 9.0))},
        {"rid": "d", "request": {"user": "boom"}, "status": 200,
         "response": _scores(("i1", 2.0))},
        {"rid": "shed", "request": {"user": "u"}, "status": 429,
         "response": {"message": "overloaded"}},          # skipped
        {"rid": "torn", "status": 200, "response": {}},   # no request
    ]
    rep = replay_records(records, server=_Stub())
    assert rep["total"] == 4 and rep["skipped"] == 2
    assert rep["tiers"]["bitwise"] == 2
    assert rep["tiers"]["mismatch"] == 1 and rep["tiers"]["error"] == 1
    assert rep["parityPct"] == 50.0
    assert rep["latencyMs"]["captured"] == 1.0
    assert rep["provenance"]["delta"]["patchEpoch"] == {
        "captured": 0, "replayed": 3}
    by_rid = {m["rid"]: m for m in rep["mismatches"]}
    assert set(by_rid) == {"c", "d"}
    assert by_rid["d"]["tier"] == "error"
    with pytest.raises(ValueError):
        replay_records(records)  # neither target nor server
    with pytest.raises(ValueError):
        replay_records(records, target="http://x", server=_Stub())


def test_replay_groups_parity_by_capture_variant():
    """One capture of A/B traffic yields per-variant parity blocks: the
    report's ``variants`` section groups tiers by the variantId stamped
    in each record's provenance at capture time (absent → default)."""
    class _Stub:
        def serve_query(self, q):
            return _scores(("i1", 2.0))

        def provenance(self):
            return {"mode": "normal"}

    def _rec(rid, vid, response):
        prov = {"variantId": vid} if vid else {}
        return {"rid": rid, "request": {"user": rid}, "status": 200,
                "response": response, "provenance": prov}

    records = [
        _rec("a1", "a", _scores(("i1", 2.0))),      # bitwise
        _rec("a2", "a", _scores(("i9", 9.0))),      # mismatch
        _rec("b1", "b", _scores(("i1", 2.0))),      # bitwise
        _rec("d1", None, _scores(("i1", 2.0))),     # no variantId stamped
    ]
    rep = replay_records(records, server=_Stub())
    assert set(rep["variants"]) == {"a", "b", "default"}
    va, vb = rep["variants"]["a"], rep["variants"]["b"]
    assert va["total"] == 2 and va["tiers"]["bitwise"] == 1 \
        and va["tiers"]["mismatch"] == 1 and va["parityPct"] == 50.0
    assert vb["total"] == 1 and vb["parityPct"] == 100.0
    assert rep["variants"]["default"]["parityPct"] == 100.0
    # grouped counts must reconcile with the flat tier totals
    assert sum(v["total"] for v in rep["variants"].values()) == rep["total"]


# ---------------------------------------------------------------------------
# satellite 1: X-PIO-Request-ID on every response from every app


def test_trace_header_on_every_surface(tmp_path):
    from predictionio_tpu.api import create_event_app
    from predictionio_tpu.tools.admin import create_admin_app
    from predictionio_tpu.tools.dashboard import create_dashboard_app
    from predictionio_tpu.workflow.create_server import (
        EngineServer,
        create_engine_server_app,
    )
    from tests.test_resilience import _trained

    engine, inst = _trained()
    server = EngineServer(engine, inst)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        # the named engine-app gaps: /reload/delta, /debug/*, /metrics —
        # plus aiohttp-raised 404s (middleware, not handler, stamps them)
        for method, path, status in (
                ("post", "/reload/delta", 400),           # malformed body
                ("get", "/debug/flight.json", 200),
                ("get", "/metrics", 200),
                ("post", "/queries.json", 200),
                ("get", "/no/such/route", 404)):
            r = getattr(requests, method)(
                st.url + path,
                **({"json": {"q": 1}} if method == "post" else {}))
            assert r.status_code == status, (path, r.status_code)
            assert r.headers.get(TRACE_HEADER), f"{path} missing trace id"
        # echo: a client-supplied id comes back verbatim
        r = requests.post(st.url + "/queries.json", json={"q": 1},
                          headers={TRACE_HEADER: "pinned-rid"})
        assert r.headers[TRACE_HEADER] == "pinned-rid"
        # provenance rides every serving response (tentpole 1)
        prov = json.loads(r.headers[PROVENANCE_HEADER])
        assert prov["engineInstanceId"] == inst.id
        assert prov["mode"] == "normal" and prov["patchEpoch"] == 0
    finally:
        st.stop()

    for factory, probe, expect in (
            (create_event_app, "/", 200),
            (create_event_app, "/nope", 404),
            (create_dashboard_app, "/", 200),
            (create_dashboard_app, "/nope", 404),
            (create_admin_app, "/", 200),
            (create_admin_app, "/nope", 404)):
        app_st = ServerThread(factory)
        try:
            r = requests.get(app_st.url + probe)
            assert r.status_code == expect, (factory.__name__, probe)
            assert r.headers.get(TRACE_HEADER), \
                f"{factory.__name__} {probe} missing trace id"
        finally:
            app_st.stop()


# ---------------------------------------------------------------------------
# the acceptance e2e


def _train_quickstart(tmp_path, rng, app_name: str):
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.tools.cli import main as pio
    from predictionio_tpu.workflow import resolve_engine_factory
    from tests.test_quickstart_e2e import REPO, make_events_file

    engine_dir = tmp_path / "myrec"
    shutil.copytree(REPO / "templates" / "recommendation", engine_dir)
    variant = json.loads((engine_dir / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = app_name
    (engine_dir / "engine.json").write_text(json.dumps(variant))
    assert pio(["app", "new", app_name]) == 0
    app = Storage.get_metadata().app_get_by_name(app_name)
    events_file = tmp_path / "events.jsonl"
    make_events_file(events_file, rng)
    assert pio(["import", "--appid", str(app.id),
                "--input", str(events_file)]) == 0
    assert pio(["train", "--engine-dir", str(engine_dir)]) == 0
    insts = Storage.get_metadata().engine_instance_get_completed(
        "default", "1", "default")
    engine = resolve_engine_factory("engine:engine_factory",
                                    engine_dir=engine_dir)
    return engine, insts[0]


def test_e2e_capture_replay_parity_then_delta_diff(tmp_path, rng):
    """ISSUE 13 acceptance: >= 200 captured live requests (exact and
    brownout-clamped paths) replay against the same instance at 100%
    bitwise parity; after a streaming delta patch the replay diff names
    exactly the patched users, keyed by the patchEpoch provenance
    delta."""
    from predictionio_tpu.workflow.create_server import (
        EngineServer,
        create_engine_server_app,
    )

    engine, inst = _train_quickstart(tmp_path, rng, "captest")
    cap_dir = tmp_path / "capture"
    server = EngineServer(engine, inst, capture_dir=str(cap_dir),
                          capture_sample=1.0, brownout_topk=2)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        users = [f"u{i}" for i in range(10)] + ["nobody"]
        n_sent = 0
        for round_i in range(20):
            for u in users:
                r = requests.post(st.url + "/queries.json",
                                  json={"user": u, "num": 4})
                assert r.status_code == 200
                n_sent += 1
        assert n_sent >= 200
        # a brownout stretch: capture must store the CLAMPED query so
        # replay of these records is deterministic under normal mode
        server._set_mode("brownout")
        for u in ("u0", "u1"):
            r = requests.post(st.url + "/queries.json",
                              json={"user": u, "num": 8})
            assert len(r.json()["itemScores"]) == 2  # brownout_topk
            n_sent += 1
        server._set_mode("normal")

        # stop + flush over the wire (the pio capture stop path)
        r = requests.post(st.url + "/capture/stop")
        assert r.status_code == 200
        assert r.json()["capture"]["enabled"] is False

        records = list(iter_capture(cap_dir))
        assert len(records) == n_sent
        clamped = [rec for rec in records if rec["request"].get("num") == 2]
        assert len(clamped) == 2  # effective (post-clamp) query captured
        prov = records[0]["provenance"]
        assert prov["engineInstanceId"] == inst.id
        assert str(prov["modelBlobSha256"]).startswith("sha256:")
        # ISSUE 16: the pipelined default serves the compiled exact
        # retriever on every backend, so the mode is "exact", not "host"
        assert prov["retrieval"]["mode"] == "exact"

        # -- replay against the SAME live instance: total parity -------
        report = replay_records(records, target=st.url)
        assert report["total"] == n_sent and report["skipped"] == 0
        assert report["tiers"]["bitwise"] == n_sent
        assert report["parityPct"] == 100.0
        assert report["mismatches"] == []
        assert report["provenance"]["delta"] == {}

        # -- streaming delta patch, then replay names exactly it -------
        model = server.deployed.result.models[0]
        rank = int(np.asarray(model.user_factors).shape[1])
        patched = {"u1": (10.0 * np.ones(rank)).tolist(),
                   "u7": (-10.0 * np.ones(rank)).tolist()}
        r = requests.post(st.url + "/reload/delta",
                          json={"users": patched})
        assert r.status_code == 200 and r.json()["appliedCount"] == 2

        report2 = replay_records(records, target=st.url)
        assert report2["tiers"]["bitwise"] == n_sent - len(
            [rec for rec in records if rec["request"]["user"] in patched])
        mismatched_users = {m["request"]["user"]
                            for m in report2["mismatches"]}
        assert mismatched_users == set(patched)
        epoch_delta = report2["provenance"]["delta"]["patchEpoch"]
        assert epoch_delta == {"captured": 0, "replayed": 1}
        for m in report2["mismatches"]:
            assert m["provenanceDelta"]["patchEpoch"]["replayed"] == 1

        # /stats.json exposes the unified provenance block (tentpole 1)
        stats = requests.get(st.url + "/stats.json").json()
        assert stats["provenance"]["engineInstanceId"] == inst.id
        assert stats["provenance"]["patchEpoch"] == 1
        assert stats["provenance"]["modelBlobSha256"] == prov["modelBlobSha256"]
        assert stats["capture"]["enabled"] is False
        assert stats["capture"]["journalRecords"] == n_sent
    finally:
        st.stop()


def test_replay_in_process_ann_full_cover_delegate(tmp_path, rng):
    """The ANN path's determinism pin: with nprobe >= n_cells the index
    delegates to exact scoring, so live ANN capture replays bitwise
    against a fresh in-process rehydration of the same instance (the
    `pio replay --engine-instance-id` path, no HTTP)."""
    from predictionio_tpu.workflow.create_server import (
        EngineServer,
        create_engine_server_app,
    )

    engine, inst = _train_quickstart(tmp_path, rng, "anntest")
    retrieval = {"mode": "ann", "min_items": 0, "n_cells": 4, "nprobe": 99}
    cap_dir = tmp_path / "capture"
    live = EngineServer(engine, inst, capture_dir=str(cap_dir),
                        capture_sample=1.0, retrieval=retrieval)
    st = ServerThread(lambda: create_engine_server_app(live))
    try:
        for i in range(12):
            r = requests.post(st.url + "/queries.json",
                              json={"user": f"u{i % 6}", "num": 3})
            assert r.status_code == 200
            prov = json.loads(r.headers[PROVENANCE_HEADER])
            assert prov["retrieval"]["mode"] == "ann"
        requests.post(st.url + "/capture/stop")
    finally:
        st.stop()

    records = list(iter_capture(cap_dir))
    assert len(records) == 12
    fresh = EngineServer(engine, inst, batch_window_ms=0,
                         retrieval=retrieval)
    report = replay_records(records, server=fresh)
    assert report["tiers"]["bitwise"] == 12
    assert report["parityPct"] == 100.0
    # the in-process issuer reports its own provenance: same blob, same
    # epoch -> empty delta even across two server constructions
    assert report["provenance"]["delta"] == {}


# ---------------------------------------------------------------------------
# ISSUE 16 parity gate: retriever-only capture -> pipeline replay, bitwise


def _capture_retriever_only(tmp_path, engine, inst, retrieval, *, name: str,
                            delta: dict | None = None):
    """Capture B=1 golden traffic on a server whose models serve through
    their retriever alone (the pipeline detached: the state
    ``batch_recommend`` handles for a model without one); when ``delta``
    is given, patch mid-stream so the tail of the capture carries
    patchEpoch 1 (the delta-patched variant capture)."""
    from predictionio_tpu.workflow.create_server import (
        EngineServer,
        create_engine_server_app,
    )

    cap_dir = tmp_path / name
    plain = EngineServer(engine, inst, capture_dir=str(cap_dir),
                         capture_sample=1.0, retrieval=retrieval)
    for model in plain.deployed.result.models:
        assert model._retriever is not None
        model._pipeline = None
    st = ServerThread(lambda: create_engine_server_app(plain))
    try:
        users = [f"u{i}" for i in range(8)] + ["nobody"]
        for u in users:
            r = requests.post(st.url + "/queries.json",
                              json={"user": u, "num": 4})
            assert r.status_code == 200
        n = len(users)
        if delta is not None:
            r = requests.post(st.url + "/reload/delta",
                              json={"users": delta})
            assert r.status_code == 200
            assert r.json()["appliedCount"] == len(delta)
            for u in ("u0", "u1", "u5"):
                r = requests.post(st.url + "/queries.json",
                                  json={"user": u, "num": 4})
                assert r.status_code == 200
            n += 3
        requests.post(st.url + "/capture/stop")
    finally:
        st.stop()
    records = list(iter_capture(cap_dir))
    assert len(records) == n
    return records


def test_pipeline_replays_retriever_only_capture_bitwise(tmp_path, rng):
    """ISSUE 16 parity gate: a golden-traffic capture taken through the
    retriever alone (host gather, pad and upload around the compiled
    top-k program) replays 100% bitwise through the device-resident
    pipeline — including a delta-patched variant stretch. Both sides
    score through the same compiled-executable family (host numpy vs
    XLA differ in reduction order at B=1; the pipeline is pinned
    against the compiled program, which is the TPU serving reality)."""
    from predictionio_tpu.workflow.create_server import EngineServer

    engine, inst = _train_quickstart(tmp_path, rng, "pipepartest")
    retrieval = {"mode": "exact"}
    pre = _capture_retriever_only(tmp_path, engine, inst, retrieval,
                                  name="cap0")

    fresh = EngineServer(engine, inst, batch_window_ms=0,
                         retrieval=retrieval)
    model = fresh.deployed.result.models[0]
    assert getattr(model, "_pipeline", None) is not None, \
        "pipeline did not attach — the test would compare a path to itself"
    report = replay_records(pre, server=fresh)
    assert report["total"] == len(pre) and report["skipped"] == 0
    assert report["tiers"]["bitwise"] == len(pre)
    assert report["parityPct"] == 100.0
    # blob, instance, epoch must agree; only the exec digest may move
    assert set(report["provenance"]["delta"]) <= {"execCacheKey"}

    # delta-patched variant: the capture carries patchEpoch 1 on its
    # tail; the pipeline-side replayer applies the same patch (the
    # copy-on-write refresh — no recompile) and matches bitwise
    rank = int(np.asarray(model.user_factors).shape[1])
    patch = {"u1": (3.5 * np.ones(rank)).tolist(),
             "u5": (-2.0 * np.ones(rank)).tolist()}
    tagged = _capture_retriever_only(tmp_path, engine, inst, retrieval,
                                     name="cap1", delta=patch)
    pre_d = [r for r in tagged if r["provenance"]["patchEpoch"] == 0]
    post_d = [r for r in tagged if r["provenance"]["patchEpoch"] == 1]
    assert len(post_d) == 3

    from predictionio_tpu.ops.retrieval import EXEC_CACHE

    fresh2 = EngineServer(engine, inst, batch_window_ms=0,
                          retrieval=retrieval)
    rep_pre = replay_records(pre_d, server=fresh2)
    assert rep_pre["tiers"]["bitwise"] == len(pre_d)
    misses0 = EXEC_CACHE.stats()["misses"]
    out = fresh2.apply_delta(patch)
    assert out["appliedCount"] == len(patch)
    pm = fresh2.deployed.result.models[0]
    assert getattr(pm, "_pipeline", None) is not None
    rep_post = replay_records(post_d, server=fresh2)
    assert rep_post["tiers"]["bitwise"] == len(post_d)
    assert rep_post["parityPct"] == 100.0
    # epoch 1 == epoch 1: the patch itself leaves no provenance delta
    assert "patchEpoch" not in rep_post["provenance"]["delta"]
    # the refresh was copy-on-write: serving the patched table compiled
    # nothing new
    assert EXEC_CACHE.stats()["misses"] == misses0


def test_pipeline_replays_retriever_only_ann_capture_bitwise(tmp_path, rng):
    """ISSUE 16 parity gate, ANN-mode variant: with nprobe >= n_cells
    the index delegates to exact scoring, and the pipeline's gather
    front end hands the ANN retriever a bit-identical query matrix —
    an ANN capture taken through the retriever alone replays 100%
    bitwise through the pipeline's gather dispatch."""
    from predictionio_tpu.workflow.create_server import EngineServer

    engine, inst = _train_quickstart(tmp_path, rng, "pipeanntest")
    retrieval = {"mode": "ann", "min_items": 0, "n_cells": 4, "nprobe": 99}
    records = _capture_retriever_only(tmp_path, engine, inst, retrieval,
                                      name="capann")
    fresh = EngineServer(engine, inst, batch_window_ms=0,
                         retrieval=retrieval)
    model = fresh.deployed.result.models[0]
    pipe = getattr(model, "_pipeline", None)
    assert pipe is not None and pipe.stats()["mode"] == "gather"
    report = replay_records(records, server=fresh)
    assert report["total"] == len(records)
    assert report["tiers"]["bitwise"] == len(records)
    assert report["parityPct"] == 100.0
    assert set(report["provenance"]["delta"]) <= {"execCacheKey"}


# ---------------------------------------------------------------------------
# shadow mirror


def test_shadow_mirror_diffs_against_live_target(tmp_path):
    """Deploy-time shadowing: the primary mirrors its served queries to
    a second instance fire-and-forget; identical models diff bitwise on
    pio_shadow_diff_total and the lag gauge moves."""
    from predictionio_tpu.workflow.create_server import (
        EngineServer,
        create_engine_server_app,
    )
    from tests.test_resilience import _trained

    engine, inst = _trained()
    shadow_st = ServerThread(
        lambda: create_engine_server_app(EngineServer(engine, inst)))
    primary = EngineServer(engine, inst,
                           shadow_target=shadow_st.url, shadow_sample=1.0)
    primary_st = ServerThread(lambda: create_engine_server_app(primary))
    try:
        for i in range(5):
            r = requests.post(primary_st.url + "/queries.json",
                              json={"q": i})
            assert r.status_code == 200
        deadline = time.monotonic() + 15.0
        while (primary.shadow.mirrored < 5
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert primary.shadow.mirrored == 5
        assert primary.shadow.tiers["bitwise"] == 5
        assert METRICS.get("pio_shadow_diff_total").value("bitwise") == 5
        stats = requests.get(primary_st.url + "/stats.json").json()
        assert stats["shadow"]["target"] == shadow_st.url
        assert stats["shadow"]["tiers"]["bitwise"] == 5
    finally:
        primary_st.stop()
        shadow_st.stop()


def test_shadow_mirror_bounds_and_unreachable_target():
    """The mirror never blocks or wedges the primary: over the
    in-flight bound samples drop (counted), and an unreachable shadow
    lands in the error tier instead of raising."""
    import asyncio

    async def _run():
        m = ShadowMirror("http://127.0.0.1:9", sample=1.0,
                         max_inflight=1, timeout_s=0.5)
        m.mirror({"q": 1}, {"x": 1}, "r1")
        m.mirror({"q": 2}, {"x": 2}, "r2")  # over the bound -> dropped
        assert m.dropped == 1
        await asyncio.gather(*m._tasks, return_exceptions=True)
        assert m.tiers["error"] == 1  # nothing listens on port 9
        await m.aclose()
        m.mirror({"q": 3}, {"x": 3}, "r3")  # closed -> no-op
        assert len(m._tasks) == 0

    asyncio.run(_run())
