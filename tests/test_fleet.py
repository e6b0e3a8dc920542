"""Serving fleet (ISSUE 17): the FleetRouter routing tier over M
engine-server replicas — consistent-hash routing with least-loaded
spillover, per-replica health→breaker, hedged retry within the deadline
budget, delta fan-out with journal-replay epoch reconciliation, the
rolling reload canary gate, and the kill-a-replica acceptance gate
(SIGKILL one of two REAL `pio deploy` subprocess replicas under a
concurrent query hammer: zero dropped in-deadline requests, breaker
open within one probe interval, epoch-consistent rejoin proven via
provenance envelopes).

Unit tests drive the router over stub replica apps (controllable
health/epoch/latency); the acceptance test uses real subprocesses so
the SIGKILL, the shared-storage blob pull and the cross-process
deadline/trace headers are all the real thing.
"""

from __future__ import annotations

import asyncio
import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest
import requests

from predictionio_tpu.obs.metrics import METRICS
from predictionio_tpu.obs.replay import PROVENANCE_HEADER
from predictionio_tpu.obs.trace import TRACE_HEADER
from predictionio_tpu.faults import FAULTS, FaultInjected
from predictionio_tpu.workflow.fleet import (
    DEADLINE_HEADER,
    FLEET_REPLICA_HEADER,
    FleetRouter,
    _rendezvous,
    create_fleet_app,
    spawn_replicas,
    write_fleet_state,
)
from tests.helpers import ServerThread
from tests.test_resilience import _poll, _trained

REPO = Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.fleet


# ---------------------------------------------------------------------------
# stub replicas: a controllable engine-server lookalike


def _stub_state(name: str, **over) -> dict:
    state = {
        "name": name,
        "ready": True,
        "status": "ok",
        "start_time": f"{name}-boot-1",
        "epoch": 0,            # the replica's own patchEpoch
        "delay_s": 0.0,        # per-query serving latency
        "fail_queries": False,
        "model": "old",        # canary answers depend on this
        "slo": None,
        "queries": [],         # what /queries.json received (body+headers)
        "deltas": [],          # bodies received on /reload/delta
        "reloads": 0,
        "stops": 0,
    }
    state.update(over)
    return state


def _stub_factory(state: dict):
    from aiohttp import web

    async def queries(request):
        body = await request.json()
        state["queries"].append({
            "body": body,
            "rid": request.headers.get(TRACE_HEADER),
            "deadline": request.headers.get(DEADLINE_HEADER),
            "variant": request.headers.get("X-PIO-Variant"),
        })
        if state["delay_s"]:
            await asyncio.sleep(state["delay_s"])
        if state["fail_queries"]:
            return web.json_response({"message": "boom"}, status=500)
        # NOTE: no replica-identifying field in the BODY — the canary
        # diffs bodies across replicas; identity rides the router's
        # X-PIO-Fleet-Replica header instead
        return web.json_response(
            {"value": body, "model": state["model"]},
            headers={PROVENANCE_HEADER: json.dumps(
                {"patchEpoch": state["epoch"], "stub": state["name"]})})

    async def health(request):
        draining = state["status"] == "draining"
        return web.json_response({
            "status": state["status"],
            "live": True,
            "ready": state["ready"] and not draining,
            "startTime": state["start_time"],
            "model": {"patchEpoch": state["epoch"]},
            "slo": state["slo"],
        }, status=503 if draining else 200)

    async def reload(request):
        state["reloads"] += 1
        state["model"] = state.get("next_model", state["model"])
        return web.json_response({"message": "Reloaded",
                                  "engineInstanceId": f"{state['name']}-i"})

    async def reload_delta(request):
        body = await request.json()
        state["deltas"].append(body)
        state["epoch"] += 1
        return web.json_response({
            "message": "Patched", "epoch": state["epoch"],
            "appliedCount": len(body.get("users") or {})})

    async def stop(request):
        state["stops"] += 1
        return web.json_response({"message": "Shutting down."})

    def factory():
        app = web.Application()
        app.router.add_post("/queries.json", queries)
        app.router.add_get("/health.json", health)
        app.router.add_get("/reload", reload)
        app.router.add_post("/reload/delta", reload_delta)
        app.router.add_get("/stop", stop)
        return app

    return factory


class _Fleet:
    """Router-over-stubs harness: N stub replicas + a live FleetRouter
    app, all torn down in close()."""

    def __init__(self, n: int = 2, router_kw: dict | None = None,
                 states: list[dict] | None = None):
        self.states = states or [_stub_state(f"s{i}") for i in range(n)]
        self.stubs = [ServerThread(_stub_factory(s)) for s in self.states]
        kw = {"probe_interval_s": 0.15, "probe_timeout_s": 1.0,
              "breaker_reset_s": 0.4, "dispatch_timeout_s": 5.0}
        kw.update(router_kw or {})
        self.router = FleetRouter([st.url for st in self.stubs], **kw)
        self.st = ServerThread(lambda: create_fleet_app(self.router))
        self.url = self.st.url

    def post(self, query: dict, **kw) -> requests.Response:
        kw.setdefault("timeout", 15)
        return requests.post(self.url + "/queries.json", json=query, **kw)

    def replica_of(self, resp: requests.Response) -> str:
        return resp.headers[FLEET_REPLICA_HEADER]

    def close(self):
        self.st.stop()
        for st in self.stubs:
            try:
                st.stop()
            except Exception:  # noqa: BLE001 — some tests kill stubs early
                pass


@pytest.fixture
def fleet2():
    f = _Fleet(2)
    yield f
    f.close()


# ---------------------------------------------------------------------------
# rendezvous hashing: the pure-function properties


def test_rendezvous_balance_and_minimal_disruption():
    keys = [f"u{i}" for i in range(10_000)]

    def owner(key, names):
        return max(names, key=lambda n: _rendezvous(key, n))

    counts = {"r0": 0, "r1": 0}
    for k in keys:
        counts[owner(k, ["r0", "r1"])] += 1
    assert 0.45 < counts["r0"] / len(keys) < 0.55

    # consistent-hashing: removing r2 moves ONLY r2's keys
    moved = sum(1 for k in keys
                if owner(k, ["r0", "r1", "r2"]) != owner(k, ["r0", "r1"])
                and owner(k, ["r0", "r1", "r2"]) != "r2")
    assert moved == 0


# ---------------------------------------------------------------------------
# routing: stickiness, header propagation, deadline decrement


def test_sticky_routing_and_header_propagation(fleet2):
    # same entity key -> same replica, every time
    owners = {}
    for uid in (f"u{i}" for i in range(12)):
        got = {fleet2.replica_of(fleet2.post({"user": uid, "num": 1}))
               for _ in range(3)}
        assert len(got) == 1, f"key {uid} bounced between replicas: {got}"
        owners[uid] = got.pop()
    assert len(set(owners.values())) == 2  # both replicas carry keys

    # the router hop preserves the request id and DECREMENTS the
    # deadline budget by its own elapsed time (satellite 2) — a slow
    # fault on the routing site makes the elapsed time deterministic
    rid = "fleet-rid-0001"
    FAULTS.inject("fleet.route", "slow", delay_s=0.05, times=1)
    r = fleet2.post({"user": "u1", "num": 1},
                    headers={TRACE_HEADER: rid, DEADLINE_HEADER: "5000",
                             "X-PIO-Variant": "champion"})
    assert r.status_code == 200
    assert r.headers[TRACE_HEADER] == rid
    assert PROVENANCE_HEADER in r.headers  # replica envelope passed back
    seen = [q for s in fleet2.states for q in s["queries"]
            if q["rid"] == rid]
    assert len(seen) == 1
    assert seen[0]["variant"] == "champion"  # variant pin passed through
    fwd = float(seen[0]["deadline"])
    # 50 ms burned in the router: the replica must see < 4950 remaining
    assert 0 < fwd < 4975.0


def test_bad_json_and_router_health(fleet2):
    r = requests.post(fleet2.url + "/queries.json", data=b"{nope",
                      timeout=10)
    assert r.status_code == 400
    h = requests.get(fleet2.url + "/health.json", timeout=10).json()
    assert h["role"] == "fleet-router"
    assert h["ready"] is True and h["eligible"] == 2
    fj = requests.get(fleet2.url + "/fleet.json", timeout=10).json()
    assert [x["name"] for x in fj["replicas"]] == ["r0", "r1"]
    assert fj["eligible"] == ["r0", "r1"]


def test_deadline_budget_exhausted_is_504():
    f = _Fleet(2, router_kw={"default_deadline_ms": 1.0,
                             "hedge_floor_ms": 5.0})
    try:
        r = f.post({"user": "u1"})
        assert r.status_code == 504
        assert "deadline" in r.json()["message"]
    finally:
        f.close()


# ---------------------------------------------------------------------------
# failure isolation: breaker, hedged retry, chaos sites


def test_dead_replica_hedges_and_opens_breaker():
    """Kill one stub with traffic flowing and NO probe assist (30 s
    interval): the dispatch failure itself must open the breaker and
    the hedge must answer every query from the sibling."""
    f = _Fleet(2, router_kw={"probe_interval_s": 30.0})
    try:
        owners = {}
        for uid in (f"u{i}" for i in range(16)):
            owners[uid] = f.replica_of(f.post({"user": uid}))
        dead_name = "r0"
        dead_idx = 0
        f.stubs[dead_idx].stop()  # connection refused from now on

        codes = [f.post({"user": uid},
                        headers={DEADLINE_HEADER: "8000"}).status_code
                 for uid in owners]
        assert codes == [200] * len(codes)  # zero dropped in-deadline
        assert METRICS.get("pio_fleet_hedges_total").value("rescued") >= 1
        dead = f.router.replicas[dead_idx]
        assert dead.breaker == "open"  # first failed dispatch opened it
        assert dead_name not in f.router.status()["eligible"]
        # with the breaker open the survivor owns EVERY key
        assert all(f.replica_of(f.post({"user": uid})) == "r1"
                   for uid in list(owners)[:4])
    finally:
        f.close()


def test_probe_opens_breaker_within_one_interval_and_recovers():
    """No traffic at all: the probe loop alone must notice a dead
    replica within one probe interval, and a restart on the SAME port
    must walk open -> half_open -> closed and rejoin."""
    f = _Fleet(2)
    try:
        port = f.stubs[0].port
        f.stubs[0].stop()
        t0 = time.monotonic()
        assert _poll(lambda: f.router.replicas[0].breaker == "open",
                     timeout_s=5)
        # one 0.15 s probe interval + connection-refused latency + slack
        assert time.monotonic() - t0 < 2.0
        assert f.router.status()["eligible"] == ["r1"]

        # restart at the same address: half-open probe closes the breaker
        f.states[0] = _stub_state("s0-reborn", start_time="s0-boot-2")
        f.stubs[0] = ServerThread(_stub_factory(f.states[0]), port=port)
        assert _poll(lambda: f.router.replicas[0].breaker == "closed",
                     timeout_s=5)
        assert _poll(
            lambda: f.router.status()["eligible"] == ["r0", "r1"],
            timeout_s=5)
    finally:
        f.close()


def test_chaos_fleet_route_is_a_500(fleet2):
    FAULTS.inject("fleet.route", "error", times=1)
    r = fleet2.post({"user": "u1"})
    assert r.status_code == 500
    assert "routing failure" in r.json()["message"]
    assert METRICS.get("pio_fleet_requests_total").value("route_error") == 1
    assert fleet2.post({"user": "u1"}).status_code == 200  # budget spent


def test_chaos_replica_dispatch_error_is_rescued_by_hedge(fleet2):
    """An injected dispatch fault (the replica dying mid-dispatch) must
    hedge onto the sibling and still answer 200."""
    FAULTS.inject("fleet.replica_dispatch", "error", times=1)
    r = fleet2.post({"user": "u1"}, headers={DEADLINE_HEADER: "8000"})
    assert r.status_code == 200
    assert METRICS.get("pio_fleet_hedges_total").value("rescued") == 1


# ---------------------------------------------------------------------------
# spillover: a hot owner sheds to the least-loaded sibling


def test_hot_owner_spills_to_least_loaded():
    f = _Fleet(2, router_kw={"spillover_inflight": 1,
                             "probe_interval_s": 30.0})
    try:
        first = f.post({"user": "hot1"})
        owner = f.replica_of(first)
        owner_state = f.states[int(owner[1:])]
        owner_state["delay_s"] = 0.6

        got = {}

        def slow_one():
            got["slow"] = f.post({"user": "hot1"})

        t = threading.Thread(target=slow_one, daemon=True)
        t.start()
        assert _poll(
            lambda: f.router.replicas[int(owner[1:])].inflight >= 1,
            timeout_s=5)
        fast = f.post({"user": "hot1"})  # owner hot: must spill
        t.join(10)
        assert fast.status_code == got["slow"].status_code == 200
        assert f.replica_of(fast) != owner
        assert METRICS.get("pio_fleet_spillover_total").value() >= 1
    finally:
        f.close()


# ---------------------------------------------------------------------------
# eligibility: readiness, graceful drain, admin drain, SLO burn


def test_not_ready_and_draining_replicas_leave_rotation():
    f = _Fleet(2)
    try:
        # replica reports live-but-not-ready (prewarm in progress)
        f.states[0]["ready"] = False
        assert _poll(lambda: f.router.status()["eligible"] == ["r1"],
                     timeout_s=5)
        # not a fault: the breaker never moved
        assert f.router.replicas[0].breaker == "closed"

        # 503-draining is honored the same way (graceful, not a failure)
        f.states[0]["ready"] = True
        f.states[0]["status"] = "draining"
        assert _poll(
            lambda: f.router.replicas[0].draining
            and f.router.status()["eligible"] == ["r1"], timeout_s=5)
        assert f.router.replicas[0].breaker == "closed"

        f.states[0]["status"] = "ok"
        assert _poll(lambda: f.router.status()["eligible"] == ["r0", "r1"],
                     timeout_s=5)
    finally:
        f.close()


def test_admin_drain_undrain_and_stop(fleet2):
    r = requests.post(fleet2.url + "/fleet/drain",
                      json={"replica": "nope"}, timeout=10)
    assert r.status_code == 404
    r = requests.post(fleet2.url + "/fleet/drain",
                      json={"replica": "r0", "stop": True}, timeout=10)
    assert r.status_code == 200 and r.json()["stopped"] is True
    assert fleet2.states[0]["stops"] == 1
    assert "r0" not in fleet2.router.status()["eligible"]
    assert all(fleet2.replica_of(fleet2.post({"user": f"u{i}"})) == "r1"
               for i in range(6))
    r = requests.post(fleet2.url + "/fleet/undrain",
                      json={"replica": "r0"}, timeout=10)
    assert r.status_code == 200
    assert _poll(
        lambda: fleet2.router.status()["eligible"] == ["r0", "r1"],
        timeout_s=5)


def test_slo_burn_drains_and_recovers():
    f = _Fleet(2, router_kw={"slo_drain_burn": 2.0})
    try:
        f.states[0]["slo"] = {"objectives": [
            {"windows": {"5m": {"burnRate": 6.0}}}]}
        assert _poll(lambda: f.router.replicas[0].slo_drained, timeout_s=5)
        assert f.router.status()["eligible"] == ["r1"]
        snap = f.router.status()["replicas"][0]
        assert snap["sloDrained"] is True and snap["sloBurn"] == 6.0

        f.states[0]["slo"] = {"objectives": [
            {"windows": {"5m": {"burnRate": 0.1}}}]}
        assert _poll(lambda: not f.router.replicas[0].slo_drained,
                     timeout_s=5)
        assert _poll(lambda: f.router.status()["eligible"] == ["r0", "r1"],
                     timeout_s=5)
    finally:
        f.close()


# ---------------------------------------------------------------------------
# delta fan-out, the journal, epoch reconciliation


def _delta(n: int) -> dict:
    return {"users": {f"du{n}": [0.1 * n, 0.2]}}


def test_delta_fanout_reaches_every_replica(fleet2):
    r = requests.post(fleet2.url + "/reload/delta", json=_delta(1),
                      timeout=10)
    assert r.status_code == 200
    body = r.json()
    assert body["epoch"] == 1 and body["applied"] == ["r0", "r1"]
    assert [len(s["deltas"]) for s in fleet2.states] == [1, 1]
    assert fleet2.router.fleet_epoch == 1
    assert METRICS.get("pio_fleet_epoch").value() == 1.0
    # malformed bodies never bump the epoch
    r = requests.post(fleet2.url + "/reload/delta", json={"users": {}},
                      timeout=10)
    assert r.status_code == 400 and fleet2.router.fleet_epoch == 1


def test_missed_delta_reconciles_from_journal(fleet2):
    assert requests.post(fleet2.url + "/reload/delta", json=_delta(1),
                         timeout=10).status_code == 200
    FAULTS.inject("fleet.delta_fanout", "error", times=1)
    r = requests.post(fleet2.url + "/reload/delta", json=_delta(2),
                      timeout=10)
    assert r.status_code == 200  # one replica took it: the epoch commits
    applied = r.json()["applied"]
    assert len(applied) == 1
    (lagger,) = {"r0", "r1"} - set(applied)
    li = int(lagger[1:])
    # the lagging replica is OUT of hashed rotation until reconciled ...
    assert lagger not in fleet2.router.status()["eligible"]
    # ... and the probe loop replays the missed journal entry
    assert _poll(lambda: fleet2.router.replicas[li].synced_epoch == 2,
                 timeout_s=5)
    assert len(fleet2.states[li]["deltas"]) == 2
    assert fleet2.states[li]["deltas"][-1] == _delta(2)
    assert METRICS.get("pio_fleet_reconciliations_total").value(
        lagger, "replay") == 1
    assert _poll(
        lambda: fleet2.router.status()["eligible"] == ["r0", "r1"],
        timeout_s=5)


def test_restarted_replica_full_resyncs_before_traffic():
    """A replica that comes back EMPTY (fresh process, patch epoch
    regressed to 0) must take a full reload plus a whole-journal replay
    before it is eligible again."""
    f = _Fleet(2)
    try:
        for n in (1, 2):
            assert requests.post(f.url + "/reload/delta", json=_delta(n),
                                 timeout=10).status_code == 200
        assert f.states[0]["epoch"] == 2
        port = f.stubs[0].port
        f.stubs[0].stop()
        assert _poll(lambda: f.router.replicas[0].breaker == "open",
                     timeout_s=5)

        # reborn: new startTime, empty patch table. NOTE the router's
        # synced_epoch stays stale until the first successful probe
        # detects the patch-epoch REGRESSION — poll the reconciliation
        # itself, not the router's cached view.
        reborn = _stub_state("s0-reborn", start_time="s0-boot-2")
        f.states[0] = reborn
        f.stubs[0] = ServerThread(_stub_factory(reborn), port=port)
        assert _poll(lambda: reborn["reloads"] == 1
                     and reborn["epoch"] == 2, timeout_s=15)
        assert f.router.replicas[0].synced_epoch == 2
        assert [d for d in reborn["deltas"]] == [_delta(1), _delta(2)]
        assert reborn["epoch"] == 2            # journal replayed in order
        assert METRICS.get("pio_fleet_reconciliations_total").value(
            "r0", "full_reload") == 1
        assert _poll(lambda: f.router.status()["eligible"] == ["r0", "r1"],
                     timeout_s=5)
    finally:
        f.close()


# ---------------------------------------------------------------------------
# rolling reload + shadow-diff canary gate


def test_rolling_reload_passes_clean_canary(fleet2):
    for i in range(4):
        assert fleet2.post({"user": f"cu{i}"}).status_code == 200
    r = requests.get(fleet2.url + "/reload", timeout=15)
    assert r.status_code == 200
    body = r.json()
    assert [w["replica"] for w in body["wave"]] == ["r0", "r1"]
    assert body["canary"]["mismatchFraction"] == 0.0
    assert body["canary"]["sampled"] == 4
    assert [s["reloads"] for s in fleet2.states] == [1, 1]


def test_canary_mismatch_aborts_the_wave(fleet2):
    for i in range(4):
        assert fleet2.post({"user": f"cu{i}"}).status_code == 200
    # the fresh model on the first-reloaded replica answers differently
    fleet2.states[0]["next_model"] = "new"
    r = requests.get(fleet2.url + "/reload", timeout=15)
    assert r.status_code == 409
    body = r.json()
    assert body["canary"]["mismatchFraction"] == 1.0
    # the wave stopped: the baseline replica still serves the OLD model
    assert fleet2.states[0]["reloads"] == 1
    assert fleet2.states[1]["reloads"] == 0


# ---------------------------------------------------------------------------
# router lifecycle: draining refuses queries


def test_router_drain_refuses_queries_then_stop_exits():
    f = _Fleet(1)
    try:
        assert f.post({"user": "u1"}).status_code == 200
        # drain: the router stops taking queries but still answers
        # health (503 draining) so orchestrators can watch it leave
        asyncio.run_coroutine_threadsafe(f.router.close(),
                                         f.st._loop).result(15)
        assert f.post({"user": "u1"}).status_code == 503
        h = requests.get(f.url + "/health.json", timeout=10)
        assert h.status_code == 503 and h.json()["status"] == "draining"
    finally:
        f.close()

    # /stop ends the router process (GracefulExit): the HTTP answer is
    # the last thing it says, then the listener goes away
    f = _Fleet(1)
    try:
        r = requests.get(f.url + "/stop", timeout=10)
        assert r.status_code == 200

        def _gone():
            try:
                requests.post(f.url + "/queries.json", json={"q": 1},
                              timeout=(2, 2))
                return False
            except requests.RequestException:
                return True

        assert _poll(_gone, timeout_s=10)
    finally:
        f.close()


# ---------------------------------------------------------------------------
# satellite 1: readiness vs liveness on the ENGINE server itself


def test_engine_server_readiness_splits_from_liveness():
    from predictionio_tpu.workflow.create_server import EngineServer

    engine, inst = _trained()
    server = EngineServer(engine, inst, batch_window_ms=0,
                          defer_prewarm=True)
    h = server.health()
    # prewarm in progress: LIVE (don't restart me) but NOT ready
    assert h["live"] is True and h["status"] == "ok"
    assert h["ready"] is False and h["prewarming"] is True

    server.complete_prewarm()
    h = server.health()
    assert h["ready"] is True and h["prewarming"] is False
    server.complete_prewarm()  # idempotent

    # draining: still live, no longer ready, status says why
    asyncio.run(server.drain())
    h = server.health()
    assert h["live"] is True and h["ready"] is False
    assert h["status"] == "draining"


# ---------------------------------------------------------------------------
# chaos: replica.blob_pull — a poisoned model pull at deploy time


def test_replica_blob_pull_fault_falls_back_then_fails_loud():
    from predictionio_tpu.workflow.create_server import EngineServer

    engine, inst1 = _trained()
    _, inst2 = _trained()  # second COMPLETED instance, newest
    FAULTS.inject("replica.blob_pull", "error", times=1)
    server = EngineServer(engine, inst2)
    # the poisoned pull was quarantined; the fallback walk served the
    # previous COMPLETED instance
    assert server.deployed.instance.id == inst1.id
    assert [s["engineInstanceId"] for s in server.deploy_skips] == [inst2.id]
    assert server.health()["model"]["fallbackActive"] is True

    # with no fallback candidate left the deploy fails LOUD, not silent
    FAULTS.inject("replica.blob_pull", "error", times=10)
    with pytest.raises(FaultInjected):
        EngineServer(engine, inst1)
    FAULTS.clear()


# ---------------------------------------------------------------------------
# the acceptance gate: SIGKILL a real replica under a query hammer


def _free_port_pair() -> int:
    """A base port p where p and p+1 both bind."""
    for _ in range(32):
        with socket.socket() as a:
            a.bind(("127.0.0.1", 0))
            p = a.getsockname()[1]
            with socket.socket() as b:
                try:
                    b.bind(("127.0.0.1", p + 1))
                except OSError:
                    continue
                return p
    raise RuntimeError("no consecutive free port pair")


def _subprocess_env(tmp_path: Path) -> dict:
    env = dict(os.environ)
    env["PIO_HOME"] = str(tmp_path / "home")
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (str(REPO) + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    return env


def _train_in_subprocess(tmp_path: Path, env: dict) -> Path:
    """Quickstart app/import/train in ONE child process against the
    durable $PIO_HOME storage every replica subprocess will share."""
    import shutil

    from tests.test_quickstart_e2e import make_events_file

    engine_dir = tmp_path / "myrec"
    shutil.copytree(REPO / "templates" / "recommendation", engine_dir)
    variant = json.loads((engine_dir / "engine.json").read_text())
    variant["datasource"]["params"]["app_name"] = "fleettest"
    (engine_dir / "engine.json").write_text(json.dumps(variant))

    import numpy as np

    events = tmp_path / "events.jsonl"
    make_events_file(events, np.random.default_rng(11))
    script = tmp_path / "prep.py"
    script.write_text(
        "import sys\n"
        "from predictionio_tpu.tools.cli import main as pio\n"
        "from predictionio_tpu.storage import Storage\n"
        "assert pio(['app', 'new', 'fleettest']) == 0\n"
        "app = Storage.get_metadata().app_get_by_name('fleettest')\n"
        "assert pio(['import', '--appid', str(app.id),\n"
        "            '--input', sys.argv[2]]) == 0\n"
        "assert pio(['train', '--engine-dir', sys.argv[1]]) == 0\n"
        "print('TRAINED-OK')\n")
    out = subprocess.run(
        [sys.executable, str(script), str(engine_dir), str(events)],
        capture_output=True, text=True, env=env, timeout=180)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "TRAINED-OK" in out.stdout
    return engine_dir


def _wait_ready(url: str, timeout_s: float = 45.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            b = requests.get(url + "/health.json", timeout=2).json()
            if b.get("ready"):
                return
        except requests.RequestException:
            pass
        time.sleep(0.2)
    raise AssertionError(f"replica {url} never became ready")


def test_kill_a_replica_acceptance(tmp_path):
    """ISSUE 17 acceptance: two REAL `pio deploy` replica subprocesses
    (shared sqlite/localfs storage, blob trained once, pulled twice via
    the sha256 path), a live router, a concurrent query hammer. SIGKILL
    one replica: zero non-200 answers for in-deadline requests (hedged
    onto the survivor), the dead replica's breaker opens within one
    probe interval, and the restarted replica reconciles to the live
    fleet patch epoch — proven by its provenance envelope — before it
    receives hashed traffic again."""
    env = _subprocess_env(tmp_path)
    engine_dir = _train_in_subprocess(tmp_path, env)
    base_port = _free_port_pair()
    urls = [f"http://127.0.0.1:{base_port + i}" for i in range(2)]

    procs = spawn_replicas(str(engine_dir), 2, base_port, env=env)
    router = FleetRouter(urls, probe_interval_s=0.25, probe_timeout_s=1.0,
                         breaker_reset_s=0.5, dispatch_timeout_s=5.0,
                         max_hedges=1)
    st = None
    stop = threading.Event()
    failures: list[str] = []
    n_ok = [0]

    def hammer(seed: int) -> None:
        n = 0
        while not stop.is_set():
            n += 1
            try:
                r = requests.post(
                    st.url + "/queries.json",
                    json={"user": f"u{(seed * 7 + n) % 30}", "num": 2},
                    headers={DEADLINE_HEADER: "8000"}, timeout=10)
            except requests.RequestException as e:
                failures.append(repr(e))
                return
            if r.status_code != 200:
                failures.append(f"{r.status_code}: {r.text[:160]}")
                return
            n_ok[0] += 1

    try:
        for u in urls:
            _wait_ready(u)
        st = ServerThread(lambda: create_fleet_app(router))

        # one streaming delta through the router -> fleet epoch 1; both
        # replicas apply it (rank from the engine variant: real factors)
        rank = json.loads((engine_dir / "engine.json").read_text())[
            "algorithms"][0]["params"]["rank"]
        r = requests.post(st.url + "/reload/delta",
                          json={"users": {"freshF": [0.25] * rank}},
                          timeout=15)
        assert r.status_code == 200
        assert r.json()["applied"] == ["r0", "r1"], r.text
        assert router.fleet_epoch == 1

        threads = [threading.Thread(target=hammer, args=(i,), daemon=True)
                   for i in range(4)]
        for t in threads:
            t.start()
        assert _poll(lambda: n_ok[0] >= 20, timeout_s=20)

        # -- SIGKILL one replica under load --------------------------------
        os.kill(procs[0].pid, signal.SIGKILL)
        t_kill = time.monotonic()
        assert _poll(lambda: router.replicas[0].breaker == "open",
                     timeout_s=5, interval_s=0.005)
        # within one 0.25 s probe interval (+ refused-connection latency
        # and scheduling slack) — never a full probe-timeout away
        assert time.monotonic() - t_kill < 1.5
        ok_at_kill = n_ok[0]
        assert _poll(lambda: n_ok[0] >= ok_at_kill + 30, timeout_s=20)
        stop.set()
        for t in threads:
            t.join(15)
        assert not failures, failures[:5]  # ZERO dropped in-deadline

        # -- restart the replica: rejoin is epoch-consistent ---------------
        procs += spawn_replicas(str(engine_dir), 1, base_port, env=env)
        assert _poll(
            lambda: "r0" in router.status()["eligible"], timeout_s=45,
            interval_s=0.1)
        # a FRESH process regressed its patch epoch -> full resync
        assert router.replicas[0].synced_epoch == 1
        assert METRICS.get("pio_fleet_reconciliations_total").value(
            "r0", "full_reload") >= 1

        # hashed traffic reaches r0 again, and its provenance envelope
        # proves the delta epoch was reconciled BEFORE this query
        prov = None
        for i in range(200):
            rr = requests.post(st.url + "/queries.json",
                               json={"user": f"v{i}", "num": 2},
                               headers={DEADLINE_HEADER: "8000"},
                               timeout=10)
            assert rr.status_code == 200
            if rr.headers.get(FLEET_REPLICA_HEADER) == "r0":
                prov = json.loads(rr.headers[PROVENANCE_HEADER])
                break
        assert prov is not None, "rejoined replica never answered"
        assert prov["patchEpoch"] == 1
    finally:
        stop.set()
        if st is not None:
            st.stop()
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# ---------------------------------------------------------------------------
# CLI surfaces against a live router: `pio fleet status` and `pio status`


def test_pio_fleet_status_and_pio_status_against_live_router(
        tmp_path, monkeypatch, fleet2):
    monkeypatch.setenv("PIO_HOME", str(tmp_path))
    write_fleet_state(fleet2.url, [
        {"name": f"r{i}", "url": s.url, "pid": None}
        for i, s in enumerate(fleet2.stubs)])
    env = dict(os.environ, PIO_HOME=str(tmp_path), JAX_PLATFORMS="cpu")

    out = subprocess.run(
        [str(REPO / "bin" / "pio"), "fleet", "status"],
        capture_output=True, text=True, env=env, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "fleet router" in out.stdout
    assert "r0" in out.stdout and "r1" in out.stdout

    out = subprocess.run([str(REPO / "bin" / "pio"), "status"],
                         capture_output=True, text=True, env=env,
                         timeout=60)
    assert out.returncode == 0, out.stderr
    assert "serving fleet" in out.stdout
    assert "2/2 eligible" in out.stdout
    assert "replica r0" in out.stdout and "live=true" in out.stdout
