"""Fleet observability plane (ISSUE 20): exact cross-replica metric
aggregation, fleet SLO + outlier detection, correlated incident bundles
and one-command cross-process trace assembly.

The merge-correctness property test is the heart: render three
independent registries to Prometheus text, parse them back, merge — and
the merged histogram must be BITWISE equal (integer bucket counts) to a
single histogram fed the union of every sample, with exact (==, not
approx) p50/p95/p99. Exactness is by construction (shared bucket table +
shared quantile function), so the test pins the construction.

Unit tests drive the FleetCollector directly with an injected clock;
the router-integration tests use stub replicas that serve controllable
/metrics + /stats.json pages; the chaos acceptance uses two REAL
`pio deploy` subprocesses (SIGKILL one mid-scrape) so staleness,
survivor-only merges and the correlated incident bundle are the real
thing end to end.
"""

from __future__ import annotations

import json
import os
import random
import signal
import subprocess
import threading
import time
from pathlib import Path

import pytest
import requests

from predictionio_tpu.obs.aggregate import (FleetCollector, fleet_snapshot,
                                            merge_histograms,
                                            parse_prometheus)
from predictionio_tpu.obs.metrics import (DEFAULT_TIME_BUCKETS_S, METRICS,
                                          Histogram, MetricsRegistry,
                                          quantile_from_counts)
from predictionio_tpu.obs.slo import Objective, SloTracker
from predictionio_tpu.obs.trace import (TRACE_HEADER, render_span_tree,
                                        spans_from_waterfall)
from predictionio_tpu.workflow.fleet import (DEADLINE_HEADER, FleetRouter,
                                             create_fleet_app,
                                             spawn_replicas)
from tests.helpers import ServerThread
from tests.test_fleet import (_free_port_pair, _subprocess_env,
                              _train_in_subprocess, _wait_ready)
from tests.test_resilience import _poll

REPO = Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.obsfleet


# ---------------------------------------------------------------------------
# helpers: one simulated replica = one private MetricsRegistry


def _replica_registry() -> tuple[MetricsRegistry, dict]:
    reg = MetricsRegistry()
    handles = {
        "queries": reg.counter("pio_queries_total",
                               "query outcomes", labelnames=("status",)),
        "mode": reg.gauge("pio_server_mode", "serving mode ladder"),
        "latency": reg.histogram("pio_serving_latency_seconds",
                                 "serve wall latency"),
    }
    return reg, handles


def _slo_summary(good: int, bad: int, target: float = 0.999,
                 name: str = "availability") -> dict:
    """A SloTracker.summary()-shaped block built from raw counts."""
    total = good + bad
    frac = (bad / total) if total else 0.0
    budget = max(1.0 - target, 1e-9)
    win = {"events": total, "good": good, "bad": bad,
           "badFraction": round(frac, 6), "burnRate": round(frac / budget, 4)}
    return {"objectives": [{
        "name": name, "kind": "availability", "target": target,
        "windows": {"5m": dict(win), "1h": dict(win)},
        "breaching": win["burnRate"] > 1.0,
    }], "breaching": win["burnRate"] > 1.0}


# ---------------------------------------------------------------------------
# the tentpole property: render -> parse -> merge is EXACT


@pytest.mark.parametrize("seed", [7, 11, 42])
def test_merge_reproduces_union_histogram_bitwise(seed):
    """Three simulated replicas, random lognormal latencies spanning the
    whole bucket table (including the overflow bucket): the parsed
    per-replica bucket counts are bitwise the registry's own, and the
    merged quantiles equal a union-fed histogram's with ==."""
    rng = random.Random(seed)
    union = Histogram("union", "reference fed every sample")
    parsed_by_replica: dict[str, dict] = {}
    expect_queries: dict[str, float] = {}
    regs = []
    for i in range(3):
        reg, h = _replica_registry()
        regs.append((reg, h))
        for _ in range(rng.randint(50, 400)):
            v = rng.lognormvariate(-6.0, 3.0)  # ~2.5 us .. minutes
            h["latency"].record(v)
            union.record(v)
            status = rng.choice(["ok", "ok", "ok", "error", "shed"])
            h["queries"].inc(status=status)
            key = f'pio_queries_total{{status="{status}"}}'
            expect_queries[key] = expect_queries.get(key, 0.0) + 1.0
        h["mode"].set(float(i))
        parsed = parse_prometheus(reg.render_prometheus())
        # the parse round-trip itself is bitwise: raw integer bucket
        # counts and exact float bounds
        got = parsed["histograms"]["pio_serving_latency_seconds"]
        counts, count, _ = reg.get("pio_serving_latency_seconds"
                                   ).bucket_counts()
        assert got["bounds"] == tuple(DEFAULT_TIME_BUCKETS_S)
        assert got["counts"] == counts
        assert got["count"] == count
        parsed_by_replica[f"r{i}"] = parsed

    merged = fleet_snapshot(parsed_by_replica)

    # counters: summed exactly per (family, label set)
    for key, v in expect_queries.items():
        assert merged["counters"][key] == v

    # gauges: per-replica identity survives, rollups are min/max/sum
    g = merged["gauges"]["pio_server_mode"]
    assert g["byReplica"] == {"r0": 0.0, "r1": 1.0, "r2": 2.0}
    assert (g["min"], g["max"], g["sum"]) == (0.0, 2.0, 3.0)

    # histograms: the merged quantiles ARE the union histogram's —
    # bitwise float equality, not pytest.approx
    m = merged["histograms"]["pio_serving_latency_seconds"]
    assert m["count"] == union.bucket_counts()[1]
    assert m["p50"] == union.quantile(0.50)
    assert m["p95"] == union.quantile(0.95)
    assert m["p99"] == union.quantile(0.99)

    # and the merged raw counts equal the union's, bucket for bucket
    mh = merge_histograms({r: p["histograms"]
                           for r, p in parsed_by_replica.items()})
    assert (mh["pio_serving_latency_seconds"]["counts"]
            == union.bucket_counts()[0])


def test_bucket_bounds_mismatch_drops_family_with_counter():
    """Version skew: one replica buckets differently. The family is
    dropped from the merge (its numbers would be lies), the drop is
    counted, and nothing crashes; families that agree still merge."""
    reg_a, h_a = _replica_registry()
    h_a["latency"].record(0.01)
    h_a["queries"].inc(status="ok")
    reg_b = MetricsRegistry()
    reg_b.counter("pio_queries_total", "q", labelnames=("status",)
                  ).inc(status="ok")
    reg_b.histogram("pio_serving_latency_seconds", "skewed",
                    buckets=(0.1, 1.0, 10.0)).record(0.01)

    coll = FleetCollector()
    coll.ingest("r0", reg_a.render_prometheus())
    coll.ingest("r1", reg_b.render_prometheus())
    sj = coll.stats_json()
    assert "pio_serving_latency_seconds" not in sj["merged"]["histograms"]
    assert sj["collector"]["droppedFamilies"] == [
        "pio_serving_latency_seconds"]
    # the counter family (bounds-free) still merged exactly
    assert sj["merged"]["counters"]['pio_queries_total{status="ok"}'] == 2.0
    assert METRICS.get("pio_fleet_merge_dropped_total").value(
        "pio_serving_latency_seconds") >= 1
    # the dropped family is also visible on the rendered fleet page
    page = coll.render_prometheus()
    assert "pio_fleet_merge_dropped_total" in page
    assert 'pio_queries_total{status="ok",replica="r0"}' in page


# ---------------------------------------------------------------------------
# collector hygiene: failures keep the last snapshot, staleness excludes


def test_scrape_failure_keeps_snapshot_then_staleness_excludes():
    clock = [0.0]
    coll = FleetCollector(stale_after_s=5.0, now_fn=lambda: clock[0],
                          wall_fn=lambda: 1_000_000.0 + clock[0])
    reg0, h0 = _replica_registry()
    reg1, h1 = _replica_registry()
    h0["queries"].inc(status="ok", n=3)
    h1["queries"].inc(status="ok", n=4)
    coll.ingest("r0", reg0.render_prometheus())
    coll.ingest("r1", reg1.render_prometheus())
    assert coll.stats_json()["collector"]["freshReplicas"] == 2

    # r1's scrape fails: the LAST snapshot keeps serving (merge still
    # sums both), the failure is booked and stamped
    coll.mark_failed("r1", "scrape: TimeoutError")
    sj = coll.stats_json()
    assert sj["merged"]["counters"]['pio_queries_total{status="ok"}'] == 7.0
    assert sj["replicas"]["r1"]["failures"] == 1
    assert sj["replicas"]["r1"]["lastError"] == "scrape: TimeoutError"
    assert sj["replicas"]["r1"]["stale"] is False
    assert METRICS.get("pio_fleet_scrape_failures_total").value("r1") == 1.0

    # age past stale_after_s: r1 leaves the merge entirely, visibly
    clock[0] = 3.0
    coll.ingest("r0", reg0.render_prometheus())
    clock[0] = 6.0
    sj = coll.stats_json()
    assert sj["collector"]["freshReplicas"] == 1
    assert sj["replicas"]["r1"]["stale"] is True
    assert sj["replicas"]["r1"]["ageSeconds"] == 6.0
    assert sj["merged"]["counters"]['pio_queries_total{status="ok"}'] == 3.0
    # the meta gauges refresh on every scrape and on every rendered
    # /fleet/metrics page — the stale replica's age is scrapeable
    coll.render_prometheus()
    assert METRICS.get("pio_fleet_replicas_fresh").value() == 1.0
    assert METRICS.get("pio_fleet_scrape_age_seconds").value("r1") == 6.0


def test_ingest_detects_flight_recorder_firing():
    coll = FleetCollector()
    assert coll.ingest("r0", "", stats={"flight": {"dumps": 0}}) is False
    assert coll.ingest("r0", "", stats={"flight": {"dumps": 0}}) is False
    assert coll.ingest("r0", "", stats={"flight": {"dumps": 2}}) is True
    assert coll.ingest("r0", "", stats={"flight": {"dumps": 2}}) is False
    # a replica that never reports a flight block never fires
    assert coll.ingest("r1", "", stats={}) is False
    assert coll.ingest("r1", "", stats={}) is False


# ---------------------------------------------------------------------------
# windowed signals + outlier detection


def _scrape_round(coll, clock, regs, t):
    clock[0] = t
    for name, (reg, _) in regs.items():
        coll.ingest(name, reg.render_prometheus())


def test_windowed_signals_flag_the_outlier_then_clear():
    clock = [0.0]
    coll = FleetCollector(stale_after_s=60.0, outlier_band=0.75,
                          min_window_events=20, now_fn=lambda: clock[0])
    regs = {f"r{i}": _replica_registry() for i in range(3)}

    def burst(name, n, latency, statuses=("ok",)):
        _, h = regs[name]
        for k in range(n):
            h["latency"].record(latency)
            h["queries"].inc(status=statuses[k % len(statuses)])

    for name in regs:
        burst(name, 30, 0.0002)
    _scrape_round(coll, clock, regs, 0.0)  # baseline: no window yet

    # r2 turns slow AND erroring AND shedding; r0/r1 stay clean
    burst("r0", 40, 0.0002)
    burst("r1", 40, 0.0002)
    burst("r2", 40, 0.05, statuses=("ok", "error", "shed", "error"))
    _scrape_round(coll, clock, regs, 2.0)

    sj = coll.stats_json()
    w0, w2 = sj["replicas"]["r0"]["window"], sj["replicas"]["r2"]["window"]
    assert w0["events"] == 40 and w0["qps"] == pytest.approx(20.0)
    assert w2["p99"] > w0["p99"] * 10
    assert w2["errorFraction"] == pytest.approx(0.5)
    assert w2["shedRate"] == pytest.approx(0.25)
    assert w0["errorFraction"] == 0.0

    flags = sj["outliers"]
    assert set(flags) == {"r2"}
    assert set(flags["r2"]) == {"p99", "errorFraction", "shedRate"}
    assert METRICS.get("pio_fleet_outlier").value("r2", "p99") == 1.0
    assert METRICS.get("pio_fleet_outlier").value("r0", "p99") == 0.0

    # r2 recovers: the flags — and the gauges — clear
    for name in regs:
        burst(name, 40, 0.0002)
    _scrape_round(coll, clock, regs, 4.0)
    assert coll.outliers() == {}
    assert METRICS.get("pio_fleet_outlier").value("r2", "p99") == 0.0


def test_outliers_need_two_fresh_replicas_with_traffic():
    clock = [0.0]
    coll = FleetCollector(min_window_events=20, now_fn=lambda: clock[0])
    regs = {"r0": _replica_registry()}
    _, h = regs["r0"]
    for _ in range(50):
        h["latency"].record(0.5)
        h["queries"].inc(status="error")
    _scrape_round(coll, clock, regs, 0.0)
    for _ in range(50):
        h["latency"].record(0.5)
        h["queries"].inc(status="error")
    _scrape_round(coll, clock, regs, 1.0)
    # one replica, however bad, is never an outlier (no fleet to
    # deviate from) — and never crashes the detector
    assert coll.outliers() == {}


# ---------------------------------------------------------------------------
# fleet SLO: exact merged burn from raw counts


def test_fleet_slo_merges_raw_counts_exactly():
    clock = [0.0]
    trackers = [
        SloTracker([Objective(name="availability", kind="availability",
                              target=0.999)], now_fn=lambda: clock[0])
        for _ in range(2)]
    for _ in range(90):
        trackers[0].observe(0.0, ok=True)
    for _ in range(10):
        trackers[0].observe(0.0, ok=False)
    for _ in range(95):
        trackers[1].observe(0.0, ok=True)
    for _ in range(5):
        trackers[1].observe(0.0, ok=False)

    coll = FleetCollector(now_fn=lambda: clock[0])
    coll.ingest("r0", "", stats={"slo": trackers[0].summary()})
    coll.ingest("r1", "", stats={"slo": trackers[1].summary()})
    merged = coll.fleet_slo()
    win = merged["objectives"][0]["windows"]["5m"]
    # raw integer counts summed — NOT an average of the two fractions
    assert (win["good"], win["bad"], win["events"]) == (185, 15, 200)
    assert win["badFraction"] == round(15 / 200, 6)
    assert win["burnRate"] == round((15 / 200) / 0.001, 4)
    assert merged["replicas"] == 2

    # exclude=: "is the fleet healthy WITHOUT r0?" — the drain question
    solo = coll.fleet_slo(exclude="r0")["objectives"][0]["windows"]["5m"]
    assert (solo["good"], solo["bad"]) == (95, 5)
    assert coll.fleet_burn(exclude="r0") == round((5 / 100) / 0.001, 4)
    assert coll.fleet_burn(exclude=None) == round((15 / 200) / 0.001, 4)
    # no SLO-bearing replica at all -> None (callers fall back to
    # per-replica truth, preserving pre-fleet behavior)
    empty = FleetCollector()
    empty.ingest("r0", "", stats={})
    assert empty.fleet_burn() is None


def test_fleet_slo_reconstructs_version_skewed_summary():
    """A replica mid-rolling-deploy still sends the OLD wire format
    (no raw good/bad): the merge reconstructs from events*badFraction."""
    coll = FleetCollector()
    coll.ingest("r0", "", stats={"slo": _slo_summary(90, 10)})
    old_wire = {"objectives": [{
        "name": "availability", "kind": "availability", "target": 0.999,
        "windows": {"5m": {"events": 100, "badFraction": 0.1,
                           "burnRate": 100.0}},
    }], "breaching": True}
    coll.ingest("r1", "", stats={"slo": old_wire})
    win = coll.fleet_slo()["objectives"][0]["windows"]["5m"]
    assert (win["good"], win["bad"]) == (180, 20)


# ---------------------------------------------------------------------------
# stub replicas with observability surfaces, for router integration


def _obs_stub_state(name: str) -> dict:
    return {"name": name, "health_slo": None, "metrics_text": "",
            "stats": {}, "flight_records": [], "queries": 0}


def _obs_stub_factory(state: dict):
    from aiohttp import web

    async def queries(request):
        await request.read()
        state["queries"] += 1
        return web.json_response({"ok": True, "name": state["name"]})

    async def health(request):
        return web.json_response({
            "status": "ok", "live": True, "ready": True,
            "startTime": f"{state['name']}-boot-1",
            "model": {"patchEpoch": 0}, "slo": state["health_slo"]})

    async def metrics(request):
        return web.Response(text=state["metrics_text"],
                            content_type="text/plain")

    async def stats(request):
        return web.json_response(state["stats"])

    async def flight(request):
        return web.json_response({"records": state["flight_records"]})

    def factory():
        app = web.Application()
        app.router.add_post("/queries.json", queries)
        app.router.add_get("/health.json", health)
        app.router.add_get("/metrics", metrics)
        app.router.add_get("/stats.json", stats)
        app.router.add_get("/debug/flight.json", flight)
        return app

    return factory


class _ObsFleet:
    def __init__(self, n: int = 2, router_kw: dict | None = None):
        self.states = [_obs_stub_state(f"s{i}") for i in range(n)]
        self.stubs = [ServerThread(_obs_stub_factory(s))
                      for s in self.states]
        kw = {"probe_interval_s": 0.1, "probe_timeout_s": 1.0,
              "breaker_reset_s": 0.4, "dispatch_timeout_s": 5.0}
        kw.update(router_kw or {})
        self.router = FleetRouter([st.url for st in self.stubs], **kw)
        self.st = ServerThread(lambda: create_fleet_app(self.router))
        self.url = self.st.url

    def close(self):
        self.st.stop()
        for st in self.stubs:
            try:
                st.stop()
            except Exception:  # noqa: BLE001
                pass


def test_slo_drain_holds_when_the_whole_fleet_burns():
    """Fleet-truth drain policy: a burning replica is drained only when
    the REST of the fleet is healthy. When everyone burns, the problem
    is fleet-wide and removing capacity makes it worse — hold."""
    f = _ObsFleet(2, router_kw={"slo_drain_burn": 2.0})
    try:
        # both replicas report a burning SLO through /stats.json
        f.states[0]["stats"] = {"slo": _slo_summary(50, 50)}
        f.states[1]["stats"] = {"slo": _slo_summary(50, 50)}
        # r0's own health block crosses the drain threshold
        f.states[0]["health_slo"] = {"objectives": [
            {"windows": {"5m": {"burnRate": 6.0}}}]}
        # wait until the collector has BOTH replicas' SLO truth
        assert _poll(lambda: (f.router.collector.fleet_burn(exclude="r0")
                              or 0) >= 2.0, timeout_s=5)
        # several probe rounds with everyone burning: the drain HOLDS
        time.sleep(0.6)
        assert f.router.replicas[0].slo_drained is False
        assert "r0" in f.router.status()["eligible"]

        # the rest of the fleet recovers -> r0 is now the true outlier
        # and the drain proceeds
        f.states[1]["stats"] = {"slo": _slo_summary(100, 0)}
        assert _poll(lambda: f.router.replicas[0].slo_drained, timeout_s=5)
        assert _poll(lambda: f.router.status()["eligible"] == ["r1"],
                     timeout_s=5)
    finally:
        f.close()


def test_fleet_surfaces_and_cli_over_stub_fleet(capsys):
    """/fleet/metrics, /fleet/stats.json, /fleet/slo.json, `pio fleet
    status` columns, `pio top --fleet`, `pio admin metrics --url` (both
    behaviors) and `pio trace` — one stub fleet, every surface."""
    f = _ObsFleet(2)
    try:
        regs = {f"s{i}": _replica_registry() for i in range(2)}

        def publish(extra_fast=0, extra_slow=0):
            for i, (name, (reg, h)) in enumerate(sorted(regs.items())):
                for _ in range(extra_fast if i == 0 else extra_slow):
                    h["latency"].record(0.0002 if i == 0 else 0.05)
                    h["queries"].inc(status="ok")
                f.states[i]["metrics_text"] = reg.render_prometheus()
                f.states[i]["stats"] = {"slo": _slo_summary(90, 10),
                                        "flight": {"dumps": 0}}

        publish(extra_fast=30, extra_slow=30)
        assert _poll(lambda: all(
            (f.router.collector.replica_view().get(r) or {}).get("scrapes", 0)
            >= 1 for r in ("r0", "r1")), timeout_s=5)
        publish(extra_fast=40, extra_slow=40)
        # both replicas scraped at the final page -> merged is exact
        assert _poll(lambda: f.router.collector.stats_json()["merged"]
                     ["counters"].get('pio_queries_total{status="ok"}')
                     == 140.0, timeout_s=5)

        # -- /fleet/metrics: replica-labeled series + merged histogram
        page = requests.get(f.url + "/fleet/metrics", timeout=10).text
        assert 'pio_queries_total{status="ok",replica="r0"}' in page
        assert 'pio_queries_total{status="ok",replica="r1"}' in page
        assert "pio_serving_latency_seconds_bucket" in page
        assert 'pio_serving_latency_seconds_summary{quantile="0.99"}' in page
        assert "pio_fleet_replicas_fresh 2" in page

        # -- /fleet/stats.json: counters summed, slo merged
        sj = requests.get(f.url + "/fleet/stats.json", timeout=10).json()
        assert sj["merged"]["counters"][
            'pio_queries_total{status="ok"}'] == 140.0
        assert sj["slo"]["objectives"][0]["windows"]["5m"]["bad"] == 20
        slo = requests.get(f.url + "/fleet/slo.json", timeout=10).json()
        assert slo["replicas"] == 2

        from predictionio_tpu.tools.cli import main as pio_main

        # -- pio admin metrics --url against the ROUTER (the bugfix):
        # detects the fleet surface, prints the merged snapshot + a
        # breadcrumb — never the bare router-process registry
        assert pio_main(["admin", "metrics", "--url", f.url]) == 0
        out = capsys.readouterr().out
        assert "fleet: merged across 2 fresh replica(s)" in out
        assert f"{f.url}/fleet/metrics" in out
        assert 'pio_queries_total{status="ok"}' in out
        assert pio_main(["admin", "metrics", "--url", f.url, "--json"]) == 0
        body = json.loads(capsys.readouterr().out)
        assert body["merged"]["counters"][
            'pio_queries_total{status="ok"}'] == 140.0

        # -- pio admin metrics --url against a PLAIN engine server:
        # falls through to its /metrics page, parsed into the table
        assert pio_main(["admin", "metrics",
                         "--url", f.stubs[0].url]) == 0
        out = capsys.readouterr().out
        assert 'pio_queries_total{status="ok"}' in out
        assert "fleet: merged" not in out

        # -- pio trace: router hop + replica waterfall in one tree
        rid = "trace-rid-0001"
        r = requests.post(f.url + "/queries.json", json={"user": "u1"},
                          headers={TRACE_HEADER: rid}, timeout=10)
        assert r.status_code == 200
        owner = r.headers["X-PIO-Fleet-Replica"]
        f.states[int(owner[1:])]["flight_records"] = [{
            "requestId": rid, "path": "/queries.json", "status": 200,
            "finished": True, "wallMs": 3.2,
            "stagesMs": {"preprocess": 0.2, "device_execute": 2.4}}]
        assert pio_main(["trace", rid, "--router-url", f.url]) == 0
        out = capsys.readouterr().out
        assert f"trace {rid}" in out
        assert f"router hop -> {owner}" in out
        assert f"replica {owner}" in out
        assert "device_execute" in out
        # unknown id: explicit empty answer, exit 1
        assert pio_main(["trace", "nope-rid",
                         "--router-url", f.url]) == 1
        assert "no spans found" in capsys.readouterr().out

        # -- windowed columns need LIVE deltas between scrapes (a static
        # page means a 0-qps window): pump samples continuously, then
        # pin the `pio fleet status` + `pio top --fleet` columns
        stop_pump = threading.Event()

        def _pump():
            while not stop_pump.is_set():
                for i, name in enumerate(sorted(regs)):
                    reg, h = regs[name]
                    h["latency"].record(0.0002 if i == 0 else 0.05)
                    h["queries"].inc(status="ok")
                    f.states[i]["metrics_text"] = reg.render_prometheus()
                time.sleep(0.01)

        pump = threading.Thread(target=_pump, daemon=True)
        pump.start()
        try:
            def windows_live():
                w = (f.router.collector.replica_view()["r1"].get("window")
                     or {})
                return bool(w.get("qps")) and w.get("p99") is not None

            assert _poll(windows_live, timeout_s=10)

            # -- pio fleet status: windowed qps/p99 columns ride along
            assert pio_main(["fleet", "status", "--router-url", f.url]) == 0
            out = capsys.readouterr().out
            assert "qps" in out and "p99" in out

            # -- pio top --fleet: merged header + per-replica table
            assert pio_main(["top", "--fleet", "--once",
                             "--url", f.url]) == 0
            out = capsys.readouterr().out
            assert "fleet" in out and "replica" in out and "r1" in out
        finally:
            stop_pump.set()
            pump.join(5)
    finally:
        f.close()


def test_pio_trace_joins_ingest_wal_records(tmp_path, capsys):
    """The event path: a WAL record carrying the request id in its "t"
    field joins the tree even with no router reachable."""
    from predictionio_tpu.storage.journal import EventJournal

    rid = "wal-rid-7"
    j = EventJournal(tmp_path / "wal", fsync="never")
    j.append(json.dumps({
        "e": {"event": "$set", "entityType": "user", "entityId": "u7",
              "eventTime": "2026-08-07T00:00:00Z"},
        "a": 3, "c": None, "t": rid}).encode())
    j.append(json.dumps({"e": {"event": "rate"}, "a": 3,
                         "t": "other-rid"}).encode())
    j.sync()

    from predictionio_tpu.tools.cli import main as pio_main

    # port 9 is discard/unassigned: connection refused immediately
    rc = pio_main(["trace", rid, "--router-url", "http://127.0.0.1:9",
                   "--wal-dir", str(tmp_path / "wal")])
    captured = capsys.readouterr()
    assert rc == 0
    assert "ingest WAL: $set user/u7" in captured.out
    assert "other-rid" not in captured.out
    assert "unreachable" in captured.err  # the warn, not a crash


def test_span_tree_rendering_shape():
    rec = {"requestId": "x", "path": "/queries.json", "status": 200,
           "finished": False, "wallMs": 12.5,
           "stagesMs": {"queue_wait": 1.0, "device_execute": 9.0}}
    node = spans_from_waterfall(rec, label="replica r1")
    tree = render_span_tree([node], title="trace x")
    lines = tree.splitlines()
    assert lines[0] == "trace x"
    assert lines[1].startswith("replica r1  12.500 ms")
    assert "unfinished" in lines[1]
    assert lines[2].startswith("├─ queue_wait  1.000 ms")
    assert lines[3].startswith("└─ device_execute  9.000 ms")


# ---------------------------------------------------------------------------
# scrape failure never stalls the probe loop (stub fleet, broken pages)


def test_broken_metrics_page_never_breaks_probing_or_surfaces():
    f = _ObsFleet(2)
    try:
        f.states[0]["metrics_text"] = "#### utterly {{{ not prometheus\n"
        f.states[1]["metrics_text"] = "pio_queries_total 3\n"
        # both stubs stay eligible: scrape trouble is not a health fault
        assert _poll(lambda: f.router.status()["eligible"] == ["r0", "r1"],
                     timeout_s=5)
        assert _poll(lambda: (f.router.collector.replica_view()
                              .get("r1", {}).get("scrapes", 0)) >= 2,
                     timeout_s=5)
        sj = requests.get(f.url + "/fleet/stats.json", timeout=10).json()
        assert sj["collector"]["freshReplicas"] == 2  # junk parses to {}
        assert requests.get(f.url + "/fleet/metrics", timeout=10
                            ).status_code == 200
    finally:
        f.close()


def test_collector_disabled_surfaces_answer_404():
    f = _ObsFleet(1, router_kw={"collect_metrics": False})
    try:
        assert f.router.collector is None
        r = requests.get(f.url + "/fleet/metrics", timeout=10)
        assert r.status_code == 404
        r = requests.get(f.url + "/fleet/slo.json", timeout=10)
        assert r.status_code == 404
        sj = requests.get(f.url + "/fleet/stats.json", timeout=10).json()
        assert sj["collector"] is None
    finally:
        f.close()


# ---------------------------------------------------------------------------
# correlated incident bundle (stub fleet: deterministic trigger)


def test_flight_fire_produces_correlated_incident_bundle(tmp_path):
    f = _ObsFleet(2, router_kw={"incident_dir": tmp_path / "inc",
                                "incident_cooldown_s": 0.0})
    try:
        for i in range(2):
            f.states[i]["stats"] = {"flight": {"dumps": 0}}
            f.states[i]["flight_records"] = [{
                "requestId": f"req-{i}", "path": "/queries.json",
                "status": 200, "finished": True, "wallMs": 1.0,
                "stagesMs": {"device_execute": 0.8}}]
        assert _poll(lambda: all(
            (f.router.collector.replica_view().get(r) or {}
             ).get("flightDumps") == 0 for r in ("r0", "r1")), timeout_s=5)

        # s1's flight recorder fires (dump counter advances)
        f.states[1]["stats"] = {"flight": {"dumps": 1}}
        assert _poll(lambda: list((tmp_path / "inc").glob(
            "fleet-incident-*.json")), timeout_s=5)
        bundle = json.loads(sorted((tmp_path / "inc").glob(
            "fleet-incident-*.json"))[0].read_text())
        assert bundle["trigger"] == "r1"
        # BOTH replicas' waterfalls were pulled into the one bundle
        assert bundle["replicas"]["r0"]["records"][0]["requestId"] == "req-0"
        assert bundle["replicas"]["r1"]["records"][0]["requestId"] == "req-1"
        # router context rides along: breakers + fleet views
        assert bundle["router"]["breakers"] == {"r0": "closed",
                                                "r1": "closed"}
        assert set(bundle["fleet"]["replicas"]) == {"r0", "r1"}
        assert METRICS.get("pio_fleet_incidents_total").value() >= 1
    finally:
        f.close()


# ---------------------------------------------------------------------------
# the chaos acceptance: a REAL 2-replica fleet under the hammer


def test_fleet_observability_chaos_acceptance(tmp_path):
    """ISSUE 20 acceptance. Two real `pio deploy` replicas + a router
    with the collector on. (1) The merged surfaces serve real scraped
    truth. (2) A deadline burst on r0 fires its flight recorder and the
    router writes ONE correlated bundle naming both replicas. (3)
    SIGKILL r0 mid-scrape: its snapshot goes stale within one staleness
    window, every /fleet/* surface keeps serving from the survivor, and
    a survivor-side incident still bundles with the router's breaker
    context showing r0 open. (4) `pio trace <rid>` assembles a real
    cross-process tree."""
    env = _subprocess_env(tmp_path)
    engine_dir = _train_in_subprocess(tmp_path, env)
    base_port = _free_port_pair()
    urls = [f"http://127.0.0.1:{base_port + i}" for i in range(2)]
    inc_dir = tmp_path / "incidents"

    procs = spawn_replicas(str(engine_dir), 2, base_port, env=env)
    router = FleetRouter(urls, probe_interval_s=0.25, probe_timeout_s=2.0,
                         breaker_reset_s=0.5, dispatch_timeout_s=5.0,
                         metrics_stale_after_s=1.0,
                         incident_dir=inc_dir, incident_cooldown_s=0.0)
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
                    json={"user": f"u{(seed * 5 + n) % 30}", "num": 2},
                    headers={DEADLINE_HEADER: "8000"}, timeout=10)
            except requests.RequestException as e:
                failures.append(repr(e))
                return
            if r.status_code != 200:
                failures.append(f"{r.status_code}: {r.text[:160]}")
                return
            n_ok[0] += 1

    def incidents():
        return sorted(inc_dir.glob("fleet-incident-*.json"))

    try:
        for u in urls:
            _wait_ready(u)
        st = ServerThread(lambda: create_fleet_app(router))
        threads = [threading.Thread(target=hammer, args=(i,), daemon=True)
                   for i in range(3)]
        for t in threads:
            t.start()
        assert _poll(lambda: n_ok[0] >= 20, timeout_s=20)

        # -- (1) merged surfaces serve real scraped truth ---------------
        def merged_serving():
            sj = requests.get(st.url + "/fleet/stats.json", timeout=10
                              ).json()
            h = (sj.get("merged", {}).get("histograms") or {}).get(
                "pio_serving_latency_seconds") or {}
            return (sj.get("collector", {}).get("freshReplicas") == 2
                    and h.get("count", 0) > 0)

        assert _poll(merged_serving, timeout_s=10)
        page = requests.get(st.url + "/fleet/metrics", timeout=10).text
        assert 'replica="r0"' in page and 'replica="r1"' in page
        assert "pio_serving_latency_seconds_bucket" in page

        # -- (2) deadline burst on r0 -> correlated bundle --------------
        # 1 us budgets are expired by the time submit() checks them
        # (the same trigger the PR-5 acceptance uses); >=10 inside 5 s
        # fire the deadline_burst flight incident, the next scrape sees
        # the dump counter advance, the router bundles the whole fleet
        for _ in range(16):
            try:
                requests.post(urls[0] + "/queries.json",
                              json={"user": "u1", "num": 2},
                              headers={DEADLINE_HEADER: "0.001"},
                              timeout=10)
            except requests.RequestException:
                pass
        assert _poll(lambda: len(incidents()) >= 1, timeout_s=15)
        bundle = json.loads(incidents()[0].read_text())
        assert bundle["trigger"] == "r0"
        assert set(bundle["replicas"]) == {"r0", "r1"}  # both waterfalls
        assert bundle["replicas"]["r0"]["records"], "empty trigger ring"
        assert "breakers" in bundle["router"]
        n_before_kill = len(incidents())

        # -- (3) SIGKILL r0 mid-scrape -----------------------------------
        os.kill(procs[0].pid, signal.SIGKILL)
        t_kill = time.monotonic()

        def r0_stale():
            sj = requests.get(st.url + "/fleet/stats.json", timeout=10
                              ).json()
            return (sj["replicas"]["r0"]["stale"]
                    and sj["collector"]["freshReplicas"] == 1)

        assert _poll(r0_stale, timeout_s=10)
        # staleness declared within stale_after (1 s) + one probe
        # interval + scheduling slack — not a silent forever-fresh lie
        assert time.monotonic() - t_kill < 5.0
        # surfaces keep serving from the survivor: r1's data series are
        # there, r0's are out of the merge (its name survives only in
        # the collector's own meta families — scrape age, failures)
        page = requests.get(st.url + "/fleet/metrics", timeout=10).text
        assert 'pio_queries_total{status="ok",replica="r1"}' in page
        assert 'pio_queries_total{status="ok",replica="r0"}' not in page
        assert requests.get(st.url + "/fleet/slo.json", timeout=10
                            ).status_code == 200
        stop.set()
        for t in threads:
            t.join(15)
        assert not failures, failures[:3]

        # survivor-side incident still bundles, with breaker context
        for _ in range(16):
            try:
                requests.post(urls[1] + "/queries.json",
                              json={"user": "u2", "num": 2},
                              headers={DEADLINE_HEADER: "0.001"},
                              timeout=10)
            except requests.RequestException:
                pass
        assert _poll(lambda: len(incidents()) > n_before_kill,
                     timeout_s=15)
        bundle = json.loads(incidents()[-1].read_text())
        assert bundle["trigger"] == "r1"
        # r0's breaker context rides along (half_open only in the ~ms
        # window where a reset-probe of the dead replica is in flight)
        assert bundle["router"]["breakers"]["r0"] in ("open", "half_open")
        assert "r1" in bundle["replicas"]  # the dead r0 has no page now

        # -- (4) one-command cross-process trace assembly ----------------
        # the traced request goes out here, with the hammers stopped:
        # the router's hop log (512) and a replica's flight ring (256)
        # are bounded, and sent before the bursts the id had turned out
        # of both after a few seconds more of hammering
        rid = "chaos-rid-0001"
        r = requests.post(st.url + "/queries.json",
                          json={"user": "u3", "num": 2},
                          headers={TRACE_HEADER: rid,
                                   DEADLINE_HEADER: "8000"}, timeout=10)
        assert r.status_code == 200
        out = subprocess.run(
            [str(REPO / "bin" / "pio"), "trace", rid,
             "--router-url", st.url],
            capture_output=True, text=True, env=env, timeout=60)
        assert out.returncode == 0, out.stderr[-800:]
        assert f"trace {rid}" in out.stdout
        assert "router hop -> r" in out.stdout
        assert "replica r" in out.stdout     # the replica's waterfall
        assert "device_compute" in out.stdout  # a real pipeline stage
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
