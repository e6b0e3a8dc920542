"""Unified telemetry core (ISSUE 5): metrics registry, Prometheus text
exposition, cross-subsystem request tracing, and the /metrics surfaces.

Covers the satellite checklist:

- exposition parse round-trip: every sample line is ``name{labels} value``
  and no metric family is declared twice;
- histogram bucket edges: 0, sub-bucket-min, above-max overflow;
- concurrent record() from threads AND asyncio tasks;
- guard: every fault site in predictionio_tpu/faults.py has a pre-registered
  ``faults_injected_total{site=...}`` series, and SITES is exactly the
  set of literal FAULTS.fire/afire call sites in the package;

plus the ISSUE acceptance scenario: queries through a chaos-degraded
server make the deadline-expiry counter, watchdog-reclaim counter and a
nonzero serving p99 visible via ``GET /metrics``, while one trace id
joins ingress -> journal append -> drainer batch in the structured log.
"""

from __future__ import annotations

import asyncio
import json
import logging
import pathlib
import re
import threading
import time

import pytest
import requests

from predictionio_tpu.obs.metrics import (
    DEFAULT_TIME_BUCKETS_S,
    METRICS,
    Histogram,
    MetricsRegistry,
)
from predictionio_tpu.obs.trace import (
    TRACE_HEADER,
    current_request_id,
    ensure_request_id,
    set_request_id,
    span,
    trace_event,
)
from predictionio_tpu import faults
from predictionio_tpu.faults import FAULTS, FaultInjected
from tests.helpers import ServerThread

# ---------------------------------------------------------------------------
# exposition format

#: one sample line: metric name, optional {labels}, one value
_SAMPLE_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
    r'(?:,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
    r' (-?\d+(?:\.\d+)?(?:[eE][+-]?\d+)?|\+Inf|-Inf|NaN)$')


def _parse_exposition(text: str) -> dict[str, str]:
    """Validate ``text`` as Prometheus v0.0.4 exposition; return the
    family -> kind map. Asserts: trailing newline, every non-comment
    line matches the sample grammar, no family declared twice, every
    sample belongs to a declared family."""
    assert text.endswith("\n")
    families: dict[str, str] = {}
    samples: list[str] = []
    for line in text.splitlines():
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ", 3)
            assert name not in families, f"duplicate family: {name}"
            assert kind in ("counter", "gauge", "histogram", "summary")
            families[name] = kind
            continue
        assert line, "blank line inside exposition"
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable sample line: {line!r}"
        samples.append(m.group(1))
    for s in samples:
        base_candidates = [s]
        for suffix in ("_bucket", "_sum", "_count"):
            if s.endswith(suffix):
                base_candidates.append(s[: -len(suffix)])
        assert any(b in families for b in base_candidates), \
            f"sample {s} has no declared family"
    return families


def test_prometheus_exposition_roundtrip():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "by status", labelnames=("status",))
    c.inc(status="ok")
    c.inc(3, status='we"ird\nlabel')  # escaping must round-trip
    reg.gauge("t_depth", "queue depth").set(7)
    h = reg.histogram("t_latency_seconds", "latency")
    for v in (0.0002, 0.004, 0.07):
        h.record(v)
    families = _parse_exposition(reg.render_prometheus())
    assert families["t_requests_total"] == "counter"
    assert families["t_depth"] == "gauge"
    assert families["t_latency_seconds"] == "histogram"
    # histogram quantiles ride a SIBLING summary family (not a duplicate)
    assert families["t_latency_seconds_summary"] == "summary"


def test_global_registry_renders_valid_exposition():
    """The real process registry — with every subsystem's import-time
    families registered — must parse clean too."""
    import predictionio_tpu.workflow.create_server  # noqa: F401

    METRICS.get("pio_serving_latency_seconds").record(0.005)
    _parse_exposition(METRICS.render_prometheus())


# ---------------------------------------------------------------------------
# histogram edges

def test_histogram_zero_and_sub_min_land_in_first_bucket():
    h = Histogram("t_h1", "t")
    h.record(0.0)
    h.record(1e-9)  # below the 1e-4 minimum boundary
    h.record(DEFAULT_TIME_BUCKETS_S[0])  # exactly the first boundary
    snap = h.snapshot()
    assert snap["count"] == 3
    # all three sit in bucket 0: every quantile interpolates within it
    assert 0.0 <= snap["p99"] <= DEFAULT_TIME_BUCKETS_S[0]
    rendered = "\n".join(h.render())
    first = DEFAULT_TIME_BUCKETS_S[0]
    assert f'le="{first!r}"}} 3' in rendered or 'le="0.0001"} 3' in rendered


def test_histogram_overflow_reports_top_boundary():
    h = Histogram("t_h2", "t")
    h.record(1e9)  # far above the top finite boundary
    assert h.snapshot()["count"] == 1
    # the histogram cannot see past its table: quantiles report the top
    # finite boundary instead of inventing a number
    assert h.quantile(0.5) == pytest.approx(DEFAULT_TIME_BUCKETS_S[-1])
    rendered = "\n".join(h.render())
    assert 'le="+Inf"} 1' in rendered


def test_histogram_bucket_boundaries_are_inclusive():
    h = Histogram("t_h3", "t", buckets=(0.001, 0.01, 0.1))
    h.record(0.001)   # == first boundary -> bucket 0
    h.record(0.0011)  # just past it -> bucket 1
    h.record(0.1)     # == last boundary -> bucket 2, not overflow
    rendered = "\n".join(h.render())
    assert 'le="0.001"} 1' in rendered
    assert 'le="0.01"} 2' in rendered
    assert 'le="0.1"} 3' in rendered
    assert 'le="+Inf"} 3' in rendered


def test_histogram_sum_count_and_interpolation():
    h = Histogram("t_h4", "t")
    for _ in range(100):
        h.record(0.0015)  # bucket (0.0008, 0.0016]
    snap = h.snapshot()
    assert snap["count"] == 100
    assert snap["sum"] == pytest.approx(0.15)
    for q in ("p50", "p95", "p99"):
        assert 0.0008 <= snap[q] <= 0.0016


# ---------------------------------------------------------------------------
# concurrency

def test_concurrent_record_from_threads_and_asyncio():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "t", labelnames=("src",))
    h = reg.histogram("t_secs", "t")
    N, T = 500, 6

    def worker():
        for _ in range(N):
            c.inc(src="thread")
            h.record(0.001)

    threads = [threading.Thread(target=worker) for _ in range(T)]
    for t in threads:
        t.start()

    async def amain():
        async def one():
            for _ in range(N):
                c.inc(src="aio")
                h.record(0.002)
                if _ % 100 == 0:
                    await asyncio.sleep(0)  # force interleaving

        await asyncio.gather(*(one() for _ in range(T)))

    asyncio.run(amain())
    for t in threads:
        t.join()
    assert c.value("thread") == N * T
    assert c.value("aio") == N * T
    assert h.snapshot()["count"] == 2 * N * T


def test_reset_zeroes_in_place_and_keeps_handles():
    reg = MetricsRegistry()
    c = reg.counter("t_keep_total", "t")
    h = reg.histogram("t_keep_secs", "t")
    c.inc(5)
    h.record(0.01)
    reg.reset()
    assert c.value() == 0.0
    assert h.snapshot()["count"] == 0
    c.inc()  # the pre-reset handle still feeds the registry
    assert reg.get("t_keep_total").value() == 1.0


def test_reregistration_returns_same_family_and_kind_clash_raises():
    reg = MetricsRegistry()
    a = reg.counter("t_one_total", "t")
    assert reg.counter("t_one_total", "t") is a
    with pytest.raises(ValueError):
        reg.gauge("t_one_total", "t")


# ---------------------------------------------------------------------------
# faults guard (satellite 6)

def test_every_fault_site_has_a_counter_series():
    text = METRICS.render_prometheus()
    for site in faults.SITES:
        assert f'faults_injected_total{{site="{site}"}}' in text, site


def test_sites_matches_literal_fire_call_sites():
    """SITES must be exactly the literal FAULTS.fire/afire sites in the
    package — a new injection point without a counter series (or a stale
    SITES entry) fails here."""
    pkg = pathlib.Path(faults.__file__).resolve().parent
    found: set[str] = set()
    for p in pkg.rglob("*.py"):
        for m in re.finditer(r'FAULTS\.a?fire\(\s*["\']([^"\']+)["\']',
                             p.read_text()):
            found.add(m.group(1))
    assert found == set(faults.SITES)


def test_every_pio_metric_is_documented_in_operations_md():
    """Every ``pio_*`` metric family must have a catalog row in
    docs/operations.md — telemetry nobody can look up is noise
    (ISSUE 11 guard). Two sweeps, unioned: the live registry after
    importing the whole package (catches families registered under
    computed names, e.g. the per-stage waterfall histograms built from
    an f-string), and a source scan of literal METRICS registrations
    (catches families a test run might not import). ``Histogram``
    instances constructed outside the registry are intentionally out of
    scope: they never reach /metrics."""
    import importlib
    import pkgutil

    import predictionio_tpu as pkg_mod

    for info in pkgutil.walk_packages(pkg_mod.__path__,
                                      prefix="predictionio_tpu."):
        importlib.import_module(info.name)

    with METRICS._lock:
        names = {n for n in METRICS._metrics if n.startswith("pio_")}

    root = pathlib.Path(pkg_mod.__file__).resolve().parent
    for p in root.rglob("*.py"):
        for m in re.finditer(
                r'METRICS\.(?:counter|gauge|histogram)\(\s*'
                r'["\'](pio_[a-z0-9_]+)["\']',
                p.read_text()):
            names.add(m.group(1))
    assert names, "metric sweep found nothing — the scan regex rotted"

    doc = (root.parent / "docs" / "operations.md").read_text()
    undocumented = sorted(n for n in names if f"`{n}`" not in doc)
    assert not undocumented, (
        "metrics missing a docs/operations.md catalog row: "
        + ", ".join(undocumented))


@pytest.mark.chaos
def test_fired_fault_increments_site_counter():
    before = METRICS.get("faults_injected_total").value("journal.append")
    FAULTS.inject("journal.append", "error", times=1)
    with pytest.raises(FaultInjected):
        FAULTS.fire("journal.append")
    after = METRICS.get("faults_injected_total").value("journal.append")
    assert after == before + 1


# ---------------------------------------------------------------------------
# tracing primitives

def test_ensure_request_id_adopts_keeps_and_mints():
    tok = set_request_id(None)
    try:
        minted = ensure_request_id(None)
        assert minted and current_request_id() == minted
        assert ensure_request_id(None) == minted          # keeps
        assert ensure_request_id("client-1") == "client-1"  # adopts
        assert current_request_id() == "client-1"
    finally:
        set_request_id(None)
        del tok


def test_trace_event_and_span_emit_single_line_json(caplog):
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        trace_event("t.evt", trace="abc123", n=3)
        with span("t.span", trace="abc123") as extra:
            extra["rows"] = 7
    lines = [json.loads(r.message) for r in caplog.records
             if r.name == "pio.trace"]
    assert {"evt": "t.evt", "n": 3, "trace": "abc123"} == lines[0]
    assert lines[1]["evt"] == "t.span"
    assert lines[1]["trace"] == "abc123"
    assert lines[1]["rows"] == 7
    assert lines[1]["ms"] >= 0
    for r in caplog.records:
        if r.name == "pio.trace":
            assert "\n" not in r.message  # one grep-able line each


def test_span_records_error_field(caplog):
    with caplog.at_level(logging.INFO, logger="pio.trace"):
        with pytest.raises(RuntimeError):
            with span("t.boom"):
                raise RuntimeError("nope")
    line = json.loads(caplog.records[-1].message)
    assert line["evt"] == "t.boom"
    assert line["error"] == "RuntimeError: nope"


# ---------------------------------------------------------------------------
# /metrics surfaces + acceptance

def _poll(cond, timeout_s: float = 15.0, interval_s: float = 0.05):
    t_end = time.monotonic() + timeout_s
    while time.monotonic() < t_end:
        if cond():
            return True
        time.sleep(interval_s)
    return cond()


def _metric_value(text: str, sample: str) -> float:
    for line in text.splitlines():
        if line.startswith(sample + " "):
            return float(line.rsplit(" ", 1)[1])
    raise AssertionError(f"{sample} not in exposition")


def test_event_server_metrics_endpoint():
    from predictionio_tpu.api import create_event_app

    meta = __import__("predictionio_tpu.storage",
                      fromlist=["Storage"]).Storage.get_metadata()
    app = meta.app_insert("obsapp")
    key = meta.access_key_insert(app.id).key
    st = ServerThread(lambda: create_event_app(stats=True))
    try:
        ev = {"event": "rate", "entityType": "user", "entityId": "u1",
              "properties": {"rating": 4}}
        r = requests.post(f"{st.url}/events.json?accessKey={key}", json=ev,
                          timeout=10)
        assert r.status_code == 201
        assert r.headers[TRACE_HEADER]  # ingress echoes a trace id
        m = requests.get(f"{st.url}/metrics", timeout=10)
        assert m.status_code == 200
        assert m.headers["Content-Type"].startswith("text/plain")
        _parse_exposition(m.text)
        assert _metric_value(
            m.text, 'pio_events_ingested_total{status="201"}') >= 1
    finally:
        st.stop()


def test_dashboard_metrics_endpoint():
    from predictionio_tpu.tools.dashboard import create_dashboard_app

    st = ServerThread(create_dashboard_app)
    try:
        m = requests.get(f"{st.url}/metrics", timeout=10)
        assert m.status_code == 200
        _parse_exposition(m.text)
    finally:
        st.stop()


@pytest.mark.chaos
def test_acceptance_chaos_metrics_via_exposition():
    """ISSUE 5 acceptance (query plane): deadline expiries, a watchdog
    trip, then ~200 queries through the degraded server — all three
    signals plus a nonzero serving p99 must be readable off /metrics."""
    from tests.test_resilience import _trained
    from predictionio_tpu.workflow.create_server import (
        EngineServer, create_engine_server_app)

    engine, inst = _trained()
    server = EngineServer(
        engine, inst,
        batch_window_ms=0.5, batch_max=8, batch_inflight=2,
        dispatch_timeout_s=0.3,
        degraded_cooldown_s=60.0,  # stay degraded for the whole drive
    )
    FAULTS.inject("microbatch.dispatch", "hang", times=1, max_hang_s=20)
    st = ServerThread(lambda: create_engine_server_app(server))
    try:
        sess = requests.Session()
        # 1) deadline expiries on the healthy batched path
        for i in range(3):
            r = sess.post(st.url + "/queries.json", json={"q": i},
                          headers={"X-PIO-Deadline-Ms": "0.001"}, timeout=10)
            assert r.status_code == 504
        # 2) one hung dispatch -> watchdog reclaim -> degraded mode
        r = sess.post(st.url + "/queries.json", json={"q": 99}, timeout=30)
        assert r.status_code == 504
        assert _poll(lambda: server.degraded)
        # 3) ~200 queries against the degraded (fallback-path) server
        ok = 0
        for i in range(200):
            r = sess.post(st.url + "/queries.json", json={"q": i},
                          timeout=10)
            ok += r.status_code == 200
        assert ok == 200

        m = sess.get(st.url + "/metrics", timeout=10)
        assert m.status_code == 200
        _parse_exposition(m.text)
        assert _metric_value(m.text, "pio_deadline_expired_total") >= 3
        assert _metric_value(m.text, "pio_watchdog_reclaims_total") >= 1
        assert _metric_value(m.text, "pio_degraded_mode") == 1
        p99 = _metric_value(
            m.text, 'pio_serving_latency_seconds_summary{quantile="0.99"}')
        assert p99 > 0
        assert _metric_value(
            m.text, "pio_serving_latency_seconds_count") >= 204
        assert _metric_value(
            m.text, 'pio_queries_total{status="ok"}') == 200
        # the registry view and the /stats.json thin view agree
        stats = sess.get(st.url + "/stats.json", timeout=10).json()
        assert stats["latency"]["serving"]["count"] >= 204
        assert stats["latency"]["serving"]["p99"] > 0
    finally:
        FAULTS.clear()
        _poll(lambda: server.batcher.stats()["zombieDispatches"] == 0,
              timeout_s=5)
        st.stop()


@pytest.mark.ingest
def test_trace_id_joins_ingress_journal_drain(tmp_path, caplog):
    """ISSUE 5 acceptance (event plane): one client-chosen trace id is
    visible on the ingress line, the journal-append line, and the
    drainer's batch line — ``grep <id>`` follows the event end to end."""
    from predictionio_tpu.api import DurableIngestor, create_event_app
    from predictionio_tpu.storage import Storage

    meta = Storage.get_metadata()
    app = meta.app_insert("traceapp")
    key = meta.access_key_insert(app.id).key
    Storage.get_events().init_app(app.id)
    ingestor = DurableIngestor(str(tmp_path / "wal"), fsync="batch")
    st = ServerThread(lambda: create_event_app(stats=True,
                                               ingestor=ingestor))
    rid = "trace-join-e2e-0001"
    ev = {"event": "rate", "entityType": "user", "entityId": "u9",
          "properties": {"rating": 5}}

    def trace_lines():
        return [json.loads(r.message) for r in caplog.records
                if r.name == "pio.trace"]

    try:
        with caplog.at_level(logging.INFO, logger="pio.trace"):
            r = requests.post(f"{st.url}/events.json?accessKey={key}",
                              json=ev, headers={TRACE_HEADER: rid},
                              timeout=10)
            assert r.status_code == 201
            assert r.headers[TRACE_HEADER] == rid  # echoed back
            assert _poll(lambda: any(
                ln["evt"] == "ingest.drain_batch"
                and rid in (ln.get("traces") or [])
                for ln in trace_lines()), timeout_s=20)
        lines = trace_lines()
        assert any(ln["evt"] == "ingest.ingress" and ln.get("trace") == rid
                   for ln in lines)
        assert any(ln["evt"] == "ingest.journal_append"
                   and ln.get("trace") == rid for ln in lines)
    finally:
        st.stop()
