"""The event server: REST ingestion API on :7070.

Analog of the reference's spray/akka ``EventServiceActor``/``EventServer``
(reference: data/src/main/scala/io/prediction/data/api/EventAPI.scala:60-479)
re-built on asyncio/aiohttp. Route surface kept wire-compatible:

- ``GET  /``                     -> {"status": "alive"}
- ``POST /events.json``          -> 201 {"eventId": ...}
- ``POST /batch/events.json``    -> per-event status list (batch ingest)
- ``GET  /events.json``          -> filtered scan (default limit 20)
- ``GET  /events/<id>.json``     -> one event
- ``DELETE /events/<id>.json``   -> {"message": "Found"} | 404
- ``GET  /stats.json``           -> ingestion counters (with --stats)
- ``GET  /health.json``          -> ok/degraded + journal lag (no auth,
  engine-server parity — wire it as the LB readiness check)
- ``POST /webhooks/<name>.json`` -> JSON connector ingestion
- ``POST /webhooks/<name>``      -> form connector ingestion
- ``GET  /webhooks/<name>[.json]`` -> connector presence check

Auth: ``?accessKey=`` resolved against the metadata store; optional
``?channel=`` resolved per app (EventAPI.scala:88-116). Event writes run
in a thread pool so slow storage never blocks the accept loop.

Durable mode (``pio eventserver --journal-dir ...``): writes ack 201
after a durable append to the ingestion journal (storage/journal.py) and
a background drainer pushes them into the event backend — a storage
outage degrades reads, never loses acked events (api/ingest.py). A full
journal answers **503 + Retry-After** (backpressure, not silent loss).
"""

from __future__ import annotations

import asyncio
import json
import logging
from dataclasses import dataclass
from datetime import datetime

from aiohttp import web

from ..obs.http import handle_metrics, make_trace_middleware
from ..obs.metrics import METRICS
from ..obs.trace import TRACE_HEADER, ensure_request_id, trace_event
from ..storage import (
    EventQuery,
    Storage,
    ValidationError,
    event_from_api_dict,
    event_to_api_dict,
)
from ..storage.event import _dt_from_wire
from ..storage.events_base import StorageError, TableNotInitialized
from ..storage.journal import JournalFull
from ..workflow.admission import AdmissionController
from ..faults import FAULTS
from .ingest import DurableIngestor
from .stats import Stats
from .webhooks import ConnectorException, FormConnector, JsonConnector, get_connector

log = logging.getLogger("predictionio_tpu.eventserver")

__all__ = ["create_event_app", "run_event_server", "AuthData"]

STATS_KEY = web.AppKey("stats", object)
INGEST_KEY = web.AppKey("ingest", object)
ADMISSION_KEY = web.AppKey("admission", object)

#: FALLBACK Retry-After seconds on journal-full 503s, used only before
#: the drainer has any throughput history; once it does, the header is
#: computed dynamically from journal lag / drain rate
#: (DurableIngestor.retry_after_s, via the shared admission helper).
BACKPRESSURE_RETRY_AFTER_S = 1

# ISSUE 5: every booked ingest outcome, by HTTP status — the scrapeable
# twin of the per-app Stats bookkeeping (which stays hourly/per-app)
_M_EVENTS = METRICS.counter(
    "pio_events_ingested_total",
    "ingest outcomes by HTTP status (201/400/401/403/429/500/503)",
    labelnames=("status",))


@dataclass
class AuthData:
    app_id: int
    channel_id: int | None
    #: allowed event names; empty = all (AccessKeys.scala:27-34)
    events: tuple = ()


def _json_error(status: int, message: str) -> web.Response:
    return web.json_response({"message": message}, status=status)


async def _authenticate(request: web.Request,
                        ingest: bool = False) -> AuthData | web.Response:
    """Query-param access-key auth (EventAPI.scala:88-116). ``ingest``:
    the caller is a write path, so a bookable auth failure (invalid
    channel on a known app) counts toward /stats.json — read paths must
    not book, or polling a bad channel would masquerade as rejected
    ingest traffic."""
    access_key = request.query.get("accessKey")
    if not access_key:
        return _json_error(401, "Missing accessKey.")
    meta = Storage.get_metadata()
    ak = await asyncio.to_thread(meta.access_key_get, access_key)
    if ak is None:
        return _json_error(401, "Invalid accessKey.")
    channel = request.query.get("channel")
    if channel is None:
        return AuthData(app_id=ak.appid, channel_id=None, events=tuple(ak.events))
    channels = await asyncio.to_thread(meta.channel_get_by_appid, ak.appid)
    for ch in channels:
        if ch.name == channel:
            return AuthData(app_id=ak.appid, channel_id=ch.id, events=tuple(ak.events))
    if ingest:
        # the one auth failure with a known app: bookable per-app
        _bump_stats(request, ak.appid, 401)
    return _json_error(401, f"Invalid channel '{channel}'.")


def _parse_time(s: str | None) -> datetime | None:
    return None if s is None else _dt_from_wire(s)


def _validate_api_event(auth: AuthData, data: dict):
    """API-JSON dict -> Event, or an error (status, body, event|None)
    triple — the ONE home of API-path validation for the single and batch
    endpoints. The triple carries the parsed Event when one exists (the
    403 key-scope reject) so the reject can be booked under its real
    (entityType, event) key. Never trusts a client-supplied eventId: ids
    are assigned server-side (the reference's APISerializer doesn't read
    eventId either); the bulk-import tool is the only id-preserving
    path."""
    if not isinstance(data, dict):
        return 400, {"message": "Event must be a JSON object."}, None
    try:
        event = event_from_api_dict(
            {k: v for k, v in data.items() if k != "eventId"})
    except ValidationError as e:
        return 400, {"message": str(e)}, None
    if auth.events and event.event not in auth.events:
        return 403, {
            "message": f"event {event.event!r} is not allowed by this access key"
        }, event
    return event


def _bump_stats(request: web.Request, app_id: int, status: int,
                event=None) -> None:
    """Book one ingest outcome with its ACTUAL status — 201s, 400
    validation rejects, 403 key-scope rejects, 500 storage errors — the
    way the reference books ``result.status`` per request
    (EventAPI.scala:195-199 -> StatsActor.scala:28-70); that is what
    makes /stats.json useful for spotting rejected events. Requests
    failing auth before an app is known cannot be booked per-app."""
    _M_EVENTS.inc(status=str(status))
    stats: Stats | None = request.app.get(STATS_KEY)
    if stats is None:
        return
    if event is None:
        stats.update(app_id, status)
    else:
        stats.update(
            app_id, status,
            entity_type=event.entity_type,
            target_entity_type=event.target_entity_type,
            event=event.event,
        )


async def _insert_one(
    request: web.Request, auth: AuthData, event
) -> tuple[int, dict]:
    """Insert one already-validated Event; returns (status, body).

    With a journal configured, the ack means "durably journaled" and the
    backend write happens on the drainer's schedule; otherwise it is a
    direct backend insert. Re-inserting an event the backend already
    persisted is idempotent at the storage layer only if the backend
    deduplicates; the API contract here mirrors the reference's (each
    POST is one event record)."""
    ingest: DurableIngestor | None = request.app.get(INGEST_KEY)
    if ingest is not None:
        e = ingest.assign_id(event)
        statuses, err = await ingest.submit([e], auth.app_id, auth.channel_id)
        if statuses[0] == "ok":
            # event-path join, middle hop: ingress line -> this line ->
            # the drainer's ingest.drain_batch line, all by trace id
            trace_event("ingest.journal_append", event_id=e.event_id)
            _bump_stats(request, auth.app_id, 201, e)
            return 201, {"eventId": e.event_id}
        if statuses[0] == "full":
            _bump_stats(request, auth.app_id, 503, event)
            return 503, {"message": "event journal at capacity; retry"}
        _bump_stats(request, auth.app_id, 500, event)
        return 500, {"message": f"journal append failed: {err}"}
    events = Storage.get_events()
    try:
        # chaos site: arm a StorageError here to exercise the real
        # 500/stats path without a broken backend (faults.py)
        await FAULTS.afire("eventserver.insert")
        event_id = await asyncio.to_thread(
            events.insert, event, auth.app_id, auth.channel_id
        )
    except StorageError as e:
        _bump_stats(request, auth.app_id, 500, event)
        return 500, {"message": str(e)}
    _bump_stats(request, auth.app_id, 201, event)
    return 201, {"eventId": event_id}


async def _insert_event_dict(
    request: web.Request, auth: AuthData, data: dict
) -> tuple[int, dict]:
    """Validate + insert one API-JSON event; returns (status, body)."""
    validated = _validate_api_event(auth, data)
    if isinstance(validated, tuple):
        status, body, event = validated
        _bump_stats(request, auth.app_id, status, event)
        return status, body
    return await _insert_one(request, auth, validated)


def _ingest_response(request: web.Request, status: int, body) -> web.Response:
    """json_response + the backpressure contract: every 503 (or batch
    containing one) carries Retry-After so well-behaved clients pace
    themselves instead of hammering a full journal. The delay is
    lag-proportional (journal lag / drain rate, jittered) once the
    drainer has throughput history; a fixed fallback before that."""
    full = status == 503 or (
        isinstance(body, list)
        and any(isinstance(x, dict) and x.get("status") == 503 for x in body))
    headers = None
    if full:
        ingest: DurableIngestor | None = request.app.get(INGEST_KEY)
        ra = (ingest.retry_after_s() if ingest is not None
              else float(BACKPRESSURE_RETRY_AFTER_S))
        headers = {"Retry-After": f"{max(0.0, ra):.3f}"}
    return web.json_response(body, status=status, headers=headers)


def _admission_check(request: web.Request, auth: AuthData) -> web.Response | None:
    """Adaptive admission for the ingest write paths (ISSUE 6): sheds
    429 + Retry-After off journal pressure / per-access-key token
    buckets BEFORE the validate + journal-append work is spent. Returns
    the 429 response, or None to admit."""
    adm: AdmissionController | None = request.app.get(ADMISSION_KEY)
    if adm is None:
        return None
    decision = adm.decide("ingest", key=request.query.get("accessKey"))
    if decision.admitted:
        return None
    _bump_stats(request, auth.app_id, 429)
    return web.json_response(
        {"message": f"overloaded; retry later ({decision.reason})"},
        status=429,
        headers={"Retry-After": f"{max(0.0, decision.retry_after_s):.3f}"})


# -- handlers ---------------------------------------------------------------

async def handle_root(request: web.Request) -> web.Response:
    return web.json_response({"status": "alive"})


async def handle_post_event(request: web.Request) -> web.Response:
    # trace ingress (event path): the id set here rides inside the
    # journal payload (api/ingest.py encode) so the drainer — even a
    # post-crash replay in another process — joins back to this line
    rid = ensure_request_id(request.headers.get(TRACE_HEADER))
    auth = await _authenticate(request, ingest=True)
    if isinstance(auth, web.Response):
        return auth
    shed = _admission_check(request, auth)
    if shed is not None:
        shed.headers[TRACE_HEADER] = rid
        return shed
    try:
        data = await request.json()
    except (json.JSONDecodeError, UnicodeDecodeError):
        _bump_stats(request, auth.app_id, 400)
        return _json_error(400, "Malformed JSON body.")
    status, body = await _insert_event_dict(request, auth, data)
    trace_event("ingest.ingress", status=status,
                event_id=body.get("eventId") if isinstance(body, dict) else None)
    resp = _ingest_response(request, status, body)
    resp.headers[TRACE_HEADER] = rid
    return resp


async def handle_post_batch(request: web.Request) -> web.Response:
    """Batch ingestion: a JSON array of events; per-event status in order.
    (The reference gained /batch/events.json right after 0.9.2; the import
    tool also needs it.) Max 50 per request, like the official SDKs."""
    rid = ensure_request_id(request.headers.get(TRACE_HEADER))
    auth = await _authenticate(request, ingest=True)
    if isinstance(auth, web.Response):
        return auth
    shed = _admission_check(request, auth)
    if shed is not None:
        shed.headers[TRACE_HEADER] = rid
        return shed
    try:
        data = await request.json()
    except (json.JSONDecodeError, UnicodeDecodeError):
        _bump_stats(request, auth.app_id, 400)
        return _json_error(400, "Malformed JSON body.")
    if not isinstance(data, list):
        _bump_stats(request, auth.app_id, 400)
        return _json_error(400, "Batch body must be a JSON array of events.")
    if len(data) > 50:
        # one row PER rejected event, matching the accepted path's
        # per-event rows — else a size-capped batch books 1 against the
        # accepted batch's 50 and rejected volume reads ~2% of reality
        for _ in data:
            _bump_stats(request, auth.app_id, 400)
        return _json_error(400, "Batch size exceeds the limit of 50 events.")
    # validate everything first, then ONE backend insert_batch for the
    # valid events (sqlite overrides it with a single executemany
    # transaction — per-event inserts pay a commit each, measured ~3x
    # slower through the HTTP plane); per-event statuses keep their
    # order, invalid events don't block valid ones
    results: list[dict | None] = []
    valid: list[tuple[int, object]] = []  # (result slot, Event)
    for item in data:
        validated = _validate_api_event(auth, item)
        if isinstance(validated, tuple):
            status, body, ev = validated
            _bump_stats(request, auth.app_id, status, ev)
            results.append({"status": status, **body})
            continue
        results.append(None)  # filled from the batch insert below
        valid.append((len(results) - 1, validated))
    ingest: DurableIngestor | None = request.app.get(INGEST_KEY)
    if valid and ingest is not None:
        # durable mode: the batch is routed by entity hash and appended
        # to its journal partitions concurrently, ONE fsync per touched
        # partition (the fsync-amortization point of the `batch` policy);
        # the backend writes happen on the drainers' schedules. A full
        # partition 503s only ITS events — per-event statuses stay
        # exact, siblings keep acking, nothing is silently dropped.
        withids = [(slot, ingest.assign_id(e)) for slot, e in valid]
        statuses, err = await ingest.submit(
            [e for _, e in withids], auth.app_id, auth.channel_id)
        for (slot, e), s in zip(withids, statuses):
            if s == "ok":
                results[slot] = {"status": 201, "eventId": e.event_id}
                _bump_stats(request, auth.app_id, 201, e)
            elif s == "full":
                results[slot] = {"status": 503,
                                 "message": "event journal at capacity; retry"}
                _bump_stats(request, auth.app_id, 503, e)
            else:
                results[slot] = {"status": 500,
                                 "message": f"journal append failed: {err}"}
                _bump_stats(request, auth.app_id, 500, e)
    elif valid:
        events_dao = Storage.get_events()
        # only atomic backends take the one-call fast path: a non-atomic
        # backend could persist a prefix of the batch before failing, and
        # a blanket 500 would then make clients re-send events that
        # already landed (double ingestion). Per-event inserts give exact
        # statuses for those backends.
        if getattr(events_dao, "BATCH_ATOMIC", False):
            try:
                ids = await asyncio.to_thread(
                    events_dao.insert_batch, [e for _, e in valid],
                    auth.app_id, auth.channel_id)
            except StorageError as e:
                # atomic contract: nothing persisted — 500 for all is exact
                for slot, event in valid:
                    results[slot] = {"status": 500, "message": str(e)}
                    _bump_stats(request, auth.app_id, 500, event)
            else:
                if len(ids) != len(valid):
                    # contract violation AFTER a successful insert: events
                    # ARE persisted, so this must not read as retryable —
                    # distinct from the nothing-persisted 500 above
                    log.error("insert_batch returned %d ids for %d events",
                              len(ids), len(valid))
                    for slot, event in valid:
                        results[slot] = {
                            "status": 500,
                            "message": "backend returned inconsistent ids; "
                                       "events may already be persisted — "
                                       "do not blindly retry"}
                        _bump_stats(request, auth.app_id, 500, event)
                else:
                    for (slot, event), event_id in zip(valid, ids):
                        results[slot] = {"status": 201, "eventId": event_id}
                        _bump_stats(request, auth.app_id, 201, event)
        else:
            for slot, event in valid:
                status, body = await _insert_one(request, auth, event)
                results[slot] = {"status": status, **body}
    trace_event("ingest.ingress", batch=len(data),
                accepted=sum(1 for r in results
                             if r and r.get("status") == 201))
    resp = _ingest_response(request, 200, results)
    resp.headers[TRACE_HEADER] = rid
    return resp


async def handle_get_events(request: web.Request) -> web.Response:
    auth = await _authenticate(request)
    if isinstance(auth, web.Response):
        return auth
    q = request.query
    try:
        start_time = _parse_time(q.get("startTime"))
        until_time = _parse_time(q.get("untilTime"))
    except ValueError as e:
        return _json_error(400, f"Invalid time: {e}")
    try:
        limit = int(q.get("limit", 20))
        reversed_ = q.get("reversed", "false").lower() == "true"
    except ValueError as e:
        return _json_error(400, str(e))
    event_name = q.get("event")
    query = EventQuery(
        app_id=auth.app_id,
        channel_id=auth.channel_id,
        start_time=start_time,
        until_time=until_time,
        entity_type=q.get("entityType"),
        entity_id=q.get("entityId"),
        event_names=(event_name,) if event_name else None,
        target_entity_type=q.get("targetEntityType", EventQuery.target_entity_type),
        target_entity_id=q.get("targetEntityId", EventQuery.target_entity_id),
        limit=limit,
        reversed=reversed_,
    )
    events = Storage.get_events()
    try:
        found = await asyncio.to_thread(lambda: list(events.find(query)))
    except TableNotInitialized as e:
        # an app whose table was never init'd legitimately has no events
        return _json_error(404, str(e))
    except StorageError as e:
        # a real backend outage must NOT masquerade as "Not Found"
        return _json_error(500, str(e))
    if not found:
        # reference returns 404 on empty result (EventAPI.scala:255-260)
        return _json_error(404, "Not Found")
    return web.json_response([event_to_api_dict(e) for e in found])


async def handle_get_event(request: web.Request) -> web.Response:
    auth = await _authenticate(request)
    if isinstance(auth, web.Response):
        return auth
    event_id = request.match_info["event_id"]
    events = Storage.get_events()
    try:
        e = await asyncio.to_thread(events.get, event_id, auth.app_id, auth.channel_id)
    except TableNotInitialized as err:
        return _json_error(404, str(err))
    except StorageError as err:
        return _json_error(500, str(err))
    if e is None:
        return _json_error(404, "Not Found")
    return web.json_response(event_to_api_dict(e))


async def handle_delete_event(request: web.Request) -> web.Response:
    auth = await _authenticate(request)
    if isinstance(auth, web.Response):
        return auth
    event_id = request.match_info["event_id"]
    events = Storage.get_events()
    try:
        found = await asyncio.to_thread(
            events.delete, event_id, auth.app_id, auth.channel_id
        )
    except TableNotInitialized as err:
        return _json_error(404, str(err))
    except StorageError as err:
        return _json_error(500, str(err))
    if found:
        return web.json_response({"message": "Found"})
    return _json_error(404, "Not Found")


async def handle_stats(request: web.Request) -> web.Response:
    auth = await _authenticate(request)
    if isinstance(auth, web.Response):
        return auth
    stats: Stats | None = request.app.get(STATS_KEY)
    if stats is None:
        return _json_error(
            404, "To see stats, launch Event Server with --stats argument."
        )
    body = stats.get(auth.app_id)
    ingest: DurableIngestor | None = request.app.get(INGEST_KEY)
    if ingest is not None:
        # journal/drain counters are server-wide (one journal serves every
        # app), reported alongside the per-app ingest bookkeeping
        body["ingest"] = ingest.stats()
    adm: AdmissionController | None = request.app.get(ADMISSION_KEY)
    if adm is not None:
        body["admission"] = adm.stats()
    slo = stats.slo_summary()
    if slo is not None:
        body["slo"] = slo
    return web.json_response(body)


async def handle_health(request: web.Request) -> web.Response:
    """Liveness/readiness, engine-server parity (create_server.py): no
    auth — load balancers probe this. 200 with ``ok`` or ``degraded``
    (acks still flow in degraded; only the backend push path is down),
    and the journal lag / unsynced bytes an autoscaler or operator needs."""
    ingest: DurableIngestor | None = request.app.get(INGEST_KEY)
    if ingest is None:
        body = {"status": "ok", "live": True, "ready": True,
                "journal": None, "drain": None}
    else:
        body = ingest.health()
    return web.json_response(body)


async def handle_webhook_post(request: web.Request) -> web.Response:
    """JSON (.json suffix) and form connectors (Webhooks.scala:36-120)."""
    auth = await _authenticate(request, ingest=True)
    if isinstance(auth, web.Response):
        return auth
    shed = _admission_check(request, auth)
    if shed is not None:
        return shed
    name = request.match_info["name"]
    is_json = name.endswith(".json")
    connector = get_connector(name[:-5] if is_json else name)
    expected = JsonConnector if is_json else FormConnector
    if not isinstance(connector, expected):
        return _json_error(404, f"webhooks connection for {name} is not supported.")
    try:
        if is_json:
            payload = await request.json()
            if not isinstance(payload, dict):
                _bump_stats(request, auth.app_id, 400)
                return _json_error(400, "Webhook body must be a JSON object.")
        else:
            form = await request.post()
            payload = {k: form[k] for k in form}
        event_json = connector.to_event_json(payload)
    except ConnectorException as e:
        _bump_stats(request, auth.app_id, 400)
        return _json_error(400, str(e))
    except (json.JSONDecodeError, UnicodeDecodeError):
        _bump_stats(request, auth.app_id, 400)
        return _json_error(400, "Malformed body.")
    status, body = await _insert_event_dict(request, auth, event_json)
    return _ingest_response(request, status, body)


async def handle_webhook_get(request: web.Request) -> web.Response:
    auth = await _authenticate(request)
    if isinstance(auth, web.Response):
        return auth
    name = request.match_info["name"]
    is_json = name.endswith(".json")
    connector = get_connector(name[:-5] if is_json else name)
    expected = JsonConnector if is_json else FormConnector
    if isinstance(connector, expected):
        return web.json_response({"message": "Ok"})
    return _json_error(404, f"webhooks connection for {name} is not supported.")


def create_event_app(stats: bool = False,
                     ingestor: DurableIngestor | None = None,
                     admission: AdmissionController | None = None,
                     ) -> web.Application:
    """``ingestor`` switches the write path to durable journal-acked
    mode; its lifecycle (startup replay, background drainer, final
    fsync) rides the app's startup/cleanup signals. ``admission``
    enables 429 shedding (journal pressure + per-key rate limits) on
    the write endpoints."""
    # ISSUE 11 satellite: every response carries X-PIO-Request-ID, not
    # just the happy path — the webhook connectors, admission-shed 429s,
    # journal-full 503s and auth 401s never called ensure_request_id, so
    # their responses were unquotable in incident reports. setdefault in
    # the middleware keeps the handlers' own stamps authoritative.
    app = web.Application(middlewares=[make_trace_middleware()])
    if stats:
        from ..obs.slo import SloTracker, ingest_objectives
        app[STATS_KEY] = Stats(slo=SloTracker(ingest_objectives()))
    else:
        app[STATS_KEY] = None
    app[INGEST_KEY] = ingestor
    app[ADMISSION_KEY] = admission
    app.router.add_get("/", handle_root)
    app.router.add_post("/events.json", handle_post_event)
    app.router.add_post("/batch/events.json", handle_post_batch)
    app.router.add_get("/events.json", handle_get_events)
    app.router.add_get("/events/{event_id}.json", handle_get_event)
    app.router.add_delete("/events/{event_id}.json", handle_delete_event)
    app.router.add_get("/stats.json", handle_stats)
    app.router.add_get("/metrics", handle_metrics)
    app.router.add_get("/health.json", handle_health)
    app.router.add_post("/webhooks/{name}", handle_webhook_post)
    app.router.add_get("/webhooks/{name}", handle_webhook_get)
    if ingestor is not None:
        async def _start_ingest(app):
            # replay undrained records from a previous process BEFORE the
            # listener takes traffic (runner.setup runs startup first)
            await ingestor.start()

        async def _stop_ingest(app):
            await ingestor.aclose()

        app.on_startup.append(_start_ingest)
        app.on_cleanup.append(_stop_ingest)
    return app


def run_event_server(ip: str = "0.0.0.0", port: int = 7070,
                     stats: bool = False, journal_dir: str | None = None,
                     journal_fsync: str = "batch",
                     journal_max_mb: int = 256,
                     journal_partitions: int = 1,
                     admission: bool = False,
                     rate_limit_qps: float = 0.0,
                     rate_limit_burst: float = 0.0) -> None:
    """Blocking entry (reference: EventServer.createEventServer,
    EventAPI.scala:449-468; default port 7070). ``journal_dir`` enables
    durable ingestion (ack-from-journal, background drain);
    ``journal_partitions`` shards the journal + drainers by entity hash
    (per-entity ordering, concurrent fsync/drain — docs/operations.md
    "Ingestion at scale"); ``admission``/``rate_limit_qps`` enable 429
    overload shedding on the write endpoints (journal-fill pressure +
    per-access-key buckets)."""
    logging.basicConfig(level=logging.INFO)
    ingestor = None
    if journal_dir:
        ingestor = DurableIngestor(
            journal_dir, fsync=journal_fsync,
            max_bytes=int(journal_max_mb) * 1024 * 1024,
            partitions=journal_partitions)
        log.info("Durable ingestion: journal at %s (fsync=%s, cap=%dMB, "
                 "partitions=%d)", journal_dir, journal_fsync,
                 journal_max_mb, ingestor.partitions)
    controller = None
    if admission or rate_limit_qps > 0:
        controller = AdmissionController(
            "ingest",
            journal_fill=ingestor.fill_fraction if ingestor else None,
            backlog=(lambda: ingestor.journal.lag) if ingestor else None,
            drain_per_s=ingestor.drain_rate_per_s if ingestor else None,
            rate_limit_qps=rate_limit_qps,
            rate_limit_burst=rate_limit_burst)
        log.info("Admission control: journal-pressure shedding%s",
                 f" + {rate_limit_qps:g} qps/key rate limit"
                 if rate_limit_qps > 0 else "")
    log.info("Event server starting on %s:%d", ip, port)
    web.run_app(create_event_app(stats=stats, ingestor=ingestor,
                                 admission=controller),
                host=ip, port=port, print=None)
