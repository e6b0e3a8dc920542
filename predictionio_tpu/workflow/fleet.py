"""Serving fleet: a routing tier fronting M engine-server replicas
(ISSUE 17).

PR 16 made one engine-server process fast; this module makes the
deployment survive losing one. A ``FleetRouter`` is its own asyncio
process that fronts M replicas (each a full ``pio deploy`` process on
its own port) the way the reference ran N deployed engines behind a
load balancer — except this router understands the engine server's
health vocabulary instead of treating every 200 as equal:

- **Consistent-hash routing by entity id** — the same weighted
  rendezvous construction as :mod:`workflow.variants`, over the
  *eligible* replica set. Keeping a key on one replica is what keeps
  the per-key token buckets, sticky variant assignment, and delta
  patches coherent; the hash only re-buckets the keys whose owner
  actually changed when a replica leaves or rejoins.
- **Least-loaded spillover** — a hot key whose owner already carries
  ``spillover_inflight`` router-side in-flight requests spills to the
  least-loaded eligible sibling instead of queueing behind itself
  (stateless reads tolerate this; the patch tables on every replica
  converge through the fan-out below).
- **Per-replica health → breaker** — a probe loop polls each replica's
  ``/health.json`` every ``probe_interval_s`` and drives a classic
  closed→open→half-open breaker per replica (reported through the
  shared ``pio_breaker_state{subsystem="fleet.<name>"}`` families).
  A replica that answers 503-draining is *not* a failure: it leaves
  the eligible set gracefully and its in-flight requests finish.
- **Hedged retry** — ``/queries.json`` is an idempotent read, so a
  dispatch that dies (connection error, timeout, 5xx) retries on the
  next-ranked sibling, bounded by ``max_hedges`` and by the request's
  *remaining* deadline budget (the ``X-PIO-Deadline-Ms`` the router
  forwards is decremented by elapsed router time, so a replica never
  believes it has more budget than the client does).
- **Delta fan-out + epoch reconciliation** — the streaming updater
  publishes ``POST /reload/delta`` once, to the router; the router
  stamps a monotonically increasing *fleet epoch*, journals the patch
  (bounded), and fans it out to every reachable replica. A replica
  that missed patches (dead, draining, restarted) is detected by its
  lagging ``synced_epoch`` — or by its own patch epoch *regressing*,
  which is how a restart with an empty patch table looks — and is
  reconciled before it sees hashed traffic again: missed journal
  entries are replayed in order when the journal still covers the gap,
  else the replica takes a full ``GET /reload`` (fresh blob by the
  PR-4 sha256 integrity story) followed by a full journal replay.
- **Rolling reload with canary gate** — ``GET /reload`` on the router
  reloads replicas one at a time; after the first, up to
  ``canary_sample`` recent queries are replayed against the freshly
  reloaded replica and a not-yet-reloaded baseline and diffed with the
  PR-13 shadow-diff tiers; a mismatch fraction above
  ``canary_max_mismatch`` aborts the wave with the old model still
  serving on the remaining replicas.
- **SLO-burn drain** — when ``slo_drain_burn`` > 0, a replica whose
  fast-window burn rate (PR 11) meets it is drained from hashed
  traffic until the burn recovers; ``POST /fleet/drain`` is the manual
  equivalent (and optionally asks the replica to ``/stop``, which the
  replica honors with its own graceful drain).

Self-healing (ISSUE 18) adds two things on top:

- **Durable router state** — with ``state_dir`` set, the fleet epoch
  and the delta journal survive a router crash: every accepted delta
  is appended (CRC-framed, fsync'd — the ``storage/journal.py``
  segment writer) and the epoch marker is published by
  tmp+fsync+rename *before* the in-memory epoch bumps. A restarted
  router resumes at the durable epoch floor and bridges lagging
  replicas by journal replay instead of forcing full ``/reload``
  resyncs; a replica reporting a patch epoch *ahead* of a
  freshly-restarted router is recognized as router amnesia (state dir
  lost) — the router adopts the higher floor and counts
  ``pio_fleet_router_amnesia_total`` — never as replica corruption.
- **Quarantine** — ``workflow/supervise.FleetSupervisor`` owns the
  replica processes (reap, backoff respawn, crash-loop detection) and
  reports a crash-looping replica here via ``set_quarantined``; a
  quarantined replica leaves the eligible set so rendezvous traffic
  redistributes, until the supervisor's cooldown retry succeeds.
  ``GET /fleet/restart`` delegates a rolling, canary-gated restart
  wave to the attached supervisor.

Chaos sites (``faults.py`` harness): ``fleet.route`` at the
head of the routing decision, ``fleet.replica_dispatch`` before every
proxied query attempt (arm an error to prove the hedge path),
``fleet.delta_fanout`` before every per-replica delta POST (a lagging
replica must reconcile by epoch, never serve stale factors),
``router.state_write`` inside the atomic state write (kill-mid-write:
the previous file must survive). The replica-side
``replica.blob_pull`` site lives at the head of ``prepare_deploy``'s
blob fetch (core_workflow.py) — a poisoned pull either falls back to
an older COMPLETED instance or keeps the replica not-ready, and the
router keeps it out of rotation either way.
"""

from __future__ import annotations

import asyncio
import atexit
import hashlib
import json
import logging
import os
import subprocess
import sys
import tempfile
import threading
import time
from collections import deque
from dataclasses import dataclass
from pathlib import Path

import aiohttp
from aiohttp import web

from ..faults import FAULTS
from ..obs.aggregate import FleetCollector
from ..obs.breaker import breaker_set
from ..obs.metrics import METRICS
from ..obs.replay import PROVENANCE_HEADER, diff_tier
from ..obs.trace import TRACE_HEADER, ensure_request_id, trace_event
from ..storage.journal import EventJournal, JournalFull, iter_journal_records
from .variants import VARIANT_HEADER, entity_key

__all__ = [
    "DEADLINE_HEADER", "FLEET_REPLICA_HEADER", "Replica", "FleetRouter",
    "RouterStateStore", "create_fleet_app", "run_fleet_router",
    "spawn_replicas", "reap_replicas", "fleet_state_path",
    "write_fleet_state", "read_fleet_state",
]

log = logging.getLogger(__name__)

#: request-budget header (same wire name the engine server parses in
#: ``EngineServer.request_deadline``); the router forwards it DECREMENTED
#: by its own elapsed time so cross-process deadline expiry is exact
DEADLINE_HEADER = "X-PIO-Deadline-Ms"

#: response header naming the replica that actually answered — the
#: fleet-level analog of the provenance envelope's engineInstanceId
FLEET_REPLICA_HEADER = "X-PIO-Fleet-Replica"

_M_REQS = METRICS.counter(
    "pio_fleet_requests_total",
    "fleet router requests by outcome (ok/client_error/no_replica/"
    "upstream_error/deadline/draining/bad_request/route_error)",
    labelnames=("outcome",))
_M_REPLICA_REQS = METRICS.counter(
    "pio_fleet_replica_requests_total",
    "per-replica proxied query attempts by outcome",
    labelnames=("replica", "outcome"))
_M_HEDGES = METRICS.counter(
    "pio_fleet_hedges_total",
    "hedged retries of idempotent queries onto a sibling replica "
    "(rescued = a hedge answered after the owner failed)",
    labelnames=("outcome",))
_M_SPILL = METRICS.counter(
    "pio_fleet_spillover_total",
    "hot-key queries routed off their hash owner to the least-loaded "
    "eligible replica")
_M_ROUTE = METRICS.histogram(
    "pio_fleet_route_seconds",
    "router-observed end-to-end latency per proxied query")
_M_READY = METRICS.gauge(
    "pio_fleet_replica_ready",
    "router eligibility per replica (1 = receiving hashed traffic)",
    labelnames=("replica",))
_M_EPOCH = METRICS.gauge(
    "pio_fleet_epoch",
    "fleet-wide delta patch epoch (bumped per fan-out)")
_M_REPLICA_EPOCH = METRICS.gauge(
    "pio_fleet_replica_epoch",
    "last fleet epoch each replica is known to have applied",
    labelnames=("replica",))
_M_FANOUT = METRICS.counter(
    "pio_fleet_delta_fanout_total",
    "per-replica delta fan-out attempts by status",
    labelnames=("replica", "status"))
_M_RECONCILE = METRICS.counter(
    "pio_fleet_reconciliations_total",
    "epoch reconciliations per replica (replay = missed journal "
    "entries re-sent in order; full_reload = journal could not bridge "
    "the gap, replica reloaded the latest blob then replayed)",
    labelnames=("replica", "kind"))
_M_AMNESIA = METRICS.counter(
    "pio_fleet_router_amnesia_total",
    "a replica reported a patch epoch AHEAD of a freshly-restarted "
    "router (durable state lost) — the router adopts the higher floor "
    "instead of treating the replica as corrupt")
_M_EPOCH_FLOOR = METRICS.gauge(
    "pio_fleet_epoch_floor",
    "durable fleet epoch recovered from the state dir at router start")
_M_INCIDENTS = METRICS.counter(
    "pio_fleet_incidents_total",
    "correlated fleet-incident bundles written (a replica flight "
    "recorder fired; the router joined its dump with routing/breaker "
    "context)")


def _rendezvous(key: str, name: str) -> float:
    """Uniform (0,1] draw per (key, replica) — same construction as
    workflow/variants.bucket_for, unweighted (replicas are peers)."""
    h = hashlib.blake2b(f"{name}\x00{key}".encode("utf-8", "replace"),
                        digest_size=8).digest()
    return (int.from_bytes(h, "big") + 1) / (2 ** 64 + 1)


@dataclass
class Replica:
    """Router-side view of one engine-server replica."""

    name: str
    url: str
    breaker: str = "closed"          # closed | open | half_open
    failures: int = 0                # consecutive, feeds the breaker
    opened_at: float = 0.0           # monotonic instant the breaker opened
    live: bool = False
    ready: bool = False              # replica-reported readiness
    status: str = "unknown"          # ok/brownout/degraded/draining/...
    draining: bool = False
    admin_drained: bool = False      # POST /fleet/drain
    slo_drained: bool = False        # burn-rate policy
    quarantined: bool = False        # supervisor crash-loop verdict
    synced_epoch: int = 0            # last fleet epoch applied (-1 = resync)
    reported_epoch: int = 0          # replica's OWN patch epoch, last seen
    start_time: str | None = None    # replica startTime — restart detector
    inflight: int = 0                # router-side in-flight requests
    probed_at: float = 0.0
    requests: int = 0
    last_error: str | None = None
    slo_burn: float = 0.0
    pid: int | None = None           # set by `pio fleet start` (local fleet)

    def snapshot(self, fleet_epoch: int) -> dict:
        return {
            "name": self.name,
            "url": self.url,
            "breaker": self.breaker,
            "live": self.live,
            "ready": self.ready,
            "status": self.status,
            "draining": self.draining,
            "adminDrained": self.admin_drained,
            "sloDrained": self.slo_drained,
            "quarantined": self.quarantined,
            "sloBurn": round(self.slo_burn, 4),
            "syncedEpoch": self.synced_epoch,
            "patchEpoch": self.reported_epoch,
            "epochLag": max(0, fleet_epoch - max(0, self.synced_epoch)),
            "inflight": self.inflight,
            "requests": self.requests,
            "lastError": self.last_error,
            "pid": self.pid,
        }


def _atomic_write_json(path: Path, obj: dict) -> None:
    """Crash-safe JSON publish: write a UNIQUE sibling tmp file
    (``mkstemp`` — concurrent writers such as the supervisor thread's
    state_writer and the CLI main thread must never interleave on one
    tmp name), fsync it, then ``os.replace`` over the target — a kill
    at ANY instant leaves either the previous complete file or the new
    complete file, never a torn one. The ``router.state_write`` chaos
    site fires in the widest kill window (tmp durable, rename not yet
    done); an armed error must leave the previous file intact."""
    path.parent.mkdir(parents=True, exist_ok=True)
    fd, tmp_name = tempfile.mkstemp(prefix=path.name + ".",
                                    suffix=".tmp", dir=path.parent)
    tmp = Path(tmp_name)
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            f.write(json.dumps(obj, indent=2))
            f.flush()
            os.fchmod(f.fileno(), 0o644)
            os.fsync(f.fileno())
        FAULTS.fire("router.state_write")
        os.replace(tmp, path)
    except BaseException:
        try:
            tmp.unlink()
        except OSError:
            pass
        raise


class RouterStateStore:
    """Durable fleet-router state under one directory (ISSUE 18):

    - ``epoch.json`` — the fleet-epoch marker, published atomically
      (tmp+fsync+rename) so a crash can never tear it;
    - ``delta-journal/`` — every accepted delta body as a CRC-framed
      record (``storage/journal.py`` segment writer, ``fsync="always"``
      — a delta is only acked after it is durable), each payload an
      8-byte little-endian fleet epoch followed by the raw JSON body.

    ``load()`` trusts whichever source is further ahead: the journal's
    last record wins over a marker that lost the race with a crash
    (the marker is written after the journal append)."""

    def __init__(self, directory: str | os.PathLike, *,
                 max_bytes: int = 16 * 1024 * 1024):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self._marker = self.dir / "epoch.json"
        seg = max(64 * 1024, int(max_bytes) // 16)
        self._journal = EventJournal(
            self.dir / "delta-journal", fsync="always",
            max_bytes=max(seg + 1, int(max_bytes)), segment_max_bytes=seg)
        #: marker writes come from concurrent ``to_thread`` workers
        #: (delta appends, amnesia floor adoptions for several replicas
        #: probed at once) — serialize them and never let a slow writer
        #: regress the published epoch below one already on disk
        self._marker_mutex = threading.Lock()
        self._published_epoch = 0

    def load(self) -> tuple[int, list[tuple[int, bytes]]]:
        """Durable (epoch floor, [(epoch, raw delta), ...]) oldest-first."""
        epoch = 0
        try:
            epoch = int(json.loads(self._marker.read_text())
                        .get("epoch", 0) or 0)
        except (OSError, ValueError, TypeError, AttributeError):
            epoch = 0
        entries: list[tuple[int, bytes]] = []
        for payload in iter_journal_records(self.dir / "delta-journal"):
            if len(payload) < 8:
                continue
            entries.append((int.from_bytes(payload[:8], "little"),
                            payload[8:]))
        if entries:
            epoch = max(epoch, entries[-1][0])
        with self._marker_mutex:
            self._published_epoch = max(self._published_epoch, epoch)
        return epoch, entries

    def append(self, epoch: int, raw: bytes) -> None:
        """Durably append one delta, then publish the epoch marker.
        Drop-oldest on ``JournalFull`` (same cursor-advance GC as
        ``obs/capture.CaptureRing``): old deltas past the retention
        window force laggards to a full reload anyway."""
        payload = epoch.to_bytes(8, "little") + raw
        for _ in range(64):
            try:
                self._journal.append(payload)
                break
            except JournalFull:
                recs, pos = self._journal.peek_batch(256)
                if not recs:
                    raise
                before = self._journal.size_bytes()
                self._journal.advance(pos)
                if self._journal.size_bytes() >= before:
                    raise
        else:
            # retry budget exhausted without an append: the delta was
            # NEVER made durable, so the epoch marker must not be
            # published (the caller 500s and the updater retries) —
            # falling through would ack an epoch the journal can't replay
            raise JournalFull(
                f"delta journal still full after 64 GC passes "
                f"(epoch {epoch}, {len(payload)} bytes)")
        self.write_epoch(epoch)

    def write_epoch(self, epoch: int) -> None:
        with self._marker_mutex:
            if int(epoch) <= self._published_epoch:
                return              # a concurrent writer already got further
            _atomic_write_json(self._marker, {"epoch": int(epoch),
                                              "ts": time.time()})
            self._published_epoch = int(epoch)

    def close(self) -> None:
        try:
            self._journal.close()
        except Exception:  # noqa: BLE001 — closing must never raise
            log.exception("router state journal close failed")


ROUTER_KEY = web.AppKey("fleet_router", object)

#: dispatch failures the hedge path may retry — the request never
#: reached a handler (or the replica died under it), and /queries.json
#: is an idempotent read
_RETRYABLE = (aiohttp.ClientError, asyncio.TimeoutError, ConnectionError,
              OSError)


class FleetRouter:
    """Routing tier over M engine-server replicas (see module doc)."""

    def __init__(
        self,
        replica_urls: list[str] | tuple[str, ...],
        *,
        probe_interval_s: float = 1.0,
        probe_timeout_s: float = 2.0,
        breaker_threshold: int = 1,
        breaker_reset_s: float = 3.0,
        dispatch_timeout_s: float = 10.0,
        default_deadline_ms: float = 0.0,
        max_hedges: int = 1,
        hedge_floor_ms: float = 5.0,
        spillover_inflight: int = 32,
        journal_max: int = 64,
        reload_timeout_s: float = 120.0,
        slo_drain_burn: float = 0.0,
        canary_sample: int = 8,
        canary_max_mismatch: float = 0.25,
        recent_ring: int = 64,
        state_dir: str | os.PathLike | None = None,
        state_max_bytes: int = 16 * 1024 * 1024,
        collect_metrics: bool = True,
        metrics_stale_after_s: float = 10.0,
        scrape_timeout_s: float | None = None,
        outlier_band: float = 0.75,
        incident_dir: str | os.PathLike | None = None,
        incident_cooldown_s: float = 30.0,
    ):
        if not replica_urls:
            raise ValueError("a fleet needs at least one replica URL")
        self.replicas: list[Replica] = [
            Replica(name=f"r{i}", url=u.rstrip("/"))
            for i, u in enumerate(replica_urls)]
        self.probe_interval_s = max(0.05, probe_interval_s)
        self.probe_timeout_s = max(0.1, probe_timeout_s)
        self.breaker_threshold = max(1, breaker_threshold)
        self.breaker_reset_s = max(0.1, breaker_reset_s)
        self.dispatch_timeout_s = max(0.1, dispatch_timeout_s)
        self.default_deadline_ms = max(0.0, default_deadline_ms)
        self.max_hedges = max(0, max_hedges)
        self.hedge_floor_ms = max(0.0, hedge_floor_ms)
        self.spillover_inflight = max(1, spillover_inflight)
        self.reload_timeout_s = reload_timeout_s
        self.slo_drain_burn = max(0.0, slo_drain_burn)
        self.canary_sample = max(0, canary_sample)
        self.canary_max_mismatch = max(0.0, canary_max_mismatch)
        self.fleet_epoch = 0
        #: bounded journal of (fleet_epoch, raw delta body) — the replay
        #: source for lagging replicas; past its window a full reload is
        #: the only safe reconciliation
        self._journal: deque[tuple[int, bytes]] = deque(maxlen=max(1, journal_max))
        #: recent query bodies, the canary replay sample
        self._recent: deque[dict] = deque(maxlen=max(1, recent_ring))
        self._session: aiohttp.ClientSession | None = None
        #: serializes epoch allocation + durable append + bump: the
        #: awaited journal write in handle_reload_delta yields to the
        #: event loop, and two concurrent deltas must never read the
        #: same fleet_epoch (two deltas journaled under one epoch would
        #: let a replica that applied only the first report fully
        #: synced, hiding the second forever)
        self._epoch_lock = asyncio.Lock()
        self._probe_task: asyncio.Task | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._draining = False
        self._inflight = 0
        self.start_time = time.time()
        # ISSUE 20: fleet observability plane. The collector rides the
        # probe loop (scrapes gathered alongside probes, each with its
        # own timeout) and owns the exact merge; the router keeps the
        # hop log for /fleet/trace.json and writes correlated incident
        # bundles when a replica's flight recorder fires.
        self.collector: FleetCollector | None = (
            FleetCollector(stale_after_s=metrics_stale_after_s,
                           outlier_band=outlier_band)
            if collect_metrics else None)
        self.scrape_timeout_s = (probe_timeout_s if scrape_timeout_s is None
                                 else max(0.1, scrape_timeout_s))
        self.incident_dir = (Path(incident_dir) if incident_dir is not None
                             else None)
        self.incident_cooldown_s = max(0.0, incident_cooldown_s)
        self._last_incident: dict[str, float] = {}
        #: recent routed hops — the router's side of `pio trace <rid>`
        self._route_log: deque[dict] = deque(maxlen=512)
        #: attached by `pio fleet start --supervise` — the rolling
        #: restart endpoint delegates here
        self.supervisor = None
        self._store: RouterStateStore | None = None
        if state_dir is not None:
            # durable state: resume at the epoch floor that survived
            # the last router process, with the retained delta journal
            # rehydrated as the replay source for lagging replicas
            self._store = RouterStateStore(state_dir,
                                           max_bytes=state_max_bytes)
            floor, entries = self._store.load()
            self.fleet_epoch = floor
            for entry in entries:
                self._journal.append(entry)
            _M_EPOCH.set(floor)
            _M_EPOCH_FLOOR.set(floor)
            if floor:
                log.info("fleet router resumed at durable epoch %d "
                         "(%d journal entries)", floor, len(entries))
        for r in self.replicas:
            breaker_set(f"fleet.{r.name}", "closed")
            _M_READY.set(0, replica=r.name)
            _M_REPLICA_EPOCH.set(0, replica=r.name)

    # -- lifecycle ---------------------------------------------------------
    async def start(self) -> None:
        """Create the client session, run ONE full probe round (so the
        eligible set is known before the first query), start the loop."""
        self._session = aiohttp.ClientSession()
        self._loop = asyncio.get_running_loop()
        await self._probe_all()
        self._probe_task = asyncio.create_task(self._probe_loop())

    async def close(self) -> None:
        self._draining = True
        deadline = time.monotonic() + 10.0
        while self._inflight > 0 and time.monotonic() < deadline:
            await asyncio.sleep(0.02)
        if self._probe_task is not None:
            self._probe_task.cancel()
            try:
                await self._probe_task
            except (asyncio.CancelledError, Exception):  # noqa: BLE001
                pass
            self._probe_task = None
        if self._session is not None:
            await self._session.close()
            self._session = None
        if self._store is not None:
            self._store.close()

    # -- health / breaker --------------------------------------------------
    def _set_breaker(self, r: Replica, state: str) -> None:
        if r.breaker == state:
            return
        prev, r.breaker = r.breaker, state
        breaker_set(f"fleet.{r.name}", state, prev=prev)
        trace_event("fleet.breaker", replica=r.name, state=state, prev=prev)
        if state == "open":
            r.opened_at = time.monotonic()

    def _record_failure(self, r: Replica, why: str) -> None:
        r.failures += 1
        r.last_error = why
        if r.breaker == "half_open" or r.failures >= self.breaker_threshold:
            self._set_breaker(r, "open")
            self._mark_ready(r, False)

    def _record_success(self, r: Replica) -> None:
        r.failures = 0
        r.last_error = None
        if r.breaker != "closed":
            self._set_breaker(r, "closed")

    def _mark_ready(self, r: Replica, ready: bool) -> None:
        r.ready = ready
        _M_READY.set(1 if self._eligible_one(r) else 0, replica=r.name)

    def _eligible_one(self, r: Replica) -> bool:
        return (r.breaker == "closed" and r.live and r.ready
                and not r.draining and not r.admin_drained
                and not r.slo_drained and not r.quarantined
                and r.synced_epoch >= self.fleet_epoch)

    def _eligible(self) -> list[Replica]:
        return [r for r in self.replicas if self._eligible_one(r)]

    async def _probe_loop(self) -> None:
        while True:
            await asyncio.sleep(self.probe_interval_s)
            try:
                await self._probe_all()
            except asyncio.CancelledError:
                raise
            except Exception:  # noqa: BLE001 — the loop must survive
                log.exception("fleet probe round failed")

    async def _probe_all(self) -> None:
        tasks = [self._probe(r) for r in self.replicas]
        if self.collector is not None:
            # scrapes ride the probe cadence but are separate coroutines
            # with their own timeout: a hung /metrics page can neither
            # stall a health probe nor wedge the round
            tasks += [self._scrape(r) for r in self.replicas]
        await asyncio.gather(*tasks, return_exceptions=True)

    async def _scrape(self, r: Replica) -> None:
        """Pull one replica's /metrics + /stats.json into the collector.
        Failure is handled like a probe failure: the last snapshot is
        kept (it ages out of merges past ``metrics_stale_after_s``) and
        the probe loop never crashes."""
        timeout = aiohttp.ClientTimeout(total=self.scrape_timeout_s)
        try:
            async with self._session.get(f"{r.url}/metrics",
                                         timeout=timeout) as resp:
                if resp.status != 200:
                    raise RuntimeError(f"HTTP {resp.status}")
                text = await resp.text()
            stats: dict = {}
            async with self._session.get(f"{r.url}/stats.json",
                                         timeout=timeout) as resp:
                if resp.status == 200:
                    stats = await resp.json()
        except asyncio.CancelledError:
            raise
        except Exception as e:  # noqa: BLE001 — scrape failure is routine
            self.collector.mark_failed(r.name, f"scrape: {type(e).__name__}")
            return
        try:
            # parse + merge bookkeeping off the event loop: a scrape
            # must not add latency blips to concurrently routed queries
            fired = await asyncio.to_thread(
                self.collector.ingest, r.name, text, stats)
        except Exception:  # noqa: BLE001 — aggregation never kills probing
            log.exception("metric ingest failed for %s", r.name)
            return
        if fired:
            await self._fleet_incident(r)

    def _incident_path_dir(self) -> Path:
        return (self.incident_dir if self.incident_dir is not None
                else fleet_state_path().parent / "fleet-incidents")

    async def _fleet_incident(self, trigger: Replica) -> None:
        """A replica's flight recorder fired between scrapes: pull every
        replica's /debug/flight.json and write ONE correlated bundle
        with the router-side routing/breaker context for the window."""
        now = time.monotonic()
        last = self._last_incident.get(trigger.name)
        if last is not None and now - last < self.incident_cooldown_s:
            return
        self._last_incident[trigger.name] = now
        flights: dict[str, dict] = {}

        async def _pull(r: Replica) -> None:
            try:
                async with self._session.get(
                        f"{r.url}/debug/flight.json",
                        timeout=aiohttp.ClientTimeout(
                            total=self.scrape_timeout_s)) as resp:
                    if resp.status == 200:
                        flights[r.name] = await resp.json()
            except Exception:  # noqa: BLE001 — a dead sibling still bundles
                pass

        await asyncio.gather(*(_pull(r) for r in self.replicas))
        bundle = {
            "trigger": trigger.name,
            "wallTime": time.time(),
            "router": {
                "status": self.status(),
                "breakers": {r.name: r.breaker for r in self.replicas},
                "recentRoutes": list(self._route_log)[-64:],
            },
            "fleet": {
                "slo": self.collector.fleet_slo(),
                "outliers": self.collector.outliers(),
                "replicas": self.collector.replica_view(),
            },
            "replicas": flights,
        }
        directory = self._incident_path_dir()
        path = directory / f"fleet-incident-{int(time.time() * 1e3)}.json"
        try:
            await asyncio.to_thread(directory.mkdir, exist_ok=True,
                                    parents=True)
            await asyncio.to_thread(_atomic_write_json, path, bundle)
        except OSError:
            log.exception("fleet incident bundle write failed")
            return
        _M_INCIDENTS.inc()
        trace_event("fleet.incident", replica=trigger.name, path=str(path))
        log.warning("fleet incident bundle written: %s (trigger %s)",
                    path, trigger.name)

    async def _probe(self, r: Replica) -> None:
        now = time.monotonic()
        if r.breaker == "open":
            if now - r.opened_at < self.breaker_reset_s:
                return  # stay open until the reset window elapses
            self._set_breaker(r, "half_open")
        try:
            async with self._session.get(
                    f"{r.url}/health.json",
                    timeout=aiohttp.ClientTimeout(total=self.probe_timeout_s),
            ) as resp:
                code = resp.status
                body = await resp.json()
        except Exception as e:  # noqa: BLE001 — every probe failure counts
            r.live = False
            self._record_failure(r, f"probe: {type(e).__name__}")
            self._mark_ready(r, False)
            return
        r.probed_at = now
        r.live = bool(body.get("live", True))
        r.status = str(body.get("status", "unknown"))
        r.draining = code == 503 or r.status == "draining"
        if r.draining:
            # graceful exit is NOT a fault: no breaker failure, just out
            # of the eligible set while it finishes in-flight work
            self._mark_ready(r, False)
            return
        self._record_success(r)
        reported = int((body.get("model") or {}).get("patchEpoch", 0) or 0)
        started = body.get("startTime")
        first_sight = r.start_time is None
        restarted = (r.start_time is not None and started != r.start_time)
        if restarted or reported < r.reported_epoch:
            # a fresh process (or one that lost its patch table) looks
            # like a patch-epoch regression: force a full resync
            log.info("replica %s restarted (epoch %d -> %d); resyncing",
                     r.name, r.reported_epoch, reported)
            r.synced_epoch = -1
        elif first_sight and reported > 0:
            # first contact by THIS router process with a replica that
            # already holds patches. Deltas reach replicas only through
            # a router, so the replica's own patch epoch IS its fleet
            # sync point: adopt it instead of forcing a resync. A
            # replica AHEAD of the router's epoch means the router lost
            # its durable state (amnesia) — adopt the higher floor and
            # count it; it is never replica corruption.
            if reported > self.fleet_epoch:
                log.warning(
                    "replica %s reports epoch %d ahead of router epoch "
                    "%d: router amnesia — adopting the replica's floor",
                    r.name, reported, self.fleet_epoch)
                _M_AMNESIA.inc()
                self.fleet_epoch = reported
                _M_EPOCH.set(reported)
                trace_event("fleet.amnesia", replica=r.name,
                            epoch=reported)
                if self._store is not None:
                    try:
                        await asyncio.to_thread(self._store.write_epoch,
                                                reported)
                    except Exception:  # noqa: BLE001 — floor is advisory
                        log.exception("epoch marker write failed")
            r.synced_epoch = max(r.synced_epoch,
                                 min(reported, self.fleet_epoch))
            _M_REPLICA_EPOCH.set(r.synced_epoch, replica=r.name)
        r.start_time = started
        r.reported_epoch = reported
        if self.slo_drain_burn > 0:
            r.slo_burn = _max_burn(body.get("slo"))
            was = r.slo_drained
            want = r.slo_burn >= self.slo_drain_burn
            if want and self.collector is not None:
                # ISSUE 20: the drain signal sees fleet truth. Drain a
                # burning replica only while the REST of the fleet is
                # healthy enough to absorb it — when the merged burn of
                # the other replicas also breaches, the problem is the
                # fleet (bad deploy, overload), and removing capacity
                # would make it worse.
                rest = self.collector.fleet_burn(exclude=r.name)
                if rest is not None and rest >= self.slo_drain_burn:
                    want = False
                    if not was:
                        trace_event("fleet.slo_drain_hold", replica=r.name,
                                    burn=r.slo_burn, fleetBurn=rest)
            r.slo_drained = want
            if r.slo_drained != was:
                trace_event("fleet.slo_drain", replica=r.name,
                            active=r.slo_drained, burn=r.slo_burn)
        if r.synced_epoch < self.fleet_epoch:
            if not await self._reconcile(r):
                self._mark_ready(r, False)
                return
        self._mark_ready(r, bool(body.get("ready", code == 200)))

    async def _reconcile(self, r: Replica) -> bool:
        """Bring a lagging replica to the live fleet epoch BEFORE it
        rejoins the eligible set. Returns True when current."""
        target = self.fleet_epoch
        journal = list(self._journal)
        floor = journal[0][0] if journal else target + 1
        covered = r.synced_epoch >= 0 and floor <= r.synced_epoch + 1
        kind = "replay" if covered else "full_reload"
        try:
            if kind == "full_reload":
                # the journal cannot bridge the gap: pull the latest
                # blob (sha256-verified replica-side) then replay the
                # whole retained journal in order — idempotent, ends at
                # the newest factors
                async with self._session.get(
                        f"{r.url}/reload",
                        timeout=aiohttp.ClientTimeout(
                            total=self.reload_timeout_s)) as resp:
                    if resp.status != 200:
                        raise RuntimeError(f"reload HTTP {resp.status}")
                to_replay = journal
            else:
                to_replay = [(e, b) for e, b in journal
                             if e > r.synced_epoch]
            for epoch, raw in to_replay:
                async with self._session.post(
                        f"{r.url}/reload/delta", data=raw,
                        headers={"Content-Type": "application/json"},
                        timeout=aiohttp.ClientTimeout(
                            total=self.probe_timeout_s * 5)) as resp:
                    if resp.status != 200:
                        raise RuntimeError(
                            f"delta replay epoch {epoch} HTTP {resp.status}")
                    out = await resp.json()
                    r.reported_epoch = int(out.get("epoch", 0) or 0)
        except Exception as e:  # noqa: BLE001 — reconcile retries next probe
            log.warning("reconcile(%s) failed for %s: %r", kind, r.name, e)
            r.last_error = f"reconcile: {type(e).__name__}"
            return False
        r.synced_epoch = target
        _M_REPLICA_EPOCH.set(target, replica=r.name)
        _M_RECONCILE.inc(replica=r.name, kind=kind)
        trace_event("fleet.reconcile", replica=r.name, kind=kind,
                    epoch=target, replayed=len(to_replay))
        return True

    # -- routing -----------------------------------------------------------
    def _rank(self, key: str) -> list[Replica]:
        elig = self._eligible()
        return sorted(elig, key=lambda r: _rendezvous(key, r.name),
                      reverse=True)

    def _pick(self, ranked: list[Replica]) -> tuple[Replica, bool]:
        """Hash owner, unless the owner is hot and a sibling is
        meaningfully less loaded (least-loaded spillover)."""
        owner = ranked[0]
        if (len(ranked) > 1
                and owner.inflight >= self.spillover_inflight):
            least = min(ranked, key=lambda r: r.inflight)
            if least is not owner and least.inflight < owner.inflight:
                return least, True
        return owner, False

    async def handle_query(self, request: web.Request) -> web.Response:
        t0 = time.monotonic()
        rid = ensure_request_id(request.headers.get(TRACE_HEADER))
        base_headers = {TRACE_HEADER: rid}

        def _fail(outcome: str, message: str, status: int) -> web.Response:
            _M_REQS.inc(outcome=outcome)
            _M_ROUTE.record(time.monotonic() - t0)
            return web.json_response({"message": message}, status=status,
                                     headers=base_headers)

        if self._draining:
            return _fail("draining",
                         "Fleet router is draining; not accepting queries.",
                         503)
        raw = await request.read()
        try:
            query = json.loads(raw)
            if not isinstance(query, dict):
                raise ValueError("query must be a JSON object")
        except (ValueError, UnicodeDecodeError):
            return _fail("bad_request", "Malformed JSON body.", 400)
        try:
            await FAULTS.afire("fleet.route")
        except Exception as e:  # noqa: BLE001 — a routing-tier bug is a 500
            return _fail("route_error", f"routing failure: {e}", 500)
        deadline = self._request_deadline(request, t0)
        self._recent.append(query)
        self._inflight += 1
        try:
            return await self._route(request, query, raw, rid, t0, deadline,
                                     _fail)
        finally:
            self._inflight -= 1

    def _request_deadline(self, request: web.Request,
                          t0: float) -> float | None:
        ms = self.default_deadline_ms
        hdr = request.headers.get(DEADLINE_HEADER)
        if hdr is not None:
            try:
                client_ms = float(hdr)
                if client_ms > 0:
                    ms = min(ms, client_ms) if ms > 0 else client_ms
            except ValueError:
                pass
        return t0 + ms / 1e3 if ms > 0 else None

    async def _route(self, request, query, raw, rid, t0, deadline,
                     _fail) -> web.Response:
        key = entity_key(query)
        ranked = self._rank(key)
        if not ranked:
            return _fail("no_replica",
                         "No eligible replica (fleet degraded).", 503)
        first, spilled = self._pick(ranked)
        if spilled:
            _M_SPILL.inc()
        order = [first] + [r for r in ranked if r is not first]
        attempts = min(1 + self.max_hedges, len(order))
        last_why = "unreachable"
        hedged = False
        for i, r in enumerate(order[:attempts]):
            remaining = (None if deadline is None
                         else deadline - time.monotonic())
            if remaining is not None and remaining * 1e3 <= self.hedge_floor_ms:
                break  # budget exhausted: do not start a doomed attempt
            headers = {"Content-Type": "application/json",
                       TRACE_HEADER: rid}
            if remaining is not None:
                # the cross-process deadline: client budget minus time
                # already burned in the router (and earlier attempts)
                headers[DEADLINE_HEADER] = f"{remaining * 1e3:.1f}"
            for passthrough in (VARIANT_HEADER, "X-PIO-Access-Key"):
                v = request.headers.get(passthrough)
                if v is not None:
                    headers[passthrough] = v
            timeout_s = (self.dispatch_timeout_s if remaining is None
                         else min(self.dispatch_timeout_s, remaining))
            hedged = hedged or i > 0
            try:
                await FAULTS.afire("fleet.replica_dispatch")
                r.inflight += 1
                try:
                    async with self._session.post(
                            f"{r.url}/queries.json", data=raw,
                            headers=headers,
                            timeout=aiohttp.ClientTimeout(total=timeout_s),
                    ) as resp:
                        status = resp.status
                        payload = await resp.read()
                        resp_headers = resp.headers
                finally:
                    r.inflight -= 1
            except _RETRYABLE as e:
                self._record_failure(r, f"dispatch: {type(e).__name__}")
                _M_REPLICA_REQS.inc(replica=r.name, outcome="conn_error")
                last_why = f"{type(e).__name__} from {r.name}"
                continue
            except Exception as e:  # noqa: BLE001 — injected faults hedge too
                self._record_failure(r, f"dispatch: {type(e).__name__}")
                _M_REPLICA_REQS.inc(replica=r.name, outcome="error")
                last_why = f"{type(e).__name__} from {r.name}"
                continue
            if status >= 500:
                # the replica answered but could not serve — still safe
                # to hedge an idempotent read
                self._record_failure(r, f"dispatch: HTTP {status}")
                _M_REPLICA_REQS.inc(replica=r.name, outcome="5xx")
                last_why = f"HTTP {status} from {r.name}"
                continue
            # authoritative answer (2xx — or 4xx: shed/bad request are
            # the replica speaking for the fleet, not a fleet fault)
            self._record_success(r)
            r.requests += 1
            _M_REPLICA_REQS.inc(
                replica=r.name,
                outcome="ok" if status < 400 else "client_error")
            if hedged:
                _M_HEDGES.inc(outcome="rescued")
            _M_REQS.inc(outcome="ok" if status < 400 else "client_error")
            wall = time.monotonic() - t0
            _M_ROUTE.record(wall)
            trace_event("fleet.route", replica=r.name, http=status,
                        hedges=i, spillover=spilled,
                        ms=round(wall * 1e3, 3))
            self._route_log.append({
                "rid": rid, "replica": r.name, "http": status,
                "hedges": i, "spillover": spilled,
                "ms": round(wall * 1e3, 3), "wallTime": time.time()})
            out_headers = {TRACE_HEADER: rid, FLEET_REPLICA_HEADER: r.name}
            for h in (PROVENANCE_HEADER, VARIANT_HEADER, "Retry-After"):
                v = resp_headers.get(h)
                if v is not None:
                    out_headers[h] = v
            return web.Response(
                body=payload, status=status,
                content_type="application/json", headers=out_headers)
        if hedged:
            _M_HEDGES.inc(outcome="failed")
        self._route_log.append({
            "rid": rid, "replica": None, "outcome": "failed",
            "error": last_why, "ms": round((time.monotonic() - t0) * 1e3, 3),
            "wallTime": time.time()})
        if deadline is not None and time.monotonic() >= deadline - (
                self.hedge_floor_ms / 1e3):
            return _fail("deadline",
                         f"deadline expired during fleet routing "
                         f"(last: {last_why})", 504)
        return _fail("upstream_error",
                     f"every dispatch attempt failed (last: {last_why})",
                     502)

    # -- delta fan-out -----------------------------------------------------
    async def handle_reload_delta(self, request: web.Request) -> web.Response:
        rid = ensure_request_id(request.headers.get(TRACE_HEADER))
        headers = {TRACE_HEADER: rid}
        if self._draining:
            return web.json_response(
                {"message": "Fleet router is draining."}, status=503,
                headers=headers)
        raw = await request.read()
        try:
            body = json.loads(raw)
            users = body.get("users") if isinstance(body, dict) else None
            if not isinstance(users, dict) or not users:
                raise ValueError
        except (ValueError, UnicodeDecodeError):
            return web.json_response(
                {"message": 'Body must be {"users": {user_id: [factor]}}.'},
                status=400, headers=headers)
        async with self._epoch_lock:
            # allocate + journal + bump under one lock: the awaited
            # durable append yields to the event loop, and a concurrent
            # delta reading the same fleet_epoch would journal two
            # different deltas under ONE epoch — a replica that applied
            # only the first would look fully synced and never be
            # reconciled
            epoch = self.fleet_epoch + 1
            if self._store is not None:
                # durability BEFORE visibility: the delta is journaled
                # and the epoch marker published before the in-memory
                # epoch bumps, so a router killed at any instant either
                # never acked this epoch or can replay it after restart
                try:
                    await asyncio.to_thread(self._store.append, epoch, raw)
                except Exception as e:  # noqa: BLE001 — updater retries
                    log.exception("durable delta append failed at epoch %d",
                                  epoch)
                    return web.json_response(
                        {"message": f"router state write failed: {e}"},
                        status=500, headers=headers)
            self.fleet_epoch = epoch
            _M_EPOCH.set(epoch)
            self._journal.append((epoch, raw))
        results: dict[str, dict] = {}

        async def _one(r: Replica) -> None:
            try:
                await FAULTS.afire("fleet.delta_fanout")
                async with self._session.post(
                        f"{r.url}/reload/delta", data=raw,
                        headers={"Content-Type": "application/json",
                                 TRACE_HEADER: rid},
                        timeout=aiohttp.ClientTimeout(
                            total=self.probe_timeout_s * 5)) as resp:
                    out = (await resp.json()
                           if resp.status in (200, 400, 503) else {})
                    if resp.status == 200:
                        # max(): fan-outs for successive epochs overlap
                        # (only allocation is serialized), and a slow
                        # reply for epoch N must not regress a replica
                        # already synced to N+1
                        r.synced_epoch = max(r.synced_epoch, epoch)
                        r.reported_epoch = int(out.get("epoch", 0) or 0)
                        _M_REPLICA_EPOCH.set(r.synced_epoch, replica=r.name)
                        _M_FANOUT.inc(replica=r.name, status="ok")
                        results[r.name] = {"ok": True,
                                           "epoch": r.reported_epoch}
                    else:
                        _M_FANOUT.inc(replica=r.name, status="error")
                        results[r.name] = {"ok": False,
                                           "status": resp.status,
                                           "message": out.get("message")}
            except Exception as e:  # noqa: BLE001 — laggards reconcile later
                _M_FANOUT.inc(replica=r.name, status="error")
                results[r.name] = {"ok": False, "error": str(e)}

        targets = [r for r in self.replicas
                   if r.breaker == "closed" and not r.admin_drained]
        for r in self.replicas:
            if r not in targets:
                _M_FANOUT.inc(replica=r.name, status="skipped")
                results[r.name] = {"ok": False, "skipped": True,
                                   "breaker": r.breaker}
        await asyncio.gather(*(_one(r) for r in targets))
        applied = sorted(n for n, v in results.items() if v.get("ok"))
        trace_event("fleet.delta", epoch=epoch, applied=len(applied),
                    replicas=len(self.replicas))
        # 200 as long as ONE replica took the patch: the epoch is
        # journaled and every laggard reconciles before rejoining, so
        # the updater's cursor may commit; zero takers is transient
        # (replays against the same journal entry are idempotent)
        return web.json_response(
            {"message": "Patched" if applied else "No replica reachable",
             "epoch": epoch, "applied": applied, "replicas": results},
            status=200 if applied else 503, headers=headers)

    # -- rolling reload + canary -------------------------------------------
    async def handle_reload(self, request: web.Request) -> web.Response:
        try:
            sample = int(request.query.get("canary", self.canary_sample))
        except ValueError:
            sample = self.canary_sample
        targets = [r for r in self.replicas
                   if r.breaker == "closed" and not r.draining
                   and not r.admin_drained]
        if not targets:
            return web.json_response(
                {"message": "No reachable replica to reload."}, status=503)
        wave: list[dict] = []
        canary: dict | None = None
        for i, r in enumerate(targets):
            try:
                async with self._session.get(
                        f"{r.url}/reload",
                        timeout=aiohttp.ClientTimeout(
                            total=self.reload_timeout_s)) as resp:
                    out = await resp.json()
                    if resp.status != 200:
                        raise RuntimeError(
                            out.get("message", f"HTTP {resp.status}"))
            except Exception as e:  # noqa: BLE001 — abort the wave
                return web.json_response(
                    {"message": f"reload failed on {r.name}: {e}",
                     "reloaded": wave}, status=500)
            wave.append({"replica": r.name,
                         "engineInstanceId": out.get("engineInstanceId")})
            if i == 0 and sample > 0 and len(targets) > 1:
                canary = await self._canary(r, targets[-1], sample)
                if canary["mismatchFraction"] > self.canary_max_mismatch:
                    # the rest of the wave keeps the OLD model — the
                    # rollback is not doing the rollout
                    trace_event("fleet.canary", verdict="abort", **{
                        k: v for k, v in canary.items() if k != "tiers"})
                    return web.json_response(
                        {"message": "shadow-diff canary gate failed; "
                                    "wave aborted",
                         "canary": canary, "reloaded": wave}, status=409)
        return web.json_response(
            {"message": "Reloaded", "wave": wave, "canary": canary})

    async def _canary(self, fresh: Replica, baseline: Replica,
                      sample: int) -> dict:
        """Replay recent queries against the freshly reloaded replica
        and a not-yet-reloaded baseline; shadow-diff tier per pair."""
        queries = list(self._recent)[-sample:]
        tiers: dict[str, int] = {}
        mismatches = 0
        for q in queries:
            raw = json.dumps(q).encode()

            async def _ask(rep: Replica):
                async with self._session.post(
                        f"{rep.url}/queries.json", data=raw,
                        headers={"Content-Type": "application/json"},
                        timeout=aiohttp.ClientTimeout(
                            total=self.dispatch_timeout_s)) as resp:
                    return await resp.json()

            try:
                old, new = await asyncio.gather(_ask(baseline), _ask(fresh))
                tier = diff_tier(old, new)
            except Exception:  # noqa: BLE001 — an unanswerable pair diverges
                tier = "error"
            tiers[tier] = tiers.get(tier, 0) + 1
            if tier in ("mismatch", "error"):
                mismatches += 1
        frac = (mismatches / len(queries)) if queries else 0.0
        return {"sampled": len(queries), "tiers": tiers,
                "mismatchFraction": round(frac, 4),
                "baseline": baseline.name, "fresh": fresh.name}

    # -- admin -------------------------------------------------------------
    def _find(self, token: str) -> Replica | None:
        for r in self.replicas:
            if token in (r.name, r.url):
                return r
        return None

    async def handle_fleet_drain(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            body = {}
        r = self._find(str(body.get("replica", "")))
        if r is None:
            return web.json_response(
                {"message": f"unknown replica {body.get('replica')!r}"},
                status=404)
        r.admin_drained = True
        self._mark_ready(r, r.ready)
        stopped = False
        if bool(body.get("stop", False)):
            try:
                async with self._session.get(
                        f"{r.url}/stop",
                        timeout=aiohttp.ClientTimeout(total=5)) as resp:
                    stopped = resp.status == 200
            except Exception:  # noqa: BLE001 — already dead is drained too
                pass
        trace_event("fleet.drain", replica=r.name, stop=stopped)
        return web.json_response(
            {"message": "draining", "replica": r.name, "stopped": stopped})

    async def handle_fleet_undrain(self, request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            body = {}
        r = self._find(str(body.get("replica", "")))
        if r is None:
            return web.json_response(
                {"message": f"unknown replica {body.get('replica')!r}"},
                status=404)
        r.admin_drained = False
        self._mark_ready(r, r.ready)
        return web.json_response({"message": "undrained", "replica": r.name})

    # -- supervisor integration (ISSUE 18) ---------------------------------
    def set_quarantined(self, token: str, active: bool) -> bool:
        """Supervisor verdict on a crash-looping replica. Plain field
        mutation — safe to call from the supervisor's thread; the next
        routing decision sees the new eligible set."""
        r = self._find(token)
        if r is None:
            return False
        if r.quarantined != active:
            r.quarantined = active
            log.warning("replica %s %s", r.name,
                        "QUARANTINED (crash loop)" if active
                        else "released from quarantine")
            trace_event("fleet.quarantine", replica=r.name, active=active)
        self._mark_ready(r, r.ready)
        return True

    def set_admin_drained(self, token: str, active: bool) -> bool:
        """Thread-safe drain toggle for the supervisor's rolling wave
        (the HTTP handlers above are the loop-side equivalent)."""
        r = self._find(token)
        if r is None:
            return False
        r.admin_drained = active
        self._mark_ready(r, r.ready)
        return True

    def canary_from_thread(self, fresh: str, baseline: str,
                           sample: int, timeout_s: float = 60.0) -> dict:
        """Run the shadow-diff canary on the router's event loop from a
        foreign (supervisor) thread."""
        fr, br = self._find(fresh), self._find(baseline)
        if fr is None or br is None or self._loop is None:
            return {"sampled": 0, "tiers": {}, "mismatchFraction": 0.0,
                    "baseline": baseline, "fresh": fresh}
        fut = asyncio.run_coroutine_threadsafe(
            self._canary(fr, br, sample), self._loop)
        return fut.result(timeout=timeout_s)

    async def handle_fleet_quarantine(self,
                                      request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except (json.JSONDecodeError, UnicodeDecodeError):
            body = {}
        token = str(body.get("replica", ""))
        active = bool(body.get("active", True))
        if not self.set_quarantined(token, active):
            return web.json_response(
                {"message": f"unknown replica {body.get('replica')!r}"},
                status=404)
        return web.json_response(
            {"message": "quarantined" if active else "released",
             "replica": token})

    async def handle_fleet_restart(self,
                                   request: web.Request) -> web.Response:
        """Rolling, canary-gated restart wave — delegated to the
        attached FleetSupervisor (`pio fleet start --supervise`)."""
        sup = self.supervisor
        if sup is None:
            return web.json_response(
                {"message": "no supervisor attached to this router "
                            "(start the fleet with --supervise)"},
                status=409)
        try:
            sample = int(request.query.get("canary", self.canary_sample))
        except ValueError:
            sample = self.canary_sample
        report = await asyncio.to_thread(sup.rolling_restart,
                                         canary_sample=sample)
        return web.json_response(
            report, status=200 if report.get("outcome") == "ok" else 409)

    # -- status ------------------------------------------------------------
    def status(self) -> dict:
        out = {
            "fleetEpoch": self.fleet_epoch,
            "journal": {"entries": len(self._journal),
                        "floorEpoch": (self._journal[0][0]
                                       if self._journal else None)},
            "durable": self._store is not None,
            "draining": self._draining,
            "eligible": [r.name for r in self._eligible()],
            "quarantined": [r.name for r in self.replicas
                            if r.quarantined],
            "replicas": [r.snapshot(self.fleet_epoch)
                         for r in self.replicas],
        }
        if self.supervisor is not None:
            out["supervisor"] = self.supervisor.status()
        return out

    async def handle_fleet_json(self, request: web.Request) -> web.Response:
        return web.json_response(self.status())

    async def handle_health(self, request: web.Request) -> web.Response:
        eligible = self._eligible()
        body = {
            "status": "draining" if self._draining else "ok",
            "live": True,
            "ready": not self._draining and bool(eligible),
            "role": "fleet-router",
            "replicas": len(self.replicas),
            "eligible": len(eligible),
            "fleetEpoch": self.fleet_epoch,
        }
        return web.json_response(body,
                                 status=503 if self._draining else 200)

    async def handle_metrics(self, request: web.Request) -> web.Response:
        return web.Response(text=METRICS.render_prometheus(),
                            content_type="text/plain")

    # -- fleet observability plane (ISSUE 20) ------------------------------
    async def handle_fleet_metrics(self,
                                   request: web.Request) -> web.Response:
        """Prometheus exposition of the whole fleet: per-replica
        counters/gauges with a ``replica`` label, exactly-merged
        histograms, and the collector's own meta families."""
        if self.collector is None:
            return web.json_response(
                {"message": "fleet metric collection is disabled "
                            "(--no-collect-metrics)"}, status=404)
        text = await asyncio.to_thread(self.collector.render_prometheus)
        return web.Response(text=text, content_type="text/plain")

    async def handle_fleet_stats(self,
                                 request: web.Request) -> web.Response:
        body: dict = {
            "role": "fleet-router",
            "fleetEpoch": self.fleet_epoch,
            "eligible": [r.name for r in self._eligible()],
        }
        if self.collector is not None:
            body.update(await asyncio.to_thread(self.collector.stats_json))
        else:
            body["collector"] = None
        return web.json_response(body)

    async def handle_fleet_slo(self, request: web.Request) -> web.Response:
        if self.collector is None:
            return web.json_response(
                {"message": "fleet metric collection is disabled"},
                status=404)
        return web.json_response(self.collector.fleet_slo())

    async def handle_fleet_trace(self,
                                 request: web.Request) -> web.Response:
        """Join one request id across the fleet: the router's hop log
        plus every replica's flight-recorder records for that id. The
        ``pio trace <rid>`` command renders this (plus local WAL
        records) as one span tree."""
        rid = request.query.get("rid", "").strip()
        if not rid:
            return web.json_response({"message": "rid= is required"},
                                     status=400)
        hops = [h for h in list(self._route_log) if h.get("rid") == rid]
        replicas: dict[str, list] = {}

        async def _pull(r: Replica) -> None:
            try:
                async with self._session.get(
                        f"{r.url}/debug/flight.json",
                        timeout=aiohttp.ClientTimeout(
                            total=self.scrape_timeout_s)) as resp:
                    if resp.status != 200:
                        return
                    body = await resp.json()
            except Exception:  # noqa: BLE001 — a dead replica has no spans
                return
            recs = [rec for rec in (body.get("records") or [])
                    if isinstance(rec, dict) and rec.get("requestId") == rid]
            if recs:
                replicas[r.name] = recs

        await asyncio.gather(*(_pull(r) for r in self.replicas))
        return web.json_response(
            {"rid": rid, "router": hops, "replicas": replicas})

    async def handle_stop(self, request: web.Request) -> web.Response:
        async def _stop():
            await self.close()
            raise web.GracefulExit()

        asyncio.create_task(_stop())
        return web.json_response({"message": "Shutting down."})


def _max_burn(slo: dict | None) -> float:
    """Worst fast-window (5m) burn rate across a replica's objectives."""
    burn = 0.0
    for o in (slo or {}).get("objectives", []) or []:
        w = (o.get("windows") or {}).get("5m") or {}
        try:
            burn = max(burn, float(w.get("burnRate", 0.0)))
        except (TypeError, ValueError):
            pass
    return burn


def create_fleet_app(router: FleetRouter) -> web.Application:
    app = web.Application()
    app[ROUTER_KEY] = router
    app.router.add_post("/queries.json", router.handle_query)
    app.router.add_get("/health.json", router.handle_health)
    app.router.add_get("/fleet.json", router.handle_fleet_json)
    app.router.add_get("/metrics", router.handle_metrics)
    app.router.add_get("/fleet/metrics", router.handle_fleet_metrics)
    app.router.add_get("/fleet/stats.json", router.handle_fleet_stats)
    app.router.add_get("/fleet/slo.json", router.handle_fleet_slo)
    app.router.add_get("/fleet/trace.json", router.handle_fleet_trace)
    app.router.add_get("/reload", router.handle_reload)
    app.router.add_post("/reload/delta", router.handle_reload_delta)
    app.router.add_post("/fleet/drain", router.handle_fleet_drain)
    app.router.add_post("/fleet/undrain", router.handle_fleet_undrain)
    app.router.add_post("/fleet/quarantine", router.handle_fleet_quarantine)
    app.router.add_post("/fleet/restart", router.handle_fleet_restart)
    app.router.add_get("/stop", router.handle_stop)

    async def _start(app):
        await router.start()

    async def _close(app):
        await router.close()

    app.on_startup.append(_start)
    app.on_shutdown.append(_close)
    return app


def run_fleet_router(replica_urls: list[str], ip: str = "0.0.0.0",
                     port: int = 8000, supervisor=None, **kwargs) -> None:
    """Blocking entry for the router process (`pio fleet start`)."""
    logging.basicConfig(level=logging.INFO)
    router = FleetRouter(replica_urls, **kwargs)
    if supervisor is not None:
        router.supervisor = supervisor
        supervisor.router = router
    log.info("Fleet router starting on %s:%d over %d replica(s)",
             ip, port, len(router.replicas))
    web.run_app(create_fleet_app(router), host=ip, port=port, print=None)


# -- local fleet process management (`pio fleet start`) --------------------

def fleet_state_path() -> Path:
    """``$PIO_HOME/run/fleet.json`` — the same run/ directory the
    pio-start-all daemons use for pidfiles."""
    home = Path(os.environ.get("PIO_HOME",
                               str(Path.home() / ".predictionio_tpu")))
    return home / "run" / "fleet.json"


def write_fleet_state(router_url: str, replicas: list[dict], *,
                      router_pid: int | None = None,
                      router_started_at: float | None = None,
                      quarantined: list[dict] | None = None) -> Path:
    """Atomically publish the fleet pidfile (tmp+fsync+rename — a kill
    mid-write leaves the previous state intact). ``replicas`` is the
    ACTIVE set; quarantined replicas move to the ``quarantined`` list
    so rendezvous consumers of the file never route to them."""
    p = fleet_state_path()
    _atomic_write_json(p, {
        "routerUrl": router_url,
        "routerPid": router_pid,
        "routerStartedAt": router_started_at,
        "replicas": replicas,
        "quarantined": quarantined or [],
        "ts": time.time(),
    })
    return p


def _pid_alive(pid) -> bool:
    try:
        pid = int(pid)
    except (TypeError, ValueError):
        return False
    if pid <= 0:
        return False
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, owned by someone else
    except OSError:
        return False
    return True


def read_fleet_state() -> dict | None:
    """Parse the fleet pidfile; None on missing/truncated/garbage —
    corruption is 'no fleet', never a traceback. When the file records
    PIDs and none of them is still alive, the returned dict carries
    ``stale: True`` so callers report 'fleet not running' instead of
    probing a dead URL."""
    p = fleet_state_path()
    try:
        state = json.loads(p.read_text())
    except (OSError, ValueError):
        return None
    if not isinstance(state, dict):
        return None
    pids = [state.get("routerPid")]
    for r in state.get("replicas") or []:
        if isinstance(r, dict):
            pids.append(r.get("pid"))
    pids = [q for q in pids if q]
    state["stale"] = bool(pids) and not any(_pid_alive(q) for q in pids)
    return state


#: every brood ever spawned by this process — the atexit sweep
#: terminates whatever is still running so a failed `pio fleet start`
#: (or a crashed supervisor) never strands orphan deploy children
_BROODS: list[list[subprocess.Popen]] = []
_BROOD_ATEXIT = [False]


def _prune_broods() -> None:
    """Drop already-exited children from the atexit sweep's registry.
    Every supervisor respawn routes through ``spawn_replicas``, so in a
    long-lived supervised fleet the brood history would otherwise grow
    one dead Popen per respawn, unbounded. In-place so callers holding
    a brood list keep seeing their own still-running children."""
    for procs in _BROODS:
        procs[:] = [p for p in procs if p.poll() is None]
    _BROODS[:] = [procs for procs in _BROODS if procs]


def _terminate_broods() -> None:
    for procs in _BROODS:
        for proc in procs:
            if proc.poll() is None:
                try:
                    proc.terminate()
                except OSError:
                    pass
    deadline = time.monotonic() + 5.0
    for procs in _BROODS:
        for proc in procs:
            if proc.poll() is None:
                try:
                    proc.wait(timeout=max(0.1,
                                          deadline - time.monotonic()))
                except (subprocess.TimeoutExpired, OSError):
                    try:
                        proc.kill()
                        proc.wait(timeout=1.0)
                    except (subprocess.TimeoutExpired, OSError):
                        pass


def reap_replicas(procs: list[subprocess.Popen]) -> list[tuple[int, int]]:
    """Poll-reap exited replica children (no zombies). Returns
    [(port, returncode)] for the newly exited; a nonzero exit is
    logged with the replica's port so a crashing deploy child is
    visible instead of silently absent."""
    exited: list[tuple[int, int]] = []
    for proc in procs:
        rc = proc.poll()
        if rc is None:
            continue
        port = getattr(proc, "pio_port", -1)
        exited.append((port, rc))
        if rc != 0:
            log.warning("replica child pid=%d port=%s exited rc=%d",
                        proc.pid, port, rc)
    return exited


def spawn_replicas(engine_dir: str, n: int, base_port: int,
                   *, ip: str = "127.0.0.1",
                   extra_args: tuple[str, ...] = (),
                   env: dict | None = None) -> list[subprocess.Popen]:
    """Start ``n`` engine-server replica processes on consecutive ports.

    Each replica is a full ``pio deploy`` child sharing the parent's
    storage configuration (``PIO_HOME`` / ``PIO_STORAGE_*`` env): the
    blob trained ONCE is pulled by every replica through the
    sha256-checked ``prepare_deploy`` path. ``--prewarm-async`` makes
    the replica bind fast and report live-but-not-ready until its
    executable prewarm completes — the router holds hashed traffic
    until then.

    Every spawned brood is registered with an atexit sweep that
    terminates still-running children on interpreter exit; each proc
    carries its port as ``proc.pio_port`` for ``reap_replicas``."""
    _prune_broods()
    procs: list[subprocess.Popen] = []
    child_env = dict(os.environ if env is None else env)
    for i in range(n):
        cmd = [sys.executable, "-m", "predictionio_tpu.tools.cli",
               "deploy", "--engine-dir", engine_dir,
               "--ip", ip, "--port", str(base_port + i),
               "--prewarm-async", *extra_args]
        proc = subprocess.Popen(cmd, env=child_env)
        proc.pio_port = base_port + i
        procs.append(proc)
    _BROODS.append(procs)
    if not _BROOD_ATEXIT[0]:
        atexit.register(_terminate_broods)
        _BROOD_ATEXIT[0] = True
    return procs
