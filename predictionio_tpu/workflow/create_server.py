"""Engine server: deploys a trained engine instance as an HTTP service.

Analog of reference ``CreateServer`` (core/src/main/scala/io/prediction/
workflow/CreateServer.scala:106-613) on asyncio/aiohttp instead of
spray/akka actors:

- ``POST /queries.json``  -> serve one query (the hot path, :462-591)
- ``GET  /``              -> engine status JSON (Twirl HTML page analog)
- ``GET  /stats.json``    -> serving telemetry: request counters, the
  micro-batcher's adaptive window + pipeline occupancy, and the shared
  executable-cache hit/miss/eviction counters (no reference analog —
  operational surface for the TPU serving path)
- ``GET  /reload``        -> hot-swap to the latest COMPLETED instance
  (MasterActor's UpgradeActor/ReloadServer, :592-598) — models are
  rehydrated into a fresh ``Deployed`` bundle, then the reference is
  swapped atomically (double-buffering; on-device factor arrays from the
  old bundle are dropped after the swap).
- ``GET  /health.json``   -> liveness/readiness for load balancers:
  deployed-bundle state, degraded mode, watchdog trips, drain status
  (503 while draining so an LB rotates the instance out before exit)
- ``GET  /stop``          -> graceful shutdown (:600-608); drains first
- feedback loop: when enabled, every query/prediction pair is POSTed to
  the event server with prId threading (:488-541) through a lifecycle-
  owned publisher (workflow/feedback.py): one shared ClientSession,
  tracked tasks, bounded retries, circuit breaker.

Resilience (no reference analog — the akka stack got this from actor
supervision + spray timeouts): requests carry end-to-end deadlines
(``--deadline-ms`` or the ``X-PIO-Deadline-Ms`` header; expiry answers
504 without consuming a batch slot), every dispatched batch runs under a
stuck-dispatch watchdog that reclaims its pipeline slot instead of
wedging it, and a watchdog trip flips the server DEGRADED: queries
bypass the batcher onto a per-query fallback path, the pipeline shrinks,
and a half-open probe per cooldown window decides when to resume
batching. SIGTERM and ``/stop`` perform a graceful drain (stop
accepting, flush the queue, finish in-flight batches, close the
feedback loop) before exit.

Queries are parsed with the algorithm's ``query_class`` dataclass when
declared (the reference's per-algorithm querySerializer), else passed as
raw dicts; predictions are serialized from dataclasses or plain JSON
values.
"""

from __future__ import annotations

import asyncio
import dataclasses
import hashlib
import os
import signal
import tempfile
import threading
import json
import logging
import time
import uuid
from datetime import datetime, timezone
from typing import Any

import numpy as np
from aiohttp import web

from ..controller.engine import Engine, TrainResult
from ..controller.params import parse_params
from ..faults import FAULTS
from ..obs.device import LEDGER, device_identity
from ..obs.flight import FLIGHT
from ..obs.http import handle_metrics, make_trace_middleware
from ..obs.ingress_lines import (IngressLines, buffered_access_logger,
                                 note_serve_ingress)
from ..obs.metrics import METRICS
from ..obs.replay import PROVENANCE_HEADER
from ..obs.training import TRAINING
from ..obs.slo import SloTracker, default_objectives
from ..obs.startup import STARTUP
from ..obs.trace import TRACE_HEADER, ensure_request_id, span, trace_event
from ..obs.waterfall import (Waterfall, mark_stage, reset_stage_sink,
                             set_stage_sink, stage_span, stage_summary)
from ..ops.pipeline import reset_step_end_hook, set_step_end_hook
from ..storage import EngineInstance, Storage
from .admission import AdmissionController
from .feedback import FeedbackPublisher
from .microbatch import DeadlineExceeded, DispatchTimeout, ServerBusy
from .context import Context
from .core_workflow import prepare_deploy
from .variants import VARIANT_HEADER, VariantTable, entity_key

log = logging.getLogger("predictionio_tpu.server")

__all__ = ["EngineServer", "create_engine_server_app", "run_engine_server"]

# ISSUE 5: the query plane's registry handles. The serving histogram is
# end-to-end (parse -> dispatch -> feedback fan-out), i.e. what the
# client experienced, not just device time (microbatch.py records the
# inner stages separately).
_M_SERVE = METRICS.histogram(
    "pio_serving_latency_seconds",
    "end-to-end POST /queries.json latency as the client saw it")
_M_QUERIES = METRICS.counter(
    "pio_queries_total",
    "queries by outcome (ok/bad_request/busy/deadline/watchdog/draining/"
    "shed)",
    labelnames=("status",))
_M_DEGRADED = METRICS.gauge(
    "pio_degraded_mode",
    "1 while the engine server serves on the degraded fallback path")
# ISSUE 6: ONE unified server mode — brownout (overload pressure) and
# degraded (watchdog trips) share this gauge so the two mechanisms can
# never disagree about what state the server is in
_MODE_LEVELS = {"normal": 0, "brownout": 1, "degraded": 2}
_M_MODE = METRICS.gauge(
    "pio_server_mode",
    "unified engine-server mode: 0 normal, 1 brownout (overload "
    "degradation), 2 degraded (watchdog fallback)")
# same family microbatch.py counts on its paths — the fallback path's
# expiries must not vanish from the counter just because batching is off
_M_DEADLINE = METRICS.counter(
    "pio_deadline_expired_total",
    "queries answered 504 because their end-to-end deadline expired")
# ISSUE 10: delta hot-patch surface (POST /reload/delta) — per-request
# outcome counter plus the monotonic patch epoch, so the streaming
# updater's view (pio_stream_patch_epoch) can be joined against the
# server's own idea of what it applied
_M_DELTA = METRICS.counter(
    "pio_delta_patch_total",
    "POST /reload/delta requests by outcome (ok/empty/bad_request/error)",
    labelnames=("status",))
_M_DELTA_EPOCH = METRICS.gauge(
    "pio_delta_patch_epoch",
    "monotonic serving-bundle patch epoch (bumps per applied delta batch "
    "and per full-reload reconciliation)")
# ISSUE 11: live jax.profiler windows served via POST /debug/profile
_M_PROFILE = METRICS.counter(
    "pio_profile_captures_total",
    "live jax.profiler traces captured of the serving process")


def _to_jsonable(x: Any) -> Any:
    if dataclasses.is_dataclass(x) and not isinstance(x, type):
        return {k: _to_jsonable(v) for k, v in dataclasses.asdict(x).items()}
    if isinstance(x, dict):
        return {k: _to_jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_to_jsonable(v) for v in x]
    if hasattr(x, "item") and not isinstance(x, (str, bytes)):  # numpy scalar
        try:
            return x.item()
        except Exception:
            return x
    return x


@dataclasses.dataclass
class Deployed:
    """One rehydrated engine instance (swap unit for hot reload).

    ``retriever_mesh``/``retriever_axis``: when set, catalogs attach
    SHARDED over that mesh axis (ShardedDeviceRetriever) instead of
    replicated on one device — and the reload path passes them through,
    so /reload preserves the sharded configuration rather than silently
    de-sharding a catalog that was sharded because it exceeds one chip's
    HBM. ``retriever_mesh="auto"`` defers the width to the
    ``ops/retrieval.choose_shard_count`` cost model per catalog (1-way
    where the model says the merge costs more than the sharding saves).

    ``retrieval``: the engine-params ``retrieval: {mode: exact|ann,
    nprobe, quantize, ...}`` block (ISSUE 7). ``mode: "ann"`` attaches
    the IVF approximate-MIPS retriever (ops/ann.AnnRetriever) on ANY
    backend — it is a plain XLA program — with automatic exact fallback
    for small catalogs and failed index builds; reload preserves it.
    """

    instance: EngineInstance
    result: TrainResult
    retriever_mesh: object = None
    retriever_axis: str = "model"
    prewarm_batch: int = 0  # pre-compile executables for this batch ceiling
    retrieval: dict | None = None
    # ISSUE 13 provenance facts, stamped at rehydration time: the model
    # blob's content hash (storage metadata checksum) and a digest over
    # the executable-cache keys this bundle compiled — together they
    # name WHAT is serving, independent of instance-id reuse
    blob_sha: str | None = dataclasses.field(default=None, init=False)
    exec_cache_key: str | None = dataclasses.field(default=None, init=False)

    def _resolved_mesh(self, model):
        """``retriever_mesh`` for one model: pass-through, or the
        cost-model width when configured "auto" (1 → no mesh at all)."""
        if self.retriever_mesh != "auto":
            return self.retriever_mesh
        import jax

        from ..ops.retrieval import choose_shard_count

        catalog = getattr(model, getattr(model, "_retrieval_attr", ""), None)
        n = 0 if catalog is None else len(catalog)
        w = choose_shard_count(n, len(jax.devices()))
        log.info("retriever_mesh=auto: cost model picked %d-way for a "
                 "%d-row catalog", w, n)
        if w <= 1:
            return None
        from ..parallel.mesh import make_mesh

        return make_mesh((w,), (self.retriever_axis,))

    def __post_init__(self):
        # Move catalog factors device-resident so queries run through a
        # compiled top-k program (the fused Pallas kernel on TPU, plain
        # XLA elsewhere). Building the retriever on the NEW bundle before
        # the swap is the double-buffered /reload: the old bundle keeps
        # serving until this one is fully on-device.
        # the bundle's provenance: the checksum `prepare_deploy` verified
        # and kept; only a result made another way (a test, a retrain)
        # costs a read of the blob for it
        self.blob_sha = getattr(self.result, "blob_checksum", None)
        if self.blob_sha is None:
            try:
                with span("deploy.blob_read", sink=STARTUP.phase,
                          reason="provenance"):
                    blob = Storage.get_models().get(self.instance.id)
                self.blob_sha = getattr(blob, "checksum", None)
            except Exception:  # noqa: BLE001 — provenance is best-effort
                self.blob_sha = None

        mode = str((self.retrieval or {}).get("mode", "exact")).lower()
        for model in self.result.models:
            mesh = None
            if mode == "ann":
                # ANN outranks a configured mesh: the index is the
                # scale mechanism, and the retriever handles its own
                # exact fallback (small catalog / failed build) — so
                # the mesh is never resolved here (running the "auto"
                # cost model would log a width that is then discarded)
                attach = getattr(model, "attach_ann_retriever", None)
                args = ()
                kwargs = {k: v for k, v in (self.retrieval or {}).items()
                          if k != "mode"}
            elif (mesh := self._resolved_mesh(model)) is not None:
                attach = getattr(model, "attach_sharded_retriever", None)
                args = (mesh,)
                kwargs = {"axis": self.retriever_axis}
            else:
                attach = getattr(model, "attach_retriever", None)
                args, kwargs = (), {}
            if attach is not None:
                # a failed attach raises: deploy exits non-zero with
                # nothing bound, /reload fails and the old bundle keeps
                # serving. Host scoring is what serves where no
                # retriever is configured, never what an exception picks
                with span("deploy.attach_retriever", sink=STARTUP.phase,
                          model=type(model).__name__):
                    attach(*args, **kwargs)
                log.info(
                    "%s retriever attached to %s",
                    "ann" if mode == "ann"
                    else "sharded" if mesh is not None else "device",
                    type(model).__name__)
            if getattr(model, "_retriever", None) is not None:
                ap = getattr(model, "attach_pipeline", None)
                # models without a query-factor table (similarity-only)
                # have no query side to make device-resident: skip, the
                # retriever alone is their whole serving path
                if getattr(model, getattr(model, "_query_attr", ""),
                           None) is None:
                    ap = None
                if ap is not None:
                    with span("deploy.attach_pipeline", sink=STARTUP.phase,
                              model=type(model).__name__):
                        ap()
                    log.info("serving pipeline attached to %s (%s)",
                             type(model).__name__,
                             model._pipeline.stats()["mode"])
        if self.prewarm_batch > 0:
            self._prewarm()

    def _prewarm(self):
        """AOT-compile the hot serving shapes at DEPLOY time so the first
        real query (and the first full micro-batch) never pays a compile:
        the FULL pad-bucketed batch lattice — a lone query (pad 1) and
        every power-of-two bucket up to the micro-batcher's ceiling — so
        an adaptive window that dispatches a partial batch never hits a
        cold executable. All are pinned in the executable cache
        (ops/retrieval.py EXEC_CACHE); the pipeline's prewarm also
        allocates the pinned staging pairs and accounts them in the
        device ledger."""
        lattice = {1, self.prewarm_batch}
        b = 8
        while b < self.prewarm_batch:
            lattice.add(b)
            b *= 2
        sizes = sorted(lattice)
        warmed_keys: list = []
        with span("deploy.prewarm", sink=STARTUP.phase,
                  batch=self.prewarm_batch):
            for model in self.result.models:
                for attr in ("_retriever", "_sim_retriever", "_pipeline"):
                    r = getattr(model, attr, None)
                    if r is None or not hasattr(r, "prewarm"):
                        continue
                    # a program that does not compile here will not
                    # compile on the first query either: the failure is
                    # the deploy's
                    warmed = r.prewarm(batch_sizes=sizes)
                    warmed_keys.extend(warmed or ())
                    log.info("prewarmed %s.%s shapes %s",
                             type(model).__name__, attr, warmed)
        if warmed_keys:
            # one digest naming the compiled-program configuration this
            # bundle serves from (the warmed EXEC_CACHE keys carry
            # namespace + shapes + dtype + quantization); None when the
            # bundle serves host scoring (nothing compiled to name)
            self.exec_cache_key = hashlib.sha256(
                "\n".join(sorted(repr(k) for k in warmed_keys)).encode()
            ).hexdigest()[:16]


class EngineServer:
    """Holds the deployed bundle + bookkeeping; handlers delegate here."""

    #: class-level default so partially-constructed skeletons (tests
    #: build them with object.__new__) still carry a variant identity
    variant_id: str = "default"

    #: latest eval-gate block a streaming updater rode along with its
    #: delta publish (ISSUE 14: per-variant online hit@k for the A/B
    #: dashboard view); None until a gated publish arrives
    last_stream_gate: dict | None = None

    #: class-level default so skeleton servers (object.__new__ in
    #: tests) report ready the way a fully-built server does
    _prewarming: bool = False

    def __init__(
        self,
        engine: Engine,
        instance: EngineInstance,
        ctx: Context | None = None,
        *,
        feedback_url: str | None = None,
        access_key: str | None = None,
        batch_window_ms: float = 1.0,
        batch_max: int = 128,
        batch_inflight: int = 8,
        deadline_ms: float = 0.0,
        dispatch_timeout_s: float | None = 30.0,
        degraded_cooldown_s: float = 15.0,
        engine_dir=None,
        retriever_mesh=None,
        retriever_axis: str = "model",
        fallback: bool = True,
        admission: bool = False,
        admission_queue_high: int = 64,
        admission_wait_budget_ms: float = 0.0,
        rate_limit_qps: float = 0.0,
        rate_limit_burst: float = 0.0,
        brownout_topk: int = 10,
        retrieval: dict | None = None,
        patch_table_max: int = 100_000,
        slo_latency_ms: float = 0.0,
        flight_capacity: int = 256,
        flight_dump_dir: str | None = None,
        capture_dir: str | None = None,
        capture_sample: float = 1.0,
        capture_ring: int = 256,
        capture_max_mb: float = 64.0,
        shadow_target: str | None = None,
        shadow_sample: float = 1.0,
        variant_id: str = "default",
        defer_prewarm: bool = False,
    ):
        self.engine = engine
        self.ctx = ctx or Context(mode="Serving")
        self.engine_dir = engine_dir  # for re-resolving blob classes
        self.batch_max = batch_max
        # ISSUE 14: the variant identity of THIS bundle. Every server is
        # a variant (the single-engine case is a one-entry table); the
        # PRIMARY server's table is the process-wide router that the
        # /variants endpoints mutate.
        self.variant_id = str(variant_id) or "default"
        #: instances skipped by the most recent deploy/reload because
        #: their blob was corrupt or unloadable — surfaced in
        #: /health.json and /stats.json so operators see the quarantine
        self.deploy_skips: list[dict] = []
        # ISSUE 17: readiness vs liveness. While True the server is
        # LIVE (answers queries, compiling on demand) but NOT READY —
        # /health.json reports ready=false so a fleet router withholds
        # hashed traffic until the executable prewarm lands, instead of
        # today's ambiguous 200. Set by defer_prewarm; cleared by
        # complete_prewarm().
        self._prewarming = bool(defer_prewarm)
        prewarm_batch = 0 if defer_prewarm else batch_max
        if fallback:
            inst, result, self.deploy_skips = self._deploy_with_fallback(instance)
            self.deployed = Deployed(
                inst, result,
                retriever_mesh=retriever_mesh, retriever_axis=retriever_axis,
                prewarm_batch=prewarm_batch, retrieval=retrieval)
        else:  # explicitly pinned instance: fail loud, never substitute
            self.deployed = Deployed(
                instance,
                prepare_deploy(engine, instance, self.ctx, engine_dir=engine_dir),
                retriever_mesh=retriever_mesh, retriever_axis=retriever_axis,
                prewarm_batch=prewarm_batch, retrieval=retrieval)
        self.feedback_url = feedback_url
        self.access_key = access_key
        # lifecycle-owned feedback publisher: one shared session, tracked
        # tasks, bounded retry queue, circuit breaker (workflow/feedback.py)
        self.feedback = (FeedbackPublisher(feedback_url, access_key)
                         if feedback_url and access_key else None)
        self.start_time = datetime.now(timezone.utc)
        # bookkeeping (CreateServer.scala:396-398)
        self.request_count = 0
        self.avg_serving_sec = 0.0
        self.last_serving_sec = 0.0
        # serving stats are read-modify-written from the MicroBatcher
        # worker and from asyncio.to_thread workers when batching is off —
        # a lock keeps the running average exact (reference keeps these on
        # a single actor, CreateServer.scala:552-559)
        self._stats_lock = threading.Lock()
        self._reload_lock = threading.Lock()  # serialize expensive reloads
        # ISSUE 10: delta hot-patch state (POST /reload/delta). The patch
        # table records every user-factor delta applied since the last
        # full reload, so reconciliation can tell superseded deltas (the
        # fresh instance trained the user) from ones that must carry over
        # (user still unseen by training). Bounded: a runaway updater
        # must not grow the serving bundle without limit.
        self.patch_epoch = 0
        self.patch_table: dict[str, np.ndarray] = {}
        self.patch_table_max = max(1, patch_table_max)
        self.patch_discarded = 0  # lifetime deltas superseded by reloads
        # resilience state: deadlines, degraded mode, drain
        self.deadline_ms = max(0.0, deadline_ms)
        self.dispatch_timeout_s = (dispatch_timeout_s
                                   if dispatch_timeout_s and
                                   dispatch_timeout_s > 0 else None)
        self.degraded_cooldown_s = max(0.1, degraded_cooldown_s)
        # unified server mode (ISSUE 6): normal < brownout < degraded.
        # Brownout is entered/left by admission pressure; degraded only
        # by watchdog trips / probe success. ONE field means the two
        # mechanisms cannot disagree about what state the server is in.
        self._mode = "normal"
        self.degraded_since: str | None = None
        self._probe_at: float | None = None  # next half-open probe instant
        self.brownout_topk = max(0, brownout_topk)
        self.brownout_since: str | None = None
        self._inflight_configured = max(1, batch_inflight)
        self._draining = False
        self._drained = False
        # micro-batching dispatcher (workflow/microbatch.py): coalesce
        # concurrent queries into fixed-shape batched device calls;
        # window <= 0 disables (per-query dispatch, reference behavior)
        self.batcher = None
        if batch_window_ms > 0:
            from .microbatch import MicroBatcher

            self.batcher = MicroBatcher(
                self.serve_query_batch,
                max_batch=batch_max, window_s=batch_window_ms / 1000.0,
                max_inflight=batch_inflight,
                adaptive=True,  # window_s becomes the CEILING: idle
                # servers converge to ~0 added latency, loaded ones
                # stretch toward a full batch (workflow/microbatch.py)
                dispatch_timeout_s=self.dispatch_timeout_s,
                on_watchdog=self._on_watchdog_trip,
                costing=self._costing,
            )
        # adaptive admission (ISSUE 6): shed 429 + Retry-After at ingress
        # off live batcher/registry signals, before work can blow its
        # deadline downstream. Off unless --admission or a rate limit is
        # set — shedding policy is an operator opt-in.
        self.admission: AdmissionController | None = None
        if admission or rate_limit_qps > 0:
            b = self.batcher
            wait_budget_s = (
                admission_wait_budget_ms / 1e3 if admission_wait_budget_ms > 0
                else (self.deadline_ms / 2e3 if self.deadline_ms > 0 else 0.0))
            self.admission = AdmissionController(
                # per-variant pressure plane: a candidate sheds alone
                # without polluting the live variant's gauge series
                ("serve" if self.variant_id == "default"
                 else f"serve/{self.variant_id}"),
                queue_depth=(lambda: len(b._pending)) if b else None,
                queue_high=admission_queue_high,
                wait_hist_name="pio_microbatch_queue_wait_seconds",
                wait_budget_s=wait_budget_s,
                inflight=(lambda: b._live / b.max_inflight) if b else None,
                expiry_counter_name="pio_deadline_expired_total",
                backlog=(lambda: len(b._pending)) if b else None,
                drain_per_s=b.drain_rate_per_s if b else None,
                rate_limit_qps=rate_limit_qps,
                rate_limit_burst=rate_limit_burst,
            )
        # SLO engine: latency objective defaults to the request deadline
        # (a request slower than its deadline was worthless), 250 ms when
        # no deadline is configured; availability is always three nines.
        slo_latency_s = (
            slo_latency_ms / 1e3 if slo_latency_ms > 0
            else (self.deadline_ms / 1e3 if self.deadline_ms > 0 else 0.25))
        objectives = default_objectives(deadline_s=slo_latency_s)
        if self.variant_id != "default":
            # the SLO gauges (pio_slo_burn_rate{slo,window}) are shared
            # label series — co-hosted variants need distinct slo names
            # or two trackers would fight over one series
            objectives = [dataclasses.replace(o, name=f"{o.name}@{self.variant_id}")
                          for o in objectives]
        self.slo = SloTracker(objectives)
        # flight recorder: the process singleton, configured per server
        # (ONE engine per process today; the singleton matches METRICS/
        # FAULTS idiom and lets the micro-batcher push hung waterfalls
        # without holding a server reference)
        self.flight = FLIGHT
        self.flight.configure(capacity=flight_capacity,
                              dump_dir=flight_dump_dir)
        self.flight.set_context_provider(self._flight_context)
        # the query path's two log lines a request (serve.ingress, the
        # access line), held for a turn of the loop and written in a batch
        self.ingress = IngressLines()
        self._profiling = False  # one live jax.profiler window at a time
        # ISSUE 13: provenance envelope cache — assembled once per
        # (bundle, patch epoch, mode) and stamped (as a compact-JSON
        # header) on every response, so the hot path pays a tuple
        # compare, not a retrieval-stats walk + json.dumps per request
        self._prov_cache: tuple | None = None
        # golden-traffic capture (obs/capture.py): per-server, active
        # only when a capture directory is configured; /capture/start
        # and /capture/stop toggle recording at runtime
        self.capture = None
        if capture_dir:
            from ..obs.capture import CaptureRing

            self.capture = CaptureRing(
                capture_dir, sample=capture_sample,
                ring_capacity=capture_ring,
                max_bytes=int(capture_max_mb * 1024 * 1024))
            # incident flush: the requests that led INTO an incident are
            # exactly the golden traffic worth keeping on disk
            self.flight.add_incident_listener(
                lambda reason, path: self.capture.flush("incident"))
        # shadow mirror (obs/replay.py): sampled live traffic re-issued
        # fire-and-forget against a second instance with online diffs
        self.shadow = None
        if shadow_target:
            from ..obs.replay import ShadowMirror

            self.shadow = ShadowMirror(shadow_target, sample=shadow_sample)
        # ISSUE 14: every server starts as the sole live variant of its
        # own table; registering more variants turns the table into the
        # hashed A/B router. Child servers' own tables sit unused — only
        # the table on the server bound to the aiohttp app routes.
        self.variants = VariantTable(self.variant_id, self)

    @property
    def engine_instance_id(self) -> str:
        return self.deployed.instance.id

    def _flight_context(self) -> dict:
        """Ambient context stamped into flight snapshots/dumps: what the
        server looked like at capture time."""
        b = self.batcher
        ctx = {
            "mode": self._mode,
            "queueDepth": len(b._pending) if b else 0,
            "inflight": b._live if b else 0,
            "maxInflight": b.max_inflight if b else None,
            "watchdogTrips": b.watchdog_trips if b else 0,
            "deadlineExpired": b.deadline_expired if b else 0,
            "draining": self._draining,
        }
        if self.admission is not None:
            ctx["admission"] = self.admission.pressure_snapshot()
        # ISSUE 13: an incident file must name the exact model/config
        # that was serving when it fired — same block /stats.json shows
        try:
            ctx["provenance"] = self.provenance()
        except Exception:  # noqa: BLE001 — context must never block a dump
            pass
        return ctx

    # -- provenance envelope (ISSUE 13) ------------------------------------
    def provenance(self, bundle: "Deployed | None" = None) -> dict:
        """The identity of what is serving, as one block: engine
        instance id, model blob sha256, delta patch epoch, retrieval
        mode/nprobe/mesh, executable-cache key, and server mode. Cached
        per (bundle, epoch, mode) — cheap enough to stamp per request."""
        bundle = bundle if bundle is not None else self.deployed
        cached = self._prov_cache
        if cached is not None and cached[0] is bundle \
                and cached[1] == self.patch_epoch and cached[2] == self._mode:
            return cached[3]
        r = self._retrieval_stats(bundle) or {}
        mesh = bundle.retriever_mesh
        if mesh is None or isinstance(mesh, str):
            mesh_desc = mesh
        else:
            try:
                mesh_desc = dict(getattr(mesh, "shape", {})) or str(mesh)
            except Exception:  # noqa: BLE001
                mesh_desc = str(mesh)
        prov = {
            "engineInstanceId": bundle.instance.id,
            # ISSUE 14: which variant answered — capture persists this,
            # replay routes by it, and the parity report groups on it
            "variantId": self.variant_id,
            "modelBlobSha256": bundle.blob_sha,
            "patchEpoch": self.patch_epoch,
            "retrieval": {
                "mode": r.get("mode", "host"),
                "nprobe": r.get("nprobe"),
                "mesh": mesh_desc,
            },
            "execCacheKey": bundle.exec_cache_key,
            "mode": self._mode,
        }
        header = json.dumps(prov, separators=(",", ":"), default=str)
        self._prov_cache = (bundle, self.patch_epoch, self._mode, prov,
                            header)
        return prov

    def provenance_header(self) -> str:
        """The same envelope as compact JSON for the response header."""
        self.provenance()
        return self._prov_cache[4]

    # -- resilience: unified mode (normal/brownout/degraded), deadlines ----
    @property
    def mode(self) -> str:
        return self._mode

    @property
    def degraded(self) -> bool:
        return self._mode == "degraded"

    def _set_mode(self, mode: str) -> None:
        if mode == self._mode:
            return
        prev, self._mode = self._mode, mode
        _M_MODE.set(_MODE_LEVELS[mode])
        _M_DEGRADED.set(1 if mode == "degraded" else 0)
        now_iso = datetime.now(timezone.utc).isoformat()
        self.degraded_since = now_iso if mode == "degraded" else None
        self.brownout_since = now_iso if mode == "brownout" else None
        log.warning("server mode: %s -> %s", prev, mode)
        if mode in ("brownout", "degraded"):
            # ISSUE 11: entering a degraded rung is an incident — dump
            # the flight ring NOW, while it still holds the requests
            # that led in (cooldown-limited inside the recorder)
            self.flight.incident(f"mode_{mode}")

    def _update_brownout(self) -> None:
        """Enter/leave brownout from admission pressure. Never touches
        degraded — the watchdog outranks overload, and only a successful
        half-open probe may leave degraded."""
        if self.admission is None or self._mode == "degraded":
            return
        if self._mode == "normal" and self.admission.overloaded:
            self._set_mode("brownout")
        elif self._mode == "brownout" and self.admission.recovered:
            self._set_mode("normal")

    def brownout_degrade(self, query_json: dict) -> dict:
        """Brownout/degraded quality reduction: clamp top-k-style count
        fields so each admitted query costs less while the server digs
        out. Returns the query unchanged in normal mode."""
        if self._mode == "normal" or self.brownout_topk <= 0:
            return query_json
        out = None
        for k in ("num", "k", "topK", "top_k", "limit"):
            v = query_json.get(k)
            if isinstance(v, int) and not isinstance(v, bool) \
                    and v > self.brownout_topk:
                if out is None:
                    out = dict(query_json)
                out[k] = self.brownout_topk
        return out if out is not None else query_json

    def _on_watchdog_trip(self) -> None:
        """Runs on the event loop after each stuck-dispatch watchdog trip
        (microbatch.MicroBatcher.on_watchdog): enter degraded mode —
        queries bypass the batcher onto the per-query fallback path and
        the dispatch pipeline shrinks (hung calls mean device distress;
        piling more concurrency onto it digs the hole deeper). A
        half-open probe per cooldown window decides when to resume."""
        if not self.degraded:
            # degraded outranks brownout: a watchdog trip preempts any
            # overload state (the _set_mode transition keeps it unified)
            self._set_mode("degraded")
            if self.batcher is not None:
                self.batcher.set_max_inflight(
                    max(1, self.batcher.max_inflight // 2))
            log.error(
                "entering DEGRADED mode: per-query fallback serving, "
                "max_inflight shrunk to %d; probe in %.1fs",
                self.batcher.max_inflight if self.batcher else 0,
                self.degraded_cooldown_s)
        # the micro-batcher pushed the hung members' waterfalls into the
        # ring (stalled stage stamped) before calling this hook, so the
        # watchdog dump contains its victims
        self.flight.incident("watchdog")
        self._probe_at = time.monotonic() + self.degraded_cooldown_s

    def _exit_degraded(self) -> None:
        log.info("leaving degraded mode (probe batch succeeded); "
                 "max_inflight restored to %d", self._inflight_configured)
        self._probe_at = None
        if self.batcher is not None:
            self.batcher.set_max_inflight(self._inflight_configured)
        # drop to brownout (not straight to normal) when overload
        # pressure is still high — the probe proved the DEVICE healthy,
        # not the queue empty
        if self.admission is not None and self.admission.overloaded:
            self._set_mode("brownout")
        else:
            self._set_mode("normal")

    @property
    def draining(self) -> bool:
        return self._draining

    def request_deadline(self, request) -> float | None:
        """Absolute monotonic deadline for one request: the client's
        ``X-PIO-Deadline-Ms`` header when present (a tighter client
        budget wins), else the server's ``--deadline-ms`` default; None
        when neither is set."""
        ms = self.deadline_ms
        hdr = request.headers.get("X-PIO-Deadline-Ms")
        if hdr is not None:
            try:
                client_ms = float(hdr)
                if client_ms > 0:
                    ms = min(ms, client_ms) if ms > 0 else client_ms
            except ValueError:
                pass  # malformed header: fall back to the server default
        return time.monotonic() + ms / 1e3 if ms > 0 else None

    async def dispatch_query(self, query_json: dict,
                             deadline: float | None = None):
        """The one query entry for the HTTP layer: batched path when
        healthy, per-query fallback when degraded (with one half-open
        probe through the batcher per cooldown window), fallback also
        when batching is disabled."""
        if self.batcher is None:
            return await self._fallback_query(query_json, deadline)
        if self.degraded:
            now = time.monotonic()
            if self._probe_at is not None and now >= self._probe_at:
                # half-open probe: push the cooldown forward FIRST so
                # concurrent queries keep falling back while this one
                # tests the batched path
                self._probe_at = now + self.degraded_cooldown_s
                result = await self.batcher.submit(query_json,
                                                   deadline=deadline)
                # a tripped probe raises DispatchTimeout out of submit()
                # (another watchdog trip re-arms the cooldown); reaching
                # here means the batched path is healthy again
                self._exit_degraded()
                return result
            return await self._fallback_query(query_json, deadline)
        if self._mode == "brownout":
            # brownout serves on the per-query fallback path too: the
            # batcher's queue is the thing under pressure, and the
            # fallback path is bounded by deadline + watchdog
            return await self._fallback_query(query_json, deadline)
        return await self.batcher.submit(query_json, deadline=deadline)

    async def _fallback_query(self, query_json: dict,
                              deadline: float | None):
        """Per-query serving off the batcher (degraded mode or batching
        disabled), still bounded: the watchdog timeout and the request
        deadline both apply, whichever is tighter."""
        timeout = self.dispatch_timeout_s
        if deadline is not None:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                _M_DEADLINE.inc()
                raise DeadlineExceeded("request deadline expired")
            timeout = min(timeout, remaining) if timeout else remaining
        work = asyncio.to_thread(self.serve_query, query_json)
        if timeout is None:
            return await work
        try:
            return await asyncio.wait_for(work, timeout)
        except asyncio.TimeoutError:
            if deadline is not None and time.monotonic() >= deadline:
                _M_DEADLINE.inc()
                raise DeadlineExceeded(
                    "request deadline expired during serving") from None
            raise DispatchTimeout(
                f"per-query serve exceeded {timeout:.1f}s watchdog"
            ) from None

    async def drain(self) -> None:
        """Graceful drain (SIGTERM / /stop / app shutdown): stop
        accepting queries (handle_query 503s), flush the micro-batch
        queue, finish in-flight batches, close the feedback loop.
        Idempotent — /stop and the app-shutdown hook may both call it."""
        if self._draining:
            return
        self._draining = True
        log.info("drain: stopped accepting; flushing micro-batch queue")
        if self.batcher is not None:
            await self.batcher.drain()
        if self.feedback is not None:
            await self.feedback.aclose()
        if self.capture is not None:
            self.capture.close()
        if self.shadow is not None:
            await self.shadow.aclose()
        self._drained = True
        log.info("drain complete (served %d request(s) lifetime)",
                 self.request_count)

    @property
    def prewarming(self) -> bool:
        return self._prewarming

    def complete_prewarm(self) -> None:
        """Run the executable prewarm a ``defer_prewarm`` construction
        skipped, then flip ready. Lets a replica bind its port and
        answer /health.json (live, not ready) while the AOT compile of
        the batch lattice runs — the fleet router holds hashed traffic
        until ``ready`` goes true. Idempotent once it has succeeded."""
        if not self._prewarming:
            return
        # a failed prewarm raises and leaves the server not ready
        with self._reload_lock:
            self.deployed.prewarm_batch = self.batch_max
            self.deployed._prewarm()
        self._prewarming = False
        log.info("deferred prewarm complete; server is ready")

    def undrain(self) -> None:
        """Re-arm after a drain that did NOT end the process: a failed
        bind tears the app down (running the drain hook) before
        run_engine_server retries, and the retry must serve again."""
        self._draining = False
        self._drained = False
        if self.feedback is not None:
            self.feedback.reopen()

    def health(self) -> dict:
        """GET /health.json body: liveness + readiness + why. Load
        balancers key on the HTTP status (503 while draining); humans and
        autoscalers get the degraded/watchdog/drain detail.

        ISSUE 17 splits the two semantics cleanly: ``status``/``live``
        are LIVENESS (the process answers; restart it only when they
        say so), ``ready`` is ROUTER ELIGIBILITY — false during a
        deferred startup prewarm AND while draining, so a fleet router
        neither routes hashed traffic to a cold replica nor to one on
        its way out."""
        inst = self.deployed.instance
        b = self.batcher
        return {
            "status": ("draining" if self._draining
                       else self._mode if self._mode != "normal" else "ok"),
            "mode": self._mode,
            "live": True,
            "ready": not self._draining and not self._prewarming,
            "prewarming": self._prewarming,
            "variant": self.variant_id,
            "engineInstanceId": inst.id,
            "startTime": self.start_time.isoformat(),
            "admission": (self.admission.stats()
                          if self.admission is not None else None),
            "brownout": {
                "active": self._mode == "brownout",
                "since": self.brownout_since,
                "topk": self.brownout_topk,
            },
            "degraded": {
                "active": self.degraded,
                "since": self.degraded_since,
                "watchdogTrips": b.watchdog_trips if b else 0,
                "zombieDispatches": b.stats()["zombieDispatches"] if b else 0,
                "maxInflight": b.max_inflight if b else None,
                "dispatchTimeoutS": self.dispatch_timeout_s,
            },
            "drain": {"active": self._draining, "complete": self._drained},
            # ISSUE 11: burn rates next to liveness — the first question
            # after "is it up" is "is it eating its error budget"
            "slo": self.slo.summary(),
            "flight": self.flight.stats(),
            "model": {
                "engineInstanceId": inst.id,
                "fallbackActive": bool(self.deploy_skips),
                "skipped": self.deploy_skips,
                "patchEpoch": self.patch_epoch,
                "patchedUsers": len(self.patch_table),
            },
            "feedback": self.feedback.stats() if self.feedback else None,
        }

    # -- query hot path ----------------------------------------------------
    @staticmethod
    def _decode(algo, query_json: dict):
        decode = getattr(algo, "decode_query", None)
        if decode is not None:
            # CustomQuerySerializer hook (reference: controller/
            # CustomQuerySerializer.scala) — engine-defined decoding
            return decode(query_json)
        qcls = getattr(algo, "query_class", None)
        return parse_params(qcls, query_json) if qcls is not None else query_json

    def _costing(self):
        """What the micro-batcher asks at every cut (a /reload may swap
        the model): ``(cost_of(query_json), budget)`` where an algorithm
        of the CURRENT bundle states that its queries cost unequally
        (``Algorithm.cost_budget``), else None. The cost is the
        algorithm's own, of the parsed query; a query that does not
        parse costs nothing here and fails alone in
        ``serve_query_batch``."""
        result = self.deployed.result
        for algo, model in zip(result.algorithms, result.models):
            stated = getattr(algo, "cost_budget", None)
            budget = stated(model) if stated is not None else None
            if budget is None:
                continue

            def cost_of(query_json, algo=algo, model=model) -> int:
                try:
                    return int(algo.query_cost(
                        model, self._decode(algo, query_json)))
                except Exception:  # noqa: BLE001 - per-query isolation
                    return 0

            return cost_of, budget
        return None

    def serve_query(self, query_json: dict) -> dict:
        """Single-query path (batching disabled)."""
        tag, payload = self.serve_query_batch([query_json])[0]
        if tag == "err":
            raise payload
        return payload

    def serve_query_batch(self, query_jsons) -> list[tuple[str, Any]]:
        """Serve a coalesced batch; one outcome ("ok", result) |
        ("err", exception) PER query — a malformed query fails alone.

        Each algorithm predicts its whole sub-batch through
        ``batch_predict`` (retrieval models override it with one fused
        device call); serving blends per query as usual.
        """
        FAULTS.fire("server.serve_batch")
        # stage waterfall: time since the previous stage (the to_thread
        # hop on the fallback path; ~0 on the batched path, whose clock
        # just marked batch_form) is waiting-to-be-served time
        mark_stage("queue_wait")
        t0 = time.perf_counter()
        bundle = self.deployed  # snapshot reference (atomic swap safety)
        result = bundle.result
        n = len(query_jsons)
        errors: dict[int, Exception] = {}
        first_qs: list[Any] = list(query_jsons)
        per_algo: list[dict[int, Any]] = []
        for ai, (algo, model) in enumerate(zip(result.algorithms, result.models)):
            decoded: list[tuple[int, Any]] = []
            for i, qj in enumerate(query_jsons):
                if i in errors:
                    continue
                try:
                    q = self._decode(algo, qj)
                except Exception as e:  # noqa: BLE001 — per-query isolation
                    errors[i] = e
                    continue
                if ai == 0:
                    first_qs[i] = q
                decoded.append((i, q))
            preds: dict[int, Any] = {}
            if decoded:
                # only the batch's LAST device step may open the
                # micro-batcher's gate: an earlier algorithm's is muted
                muted = (set_step_end_hook(None)
                         if ai < len(result.algorithms) - 1 else None)
                try:
                    preds = dict(algo.batch_predict(model, decoded))
                except Exception:  # noqa: BLE001
                    # batch path failed; retry per query so one poison
                    # query doesn't take down its whole batch
                    log.exception("batch_predict failed; per-query fallback")
                    for i, q in decoded:
                        try:
                            preds[i] = algo.predict(model, q)
                        except Exception as e:  # noqa: BLE001
                            errors[i] = e
                finally:
                    if muted is not None:
                        reset_step_end_hook(muted)
            per_algo.append(preds)

        outcomes: list[tuple[str, Any]] = []
        # serving blend + outcome packaging (and, for models with no
        # device retriever, the host predict itself — documented in
        # obs/waterfall.py) is result-scatter work
        with stage_span("result_scatter", rows=n):
            for i in range(n):
                if i in errors:
                    outcomes.append(("err", errors[i]))
                    continue
                try:
                    preds = [pa[i] for pa in per_algo]
                    served = result.serving.serve(first_qs[i], preds)
                    outcomes.append(("ok", _to_jsonable(served)))
                except Exception as e:  # noqa: BLE001
                    outcomes.append(("err", e))

        dt = time.perf_counter() - t0
        with self._stats_lock:
            self.request_count += n
            self.last_serving_sec = dt / n
            self.avg_serving_sec += (
                (dt / n - self.avg_serving_sec) * n / self.request_count)
        return outcomes

    # -- deploy fallback (blob integrity / unloadable blobs) ---------------
    def _deploy_with_fallback(self, first: EngineInstance):
        """Try ``first``; when its blob is corrupt (ModelIntegrityError)
        or unloadable, walk the next-newest COMPLETED instances of the
        same engine triple. Returns (instance, TrainResult, skips);
        re-raises the FIRST error when every candidate fails."""
        candidates = [first]
        try:
            meta = Storage.get_metadata()
            for c in meta.engine_instance_get_completed(
                    first.engine_id, first.engine_version, first.engine_variant):
                if all(c.id != x.id for x in candidates):
                    candidates.append(c)
        except Exception:  # metadata unreachable: just try `first`
            log.exception("could not list fallback candidates")
        skips: list[dict] = []
        first_err: Exception | None = None
        for cand in candidates:
            try:
                result = prepare_deploy(self.engine, cand, self.ctx,
                                        engine_dir=self.engine_dir)
            except Exception as e:  # noqa: BLE001 — try the next-newest
                if first_err is None:
                    first_err = e
                skips.append({"engineInstanceId": cand.id,
                              "error": f"{type(e).__name__}: {e}"})
                log.error(
                    "deploy of engine instance %s failed (%s: %s); "
                    "falling back to the next-newest COMPLETED instance",
                    cand.id, type(e).__name__, e)
                continue
            if skips:
                log.warning(
                    "deployed engine instance %s after skipping %d "
                    "corrupt/unloadable newer instance(s): %s",
                    cand.id, len(skips),
                    [s["engineInstanceId"] for s in skips])
            return cand, result, skips
        assert first_err is not None
        raise first_err

    # -- hot reload (MasterActor ReloadServer, :315-336) -------------------
    def reload_latest(self) -> str:
        with self._reload_lock:
            return self._reload_latest()

    def _reload_latest(self) -> str:
        meta = Storage.get_metadata()
        inst = self.deployed.instance
        latest = meta.engine_instance_get_latest_completed(
            inst.engine_id, inst.engine_version, inst.engine_variant
        )
        if latest is None:
            raise RuntimeError("no COMPLETED engine instance to reload")
        # fallback walk: a corrupt newest blob must not take down a
        # healthy server — the old bundle keeps serving while we try the
        # next-newest COMPLETED instance
        fresh_inst, result, skips = self._deploy_with_fallback(latest)
        fresh = Deployed(fresh_inst, result,
                         retriever_mesh=self.deployed.retriever_mesh,
                         retriever_axis=self.deployed.retriever_axis,
                         prewarm_batch=self.batch_max,
                         # /reload preserves the ANN configuration (and
                         # rebuilds the index over the fresh factors)
                         retrieval=self.deployed.retrieval)
        # ISSUE 10: reconcile outstanding delta patches before the swap.
        # Deltas for users the fresh instance trained are superseded
        # (training saw their journaled events) and are discarded; deltas
        # for users STILL unseen by training re-apply onto the fresh
        # bundle so a reload never un-personalizes a folded-in user.
        if self.patch_table:
            keep = {u: f for u, f in self.patch_table.items()
                    if not any(u in getattr(m, "user_ids", ())
                               for m in fresh.result.models)}
            discarded = len(self.patch_table) - len(keep)
            if keep:
                models, applied = self._patch_models(fresh.result.models, keep)
                fresh.result = dataclasses.replace(fresh.result, models=models)
                keep = {u: keep[u] for u in applied}
            self.patch_table = keep
            self.patch_discarded += discarded
            self.patch_epoch += 1
            _M_DELTA_EPOCH.set(self.patch_epoch)
            self._track_patch_table_bytes()
            log.info("reload reconciled delta patches: %d discarded as "
                     "superseded, %d re-applied", discarded, len(keep))
        self.deployed = fresh  # atomic reference swap
        self.deploy_skips = skips
        log.info("Reloaded engine instance %s", fresh_inst.id)
        return fresh_inst.id

    # -- delta hot-patch (ISSUE 10: streaming fold-in publish target) ------
    @staticmethod
    def _patch_models(models, patches: dict) -> tuple[list, set]:
        """Apply ``{user_id: factor}`` to every model carrying user-side
        factors whose rank matches. Copy-on-write: patched models are
        shallow clones with fresh ``user_factors`` (and an extended
        ``user_ids`` map for users unseen at train time); attached
        item-side retrievers carry over untouched — item factors never
        change here, so the ANN index and compiled retrieval programs
        stay valid. Returns ``(new_models, applied_user_ids)``."""
        new_models = list(models)
        applied: set = set()
        for mi, model in enumerate(models):
            ids = getattr(model, "user_ids", None)
            uf = getattr(model, "user_factors", None)
            if ids is None or uf is None or getattr(uf, "ndim", 0) != 2:
                continue
            rank = uf.shape[1]
            updates: dict[int, np.ndarray] = {}
            appends: list[tuple[str, np.ndarray]] = []
            for uid, vec in patches.items():
                if vec.shape != (rank,):
                    continue
                row = ids.get(uid)
                if row is None:
                    appends.append((uid, vec))
                else:
                    updates[int(row)] = vec
            if not updates and not appends:
                continue
            # NOT copy.copy: the serving mixin's __getstate__ strips the
            # attached retriever from pickles, and copy() rides that —
            # a delta patch must never silently de-attach the retriever
            clone = object.__new__(type(model))
            clone.__dict__.update(model.__dict__)
            factors = np.array(uf, dtype=uf.dtype)
            for row, vec in updates.items():
                factors[row] = vec.astype(factors.dtype)
            if appends:
                mapping = ids.to_dict()
                base = factors.shape[0]
                for j, (uid, vec) in enumerate(appends):
                    mapping[uid] = base + j
                factors = np.vstack(
                    [factors] + [v[None, :].astype(factors.dtype)
                                 for _, v in appends])
                clone.user_ids = type(ids)(mapping)
            clone.user_factors = factors
            pipe = getattr(clone, "_pipeline", None)
            if pipe is not None:
                # ISSUE 16: the epoch bump re-uploads the device query
                # table copy-on-write — compiled programs stay valid
                # (capacity headroom absorbs appended users), in-flight
                # dispatches keep the table they were launched with
                try:
                    clone._pipeline = pipe.refresh(factors)
                except Exception:  # noqa: BLE001 — serving must not die
                    log.exception("pipeline refresh failed; detaching "
                                  "(retriever-only dispatch until next "
                                  "reload)")
                    clone._pipeline = None
            new_models[mi] = clone
            applied.update(u for u, _ in appends)
            applied.update(u for u, v in patches.items()
                           if v.shape == (rank,) and ids.get(u) is not None)
        return new_models, applied

    def apply_delta(self, patches: dict) -> dict:
        """POST /reload/delta body ``users``: ``{user_id: [factor]}``.
        Validates, bounds the patch table, swaps a copy-on-write bundle
        under the reload lock, bumps the monotonic patch epoch."""
        with self._reload_lock:
            return self._apply_delta(patches)

    def _apply_delta(self, patches: dict) -> dict:
        clean: dict[str, np.ndarray] = {}
        invalid: list[str] = []
        for uid, vec in patches.items():
            uid = str(uid)
            try:
                arr = np.asarray(vec, dtype=np.float32)
            except (TypeError, ValueError):
                invalid.append(uid)
                continue
            if arr.ndim != 1 or arr.size == 0 or not np.all(np.isfinite(arr)):
                invalid.append(uid)
                continue
            clean[uid] = arr
        # rank-check BEFORE bounding: a vector no model can absorb must
        # not consume a table slot that a valid user would have kept
        bundle = self.deployed
        ranks = {m.user_factors.shape[1] for m in bundle.result.models
                 if getattr(m, "user_ids", None) is not None
                 and getattr(getattr(m, "user_factors", None),
                             "ndim", 0) == 2}
        rank_mismatch = sorted(u for u, v in clean.items()
                               if v.size not in ranks)
        for u in rank_mismatch:
            clean.pop(u)
        # bounded patch table: users already tracked always re-patch;
        # NEW users only while there is room (deterministic drop order)
        room = self.patch_table_max - len(self.patch_table)
        fresh_users = sorted(u for u in clean if u not in self.patch_table)
        table_full = fresh_users[max(0, room):]
        for u in table_full:
            clean.pop(u)
        new_models, applied = self._patch_models(bundle.result.models, clean)
        if applied:
            fresh = object.__new__(Deployed)
            fresh.__dict__.update(bundle.__dict__)
            fresh.result = dataclasses.replace(bundle.result,
                                               models=new_models)
            self.deployed = fresh  # atomic reference swap
            self.patch_epoch += 1
            _M_DELTA_EPOCH.set(self.patch_epoch)
            for u in applied:
                self.patch_table[u] = clean[u]
            self._track_patch_table_bytes()
        return {
            "appliedCount": len(applied),
            "applied": sorted(applied),
            "epoch": self.patch_epoch,
            "patchedUsers": len(self.patch_table),
            "dropped": {"invalid": invalid, "tableFull": table_full,
                        "rankMismatch": rank_mismatch},
        }

    def _track_patch_table_bytes(self) -> None:
        """Re-count the delta patch table's residency whole (absolute
        set, self-healing) into the device ledger's HBM gauge — the
        table's factor rows are the one serving-side buffer that grows
        with traffic rather than with deployed shapes (ISSUE 12)."""
        LEDGER.track_buffer(
            ("patch_table" if self.variant_id == "default"
             else f"patch_table/{self.variant_id}"),
            sum(int(v.nbytes) for v in self.patch_table.values()))

    def status(self) -> dict:
        inst = self.deployed.instance
        return {
            "status": "alive",
            "engineInstanceId": inst.id,
            "engineVariant": inst.engine_variant,
            "engineFactory": inst.engine_factory,
            "startTime": self.start_time.isoformat(),
            "requestCount": self.request_count,
            "avgServingSec": self.avg_serving_sec,
            "lastServingSec": self.last_serving_sec,
            "algorithms": [type(a).__name__ for a in self.deployed.result.algorithms],
            **({"batching": self.batcher.stats()} if self.batcher else {}),
        }

    def _retrieval_stats(self, bundle: "Deployed | None" = None,
                         ) -> dict | None:
        """The deployed bundle's retrieval posture: the first attached
        retriever's stats() (AnnRetriever: index cells / nprobe /
        quantize / build seconds / exact-fallback flag), mode plus the
        resolved kernel (native | xla | interpret) for exact device
        retrievers, None when serving from host
        scoring. Pass the bundle snapshot serving_stats took under the
        reload lock so the block cannot tear against a concurrent swap."""
        bundle = bundle if bundle is not None else self.deployed
        for model in bundle.result.models:
            r = getattr(model, "_retriever", None)
            if r is None:
                continue
            if hasattr(r, "stats"):
                return r.stats()
            return {"mode": "exact", "kernel": r.kernel,
                    "nTotal": getattr(r, "n_total", None),
                    "sharded": type(r).__name__ == "ShardedDeviceRetriever"}
        return None

    def _pipeline_stats(self, bundle: "Deployed | None" = None) -> dict:
        """The first attached ServingPipeline's stats() (ISSUE 16;
        overlap ratio, staging pool, table capacity) — an empty block
        when nothing attached."""
        bundle = bundle if bundle is not None else self.deployed
        for model in bundle.result.models:
            p = getattr(model, "_pipeline", None)
            if p is not None:
                return p.stats()
        return {}

    def variant_stats(self) -> dict:
        """The per-variant slice of serving_stats (ISSUE 14): what is
        distinct about THIS variant — counters, mode, SLO, admission,
        patch posture, provenance. Shared-process blocks (execCache,
        device ledger, waterfall histograms) stay on the top level of
        /stats.json: they are shared by construction."""
        with self._stats_lock:
            counters = {
                "requestCount": self.request_count,
                "avgServingSec": self.avg_serving_sec,
                "lastServingSec": self.last_serving_sec,
            }
        with self._reload_lock:
            bundle = self.deployed
            patches_block = {
                "epoch": self.patch_epoch,
                "patchedUsers": len(self.patch_table),
                "tableMax": self.patch_table_max,
                "discardedByReload": self.patch_discarded,
            }
            prov_block = self.provenance(bundle)
        return {
            "variant": self.variant_id,
            **counters,
            "mode": self._mode,
            "slo": self.slo.summary(),
            "admission": (self.admission.stats()
                          if self.admission is not None else None),
            "batching": self.batcher.stats() if self.batcher else None,
            "patches": patches_block,
            "streamGate": self.last_stream_gate,
            "provenance": prov_block,
        }

    def serving_stats(self) -> dict:
        """Machine-readable serving telemetry (GET /stats.json): request
        counters, micro-batcher window/occupancy, and the shared
        executable-cache hit/miss/eviction counters."""
        from ..ops.retrieval import EXEC_CACHE

        with self._stats_lock:
            counters = {
                "requestCount": self.request_count,
                "avgServingSec": self.avg_serving_sec,
                "lastServingSec": self.last_serving_sec,
            }
        # ISSUE 11 fix: every bundle-derived sub-block snapshots under
        # the reload lock, so a concurrent delta patch / full reload can
        # never interleave a torn view (patch epoch from the new bundle,
        # model/retrieval blocks from the old). The bundle reference is
        # immutable once swapped, so the derived retrieval stats are
        # computed OUTSIDE the lock from the snapshot.
        with self._reload_lock:
            bundle = self.deployed
            model_block = {
                "engineInstanceId": bundle.instance.id,
                "fallbackActive": bool(self.deploy_skips),
                "skipped": self.deploy_skips,
            }
            patches_block = {
                "epoch": self.patch_epoch,
                "patchedUsers": len(self.patch_table),
                "tableMax": self.patch_table_max,
                "discardedByReload": self.patch_discarded,
            }
            # ISSUE 13: the scattered identity fields above, unified in
            # one block — the same envelope every response header carries
            prov_block = self.provenance(bundle)
        # ISSUE 14: traffic split + per-variant slices. On a child
        # server this is its own one-entry table; on the primary it is
        # the process router the /variants endpoints mutate.
        pipeline_block = self._pipeline_stats(bundle)
        # a sequence model's step counters (tokens real and computed,
        # steps, loop passes): their own block, beside `pipeline`
        sequence_block = pipeline_block.pop("sequence", None)
        variants_block = self.variants.snapshot()
        if variants_block["count"] > 1:
            variants_block["byVariant"] = {
                e.variant_id: e.server.variant_stats()
                for e in self.variants.entries()}

        def _hist(name: str):
            h = METRICS.get(name)
            return h.snapshot() if h is not None else None

        return {
            **counters,
            # thin view over the obs registry: the same histograms
            # /metrics exports, as count/sum/p50/p95/p99 (seconds)
            "latency": {
                "serving": _hist("pio_serving_latency_seconds"),
                "queueWait": _hist("pio_microbatch_queue_wait_seconds"),
                "dispatch": _hist("pio_microbatch_dispatch_seconds"),
                "device": _hist("pio_microbatch_device_seconds"),
            },
            # ISSUE 11: per-stage attribution + host/device split — the
            # live answer to "where did the milliseconds go"
            "waterfall": stage_summary(),
            "slo": self.slo.summary(),
            "flight": self.flight.stats(),
            "ingress": self.ingress.stats(),
            "batching": self.batcher.stats() if self.batcher else None,
            "execCache": EXEC_CACHE.stats(),
            # ISSUE 7: the active retrieval mode + ANN index facts
            # (cells / nprobe / quantize / build seconds / fallback)
            "retrieval": self._retrieval_stats(bundle),
            # ISSUE 16: device-resident dispatch posture (overlap ratio,
            # staging pool, capacity); empty where no model has a pipeline
            "pipeline": pipeline_block,
            **({"sequence": sequence_block} if sequence_block else {}),
            "admission": (self.admission.stats()
                          if self.admission is not None else None),
            "resilience": {
                "mode": self._mode,
                "degraded": self.degraded,
                "degradedSince": self.degraded_since,
                "brownoutSince": self.brownout_since,
                "codelDropped": (self.batcher.codel_dropped
                                 if self.batcher else 0),
                "watchdogTrips": (self.batcher.watchdog_trips
                                  if self.batcher else 0),
                "deadlineExpired": (self.batcher.deadline_expired
                                    if self.batcher else 0),
                "draining": self._draining,
            },
            "model": model_block,
            # ISSUE 10: streaming delta hot-patch posture
            "patches": patches_block,
            "provenance": prov_block,
            # ISSUE 14: variant table — traffic split and per-variant
            # request/SLO/admission/patch slices
            "variants": variants_block,
            "capture": self.capture.stats() if self.capture else None,
            "shadow": self.shadow.stats() if self.shadow else None,
            "feedback": self.feedback.stats() if self.feedback else None,
            # ISSUE 12: the device ledger (HBM by component, compile
            # times, padding waste) + train/stream convergence; and which
            # device this process holds, as JAX reports it
            "device": {**device_identity(), **LEDGER.snapshot()},
            "train": TRAINING.snapshot(),
            # what this process spent before it was ready, phase by
            # phase, with the host memory in use at each phase's end
            "startup": STARTUP.snapshot(),
        }


SERVER_KEY = web.AppKey("engine_server", EngineServer)


async def handle_query(request: web.Request) -> web.Response:
    primary: EngineServer = request.app[SERVER_KEY]
    # ISSUE 14: `server` is rebound to the ROUTED variant's server once
    # the routing key is known; until then (draining / parse errors) the
    # primary answers and the outcome is attributed to it.
    server: EngineServer = primary
    # trace ingress: adopt the client's X-PIO-Request-ID or mint one;
    # the contextvar follows the request through the micro-batcher and
    # into the feedback event (pio_request_id), and every response
    # echoes the id so the client can quote it back
    rid = ensure_request_id(request.headers.get(TRACE_HEADER))
    t0 = time.perf_counter()
    # ISSUE 11: per-request stage waterfall. Installed as the ambient
    # stage sink so the FALLBACK path's to_thread worker (which copies
    # this context) marks straight onto it; the batched path's shared
    # stages ride the dispatch BatchClock and merge in at completion.
    wf = Waterfall(rid=rid)
    sink_token = set_stage_sink(wf)
    # the EFFECTIVE query (post brownout clamp) — what capture persists
    # and replay re-issues, so replay against a normal-mode server is
    # still deterministic
    eff_query: dict | None = None

    def _done(status_label: str, body: dict, status: int = 200,
              retry_after_s: float | None = None) -> web.Response:
        wall = time.perf_counter() - t0
        _M_SERVE.record(wall)
        _M_QUERIES.inc(status=status_label)
        # per-variant outcome series rides the primary's router table
        primary.variants.count_query(server.variant_id, status_label)
        # SLO accounting: latency objective sees the client-observed
        # wall; availability counts server-side failures (5xx) as bad
        server.slo.observe(wall, ok=status < 500)
        reset_stage_sink(sink_token)
        wf.finish(status_label)
        wf.meta["http"] = status
        wf.meta["mode"] = server.mode
        wf.meta["variant"] = server.variant_id
        server.flight.record(wf.to_dict())
        # held, and written by the loop in a batch (obs/ingress_lines.py)
        note_serve_ingress(primary.ingress, rid, status_label, status,
                           round((time.perf_counter() - t0) * 1e3, 3))
        headers = {TRACE_HEADER: rid}
        # ISSUE 13: every response names exactly what served it — the
        # ROUTED variant's envelope (carries variantId, ISSUE 14)
        try:
            headers[PROVENANCE_HEADER] = server.provenance_header()
        except Exception:  # noqa: BLE001 — provenance must not 500 a query
            pass
        # capture rides the primary's ring (one journal per process) but
        # persists the routed variant's provenance, so replay can re-pin
        # each record to the variant that answered it
        if primary.capture is not None and eff_query is not None:
            primary.capture.record(
                rid=rid, request=eff_query, response=body, status=status,
                latency_ms=wall * 1e3, provenance=server.provenance())
        if retry_after_s is not None:
            # decimal seconds: our own clients (FeedbackPublisher) parse
            # floats, and sub-second pacing matters at serving rates
            headers["Retry-After"] = f"{max(0.0, retry_after_s):.3f}"
        return web.json_response(body, status=status, headers=headers)

    if primary.draining:
        return _done("draining",
                     {"message": "Server is draining; not accepting queries."},
                     503)
    try:
        # the body first (the await), then parse and admit as one
        # synchronous span: a span never holds an await
        body_text = await request.text()
    except UnicodeDecodeError:
        return _done("bad_request", {"message": "Malformed JSON body."}, 400)
    # body parsed + admission decided: everything since ingress is the
    # admission stage, marked at the span's end; the batcher (or the
    # fallback path) owns time from there
    with stage_span("admission"):
        try:
            query_json = json.loads(body_text)
        except json.JSONDecodeError:
            return _done("bad_request", {"message": "Malformed JSON body."},
                         400)
        if not isinstance(query_json, dict):
            return _done("bad_request",
                         {"message": "Query must be a JSON object."}, 400)
        # ISSUE 14: pick the serving variant — forced by header (replay,
        # debugging; unknown names fail loud) or hashed on the entity id
        # so a user sticks to one variant between weight changes
        forced = request.headers.get(VARIANT_HEADER)
        try:
            entry, _how = primary.variants.route(
                entity_key(query_json), forced=forced)
        except KeyError:
            return _done("bad_request",
                         {"message": f"unknown variant {forced!r}"}, 400)
        server = entry.server
        if server.admission is not None:
            # adaptive admission (ISSUE 6): shed at ingress with 429 +
            # Retry-After before the request can pay the queue just to
            # 504. Per-variant (ISSUE 14): an overloaded candidate sheds
            # alone.
            client_key = (request.query.get("accessKey")
                          or request.headers.get("X-PIO-Access-Key")
                          or (request.remote or "unknown"))
            decision = server.admission.decide("serve", key=client_key)
            server._update_brownout()
            if not decision.admitted:
                return _done("shed",
                             {"message": f"overloaded; retry later "
                                         f"({decision.reason})"},
                             429, retry_after_s=decision.retry_after_s)
    try:
        eff_query = server.brownout_degrade(query_json)
        result = await server.dispatch_query(
            eff_query, deadline=server.request_deadline(request))
    except DeadlineExceeded as e:
        return _done("deadline", {"message": str(e)}, 504)
    except DispatchTimeout as e:
        return _done("watchdog", {"message": str(e)}, 504)
    except ServerBusy as e:
        return _done("busy", {"message": str(e)}, 503)
    except Exception as e:  # noqa: BLE001 — surface as 400 like the reference
        log.exception("query failed")
        return _done("error", {"message": str(e)}, 400)
    if server.shadow is not None and isinstance(result, dict):
        # fire-and-forget mirror of the effective query to the shadow
        # target; the diff tier lands on pio_shadow_diff_total
        server.shadow.mirror(eff_query, result, rid)
    publish = server.feedback is not None
    if publish and server.mode != "normal":
        # brownout/degraded sheds feedback publication first — it is the
        # cheapest work to lose and its class threshold agrees (0.7)
        publish = False
    if publish and server.admission is not None:
        publish = server.admission.decide("feedback").admitted
    if publish:
        pr_id = uuid.uuid4().hex
        result_with_pr = {**result, "prId": pr_id} if isinstance(result, dict) else result
        server.feedback.publish(query_json, result, pr_id, request_id=rid)
        return _done("ok", result_with_pr)
    return _done("ok", result)


def _status_html(s: dict) -> str:
    """Minimal server-rendered status page — the analog of the reference's
    Twirl index template (core/src/main/twirl/, served from
    CreateServer.scala:433-460). Same data as the JSON status."""
    import html as _html

    rows = "".join(
        f"<tr><th>{_html.escape(str(k))}</th>"
        f"<td>{_html.escape(json.dumps(v) if isinstance(v, (dict, list)) else str(v))}</td></tr>"
        for k, v in s.items()
    )
    return (
        "<!DOCTYPE html><html><head><title>PredictionIO-TPU Engine Server"
        "</title><style>body{font-family:sans-serif;margin:2em}"
        "table{border-collapse:collapse}th,td{border:1px solid #ccc;"
        "padding:.35em .7em;text-align:left}th{background:#f3f3f3}"
        "code{background:#f7f7f7;padding:0 .3em}</style></head><body>"
        "<h1>Engine server is running</h1>"
        f"<table>{rows}</table>"
        "<p>POST a query to <code>/queries.json</code>; "
        "<a href='/reload'>reload</a> the latest trained instance.</p>"
        "</body></html>"
    )


async def handle_status(request: web.Request) -> web.Response:
    s = request.app[SERVER_KEY].status()
    accept = request.headers.get("Accept", "")
    if "text/html" in accept and "application/json" not in accept.split(";")[0]:
        return web.Response(text=_status_html(s), content_type="text/html")
    return web.json_response(s)


async def handle_stats_json(request: web.Request) -> web.Response:
    return web.json_response(request.app[SERVER_KEY].serving_stats())


async def handle_reload(request: web.Request) -> web.Response:
    server: EngineServer = request.app[SERVER_KEY]
    # ISSUE 14: a full reload reconciles EVERY non-retired variant — each
    # variant reloads its own (engine_id, version, variant) triple and
    # re-applies its own surviving delta patches
    reloaded: dict[str, str] = {}
    for e in server.variants.entries():
        if e.state == "retired":
            continue
        try:
            reloaded[e.variant_id] = await asyncio.to_thread(
                e.server.reload_latest)
        except Exception as exc:  # noqa: BLE001
            return web.json_response(
                {"message": str(exc), "variant": e.variant_id}, status=500)
    body = {"message": "Reloaded",
            "engineInstanceId": reloaded.get(
                server.variant_id, next(iter(reloaded.values()), None))}
    if len(reloaded) > 1:
        body["variants"] = reloaded
    return web.json_response(body)


async def handle_reload_delta(request: web.Request) -> web.Response:
    """POST /reload/delta — the streaming updater's publish target
    (ISSUE 10): ``{"users": {user_id: [factor]}}`` hot-patches user-side
    factors copy-on-write under the reload lock. Item factors are never
    touched, so the ANN index and compiled retrieval programs stay
    valid; unseen users are appended (bounded by the patch table).

    ISSUE 14: an optional ``"variant"`` field routes the patch to that
    variant's OWN bounded patch table; unknown or retired variants are
    rejected 400 (counted) — a delta must never silently land on
    whatever bundle happens to be live. Without the field the patch
    goes to the live variant (single-variant behavior unchanged)."""
    primary: EngineServer = request.app[SERVER_KEY]
    rid = ensure_request_id(request.headers.get(TRACE_HEADER))
    headers = {TRACE_HEADER: rid}
    if primary.draining:
        _M_DELTA.inc(status="draining")
        return web.json_response(
            {"message": "Server is draining; not accepting patches."},
            status=503, headers=headers)
    try:
        body = await request.json()
    except (json.JSONDecodeError, UnicodeDecodeError):
        _M_DELTA.inc(status="bad_request")
        return web.json_response({"message": "Malformed JSON body."},
                                 status=400, headers=headers)
    users = body.get("users") if isinstance(body, dict) else None
    if not isinstance(users, dict) or not users:
        _M_DELTA.inc(status="bad_request")
        return web.json_response(
            {"message": 'Body must be {"users": {user_id: [factor, ...]}}.'},
            status=400, headers=headers)
    vid = body.get("variant") if isinstance(body, dict) else None
    if vid is not None:
        entry = primary.variants.get(str(vid))
        if entry is None:
            _M_DELTA.inc(status="bad_request")
            primary.variants.count_delta_rejected(str(vid), "unknown")
            return web.json_response(
                {"message": f"unknown variant {vid!r}"},
                status=400, headers=headers)
        if entry.state == "retired":
            _M_DELTA.inc(status="bad_request")
            primary.variants.count_delta_rejected(str(vid), "retired")
            return web.json_response(
                {"message": f"variant {vid!r} is retired"},
                status=400, headers=headers)
        server = entry.server
    else:
        live = primary.variants.live()
        server = live.server if live is not None else primary
    try:
        out = await asyncio.to_thread(server.apply_delta, users)
    except Exception as e:  # noqa: BLE001 — publish path must see a 500
        log.exception("delta patch failed")
        _M_DELTA.inc(status="error")
        return web.json_response({"message": str(e)}, status=500,
                                 headers=headers)
    gate = body.get("gate")
    if isinstance(gate, dict):
        # the publisher's latest eval-gate hit@k rides along with the
        # patch; keep it on the variant it was measured FOR
        server.last_stream_gate = gate
    _M_DELTA.inc(status="ok" if out["appliedCount"] else "empty")
    trace_event("serve.delta", users=out["appliedCount"],
                epoch=out["epoch"], variant=server.variant_id)
    return web.json_response(
        {"message": "Patched", "variant": server.variant_id, **out},
        headers=headers)


async def handle_health(request: web.Request) -> web.Response:
    """Liveness/readiness. 200 while serving (even degraded — the
    instance still answers queries on the fallback path), 503 while
    draining so a load balancer rotates it out before exit."""
    server: EngineServer = request.app[SERVER_KEY]
    body = server.health()
    # ISSUE 14: per-variant liveness — each co-hosted variant's mode,
    # SLO posture and patch epoch, keyed for the triage queries in the
    # multi-variant runbook
    if len(server.variants) > 1:
        body["variants"] = {
            e.variant_id: {
                "state": e.state,
                "weight": e.weight,
                "mode": e.server.mode,
                "engineInstanceId": e.server.engine_instance_id,
                "patchEpoch": e.server.patch_epoch,
                "slo": e.server.slo.summary(),
            }
            for e in server.variants.entries()}
    return web.json_response(body, status=503 if server.draining else 200)


async def handle_flight(request: web.Request) -> web.Response:
    """GET /debug/flight.json — the always-on flight recorder: the last
    N request waterfalls with mode/queue context, the same payload the
    recorder dumps to disk on an incident. Safe to hit in production —
    it is a ring snapshot, no locks shared with the serve path beyond
    the recorder's own."""
    server: EngineServer = request.app[SERVER_KEY]
    return web.json_response(server.flight.snapshot())


async def handle_profile(request: web.Request) -> web.Response:
    """POST /debug/profile?seconds=S[&dir=...] — capture a jax.profiler
    trace of the LIVE serving process for S seconds, bracketed by flight
    snapshots so the trace can be lined up against the waterfalls that
    fell inside the window. One capture at a time (409 while busy)."""
    server: EngineServer = request.app[SERVER_KEY]
    try:
        seconds = float(request.query.get("seconds", "5"))
    except ValueError:
        return web.json_response({"message": "seconds must be a number"},
                                 status=400)
    seconds = min(max(seconds, 0.1), 120.0)
    trace_dir = request.query.get("dir") or os.path.join(
        tempfile.gettempdir(), f"pio-profile-{int(time.time() * 1e3)}")
    if server._profiling:
        return web.json_response(
            {"message": "a profile capture is already running"}, status=409)
    server._profiling = True
    try:
        before = server.flight.snapshot()
        from .tracing import maybe_profile
        with maybe_profile(trace_dir):
            await asyncio.sleep(seconds)
        after = server.flight.snapshot()
        _M_PROFILE.inc()
    finally:
        server._profiling = False
    return web.json_response({
        "message": "Profile captured",
        "traceDir": trace_dir,
        "seconds": seconds,
        "flightBefore": before,
        "flightAfter": after,
    })


async def handle_capture_start(request: web.Request) -> web.Response:
    """POST /capture/start — (re-)enable golden-traffic recording. 409
    when the server was deployed without --capture-dir: the ring and its
    journal only exist when a directory was provisioned at deploy."""
    server: EngineServer = request.app[SERVER_KEY]
    if server.capture is None:
        return web.json_response(
            {"message": "capture is not configured; deploy with "
                        "--capture-dir"}, status=409)
    server.capture.start()
    return web.json_response({"message": "Capture started.",
                              "capture": server.capture.stats()})


async def handle_capture_stop(request: web.Request) -> web.Response:
    """POST /capture/stop — stop recording and flush the ring so
    everything captured so far is on disk for export/replay."""
    server: EngineServer = request.app[SERVER_KEY]
    if server.capture is None:
        return web.json_response(
            {"message": "capture is not configured; deploy with "
                        "--capture-dir"}, status=409)
    server.capture.stop()
    return web.json_response({"message": "Capture stopped and flushed.",
                              "capture": server.capture.stats()})


async def handle_variants(request: web.Request) -> web.Response:
    """GET /variants.json — the variant table: lifecycle state, weight,
    normalized traffic share and routed-query counts per variant."""
    server: EngineServer = request.app[SERVER_KEY]
    return web.json_response(server.variants.snapshot())


async def handle_variant_register(request: web.Request) -> web.Response:
    """POST /variants — register another trained engine variant into
    THIS process (``pio deploy --variant-of`` lands here). The bundle
    must rehydrate inside the serving process, so the body names what to
    load (engineDir [+ engineJson] or a pinned engineInstanceId) and the
    server does the deploy work itself; the new variant starts as a
    ``candidate`` with the given traffic weight."""
    primary: EngineServer = request.app[SERVER_KEY]
    try:
        body = await request.json()
    except (json.JSONDecodeError, UnicodeDecodeError):
        return web.json_response({"message": "Malformed JSON body."},
                                 status=400)
    if not isinstance(body, dict):
        return web.json_response({"message": "Body must be an object."},
                                 status=400)
    vid = str(body.get("variantId") or "").strip()
    if not vid:
        return web.json_response({"message": "variantId is required."},
                                 status=400)
    if primary.variants.get(vid) is not None:
        return web.json_response(
            {"message": f"variant {vid!r} already registered"}, status=409)
    engine_dir = body.get("engineDir")
    if not engine_dir:
        return web.json_response({"message": "engineDir is required."},
                                 status=400)
    try:
        weight = float(body.get("weight", 0.0))
    except (TypeError, ValueError):
        return web.json_response({"message": "weight must be a number."},
                                 status=400)

    def _build() -> EngineServer:
        from pathlib import Path

        from .core_workflow import resolve_engine_factory

        edir = Path(engine_dir)
        variant_json = edir / (body.get("engineJson") or "engine.json")
        variant = json.loads(variant_json.read_text())
        factory = variant.get("engineFactory")
        if not factory:
            raise ValueError(f"{variant_json} has no engineFactory field")
        engine = resolve_engine_factory(factory, engine_dir=edir)
        meta = Storage.get_metadata()
        pinned = body.get("engineInstanceId")
        if pinned:
            inst = meta.engine_instance_get(str(pinned))
            if inst is None:
                raise LookupError(f"engine instance {pinned!r} not found")
        else:
            engine_id = variant.get("id") or edir.resolve().name
            version = str(variant.get("version", "1"))
            meta_variant = str(variant.get("variantId", "default"))
            inst = meta.engine_instance_get_latest_completed(
                engine_id, version, meta_variant)
            if inst is None:
                raise LookupError(
                    f"no COMPLETED training of engine {engine_id} found")
        return EngineServer(
            engine, inst,
            variant_id=vid,
            engine_dir=edir,
            fallback=not pinned,
            batch_window_ms=float(body.get("batchWindowMs", 1.0)),
            batch_max=int(body.get("batchMax", primary.batch_max)),
            batch_inflight=int(body.get("batchInflight", 8)),
            deadline_ms=float(body.get("deadlineMs", primary.deadline_ms)),
            admission=bool(body.get("admission", False)),
            admission_queue_high=int(body.get("admissionQueueHigh", 64)),
            admission_wait_budget_ms=float(
                body.get("admissionWaitBudgetMs", 0.0)),
            rate_limit_qps=float(body.get("rateLimitQps", 0.0)),
            rate_limit_burst=float(body.get("rateLimitBurst", 0.0)),
            brownout_topk=int(body.get("brownoutTopk", 10)),
            slo_latency_ms=float(body.get("sloLatencyMs", 0.0)),
            patch_table_max=int(
                body.get("patchTableMax", primary.patch_table_max)),
            retrieval=(body.get("retrieval")
                       if isinstance(body.get("retrieval"), dict) else None),
        )

    try:
        child = await asyncio.to_thread(_build)
    except (LookupError, FileNotFoundError) as e:
        return web.json_response({"message": str(e)}, status=404)
    except Exception as e:  # noqa: BLE001 — registration must not 500-loop
        log.exception("variant registration failed")
        return web.json_response({"message": str(e)}, status=400)
    # the child's ctor pointed the shared flight recorder's ambient
    # context at itself; the app's primary stays authoritative
    primary.flight.set_context_provider(primary._flight_context)
    try:
        entry = primary.variants.register(vid, child, weight=weight)
    except ValueError as e:
        return web.json_response({"message": str(e)}, status=409)
    log.info("registered variant %r (instance %s, weight %s)",
             vid, child.engine_instance_id, weight)
    return web.json_response({"message": "Registered", **entry.snapshot()})


async def handle_variant_weight(request: web.Request) -> web.Response:
    """POST /variants/{vid}/weight — body ``{"weight": W}``. Only the
    two hash buckets whose relative weight changed re-shuffle users
    (rendezvous hashing); everyone else keeps their variant."""
    server: EngineServer = request.app[SERVER_KEY]
    vid = request.match_info["vid"]
    try:
        body = await request.json()
        weight = float(body["weight"])
    except (json.JSONDecodeError, UnicodeDecodeError, KeyError, TypeError,
            ValueError):
        return web.json_response(
            {"message": 'Body must be {"weight": <number>}.'}, status=400)
    try:
        entry = server.variants.set_weight(vid, weight)
    except KeyError:
        return web.json_response({"message": f"unknown variant {vid!r}"},
                                 status=404)
    except ValueError as e:
        return web.json_response({"message": str(e)}, status=400)
    return web.json_response({"message": "Weight set", **entry.snapshot()})


async def handle_variant_promote(request: web.Request) -> web.Response:
    """POST /variants/{vid}/promote — candidate becomes live, swapping
    weights with the previous live variant. Purely a routing-table flip:
    both bundles stay deployed, in-flight requests finish on whichever
    variant admitted them."""
    server: EngineServer = request.app[SERVER_KEY]
    vid = request.match_info["vid"]
    try:
        out = server.variants.promote(vid)
    except KeyError:
        return web.json_response({"message": f"unknown variant {vid!r}"},
                                 status=404)
    except ValueError as e:
        return web.json_response({"message": str(e)}, status=400)
    log.info("promoted variant %r (previous live: %s)",
             vid, out.get("previousLive"))
    return web.json_response({"message": "Promoted", **out,
                              "variants": server.variants.snapshot()})


async def handle_variant_retire(request: web.Request) -> web.Response:
    """POST /variants/{vid}/retire — take a candidate out of rotation.
    The bundle stays resident (forced-header routing still reaches it
    for replay) until the process restarts without it."""
    server: EngineServer = request.app[SERVER_KEY]
    vid = request.match_info["vid"]
    try:
        entry = server.variants.retire(vid)
    except KeyError:
        return web.json_response({"message": f"unknown variant {vid!r}"},
                                 status=404)
    except ValueError as e:
        return web.json_response({"message": str(e)}, status=400)
    log.info("retired variant %r", vid)
    return web.json_response({"message": "Retired", **entry.snapshot()})


def _raise_graceful_exit() -> None:
    raise web.GracefulExit()


async def handle_stop(request: web.Request) -> web.Response:
    server: EngineServer = request.app[SERVER_KEY]

    async def _stop():
        # drain BEFORE GracefulExit: stop accepting, flush the queue,
        # finish in-flight batches, close the feedback loop — then let
        # run_app tear the listener down
        try:
            await server.drain()
        except Exception:  # noqa: BLE001 — exit regardless
            log.exception("drain failed during /stop; exiting anyway")
        # raised from a loop callback, the way run_app's own signal
        # handlers do: raised inside this task it would also be stored
        # as the task's never-retrieved exception and logged at ERROR
        asyncio.get_running_loop().call_soon(_raise_graceful_exit)

    server._stop_task = asyncio.create_task(_stop())
    return web.json_response({"message": "Shutting down."})


def create_engine_server_app(server: EngineServer) -> web.Application:
    # trace middleware is defense in depth: handle_query stamps its own
    # header (setdefault keeps those authoritative) but aiohttp-raised
    # errors (404, 405, oversized body) get stamped here too
    app = web.Application(middlewares=[make_trace_middleware()])
    app[SERVER_KEY] = server
    app.router.add_post("/queries.json", handle_query)
    app.router.add_get("/", handle_status)
    app.router.add_get("/stats.json", handle_stats_json)
    app.router.add_get("/metrics", handle_metrics)
    app.router.add_get("/health.json", handle_health)
    app.router.add_get("/reload", handle_reload)
    app.router.add_post("/reload/delta", handle_reload_delta)
    app.router.add_get("/debug/flight.json", handle_flight)
    app.router.add_post("/debug/profile", handle_profile)
    app.router.add_post("/capture/start", handle_capture_start)
    app.router.add_post("/capture/stop", handle_capture_stop)
    # ISSUE 14: variant lifecycle — register / list / weight / promote /
    # retire N co-hosted engine variants on one device pool
    app.router.add_get("/variants.json", handle_variants)
    app.router.add_post("/variants", handle_variant_register)
    app.router.add_post("/variants/{vid}/weight", handle_variant_weight)
    app.router.add_post("/variants/{vid}/promote", handle_variant_promote)
    app.router.add_post("/variants/{vid}/retire", handle_variant_retire)
    app.router.add_get("/stop", handle_stop)

    def _variant_servers():
        # stub servers in tests may carry no VariantTable at all
        table = getattr(server, "variants", None)
        return table.servers() if table is not None else [server]

    async def _drain_server(app):
        # graceful drain on ANY teardown (SIGTERM -> run_app's
        # GracefulExit, /stop, test cleanup): flush queued queries,
        # finish in-flight batches, close the feedback session.
        # server.drain() is idempotent — /stop may already have run it.
        # Every registered variant drains (the primary is in its own
        # table), so in-flight requests on candidates finish too.
        for s in _variant_servers():
            await s.drain()

    async def _close_batcher(app):
        # after drain, stop the dispatcher loop so nothing leaks; any
        # future still pending at this point gets CancelledError
        for s in _variant_servers():
            if s.batcher is not None:
                await s.batcher.close()

    app.on_shutdown.append(_drain_server)
    app.on_cleanup.append(_close_batcher)
    # the held log lines (a stub server in the tests holds none): the loop
    # is about to stop and may never run the flush it was asked for
    lines = getattr(server, "ingress", None)
    if lines is not None:
        async def _flush_ingress_lines(app):
            lines.flush()

        app.on_cleanup.append(_flush_ingress_lines)
    return app


def undeploy_stale(ip: str, port: int) -> None:
    """Probe ``ip:port`` for a stale engine server and ask it to stop —
    the MasterActor's pre-bind undeploy (reference CreateServer.scala:
    266-288): GET /stop on a live engine server frees the port; a 404 or
    unexpected status means some OTHER process owns the port (log and
    let the bind retries surface the failure); connection refused means
    the port is free."""
    import urllib.error
    import urllib.request

    url = f"http://{ip}:{port}"
    try:
        with urllib.request.urlopen(f"{url}/stop", timeout=3) as resp:
            if resp.status == 200:
                log.info("Undeployed a stale engine server at %s", url)
                time.sleep(0.5)  # let it release the port
            else:
                log.error("Another process is using %s (HTTP %d). "
                          "Unable to undeploy.", url, resp.status)
    except urllib.error.HTTPError as e:
        if e.code == 404:
            log.error("Another process is using %s. Unable to undeploy.",
                      url)
        else:
            log.error("An existing server at %s is not responding "
                      "properly (HTTP %d). Unable to undeploy.", url, e.code)
    except (ConnectionError, urllib.error.URLError, OSError, TimeoutError):
        log.debug("Nothing at %s", url)


def run_engine_server(
    engine: Engine,
    instance: EngineInstance,
    ip: str = "0.0.0.0",
    port: int = 8000,
    bind_retries: int = 3,
    prewarm_async: bool = False,
    **kwargs,
) -> None:
    """Blocking entry (reference default port 8000, ServerConfig :77-92).

    Before binding, any stale engine server on the port is asked to
    /stop, and a failed bind retries ``bind_retries`` times with 1 s
    backoff before exiting with a diagnostic instead of a raw traceback
    (reference MasterActor, CreateServer.scala:264-288 + :340-350).

    ``prewarm_async`` (ISSUE 17, fleet replicas): bind the port FIRST
    and run the executable prewarm in the background — /health.json
    answers live-but-not-ready until it lands, so a router can track
    the replica's startup without routing hashed traffic at it.

    A retriever or pipeline attach that raises, or a prewarm that
    raises, ends the deploy with a non-zero exit: before anything is
    bound, or (``prewarm_async``) without ever reporting ready."""
    import errno

    logging.basicConfig(level=logging.INFO)
    # probe BEFORE the expensive model rehydration: a stale server gets
    # the whole prepare_deploy duration to release the port, and a
    # foreign occupant is reported without first loading a model
    undeploy_stale("127.0.0.1" if ip in ("0.0.0.0", "::") else ip, port)
    server = EngineServer(engine, instance, defer_prewarm=prewarm_async,
                          **kwargs)
    prewarm_failed = threading.Event()
    bound = threading.Event()

    def _ready_if_bound_and_warm() -> None:
        if bound.is_set() and not server.prewarming:
            STARTUP.mark_ready()

    if prewarm_async:
        def _prewarm():
            try:
                server.complete_prewarm()
                _ready_if_bound_and_warm()
            except Exception:  # noqa: BLE001 — thread boundary
                # the port is already bound, so fatal means: never
                # ready, stop serving, exit non-zero below
                log.exception("deferred prewarm failed; shutting down")
                prewarm_failed.set()
                os.kill(os.getpid(), signal.SIGTERM)

        threading.Thread(target=_prewarm, name="pio-prewarm",
                         daemon=True).start()
    log.info("Engine server (instance %s) starting on %s:%d", instance.id, ip, port)
    for attempt in range(bind_retries + 1):
        # run_app returns when the server stops, so this one span is
        # entered and left by hand, on this thread: it ends where aiohttp
        # would print its banner, the one call run_app makes once every
        # site listens
        binding = span("deploy.bind", sink=STARTUP.phase, port=port)

        def _bound(*_banner, binding=binding) -> None:
            binding.__exit__(None, None, None)
            bound.set()
            _ready_if_bound_and_warm()

        try:
            binding.__enter__()
            # a fresh app per attempt: a failed bind runs the previous
            # app's cleanup hooks
            web.run_app(create_engine_server_app(server), host=ip,
                        port=port, print=_bound,
                        access_log_class=buffered_access_logger(
                            server.ingress))
            if prewarm_failed.is_set():
                raise SystemExit("executable prewarm failed; see the "
                                 "traceback above")
            return
        except OSError as e:
            if e.errno != errno.EADDRINUSE:
                raise
            binding.__exit__(OSError, e, None)
            if attempt < bind_retries:
                # the failed app already ran its shutdown hooks (drain);
                # re-arm so the retry actually serves
                server.undrain()
                log.error("Bind to %s:%d failed (address in use). "
                          "Retrying... (%d more trial(s))",
                          ip, port, bind_retries - attempt)
                time.sleep(1.0)
    raise SystemExit(
        f"Bind to {ip}:{port} failed after {bind_retries + 1} attempts: "
        f"the address is in use and the occupant did not answer /stop. "
        f"Choose another --port or stop the other process.")
