"""Device-resident serving pipeline (ISSUE 16).

A model served through its retriever alone (``RetrievalServingMixin.
batch_recommend`` without a pipeline: models with no query table, the
degraded per-query path, library callers) does Python host work around
``_dispatch_topk`` on EVERY batch: per-user ``dict`` lookups, a numpy
gather of the query factor rows, fresh padding allocations, and a
host->device upload of the padded query matrix. This module removes that
work by making the query side of serving device-resident, the way the
item side already is (``DeviceRetriever``). What share of a request it
was is not measured on the chip.

* **Device-resident query table** — the model's user-factor matrix is
  uploaded ONCE into a capacity-padded ``[cap, D_pad]`` device buffer.
  The hot path ships only a tiny ``int32[b_pad]`` row-index vector; the
  compiled program gathers the factor rows on device. Row ``cap - 1``
  is a permanent zero sentinel: padding slots and unknown users gather
  it, which reproduces bit-for-bit the zero-row padding the retriever-only
  path builds with ``np.pad`` — the PR 13 bitwise replay gate holds across
  the rewrite.

* **Fused dispatch** — for an exact single-device retriever the gather
  composes with the SAME raw scoring program the retriever compiles
  (``_raw_xla_call`` / the Pallas kernel), into one executable per
  (b_pad, k_pad) lattice point: rows -> gather -> dot -> top_k ->
  one packed pull (``retrieval._pack``: values, indices, and under the
  Pallas kernel its counters). For ANN / sharded retrievers the gather
  program materializes the query matrix on device and hands it to the
  retriever's own compiled programs, so their numerics (and their exact
  fallback policies) are untouched.

* **Double-buffered staging** — each b_pad lattice point owns two
  pinned int32 staging buffers. Batch N+1's host assembly fills one
  while batch N's device step holds the other. ``STAGING_DEPTH`` is
  also how many batches are worth having ahead of the device, and the
  one constant for it: whoever feeds this pipeline (the micro-batcher
  does) reads the depth here and can be told, through
  ``set_step_end_hook``, when a device step ends (where ``in_device``
  falls). A third concurrent dispatch (a caller that bounds nothing, or
  a hung swap — chaos site ``pipeline.swap``) falls back
  to a transient buffer, so a wedged handoff degrades through the
  micro-batcher's watchdog without poisoning the pinned pool. The
  BatchClock stage fence (obs/waterfall.py) marks host_assembly /
  device_dispatch / device_compute / result_scatter exactly like the
  retriever-only path, so the waterfall proves the overlap.

* **Buffer donation** — on backends with real buffer aliasing
  (tpu/gpu) the staging argument is donated (``donate_argnums``, the
  ALX pattern) so XLA reuses its allocation; on CPU donation is a
  no-op-with-warning, so it is gated off and
  ``pio_pipeline_donated_dispatch_total`` stays 0.

* **Copy-on-write refresh** — delta hot-patches (ISSUE 10) call
  ``refresh(new_table)``: the table is re-uploaded into a fresh device
  buffer of the SAME capacity and a clone sharing the compiled-program
  token is returned, so epoch bumps never invalidate compiled programs;
  in-flight dispatches keep the old table because it is an *argument*
  of the compiled call, not a captured constant. Only outgrowing the
  capacity headroom (rare) re-tokenizes and recompiles.

Deploy-time ``prewarm`` walks the full pad-bucketed (b_pad, k_pad)
lattice and accounts every pinned buffer in the PR 12 device ledger
(components ``pipeline_query_table`` / ``pipeline_staging``).
"""

from __future__ import annotations

import logging
import threading
import time
from contextvars import ContextVar
from typing import Callable

import numpy as np

from ..obs.device import LEDGER
from ..obs.metrics import METRICS
from ..obs.startup import STARTUP
from ..obs.trace import span
from ..obs.waterfall import stage_span
from ..faults import FAULTS
from .retrieval import (
    EXEC_CACHE,
    PACKED_IDX_LIMIT,
    _pack,
    _query_shapes,
    _raw_call,
    _raw_xla_call,
    _RETRIEVER_TOKENS,
    _unpack,
    DeviceRetriever,
)

log = logging.getLogger("pio.pipeline")

_M_OVERLAP = METRICS.gauge(
    "pio_pipeline_overlap_ratio",
    "fraction of pipelined dispatches whose host assembly overlapped "
    "another batch's in-flight device step (the double-buffer doing "
    "its job; ~0 under serial load, -> 1 under pipelined load)")
_M_STAGE_WAIT = METRICS.histogram(
    "pio_pipeline_staging_wait_seconds",
    "wait to acquire a pinned staging buffer for a pipelined dispatch "
    "(0 when one is free; bounded by the transient-fallback timeout)")
_M_DONATED = METRICS.counter(
    "pio_pipeline_donated_dispatch_total",
    "pipelined dispatches through a donating executable "
    "(donate_argnums engages on tpu/gpu backends only)")

#: How long a dispatch waits for a pinned staging buffer before falling
#: back to a transient allocation. Short on purpose: the fallback is
#: cheap (np.empty of a few hundred bytes) and a longer wait would let
#: a hung pipeline.swap handoff stall HEALTHY batches behind it.
STAGING_WAIT_S = 0.002

#: Pinned staging buffers per b_pad lattice point (the double buffer):
#: one for the batch in its device step and one for the batch behind it.
#: More batches than this ahead of the device only queue there.
STAGING_DEPTH = 2

_STEP_END: ContextVar[Callable[[], None] | None] = ContextVar(
    "pio_device_step_end", default=None)


def set_step_end_hook(hook: Callable[[], None] | None):
    """Install ``hook`` for this context (a dispatch worker thread): it
    is called, on that thread, where a batch's device step ends. ``None``
    mutes it for a batch that has another step to come. Returns the reset
    token, like the stage sink's."""
    return _STEP_END.set(hook)


def reset_step_end_hook(token) -> None:
    _STEP_END.reset(token)


def device_step_ended() -> None:
    """A batch's device step is over on this thread: tell whoever asked
    (nobody, outside a dispatch that installed a hook)."""
    hook = _STEP_END.get()
    if hook is not None:
        hook()


def _capacity(n_rows: int) -> int:
    """Query-table capacity for ``n_rows`` factor rows: ~12.5% headroom
    (plus the sentinel row) rounded up to a multiple of 256, so delta
    fold-ins append new users for a long time before a capacity growth
    forces a recompile. The ONE home of the policy — tests pin it."""
    need = n_rows + 1 + max(n_rows // 8, 63)
    return ((need + 255) // 256) * 256


def _fused_fn(raw, packed: bool):
    """rows -> gather -> ``raw`` (score + top-k) -> the packed result
    (``retrieval._pack``): the function the fused executable is compiled
    from, apart so that tests/test_tpu_compile.py compiles the same text
    for v5e."""

    def fn(rows, qtab, items):
        out = raw(qtab[rows], items)
        return _pack(*out) if packed else out

    return fn


class _SharedState:
    """Mutable pipeline state shared across copy-on-write ``refresh``
    clones: the staging pools, the overlap/dispatch counters, and the
    locks guarding them. Sharing by reference keeps the metrics and the
    double buffers continuous across delta epochs."""

    def __init__(self, clock=time.perf_counter):
        self.cond = threading.Condition()
        self.staging: dict[int, list[np.ndarray]] = {}
        self.in_device = 0       # dispatches currently in their device step
        self.dispatches = 0
        self.overlapped = 0
        self.transient = 0       # dispatches that fell back off the pool
        # how well the device is fed, integrated at each change of
        # in_device: seconds with nothing in its device step, and the
        # integral of in_device over time (batch-seconds), since attach
        self.clock = clock
        self.t_attach = self.t_last = clock()
        self.idle_s = 0.0
        self.depth_s = 0.0

    def advance(self, delta: int = 0) -> float:
        """Account the time since the last change at the depth it was
        spent at, then change ``in_device`` by ``delta``. Called with
        ``cond`` held; one clock read. Returns that reading."""
        now = self.clock()
        dt = now - self.t_last
        if self.in_device == 0:
            self.idle_s += dt
        self.depth_s += self.in_device * dt
        self.t_last = now
        self.in_device += delta
        return now


class ServingPipeline:
    """Device-resident query-side serving for one model's user factors.

    Built by ``RetrievalServingMixin.attach_pipeline`` over the model's
    attached retriever; ``topk_rows(rows, k)`` is the whole hot path:
    catalog-row indices in, (values, indices) out, zero per-request
    numpy factor math.
    """

    def __init__(self, query_table: np.ndarray, retriever, *,
                 _token: int | None = None, _capacity_rows: int | None = None):
        import jax
        import jax.numpy as jnp

        if retriever is None:
            raise ValueError("ServingPipeline requires an attached retriever")
        qt = np.asarray(query_table, np.float32)
        if qt.ndim != 2:
            raise ValueError("query table must be [rows, dim]")
        self._retriever = retriever
        self._fused = isinstance(retriever, DeviceRetriever)
        self.n_rows, self.dim = qt.shape
        self._cap = _capacity_rows or _capacity(self.n_rows)
        if self.n_rows + 1 > self._cap:
            self._cap = _capacity(self.n_rows)
        # lane width follows the retriever's own contract (lane_dim):
        # fused mode needs the width its scoring program takes a query
        # at (whole 128-lane rows; the program reads the first d_pad);
        # gather mode needs whatever width makes the retriever's lane
        # pad a no-op. 128-rounding is only the fallback for retrievers
        # that predate the accessor.
        self._d_pad = int(getattr(retriever, "lane_dim", 0)) or (
            ((self.dim + 127) // 128) * 128)
        if self._d_pad < self.dim:
            raise ValueError("retriever lane width narrower than factors")
        self._token = _token if _token is not None else next(_RETRIEVER_TOKENS)
        self._sentinel = self._cap - 1  # permanently a zero row
        tab = np.zeros((self._cap, self._d_pad), np.float32)
        tab[: self.n_rows, : self.dim] = qt
        self._qtab = jax.device_put(jnp.asarray(tab))
        self._donate = jax.default_backend() in ("tpu", "gpu")
        self._state = _SharedState()
        LEDGER.track_buffer("pipeline_query_table", int(self._qtab.nbytes))

    # -- compiled programs --------------------------------------------

    def _exec_fused(self, b_pad: int, k_pad: int, *, pin: bool = False):
        """(compiled, is_packed) for rows -> gather -> score -> top_k.
        Composes the SAME raw scoring program the retriever compiles,
        so a gathered batch scores bit-for-bit like a host-assembled
        one (the parity tests pin this)."""
        r = self._retriever
        n_total = r.n_total
        key = ("pipeline", self._token, "fused", b_pad, k_pad, self._cap,
               self._d_pad, int(r._items.shape[1]), n_total, self._donate)

        def build():
            import jax
            import jax.numpy as jnp

            if r._mode == "xla":
                raw = _raw_xla_call(n_total, k_pad)
            else:
                raw = _raw_call(b_pad, *r._items.shape, n_total, k_pad,
                                r._mode == "interpret")
            packed = n_total < PACKED_IDX_LIMIT
            fn = _fused_fn(raw, packed)
            jitted = (jax.jit(fn, donate_argnums=(0,)) if self._donate
                      else jax.jit(fn))
            compiled = jitted.lower(
                jax.ShapeDtypeStruct((b_pad,), jnp.int32),
                jax.ShapeDtypeStruct((self._cap, self._d_pad), jnp.float32),
                jax.ShapeDtypeStruct(r._items.shape, jnp.float32),
            ).compile()
            return compiled, packed

        out = EXEC_CACHE.get_or_build(key, build)
        if pin:
            EXEC_CACHE.pin(key)
        return out

    def _exec_gather(self, b_pad: int, *, pin: bool = False):
        """Compiled rows -> [b_pad, D_pad] device gather (the front end
        for retrievers with their own scoring programs: ANN, sharded)."""
        key = ("pipeline", self._token, "gather", b_pad, self._cap,
               self._d_pad, self._donate)

        def build():
            import jax
            import jax.numpy as jnp

            def fn(rows, qtab):
                return qtab[rows]

            jitted = (jax.jit(fn, donate_argnums=(0,)) if self._donate
                      else jax.jit(fn))
            return jitted.lower(
                jax.ShapeDtypeStruct((b_pad,), jnp.int32),
                jax.ShapeDtypeStruct((self._cap, self._d_pad), jnp.float32),
            ).compile()

        out = EXEC_CACHE.get_or_build(key, build)
        if pin:
            EXEC_CACHE.pin(key)
        return out

    # -- staging double buffer ----------------------------------------

    def _acquire_staging(self, b_pad: int) -> tuple[np.ndarray, bool]:
        """A staging buffer for one dispatch: a pinned one when the pool
        has a free slot (waiting at most STAGING_WAIT_S for the double
        buffer to swap), else a transient allocation — slow, but a hung
        handoff can never wedge the pool. Returns (buffer, transient)."""
        st = self._state
        t0 = time.perf_counter()
        with st.cond:
            pool = st.staging.get(b_pad)
            if pool is None:
                pool = st.staging[b_pad] = [
                    np.empty(b_pad, np.int32) for _ in range(STAGING_DEPTH)]
            if not pool:
                st.cond.wait(timeout=STAGING_WAIT_S)
            buf = pool.pop() if pool else None
        _M_STAGE_WAIT.record(time.perf_counter() - t0)
        if buf is None:
            with st.cond:
                st.transient += 1
            return np.empty(b_pad, np.int32), True
        return buf, False

    def _release_staging(self, b_pad: int, buf: np.ndarray,
                         transient: bool) -> None:
        if transient:
            return
        st = self._state
        with st.cond:
            st.staging.setdefault(b_pad, []).append(buf)
            st.cond.notify()

    def _fill_staging(self, buf: np.ndarray, rows: np.ndarray) -> None:
        """Host assembly: row ids into the staging buffer, out-of-table
        ids (unknown users, padding slots) redirected to the zero
        sentinel — the device-side equivalent of the host zero-pad."""
        b = rows.shape[0]
        np.copyto(buf[:b], np.where(
            (rows >= 0) & (rows < self.n_rows), rows, self._sentinel))
        buf[b:] = self._sentinel

    # -- hot path ------------------------------------------------------

    def topk_rows(self, rows, k: int):
        """(values [b, k_eff], indices [b, k_eff]) for a batch of
        catalog-row indices (int32; negatives score as unknown). The
        pipelined replacement for gather-pad-upload-score: the only
        per-request host work is filling an int32 staging buffer."""
        rows = np.asarray(rows, np.int32)
        b = rows.shape[0]
        n_total = self._retriever.n_total
        k_eff = min(k, n_total)
        if b == 0 or k_eff <= 0 or n_total == 0:
            return (np.zeros((b, 0), np.float32), np.zeros((b, 0), np.int32))
        b_pad, k_pad = _query_shapes(b, k_eff, n_total)
        LEDGER.record_padding_waste(b, b_pad)
        st = self._state
        facts = {"rows": b, "b_pad": b_pad}
        buf, transient = None, True
        try:
            with stage_span("host_assembly", **facts):
                buf, transient = self._acquire_staging(b_pad)
                with st.cond:
                    overlapped = st.in_device > 0
                self._fill_staging(buf, rows)
                # the filled buffer is handed to the device step: the
                # double-buffer swap point (chaos site; a hang here holds
                # ONE pinned buffer and the watchdog 504s the batch)
                FAULTS.fire("pipeline.swap")
            if self._fused:
                out = self._dispatch_fused(buf, b, k_eff, k_pad, facts)
            else:
                out = self._dispatch_gather(buf, b, k, facts)
            with st.cond:
                st.dispatches += 1
                st.overlapped += 1 if overlapped else 0
                ratio = st.overlapped / st.dispatches
            _M_OVERLAP.set(ratio)
            return out
        finally:
            if buf is not None:
                self._release_staging(b_pad, buf, transient)

    def _dispatch_fused(self, buf, b, k_eff, k_pad, facts):
        import jax

        st = self._state
        in_device = False
        try:
            with stage_span("device_dispatch", **facts):
                call, is_packed = self._exec_fused(facts["b_pad"], k_pad)
                with st.cond:
                    st.advance(+1)
                in_device = True
                out = call(buf, self._qtab, self._retriever._items)
                if self._donate:
                    _M_DONATED.inc()
            with stage_span("device_compute", **facts):
                jax.block_until_ready(out)
        finally:
            if in_device:
                with st.cond:
                    st.advance(-1)
                device_step_ended()
        with stage_span("result_scatter", **facts):
            vals, idx, counts = _unpack(out, is_packed, b, k_eff, k_pad)
        if counts is not None:
            self._retriever.record_scan(counts)
        return vals, idx

    def _dispatch_gather(self, buf, b, k, facts):
        """ANN / sharded: gather the query matrix on device, pull it,
        and hand it to the retriever's own compiled programs. The
        gathered rows are bit-identical to the host gather the
        retriever-only path does, so the retriever's numerics (and its
        exact-fallback policy) are untouched."""
        import jax

        call = self._exec_gather(facts["b_pad"])
        st = self._state
        with st.cond:
            st.advance(+1)
        try:
            qdev = call(buf, self._qtab)
            if self._donate:
                _M_DONATED.inc()
            jax.block_until_ready(qdev)
        finally:
            with st.cond:
                st.advance(-1)
        # the retriever's _dispatch_topk fences the stage waterfall
        # itself and re-pads lanes (a no-op: the gather already padded);
        # its scan, not the gather, is this batch's device step
        try:
            return self._retriever.topk(np.asarray(qdev)[:b], k)
        finally:
            device_step_ended()

    # -- lifecycle -----------------------------------------------------

    def prewarm(self, batch_sizes=(1,), ks=(10,)) -> list[tuple]:
        """AOT-build and PIN this pipeline's executables for the full
        pad-bucketed lattice, allocate the pinned staging pairs, and
        account every pinned buffer in the device ledger. Returns the
        distinct cache keys warmed (digested into exec_cache_key)."""
        warmed: list[tuple] = []
        seen: set[tuple[int, int]] = set()
        gathered: set[int] = set()
        n_total = self._retriever.n_total
        for b in batch_sizes:
            for k in ks:
                k_eff = min(k, n_total)
                if b <= 0 or k_eff <= 0:
                    continue
                b_pad, k_pad = _query_shapes(b, k_eff, n_total)
                if (b_pad, k_pad) in seen:
                    continue
                seen.add((b_pad, k_pad))
                if self._fused:
                    with span("deploy.prewarm.program", sink=STARTUP.phase,
                              kind="fused", b_pad=b_pad, k_pad=k_pad):
                        self._exec_fused(b_pad, k_pad, pin=True)
                    warmed.append(("pipeline", "fused", b_pad, k_pad))
                elif b_pad not in gathered:
                    # the gather program is k-independent: one per b_pad
                    gathered.add(b_pad)
                    with span("deploy.prewarm.program", sink=STARTUP.phase,
                              kind="gather", b_pad=b_pad):
                        self._exec_gather(b_pad, pin=True)
                    warmed.append(("pipeline", "gather", b_pad))
                with self._state.cond:
                    self._state.staging.setdefault(b_pad, [
                        np.empty(b_pad, np.int32)
                        for _ in range(STAGING_DEPTH)])
        self._account_buffers()
        return warmed

    def _account_buffers(self) -> None:
        with self._state.cond:
            staged = sum(STAGING_DEPTH * b_pad * 4
                         for b_pad in self._state.staging)
        LEDGER.track_buffer("pipeline_staging", staged)
        LEDGER.track_buffer("pipeline_query_table", int(self._qtab.nbytes))

    def refresh(self, query_table: np.ndarray) -> "ServingPipeline":
        """Copy-on-write table swap for a delta epoch bump: re-upload
        ``query_table`` at the SAME capacity and return a clone sharing
        the compiled-program token, staging pools and counters — no
        compiled program is invalidated, and in-flight dispatches keep
        the old table (it is an argument, not a captured constant).
        Outgrowing the capacity headroom rebuilds from scratch (new
        token; the rare recompile is the documented cost of growth)."""
        import jax
        import jax.numpy as jnp

        qt = np.asarray(query_table, np.float32)
        if qt.ndim != 2 or qt.shape[1] != self.dim:
            raise ValueError("refresh requires a [rows, %d] table" % self.dim)
        if qt.shape[0] + 1 > self._cap:
            log.info("pipeline query table outgrew capacity %d -> "
                     "rebuilding (recompile)", self._cap)
            return ServingPipeline(qt, self._retriever)
        new = object.__new__(ServingPipeline)
        new.__dict__.update(self.__dict__)
        tab = np.zeros((self._cap, self._d_pad), np.float32)
        tab[: qt.shape[0], : self.dim] = qt
        new._qtab = jax.device_put(jnp.asarray(tab))
        new.n_rows = qt.shape[0]
        LEDGER.track_buffer("pipeline_query_table", int(new._qtab.nbytes))
        return new

    def stats(self) -> dict:
        st = self._state
        with st.cond:
            staged = {int(b): len(p) for b, p in st.staging.items()}
            now = st.advance()
            return {
                "mode": "fused" if self._fused else "gather",
                "rows": self.n_rows,
                "capacity": self._cap,
                "dispatches": st.dispatches,
                "overlapRatio": (st.overlapped / st.dispatches
                                 if st.dispatches else 0.0),
                "transientStaging": st.transient,
                "stagingFree": staged,
                "donation": self._donate,
                # deviceIdleSeconds + (seconds with a batch in its
                # device step) = clockSeconds; inDeviceSeconds over
                # clockSeconds is the mean number of batches in flight
                "deviceIdleSeconds": st.idle_s,
                "inDeviceSeconds": st.depth_s,
                "clockSeconds": now - st.t_attach,
            }
