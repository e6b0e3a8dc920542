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

* **Encoder seam** — a model whose query vector is COMPUTED (a sequence
  model: rows -> histories[rows] -> encoder(params, .) -> score) hands
  the pipeline an ``encoder``. The query table is then the int32 history
  table, kept on the host (a step's tokens are a few KB); a step packs
  its rows' histories into one token stream of a lattice length, the
  encoder's program turns the stream into that step's query table on
  the device, and the SAME fused program gathers the histories' last
  positions from it and scores them. The lattice has a second
  dimension, tokens: ``(t_pad)`` for the encoder's executables and
  staging, ``(b_pad, k_pad)`` for the fused ones; the two are chained on
  the device with no host sync between. A model with no encoder (ALS)
  compiles and runs exactly the program it had. An encoder is any
  object with

  - ``dim``: width of the states it emits; ``max_len``; ``aux_name``
    or None, and ``passes``, the most a position's aux can read;
  - ``dense``: True takes every row whole, pads included, at a cost of
    ``max_len`` tokens (the stream is then ``[rows, max_len]`` row by
    row); False packs only the real events, a row costing its length;
  - ``budget``: most tokens one step holds, and ``lattice``: the
    ascending stream lengths a step is padded to, ending at the budget;
  - ``params``: a pytree of device arrays, an argument of every call
    (``param_bytes``, where it has one, is what the ledger accounts);
  - ``program(t_pad)``: ``fn(stream int32[3, t_pad], params) ->
    (states [t_pad, dim] float32, aux int32[t_pad] | None, passes int32
    | None)``, the stream being tokens, segment ids (1.. per history, 0
    for padding) and positions within the history; ``passes`` is what
    the program itself counted of its loop's passes (``loopPasses`` adds
    it up: no product of the configuration), None where it has no loop.
    A program may hand out a fourth value, ``counters`` int32[n], what
    the device program counted of its own work in this step, named by
    the encoder's ``counter_names``: each is added up under its name in
    ``/stats.json``'s ``sequence`` block;
  - optionally ``counter_names`` (above).
"""

from __future__ import annotations

import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from contextvars import ContextVar
from typing import Callable

import numpy as np

from ..obs.device import LEDGER
from ..obs.metrics import METRICS
from ..obs.startup import STARTUP
from ..obs.trace import DEVICE_SCOPES, span
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


def _scoped(fn, scope: str):
    """``fn`` under a ``jax.named_scope``, so that a capture opened in a
    viewer groups its operations by that name."""

    def scoped(*args):
        import jax

        with jax.named_scope(scope):
            return fn(*args)

    return scoped


def _encoder_fn(program, cap: int, d_pad: int):
    """stream, params -> (a step's query table [cap, d_pad], aux
    int32[t_pad + 1 + counters]): the encoder's states laid into the
    zeroed table the fused program gathers from (rows past the stream
    stay zero: the sentinel), and its aux with the passes it counted
    and then its counters behind it, one array for the one pull; apart for tests/test_tpu_compile.py like
    `_fused_fn`."""

    def fn(stream, params):
        import jax
        import jax.numpy as jnp

        states, aux, passes, *counters = program(stream, params)
        table = jax.lax.dynamic_update_slice(
            jnp.zeros((cap, d_pad), jnp.float32),
            states.astype(jnp.float32), (0, 0))
        if aux is None:
            aux = jnp.zeros(stream.shape[1:], jnp.int32)
        if passes is None:
            passes = jnp.int32(0)
        return table, jnp.concatenate(
            [aux.astype(jnp.int32), passes.astype(jnp.int32).reshape(1),
             *(c.astype(jnp.int32) for c in counters)])

    return fn


class _SharedState:
    """Mutable pipeline state shared across copy-on-write ``refresh``
    clones: the staging pools, the overlap/dispatch counters, and the
    locks guarding them. Sharing by reference keeps the metrics and the
    double buffers continuous across delta epochs."""

    def __init__(self, clock=time.perf_counter):
        self.cond = threading.Condition()
        self.staging: dict[int, list[np.ndarray]] = {}
        # encoder pipelines: token-stream buffers int32[3, t_pad] by
        # t_pad, and what /stats.json's `sequence` block counts
        self.streams: dict[int, list[np.ndarray]] = {}
        self.seq = {"steps": 0, "rows": 0, "tokensReal": 0,
                    "tokensComputed": 0, "attentionPairs": 0,
                    "loopPasses": 0}
        self.aux_hist: np.ndarray | None = None
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
                 encoder=None, ks: tuple[int, ...] = (10,),
                 _token: int | None = None, _capacity_rows: int | None = None):
        import jax
        import jax.numpy as jnp

        if retriever is None:
            raise ValueError("ServingPipeline requires an attached retriever")
        self._retriever = retriever
        self._fused = isinstance(retriever, DeviceRetriever)
        self._encoder = encoder
        #: the k's `prewarm` compiles for (a sequence model's lattice)
        self.ks = tuple(ks)
        self._token = _token if _token is not None else next(_RETRIEVER_TOKENS)
        self._donate = jax.default_backend() in ("tpu", "gpu")
        self._state = _SharedState()
        if encoder is not None:
            self._init_encoded(query_table)
            return
        qt = np.asarray(query_table, np.float32)
        if qt.ndim != 2:
            raise ValueError("query table must be [rows, dim]")
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
        self._sentinel = self._cap - 1  # permanently a zero row
        tab = np.zeros((self._cap, self._d_pad), np.float32)
        tab[: self.n_rows, : self.dim] = qt
        self._qtab = jax.device_put(jnp.asarray(tab))
        LEDGER.track_buffer("pipeline_query_table", int(self._qtab.nbytes))

    def _init_encoded(self, histories) -> None:
        """The encoder's side of the constructor: the history table and
        its lengths on the host, and the shape of the per-step query
        table the encoder's program emits and the fused program reads."""
        enc = self._encoder
        if not self._fused:
            raise ValueError(
                "an encoder serves through the exact single-device "
                "retriever (the fused program gathers from the step's "
                "table); ANN and sharded retrievers take no encoder")
        self._set_histories(histories)
        self.dim = int(enc.dim)
        self._d_pad = int(self._retriever.lane_dim)
        if self._d_pad < self.dim:
            raise ValueError("retriever lane width narrower than the "
                             "encoder's states")
        # the step's table: the budget's positions, then zero rows
        self._cap = int(enc.budget) + 8
        self._sentinel = self._cap - 1
        self._qtab = None
        self._state.aux_hist = np.zeros(int(enc.passes) + 1, np.int64)
        for name in getattr(enc, "counter_names", ()):
            self._state.seq.setdefault(name, 0)

    def _set_histories(self, histories) -> None:
        hist = np.asarray(histories)
        if hist.dtype != np.uint16:  # ids under 65,536 stay two bytes
            hist = hist.astype(np.int32, copy=False)
        hist = np.ascontiguousarray(hist)
        if hist.ndim != 2 or hist.shape[1] != self._encoder.max_len:
            raise ValueError("history table must be [rows, max_len]")
        self._hist = hist
        self.n_rows = hist.shape[0]
        self._lengths = (hist > 0).sum(axis=1).astype(np.int32)

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
            if self._encoder is not None:
                fn = _scoped(fn, "pio.seq.head_topk")
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

    def _exec_encoder(self, t_pad: int, *, pin: bool = False):
        """Compiled stream -> (the step's query table, aux) for one
        lattice point of the token dimension."""
        enc = self._encoder
        key = ("pipeline", self._token, "encoder", t_pad, self._cap,
               self._d_pad)

        def build():
            import jax
            import jax.numpy as jnp

            fn = _encoder_fn(enc.program(t_pad), self._cap, self._d_pad)
            compiled = jax.jit(fn).lower(
                jax.ShapeDtypeStruct((3, t_pad), jnp.int32),
                jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                    enc.params),
            ).compile()
            # a capture reads this program's device time by named scope
            DEVICE_SCOPES.record(compiled.as_text())
            return compiled

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

    def _acquire_staging(self, b_pad: int, *, stream: bool = False
                         ) -> tuple[np.ndarray, bool]:
        """A staging buffer for one dispatch: a pinned one when the pool
        has a free slot (waiting at most STAGING_WAIT_S for the double
        buffer to swap), else a transient allocation — slow, but a hung
        handoff can never wedge the pool. Returns (buffer, transient).
        ``stream``: an encoder's token stream int32[3, b_pad] (b_pad
        then counts tokens), from its own pools."""
        st = self._state
        shape = (3, b_pad) if stream else (b_pad,)
        t0 = time.perf_counter()
        with st.cond:
            pools = st.streams if stream else st.staging
            pool = pools.get(b_pad)
            if pool is None:
                pool = pools[b_pad] = [
                    np.empty(shape, np.int32) for _ in range(STAGING_DEPTH)]
            if not pool:
                st.cond.wait(timeout=STAGING_WAIT_S)
            buf = pool.pop() if pool else None
        _M_STAGE_WAIT.record(time.perf_counter() - t0)
        if buf is None:
            with st.cond:
                st.transient += 1
            return np.empty(shape, np.int32), True
        return buf, False

    def _release_staging(self, b_pad: int, buf: np.ndarray,
                         transient: bool, *, stream: bool = False) -> None:
        if transient:
            return
        st = self._state
        with st.cond:
            pools = st.streams if stream else st.staging
            pools.setdefault(b_pad, []).append(buf)
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
        if self._encoder is not None:
            return self._topk_encoded(rows, k_eff)
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

    # -- the encoder's hot path ---------------------------------------

    @property
    def cost_budget(self) -> int | None:
        """Most cost (tokens) one device step takes; None where every row
        costs the same (no encoder)."""
        return None if self._encoder is None else int(self._encoder.budget)

    def history_lengths(self, rows) -> np.ndarray:
        return self._lengths[np.asarray(rows, np.int64)]

    def _row_costs(self, rows: np.ndarray) -> np.ndarray:
        """Tokens each row adds to a step: its history's length where the
        encoder packs, ``max_len`` where it takes rows whole."""
        if self._encoder.dense:
            return np.full(len(rows), self._encoder.max_len, np.int64)
        return self._lengths[rows].astype(np.int64)

    def row_cost(self, row: int) -> int:
        return int(self._row_costs(np.asarray([row]))[0])

    def _topk_encoded(self, rows: np.ndarray, k_eff: int):
        """Rows -> steps of at most the encoder's budget (a caller that
        cuts by cost, the micro-batcher, gets one; an evaluation fold gets
        as many as it needs), in order. Only the last step's end is
        reported: the batch holds its place ahead of the device until
        then."""
        enc = self._encoder
        costs = self._row_costs(rows)
        bounds, spent = [0], 0
        for j, c in enumerate(costs.tolist()):
            if spent + c > enc.budget and j > bounds[-1]:
                bounds.append(j)
                spent = 0
            spent += c
        bounds.append(len(rows))
        hook = set_step_end_hook(None) if len(bounds) > 2 else None
        parts = []
        try:
            for lo, hi in zip(bounds[:-1], bounds[1:]):
                if hook is not None and hi == len(rows):
                    reset_step_end_hook(hook)
                    hook = None
                parts.append(self._encoded_step(rows[lo:hi], costs[lo:hi],
                                                k_eff))
        finally:
            if hook is not None:
                reset_step_end_hook(hook)
        if len(parts) == 1:
            return parts[0]
        return (np.concatenate([p[0] for p in parts]),
                np.concatenate([p[1] for p in parts]))

    def _encoded_step(self, rows: np.ndarray, costs: np.ndarray, k_eff: int):
        """One device step: the rows' histories packed into a stream of a
        lattice length -> the encoder's program -> the fused program over
        the states of the histories' last positions."""
        import jax

        enc, st = self._encoder, self._state
        b, tokens = len(rows), int(costs.sum())
        t_pad = next((t for t in enc.lattice if t >= tokens), None)
        if t_pad is None:
            raise ValueError(f"one row of {tokens} tokens is over the "
                             f"step's budget of {enc.budget}")
        b_pad, k_pad = _query_shapes(b, k_eff, self._retriever.n_total)
        LEDGER.record_padding_waste(tokens, t_pad)
        facts = {"rows": b, "b_pad": b_pad, "t_pad": t_pad}
        stream = last = None
        s_transient = l_transient = True
        in_device = False
        try:
            with stage_span("host_assembly", **facts):
                with span("serve.seq_encode", level=logging.DEBUG,
                          tokens=tokens, **facts):
                    stream, s_transient = self._acquire_staging(
                        t_pad, stream=True)
                    last, l_transient = self._acquire_staging(b_pad)
                    with st.cond:
                        overlapped = st.in_device > 0
                    starts = np.cumsum(costs) - costs
                    L = self._hist.shape[1]
                    stream[:, tokens:] = 0
                    stream[0, :tokens] = np.concatenate(
                        [self._hist[r, L - c:] for r, c
                         in zip(rows.tolist(), costs.tolist())])
                    stream[1, :tokens] = np.repeat(
                        np.arange(1, b + 1, dtype=np.int32), costs)
                    stream[2, :tokens] = (np.arange(tokens)
                                          - np.repeat(starts, costs))
                    last[:b] = starts + costs - 1
                    last[b:] = self._sentinel
                FAULTS.fire("pipeline.swap")
            try:
                with stage_span("device_dispatch", **facts):
                    encode = self._exec_encoder(t_pad)
                    score, is_packed = self._exec_fused(b_pad, k_pad)
                    with st.cond:
                        st.advance(+1)
                    in_device = True
                    table, aux = encode(stream, enc.params)
                    out = score(last, table, self._retriever._items)
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
                aux = np.asarray(aux)
                exits = aux[last[:b]] if enc.aux_name else None
            if counts is not None:
                self._retriever.record_scan(counts)
            real = self._lengths[rows].astype(np.int64)
            with st.cond:
                st.dispatches += 1
                st.overlapped += 1 if overlapped else 0
                seq = st.seq
                seq["steps"] += 1
                seq["rows"] += b
                seq["tokensReal"] += int(real.sum())
                seq["tokensComputed"] += t_pad
                seq["attentionPairs"] += int((real * (real + 1) // 2).sum())
                seq["loopPasses"] += int(aux[t_pad])
                for name, c in zip(getattr(enc, "counter_names", ()),
                                   aux[t_pad + 1:].tolist()):
                    seq[name] += c
                if exits is not None:
                    st.aux_hist += np.bincount(
                        exits, minlength=len(st.aux_hist))[:len(st.aux_hist)]
                ratio = st.overlapped / st.dispatches
            _M_OVERLAP.set(ratio)
            return vals, idx
        finally:
            if stream is not None:
                self._release_staging(t_pad, stream, s_transient, stream=True)
            if last is not None:
                self._release_staging(b_pad, last, l_transient)

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

    def prewarm(self, batch_sizes=(1,), ks=None) -> list[tuple]:
        """AOT-build and PIN this pipeline's executables for the full
        pad-bucketed lattice, allocate the pinned staging pairs, and
        account every pinned buffer in the device ledger. Returns the
        distinct cache keys warmed (digested into exec_cache_key)."""
        warmed: list[tuple] = []
        ks = self.ks if ks is None else ks
        if self._encoder is None:
            self._prewarm_scoring(batch_sizes, ks, warmed)
        else:
            # the token dimension of the lattice, its points compiled
            # side by side and beside the scoring programs (a compile
            # holds no interpreter lock and one program of unlike layers
            # takes tens of seconds); a program that does not compile
            # fails the deploy when its result is read
            def warm(t_pad):
                with span("deploy.prewarm.program", sink=STARTUP.phase,
                          kind="encoder", t_pad=t_pad):
                    self._exec_encoder(t_pad, pin=True)

            lattice = self._encoder.lattice
            with ThreadPoolExecutor(len(lattice)) as encoders:
                pending = [encoders.submit(warm, t_pad) for t_pad in lattice]
                for t_pad in lattice:
                    warmed.append(("pipeline", "encoder", t_pad))
                    with self._state.cond:
                        self._state.streams.setdefault(t_pad, [
                            np.empty((3, t_pad), np.int32)
                            for _ in range(STAGING_DEPTH)])
                self._prewarm_scoring(batch_sizes, ks, warmed)
                for job in pending:
                    job.result()
        self._account_buffers()
        return warmed

    def _prewarm_scoring(self, batch_sizes, ks, warmed: list) -> None:
        """The (b_pad, k_pad) programs of `prewarm` and their staging."""
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

    def _account_buffers(self) -> None:
        with self._state.cond:
            staged = sum(STAGING_DEPTH * b_pad * 4
                         for b_pad in self._state.staging)
            staged += sum(STAGING_DEPTH * 3 * t_pad * 4
                          for t_pad in self._state.streams)
        LEDGER.track_buffer("pipeline_staging", staged)
        if self._encoder is None:
            LEDGER.track_buffer("pipeline_query_table",
                                int(self._qtab.nbytes))
        else:  # a step's table, alive while the step is
            LEDGER.track_buffer("pipeline_query_table",
                                self._cap * self._d_pad * 4)
            LEDGER.track_buffer("pipeline_encoder_params", int(
                getattr(self._encoder, "param_bytes", 0)))

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

        if self._encoder is not None:
            # the histories live on the host: a new table, same programs
            new = object.__new__(ServingPipeline)
            new.__dict__.update(self.__dict__)
            new._set_histories(query_table)
            return new
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
            sequence = {}
            if self._encoder is not None:
                seq = st.seq
                sequence = {"sequence": {
                    **seq,
                    "rowsPerStep": (seq["rows"] / seq["steps"]
                                    if seq["steps"] else 0.0),
                    "tokenBudget": int(self._encoder.budget),
                    "tokenLattice": list(self._encoder.lattice),
                    **({self._encoder.aux_name: st.aux_hist.tolist()}
                       if self._encoder.aux_name else {}),
                }}
            return {
                **sequence,
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
