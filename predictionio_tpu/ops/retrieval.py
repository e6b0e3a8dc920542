"""Fused top-k retrieval — the serving hot path as a Pallas TPU kernel.

Every recommendation family in this framework ends serving with the same
shape of work: score a catalog ([N, D] factors / embeddings) against a
query vector and keep the top k (the reference does this per query on the
Spark driver with a full sort, e.g. examples/scala-parallel-similarproduct/
multi/src/main/scala/ALSAlgorithm.scala:146-200 and ALSModel.scala:200-219).
On TPU the naive form materializes a [B, N] score matrix in HBM and then
runs top_k over it — 2x the HBM traffic of the matmul itself for large N.

The catalog lies on the device with its items along the lanes,
``[d_pad, N_pad]``: the rank padded to the 8 sublanes of a float32 tile
and not to 128 lanes, so that a scan reads the bytes it needs (rank 64:
all of them; rank 10: 16 sublanes, an eighth of a 128-lane row's). The
kernel streams item tiles through VMEM once, so the full score matrix
never exists. A grid step takes a tile of catalog columns sized from the
shapes (`_tile_rows`: 16,384 items at rank 64 and k 16) and scores it one
sub-tile at a time on the MXU. Each row of the batch keeps its k best
scores so far, in descending order, in VMEM scratch; a sub-tile's common
cost is the matmul, one elementwise maximum and one compare of each row's
best score with that row's own k-th kept value. Only when some row's best
score is strictly above its own k-th value does the sub-tile merge:
rounds that move each such row's best remaining score into its kept list,
as many as the most entrants any row has (about one late in a scan, k in
the first sub-tile). A zero row (padding slot, unknown user) fills its
list from the first sub-tile and never opens the gate again. Ties keep
the lower catalog row. On the v5e a scan of 15.2 M items at rank 64 takes
24.4 ms at B 128 (a third of the sub-tiles merge, 1.07 rounds each) and
6.3 ms at B 8 (PERF.md, PR 30; 11.6 when the catalog was padded to 128
lanes); what is left at B 128 is the six bf16 passes of
``Precision.HIGHEST``, at B 8 the 3.89 GB of catalog at nine tenths of
the chip's bandwidth. The kernel counts the sub-tiles it
scanned and merged and the rounds it ran; the counts leave the device in
the packed result (`_pack`) and reach /stats.json's ``retrieval`` block
and ``pio_topk_tiles_total`` / ``pio_topk_merge_rounds_total``.

Off-TPU, serving auto-selects a plain-XLA top-k over the same device
array (`_run_topk_xla` — fast compiled host code with the identical
output contract); ``interpret=True`` forces the kernel under the Pallas
interpreter (numerically identical, ~65x slower on CPU), the parity
path the kernel tests pin TPU semantics with.
"""

from __future__ import annotations

import functools
import itertools
import threading
import time

import numpy as np

from ..obs.device import LEDGER
from ..obs.metrics import METRICS
from ..obs.startup import STARTUP
from ..obs.trace import span
from ..obs.waterfall import stage_sink_active, stage_span
from ..faults import FAULTS

__all__ = ["topk_scores", "DeviceRetriever", "ShardedDeviceRetriever",
           "RetrievalServingMixin", "row_normalize", "ExecutableCache",
           "EXEC_CACHE", "choose_shard_count"]

# ISSUE 5: the executable cache's behavior under shape churn, scrapeable
# (stats() keeps its dict shape for /stats.json; same increments)
_M_EXEC_CACHE = METRICS.counter(
    "pio_exec_cache_total",
    "compiled-executable cache events (hit/miss/evict)",
    labelnames=("event",))

# what the top-k kernel's gate let through, per call, from the counters
# that ride the packed result (DeviceRetriever.record_scan)
_M_TILES = METRICS.counter(
    "pio_topk_tiles_total",
    "sub-tiles of the catalog the top-k kernel scored (scanned: matmul, "
    "one maximum, one compare) and those of them in which some row's "
    "best score beat its k-th kept value (merged)",
    labelnames=("event",))
_M_ROUNDS = METRICS.counter(
    "pio_topk_merge_rounds_total",
    "extraction rounds the top-k kernel ran inside merging sub-tiles "
    "(each moves at most one score per row into the kept lists)")


def row_normalize(x: np.ndarray) -> np.ndarray:
    """Unit-normalize rows (cosine scoring). The ONE home of the epsilon:
    the device similarity retriever and the host cosine fallback must
    score identically (test_als device/host parity pins it)."""
    x = np.asarray(x, np.float32)
    return x / (np.linalg.norm(x, axis=1, keepdims=True) + 1e-9)


#: Largest catalog whose indices are exact in float32 — above it the
#: packed single-pull result buffer would corrupt indices, so callers
#: fall back to the two-buffer path. One home for both retrievers.
PACKED_IDX_LIMIT = 1 << 24


class ExecutableCache:
    """THE bounded cache of compiled top-k serving executables — one home
    for what used to be three ad-hoc caches (`_build_call`'s lru_cache,
    `_build_xla_call`'s lru_cache, and ShardedDeviceRetriever's `_calls`
    dict), so a long-lived server has ONE executable budget and ONE set
    of hit/miss/eviction counters (surfaced through the engine server's
    /stats.json).

    Keys are namespaced tuples carrying every shape the executable was
    specialized on. Entries pinned via ``pin()`` (the deploy path's
    AOT-pre-warmed hot serving shapes) are skipped by LRU eviction, so
    shape churn from odd client batch sizes can never evict the hot
    shape; the pin set itself is bounded (oldest pin unpinned past
    ``PIN_LIMIT``) so repeated /reloads of token-keyed sharded entries
    cannot grow it without bound.
    """

    PIN_LIMIT = 16

    def __init__(self, maxsize: int = 64):
        self.maxsize = max(1, maxsize)
        self._entries: dict = {}  # insertion order = LRU order
        self._pinned: dict = {}   # ordered set of pinned keys
        self._lock = threading.Lock()
        self._building: dict = {}  # key -> per-key build lock
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_or_build(self, key, build):
        """Return the cached value for ``key``, building (and inserting)
        it on a miss. ``build()`` runs OUTSIDE the cache lock — compiles
        take seconds and must not serialize the serving threads — but
        UNDER a per-key build lock, so two threads missing the same key
        compile it once: the loser waits and takes the winner's entry
        as a hit instead of burning a duplicate compile that the ledger
        would have to discard (ISSUE 16 satellite; the two-thread test
        pins exactly one pio_xla_compile_* observation)."""
        with self._lock:
            if key in self._entries:
                self.hits += 1
                val = self._entries.pop(key)
                self._entries[key] = val  # re-insert at the recent end
                _M_EXEC_CACHE.inc(event="hit")
                return val
            key_lock = self._building.setdefault(key, threading.Lock())
        with key_lock:
            with self._lock:
                if key in self._entries:
                    # a racing thread finished this build while we
                    # waited on the key lock: that's a hit, not a
                    # second compile
                    self.hits += 1
                    val = self._entries.pop(key)
                    self._entries[key] = val
                    _M_EXEC_CACHE.inc(event="hit")
                    return val
                self.misses += 1
            _M_EXEC_CACHE.inc(event="miss")
            t0 = time.perf_counter()
            val = build()
            # analysis probes outside the lock (they can walk the whole
            # HLO); residency bookkeeping (admit/discard) inside, in
            # lockstep with the insert/evict it accounts for — ISSUE 12
            entry = LEDGER.analyze(key, val, time.perf_counter() - t0)
            with self._lock:
                while len(self._entries) >= self.maxsize:
                    victim = next((k for k in self._entries
                                   if k not in self._pinned), None)
                    if victim is None:
                        break  # everything pinned: admit over budget
                    self._entries.pop(victim)
                    self.evictions += 1
                    _M_EXEC_CACHE.inc(event="evict")
                    LEDGER.discard(victim)
                self._entries[key] = val
                LEDGER.admit(entry)
                self._building.pop(key, None)
            return val

    def pin(self, key) -> None:
        """Exempt ``key`` from eviction (hot serving shapes)."""
        with self._lock:
            self._pinned.pop(key, None)
            self._pinned[key] = True
            while len(self._pinned) > self.PIN_LIMIT:
                self._pinned.pop(next(iter(self._pinned)))

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {
                "size": len(self._entries),
                "pinned": len(self._pinned),
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "hitRate": (self.hits / total) if total else 0.0,
            }


#: Process-wide singleton: every retriever in the process shares one
#: executable budget (a server deploys several models over one backend).
EXEC_CACHE = ExecutableCache()

#: Distinguishes sharded-cache keys across retriever instances. A counter
#: rather than id(): id() values recycle after gc, and a recycled key
#: would serve a stale executable built over a DIFFERENT catalog.
_RETRIEVER_TOKENS = itertools.count()

#: Serializes multi-device (collective) executable launches process-wide.
#: Two collective programs launched concurrently from different threads
#: can interleave their per-device partitions on the backend's worker
#: pool; each partition then blocks in a rendezvous the other program's
#: partitions are occupying the pool for — a deadlock, not a slowdown
#: (pinned by test_microbatch's sharded-serving hammer). The lock is held
#: through block_until_ready so a launch fully drains before the next
#: one starts; single-device executables have no rendezvous and bypass
#: it. The retriever step is serialized across models either way: the
#: programs contend for the same device set.
_COLLECTIVE_LAUNCH_LOCK = threading.Lock()


def _pad_to(x, mult, axis, value=0.0):
    n = x.shape[axis]
    target = ((n + mult - 1) // mult) * mult
    if target == n:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, target - n)
    return np.pad(x, pad, constant_values=value) if isinstance(x, np.ndarray) else None


def _lanes(d: int) -> int:
    """Width of a query of rank ``d``: whole 128-lane groups, of which
    the scoring programs read the catalog's ``d_pad`` first."""
    return -(-d // 128) * 128


#: VMEM the kernel sizes its tile for: the double-buffered catalog tile,
#: one sub-tile's scores and the kept lists, inside Mosaic's 16 MiB scoped
#: default with room left for the compiler's own temporaries.
_VMEM_BUDGET = 12 << 20

#: Most items a grid step takes; `_padded_shape` pads a large catalog to a
#: multiple of it so that every smaller power-of-two tile divides it. At
#: rank 64 the VMEM budget allows it (two buffers of 4 MiB), and the scan
#: of 15.2 M items at B 8 takes 5.24 ms where 8,192 take 5.33 (PERF.md,
#: PR 30).
_TILE_MAX = 16384

#: Scores of one gated sub-tile, [B, chunk]. The chunk narrows as the
#: batch grows, so the entrants a sub-tile holds (B * k * chunk / rows
#: scanned) do not grow with B. Swept on the v5e over the serving cells'
#: catalog (PERF.md, PR 25): at B 128 a scan takes 29.1 ms at 2^16 (twice
#: the gates), 24.8 at 2^17 with a third of the sub-tiles merging, 23.1 at
#: 2^18 with half of them merging.
_CHUNK_ELEMS = 1 << 17

#: Widest sub-tile. A small batch gains nothing from a wider one (B 8:
#: 11.7 ms a scan at 2,048 columns, 11.4 at 8,192, same sweep), and
#: Mosaic unrolls the sub-tile's matmul, so its compile time grows with it.
_CHUNK_MAX = 2048


def _tile_rows(B: int, D: int, k: int, N_pad: int) -> tuple[int, int]:
    """(tile, chunk): items per grid step and per gated sub-tile, from
    the shapes alone (``D`` the catalog's padded rank, its sublanes). The
    tile is the largest power-of-two multiple of 128 that divides
    ``N_pad`` and fits the VMEM budget beside the sub-tile's scores and
    the kept lists (which grow with k, so a large k shrinks the tile, as
    a large rank does); the chunk is `_CHUNK_ELEMS / B`, at least one
    lane group and at most `_CHUNK_MAX` and the tile."""
    k_lanes = -(-k // 128) * 128
    chunk = 128
    while chunk * 2 * B <= _CHUNK_ELEMS and chunk < _CHUNK_MAX:
        chunk *= 2
    # scores, their scratch copy and two temporaries of an extraction
    # round; kept values and ids as scratch and as (double-buffered)
    # output blocks; the k-th values broadcast over one lane group
    fixed = 4 * B * chunk * 4 + 6 * B * k_lanes * 4 + B * 128 * 4
    tile = 128
    while (tile < _TILE_MAX and N_pad % (tile * 2) == 0
           and fixed + 2 * (tile * 2) * D * 4 <= _VMEM_BUDGET):
        tile *= 2
    return tile, min(chunk, tile)


def _topk_kernel(q_ref, items_ref, vals_ref, idx_ref, cnt_ref,
                 kv_ref, ki_ref, kth_ref, s_ref, *,
                 k, tile_n, chunk, n_total, n_pad):
    """One grid step: score a tile of the catalog (``items_ref``
    [D, tile_n], an item a lane), sub-tile by sub-tile, and let a
    sub-tile touch the kept lists only if some row's best score in it
    beats that row's own k-th kept value.

    kv_ref / ki_ref: [B, k_lanes] kept values (descending) and their
    catalog rows, lane-padded so that an insertion is one lane roll;
    kth_ref: [B, 128] each row's k-th kept value over a lane group (what
    the gate compares with); s_ref: [B, chunk] the scores of a merging
    sub-tile, consumed by the extraction rounds; cnt_ref: int32[3] in
    SMEM, (sub-tiles scanned, sub-tiles merged, extraction rounds)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    j = pl.program_id(0)
    last = pl.num_programs(0) - 1
    B = q_ref.shape[0]
    neg_inf = jnp.float32(-jnp.inf)

    @pl.when(j == 0)
    def _():
        kv_ref[...] = jnp.full(kv_ref.shape, neg_inf, kv_ref.dtype)
        ki_ref[...] = jnp.full(ki_ref.shape, -1, ki_ref.dtype)
        kth_ref[...] = jnp.full(kth_ref.shape, neg_inf, kth_ref.dtype)
        cnt_ref[0] = n_pad // chunk
        cnt_ref[1] = 0
        cnt_ref[2] = 0

    q = q_ref[:, :items_ref.shape[0]]  # [B, D] of the 128-lane query
    col = jax.lax.broadcasted_iota(jnp.int32, (B, chunk), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, kv_ref.shape, 1)

    def beats_kth(s):
        """Does any row hold a score above its own k-th kept value?
        Lane groups are folded elementwise first: one compare of
        [B, 128] and one reduction to a scalar, no per-row lane reduce."""
        m = s
        while m.shape[1] > 128:
            half = m.shape[1] // 2
            m = jnp.maximum(m[:, :half], m[:, half:])
        return jnp.max(jnp.where(m > kth_ref[...], 1.0, 0.0)) > 0.0

    def extract_round(base):
        """Move each row's best remaining score of the sub-tile into its
        kept list, if it beats the row's k-th value: the first column
        holding the maximum (the lower catalog row wins a tie inside
        the sub-tile), inserted behind every kept value not below it
        (an earlier catalog row wins a tie across sub-tiles)."""
        s = s_ref[...]
        m = jnp.max(s, axis=1, keepdims=True)  # [B, 1]
        pick = jnp.min(jnp.where(s == m, col, chunk), axis=1, keepdims=True)
        s = jnp.where(col == pick, neg_inf, s)
        s_ref[...] = s
        kv, ki = kv_ref[...], ki_ref[...]
        enters = m > kth_ref[:, :1]
        pos = jnp.sum(jnp.where(kv >= m, 1, 0), axis=1, keepdims=True)
        new_v = jnp.where(lane < pos, kv, jnp.where(
            lane == pos, m, pltpu.roll(kv, 1, 1)))
        new_i = jnp.where(lane < pos, ki, jnp.where(
            lane == pos, base + pick, pltpu.roll(ki, 1, 1)))
        kv = jnp.where(enters, new_v, kv)
        kv_ref[...] = kv
        ki_ref[...] = jnp.where(enters, new_i, ki)
        kth = jnp.max(jnp.where(lane == k - 1, kv, neg_inf), axis=1,
                      keepdims=True)
        kth_ref[...] = jnp.broadcast_to(kth, kth_ref.shape)
        return beats_kth(s)

    def sub_tile(c, carry):
        off = pl.multiple_of(c * chunk, chunk)
        s = jax.lax.dot_general(
            q, items_ref[:, pl.ds(off, chunk)], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,  # full-f32 MXU passes:
            # scores must rank stably against host-side float32 references
        )  # [B, chunk]
        base = j * tile_n + off

        @pl.when(beats_kth(s))
        def _():
            s_ref[...] = s
            merges = True
            if n_pad > n_total:
                # The catalog's zero padding scores 0, not -inf. Only a
                # sub-tile that reaches into it is masked, here where
                # that is rare, and asks the gate again: padding that
                # beat a negative k-th value neither enters nor counts.
                def mask_padding():
                    s_ref[...] = jnp.where(col < n_total - base, s, neg_inf)
                    return beats_kth(s_ref[...])

                merges = jax.lax.cond(base + chunk > n_total, mask_padding,
                                      lambda: jnp.bool_(True))
            # at most k rounds: a row's k-th value only rises, so a
            # sub-tile holds at most k entrants for any row
            rounds = jax.lax.while_loop(
                lambda c: c[0],
                lambda c: (extract_round(base), c[1] + 1),
                (merges, jnp.int32(0)))[1]
            cnt_ref[1] += jnp.int32(merges)
            cnt_ref[2] += rounds

        return carry

    jax.lax.fori_loop(0, tile_n // chunk, sub_tile, 0)

    @pl.when(j == last)
    def _():
        vals_ref[...] = kv_ref[:, :k]
        idx_ref[...] = ki_ref[:, :k]


def _raw_call(B, D, N_pad, n_total, k, interpret, *, tile_n=None):
    """The un-jitted fused top-k pallas call, ``(q, items) -> (values
    [B, k], rows [B, k], counters int32[3])`` over a query of
    `_lanes(D)` lanes and the catalog as [D, N_pad]: shared by the jitted
    serving entry (`_build_call`) and the serving pipeline's fused
    program (ops/pipeline.py), which composes it with the row gather.
    ``tile_n`` is for tests that pin one tile size; serving never passes
    it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    tile, chunk = _tile_rows(B, D, k, N_pad)
    if tile_n is not None:
        tile, chunk = tile_n, min(chunk, tile_n)
    k_lanes = -(-k // 128) * 128
    kernel = functools.partial(_topk_kernel, k=k, tile_n=tile, chunk=chunk,
                               n_total=n_total, n_pad=N_pad)
    return pl.pallas_call(
        kernel,
        grid=(N_pad // tile,),
        in_specs=[
            pl.BlockSpec((B, _lanes(D)), lambda j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((D, tile), lambda j: (0, j), memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((B, k), lambda j: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((B, k), lambda j: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, k), jnp.float32),
            jax.ShapeDtypeStruct((B, k), jnp.int32),
            jax.ShapeDtypeStruct((3,), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B, k_lanes), jnp.float32),
            pltpu.VMEM((B, k_lanes), jnp.int32),
            pltpu.VMEM((B, 128), jnp.float32),
            pltpu.VMEM((B, chunk), jnp.float32),
        ],
        interpret=interpret,
    )


def _build_call(B, D, N_pad, n_total, k, interpret, *, pin=False):
    """Compiled kernel + result packing: values, indices and the scan's
    counters leave the device as ONE [B, 2k + 3] f32 buffer, so a served
    batch costs one blocking device-to-host pull. Indices are exact in
    f32 below 2^24; a larger catalog falls back to the several-buffer
    path. The executable is AOT-built (jit -> lower -> compile) into
    EXEC_CACHE; ``pin=True`` (the deploy path's pre-warm) exempts the
    shape from eviction."""
    key = ("kernel", B, D, N_pad, n_total, k, interpret)
    out = EXEC_CACHE.get_or_build(key, lambda: _aot_with_packing(
        _raw_call(B, D, N_pad, n_total, k, interpret),
        n_total, B, D, N_pad))
    if pin:
        EXEC_CACHE.pin(key)
    return out


def _pack(vals, idx, *counts):
    """One f32 buffer for one host pull: [B, 2k] values and indices,
    then the kernel's counters (when the scoring program has any)
    repeated down the rows. All exact in f32 below 2^24."""
    import jax.numpy as jnp

    cols = [vals, idx.astype(jnp.float32)]
    cols += [jnp.broadcast_to(c.astype(jnp.float32), (vals.shape[0], c.size))
             for c in counts]
    return jnp.concatenate(cols, axis=1)


def _unpack(out, is_packed: bool, b: int, k_eff: int, k_pad: int):
    """Host side of `_pack`: (values [b, k_eff], indices [b, k_eff],
    counters or None) from what a scoring program returned, padding
    rows and columns cut off. Packed: ONE pull."""
    if is_packed:
        host = np.asarray(out)
        vals = host[:b, :k_eff]
        idx = host[:b, k_pad:k_pad + k_eff].astype(np.int32)
        counts = host[0, 2 * k_pad:]
    else:
        vals, idx, *rest = out
        vals = np.asarray(vals)[:b, :k_eff]
        idx = np.asarray(idx)[:b, :k_eff]
        counts = np.asarray(rest[0]) if rest else ()
    return vals, idx, (counts if len(counts) else None)


def _aot_with_packing(call, n_total: int, B: int, D: int, N_pad: int):
    """The ONE home of the pack/no-pack policy for every single-device
    top-k builder (kernel and XLA): below PACKED_IDX_LIMIT, what the
    program returns leaves the device as one f32 buffer (`_pack`: one
    host pull); at/above it, separate buffers keep indices exact. The
    executable is compiled AHEAD of the first call
    (``jax.jit(...).lower(...).compile()``) so a pre-warmed shape never
    pays tracing or compilation on the serving path. Returns (compiled
    executable, is_packed)."""
    import jax
    import jax.numpy as jnp

    if n_total >= PACKED_IDX_LIMIT:
        fn, is_packed = call, False
    else:
        def fn(q, items):
            return _pack(*call(q, items))

        is_packed = True
    compiled = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((B, _lanes(D)), jnp.float32),
        jax.ShapeDtypeStruct((D, N_pad), jnp.float32),
    ).compile()
    return compiled, is_packed


def _raw_xla_call(n_total: int, k: int):
    """Un-jitted plain-XLA top-k over the same device array — the
    serving path for NON-TPU backends, where running the Pallas kernel
    under ``interpret=True`` is a correctness tool, not a serving path
    (the interpreter is orders of magnitude slower than compiled XLA
    on the CPU backend). Same output contract as the kernel: padded/overflow slots
    carry value -inf and index -1."""
    import jax
    import jax.numpy as jnp

    def run(q, items):  # q [B, 128 lanes] f32, items [D_pad, N_pad] f32
        scores = jax.lax.dot_general(
            q[:, :items.shape[0]], items, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST,  # rank-stable vs the
            # kernel / sharded paths and host f32 references (DEFAULT
            # would allow TF32-class matmuls on some non-TPU backends)
        )
        col = jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
        scores = jnp.where(col < n_total, scores, -jnp.inf)
        vals, idx = jax.lax.top_k(scores, k)
        idx = jnp.where(jnp.isfinite(vals), idx, -1).astype(jnp.int32)
        return vals, idx

    return run


def _build_xla_call(B, D, N_pad, n_total, k, *, pin=False):
    """Compiled XLA top-k behind the shared packing policy, AOT-built
    into EXEC_CACHE like the kernel path (full shape key: the executable
    is compiled, not a retracing jit)."""
    key = ("xla", B, D, N_pad, n_total, k)
    out = EXEC_CACHE.get_or_build(key, lambda: _aot_with_packing(
        _raw_xla_call(n_total, k), n_total, B, D, N_pad))
    if pin:
        EXEC_CACHE.pin(key)
    return out


def _run_topk_xla(q: np.ndarray, items_dev, n_total: int, k: int):
    """Single-device entry, plain-XLA path (non-TPU serving)."""

    def invoke(qp, k_pad):
        call, is_packed = _build_xla_call(
            qp.shape[0], *items_dev.shape, n_total, k_pad)
        # the compiled executable takes the padded numpy batch directly —
        # no jnp.asarray bounce through the default device
        return call(qp, items_dev), is_packed

    return _dispatch_topk(q, n_total, k, invoke)


def _padded_shape(n: int, d: int) -> tuple[int, int]:
    """(sublanes, lanes) of an [n, d] catalog on the device: the rank
    padded to the 8 sublanes of a float32 tile, the items so that the
    kernel's tiles divide them: to a multiple of the largest power of
    two between 128 and `_TILE_MAX` that is at most a sixteenth of the
    items (a small catalog is not padded by a large tile's worth)."""
    quantum = 128
    while quantum < _TILE_MAX and quantum * 16 <= n:
        quantum *= 2
    return -(-d // 8) * 8, -(-n // quantum) * quantum


#: Catalog rows one block of the upload holds (128 MiB at rank 64).
_UPLOAD_ROWS = 1 << 19


@functools.lru_cache(maxsize=None)
def _place_block():
    """The jitted step of `_pad_items`: a block of catalog rows
    transposed into its columns of the device array. The array is
    donated where the backend can alias it; elsewhere a step copies it,
    which the sizes that run there can afford."""
    import jax

    def place(out, block, start):
        return jax.lax.dynamic_update_slice(out, block.T, (0, start))

    donate = (0,) if jax.default_backend() in ("tpu", "gpu") else ()
    return jax.jit(place, donate_argnums=donate)


def _pad_items(items: np.ndarray):
    """The catalog as the kernel and the XLA program read it: a zeroed
    [d_pad, N_pad] device array (`_padded_shape`) with item i in column
    i. The model's own [N, d] array goes up block by block and is
    transposed on the device, each block waited for: the host holds no
    second copy, the device one block beside the array (0.7 s for 3.89
    GB on the v5e; PERF.md, PR 30)."""
    import jax
    import jax.numpy as jnp

    n, d = items.shape
    out = jnp.zeros(_padded_shape(n, d), jnp.float32)
    place = _place_block()
    for start in range(0, n, _UPLOAD_ROWS):
        out = jax.block_until_ready(
            place(out, items[start:start + _UPLOAD_ROWS], start))
    return out


def _query_shapes(b: int, k_eff: int, n_total: int) -> tuple[int, int]:
    """Shape discipline on the serving hot path: batch padded to a power
    of two (>=8) and k rounded up to a multiple of 8, so traffic-dependent
    batch sizes / client-chosen num values map onto a handful of compiled
    kernels instead of one per (B, k) pair. The ONE home of this policy:
    the retrievers, their prewarm and the serving pipeline all key their
    compiled programs on it."""
    b_pad = 8
    while b_pad < b:
        b_pad *= 2
    return b_pad, min(((k_eff + 7) // 8) * 8, n_total)


def _dispatch_topk(q: np.ndarray, n_total: int, k: int, invoke,
                   on_scan=None):
    """Query-side prep + result un-pad shared by EVERY top-k entry point
    (``topk_scores``, ``DeviceRetriever.topk``, ``ShardedDeviceRetriever
    .topk``) — one home so padding/empty-catalog/pack handling cannot
    drift between them. ``invoke(q_padded, k_pad)`` runs the compiled
    call and returns ``(out, is_packed)``: the packed f32 buffer or the
    separate (vals, idx[, counters]) buffers. ``on_scan`` takes the
    kernel's counters where the scoring program has any."""
    FAULTS.fire("retrieval.topk")  # chaos site: a hang here IS a hung
    # device call (faults.py); no-op unless a test armed it
    single = q.ndim == 1
    if single:
        q = q[None, :]
    k_eff = min(k, n_total)
    if n_total == 0 or k_eff <= 0:
        empty_v = np.zeros((q.shape[0], 0), np.float32)
        empty_i = np.zeros((q.shape[0], 0), np.int32)
        return (empty_v[0], empty_i[0]) if single else (empty_v, empty_i)
    b_orig = q.shape[0]
    b_pad, k_pad = _query_shapes(q.shape[0], k_eff, n_total)
    facts = {"rows": b_orig, "b_pad": b_pad}
    # Stage spans (obs/waterfall.py): each block is a named event in a
    # profiler capture, and its end is the stage's mark when a serve
    # request is being attributed. Only then is the invoke split into
    # dispatch (the call returning an async device handle) and compute
    # (block_until_ready): un-attributed callers (training, bench
    # device-spin) keep the async pipeline untouched.
    with stage_span("host_assembly", **facts):
        LEDGER.record_padding_waste(b_orig, b_pad)
        q = _pad_to(q, b_pad, 0)
        q = _pad_to(q, 128, 1)
    with stage_span("device_dispatch", **facts):
        out, is_packed = invoke(q, k_pad)
    if stage_sink_active():
        with stage_span("device_compute", **facts):
            try:
                import jax

                jax.block_until_ready(out)
            except Exception:
                pass  # numpy results / non-jax invokes: nothing to fence
    with stage_span("result_scatter", **facts):
        vals, idx, counts = _unpack(out, is_packed, b_orig, k_eff, k_pad)
    if on_scan is not None and counts is not None:
        on_scan(counts)
    return (vals[0], idx[0]) if single else (vals, idx)


def _run_topk(q: np.ndarray, items_dev, n_total: int, k: int,
              interpret: bool, on_scan=None):
    """Single-device entry: fused Pallas kernel behind ``_dispatch_topk``."""

    def invoke(qp, k_pad):
        call, is_packed = _build_call(
            qp.shape[0], *items_dev.shape, n_total, k_pad, interpret)
        return call(qp, items_dev), is_packed

    return _dispatch_topk(q, n_total, k, invoke, on_scan)


def _resolve_topk_mode(interpret) -> str:
    """``interpret=None`` picks the serving path for the backend: the
    native Pallas kernel on TPU, plain XLA elsewhere (fast compiled
    host code). ``interpret=True`` forces the Pallas kernel under the
    interpreter — the TPU-semantics parity path tests use, ~65x slower
    than the XLA path on CPU, never a serving default. ``False`` forces
    the native kernel."""
    if interpret is None:
        import jax

        return "native" if jax.default_backend() == "tpu" else "xla"
    return "interpret" if interpret else "native"


def topk_scores(queries, items, k: int, *, interpret=None):
    """Top-k inner-product retrieval: (values [B, k], indices [B, k]).

    queries: [B, D] or [D]; items: [N, D]. Indices of padded/overflow slots
    are -1. Runs the Pallas kernel natively on TPU, plain XLA elsewhere;
    ``interpret=True`` forces the interpret-mode kernel (parity testing).
    """
    mode = _resolve_topk_mode(interpret)
    q = np.asarray(queries, dtype=np.float32)
    it = np.asarray(items, dtype=np.float32)
    n_total = it.shape[0]
    items_dev = _pad_items(it)
    if mode == "xla":
        return _run_topk_xla(q, items_dev, n_total, k)
    return _run_topk(q, items_dev, n_total, k, mode == "interpret")


class DeviceRetriever:
    """Catalog factors kept device-resident for serving: one host->device
    transfer at load/reload, then every query is a single compiled
    fused-top-k call (the engine server's /reload double-buffers by
    building a new DeviceRetriever and swapping the reference)."""

    def __init__(self, items: np.ndarray, *, interpret=None):
        self._mode = _resolve_topk_mode(interpret)
        self._scan_lock = threading.Lock()
        # sub-tiles scanned, merged, rounds; catalog bytes needed, read
        self._scan = np.zeros(5, np.int64)
        with span("deploy.attach_retriever.catalog_pad",
                  sink=STARTUP.phase) as s:
            # the model's own array where it is float32 already
            it = np.asarray(items, dtype=np.float32)
            self.n_total, self.dim = it.shape
            s["bytes"] = int(it.nbytes)
        with span("deploy.attach_retriever.catalog_upload",
                  sink=STARTUP.phase) as s:
            # every block waited for, so that the upload's seconds stand
            # here and not in whatever first needs the catalog
            self._items = _pad_items(it)
            s["bytes"] = int(self._items.nbytes)

    @property
    def kernel(self) -> str:
        """Which program scores the catalog: ``native`` (the Pallas
        kernel compiled by Mosaic), ``xla`` or ``interpret``."""
        return self._mode

    @property
    def lane_dim(self) -> int:
        """Query lane width this retriever's compiled programs take.
        ``topk`` accepts queries already padded to this width unchanged
        (``_dispatch_topk``'s lane pad is then a no-op), which is what
        lets the device-resident pipeline's gathered query matrix hand
        off with zero re-pad."""
        return _lanes(self.dim)

    def record_scan(self, counts) -> None:
        """Add one kernel call's counters (sub-tiles scanned, sub-tiles
        merged, extraction rounds) to this retriever's totals and to the
        registry, and with them the bytes of catalog the scan needed
        (float32 factors of the real items) and those of the device
        array it read. Called with what `_unpack` found in the pulled
        buffer, by ``topk`` and by the serving pipeline's fused
        dispatch."""
        scanned, merged, rounds = (int(c) for c in counts)
        with self._scan_lock:
            self._scan += (scanned, merged, rounds,
                           self.n_total * self.dim * 4,
                           int(self._items.nbytes))
        _M_TILES.inc(scanned, event="scanned")
        _M_TILES.inc(merged, event="merged")
        _M_ROUNDS.inc(rounds)

    def stats(self) -> dict:
        """/stats.json's ``retrieval`` block. The counters stay 0 where
        the XLA program scores the catalog: it has no tiles and reports
        no scan."""
        with self._scan_lock:
            scanned, merged, rounds, needed, read = (
                int(c) for c in self._scan)
        return {"mode": "exact", "kernel": self._mode,
                "nTotal": self.n_total, "sharded": False,
                "tilesScanned": scanned, "tilesMerged": merged,
                "mergeRounds": rounds, "catalogBytesNeeded": needed,
                "catalogBytesScanned": read}

    def topk(self, queries, k: int):
        """(values [B, k], indices [B, k]) — indices -1 beyond catalog."""
        q = np.asarray(queries, dtype=np.float32)
        if self._mode == "xla":
            return _run_topk_xla(q, self._items, self.n_total, k)
        return _run_topk(q, self._items, self.n_total, k,
                         self._mode == "interpret", self.record_scan)

    def prewarm(self, batch_sizes=(1,), ks=(10,)) -> list[tuple[int, int]]:
        """AOT-build and PIN the executables for the hot serving shapes,
        so the first query of a pre-warmed shape never pays a compile and
        executable-cache churn can never evict it. Called by the deploy
        path (workflow/create_server.Deployed) with the micro-batcher's
        max_batch and the single-query pad. Returns the distinct
        (b_pad, k_pad) shapes warmed."""
        warmed: list[tuple[int, int]] = []
        for b in batch_sizes:
            for k in ks:
                k_eff = min(k, self.n_total)
                if b <= 0 or k_eff <= 0:
                    continue
                b_pad, k_pad = _query_shapes(b, k_eff, self.n_total)
                if (b_pad, k_pad) in warmed:
                    continue
                with span("deploy.prewarm.program", sink=STARTUP.phase,
                          kind="topk", b_pad=b_pad, k_pad=k_pad):
                    if self._mode == "xla":
                        _build_xla_call(b_pad, *self._items.shape,
                                        self.n_total, k_pad, pin=True)
                    else:
                        _build_call(b_pad, *self._items.shape,
                                    self.n_total, k_pad,
                                    self._mode == "interpret", pin=True)
                warmed.append((b_pad, k_pad))
        return warmed


class ShardedDeviceRetriever:
    """Catalog top-k with the item matrix SHARDED over a mesh axis — the
    serving-plane counterpart of model-parallel training: a catalog too
    large for one chip's HBM (or co-resident with a model-sharded training
    job) serves top-N without ever being replicated.

    Communication structure (the point of the design): each device scores
    its own [N/P, D] shard and reduces it to a local [B, k] candidate set
    inside ``shard_map``; the only collective is ONE all-gather of the
    packed [B, 2k] candidate buffers for the final merge — O(B*P*k) bytes
    over ICI, independent of catalog size. The cross-shard top-k-of-
    candidates merge ALSO runs inside the shard_map (every device merges
    the replicated [B, P*2k] gather redundantly — P*k is tiny), so the
    program leaves the device as the packed [B, 2k] result: one host
    pull, no GSPMD resharding step between the gather and the merge. No
    all-reduce, no all-to-all, and the [B, N] score matrix never exists
    globally (the reference's analog ships whole factor RDD partitions
    through Spark's shuffle to one driver-side sort, examples/scala-
    parallel-similarproduct/multi/src/main/scala/ALSAlgorithm.scala:
    146-200).

    API-compatible with ``DeviceRetriever`` (``topk``, ``n_total``,
    ``prewarm``): the serving mixin and micro-batcher use either
    interchangeably.
    """

    #: Where the cross-shard candidate merge runs. "device" = inside the
    #: shard_map program (one packed pull). `pio bench serve` prints it
    #: per row so the sweep is self-describing.
    merge = "device"

    #: Each shard is scored by a plain XLA dot + top_k inside the
    #: shard_map program, on every backend (same vocabulary as
    #: ``DeviceRetriever.kernel``).
    kernel = "xla"

    def __init__(self, items: np.ndarray, mesh, *, axis: str = "model"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._mesh = mesh
        self._axis = axis
        self._nshards = int(mesh.shape[axis])
        it = np.asarray(items, dtype=np.float32)
        self.n_total, self.dim = it.shape
        it = _pad_to(it, 128, 1)
        # row-pad so every shard is equal-sized and lane-aligned
        it = _pad_to(it, 128 * self._nshards, 0)
        self._shard_rows = it.shape[0] // self._nshards
        # per-shard callback instead of a plain device_put: each process
        # materializes only its ADDRESSABLE shards, so the same code
        # serves from a mesh spanning multiple hosts (every host holds
        # the catalog on the host side; only 1/P lands in its HBM)
        self._items = jax.make_array_from_callback(
            it.shape, NamedSharding(mesh, P(axis, None)),
            lambda index: it[index])  # numpy slice: one direct
        # host->target-device transfer per shard (jnp.asarray here would
        # bounce every shard through the default device first)
        self._token = next(_RETRIEVER_TOKENS)  # EXEC_CACHE key namespace

    @property
    def lane_dim(self) -> int:
        """Query lane width (queries pre-padded to it pass through
        ``_dispatch_topk``'s lane pad unchanged — the pipeline's gather
        handoff contract, same as ``DeviceRetriever.lane_dim``)."""
        return int(self._items.shape[1])

    def _call_for(self, b_pad: int, k_local: int, k_out: int, *,
                  pin: bool = False):
        key = ("sharded", self._token, b_pad, k_local, k_out)
        fn = EXEC_CACHE.get_or_build(
            key, lambda: self._build(b_pad, k_local, k_out))
        if pin:
            EXEC_CACHE.pin(key)
        return fn

    def _build(self, b_pad: int, k_local: int, k_out: int):
        # k_local: per-shard candidates (<= shard rows; a global top-k_out
        # set takes at most shard_rows entries from any one shard, so
        # k_local = min(k_out, shard_rows) is exact, not approximate).
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        axis, n_total, S = self._axis, self.n_total, self._shard_rows
        nsh = self._nshards
        packed = n_total < PACKED_IDX_LIMIT

        def local_merge(q, shard):  # q [B, D] replicated; shard [S, D]
            scores = jax.lax.dot_general(
                q, shard, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
                precision=jax.lax.Precision.HIGHEST,  # rank-stable vs the
                # single-device kernel and the host f32 reference
            )  # [B, S]
            offset = jax.lax.axis_index(axis) * S
            cand = offset + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
            scores = jnp.where(cand < n_total, scores, -jnp.inf)
            v, i = jax.lax.top_k(scores, k_local)
            i = jnp.take_along_axis(cand, i, axis=1)
            # the gather is shard-major, and within a shard top_k orders
            # ties by ascending index — so candidate order in the merged
            # buffer IS ascending global index per score, and the final
            # top_k tie-breaks exactly like the full-catalog top_k
            # (bitwise parity, pinned by test_sharded_bitwise_parity)
            if packed:
                # indices ride the gather as f32 (exact below 2^24):
                # ONE collective instead of two
                buf = jnp.concatenate([v, i.astype(jnp.float32)], axis=1)
                g = jax.lax.all_gather(buf, axis, axis=1, tiled=True)
                g = g.reshape(g.shape[0], nsh, 2 * k_local)
                v_all = g[:, :, :k_local].reshape(-1, nsh * k_local)
                i_all = g[:, :, k_local:].reshape(
                    -1, nsh * k_local).astype(jnp.int32)
            else:
                v_all = jax.lax.all_gather(v, axis, axis=1, tiled=True)
                i_all = jax.lax.all_gather(i, axis, axis=1, tiled=True)
            mv, sel = jax.lax.top_k(v_all, k_out)
            mi = jnp.take_along_axis(i_all, sel, axis=1)
            mi = jnp.where(jnp.isfinite(mv), mi, -1)
            if packed:  # packed result: ONE host pull
                return jnp.concatenate([mv, mi.astype(jnp.float32)], axis=1)
            return mv, mi

        def run(q, items):
            return jax.shard_map(
                local_merge, mesh=self._mesh,
                in_specs=(P(), P(axis, None)),
                out_specs=P() if packed else (P(), P()),
                check_vma=False,
            )(q, items)

        return jax.jit(run, in_shardings=(
            NamedSharding(self._mesh, P()),
            NamedSharding(self._mesh, P(axis, None)),
        )).lower(
            jax.ShapeDtypeStruct((b_pad, self._items.shape[1]), jnp.float32),
            jax.ShapeDtypeStruct(self._items.shape, jnp.float32),
        ).compile()

    def topk(self, queries, k: int):
        """(values [B, k], indices [B, k]) — indices -1 beyond catalog.
        Accepts [D] or [B, D]; exact parity with DeviceRetriever.topk
        (pinned by test_retrieval.test_sharded_matches_single_device)."""
        import jax

        def invoke(qp, k_pad):
            k_local = min(k_pad, self._shard_rows)
            call = self._call_for(qp.shape[0], k_local, k_pad)
            # padded numpy batch straight into the compiled executable
            # (an asarray here would land it on the default device first,
            # just to be resharded by the in_shardings)
            with _COLLECTIVE_LAUNCH_LOCK:
                out = jax.block_until_ready(call(qp, self._items))
            return out, self.n_total < PACKED_IDX_LIMIT

        return _dispatch_topk(np.asarray(queries, dtype=np.float32),
                              self.n_total, k, invoke)

    def prewarm(self, batch_sizes=(1,), ks=(10,)) -> list[tuple[int, int]]:
        """AOT-build and PIN the hot serving shapes' executables — same
        contract as ``DeviceRetriever.prewarm``."""
        warmed: list[tuple[int, int]] = []
        for b in batch_sizes:
            for k in ks:
                k_eff = min(k, self.n_total)
                if b <= 0 or k_eff <= 0:
                    continue
                b_pad, k_pad = _query_shapes(b, k_eff, self.n_total)
                if (b_pad, k_pad) in warmed:
                    continue
                self._call_for(b_pad, min(k_pad, self._shard_rows), k_pad,
                               pin=True)
                warmed.append((b_pad, k_pad))
        return warmed


#: choose_shard_count's cost model, in scanned-item units per query:
#: sharding w ways scans N/w rows per device but pays the cross-shard
#: candidate merge — a near-fixed collective/launch cost plus a small
#: per-way term. The two constants were set on a forced CPU mesh; not
#: measured on the chip.
MERGE_COST_FIXED = 192_000
MERGE_COST_PER_WAY = 16_000


def choose_shard_count(n_total: int, ndev: int, *,
                       merge_fixed: int = MERGE_COST_FIXED,
                       merge_per_way: int = MERGE_COST_PER_WAY) -> int:
    """Shard count for a catalog of ``n_total`` rows on ``ndev`` devices:
    argmin over power-of-two widths of ``N/w + (w > 1) * (merge_fixed +
    merge_per_way * w)``. A width is only picked when its per-shard scan
    saving exceeds the merge it adds, so 8-way can never be selected
    where the model says 1-way is faster. Deploy (``--retriever-mesh
    auto``) and ``pio bench serve --ways auto`` both route through here
    at executable-build time."""
    ndev = max(1, int(ndev))
    best_w, best_cost = 1, float(max(0, n_total))
    w = 2
    while w <= ndev:
        cost = n_total / w + merge_fixed + merge_per_way * w
        if cost < best_cost:
            best_w, best_cost = w, cost
        w *= 2
    return best_w


class RetrievalServingMixin:
    """Serving-side device retrieval for models whose predict step is
    "score a catalog matrix against one query row, keep top-k" (ALS
    factors, two-tower embeddings, ...).

    Provides ``attach_retriever`` (build a DeviceRetriever over the
    catalog attribute named by ``_retrieval_attr``) and keeps the device
    handle out of pickled MODELDATA blobs.
    """

    _retrieval_attr = "item_factors"
    _retrieval_ids_attr = "item_ids"

    def _catalog_ids_inverse(self):
        """Row -> item id. The id map builds its inverse at first use,
        which is the first answer a deployed model gives (8-26 s at 15.2
        million items): that build is ``pio.serve.id_map_inverse``."""
        ids = getattr(self, self._retrieval_ids_attr)
        if getattr(ids, "inverse_built", True):
            return ids.inverse
        with span("serve.id_map_inverse", sink=STARTUP.phase,
                  entries=len(ids)):
            return ids.inverse

    def top_n_from_catalog(self, query_vec, num: int) -> list[tuple[str, float]]:
        """[(id, score)] top-N of catalog·query: through the device
        retriever when attached, else a host argpartition. The single
        home of this logic for every retrieval-serving model."""
        inv = self._catalog_ids_inverse()
        via_device = self._retriever_topk(query_vec, num, inv)
        if via_device is not None:
            return via_device
        catalog = getattr(self, self._retrieval_attr)
        scores = catalog @ np.asarray(query_vec, catalog.dtype)
        num = min(num, len(scores))
        if num <= 0:
            return []
        top = np.argpartition(-scores, num - 1)[:num]
        top = top[np.argsort(-scores[top])]
        return [(inv[int(i)], float(scores[i])) for i in top]

    def top_n_batch(self, query_mat, num: int) -> list[list[tuple[str, float]]]:
        """Batched ``top_n_from_catalog``: one fused device call (or one
        host matmul) for a whole micro-batch of query vectors [B, D]."""
        q = np.asarray(query_mat, np.float32)
        if q.ndim != 2 or len(q) == 0:
            return []
        inv = self._catalog_ids_inverse()
        retriever = getattr(self, "_retriever", None)
        if retriever is not None:
            vals, idx = retriever.topk(q, num)
            return [
                [(inv[int(i)], float(v)) for v, i in zip(vr, ir) if i >= 0]
                for vr, ir in zip(vals, idx)
            ]
        catalog = getattr(self, self._retrieval_attr)
        scores = q @ catalog.T  # [B, N]
        k = min(num, scores.shape[1])
        if k <= 0:
            return [[] for _ in range(len(q))]
        top = np.argpartition(-scores, k - 1, axis=1)[:, :k]
        out = []
        for r, t in zip(scores, top):
            t = t[np.argsort(-r[t])]
            out.append([(inv[int(i)], float(r[i])) for i in t])
        return out

    _query_attr = "user_factors"
    _query_ids_attr = "user_ids"

    def batch_recommend(self, users: list, nums: list) -> list[list[tuple[str, float]]]:
        """Per-user top-N for a whole micro-batch in one device call;
        unknown users get []. The single home of the unknown-user/kmax/
        trim dance for every retrieval-serving model's batch_predict.

        With a serving pipeline attached (ISSUE 16), the host side of
        this shrinks to ONE vectorized id->row translation: the factor
        gather, padding and scoring all run in the pipeline's compiled
        device programs. The compacted row batch and the trim dance are
        identical to the retriever branch below (what serves a model
        with no pipeline attached, and the tests' reference), so results
        are bit-for-bit the same (the capture/replay parity tests pin
        it)."""
        uids = getattr(self, self._query_ids_attr)
        qmat = getattr(self, self._query_attr)
        out: list = [[] for _ in users]
        pipe = getattr(self, "_pipeline", None)
        if pipe is not None and pipe.n_rows == len(qmat):
            rows = uids.map_array(users)
            known = np.flatnonzero(rows >= 0)
            if known.size == 0:
                return out
            kmax = max(max(nums[j] for j in known), 0)
            vals, idx = pipe.topk_rows(rows[known], kmax)
            inv = self._catalog_ids_inverse()
            for j, vr, ir in zip(known.tolist(), vals, idx):
                rec = [(inv[int(i)], float(v))
                       for v, i in zip(vr, ir) if i >= 0]
                out[j] = rec[: max(nums[j], 0)]
            return out
        known = [(j, uids.get(u)) for j, u in enumerate(users)]
        known = [(j, r) for j, r in known if r is not None]
        if not known:
            return out
        kmax = max(max(nums[j] for j, _ in known), 0)
        recs = self.top_n_batch(qmat[[r for _, r in known]], kmax)
        for (j, _r), rec in zip(known, recs):
            out[j] = rec[: max(nums[j], 0)]
        return out

    def attach_retriever(self, interpret=None) -> None:
        """Move the catalog device-resident and serve top-N through the
        fused Pallas retrieval kernel. Called by the engine server at
        deploy/reload time on TPU backends; replacing the retriever
        wholesale is the /reload double-buffer swap."""
        self._retriever = DeviceRetriever(
            getattr(self, self._retrieval_attr), interpret=interpret
        )

    def attach_ann_retriever(self, interpret=None, **params) -> None:
        """Serve top-N through the IVF approximate-MIPS index
        (ops/ann.py AnnRetriever) — same serving surface, sub-linear
        scan. ``params`` is the engine-params ``retrieval`` block minus
        ``mode`` (nprobe / quantize / n_cells / min_items /
        kmeans_iters / kmeans_sample / max_cell_factor / seed). Small
        catalogs and failed builds fall back to exact inside the
        retriever; /reload swaps it like any retriever."""
        from .ann import AnnRetriever

        self._retriever = AnnRetriever(
            getattr(self, self._retrieval_attr), interpret=interpret,
            **params)

    def attach_pipeline(self) -> None:
        """Make the QUERY side of serving device-resident too (ISSUE
        16): upload the user-factor table into a ServingPipeline over
        the already-attached retriever, so ``batch_recommend`` ships
        only int32 row indices per request. Requires a retriever
        (exact, ANN or sharded — the pipeline adapts); /reload builds a
        fresh bundle and re-attaches, delta patches ``refresh`` the
        table copy-on-write without invalidating compiled programs."""
        from .pipeline import ServingPipeline

        self._pipeline = ServingPipeline(
            getattr(self, self._query_attr),
            getattr(self, "_retriever", None))

    def attach_sharded_retriever(self, mesh, *, axis: str = "model") -> None:
        """Serve top-N from a catalog SHARDED over ``mesh``'s ``axis`` —
        same serving surface, ShardedDeviceRetriever underneath. For
        catalogs past one chip's HBM or deployments co-resident with a
        model-sharded trainer; /reload swaps it like any retriever."""
        self._retriever = ShardedDeviceRetriever(
            getattr(self, self._retrieval_attr), mesh, axis=axis)

    def attach_similarity_retriever(self, interpret=None) -> None:
        """Row-NORMALIZED catalog retriever: cosine similar-items serving
        (the similarproduct family) as the same fused top-k kernel — an
        aggregate cosine over query items is one retrieval with the
        summed normalized query vectors (Σ over k query items of
        cn·qn_k = cn·Σqn_k)."""
        cn = row_normalize(getattr(self, self._retrieval_attr))
        self._sim_retriever = DeviceRetriever(cn, interpret=interpret)

    def attach_sharded_similarity_retriever(self, mesh, *,
                                            axis: str = "model") -> None:
        """Sharded variant of ``attach_similarity_retriever``: the
        normalized catalog shards over ``mesh``'s ``axis`` so cosine
        similar-items serving scales past one chip's HBM like the
        inner-product path does."""
        cn = row_normalize(getattr(self, self._retrieval_attr))
        self._sim_retriever = ShardedDeviceRetriever(cn, mesh, axis=axis)

    def __getstate__(self):
        state = dict(self.__dict__)
        # device arrays and derived caches never enter MODELDATA
        state.pop("_retriever", None)
        state.pop("_sim_retriever", None)
        state.pop("_pipeline", None)
        state.pop("_vtv_cache", None)
        state.pop("_cn_cache", None)
        return state

    def _retriever_topk(self, query_vec, num, inverse_ids):
        """[(id, score)] via the attached retriever, or None if detached."""
        retriever = getattr(self, "_retriever", None)
        if retriever is None:
            return None
        vals, idx = retriever.topk(query_vec, num)
        return [(inverse_ids[int(i)], float(v))
                for v, i in zip(vals, idx) if i >= 0]
