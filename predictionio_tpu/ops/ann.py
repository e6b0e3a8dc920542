"""IVF-style approximate MIPS retrieval in pure JAX (the 100M-item path).

Brute-force top-k (ops/retrieval.py) scans every catalog row per query —
the right answer until the catalog outgrows what one scan per query can
afford. This module trades a bounded slice of recall for a sub-linear
scan, with the structure large-scale ads/recsys serving stacks use
(arXiv:2501.10546: quantize, prune, then exact-rescore the survivors):

1. **Partition** — k-means the item factors into ~sqrt(N) cells at
   deploy/reload time (jitted Lloyd iterations over a bounded training
   sample, then a chunked full-catalog assignment). Cell sizes are
   CAPPED at ``max_cell_factor`` x the mean: natural k-means cell sizes
   are heavily skewed, and the padded dense cell layout below pays for
   the LARGEST cell on every probe — overflow items spill to their
   next-nearest cell instead (bounded padding beats a point of recall;
   the spill fraction is small because items fill nearest-first).
2. **Quantize** — centroids are stored int8 (per-centroid scale) or
   bf16; the coarse [B, C] scoring pass runs over dequantized
   centroids, so cell selection is cheap and the full-precision item
   factors are only touched for cells that survive.
3. **Probe + rescore** — the top ``nprobe`` cells per query are
   gathered ([B, L, D] per probe step inside one ``lax.scan``) and
   exact-rescored in f32 (HIGHEST precision, matching the exact path's
   ranking), then one ``lax.top_k`` over the [B, nprobe*L] candidates.

Everything after build time is one compiled XLA program, AOT-warmed
through the shared ``ExecutableCache`` exactly like the exact
retrievers, and served through the same ``_dispatch_topk`` entry (same
padding/empty-catalog/packed-pull policy, same ``retrieval.topk`` chaos
site).

Escape hatches, all automatic:

- catalogs under ``min_items`` never build an index (``exact_fallback``
  — the scan is already fast there);
- a failed index build (chaos site ``retrieval.ann_build``) falls back
  to exact retrieval instead of failing the deploy;
- ``nprobe >= n_cells`` would scan everything anyway, so those queries
  DELEGATE to the exact compiled program — bit-for-bit equal to
  ``DeviceRetriever`` (the parity edge tests pin this), because a
  gathered-rescore matmul is NOT bitwise identical to the full
  dot_general even at HIGHEST precision.

The probe budget scales with the requested k (``effective_nprobe``):
a brownout-clamped k=10 query probes ~sqrt(10/64) of the configured
budget, so the PR-6 top-k clamp reduces rescore work, not just the
response length.
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from ..obs.metrics import METRICS
from ..faults import FAULTS
from .retrieval import (EXEC_CACHE, PACKED_IDX_LIMIT, _RETRIEVER_TOKENS,
                        _dispatch_topk, _query_shapes, _resolve_topk_mode,
                        DeviceRetriever)

__all__ = ["AnnIndex", "AnnRetriever", "build_index", "pick_cells",
           "effective_nprobe", "kmeans_centroids", "DEFAULT_NPROBE",
           "ANN_MIN_ITEMS", "NPROBE_REF_K"]

#: Catalogs below this serve exact — the brute scan is already fast and
#: an index would spend build seconds to make recall worse than 1.0.
ANN_MIN_ITEMS = 16_384

#: The k at which the configured ``nprobe`` applies in full; smaller
#: requests probe ~sqrt(k / NPROBE_REF_K) of it (see effective_nprobe).
NPROBE_REF_K = 64

#: Default probe budget, calibrated on the committed bench's clustered
#: 262k catalog: effective ~26 at k=10 lands recall@10 ~0.96 at ~1.5x
#: the exact scan's throughput (docs/operations.md "Retrieval at scale").
DEFAULT_NPROBE = 52

# ISSUE 7 satellites: the index must be scrapeable — cells / probe
# budget / dtype / build cost / fallback state as pio_retrieval_*
# metrics (docs/operations.md metric catalog has one row each)
_M_CELLS = METRICS.gauge(
    "pio_retrieval_index_cells",
    "k-means cells in the active ANN index (0 = exact retrieval)")
_M_NPROBE = METRICS.gauge(
    "pio_retrieval_nprobe_effective",
    "effective probe budget of the most recent ANN query (k-scaled)")
_M_BUILD = METRICS.histogram(
    "pio_retrieval_index_build_seconds",
    "wall seconds building the ANN index (k-means + layout + quantize)")
_M_FALLBACK = METRICS.gauge(
    "pio_retrieval_exact_fallback",
    "1 when an ANN-configured retriever is serving exact instead "
    "(small catalog or failed index build)")
_M_DTYPE = METRICS.gauge(
    "pio_retrieval_index_dtype",
    "active ANN centroid quantization (1 on the active dtype's series)",
    labelnames=("dtype",))
_M_QUERIES = METRICS.counter(
    "pio_retrieval_queries_total",
    "retrieval calls by serving mode (ann / exact_delegate when "
    "nprobe covers every cell / exact_fallback)",
    labelnames=("mode",))


def pick_cells(n_total: int) -> int:
    """Default cell count: the power of two nearest sqrt(N) (coarse scan
    and per-probe rescore balance at ~sqrt(N) cells of ~sqrt(N) items),
    clamped to [32, 4096]."""
    if n_total <= 1:
        return 1
    return int(min(4096, max(32, 2 ** round(math.log2(math.sqrt(n_total))))))


def effective_nprobe(nprobe: int, k: int, n_cells: int, cell_len: int) -> int:
    """Probe budget for one query: ``nprobe`` scaled by sqrt(k /
    NPROBE_REF_K) — half the cells for a quarter of the k — capped at
    ``nprobe``, then floored so the probed rows can still hold k
    results. The floor OVERRIDES the cap: the compiled program calls
    ``top_k(candidates, k)`` and under-gathering is a shape error, not
    a recall loss (and the floor is always satisfiable because
    n_cells * cell_len >= n_total >= k_pad). When the floor reaches
    n_cells the caller's full-cover path delegates to exact.
    A full-cover budget (nprobe >= n_cells) is never reduced: it is the
    exact-parity contract, not a performance setting."""
    nprobe = max(1, min(int(nprobe), n_cells))
    if nprobe >= n_cells:
        return n_cells
    min_probe = max(1, math.ceil(k / max(1, cell_len)))
    if min_probe >= n_cells:
        return n_cells
    eff = math.ceil(nprobe * math.sqrt(max(1, k) / NPROBE_REF_K))
    return max(min(eff, nprobe), min_probe)


def kmeans_centroids(items: np.ndarray, n_cells: int, *, iters: int = 30,
                     sample: int = 262_144, seed: int = 0) -> np.ndarray:
    """Lloyd k-means over a bounded sample of the catalog; each
    iteration is ONE jitted program (argmin assignment + one-hot
    aggregation), so build time stays seconds at bench scale."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    n = len(items)
    sample = max(int(sample), n_cells)
    tr = items if n <= sample else items[rng.choice(n, sample, replace=False)]
    cent = tr[rng.choice(len(tr), n_cells, replace=False)].astype(np.float32)

    @jax.jit
    def step(cent, x):
        d = (jnp.sum(x * x, 1)[:, None] - 2.0 * (x @ cent.T)
             + jnp.sum(cent * cent, 1)[None, :])
        a = jnp.argmin(d, 1)
        one = jax.nn.one_hot(a, cent.shape[0], dtype=x.dtype)
        cnt = one.sum(0)
        newc = (one.T @ x) / jnp.maximum(cnt, 1.0)[:, None]
        # an emptied centroid keeps its position instead of collapsing
        # to zero (it can re-acquire items in a later iteration)
        return jnp.where(cnt[:, None] > 0, newc, cent)

    xs = jnp.asarray(tr, jnp.float32)
    for _ in range(max(1, iters)):
        cent = step(cent, xs)
    return np.asarray(cent)


def _capped_labels(items: np.ndarray, cent: np.ndarray, cap: int,
                   fanout: int = 8) -> np.ndarray:
    """Nearest-centroid assignment with a hard per-cell capacity: every
    item ranks its ``fanout`` nearest centroids (chunked host matmuls),
    then items place nearest-first — an item whose best cell is full
    spills to its next-nearest with room. Caps the padded cell length
    the probe loop pays for at ``cap`` without re-clustering."""
    n, n_cells = len(items), len(cent)
    fanout = min(fanout, n_cells)
    cn = np.sum(cent * cent, axis=1)
    ranks = np.empty((n, fanout), np.int32)
    d1 = np.empty(n, np.float32)
    for i in range(0, n, 65_536):
        d2 = cn[None, :] - 2.0 * (items[i:i + 65_536] @ cent.T)
        part = np.argpartition(d2, fanout - 1, axis=1)[:, :fanout]
        pd = np.take_along_axis(d2, part, axis=1)
        o = np.argsort(pd, axis=1, kind="stable")
        ranks[i:i + 65_536] = np.take_along_axis(part, o, axis=1)
        d1[i:i + 65_536] = pd[np.arange(len(pd)), o[:, 0]]
    # Vectorized nearest-first placement: one pass per fanout rank, not
    # one Python iteration per item (O(N) interpreter loops are minutes
    # at 100M rows). Within a pass, items are grouped by candidate cell
    # (stable sort keeps the confident-first order inside each group)
    # and each group accepts up to its remaining capacity.
    labels = np.full(n, -1, np.int32)
    counts = np.zeros(n_cells, np.int64)
    remaining = np.argsort(d1, kind="stable")  # confident items first
    for r in range(fanout):
        if not len(remaining):
            break
        cand = ranks[remaining, r].astype(np.int64)
        o = np.argsort(cand, kind="stable")
        sc = cand[o]
        first = np.r_[True, sc[1:] != sc[:-1]]
        run_start = np.maximum.accumulate(
            np.where(first, np.arange(len(sc)), 0))
        pos = np.arange(len(sc)) - run_start  # rank within the cell group
        ok = pos < (cap - counts[sc])
        placed = np.zeros(len(remaining), bool)
        placed[o[ok]] = True
        labels[remaining[placed]] = cand[placed]
        counts += np.bincount(sc[ok], minlength=n_cells)
        remaining = remaining[~placed]
    if len(remaining):
        # every ranked cell full: pack the emptiest cells' free slots
        # (total capacity n_cells * cap >= n, so slots always suffice)
        free = np.maximum(cap - counts, 0)
        cell_order = np.argsort(counts, kind="stable")
        slots = np.repeat(cell_order, free[cell_order])[:len(remaining)]
        labels[remaining] = slots.astype(np.int32)
    return labels


@dataclasses.dataclass
class AnnIndex:
    """The built index: dense padded cells + quantized centroids.

    ``cells`` is [n_cells, cell_len, dim] f32 (cell-major reorder of the
    catalog; pad rows are zero), ``ids`` is [n_cells, cell_len] int32
    original row ids with -1 pads. ``centroids`` is int8 [C, D] with
    per-centroid ``scales`` [C, 1] f32 (or bf16 with unit scales)."""

    centroids: np.ndarray
    scales: np.ndarray
    cells: np.ndarray
    ids: np.ndarray
    n_total: int
    dim: int
    n_cells: int
    cell_len: int
    quantize: str
    build_seconds: float


def build_index(items: np.ndarray, *, n_cells: int | None = None,
                kmeans_iters: int = 30, kmeans_sample: int = 262_144,
                max_cell_factor: float = 2.0, quantize: str = "int8",
                seed: int = 0) -> AnnIndex:
    """Partition + quantize the catalog (the deploy/reload-time step)."""
    if quantize not in ("int8", "bf16"):
        raise ValueError(f"quantize must be 'int8' or 'bf16', got {quantize!r}")
    t0 = time.perf_counter()
    items = np.asarray(items, np.float32)
    n, d = items.shape
    n_cells = int(n_cells) if n_cells else pick_cells(n)
    n_cells = max(1, min(n_cells, n))
    cent = kmeans_centroids(items, n_cells, iters=kmeans_iters,
                            sample=kmeans_sample, seed=seed)
    cap = max(1, math.ceil(max(1.0, max_cell_factor) * n / n_cells))
    labels = _capped_labels(items, cent, cap)
    order = np.argsort(labels, kind="stable")
    counts = np.bincount(labels, minlength=n_cells)
    cell_len = int(max(8, ((counts.max() + 7) // 8) * 8))
    cells = np.zeros((n_cells, cell_len, d), np.float32)
    ids = np.full((n_cells, cell_len), -1, np.int32)
    start = 0
    for c in range(n_cells):
        cnt = int(counts[c])
        cells[c, :cnt] = items[order[start:start + cnt]]
        ids[c, :cnt] = order[start:start + cnt]
        start += cnt
    if quantize == "int8":
        scales = (np.max(np.abs(cent), axis=1, keepdims=True) / 127.0
                  ).astype(np.float32)
        scales = np.maximum(scales, 1e-12)
        cent_q = np.clip(np.round(cent / scales), -127, 127).astype(np.int8)
    else:  # bf16 storage, unit scales — same dequant program shape
        import ml_dtypes

        cent_q = cent.astype(ml_dtypes.bfloat16)
        scales = np.ones((n_cells, 1), np.float32)
    return AnnIndex(centroids=cent_q, scales=scales, cells=cells, ids=ids,
                    n_total=n, dim=d, n_cells=n_cells, cell_len=cell_len,
                    quantize=quantize,
                    build_seconds=time.perf_counter() - t0)


class AnnRetriever:
    """Serving-surface twin of ``DeviceRetriever`` (``topk`` /
    ``prewarm`` / ``n_total``) over an IVF index. Can always produce an
    exact compiled program too — the delegate for full-cover probes, the
    fallback for small catalogs and failed builds (so a deploy
    configured ``mode: ann`` can never be LESS available than exact).
    The delegate is built LAZILY on first use: the padded cells already
    cost up to ``max_cell_factor`` x the catalog in HBM, and a
    replicated full-precision copy on top of that is exactly what will
    not fit at the catalog sizes ANN exists for (the host f32 array is
    kept instead — RAM, not HBM)."""

    def __init__(self, items: np.ndarray, *, nprobe: int = DEFAULT_NPROBE,
                 quantize: str = "int8", n_cells: int | None = None,
                 min_items: int = ANN_MIN_ITEMS, kmeans_iters: int = 30,
                 kmeans_sample: int = 262_144, max_cell_factor: float = 2.0,
                 interpret=None, seed: int = 0):
        import jax
        import jax.numpy as jnp

        items = np.asarray(items, np.float32)
        self.n_total, self.dim = items.shape
        self.nprobe = max(1, int(nprobe))
        self.min_items = max(0, int(min_items))
        self.last_effective_nprobe: int | None = None
        self._token = next(_RETRIEVER_TOKENS)
        # the exact delegate/fallback is built lazily from this host
        # copy — only the full-cover / fallback / empty-k paths pay its
        # HBM, not every ANN deploy
        self._items = items
        self._interpret = interpret
        self._exact_cached: DeviceRetriever | None = None
        self.index: AnnIndex | None = None
        self.fallback_reason: str | None = None
        if self.n_total < max(self.min_items, 2):
            self.fallback_reason = "small_catalog"
        else:
            try:
                FAULTS.fire("retrieval.ann_build")  # chaos site: a failed
                # build must degrade to exact, never fail the deploy
                self.index = build_index(
                    items, n_cells=n_cells, kmeans_iters=kmeans_iters,
                    kmeans_sample=kmeans_sample,
                    max_cell_factor=max_cell_factor, quantize=quantize,
                    seed=seed)
            except Exception as e:  # noqa: BLE001 — availability first
                self.fallback_reason = f"build_failed: {e}"
        if self.index is not None:
            ix = self.index
            self._cent_dev = jax.device_put(jnp.asarray(ix.centroids))
            self._scales_dev = jax.device_put(jnp.asarray(ix.scales))
            self._cells_dev = jax.device_put(jnp.asarray(ix.cells))
            self._ids_dev = jax.device_put(jnp.asarray(ix.ids))
            _M_BUILD.record(ix.build_seconds)
            _M_CELLS.set(ix.n_cells)
            _M_DTYPE.set(0, dtype="int8")
            _M_DTYPE.set(0, dtype="bf16")
            _M_DTYPE.set(1, dtype=ix.quantize)
            _M_FALLBACK.set(0)
        else:
            _M_CELLS.set(0)
            _M_FALLBACK.set(1)

    @property
    def _exact(self) -> DeviceRetriever:
        if self._exact_cached is None:
            self._exact_cached = DeviceRetriever(self._items,
                                                 interpret=self._interpret)
        return self._exact_cached

    @property
    def lane_dim(self) -> int:
        """Query lane width the compiled ANN programs lower against
        (128-rounded feature dim; the programs slice ``q[:, :d]`` back
        out). Queries pre-padded to this width pass through
        ``_dispatch_topk``'s lane pad unchanged AND through the exact
        delegate bitwise-identically — the contract the device-resident
        pipeline's gather handoff (``ops/pipeline.py``) relies on: a
        gathered ``[b_pad, lane_dim]`` matrix needs no host re-pad and
        cannot perturb the delegate-vs-ann fallback numerics."""
        return ((self.dim + 127) // 128) * 128

    # -- compiled ANN program ---------------------------------------------
    def _build_call(self, b_pad: int, k_pad: int, eff: int, *,
                    pin: bool = False):
        key = ("ann", self._token, b_pad, k_pad, eff)
        call = EXEC_CACHE.get_or_build(
            key, lambda: self._compile(b_pad, k_pad, eff))
        if pin:
            EXEC_CACHE.pin(key)
        return call

    def _compile(self, b_pad: int, k_pad: int, eff: int):
        """AOT-compile one (batch, k, nprobe) ANN shape: coarse
        quantized-centroid scan -> top-eff probe -> scan-over-probes
        gather + batched f32 rescore -> masked top-k. Returns the packed
        [B, 2k] executable under the shared packing policy."""
        import jax
        import jax.numpy as jnp

        ix = self.index
        d, n_total = ix.dim, self.n_total
        packed = n_total < PACKED_IDX_LIMIT

        def run(q, cent, scales, cells, ids):
            q = q[:, :d]  # _dispatch_topk lane-pads queries to 128
            cent_f = cent.astype(jnp.float32) * scales
            coarse = jax.lax.dot_general(
                q, cent_f, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            _, probe = jax.lax.top_k(coarse, eff)
            # ascending probe order: the gathered candidate buffer is
            # cell-major like the exact scan, so ties resolve stably
            probe = jnp.sort(probe, axis=1)

            def body(carry, pj):  # pj: [B] — one probed cell per query
                g = cells[pj]           # [B, L, D] gather
                gi = ids[pj]            # [B, L]
                sc = jax.lax.dot_general(
                    q, g, (((1,), (2,)), ((0,), (0,))),
                    preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.HIGHEST)  # rank-stable
                # against the exact path's f32 scores
                return carry, (sc, gi)

            _, (ss, ii) = jax.lax.scan(body, 0, probe.T)
            b = q.shape[0]
            ss = jnp.transpose(ss, (1, 0, 2)).reshape(b, -1)
            ii = jnp.transpose(ii, (1, 0, 2)).reshape(b, -1)
            ss = jnp.where(ii >= 0, ss, -jnp.inf)  # cell pads out
            vals, sel = jax.lax.top_k(ss, k_pad)
            idx = jnp.take_along_axis(ii, sel, axis=1)
            idx = jnp.where(jnp.isfinite(vals), idx, -1).astype(jnp.int32)
            if packed:
                return jnp.concatenate(
                    [vals, idx.astype(jnp.float32)], axis=1)
            return vals, idx

        d_pad = ((d + 127) // 128) * 128
        sds = jax.ShapeDtypeStruct
        compiled = jax.jit(run).lower(
            sds((b_pad, d_pad), jnp.float32),
            sds(ix.centroids.shape, ix.centroids.dtype),
            sds(ix.scales.shape, jnp.float32),
            sds(ix.cells.shape, jnp.float32),
            sds(ix.ids.shape, jnp.int32),
        ).compile()
        return compiled, packed

    # -- serving surface ---------------------------------------------------
    def topk(self, queries, k: int):
        """(values [B, k], indices [B, k]) — same contract as the exact
        retrievers (indices -1 beyond catalog / past the candidates the
        probed cells held)."""
        if self.index is None:
            _M_QUERIES.inc(mode="exact_fallback")
            return self._exact.topk(queries, k)
        q = np.asarray(queries, np.float32)
        b = 1 if q.ndim == 1 else q.shape[0]
        k_eff = min(k, self.n_total)
        if k_eff <= 0:
            return self._exact.topk(queries, k)  # empty-result contract
        _, k_pad = _query_shapes(b, k_eff, self.n_total)
        eff = effective_nprobe(self.nprobe, k_pad, self.index.n_cells,
                               self.index.cell_len)
        self.last_effective_nprobe = eff
        _M_NPROBE.set(eff)
        if eff >= self.index.n_cells:
            # full cover: every cell would be rescored — the exact
            # program IS that computation, bit-for-bit (the gathered
            # rescore is not bitwise identical to one full dot_general)
            _M_QUERIES.inc(mode="exact_delegate")
            return self._exact.topk(queries, k)
        _M_QUERIES.inc(mode="ann")
        # probe planning (nprobe calibration, cell cover) is host-side
        # assembly work in the stage waterfall: _dispatch_topk's own
        # host_assembly mark takes all the time since the previous mark,
        # then splits the invoke into dispatch/compute/scatter

        def invoke(qp, k_pad_):
            call, packed = self._build_call(qp.shape[0], k_pad_, eff)
            out = call(qp, self._cent_dev, self._scales_dev,
                       self._cells_dev, self._ids_dev)
            return out, packed

        return _dispatch_topk(q, self.n_total, k, invoke)

    def prewarm(self, batch_sizes=(1,), ks=(10,)) -> list[tuple[int, int]]:
        """AOT-build and PIN the hot (batch, k) ANN executables — same
        deploy-time contract as the exact retrievers; full-cover shapes
        warm the exact delegate instead."""
        warmed: list[tuple[int, int]] = []
        delegate_ks: list[int] = []
        for b in batch_sizes:
            for k in ks:
                k_eff = min(k, self.n_total)
                if b <= 0 or k_eff <= 0:
                    continue
                b_pad, k_pad = _query_shapes(b, k_eff, self.n_total)
                if (b_pad, k_pad) in warmed:
                    continue
                if self.index is None:
                    continue  # fallback: warmed via _exact below
                eff = effective_nprobe(self.nprobe, k_pad,
                                       self.index.n_cells,
                                       self.index.cell_len)
                if eff >= self.index.n_cells:
                    delegate_ks.append(k)
                    continue
                self._build_call(b_pad, k_pad, eff, pin=True)
                warmed.append((b_pad, k_pad))
        if self.index is None:
            warmed.extend(self._exact.prewarm(batch_sizes=batch_sizes, ks=ks))
        elif delegate_ks:
            warmed.extend(self._exact.prewarm(batch_sizes=batch_sizes,
                                              ks=tuple(delegate_ks)))
        return warmed

    def stats(self) -> dict:
        """Index/serving facts for /stats.json's ``retrieval`` block."""
        ix = self.index
        return {
            "mode": "exact_fallback" if ix is None else "ann",
            # the IVF scan is a plain XLA program on every backend; the
            # exact fallback scores like any DeviceRetriever
            "kernel": ("xla" if ix is not None
                       else _resolve_topk_mode(self._interpret)),
            "exactFallback": ix is None,
            "fallbackReason": self.fallback_reason,
            "nTotal": self.n_total,
            "cells": ix.n_cells if ix else 0,
            "cellLen": ix.cell_len if ix else 0,
            "nprobe": self.nprobe,
            "lastEffectiveNprobe": self.last_effective_nprobe,
            "quantize": ix.quantize if ix else None,
            "indexBuildSeconds": round(ix.build_seconds, 3) if ix else None,
            "minItems": self.min_items,
        }
