"""Blocked WALS matrix factorization on TPU.

The flagship algorithm: the TPU-native replacement for MLlib ALS, which the
reference's recommendation templates train via Spark (reference:
examples/scala-parallel-recommendation/custom-serving/src/main/scala/
ALSAlgorithm.scala:96-154 calling org.apache.spark.mllib.recommendation
.ALS.train; implicit variant examples/scala-parallel-similarproduct/multi/
src/main/scala/ALSAlgorithm.scala:130).

Design (ALX-style, arxiv 2112.02194 — see PAPERS.md):

- Ratings live as padded fixed-shape neighbor blocks in a PERMUTED
  two-sided layout (ops/neighbors.py build_bilinear_layout); no shuffles
  — layout is computed once and stays in HBM for every iteration.
- One half-step solves all users (then all items) with batched normal
  equations A_u = Σ_j v_j v_jᵀ (+ λ·n_u·I), b_u = Σ_j r_uj v_j, per
  degree tier: gramian einsums (lax.map over row blocks bounds peak
  memory), then a Jacobi-preconditioned batched CG whose matvec rides
  the VPU (see _spd_solve). Tier outputs CONCATENATE into the permuted
  factor array — the step contains zero scatters (measured ~3-12M
  rows/s on v5e vs ~470M rows/s for gathers).
- Rows heavier than ``chunk_cap`` ride a dedicated tier as balanced
  chunks whose partial equations segment-sum per owner row.
- Rows within a block shard over every mesh axis (data AND model — the
  gramian phase consumes replicated factors, so block work parallelizes
  over all devices); the opposite factor matrix is replicated (or
  row-sharded over ``model`` with ``model_sharded``, explicitly
  re-replicated once per half-step), so the only collective in the
  compiled step is the all-gather of freshly-updated factors between
  half-steps — that is the ICI traffic, replacing MLlib's factor-block
  shuffle (pinned by test_als.test_model_sharded_collective_inventory).
- Implicit feedback (Hu-Koren-Volinsky): per-entry confidence
  c = 1 + alpha·r with the VᵀV gramian trick; gramian is one einsum
  (psum'd over shards by XLA when V is sharded).

Regularization matches MLlib's ALS-WR: λ scaled by each row's degree in
explicit mode; plain λ in implicit mode.
"""

from __future__ import annotations

import dataclasses
import logging
import time

import numpy as np

from ..obs.metrics import METRICS
from ..obs.startup import STARTUP
from ..obs.trace import span
from ..obs.training import TRAINING
from ..ops.neighbors import build_bilinear_layout
from ..ops.retrieval import RetrievalServingMixin
from ..storage.bimap import BiMap
from ..storage.frame import Ratings
from ..faults import FAULTS

log = logging.getLogger("predictionio_tpu.als")

# ISSUE 5: per-iteration device time — the number ALX-style TPU ALS
# tuning is done against (arXiv:2112.02194)
_M_TRAIN_STEP = METRICS.histogram(
    "pio_train_step_seconds",
    "one ALS alternation (user+item half-steps), timed to the end of "
    "its device work; an attempt's first step includes the compile")
# ISSUE 15: one vmapped grid alternation — EVERY trial's user+item
# half-steps in a single compiled dispatch (workflow/tuning.py divides by
# the trial count for a per-trial figure)
_M_GRID_STEP = METRICS.histogram(
    "pio_tune_grid_step_seconds",
    "one multi-trial ALS grid alternation: all trials' user+item "
    "half-steps in one compiled program (train_als_grid)")

__all__ = ["ALSModel", "ALSConfig", "train_als", "train_als_grid"]

#: single source of truth for the CG inner-solver depth — ALSConfig, the
#: bench, and direct make_train_step/_half_step callers must agree, or an
#: accuracy gate could validate a different config than the timed one.
#: 8 Jacobi-preconditioned iterations replace the old 32 plain-CG ones:
#: CG re-reads the [N, R, R] gramians every iteration, a dominant HBM
#: term of a training step, so depth is the single biggest solver knob —
#: diagonal preconditioning buys the depth back (solver-parity tests and
#: the bench accuracy gate pin end-model quality). Implicit mode's
#: normal equations (dense VᵀV + plain-λ ridge) are worse conditioned
#: AND less diagonal — Jacobi helps less — so it runs deeper.
#: equation-concat budget for _solve_side: below this, all tiers' normal
#: equations concatenate into ONE batched solve (fewest launches); above
#: it, tiers solve one at a time so peak HBM is bounded by the largest
#: tier instead of [all rows, R, R] (at 2M users x rank 64 the concat is
#: a 16+ GB buffer — more than a v5e's whole HBM). Same math either way:
#: the batched CG is row-independent.
SOLVE_EQ_BUDGET_BYTES = 1024**3

DEFAULT_CG_ITERS = 8
#: warm-started explicit solves (the training sweep seeds each inner
#: solve with the row's previous factors, leaving CG only the sweep's
#: delta) converge in fewer iterations: measured on the bench accuracy
#: gate, warm depth 4 lands at noise distance from the exact solver
#: (gap 2.5e-06..4.3e-05 across seed pairs at ML-20M shape, vs 3.5e-05
#: at depth 5) and cuts half the solve phase's gramian re-read traffic
#: vs cold depth 8 (~2% on the full step vs depth 5). Depth 3 passes
#: the 1e-3 gate with only ~2x margin (4.8e-04) — too thin to ship.
#: Cold solves (no x0) keep DEFAULT_CG_ITERS.
DEFAULT_CG_ITERS_WARM = 4
DEFAULT_CG_ITERS_IMPLICIT = 16


def _resolve_cg_iters(cg_iters, implicit: bool, *, warm: bool = False) -> int:
    if cg_iters is not None:
        return cg_iters
    if implicit:
        # implicit normal equations are worse conditioned and less
        # diagonal (Jacobi helps less) — no measured warm shortcut
        return DEFAULT_CG_ITERS_IMPLICIT
    return DEFAULT_CG_ITERS_WARM if warm else DEFAULT_CG_ITERS


@dataclasses.dataclass(frozen=True)
class ALSConfig:
    rank: int = 32
    iterations: int = 10
    lambda_: float = 0.1
    implicit_prefs: bool = False
    alpha: float = 1.0  # implicit confidence scale
    #: degree tiers of the bucketed layout. "auto" (default) computes
    #: histogram-optimal edges (ops/neighbors.py optimal_tiers) — zero
    #: dropped entries, ~5-15% padding; an explicit tuple is auto-extended
    #: to the observed max so it is lossless too
    tiers: tuple | str = "auto"
    #: per-block gather budget in elements (B*D cap) — bounds peak memory
    gather_budget: int = 2_000_000
    #: rows heavier than this split into balanced chunks riding a
    #: dedicated tier, partial normal equations segment-summed per owner
    #: (ops/neighbors.py build_bilinear_layout)
    chunk_cap: int = 2048
    #: "bfloat16" halves the HBM traffic of the factor gather and runs the
    #: gramian einsums at MXU bf16 rate (f32 accumulation; the normal-
    #: equation solve stays f32). "float32" is bit-stable default.
    compute_dtype: str = "float32"
    #: normal-equation solver: "cg" (batched conjugate gradient — fully
    #: vectorized, ~10x faster than factorizations on TPU where batched
    #: small-matrix LU/cholesky serialize), "cholesky", or "lu"
    solver: str = "cg"
    #: CG iteration count. CG here is an inexact inner solver (classic
    #: inexact-ALS): per-solve residuals land around 1e-3..1e-5 depending
    #: on conditioning, which is below the movement of an ALS sweep, and
    #: the alternation self-corrects across iterations — final model
    #: quality matches the exact solvers (see test_als solver parity).
    #: None = auto (DEFAULT_CG_ITERS explicit / _IMPLICIT implicit).
    #: Raise for small-λ / ill-conditioned setups, or set solver="cholesky".
    cg_iters: int | None = None
    #: shard the factor matrices' rows over the mesh's ``model`` axis
    #: (tensor-parallel factors, ALX-style). Requires a mesh with a
    #: ``model`` axis; silently equivalent to replicated when that axis
    #: has size 1. The math is identical — XLA inserts the all-gathers the
    #: cross-shard factor gathers need.
    model_sharded: bool = False
    seed: int = 7


@dataclasses.dataclass
class ALSModel(RetrievalServingMixin):
    """Trained factors + id maps. Arrays are host numpy (device-independent
    for checkpointing); ``scores_for_user`` & co. jit on demand."""

    user_factors: np.ndarray  # [num_users, rank] f32
    item_factors: np.ndarray  # [num_items, rank] f32
    user_ids: BiMap  # str -> row
    item_ids: BiMap  # str -> row
    config: ALSConfig

    def __setattr__(self, name, value):
        # _vtv_cache/_cn_cache are derived from item_factors; replacing
        # the factors (reload/restore paths) must drop them or fold-in
        # keeps solving against the OLD VᵀV. In-place mutation
        # (item_factors[:] = ...) bypasses this — call
        # invalidate_item_caches() explicitly there.
        super().__setattr__(name, value)
        if name == "item_factors":
            self.__dict__.pop("_vtv_cache", None)
            self.__dict__.pop("_cn_cache", None)

    def invalidate_item_caches(self) -> None:
        """Drop every cache derived from ``item_factors`` (the implicit
        VᵀV term and the normalized catalog). Assigning a new
        ``item_factors`` array does this automatically; call this after
        mutating the array IN PLACE."""
        self.__dict__.pop("_vtv_cache", None)
        self.__dict__.pop("_cn_cache", None)

    # -- serving-side scoring (CreateServer hot path) ----------------------
    def scores_for_user(self, user_id: str) -> np.ndarray | None:
        row = self.user_ids.get(user_id)
        if row is None:
            return None
        return self.item_factors @ self.user_factors[row]

    def recommend_products(self, user_id: str, num: int) -> list[tuple[str, float]]:
        """Top-N items for a user (reference ALSModel.recommendProducts,
        examples/.../ALSModel.scala:200-219)."""
        row = self.user_ids.get(user_id)
        if row is None:
            return []
        return self.top_n_from_catalog(self.user_factors[row], num)

    def _normalized_catalog(self) -> np.ndarray:
        """Row-normalized item factors, computed once (immutable after
        training; a masked micro-batch would otherwise re-normalize the
        whole catalog per query). Stripped from MODELDATA blobs by the
        mixin __getstate__."""
        cn = getattr(self, "_cn_cache", None)
        if cn is None:
            from ..ops.retrieval import row_normalize

            cn = row_normalize(self.item_factors)
            self._cn_cache = cn
        return cn

    def batch_similar_items(self, queries) -> list:
        """Batched ``similar_items`` for a whole micro-batch — see
        ``_batch_similar_items``."""
        return _batch_similar_items(self, queries)

    def fold_in_user(self, item_ids: list, ratings=None) -> "np.ndarray | None":
        """Exact WALS fold-in: solve one user's normal equations against
        the trained item factors — the factor vector training WOULD have
        produced for a user with these events, without retraining.

        Serves users who appeared after training. The reference's
        predictNewUser (examples/scala-parallel-ecommercerecommendation/
        train-with-rate-event/src/main/scala/ALSAlgorithm.scala:285+)
        averages the recent items' factors; this is the exact half-step
        solve instead (same formulation as training: ALS-WR λ·max(n,1)
        ridge in explicit mode, the Hu-Koren-Volinsky VᵀV + confidence
        form in implicit mode). One R×R host solve — serving-cheap.

        ``ratings``: per-item values aligned with ``item_ids`` (explicit
        ratings, or implicit confidence inputs); defaults to 1.0 each.
        Unknown item ids are skipped; returns None if none are known.
        """
        prep = self._fold_in_prep(item_ids, ratings)
        if prep is None:
            return None
        a, b = self._fold_in_equations(*prep)
        return np.linalg.solve(a, b).astype(np.float32)

    def _fold_in_lookup(self, item_ids) -> tuple[np.ndarray, np.ndarray]:
        """One vectorized id→row pass (``BiMap.map_array``) — shared by
        the single and batched fold-in paths. Returns ``(rows, kept)``:
        ``kept`` is the boolean keep-mask over ``item_ids`` and ``rows``
        the factor rows of the kept (known) ids."""
        idx = self.item_ids.map_array(list(item_ids), default=-1)
        kept = idx >= 0
        return idx[kept], kept

    def _fold_in_prep(self, item_ids, ratings):
        """(rows, r) of the known items in float64, or None when no item
        is known — the normal-equation inputs of one user's fold-in."""
        rows, kept = self._fold_in_lookup(item_ids)
        if rows.size == 0:
            return None
        if ratings is None:
            r = np.ones(rows.size, np.float64)
        else:
            r = np.asarray([float(x) for x in ratings], np.float64)[kept]
        return rows, r

    def _vtv(self) -> np.ndarray:
        """The implicit-mode VᵀV term, cached (depends only on the item
        factors; dropped by ``invalidate_item_caches`` / item-factor
        replacement, and stripped from MODELDATA blobs by the mixin
        ``__getstate__``)."""
        vtv = getattr(self, "_vtv_cache", None)
        if vtv is None:
            v_all = self.item_factors.astype(np.float64)
            vtv = v_all.T @ v_all
            self._vtv_cache = vtv
        return vtv

    def _fold_in_equations(self, rows: np.ndarray, r: np.ndarray):
        """One user's regularized normal equations (a, b) in float64 —
        the exact system ``fold_in_user`` has always solved, factored
        out so the batched kernel stacks the IDENTICAL matrices."""
        v_s = self.item_factors[rows].astype(np.float64)  # [k, R]
        lam = self.config.lambda_
        rank = v_s.shape[1]
        eye = np.eye(rank)
        if self.config.implicit_prefs:
            alpha = self.config.alpha
            a = self._vtv() + (v_s * (alpha * r)[:, None]).T @ v_s + lam * eye
            b = ((1.0 + alpha * r)[:, None] * v_s).sum(axis=0)
        else:
            a = v_s.T @ v_s + lam * max(len(rows), 1) * eye
            b = (r[:, None] * v_s).sum(axis=0)
        return a, b

    def fold_in_users(self, batch, solver: str = "host"):
        """Batched fold-in: ``batch = [(item_ids, ratings|None), ...]``
        over B users in one call (the streaming updater's kernel —
        ISSUE 10). Returns ``(factors, kept_users)``: ``kept_users`` is
        a boolean [B] mask of users with at least one known item;
        ``factors`` is ``[kept_users.sum(), R]`` float32, rows aligned
        with the surviving users in order.

        ``solver="host"`` (default): per-user float64 normal equations
        stacked into ONE batched LAPACK solve — bitwise identical to B
        independent ``fold_in_user`` calls (the gufunc loops the same
        dgesv over each matrix), so this is the publish/reference path.
        ``solver="device"``: one jitted dispatch — padded [B, D] gather
        → ``_gram_blocks`` → batched Cholesky (``_spd_solve``) in f32,
        for refreshing hundreds of users per dispatch; matches host to
        f32 tolerance, not bitwise.
        """
        prep: list = []
        kept_users = np.zeros(len(batch), bool)
        for u, (iids, ratings) in enumerate(batch):
            p = self._fold_in_prep(iids, ratings)
            if p is None:
                continue
            kept_users[u] = True
            prep.append(p)
        rank = self.config.rank
        if not prep:
            return np.zeros((0, rank), np.float32), kept_users
        if solver == "device":
            return self._fold_in_users_device(prep), kept_users
        nb = len(prep)
        a = np.empty((nb, rank, rank), np.float64)
        b = np.empty((nb, rank), np.float64)
        for i, (rows, r) in enumerate(prep):
            a[i], b[i] = self._fold_in_equations(rows, r)
        x = np.linalg.solve(a, b[..., None]).squeeze(-1)
        return x.astype(np.float32), kept_users

    def _fold_in_users_device(self, prep) -> np.ndarray:
        """The jitted one-dispatch path: pad each user's (rows, vals) to
        a shared power-of-two depth D (padded slots: id 0 / val 0 — the
        ``_gram_blocks`` masked convention), gather + Gram + batched
        Cholesky compiled once per (B_pad, D, rank, mode) shape."""
        import jax.numpy as jnp

        cfg = self.config
        depth = max(int(rows.size) for rows, _ in prep)
        d_pad = 1 << max(3, (depth - 1).bit_length())
        b_pad = 1 << max(0, (len(prep) - 1).bit_length())
        ids = np.zeros((b_pad, d_pad), np.int32)
        vals = np.zeros((b_pad, d_pad), np.float32)
        for i, (rows, r) in enumerate(prep):
            ids[i, :rows.size] = rows
            # a genuine 0.0 rating must stay a VALID slot: vals==0 is
            # the padding mask, so nudge it (the layout builder's own
            # convention, ops/neighbors.py)
            vf = r.astype(np.float32)
            vf[vf == 0.0] = 1e-30
            vals[i, :rows.size] = vf
        run = _fold_in_program(cfg.rank, cfg.implicit_prefs,
                               float(cfg.alpha), float(cfg.lambda_),
                               b_pad, d_pad, self.item_factors.shape[0])
        x = run(jnp.asarray(ids), jnp.asarray(vals),
                jnp.asarray(self.item_factors, jnp.float32))
        return np.asarray(x)[:len(prep)].astype(np.float32)

    def similar_items(self, item_rows: list[int], num: int,
                      candidate_mask: np.ndarray | None = None) -> list[tuple[int, float]]:
        """Cosine top-N against the whole catalog — the similarproduct
        template's scoring (examples/scala-parallel-similarproduct/multi/
        src/main/scala/ALSAlgorithm.scala:146-200) as one retrieval.

        With a similarity retriever attached (attach_similarity_retriever
        — the engine server does this at deploy) the unfiltered path runs
        the fused device top-k over the normalized catalog: aggregate
        cosine = one query with the summed normalized query vectors.
        Filtered queries (candidate_mask) fall back to the host matmul —
        a mask can exclude arbitrarily much, so over-fetching from the
        device result has no bound."""
        from ..ops.retrieval import row_normalize

        if not item_rows:
            return []
        if getattr(self, "_sim_retriever", None) is not None \
                and candidate_mask is None:
            # single home of the over-fetch/skip/trim dance: batch of one
            return _batch_similar_items(self, [(item_rows, num, None)])[0]
        qn = row_normalize(self.item_factors[item_rows])  # [k, R]
        cn = self._normalized_catalog()
        scores = (cn @ qn.T).sum(axis=1)  # aggregate cosine over query items
        scores[item_rows] = -np.inf  # exclude the query items themselves
        if candidate_mask is not None:
            scores = np.where(candidate_mask, scores, -np.inf)
        num = min(num, len(scores))
        top = np.argpartition(-scores, num - 1)[:num]
        top = top[np.argsort(-scores[top])]
        return [(int(i), float(scores[i])) for i in top if np.isfinite(scores[i])]


def _batch_similar_items(model: "ALSModel", queries) -> list:
    """Batched ``similar_items``: queries = [(item_rows, num, mask|None)].
    Unmasked queries ride ONE fused retrieval call (aggregate cosine =
    one [B, R] matrix of summed normalized query vectors — each query is
    one row); masked or retriever-less queries fall back to the single
    path. Same results as per-query ``similar_items`` (pinned by
    test_templates batch/single parity)."""
    from ..ops.retrieval import row_normalize

    out: list = [[] for _ in queries]
    sim = getattr(model, "_sim_retriever", None)
    device_js = [j for j, (rows, _num, m) in enumerate(queries)
                 if rows and m is None and sim is not None]
    device_set = set(device_js)
    for j, (rows, num, m) in enumerate(queries):
        if j in device_set or not rows:
            continue
        out[j] = model.similar_items(rows, num, candidate_mask=m)
    if device_js:
        qmat = np.stack([
            row_normalize(model.item_factors[queries[j][0]]).sum(0)
            for j in device_js])
        # enough to survive dropping each query's own items (a shared k
        # only over-fetches, which cannot change any query's top-num)
        kmax = max(min(queries[j][1] + len(queries[j][0]), sim.n_total)
                   for j in device_js)
        vals, idx = sim.topk(qmat, kmax)
        for pos, j in enumerate(device_js):
            rows, num, _m = queries[j]
            skip = set(int(r) for r in rows)
            res = [(int(i), float(v)) for v, i in zip(vals[pos], idx[pos])
                   if i >= 0 and int(i) not in skip]
            out[j] = res[:num]
    return out


def _run_fingerprint(ratings: Ratings, config: ALSConfig) -> int:
    """64-bit fingerprint of (ratings, config) gating checkpoint resume.
    crc32 runs at memory speed, so hashing 20M triples is negligible next
    to one training iteration."""
    import json
    import zlib

    cfg_d = dataclasses.asdict(config)
    # iterations excluded: continuing a crashed or shorter run to a larger
    # iteration target is legitimate resume (the `it <= iterations` check
    # handles checkpoints past the current target)
    cfg_d.pop("iterations", None)
    # model_sharded excluded: it changes array placement, not the math —
    # a replicated-run checkpoint is resumable under factor sharding and
    # vice versa
    cfg_d.pop("model_sharded", None)
    cfg_js = json.dumps(cfg_d, sort_keys=True, default=str)
    parts = (
        zlib.crc32(np.ascontiguousarray(ratings.user_indices).tobytes()),
        zlib.crc32(np.ascontiguousarray(ratings.item_indices).tobytes()),
        zlib.crc32(np.ascontiguousarray(ratings.ratings).tobytes()),
        zlib.crc32(cfg_js.encode()),
    )
    h = 0xCBF29CE484222325
    for p in parts:
        h = ((h ^ p) * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


# ---------------------------------------------------------------------------
# the pjit'd half-step
# ---------------------------------------------------------------------------

def _spd_solve(a, b, *, solver="cg", cg_iters=DEFAULT_CG_ITERS,
               matvec_dtype=None, shift=None, gram=None, diag=None,
               x0=None):
    """Batched SPD solve of (a + diag(shift) + gram) x = b, [B, R, R] x [B, R].

    ``a`` arrives UNREGULARIZED (and possibly bf16); the ridge lives in
    ``shift`` ([B] or scalar, the ALS-WR λ·n_u term) and ``gram`` ([R, R],
    the implicit-mode VᵀV term), applied EXACTLY in f32 inside the
    matvec — ap += shift·p (+ p@gram) — so quantizing a to bf16 never
    touches the conditioning-critical ridge. ``diag`` optionally supplies
    a's f32 diagonal (the gramian kernel emits it for free; extracting it
    from a afterwards is a strided read of the whole array).

    "cg": fixed-iteration conjugate gradient — every step is a batched
    matvec/axpy, fully vectorized on TPU. Measured ~10x faster than
    jnp.linalg.solve at B=16k, R=64 on v5e (batched small-matrix LU and
    cholesky factorizations serialize per row on the TPU; CG never
    factorizes). This is an INEXACT solve: depending on the ridge-set
    condition number, ``cg_iters`` iterations land residuals around
    1e-3..1e-5 — fine as the inner solver of an alternating sweep (the
    next half-step corrects), not as a general linear solver.
    "cholesky"/"lu": exact factorizations (cholesky ≈ 2x LU).

    ``x0`` WARM-STARTS the CG path (ignored by the exact solvers): ALS
    factors move less and less between sweeps, so seeding each inner
    solve with the row's previous factors leaves CG only the sweep's
    *delta* to resolve — measured on the bench gate, warm-started depth
    4 (DEFAULT_CG_ITERS_WARM, what the training sweep resolves to) lands
    at noise distance from the exact solver, cutting the solve phase's
    dominant gramian re-read traffic roughly in half vs cold depth 8 net
    of the one extra matvec the seed costs (initial residual
    r0 = b - A·x0). Depth ladder: see the DEFAULT_CG_ITERS_WARM comment.

    The CG path is JACOBI-PRECONDITIONED: z = r / diag(A). The ridge-set
    gramians' diagonals span the degree skew (λ·n_u ranges over 4 decades
    on zipf data), which is exactly the variation a diagonal scaling
    removes — measured, 10 preconditioned iterations match 32 plain ones
    on the solver-parity suite, a 3.2x cut of CG's gramian re-read
    traffic (the dominant HBM term of a training step).

    ``matvec_dtype=bfloat16`` runs the A·p matvec with a bf16 copy of A
    (f32 accumulation, f32 residual/search-vector updates): CG is HBM-
    bound on re-reading the [B, R, R] gramians every iteration, so this
    halves its traffic. The perturbed matvec only loosens the inner
    residual, which the next ALS half-step absorbs (bench accuracy gate
    pins the end-model quality).
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    rank = a.shape[-1]
    if shift is None:
        shift_b = jnp.zeros((), f32)
    else:
        shift_b = jnp.asarray(shift, f32)
        if shift_b.ndim == 1:
            shift_b = shift_b[:, None]  # [B, 1] broadcasting over R

    if solver in ("lu", "cholesky"):
        a_full = a.astype(f32)
        eye = jnp.eye(rank, dtype=f32)
        a_full = a_full + (shift_b[..., None] if shift_b.ndim else shift_b) * eye
        if gram is not None:
            a_full = a_full + gram.astype(f32)[None]
        if solver == "lu":
            return jnp.linalg.solve(a_full, b[..., None]).squeeze(-1)
        chol = jnp.linalg.cholesky(a_full)  # [B, R, R] lower
        y = jax.lax.linalg.triangular_solve(
            chol, b[..., None], left_side=True, lower=True)
        x = jax.lax.linalg.triangular_solve(
            chol, y, left_side=True, lower=True, transpose_a=True)
        return x.squeeze(-1)

    mdt = jnp.dtype(matvec_dtype) if matvec_dtype is not None else a.dtype
    a_m = a.astype(mdt)
    gram_f = gram.astype(f32) if gram is not None else None
    if diag is None:
        diag = jnp.diagonal(a, axis1=-2, axis2=-1).astype(f32)
    diag_eff = diag + shift_b
    if gram_f is not None:
        diag_eff = diag_eff + jnp.diagonal(gram_f)[None]
    # Jacobi preconditioner (SPD ⇒ diag > 0; the floor only guards
    # all-padding rows whose system is exactly 0·x = 0)
    dinv = 1.0 / jnp.maximum(diag_eff, 1e-30)

    def matvec(p):
        # matvec as broadcast-multiply + lane reduction, NOT einsum: a
        # batched [R, R] x [R] matvec is an N=1 matmul the MXU executes at
        # ~3x the wall time of the VPU doing the same reads (measured on
        # v5e; the op is HBM-bound on re-reading a_m either way)
        ap = (a_m.astype(f32) * p[:, None, :]).sum(-1)
        ap = ap + shift_b * p
        if gram_f is not None:
            ap = ap + p @ gram_f  # [B, R] x [R, R]: MXU-sized matmul
        return ap

    def body(_, carry):
        x, r, p, rz = carry
        ap = matvec(p)
        alpha = rz / jnp.maximum(jnp.einsum("br,br->b", p, ap), 1e-30)
        x = x + alpha[:, None] * p
        r = r - alpha[:, None] * ap
        z = r * dinv
        rz_new = jnp.einsum("br,br->b", r, z)
        p = z + (rz_new / jnp.maximum(rz, 1e-30))[:, None] * p
        return x, r, p, rz_new

    if x0 is None:
        x0 = jnp.zeros_like(b)
        r0 = b
    else:
        x0 = x0.astype(f32)
        r0 = b - matvec(x0)
    z0 = r0 * dinv
    rz0 = jnp.einsum("br,br->b", r0, z0)
    x, *_ = jax.lax.fori_loop(0, cg_iters, body, (x0, r0, z0, rz0))
    return x


def _gram_blocks(ids, vals, other_c, *, implicit, alpha, rank, masked=False,
                 out_dtype=None, with_diag=False, hot=None):
    """Partial normal equations for every block row, NO regularization.

    ids/vals: [NB, B, D]; other_c: [NO, R] already in compute dtype.
    ``hot`` = (slice [H, R] of other_c, ids [NB, B, Dh] local to it, vals
    [NB, B, Dh]) is a split bucket's hot part (ops/neighbors._split_hot):
    the block row's equations are the sum of both parts', each part
    gathered from its own table: a row from a table of up to 147,456
    rows costs a third of one from a larger table (the probe beside
    ``neighbors.GATHER_NS_BY_TABLE_ROWS``).
    Returns (a [NB, B, R, R] out_dtype (default f32), b [NB, B, R] f32,
    n [NB, B] f32[, d [NB, B, R] f32 — a's f32 diagonal, when
    ``with_diag``]). The cast and diagonal ride INSIDE the lax.map body:
    materializing f32 gramians and extracting the diagonal afterwards
    costs three extra HBM passes over the step's largest array (measured
    ~58ms/iter on the ML-20M user side).

    a/b are this block row's *contribution*: a chunked heavy row's pieces
    are segment-summed per owner by the caller (ops/neighbors.py
    chunk_cap), so Σ chunks reproduces the whole-row equations exactly.
    n counts valid entries (the ALS-WR λ·n_u term needs the total).

    Validity derives from ``vals != 0``: the layout (ops/neighbors.py)
    zeroes padded slots and nudges genuine zero ratings to 1e-30, so no
    separate mask array rides along. With ``masked=False`` (the permuted
    layout) padded ids point at a guaranteed-zero factor slot, so even
    the [B, D, R]-shaped mask MULTIPLY disappears — that multiply is a
    second full pass over the gathered factors that XLA cannot fuse into
    the gramian matmul's operand, ~40% of the phase's HBM traffic.
    ``masked=True`` is the standalone-blocks path (pad ids point at row
    0, a real row, so gathered garbage must be zeroed).

    With a bf16 ``other_c`` the [B, D, R] factor gather (the bandwidth-
    bound part) moves half the bytes; einsums accumulate in f32.
    """
    import jax
    import jax.numpy as jnp

    cdt = other_c.dtype
    f32 = jnp.float32
    odt = out_dtype or f32
    eye = jnp.eye(rank, dtype=f32)

    def part(table, b_ids, b_vals):
        valid = b_vals != 0  # [B, D] — padded slots are exactly 0
        f = table[b_ids]  # [B, D, R] gather — bf16 halves this traffic
        if masked:
            f = f * valid.astype(cdt)[..., None]
        vals_f32 = b_vals.astype(f32)
        n = jnp.sum(valid, axis=1).astype(f32)
        if implicit:
            # confidence c = 1 + alpha*r; (c-1) is 0 at padded slots
            # already. The global VᵀV term is added ONCE per owner row by
            # the solver's `gram` shift, not per chunk.
            cw = (alpha * vals_f32).astype(cdt)
            a = jnp.einsum("bd,bdr,bds->brs", cw, f, f,
                           preferred_element_type=f32)
            b = jnp.einsum("bd,bdr->br",
                           ((1.0 + alpha * vals_f32)
                            * valid.astype(f32)).astype(cdt), f,
                           preferred_element_type=f32)
        else:
            a = jnp.einsum("bdr,bds->brs", f, f, preferred_element_type=f32)
            b = jnp.einsum("bd,bdr->br", b_vals.astype(cdt), f,
                           preferred_element_type=f32)
        return a, b, n

    def gram_block(blk):
        if hot is not None:
            # the hot part FIRST: only then does the compiler keep the
            # slice in VMEM across the loop (its operand of the gather
            # reads `S(1)`; tests/test_tpu_compile.py pins it), which is
            # what makes its rows cheap. Two einsums summed, not one over
            # the concatenated gathers: the probe read the sum faster at
            # every shape it tried
            a, b, n = part(hot[0], blk[2], blk[3])
            a_c, b_c, n_c = part(other_c, blk[0], blk[1])
            a, b, n = a + a_c, b + b_c, n + n_c
        else:
            a, b, n = part(other_c, blk[0], blk[1])
        out = (a.astype(odt), b, n)
        if with_diag:
            out = out + ((a * eye[None]).sum(-1),)
        return out

    xs = (ids, vals) if hot is None else (ids, vals, hot[1], hot[2])
    return jax.lax.map(gram_block, xs)


# NOTE on a road not taken: a fused Pallas gramian kernel (per-row
# [D,R]ᵀ[D,R] dots over the gathered factors) was prototyped and measured
# SLOWER than XLA's batched einsum on v5e (16.5ms vs 7.5ms per
# [8192,176,64] block — Mosaic serializes the per-row MXU dots, and
# dot_general with batch dims hits a lowering bug in this jaxlib), and
# Mosaic's dynamic-gather lowering cannot express the [NO,R] row gather
# at all. The einsum path IS the fast path; the step's floor is the XLA
# gather itself, and its cost is per gathered ROW, not per byte or tile:
# 10-12 ns a row from a table of 163,840 rows or more, 4 ns from a
# smaller one, whatever the ids (the probe of PR 40 beside
# ops/neighbors.GATHER_NS_BY_TABLE_ROWS). Hence the hot slice.


def _ridge(other_c, n, *, lambda_, implicit):
    """(shift, gram) regularization pair for _spd_solve: ALS-WR
    λ·max(n,1) diagonal shift in explicit mode; the Hu-Koren-Volinsky
    VᵀV gramian + plain-λ shift in implicit mode."""
    import jax.numpy as jnp

    if implicit:
        gram = jnp.einsum("dr,ds->rs", other_c, other_c,
                          preferred_element_type=jnp.float32)  # VᵀV
        return lambda_, gram
    return lambda_ * jnp.maximum(n, 1.0), None


def _fold_in_program(rank: int, implicit: bool, alpha: float, lambda_: float,
                     b_pad: int, d_pad: int, n_items: int):
    """AOT-compiled batched fold-in: [B, D] gathered events →
    _gram_blocks → regularized batched Cholesky. Exact factorization,
    not CG — fold-in has no next half-step to absorb an inexact inner
    solve.

    Compiled through the shared ``ExecutableCache`` (key namespace
    ``"fold_in"``, fully shape-qualified) rather than a private jit
    cache: a long-lived streaming updater then shares the serving
    executable budget AND every fold-in compile lands in the device
    ledger's HBM/compile accounting (ISSUE 12)."""
    from ..ops.retrieval import EXEC_CACHE

    key = ("fold_in", rank, implicit, alpha, lambda_, b_pad, d_pad, n_items)

    def build():
        import jax
        import jax.numpy as jnp

        def run(ids, vals, item_factors):
            a, b, n = _gram_blocks(ids[None], vals[None], item_factors,
                                   implicit=implicit, alpha=alpha, rank=rank,
                                   masked=True)
            nb = ids.shape[0]
            shift, gram = _ridge(item_factors, n.reshape(-1), lambda_=lambda_,
                                 implicit=implicit)
            return _spd_solve(a.reshape(nb, rank, rank),
                              b.reshape(nb, rank),
                              solver="cholesky", shift=shift, gram=gram)

        sds = jax.ShapeDtypeStruct
        return jax.jit(run).lower(
            sds((b_pad, d_pad), jnp.int32),
            sds((b_pad, d_pad), jnp.float32),
            sds((n_items, rank), jnp.float32),
        ).compile()

    return EXEC_CACHE.get_or_build(key, build)


def _half_step(ids, vals, other, *, lambda_, implicit, alpha, rank,
               compute_dtype="float32", solver="cg", cg_iters=None):
    """Solve all rows of one (un-chunked) block layout given the other
    side's factors — the self-contained single-shot path (graft entry,
    direct callers). ids/vals: [NB, B, D]; other: [NO, R] (replicated).
    Returns [NB, B, R] float32. The production training path goes through
    ``_solve_side`` instead, which accumulates gramians across buckets
    before one global solve."""
    import jax.numpy as jnp

    cdt = jnp.bfloat16 if compute_dtype == "bfloat16" else jnp.float32
    other_c = other.astype(cdt)
    cg_iters = _resolve_cg_iters(cg_iters, implicit)
    a, b, n = _gram_blocks(ids, vals, other_c, implicit=implicit,
                           alpha=alpha, rank=rank, masked=True)
    nb, blk = ids.shape[:2]
    shift, gram = _ridge(other_c, n.reshape(-1), lambda_=lambda_,
                         implicit=implicit)
    x = _spd_solve(a.reshape(nb * blk, rank, rank), b.reshape(nb * blk, rank),
                   solver=solver, cg_iters=cg_iters, matvec_dtype=cdt,
                   shift=shift, gram=gram)
    return x.reshape(nb, blk, rank)


def put_layout(layout, mesh, *, vals_dtype=None):
    """Device-put one side of the permuted layout: neighbor block rows
    (a split bucket's hot part with them) sharded over the data AND model
    axes combined, chunk segment ids replicated. No mask upload —
    validity is encoded in vals, and padded ids point at the other side's
    zero slot (ops/neighbors.py). ``vals_dtype=bfloat16`` halves the
    ratings' transfer + HBM footprint (exact for half-star ratings;
    otherwise a rounding the bf16 compute path would apply anyway).

    Under a multi-process mesh (``jax.process_count() > 1``) each process
    contributes only ITS device-local slice of every block via
    ``jax.make_array_from_process_local_data`` — the executor-side half of
    the Spark factor-block distribution this design replaces
    (reference examples/.../ALSModel.scala:172-179); the caller feeds each
    process the same (deterministically rebuilt) layout."""
    import jax

    blk, rep = _layout_shardings(mesh)
    multi = jax.process_count() > 1

    def put(arr, sharding):
        if not multi:
            return jax.device_put(arr, sharding)
        return jax.make_array_from_process_local_data(
            sharding, _process_local_slice(arr, sharding),
            global_shape=arr.shape)

    def cast(vals):
        if vals_dtype is None:
            return vals
        import ml_dtypes

        return vals.astype(ml_dtypes.bfloat16 if vals_dtype == "bfloat16"
                           else vals_dtype)

    out = []
    for b, m in zip(layout.buckets, layout.metas):
        vals = cast(b.vals)
        e = {"ids": put(b.ids, blk), "vals": put(vals, blk)}
        if b.hot_ids is not None:
            e["hot_ids"] = put(b.hot_ids, blk)
            e["hot_vals"] = put(cast(b.hot_vals), blk)
        if m.seg is not None:
            e["seg"] = put(m.seg, rep)
        out.append(e)
    return out


def _layout_shardings(mesh):
    """(sharding of a bucket's ids/vals blocks, sharding of its seg ids)
    — the one place that says how a layout lies on a mesh."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    # block rows shard over EVERY mesh axis, not just "data": the gramian
    # phase consumes replicated opposite factors, so its work parallelizes
    # over all devices regardless of how the factor MATRICES are sharded.
    # With only "data" here, a (4,2) data x model mesh would compute every
    # block twice (the model pair replicates the gather+einsum — seen as
    # 2x slower than 8x1 on a forced CPU mesh; not measured on the chip);
    # the model axis must carry block work too.
    row_axes = tuple(a for a in ("data", "model") if a in mesh.axis_names)
    return (NamedSharding(mesh, P(None, row_axes or None, None)),
            NamedSharding(mesh, P()))


def _process_local_slice(arr, sharding):
    """This process's contiguous slice of a host array for
    ``make_array_from_process_local_data`` (jax device order is
    process-major, so each process's shards are one contiguous range
    along every sharded dim; replicated dims pass through whole)."""
    import jax

    pid, pc = jax.process_index(), jax.process_count()
    out = arr
    for dim, part in enumerate(sharding.spec):
        if part is None:
            continue
        axes = part if isinstance(part, tuple) else (part,)
        axis_size = 1
        for a in axes:
            axis_size *= sharding.mesh.shape[a]
        if axis_size % pc or arr.shape[dim] % pc:
            raise ValueError(
                f"dim {dim} (axis {part!r}) does not split evenly over "
                f"{pc} processes: mesh axes {axis_size}, "
                f"dim size {arr.shape[dim]}")
        step = arr.shape[dim] // pc
        sl = [slice(None)] * arr.ndim
        sl[dim] = slice(pid * step, (pid + 1) * step)
        out = out[tuple(sl)]
    return out


def _host_global(arr):
    """Full host copy of a device array regardless of process topology:
    fully-addressable arrays (single process, or replicated factors)
    transfer directly; multi-process model-sharded arrays allgather their
    per-process shards first. Checkpoints and the final model need the
    TRUE global matrix — the sharded checkpointer then writes only this
    process's row slice of it."""
    if getattr(arr, "is_fully_addressable", True):
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


def _solve_side(buckets, layout, other, *, kw, x0=None):
    """One side's full half-step over the permuted layout:

    per tier, ``_gram_blocks`` computes each block row's partial normal
    equations (lax.map bounds peak memory) and the chunked tier segment-
    sums its pieces per owner; regularization and the compute-dtype cast
    fuse into each tier's einsum epilogue (the solver never touches an
    f32 gramian — at bf16 that halves CG's dominant re-read traffic);
    then the tiers' equations CONCATENATE and one batched PCG solves the
    whole side (piece-wise past the equation budget — see below),
    emitting factors already in permuted order — the step contains no
    scatter at all (a TPU scatter runs at ~3-12M rows/s; the concats are
    contiguous writes). Degree-0 rows and padding slots are the all-zero
    tail the layout reserves.

    ``buckets`` are the device dicts from ``put_layout``; ``layout`` the
    host ``SideLayout`` (static spans/segments metadata). ``x0`` is this
    side's PREVIOUS permuted factor array ([slots, R]) used to warm-start
    the CG solve — its first ``covered`` rows line up with the
    concatenated equations by construction (factors live in
    tier-concatenation order).

    Above ``SOLVE_EQ_BUDGET_BYTES`` of equations, the single global
    batched solve gives way to piece-wise solves (per tier, and within
    large tiers per block group) so peak HBM is bounded by the budget —
    the 100M-rating scale path; same math either way (CG is per-row)."""
    import jax
    import jax.numpy as jnp

    rank, implicit = kw["rank"], kw["implicit"]
    # the bf16 gramian quantization only pays for CG (halves its HBM
    # re-reads); the exact factorizations are chosen for precision, so
    # they always get f32 equations
    cdt = (jnp.bfloat16 if kw.get("compute_dtype") == "bfloat16"
           and kw.get("solver") == "cg" else jnp.float32)
    other_c = other.astype(cdt)
    f32 = jnp.float32

    order = []  # a zero that is known only once the last piece is solved

    def hot_part(b):
        """A split bucket's (slice of the other side's factors, ids,
        vals). The slice is taken anew for every `lax.map` (under
        model_sharded from the replicated factors, after the one
        all-gather), 38 MB copied: a buffer that lives from here to its
        loop's end the compiler places in VMEM, which is what makes a
        hot row cheap; one slice shared by a half-step's loops lives
        across all of them and stays in HBM for most of them, and so
        does a slice the scheduler is free to take while the loop
        before still holds its own (read off the step compiled at the
        train cell's shapes, `PERF.md` section 6, PR 40;
        tests/test_tpu_compile.py pins one loop's). So the start is the
        layout's plus ``order``'s zero, which the compiler cannot
        prove and cannot have before the piece before is solved."""
        if "hot_ids" not in b:
            return None
        start = layout.hot_lo + (order[-1] if order else 0)
        return (jax.lax.dynamic_slice_in_dim(other_c, start, layout.hot_rows),
                b["hot_ids"], b["hot_vals"])

    def tier_equations(b, m):
        """One tier's regularization-free normal equations
        (pa [span, R, R] cdt, pb [span, R] f32, pn [span] f32,
        pd [span, R] f32)."""
        chunked = m.seg is not None
        hot = hot_part(b)
        if chunked:
            # partial gramians stay f32 through the per-owner sums so the
            # chunk accumulation doesn't round at bf16
            pa, pb, pn = _gram_blocks(b["ids"], b["vals"], other_c,
                                      implicit=implicit, alpha=kw["alpha"],
                                      rank=rank, hot=hot)
            seg = b["seg"]
            pa = jax.ops.segment_sum(pa.reshape(-1, rank, rank), seg,
                                     num_segments=m.span,
                                     indices_are_sorted=True)
            pb = jax.ops.segment_sum(pb.reshape(-1, rank), seg,
                                     num_segments=m.span,
                                     indices_are_sorted=True)
            pn = jax.ops.segment_sum(pn.reshape(-1), seg,
                                     num_segments=m.span,
                                     indices_are_sorted=True)
            pd = jnp.diagonal(pa, axis1=-2, axis2=-1).astype(f32)
            pa = pa.astype(cdt)
        else:
            pa, pb, pn, pd = _gram_blocks(b["ids"], b["vals"], other_c,
                                          implicit=implicit, alpha=kw["alpha"],
                                          rank=rank, out_dtype=cdt,
                                          with_diag=True, hot=hot)
            pa = pa.reshape(-1, rank, rank)
            pb = pb.reshape(-1, rank)
            pn = pn.reshape(-1)
            pd = pd.reshape(-1, rank)
        return pa, pb, pn, pd

    def tier_solve(pa, pb, pn, pd, x0_t):
        shift, gram = _ridge(other_c, pn, lambda_=kw["lambda_"],
                             implicit=implicit)
        x = _spd_solve(pa, pb, solver=kw["solver"],
                       cg_iters=kw["cg_iters"], matvec_dtype=cdt,
                       shift=shift, gram=gram, diag=pd, x0=x0_t)
        if layout.hot_rows:
            # a row's count of entries is never negative
            x, zero = jax.lax.optimization_barrier(
                (x, jnp.minimum(pn[0].astype(jnp.int32), 0)))
            order.append(zero)
        return x

    covered = sum(m.span for m in layout.metas)
    eq_bytes = covered * rank * rank * jnp.dtype(cdt).itemsize
    cat = lambda xs: jnp.concatenate(xs) if len(xs) > 1 else xs[0]  # noqa: E731
    if eq_bytes <= SOLVE_EQ_BUDGET_BYTES:
        # one global batched solve over the concatenated equations (fewer
        # launches; the default path at ML-20M scale)
        eqs = [tier_equations(b, m) for b, m in zip(buckets, layout.metas)]
        a, bvec, n, d = (cat([e[i] for e in eqs]) for i in range(4))
        x = tier_solve(a, bvec, n, d,
                       None if x0 is None else x0[:covered])
    else:
        # PIECE-WISE solves: the [covered, R, R] equation concat would
        # exceed the budget (at 2M rows x rank 64 it is a 16+ GB buffer —
        # past a v5e's whole HBM). Regular tiers additionally split into
        # block groups of at most ``rows_budget`` rows (a single tier can
        # hold ~800k rows at 100M-rating scale — itself over budget once
        # CG's relayouted matvec copy of the equations is counted); each
        # piece's equations free right after its solve, bounding peak
        # memory by the budget. CG here is row-independent (per-row
        # alpha/beta, _spd_solve), so the split is mathematically
        # identical to the global batch. Chunked tiers stay whole — their
        # owner span is small by construction.
        itemsize = jnp.dtype(cdt).itemsize
        rows_budget = max(1, SOLVE_EQ_BUDGET_BYTES // (rank * rank * itemsize))
        xs = []
        off = 0
        for b, m in zip(buckets, layout.metas):
            if m.seg is not None:
                pa, pb, pn, pd = tier_equations(b, m)
                xs.append(tier_solve(
                    pa, pb, pn, pd,
                    None if x0 is None else x0[off:off + m.span]))
                off += m.span
                continue
            nb, blk = b["ids"].shape[:2]
            g = max(1, rows_budget // blk)  # blocks per solve group
            for s in range(0, nb, g):
                sub = {k: v[s:s + g] for k, v in b.items()}
                rows = int(sub["ids"].shape[0]) * blk
                pa, pb, pn, pd = tier_equations(sub, m)
                xs.append(tier_solve(
                    pa, pb, pn, pd,
                    None if x0 is None else x0[off:off + rows]))
                off += rows
        x = cat(xs)
    tail = layout.slots - covered
    if tail:
        x = jnp.concatenate([x, jnp.zeros((tail, rank), f32)])
    return x


def make_train_step(mesh, u_layout, i_layout, *, rank, lambda_=0.1,
                    implicit=False, alpha=1.0, model_sharded: bool = False,
                    compute_dtype: str = "float32", solver: str = "cg",
                    cg_iters: int | None = None):
    """One full ALS iteration (user half-step + item half-step) over the
    permuted two-sided layout as a single jitted function — the program
    ``tests/test_tpu_compile.py`` compiles for v5e, and the inner loop of
    ``train_als``.
    ``step(u_buckets, i_buckets, u_perm, v_perm) -> (u_perm, v_perm)``
    operates entirely in permuted slot space ([slots_u, R] / [slots_i, R]);
    the incoming factors seed the CG warm start (both are donated — each
    sweep's output reuses the previous sweep's buffers).

    ``model_sharded=True`` shards the factor matrices' rows over the mesh's
    ``model`` axis (tensor-parallel factors, ALX-style); the opposite
    factors are explicitly replicated once per half-step (one all-gather —
    see the ``step`` body comment). Neighbor blocks shard block rows over
    every mesh axis (``put_layout``), so the gramian phase parallelizes
    over all devices regardless of factor-matrix sharding.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    row_ax = "model" if model_sharded else None
    fac = NamedSharding(mesh, P(row_ax, None))
    rep = NamedSharding(mesh, P(None, None))
    warm = solver == "cg"
    kw = dict(lambda_=lambda_, implicit=implicit, alpha=alpha, rank=rank,
              compute_dtype=compute_dtype, solver=solver,
              cg_iters=_resolve_cg_iters(cg_iters, implicit, warm=warm))

    def step(u_buckets, i_buckets, u_prev, v):
        # Replicate the opposite factors ONCE per half-step (one
        # all-gather of [slots, R] — the module docstring's intended ICI
        # traffic). Without the explicit constraint GSPMD lowers every
        # per-tier row gather from the model-sharded operand as
        # mask+all-reduce over the GATHERED block — traffic proportional
        # to nnz_padded, per tier, inside lax.map (on a forced CPU mesh
        # the 4x2 data x model mesh ran SLOWER than 8x1 data-only; not
        # measured on the chip; the HLO collective-inventory test in
        # test_als.py pins the lowering).
        v_full = jax.lax.with_sharding_constraint(v, rep) if model_sharded else v
        u = _solve_side(u_buckets, u_layout, v_full, kw=kw,
                        x0=u_prev if warm else None)
        u = jax.lax.with_sharding_constraint(u, fac)
        u_full = jax.lax.with_sharding_constraint(u, rep) if model_sharded else u
        v_new = _solve_side(i_buckets, i_layout, u_full, kw=kw,
                            x0=v if warm else None)
        return u, v_new

    return jax.jit(step, out_shardings=(fac, fac), donate_argnums=(2, 3))


class _ConvergenceSampler:
    """Sampled-holdout convergence probe for the training loop
    (ISSUE 12): a fixed seeded sample of <=512 rating triples, scored
    against the live factor matrices each iteration — sampled RMSE plus
    the relative user-factor delta norm, streamed into ``TRAINING``.
    Factors live in PERMUTED slot order during training, so true rows
    map through ``SideLayout.pos`` once at construction; the per-
    iteration cost is one [S, R] gather per side (S <= 512), far below
    the half-steps it measures. Pure telemetry: any failure disables
    the probe, never the run."""

    SAMPLE = 512

    def __init__(self, ratings: Ratings, config: ALSConfig, u_lay, i_lay):
        self.ok = False
        self._prev = None
        try:
            n = int(len(ratings.ratings))
            take = min(self.SAMPLE, n)
            if take == 0:
                return
            rng = np.random.default_rng((config.seed or 0) ^ 0x5EED)
            sel = rng.choice(n, size=take, replace=False)
            self.u_slots = np.asarray(u_lay.pos)[
                np.asarray(ratings.user_indices)[sel]]
            self.i_slots = np.asarray(i_lay.pos)[
                np.asarray(ratings.item_indices)[sel]]
            self.r = np.asarray(ratings.ratings)[sel].astype(np.float32)
            self.ok = True
        except Exception:
            self.ok = False

    def observe(self, it: int, u, v, step_seconds: float) -> None:
        loss = delta = None
        if self.ok:
            try:
                uu = np.asarray(u[self.u_slots], np.float32)
                vv = np.asarray(v[self.i_slots], np.float32)
                pred = (uu * vv).sum(axis=1)
                loss = float(np.sqrt(np.mean((pred - self.r) ** 2)))
                if self._prev is not None:
                    delta = float(
                        np.linalg.norm(uu - self._prev)
                        / (np.linalg.norm(self._prev) + 1e-12))
                self._prev = uu
            except Exception:
                loss = delta = None
        TRAINING.observe("train", it, loss=loss, delta_norm=delta,
                         step_seconds=step_seconds)


#: where each phase of ``train_als`` leaves its seconds in the attempt's
#: record (``EngineInstance.convergence``); the steps and the probe leave
#: theirs through ``TRAINING.observe``
_PHASE_KEYS = {
    "train.als.layout": "layoutSeconds",
    "train.als.layout.plan": "layoutPlanSeconds",
    "train.als.layout.user": "layoutUserSeconds",
    "train.als.layout.item": "layoutItemSeconds",
    "train.als.upload": "uploadSeconds",
    "train.als.init_factors": "initSeconds",
    "train.als.final_pull": "finalPullSeconds",
}


def _note_phase(name: str, t0: float, t1: float) -> None:
    """Span sink of ``train_als``'s phases."""
    key = _PHASE_KEYS.get(name)
    if key is not None:
        TRAINING.note("train", **{key: t1 - t0})


def train_als(ratings: Ratings, config: ALSConfig, mesh=None, *,
              checkpointer=None, checkpoint_every: int = 0) -> ALSModel:
    """Alternate user/item half-steps for ``config.iterations`` rounds.

    With a ``TrainCheckpointer`` and ``checkpoint_every > 0``, the
    item-factor matrix + iteration counter snapshot every k iterations and
    a rerun with the same checkpoint directory resumes from the latest
    step — mid-training resume the reference lacks (its only persistence
    is the finished model, CoreWorkflow.scala:69-74)."""
    import jax
    import jax.numpy as jnp

    if mesh is None:
        from ..parallel.mesh import make_mesh

        mesh = make_mesh()

    nu, ni = ratings.num_users, ratings.num_items
    if nu == 0 or ni == 0:
        raise ValueError("empty ratings: no users or items")
    rank = config.rank

    from jax.sharding import NamedSharding, PartitionSpec as P

    model_sharded = bool(config.model_sharded)
    if model_sharded and "model" not in mesh.axis_names:
        log.warning("model_sharded requested but mesh %s has no 'model' "
                    "axis; training with replicated factors", dict(mesh.shape))
        model_sharded = False

    TRAINING.begin("train", total_iterations=config.iterations)
    if STARTUP.process_to_device_seconds is not None:
        TRAINING.note("train", processToDeviceSeconds=
                      STARTUP.process_to_device_seconds)
    # the phases from here to the return are one chain of spans, each
    # starting on the clock reading that ended the one before (`t0=`), so
    # their seconds add up to the whole call with nothing between them
    with span("train.als.layout", sink=_note_phase) as sp:
        u_lay, i_lay = build_bilinear_layout(
            ratings.user_indices, ratings.item_indices, ratings.ratings,
            nu, ni, tiers=config.tiers, gather_budget=config.gather_budget,
            seed=config.seed, chunk_cap=config.chunk_cap,
            align=mesh.shape["model"] if model_sharded else 8,
            sink=_note_phase,
        )
        dropped = u_lay.dropped + i_lay.dropped
        if dropped:
            log.info("degree tiers dropped %d entries beyond the last tier",
                     dropped)
        # what the layout says an iteration gathers, padding included, and
        # how much of it from the two hot slices (0 rows: that table is
        # not sliced): how often the split engages, known before any step
        (u_rows, u_hot), (i_rows, i_hot) = u_lay.gather_rows, i_lay.gather_rows
        TRAINING.note(
            "train", gatherRowsPerIteration=u_rows + i_rows,
            hotGatherShare=100.0 * (u_hot + i_hot) / max(1, u_rows + i_rows),
            hotSliceRowsItems=u_lay.hot_rows, hotSliceRowsUsers=i_lay.hot_rows)
        # factor matrices live in PERMUTED slot order during training
        # (tier-concatenation order, SideLayout.pos maps true rows to
        # slots); slot counts are 8-aligned so rows shard evenly over the
        # model axis when tensor-parallel. Everything host-facing
        # (checkpoints, the final model) is unpermuted via pos.
        fac = NamedSharding(mesh, P("model" if model_sharded else None, None))
        vals_dtype = ("bfloat16" if config.compute_dtype == "bfloat16"
                      else None)
    with span("train.als.upload", sink=_note_phase, t0=sp.t1) as sp:
        u_bk = put_layout(u_lay, mesh, vals_dtype=vals_dtype)
        i_bk = put_layout(i_lay, mesh, vals_dtype=vals_dtype)
        jax.block_until_ready((u_bk, i_bk))
    # what each device holds once the layout is up: a layout that landed
    # whole on device 0 shows here, not in a slow step later (CPU
    # devices report no memory statistics)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in mesh.local_devices]
    TRAINING.note("train", deviceBytesInUse=in_use)
    if len(in_use) > 1:
        log.info("bytes in use per device after the layout upload: %s",
                 in_use)

    with span("train.als.init_factors", sink=_note_phase,
              t0=sp.t1) as sp:
        def _to_slots(host_arr, lay):
            """True-row-order host array -> permuted device layout (non-owner
            slots stay exactly zero: padded ids gather from them). This is
            where a restored GLOBAL checkpoint state — possibly reassembled
            from a different process count's shards — gets re-sliced for the
            CURRENT mesh: every process holds the same host array and
            contributes only its device-local slice under multi-process."""
            perm = np.zeros((lay.slots, rank), np.float32)
            perm[lay.pos] = np.asarray(host_arr)
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(
                    fac, _process_local_slice(perm, fac), global_shape=perm.shape)
            return jax.device_put(perm, fac)

        # run fingerprint: a checkpoint is only resumable for the exact same
        # ratings + config — resuming across changed data or hyperparameters
        # would silently return a model of the wrong run
        fp = _run_fingerprint(ratings, config)

        def _same_run(state) -> bool:
            v_arr, u_arr = state.get("v"), state.get("u")
            return (state.get("fp") is not None and int(state["fp"]) == fp
                    and v_arr is not None and u_arr is not None
                    and v_arr.shape == (ni, rank) and u_arr.shape == (nu, rank))

        saw_same_run = False

        def _resumable(state) -> bool:
            nonlocal saw_same_run
            if not _same_run(state):
                return False
            saw_same_run = True
            return int(state["it"]) <= config.iterations

        start_it = 0
        v = None
        u_restored = None
        if checkpointer is not None:
            restored = checkpointer.restore_first_valid(_resumable)
            if restored is not None:
                ck_step, state = restored
                start_it = int(state["it"])
                # checkpoints hold true-row-order arrays (resumable under any
                # mesh/layout); re-permute into this run's slot order
                v = _to_slots(state["v"], i_lay)
                u_restored = _to_slots(state["u"], u_lay)
                log.info("resuming ALS from checkpoint step %d (iter %d)",
                         ck_step, start_it)
            elif checkpointer.steps():
                if saw_same_run:
                    # same data+config, just trained past the current target:
                    # those checkpoints stay valid for a later higher-target
                    # run — keep them (retention only prunes steps <= the one
                    # being saved, so this run's fresh saves are safe)
                    log.warning(
                        "checkpoint steps exist beyond the current iteration "
                        "target (%d); keeping them and training fresh",
                        config.iterations)
                else:
                    # genuinely stale (data/config changed); purge or retention
                    # would prefer them over this run's fresh saves
                    log.warning("no resumable checkpoint (data/config changed); "
                                "clearing %d stale step(s) and starting fresh",
                                len(checkpointer.steps()))
                    checkpointer.clear()
        if v is None:
            key = jax.random.PRNGKey(config.seed)
            k_u, k_v = jax.random.split(key)
            # MLlib-style init: small positive factors (true rows only — the
            # layout's padding slots must stay exactly zero)
            v = _to_slots(
                np.abs(np.asarray(jax.random.normal(k_v, (ni, rank),
                                                    dtype=jnp.float32)))
                / np.sqrt(rank), i_lay)
            # the user side starts from the same init scheme purely as the
            # first sweep's CG warm-start seed (the first half-step solves u
            # from v, so u's init never enters the math beyond that seed).
            # Kept separate from u_restored: a seed is not a trained factor,
            # and the iterations==0 fallback below must not return it.
            u_seed = _to_slots(
                np.abs(np.asarray(jax.random.normal(k_u, (nu, rank),
                                                    dtype=jnp.float32)))
                / np.sqrt(rank), u_lay)
        else:
            u_seed = None

        # the warm-start depth (DEFAULT_CG_ITERS_WARM) presumes alternation
        # corrects the shallower inner solves — true from the accuracy-gated
        # 3-iteration config up; for 1-2 iteration runs the first sweep's
        # "warm" seed is still the random init and nothing corrects after it,
        # so those keep the cold depth
        cg_iters = config.cg_iters
        if (cg_iters is None and config.solver == "cg"
                and not config.implicit_prefs and config.iterations < 3):
            cg_iters = DEFAULT_CG_ITERS
        step = make_train_step(
            mesh, u_lay, i_lay, rank=rank, lambda_=config.lambda_,
            implicit=config.implicit_prefs, alpha=config.alpha,
            model_sharded=model_sharded,
            compute_dtype=config.compute_dtype, solver=config.solver,
            cg_iters=cg_iters,
        )
        u = None
        carry_u = u_restored if u_restored is not None else u_seed
        conv = _ConvergenceSampler(ratings, config, u_lay, i_lay)
    for it in range(start_it, config.iterations):
        # timed to the end of the device work (dispatch returns before
        # it); the sampler below pulls from u and v anyway, so waiting
        # here delays nothing. The first step of an attempt traces and
        # compiles (or reads the compilation cache) before it runs.
        with span("train.als.first_step" if it == start_it
                  else "train.als.step", t0=sp.t1, step=it) as sp:
            # chaos site: a preemption striking mid-training (arm with
            # after=N to let N iterations — and their checkpoints — land)
            FAULTS.fire("train.step")
            u, v = jax.block_until_ready(step(u_bk, i_bk, carry_u, v))
        step_s = sp.t1 - sp.t0
        _M_TRAIN_STEP.record(step_s)
        with span("train.als.observe", t0=sp.t1, iteration=it) as sp:
            conv.observe(it, u, v, step_s)
        carry_u = u
        done = it + 1
        if (checkpointer is not None and checkpoint_every > 0
                and (done % checkpoint_every == 0 or done == config.iterations)):
            # both sides: the final model pairs u_k (solved from v_{k-1})
            # with v_k, so v alone cannot reconstruct it exactly.
            # checkpoints hold true-row-order arrays — they must be
            # resumable under any mesh/layout permutation
            with span("train.als.checkpoint", t0=sp.t1,
                      step_done=done) as sp:
                checkpointer.save(done, {"u": _host_global(u)[u_lay.pos],
                                         "v": _host_global(v)[i_lay.pos],
                                         "it": np.int64(done),
                                         "fp": np.uint64(fp)})
    with span("train.als.final_pull", sink=_note_phase, t0=sp.t1):
        if u is None:
            # checkpoint was already at the final iteration
            u = u_restored if u_restored is not None else jax.jit(
                lambda bk, vv: _solve_side(bk, u_lay, vv, kw=dict(
                    lambda_=config.lambda_, implicit=config.implicit_prefs,
                    alpha=config.alpha, rank=rank,
                    compute_dtype=config.compute_dtype, solver=config.solver,
                    cg_iters=_resolve_cg_iters(
                        config.cg_iters, config.implicit_prefs))))(u_bk, v)
        u.block_until_ready()
        log.info("ALS done: %d iters, U %s, V %s", config.iterations,
                 (nu, rank), (ni, rank))
        return ALSModel(
            user_factors=_host_global(u)[u_lay.pos],
            item_factors=_host_global(v)[i_lay.pos],
            user_ids=ratings.user_ids,
            item_ids=ratings.item_ids,
            config=config,
        )


#: ALSConfig fields a grid must share — everything that shapes the layout,
#: the compiled program, or the init. Only rank/lambda_/alpha may vary
#: (rank via per-rank program groups; λ/α as vmapped trial-lane inputs).
_GRID_SHARED_FIELDS = ("iterations", "implicit_prefs", "tiers",
                       "gather_budget", "chunk_cap", "compute_dtype",
                       "solver", "cg_iters", "seed")


def train_als_grid(ratings: Ratings, configs, mesh=None, *,
                   observe=None) -> "list[ALSModel]":
    """Train a whole hyperparameter grid as ONE compiled program (ISSUE 15).

    The ALX lesson (arXiv:2112.02194) is that TPU ALS wins by keeping the
    chips saturated; a rank/λ/α sweep of dozens of SMALL independent
    trains is the many-small-problems version of that workload. Instead
    of a serial per-trial loop (one under-utilizing program per config,
    each re-paying layout + device upload + compile), this stacks the
    trials along a leading ``trial`` lane axis and runs every trial's
    user+item half-steps in a single jitted dispatch per iteration:

    - the permuted two-sided layout and neighbor buckets depend only on
      the DATA and the shared seed — built once, uploaded once
      (``put_layout`` block-row sharding over every mesh axis, exactly as
      the serial path), and closed over by every trial;
    - trials GROUP BY RANK (rank is a static shape); within a group the
      λ/α lanes ride ``jax.vmap`` over ``_solve_side`` — λ and α enter
      the math as traced per-lane scalars (the ridge shift and the
      implicit confidence scale), so one compiled program serves every
      lane. All rank groups' sweeps live in the SAME jitted step, so the
      whole grid is one dispatch per iteration;
    - per-lane init replicates ``train_als``'s exactly (same PRNGKey
      split, same abs/√rank scheme, same slot permutation — the seed is
      shared, so every lane of a rank group starts identically), CG warm
      starts carry per-lane previous factors, and factor buffers are
      donated across iterations — matching the serial step so per-trial
      factors come out bitwise-equal to individually-trained runs
      (pinned by test_tuning.py's parity test).

    ``configs`` may vary only ``rank``/``lambda_``/``alpha``; all other
    fields (and the seed) must match trial 0, and ``model_sharded`` grids
    are not supported — the grid IS the parallelism. No checkpointer:
    grids are short exploratory runs; per-trial failure isolation lives
    in ``workflow/tuning.py``.

    ``observe(trial_idx, it, loss, delta_norm, step_seconds)`` is called
    per trial per iteration with the sampled-holdout probe's RMSE/delta
    (``step_seconds`` is the WHOLE grid step — the caller owns per-trial
    attribution), feeding ConvergenceTracker ``tune:<trial>`` series.
    Returns one ``ALSModel`` per config, in input order.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    configs = list(configs)
    if not configs:
        raise ValueError("empty config grid")
    base = configs[0]
    for i, c in enumerate(configs):
        if c.model_sharded:
            raise ValueError(
                f"trial {i}: model_sharded is not supported in a grid "
                "(the trial axis is the parallelism)")
        for f in _GRID_SHARED_FIELDS:
            if getattr(c, f) != getattr(base, f):
                raise ValueError(
                    f"trial {i}: {f}={getattr(c, f)!r} differs from trial "
                    f"0's {getattr(base, f)!r}; a grid may vary only "
                    "rank/lambda_/alpha")
    if base.iterations < 1:
        raise ValueError("grid training needs iterations >= 1")

    if mesh is None:
        from ..parallel.mesh import make_mesh

        mesh = make_mesh()

    nu, ni = ratings.num_users, ratings.num_items
    if nu == 0 or ni == 0:
        raise ValueError("empty ratings: no users or items")

    # layout + buckets: identical to the serial path (they depend only on
    # data + seed, never on rank/λ/α) — built and uploaded ONCE for the
    # whole grid
    u_lay, i_lay = build_bilinear_layout(
        ratings.user_indices, ratings.item_indices, ratings.ratings, nu, ni,
        tiers=base.tiers, gather_budget=base.gather_budget,
        seed=base.seed, chunk_cap=base.chunk_cap, align=8,
    )
    dropped = u_lay.dropped + i_lay.dropped
    if dropped:
        log.info("degree tiers dropped %d entries beyond the last tier", dropped)
    vals_dtype = "bfloat16" if base.compute_dtype == "bfloat16" else None
    u_bk = put_layout(u_lay, mesh, vals_dtype=vals_dtype)
    i_bk = put_layout(i_lay, mesh, vals_dtype=vals_dtype)

    # rank groups in first-occurrence order, remembering each trial's
    # original index so results come back in input order
    by_rank: dict[int, list[int]] = {}
    for idx, c in enumerate(configs):
        by_rank.setdefault(c.rank, []).append(idx)
    groups = list(by_rank.items())  # [(rank, [trial_idx, ...]), ...]

    # init: the EXACT serial scheme — one PRNGKey split per grid (seed is
    # shared), per-rank normal draws, abs/√rank, permuted into slot order
    # with padding slots exactly zero — then stacked per lane (identical
    # lanes: the serial run at the same seed starts from the same init)
    key = jax.random.PRNGKey(base.seed)
    k_u, k_v = jax.random.split(key)

    def _perm_init(k, n_rows, lay, rank):
        host = (np.abs(np.asarray(jax.random.normal(
            k, (n_rows, rank), dtype=jnp.float32))) / np.sqrt(rank))
        perm = np.zeros((lay.slots, rank), np.float32)
        perm[lay.pos] = host
        return perm

    rep3 = NamedSharding(mesh, P(None, None, None))
    facs = []
    hypers = []
    for rank_g, idxs in groups:
        lanes = len(idxs)
        v0 = _perm_init(k_v, ni, i_lay, rank_g)
        u0 = _perm_init(k_u, nu, u_lay, rank_g)
        facs.append((
            jax.device_put(np.stack([u0] * lanes), rep3),
            jax.device_put(np.stack([v0] * lanes), rep3),
        ))
        hypers.append((
            jnp.asarray([configs[i].lambda_ for i in idxs], jnp.float32),
            jnp.asarray([configs[i].alpha for i in idxs], jnp.float32),
        ))
    facs, hypers = tuple(facs), tuple(hypers)

    # CG depth: replicate train_als's cold-depth override (short runs
    # never benefit from the warm shortcut), then make_train_step's
    # warm-aware resolution — the grid and the serial trial must compile
    # the same inner-solver depth or parity dies
    implicit = bool(base.implicit_prefs)
    warm = base.solver == "cg"
    cg_iters = base.cg_iters
    if (cg_iters is None and base.solver == "cg"
            and not implicit and base.iterations < 3):
        cg_iters = DEFAULT_CG_ITERS
    cg_resolved = _resolve_cg_iters(cg_iters, implicit, warm=warm)

    def grid_step(u_buckets, i_buckets, facs, hypers):
        out = []
        for (rank_g, _idxs), (u_prev, v), (lam, alp) in zip(
                groups, facs, hypers):

            def one(u_p, v_p, lam_t, alp_t, rank_g=rank_g):
                # the serial step body verbatim (make_train_step.step,
                # model_sharded=False) with λ/α as traced lane scalars
                kw = dict(lambda_=lam_t, implicit=implicit, alpha=alp_t,
                          rank=rank_g, compute_dtype=base.compute_dtype,
                          solver=base.solver, cg_iters=cg_resolved)
                u_new = _solve_side(u_buckets, u_lay, v_p, kw=kw,
                                    x0=u_p if warm else None)
                v_new = _solve_side(i_buckets, i_lay, u_new, kw=kw,
                                    x0=v_p if warm else None)
                return u_new, v_new

            out.append(jax.vmap(one)(u_prev, v, lam, alp))
        return tuple(out)

    step = jax.jit(
        grid_step,
        out_shardings=tuple((rep3, rep3) for _ in groups),
        donate_argnums=(2,))

    probe = (_ConvergenceSampler(ratings, base, u_lay, i_lay)
             if observe is not None else None)
    prev_uu: dict[int, np.ndarray] = {}
    n_trials = len(configs)
    log.info("ALS grid: %d trial(s) in %d rank group(s) %s, %d iters",
             n_trials, len(groups), [r for r, _ in groups], base.iterations)
    for it in range(base.iterations):
        t_step = time.perf_counter()
        facs = step(u_bk, i_bk, facs, hypers)
        step_s = time.perf_counter() - t_step
        _M_GRID_STEP.record(step_s)
        if observe is not None:
            for (_rank_g, idxs), (u_g, v_g) in zip(groups, facs):
                ug = vg = None
                if probe.ok:
                    try:
                        ug = np.asarray(u_g)[:, probe.u_slots, :]
                        vg = np.asarray(v_g)[:, probe.i_slots, :]
                    except Exception:
                        ug = vg = None
                for lane, idx in enumerate(idxs):
                    loss = delta = None
                    if ug is not None:
                        try:
                            uu, vv = ug[lane], vg[lane]
                            pred = (uu * vv).sum(axis=1)
                            loss = float(np.sqrt(np.mean(
                                (pred - probe.r) ** 2)))
                            p = prev_uu.get(idx)
                            if p is not None:
                                delta = float(
                                    np.linalg.norm(uu - p)
                                    / (np.linalg.norm(p) + 1e-12))
                            prev_uu[idx] = uu
                        except Exception:
                            loss = delta = None
                    observe(idx, it, loss, delta, step_s)
    jax.block_until_ready(facs)

    models: list[ALSModel | None] = [None] * n_trials
    for (_rank_g, idxs), (u_g, v_g) in zip(groups, facs):
        uh = _host_global(u_g)
        vh = _host_global(v_g)
        for lane, idx in enumerate(idxs):
            models[idx] = ALSModel(
                user_factors=uh[lane][u_lay.pos],
                item_factors=vh[lane][i_lay.pos],
                user_ids=ratings.user_ids,
                item_ids=ratings.item_ids,
                config=configs[idx],
            )
    return models
