"""The plain references the timed path is held to, in numpy alone.

Nothing here imports the program or takes anything it made: the served
factors are made again from the seed (benchmarks/lib/draw.py), the
trained model's ratings were set aside by the benchmark's own DataSource
before the program saw them.

Each reference can also be computed in the nearest precision below the
one the configuration states (bfloat16 products for float32): put in the
program's place, that is the control the comparison has to refuse.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import draw

#: Served scores must agree with `user @ items.T` in float32 to this
#: relative error. The kernel scores at Precision.HIGHEST (float32 rebuilt
#: from bf16 passes, about 1e-6 on a rank-64 sum); one bf16 pass is about
#: 4e-3 per product and lands near 1e-3 on the sum. PERF.md, section 2,
#: has the readings on the chip that the limit stands between.
SCORE_RTOL = 1e-4
#: threads of the catalog scan: each holds one block and its scores
SCAN_THREADS = 4


def to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    bits = np.ascontiguousarray(x, np.float32).view(np.uint32)
    rounded = bits + (np.uint32(0x7FFF) + ((bits >> np.uint32(16))
                                           & np.uint32(1)))
    return (rounded & np.uint32(0xFFFF0000)).view(np.float32)


def scan_catalog(seed: int, q: np.ndarray, n_items: int, rank: int, k: int,
                 at_ids: np.ndarray | None = None, *,
                 precision: str = "float32"):
    """One pass over the catalog, made again block by block and never held
    whole: the top-k of `q @ V.T` (ids [B, k], scores [B, k]) and, for
    `at_ids` [B, m], the score of each of those items for its query."""
    if precision == "bfloat16":
        q = to_bf16(q)
    elif precision != "float32":
        raise ValueError(precision)

    def block_scan(block: int):
        v = draw.factor_block(seed, draw.ITEM_SIDE, block, n_items, rank)
        if precision == "bfloat16":
            v = to_bf16(v)
        s = q @ v.T                                     # [B, rows] float32
        lo = block * draw.FACTOR_BLOCK
        kk = min(k, s.shape[1])
        part = np.argpartition(s, s.shape[1] - kk, axis=1)[:, -kk:]
        top = (part + lo, np.take_along_axis(s, part, axis=1))
        at = None
        if at_ids is not None:
            here = (at_ids >= lo) & (at_ids < lo + s.shape[1])
            b, j = np.nonzero(here)
            at = (b, j, s[b, at_ids[b, j] - lo])
        return top, at

    with ThreadPoolExecutor(SCAN_THREADS) as pool:
        parts = list(pool.map(block_scan, range(draw.n_blocks(n_items))))
    ids = np.concatenate([p[0][0] for p in parts], axis=1)
    scores = np.concatenate([p[0][1] for p in parts], axis=1)
    order = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    at_scores = None
    if at_ids is not None:
        at_scores = np.full(at_ids.shape, np.nan, np.float32)
        for _top, (b, j, val) in parts:
            at_scores[b, j] = val
    return (np.take_along_axis(ids, order, axis=1),
            np.take_along_axis(scores, order, axis=1), at_scores)


def check_served(seed: int, user_rows: np.ndarray, served: list[list[dict]],
                 n_users: int, n_items: int, rank: int, k: int) -> dict:
    """Served answers of known users against the float32 reference.

    Numbers compared (each beside its limit in the caller's print):
    `score_rel_err` - the worst relative gap between a served score and
    the reference's score of the same item; `wrong_ids` - served items
    that are not the reference's item at that place and whose reference
    score differs from it by more than the tolerance (a tie the reference
    cannot tell apart is no error); `short` - answers without k items.
    """
    short = sum(1 for s in served if len(s) != k)
    got_ids = np.full((len(served), k), -1, np.int64)
    got_scores = np.full((len(served), k), np.nan, np.float32)
    for b, answer in enumerate(served):
        for j, it in enumerate(answer[:k]):
            got_ids[b, j] = int(it["item"][1:])
            got_scores[b, j] = it["score"]
    valid = (got_ids >= 0) & (got_ids < n_items)
    q = draw.factor_rows(seed, draw.USER_SIDE, user_rows, n_users, rank)
    ref_ids, ref_scores, at_ref = scan_catalog(
        seed, q, n_items, rank, k, np.where(valid, got_ids, -1))
    rel = np.abs(got_scores - at_ref) / np.maximum(np.abs(at_ref), 1e-30)
    rel = np.where(valid, rel, 0.0)
    differ = valid & (got_ids != ref_ids)
    tol = SCORE_RTOL * np.abs(ref_scores)
    wrong = (differ & (np.abs(at_ref - ref_scores) > tol)) | ~valid
    return {"answers": len(served), "short": int(short),
            "score_rel_err": float(rel.max()) if rel.size else 0.0,
            "wrong_ids": int(wrong.sum()),
            "near_ties": int((differ & ~wrong).sum())}


def control_served(seed: int, user_rows: np.ndarray, n_users: int,
                   n_items: int, rank: int, k: int) -> list[list[dict]]:
    """What a server scoring with one bfloat16 pass would have answered:
    the reference in the lower precision, in the program's place."""
    q = draw.factor_rows(seed, draw.USER_SIDE, user_rows, n_users, rank)
    ids, scores, _at = scan_catalog(seed, q, n_items, rank, k,
                                    precision="bfloat16")
    return [[{"item": f"i{int(i)}", "score": float(s)}
             for i, s in zip(ir, sr)] for ir, sr in zip(ids, scores)]


# -- training ----------------------------------------------------------------

def als_item_residuals(user_factors: np.ndarray, item_factors: np.ndarray,
                       rows: np.ndarray, counts: np.ndarray,
                       users: np.ndarray, ratings: np.ndarray,
                       lambda_: float) -> np.ndarray:
    """For each sampled item row i with ratings r_ui from users u: the
    relative residual |A v_i - b| / |b| of the ALS-WR ridge equations
    A = sum_u x_u x_u^T + lambda * n_i * I, b = sum_u r_ui x_u, built in
    float64 from the persisted user factors x and solved for by the
    persisted item factor v_i. The last half-step of a run solves exactly
    these, so a sound solver leaves a small residual."""
    out = np.empty(len(rows), np.float64)
    start = 0
    for j, (row, n) in enumerate(zip(rows.tolist(), counts.tolist())):
        x = user_factors[users[start:start + n]].astype(np.float64)
        r = ratings[start:start + n].astype(np.float64)
        start += n
        a = x.T @ x + lambda_ * max(n, 1) * np.eye(x.shape[1])
        b = x.T @ r
        v = item_factors[row].astype(np.float64)
        out[j] = np.linalg.norm(a @ v - b) / max(np.linalg.norm(b), 1e-300)
    return out
