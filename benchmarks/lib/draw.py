"""Everything a run makes from `--seed`: factor matrices for the served
models and rating triples for the trained one. Drawn in fixed blocks,
each from its own generator, so that any block can be made again alone
(the reference does) and threads change nothing but the time."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: rows of one factor block; part of what a seed means, never change it
FACTOR_BLOCK = 1 << 18
#: ratings of one block of the rating draw; likewise
RATING_BLOCK = 1 << 24
THREADS = 8

USER_SIDE, ITEM_SIDE = 0, 1


def factor_block(seed: int, side: int, block: int, n_rows: int, rank: int
                 ) -> np.ndarray:
    """Rows [block*FACTOR_BLOCK, ...) of one side's factors: iid normal
    at 1/sqrt(rank), float32."""
    lo = block * FACTOR_BLOCK
    rows = min(FACTOR_BLOCK, n_rows - lo)
    rng = np.random.default_rng([seed, 0xFAC, side, block])
    out = rng.standard_normal((rows, rank), dtype=np.float32)
    out *= np.float32(1.0 / np.sqrt(rank))
    return out


def n_blocks(n_rows: int) -> int:
    return (n_rows + FACTOR_BLOCK - 1) // FACTOR_BLOCK


def factors(seed: int, side: int, n_rows: int, rank: int) -> np.ndarray:
    """One side's whole factor matrix, float32 [n_rows, rank]."""
    out = np.empty((n_rows, rank), np.float32)

    def fill(block: int) -> None:
        lo = block * FACTOR_BLOCK
        out[lo:lo + FACTOR_BLOCK] = factor_block(seed, side, block, n_rows,
                                                 rank)

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(n_blocks(n_rows))))
    return out


def factor_rows(seed: int, side: int, rows: np.ndarray, n_rows: int,
                rank: int) -> np.ndarray:
    """Only the given rows, by making again the blocks that hold them."""
    rows = np.asarray(rows, np.int64)
    out = np.empty((len(rows), rank), np.float32)
    for block in np.unique(rows // FACTOR_BLOCK):
        sel = np.flatnonzero(rows // FACTOR_BLOCK == block)
        blk = factor_block(seed, side, int(block), n_rows, rank)
        out[sel] = blk[rows[sel] - int(block) * FACTOR_BLOCK]
    return out


# -- ratings ---------------------------------------------------------------

def user_degrees(seed: int, n_users: int, n_ratings: int, sigma: float
                 ) -> np.ndarray:
    """Ratings per user: log-normal activity, every user at least one,
    summing to n_ratings exactly."""
    if n_ratings < n_users:
        raise ValueError("fewer ratings than users")
    rng = np.random.default_rng([seed, 0xDE6])
    w = np.exp(sigma * rng.standard_normal(n_users))
    target = w / w.sum() * (n_ratings - n_users)
    deg = 1 + np.floor(target).astype(np.int64)
    short = int(n_ratings - deg.sum())
    if short:
        frac = target - np.floor(target)
        deg[np.argpartition(-frac, short - 1)[:short]] += 1
    return deg


def item_popularity(n_items: int, exponent: float, top_share: float
                    ) -> np.ndarray:
    """Zipf popularity by rank with the head cut at the top item's share."""
    pop = 1.0 / np.arange(1, n_items + 1, dtype=np.float64) ** exponent
    pop /= pop.sum()
    for _ in range(8):  # the cut moves mass to the tail; settle it
        pop = np.minimum(pop, top_share)
        pop /= pop.sum()
    return pop


def alias_tables(p: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Vose's alias method: (accept probability, alias) per entry."""
    n = len(p)
    scaled = (p * n).tolist()
    prob = np.ones(n, np.float64)
    alias = np.arange(n, dtype=np.int32)
    small = [i for i, x in enumerate(scaled) if x < 1.0]
    large = [i for i, x in enumerate(scaled) if x >= 1.0]
    while small and large:
        s = small.pop()
        g = large.pop()
        prob[s] = scaled[s]
        alias[s] = g
        scaled[g] = scaled[g] + scaled[s] - 1.0
        (small if scaled[g] < 1.0 else large).append(g)
    return prob.astype(np.float32), alias


def draw_ratings(seed: int, n_users: int, n_items: int, n_ratings: int, *,
                 structure_seed: int, user_sigma: float,
                 item_exponent: float, item_top_share: float,
                 rating_max: int
                 ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(users int32, items int32, ratings float32), each [n_ratings].

    The data set's shape - how many ratings each user has, and which
    popularity rank each rating falls on - comes from `structure_seed`,
    the same for every run: the trainer's layout, its compiled shapes and
    the work of an iteration follow from the two degree histograms, and a
    seed must not change the work. `seed` says which user has which
    degree, which item holds which rank, and every rating's value.

    Users come grouped (a user's ratings are contiguous); ranks are iid
    from the cut Zipf; ratings are uniform integers 0..rating_max. One
    evenly spaced rating in every n_ratings // n_items is overwritten so
    that every item has at least one rating and the trained id space is
    exactly n_users x n_items."""
    if n_ratings < n_items:
        raise ValueError("fewer ratings than items")
    deg = user_degrees(structure_seed, n_users, n_ratings, user_sigma)
    deg = deg[np.random.default_rng([seed, 0xDE6]).permutation(n_users)]
    # np.repeat takes 5 s for 260 million; marks at the group starts and
    # one running sum take under one
    marks = np.zeros(n_ratings, np.int32)
    marks[np.cumsum(deg)[:-1]] = 1
    users = np.cumsum(marks, dtype=np.int32)
    del marks
    prob, alias = alias_tables(item_popularity(n_items, item_exponent,
                                               item_top_share))
    rng0 = np.random.default_rng([seed, 0x17E])
    ident = rng0.permutation(n_items).astype(np.int32)
    items = np.empty(n_ratings, np.int32)
    vals = np.empty(n_ratings, np.float32)

    def fill(block: int) -> None:
        lo = block * RATING_BLOCK
        m = min(RATING_BLOCK, n_ratings - lo)
        rng = np.random.default_rng([structure_seed, 0x8A7, block])
        idx = rng.integers(0, n_items, m, dtype=np.int32)
        take_alias = rng.random(m, dtype=np.float32) >= prob[idx]
        items[lo:lo + m] = ident[np.where(take_alias, alias[idx], idx)]
        rng = np.random.default_rng([seed, 0x7A1, block])
        vals[lo:lo + m] = rng.integers(0, rating_max + 1, m, dtype=np.uint8)

    blocks = (n_ratings + RATING_BLOCK - 1) // RATING_BLOCK
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(blocks)))
    stride = n_ratings // n_items
    items[np.arange(n_items, dtype=np.int64) * stride] = ident[
        np.random.default_rng([structure_seed, 0xC0F]).permutation(n_items)]
    return users, items, vals
