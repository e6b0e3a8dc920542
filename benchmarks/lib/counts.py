"""Operations and bytes a kernel's work NEEDS, from its shapes alone.

These are the algorithm's needs, not what an implementation moves: a
catalog stored padded to 128 lanes still only needs its `dim` columns
read once, and a gramian that is written out and read back by the solver
needs neither of those passes. A share of the roofline computed from
these therefore cannot pass 100% unless the time leaves out work.
"""

from __future__ import annotations


def topk_counts(batch: int, n_items: int, dim: int, k: int) -> dict:
    """One fused top-k call: scores of `batch` queries against `n_items`
    catalog rows of `dim` float32, keeping k (value, index) pairs each."""
    flops = 2.0 * batch * n_items * dim
    nbytes = 4.0 * (n_items * dim       # the catalog, read once
                    + batch * dim       # the query rows
                    + batch * k * 2)    # values and indices out
    return {"flops": flops, "bytes": nbytes}


def als_iteration_counts(n_ratings: int, n_users: int, n_items: int,
                         rank: int, cg_iters: int) -> dict:
    """One full explicit-ALS iteration (user half, item half) at float32.

    Per half: for every rating one factor row of the other side is
    gathered (rank * 4 bytes) and its id and value read (8 bytes); the
    gramian takes 2*rank^2 and the right-hand side 2*rank operations per
    rating; each of the side's rows is solved by `cg_iters` matrix-vector
    products of 2*rank^2 operations and written once (rank * 4 bytes).
    """
    per_half_flops = n_ratings * (2.0 * rank * rank + 2.0 * rank)
    per_half_bytes = n_ratings * (4.0 * rank + 8.0)
    rows = n_users + n_items
    flops = 2 * per_half_flops + rows * cg_iters * 2.0 * rank * rank
    nbytes = 2 * per_half_bytes + rows * rank * 4.0
    return {"flops": flops, "bytes": nbytes}
