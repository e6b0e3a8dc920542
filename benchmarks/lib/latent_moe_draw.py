"""What an `axk1-seqrec` run makes from `--seed` beside what
lib/seq_draw.py already makes (the tables, the lengths of the histories,
the closed loop's plan: imported from there, not copied): the decoder's
unlike layers, row block by row block, in the program's PUBLIC layout,
and the histories as uint16. Nothing here imports the program; the
seeding child and the check child both draw from these functions.

The routers are iid like every other matrix (float32, as the program
keeps them): at these widths the hidden states a router sees carry 2.5%
of their energy in their common direction, and the held experts'
fullest reads 2.7-3.8 times their mean on the chip (PERF.md, PR 37); a
projection of each W_r off that direction, tried first, cost 66 s of
host forward for a fullest of 2.7 against 3.8 and is not made."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import latent_moe_reference as ref
from . import loadgen, seq_draw

#: where a layer's matrices stand in the seed (never renumbered)
MATRIX_IDS = {name: j for j, name in enumerate((
    "wq_a", "wq_b", "wkv_a", "wkv_b", "wo", "w_gate", "w_up", "w_down",
    "router", "experts_gate", "experts_up", "experts_down", "shared_gate",
    "shared_up", "shared_down"))}
LAYER_BASE = 2000        # beside seq_draw's EMBED, HEAD at 1000, 1001


#: rows of a matrix one job draws: every matrix is made in row blocks,
#: each from its own place in the seed, so that threads only change the
#: time (a dense layer's 7,168 x 18,432 matrix as one job would hold a
#: thread for seconds while the others wait)
BLOCK_ROWS = 512


def layer_jobs(seed: int, cfg: dict, i: int, out: dict) -> list:
    """Allocates held layer ``i``'s public weights into ``out`` (bfloat16
    matrices, float32 gains of 1, the router's matrix float32) and
    returns the jobs that fill the matrices, a row block each."""
    import ml_dtypes

    jobs = []
    for name, shape in ref.layer_shapes(cfg, i).items():
        if name in ref.NORMS:
            out[name] = np.ones(shape, np.float32)
            continue
        out[name] = np.empty(
            shape, np.float32 if name == "router" else ml_dtypes.bfloat16)
        experts = range(shape[0]) if len(shape) == 3 else (None,)
        for e in experts:
            target = out[name] if e is None else out[name][e]
            for lo in range(0, target.shape[0], BLOCK_ROWS):
                where = (LAYER_BASE + i, MATRIX_IDS[name],
                         0 if e is None else e + 1, lo)
                jobs.append((target, lo, where))
    return [(seed, *job) for job in jobs]


def run_job(job) -> None:
    seed, target, lo, where = job
    rows = min(BLOCK_ROWS, target.shape[0] - lo)
    target[lo:lo + rows] = seq_draw._normal(
        seed, *where, shape=(rows, target.shape[1]))


def layer_weights(seed: int, cfg: dict, i: int, pool=None) -> dict:
    """Held layer ``i``'s weights in the public layout, drawn in
    ``pool``'s threads (or a pool of its own)."""
    out: dict = {}
    jobs = layer_jobs(seed, cfg, i, out)
    if pool is not None:
        list(pool.map(run_job, jobs))
    else:
        with ThreadPoolExecutor(seq_draw.THREADS) as own:
            list(own.map(run_job, jobs))
    return out


def all_layers(seed: int, cfg: dict) -> dict:
    """{str(i): public weights} of every held layer, all their row
    blocks in one pool."""
    layers = {str(i): {} for i in range(cfg["num_hidden_layers"])}
    jobs = [job for i in range(cfg["num_hidden_layers"])
            for job in layer_jobs(seed, cfg, i, layers[str(i)])]
    with ThreadPoolExecutor(seq_draw.THREADS) as pool:
        list(pool.map(run_job, jobs))
    return layers


def histories(traffic: dict, seed: int, n_users: int, n_items: int,
              max_len: int, seconds: float) -> np.ndarray:
    """uint16 [n_users, max_len], left-padded with 0, item i stored as
    i + 1 (ids must fit 16 bits): every user's length is
    `seq_draw.history_lengths`' (the plan's lengths are the mix's), the
    items Zipf over a seeded permutation, made in blocks of users, a
    generator and a thread each (seq_draw.histories makes the same table
    as int32 in one piece: 2.1 GB and half a minute at these sizes)."""
    if n_items >= 1 << 16:
        raise ValueError("item ids do not fit 16 bits")
    h = traffic["history"]
    lengths = np.minimum(seq_draw.history_lengths(traffic, seed, n_users,
                                                  seconds), max_len)
    ident = (np.random.default_rng([seed, 0x415]).permutation(n_items)
             + 1).astype(np.uint16)
    out = np.zeros((n_users, max_len), np.uint16)
    block = 1024

    def fill(lo: int) -> None:
        own = lengths[lo:lo + block].astype(np.int64)
        total = int(own.sum())
        ranks = loadgen._zipf_ranks(
            np.random.default_rng([seed, 0x415, lo + 1]), total, n_items,
            float(h["item_zipf_exponent"]))
        # the flat place of every event in this block of rows: its row's
        # right end less what is still to come of that row
        ends = np.arange(1, len(own) + 1, dtype=np.int64) * max_len
        place = np.repeat(ends - np.cumsum(own), own) + np.arange(total)
        out[lo:lo + block].reshape(-1)[place] = ident[ranks]

    with ThreadPoolExecutor(seq_draw.THREADS) as pool:
        list(pool.map(fill, range(0, n_users, block)))
    return out
