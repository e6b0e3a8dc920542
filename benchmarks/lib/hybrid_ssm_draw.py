"""What a `granite-4.0-h-micro-seqrec` run makes from `--seed` beside
what lib/seq_draw.py already makes (the tied table, the lengths of the
histories, the closed loop's plan: imported from there, not copied): the
decoder's layers in the program's PUBLIC layout (stacked by kind, in the
published order), the matrices iid normal at 0.02 in bfloat16, row block
by row block, what is no matrix as Mamba-2 initialises it (float32), and
the histories as int32 (ids up to 100,351 do not fit 16 bits). Nothing
here imports the program; the seeding child and the check child both
draw from these functions, layer by layer, so the reference sees the
weights the served model was given."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import hybrid_ssm_reference as ref
from . import loadgen, seq_draw

#: where a layer's leaves stand in the seed (never renumbered)
LEAF_IDS = {name: j for j, name in enumerate((
    "in_proj", "out_proj", "wq", "wk", "wv", "wo", "w_in", "w_out",
    "conv_w", "conv_b", "dt_bias", "A_log"))}
MATRICES = ref.MAMBA_MATRICES + ref.ATTENTION_MATRICES + ref.MLP_MATRICES
LAYER_BASE = 3000        # beside seq_draw's EMBED at 1000, latent's 2000
#: rows of a matrix one job draws, each from its own place in the seed,
#: so that threads only change the time
BLOCK_ROWS = 512


def vector(seed: int, cfg: dict, layer: int, name: str, shape) -> np.ndarray:
    """What is no matrix, float32: gains and the skip 1; A_log the log of
    uniform 1..16; dt_bias the inverse softplus of a time step
    log-uniform in 0.001..0.1; the convolution uniform in
    +-mamba_d_conv^-0.5."""
    if name in ("input_norm", "post_norm", "norm", "D"):
        return np.ones(shape, np.float32)
    rng = np.random.default_rng([seed, 0x0B10, LAYER_BASE + layer,
                                 LEAF_IDS[name]])
    if name == "A_log":
        return np.log(rng.uniform(1.0, 16.0, shape)).astype(np.float32)
    if name == "dt_bias":
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
        return (dt + np.log(-np.expm1(-dt))).astype(np.float32)
    bound = cfg["mamba_d_conv"] ** -0.5
    return rng.uniform(-bound, bound, shape).astype(np.float32)


def layer_jobs(seed: int, cfg: dict, layer: int, out: dict) -> list:
    """Fills layer ``layer``'s weights (mixer and MLP: one flat dict, as
    lib/hybrid_ssm_reference.layer_shapes names them) into ``out``,
    allocating what it does not hold yet (a caller may hand in views of
    a stack), and returns the jobs that fill the matrices, a row block
    each."""
    import ml_dtypes

    jobs = []
    kind = cfg["layer_types"][layer]
    for name, shape in ref.layer_shapes(cfg, kind).items():
        if name not in MATRICES:
            out.setdefault(name, np.empty(shape, np.float32))[...] = vector(
                seed, cfg, layer, name, shape)
            continue
        target = out.setdefault(name, np.empty(shape, ml_dtypes.bfloat16))
        jobs += [(seed, target, lo, (LAYER_BASE + layer, LEAF_IDS[name], lo))
                 for lo in range(0, shape[0], BLOCK_ROWS)]
    return jobs


def run_job(job) -> None:
    seed, target, lo, where = job
    rows = min(BLOCK_ROWS, target.shape[0] - lo)
    target[lo:lo + rows] = seq_draw._normal(
        seed, *where, shape=(rows, target.shape[1]))


def layer_weights(seed: int, cfg: dict, layer: int, pool=None) -> dict:
    """Layer ``layer``'s weights, drawn in ``pool``'s threads (or a pool
    of its own)."""
    out: dict = {}
    jobs = layer_jobs(seed, cfg, layer, out)
    if pool is not None:
        list(pool.map(run_job, jobs))
    else:
        with ThreadPoolExecutor(seq_draw.THREADS) as own:
            list(own.map(run_job, jobs))
    return out


def stacked_layers(seed: int, cfg: dict) -> dict:
    """{"mamba", "attention", "mlp"}: the program's public stacks, every
    layer drawn straight into its place in its stack (no second copy of
    6 GB), all row blocks in one pool."""
    import ml_dtypes

    kinds = cfg["layer_types"]
    mlp_names = ("post_norm",) + ref.MLP_MATRICES
    stacks: dict = {"mamba": {}, "attention": {}, "mlp": {}}
    for kind in ("mamba", "attention"):
        n = sum(1 for t in kinds if t == kind)
        for name, shape in ref.layer_shapes(cfg, kind).items():
            group, depth = (("mlp", len(kinds)) if name in mlp_names
                            else (kind, n))
            stacks[group].setdefault(name, np.empty(
                (depth, *shape),
                ml_dtypes.bfloat16 if name in MATRICES else np.float32))
    jobs, seen = [], {"mamba": 0, "attention": 0}
    for layer, kind in enumerate(kinds):
        views = {name: (stacks["mlp"][name][layer] if name in mlp_names
                        else stacks[kind][name][seen[kind]])
                 for name in ref.layer_shapes(cfg, kind)}
        seen[kind] += 1
        jobs += layer_jobs(seed, cfg, layer, views)
    with ThreadPoolExecutor(seq_draw.THREADS) as pool:
        list(pool.map(run_job, jobs))
    return stacks


def histories(traffic: dict, seed: int, n_users: int, n_items: int,
              max_len: int, seconds: float) -> np.ndarray:
    """int32 [n_users, max_len], left-padded with 0, item i stored as
    i + 1: every user's length is `seq_draw.history_lengths`' (the
    plan's lengths are the mix's), the items Zipf over a seeded
    permutation, made in blocks of users, a generator and a thread each
    (seq_draw.histories draws the same table's events in one piece and
    holds three arrays of their count beside it)."""
    h = traffic["history"]
    lengths = np.minimum(seq_draw.history_lengths(traffic, seed, n_users,
                                                  seconds), max_len)
    ident = (np.random.default_rng([seed, 0x415]).permutation(n_items)
             + 1).astype(np.int32)
    out = np.zeros((n_users, max_len), np.int32)
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
