"""Everything a sequence-serving run makes from `--seed`: the looped
decoder's weights, matrix by matrix, every user's history, and the plan
the closed loop sends. Nothing here imports the program; the seeding
child and the check child both draw from these functions, so the
reference sees the weights and the histories the served model was given.

What a seed may change and what it may not. lib/loadgen.py has every seed
send the same multiset of popularity ranks in another order, "so that a
seed changes which users are asked and never how much work is offered":
that holds where every row costs what every other row costs. Here a row
costs its history's length, a closed loop sends only the head of its
plan (some 2,500 requests of 132,000), and another order is another
2,500 lengths: 1.9% more or less work an answer (their deviation is
0.88 of their mean), which `served_qps` then reads as the server's. So
the ORDER of the ranks is the mix's too (`closed_plan`): the popularity
ranks in sending order and each rank's history LENGTH come from the
mix's `base_seed`, the same for every run, as als-kdd11's shape comes
from its `structure_seed`; `--seed` says which user holds which rank,
what the histories hold, and every weight. No length depends on where
the plan asks for it: the lengths are one draw of the issue's
log-normal, and the plan one draw of its Zipf."""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import loadgen

THREADS = 12
#: the layer's matrices as [rows key, columns key] of the model block
MATRICES = {
    "wq": ("hidden_size", "attn"), "wk": ("hidden_size", "attn"),
    "wv": ("hidden_size", "attn"), "wo": ("attn", "hidden_size"),
    "wg": ("hidden_size", "intermediate_size"),
    "wu": ("hidden_size", "intermediate_size"),
    "wd": ("intermediate_size", "hidden_size"),
}
NORMS = ("norm1", "norm2", "norm3", "norm4")
INIT_STD = 0.02
EMBED, HEAD, GATE = 1000, 1001, 1002   # the tables' places in the seed


def _normal(seed: int, *where: int, shape) -> np.ndarray:
    """iid normal at 0.02 in the bfloat16 the configuration stores
    (rounded to nearest even from the float32 draw)."""
    import ml_dtypes

    rng = np.random.default_rng([seed, 0x0B10, *where])
    out = rng.standard_normal(shape, dtype=np.float32)
    out *= np.float32(INIT_STD)
    return out.astype(ml_dtypes.bfloat16)


def matrix_shape(model: dict, name: str) -> tuple[int, int]:
    dims = dict(model, attn=model["num_attention_heads"] * model["head_dim"])
    rows, cols = MATRICES[name]
    return dims[rows], dims[cols]


def layer_weights(seed: int, model: dict, layer: int) -> dict:
    """One layer's weights: bfloat16 matrices, float32 gains of 1."""
    names = list(MATRICES)
    with ThreadPoolExecutor(len(names)) as pool:
        mats = list(pool.map(
            lambda j: _normal(seed, layer, j,
                              shape=matrix_shape(model, names[j])),
            range(len(names))))
    out = dict(zip(names, mats))
    out.update({k: np.ones(model["hidden_size"], np.float32) for k in NORMS})
    return out


def stacked_layers(seed: int, model: dict) -> dict:
    """All layers' weights stacked [layers, ...], as the program's
    parameter tree holds them: the same draws as `layer_weights`."""
    import ml_dtypes

    n = model["num_hidden_layers"]
    names = list(MATRICES)
    out = {k: np.empty((n, *matrix_shape(model, k)), ml_dtypes.bfloat16)
           for k in names}

    def fill(job):
        layer, j = job
        out[names[j]][layer] = _normal(seed, layer, j,
                                       shape=matrix_shape(model, names[j]))

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, [(layer, j) for layer in range(n)
                             for j in range(len(names))]))
    out.update({k: np.ones((n, model["hidden_size"]), np.float32)
                for k in NORMS})
    return out


def table(seed: int, which: int, rows: int, width: int) -> np.ndarray:
    """The embedding (EMBED) or the output head (HEAD), bfloat16
    [rows, width], in blocks of 4,096 rows so that threads only change
    the time."""
    import ml_dtypes

    out = np.empty((rows, width), ml_dtypes.bfloat16)
    step = 4096

    def fill(lo: int) -> None:
        out[lo:lo + step] = _normal(seed, which, lo,
                                    shape=(min(step, rows - lo), width))

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(0, rows, step)))
    return out


def top_weights(seed: int, model: dict) -> dict:
    """The final norm's gain, the gate's vector and its bias."""
    return {"norm_f": np.ones(model["hidden_size"], np.float32),
            "gate_w": _normal(seed, GATE, shape=(model["hidden_size"],)
                              ).astype(np.float32),
            "gate_b": np.zeros((), np.float32)}


# -- histories ---------------------------------------------------------------

def lognormal_lengths(rng, n: int, h: dict) -> np.ndarray:
    """n history lengths: log-normal around the median, clipped."""
    raw = np.exp(np.log(h["median"]) + h["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(raw), h["min"], h["max"]).astype(np.int32)


def plan_ranks(traffic: dict, n_users: int, seconds: float
               ) -> tuple[np.ndarray, np.ndarray]:
    """(popularity rank, whether it names an unknown user) of every
    request of the closed loop's plan, in sending order: the mix's draw
    (`base_seed`), the same for every seed."""
    n = int(loadgen.CLOSED_PLAN_REQUESTS_PER_S
            * (float(traffic["warmup_s"]) + seconds))
    base = np.random.default_rng([int(traffic["base_seed"]), 0x10AD])
    ranks = loadgen._zipf_ranks(base, n, n_users,
                                float(traffic["zipf_exponent"]))
    return ranks, base.random(n) < float(traffic["unknown_share"])


def rank_rows(traffic: dict, seed: int, n_users: int, seconds: float
              ) -> tuple[np.ndarray, np.ndarray]:
    """(ranks asked, the user row that holds each): which user holds
    which popularity rank is the seed's to say; only the ranks that are
    asked need a row."""
    asked = np.unique(plan_ranks(traffic, n_users, seconds)[0])
    rows = np.random.default_rng([seed, 0x10AD]).choice(
        n_users, len(asked), replace=False)
    return asked, rows


def closed_plan(traffic: dict, seed: int, n_users: int, seconds: float
                ) -> loadgen.Plan:
    """What the closed loop sends, as `loadgen.make_plan` would lay it
    out, but in the mix's own order (see the head of this file): request
    i asks the user who holds rank `plan_ranks[i]` under this seed."""
    ranks, unknown = plan_ranks(traffic, n_users, seconds)
    asked, rows_of = rank_rows(traffic, seed, n_users, seconds)
    rows = rows_of[np.searchsorted(asked, ranks)].astype(np.int64)
    rows[unknown] = -1
    users = [f"u{r}" if r >= 0 else f"nobody{i}"
             for i, r in enumerate(rows.tolist())]
    return loadgen.Plan(users=users, rows=rows, due=None,
                        warmup_s=float(traffic["warmup_s"]),
                        seconds=seconds, num=int(traffic["num"]))


def history_lengths(traffic: dict, seed: int, n_users: int, seconds: float
                    ) -> np.ndarray:
    """Every user's history length: log-normal, clipped. A user whom the
    plan asks holds the length of their popularity rank (the mix's, the
    same for every seed: request i of the plan is then as long in every
    run); the others a draw of the seed's own."""
    h = traffic["history"]
    lengths = lognormal_lengths(np.random.default_rng([seed, 0x1E6]),
                                n_users, h)
    asked, rows = rank_rows(traffic, seed, n_users, seconds)
    by_rank = lognormal_lengths(
        np.random.default_rng([int(traffic["base_seed"]), 0x1E6]),
        n_users, h)
    lengths[rows] = by_rank[asked]
    return lengths


def histories(traffic: dict, seed: int, n_users: int, n_items: int,
              max_len: int, seconds: float) -> np.ndarray:
    """int32 [n_users, max_len], left-padded with 0, item i stored as
    i + 1: item popularity Zipf over a seeded permutation of the items."""
    h = traffic["history"]
    lengths = np.minimum(history_lengths(traffic, seed, n_users, seconds),
                         max_len)
    rng = np.random.default_rng([seed, 0x415])
    ident = rng.permutation(n_items).astype(np.int32)
    total = int(lengths.sum())
    # the events' popularity ranks, in blocks with a generator each
    ranks = np.empty(total, np.int64)
    block = 1 << 22

    def fill(lo: int) -> None:
        ranks[lo:lo + block] = loadgen._zipf_ranks(
            np.random.default_rng([seed, 0x415, lo]),
            min(block, total - lo), n_items, float(h["item_zipf_exponent"]))

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(0, total, block)))
    out = np.zeros(n_users * max_len, np.int32)
    # the flat place of every event: its row's right end less what is
    # still to come of that row
    lengths = lengths.astype(np.int64)
    ends = np.arange(1, n_users + 1, dtype=np.int64) * max_len
    done = np.cumsum(lengths)
    place = np.repeat(ends - done, lengths) + np.arange(total)
    out[place] = ident[ranks] + 1
    return out.reshape(n_users, max_len)
