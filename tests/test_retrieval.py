"""Top-k retrieval (ops/retrieval.py) on the CPU backend: the Pallas
kernel under interpret mode (TPU-semantics parity) AND the plain-XLA
serving path non-TPU backends default to — both must match exact numpy
scoring through the same output contract."""

import numpy as np
import pytest

from predictionio_tpu.ops.retrieval import DeviceRetriever, topk_scores


def exact_topk(q, items, k):
    scores = q @ items.T  # [B, N]
    idx = np.argsort(-scores, axis=1, kind="stable")[:, :k]
    vals = np.take_along_axis(scores, idx, axis=1)
    return vals, idx


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "default-xla"])
@pytest.mark.parametrize("B,N,D,k", [
    (1, 100, 10, 5),       # tiny, unpadded everything
    (3, 1000, 32, 10),     # N not a multiple of the tile
    (8, 512, 64, 512),     # k == N (full ranking)
    (2, 2000, 16, 1),      # k = 1
    (2, 100, 10, 100),     # k_pad == n_total, not a multiple of 8
    (8, 5000, 64, 16),     # five 1,024-row tiles, the last ragged
    (8, 140_000, 16, 10),  # eighteen 8,192-row tiles, the last ragged
    (128, 3000, 32, 16),   # b_pad 128: 512-column sub-tiles
    (8, 3000, 100, 16),    # rank 100: 104 sublanes, four of them zeros
    (4, 3000, 128, 16),    # rank 128: the widest a 128-lane pad held
])
def test_matches_exact(rng, B, N, D, k, interpret):
    q = rng.standard_normal((B, D)).astype(np.float32)
    items = rng.standard_normal((N, D)).astype(np.float32)
    vals, idx = topk_scores(q, items, k, interpret=interpret)
    want_v, want_i = exact_topk(q, items, k)
    np.testing.assert_allclose(vals, want_v, rtol=1e-5, atol=1e-5)
    # indices may differ on exact ties; compare score-at-index instead
    got_scores = np.take_along_axis(q @ items.T, idx.astype(np.int64), axis=1)
    np.testing.assert_allclose(got_scores, want_v, rtol=1e-5, atol=1e-5)
    assert (idx >= 0).all() and (idx < N).all()


def _old_topk_call(B, D, N_pad, n_total, k, tile_n):
    """The kernel as it stood before ISSUE 25 (merge gated on the best
    score of ANY row against the lowest kept value of ANY row, k
    unconditional extraction rounds over [B, k + T]), kept here as the
    reference the new kernel's answers must equal bit for bit. It reads
    the catalog as the device holds it since ISSUE 30, [D, N_pad]: what
    it pins is the merge, not a layout."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(q_ref, items_ref, vals_ref, idx_ref):  # items [D, tile_n]
        j = pl.program_id(0)

        @pl.when(j == 0)
        def _():
            vals_ref[:] = jnp.full(vals_ref.shape, -jnp.inf, vals_ref.dtype)
            idx_ref[:] = jnp.full(idx_ref.shape, -1, idx_ref.dtype)

        scores = jax.lax.dot_general(
            q_ref[:, :D], items_ref[:], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
        cand = j * tile_n + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(cand < n_total, scores, -jnp.inf)

        @pl.when(jnp.max(scores) > jnp.min(vals_ref[:]))
        def _():
            merged_v = jnp.concatenate([vals_ref[:], scores], axis=1)
            merged_i = jnp.concatenate([idx_ref[:], cand], axis=1)
            col = jax.lax.broadcasted_iota(jnp.int32, merged_v.shape, 1)
            out_col = jax.lax.broadcasted_iota(jnp.int32, (B, k), 1)

            def extract(t, carry):
                mv, out_v, out_i = carry
                m = jnp.max(mv, axis=1)
                pick_col = jnp.min(
                    jnp.where(mv == m[:, None], col, mv.shape[1]), axis=1)
                chosen = col == pick_col[:, None]
                pick = jnp.sum(jnp.where(chosen, merged_i, 0), axis=1)
                pick = jnp.where(jnp.isfinite(m), pick, -1).astype(jnp.int32)
                slot = out_col == t
                return (jnp.where(chosen, -jnp.inf, mv),
                        jnp.where(slot, m[:, None], out_v),
                        jnp.where(slot, pick[:, None], out_i))

            _, out_v, out_i = jax.lax.fori_loop(0, k, extract, (
                merged_v, jnp.full((B, k), -jnp.inf, jnp.float32),
                jnp.full((B, k), -1, jnp.int32)))
            vals_ref[:] = out_v
            idx_ref[:] = out_i

    return pl.pallas_call(
        kernel, grid=(N_pad // tile_n,),
        in_specs=[pl.BlockSpec((B, 128), lambda j: (0, 0)),
                  pl.BlockSpec((D, tile_n), lambda j: (0, j))],
        out_specs=[pl.BlockSpec((B, k), lambda j: (0, 0)),
                   pl.BlockSpec((B, k), lambda j: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((B, k), jnp.float32),
                   jax.ShapeDtypeStruct((B, k), jnp.int32)],
        interpret=True)


def _padded(q, items):
    """(q [B, 128], items [d_pad, N_pad]) as the kernel takes them: the
    query in 128 lanes, the catalog's rank in whole sublane groups, its
    items in whole tiles."""
    from predictionio_tpu.ops.retrieval import _pad_items

    qp = np.zeros((q.shape[0], 128), np.float32)
    qp[:, :q.shape[1]] = q
    return qp, np.asarray(_pad_items(items))


def test_answers_are_the_old_extractions_bit_for_bit(rng):
    """ISSUE 25 acceptance: at one tile size (512) the per-row gate and
    the bounded extraction return the values AND the catalog rows the
    old every-tile merge returned, ties, zero rows and padding
    included."""
    from predictionio_tpu.ops.retrieval import _raw_call

    B, N, D, k = 8, 2900, 24, 16
    items = rng.standard_normal((N, D)).astype(np.float32)
    items[700:764] = items[100:164]     # exact duplicates across tiles
    items[1500:1510] = items[1490:1500]  # and inside one
    q = rng.standard_normal((B, D)).astype(np.float32)
    q[3] = 0.0  # the sentinel row: every score ties at 0
    qp, ip = _padded(q, items)
    assert ip.shape[0] == D and ip.shape[1] % 512 == 0 and ip.shape[1] > N
    want_v, want_i = _old_topk_call(B, *ip.shape, N, k, 512)(qp, ip)
    got_v, got_i, counts = _raw_call(B, *ip.shape, N, k, True,
                                     tile_n=512)(qp, ip)
    assert np.array_equal(np.asarray(got_v), np.asarray(want_v))
    assert np.array_equal(np.asarray(got_i), np.asarray(want_i))
    assert int(counts[0]) == ip.shape[1] // 512
    assert list(np.asarray(want_i)[3]) == list(range(k))  # lower row wins


def _replay_gate(q, items, n_total, k, tile, chunk):
    """numpy replay of the kernel's gate and extraction, sub-tile by
    sub-tile: (values, rows, [scanned, merged, rounds])."""
    B = q.shape[0]
    scores = (q @ items.T).astype(np.float32)
    scores[:, n_total:] = -np.inf
    kv = np.full((B, k), -np.inf, np.float32)
    ki = np.full((B, k), -1, np.int64)
    merged = rounds = 0
    for base in range(0, items.shape[0], chunk):
        s = scores[:, base:base + chunk].copy()
        if not (s.max(axis=1) > kv[:, -1]).any():
            continue
        merged += 1
        while True:
            rounds += 1
            pick = s.argmax(axis=1)  # the first column holding the max
            m = s[np.arange(B), pick]
            for r in np.flatnonzero(m > kv[:, -1]):
                pos = int((kv[r] >= m[r]).sum())
                kv[r] = np.insert(kv[r], pos, m[r])[:k]
                ki[r] = np.insert(ki[r], pos, base + pick[r])[:k]
            s[np.arange(B), pick] = -np.inf
            if not (s.max(axis=1) > kv[:, -1]).any():
                break
    return kv, ki, [items.shape[0] // chunk, merged, rounds]


@pytest.mark.parametrize("B,N,k", [(8, 60_000, 16), (128, 20_000, 16),
                                   (16, 2048, 8)],
                         ids=["b8", "b128", "no-padding"])
def test_counters_equal_a_replay_of_the_gate(rng, B, N, k):
    """The counters that ride the packed result are what a numpy replay
    of the per-row gate counts on the same inputs: small integers, so
    every score is exact whatever the order of the sum, and ties
    abound. The replay's answers are the kernel's too, and a stable
    sort's (the lower catalog row wins a tie)."""
    from predictionio_tpu.ops.retrieval import _raw_call, _tile_rows

    q = rng.integers(-4, 5, (B, 16)).astype(np.float32)
    q[B // 2] = 0.0
    items = rng.integers(-4, 5, (N, 16)).astype(np.float32)
    # long rows first, so that the late sub-tiles hold no entrant
    items = items[np.argsort(-np.abs(items).sum(axis=1), kind="stable")]
    qp, ip = _padded(q, items)
    tile, chunk = _tile_rows(B, ip.shape[0], k, ip.shape[1])
    assert ip.shape[1] // tile > 1 or N == 2048
    got_v, got_i, counts = _raw_call(B, *ip.shape, N, k, True)(qp, ip)
    want_v, want_i, want_counts = _replay_gate(qp[:, :ip.shape[0]], ip.T,
                                               N, k, tile, chunk)
    assert np.array_equal(np.asarray(got_v), want_v)
    assert np.array_equal(np.asarray(got_i), want_i)
    assert [int(c) for c in counts] == want_counts
    sort_v, sort_i = exact_topk(q, items, k)
    assert np.array_equal(want_i, sort_i)
    assert np.array_equal(want_v, sort_v)
    if N > 2048:  # some sub-tile was skipped
        assert want_counts[1] < want_counts[0]
    assert want_counts[1] <= want_counts[2] <= k * want_counts[1]


def test_zero_rows_among_real_ones_stop_merging(rng):
    """The sentinel case (padding slots, unknown users): zero rows fill
    their k slots from the first sub-tile and never open the gate
    again, so the batch equals the float32 reference and merges in
    fewer sub-tiles than it scans. /stats.json's retrieval block and
    the registry carry the counts."""
    from predictionio_tpu.obs.metrics import METRICS

    items = rng.standard_normal((40_000, 32)).astype(np.float32)
    # scores shrink along the catalog: real rows stop merging early too,
    # while the old gate (any row's best score against the LOWEST kept
    # value of any row, 0 here) would have merged every tile
    items /= (1.0 + np.arange(len(items), dtype=np.float32) / 500)[:, None]
    q = rng.standard_normal((5, 32)).astype(np.float32)
    q[1] = q[4] = 0.0
    r = DeviceRetriever(items, interpret=True)
    before = METRICS.snapshot()["counters"]
    vals, idx = r.topk(q, 10)
    want_v, want_i = exact_topk(q, items, 10)
    np.testing.assert_allclose(vals, want_v, rtol=1e-5, atol=1e-5)
    assert np.array_equal(idx[[1, 4]], want_i[[1, 4]])  # rows 0..9
    st = r.stats()
    assert st["mode"] == "exact" and st["kernel"] == "interpret"
    assert 0 < st["tilesMerged"] < st["tilesScanned"]
    assert st["tilesMerged"] <= st["mergeRounds"]
    after = METRICS.snapshot()["counters"]

    def gain(series):
        return after[series] - before.get(series, 0)

    assert gain('pio_topk_tiles_total{event="scanned"}') == st["tilesScanned"]
    assert gain('pio_topk_tiles_total{event="merged"}') == st["tilesMerged"]
    assert gain("pio_topk_merge_rounds_total") == st["mergeRounds"]
    # a second call adds to the totals; the XLA program has no tiles
    r.topk(q, 10)
    assert r.stats()["tilesScanned"] == 2 * st["tilesScanned"]
    assert DeviceRetriever(items[:500]).stats()["tilesScanned"] == 0


@pytest.mark.parametrize("rank", [10, 32, 64, 100, 128])
def test_layout_round_trip(rng, rank, monkeypatch):
    """`_pad_items` and back gives the catalog: item i is column i of a
    [d_pad, N_pad] array, the rank in whole sublane groups of 8 and not
    in 128 lanes, everything beyond the catalog zero. The upload goes in
    blocks (here three, the last ragged) and no block shows a seam."""
    from predictionio_tpu.ops import retrieval

    monkeypatch.setattr(retrieval, "_UPLOAD_ROWS", 1024)
    n = 2500
    items = rng.standard_normal((n, rank)).astype(np.float32)
    dev = retrieval._pad_items(items)
    d_pad, n_pad = retrieval._padded_shape(n, rank)
    assert dev.shape == (d_pad, n_pad) == (-(-rank // 8) * 8, 2560)
    back = np.asarray(dev)
    assert np.array_equal(back[:rank, :n].T, items)
    assert not back[rank:].any() and not back[:, n:].any()
    # the bytes a scan reads are the bytes it needs, but for that padding
    assert dev.nbytes * rank * n == items.nbytes * d_pad * n_pad
    # a strided view of a wider array (a caller's slice) goes up the same
    wide = rng.standard_normal((n, rank + 3)).astype(np.float32)
    assert np.array_equal(
        np.asarray(retrieval._pad_items(wide[:, 3:]))[:rank, :n].T,
        wide[:, 3:])


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "default-xla"])
def test_tie_across_the_seams_of_a_sub_tile_answers_the_lower_row(
        rng, interpret):
    """Equal scores inside ONE sub-tile whose items are not neighbours
    in memory: across two lane groups (columns 127 and 128), at the same
    lane of two lane groups (the halves the gate folds onto each other:
    columns 40 and 1064) and at a sub-tile's two ends. Each tie answers
    the lower catalog row first, as a stable sort of the scores does."""
    B, N, D, k = 8, 5000, 64, 8
    items = 0.01 * rng.standard_normal((N, D)).astype(np.float32)
    q = rng.standard_normal((B, D)).astype(np.float32)
    # four pairs of duplicates inside the second 2,048-column sub-tile,
    # each pair the best of some row
    pairs = [(2048 + 127, 2048 + 128), (2048 + 40, 2048 + 1064),
             (2048, 4095), (2048 + 1023, 2048 + 1024)]
    for row, (lo, hi) in enumerate(pairs):
        items[lo] = items[hi] = (row + 2) * q[row] / np.linalg.norm(q[row])
    vals, idx = topk_scores(q, items, k, interpret=interpret)
    want_v, want_i = exact_topk(q, items, k)
    assert np.array_equal(idx, want_i)
    for row, (lo, hi) in enumerate(pairs):
        assert list(idx[row, :2]) == [lo, hi]
        assert vals[row, 0] == vals[row, 1]


def test_catalog_bytes_add_up_per_scan(rng):
    """/stats.json's `retrieval.catalogBytesNeeded` and
    `catalogBytesScanned`: each scan adds the float32 factors of the
    real items and the bytes of the device array it read, through
    `topk` and through the serving pipeline's fused dispatch alike;
    their ratio is the fill of the layout (100% less the padding)."""
    from predictionio_tpu.ops.pipeline import ServingPipeline

    n, rank = 3000, 10
    items = rng.standard_normal((n, rank)).astype(np.float32)
    users = rng.standard_normal((20, rank)).astype(np.float32)
    r = DeviceRetriever(items, interpret=True)
    assert r.lane_dim == 128 and r._items.shape == (16, 3072)
    st = r.stats()
    assert st["catalogBytesNeeded"] == st["catalogBytesScanned"] == 0
    r.topk(users[:3], 5)
    st = r.stats()
    assert st["catalogBytesNeeded"] == n * rank * 4
    assert st["catalogBytesScanned"] == 16 * 3072 * 4 == r._items.nbytes
    ServingPipeline(users, r).topk_rows(np.array([1, 2], np.int32), 5)
    r.topk(users[0], 5)
    st = r.stats()
    assert st["catalogBytesNeeded"] == 3 * n * rank * 4
    assert st["catalogBytesScanned"] == 3 * r._items.nbytes
    # a 128-lane pad of this rank would have read eight times as much
    assert st["catalogBytesScanned"] * 8 == 3 * 3072 * 128 * 4
    # the XLA program reports no scan
    x = DeviceRetriever(items)
    x.topk(users[:3], 5)
    assert x.stats()["catalogBytesScanned"] == 0


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "default-xla"])
def test_single_query_vector(rng, interpret):
    q = rng.standard_normal(24).astype(np.float32)
    items = rng.standard_normal((300, 24)).astype(np.float32)
    vals, idx = topk_scores(q, items, 7, interpret=interpret)
    assert vals.shape == (7,) and idx.shape == (7,)
    want = np.sort(items @ q)[::-1][:7]
    np.testing.assert_allclose(vals, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "default-xla"])
def test_k_larger_than_catalog(rng, interpret):
    q = rng.standard_normal((2, 8)).astype(np.float32)
    items = rng.standard_normal((5, 8)).astype(np.float32)
    vals, idx = topk_scores(q, items, 20, interpret=interpret)
    assert vals.shape == (2, 5)
    want_v, _ = exact_topk(q, items, 5)
    np.testing.assert_allclose(vals, want_v, rtol=1e-5, atol=1e-5)


def test_empty_catalog():
    vals, idx = topk_scores(np.zeros((2, 4), np.float32),
                            np.zeros((0, 4), np.float32), 3)
    assert vals.shape == (2, 0) and idx.shape == (2, 0)


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "default-xla"])
def test_device_retriever_reuse(rng, interpret):
    items = rng.standard_normal((777, 48)).astype(np.float32)
    r = DeviceRetriever(items, interpret=interpret)
    for _ in range(2):  # second call hits the jit cache
        q = rng.standard_normal((4, 48)).astype(np.float32)
        vals, idx = r.topk(q, 9)
        want_v, _ = exact_topk(q, items, 9)
        np.testing.assert_allclose(vals, want_v, rtol=1e-5, atol=1e-5)


def test_als_model_retriever_matches_host(rng):
    from predictionio_tpu.models.als import ALSConfig, ALSModel
    from predictionio_tpu.storage.bimap import BiMap
    import pickle

    nu, ni, r = 6, 40, 8
    uids = BiMap({f"u{i}": i for i in range(nu)})
    iids = BiMap({f"i{i}": i for i in range(ni)})
    m = ALSModel(
        user_factors=rng.standard_normal((nu, r)).astype(np.float32),
        item_factors=rng.standard_normal((ni, r)).astype(np.float32),
        user_ids=uids, item_ids=iids, config=ALSConfig(rank=r),
    )
    host = m.recommend_products("u3", 5)
    m.attach_retriever(interpret=True)
    dev = m.recommend_products("u3", 5)
    assert [i for i, _ in dev] == [i for i, _ in host]
    np.testing.assert_allclose([s for _, s in dev], [s for _, s in host],
                               rtol=1e-5, atol=1e-5)
    # device arrays never enter the pickled MODELDATA blob
    m2 = pickle.loads(pickle.dumps(m))
    assert getattr(m2, "_retriever", None) is None
    assert m2.recommend_products("u3", 5)


# ---------------------------------------------------------------------------
# ShardedDeviceRetriever: catalog sharded over the 8-device virtual mesh.


def _sharded(items, axis_len=8):
    from predictionio_tpu.ops.retrieval import ShardedDeviceRetriever
    from predictionio_tpu.parallel.mesh import make_mesh

    mesh = make_mesh((axis_len,), ("model",))
    return ShardedDeviceRetriever(items, mesh)


@pytest.mark.parametrize("B,N,D,k", [
    (1, 100, 10, 5),       # catalog smaller than 128*P padding
    (3, 1303, 32, 10),     # N not divisible by the shard count
    (8, 2048, 64, 40),     # aligned
])
def test_sharded_matches_single_device(rng, B, N, D, k):
    q = rng.standard_normal((B, D)).astype(np.float32)
    items = rng.standard_normal((N, D)).astype(np.float32)
    ret = _sharded(items)
    vals, idx = ret.topk(q, k)
    want_v, _ = exact_topk(q, items, k)
    np.testing.assert_allclose(vals, want_v, rtol=1e-5, atol=1e-5)
    got_scores = np.take_along_axis(q @ items.T, idx.astype(np.int64), axis=1)
    np.testing.assert_allclose(got_scores, want_v, rtol=1e-5, atol=1e-5)
    assert (idx >= 0).all() and (idx < N).all()
    # single-vector query path
    v1, i1 = ret.topk(q[0], k)
    np.testing.assert_allclose(v1, vals[0], rtol=1e-6)


def test_sharded_items_actually_sharded(rng):
    """The catalog must live sharded over the model axis (the capability
    claim is HBM scaling), and query results must survive k > catalog."""
    items = rng.standard_normal((1024, 16)).astype(np.float32)
    ret = _sharded(items)
    assert len(ret._items.sharding.device_set) == 8
    assert ret._items.shape[0] % 8 == 0
    # per-device shard is 1/8 of the padded rows
    db = ret._items.addressable_shards[0].data
    assert db.shape[0] == ret._items.shape[0] // 8
    v, i = ret.topk(rng.standard_normal(16).astype(np.float32), 5000)
    assert v.shape == (1024,)  # clamped to catalog


def test_sharded_collective_inventory(rng):
    """The compiled sharded top-k must move only the [B, P*k] candidate
    sets: all-gather(s) bounded by candidate size, and NO all-reduce /
    all-to-all / reduce-scatter (the score matrix never crosses ICI).
    Mirrors test_als.test_model_sharded_collective_inventory."""
    import re

    items = rng.standard_normal((4096, 32)).astype(np.float32)
    ret = _sharded(items)
    b_pad, k_pad = 8, 16
    # _call_for now returns an AOT-compiled executable (the serving path
    # never traces at request time), so the HLO comes straight off it
    hlo = ret._call_for(b_pad, k_pad, k_pad).as_text()
    assert not re.search(r"all-reduce(?!-scatter)", hlo), "unexpected all-reduce"
    assert "all-to-all" not in hlo, "unexpected all-to-all"
    assert "reduce-scatter" not in hlo, "unexpected reduce-scatter"
    # keyed on the op, not on the instruction's name (the printer of the
    # installed jaxlib writes `%all_gather.3 = f32[8,256]{0,1} all-gather(`)
    gathered = re.findall(
        r"=\s*f32\[([\d,]+)\]\S*\s+all-gather(?:-start)?\(", hlo)
    assert len(gathered) == 1, (
        f"expected ONE candidate-merge all-gather, found {gathered}")
    for dims in gathered:
        size = np.prod([int(x) for x in dims.split(",")])
        assert size <= 8 * b_pad * 2 * k_pad * 4, (
            f"all-gather of {dims} exceeds candidate-set scale")


@pytest.mark.parametrize("width", [1, 2, 4, 8])
def test_sharded_bitwise_parity(rng, width):
    """On-device merge parity is BITWISE, not approximate: every mesh
    width must return byte-identical values AND indices to the
    single-device retriever — including on exact score ties (duplicated
    catalog rows) and all-zero scores (a zero query ties the whole
    catalog), where the tie-break order is the contract. Works because
    the tiled all-gather is shard-major (candidates in ascending global
    index order) and top_k breaks ties by lowest index on both paths.

    Factors are small integers, so a score is exact whatever the order
    of its sum: since ISSUE 30 the single device holds the catalog as
    [d_pad, N_pad] and sums a score down 24 sublanes, a shard holds
    [S, 128] and sums along 128 lanes, and the CPU backend's two dots
    round iid normal factors apart in the last bit of a few scores (the
    merge and the tie order, which this test pins, never moved)."""
    N, D, k = 1536, 24, 10
    base = rng.integers(-4, 5, (N - 64, D)).astype(np.float32)
    items = np.concatenate([base, base[:64]], axis=0)  # exact dup rows
    q = rng.integers(-4, 5, (5, D)).astype(np.float32)
    q[0] = 0.0  # full-catalog tie
    want_v, want_i = DeviceRetriever(items).topk(q, k)
    ret = _sharded(items, axis_len=width)
    assert ret.merge == "device"
    vals, idx = ret.topk(q, k)
    assert np.array_equal(vals, want_v)
    assert np.array_equal(idx, want_i)


class TestExecutableCache:
    def _cache(self, **kw):
        from predictionio_tpu.ops.retrieval import ExecutableCache

        return ExecutableCache(**kw)

    def test_hit_miss_counters(self):
        c = self._cache()
        built = []
        for _ in range(3):
            c.get_or_build("a", lambda: built.append(1) or "exe")
        assert built == [1]  # built once, then served from cache
        s = c.stats()
        assert s["misses"] == 1 and s["hits"] == 2
        assert s["hitRate"] == pytest.approx(2 / 3)

    def test_eviction_is_lru(self):
        c = self._cache(maxsize=2)
        c.get_or_build("a", lambda: "A")
        c.get_or_build("b", lambda: "B")
        c.get_or_build("a", lambda: "A")  # refresh a: b is now oldest
        c.get_or_build("c", lambda: "C")  # evicts b
        assert c.stats()["evictions"] == 1
        rebuilt = []
        c.get_or_build("a", lambda: rebuilt.append("a") or "A")
        c.get_or_build("b", lambda: rebuilt.append("b") or "B")
        assert rebuilt == ["b"]  # a survived, b was the victim

    def test_pinned_never_evicted(self):
        c = self._cache(maxsize=2)
        c.get_or_build("hot", lambda: "H")
        c.pin("hot")
        for key in "abcdef":
            c.get_or_build(key, lambda: key.upper())
        rebuilt = []
        c.get_or_build("hot", lambda: rebuilt.append(1) or "H")
        assert rebuilt == []  # survived every eviction round
        assert c.stats()["pinned"] == 1

    def test_double_build_race_compiles_once(self):
        """ISSUE 16 satellite: two threads missing the same key must
        compile it ONCE — the loser waits on the per-key build lock and
        takes the winner's entry as a hit. Pinned by exactly one
        pio_xla_compile_pipeline_seconds observation."""
        import threading
        import time as _time

        from predictionio_tpu.obs.device import COMPILE_HISTOGRAMS

        c = self._cache()
        key = ("pipeline", 0, "race", 8, 8)
        count0 = COMPILE_HISTOGRAMS["pipeline"].snapshot()["count"]
        barrier = threading.Barrier(2)
        built = []

        def build():
            built.append(1)
            _time.sleep(0.05)  # long enough for the loser to pile in
            return "exe"

        results = [None, None]

        def worker(i):
            barrier.wait()
            results[i] = c.get_or_build(key, build)

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert built == [1], "racing threads burned a duplicate compile"
        assert results == ["exe", "exe"]
        s = c.stats()
        assert s["misses"] == 1 and s["hits"] == 1
        after = COMPILE_HISTOGRAMS["pipeline"].snapshot()["count"]
        assert after - count0 == 1  # the ledger saw ONE compile


@pytest.mark.parametrize("make", [
    pytest.param(lambda items: DeviceRetriever(items), id="single"),
    pytest.param(lambda items: _sharded(items), id="sharded"),
])
def test_prewarm_precompiles_serving_shapes(rng, make):
    """A serving call whose padded shape was prewarmed must be a pure
    cache HIT — zero compiles at request time (the AOT deploy-time
    warming create_server.py does with prewarm_batch)."""
    from predictionio_tpu.ops.retrieval import EXEC_CACHE

    items = rng.standard_normal((600, 16)).astype(np.float32)
    ret = make(items)
    warmed = ret.prewarm(batch_sizes=(1, 32), ks=(10,))
    assert warmed  # at least one (b_pad, k_pad) compiled
    before = EXEC_CACHE.stats()
    ret.topk(rng.standard_normal((32, 16)).astype(np.float32), 10)
    ret.topk(rng.standard_normal(16).astype(np.float32), 10)
    after = EXEC_CACHE.stats()
    assert after["misses"] == before["misses"]
    assert after["hits"] >= before["hits"] + 2


def test_dispatch_topk_pad_bucket_lattice(rng):
    """ISSUE 16 satellite: ``_dispatch_topk`` maps every (b, k) in
    b 1..65 x k {1, 10, 64} onto the MINIMAL pad bucket (power-of-two
    batch >= 8, k rounded to 8s), records the padding waste for every
    dispatch, and — after a prewarm of the lattice — never compiles at
    request time."""
    from predictionio_tpu.obs.device import LEDGER
    from predictionio_tpu.ops.retrieval import (
        EXEC_CACHE,
        _dispatch_topk,
        _query_shapes,
    )

    n_total = 600
    seen: list[tuple[int, int]] = []

    def invoke(q_padded, k_pad):
        seen.append((q_padded.shape[0], k_pad))
        return (np.zeros((q_padded.shape[0], k_pad), np.float32),
                np.zeros((q_padded.shape[0], k_pad), np.int32)), False

    waste0 = LEDGER.snapshot()["paddingWaste"]["count"]
    dispatches = 0
    for b in range(1, 66):
        q = np.zeros((b, 16), np.float32)
        for k in (1, 10, 64):
            k_eff = min(k, n_total)
            vals, idx = _dispatch_topk(q, n_total, k, invoke)
            dispatches += 1
            b_pad, k_pad = _query_shapes(b, k_eff, n_total)
            assert seen[-1] == (b_pad, k_pad)
            assert k_pad == min(((k_eff + 7) // 8) * 8, n_total)
            assert b_pad >= max(b, 8)
            assert b_pad == 8 or b_pad < 2 * b  # minimal bucket
            assert vals.shape == (b, k_eff)  # un-padded back out
    assert LEDGER.snapshot()["paddingWaste"]["count"] - waste0 == dispatches
    # the whole lattice collapses onto a handful of compiled shapes
    assert len(set(seen)) <= 5 * 3

    # and against a REAL retriever: prewarming those buckets means zero
    # request-time compiles across the full lattice
    items = rng.standard_normal((n_total, 16)).astype(np.float32)
    ret = DeviceRetriever(items)
    ret.prewarm(batch_sizes=(1, 16, 32, 64, 65), ks=(1, 10, 64))
    before = EXEC_CACHE.stats()["misses"]
    for b in (1, 7, 8, 9, 33, 65):
        for k in (1, 10, 64):
            ret.topk(rng.standard_normal((b, 16)).astype(np.float32), k)
    assert EXEC_CACHE.stats()["misses"] == before, \
        "a lattice shape compiled at request time after prewarm"


def test_sharded_mixin_swaps_in(rng):
    """attach_sharded_retriever must feed the SAME serving surface
    (top_n_from_catalog / top_n_batch) the single-device retriever does."""
    from predictionio_tpu.ops.retrieval import RetrievalServingMixin
    from predictionio_tpu.parallel.mesh import make_mesh
    from predictionio_tpu.storage.bimap import BiMap

    class M(RetrievalServingMixin):
        pass

    m = M()
    m.item_factors = rng.standard_normal((300, 8)).astype(np.float32)
    m.item_ids = BiMap.from_iterable(f"i{j}" for j in range(300))
    q = rng.standard_normal(8).astype(np.float32)
    host = m.top_n_from_catalog(q, 7)
    m.attach_sharded_retriever(make_mesh((8,), ("model",)))
    dev = m.top_n_from_catalog(q, 7)
    assert [i for i, _ in dev] == [i for i, _ in host]
    np.testing.assert_allclose([s for _, s in dev], [s for _, s in host],
                               rtol=1e-5)
    # MODELDATA serialization must drop the device handle
    assert "_retriever" not in m.__getstate__()


def test_deployed_preserves_sharded_attach(rng):
    """A Deployed bundle built with retriever_mesh attaches the SHARDED
    retriever (and the reload path re-passes the mesh — create_server.py
    reload() — so /reload cannot silently de-shard a catalog)."""
    from types import SimpleNamespace

    from predictionio_tpu.ops.retrieval import (RetrievalServingMixin,
                                                ShardedDeviceRetriever)
    from predictionio_tpu.parallel.mesh import make_mesh
    from predictionio_tpu.storage.bimap import BiMap
    from predictionio_tpu.workflow.create_server import Deployed

    class M(RetrievalServingMixin):
        pass

    m = M()
    m.item_factors = rng.standard_normal((64, 8)).astype(np.float32)
    m.item_ids = BiMap.from_iterable(f"i{j}" for j in range(64))
    mesh = make_mesh((8,), ("model",))
    d = Deployed(None, SimpleNamespace(models=[m]), retriever_mesh=mesh)
    assert isinstance(m._retriever, ShardedDeviceRetriever)
    assert d.retriever_mesh is mesh and d.retriever_axis == "model"


def test_sharded_similarity_retriever_matches_host(rng):
    """Cosine similar-items through the SHARDED normalized catalog must
    match host scoring (the similarproduct family's sharded deploy)."""
    from predictionio_tpu.models.als import ALSConfig, ALSModel
    from predictionio_tpu.parallel.mesh import make_mesh
    from predictionio_tpu.storage.bimap import BiMap

    ni, r = 120, 8
    m = ALSModel(
        user_factors=rng.standard_normal((5, r)).astype(np.float32),
        item_factors=rng.standard_normal((ni, r)).astype(np.float32),
        user_ids=BiMap({f"u{i}": i for i in range(5)}),
        item_ids=BiMap({f"i{i}": i for i in range(ni)}),
        config=ALSConfig(rank=r),
    )
    host = m.similar_items([3, 7], 6)
    m.attach_sharded_similarity_retriever(make_mesh((8,), ("model",)))
    sharded = m.similar_items([3, 7], 6)
    assert [i for i, _ in sharded] == [i for i, _ in host]
    np.testing.assert_allclose([s for _, s in sharded],
                               [s for _, s in host], rtol=1e-5, atol=1e-6)
    # serialization still strips the device handle
    assert "_sim_retriever" not in m.__getstate__()
