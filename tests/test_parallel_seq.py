"""Sequence/context parallelism tests: ring attention and Ulysses
all-to-all must be *exact* (match dense attention to float tolerance) on
the 8-device virtual CPU mesh, and the SASRec-style sequence recommender
must learn and serve with either attention path.

(The reference has no analog — no sequence models exist there; see
SURVEY.md §5 "long-context". These tests play the role its
SharedSparkContext suites play for Spark logic: multi-device semantics
verified without real hardware.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from functools import partial

from predictionio_tpu.models.seq_attention import (
    SeqRecConfig,
    build_sequences,
    train_seq_rec,
)
from predictionio_tpu.parallel.ring_attention import (
    blockwise_attention,
    ring_attention,
    ring_self_attention,
    ulysses_attention,
)

shard_map = jax.shard_map


def dense_attention(q, k, v, causal=False):
    """Reference implementation: full [L, L] softmax attention."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (d**0.5)
    if causal:
        L = q.shape[1]
        pos = jnp.arange(L)
        s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(rng, B=2, L=32, H=4, D=8):
    return tuple(
        jnp.asarray(rng.standard_normal((B, L, H, D)).astype(np.float32))
        for _ in range(3)
    )


@pytest.fixture(scope="module")
def seq_mesh():
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs 8 virtual devices")
    return Mesh(np.asarray(devices).reshape(2, 4), ("data", "seq"))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_dense(seq_mesh, rng, causal):
    q, k, v = qkv(rng)
    want = dense_attention(q, k, v, causal=causal)
    got = ring_self_attention(seq_mesh, q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_attention_matches_dense(rng, causal):
    q, k, v = qkv(rng)
    want = dense_attention(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, causal=causal, block_size=8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_blockwise_attention_bf16_path(rng):
    """bf16 inputs take the bf16-matmul / f32-accumulation branch
    (mm_dtype) — pin it against the f32 dense reference at bf16
    tolerance, and pin the output dtype contract (returns q.dtype)."""
    import jax.numpy as jnp

    q, k, v = qkv(rng)
    want = dense_attention(q, k, v, causal=True)
    qb, kb, vb = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
    got = blockwise_attention(qb, kb, vb, causal=True, block_size=8)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.1, atol=0.05)


def test_ring_attention_bf16_path(seq_mesh, rng):
    """The ring's bf16 branch (input-dtype ppermuted K/V blocks, f32
    carries) must match the f32 dense reference at bf16 tolerance."""
    import jax.numpy as jnp

    q, k, v = qkv(rng)
    want = dense_attention(q, k, v, causal=True)
    qb, kb, vb = (jnp.asarray(x, jnp.bfloat16) for x in (q, k, v))
    got = ring_self_attention(seq_mesh, qb, kb, vb, causal=True)
    assert got.dtype == jnp.bfloat16  # the returns-q.dtype contract
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=0.1, atol=0.05)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(seq_mesh, rng, causal):
    q, k, v = qkv(rng)  # H=4 divisible by seq axis 4
    want = dense_attention(q, k, v, causal=causal)
    spec = P("data", "seq", None, None)
    fn = shard_map(
        partial(ulysses_attention, axis_name="seq", causal=causal),
        mesh=seq_mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )
    sh = NamedSharding(seq_mesh, spec)
    got = fn(*(jax.device_put(x, sh) for x in (q, k, v)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_ring_attention_under_jit(seq_mesh, rng):
    """The ring path must compose under jit (it is used inside compiled
    train steps)."""
    q, k, v = qkv(rng, L=16)
    f = jax.jit(lambda a, b, c: ring_self_attention(seq_mesh, a, b, c, causal=True))
    got = f(q, k, v)
    want = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_build_sequences_left_pad_time_order():
    users = np.asarray(["u1", "u1", "u2", "u1"], dtype=object)
    items = np.asarray(["a", "b", "a", "c"], dtype=object)
    times = np.asarray([3.0, 1.0, 5.0, 2.0])
    seqs, uids, iids = build_sequences(users, items, times, max_len=4)
    u1 = seqs[uids["u1"]]
    # time order: b(1) -> c(2) -> a(3), left-padded
    assert u1[0] == 0
    assert [iids.inverse[i - 1] for i in u1[1:]] == ["b", "c", "a"]
    u2 = seqs[uids["u2"]]
    assert list(u2[:3]) == [0, 0, 0] and iids.inverse[u2[3] - 1] == "a"


def _cyclic_history(n_users=32, n_items=6, hist=12, seed=0):
    """User u's history cycles items (u % k, u%k+1, ...): next item is
    fully determined by the last one."""
    users, items, times = [], [], []
    for u in range(n_users):
        for t in range(hist):
            users.append(f"u{u}")
            items.append(f"i{(u + t) % n_items}")
            times.append(float(t))
    return (
        np.asarray(users, dtype=object),
        np.asarray(items, dtype=object),
        np.asarray(times),
    )


def test_seq_rec_learns_cycle():
    users, items, times = _cyclic_history()
    cfg = SeqRecConfig(max_len=12, embed_dim=32, num_heads=2, num_blocks=1,
                       epochs=30, batch_size=32, lr=3e-3)
    seqs, uids, iids = build_sequences(users, items, times, max_len=cfg.max_len)
    model = train_seq_rec(seqs, uids, iids, cfg)
    # user u0 last saw i{11 % 6}=i5 -> next is i0
    recs = model.recommend_products("u0", 2, exclude_seen=False)
    assert recs, "no recommendations"
    assert recs[0][0] == "i0"
    # through the sequence models' one route: the serving pipeline's
    # encoder seam and the retriever's top-k, no forward of its own
    stats = model._pipeline.stats()
    assert stats["mode"] == "fused" and model._retriever.kernel == "xla"
    assert stats["sequence"]["steps"] == 1
    assert stats["sequence"]["tokensComputed"] == 8 * cfg.max_len


def test_seq_rec_serves_a_fold_in_steps_of_whole_rows():
    """What `BATCH_CHUNK` did, on the shared route: a caller that cuts
    nothing (an evaluation fold of 300 users) is served in steps of at
    most `SeqRecEncoder.STEP_ROWS` whole left-padded rows, on the
    lattice of 8 to 128 rows, with the answers a lone query gets; an
    unknown user and a user without events get []."""
    from predictionio_tpu.models.seq_attention import (SeqRecEncoder,
                                                       SeqRecModel,
                                                       _make_model)
    from predictionio_tpu.storage.bimap import BiMap

    cfg = SeqRecConfig(max_len=8, embed_dim=16, num_heads=2, num_blocks=1)
    n_items, n_users = 37, 300
    rng = np.random.default_rng(0)
    seqs = rng.integers(1, n_items + 1, (n_users, cfg.max_len)).astype(
        np.int32)
    seqs[:, :3] = 0          # left pads
    seqs[7] = 0              # a user without a single event
    params = _make_model(n_items, cfg).init(
        jax.random.PRNGKey(1), jnp.asarray(seqs[:2]))
    model = SeqRecModel(
        jax.tree_util.tree_map(np.asarray, params), seqs,
        BiMap({f"u{i}": i for i in range(n_users)}),
        BiMap({f"i{i}": i for i in range(n_items)}), cfg)
    users = [f"u{i}" for i in range(n_users)] + ["nobody"]
    got = model.batch_recommend(users, [3] * len(users))
    assert got[7] == [] and got[-1] == []
    assert all(len(g) == 3 for j, g in enumerate(got[:-1]) if j != 7)
    seq = model._pipeline.stats()["sequence"]
    assert seq["steps"] == -(-(n_users - 1) // SeqRecEncoder.STEP_ROWS) == 3
    assert seq["tokenLattice"] == [64, 128, 256, 512, 1024]
    assert seq["tokensComputed"] == (128 + 128 + 64) * cfg.max_len
    for j in (0, 150, 299):
        alone = model.recommend_products(users[j], 3)
        assert [i for i, _s in alone] == [i for i, _s in got[j]]
        np.testing.assert_allclose([s for _i, s in alone],
                                   [s for _i, s in got[j]], rtol=1e-5)
        seen = {f"i{t - 1}" for t in seqs[j] if t}
        assert not seen & {i for i, _s in got[j]}
    # scores are the model's own logits
    logits = np.asarray(_make_model(n_items, cfg).apply(
        params, jnp.asarray(seqs[:1])))[0, -1, 1:]
    for item, score in got[0]:
        assert abs(logits[int(item[1:])] - score) < 1e-4


def test_seq_rec_seq_parallel_matches_serial(seq_mesh):
    """Same params, same input: ring-attention forward == blockwise
    forward. Catches any divergence between the sharded and local paths."""
    from predictionio_tpu.models.seq_attention import _make_model

    users, items, times = _cyclic_history(n_users=8)
    cfg = SeqRecConfig(max_len=16, embed_dim=32, num_heads=4, num_blocks=2)
    seqs, uids, iids = build_sequences(users, items, times, max_len=cfg.max_len)
    serial = _make_model(len(iids), cfg)
    ring = _make_model(
        len(iids),
        SeqRecConfig(**{**cfg.__dict__, "seq_parallel": True}),
        seq_mesh,
    )
    params = serial.init(jax.random.PRNGKey(0), jnp.asarray(seqs[:2]))
    a = serial.apply(params, jnp.asarray(seqs))
    b = ring.apply(params, jnp.asarray(seqs))
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("L,bs", [(25, 10), (24, 10), (7, 512)])
def test_blockwise_attention_unaligned_blocks(rng, L, bs):
    """block_size need not divide L: the tail K/V block is padded and the
    padded keys masked out."""
    q, k, v = qkv(rng, L=L)
    for causal in (False, True):
        want = dense_attention(q, k, v, causal=causal)
        got = blockwise_attention(q, k, v, causal=causal, block_size=bs)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
