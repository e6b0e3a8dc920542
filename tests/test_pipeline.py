"""Device-resident serving pipeline (ISSUE 16) — ops/pipeline.py.

Layers under test, bottom-up:

- fused dispatch parity: ``topk_rows`` through the device-side gather
  scores bit-for-bit like the legacy host-gather path through the same
  compiled program (unknown rows gather the zero sentinel exactly like
  ``np.pad``'s zero rows);
- the pinned staging double buffer: bounded wait, transient fallback
  when the pool is empty, the overlap counters;
- deploy-time ``prewarm`` over the full pad-bucketed lattice: zero
  request-time compiles afterwards, every pinned buffer accounted in
  the PR 12 device ledger;
- copy-on-write ``refresh``: a delta epoch bump swaps the table without
  invalidating a single compiled program; only capacity overgrowth
  re-tokenizes;
- chaos site ``pipeline.swap``: a hung double-buffer handoff holds ONE
  pinned buffer, concurrent dispatches keep flowing, and release
  returns the pool intact (the watchdog-degrades-never-wedges gate).
"""

import threading
import time

import numpy as np
import pytest

from predictionio_tpu.obs.device import LEDGER
from predictionio_tpu.ops.pipeline import (
    STAGING_DEPTH,
    ServingPipeline,
    _capacity,
)
from predictionio_tpu.ops.retrieval import (
    EXEC_CACHE,
    DeviceRetriever,
    _query_shapes,
)
from predictionio_tpu.faults import FAULTS


def _fixture(rng, n_items=500, n_users=60, dim=16):
    items = rng.standard_normal((n_items, dim)).astype(np.float32)
    users = rng.standard_normal((n_users, dim)).astype(np.float32)
    ret = DeviceRetriever(items)
    return users, ret, ServingPipeline(users, ret)


# ---------------------------------------------------------------------------
# numerics: the bitwise-parity contract


def test_fused_dispatch_bitwise_matches_legacy_host_gather(rng):
    """The pipelined rows->gather->score program must reproduce the
    legacy path (host numpy gather + the SAME compiled scorer)
    bit-for-bit — the invariant the PR 13 replay gate rides on."""
    users, ret, pipe = _fixture(rng)
    rows = np.array([3, 0, 59, 17, 17], np.int32)
    vals, idx = pipe.topk_rows(rows, 10)
    legacy_v, legacy_i = ret.topk(users[rows], 10)
    assert np.array_equal(vals, legacy_v)
    assert np.array_equal(idx, legacy_i)


@pytest.mark.parametrize("interpret", [True, None],
                         ids=["kernel", "default-xla"])
@pytest.mark.parametrize("dim", [10, 64, 100])
def test_fused_program_scores_like_the_retriever_at_its_lane_dim(
        rng, dim, interpret):
    """The query table keeps whole 128-lane rows
    (`DeviceRetriever.lane_dim`: it is gathered by row, never scanned)
    while the catalog holds the rank in sublane groups of 8; the scoring
    program reads the query's first `d_pad` lanes. The fused program
    answers bit for bit what the retriever's own program answers for
    the same rows, zero rows included, and feeds the retriever's
    counters."""
    items = rng.standard_normal((3000, dim)).astype(np.float32)
    users = rng.standard_normal((40, dim)).astype(np.float32)
    ret = DeviceRetriever(items, interpret=interpret)
    pipe = ServingPipeline(users, ret)
    assert pipe._d_pad == ret.lane_dim == 128
    assert ret._items.shape[0] == -(-dim // 8) * 8
    assert pipe._qtab.shape == (pipe._cap, 128)
    rows = np.array([7, -1, 39, 7, 0], np.int32)
    vals, idx = pipe.topk_rows(rows, 10)
    q = users[rows]
    q[1] = 0.0
    want_v, want_i = ret.topk(q, 10)
    assert np.array_equal(vals, want_v)
    assert np.array_equal(idx, want_i)
    scans = 2 if interpret else 0  # the XLA program reports none
    assert ret.stats()["catalogBytesNeeded"] == scans * items.nbytes


def test_unknown_rows_gather_the_zero_sentinel(rng):
    """Negative / out-of-table row ids must score exactly like the
    zero-padded rows the legacy path builds with np.pad."""
    users, ret, pipe = _fixture(rng)
    rows = np.array([-1, 5, 10_000], np.int32)
    vals, idx = pipe.topk_rows(rows, 4)
    zq = np.zeros((1, users.shape[1]), np.float32)
    legacy_v, legacy_i = ret.topk(
        np.vstack([zq, users[5][None, :], zq]), 4)
    assert np.array_equal(vals, legacy_v)
    assert np.array_equal(idx, legacy_i)


def test_empty_batch_and_empty_k(rng):
    _, _, pipe = _fixture(rng)
    v, i = pipe.topk_rows(np.zeros(0, np.int32), 5)
    assert v.shape == (0, 0) and i.shape == (0, 0)
    v, i = pipe.topk_rows(np.array([1], np.int32), 0)
    assert v.shape == (1, 0) and i.shape == (1, 0)


def test_capacity_policy(rng):
    """~12.5% headroom + sentinel, rounded to 256 — the ONE home of the
    policy (delta fold-ins must append for a long time pre-recompile)."""
    assert _capacity(0) == 256
    assert _capacity(60) == 256
    assert _capacity(1000) == 1280
    _, _, pipe = _fixture(rng)
    assert pipe._cap == _capacity(60)
    assert pipe._sentinel == pipe._cap - 1


# ---------------------------------------------------------------------------
# staging double buffer


def test_staging_transient_fallback_when_pool_drained(rng):
    """Both pinned buffers held -> a dispatch falls back to a transient
    allocation (slow, but the pool can never wedge a healthy batch)."""
    users, ret, pipe = _fixture(rng)
    rows = np.array([1, 2, 3], np.int32)
    b_pad, _ = _query_shapes(3, 5, ret.n_total)
    held = [pipe._acquire_staging(b_pad)[0] for _ in range(STAGING_DEPTH)]
    t0 = time.perf_counter()
    vals, idx = pipe.topk_rows(rows, 5)
    assert time.perf_counter() - t0 < 1.0  # bounded by STAGING_WAIT_S
    assert pipe.stats()["transientStaging"] == 1
    assert np.array_equal(vals, ret.topk(users[rows], 5)[0])
    for buf in held:
        pipe._release_staging(b_pad, buf, False)
    pipe.topk_rows(rows, 5)  # pool restored: pinned again
    s = pipe.stats()
    assert s["transientStaging"] == 1
    assert s["stagingFree"][b_pad] == STAGING_DEPTH


def test_overlap_counter_sees_inflight_device_step(rng):
    """A dispatch that assembles while another batch holds its device
    step counts as overlapped — the double buffer doing its job."""
    _, _, pipe = _fixture(rng)
    rows = np.array([1], np.int32)
    pipe.topk_rows(rows, 5)  # serial: not overlapped
    st = pipe._state
    with st.cond:
        st.in_device += 1  # simulate a batch in flight
    try:
        pipe.topk_rows(rows, 5)
    finally:
        with st.cond:
            st.in_device -= 1
    s = pipe.stats()
    assert s["dispatches"] == 2
    assert s["overlapRatio"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# prewarm lattice + ledger


def test_prewarm_full_lattice_no_request_time_compiles(rng):
    """ISSUE 16 satellite: after prewarming the pad-bucketed (b, k)
    lattice, EVERY batch shape b in 1..65 x k in {1, 10, 64} lands on a
    minimal prewarmed bucket — zero compiles at request time, and the
    padding-waste gauge observes every dispatch."""
    users, ret, pipe = _fixture(rng, n_users=70)
    warmed = pipe.prewarm(batch_sizes=(1, 16, 32, 64, 65), ks=(1, 10, 64))
    assert len(warmed) == len(set(warmed))  # lattice points, deduped
    before = EXEC_CACHE.stats()
    waste0 = LEDGER.snapshot()["paddingWaste"]["count"]
    dispatches = 0
    for b in range(1, 66):
        rows = np.arange(b, dtype=np.int32) % 70
        for k in (1, 10, 64):
            vals, idx = pipe.topk_rows(rows, k)
            dispatches += 1
            assert vals.shape == (b, min(k, ret.n_total))
            b_pad, _ = _query_shapes(b, min(k, ret.n_total), ret.n_total)
            assert b_pad >= max(b, 8)
            assert b_pad == 8 or b_pad < 2 * b  # minimal bucket
    after = EXEC_CACHE.stats()
    assert after["misses"] == before["misses"], \
        "a request-time compile slipped past the prewarmed lattice"
    assert after["hits"] >= before["hits"] + dispatches
    assert LEDGER.snapshot()["paddingWaste"]["count"] - waste0 == dispatches


def test_prewarm_accounts_pinned_buffers_in_ledger(rng):
    """PR 12 accounting: the query table and every pinned staging pair
    show up as ledger components with exact byte sizes."""
    _, _, pipe = _fixture(rng)
    pipe.prewarm(batch_sizes=(1, 32), ks=(10,))
    comps = LEDGER.snapshot()["components"]
    assert comps["pipeline_query_table"]["bytes"] == (
        pipe._cap * pipe._d_pad * 4)
    staged = sum(STAGING_DEPTH * b_pad * 4
                 for b_pad in pipe._state.staging)
    assert comps["pipeline_staging"]["bytes"] == staged


# ---------------------------------------------------------------------------
# copy-on-write refresh (delta epochs)


def test_refresh_swaps_table_without_recompiling(rng):
    """The delta epoch bump: same token, same compiled programs, same
    staging pools — only the device table (an ARGUMENT of the compiled
    call) changes, so results move and misses do not."""
    users, ret, pipe = _fixture(rng)
    pipe.prewarm(batch_sizes=(1, 8), ks=(5,))
    rows = np.array([3, 7], np.int32)
    v1, _ = pipe.topk_rows(rows, 5)
    misses0 = EXEC_CACHE.stats()["misses"]
    p2 = pipe.refresh(users * 2.0)
    v2, _ = p2.topk_rows(rows, 5)
    assert np.array_equal(v2, v1 * 2.0)  # x2 is exact in f32
    assert EXEC_CACHE.stats()["misses"] == misses0
    assert p2._token == pipe._token
    assert p2._state is pipe._state  # counters/pools continuous
    # the ORIGINAL still serves the old table (in-flight safety)
    v1_again, _ = pipe.topk_rows(rows, 5)
    assert np.array_equal(v1_again, v1)


def test_refresh_capacity_overgrowth_rebuilds(rng):
    """Appending past the headroom is the documented recompile: a fresh
    token (new executable family), larger capacity."""
    users, ret, pipe = _fixture(rng)
    grown = np.vstack([users] * 10)  # 600 rows >> cap 256
    p2 = pipe.refresh(grown)
    assert p2._token != pipe._token
    assert p2._cap > pipe._cap
    v, i = p2.topk_rows(np.array([599], np.int32), 3)
    lv, li = ret.topk(grown[599], 3)
    assert np.array_equal(v[0], lv) and np.array_equal(i[0], li)


def test_refresh_rejects_wrong_rank(rng):
    _, _, pipe = _fixture(rng)
    with pytest.raises(ValueError, match="refresh requires"):
        pipe.refresh(np.zeros((10, 99), np.float32))


def test_requires_retriever():
    with pytest.raises(ValueError, match="requires an attached retriever"):
        ServingPipeline(np.zeros((4, 8), np.float32), None)


# ---------------------------------------------------------------------------
# chaos: pipeline.swap


@pytest.mark.chaos
def test_hung_swap_holds_one_buffer_never_wedges_pool(rng):
    """ISSUE 16 resilience gate: a hung double-buffer handoff (chaos
    site ``pipeline.swap``) holds exactly ONE pinned buffer; concurrent
    dispatches keep serving through the second buffer (and transients
    past that), and release returns the full pool — degraded via the
    watchdog, never wedged."""
    users, ret, pipe = _fixture(rng)
    rows = np.array([1, 2, 3], np.int32)
    b_pad, _ = _query_shapes(3, 5, ret.n_total)
    pipe.topk_rows(rows, 5)  # warm the executable outside the chaos
    FAULTS.inject("pipeline.swap", "hang", times=1, max_hang_s=15)
    done = threading.Event()
    hung_out = {}

    def victim():
        hung_out["result"] = pipe.topk_rows(rows, 5)
        done.set()

    t = threading.Thread(target=victim, daemon=True)
    t.start()
    assert not done.wait(0.3), "pipeline.swap hang did not hold the batch"
    assert pipe.stats()["stagingFree"][b_pad] == STAGING_DEPTH - 1

    # healthy traffic flows around the hung handoff
    expected = ret.topk(users[rows], 5)
    for _ in range(3):
        v, i = pipe.topk_rows(rows, 5)
        assert np.array_equal(v, expected[0])
        assert np.array_equal(i, expected[1])

    FAULTS.release("pipeline.swap")
    assert done.wait(5), "released swap did not complete"
    t.join(5)
    v, i = hung_out["result"]
    assert np.array_equal(v, expected[0])  # the hung batch still answers
    assert pipe.stats()["stagingFree"][b_pad] == STAGING_DEPTH
    pipe.topk_rows(rows, 5)  # and the pool serves pinned again
    assert pipe.stats()["stagingFree"][b_pad] == STAGING_DEPTH


# ---------------------------------------------------------------------------
# the micro-batcher's gate (ISSUE 28): one depth, and the signal that opens it


def test_staging_depth_is_the_batchers_gate_depth():
    """One constant, owned down here: the batcher reads the pipeline's
    depth (it defines none of its own), and the pipeline imports nothing
    from the workflow layer."""
    import ast
    import inspect

    from predictionio_tpu.ops import pipeline
    from predictionio_tpu.workflow import microbatch

    assert microbatch.STAGING_DEPTH is pipeline.STAGING_DEPTH == 2
    assert not hasattr(microbatch, "DEVICE_QUEUE_DEPTH")
    imported = {node.module for node in ast.walk(
        ast.parse(inspect.getsource(pipeline)))
        if isinstance(node, ast.ImportFrom) and node.level == 2}
    assert not {m for m in imported if m.startswith("workflow")}


class _OwnPrograms:
    """A retriever with scoring programs of its own, as the pipeline
    sees one (ANN, sharded): served in gather mode."""

    def __init__(self, ret):
        self._ret = ret
        self.n_total, self.lane_dim = ret.n_total, ret.lane_dim

    def topk(self, q, k):
        return self._ret.topk(q, k)


@pytest.mark.parametrize("mode", ["fused", "gather"])
def test_device_step_end_is_signalled_once_where_in_device_falls(rng, mode):
    """``topk_rows`` tells the hook its caller installed that its device
    step is over: once a dispatch, on the calling thread, with the step
    out of the ``in_device`` count, before the result is scattered back;
    a muted hook (a batch with another step to come) hears nothing, and
    the reset gives the outer hook back."""
    from predictionio_tpu.ops.pipeline import (reset_step_end_hook,
                                               set_step_end_hook)

    users, ret, pipe = _fixture(rng)
    if mode == "gather":
        pipe = ServingPipeline(users, _OwnPrograms(ret))
    assert pipe.stats()["mode"] == mode
    rows = np.array([4, 9], np.int32)
    seen = []

    def hook():
        seen.append((threading.get_ident(), pipe._state.in_device))

    token = set_step_end_hook(hook)
    try:
        muted = set_step_end_hook(None)
        pipe.topk_rows(rows, 5)
        assert seen == []
        reset_step_end_hook(muted)
        vals, idx = pipe.topk_rows(rows, 5)
        assert len(seen) == 1  # signalled before topk_rows returned
    finally:
        reset_step_end_hook(token)
    assert seen == [(threading.get_ident(), 0)]
    assert np.array_equal(vals, ret.topk(users[rows], 5)[0])
    pipe.topk_rows(rows, 5)  # no hook installed: nothing to call
    assert len(seen) == 1


def test_batcher_gate_opens_at_the_pipelines_step_end_not_the_calls(rng):
    """End to end on the CPU: a batch_fn that serves through the
    pipeline and then lingers in its host work gives its place ahead of
    the device back when the device step ends."""
    import asyncio

    from predictionio_tpu.workflow.microbatch import MicroBatcher

    users, ret, pipe = _fixture(rng)
    linger = threading.Event()

    def batch_fn(queries):
        vals, _ = pipe.topk_rows(np.asarray(queries, np.int32), 3)
        assert linger.wait(10)  # a slow result_scatter
        return [("ok", float(v[0])) for v in vals]

    async def main():
        mb = MicroBatcher(batch_fn, max_batch=8, window_s=0.0,
                          max_inflight=8)
        tasks = [asyncio.create_task(mb.submit(u)) for u in (1, 2)]
        for _ in range(500):
            s = mb.stats()
            if s["inflight"] >= 1 and s["aheadOfDevice"] == 0:
                break
            await asyncio.sleep(0.01)
        assert s["inflight"] >= 1 and s["aheadOfDevice"] == 0
        linger.set()
        out = await asyncio.gather(*tasks)
        await mb.close()
        return out

    try:
        out = asyncio.new_event_loop().run_until_complete(main())
    finally:
        linger.set()
    expect = ret.topk(users[[1, 2]], 3)[0][:, 0]
    assert out == [float(v) for v in expect]


# ---------------------------------------------------------------------------
# the encoder seam: a model whose query vector is computed


class _MeanEncoder:
    """A toy encoder (the contract is in ops/pipeline.py's docstring):
    a position's state is the running mean of its history's item rows of
    a fixed table, so the reference is numpy's."""

    dense = False
    aux_name = "parity"
    passes = 1

    def __init__(self, table, max_len=6, budget=32):
        import jax.numpy as jnp

        self.dim, self.max_len, self.budget = table.shape[1], max_len, budget
        self.lattice = (16, 32)
        self.params = {"table": jnp.asarray(table)}

    def program(self, t_pad):
        def fn(stream, params):
            import jax.numpy as jnp

            tokens, seg, pos = stream
            same = (seg[:, None] == seg[None, :]) & (
                jnp.arange(t_pad)[None, :] <= jnp.arange(t_pad)[:, None])
            emb = params["table"][tokens]
            mean = (same[:, :, None] * emb[None]).sum(1) / (pos + 1)[:, None]
            return mean, pos % 2, jnp.int32(1)
        return fn


def _encoded_fixture(rng, n_users=40, n_items=50, dim=16):
    table = rng.standard_normal((n_items + 1, dim)).astype(np.float32)
    hist = np.zeros((n_users, 6), np.int32)
    for u in range(n_users):
        n = int(rng.integers(1, 7))
        hist[u, -n:] = rng.integers(1, n_items + 1, n)
    ret = DeviceRetriever(table[1:])
    enc = _MeanEncoder(table)
    return table, hist, ret, ServingPipeline(hist, ret, encoder=enc, ks=(8,))


def test_encoder_seam_scores_the_last_positions_states(rng):
    """rows -> histories -> the encoder's program -> the SAME fused
    program over the step's table: the answers are numpy's, in steps of
    at most the token budget, and the counters count tokens."""
    table, hist, ret, pipe = _encoded_fixture(rng)
    rows = np.arange(40, dtype=np.int32)
    vals, idx = pipe.topk_rows(rows, 4)
    want_q = np.stack([table[h[h > 0]].mean(0) for h in hist])
    scores = want_q @ table[1:].T
    want = np.argsort(-scores, axis=1, kind="stable")[:, :4]
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_allclose(
        vals, np.take_along_axis(scores, want, 1), rtol=1e-5, atol=1e-6)
    seq = pipe.stats()["sequence"]
    real = int((hist > 0).sum())
    assert seq["tokensReal"] == real and seq["rows"] == 40
    assert seq["steps"] >= -(-real // 32) and seq["steps"] == seq["loopPasses"]
    assert seq["tokensComputed"] <= 32 * seq["steps"]
    assert sum(seq["parity"]) == 40          # the encoder's aux, a row
    assert seq["attentionPairs"] == sum(
        n * (n + 1) // 2 for n in (hist > 0).sum(1).tolist())
    assert pipe.cost_budget == 32
    assert pipe.row_cost(3) == int((hist[3] > 0).sum())
    assert "sequence" not in _fixture(rng)[2].stats()
    assert _fixture(rng)[2].cost_budget is None


def test_encoder_prewarm_walks_tokens_then_rows_and_refresh_keeps_them(rng):
    _table, hist, _ret, pipe = _encoded_fixture(rng)
    warmed = pipe.prewarm(batch_sizes=(1, 8, 16))
    assert [w for w in warmed if w[1] == "encoder"] == [
        ("pipeline", "encoder", 16), ("pipeline", "encoder", 32)]
    assert [w for w in warmed if w[1] == "fused"] == [
        ("pipeline", "fused", 8, 8), ("pipeline", "fused", 16, 8)]
    before = EXEC_CACHE.stats()["misses"]
    pipe.topk_rows(np.arange(12, dtype=np.int32), 5)
    newer = pipe.refresh(np.roll(hist, 1, axis=0))
    vals, _idx = newer.topk_rows(np.arange(12, dtype=np.int32), 5)
    assert EXEC_CACHE.stats()["misses"] == before     # nothing compiled
    old, _idx = pipe.topk_rows(np.arange(11, dtype=np.int32), 5)
    np.testing.assert_allclose(vals[1:], old[:11], rtol=1e-6)
    assert newer.stats()["sequence"]["steps"] == pipe.stats()[
        "sequence"]["steps"]                          # shared counters


def test_encoder_needs_the_exact_single_device_retriever(rng):
    table, hist, _ret, _pipe = _encoded_fixture(rng)
    with pytest.raises(ValueError, match="exact single-device"):
        ServingPipeline(hist, _OwnPrograms(DeviceRetriever(table[1:])),
                        encoder=_MeanEncoder(table))
    with pytest.raises(ValueError, match="max_len"):
        ServingPipeline(hist[:, :4], DeviceRetriever(table[1:]),
                        encoder=_MeanEncoder(table))
