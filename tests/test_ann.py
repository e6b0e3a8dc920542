"""Quantized ANN retrieval (ops/ann.py) + the adaptive shard-count cost
model (ISSUE 7): the parity/fallback contracts that make `mode: ann` safe
to deploy, the probe-budget scaling the brownout clamp rides on, and the
cost model that closes the r5 8-way inversion.

All marked ``retrieval`` (select with -m retrieval); chaos-marked tests
additionally ride the conftest chaos guard (fault cleanup + SIGALRM).
"""

import numpy as np
import pytest

from predictionio_tpu.ops.ann import (ANN_MIN_ITEMS, AnnRetriever,
                                      DEFAULT_NPROBE, build_index,
                                      effective_nprobe, pick_cells)
from predictionio_tpu.ops.retrieval import DeviceRetriever, choose_shard_count

pytestmark = pytest.mark.retrieval


def _clustered(rng, n, d, n_centers=64, noise=0.25, batch=0):
    """Mixture-of-Gaussians factors — the structure an IVF index prunes
    against (isotropic catalogs are unprunable, so they test nothing).
    With ``batch``, queries come from the SAME mixture: trained query
    towers put queries near their items, and that in-distribution
    contract is what ANN recall is measured under."""
    centers = rng.standard_normal((n_centers, d)).astype(np.float32)
    centers /= np.sqrt(d)
    items = (centers[rng.integers(0, n_centers, size=n)]
             + (noise / np.sqrt(d))
             * rng.standard_normal((n, d))).astype(np.float32)
    if not batch:
        return items
    q = (centers[rng.integers(0, n_centers, size=batch)]
         + (noise / np.sqrt(d))
         * rng.standard_normal((batch, d))).astype(np.float32)
    return items, q


# ---------------------------------------------------------------------------
# parity edges
# ---------------------------------------------------------------------------

def test_full_cover_probe_is_bitwise_exact(rng):
    """nprobe >= n_cells must DELEGATE to the exact compiled program —
    bit-for-bit equal to DeviceRetriever, not merely allclose (the
    gathered rescore is a different XLA program, so delegation is the
    only way to honor the exactness contract)."""
    items = _clustered(rng, 2_000, 16)
    q = rng.standard_normal((9, 16)).astype(np.float32)
    ev, ei = DeviceRetriever(items).topk(q, 10)
    ann = AnnRetriever(items, nprobe=8, n_cells=8, min_items=0)
    av, ai = ann.topk(q, 10)
    assert np.array_equal(np.asarray(ai), np.asarray(ei))
    assert np.array_equal(np.asarray(av), np.asarray(ev))


def test_ann_recall_and_value_consistency(rng):
    """A true pruned probe (eff < n_cells) on clustered data: recall@10
    stays high and every returned value IS the dot product of the query
    with the row its index names (no score/index skew)."""
    items, q = _clustered(rng, 20_000, 32, n_centers=32, batch=16)
    ev, ei = DeviceRetriever(items).topk(q, 10)
    ann = AnnRetriever(items, nprobe=24, n_cells=32, min_items=0)
    av, ai = ann.topk(q, 10)
    assert ann.last_effective_nprobe < 32  # really pruned, not delegated
    recall = np.mean([len(set(a) & set(e)) / 10
                      for a, e in zip(np.asarray(ai), np.asarray(ei))])
    assert recall >= 0.9, recall
    av, ai = np.asarray(av), np.asarray(ai)
    np.testing.assert_allclose(
        av, np.take_along_axis(q @ items.T, ai, axis=1), rtol=1e-5,
        atol=1e-6)


def test_small_catalog_falls_back_to_exact(rng):
    """Below min_items no index is built — the retriever IS the exact
    one, and says so in stats()."""
    items = rng.standard_normal((100, 8)).astype(np.float32)
    q = rng.standard_normal((4, 8)).astype(np.float32)
    ann = AnnRetriever(items)  # default ANN_MIN_ITEMS floor
    st = ann.stats()
    assert st["exactFallback"] and st["fallbackReason"] == "small_catalog"
    assert st["cells"] == 0 and ann.index is None
    ev, ei = DeviceRetriever(items).topk(q, 5)
    av, ai = ann.topk(q, 5)
    assert np.array_equal(np.asarray(ai), np.asarray(ei))
    assert np.array_equal(np.asarray(av), np.asarray(ev))


@pytest.mark.chaos
def test_edge_shapes_route_through_dispatch(rng):
    """k > N and the single-vector query must flow through the shared
    _dispatch_topk entry (proven by arming its chaos site), with the
    exact path's -1/-inf padding contract."""
    from predictionio_tpu.faults import FAULTS, FaultInjected

    items = _clustered(rng, 20_000, 16)
    ann = AnnRetriever(items, nprobe=4, n_cells=64, min_items=0)
    FAULTS.inject("retrieval.topk", "error", times=1)
    with pytest.raises(FaultInjected):
        ann.topk(rng.standard_normal((2, 16)).astype(np.float32), 10)
    FAULTS.clear()
    # k > N clamps to the catalog and pads the tail with -1 ids
    few = AnnRetriever(items[:30], min_items=0, n_cells=4, nprobe=2)
    v, i = few.topk(rng.standard_normal(16).astype(np.float32), 40)
    assert np.asarray(v).shape == (30,) and np.asarray(i).shape == (30,)
    # single-vector unwrap: 1-D in, 1-D out
    v1, i1 = ann.topk(rng.standard_normal(16).astype(np.float32), 5)
    assert np.asarray(v1).shape == (5,)
    # empty catalog: the 0-row contract of the shared dispatch holds
    empty = AnnRetriever(np.zeros((0, 16), np.float32))
    v0, i0 = empty.topk(rng.standard_normal((2, 16)).astype(np.float32), 5)
    assert np.asarray(v0).shape == (2, 0) and np.asarray(i0).shape == (2, 0)


# ---------------------------------------------------------------------------
# probe budget / brownout coupling
# ---------------------------------------------------------------------------

def test_effective_nprobe_contract():
    # frozen bench calibration point: nprobe=52 at k_pad=16 probes 26
    assert effective_nprobe(52, 16, 512, 1024) == 26
    # monotone in k, capped at the configured budget
    effs = [effective_nprobe(52, k, 512, 1024) for k in (8, 16, 64, 256)]
    assert effs == sorted(effs) and max(effs) <= 52
    assert effective_nprobe(52, 64, 512, 1024) == 52
    # full cover is never reduced — it is the exactness contract
    assert effective_nprobe(512, 8, 512, 1024) == 512
    assert effective_nprobe(9_999, 8, 512, 1024) == 512
    # the floor: enough probed rows to hold k results
    assert effective_nprobe(40, 256, 500, 16) >= 16


def test_k_floor_overrides_nprobe_cap(rng):
    """When ceil(k_pad / cell_len) exceeds nprobe the floor must WIN —
    the compiled program calls top_k(candidates, k_pad), so an
    under-gathered buffer is a shape error on the serving path, not a
    recall trade. When the floor covers every cell, full-cover
    delegation to exact takes over."""
    # floor beats the configured cap (2 > nprobe=1; 55 > nprobe=52)
    assert effective_nprobe(1, 30, 4, 16) == 2
    assert effective_nprobe(52, 14_000, 64, 256) == 55
    # floor reaching n_cells means full cover -> exact delegate
    assert effective_nprobe(1, 1_000, 4, 16) == 4
    # end to end (the review repro): 30 items, 4 cells, nprobe=1, k=40
    # used to raise inside lax.top_k; it must serve like any valid query
    items = _clustered(rng, 100, 16)[:30]
    ann = AnnRetriever(items, min_items=0, n_cells=4, nprobe=1)
    v, i = ann.topk(rng.standard_normal(16).astype(np.float32), 40)
    v, i = np.asarray(v), np.asarray(i)
    assert v.shape == (30,) and i.shape == (30,)
    assert ann.last_effective_nprobe < 4  # a real probe, not a delegate
    got = i[i >= 0]
    assert len(got) == len(set(got)) > 0  # valid, deduplicated ids


def test_brownout_clamp_shrinks_probe_work(rng):
    """Satellite 1: the PR-6 brownout top-k clamp must reduce ANN
    rescore work (fewer probed cells), not post-hoc truncate a full
    result. 100 -> 10 through EngineServer.brownout_degrade, then the
    probe budget at the clamped k is strictly smaller."""
    from types import SimpleNamespace

    from predictionio_tpu.workflow.create_server import EngineServer

    srv = SimpleNamespace(_mode="brownout", brownout_topk=10)
    q = {"user": "u1", "num": 100}
    clamped = EngineServer.brownout_degrade(srv, q)
    assert clamped["num"] == 10

    items = _clustered(rng, 30_000, 16)
    ann = AnnRetriever(items, nprobe=48, n_cells=128, min_items=0)
    ann.topk(rng.standard_normal((4, 16)).astype(np.float32), 100)
    eff_full = ann.last_effective_nprobe
    ann.topk(rng.standard_normal((4, 16)).astype(np.float32), clamped["num"])
    eff_clamped = ann.last_effective_nprobe
    assert eff_clamped < eff_full


# ---------------------------------------------------------------------------
# chaos: failed build degrades, never fails
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_failed_index_build_degrades_to_exact(rng):
    from predictionio_tpu.obs.metrics import METRICS
    from predictionio_tpu.faults import FAULTS, SITES

    assert "retrieval.ann_build" in SITES
    items = _clustered(rng, 20_000, 16)
    q = rng.standard_normal((3, 16)).astype(np.float32)
    FAULTS.inject("retrieval.ann_build", "error", times=1)
    ann = AnnRetriever(items, min_items=0)  # build fires the fault
    assert FAULTS.fired("retrieval.ann_build") == 1
    st = ann.stats()
    assert st["exactFallback"]
    assert str(st["fallbackReason"]).startswith("build_failed")
    ev, ei = DeviceRetriever(items).topk(q, 5)
    av, ai = ann.topk(q, 5)
    assert np.array_equal(np.asarray(ai), np.asarray(ei))
    text = METRICS.render_prometheus()
    assert "pio_retrieval_exact_fallback 1" in text


def test_index_metrics_and_stats(rng):
    """Satellite 3: the index is scrapeable — cells / dtype / build
    seconds / fallback land in pio_retrieval_* and stats()."""
    from predictionio_tpu.obs.metrics import METRICS

    items = _clustered(rng, 20_000, 16)
    ann = AnnRetriever(items, nprobe=8, n_cells=64, min_items=0)
    st = ann.stats()
    assert st["mode"] == "ann" and st["cells"] == 64
    assert st["quantize"] == "int8" and st["indexBuildSeconds"] >= 0
    ann.topk(rng.standard_normal((2, 16)).astype(np.float32), 10)
    text = METRICS.render_prometheus()
    assert "pio_retrieval_index_cells 64" in text
    assert 'pio_retrieval_index_dtype{dtype="int8"} 1' in text
    assert "pio_retrieval_exact_fallback 0" in text
    assert "pio_retrieval_index_build_seconds_count 1" in text
    assert 'pio_retrieval_queries_total{mode="ann"}' in text


def test_bf16_quantization_mode(rng):
    items = _clustered(rng, 20_000, 16)
    ix = build_index(items, n_cells=32, quantize="bf16")
    assert ix.centroids.dtype.name == "bfloat16"
    assert np.all(ix.scales == 1.0)
    ann = AnnRetriever(items, nprobe=8, n_cells=32, min_items=0,
                       quantize="bf16")
    v, i = ann.topk(rng.standard_normal((2, 16)).astype(np.float32), 5)
    assert np.asarray(i).shape == (2, 5)
    with pytest.raises(ValueError):
        build_index(items, quantize="fp4")


# ---------------------------------------------------------------------------
# adaptive shard count
# ---------------------------------------------------------------------------

def test_choose_shard_count_cost_model():
    """The r5 inversion closure: at the committed bench's 64k (and the
    262k ANN gate size) the model picks the UNSHARDED program — the
    cross-shard merge costs more rows than sharding saves — and only
    goes wide when the per-shard scan dominates the merge."""
    assert choose_shard_count(65_536, 8) == 1
    assert choose_shard_count(262_144, 8) == 1
    assert choose_shard_count(6_000_000, 8) == 8
    # never exceeds the device count, powers of two only
    assert choose_shard_count(6_000_000, 4) == 4
    assert choose_shard_count(6_000_000, 1) == 1
    assert choose_shard_count(0, 8) == 1


def test_deployed_auto_mesh_and_ann_attach(rng):
    """Deployed wiring: retrieval={'mode': 'ann'} attaches an
    AnnRetriever (ANN outranks a configured mesh); retriever_mesh='auto'
    resolves through the cost model (64k rows -> 1-way -> host scoring
    stays the exact baseline on CPU)."""
    from types import SimpleNamespace

    from predictionio_tpu.ops.retrieval import RetrievalServingMixin
    from predictionio_tpu.storage.bimap import BiMap
    from predictionio_tpu.workflow.create_server import Deployed

    class M(RetrievalServingMixin):
        pass

    m = M()
    m.item_factors = _clustered(rng, 2_048, 8)
    m.item_ids = BiMap.from_iterable(f"i{j}" for j in range(2_048))
    d = Deployed(None, SimpleNamespace(models=[m]),
                 retrieval={"mode": "ann", "min_items": 0, "n_cells": 16,
                            "nprobe": 4})
    assert isinstance(m._retriever, AnnRetriever)
    assert d.retrieval["mode"] == "ann"
    q = rng.standard_normal(8).astype(np.float32)
    got = m.top_n_from_catalog(q, 5)
    assert len(got) == 5
    # serialization still drops the device handle
    assert "_retriever" not in m.__getstate__()

    m2 = M()
    m2.item_factors = m.item_factors
    m2.item_ids = m.item_ids
    d2 = Deployed(None, SimpleNamespace(models=[m2]), retriever_mesh="auto")
    # cost model says 1-way at 2k rows; the server (ISSUE 16) serves the
    # compiled exact program on EVERY backend, CPU included
    assert isinstance(getattr(m2, "_retriever", None), DeviceRetriever)
