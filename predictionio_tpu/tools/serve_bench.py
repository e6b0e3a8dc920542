"""Sharded-serving sweep: QPS/p50 across mesh widths, one process.

``pio bench serve`` (tools/cli.py) runs this in a FRESH subprocess so the
device count can be forced (on CPU, ``--xla_force_host_platform_device_
count`` must be set before jax initializes). The first line printed
names the platform the sweep ran on.

Each row serves a fixed padded batch through ``ShardedDeviceRetriever``
after ``prewarm()`` (AOT executables pinned in EXEC_CACHE), so the timed
loop measures the serving path the engine server actually runs: compiled
call in, ONE packed host pull out, merge on device. The emitted row
records ``merge`` (the retriever's merge location contract) and the
executable-cache hit rate so a regression to recompile-per-call or a
host-side merge is visible in the numbers, not just the timings.
"""

from __future__ import annotations

import argparse
import time

import numpy as np

from ..obs.metrics import Histogram
from ..workflow.faults import FAULTS

__all__ = ["sweep", "ann_sweep", "clustered_items", "format_table", "main",
           "DEFAULT_WAYS", "DEFAULT_BATCH"]

DEFAULT_WAYS = (1, 2, 4, 8)
# B=128 is the micro-batcher's default ceiling (create_server batch_max)
DEFAULT_BATCH = 128

# The serving histograms' default table doubles per bucket — right for
# always-on telemetry, too coarse for committed benchmark numbers. The
# bench rows use the same Histogram machinery over a ~19%-step geometric
# table (10 us .. ~2.4 s), so interpolation error stays under one step.
_BENCH_BUCKETS_S = tuple(1e-5 * (2 ** 0.25) ** i for i in range(72))


def clustered_items(n_items: int, rank: int, *, batch: int = 0,
                    seed: int = 7, n_centers: int = 4096,
                    noise: float = 0.25):
    """Mixture-of-Gaussians item factors — the cluster structure trained
    embeddings exhibit (co-consumed items land near each other), and the
    structure an IVF index prunes against. Isotropic Gaussian catalogs
    are unprunable: every cell is equidistant from every query, so ANN
    numbers on them measure nothing.

    With ``batch`` > 0 also returns query vectors drawn from the SAME
    mixture: a trained user/query tower puts queries near the items they
    should retrieve, so in-distribution queries are the contract ANN
    recall is measured under (an isotropic query spreads its true top-k
    across many weakly-aligned cells and no index can prune for it)."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_centers, rank)).astype(np.float32)
    centers /= np.sqrt(rank)
    assign = rng.integers(0, n_centers, size=n_items)
    items = (centers[assign] + (noise / np.sqrt(rank)) * rng.normal(
        size=(n_items, rank)).astype(np.float32)).astype(np.float32)
    if not batch:
        return items
    qa = rng.integers(0, n_centers, size=batch)
    q = (centers[qa] + (noise / np.sqrt(rank)) * rng.normal(
        size=(batch, rank)).astype(np.float32)).astype(np.float32)
    return items, q


def _timed_rows(ret, q, *, batch, k, iters):
    """p50/p95/p99 + QPS of a batched topk through ``ret``, the same
    timed loop for every retriever flavor.

    ISSUE 11: the loop also runs with a stage-waterfall sink installed,
    so the retrieval path's ``mark_stage`` calls attribute each
    iteration's time to host_assembly / device_dispatch / device_compute
    / result_scatter — the row carries a ``stage_breakdown`` of mean ms
    per stage plus the host/device share split. The waterfall is local
    (never finished), so bench iterations stay out of the registry's
    serving histograms."""
    from ..obs.waterfall import (DEVICE_STAGES, STAGES, Waterfall,
                                 reset_stage_sink, set_stage_sink)

    hist = Histogram("pio_bench_serve_seconds",
                     "one batched topk round trip (device call + the "
                     "single packed host pull)", buckets=_BENCH_BUCKETS_S)
    wf = Waterfall(path="bench")
    token = set_stage_sink(wf)
    try:
        for _ in range(iters):
            # re-seat the cursor: time between iterations (loop
            # bookkeeping, FAULTS dispatch) must not leak into the
            # first marked stage of the next iteration
            wf.cursor()
            t0 = time.perf_counter()
            # chaos site: arm `slow` to model a degraded device under
            # generated load — the delay lands inside the timed window,
            # so it shows up in the emitted latency percentiles
            FAULTS.fire("loadgen.slow_device")
            vals, _ = ret.topk(q, k)
            np.asarray(vals)  # host fence: time includes the one pull
            hist.record(time.perf_counter() - t0)
    finally:
        reset_stage_sink(token)
    snap = hist.snapshot()
    total = sum(wf.stages.values())
    device = sum(wf.stages.get(s, 0.0) for s in DEVICE_STAGES)
    return {"p50_ms": snap["p50"] * 1e3, "p95_ms": snap["p95"] * 1e3,
            "p99_ms": snap["p99"] * 1e3,
            "qps": batch / max(snap["p50"], 1e-9),
            "stage_breakdown": {
                s: round(wf.stages[s] / max(iters, 1) * 1e3, 4)
                for s in STAGES if s in wf.stages},
            "host_share": round((total - device) / total, 4) if total else None,
            "device_share": round(device / total, 4) if total else None}


def _device_evidence(before: dict | None = None) -> dict:
    """ISSUE 12: the device ledger's compile/HBM stamp for a bench row.
    Without ``before``: the current absolute totals (a baseline).
    With ``before``: the delta since that baseline — what THIS row's
    retriever cost to compile and holds resident, so a row carries
    device-side evidence alongside qps."""
    from ..obs.device import COMPILE_HISTOGRAMS, LEDGER

    cur = {
        "compile_seconds": sum(h.snapshot()["sum"]
                               for h in COMPILE_HISTOGRAMS.values()),
        "hbm_bytes": LEDGER.snapshot()["totalBytes"],
    }
    if before is None:
        return cur
    return {
        "compile_seconds": round(
            cur["compile_seconds"] - before["compile_seconds"], 4),
        "hbm_bytes": int(cur["hbm_bytes"] - before["hbm_bytes"]),
    }


def _recall_at_k(approx_idx, exact_idx) -> float:
    """Mean fraction of the exact top-k the approximate top-k recovered."""
    hits = 0
    total = 0
    for a, e in zip(np.asarray(approx_idx), np.asarray(exact_idx)):
        e_set = set(int(i) for i in e if int(i) >= 0)
        if not e_set:
            continue
        hits += len(e_set & set(int(i) for i in a))
        total += len(e_set)
    return hits / max(total, 1)


def ann_sweep(*, n_items: int = 65_536, rank: int = 64,
              batch: int = DEFAULT_BATCH, k: int = 10, iters: int = 12,
              seed: int = 7, nprobe: int | None = None) -> list[dict]:
    """Exact-vs-ANN pair of rows over ONE clustered catalog: the exact
    brute-force baseline, then the quantized IVF index with its
    recall@k measured against that baseline (exact rows are recall 1.0
    by construction). Mesh width is irrelevant here — the index is a
    single-device program — so both rows report ways=1."""
    from ..ops.ann import DEFAULT_NPROBE, AnnRetriever
    from ..ops.retrieval import EXEC_CACHE, DeviceRetriever

    items, q = clustered_items(n_items, rank, batch=batch, seed=seed)

    dev0 = _device_evidence()
    exact = DeviceRetriever(items)
    exact.prewarm(batch_sizes=(batch,), ks=(k,))
    exact.topk(q, k)
    row_e = {"ways": 1, "mode": "exact", "recall_at_k": 1.0,
             "build_s": 0.0,
             **_timed_rows(exact, q, batch=batch, k=k, iters=iters),
             "merge": "exact", "exec_cache_hit_rate":
                 EXEC_CACHE.stats()["hitRate"],
             "batch": batch, "k": k, "n_items": n_items,
             **_device_evidence(dev0)}
    _, exact_idx = exact.topk(q, k)

    dev1 = _device_evidence()
    ann = AnnRetriever(items, nprobe=nprobe or DEFAULT_NPROBE,
                       min_items=0, seed=seed)
    ann.prewarm(batch_sizes=(batch,), ks=(k,))
    ann.topk(q, k)
    _, ann_idx = ann.topk(q, k)
    st = ann.stats()
    row_a = {"ways": 1, "mode": "ann",
             "recall_at_k": _recall_at_k(ann_idx, exact_idx),
             "build_s": st["indexBuildSeconds"],
             **_timed_rows(ann, q, batch=batch, k=k, iters=iters),
             "merge": f"ivf:{st['cells']}c/{st['lastEffectiveNprobe']}p",
             "exec_cache_hit_rate": EXEC_CACHE.stats()["hitRate"],
             "batch": batch, "k": k, "n_items": n_items,
             **_device_evidence(dev1)}
    return [row_e, row_a]


def sweep(ways=DEFAULT_WAYS, *, n_items: int = 65_536, rank: int = 64,
          batch: int = DEFAULT_BATCH, k: int = 10, iters: int = 12,
          seed: int = 7) -> list[dict]:
    """One row per mesh width: p50 latency + QPS for a batched topk.
    A width given as the string ``"auto"`` resolves through the
    catalog-size cost model (ops/retrieval.choose_shard_count) and its
    row is marked ``auto=True``."""
    import jax

    from ..ops.retrieval import (EXEC_CACHE, ShardedDeviceRetriever,
                                 choose_shard_count)
    from ..parallel.mesh import make_mesh

    ndev = len(jax.devices())
    int_ways = [w for w in ways if w != "auto"]
    if int_ways and ndev < max(int_ways):
        raise RuntimeError(
            f"sweep needs {max(int_ways)} devices, jax sees {ndev} — on "
            f"CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{max(int_ways)} before jax initializes (pio bench serve "
            f"does this for you)")
    resolved = [(choose_shard_count(n_items, ndev), True) if w == "auto"
                else (int(w), False) for w in ways]

    rng = np.random.default_rng(seed)
    items = (rng.normal(size=(n_items, rank)) / np.sqrt(rank)).astype(
        np.float32)
    q = (rng.normal(size=(batch, rank)) / np.sqrt(rank)).astype(np.float32)

    rows = []
    for w, auto in resolved:
        dev0 = _device_evidence()
        mesh = make_mesh((w,), ("model",))
        ret = ShardedDeviceRetriever(items, mesh)
        ret.prewarm(batch_sizes=(batch,), ks=(k,))
        ret.topk(q, k)  # warm the non-compile parts of the path too
        rows.append({
            "ways": w,
            "auto": auto,
            **_timed_rows(ret, q, batch=batch, k=k, iters=iters),
            "merge": ret.merge,
            "exec_cache_hit_rate": EXEC_CACHE.stats()["hitRate"],
            "batch": batch,
            "k": k,
            "n_items": n_items,
            **_device_evidence(dev0),
        })
    return rows


def format_table(rows: list[dict]) -> str:
    with_mode = any("mode" in r for r in rows)
    head = f"{'ways':>4}  "
    if with_mode:
        head += f"{'mode':>6}  {'recall@k':>8}  "
    head += f"{'p50_ms':>8}  {'p95_ms':>8}  {'p99_ms':>8}  " \
            f"{'qps':>8}  {'merge':>12}  {'cache_hit':>9}"
    lines = [head, "-" * len(head)]
    for r in rows:
        ways = f"{r['ways']}*" if r.get("auto") else str(r["ways"])
        line = f"{ways:>4}  "
        if with_mode:
            line += f"{r.get('mode', 'exact'):>6}  " \
                    f"{r.get('recall_at_k', 1.0):>8.4f}  "
        line += (f"{r['p50_ms']:>8.3f}  {r['p95_ms']:>8.3f}  "
                 f"{r['p99_ms']:>8.3f}  {r['qps']:>8.0f}  "
                 f"{str(r['merge']):>12}  {r['exec_cache_hit_rate']:>9.3f}")
        lines.append(line)
    if any(r.get("auto") for r in rows):
        lines.append("(* = width chosen by the catalog-size cost model)")
    if any(r.get("stage_breakdown") for r in rows):
        lines.append("stage breakdown (mean ms/iter; dev = "
                     "device_dispatch+device_compute share):")
        for r in rows:
            bd = r.get("stage_breakdown")
            if not bd:
                continue
            ways = f"{r['ways']}*" if r.get("auto") else str(r["ways"])
            label = ways + (f"/{r['mode']}" if "mode" in r else "")
            stages = "  ".join(f"{s}={ms:.3f}" for s, ms in bd.items())
            dev = r.get("device_share")
            share = f"  dev={dev:.0%}" if dev is not None else ""
            lines.append(f"  {label:>8}  {stages}{share}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="sharded-serving QPS/p50 sweep across mesh widths")
    p.add_argument("--ways", default=",".join(map(str, DEFAULT_WAYS)),
                   help="comma-separated mesh widths, e.g. 1,8 "
                        "('auto' = cost-model pick)")
    p.add_argument("--batch", type=int, default=DEFAULT_BATCH)
    p.add_argument("--k", type=int, default=10)
    p.add_argument("--iters", type=int, default=12)
    p.add_argument("--n-items", type=int, default=65_536)
    p.add_argument("--rank", type=int, default=64)
    p.add_argument("--retrieval", choices=["exact", "ann"], default="exact",
                   help="'ann' benches the quantized IVF index against "
                        "exact brute force on a clustered catalog")
    args = p.parse_args(argv)
    import jax

    devs = jax.devices()
    print(f"platform: {devs[0].platform} ({devs[0].device_kind}) "
          f"x{len(devs)}")
    if args.retrieval == "ann":
        rows = ann_sweep(n_items=args.n_items, rank=args.rank,
                         batch=args.batch, k=args.k, iters=args.iters)
    else:
        ways = tuple(w.strip() if w.strip().lower() == "auto"
                     else int(w) for w in args.ways.split(",") if w.strip())
        rows = sweep(ways, n_items=args.n_items, rank=args.rank,
                     batch=args.batch, k=args.k, iters=args.iters)
    print(format_table(rows))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
