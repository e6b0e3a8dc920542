"""Device ledger: per-executable XLA cost/memory accounting (ISSUE 12).

Every compile that lands in the shared ``ExecutableCache`` (fused top-k
kernels, XLA fallback programs, sharded retrievers, the ANN scorer, the
ALS fold-in solver) is analyzed here: ``cost_analysis()`` flops/bytes and
``memory_analysis()`` argument/output/temp sizes become a ledger entry,
so at any moment the ledger answers "how much HBM does this deployment
hold and in what?" — the accounting substrate the multi-engine A/B and
device-resident pipeline arcs need before N variants can share a device
pool (ALX, arXiv:2112.02194, attributes step time and memory per shard;
Google's ads-serving paper, arXiv:2501.10546, treats compile/memory
telemetry as a precondition for co-locating models).

Graceful degradation is a hard contract: cpu jaxlib builds may lack one
or both analyses (or return them in a different shape), so every probe
runs under ``try/except`` and a failed probe just flags the entry
``analysisUnavailable`` — telemetry must NEVER take down serving or
training. Accounting invariant (pinned by test_device_telemetry):
``pio_hbm_bytes{component}`` equals the sum of resident ledger entry
bytes per component; evicting a cache entry decrements the gauge by
exactly the entry's bytes.
"""

from __future__ import annotations

import dataclasses
import threading

from .metrics import METRICS

#: executable kinds = ExecutableCache key namespaces (key[0]). "other"
#: absorbs future namespaces without a registry change — compile
#: histograms are per-kind FAMILIES (the registry's histograms are
#: unlabeled), pre-registered from this fixed tuple so the doc-catalog
#: guard sees every concrete name at import time.
KINDS = ("kernel", "xla", "sharded", "ann", "fold_in", "pipeline", "other")

COMPILE_HISTOGRAMS = {
    k: METRICS.histogram(
        f"pio_xla_compile_{k}_seconds",
        f"wall time of one {k} executable build (trace+lower+compile) "
        "admitted to the ExecutableCache")
    for k in KINDS
}

_G_HBM = METRICS.gauge(
    "pio_hbm_bytes",
    "bytes resident on device per component, from each executable's "
    "memory_analysis (argument+output+temp+code) or tracked buffer "
    "sizes; decremented on cache evict",
    labelnames=("component",))

_G_HBM_WATERMARK = METRICS.gauge(
    "pio_hbm_watermark_bytes",
    "high-water mark of the summed pio_hbm_bytes ledger total since "
    "process start (or last reset)")

#: dispatch-level padding waste: (b_pad - b_orig) / b_pad per retrieval
#: dispatch. Ratio buckets, not time buckets; record() clamps values
#: <= bounds[0] into bucket 0, so a 0.0 (full bucket) observation is
#: well-defined.
_M_PADDING_WASTE = METRICS.histogram(
    "pio_dispatch_padding_waste_ratio",
    "fraction of each dispatched batch that is padding: "
    "(padded_batch - real_batch) / padded_batch",
    buckets=(1 / 64, 1 / 32, 1 / 16, 1 / 8, 1 / 4, 3 / 8, 1 / 2,
             5 / 8, 3 / 4, 7 / 8, 1.0))

_M_ANALYSIS_UNAVAILABLE = METRICS.counter(
    "pio_xla_analysis_unavailable_total",
    "executables whose cost/memory analysis probe failed (cpu jaxlib "
    "or incompatible executable shape) — flagged, never fatal")


def device_identity() -> dict:
    """The device this process holds, as JAX reports it — the three
    fields every result must carry so a host run is never read as a
    chip run (`pio train` stamps them into ``backend_conf``,
    ``/stats.json`` shows them in its ``device`` block)."""
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


@dataclasses.dataclass
class LedgerEntry:
    """One executable's accounting record. ``bytes`` fields come from
    ``memory_analysis``; flops/cost_bytes from ``cost_analysis``;
    either may be unavailable (``analysis_unavailable``)."""
    key: tuple
    kind: str
    compile_seconds: float = 0.0
    flops: float = 0.0
    cost_bytes: float = 0.0
    argument_bytes: int = 0
    output_bytes: int = 0
    temp_bytes: int = 0
    generated_code_bytes: int = 0
    analysis_unavailable: bool = False

    @property
    def total_bytes(self) -> int:
        return (self.argument_bytes + self.output_bytes
                + self.temp_bytes + self.generated_code_bytes)

    def describe(self) -> dict:
        return {
            "key": repr(self.key),
            "kind": self.kind,
            "compileSeconds": round(self.compile_seconds, 6),
            "flops": self.flops,
            "costBytes": self.cost_bytes,
            "argumentBytes": self.argument_bytes,
            "outputBytes": self.output_bytes,
            "tempBytes": self.temp_bytes,
            "generatedCodeBytes": self.generated_code_bytes,
            "totalBytes": self.total_bytes,
            "analysisUnavailable": self.analysis_unavailable,
        }


def _unwrap_executable(value):
    """Cache values are either a bare compiled executable or a
    ``(compiled, flag)`` tuple (the packing convention)."""
    if isinstance(value, tuple) and value:
        return value[0]
    return value


def _probe_cost(exe, entry: LedgerEntry) -> bool:
    """cost_analysis() → flops / bytes accessed. Returns False when the
    probe fails (entry untouched)."""
    try:
        cost = exe.cost_analysis()
        # some jaxlib versions wrap the per-computation dict in a list
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        if not isinstance(cost, dict):
            return False
        entry.flops = float(cost.get("flops", 0.0))
        entry.cost_bytes = float(cost.get("bytes accessed", 0.0))
        return True
    except Exception:
        return False


def _probe_memory(exe, entry: LedgerEntry) -> bool:
    """memory_analysis() → argument/output/temp/code sizes. Returns
    False when the probe fails (entry untouched)."""
    try:
        mem = exe.memory_analysis()
        if mem is None:
            return False
        entry.argument_bytes = int(
            getattr(mem, "argument_size_in_bytes", 0) or 0)
        entry.output_bytes = int(
            getattr(mem, "output_size_in_bytes", 0) or 0)
        entry.temp_bytes = int(getattr(mem, "temp_size_in_bytes", 0) or 0)
        entry.generated_code_bytes = int(
            getattr(mem, "generated_code_size_in_bytes", 0) or 0)
        return True
    except Exception:
        return False


class DeviceLedger:
    """Process-wide accounting of device-resident executables/buffers.

    Two-phase protocol mirroring ExecutableCache.get_or_build's locking:
    ``analyze`` runs OUTSIDE the cache lock (the analysis probes can be
    arbitrarily slow), ``admit``/``discard`` run inside it (cheap dict +
    gauge ops), so the ledger's residency view and the cache's never
    diverge. Lock order is strictly cache → ledger; the ledger never
    calls back into a cache."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: dict[tuple, LedgerEntry] = {}
        #: non-executable device buffers (e.g. the delta patch table),
        #: component -> bytes, set absolutely via track_buffer
        self._buffers: dict[str, int] = {}
        self._watermark = 0

    # -- compile accounting (ExecutableCache hook) --------------------

    def kind_of(self, key) -> str:
        k = key[0] if isinstance(key, tuple) and key else None
        return k if k in KINDS else "other"

    def analyze(self, key, value, compile_seconds: float) -> LedgerEntry:
        """Build a ledger entry for a freshly compiled cache value.
        Called OUTSIDE the cache lock. Never raises."""
        kind = self.kind_of(key)
        entry = LedgerEntry(key=key, kind=kind,
                            compile_seconds=float(compile_seconds))
        try:
            exe = _unwrap_executable(value)
            got_cost = _probe_cost(exe, entry)
            got_mem = _probe_memory(exe, entry)
            entry.analysis_unavailable = not (got_cost or got_mem)
        except Exception:
            entry.analysis_unavailable = True
        try:
            COMPILE_HISTOGRAMS[kind].record(entry.compile_seconds)
            if entry.analysis_unavailable:
                _M_ANALYSIS_UNAVAILABLE.inc()
        except Exception:
            pass
        return entry

    def admit(self, entry: LedgerEntry) -> None:
        """Record an entry as device-resident (call when its cache
        insert actually lands). Idempotent per key."""
        try:
            with self._lock:
                old = self._entries.get(entry.key)
                delta = entry.total_bytes - (old.total_bytes if old else 0)
                self._entries[entry.key] = entry
                self._bump_locked(entry.kind, delta)
        except Exception:
            pass

    def discard(self, key) -> None:
        """Drop a key's residency (cache evict). Unknown keys no-op."""
        try:
            with self._lock:
                entry = self._entries.pop(key, None)
                if entry is not None:
                    self._bump_locked(entry.kind, -entry.total_bytes)
        except Exception:
            pass

    def _bump_locked(self, component: str, delta: int) -> None:
        if delta:
            _G_HBM.labels(component=component).inc(delta)
        total = self._total_locked()
        if total > self._watermark:
            self._watermark = total
            _G_HBM_WATERMARK.set(float(total))

    def _total_locked(self) -> int:
        return (sum(e.total_bytes for e in self._entries.values())
                + sum(self._buffers.values()))

    # -- non-executable device buffers --------------------------------

    def track_buffer(self, component: str, nbytes: int) -> None:
        """Set a component's buffer residency ABSOLUTELY (the patch
        table is re-counted whole on every mutation — simpler and
        self-healing vs incremental deltas)."""
        try:
            with self._lock:
                old = self._buffers.get(component, 0)
                self._buffers[component] = int(nbytes)
                _G_HBM.set(float(nbytes), component=component)
                if int(nbytes) != old:
                    total = self._total_locked()
                    if total > self._watermark:
                        self._watermark = total
                        _G_HBM_WATERMARK.set(float(total))
        except Exception:
            pass

    # -- dispatch padding ----------------------------------------------

    def record_padding_waste(self, real: int, padded: int) -> None:
        """One retrieval dispatch padded ``real`` rows up to ``padded``.
        waste = (padded - real) / padded; a full bucket records 0.0."""
        try:
            if padded <= 0:
                return
            _M_PADDING_WASTE.record(max(0.0, (padded - real) / padded))
        except Exception:
            pass

    # -- views ---------------------------------------------------------

    def top_executables(self, n: int = 5) -> list[dict]:
        with self._lock:
            entries = sorted(self._entries.values(),
                             key=lambda e: e.total_bytes, reverse=True)
        return [e.describe() for e in entries[:n]]

    def entry_keys(self) -> set:
        with self._lock:
            return set(self._entries)

    def incident_brief(self) -> dict:
        """Compact block for flight-recorder incident files: the HBM
        watermark + top-5 executables by bytes — enough to triage an
        OOM-adjacent incident from the dump alone."""
        with self._lock:
            watermark = self._watermark
            total = self._total_locked()
        return {
            "totalBytes": total,
            "watermarkBytes": watermark,
            "topExecutables": self.top_executables(5),
        }

    def snapshot(self) -> dict:
        with self._lock:
            comps: dict[str, dict] = {}
            for e in self._entries.values():
                c = comps.setdefault(e.kind, {
                    "bytes": 0, "entries": 0, "analysisUnavailable": False})
                c["bytes"] += e.total_bytes
                c["entries"] += 1
                c["analysisUnavailable"] |= e.analysis_unavailable
            for comp, nbytes in self._buffers.items():
                c = comps.setdefault(comp, {
                    "bytes": 0, "entries": 0, "analysisUnavailable": False})
                c["bytes"] += nbytes
            total = self._total_locked()
            watermark = self._watermark
            top = sorted(self._entries.values(),
                         key=lambda e: e.total_bytes, reverse=True)[:5]
        snap = {
            "components": comps,
            "totalBytes": total,
            "watermarkBytes": watermark,
            "topExecutables": [e.describe() for e in top],
            "paddingWaste": _M_PADDING_WASTE.snapshot(),
            "compile": {k: h.snapshot()
                        for k, h in COMPILE_HISTOGRAMS.items()
                        if h.snapshot()["count"]},
        }
        return snap

    def reset(self) -> None:
        with self._lock:
            self._entries.clear()
            self._buffers.clear()
            self._watermark = 0


#: process-wide singleton, mirroring METRICS / FLIGHT
LEDGER = DeviceLedger()
