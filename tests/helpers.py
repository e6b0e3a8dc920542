"""Shared test helpers."""

import asyncio
import threading
from datetime import datetime, timedelta, timezone


class ServerThread:
    """Run an aiohttp app on an ephemeral port in a daemon thread."""

    def __init__(self, app_factory, port=0):
        from aiohttp import web

        self._loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self.port = None

        async def _start():
            runner = web.AppRunner(app_factory())
            await runner.setup()
            # port=0 -> ephemeral; a fixed port lets a test "restart" a
            # replica at the same address (fleet rejoin scenarios)
            site = web.TCPSite(runner, "127.0.0.1", port)
            await site.start()
            self.port = runner.addresses[0][1]
            self._runner = runner
            self._ready.set()

        def _run():
            asyncio.set_event_loop(self._loop)
            self._loop.run_until_complete(_start())
            self._loop.run_forever()

        self._thread = threading.Thread(target=_run, daemon=True)
        self._thread.start()
        assert self._ready.wait(10)

    @property
    def url(self):
        return f"http://127.0.0.1:{self.port}"

    def stop(self):
        async def _stop():
            await self._runner.cleanup()
            self._loop.stop()

        asyncio.run_coroutine_threadsafe(_stop(), self._loop)
        self._thread.join(timeout=5)


# ---------------------------------------------------------------------------
# Shared fixtures for the property-folding and layout tests.

T0 = datetime(2020, 1, 1, tzinfo=timezone.utc)


def special(event, eid, props, minutes):
    """A $set/$unset/$delete event `minutes` past the shared T0 epoch —
    the LEventAggregatorSpec-style factory used by test_aggregate and
    test_properties."""
    from predictionio_tpu.storage import DataMap, Event

    return Event(
        event=event,
        entity_type="user",
        entity_id=eid,
        properties=DataMap(props),
        event_time=T0 + timedelta(minutes=minutes),
    )


def assert_layout_invariants(lay, other, vals, n):
    """The bilinear-layout per-side contract asserted by BOTH the
    deterministic no-loss test (test_als) and the hypothesis search
    (test_properties) — one home so the two cannot drift: nothing
    dropped, every entity exactly one in-range slot, neighbor ids in
    the other side's slot space with padding at its zero slot, chunked
    owner segments sorted, and the full value multiset preserved."""
    import numpy as np

    assert lay.dropped == 0
    assert len(set(lay.pos.tolist())) == len(lay.pos)
    assert lay.pos.max() < lay.slots
    got = []
    for b, m in zip(lay.buckets, lay.metas):
        assert b.ids.max() < other.slots
        if b.hot_ids is not None:
            # a split bucket's hot part: ids local to the other side's
            # hot slice, padded with the slice's last (always-zero) row;
            # same rows in the same order as the cold part
            assert lay.hot_rows and b.hot_ids.shape[:2] == b.ids.shape[:2]
            real = b.hot_vals != 0
            assert b.hot_ids.min() >= 0
            assert (b.hot_ids[real] < lay.hot_rows - 1).all()
            assert (b.hot_ids[~real] == lay.hot_rows - 1).all()
            got.append(b.hot_vals[real])
            # the cold part holds a neighbor of the slice only where the
            # row's hot part is full
            in_slice = ((b.ids >= lay.hot_lo)
                        & (b.ids < lay.hot_lo + lay.hot_rows - 1)
                        & (b.mask != 0))
            assert real.all(axis=-1)[in_slice.any(axis=-1)].all()
        # padding is defined by the explicit mask, not by vals == 0 —
        # a genuine zero-valued rating slot is REAL and must keep its
        # neighbor id (ADVICE r5; the builder nudges exact zeros, but
        # the invariant must not depend on that)
        assert (b.ids[b.mask == 0] == other.zero_slot).all()
        got.append(b.vals[b.mask != 0])
        if m.seg is not None:
            assert (np.diff(m.seg) >= 0).all()
            assert m.seg.max() < m.span
    assert sum(len(g) for g in got) == n
    np.testing.assert_allclose(np.sort(np.concatenate(got)), np.sort(vals))


#: gather costs that make the layout slice tables of a few hundred rows:
#: what ``neighbors.GATHER_NS_BY_TABLE_ROWS`` is patched to where a test
#: wants a split layout from a data set of test size
SMALL_HOT_SLICES = ((64, 4.0), (128, 4.1))


def zipf_coo(rng, nu=600, ni=400, n=24_000, exponent=0.9, sigma=1.2):
    """(users, items, vals): Zipf item popularity, log-normal user
    activity, integer ratings 1..5: the shape the hot slice is for."""
    import numpy as np

    p = 1.0 / np.arange(1, ni + 1) ** exponent
    items = rng.permutation(ni)[rng.choice(ni, n, p=p / p.sum())]
    w = np.exp(sigma * rng.standard_normal(nu))
    users = rng.choice(nu, n, p=w / w.sum())
    vals = rng.integers(1, 6, n).astype(np.float32)
    return users.astype(np.int64), items.astype(np.int64), vals
