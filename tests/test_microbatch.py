"""Micro-batching query dispatcher (workflow/microbatch.py) + the batched
serving path (EngineServer.serve_query_batch, template batch_predict
overrides). SURVEY §7 hard part (f): fixed-shape batched TPU calls under
concurrent load without recompilation."""

from __future__ import annotations

import asyncio

import numpy as np
import pytest

from predictionio_tpu.ops.pipeline import STAGING_DEPTH, device_step_ended
from predictionio_tpu.workflow.microbatch import MicroBatcher


def run(coro):
    return asyncio.new_event_loop().run_until_complete(coro)


class _Peak:
    """``with peak:`` around a batch_fn's body: the most calls that were
    inside it at once."""

    def __init__(self):
        import threading

        self._lock = threading.Lock()
        self.live = self.peak = 0

    def __enter__(self):
        with self._lock:
            self.live += 1
            self.peak = max(self.peak, self.live)

    def __exit__(self, *exc):
        with self._lock:
            self.live -= 1


class TestMicroBatcher:
    def test_coalesces_concurrent_submissions(self):
        calls = []

        def batch_fn(queries):
            calls.append(len(queries))
            return [("ok", q * 2) for q in queries]

        async def main():
            mb = MicroBatcher(batch_fn, max_batch=64, window_s=0.01)
            results = await asyncio.gather(*[mb.submit(i) for i in range(20)])
            await mb.close()
            return results

        results = run(main())
        assert results == [i * 2 for i in range(20)]
        assert max(calls) > 1  # actually batched
        assert sum(calls) == 20

    def test_respects_max_batch(self):
        calls = []

        def batch_fn(queries):
            calls.append(len(queries))
            return [("ok", q) for q in queries]

        async def main():
            mb = MicroBatcher(batch_fn, max_batch=4, window_s=0.01)
            out = await asyncio.gather(*[mb.submit(i) for i in range(10)])
            await mb.close()
            return out

        assert run(main()) == list(range(10))
        assert max(calls) <= 4

    def test_per_query_error_isolation(self):
        def batch_fn(queries):
            return [("err", ValueError(f"bad {q}")) if q == 3 else ("ok", q)
                    for q in queries]

        async def main():
            mb = MicroBatcher(batch_fn, max_batch=64, window_s=0.005)
            futs = await asyncio.gather(
                *[mb.submit(i) for i in range(6)], return_exceptions=True)
            await mb.close()
            return futs

        out = run(main())
        assert out[3].__class__ is ValueError
        assert [o for i, o in enumerate(out) if i != 3] == [0, 1, 2, 4, 5]

    def test_batch_level_failure_rejects_all(self):
        def batch_fn(queries):
            raise RuntimeError("device gone")

        async def main():
            mb = MicroBatcher(batch_fn, window_s=0.001)
            return await asyncio.gather(
                *[mb.submit(i) for i in range(3)], return_exceptions=True)

        out = run(main())
        assert all(isinstance(o, RuntimeError) for o in out)

    def test_stats(self):
        def batch_fn(queries):
            return [("ok", q) for q in queries]

        async def main():
            mb = MicroBatcher(batch_fn, window_s=0.005)
            await asyncio.gather(*[mb.submit(i) for i in range(8)])
            s = mb.stats()
            await mb.close()
            return s

        s = run(main())
        assert s["batchedQueries"] == 8
        assert s["avgBatchSize"] >= 1.0

    @pytest.mark.parametrize("signals,max_inflight,want_peak", [
        # a plain callable: the whole call is its device step, so two
        # calls overlap and never a third, whatever the thread bound
        (False, 4, STAGING_DEPTH),
        # a batch_fn that reports its device step's end: the host work
        # after it holds no place in the gate, so calls overlap up to the
        # thread bound
        (True, 4, 4),
        (True, 3, 3),
        # the thread bound below the gate's depth: it binds alone
        (False, 1, 1),
    ])
    def test_calls_overlap_up_to_gate_and_thread_bound(
            self, signals, max_inflight, want_peak):
        """Batch N+1 dispatches while batch N is in the air, and no more
        batch_fn calls run at once than the gate and ``max_inflight``
        allow (was: test_pipelines_batches_concurrently,
        test_inflight_bounded)."""
        import time

        calls = _Peak()

        def slow_batch(queries):
            with calls:
                time.sleep(0.01)  # the "device step"
                if signals:
                    device_step_ended()
                time.sleep(0.04)  # host work after it
            return [("ok", q) for q in queries]

        async def main():
            mb = MicroBatcher(slow_batch, max_batch=2, window_s=0.0,
                              max_inflight=max_inflight)
            out = await asyncio.gather(*[mb.submit(i) for i in range(16)])
            s = mb.stats()
            await mb.close()
            return out, s

        out, s = run(main())
        assert out == list(range(16))
        assert calls.peak == want_peak
        assert s["peakInflight"] == want_peak
        assert s["aheadOfDevice"] == 0 and s["inflight"] == 0

    def test_out_of_order_completion_resolves_correct_futures(self):
        """Batch completions landing out of order must still resolve each
        query's own future (and per-query isolation must hold across
        concurrent batches)."""
        import time

        def batch_fn(queries):
            # later batches (higher values) finish FIRST
            time.sleep(0.08 - 0.02 * (queries[0] // 2))
            return [("err", ValueError(str(q))) if q == 5 else ("ok", q * 10)
                    for q in queries]

        async def main():
            mb = MicroBatcher(batch_fn, max_batch=2, window_s=0.0,
                              max_inflight=4)
            return await asyncio.gather(
                *[mb.submit(i) for i in range(8)], return_exceptions=True)

        out = run(main())
        assert isinstance(out[5], ValueError) and str(out[5]) == "5"
        assert [o for i, o in enumerate(out) if i != 5] == \
            [i * 10 for i in range(8) if i != 5]

    def test_submit_during_close_sheds_not_resurrects(self):
        """A submit() racing a mid-drain close() must raise ServerBusy —
        not resurrect a fresh worker generation that close() then cancels
        (or leaks)."""
        import threading

        from predictionio_tpu.workflow.microbatch import ServerBusy

        release = threading.Event()

        def slow_batch(queries):
            release.wait(2)
            return [("ok", q) for q in queries]

        async def main():
            mb = MicroBatcher(slow_batch, max_batch=4, window_s=0.0)
            t = asyncio.create_task(mb.submit(1))
            while not mb._inflight:
                await asyncio.sleep(0.005)
            closer = asyncio.create_task(mb.close())
            await asyncio.sleep(0.02)  # close() is awaiting the in-flight
            with __import__("pytest").raises(ServerBusy):
                await mb.submit(2)
            release.set()
            await closer
            assert await t == 1
            # after close completes, the batcher is restartable
            assert await mb.submit(3) == 3
            await mb.close()

        run(main())

    def test_close_waits_for_inflight(self):
        """close() must let already-dispatched batches resolve their
        futures (their queries left the queue; callers are awaiting)."""
        import threading
        import time

        release = threading.Event()

        def slow_batch(queries):
            release.wait(2)
            return [("ok", q) for q in queries]

        async def main():
            mb = MicroBatcher(slow_batch, max_batch=4, window_s=0.0)
            t = asyncio.create_task(mb.submit(7))
            while not mb._inflight:  # dispatched, now in the air
                await asyncio.sleep(0.005)
            closer = asyncio.create_task(mb.close())
            await asyncio.sleep(0.02)
            release.set()
            await closer
            return await t

        assert run(main()) == 7



class _Steps:
    """A batch_fn whose device step is a controllable event: call ``i``
    stays in its device step until ``end_step(i)``, then (if it
    ``signals``) reports the step's end, then stays in its host work
    until ``end_call(i)``."""

    def __init__(self, signals: bool = True, hold_calls: bool = False):
        import threading

        self.signals = signals
        self.hold_calls = hold_calls
        self.lock = threading.Lock()
        self.calls: list[list] = []
        self._steps: list = []
        self._ends: list = []
        self._Event = threading.Event

    def __call__(self, queries):
        step, end = self._Event(), self._Event()
        with self.lock:
            self.calls.append(list(queries))
            self._steps.append(step)
            self._ends.append(end)
        assert step.wait(10), "test never ended this device step"
        if self.signals:
            device_step_ended()
        if self.hold_calls:
            assert end.wait(10), "test never ended this call"
        return [("ok", q) for q in queries]

    def end_step(self, i):
        self._steps[i].set()

    def end_call(self, i):
        self._ends[i].set()

    def end_all(self):
        with self.lock:
            for ev in self._steps + self._ends:
                ev.set()

    async def cut(self, n, timeout_s=5.0):
        """Wait until ``n`` batches have been cut and reached batch_fn."""
        import time

        t_end = time.monotonic() + timeout_s
        while len(self.calls) < n:
            assert time.monotonic() < t_end, \
                f"{len(self.calls)} batches cut, waited for {n}"
            await asyncio.sleep(0.002)


class TestDeviceGate:
    """ISSUE 28: a batch is cut only while fewer than STAGING_DEPTH
    cut batches are short of the end of their device step."""

    def test_no_third_cut_and_arrivals_join_the_next_batch(self):
        """(a) + (f): with two device steps outstanding no third batch
        is cut; what arrives meanwhile lands in the ONE batch cut when a
        step ends, and that cut counts as held."""
        fn = _Steps()

        async def main():
            mb = MicroBatcher(fn, max_batch=64, window_s=0.0,
                              max_inflight=8)
            tasks = [asyncio.create_task(mb.submit(0))]
            await fn.cut(1)
            tasks.append(asyncio.create_task(mb.submit(1)))
            await fn.cut(2)
            for q in (2, 3, 4):
                tasks.append(asyncio.create_task(mb.submit(q)))
                await asyncio.sleep(0.01)
            await asyncio.sleep(0.05)
            assert len(fn.calls) == 2, "a third batch was cut"
            s = mb.stats()
            # the gate is shut, and the thread bound's reading (what the
            # admission controller is given) says two of eight
            assert s["aheadOfDevice"] == 2 and s["occupancy"] == 0.25
            assert len(mb._pending) == 3
            fn.end_step(0)  # the running step ends: the gate opens
            await fn.cut(3)
            assert fn.calls[2] == [2, 3, 4]
            fn.end_all()
            out = await asyncio.gather(*tasks)
            s = mb.stats()
            await mb.close()
            return out, s

        try:
            out, s = run(main())
        finally:
            fn.end_all()
        assert out == [0, 1, 2, 3, 4]
        assert s["batches"] == 3 and s["cutsHeld"] == 1
        assert s["aheadOfDevice"] == 0

    def test_lone_query_on_idle_batcher_is_cut_at_once(self):
        """(b): the gate is open on an idle server, adaptive or not."""
        fn = _Steps()

        async def main(adaptive):
            mb = MicroBatcher(fn, window_s=5.0 if adaptive else 0.0,
                              adaptive=adaptive)
            n = len(fn.calls)
            t = asyncio.create_task(mb.submit("q"))
            await fn.cut(n + 1, timeout_s=1.0)  # cut with nothing ended
            fn.end_all()
            assert await t == "q"
            s = mb.stats()
            await mb.close()
            return s

        for adaptive in (False, True):
            s = run(main(adaptive))
            assert s["cutsHeld"] == 0 and s["batches"] == 1

    def test_gate_opens_on_step_end_not_call_end_without_polling(self):
        """(c): two calls whose device steps have ended but whose host
        work (a slow result_scatter) has not hold no place: later
        batches are cut past them. And while the gate is closed the
        formation loop sleeps on ONE wait: the step's end wakes it."""
        fn = _Steps(hold_calls=True)

        class CountingEvent(asyncio.Event):
            waits = 0

            async def wait(self):
                self.waits += 1
                return await super().wait()

        async def main():
            mb = MicroBatcher(fn, max_batch=64, window_s=0.0,
                              max_inflight=8)
            tasks = [asyncio.create_task(mb.submit(0))]
            await fn.cut(1)
            gate = mb._gate = CountingEvent()
            tasks.append(asyncio.create_task(mb.submit(1)))
            await fn.cut(2)
            tasks.append(asyncio.create_task(mb.submit(2)))
            await asyncio.sleep(0.1)  # gate closed all this while
            assert len(fn.calls) == 2 and gate.waits == 1
            fn.end_step(0)
            fn.end_step(1)
            await fn.cut(3)  # cut though calls 0 and 1 have not returned
            assert gate.waits == 1
            s = mb.stats()
            assert s["inflight"] == 3 and s["aheadOfDevice"] == 1
            tasks.append(asyncio.create_task(mb.submit(3)))
            await fn.cut(4)  # and a fourth: only batch 2 is ahead
            assert mb.stats()["inflight"] == 4
            fn.end_all()
            out = await asyncio.gather(*tasks)
            await mb.close()
            return out

        try:
            assert run(main()) == [0, 1, 2, 3]
        finally:
            fn.end_all()

    def test_plain_callable_is_gated_on_the_whole_call(self):
        """(d): with no signal from inside, the call's end is the
        device step's end."""
        fn = _Steps(signals=False, hold_calls=True)

        async def main():
            mb = MicroBatcher(fn, max_batch=64, window_s=0.0,
                              max_inflight=8)
            tasks = []
            for q in (0, 1):
                tasks.append(asyncio.create_task(mb.submit(q)))
                await fn.cut(q + 1)
            tasks.append(asyncio.create_task(mb.submit(2)))
            fn.end_step(0)
            fn.end_step(1)  # "device steps" over, but nothing said so
            await asyncio.sleep(0.05)
            assert len(fn.calls) == 2 and mb.stats()["aheadOfDevice"] == 2
            fn.end_call(1)  # a call returns: its place is free
            await fn.cut(3)
            fn.end_all()
            out = await asyncio.gather(*tasks)
            s = mb.stats()
            await mb.close()
            return out, s

        try:
            out, s = run(main())
        finally:
            fn.end_all()
        assert out == [0, 1, 2] and s["cutsHeld"] == 1

    def test_hung_batches_trip_watchdog_and_free_the_gate(self):
        """(e): two hung device steps close the gate; the watchdog 504s
        them and frees both places, so the held batch is served; the
        thread bound still halves and restores."""
        from predictionio_tpu.workflow.microbatch import DispatchTimeout

        fn = _Steps()

        async def main():
            trips = []
            mb = MicroBatcher(fn, max_batch=64, window_s=0.0,
                              max_inflight=4, dispatch_timeout_s=0.2,
                              on_watchdog=lambda: trips.append(1))
            hung = []
            for q in (0, 1):
                hung.append(asyncio.create_task(mb.submit(q)))
                await fn.cut(q + 1)
            held = asyncio.create_task(mb.submit(2))
            await asyncio.sleep(0.05)
            assert len(fn.calls) == 2  # held behind the two hung steps
            got = await asyncio.gather(*hung, return_exceptions=True)
            assert all(isinstance(e, DispatchTimeout) for e in got)
            await fn.cut(3)  # the watchdog gave the places back
            fn.end_step(2)
            assert await asyncio.wait_for(held, 5) == 2
            s = mb.stats()
            assert s["watchdogTrips"] == 2 and trips == [1, 1]
            assert s["zombieDispatches"] == 2 and s["aheadOfDevice"] == 0
            mb.set_max_inflight(max(1, mb.max_inflight // 2))
            assert mb.stats()["maxInflight"] == 2
            mb.set_max_inflight(4)
            assert mb.stats()["maxInflight"] == 4
            fn.end_all()  # the zombies return; their late signal is moot
            for _ in range(200):
                if mb.stats()["zombieDispatches"] == 0:
                    break
                await asyncio.sleep(0.01)
            s = mb.stats()
            assert s["zombieDispatches"] == 0 and s["aheadOfDevice"] == 0
            await mb.close()

        try:
            run(main())
        finally:
            fn.end_all()

    def test_thread_bound_binds_when_host_work_after_the_step_piles_up(self):
        """The gate's other count, at its default of 8: calls whose
        device step is over but whose host work is not hold no place
        ahead of the device, so only ``max_inflight`` keeps a slow
        scatter from piling worker threads up. The ninth batch's cut
        waits for a call to END, and counts as held."""
        fn = _Steps(hold_calls=True)

        async def main():
            mb = MicroBatcher(fn, max_batch=1, window_s=0.0)
            assert mb.max_inflight == 8
            tasks = [asyncio.create_task(mb.submit(q)) for q in range(10)]
            for i in range(8):
                await fn.cut(i + 1)
                fn.end_step(i)  # the step is over; the call lingers
            await asyncio.sleep(0.05)
            s = mb.stats()
            assert len(fn.calls) == 8, "a ninth call went live"
            assert s["inflight"] == 8 and s["aheadOfDevice"] == 0
            assert s["occupancy"] == 1.0
            fn.end_call(3)  # one call returns: one more batch is cut
            await fn.cut(9)
            await asyncio.sleep(0.05)
            assert len(fn.calls) == 9
            fn.end_all()
            await fn.cut(10)
            fn.end_all()
            out = await asyncio.gather(*tasks)
            s = mb.stats()
            await mb.close()
            return out, s

        try:
            out, s = run(main())
        finally:
            fn.end_all()
        assert out == list(range(10))
        # all ten were queued at once: every cut after the first two
        # waited, six for a step's end and two for a call's
        assert s["peakInflight"] == 8 and s["cutsHeld"] == 8

    def test_cuts_count_as_held_by_their_queries_not_by_the_loops_turn(self):
        """Two device steps end before the formation loop runs again (a
        busy loop thread): it then cuts two batches in one turn and only
        waits for the first. Both were held, because the oldest query of
        each was queued while the gate was shut; a query that arrives
        after the gate opened is not."""
        import time

        fn = _Steps()

        async def main():
            mb = MicroBatcher(fn, max_batch=1, window_s=0.0)
            tasks = []
            for q in (0, 1):
                tasks.append(asyncio.create_task(mb.submit(q)))
                await fn.cut(q + 1)
            tasks += [asyncio.create_task(mb.submit(q)) for q in (2, 3)]
            await asyncio.sleep(0.02)
            assert len(fn.calls) == 2 and len(mb._pending) == 2
            fn.end_step(0)
            fn.end_step(1)
            time.sleep(0.1)  # the loop is busy: both ends are queued
            await fn.cut(4)
            assert mb.stats()["aheadOfDevice"] == 2
            fn.end_all()
            await asyncio.gather(*tasks)
            assert mb.stats()["cutsHeld"] == 2
            tasks.append(asyncio.create_task(mb.submit(4)))  # gate open
            await fn.cut(5)
            fn.end_all()
            out = await asyncio.gather(*tasks)
            s = mb.stats()
            await mb.close()
            return out, s

        try:
            out, s = run(main())
        finally:
            fn.end_all()
        assert out == [0, 1, 2, 3, 4]
        assert s["batches"] == 5 and s["cutsHeld"] == 2

    def test_drain_flushes_through_the_gate(self):
        """drain() answers everything queued, two batches at a time."""
        import time

        calls = _Peak()

        def slow_batch(queries):
            with calls:
                time.sleep(0.01)
            return [("ok", q) for q in queries]

        async def main():
            mb = MicroBatcher(slow_batch, max_batch=1, window_s=5.0,
                              max_inflight=8)
            tasks = [asyncio.create_task(mb.submit(i)) for i in range(6)]
            await asyncio.sleep(0.01)  # queued inside the long window
            await mb.drain()
            return await asyncio.gather(*tasks)

        assert run(main()) == list(range(6))
        assert calls.peak <= STAGING_DEPTH

    def test_estimates_follow_the_gate_not_the_thread_bound(self):
        """The CoDel sojourn and the admission controller's drain rate
        count STAGING_DEPTH batches served at once, not
        max_inflight."""
        mb = MicroBatcher(lambda qs: [("ok", q) for q in qs],
                          max_batch=4, max_inflight=8)
        assert mb.drain_rate_per_s() is None
        mb._ewma_dispatch_s = 0.02
        assert mb.drain_rate_per_s() == pytest.approx(
            4 * STAGING_DEPTH / 0.02)
        mb._pending = [(i, None) for i in range(16)]  # 4 batches queued
        assert mb._estimate_sojourn_s() == pytest.approx(2 * 0.02)
        mb._ahead = STAGING_DEPTH  # gate closed: one more wave
        assert mb._estimate_sojourn_s() == pytest.approx(3 * 0.02)
        mb.set_max_inflight(1)  # degraded to one call at a time
        assert mb.drain_rate_per_s() == pytest.approx(4 / 0.02)


class TestAdaptiveWindow:
    """adaptive=True: window_s becomes a ceiling scaled by arrival rate.
    The policy itself is exercised with synthetic clocks (no sleeps), the
    integration tests only assert the coarse ends of the behavior."""

    def _mb(self, **kw):
        return MicroBatcher(lambda qs: [("ok", q) for q in qs],
                            adaptive=True, **kw)

    def test_no_history_dispatches_immediately(self):
        mb = self._mb(window_s=5.0)
        assert mb._choose_window(100.0) == 0.0  # no EWMA yet -> no wait

    def test_fast_arrivals_open_a_bounded_window(self):
        mb = self._mb(window_s=5.0, max_batch=64)
        t = 100.0
        for _ in range(20):  # ~1 kHz arrival stream
            mb._note_arrival(t)
            t += 0.001
        mb._pending = [(0, None)] * 4  # 60 more needed for a full batch
        w = mb._choose_window(t)
        assert 0 < w <= mb.window_s
        assert w >= 0.004  # at ~1 ms gaps, 60 needed -> well above 4 ms

    def test_stale_rate_overridden_by_fresh_idle_gap(self):
        mb = self._mb(window_s=0.05, max_batch=64)
        t = 100.0
        for _ in range(20):
            mb._note_arrival(t)
            t += 0.001
        # 10 s of silence: the burst-era EWMA must not hold a lone query
        assert mb._choose_window(t + 10.0) == 0.0

    def test_full_batch_never_waits(self):
        mb = self._mb(window_s=5.0, max_batch=4)
        t = 100.0
        for _ in range(8):
            mb._note_arrival(t)
            t += 0.001
        mb._pending = [(i, None) for i in range(4)]
        assert mb._choose_window(t) == 0.0

    def _slow_stream(self, mb, t=100.0, gap=0.04, n=20):
        """A ~25 Hz arrival stream: the EWMA alone would open a LONG
        window for a partial batch."""
        for _ in range(n):
            mb._note_arrival(t)
            t += gap
        return t

    def test_deadline_headroom_clamps_window(self):
        """ISSUE 16 satellite: when every queued entry carries a
        deadline, the window never holds the batch past the tightest
        deadline minus the expected dispatch wall — admission accepted
        these queries; the EWMA must not expire them in the queue."""
        mb = self._mb(window_s=5.0, max_batch=64)
        t = self._slow_stream(mb)
        mb._ewma_dispatch_s = 0.01
        mb._pending = [(i, None, t + 0.05 + 0.01 * i, t, None)
                       for i in range(3)]
        w = mb._choose_window(t)
        # tightest deadline 50 ms out, minus the 10 ms dispatch margin
        assert w == pytest.approx(0.04)

    def test_deadline_clamp_skipped_when_any_entry_deadline_free(self):
        """An entry without a deadline means there is no headroom to
        protect: the rate-scaled window stands."""
        mb = self._mb(window_s=5.0, max_batch=64)
        t = self._slow_stream(mb)
        mb._ewma_dispatch_s = 0.01
        mb._pending = [(0, None, t + 0.05, t, None), (1, None)]
        assert mb._choose_window(t) > 0.04

    def test_expired_deadline_dispatches_immediately(self):
        """Headroom already spent -> window 0: ship the batch NOW so
        the deadline rejection (or the tail of the budget) happens in
        dispatch, not in the queue."""
        mb = self._mb(window_s=5.0, max_batch=64)
        t = self._slow_stream(mb)
        mb._ewma_dispatch_s = 0.01
        mb._pending = [(0, None, t - 0.001, t, None)]
        assert mb._choose_window(t) == 0.0

    def test_lone_query_not_held_to_ceiling(self):
        """End to end: with a 5 s ceiling, an idle adaptive batcher must
        answer a lone query in wire time, not ceiling time."""
        import time

        async def main():
            mb = self._mb(window_s=5.0)
            t0 = time.perf_counter()
            out = await mb.submit(42)
            dt = time.perf_counter() - t0
            await mb.close()
            return out, dt

        out, dt = run(main())
        assert out == 42
        assert dt < 1.0, f"idle adaptive batcher paid the ceiling ({dt:.2f}s)"

    def test_burst_preserves_submit_order(self):
        async def main():
            mb = self._mb(window_s=0.01, max_batch=8)
            out = await asyncio.gather(*[mb.submit(i) for i in range(32)])
            s = mb.stats()
            await mb.close()
            return out, s

        out, s = run(main())
        assert out == list(range(32))
        assert s["adaptive"] is True
        assert s["windowCeilingMs"] == pytest.approx(10.0)
        assert "lastWindowMs" in s and "occupancy" in s
        assert s["inflight"] == 0  # drained


class TestBatchedServing:
    """serve_query_batch against the real recommendation template."""

    @pytest.fixture
    def served(self, rng, mesh8):
        import sys
        from pathlib import Path
        import importlib.util

        from predictionio_tpu.controller import EngineParams
        from predictionio_tpu.storage import DataMap, Event, Storage
        from predictionio_tpu.workflow import Context
        from predictionio_tpu.workflow.create_server import EngineServer

        repo = Path(__file__).resolve().parents[1]
        spec = importlib.util.spec_from_file_location(
            "tmpl_rec_mb", repo / "templates" / "recommendation" / "engine.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["tmpl_rec_mb"] = mod
        spec.loader.exec_module(mod)

        meta = Storage.get_metadata()
        app = meta.app_insert("MyApp")
        ev = Storage.get_events()
        ev.init_app(app.id)
        for i in range(400):
            u, it = rng.integers(0, 30), rng.integers(0, 20)
            ev.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{it}",
                properties=DataMap({"rating": float(rng.integers(1, 6))}),
            ), app.id)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("als", mod.AlgorithmParams(rank=4, num_iterations=5)),),
        )
        from predictionio_tpu.workflow import run_train
        iid = run_train(engine, ep, Context(),
                        engine_factory="tmpl_rec_mb:engine_factory")
        inst = Storage.get_metadata().engine_instance_get(iid)
        server = EngineServer(engine, inst, Context(mode="Serving"))
        return server, mod

    def test_batch_matches_single(self, served):
        server, mod = served
        queries = [{"user": f"u{i}", "num": 3} for i in range(8)]
        batched = server.serve_query_batch(queries)
        assert all(tag == "ok" for tag, _ in batched)
        for qj, (_, got) in zip(queries, batched):
            single = server.serve_query(qj)
            # same ranking; scores may differ in float low bits (batched
            # vs single matmul accumulation order)
            assert [s["item"] for s in got["itemScores"]] == \
                [s["item"] for s in single["itemScores"]]
            np.testing.assert_allclose(
                [s["score"] for s in got["itemScores"]],
                [s["score"] for s in single["itemScores"]], rtol=1e-5)

    def test_unknown_user_and_malformed_isolate(self, served):
        server, _mod = served
        out = server.serve_query_batch([
            {"user": "u1", "num": 2},
            {"user": "nobody", "num": 2},  # unknown -> empty scores, ok
        ])
        assert out[0][0] == "ok" and out[0][1]["itemScores"]
        assert out[1][0] == "ok" and out[1][1]["itemScores"] == []

    def test_negative_num_is_empty_not_crash(self, served):
        server, _mod = served
        out = server.serve_query_batch([{"user": "u1", "num": -1}])
        assert out[0][0] == "ok" and out[0][1]["itemScores"] == []

    def test_stats_json_surface(self, served):
        """GET /stats.json telemetry: request counters, the adaptive
        micro-batcher fields, and the shared executable-cache counters."""
        from predictionio_tpu.workflow.create_server import (
            create_engine_server_app)

        server, _mod = served
        server.serve_query({"user": "u1", "num": 2})
        s = server.serving_stats()
        assert s["requestCount"] >= 1
        assert s["batching"]["adaptive"] is True
        assert {"hits", "misses", "evictions", "hitRate"} <= \
            set(s["execCache"])
        app = create_engine_server_app(server)
        assert any(r.resource is not None
                   and r.resource.canonical == "/stats.json"
                   for r in app.router.routes())

    def test_close_fails_pending(self):
        import threading

        started = threading.Event()

        def slow_batch(queries):
            started.wait(1)
            return [("ok", q) for q in queries]

        async def main():
            mb = MicroBatcher(slow_batch, window_s=5.0)  # long window
            t = asyncio.create_task(mb.submit(1))
            await asyncio.sleep(0.01)  # lands in _pending, window open
            await mb.close()
            started.set()
            return await asyncio.gather(t, return_exceptions=True)

        (out,) = run(main())
        assert isinstance(out, asyncio.CancelledError)


def test_queue_cap_rejects_overload():
    """submit() raises ServerBusy past max_pending instead of queueing
    without bound."""
    import asyncio

    from predictionio_tpu.workflow.microbatch import MicroBatcher, ServerBusy

    async def run():
        started = asyncio.Event()

        def slow_batch(queries):
            return [("ok", q) for q in queries]

        mb = MicroBatcher(slow_batch, max_batch=2, window_s=5.0,
                          max_pending=3)
        tasks = [asyncio.create_task(mb.submit(i)) for i in range(3)]
        await asyncio.sleep(0)  # let them enqueue inside the open window
        with __import__("pytest").raises(ServerBusy):
            await mb.submit(99)
        await mb.close()
        for t in tasks:
            with __import__("pytest").raises(asyncio.CancelledError):
                await t

    asyncio.run(run())


class TestShardedServingConcurrency:
    def test_concurrent_batches_through_sharded_retriever(self, rng, mesh8):
        """Many threads hammer serve_query_batch while the model serves
        through a ShardedDeviceRetriever (the pipelined dispatcher runs
        batches concurrently — the retriever's compiled-call cache and
        shard_map path must hold up and stay correct under threads)."""
        import sys
        from concurrent.futures import ThreadPoolExecutor
        from pathlib import Path
        import importlib.util

        from predictionio_tpu.controller import EngineParams
        from predictionio_tpu.parallel.mesh import make_mesh
        from predictionio_tpu.storage import DataMap, Event, Storage
        from predictionio_tpu.workflow import Context, run_train
        from predictionio_tpu.workflow.create_server import EngineServer

        repo = Path(__file__).resolve().parents[1]
        spec = importlib.util.spec_from_file_location(
            "tmpl_rec_sc", repo / "templates" / "recommendation" / "engine.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules["tmpl_rec_sc"] = mod
        spec.loader.exec_module(mod)

        meta = Storage.get_metadata()
        app = meta.app_insert("MyApp")
        ev = Storage.get_events()
        ev.init_app(app.id)
        for _ in range(500):
            u, it = rng.integers(0, 30), rng.integers(0, 20)
            ev.insert(Event(
                event="rate", entity_type="user", entity_id=f"u{u}",
                target_entity_type="item", target_entity_id=f"i{it}",
                properties=DataMap({"rating": float(rng.integers(1, 6))}),
            ), app.id)
        engine = mod.engine_factory()
        ep = EngineParams(
            data_source_params=("", mod.DataSourceParams(app_name="MyApp")),
            algorithm_params_list=(
                ("als", mod.AlgorithmParams(rank=4, num_iterations=4)),),
        )
        iid = run_train(engine, ep, Context(),
                        engine_factory="tmpl_rec_sc:engine_factory")
        inst = Storage.get_metadata().engine_instance_get(iid)
        server = EngineServer(engine, inst, Context(mode="Serving"),
                              retriever_mesh=make_mesh((8,), ("model",)))
        from predictionio_tpu.ops.retrieval import ShardedDeviceRetriever

        model = server.deployed.result.models[0]
        assert isinstance(model._retriever, ShardedDeviceRetriever)

        expected = {}
        for u in range(8):
            out = server.serve_query_batch([{"user": f"u{u}", "num": 3}])
            assert out[0][0] == "ok"
            expected[u] = [s["item"] for s in out[0][1]["itemScores"]]

        def hammer(seed):
            r = np.random.default_rng(seed)
            for _ in range(10):
                us = [int(r.integers(0, 8)) for _ in range(6)]
                # varied num -> varied compiled shapes under concurrency
                out = server.serve_query_batch(
                    [{"user": f"u{u}", "num": int(r.integers(1, 4))}
                     for u in us])
                for u, (tag, payload) in zip(us, out):
                    assert tag == "ok"
                    items = [s["item"] for s in payload["itemScores"]]
                    assert items == expected[u][:len(items)]
            return True

        with ThreadPoolExecutor(max_workers=6) as ex:
            assert all(ex.map(hammer, range(6)))


class _StepAlgorithm:
    """Mixed into the sample algorithm: its batch_predict has a device
    step, and says when it ends."""

    order: list = []

    def batch_predict(self, model, queries):
        self.order.append(self.params.id)
        device_step_ended()
        return super().batch_predict(model, queries)


def make_two_step_engine():
    from predictionio_tpu.controller import Engine
    from predictionio_tpu.testing.sample_engine import (
        SampleAlgorithm, SampleDataSource, SamplePreparator, SampleQuery,
        SampleServing)

    class StepAlgorithm(_StepAlgorithm, SampleAlgorithm):
        query_class = SampleQuery

    return Engine(
        data_source_classes=SampleDataSource,
        preparator_classes=SamplePreparator,
        algorithm_classes={"step": StepAlgorithm},
        serving_classes=SampleServing,
    )


def test_only_a_batchs_last_device_step_opens_the_gate():
    """An engine with two algorithms runs two device steps a batch: the
    first one's end is muted, so the batcher's place is given back where
    the batch's LAST step ends."""
    from predictionio_tpu.controller import EngineParams
    from predictionio_tpu.ops.pipeline import (reset_step_end_hook,
                                               set_step_end_hook)
    from predictionio_tpu.storage import Storage
    from predictionio_tpu.testing.sample_engine import (
        SampleAlgoParams, SampleDataSourceParams)
    from predictionio_tpu.workflow import Context, run_train
    from predictionio_tpu.workflow.create_server import EngineServer

    engine = make_two_step_engine()
    ep = EngineParams(
        data_source_params=("", SampleDataSourceParams(id=0)),
        algorithm_params_list=(("step", SampleAlgoParams(id=1)),
                               ("step", SampleAlgoParams(id=2))),
    )
    iid = run_train(engine, ep, Context(),
                    engine_factory="tests.test_microbatch:"
                                   "make_two_step_engine")
    server = EngineServer(engine,
                          Storage.get_metadata().engine_instance_get(iid))
    order = _StepAlgorithm.order
    order.clear()
    token = set_step_end_hook(lambda: order.append("gate"))
    try:
        out = server.serve_query_batch([{"q": 3}, {"q": 4}])
    finally:
        reset_step_end_hook(token)
    assert [tag for tag, _ in out] == ["ok", "ok"]
    assert order == [1, 2, "gate"]


class TestCutByCost:
    """Rows of unequal cost (a sequence model's tokens): a cut takes
    queries in arrival order up to the budget the caller states."""

    @staticmethod
    def _serve(costs, budget, max_batch=128, **kw):
        cuts = []

        def batch_fn(queries):
            cuts.append(list(queries))
            return [("ok", q) for q in queries]

        async def go():
            mb = MicroBatcher(batch_fn, max_batch=max_batch, window_s=0.02,
                              **kw)
            try:
                got = await asyncio.gather(*[mb.submit(c) for c in costs])
            finally:
                await mb.close()
            return got, mb

        got, mb = run(go())
        assert got == list(costs)
        return cuts, mb

    @pytest.mark.parametrize("budget", [64, 100, 512])
    def test_a_cut_by_cost_never_exceeds_its_budget(self, budget):
        rng = np.random.default_rng(budget)
        costs = rng.integers(1, 60, 200).tolist()
        cuts, mb = self._serve(costs, budget,
                               costing=lambda: (lambda q: q, budget))
        assert [q for cut in cuts for q in cut] == costs  # arrival order
        assert all(sum(cut) <= budget for cut in cuts)
        # and takes all the budget allows: the next query did not fit
        for cut, nxt in zip(cuts, cuts[1:]):
            assert sum(cut) + nxt[0] > budget or len(cut) == mb.max_batch
        # the estimators still count max_batch rows a batch
        assert mb.drain_rate_per_s() == (
            mb.max_batch * mb._depth() / mb._ewma_dispatch_s)

    def test_a_row_over_the_budget_goes_alone(self):
        cuts, _mb = self._serve([300, 5, 5], 100,
                                costing=lambda: (lambda q: q, 100))
        assert cuts[0] == [300] and sum(map(len, cuts)) == 3

    @pytest.mark.parametrize("kw", [
        {}, {"costing": lambda: None},            # the model states none
        {"costing": lambda: (lambda q: 0, 5)}])   # rows that cost nothing
    def test_no_stated_cost_cuts_as_before(self, kw):
        cuts, mb = self._serve(list(range(1, 41)), None, max_batch=16, **kw)
        assert [len(c) for c in cuts][:2] == [16, 16]

    def test_the_budget_is_asked_at_every_cut(self):
        """A /reload from a model that states no cost to one that does
        (and back) is followed at the next cut: nothing is read once at
        construction."""
        stated = {"now": None}
        cuts = []

        def batch_fn(queries):
            cuts.append(list(queries))
            return [("ok", q) for q in queries]

        async def go():
            mb = MicroBatcher(batch_fn, max_batch=16, window_s=0.01,
                              costing=lambda: stated["now"])
            try:
                await asyncio.gather(*[mb.submit(5) for _ in range(8)])
                stated["now"] = (lambda q: q, 10)
                await asyncio.gather(*[mb.submit(5) for _ in range(8)])
                stated["now"] = None
                await asyncio.gather(*[mb.submit(5) for _ in range(8)])
            finally:
                await mb.close()

        run(go())
        sizes = [len(c) for c in cuts]
        assert sizes[0] == 8 and sizes[-1] == 8
        assert sizes[1:-1] == [2, 2, 2, 2]
