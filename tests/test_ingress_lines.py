"""The engine server's per-request log lines leave the event loop in
batches (obs/ingress_lines.py): every line still written, same text, same
logger, in order; a held line waits for the loop's next turn, or for the app's
cleanup."""

from __future__ import annotations

import asyncio
import json
import logging
import time

import pytest

pytest.importorskip("aiohttp")

from aiohttp import web  # noqa: E402
from aiohttp.test_utils import make_mocked_request  # noqa: E402
from aiohttp.web_log import AccessLogger  # noqa: E402

from predictionio_tpu.obs.ingress_lines import (  # noqa: E402
    IngressLines,
    buffered_access_logger,
    note_serve_ingress,
)
from predictionio_tpu.obs.trace import TRACE_HEADER  # noqa: E402
from predictionio_tpu.workflow.create_server import (  # noqa: E402
    EngineServer,
    create_engine_server_app,
)


class Records(logging.Handler):
    """Every record a logger is handed, in order."""

    def __init__(self, name: str):
        super().__init__(logging.INFO)
        self.records: list[logging.LogRecord] = []
        self._logger = logging.getLogger(name)
        self._level = self._logger.level

    def emit(self, record):
        self.records.append(record)

    def __enter__(self):
        self._logger.addHandler(self)
        self._logger.setLevel(logging.INFO)
        return self

    def __exit__(self, *exc):
        self._logger.removeHandler(self)
        self._logger.setLevel(self._level)

    def ingress(self) -> list[dict]:
        lines = [json.loads(r.getMessage()) for r in self.records]
        return [ln for ln in lines if ln["evt"] == "serve.ingress"]


@pytest.fixture
def server():
    from tests.test_resilience import _trained

    engine, inst = _trained()
    return EngineServer(engine, inst)


async def _serving(server, **runner_kw):
    runner = web.AppRunner(create_engine_server_app(server), **runner_kw)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    return runner, f"http://127.0.0.1:{runner.addresses[0][1]}"


def test_every_request_leaves_its_line_in_arrival_order(server):
    """N requests, N ``serve.ingress`` records on ``pio.trace``: each under
    its own id, with its status, http code and a non-negative ``ms``, in
    the order the requests were answered; a 4xx's line like a 200's; the
    counters count what was written."""
    import aiohttp

    sent = [(f"rid-{i:03d}", i % 4 == 3) for i in range(300)]

    async def run():
        runner, url = await _serving(server)
        try:
            async with aiohttp.ClientSession() as http:
                for rid, malformed in sent:
                    async with http.post(
                            url + "/queries.json",
                            data="{not json" if malformed
                            else json.dumps({"q": 7}),
                            headers={TRACE_HEADER: rid}) as r:
                        assert r.status == (400 if malformed else 200)
                        assert r.headers[TRACE_HEADER] == rid
                async with http.get(url + "/stats.json") as r:
                    live = (await r.json())["ingress"]
        finally:
            await runner.cleanup()
        return live

    with Records("pio.trace") as trace:
        live = asyncio.run(run())
    got = trace.ingress()
    assert [ln["trace"] for ln in got] == [rid for rid, _ in sent]
    for ln, (_, malformed) in zip(got, sent):
        assert (ln["status"], ln["http"]) == (
            ("bad_request", 400) if malformed else ("ok", 200))
        assert ln["ms"] >= 0
        assert list(ln) == sorted(ln)  # trace_event's sorted keys
    # /stats.json, read while the app served, had counted every line noted
    assert live["linesNoted"] == len(sent)
    assert 1 <= live["flushes"] <= len(sent)
    assert server.ingress.stats() == {
        "linesNoted": len(sent), "flushes": server.ingress.flushes}


def test_a_held_line_is_written_at_the_loops_next_turn():
    """Held while the turn that noted it runs; written, with no further
    traffic, once the loop has gone through what was ready."""
    lines = IngressLines()

    async def run():
        note_serve_ingress(lines, "first", "ok", 200, 1.0)
        note_serve_ingress(lines, "second", "ok", 200, 2.0)
        assert trace.ingress() == [] and lines.flushes == 0
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert [ln["trace"] for ln in trace.ingress()] == ["first", "second"]
        assert lines.stats() == {"linesNoted": 2, "flushes": 1}

    with Records("pio.trace") as trace:
        asyncio.run(run())


def test_a_turn_however_many_lines_it_notes_is_one_flush():
    lines = IngressLines()
    logger = logging.getLogger("pio.test.count")
    written = []

    async def run():
        for i in range(1000):
            lines.note(logger, lambda logger, i: written.append(i), i)
        assert written == [] and lines.flushes == 0
        await asyncio.sleep(0)
        await asyncio.sleep(0)

    with Records("pio.test.count"):
        asyncio.run(run())
    assert written == list(range(1000))
    assert lines.stats() == {"linesNoted": 1000, "flushes": 1}


def test_a_stopped_loops_line_is_flushed_and_another_loop_is_asked_anew():
    """A loop that stops inside the turn that noted a line never runs the
    flush it was asked for: a ``flush`` by hand (the app's cleanup) writes
    the line, and the same lines, on a server bound anew, ask the next
    loop."""
    lines = IngressLines()

    def a_turn_that_stops_the_loop():
        note_serve_ingress(lines, "first", "ok", 200, 1.0)
        loop.stop()

    async def bound_anew():
        note_serve_ingress(lines, "second", "ok", 200, 2.0)
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert [ln["trace"] for ln in trace.ingress()] == ["first", "second"]

    with Records("pio.trace") as trace:
        loop = asyncio.new_event_loop()
        try:
            loop.call_soon(a_turn_that_stops_the_loop)
            loop.run_forever()
        finally:
            loop.close()
        assert trace.ingress() == []
        lines.flush()
        assert [ln["trace"] for ln in trace.ingress()] == ["first"]
        asyncio.run(bound_anew())
    assert lines.stats() == {"linesNoted": 2, "flushes": 2}


def test_the_apps_cleanup_writes_what_is_held(server):
    """``runner.cleanup()`` (SIGTERM, ``/stop``, ``ServerThread.stop()``)
    runs the hook; it writes, without a turn of the loop, what the last
    requests left held."""

    async def run():
        app = create_engine_server_app(server)
        note_serve_ingress(server.ingress, "held", "ok", 200, 1.0)
        assert trace.ingress() == []
        await app.on_cleanup[-1](app)
        assert trace.ingress() == [{"evt": "serve.ingress", "trace": "held",
                                    "status": "ok", "http": 200, "ms": 1.0}]

    with Records("pio.trace") as trace:
        asyncio.run(run())
    assert server.ingress.stats() == {"linesNoted": 1, "flushes": 1}


def test_nothing_is_held_for_a_logger_that_drops_info():
    lines = IngressLines()
    quiet = logging.getLogger("pio.test.quiet")
    quiet.setLevel(logging.WARNING)

    async def run():
        lines.note(quiet, lambda logger: logger.info("never"))

    asyncio.run(run())
    assert lines.stats() == {"linesNoted": 0, "flushes": 0}


def test_a_line_that_cannot_be_written_costs_no_other_line():
    lines = IngressLines()
    logger = logging.getLogger("pio.test.broken")

    def broken(logger):
        raise TypeError("no such field")

    async def run():
        lines.note(logger, lambda logger: logger.info("before"))
        lines.note(logger, broken)
        lines.note(logger, lambda logger: logger.info("after"))
        lines.flush()

    with Records("pio.test.broken") as seen:
        asyncio.run(run())
    assert [r.getMessage() for r in seen.records] == [
        "before", "Error in logging", "after"]
    assert seen.records[1].exc_info[0] is TypeError
    assert lines.stats() == {"linesNoted": 3, "flushes": 1}


def _request(**headers):
    return make_mocked_request(
        "POST", "/queries.json?accessKey=k", headers=headers)


_RECORD_FIELDS = ("remote_address", "request_start_time",
                  "first_request_line", "response_status", "response_size",
                  "request_header")


async def _theirs_and_ours(headers, held_s: float):
    """The same request through aiohttp's AccessLogger and, held for
    ``held_s``, through the buffered one: both records."""
    lines = IngressLines()
    logger = logging.getLogger("aiohttp.access")
    response = web.json_response({"itemScores": []}, status=201)
    response._body_length = 1234
    with Records("aiohttp.access") as seen:
        # both read the clock for the second the request started in: not
        # where that second is about to tick over
        while (time.time() - 0.25) % 1 > 0.9:
            await asyncio.sleep(0.02)
        AccessLogger(logger).log(_request(**headers), response, 0.25)
        buffered_access_logger(lines)(logger).log(
            _request(**headers), response, 0.25)
        assert len(seen.records) == 1  # the second is held
        await asyncio.sleep(held_s)
        lines.flush()
        return seen.records


@pytest.mark.parametrize("headers", [
    {}, {"Referer": "http://shop/", "User-Agent": "pool/1.0"}])
def test_access_line_is_aiohttps_default_letter_for_letter(headers):
    theirs, ours = asyncio.run(_theirs_and_ours(headers, 0.0))
    assert ours.getMessage() == theirs.getMessage()
    assert '"POST /queries.json?accessKey=k HTTP/1.1" 201 1234' in (
        ours.getMessage())
    assert (ours.name, ours.levelno) == (theirs.name, theirs.levelno)
    for key in _RECORD_FIELDS:
        assert getattr(ours, key) == getattr(theirs, key), key


def test_access_line_dates_the_request_not_the_flush():
    """Held into the next second of the clock, the line still carries the
    second the request started in."""
    theirs, ours = asyncio.run(_theirs_and_ours({}, 1.1))
    assert ours.getMessage() == theirs.getMessage()
    assert ours.request_start_time == theirs.request_start_time


def test_access_lines_of_a_served_app_go_through_the_held_lines(server):
    """Through ``web.AppRunner`` with the class ``run_engine_server`` hands
    ``web.run_app``: a request's access line and its ``serve.ingress`` are
    both noted, and both written."""
    import aiohttp

    async def run():
        runner, url = await _serving(
            server, access_log_class=buffered_access_logger(server.ingress))
        try:
            async with aiohttp.ClientSession() as http:
                for i in range(3):
                    async with http.post(url + "/queries.json",
                                         json={"q": i}) as r:
                        assert r.status == 200
        finally:
            await runner.cleanup()

    with Records("aiohttp.access") as seen, Records("pio.trace") as trace:
        asyncio.run(run())
    assert len(seen.records) == len(trace.ingress()) == 3
    assert all('"POST /queries.json HTTP/1.1" 200 ' in r.getMessage()
               for r in seen.records)
    assert server.ingress.lines_noted == 6


def test_only_the_default_format_is_offered():
    with pytest.raises(ValueError):
        buffered_access_logger(IngressLines())(
            logging.getLogger("aiohttp.access"), "%a %t")
