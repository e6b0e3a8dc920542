"""The engine server's per-request log lines, written in batches.

A served query leaves two lines: ``serve.ingress`` on ``pio.trace`` and
aiohttp's access line on ``aiohttp.access``. Written one at a time they
were two ``LogRecord``s, two formats, two handler locks and two flushed
writes inside every request, on the event loop's thread, which under a
full pool is the thread that sets the server's pace. :class:`IngressLines`
holds each line for one turn of the loop, and the loop writes what is
held, in order, one record a line, through the logger the line always went
to: a batch is what one turn produced.
No thread, no timer, no option: every other ``trace_event`` of the program
stays synchronous, because at a crash its immediacy is the point and its
rate is per event, not per query.
"""

from __future__ import annotations

import asyncio
import datetime
import functools
import logging
import time

from . import trace

__all__ = ["IngressLines", "buffered_access_logger", "note_serve_ingress"]


class IngressLines:
    """``note`` costs an append; ``flush`` emits everything noted.

    The first held line asks the loop (``call_soon``) for a flush once it
    has gone through everything that was ready before. Under a full pool
    that is the two lines of every request of a dispatched batch, written
    in a row; on a quiet server a request's own two lines, written right
    behind its response. The app's cleanup flushes too: a loop about to
    stop may never run the flush it was asked for.

    Both methods belong to the event loop's thread."""

    def __init__(self):
        self._held: list = []
        self._asked = False  # a flush stands in the loop's ready queue
        self.lines_noted = 0
        self.flushes = 0

    def note(self, logger: logging.Logger, emit, *fields) -> None:
        """Hold one line: at the next flush ``emit(logger, *fields)``
        writes it. Nothing is held for a logger that would drop it."""
        if not logger.isEnabledFor(logging.INFO):
            return
        self._held.append((logger, emit, fields))
        self.lines_noted += 1
        if not self._asked:
            self._asked = True
            asyncio.get_running_loop().call_soon(self.flush)

    def flush(self) -> None:
        """Emit every held line, oldest first."""
        self._asked = False
        held = self._held
        if not held:
            return
        self._held = []
        self.flushes += 1
        for logger, emit, fields in held:
            try:
                emit(logger, *fields)
            except Exception:  # noqa: BLE001: a line must not end the loop
                logger.exception("Error in logging")

    def stats(self) -> dict:
        """``ingress`` block of /stats.json."""
        return {"linesNoted": self.lines_noted, "flushes": self.flushes}


def _emit_serve_ingress(logger: logging.Logger, rid: str, status: str,
                        http: int, ms: float) -> None:
    trace.trace_event("serve.ingress", trace=rid, status=status, http=http,
                      ms=ms)


def note_serve_ingress(lines: IngressLines, rid: str, status: str,
                       http: int, ms: float) -> None:
    """Hold ``trace_event("serve.ingress", status=, http=, ms=)`` under the
    request's id until the flush."""
    lines.note(trace.log, _emit_serve_ingress, rid, status, http, ms)


# aiohttp.web_log.AccessLogger.LOG_FORMAT ('%a %t "%r" %s %b "%{Referer}i"
# "%{User-Agent}i"') and the record fields it sets, as of aiohttp 3.13:
# tests/test_ingress_lines.py holds the copy to aiohttp's own line, letter
# for letter, so an upgrade that changes either fails there
_ACCESS_FORMAT = '%s %s "%s" %s %s "%s" "%s"'


@functools.lru_cache(maxsize=4)
def _start_time(second: int) -> str:
    # a batch's requests started in one or two seconds of the clock
    tz = datetime.timezone(datetime.timedelta(seconds=-time.timezone))
    return datetime.datetime.fromtimestamp(second, tz).strftime(
        "[%d/%b/%Y:%H:%M:%S %z]")


def _emit_access(logger: logging.Logger, remote, started: float, method: str,
                 path_qs: str, version, status: int, body_length: int,
                 referer: str, user_agent: str) -> None:
    start_time = _start_time(int(started))
    remote = remote if remote is not None else "-"
    request_line = "{} {} HTTP/{}.{}".format(
        method, path_qs, version.major, version.minor)
    logger.info(
        _ACCESS_FORMAT % (remote, start_time, request_line, status,
                          body_length, referer, user_agent),
        extra={"remote_address": remote,
               "request_start_time": start_time,
               "first_request_line": request_line,
               "response_status": status,
               "response_size": body_length,
               "request_header": {"Referer": referer,
                                  "User-Agent": user_agent}})


def buffered_access_logger(lines: IngressLines):
    """An ``access_log_class`` for ``web.run_app`` / ``web.AppRunner``
    that notes a request's fields at its end and writes aiohttp's default
    access line, letter for letter, when ``lines`` flushes. The fields
    and not the request: held requests and responses, a batch's worth of
    object graphs kept alive for a turn, cost a third of the gain on the
    chip's host (PERF.md, PR 43). (aiohttp makes one instance a
    connection, hence a class bound to ``lines``.)"""
    from aiohttp.abc import AbstractAccessLogger
    from aiohttp.web_log import AccessLogger

    class BufferedAccessLogger(AbstractAccessLogger):
        def __init__(self, logger, log_format=AccessLogger.LOG_FORMAT):
            if log_format != AccessLogger.LOG_FORMAT:
                raise ValueError("the buffered access logger writes "
                                 "aiohttp's default format only")
            super().__init__(logger, log_format)

        @property
        def enabled(self) -> bool:
            return self.logger.isEnabledFor(logging.INFO)

        def log(self, request, response, time_taken: float) -> None:
            headers = request.headers
            lines.note(self.logger, _emit_access, request.remote,
                       time.time() - time_taken, request.method,
                       request.path_qs, request.version, response.status,
                       response.body_length, headers.get("Referer", "-"),
                       headers.get("User-Agent", "-"))

    return BufferedAccessLogger
