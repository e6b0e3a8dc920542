"""What a process spent before it could do its work: the phases of a
start-up as ``obs.trace.span`` timed them, each with the host memory in
use at its end, and the time from the process's own start to its first
sight of the device.

``STARTUP.phase`` is a span sink (``span("deploy.blob_read",
sink=STARTUP.phase)``); ``/stats.json`` shows the record as ``startup``,
the deploy logs it once when ready, and ``train_als`` copies
``processToDeviceSeconds`` into its attempt's record. Reads of
``/proc`` happen once a phase, never on the request path; off Linux the
memory reads None and the process age falls back to the import time of
this module.
"""

from __future__ import annotations

import logging
import os
import threading
import time

from .trace import span

__all__ = ["STARTUP", "StartupRecord", "host_bytes_in_use",
           "process_age_seconds"]

log = logging.getLogger("pio.startup")

#: phases kept; a process that reloads appends each reload's phases
PHASE_LIMIT = 256

_IMPORTED_AT = time.perf_counter()


def host_bytes_in_use() -> int | None:
    """``MemTotal - MemAvailable`` of the machine, in bytes: what a
    machine that ends a run at a limit of memory in use goes by."""
    try:
        fields = {}
        with open("/proc/meminfo") as f:
            for line in f:
                key, _, rest = line.partition(":")
                if key in ("MemTotal", "MemAvailable"):
                    fields[key] = int(rest.split()[0]) * 1024
                    if len(fields) == 2:
                        break
        return fields["MemTotal"] - fields["MemAvailable"]
    except (OSError, KeyError, ValueError, IndexError):
        return None


def process_age_seconds() -> float:
    """Seconds since the kernel started this process: the machine's
    uptime less field 22 of ``/proc/self/stat``, so the interpreter's own
    start and every import count."""
    try:
        with open("/proc/self/stat") as f:
            # the command name (field 2) may hold spaces: count from its ")"
            after_comm = f.read().rpartition(")")[2].split()
        start_ticks = int(after_comm[19])  # field 22, 3 being after_comm[0]
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED_AT


class StartupRecord:
    """The ordered phases of this process's start-up (and of each later
    reload), as ``[name, seconds, hostBytesInUse]``; a child phase ends,
    and so stands, before its parent."""

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self.phases: list[list] = []
            self.ready_seconds: float | None = None
            self.process_to_device_seconds: float | None = None

    def phase(self, name: str, t0: float, t1: float) -> None:
        """Span sink: one finished phase, and the host memory in use now."""
        entry = ["pio." + name, t1 - t0, host_bytes_in_use()]
        with self._lock:
            self.phases.append(entry)
            del self.phases[:-PHASE_LIMIT]

    def process_to_device(self) -> float:
        """Take the process's first look at its devices inside the span
        ``pio.process.to_device`` and record the seconds from the
        process's start to that call's return: interpreter, imports and
        the backend's own start. Returns those seconds."""
        import jax

        process_start = time.perf_counter() - process_age_seconds()
        with span("process.to_device", sink=self.phase,
                  t0=process_start) as s:
            jax.devices()
        self.process_to_device_seconds = s.t1 - s.t0
        return self.process_to_device_seconds

    def mark_ready(self) -> None:
        """The process can do its work from now on (first call wins):
        ``readySeconds`` since its start, logged once with the phases."""
        with self._lock:
            if self.ready_seconds is not None:
                return
            self.ready_seconds = process_age_seconds()
        snap = self.snapshot()
        log.info("ready %.3f s after process start; phases: %s",
                 snap["readySeconds"],
                 ", ".join(f"{n}={s:.3f}s@{b}" for n, s, b in snap["phases"]))

    def snapshot(self) -> dict:
        with self._lock:
            return {"phases": [list(p) for p in self.phases],
                    "readySeconds": self.ready_seconds}


#: process-wide singleton, mirroring METRICS / FLIGHT / LEDGER / TRAINING
STARTUP = StartupRecord()
