"""Child processes of a run: every chip-holding verb is its own child,
one at a time, and the harness itself stays off jax."""

from __future__ import annotations

import json
import os
import resource
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

from .spec import REPO

#: An orderly /stop (drain, then exit) gets this long before it is a failure.
STOP_TIMEOUT_S = 60.0


class RunFailed(SystemExit):
    """The run cannot produce a result: non-zero exit, no result line."""

    def __init__(self, reason: str):
        super().__init__(f"benchmark: FAILED: {reason}")


def tail(log: Path, n: int = 40) -> str:
    lines = log.read_text(errors="replace").splitlines()
    return "\n".join(f"    | {ln}" for ln in lines[-n:])


class Children:
    """Starts, waits for and, whatever happens, stops the run's children."""

    def __init__(self, work: Path, pio_home: Path, rehearse: bool):
        self.work = work
        self.live: list[subprocess.Popen] = []
        env = dict(os.environ)
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (str(REPO), os.environ.get("PYTHONPATH")) if p)
        env["PIO_HOME"] = str(pio_home)
        env["PIO_FLIGHT_DIR"] = str(work / "flight")
        env.pop("PIO_NO_NATIVE", None)
        if rehearse:
            env["JAX_PLATFORMS"] = "cpu"
        self.env = env
        #: for children that must never touch the chip (seeding, trace
        #: reduction): they may import jax, held to the host
        self.host_env = {**env, "JAX_PLATFORMS": "cpu"}

    def start(self, name: str, argv: list[str], *, host_only: bool = False
              ) -> tuple[subprocess.Popen, Path]:
        log = self.work / f"{name}.log"
        with open(log, "w") as f:
            proc = subprocess.Popen(
                argv, env=self.host_env if host_only else self.env,
                stdout=f, stderr=subprocess.STDOUT, cwd=str(REPO))
        self.live.append(proc)
        return proc, log

    def wait(self, name: str, proc: subprocess.Popen, log: Path,
             timeout: float) -> str:
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            raise RunFailed(f"{name} still running after {timeout:.0f} s\n"
                            f"{tail(log)}") from None
        self.live.remove(proc)
        if rc != 0:
            raise RunFailed(f"{name} exited {rc}\n{tail(log)}")
        return log.read_text(errors="replace")

    def run(self, name: str, argv: list[str], timeout: float, *,
            host_only: bool = False) -> str:
        proc, log = self.start(name, argv, host_only=host_only)
        return self.wait(name, proc, log, timeout)

    @staticmethod
    def peak_rss_bytes() -> int:
        """Largest resident set of any child waited for so far."""
        return resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss * 1024

    def stop_all(self) -> None:
        for proc in self.live:
            if proc.poll() is None:
                proc.terminate()
        for proc in self.live:
            try:
                proc.wait(timeout=STOP_TIMEOUT_S)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
        self.live.clear()


def pio_argv(*verb_args: str) -> list[str]:
    return [sys.executable, "-m", "predictionio_tpu.tools.cli", *verb_args]


PROBE = ("import jax, json; d = jax.devices(); print('DEVICE ' + json.dumps("
         "{'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))")


def parse_probe(out: str) -> dict:
    found = [ln for ln in out.splitlines() if ln.startswith("DEVICE ")]
    if not found:
        raise RunFailed(f"device probe printed no device\n{out[-2000:]}")
    return json.loads(found[-1][len("DEVICE "):])


def require_chips(device: dict, chips: int, rehearse: bool) -> None:
    """No accelerator, or fewer chips than the cell asks for, ends the
    run with no result. Only `--rehearse` runs on the host, labelled."""
    if rehearse:
        return
    if device["platform"] == "cpu":
        raise RunFailed("JAX found no accelerator (platform 'cpu'); the "
                        "benchmark measures nothing off the chip")
    if device["count"] < chips:
        raise RunFailed(f"the cell needs {chips} chip(s), JAX found "
                        f"{device['count']}")


def host_memory_bytes() -> tuple[int, int]:
    """(MemTotal - MemAvailable, MemTotal) of the machine, from
    /proc/meminfo. A process's resident set is no measure of this: the
    deploy's counts some 7 GB that the machine does not count as used."""
    info = {}
    with open("/proc/meminfo") as f:
        for line in f:
            key, value = line.split(":", 1)
            info[key] = int(value.split()[0]) * 1024
    return info["MemTotal"] - info["MemAvailable"], info["MemTotal"]


def host_memory_used_bytes() -> int:
    return host_memory_bytes()[0]


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http_get(url: str, timeout: float = 30.0) -> tuple[int, str]:
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


def http_get_json(url: str, timeout: float = 30.0) -> dict:
    return json.loads(http_get(url, timeout)[1])


def http_post_json(url: str, body: dict, timeout: float = 30.0
                   ) -> tuple[int, dict]:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read().decode())
    except urllib.error.HTTPError as e:
        return e.code, {"error": e.read().decode()[:500]}


def wait_ready(url: str, server: subprocess.Popen, log: Path,
               timeout: float) -> int:
    """Returns the most host memory that was in use at any poll."""
    t_end = time.monotonic() + timeout
    peak = 0
    while True:
        peak = max(peak, host_memory_used_bytes())
        if server.poll() is not None:
            raise RunFailed(f"`pio deploy` exited {server.returncode} before "
                            f"it was ready\n{tail(log)}")
        if time.monotonic() > t_end:
            raise RunFailed(f"`pio deploy` not ready after {timeout:.0f} s\n"
                            f"{tail(log)}")
        try:
            status, body = http_get(url + "/health.json", timeout=5)
            if status == 200 and json.loads(body)["ready"]:
                return peak
        except (OSError, urllib.error.URLError, ValueError, KeyError):
            pass
        time.sleep(0.1)
