"""Fleet self-healing: supervised replica lifecycle (ISSUE 18).

PR 17's ``spawn_replicas`` was fire-and-forget: a replica that died
stayed dead (and un-reaped) until an operator noticed the router's
eligible set shrink. The reference ran every daemon under
``pio-start-all`` with pidfile lifecycle management; production serving
assumes a self-healing control loop above the router's fault isolation.
``FleetSupervisor`` is that loop — it owns the replica subprocesses
end-to-end:

- **Reaping** — a poll pass ``Popen.poll()``s every child, so an exited
  replica is reaped immediately (no zombies) and its exit code is
  logged with its port. A clean exit (rc 0) is operator intent
  (``pio fleet drain --stop``, a direct ``/stop``) — the replica goes
  to ``stopped``, never respawned and never counted toward the crash
  window.
- **Respawn with jittered exponential backoff** — a crashed replica is
  respawned on its ORIGINAL port (the router's rendezvous hash and the
  fleet state file both key on it), after ``backoff_base_s * 2^(n-1)``
  capped at ``backoff_cap_s``, with ±20% jitter so a correlated crash
  across replicas does not produce a thundering-herd respawn. The
  exponent is the death count inside the sliding crash window, so a
  crash loop that briefly reaches ready between deaths still escalates;
  the window forgetting old deaths is what resets it.
- **Crash-loop quarantine** — ``max_respawns`` deaths inside the
  sliding ``crash_window_s`` window mean respawning is not helping
  (bad model blob, poisoned port, OOM loop): the replica is
  **quarantined** — reported to the router (``set_quarantined``) so
  rendezvous traffic redistributes to its siblings, dropped from the
  fleet state file's active set, and only retried after the long
  ``quarantine_s`` cooldown.
- **Rolling restart wave** (``pio fleet restart``) — one replica at a
  time: admin-drain on the router → graceful ``/stop`` (terminate as
  fallback) → respawn → wait ready → undrain. After the first replica
  the wave is gated by the router's PR-17 shadow-diff canary: recent
  queries replayed against the restarted replica and a not-yet-restarted
  baseline; a mismatch fraction above the router's threshold aborts the
  wave with the rest of the fleet untouched.

Every recovery path is provable (TensorFlow's nonfatal-failure design,
arXiv:1605.08695 §4.2, same as the rest of ``faults.py``): the
``supervisor.respawn`` chaos site fires right before each respawn
``Popen`` — an armed error is a failed exec, which counts against the
crash window and re-enters backoff instead of busy-looping.

The supervisor is deliberately synchronous (a daemon thread around
``poll()``): child-process lifecycle is blocking-syscall territory, and
a thread keeps it testable one ``poll()`` at a time with no event loop.
Cross-thread contact with the router is limited to plain field flips
(``set_quarantined`` / ``set_admin_drained``) and
``canary_from_thread`` (``run_coroutine_threadsafe``).
"""

from __future__ import annotations

import atexit
import json
import logging
import random
import subprocess
import threading
import time
import urllib.error
import urllib.request
from collections import deque
from dataclasses import dataclass, field

from ..faults import FAULTS
from ..obs.metrics import METRICS
from ..obs.trace import trace_event

__all__ = ["SupervisedReplica", "FleetSupervisor"]

log = logging.getLogger(__name__)

_M_DEATHS = METRICS.counter(
    "pio_fleet_supervisor_deaths_total",
    "replica child exits observed by the supervisor (reaped, by "
    "replica; includes failed respawn attempts)",
    labelnames=("replica",))
_M_RESPAWNS = METRICS.counter(
    "pio_fleet_supervisor_respawns_total",
    "replica respawns launched by the supervisor",
    labelnames=("replica",))
_M_QUARANTINED = METRICS.gauge(
    "pio_fleet_supervisor_quarantined",
    "1 while a replica is quarantined for crash-looping",
    labelnames=("replica",))
_M_BACKOFF = METRICS.histogram(
    "pio_fleet_supervisor_backoff_seconds",
    "jittered exponential backoff chosen before each respawn")
_M_RESPAWN_READY = METRICS.histogram(
    "pio_fleet_supervisor_respawn_to_ready_seconds",
    "death detection -> respawned replica reports ready")
_M_WAVES = METRICS.counter(
    "pio_fleet_supervisor_restart_waves_total",
    "rolling restart waves by outcome (ok/canary_abort/failed)",
    labelnames=("outcome",))
_M_CHILDREN = METRICS.gauge(
    "pio_fleet_supervisor_children",
    "replica children currently running under the supervisor")

#: replica lifecycle: pending -> running <-> backoff, with quarantined
#: (crash loop) and restarting (rolling wave) as supervised detours and
#: stopped as the terminal state
_STATES = ("pending", "running", "backoff", "quarantined", "restarting",
           "stopped")


@dataclass
class SupervisedReplica:
    """Supervisor-side view of one replica child process."""

    name: str
    port: int
    url: str
    proc: subprocess.Popen | None = None
    state: str = "pending"
    deaths: deque = field(default_factory=deque)  # monotonic instants
    respawns: int = 0
    backoff_until: float = 0.0
    last_backoff_s: float = 0.0
    quarantined_until: float = 0.0
    awaiting_ready: bool = False
    death_detected: float = 0.0      # feeds respawn-to-ready latency
    spawned_at: float = 0.0
    ready_at: float = 0.0
    last_exit: int | None = None

    def snapshot(self, now: float) -> dict:
        return {
            "name": self.name,
            "port": self.port,
            "url": self.url,
            "pid": self.proc.pid if self.proc is not None else None,
            "state": self.state,
            "deathsInWindow": len(self.deaths),
            "respawns": self.respawns,
            "lastExit": self.last_exit,
            "backoffRemainingS": round(max(0.0, self.backoff_until - now), 3)
            if self.state == "backoff" else 0.0,
            "quarantineRemainingS":
                round(max(0.0, self.quarantined_until - now), 3)
                if self.state == "quarantined" else 0.0,
        }


class FleetSupervisor:
    """Own the replica subprocesses end-to-end (see module doc).

    ``spawn`` is a callable ``(SupervisedReplica) -> Popen`` so the
    CLI hands in a real ``pio deploy`` exec while tests supervise
    fast-booting stubs. Use as a context manager (or call ``start`` /
    ``stop``); ``terminate_all`` also runs at interpreter exit so a
    dying supervisor never strands its brood.
    """

    def __init__(
        self,
        spawn,
        replicas: list[dict],
        *,
        router=None,
        max_respawns: int = 5,
        crash_window_s: float = 60.0,
        quarantine_s: float = 300.0,
        backoff_base_s: float = 0.5,
        backoff_cap_s: float = 30.0,
        poll_interval_s: float = 0.2,
        ready_timeout_s: float = 120.0,
        ready_probe_timeout_s: float = 0.5,
        state_writer=None,
        rng: random.Random | None = None,
    ):
        self.spawn = spawn
        self.replicas: list[SupervisedReplica] = [
            SupervisedReplica(name=str(r["name"]), port=int(r["port"]),
                              url=str(r["url"]).rstrip("/"))
            for r in replicas]
        self.router = router
        self.max_respawns = max(1, int(max_respawns))
        self.crash_window_s = max(0.1, float(crash_window_s))
        self.quarantine_s = max(0.1, float(quarantine_s))
        self.backoff_base_s = max(0.01, float(backoff_base_s))
        self.backoff_cap_s = max(self.backoff_base_s, float(backoff_cap_s))
        self.poll_interval_s = max(0.01, float(poll_interval_s))
        self.ready_timeout_s = max(0.1, float(ready_timeout_s))
        self.ready_probe_timeout_s = max(0.05, float(ready_probe_timeout_s))
        self.state_writer = state_writer
        self._rng = rng if rng is not None else random.Random()
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        for rep in self.replicas:
            _M_QUARANTINED.set(0, replica=rep.name)

    # -- wiring ------------------------------------------------------------
    def replica(self, name: str) -> SupervisedReplica:
        for rep in self.replicas:
            if rep.name == name:
                return rep
        raise KeyError(name)

    def adopt(self, name: str, proc: subprocess.Popen) -> None:
        """Take ownership of an already-spawned child (the initial
        ``spawn_replicas`` brood from `pio fleet start`)."""
        with self._lock:
            rep = self.replica(name)
            rep.proc = proc
            rep.state = "running"
            rep.awaiting_ready = True
            rep.spawned_at = time.monotonic()
        self._set_children_gauge()

    # -- lifecycle ---------------------------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        atexit.register(self.terminate_all)
        self._stop.clear()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="fleet-supervisor")
        self._thread.start()

    def stop(self, timeout_s: float = 5.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=timeout_s)
            self._thread = None

    def __enter__(self) -> "FleetSupervisor":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()
        self.terminate_all()

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self.poll()
            except Exception:  # noqa: BLE001 — the loop must survive
                log.exception("supervisor poll pass failed")
            self._stop.wait(self.poll_interval_s)

    def terminate_all(self, timeout_s: float = 5.0) -> None:
        """Terminate and REAP the whole brood (idempotent; atexit)."""
        with self._lock:
            reps = [r for r in self.replicas
                    if r.proc is not None and r.proc.poll() is None]
            for rep in reps:
                rep.state = "stopped"
                try:
                    rep.proc.terminate()
                except OSError:
                    pass
            deadline = time.monotonic() + timeout_s
            for rep in reps:
                try:
                    rep.proc.wait(
                        timeout=max(0.1, deadline - time.monotonic()))
                except (subprocess.TimeoutExpired, OSError):
                    try:
                        rep.proc.kill()
                        rep.proc.wait(timeout=1.0)
                    except (subprocess.TimeoutExpired, OSError):
                        pass
            for rep in self.replicas:
                if rep.state != "stopped":
                    rep.state = "stopped"
        self._set_children_gauge()

    # -- the control loop --------------------------------------------------
    def poll(self) -> None:
        """One supervision pass over every replica — reap, respawn,
        quarantine, track readiness. Called by the loop thread; also
        directly by tests for deterministic single-stepping."""
        now = time.monotonic()
        with self._lock:
            for rep in self.replicas:
                if rep.state in ("stopped", "restarting"):
                    continue
                if rep.state == "pending":
                    self._respawn(rep, now, initial=True)
                elif rep.state == "running":
                    rc = rep.proc.poll() if rep.proc is not None else 1
                    if rc is not None:
                        self._on_death(rep, rc, now)
                    elif rep.awaiting_ready:
                        self._check_ready(rep, now)
                elif rep.state == "backoff":
                    if now >= rep.backoff_until:
                        self._respawn(rep, now)
                elif rep.state == "quarantined":
                    if now >= rep.quarantined_until:
                        log.info("replica %s quarantine cooldown over; "
                                 "retrying", rep.name)
                        self._respawn(rep, now)
        self._set_children_gauge()

    def _prune_deaths(self, rep: SupervisedReplica, now: float) -> None:
        while rep.deaths and now - rep.deaths[0] > self.crash_window_s:
            rep.deaths.popleft()

    def _on_death(self, rep: SupervisedReplica, rc: int | None,
                  now: float) -> None:
        rep.last_exit = rc
        rep.awaiting_ready = False
        _M_DEATHS.inc(replica=rep.name)
        if rc == 0:
            # a clean exit is operator intent (`pio fleet drain --stop`,
            # a direct /stop), not a crash: respawning would fight the
            # operator, and repeated graceful stops must never
            # accumulate toward quarantining a healthy replica. Only
            # rc != 0 (or a failed exec) enters the crash window.
            rep.state = "stopped"
            rep.death_detected = 0.0
            log.info("replica %s (port %d) exited cleanly; "
                     "not respawning (operator stop)", rep.name, rep.port)
            trace_event("supervisor.stop", replica=rep.name)
            self._write_state()
            return
        rep.deaths.append(now)
        self._prune_deaths(rep, now)
        rep.death_detected = now
        if rc is not None:
            log.warning("replica %s (port %d) exited rc=%s "
                        "(death %d/%d in %.0fs window)",
                        rep.name, rep.port, rc, len(rep.deaths),
                        self.max_respawns, self.crash_window_s)
        if len(rep.deaths) >= self.max_respawns:
            self._quarantine(rep, now)
            return
        # the exponent is deaths-IN-WINDOW, not a consecutive counter:
        # a crash loop that briefly reaches ready between deaths still
        # escalates its backoff until the sliding window forgets
        delay = self._backoff_delay(len(rep.deaths))
        rep.last_backoff_s = delay
        rep.backoff_until = now + delay
        rep.state = "backoff"
        _M_BACKOFF.record(delay)
        trace_event("supervisor.death", replica=rep.name, rc=rc,
                    backoff_s=round(delay, 3), deaths=len(rep.deaths))
        log.info("replica %s respawn scheduled in %.2fs", rep.name, delay)

    def _backoff_delay(self, attempt: int) -> float:
        """base * 2^(n-1) capped, ±20% jitter. The jitter band is
        narrower than the doubling, so successive delays still grow
        strictly until the cap — provable backoff, de-correlated
        respawns."""
        raw = min(self.backoff_cap_s,
                  self.backoff_base_s * (2.0 ** max(0, attempt - 1)))
        return raw * (0.8 + 0.4 * self._rng.random())

    def _quarantine(self, rep: SupervisedReplica, now: float) -> None:
        rep.state = "quarantined"
        rep.quarantined_until = now + self.quarantine_s
        _M_QUARANTINED.set(1, replica=rep.name)
        log.error("replica %s (port %d) is CRASH-LOOPING "
                  "(%d deaths in %.0fs): quarantined for %.0fs",
                  rep.name, rep.port, len(rep.deaths),
                  self.crash_window_s, self.quarantine_s)
        trace_event("supervisor.quarantine", replica=rep.name,
                    deaths=len(rep.deaths), cooldown_s=self.quarantine_s)
        if self.router is not None:
            self.router.set_quarantined(rep.name, True)
        self._write_state()

    def _respawn(self, rep: SupervisedReplica, now: float,
                 initial: bool = False) -> None:
        """Launch (or relaunch) the child on its ORIGINAL port. A
        failed exec counts against the crash window — backoff, never
        a busy loop."""
        was_quarantined = rep.state == "quarantined"
        try:
            FAULTS.fire("supervisor.respawn")
            proc = self.spawn(rep)
        except Exception as e:  # noqa: BLE001 — failed exec == a death
            log.warning("respawn of %s failed: %r", rep.name, e)
            self._on_death(rep, None, now)
            return
        rep.proc = proc
        rep.state = "running"
        rep.awaiting_ready = True
        rep.spawned_at = now
        if not initial:
            rep.respawns += 1
            _M_RESPAWNS.inc(replica=rep.name)
        if was_quarantined:
            _M_QUARANTINED.set(0, replica=rep.name)
            if self.router is not None:
                self.router.set_quarantined(rep.name, False)
        # every spawn changes the child pid — republish the state file
        # so `pio fleet status` and staleness detection see live pids
        self._write_state()
        trace_event("supervisor.respawn", replica=rep.name,
                    pid=proc.pid, initial=initial)
        log.info("replica %s %sspawned on port %d (pid %d)",
                 rep.name, "" if initial else "re", rep.port, proc.pid)

    def _check_ready(self, rep: SupervisedReplica, now: float) -> None:
        if not self._probe_ready(rep.url):
            if now - rep.spawned_at > self.ready_timeout_s:
                log.warning("replica %s not ready after %.0fs; "
                            "recycling", rep.name, self.ready_timeout_s)
                try:
                    rep.proc.kill()
                except OSError:
                    pass
            return
        rep.awaiting_ready = False
        rep.ready_at = now
        if rep.death_detected > 0.0:
            _M_RESPAWN_READY.record(now - rep.death_detected)
            trace_event("supervisor.ready", replica=rep.name,
                        respawn_to_ready_s=round(now - rep.death_detected,
                                                 3))
            rep.death_detected = 0.0

    def _probe_ready(self, url: str) -> bool:
        try:
            with urllib.request.urlopen(
                    f"{url}/health.json",
                    timeout=self.ready_probe_timeout_s) as resp:
                body = json.loads(resp.read())
            return bool(body.get("ready", resp.status == 200))
        except (urllib.error.URLError, OSError, ValueError):
            return False

    # -- rolling restart wave (`pio fleet restart`) ------------------------
    def rolling_restart(self, canary_sample: int | None = None,
                        drain_timeout_s: float = 15.0) -> dict:
        """Drain → restart → re-ready ONE replica at a time; after the
        first restarted replica, gate the rest of the wave on the
        router's shadow-diff canary against a not-yet-restarted
        baseline. Aborting leaves the remaining replicas untouched (the
        rollback is not doing the rollout)."""
        router = self.router
        sample = (router.canary_sample if canary_sample is None and
                  router is not None else int(canary_sample or 0))
        wave: list[dict] = []
        outcome = "ok"
        canary: dict | None = None
        with self._lock:
            targets = [r for r in self.replicas
                       if r.state in ("running", "backoff")]
        for i, rep in enumerate(targets):
            t0 = time.monotonic()
            with self._lock:
                rep.state = "restarting"  # poll() must not count this exit
            if router is not None:
                router.set_admin_drained(rep.name, True)
            try:
                self._graceful_stop(rep, drain_timeout_s)
                with self._lock:
                    self._respawn(rep, time.monotonic())
                    rep.state = "restarting"  # keep poll() hands-off
                if not self._await_ready(rep):
                    raise TimeoutError(
                        f"{rep.name} not ready within "
                        f"{self.ready_timeout_s}s after restart")
            except Exception as e:  # noqa: BLE001 — abort, undrain, report
                outcome = "failed"
                wave.append({"replica": rep.name, "ok": False,
                             "error": str(e)})
                with self._lock:
                    rep.state = "running"
                if router is not None:
                    router.set_admin_drained(rep.name, False)
                break
            with self._lock:
                rep.state = "running"
                rep.awaiting_ready = False
            if router is not None:
                router.set_admin_drained(rep.name, False)
            wave.append({"replica": rep.name, "ok": True,
                         "restartS": round(time.monotonic() - t0, 3)})
            baseline = next((r for r in targets[i + 1:]), None)
            if (i == 0 and sample > 0 and router is not None
                    and baseline is not None):
                canary = router.canary_from_thread(rep.name, baseline.name,
                                                  sample)
                if (canary.get("mismatchFraction", 0.0)
                        > router.canary_max_mismatch):
                    outcome = "canary_abort"
                    break
        _M_WAVES.inc(outcome=outcome)
        trace_event("supervisor.restart_wave", outcome=outcome,
                    restarted=sum(1 for w in wave if w.get("ok")))
        report = {"outcome": outcome, "wave": wave,
                  "restarted": sum(1 for w in wave if w.get("ok")),
                  "replicas": len(targets)}
        if canary is not None:
            report["canary"] = canary
        return report

    def _graceful_stop(self, rep: SupervisedReplica,
                       drain_timeout_s: float) -> None:
        proc = rep.proc
        if proc is None or proc.poll() is not None:
            return
        try:
            with urllib.request.urlopen(f"{rep.url}/stop",
                                        timeout=2.0):
                pass
        except (urllib.error.URLError, OSError, ValueError):
            pass  # dead or deaf: escalate to terminate below
        try:
            proc.wait(timeout=drain_timeout_s)
            return
        except subprocess.TimeoutExpired:
            pass
        try:
            proc.terminate()
            proc.wait(timeout=drain_timeout_s)
        except (subprocess.TimeoutExpired, OSError):
            try:
                proc.kill()
                proc.wait(timeout=2.0)
            except (subprocess.TimeoutExpired, OSError):
                pass

    def _await_ready(self, rep: SupervisedReplica) -> bool:
        deadline = time.monotonic() + self.ready_timeout_s
        while time.monotonic() < deadline:
            if rep.proc is not None and rep.proc.poll() is not None:
                return False
            if self._probe_ready(rep.url):
                return True
            time.sleep(0.05)
        return False

    # -- views -------------------------------------------------------------
    def _set_children_gauge(self) -> None:
        _M_CHILDREN.set(sum(
            1 for r in self.replicas
            if r.proc is not None and r.proc.poll() is None))

    def _write_state(self) -> None:
        if self.state_writer is None:
            return
        try:
            self.state_writer(self)
        except Exception:  # noqa: BLE001 — state file is advisory
            log.exception("fleet state rewrite failed")

    def status(self) -> dict:
        now = time.monotonic()
        with self._lock:
            return {
                "maxRespawns": self.max_respawns,
                "crashWindowS": self.crash_window_s,
                "quarantineS": self.quarantine_s,
                "replicas": [r.snapshot(now) for r in self.replicas],
            }
