"""Request-scoped tracing: one id from ingress to the last side effect.

A request id is accepted from the ``X-PIO-Request-ID`` header or minted
at ingress, stored in a :mod:`contextvars` ContextVar (so it follows the
request across ``await`` points and into ``asyncio.to_thread`` workers,
which copy the context), and emitted in structured JSON log lines that
are joinable by ``trace``:

- query path: ingress → micro-batch queue wait → batched dispatch →
  device execute → feedback publish (the feedback event also carries a
  ``pio_request_id`` property so event-store rows join back);
- event path: ingress → journal append → drainer batch → backend
  upsert (the id rides inside the journal payload so a crash/replay
  keeps the join).

Lines go to the ``pio.trace`` logger as single-line JSON:
``{"evt": "serve.ingress", "trace": "ab12...", "ms": 1.93, ...}``.
``grep <trace-id>`` over the log is the whole query language.

:class:`span` is also the program's one way to time a block: serving
stages, deploy phases, training phases. Besides its line it hands both
clock readings to the caller's sink and makes the block a named event
(``pio.<name>``) of a running profiler capture; the catalog of spans is
in docs/operations.md.
"""

from __future__ import annotations

import contextvars
import json
import logging
import re
import sys
import time
import uuid

__all__ = [
    "DEVICE_SCOPES",
    "DeviceScopes",
    "TRACE_HEADER",
    "current_request_id",
    "ensure_request_id",
    "new_request_id",
    "operation_key",
    "set_request_id",
    "span",
    "spans_from_waterfall",
    "render_span_tree",
    "trace_event",
]

#: the propagation header, accepted at ingress and echoed on responses
TRACE_HEADER = "X-PIO-Request-ID"

log = logging.getLogger("pio.trace")

_request_id: contextvars.ContextVar[str | None] = contextvars.ContextVar(
    "pio_request_id", default=None)


def new_request_id() -> str:
    return uuid.uuid4().hex


def current_request_id() -> str | None:
    return _request_id.get()


def set_request_id(rid: str | None) -> contextvars.Token:
    return _request_id.set(rid)


def ensure_request_id(rid: str | None = None) -> str:
    """Adopt ``rid`` (e.g. from the ingress header), else keep the
    context's current id, else mint one. Returns the id now in effect."""
    got = rid or _request_id.get()
    if not got:
        got = new_request_id()
    _request_id.set(got)
    return got


def trace_event(evt: str, *, trace: str | None = None, **fields) -> None:
    """Emit one structured line. ``trace`` overrides the context id (a
    batched dispatch logs once with every member id instead)."""
    rec = {"evt": evt, "trace": trace or _request_id.get()}
    rec.update(fields)
    log.info("%s", json.dumps(rec, sort_keys=True, default=str))


_annotation = None


def _annotation_type():
    """``jax.profiler.TraceAnnotation`` once this process has imported
    jax on its own account, else None. Looked up in ``sys.modules`` and
    never imported from here: the event server and the benchmark's
    harness time their blocks without ever loading jax."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class span:
    """The program's one way to time a block.

    ``with span("serve.host_assembly", sink=f, rows=7):`` reads
    ``time.perf_counter`` at both ends of the block, hands ``f(name, t0,
    t1)`` the two readings, and wraps the block in
    ``jax.profiler.TraceAnnotation("pio.serve.host_assembly", rows=7)``,
    so that while a profiler capture runs the same interval lies on the
    device trace's clock, named and with its facts as stats (the
    annotation costs 0.8 us with no capture running, 1.7 us with one; a
    whole span 2-4 us; CPU, jax 0.9.0). One
    line with the duration in ms goes to the ``pio.trace`` logger at
    ``level``. A sink is whatever the caller keeps its seconds in: a
    waterfall clock, ``ctx.phase_times``, a ``TRAINING.note`` key, the
    startup record.

    ``t0`` starts the clock at an earlier reading, the previous span's
    ``t1``, so that a chain of spans accounts for every instant between
    its first start and its last end. ``step`` makes the annotation a
    step event of the profiler (``StepTraceAnnotation``) with that step
    number. The block may add facts it learns to the log line:
    ``with span(...) as s: s["rows"] = 7``.

    A span wraps a synchronous block on one thread, never an ``await``:
    the annotation belongs to the thread that entered it. The one
    exception is ``serve.cut_held`` (workflow/microbatch.py), entered and
    left on the event loop's thread around a wait: the profiler records an
    event whole when it ends, and the loop's other spans are synchronous
    blocks, so they lie inside that interval or outside it."""

    __slots__ = ("name", "sink", "trace", "level", "facts", "t0", "t1",
                 "_annotation")

    def __init__(self, name: str, *, sink=None, trace: str | None = None,
                 t0: float | None = None, step: int | None = None,
                 level: int = logging.INFO, **facts):
        self.name = name
        self.sink = sink
        self.trace = trace
        self.level = level
        self.facts = facts
        self.t0 = t0
        self.t1: float | None = None
        if step is not None:
            facts["step_num"] = step
        self._annotation = None

    def __enter__(self) -> "span":
        annotation = _annotation_type()
        if annotation is not None:
            # `_r=1` is what StepTraceAnnotation adds to a TraceAnnotation
            step = {"_r": 1} if "step_num" in self.facts else {}
            self._annotation = annotation("pio." + self.name, **step,
                                          **self.facts)
            self._annotation.__enter__()
        if self.t0 is None:
            self.t0 = time.perf_counter()
        return self

    def __setitem__(self, fact: str, value) -> None:
        self.facts[fact] = value

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.t1 = time.perf_counter()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if self.sink is not None:
            self.sink(self.name, self.t0, self.t1)
        if log.isEnabledFor(self.level):
            fields = dict(self.facts)
            if exc is not None:
                fields["error"] = f"{type(exc).__name__}: {exc}"
            rec = {"evt": self.name, "trace": self.trace or _request_id.get(),
                   "ms": round((self.t1 - self.t0) * 1e3, 3), **fields}
            log.log(self.level, "%s",
                    json.dumps(rec, sort_keys=True, default=str))
        return False


# ---------------------------------------------------------------------------
# Device time by named scope. The v5e profiler's operation lines name an
# operation by its instruction (``%fusion.174 = bf16[...] fusion(...)``)
# and carry no ``jax.named_scope``; the compiled program's text does
# (``metadata={op_name="jit(fn)/pio.seq.router/..."}``). So a program
# whose device time is to be read by scope hands its compiled text to
# ``DEVICE_SCOPES.record`` when it is built, and a capture writes the
# map beside its ``.xplane.pb`` (``pio_scopes.json``; workflow/tracing.py).

_OPERATION = re.compile(r"^\s*(?:ROOT )?(%[\w.\-]+) = (.+?) ([\w\-]+)\(")
_OP_NAME = re.compile(r"op_name=\"([^\"]*)\"")
_SCOPE = re.compile(r"(?:^|/)(pio\.[\w.]+)(?=/|$)")
_OPERAND = re.compile(r"%[\w.\-]+")
#: operations that only contain others: their time is their bodies'
_CONTAINERS = frozenset({"while", "conditional", "call"})


def operation_key(line: str) -> str | None:
    """``%name = type opcode`` of an instruction, from a line of a
    compiled program's text or from a profiler event's name (which
    prints the operands' types too): what the two have in common."""
    m = _OPERATION.match(line)
    return None if m is None else f"{m.group(1)} = {m.group(2)} {m.group(3)}"


class DeviceScopes:
    """operation -> the innermost ``pio.*`` named scope it was traced
    under, over the programs recorded so far. An operation that carries
    no ``op_name`` at all is the compiler's own (the copy it puts before
    a reshape that is no bitcast in the layout it chose, an
    asynchronous copy's two halves): it takes the scope of the
    operations that read it, where they agree."""

    def __init__(self):
        self._map: dict[str, str] = {}

    def record(self, hlo_text: str) -> int:
        """Read one compiled program's text; returns the operations
        named. The key holds the result's type, which holds the shapes:
        two programs' ``%fusion.7`` differ unless they compute alike."""
        scope_of: dict[str, str] = {}      # %name -> scope, this program's
        nameless: dict[str, str] = {}      # %name -> key, no op_name at all
        readers: dict[str, list] = {}      # %name -> the %names that read it
        for line in hlo_text.splitlines():
            m = _OPERATION.match(line)
            if m is None:
                continue
            name, opcode = m.group(1), m.group(3)
            for operand in _OPERAND.findall(
                    line[m.end():].split(")", 1)[0]):
                readers.setdefault(operand, []).append(name)
            if opcode in _CONTAINERS:
                continue
            op_name = _OP_NAME.search(line)
            if op_name is None:
                if opcode not in ("parameter", "constant"):
                    nameless[name] = operation_key(line)
                continue
            scopes = _SCOPE.findall(op_name.group(1))
            if scopes:
                scope_of[name] = self._map[operation_key(line)] = scopes[-1]

        def read_under(name, seen=()):
            if name in scope_of or name not in nameless or name in seen:
                return scope_of.get(name)
            found = {read_under(r, seen + (name,))
                     for r in readers.get(name, ())}
            return found.pop() if len(found) == 1 else None

        named = len(scope_of)
        for name, key in nameless.items():
            scope = read_under(name)
            if scope:
                self._map[key] = scope
                named += 1
        return named

    def snapshot(self) -> dict[str, str]:
        return dict(self._map)

    def dump(self, trace_dir: str) -> None:
        """Write the map beside a capture; nothing where nothing was
        recorded (a program without named scopes)."""
        if not self._map:
            return
        import os

        os.makedirs(trace_dir, exist_ok=True)
        with open(os.path.join(trace_dir, "pio_scopes.json"), "w") as f:
            json.dump(self._map, f)


#: Process-wide: every compiled program of the process that wants its
#: device time read by scope (today: the serving pipeline's encoder
#: programs).
DEVICE_SCOPES = DeviceScopes()


# ---------------------------------------------------------------------------
# Span-tree assembly (``pio trace <rid>``): the propagation above makes a
# request id joinable across processes; these helpers turn the joined
# pieces — router hop, replica waterfalls, ingest WAL records — into one
# rendered tree. A node is ``{"label": str, "ms": float|None,
# "detail": str|None, "children": [node, ...]}``.

def spans_from_waterfall(record: dict, label: str | None = None) -> dict:
    """One flight-recorder waterfall record (``Waterfall.to_dict()``
    shape) as a span node: the request wall at the top, one child per
    attributed stage in canonical order."""
    stages = record.get("stagesMs") or {}
    details = []
    if record.get("status"):
        details.append(f"status={record['status']}")
    if record.get("stalledStage"):
        details.append(f"stalled={record['stalledStage']}")
    if not record.get("finished", True):
        details.append("unfinished")
    return {
        "label": label or f"{record.get('path', 'serve')} request",
        "ms": record.get("wallMs"),
        "detail": " ".join(details) or None,
        "children": [{"label": s, "ms": ms, "detail": None, "children": []}
                     for s, ms in stages.items()],
    }


def render_span_tree(nodes: list[dict], title: str | None = None) -> str:
    """ASCII tree of span nodes, durations right-aligned to the label."""
    lines: list[str] = []
    if title:
        lines.append(title)

    def fmt(node: dict) -> str:
        parts = [str(node.get("label", "?"))]
        ms = node.get("ms")
        if ms is not None:
            parts.append(f"{float(ms):.3f} ms")
        if node.get("detail"):
            parts.append(f"[{node['detail']}]")
        return "  ".join(parts)

    def walk(node: dict, prefix: str, last: bool, root: bool) -> None:
        if root:
            lines.append(fmt(node))
            child_prefix = ""
        else:
            lines.append(f"{prefix}{'└─ ' if last else '├─ '}{fmt(node)}")
            child_prefix = prefix + ("   " if last else "│  ")
        kids = node.get("children") or []
        for i, kid in enumerate(kids):
            walk(kid, child_prefix, i == len(kids) - 1, False)

    for node in nodes:
        walk(node, "", True, True)
    return "\n".join(lines)
