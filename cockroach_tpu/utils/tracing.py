"""Span-based tracing (the analogue of pkg/util/tracing).

A Tracer hands out nested spans with wall-clock durations and tags;
the active span propagates through a thread-local, so any layer can
child_span() without plumbing (the reference threads a Context
instead; a thread-local matches this engine's one-statement-per-thread
execution model). A capture() scope collects the finished span tree —
that recording is what EXPLAIN ANALYZE renders, like the reference's
WithRecording(trace) statement diagnostics.

Distributed recordings: the active-span stack lives in MODULE-level
thread-local state shared by every Tracer instance, so spans opened
by the RPC fabric, DistSender, or DistSQL nodes nest into whatever
recording the statement opened — no tracer needs plumbing through the
stack. `trace_context()` exports the active (trace_id, span_id) pair
for an RPC frame; the serving side runs its handler under its own
`capture()` and ships the finished subtree back with
`span_to_wire()`; the caller grafts it with `attach_remote()`. This
mirrors CockroachDB's span "recording" payloads piggybacked on
BatchResponse / SetupFlow (pkg/util/tracing/crdbspan.go).

Off is off: with no recording open on a thread, `span()` hands back
one shared no-op (no Span is allocated) and `current_span()` stays
None, so `trace_context()` ships nothing. What opens a recording is a
`capture()`: EXPLAIN ANALYZE, SET tracing, slow sampling, an armed
diagnostics request, an inbound RPC frame that asks for one — or the
process-wide collector below, which is the slow ring's mechanism made
general: while it is on, every statement root (served or library) is
recorded and kept, bounded, oldest dropped first. Stamps are
`time.monotonic_ns()`, the clock a profiler capture's host events can
be paired with, so collected roots line up with device ops.

A span also carries the CPU its thread spent while it was open
(`cpu_ns`, `time.thread_time_ns()` at open and at close; under the
collector only when it was started with `cpu=True`): wall less
CPU is the time the thread was off the processor, which inside a
named wait (`pull`, `queue`, `gate`, `admission`, `wire.queue`) is
that wait and anywhere else the interpreter lock or the OS. And a
layer's own time is cut by `stage(name)` marks, not by child spans: a
mark owns the span's self time up to the next mark or the close, so
every reader of a span's interval or self time reads what it read
before the marks were there.
"""

from __future__ import annotations

import collections
import itertools
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Optional

# One process-wide active-span stack per thread (see module doc).
class _ThreadState(threading.local):
    # class-level defaults: a thread that never recorded reads them
    # as plain attributes (a getattr that misses costs five times one
    # that hits, and the untraced path makes some thirty a statement)
    span: Optional["Span"] = None
    rec_req: bool = True
    # does the recording open on this thread read the CPU clock
    cpu: bool = True


_tls = _ThreadState()
_ids = itertools.count(1)


@dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int = 0
    tags: dict = field(default_factory=dict)
    children: list["Span"] = field(default_factory=list)
    span_id: int = 0
    trace_id: int = 0
    # CPU of the opening thread between open and close; None on a span
    # stamped from elsewhere (a wait that ended on another thread)
    cpu_ns: Optional[int] = None
    # stage marks, in order: [name, monotonic_ns, the thread's CPU
    # since the span opened, CPU another thread spent for the stage]
    stages: list = field(default_factory=list)
    # the thread's CPU clock at open: this process's, never on the wire
    cpu0_ns: int = field(default=0, repr=False, compare=False)

    @property
    def duration_ms(self) -> float:
        return (self.end_ns - self.start_ns) / 1e6

    def stage_intervals(self) -> list[tuple]:
        """[(name, start_ns, end_ns, cpu_ns, other_cpu_ns)] a mark: a
        stage runs to the next mark or the span's close. `cpu_ns` is
        None where the span's close took no CPU reading."""
        out = []
        ends = [m[1] for m in self.stages[1:]] + [self.end_ns]
        cpus = [m[2] for m in self.stages[1:]] + [self.cpu_ns]
        for (name, at, cpu, other), end, cpu_end in zip(
                self.stages, ends, cpus):
            out.append((name, at, end, None if cpu_end is None
                        else cpu_end - cpu, other))
        return out

    def tree_lines(self, indent: int = 0) -> list[str]:
        tag_s = "".join(f" {k}={v}" for k, v in self.tags.items())
        if self.cpu_ns is not None:
            tag_s = f" cpu={self.cpu_ns / 1e6:.2f}ms" + tag_s
        if self.stages:
            tag_s += " stages[" + " ".join(
                f"{name}={(end - at) / 1e6:.2f}ms"
                for name, at, end, _, _ in self.stage_intervals()) + "]"
        out = [f"{'  ' * indent}{self.name}: "
               f"{self.duration_ms:.2f}ms{tag_s}"]
        for c in self.children:
            out.extend(c.tree_lines(indent + 1))
        return out

    def find(self, name: str) -> Optional["Span"]:
        if self.name == name:
            return self
        for c in self.children:
            hit = c.find(name)
            if hit is not None:
                return hit
        return None

    def find_all(self, name: str) -> list["Span"]:
        out = [self] if self.name == name else []
        for c in self.children:
            out.extend(c.find_all(name))
        return out


def current_span() -> Optional[Span]:
    return _tls.span


# -- the process-wide collector ----------------------------------------------
# None while off. A deque(maxlen) drops the oldest root when full, and
# its append is atomic, so every thread offers without a lock.
_collected: Optional[collections.deque] = None
_collect_cpu = False
COLLECTOR_MAX_ROOTS = 8192


def start_collector(max_roots: int = COLLECTOR_MAX_ROOTS,
                    cpu: bool = False) -> None:
    """Record every statement root, on all threads, until
    stop_collector(); at most `max_roots` are kept, the oldest go.
    The roots it keeps read the CPU clock only with `cpu`: every
    other recording is one statement somebody asked about, this one
    is every statement of the process, and a `thread_time_ns()` is a
    system call some fifty times a statement (0.25 us each on a plain
    Linux host, 6 us where the benchmark runs: 0.3 ms a statement,
    PERF.md PR 38)."""
    global _collected, _collect_cpu
    _collect_cpu = bool(cpu)
    _collected = collections.deque(maxlen=max_roots)


def stop_collector() -> list:
    """Turn the collector off and hand back the finished roots it
    kept, oldest first."""
    global _collected
    roots, _collected = _collected, None
    return list(roots or ())


def collecting() -> bool:
    return _collected is not None


def collected() -> list:
    """The roots kept so far (the collector stays on)."""
    return list(_collected or ())


def recording_requested() -> bool:
    """True when the active capture asked remote participants to
    record too (SET tracing = cluster, EXPLAIN ANALYZE, slow-statement
    sampling). False when nothing records here, or when the capture
    was opened with record_request=False (SET tracing = on: gateway-
    local recording, remote nodes stay dark)."""
    return _tls.span is not None and bool(_tls.rec_req)


def trace_context() -> Optional[dict]:
    """The active trace context as a JSON-safe dict for an RPC frame
    (`{"tid": trace_id, "sid": span_id}` plus `"rec": 1` when the
    capture requests remote recording), or None when nothing is
    recording on this thread."""
    s = current_span()
    if s is None:
        return None
    tc = {"tid": s.trace_id, "sid": s.span_id}
    if _tls.rec_req:
        tc["rec"] = 1
    return tc


def _jsonable(v):
    return v if isinstance(v, (str, int, float, bool, type(None))) \
        else str(v)


def span_to_wire(s: Span) -> dict:
    """Encode a finished span subtree as JSON-safe primitives (the
    trace-frame wire format documented in OBSERVABILITY.md)."""
    d = {
        "n": s.name,
        "b": s.start_ns,
        "e": s.end_ns,
        "t": {str(k): _jsonable(v) for k, v in s.tags.items()},
        "c": [span_to_wire(c) for c in s.children],
        "sid": s.span_id,
        "tid": s.trace_id,
    }
    # beside "b" / "e", and only where there is a reading: a reader
    # that knows neither key reads the span it read before
    if s.cpu_ns is not None:
        d["u"] = s.cpu_ns
    if s.stages:
        d["g"] = [list(m) for m in s.stages]
    return d


def span_from_wire(d: dict) -> Span:
    cpu = d.get("u")
    return Span(
        name=d.get("n", "?"),
        start_ns=int(d.get("b", 0)),
        end_ns=int(d.get("e", 0)),
        tags=dict(d.get("t", {})),
        children=[span_from_wire(c) for c in d.get("c", [])],
        span_id=int(d.get("sid", 0)),
        trace_id=int(d.get("tid", 0)),
        cpu_ns=None if cpu is None else int(cpu),
        stages=[[str(m[0]), int(m[1]), int(m[2]), int(m[3])]
                for m in d.get("g", [])],
    )


def attach_remote(wire: dict) -> Optional[Span]:
    """Graft a remote recording (wire dict from span_to_wire) under
    the active span. No-op when nothing is recording here."""
    parent = current_span()
    if parent is None or not wire:
        return None
    s = span_from_wire(wire)
    parent.children.append(s)
    return s


class _NoSpan:
    """What span() returns when nothing records on this thread: one
    shared object, so the untraced path allocates no Span."""
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NO_SPAN = _NoSpan()


class _OpenSpan:
    """An open child span: pushes itself as the thread's active span,
    pops on exit. `record_request`, when given, overrides the
    recording's remote-recording bit while the span is open (a
    statement with SET tracing = cluster nested under a root that was
    opened for local recording only)."""
    __slots__ = ("span", "parent", "rec_req", "prev_req", "cpu")

    def __init__(self, s: Span, parent: Span, rec_req, cpu: bool):
        self.span, self.parent, self.rec_req = s, parent, rec_req
        self.cpu = cpu

    def __enter__(self) -> Span:
        _tls.span = self.span
        if self.rec_req is not None:
            self.prev_req = _tls.rec_req
            _tls.rec_req = bool(self.rec_req)
        return self.span

    def __exit__(self, *exc):
        s = self.span
        if self.cpu:
            s.cpu_ns = time.thread_time_ns() - s.cpu0_ns
        s.end_ns = time.monotonic_ns()
        _tls.span = self.parent
        if self.rec_req is not None:
            _tls.rec_req = self.prev_req
        return False


def span(name: str, record_request: Optional[bool] = None, **tags):
    """Child span of whatever is recording on this thread; the shared
    no-op (yields None) when nothing is."""
    parent = current_span()
    if parent is None:
        return NO_SPAN
    cpu = _tls.cpu
    s = Span(name, time.monotonic_ns(), tags=tags, span_id=next(_ids),
             trace_id=parent.trace_id,
             cpu0_ns=time.thread_time_ns() if cpu else 0)
    parent.children.append(s)
    return _OpenSpan(s, parent, record_request, cpu)


def record(name: str, start_ns: int, end_ns: int,
           cpu_ns: Optional[int] = None, **tags) -> Optional[Span]:
    """A finished child span from stamps taken elsewhere (a wait that
    ended on another thread: the frame queue, the mesh dispatcher);
    `cpu_ns` where that thread read its CPU clock too (a wait has
    none). None when nothing is recording."""
    parent = current_span()
    if parent is None:
        return None
    s = Span(name, start_ns, end_ns, tags=tags, span_id=next(_ids),
             trace_id=parent.trace_id, cpu_ns=cpu_ns)
    parent.children.append(s)
    return s


def stage(name: str, after: Optional[str] = None) -> None:
    """A mark inside the open span: the stage `name` starts here and
    runs to the next mark or the span's close, and owns the span's
    self time in between. A mark and not a child span, so the span's
    own interval, self time and label stay what every reader of them
    knows. A return when nothing records. `after`: mark only a span
    whose open stage is that one: what a callee says that does not
    own the span it marks (Prepared.run also runs beneath `plan`,
    for a subquery bound at prepare, and must not cut `build`)."""
    s = _tls.span
    if s is None:
        return
    if after is not None and not (s.stages and s.stages[-1][0] == after):
        return
    s.stages.append([name, time.monotonic_ns(),
                     time.thread_time_ns() - s.cpu0_ns if _tls.cpu
                     else 0, 0])


def reads_cpu() -> bool:
    """Does the recording open on this thread read the CPU clock (a
    caller that stamps another thread's work for it asks first)."""
    return _tls.cpu and _tls.span is not None


def stage_cpu(cpu_ns: int, **tags) -> None:
    """Credit the open span's current stage with CPU that another
    thread spent for it (the mesh dispatcher running the call this
    thread waits for); `tags` land on the span."""
    s = current_span()
    if s is None:
        return
    if s.stages:
        s.stages[-1][3] += int(cpu_ns)
    s.tags.update(tags)


def event(name: str, **tags) -> Optional[Span]:
    """Zero-duration marker under the active span (breaker-skip,
    cache-evict, ...). Returns None when nothing is recording."""
    parent = current_span()
    if parent is None:
        return None
    now = time.monotonic_ns()
    s = Span(name, now, now, tags=dict(tags), span_id=next(_ids),
             trace_id=parent.trace_id, cpu_ns=0)
    parent.children.append(s)
    return s


@contextmanager
def capture(name: str = "trace", remote_ctx: Optional[dict] = None,
            record_request: Optional[bool] = None,
            start_ns: Optional[int] = None, collect: bool = False,
            **tags):
    """Collect a full recording rooted at `name` on this thread.

    `remote_ctx` is the {"tid","sid","rec"?} dict from an inbound RPC
    frame: the new root adopts the caller's trace_id and tags the
    parent span id, so stitched recordings stay correlated across
    nodes.

    `record_request` is the per-statement remote-recording bit (the
    pgwire `SET tracing` analogue): True asks every RPC/flow this
    capture touches to record remotely and ship spans back; False
    keeps the recording gateway-local. Default: inherit the inbound
    frame's bit when remote_ctx is given, else True (every existing
    capture — EXPLAIN ANALYZE, slow sampling, tests — wants the
    stitched tree).

    `start_ns` backdates the root to a stamp taken before this thread
    had the work (the frame's arrival). `collect` marks a statement
    root: the collector, while on, keeps it when it closes."""
    prev = current_span()
    prev_req, prev_cpu = _tls.rec_req, _tls.cpu
    # a root the collector will keep reads the CPU clock only if the
    # collector asked for it (start_collector); any other recording does
    cpu = _collect_cpu or not (collect and _collected is not None)
    root = Span(name, start_ns or time.monotonic_ns(), tags=dict(tags),
                span_id=next(_ids),
                cpu0_ns=time.thread_time_ns() if cpu else 0)
    if remote_ctx:
        root.trace_id = int(remote_ctx.get("tid", 0))
        psid = int(remote_ctx.get("sid", 0))
        if psid:
            root.tags.setdefault("parent_sid", psid)
        if record_request is None:
            record_request = bool(remote_ctx.get("rec"))
    else:
        root.trace_id = next(_ids)
    _tls.span = root
    _tls.rec_req = True if record_request is None else bool(record_request)
    _tls.cpu = cpu
    try:
        yield root
    finally:
        if cpu:
            root.cpu_ns = time.thread_time_ns() - root.cpu0_ns
        root.end_ns = time.monotonic_ns()
        _tls.span = prev
        _tls.rec_req = prev_req
        _tls.cpu = prev_cpu
        kept = _collected
        if collect and kept is not None:
            kept.append(root)


def tag(**tags) -> None:
    s = current_span()
    if s is not None:
        s.tags.update(tags)


class Tracer:
    """Back-compat facade over the module-level span stack: every
    Tracer shares the same per-thread recording, which is what lets
    fabric/KV/DistSQL spans land inside the engine's capture."""

    def span(self, name: str, **tags):
        return span(name, **tags)

    def capture(self, name: str = "trace",
                record_request: Optional[bool] = None, **tags):
        return capture(name, record_request=record_request, **tags)

    def tag(self, **tags) -> None:
        tag(**tags)
