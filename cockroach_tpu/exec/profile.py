"""Per-operator execution profiles (statement diagnostics substrate).

The reference attributes execution statistics to individual
processors via execinfrapb.ComponentStats collected by the
execstatscollector and stitched into the statement bundle
(``pkg/sql/execstats/traceanalyzer.go``). Our plans normally compile
to ONE fused XLA program. Its ops do carry their plan operator
(``compile_plan`` runs each closure under ``jax.named_scope``), so a
profiler capture gives per-operator device time of the real program
(benchmark/span_reduce.py folds it); a statement cannot read that
about itself while it runs, so its own attribution happens on the
planes that already run host-side:

- **coarse plane (always on)**: every statement activates a
  ``ProfileSink`` on a thread-local (``profile.active``). The
  data-movement call sites that already meter bytes — device uploads,
  streamed page loops, spill partition sweeps, shuffle outbox/inbox —
  note their bytes/stalls into the current sink. Overhead is a
  thread-local read plus a dict update per event; results are
  untouched (the jitted program never sees the sink).
- **fine plane (diagnostics only)**: EXPLAIN ANALYZE / armed
  diagnostics re-run the plan UNJITTED with ``ExecParams(profile=…)``,
  where ``compile_plan`` wraps every operator closure with a timed
  span (``ProfileSink.op``): block_until_ready at operator exit, self
  time = inclusive elapsed minus child elapsed, so operator
  device_seconds sum to the profiled execution wall exactly. DistSQL
  remote flows run their stages eagerly anyway, so there the fine
  plane times the REAL execution and ships home as ``flow_profile``
  wire frames (like ``flow_span``) for a node-tagged cluster profile.

Concurrency discipline follows ops/pallas/groupagg_large.py
`_KernelTally`: one lock around the op table, per-statement sinks on a
thread-local (never a shared global), per-flow sinks merged at the
gateway.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

FIELDS = ("rows", "batches", "device_seconds", "bytes_uploaded",
          "bytes_shuffled", "bytes_spilled", "stall_seconds")


@dataclass
class OpProfile:
    """One operator's accumulated execution statistics."""
    rows: int = 0
    batches: int = 0
    device_seconds: float = 0.0
    bytes_uploaded: int = 0
    bytes_shuffled: int = 0
    bytes_spilled: int = 0
    stall_seconds: float = 0.0

    def add(self, **deltas) -> None:
        for k, v in deltas.items():
            setattr(self, k, getattr(self, k) + v)

    def merge(self, other: "OpProfile") -> None:
        for k in FIELDS:
            setattr(self, k, getattr(self, k) + getattr(other, k))

    def to_wire(self) -> dict:
        return {k: getattr(self, k) for k in FIELDS}

    @staticmethod
    def from_wire(d: dict) -> "OpProfile":
        return OpProfile(**{k: d.get(k, 0) for k in FIELDS})

    @property
    def bytes_moved(self) -> int:
        return (self.bytes_uploaded + self.bytes_shuffled
                + self.bytes_spilled)


class _OpFrame:
    """Mutable holder yielded by ``ProfileSink.op`` so the caller can
    report the operator's output rows after the child ran."""
    __slots__ = ("rows", "bytes_uploaded")

    def __init__(self):
        self.rows = 0
        self.bytes_uploaded = 0


def op_label(node) -> str:
    """Stable human-readable label for a plan node (collision-suffixed
    per sink: two bare Filters become ``filter`` and ``filter#2``)."""
    kind = type(node).__name__.lower()
    detail = None
    for attr in ("table", "alias"):
        v = getattr(node, attr, None)
        if isinstance(v, str) and v and not v.startswith("__"):
            detail = v
            break
    return f"{kind}:{detail}" if detail else kind


class ProfileSink:
    """Thread-safe per-statement operator profile accumulator.

    Entries are keyed ``(node_tag, label)`` where node_tag is None for
    locally-executed operators and a node id for entries stitched from
    remote ``flow_profile`` frames. The plan-node → label mapping is
    kept so EXPLAIN ANALYZE can annotate the rendered tree by node
    object identity (same contract as the est/actual `actuals` dict).
    """

    def __init__(self):
        self._mu = threading.Lock()
        self._ops: dict[tuple, OpProfile] = {}
        self._node_labels: dict[int, str] = {}   # id(plan node) -> label
        self._label_counts: dict[str, int] = {}
        self._tls = threading.local()
        # fine-plane execution wall of the profiled region (DistSQL
        # flows time their eager stage run into this, excluding
        # planning/setup — see distsql/node.py _run_local)
        self.wall_s = 0.0
        # [(node_id, device_time_s)] walls stitched from remote
        # flow_profile frames at the gateway (_pump_and_union)
        self.remote_walls: list = []

    # -- labeling --------------------------------------------------
    def _label_for(self, plan_node) -> str:
        key = id(plan_node)
        lbl = self._node_labels.get(key)
        if lbl is None:
            base = op_label(plan_node)
            n = self._label_counts.get(base, 0) + 1
            self._label_counts[base] = n
            lbl = base if n == 1 else f"{base}#{n}"
            self._node_labels[key] = lbl
        return lbl

    # -- recording -------------------------------------------------
    def note(self, label: str, node_tag=None, **deltas) -> None:
        with self._mu:
            ent = self._ops.get((node_tag, label))
            if ent is None:
                ent = self._ops[(node_tag, label)] = OpProfile()
            ent.add(**deltas)

    def note_op(self, plan_node, **deltas) -> None:
        with self._mu:
            lbl = self._label_for(plan_node)
            ent = self._ops.get((None, lbl))
            if ent is None:
                ent = self._ops[(None, lbl)] = OpProfile()
            ent.add(**deltas)

    @contextmanager
    def op(self, plan_node):
        """Timed operator span with self-time attribution: the frame's
        inclusive elapsed propagates to the parent frame's child-time,
        so per-operator device_seconds sum EXACTLY to the root's
        inclusive wall across the tree."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        child_time = [0.0]
        stack.append(child_time)
        frame = _OpFrame()
        t0 = time.monotonic()
        try:
            yield frame
        finally:
            elapsed = time.monotonic() - t0
            stack.pop()
            if stack:
                stack[-1][0] += elapsed
            self.note_op(plan_node, rows=frame.rows, batches=1,
                         device_seconds=max(0.0,
                                            elapsed - child_time[0]),
                         bytes_uploaded=frame.bytes_uploaded)

    # -- reading ---------------------------------------------------
    def op_entry(self, plan_node) -> OpProfile | None:
        with self._mu:
            lbl = self._node_labels.get(id(plan_node))
            return None if lbl is None else self._ops.get((None, lbl))

    def entries(self) -> list[tuple]:
        """[(node_tag, label, OpProfile)] snapshot, stable order."""
        with self._mu:
            return sorted(
                ((tag, lbl, OpProfile(**ent.to_wire()))
                 for (tag, lbl), ent in self._ops.items()),
                key=lambda e: (e[0] is not None, e[0] or 0, e[1]))

    def total_device_seconds(self) -> float:
        with self._mu:
            return sum(e.device_seconds for e in self._ops.values())

    def total_bytes_moved(self) -> int:
        with self._mu:
            return sum(e.bytes_moved for e in self._ops.values())

    def total_stall_seconds(self) -> float:
        with self._mu:
            return sum(e.stall_seconds for e in self._ops.values())

    def summary(self, top: int = 3) -> dict:
        """Bench-facing digest: top-N operators by device_seconds and
        the statement's total bytes moved."""
        ents = self.entries()
        ranked = sorted(ents, key=lambda e: -e[2].device_seconds)[:top]
        return {
            "top_ops": [
                {"op": (f"n{tag}/{lbl}" if tag is not None else lbl),
                 "device_seconds": round(e.device_seconds, 6),
                 "rows": e.rows, "bytes_moved": e.bytes_moved}
                for tag, lbl, e in ranked],
            "bytes_moved": sum(e[2].bytes_moved for e in ents),
            "device_seconds": round(
                sum(e[2].device_seconds for e in ents), 6),
        }

    # -- wire / merge ----------------------------------------------
    def to_wire(self, node=None) -> list[dict]:
        """Serialize for a ``flow_profile`` frame; entries already
        node-tagged keep their tag, local ones take ``node``."""
        with self._mu:
            return [dict(op=lbl, node=(tag if tag is not None else node),
                         **ent.to_wire())
                    for (tag, lbl), ent in sorted(
                        self._ops.items(),
                        key=lambda kv: (kv[0][0] is not None,
                                        kv[0][0] or 0, kv[0][1]))]

    def merge_wire(self, wire: list[dict], node=None) -> None:
        for d in wire:
            tag = d.get("node", node)
            lbl = d.get("op", "?")
            with self._mu:
                ent = self._ops.get((tag, lbl))
                if ent is None:
                    ent = self._ops[(tag, lbl)] = OpProfile()
                ent.merge(OpProfile.from_wire(d))

    def merge(self, other: "ProfileSink", node=None) -> None:
        self.merge_wire(other.to_wire(node=node))


# -- thread-local active sink (per-statement, never a global) -------
_active = threading.local()


def current() -> ProfileSink | None:
    """The executing statement's sink, if any (None off-statement)."""
    return getattr(_active, "sink", None)


def requested() -> bool:
    """True when the statement wants FINE per-operator profiles shipped
    back from remote flows (EXPLAIN ANALYZE (DEBUG) / armed capture) —
    the analogue of tracing.recording_requested()."""
    return bool(getattr(_active, "fine", False))


@contextmanager
def active(sink: ProfileSink | None, fine: bool = False):
    """Install ``sink`` as the thread's current statement sink. Nested
    activations restore the outer sink on exit (internal statements
    run by an outer one must not pollute its profile)."""
    prev = getattr(_active, "sink", None)
    prev_fine = getattr(_active, "fine", False)
    _active.sink = sink
    _active.fine = fine
    try:
        yield sink
    finally:
        _active.sink = prev
        _active.fine = prev_fine


def note(label: str, **deltas) -> None:
    """Convenience for data-plane call sites: record into the current
    statement's sink when one is active, else drop (never raises)."""
    s = current()
    if s is not None:
        s.note(label, **deltas)
