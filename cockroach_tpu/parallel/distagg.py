"""Distributed query execution over the device mesh.

The TPU answer to DistSQL physical planning (SURVEY.md §2.2, §A.6):

  reference                               this module
  ---------                               -----------
  PartitionSpans assigns key spans        table rows shard over the
  to nodes by leaseholder                 mesh's `shards` axis
  per-node TableReader + partial agg      the same compiled plan runs
  processors (SetupFlow gRPC)             as ONE SPMD program/shard_map
  final-stage merge at the gateway        jax.lax.psum/pmin/pmax over
  (Outbox/Inbox streams, HashRouter)      ICI inside the program
  lookup-join data movement               broadcast (replicated) build
                                          side — dimension tables are
                                          small; no shuffle needed

Eligibility: the plan root chain must be Limit?/Sort?/Aggregate —
ungrouped, dense segment-sum strategy, or hash strategy (round 3:
shard-local hash groups EXCHANGE to their hash-owner shard via the
all_to_all shuffle, each shard merges only its 1/D of the groups, and
the disjoint merged groups concatenate via one all_gather — see
parallel/shuffle.py + exec/compile.py _compile_hash_dist_aggregate) —
with every HashJoin build subtree scan-only (replicated).
Sharded⋈sharded joins run through the same shuffle at the ops layer
(shuffle.exchange both sides by join key, then a local join per
shard). DISTINCT aggregates fall back to single-device execution.
After the collectives, all outputs are replicated, so
Sort/Limit/HAVING above the Aggregate run identically on every shard.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass

import jax
from jax import shard_map

from ..sql import plan as P
from ..utils import tracing
from . import mesh as meshmod


@dataclass
class DistDecision:
    ok: bool
    sharded: set  # aliases row-sharded over the mesh
    replicated: set  # aliases replicated (join build sides)
    reason: str = ""


def analyze(node: P.PlanNode) -> DistDecision:
    """Decide if the plan can run as one SPMD program (see module doc)."""
    sharded: set = set()
    replicated: set = set()

    def scan_only(n) -> bool:
        if isinstance(n, P.Scan):
            replicated.add(n.alias)
            return True
        if isinstance(n, P.Filter):
            return scan_only(n.child)
        return False

    def probe_chain(n) -> bool:
        """The probe spine: Scan/Filter/Project/HashJoin(with scan-only
        build)."""
        if isinstance(n, P.Scan):
            sharded.add(n.alias)
            return True
        if isinstance(n, (P.Filter, P.Project)):
            return probe_chain(n.child)
        if isinstance(n, P.HashJoin):
            if n.join_type not in ("inner", "left", "semi", "anti"):
                return False
            return probe_chain(n.left) and scan_only(n.right)
        return False

    n = node
    if isinstance(n, P.Limit):
        n = n.child
    if isinstance(n, P.Sort):
        n = n.child
    if not isinstance(n, P.Aggregate):
        return DistDecision(False, set(), set(), "root is not an aggregate")
    for a in n.aggs:
        if a.distinct:
            return DistDecision(False, set(), set(), "DISTINCT aggregate")
    if n.grouping_sets is not None:
        return DistDecision(False, set(), set(), "grouping sets")
    if not probe_chain(n.child):
        return DistDecision(False, set(), set(), "unsupported probe chain")
    return DistDecision(True, sharded, replicated)


def partials_replannable(node: P.PlanNode) -> bool:
    """May a flow that lost a producer re-run this statement's partial
    fragments on a shrunken node set (distsql/node.py Gateway.run)?

    Yes when the partial aggregates merge associatively — sum/count/
    min/max partials recomputed under a different span assignment
    still combine to the same final answer. DISTINCT aggregates are
    the exception (their partials are sets, and our partial stage
    doesn't ship them); those degrade straight to gateway-local
    execution. Non-aggregate reads carry no partial state at all and
    are trivially replannable."""
    n = node
    if isinstance(n, P.Limit):
        n = n.child
    if isinstance(n, P.Sort):
        n = n.child
    if not isinstance(n, P.Aggregate):
        return True
    return not any(a.distinct for a in n.aggs)


# XLA's host-platform collectives rendezvous by participant count:
# when two 8-participant AllReduce executions interleave from
# different threads, each grabs some of the device slots and both
# wait forever (collective_ops_utils.h "may be stuck"). Earlier
# rounds serialized every distributed execution on one process-wide
# lock — safe, but a session held the lock for the whole device
# execution, so concurrent distributed plans ran strictly one at a
# time. The fix below keeps the ordering invariant (one thread issues
# every execution for a device set, so rendezvous never interleave)
# while dropping the hold time to just the DISPATCH: jitted calls
# return as soon as XLA enqueues the work, so the dispatcher can
# issue query i+1 while the devices still execute query i.
#
# Host-platform caveat: the CPU client runs every execution's
# per-device computations on ONE fixed-size executor pool, so two
# collective executions live at once can each grab a subset of the
# pool and starve at their rendezvous (neither can seat all its
# participants; both wait forever). Real accelerators order programs
# per core, so dispatch/execute overlap is safe there — on the cpu
# backend the dispatcher instead drains each execution to completion
# before issuing the next (_dispatch_drains below).

_SHUTDOWN = object()

_DRAIN = None  # lazily: True on the cpu backend (see caveat above)


def _dispatch_drains() -> bool:
    global _DRAIN
    if _DRAIN is None:
        _DRAIN = jax.default_backend() == "cpu"
    return _DRAIN


def _fail_future(fut, msg: str) -> None:
    try:
        fut.set_exception(CollectiveFault(msg))
    except Exception:
        pass  # already done/cancelled


class _MeshDispatcher:
    """Single-thread FIFO executor for one device set.

    Sessions enqueue collective calls and block on futures; the one
    dispatcher thread issues XLA executions back-to-back in program
    order. Keyed by the mesh's device-id tuple, NOT mesh identity:
    two equal meshes built by two engines over the same devices share
    one rendezvous domain and MUST share one dispatcher.

    A dispatcher thread that dies must not leave futures hanging: a
    loop-level failure fails the in-flight and queued futures with
    CollectiveFault (sessions fall back gateway-locally) and marks the
    dispatcher dead; the next submit() respawns the thread. shutdown()
    retires the thread cleanly (engine close / test teardown) — a
    later submit on a retired dispatcher likewise respawns."""

    def __init__(self, name: str):
        import queue
        self._name = name
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()
        self._dead = False
        self._kill_next = False  # fault-injection hook (inject_death)
        self.respawns = 0
        self._thread: threading.Thread = None
        self._spawn_locked()

    def _spawn_locked(self):
        self._thread = threading.Thread(
            target=self._loop, name=f"mesh-dispatch-{self._name}",
            daemon=True)
        self._thread.start()

    def depth(self) -> int:
        return self._q.qsize()

    def inject_death(self) -> None:
        """Fault hook (tests): the dispatcher thread dies abruptly on
        its next dequeue, outside the per-item protection — the shape
        of a real dispatch-loop bug."""
        self._kill_next = True

    def shutdown(self, timeout: float = 2.0) -> None:
        with self._lock:
            t = self._thread
            self._q.put(_SHUTDOWN)
        if t is not None:
            t.join(timeout)

    def _fail_pending_locked(self) -> None:
        import queue as _queue
        while True:
            try:
                item = self._q.get_nowait()
            except _queue.Empty:
                return
            if item is _SHUTDOWN:
                continue
            _fail_future(item[3], "mesh dispatcher thread died")

    def submit(self, fn, args, kwargs, on_start=None):
        import concurrent.futures
        import time as _time
        from ..exec import coldstart
        fut: concurrent.futures.Future = concurrent.futures.Future()
        # carry the submitting statement's compile-attribution cell:
        # tracing (and hence XLA backend compilation) happens on the
        # dispatcher thread, but the compile bill belongs to the
        # statement that enqueued the call (exec/coldstart.py)
        item = (fn, args, kwargs, fut, _time.monotonic(),
                on_start, coldstart.attribution_cell())
        with self._lock:
            if self._dead or self._thread is None \
                    or not self._thread.is_alive():
                self._fail_pending_locked()
                self._dead = False
                self.respawns += 1
                self._spawn_locked()
            self._q.put(item)
        return fut

    def _loop(self):
        import time as _time
        from ..exec import coldstart
        fut = None
        try:
            while True:
                item = self._q.get()
                if item is _SHUTDOWN:
                    with self._lock:
                        self._dead = True
                        self._fail_pending_locked()
                    return
                fn, args, kwargs, fut, t_enq, on_start, cell = item
                if self._kill_next:
                    self._kill_next = False
                    raise RuntimeError("injected dispatcher death")
                if on_start is not None:
                    try:
                        on_start(_time.monotonic() - t_enq)
                    except Exception:
                        pass
                if not fut.set_running_or_notify_cancel():
                    continue
                prev = coldstart.set_attribution_cell(cell)
                try:
                    out = fn(*args, **kwargs)
                    if _dispatch_drains():
                        jax.block_until_ready(out)
                    fut.set_result(out)
                except BaseException as e:
                    fut.set_exception(e)
                finally:
                    coldstart.set_attribution_cell(prev)
        except BaseException:
            # Loop-level failure (the per-item try above shields normal
            # execution errors): fail the in-flight future and every
            # queued one so no session blocks forever, mark dead so the
            # next submit() respawns under the same lock — no window
            # where an enqueue can race a dying thread into a hang.
            if fut is not None:
                _fail_future(fut, "mesh dispatcher thread died")
            with self._lock:
                self._dead = True
                self._fail_pending_locked()


_DISPATCHERS: dict = {}
_DISPATCHERS_LOCK = threading.Lock()


def shutdown_dispatchers(mesh=None) -> None:
    """Retire dispatcher threads (engine close / test teardown): with a
    mesh, only that device set's dispatcher; otherwise every one. The
    module dict would otherwise accumulate a live thread per device-id
    set forever; the thread is the resource, so it is joined here while
    the dispatcher OBJECT stays registered — device-set -> dispatcher
    identity must be stable (two dispatchers on one rendezvous domain
    would reintroduce the interleaving deadlock), and any later submit
    transparently respawns the retired thread."""
    with _DISPATCHERS_LOCK:
        if mesh is None:
            items = list(_DISPATCHERS.values())
        else:
            key = tuple(int(d.id) for d in mesh.devices.flat)
            d = _DISPATCHERS.get(key)
            items = [d] if d is not None else []
    for d in items:
        d.shutdown()


class CollectiveFault(RuntimeError):
    """An injected ICI fault lost a collective dispatch. Raised from
    queued_collective_call when a fault rule drops the call; the
    session layer falls back to gateway-local execution (Prepared.run
    re-prepares with distsql off)."""


# seeded rpc.context.FaultInjector aimed at the ICI dispatch path, or
# None (the default: no fault evaluation, zero overhead). Unlike the
# RPC plane's per-link rules, collectives have one logical "link" —
# the (frm, to) pair install_ici_faults registered its rules under.
_ICI_FAULTS = None


def install_ici_faults(injector, frm="ici", to="ici") -> None:
    """Point the collective dispatch path at a FaultInjector (tests/
    chaos drills). Every queued_collective_call consults
    ``injector.plan(frm, to)`` before touching the dispatcher:
    drop -> CollectiveFault (no dispatch), delay -> sleep before
    dispatch, dup -> dispatch twice and keep the last result (the
    collectives are read-only reductions, so a duplicate dispatch is
    idempotent — what at-least-once delivery would do). Pass None to
    heal."""
    global _ICI_FAULTS
    _ICI_FAULTS = (injector, frm, to) if injector is not None else None


def _dispatcher_for(mesh) -> _MeshDispatcher:
    if mesh is None:
        key: tuple = ("process",)
    else:
        key = tuple(int(d.id) for d in mesh.devices.flat)
    with _DISPATCHERS_LOCK:
        d = _DISPATCHERS.get(key)
        if d is None:
            d = _MeshDispatcher("-".join(str(k) for k in key))
            _DISPATCHERS[key] = d
        return d


def queued_collective_call(jfn, metrics=None, mesh=None,
                           movement=None, lease_bytes: int = 0):
    """Wrap a jitted multi-device callable so concurrent sessions
    cannot interleave collective rendezvous (deadlock otherwise —
    this must wrap the CALL: a lock inside the traced function would
    only run at trace time). Calls route through the per-mesh FIFO
    dispatcher above; the caller blocks on a future, so semantics
    match the old locked call, minus the serialization of device
    execution time.

    With a MetricRegistry, each call counts as one collective
    dispatch, its wall time feeds the allreduce latency histogram,
    and the queue depth / enqueue-to-dispatch wait surface as
    exec.queue.* — the data-movement accounting a distributed
    accelerator engine tunes against."""
    import time as _time
    m_calls = m_secs = m_depth = m_wait = None
    if metrics is not None:
        m_calls = metrics.counter(
            "exec.allreduce.calls",
            "distributed (collective) plan dispatches")
        m_secs = metrics.histogram(
            "exec.allreduce.seconds",
            "wall seconds per collective dispatch (incl. queue wait)")
        m_depth = metrics.gauge(
            "exec.queue.depth",
            "per-mesh collective dispatch-queue depth at enqueue")
        m_wait = metrics.histogram(
            "exec.queue.wait_seconds",
            "enqueue-to-dispatch wait per collective call")
    disp = _dispatcher_for(mesh)

    def on_start(wait: float):
        if m_wait is not None:
            m_wait.observe(wait)

    @functools.wraps(jfn)
    def call(*args, **kwargs):
        # unified transfer budget (exec/movement.py): a collective
        # dispatch's shuffle/exchange working buffers are LEASE-
        # admitted — they wait for other transient traffic to drain
        # like every other mover, degrading to observable overcommit
        # only when the pool is genuinely full (the buffers allocate
        # inside XLA either way)
        if movement is not None and lease_bytes > 0:
            with movement.exchange_lease(lease_bytes):
                return _call_inner(*args, **kwargs)
        return _call_inner(*args, **kwargs)

    def _submit_and_wait(args, kwargs):
        if tracing.current_span() is None:
            return disp.submit(jfn, args, kwargs, on_start).result()
        # recording: a `queue` span from enqueue to the dispatcher's
        # pick-up, stamped on its thread, recorded here on ours; and
        # the call itself, which that thread runs while this one
        # waits: its stamps and its CPU, credited to the open span's
        # stage (`call`, Prepared.dispatch)
        started, ran = [], []
        cpu = tracing.reads_cpu()

        def picked_up(wait: float):
            started.append(_time.monotonic_ns())
            on_start(wait)

        def timed(*a, **kw):
            c0 = _time.thread_time_ns() if cpu else 0
            t0 = _time.monotonic_ns()
            try:
                return jfn(*a, **kw)
            finally:
                ran.append((t0, _time.monotonic_ns(),
                            _time.thread_time_ns() - c0 if cpu else 0,
                            threading.current_thread().name))
        t_enq = _time.monotonic_ns()
        try:
            return disp.submit(timed, args, kwargs, picked_up).result()
        finally:
            if started:
                tracing.record("queue", t_enq, started[0])
            if ran:
                t0, t1, spent, name = ran[0]
                tracing.stage_cpu(spent, call_thread=name, call_b=t0,
                                  call_e=t1)

    def _call_inner(*args, **kwargs):
        t0 = _time.monotonic()
        try:
            # ICI-path fault hook (install_ici_faults): evaluated
            # per dispatch so chaos tests exercise the same queue +
            # fallback machinery production hits on a flaky link
            faults = _ICI_FAULTS
            deliveries = [0.0]
            if faults is not None:
                inj, frm, to = faults
                deliveries = inj.plan(frm, to)
                if not deliveries:
                    raise CollectiveFault(
                        "fault injection dropped a collective "
                        "dispatch")
            # per-link shuffle rules (parallel/shuffle.py): the
            # exchange's D*(D-1) directed links each carry their own
            # drop/dup/delay rule, aggregated host-side at dispatch
            from .shuffle import link_fault_plan
            lp = link_fault_plan()
            if lp is not None:
                if not lp:
                    raise CollectiveFault(
                        "fault injection dropped a shuffle link")
                merged = max(len(deliveries), len(lp))
                dly = max(deliveries[0], lp[0])
                deliveries = [dly] + [0.0] * (merged - 1)
            out = None
            for d in deliveries:
                if d:
                    _time.sleep(d)
                if m_depth is not None:
                    m_depth.set(disp.depth() + 1)
                # domain-family gate (parallel/mesh.py): a full-mesh
                # and a sub-mesh execution share devices, so their
                # windows must not overlap — same-mode dispatches
                # still run concurrently
                win = meshmod.execution_window(mesh)
                if win is None:
                    out = _submit_and_wait(args, kwargs)
                else:
                    with win:
                        out = _submit_and_wait(args, kwargs)
            return out
        finally:
            if m_calls is not None:
                m_calls.inc()
                m_secs.observe(_time.monotonic() - t0)
    return call


def make_distributed_fn(runf, mesh, scan_aliases: dict, decision: DistDecision):
    """Wrap a compiled plan function in shard_map over `mesh`.

    runf: RunContext -> ColumnBatch (compiled with axis_name set)
    scan_aliases: alias -> table (the RunContext scans keys)
    Returns fn(scans, read_ts) -> ColumnBatch with replicated outputs.
    """
    from ..exec.compile import RunContext

    shard_leaf = meshmod.shard_spec()
    repl_leaf = meshmod.replicated_spec()

    def one(alias):
        return shard_leaf if alias in decision.sharded else repl_leaf

    def fn(scans, read_ts, nparts, pid, lits=()):
        return runf(RunContext(scans, read_ts, nparts, pid, params=lits))

    # pytree of specs matching (scans dict, read_ts, nparts, pid, lits)
    def spec_for_scans(scans):
        return {alias: jax.tree.map(lambda _: one(alias), b)
                for alias, b in scans.items()}

    def wrapped(scans, read_ts, nparts, pid, lits=()):
        # lits: stripped statement literals riding along as replicated
        # runtime scalars (the statement-shape plan cache,
        # exec/planparam.py); () for unparameterized plans.
        in_specs = (spec_for_scans(scans), repl_leaf, repl_leaf, repl_leaf,
                    tuple(repl_leaf for _ in lits))
        return shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=repl_leaf,
                         check_vma=False)(scans, read_ts, nparts,
                                          pid, lits)
    return wrapped
