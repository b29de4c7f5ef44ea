"""Metrics: counters, gauges, histograms + Prometheus text export.

The analogue of the reference's metric registry (pkg/util/metric/
registry.go:31) and its Prometheus exporter (prometheus_exporter.go).
Every subsystem registers named metrics here; the Node's status
endpoint serves the text exposition format.

Func metrics (FuncCounter/FuncGauge) read their value from a callback
at scrape time — that lets hot paths keep their existing plain-int
counters (SocketTransport.sent, DistSender.retries, ...) and still
surface through /_status/vars without adding a lock acquisition per
frame. Registered collectors run before every snapshot/export to
refresh dynamic families (per-peer breaker gauges).

The process's own cost (`register_process_metrics`): CPU seconds of
the process and of its Python threads by role, and the collector's
pauses by generation. Func counters read at snapshot time and a
`gc.callbacks` stopwatch that runs only when a collection does, so
nothing of it is on a statement's path.
"""

from __future__ import annotations

import gc
import math
import threading
import time
import weakref
from typing import Callable, Optional


NUM_BUCKETS = 40


def log2_bucket_index(v: float, num_buckets: int = NUM_BUCKETS) -> int:
    """Bucket index for one observation in the shared log2 layout
    (used by Histogram below and utils/sqlstats latency buckets, so
    their quantiles agree)."""
    if v <= 0:
        return 0
    return min(num_buckets - 1, max(0, int(math.log2(v * 1e6) + 1)))


def log2_bucket_bound(i: int) -> float:
    """Upper bound (inclusive, seconds/units) of bucket `i`."""
    return (2.0 ** (i - 1)) / 1e6


def buckets_quantile(buckets: list, q: float) -> float:
    """Quantile estimate over log2 bucket counts: the upper bound of
    the bucket holding the q-th observation."""
    total = sum(buckets)
    if total == 0:
        return 0.0
    target = q * total
    acc = 0
    for i, c in enumerate(buckets):
        acc += c
        if acc >= target:
            return log2_bucket_bound(i)
    return log2_bucket_bound(len(buckets) - 1)


class Counter:
    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._v = 0
        self._lock = threading.Lock()

    def inc(self, delta: int = 1) -> None:
        with self._lock:
            self._v += delta

    def value(self) -> int:
        return self._v


class Gauge:
    def __init__(self, name: str, help_: str = ""):
        self.name = name
        self.help = help_
        self._v = 0.0
        self._lock = threading.Lock()

    def set(self, v: float) -> None:
        with self._lock:
            self._v = v

    def inc(self, delta: float = 1.0) -> None:
        with self._lock:
            self._v += delta

    def dec(self, delta: float = 1.0) -> None:
        self.inc(-delta)

    def value(self) -> float:
        return self._v


class FuncCounter:
    """Counter whose value is read from a callback at scrape time."""

    def __init__(self, name: str, fn: Callable[[], float],
                 help_: str = ""):
        self.name = name
        self.help = help_
        self._fn = fn

    def value(self):
        try:
            return self._fn()
        except Exception:
            return 0


class FuncGauge(FuncCounter):
    pass


class Histogram:
    """Log-bucketed latency/size histogram (the reference uses HDR-ish
    histograms; log2 buckets keep it dependency-free)."""

    def __init__(self, name: str, help_: str = "",
                 num_buckets: int = NUM_BUCKETS):
        self.name = name
        self.help = help_
        self._buckets = [0] * num_buckets
        self._sum = 0.0
        self._count = 0
        self._lock = threading.Lock()

    def observe(self, v: float) -> None:
        b = log2_bucket_index(v, len(self._buckets))
        with self._lock:
            self._buckets[b] += 1
            self._sum += v
            self._count += 1

    def value(self) -> dict:
        return {"count": self._count, "sum": self._sum}

    def bucket_bounds(self) -> list[float]:
        """Upper bound (inclusive, seconds/units) of each bucket."""
        return [log2_bucket_bound(i)
                for i in range(len(self._buckets))]

    def buckets(self) -> list[int]:
        with self._lock:
            return list(self._buckets)

    def quantile(self, q: float) -> float:
        with self._lock:
            return buckets_quantile(self._buckets, q)


class MetricRegistry:
    """Named metric registry (pkg/util/metric/registry.go:31)."""

    def __init__(self):
        self._metrics: dict[str, object] = {}
        self._collectors: list[Callable[[], None]] = []
        self._lock = threading.Lock()

    def counter(self, name: str, help_: str = "") -> Counter:
        return self._get_or_add(name, lambda: Counter(name, help_))

    def gauge(self, name: str, help_: str = "") -> Gauge:
        return self._get_or_add(name, lambda: Gauge(name, help_))

    def histogram(self, name: str, help_: str = "") -> Histogram:
        return self._get_or_add(name, lambda: Histogram(name, help_))

    def func_counter(self, name: str, fn: Callable[[], float],
                     help_: str = "") -> FuncCounter:
        return self._get_or_add(name,
                                lambda: FuncCounter(name, fn, help_))

    def func_gauge(self, name: str, fn: Callable[[], float],
                   help_: str = "") -> FuncGauge:
        return self._get_or_add(name,
                                lambda: FuncGauge(name, fn, help_))

    def add_collector(self, fn: Callable[[], None]) -> None:
        """Run `fn` before every snapshot/export; collectors refresh
        dynamic metric families (per-peer gauges) in place."""
        with self._lock:
            self._collectors.append(fn)

    def _collect(self) -> None:
        for fn in list(self._collectors):
            try:
                fn()
            except Exception:
                pass

    def _get_or_add(self, name: str, mk):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = mk()
                self._metrics[name] = m
            return m

    def get(self, name: str) -> Optional[object]:
        return self._metrics.get(name)

    def snapshot(self) -> dict:
        self._collect()
        return {name: m.value() for name, m in sorted(self._metrics.items())}

    def to_prometheus(self) -> str:
        """Text exposition format (prometheus_exporter.go)."""
        self._collect()
        out = []
        for name, m in sorted(self._metrics.items()):
            pname = name.replace(".", "_").replace("-", "_")
            if m.help:
                help_ = m.help.replace("\\", "\\\\").replace("\n", "\\n")
                out.append(f"# HELP {pname} {help_}")
            if isinstance(m, (Counter, FuncCounter)) and \
                    not isinstance(m, (Gauge, FuncGauge)):
                out.append(f"# TYPE {pname} counter")
                out.append(f"{pname} {m.value()}")
            elif isinstance(m, (Gauge, FuncGauge)):
                out.append(f"# TYPE {pname} gauge")
                out.append(f"{pname} {m.value()}")
            elif isinstance(m, Histogram):
                # Real cumulative histogram exposition: each
                # `le`-labelled bucket counts observations <= its
                # upper bound, finishing at +Inf == _count.
                v = m.value()
                out.append(f"# TYPE {pname} histogram")
                acc = 0
                for bound, c in zip(m.bucket_bounds(), m.buckets()):
                    acc += c
                    out.append(
                        f'{pname}_bucket{{le="{bound:.6g}"}} {acc}')
                out.append(f'{pname}_bucket{{le="+Inf"}} {v["count"]}')
                out.append(f"{pname}_sum {v['sum']}")
                out.append(f"{pname}_count {v['count']}")
        return "\n".join(out) + "\n"


# -- the process's own cost --------------------------------------------------

# thread-name prefix -> group of process.threads.cpu.seconds.<group>
THREAD_GROUPS = (("pgfront-loop", "reactor"), ("pgfront-worker", "workers"),
                 ("mesh-dispatch-", "mesh_dispatch"))
OTHER_THREADS = "other"
THREAD_GROUP_NAMES = tuple(g for _, g in THREAD_GROUPS) + (OTHER_THREADS,)


class _GcPauseHistogram(Histogram):
    """Observed from a gc callback only: one writer at a time (a
    collection does not nest), and no lock, because a reader that
    allocates under the lock (`buckets()`) can start the very
    collection whose callback would then wait for it."""

    def observe(self, v: float) -> None:
        self._buckets[log2_bucket_index(v, len(self._buckets))] += 1
        self._sum += v
        self._count += 1


class _ProcessStats:
    """One a process: its threads' CPU clocks by group and the
    collector's pauses. Registries share it (an Engine each has one),
    since the threads and the collector are the process's."""

    def __init__(self):
        self._lock = threading.Lock()
        # ident -> [weakref to the Thread, its CPU clock id, group,
        # CPU ns last read]; a thread that exited keeps its last
        # reading in `_retired` (an ident is reused)
        self._threads: dict = {}
        self._retired = dict.fromkeys(THREAD_GROUP_NAMES, 0)
        self._read_ns = 0
        self._last: dict = {}
        self.gc_pause = [
            _GcPauseHistogram(
                f"process.gc.pause.seconds.gen{g}",
                f"seconds the collector stopped the interpreter, a "
                f"generation-{g} pass each") for g in range(3)]
        self._gc_t0 = 0
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter_ns()
        elif self._gc_t0:
            self.gc_pause[min(int(info.get("generation", 0)), 2)].observe(
                (time.perf_counter_ns() - self._gc_t0) / 1e9)
            self._gc_t0 = 0

    @staticmethod
    def _group(name: str) -> str:
        for prefix, group in THREAD_GROUPS:
            if name.startswith(prefix):
                return group
        return OTHER_THREADS

    def thread_cpu_seconds(self) -> dict:
        """{group: CPU seconds of its threads, live and exited}. One
        walk serves the four counters of one snapshot (readings under
        a millisecond old are handed out again)."""
        with self._lock:
            now = time.monotonic_ns()
            if now - self._read_ns > 1_000_000:
                self._last = self._walk()
                self._read_ns = now
            return self._last

    def _walk(self) -> dict:
        # a thread's clock id is taken once, while it is alive;
        # reading a clock whose thread has gone is an OSError and
        # keeps the last value
        live = {t.ident: t for t in threading.enumerate()
                if t.ident is not None}
        for ident, t in live.items():
            ent = self._threads.get(ident)
            if ent is not None and ent[0]() is not t:
                self._retired[ent[2]] += ent[3]
                ent = None
            if ent is None:
                try:
                    clk = time.pthread_getcpuclockid(ident)
                except OSError:
                    continue
                ent = self._threads[ident] = [
                    weakref.ref(t), clk, self._group(t.name), 0]
            try:
                ent[3] = time.clock_gettime_ns(ent[1])
            except OSError:
                pass
        for ident in [i for i in self._threads if i not in live]:
            ent = self._threads.pop(ident)
            self._retired[ent[2]] += ent[3]
        out = dict(self._retired)
        for _, _, group, ns in self._threads.values():
            out[group] += ns
        return {g: ns / 1e9 for g, ns in out.items()}


_process_stats: Optional[_ProcessStats] = None
_process_stats_lock = threading.Lock()


def _process_readers() -> dict:
    """{metric name: (reader, help)} of the process's func counters,
    over the one _ProcessStats of the process (made on first use)."""
    global _process_stats
    with _process_stats_lock:
        if _process_stats is None:
            _process_stats = _ProcessStats()
        stats = _process_stats
    readers = {
        "process.cpu.seconds": (
            time.process_time,
            "CPU seconds of the whole process, every thread, user + "
            "system"),
        "process.wall.seconds": (
            time.monotonic,
            "the monotonic clock, beside process.cpu.seconds: a delta "
            "of one over a delta of the other is cores busy")}
    for group in THREAD_GROUP_NAMES:
        readers[f"process.threads.cpu.seconds.{group}"] = (
            lambda g=group: stats.thread_cpu_seconds()[g],
            "CPU seconds of the Python threads of one role (reactor: "
            "pgfront-loop; workers: pgfront-worker-*; mesh_dispatch: "
            "mesh-dispatch-*; other: the rest), exited ones included")
    return readers


def register_process_metrics(registry: MetricRegistry) -> None:
    """`process.cpu.seconds`, `process.wall.seconds`,
    `process.threads.cpu.seconds.{reactor,workers,mesh_dispatch,other}`
    and `process.gc.pause.seconds.gen{0,1,2}` in `registry`."""
    for name, (fn, help_) in _process_readers().items():
        registry.func_counter(name, fn, help_)
    for h in _process_stats.gc_pause:
        registry._get_or_add(h.name, lambda h=h: h)


def process_status() -> dict:
    """The same readings under their names less `process.`, read now
    (what /_status/runtime shows)."""
    out = {name: fn() for name, (fn, _) in _process_readers().items()}
    out.update({h.name: h.value() for h in _process_stats.gc_pause})
    return {k[len("process."):]: v for k, v in sorted(out.items())}
