"""Cold-start elimination: persistent compile cache + shape bucketing.

A restarted node used to recompile every plan from scratch: the
executable cache (`Engine._exec_cache`) is in-process, and XLA keeps
its compiled programs in memory only. This module wires three pieces
of cross-process warm-start state (ROADMAP item 5, the Tailwind-style
accelerator-management frame in PAPERS.md):

1. **Persistent XLA compile cache** — where the launcher set
   `JAX_COMPILATION_CACHE_DIR`, JAX already reads that directory and
   `init_compile_cache` leaves `jax_compilation_cache_dir` alone;
   where it did not, the cache goes to the fixed `<checkout>/.jax_cache`
   (the path is part of JAX's cache key, so a directory that moves
   never hits). JAX's key covers backend and compiler version, so one
   flat directory serves them all. Cluster setting
   `sql.exec.compile_cache.dir = off` disables it. Hit/miss/
   compile-seconds counters come from JAX's monitoring events and
   surface as `exec.compile.*` metrics. The shapes journal is a
   sidecar file in the same directory.

2. **Shape bucket ladder** — `ShapeLadder` generalizes the historical
   "pad row counts to the next power of two" rule into an explicit
   closed bucket set shared by resident uploads, streamed pages and
   spill partitions. `steps_per_octave = 1` IS the historical pow2
   ladder (bit-identical bucket choices); larger values insert
   evenly-spaced intermediate buckets per octave, trading a bounded
   number of extra executables for less padding waste. Every bucket
   stays a multiple of 128 so Pallas kernel eligibility
   (`n % 128 == 0`) is ladder-invariant.

3. **Shapes journal** — statements that miss the executable cache
   append their text to a journal next to the compile cache;
   `Engine.prewarm` replays the top-K texts from the previous run so
   a restarted node compiles (from the persistent cache: deserializes)
   its hot executables before the first query arrives.

Per-statement attribution: XLA backend compilation runs synchronously
on the thread that traced the jitted call, so a thread-local tally of
`/jax/core/compile/backend_compile_duration` events gives each
statement its own compile-seconds split (`thread_compile_seconds`
deltas around dispatch), surfaced in `/_status/statements` and as a
`compile_s` trace tag.
"""

from __future__ import annotations

import json
import os
import threading
from dataclasses import dataclass

_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_JOURNAL_NAME = "shapes_journal.jsonl"
_JOURNAL_MAX_BYTES = 8 << 20  # stop appending past this; bounded state

_LOCK = threading.Lock()
_LISTENERS = False
_ERROR: str | None = None  # why the cache is off, when it should be on

# process-wide tallies, bumped by the JAX monitoring listeners
_HITS = 0
_MISSES = 0
_SECONDS = 0.0
PREWARMED = 0  # statements re-prepared by Engine.prewarm

_TLS = threading.local()


def note_prewarmed() -> None:
    """Locked bump of the prewarm tally: engines prewarm on their own
    threads (tests run several engines in-process), and an unlocked
    cross-module ``PREWARMED += 1`` loses increments."""
    global PREWARMED
    with _LOCK:
        PREWARMED += 1


def cache_hits() -> int:
    return _HITS


def cache_misses() -> int:
    return _MISSES


def compile_seconds() -> float:
    return _SECONDS


def _cell() -> list:
    c = getattr(_TLS, "cell", None)
    if c is None:
        c = _TLS.cell = [0.0]
    return c


def thread_compile_seconds() -> float:
    """Cumulative XLA backend-compile seconds billed to THIS thread.
    Statement dispatch takes a delta around execution: compilation
    happens synchronously on the tracing thread — and when a plan is
    traced on a mesh-dispatcher thread instead, the dispatcher adopts
    the submitting thread's attribution cell (attribution_cell /
    set_attribution_cell), so the delta is still the statement's own
    compile bill."""
    return _cell()[0]


def attribution_cell() -> list:
    """The mutable cell compile seconds are billed to on this thread.
    Cross-thread executors (parallel/distagg._MeshDispatcher) capture
    it at submit time and adopt it on the worker around the call."""
    return _cell()


def set_attribution_cell(cell):
    """Point this thread's compile billing at `cell`; returns the
    previously active cell so callers can restore it."""
    prev = _cell()
    _TLS.cell = cell if cell is not None else [0.0]
    return prev


def _on_event(event: str, **kw) -> None:
    global _HITS, _MISSES
    if event == "/jax/compilation_cache/cache_hits":
        with _LOCK:
            _HITS += 1
    elif event == "/jax/compilation_cache/cache_misses":
        with _LOCK:
            _MISSES += 1


def _on_duration(event: str, duration: float, **kw) -> None:
    global _SECONDS
    if event == "/jax/core/compile/backend_compile_duration":
        with _LOCK:
            _SECONDS += duration
        _cell()[0] += duration


def _install_listeners() -> None:
    global _LISTENERS
    with _LOCK:
        if _LISTENERS:
            return
        _LISTENERS = True
    from jax._src import monitoring
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


def checkout_cache_dir() -> str:
    """`<checkout>/.jax_cache`: fixed beside the package, so every
    process started from this checkout computes the same cache keys."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), ".jax_cache")


def resolve_cache_dir(settings=None) -> str | None:
    """$JAX_COMPILATION_CACHE_DIR, else `<checkout>/.jax_cache`; None
    when `sql.exec.compile_cache.dir` is "off"."""
    if settings is not None and str(settings.get(
            "sql.exec.compile_cache.dir")).lower() == "off":
        return None
    return os.environ.get(_CACHE_ENV) or checkout_cache_dir()


def cache_error() -> str | None:
    """Why the last init_compile_cache left the cache off although it
    was not disabled (unwritable directory), else None."""
    return _ERROR


def init_compile_cache(settings=None) -> str | None:
    """Arm the JAX persistent compilation cache (idempotent). Returns
    the directory in effect, or None when disabled or unwritable —
    the engine then runs cold, and `cache_error()` says why."""
    global _ERROR
    d = resolve_cache_dir(settings)
    if d is None:
        return None
    import jax
    try:
        os.makedirs(d, exist_ok=True)  # sidecar tables live here too
    except OSError as e:
        with _LOCK:
            _ERROR = f"{d}: {e}"
        return None
    if os.environ.get(_CACHE_ENV):
        # placed from outside: JAX read the variable at import
        if jax.config.jax_compilation_cache_dir != d:
            raise RuntimeError(
                f"{_CACHE_ENV}={d!r} was set after jax was imported "
                f"(jax has {jax.config.jax_compilation_cache_dir!r}); "
                "set it in the environment the process starts with")
    elif jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
        from jax.experimental import compilation_cache as cc
        # a program compiled before the first Engine initialised the
        # cache with no directory; drop that so the path takes effect
        cc.compilation_cache.reset_cache()
    # every trace is worth persisting for an interactive engine: the
    # default 1s/min-size gates exist for training jobs whose tiny
    # programs aren't worth the disk
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # an op's metadata is read now: a device profile folds time by the
    # plan-operator scope on each op's path (exec/compile.compile_plan).
    # JAX's key leaves metadata out by default, and an executable
    # cached by a tree with other scope names (or none) would be
    # served with that tree's names on its ops. The price: an edit
    # that moves the lines of a traced closure compiles that plan anew.
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    _install_listeners()
    with _LOCK:
        _ERROR = None
    return d


def register_metrics(metrics) -> None:
    """exec.compile.* counters (idempotent per registry: func_counter
    re-registration under the same name returns the existing one)."""
    metrics.func_counter(
        "exec.compile.cache_hit", cache_hits,
        "XLA executables served from the persistent compile cache "
        "(process-wide; >0 on a warm restart is the cross-process "
        "reuse proof)")
    metrics.func_counter(
        "exec.compile.cache_miss", cache_misses,
        "XLA compilations that went to the backend compiler because "
        "the persistent cache had no entry")
    metrics.func_counter(
        "exec.compile.seconds", compile_seconds,
        "cumulative seconds inside XLA backend compilation "
        "(process-wide; near zero on a warm restart)")
    metrics.func_counter(
        "exec.compile.prewarmed", lambda: PREWARMED,
        "statements re-prepared by Engine.prewarm from the shapes "
        "journal at startup")


# -- shape bucket ladder -----------------------------------------------------

def _next_pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()


@dataclass(frozen=True)
class ShapeLadder:
    """The closed set of padded row counts every executable is
    compiled for. `bucket(n)` maps a row count to its ladder rung;
    `budget(max_n)` is the executable count a row sweep up to max_n
    can possibly compile — the number the bucket-parity test gates.

    steps_per_octave = 1 reproduces the historical pow2 padding
    exactly; s > 1 inserts s evenly-spaced rungs per octave
    (e.g. s=2: 1024, 1536, 2048, 3072, 4096, ...). min_rows and
    steps_per_octave must be powers of two with
    min_rows/steps_per_octave >= 128, so every rung is a multiple of
    128 (Pallas kernel eligibility is ladder-invariant)."""

    min_rows: int = 1024
    steps_per_octave: int = 1

    def __post_init__(self):
        mr, s = self.min_rows, self.steps_per_octave
        if mr < 128 or mr & (mr - 1):
            raise ValueError("min_rows must be a power of two >= 128")
        if not (1 <= s <= 8) or s & (s - 1):
            raise ValueError(
                "steps_per_octave must be a power of two in [1, 8]")
        if mr // s < 128:
            raise ValueError("min_rows/steps_per_octave must be >= 128")

    def bucket(self, n: int) -> int:
        n = max(int(n), 1)
        if n <= self.min_rows:
            return self.min_rows
        p = _next_pow2(n)
        if self.steps_per_octave == 1:
            return p
        half = p // 2
        step = half // self.steps_per_octave
        # smallest rung in (half, p] that covers n
        return half + step * (-(-(n - half) // step))

    def budget(self, max_n: int, min_n: int = 1) -> int:
        """Distinct rungs a sweep over [min_n, max_n] can touch."""
        lo, hi = self.bucket(min_n), self.bucket(max_n)
        count, b = 1, lo
        while b < hi:
            b = self.bucket(b + 1)
            count += 1
        return count

    def rungs(self, max_n: int, min_n: int = 1) -> list[int]:
        out, b = [self.bucket(min_n)], self.bucket(min_n)
        hi = self.bucket(max_n)
        while b < hi:
            b = self.bucket(b + 1)
            out.append(b)
        return out


def ladder_from_settings(settings) -> ShapeLadder:
    try:
        return ShapeLadder(
            int(settings.get("sql.exec.shape_bucket.min_rows")),
            int(settings.get("sql.exec.shape_bucket.steps_per_octave")))
    except Exception:
        return ShapeLadder()


# -- shapes journal ----------------------------------------------------------

def journal_path(cache_d: str) -> str:
    return os.path.join(cache_d, _JOURNAL_NAME)


def journal_record(cache_d: str | None, sql_text: str,
                   bucket: int = 0, vars: dict | None = None) -> None:
    """Append an executable-cache miss to the shapes journal. Best
    effort: journal loss only costs pre-warm coverage. ``vars`` holds
    the plan-key-changing session vars the statement compiled under
    (non-default values only), so a pre-warm re-prepares the SAME
    executable the statement actually ran, not the default-session
    plan of the same text."""
    if not cache_d or not sql_text:
        return
    try:
        p = journal_path(cache_d)
        try:
            if os.path.getsize(p) > _JOURNAL_MAX_BYTES:
                return
        except OSError:
            pass
        rec = {"sql": sql_text, "n": int(bucket)}
        if vars:
            rec["vars"] = dict(vars)
        with _LOCK:
            with open(p, "a", encoding="utf-8") as f:
                f.write(json.dumps(rec, sort_keys=True) + "\n")
    except Exception:
        pass


def journal_entries(cache_d: str | None, k: int) -> list[tuple]:
    """The k hottest statement texts from the journal, each paired
    with its dominant recorded shape bucket (0 when the statement
    never journaled one — resident plans) and its dominant recorded
    session-var dict ({} when it always ran at defaults). The bucket
    is what Engine.prewarm compiles streamed-page and spill-partition
    executables at, and the vars are what it re-prepares under, so a
    restarted process warms the plans the previous one actually ran,
    not just the statement texts. Corrupt lines are skipped, a
    missing journal is an empty plan."""
    if not cache_d or k <= 0:
        return []
    from collections import Counter
    counts: Counter = Counter()
    buckets: dict[str, Counter] = {}
    varcounts: dict[str, Counter] = {}
    vartabs: dict[str, dict] = {}
    try:
        with open(journal_path(cache_d), encoding="utf-8") as f:
            for line in f:
                try:
                    rec = json.loads(line)
                    sql = rec.get("sql")
                    if isinstance(sql, str) and sql:
                        counts[sql] += 1
                        b = int(rec.get("n") or 0)
                        if b > 0:
                            buckets.setdefault(sql, Counter())[b] += 1
                        jv = rec.get("vars")
                        if isinstance(jv, dict) and jv:
                            key = json.dumps(jv, sort_keys=True)
                            varcounts.setdefault(sql, Counter())[key] += 1
                            vartabs.setdefault(sql, {})[key] = jv
                except Exception:
                    continue
    except OSError:
        return []

    def dominant_vars(sql: str) -> dict:
        if sql not in varcounts:
            return {}
        return vartabs[sql][varcounts[sql].most_common(1)[0][0]]

    return [(sql,
             (buckets[sql].most_common(1)[0][0]
              if sql in buckets else 0),
             dominant_vars(sql))
            for sql, _ in counts.most_common(k)]


def journal_top(cache_d: str | None, k: int) -> list[str]:
    """The k statement texts with the most recorded compile misses,
    hottest first (journal_entries without the buckets/vars)."""
    return [e[0] for e in journal_entries(cache_d, k)]
