"""Fuzzed bit-parity gate for promoting kernel aggregate paths into
`auto` routing.

`auto` routing carries a hard contract: it may NEVER perturb results
(tests/test_pallas_large_g.py pins auto == off bit-for-bit), which is
why the large-G kernel's float accumulations and anything order-
sensitive shipped gated behind explicit `on`. Some of those paths are
exact by construction on a given backend — the ordered-int MIN/MAX
formulation reduces an order-preserving high limb in the kernel and
refines the full-width winner on XLA, so every value it returns is an
actual input value — but "exact by construction" is an argument about
MODEL arithmetic. This module turns the argument into a measured
verdict: on first use per backend it fuzzes each candidate path
against the XLA oracle on randomized shapes/data and persists which
paths came back bit-identical, in a verdict table next to the
autotune table (ops/pallas/autotune.py — same versioning, same
corrupt-table-degrades-silently contract). `auto` then admits exactly
the persisted paths; a path whose fuzz finds ONE differing bit stays
`on`-gated on that backend.

Candidate paths:

- ``int_minmax`` — exact ordered-int MIN/MAX: kernel min/max over the
  arithmetic high limb ``value >> MM_HI_SHIFT`` (|limb| < 2^23, so
  f32-exact and order-preserving), then an XLA masked refinement over
  the rows holding the winning limb. Expected to verify everywhere.
- ``float_sum`` — the f32-accumulated float SUM/AVG columns. Expected
  to FAIL verification against the f64 XLA oracle on real data; it is
  fuzzed anyway so the promotion is a measurement, not an opinion,
  and a future backend/kernel that accumulates exactly gets admitted
  with no code change.
"""

from __future__ import annotations

import json
import os
import threading

from .groupagg import _KernelTally

TABLE_VERSION = 1
_TABLE_NAME = "pallas_paritygate.json"

# arithmetic right-shift putting an int64's order-preserving high limb
# into f32-exact range: 64 - 40 = 24 magnitude bits -> |limb| <= 2^23
MM_HI_SHIFT = 40

PATHS = ("int_minmax", "float_sum")

CHECKS = _KernelTally()   # fuzz verdicts computed, by path:outcome
TABLE = _KernelTally()    # verdict-table lookups: "hit" | "miss"
SECONDS = [0.0]           # wall seconds spent fuzzing

_LOCK = threading.Lock()
_MEM: dict = {}           # (root, backend) -> tuple of exact paths
# paths whose fuzz RAISED (as opposed to returning "inexact"): path ->
# the exception text. The path stays unpromoted either way; the text
# tells a kernel the backend refused from one that rounds
# (Engine.runtime_status, chip_smoke.py)
ERRORS: dict[str, str] = {}


def register_metrics(metrics) -> None:
    metrics.func_counter(
        "exec.paritygate.checks",
        lambda: CHECKS.value("exact") + CHECKS.value("approx"),
        "parity-gate fuzz verdicts computed (first use per backend "
        "without a persisted verdict table)")
    metrics.func_counter(
        "exec.paritygate.seconds", lambda: SECONDS[0],
        "wall seconds spent fuzzing kernel paths against the XLA "
        "oracle")
    metrics.func_counter(
        "exec.paritygate.table_hit", lambda: TABLE.value("hit"),
        "promotion lookups served by the persisted verdict table")
    metrics.func_counter(
        "exec.paritygate.table_miss", lambda: TABLE.value("miss"),
        "promotion lookups with no usable verdict table (no root, "
        "corrupt, or foreign version) — nothing promotes")


def table_path(root: str) -> str:
    return os.path.join(root, _TABLE_NAME)


def load_table(root: str) -> dict:
    try:
        with open(table_path(root), encoding="utf-8") as f:
            raw = json.load(f)
        if not isinstance(raw, dict) \
                or raw.get("version") != TABLE_VERSION:
            return {}
        tables = raw.get("tables")
        return tables if isinstance(tables, dict) else {}
    except Exception:
        return {}


def _save(root: str, backend: str, exact: tuple) -> None:
    try:
        tables = load_table(root)
        tables[backend] = {"exact": sorted(exact)}
        os.makedirs(root, exist_ok=True)
        tmp = table_path(root) + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"version": TABLE_VERSION, "tables": tables}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, table_path(root))
    except Exception:
        pass  # a lost table only costs a re-fuzz next process


def _fuzz_int_minmax(interpret: bool) -> bool:
    """Kernel hi-limb MIN/MAX + XLA refinement vs aggops group_min/
    group_max, bit-compared over seeded random int64 workloads
    spanning sign changes and >2^24 magnitudes (where a plain f32
    kernel min/max would already be wrong)."""
    import jax.numpy as jnp
    import numpy as np

    from ...ops import agg as aggops
    from . import groupagg_large as pgl
    n, g = (512, 64) if interpret else (4096, 256)
    for seed in range(3):
        rng = np.random.default_rng(1000 + seed)
        gid = jnp.asarray(rng.integers(0, g, n), jnp.int32)
        sel = jnp.asarray(rng.random(n) < 0.85)
        vals = rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64)
        small = rng.random(n) < 0.3   # mix in sub-2^24 magnitudes
        vals[small] = rng.integers(-100, 100, int(small.sum()))
        d = jnp.asarray(vals)
        hi = jnp.right_shift(d, jnp.int64(MM_HI_SHIFT))
        mm = (jnp.where(sel, hi.astype(jnp.float32),
                        jnp.float32(np.inf)),
              jnp.where(sel, hi.astype(jnp.float32),
                        jnp.float32(-np.inf)))
        acc_f, _ = pgl.large_group_aggregate(
            gid, sel, (), (), (), mm, num_groups=g,
            layout=(("live",),), mm_ops=(pgl.MIN, pgl.MAX),
            interpret=interpret)
        # no f32 sum columns here, so the MM rows lead acc_f
        for row, fold in ((0, aggops.group_min),
                          (1, aggops.group_max)):
            ghi = acc_f[row, :].astype(jnp.int64)
            refine = jnp.logical_and(sel, hi == ghi[gid])
            got = fold(d, gid, refine, g)
            want = fold(d, gid, sel, g)
            live = np.asarray(aggops.group_count(gid, sel, g)) > 0
            if not np.array_equal(np.asarray(got)[live],
                                  np.asarray(want)[live]):
                return False
    return True


def _fuzz_float_sum(interpret: bool) -> bool:
    """Kernel f32-accumulated float sum vs the f64 XLA oracle —
    bit-compared, so one rounding divergence demotes the path."""
    import jax.numpy as jnp
    import numpy as np

    from ...ops import agg as aggops
    from . import groupagg_large as pgl
    n, g = (512, 64) if interpret else (4096, 256)
    for seed in range(3):
        rng = np.random.default_rng(2000 + seed)
        gid = jnp.asarray(rng.integers(0, g, n), jnp.int32)
        sel = jnp.asarray(rng.random(n) < 0.85)
        d = jnp.asarray(rng.standard_normal(n) * 1e3)
        col = jnp.where(sel, d, 0).astype(jnp.float32)
        acc_f, _ = pgl.large_group_aggregate(
            gid, sel, (), (), (col,), (), num_groups=g,
            layout=(("f", 0), ("live",)), interpret=interpret)
        got = np.asarray(acc_f[0, :].astype(jnp.float64))
        want = np.asarray(aggops.group_sum(
            d.astype(jnp.float64), gid, sel, g))
        live = np.asarray(aggops.group_count(gid, sel, g)) > 0
        if not np.array_equal(got[live], want[live]):
            return False
    return True


_FUZZERS = {"int_minmax": _fuzz_int_minmax,
            "float_sum": _fuzz_float_sum}


def fuzz(backend: str, root: str | None,
         interpret: bool) -> tuple[str, ...]:
    """Run every candidate path's fuzz, persist and return the exact
    set. A fuzz that ERRORS counts as not-exact (the gate exists to
    keep auto safe, not to explain backends)."""
    import time
    t0 = time.perf_counter()
    exact = []
    for path in PATHS:
        try:
            ok = _FUZZERS[path](interpret)
        except Exception as e:
            with _LOCK:
                ERRORS[path] = f"{type(e).__name__}: {e}"
            ok = False
        CHECKS.bump("exact" if ok else "approx")
        if ok:
            exact.append(path)
    # concurrent sessions can fuzz different backends; the unlocked
    # read-modify-write loses increments (graftlint racy-global)
    with _LOCK:
        SECONDS[0] += time.perf_counter() - t0
    out = tuple(exact)
    if root:
        _save(root, backend, out)
    return out


def promoted(backend: str, root: str | None,
             interpret: bool) -> tuple[str, ...]:
    """The kernel paths `auto` may route through on this backend —
    persisted verdicts, or one fuzz sweep on first use. With no
    persistence root the sweep still runs (cached in-process)
    so a cacheless engine gets the same routing, just re-measured per
    process."""
    key = (root, backend)
    with _LOCK:
        hit = _MEM.get(key)
    if hit is not None:
        TABLE.bump("hit")
        return hit
    if root:
        entry = load_table(root).get(backend, {})
        paths = entry.get("exact") if isinstance(entry, dict) else None
        if isinstance(paths, list) and \
                all(p in PATHS for p in paths):
            out = tuple(sorted(paths))
            with _LOCK:
                _MEM[key] = out
            TABLE.bump("hit")
            return out
    TABLE.bump("miss")
    out = fuzz(backend, root, interpret)
    with _LOCK:
        _MEM[key] = out
    return out
