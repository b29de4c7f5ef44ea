"""Pallas tile autotuner for the large-G grouped-aggregation kernel.

`groupagg_large.py` shipped with hand-picked constants
(GROUP_TILE = 512, BLOCK_ROWS = 4096) tuned on one chip generation.
The right (group_tile, block_rows) point moves with the MXU shape,
VMEM size and HBM bandwidth of the backend, so this module times a
small candidate grid on first use per backend and persists the winner
in a tuning table next to the persistent compile cache
(exec/coldstart.py). Restarted processes read the table instead of
re-timing — the autotune analogue of the compile cache. `group_tile`
is the UPPER bound of the tile: a build takes the plan's group count
rounded up to 128 lanes where that is smaller
(groupagg_large.effective_group_tile), so the sweep's q18-class shape
decides nothing for a twelve-group plan. `limb_cap` stays in the
table's format; every candidate carries the widest limb one bf16 pass
allows, 8, and an older table's wider cap reads as 8.

Correctness is NOT at stake: every candidate satisfies the kernel's
alignment contract (group_tile a multiple of 128, block_rows a power
of two) and the limb width is recomputed from the chosen block_rows
via `limb_width`'s exactness bound, so any tile choice produces
bit-identical results — the tuner only picks the fastest. That is
why a corrupt, stale or foreign tuning table degrades to the shipped
defaults silently (tallied in `exec.autotune.table_miss`), never to
an error or a wrong answer.

Session var `pallas_autotune` (mirrored by cluster setting
`sql.exec.pallas.autotune`): `auto` (default) consults the table and
tunes on first use only on a real TPU backend (interpret-mode timing
measures the Python loop, not the hardware — and would add minutes to
a CPU test run); `on` forces tuning even off-TPU at tiny shapes (the
test hook); `off` always uses the shipped constants.
"""

from __future__ import annotations

import json
import math
import os
import threading

from . import groupagg_large as pgl
from .groupagg import _KernelTally

TABLE_VERSION = 1
_TABLE_NAME = "pallas_autotune.json"

# the candidate grid: group-domain tile (multiple of 128 lanes) x
# row block (pow2) x limb-width cap. Small on purpose — each point
# costs a kernel compile at tuning time.
CANDIDATES: tuple[tuple[int, int, int], ...] = (
    (512, 4096, 8),    # the shipped constants
    (256, 4096, 8),
    (1024, 1024, 8),
    (512, 1024, 8),
    (512, 2048, 8),
)

DEFAULT = CANDIDATES[0]

RUNS = _KernelTally()     # autotune sweeps executed ("sweep") and
                          # candidate points timed ("candidate")
TABLE = _KernelTally()    # tuning-table lookups: "hit" | "miss"
SECONDS = [0.0]           # wall seconds spent timing candidates

_LOCK = threading.Lock()
_MEM: dict = {}           # (root, backend) -> (group_tile, block_rows, cap)
# candidates the backend refused in a sweep: "GTxBRwCAP" -> the
# compiler's message. A skipped non-default candidate costs nothing,
# but the text is kept so a refused kernel does not read as "default
# tile, nothing promoted" (Engine.runtime_status, chip_smoke.py)
REJECTED: dict[str, str] = {}


def register_metrics(metrics) -> None:
    metrics.func_counter(
        "exec.autotune.runs", lambda: RUNS.value("sweep"),
        "Pallas tile autotune sweeps executed (first use per backend "
        "without a tuning table)")
    metrics.func_counter(
        "exec.autotune.seconds", lambda: SECONDS[0],
        "wall seconds spent timing autotune candidates")
    metrics.func_counter(
        "exec.autotune.table_hit", lambda: TABLE.value("hit"),
        "tile lookups served by the persisted tuning table")
    metrics.func_counter(
        "exec.autotune.table_miss", lambda: TABLE.value("miss"),
        "tile lookups that fell back to the shipped constants "
        "(no/corrupt/stale table and tuning not admissible)")


def table_path(root: str) -> str:
    return os.path.join(root, _TABLE_NAME)


def _valid_entry(e) -> tuple[int, int, int] | None:
    try:
        gt, br, cap = (int(e["group_tile"]), int(e["block_rows"]),
                       int(e["limb_cap"]))
    except Exception:
        return None
    if gt <= 0 or gt % 128 or br < 128 or br & (br - 1) \
            or not (1 <= cap <= 22):
        return None
    # a table written before the one-pass kernel may hold 16 or 22
    return gt, br, min(cap, pgl.MAX_LIMB_BITS)


def load_table(root: str) -> dict:
    """Parse the tuning table; anything malformed or from another
    TABLE_VERSION reads as empty (defaults win, never an error)."""
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


def _save(root: str, backend: str, tile: tuple[int, int, int],
          timings: dict) -> None:
    try:
        tables = load_table(root)
        tables[backend] = {"group_tile": tile[0], "block_rows": tile[1],
                          "limb_cap": tile[2], "timings": timings}
        os.makedirs(root, exist_ok=True)
        tmp = table_path(root) + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"version": TABLE_VERSION, "tables": tables}, f,
                      indent=1, sort_keys=True)
        os.replace(tmp, table_path(root))
    except Exception:
        pass  # a lost table only costs a re-tune next process


def _time_candidate(gt: int, br: int, cap: int, n: int,
                    num_groups: int, interpret: bool) -> float:
    """Median-of-3 wall time of one kernel call at a synthetic shape
    modelled on the q18-class plans the kernel serves: one int64
    argument with its f32 shadow row, count + liveness + limb rows
    (limb count follows the candidate's own width bound), one MIN
    slot."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    w = pgl.limb_width(n, n, block_rows=br, cap=cap)
    rng = np.random.default_rng(n + gt + br)
    gid = jnp.asarray(rng.integers(0, num_groups, n), jnp.int32)
    sel = jnp.asarray(rng.random(n) < 0.9)
    src = jnp.where(sel, jnp.asarray(
        rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64)), 0)
    layout = (("shadow", 0),) + pgl.limb_rows(0, 64, w) \
        + (("count", 0), ("live",))
    mm = (jnp.where(sel, jnp.asarray(rng.random(n), jnp.float32),
                    jnp.float32(np.inf)),)

    def call():
        return pgl.large_group_aggregate(
            gid, sel, (src,), (sel,), (), mm, num_groups=num_groups,
            layout=layout, mm_ops=(pgl.MIN,), want_rep=True,
            group_tile=gt, block_rows=br, interpret=interpret)

    jax.block_until_ready(call())  # compile outside the timed window
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    return sorted(times)[1]


def autotune(backend: str, root: str | None, interpret: bool,
             n: int | None = None, num_groups: int | None = None,
             candidates=CANDIDATES) -> tuple[int, int, int]:
    """Time the candidate grid, persist the winner, return it.
    Interpret-mode sweeps (the `on` test hook off-TPU) shrink the
    shape so the Python grid loop stays in seconds."""
    import time
    if n is None:
        n = 1 << 12 if interpret else 1 << 16
    if num_groups is None:
        num_groups = 256 if interpret else 1 << 12
    RUNS.bump("sweep")
    t_sweep = time.perf_counter()
    best, best_t, timings = DEFAULT, math.inf, {}
    for gt, br, cap in candidates:
        if br > n:
            continue
        name = f"{gt}x{br}w{cap}"
        try:
            dt = _time_candidate(gt, br, cap, n, num_groups, interpret)
        except Exception as e:  # the backend's compiler refused it
            with _LOCK:
                REJECTED[name] = f"{type(e).__name__}: {e}"
            continue
        RUNS.bump("candidate")
        timings[name] = dt
        if dt < best_t:
            best, best_t = (gt, br, cap), dt
    # two sessions autotuning different backends sweep concurrently;
    # an unlocked read-modify-write here loses increments (graftlint
    # racy-global)
    with _LOCK:
        SECONDS[0] += time.perf_counter() - t_sweep
    if root:
        _save(root, backend, best, timings)
    return best


def params_for(backend: str, root: str | None, mode: str = "auto",
               interpret: bool = True) -> tuple[int, int, int]:
    """The (group_tile, block_rows, limb_cap) the engine should
    compile with. Never blocks beyond the one-time sweep; see module
    docstring for the mode contract."""
    if mode == "off" or not root:
        if mode != "off":
            TABLE.bump("miss")
        return DEFAULT
    key = (root, backend)
    with _LOCK:
        hit = _MEM.get(key)
    if hit is not None:
        TABLE.bump("hit")
        return hit
    entry = _valid_entry(load_table(root).get(backend, {}))
    if entry is not None:
        with _LOCK:
            _MEM[key] = entry
        TABLE.bump("hit")
        return entry
    if mode == "on" or (mode == "auto" and not interpret):
        tile = autotune(backend, root, interpret)
        with _LOCK:
            _MEM[key] = tile
        return tile
    TABLE.bump("miss")
    return DEFAULT
