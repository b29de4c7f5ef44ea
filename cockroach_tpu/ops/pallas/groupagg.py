"""Pallas TPU kernel: one-pass dense grouped aggregation.

The XLA path computes each aggregate as its own segment reduction, so
TPC-H Q1's 8 aggregates read the scan columns from HBM many times and
allocate an n-length temporary per reduction (measured: ~12GB of HLO
temps at 2^27 rows — the reason Q1's working set dwarfs its data).
This kernel makes ONE pass: each grid step loads one row-block of the
group-id/value/mask columns into VMEM and folds ALL aggregates for
every (small) dense group into an SMEM accumulator.

Mosaic-friendly formulation: rows are shaped (blk//128, 128) so every
load and mask op is a full lane-aligned VPU tile; each (group, agg)
pair is ONE full-tile masked reduction to a scalar, combined into an
accumulator in SMEM (scalar stores are legal in SMEM, not VMEM). The
grid is sequential on TPU, so read-modify-write of the accumulator
across steps is the standard Pallas reduction pattern. G*A stays small
by construction (dense strategy caps the group count), so the unrolled
reduction loop is tens of VPU reductions per block.

Dtype envelope: COUNT slots accumulate in int32 (exact to 2^31 rows;
f32 would silently round past 2^24), value slots in float32 — the
Mosaic-supported set. DECIMAL-exact int64 sums stay on the XLA path
(TPUs have no native 64-bit lanes), so the engine only offers this
kernel for float-argument aggregate sets, and only when the session
opts in (exec/compile.py gating; f32 sums are approximate vs the XLA
path's f64 accumulation).

Replaces (conceptually) the reference's per-aggregate generated
kernels: colexecagg's sum/min/max/count x ordered/hash .eg.go files.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# op kinds, per aggregate slot
COUNT, SUM, MIN, MAX = 0, 1, 2, 3

_INIT = {SUM: np.float32(0.0), MIN: np.float32(np.inf),
         MAX: np.float32(-np.inf)}

LANES = 128


def _kernel(gid_ref, sel_ref, *refs, acc_ref, cnt_ref, num_groups: int,
            ops: tuple, n_vals: int):
    """Grid step: fold one (rows//128, 128) block into the [G, A]
    accumulators (f32 values, i32 counts)."""
    step = pl.program_id(0)
    val_refs = refs[:n_vals]
    mask_refs = refs[n_vals:]

    @pl.when(step == 0)
    def _init():
        for g in range(num_groups):
            for a, op in enumerate(ops):
                if op == COUNT:
                    cnt_ref[g, a] = np.int32(0)
                else:
                    acc_ref[g, a] = _INIT[op]

    gid = gid_ref[:, :]
    sel = sel_ref[:, :] != 0
    # group membership tiles, shared across aggregates
    gms = [jnp.logical_and(gid == g, sel) for g in range(num_groups)]
    for a, op in enumerate(ops):
        am = mask_refs[a][:, :] != 0
        v = val_refs[a][:, :] if op != COUNT else None
        for g in range(num_groups):
            m = jnp.logical_and(gms[g], am)
            if op == COUNT:
                # per-block count in f32 (exact: block <= 2^16 rows,
                # far under f32's 2^24 integer range), accumulated in
                # i32 SMEM (exact to 2^31 total). An i32 jnp.sum is
                # promoted to the Mosaic-unsupported i64 by the x64
                # mode the kernel is traced under.
                part = jnp.sum(m.astype(jnp.float32))
                cnt_ref[g, a] += part.astype(jnp.int32)
            elif op == SUM:
                # explicit f32 zero: a weak Python-float literal here
                # round-trips through the interpret-mode lowering as
                # f64 when the enclosing program traces under x64
                acc_ref[g, a] += jnp.sum(jnp.where(m, v, _INIT[SUM]))
            elif op == MIN:
                part = jnp.min(jnp.where(m, v, np.float32(np.inf)))
                acc_ref[g, a] = jnp.minimum(acc_ref[g, a], part)
            else:  # MAX
                part = jnp.max(jnp.where(m, v, np.float32(-np.inf)))
                acc_ref[g, a] = jnp.maximum(acc_ref[g, a], part)


class _KernelTally:
    """Thread-safe per-kernel counter.

    The trace-time tallies are bumped inside jit-traced bodies; since
    the pipelined data plane, per-mesh dispatcher threads and
    concurrent pgwire sessions can trace simultaneously, so a bare
    ``global x; x += 1`` read-modify-write races. One lock per tally,
    keyed by kernel kind (``small`` / ``large`` / ...) so the engine
    can expose both per-kind and total func-metrics.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._counts: dict[str, int] = {}

    def bump(self, kind: str, delta: int = 1) -> None:
        with self._lock:
            self._counts[kind] = self._counts.get(kind, 0) + delta

    def value(self, kind: str | None = None) -> int:
        with self._lock:
            if kind is None:
                return sum(self._counts.values())
            return self._counts.get(kind, 0)


# Pallas kernel trace/build tallies (see the note inside
# dense_group_aggregate); read via engine func-metrics.
BUILDS = _KernelTally()      # kernel (re)builds, per kernel kind
ROWS = _KernelTally()        # rows offered to a kernel at trace time
FALLBACKS = _KernelTally()   # aggregations that wanted a kernel but
                             # compiled on the XLA segment path
OPERAND_BYTES = _KernelTally()   # bytes of the HBM arrays a build hands
                                 # its kernel (what XLA writes for it)
LIMB_BITS = _KernelTally()       # limb width of a large-G build's exact
                                 # sums, summed over builds (/ builds)
MATMUL_ROWS = _KernelTally()     # rows of a large-G build's matmul
                                 # operands, summed over builds
GROUP_TILE_LANES = _KernelTally()    # lanes of the group tile a large-G
                                     # build took, summed over builds
MXU_PASSES = _KernelTally()      # bf16 MXU passes of a large-G build's
                                 # exact-rows contraction, over builds


@functools.partial(jax.jit, static_argnames=("num_groups", "ops",
                                             "block_rows", "interpret"))
def dense_group_aggregate(gid, sel, values: tuple, masks: tuple,
                          num_groups: int, ops: tuple,
                          block_rows: int = 1 << 16,
                          interpret: bool = False):
    """One-pass grouped aggregation.

    gid: int32[n] dense group ids (0..num_groups-1; only rows with
         sel True contribute). values/masks: one f32 array + bool mask
         per aggregate (the value is ignored for COUNT slots). ops:
         per-aggregate COUNT/SUM/MIN/MAX. Returns a pair
    (f32[num_groups, n_aggs] value partials, i32[num_groups, n_aggs]
    counts) — each slot's result lives in the array its op writes.
    n must be a multiple of 128 (the engine pads tables to pow2 >= 128).
    """
    # trace-time side effect: this body runs once per (shape, static
    # args) jit-cache entry, so the tally counts kernel BUILDS, the
    # honest metric for a jitted kernel (executions happen inside XLA
    # where host counters can't see them). exec.pallas.* func-metrics
    # in the engine read it.
    n = gid.shape[0]
    BUILDS.bump("small")
    ROWS.bump("small", n)
    assert n % LANES == 0, "row count must be a multiple of 128"
    rows = n // LANES
    # largest power-of-two divisor of rows (rows & -rows), capped by
    # the block budget: any pow2 <= that divisor also divides rows, so
    # this replaces the old O(rows) linear search. The engine pads
    # tables to a power of two, but compaction can hand us
    # pow2-page-multiples (2^k * odd), which this handles too.
    blk = min(block_rows // LANES, rows & -rows)
    assert blk >= 1 and rows % blk == 0
    n_vals = len(values)
    grid = (rows // blk,)
    # the second index-map coordinate must be i32: under the engine's
    # jax_enable_x64 a literal 0 traces as i64, which Mosaic rejects
    row_spec = pl.BlockSpec((blk, LANES), lambda i: (i, jnp.int32(0)),
                            memory_space=pltpu.VMEM)
    in_specs = [row_spec, row_spec] + [row_spec] * (2 * n_vals)

    def kernel(gid_ref, sel_ref, *refs):
        _kernel(gid_ref, sel_ref, *refs[:-2], acc_ref=refs[-2],
                cnt_ref=refs[-1], num_groups=num_groups, ops=ops,
                n_vals=n_vals)

    shape2d = (rows, LANES)
    args = (gid.astype(jnp.int32).reshape(shape2d),
            sel.astype(jnp.int8).reshape(shape2d),
            *[v.astype(jnp.float32).reshape(shape2d) for v in values],
            *[m.astype(jnp.int8).reshape(shape2d) for m in masks])
    GA = (num_groups, len(ops))
    # the engine runs with jax_enable_x64; Mosaic requires i32 index
    # maps and block indices, so trace the kernel in an x64-off scope
    # (all operands already carry explicit 32-bit dtypes)
    with jax.enable_x64(False):
        acc, cnt = pl.pallas_call(
            kernel,
            out_shape=(jax.ShapeDtypeStruct(GA, jnp.float32),
                       jax.ShapeDtypeStruct(GA, jnp.int32)),
            grid=grid,
            in_specs=in_specs,
            out_specs=(pl.BlockSpec(memory_space=pltpu.SMEM),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            interpret=interpret,
        )(*args)
    return acc, cnt
