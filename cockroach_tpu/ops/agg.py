"""Aggregation kernels: masked reductions and grouped segment aggregates.

The reference's hash aggregator (pkg/sql/colexec/hash_aggregator.go:67)
builds a vectorized hash table of group keys and runs per-function
kernels (colexecagg) against bucket-selected rows. On TPU the idiomatic
formulation is *group codes + segment reduction*: map each row to a
dense group id in [0, num_groups), then aggregate with
``jax.ops.segment_sum``-style scatters, which XLA lowers to efficient
sorted/atomic updates. For low-cardinality group-bys (TPC-H Q1: 4
groups) this is a one-hot matmul-sized op; for general group-bys the
group id comes from the device hash table in ops/hashtable.py.

Distributed two-stage aggregation follows the reference's
DistAggregationTable (pkg/sql/physicalplan/aggregator_funcs.go:22-91):
every aggregate is decomposed into local-stage functions and a
final-stage merge. Local stages run per-shard inside shard_map; the
final merge is an ICI collective (psum / pmin / pmax) instead of the
reference's gRPC shuffle — see parallel/distagg.py.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel "identity" values for min/max so dead rows never win.


def _minident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(jnp.inf, dtype)
    return jnp.iinfo(dtype).max


def _maxident(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.array(-jnp.inf, dtype)
    return jnp.iinfo(dtype).min


# ---------------------------------------------------------------------------
# ungrouped (scalar) aggregates — return (value, count) partials
# ---------------------------------------------------------------------------

def masked_sum(data, mask, acc_dtype=None):
    """SUM over live rows. acc_dtype widens (decimal int64 -> float64 to
    survive SF100 products; see ops/kernels.py docstring)."""
    d = data.astype(acc_dtype) if acc_dtype is not None else data
    return jnp.sum(jnp.where(mask, d, jnp.zeros_like(d)))


def masked_count(mask):
    return jnp.sum(mask.astype(jnp.int64))


def masked_min(data, mask):
    return jnp.min(jnp.where(mask, data, _minident(data.dtype)))


def masked_max(data, mask):
    return jnp.max(jnp.where(mask, data, _maxident(data.dtype)))


# ---------------------------------------------------------------------------
# grouped aggregates over dense group ids
# ---------------------------------------------------------------------------
#
# Two formulations, chosen by group count:
# - small G (dense strategy, e.g. TPC-H Q1's 12 code slots): G unrolled
#   masked REDUCTIONS — linear VPU passes XLA fuses aggressively.
#   segment_* lowers to scatter, and scatter is catastrophically slow
#   on TPU (measured: Q1 at 8M rows was ~1000x slower via scatter-add
#   than via unrolled reductions on a v5e).
# - large G (hash strategy, capacity 2^17): scatter is the only
#   shape-sane option; those group ids are hash slots.

UNROLL_GROUPS = 32


def group_rep_index(group_ids, mask, num_groups: int):
    """(representative masked row index per group, nonempty mask) in
    ONE i32 scatter-min. The per-group-constant `any` aggregates (FD-
    reduced group keys) gather their values through this shared index
    instead of scattering every column — q18's four riding keys cost
    4 cheap gathers instead of ~12 scatter passes."""
    n = group_ids.shape[0]
    rowid = jnp.arange(n, dtype=jnp.int32)
    gid = jnp.where(mask, group_ids, 0)
    rep = jnp.full(num_groups, n, jnp.int32).at[gid].min(
        jnp.where(mask, rowid, n), mode="drop")
    return jnp.minimum(rep, n - 1), rep < n


def group_any_via_rep(data, valid, rep, nonempty):
    """Per-group `any` value via the shared representative index.
    Only valid when the value is constant within each group (the FD-
    reduced keys; NULL-ness is constant too, so the representative
    row's validity IS the group's). Empty / all-NULL groups take the
    max identity, matching group_any's scatter formulation."""
    v = jnp.logical_and(nonempty, jnp.take(valid, rep))
    ident = _maxident(data.dtype)
    d = jnp.where(v, jnp.take(data, rep), ident)
    return d, v


def group_sum(data, group_ids, mask, num_groups: int, acc_dtype=None,
              max_group_rows: int = 0, arg_bits: int = 0):
    """Per-group sum. arg_bits > 0: every unmasked value is proven
    non-negative and under 2^arg_bits (BoundAgg.arg_bits)."""
    d = data.astype(acc_dtype) if acc_dtype is not None else data
    if num_groups <= UNROLL_GROUPS:
        z = jnp.zeros_like(d)
        return jnp.stack([
            jnp.sum(jnp.where(jnp.logical_and(mask, group_ids == g),
                              d, z))
            for g in range(num_groups)])
    d = jnp.where(mask, d, jnp.zeros_like(d))
    # Dead rows scatter to group 0 with value 0 — harmless.
    gid = jnp.where(mask, group_ids, 0)
    if d.dtype == jnp.int64:
        return _group_sum_i64_limbs(d, gid, num_groups, max_group_rows,
                                    arg_bits)
    return jax.ops.segment_sum(d, gid, num_segments=num_groups)


def _group_sum_i64_limbs(d, gid, num_groups: int,
                         max_group_rows: int, arg_bits: int = 0):
    """Exact int64 group sum via limb-decomposed INT32 scatters.

    64-bit scatter-adds are software-emulated on TPU (measured ~250ms
    marginal at 2M rows vs ~14ms for one i32 scatter). Split each
    value's two's-complement bit pattern into w-bit limbs (logical
    shifts), scatter-add each limb in int32 — exact because a group's
    limb sum is bounded by max_group_rows * (2^w - 1) < 2^31 — and
    recombine with wrapping shifts/adds, which reproduces int64
    modular arithmetic bit-for-bit (including negatives). With a
    tight engine-measured group bound this is 3 i32 scatters
    (measured 2.4x the emulated scatter end-to-end, ~4.5x marginal);
    with no bound the width shrinks so the limb sums still cannot
    overflow, at worst ~7 scatters — still ~2x."""
    maxg = max(int(max_group_rows), 1) if max_group_rows > 0 \
        else max(int(d.shape[0]), 1)
    w = int(np.floor(np.log2((2.0 ** 31 - 1) / maxg + 1)))
    w = max(1, min(22, w))
    # engine-proven NON-NEGATIVE values need only arg_bits of limb
    # coverage: a 13-bit quantity column's exact sum is ONE i32
    # scatter. (Negative values need all 64 bits — their two's-
    # complement high limbs are non-zero.) A group sum can need up to
    # log2(maxg) carry bits beyond the value width; the reconstruction
    # below only sees limb sums, which carry them exactly, so `bits`
    # only bounds which limbs can be non-zero
    bits = min(64, arg_bits) if arg_bits > 0 else 64
    k = -(-bits // w)
    m = (1 << w) - 1
    total = jnp.zeros(num_groups, jnp.int64)
    for j in range(k):
        limb = (jax.lax.shift_right_logical(d, j * w) & m) \
            .astype(jnp.int32)
        s = jax.ops.segment_sum(limb, gid, num_segments=num_groups)
        total = total + (s.astype(jnp.int64) << (j * w))
    return total


def group_count(group_ids, mask, num_groups: int):
    if num_groups <= UNROLL_GROUPS:
        return jnp.stack([
            jnp.sum(jnp.logical_and(mask, group_ids == g)
                    .astype(jnp.int64))
            for g in range(num_groups)])
    # accumulate in int32: 64-bit scatters are software-emulated on
    # TPU (~10x an i32 scatter, measured ~130-220ms vs ~14ms at 2M
    # rows); batch row counts are < 2^31 by construction
    return jax.ops.segment_sum(mask.astype(jnp.int32),
                               jnp.where(mask, group_ids, 0),
                               num_segments=num_groups).astype(jnp.int64)


def group_min(data, group_ids, mask, num_groups: int):
    ident = _minident(data.dtype)
    if num_groups <= UNROLL_GROUPS:
        return jnp.stack([
            jnp.min(jnp.where(jnp.logical_and(mask, group_ids == g),
                              data, ident))
            for g in range(num_groups)])
    d = jnp.where(mask, data, ident)
    gid = jnp.where(mask, group_ids, 0)
    return jax.ops.segment_min(d, gid, num_segments=num_groups)


def group_max(data, group_ids, mask, num_groups: int):
    ident = _maxident(data.dtype)
    if num_groups <= UNROLL_GROUPS:
        return jnp.stack([
            jnp.max(jnp.where(jnp.logical_and(mask, group_ids == g),
                              data, ident))
            for g in range(num_groups)])
    d = jnp.where(mask, data, ident)
    gid = jnp.where(mask, group_ids, 0)
    return jax.ops.segment_max(d, gid, num_segments=num_groups)


def shard_extreme(x, axis_name, op: str):
    """Merge per-shard MIN/MAX partials across the mesh (op: "min" |
    "max"). XLA:TPU lowers only SUM all-reduces for 64-bit element
    types, which it emulates on 32-bit lanes ("Supported lowering only
    of Sum all reduce"), so 64-bit partials gather to every shard and
    fold locally; narrower ones take the native collective. One rule
    on every backend, so the CPU mesh tests run the program the chips
    run."""
    with jax.named_scope("shard_merge"):
        if jnp.dtype(x.dtype).itemsize < 8:
            return (jax.lax.pmin if op == "min"
                    else jax.lax.pmax)(x, axis_name)
        gathered = jax.lax.all_gather(x, axis_name)  # [shards, ...]
        return (jnp.min if op == "min" else jnp.max)(gathered, axis=0)


def shard_sum(x, axis_name):
    """psum across the mesh under the `shard_merge` scope, which a
    profile reads to tell the collectives of a distributed plan from
    the operator around them."""
    with jax.named_scope("shard_merge"):
        return jax.lax.psum(x, axis_name)


def group_any(data, group_ids, mask, num_groups: int):
    """Arbitrary per-group representative — ONLY valid when the value
    is constant within each group (the planner's FD-reduced group
    keys ride as this). Scatter-SET instead of min/max because 64-bit
    scatter REDUCTIONS are software-emulated on TPU (~12x an i32
    scatter); 64-bit values set as two i32 limbs. The limb scatters
    may pick different winner rows for a duplicated group id, which
    per-group-constant inputs make harmless. Empty groups hold a very
    negative identity so cross-shard pmax merges pick the real value."""
    if num_groups <= UNROLL_GROUPS:
        # dense small-G strategy: unrolled masked max (a valid
        # representative — values are per-group-constant) keeps these
        # queries off the scatter path entirely, like group_min/max
        return group_max(data, group_ids, mask, num_groups)
    gid = jnp.where(mask, group_ids, num_groups)  # dead rows drop
    if data.dtype in (jnp.int64, jnp.float64):
        if data.dtype == jnp.float64:
            bits = jax.lax.bitcast_convert_type(data, jnp.int64)
            # identity = bit pattern of -inf: the recombined empty
            # slot must lose any pmax merge against a real value
            ident = int(np.int64(np.array(-np.inf).view(np.int64)))
        else:
            bits = data
            # iinfo.min: below EVERY int64, and its limbs round-trip
            # (lo 0, hi int32 min) — the same identity scatter-max used
            ident = -(1 << 63)
        lo = jnp.full(num_groups, ident & 0xFFFFFFFF,
                      jnp.uint32).at[gid].set(
            bits.astype(jnp.uint32), mode="drop")
        hi = jnp.full(num_groups, ident >> 32, jnp.int32).at[gid].set(
            (bits >> 32).astype(jnp.int32), mode="drop")
        out = (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)
        if data.dtype == jnp.float64:
            return jax.lax.bitcast_convert_type(out, jnp.float64)
        return out
    # base = the MAX identity (very negative): shards lacking a group
    # must lose the cross-shard pmax merge to the shard that has it
    ident = _maxident(data.dtype)
    return jnp.full(num_groups, ident, data.dtype).at[gid].set(
        data, mode="drop")


def distinct_first_mask(data, mask, group_ids, num_groups: int,
                        sort_normalized: str = "off"):
    """True at the FIRST masked occurrence of each (group, value) pair.

    DISTINCT aggregates become ordinary aggregates with this extra
    mask: sort rows by (group, value), flag group/value changes,
    scatter the flags back — one sort, no per-group work (the
    reference dedups inside its hash aggregator per-bucket instead,
    colexec/distinct.eg.go). sort_normalized auto/on packs the
    (group, value) pair into uint64 lanes (the group field sized to
    bit_length(num_groups): the masked-out sentinel rides as code
    num_groups) and argsorts per lane instead of the lexsort."""
    from . import sortkey
    n = data.shape[0]
    sentinel = jnp.int64(num_groups)
    g = jnp.where(mask, group_ids.astype(jnp.int64), sentinel)
    order = None
    if sort_normalized in ("auto", "on"):
        enc = sortkey.encode_value(data)
        if enc is not None:
            gw = max(1, int(num_groups).bit_length())
            fields = [(g.astype(jnp.uint64), gw), enc]
            order = sortkey.sort_perm(
                sortkey.pack_lanes(fields, n), kind="distinct")
        else:
            sortkey.FALLBACKS.bump("distinct")
    if order is None:
        order = jnp.lexsort((data, g))
    gs, ds = g[order], data[order]
    first = jnp.concatenate([
        jnp.ones((1,), jnp.bool_),
        jnp.logical_or(gs[1:] != gs[:-1], ds[1:] != ds[:-1])])
    first = jnp.logical_and(first, gs < sentinel)
    return jnp.zeros((n,), jnp.bool_).at[order].set(first)


# ---------------------------------------------------------------------------
# aggregate spec machinery (mirrors AggregatorSpec_Func,
# execinfrapb/processors_sql.proto:798, and the local/final decomposition)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class AggSpec:
    """One aggregate: func in {sum,count,count_rows,min,max,avg,sum_int},
    over input column `col` (None for count_rows), output name `name`."""
    func: str
    col: Optional[str]
    name: str
    distinct: bool = False

    @property
    def local_funcs(self) -> list[str]:
        # DistAggregationTable analogue: how to split into local partials.
        if self.func == "avg":
            return ["sum", "count"]
        if self.func in ("count", "count_rows"):
            return ["count"]
        return [self.func]

    @property
    def merge_ops(self) -> list[str]:
        """Collective used to merge partials across shards."""
        if self.func == "avg":
            return ["psum", "psum"]
        if self.func in ("count", "count_rows", "sum", "sum_int"):
            return ["psum"]
        if self.func == "min":
            return ["pmin"]
        if self.func == "max":
            return ["pmax"]
        raise ValueError(self.func)
