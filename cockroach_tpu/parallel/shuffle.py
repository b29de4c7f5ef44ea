"""Hash-partitioned all_to_all exchange: the TPU shuffle.

Round-3 VERDICT #3. The reference moves rows between flow processors
with the HashRouter (pkg/sql/colflow/routers.go:425): each producer
hash-partitions its output stream and ships bucket i to consumer i
over gRPC. The TPU formulation is one ``jax.lax.all_to_all`` over ICI
inside the SPMD program:

  1. every shard assigns each local row a destination
     ``hash(key) % n_shards``;
  2. rows sort by destination and scatter into a [n_shards, cap]
     send buffer (static shapes — cap is the per-destination budget,
     with an overflow flag when skew exceeds it);
  3. ``all_to_all`` swaps buffer block d with shard d — after it,
     every row with the same key hash lives on the same shard.

That property is what unlocks sharded⋈sharded hash joins (both sides
exchanged by their join key — no replicated build side) and
hash-distributed GROUP BY whose merge touches only each shard's 1/D
of the groups instead of all_gather-ing every group to every shard
(the round-2 weakness this replaces, parallel/distagg.py:18-21).

Skew/overflow contract: cap bounds what each shard can send to one
destination. Overflow does NOT corrupt results — surplus rows are
dropped from the send buffer and the returned flag is True, which the
engine maps to HashCapacityExceeded and the partition-and-recurse
retry path (exec/scanplane.py _run_partitioned), the same discipline
the hash table uses for capacity overflow.
"""

from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from ..ops.hashtable import _hash_columns
from .mesh import SHARD_AXIS


class _ByteTally:
    """Thread-safe trace-time byte counter (the
    groupagg_large._KernelTally discipline): bumped inside jit-traced
    bodies, so it counts the bytes a TRACED exchange moves per shard
    per execution of that program build — the engine exposes it
    through the ``exec.movement.*`` family as the shuffle plane's
    contribution to the unified transfer budget."""

    def __init__(self):
        self._lock = threading.Lock()
        self._bytes = 0

    def bump(self, nbytes: int) -> None:
        with self._lock:
            self._bytes += int(nbytes)

    def value(self) -> int:
        with self._lock:
            return self._bytes


EXCHANGE_TRACED = _ByteTally()

# ---------------------------------------------------------------------------
# per-link fault injection
# ---------------------------------------------------------------------------
# The all_to_all is one fused collective, but physically it is D*(D-1)
# directed ICI links; a chaos drill wants rules per link ("shard 0 ->
# shard 2 drops"), not one blanket rule. The exchange itself runs
# inside a jitted SPMD program, so faults cannot fire mid-collective —
# they are evaluated host-side at dispatch time (distagg
# queued_collective_call) and aggregated: a dropped link loses that
# block of the exchange, which makes the WHOLE collective result wrong,
# so any dropped link faults the dispatch (CollectiveFault -> the
# session's distsql-off recovery ladder); dup and delay degrade to
# a duplicate dispatch / the worst link's delay.

_LINK_FAULTS = None  # (rpc.context.FaultInjector, n_shards) or None


def install_link_faults(injector, n_shards: int) -> None:
    """Register per-link fault rules for the shuffle exchange. Rules
    are keyed ``("shard:<s>", "shard:<d>")`` in the injector; pass
    None to heal."""
    global _LINK_FAULTS
    _LINK_FAULTS = ((injector, int(n_shards))
                    if injector is not None else None)


def link_fault_plan():
    """Aggregate every directed shard-pair's fault rule into one
    dispatch plan (FaultInjector.plan semantics: [] drop, [0.0]
    deliver, [0.0, 0.0] dup, [s] delay). None when no injector is
    installed — the zero-overhead default."""
    lf = _LINK_FAULTS
    if lf is None:
        return None
    inj, n = lf
    delay = 0.0
    dup = False
    for s in range(n):
        for d in range(n):
            if s == d:
                continue  # self-block never leaves the chip
            plan = inj.plan(f"shard:{s}", f"shard:{d}")
            if not plan:
                return []  # one lost link corrupts the exchange
            delay = max(delay, plan[0])
            dup = dup or len(plan) > 1
    return [delay, 0.0] if dup else [delay]


def dest_of(key_cols: tuple, n_shards: int) -> jnp.ndarray:
    """Destination shard per row: hash(keys) % n_shards, decorrelated
    from the hash-table slot hash by a salt column (the HashRouter
    likewise uses its own hash function)."""
    salt = jnp.full(key_cols[0].shape, 0x9E3779B9, dtype=jnp.int32)
    h = _hash_columns(tuple(key_cols) + (salt,), 1 << 16)
    return (h % jnp.int32(n_shards)).astype(jnp.int32)


def pack_for_exchange(dest: jnp.ndarray, valid: jnp.ndarray,
                      n_shards: int, cap: int, arrays: list):
    """Scatter rows into a [n_shards * cap] send buffer, block d
    holding (up to cap) rows destined for shard d.

    Returns (packed_arrays, packed_valid, overflow)."""
    n = dest.shape[0]
    # invalid rows sort to the end (dest = n_shards sentinel)
    d = jnp.where(valid, dest, jnp.int32(n_shards))
    order = jnp.argsort(d, stable=True)
    dsort = d[order]
    starts = jnp.searchsorted(dsort, jnp.arange(n_shards, dtype=dsort.dtype))
    rank = jnp.arange(n, dtype=jnp.int32) - \
        starts[jnp.clip(dsort, 0, n_shards - 1)].astype(jnp.int32)
    live = dsort < n_shards
    fits = jnp.logical_and(live, rank < cap)
    overflow = jnp.any(jnp.logical_and(live, rank >= cap))
    slot = jnp.where(fits, dsort * cap + rank, n_shards * cap)
    out_valid = jnp.zeros((n_shards * cap,), dtype=jnp.bool_) \
        .at[slot].set(True, mode="drop")
    packed = []
    for a in arrays:
        buf = jnp.zeros((n_shards * cap,) + a.shape[1:], dtype=a.dtype)
        packed.append(buf.at[slot].set(a[order], mode="drop"))
    return packed, out_valid, overflow


def exchange(dest: jnp.ndarray, valid: jnp.ndarray, n_shards: int,
             cap: int, arrays: list, axis: str = SHARD_AXIS):
    """The shuffle: pack + all_to_all. Each shard returns with the
    rows (from every shard) whose dest == its own index; row order is
    (source shard, local order). Output length n_shards * cap."""
    packed, pvalid, overflow = pack_for_exchange(
        dest, valid, n_shards, cap, arrays)
    # unified transfer accounting: the all_to_all lives inside the
    # XLA program (no host hook per execution), so tally its buffer
    # footprint at trace time — n_shards*cap rows per payload column
    EXCHANGE_TRACED.bump(sum(int(p.size) * p.dtype.itemsize
                             for p in packed))

    def a2a(x):
        return jax.lax.all_to_all(x, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
    recv = [a2a(p) for p in packed]
    rvalid = a2a(pvalid)
    # every shard must agree on overflow (it is a retry signal)
    any_ovf = jax.lax.psum(overflow.astype(jnp.int32), axis) > 0
    return recv, rvalid, any_ovf
