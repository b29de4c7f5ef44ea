"""Pallas TPU kernels: order-preserving selection compaction.

What `exec/compile.py`'s compact_batch runs: each `block`-row segment
of a batch packs its selected rows to its front, in their order, and
keeps the first `kb`. A compaction is no random permutation: every
survivor moves LEFT by the number of unselected rows before it, its
displacement d. Moving, for bit b = 0, 1, 2, ... of d in turn, every
row whose bit b is set left by 2^b is collision-free (the `compress`
network of Hacker's Delight 7-4, over rows instead of bits): no sort,
no gather, log2(block) shifted selects an array.

Two kernels, both a grid over the blocks with a block as a
[block/128, 128] tile in VMEM (row i of the block at [i // 128,
i % 128]):

- `route`: from the selection mask alone, the network's routing: for
  every step b the mask of positions a row arrives at, as bit b of one
  32-bit word a position, with LIVE where the packed block holds a
  survivor; and the block's count of selected rows. d is a prefix sum
  of the unselected rows, two triangular matmuls on the MXU (0/1
  operands in bf16, f32 accumulation: exact); the steps then move d
  itself.
- `pack`: one or more arrays (32-bit words, or an 8-bit validity
  mask) through the network `route` laid out, the first kb rows of
  each block written, the block's first packed row repeated behind
  its survivors. An array is read once and written once at kb/block
  of its size; there is no full-width temporary.

A shift never wraps a row that counts: a row with bit b of d set has
at least 2^b unselected rows before it and has moved by less than 2^b
so far, so it sits at 2^b or beyond. The shifts are therefore plain
rotations of the tile (`pltpu.roll`), with nothing masked.

One `pack` call a column (its words and its validity mask) keeps a
column XLA finds unused removable, with whatever computed it: a batch
at a Compact holds every column of the joins beneath it, and the
statement reads a handful.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# the routing word's bit for "a survivor ends up here"
LIVE = 1 << 30
# rows a block must be a multiple of: whole (8, 128) tiles
BLOCK_QUANTUM = 8 * LANES


def network_steps(block: int) -> int:
    """Steps of the network over a `block`-row segment: the bits of
    the largest displacement a surviving row can have."""
    return max(block - 1, 1).bit_length()


def _shift_left(x, s: int):
    """y.flat[i] = x.flat[(i + s) % x.size] of a [rows, 128] tile."""
    rows = x.shape[0]
    q, r = divmod(s, LANES)
    if r:
        a = pltpu.roll(x, LANES - r, axis=1)
        # lanes that ran off the end of a tile row continue in the next
        nxt = pltpu.roll(a, rows - 1, axis=0)
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where(lane < LANES - r, a, nxt)
    q %= rows
    if q:
        x = pltpu.roll(x, rows - q, axis=0)
    return x


def _route_kernel(sel_ref, route_ref, count_ref, *, steps: int):
    s = sel_ref[0].astype(jnp.int32)
    rows = s.shape[0]
    count = jnp.sum(jnp.sum(s, axis=1, keepdims=True), axis=0,
                    keepdims=True)
    count_ref[0] = jnp.broadcast_to(count, count_ref.shape[1:])
    uns = (1 - s).astype(jnp.float32).astype(jnp.bfloat16)
    # d = inclusive prefix count of unselected rows: along the lanes
    # of a tile row, plus the tile rows above
    upper = (jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 0)
             <= jax.lax.broadcasted_iota(jnp.int32, (LANES, LANES), 1))
    below = (jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 1)
             < jax.lax.broadcasted_iota(jnp.int32, (rows, rows), 0))
    within = jnp.dot(uns, upper.astype(jnp.float32).astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    above = jnp.dot(below.astype(jnp.float32).astype(jnp.bfloat16), uns,
                    preferred_element_type=jnp.float32)
    d = (within + jnp.sum(above, axis=1, keepdims=True)).astype(jnp.int32)
    # an unselected position holds 0, as a survivor that stays does:
    # nothing ever moves onto either
    d = jnp.where(s != 0, d, 0)
    route = jnp.zeros_like(d)
    for b in range(steps):
        coming = _shift_left(d, 1 << b)
        take = coming & (1 << b)
        leaves = d & (1 << b)
        d = jnp.where(take != 0, coming, jnp.where(leaves != 0, 0, d))
        route = route | take
    # LIVE (past any step's bit) at the positions the packed block's
    # survivors fill
    at = (jax.lax.broadcasted_iota(jnp.int32, d.shape, 0) * LANES
          + jax.lax.broadcasted_iota(jnp.int32, d.shape, 1))
    route_ref[0] = route | jnp.where(at < count, LIVE, 0)


def _pack_kernel(route_ref, *refs, steps: int, keep: int):
    k = len(refs) // 2
    route = route_ref[0]
    # the network moves bits: every word as int32 in here (an 8-bit
    # word, validity bits, is widened in VMEM)
    xs = [jax.lax.bitcast_convert_type(r[0], jnp.int32)
          if r.dtype.itemsize == 4 else r[0].astype(jnp.int32)
          for r in refs[:k]]
    for b in range(steps):
        take = (route & (1 << b)) != 0
        xs = [jnp.where(take, _shift_left(x, 1 << b), x) for x in xs]
    live = (route[:keep] & LIVE) != 0
    lane0 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) == 0
    for x, o in zip(xs, refs[k:]):
        # behind a block's survivors, its first row over and over: what
        # the statement does with the packed batch (probe gathers,
        # scatter-adds) touches unselected rows too, and one address a
        # block costs it next to nothing where leftover rows' own
        # values are as many random addresses. (Taken by a lane
        # reduction: Mosaic broadcasts its result, not a [1, 1] slice.)
        first = jnp.sum(jnp.where(lane0, x[:1], jnp.zeros_like(x[:1])),
                        axis=1, keepdims=True)
        o[0] = jax.lax.bitcast_convert_type(
            jnp.where(live, x[:keep], first), o.dtype)


def _tile_spec(rows: int):
    return pl.BlockSpec((1, rows, LANES), lambda i: (i, 0, 0))


def tiled(x, block: int):
    """A row-length array as the kernels take it: [n // block,
    block // 128, 128] (a bitcast of the TPU's row layout)."""
    return x.reshape(x.shape[0] // block, block // LANES, LANES)


@functools.partial(jax.jit, static_argnames=("interpret",))
def route(sel, *, interpret: bool = False):
    """(routing, count) of a selection mask, `tiled` bool [nb,
    block // 128, 128] with `block` a multiple of BLOCK_QUANTUM: the
    network's routing words, int32 of that shape, bit b of a word set
    where step b brings a row to that position and LIVE where the
    packed block holds a survivor; and the selected rows of each
    block, int32 [nb]."""
    nb, rows, _ = sel.shape
    with jax.enable_x64(False):
        routing, count = pl.pallas_call(
            functools.partial(_route_kernel,
                              steps=network_steps(rows * LANES)),
            out_shape=(jax.ShapeDtypeStruct(sel.shape, jnp.int32),
                       jax.ShapeDtypeStruct((nb, 8, LANES), jnp.int32)),
            grid=(nb,),
            in_specs=[_tile_spec(rows)],
            out_specs=(_tile_spec(rows), _tile_spec(8)),
            interpret=interpret,
        )(sel.astype(jnp.int8))
    return routing, count[:, 0, 0]


@functools.partial(jax.jit, static_argnames=("kb", "interpret"))
def pack(routing, words, *, kb: int, interpret: bool = False):
    """The `tiled` 32-bit arrays `words` (a tuple; an int8 array among
    them comes back as int32) through the network `routing`, of
    route(sel): each a flat [nb * kb] array, a block's selected rows at
    the front of its kb, in their order (behind them the block's first
    packed row, repeated). kb is a multiple of 128."""
    nb, rows, _ = routing.shape
    keep = kb // LANES
    k = len(words)
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            functools.partial(_pack_kernel, steps=network_steps(rows * LANES),
                              keep=keep),
            out_shape=tuple(
                jax.ShapeDtypeStruct(
                    (nb, keep, LANES),
                    w.dtype if w.dtype.itemsize == 4 else jnp.int32)
                for w in words),
            grid=(nb,),
            in_specs=[_tile_spec(rows)] * (1 + k),
            out_specs=tuple(_tile_spec(keep) for _ in words),
            interpret=interpret,
        )(routing, *words)
    # kb / 128 tile rows a block are seldom whole (8, 128) tiles, so the
    # flat form is a relayout: made once, here, not inside every fusion
    # that reads the column (14 MiB of program text in TPC-H Q3)
    return jax.lax.optimization_barrier(
        tuple(o.reshape(nb * kb) for o in outs))
