"""Pallas TPU kernel: one-pass LARGE-G dense grouped aggregation.

The sibling `groupagg.py` kernel Python-unrolls one masked reduction
per (group, aggregate) pair, which caps the group count at a few
dozen. This kernel handles the hash-strategy group counts (q3 ~30K,
q18 ~200K at scale) by tiling the group domain and turning the
segment sum into MXU matmuls: for each row block,

    one_hot(gid)[blk, G_tile].T @ values[blk, A]  ->  [G_tile, A]

folds the whole block into a VMEM accumulator tile with no scatters
anywhere (in the kernel the operands are laid out transposed, rows
along the lanes — see large_group_aggregate). The grid is sequential
on TPU — (group_tiles, row_blocks)
with the row-block dimension innermost, so each output tile is
revisited across consecutive steps (the standard Pallas reduction
pattern; the accumulator is initialised under `pl.when(i == 0)`).

Dtype envelope — wider than the small kernel's f32-only one:

- f32 value columns accumulate in a f32 [NF, G_tile] tile. A block
  partial is exact for integer-valued columns while
  blk * max|value| < 2^24 (f32's integer range).
- exact int64 SUMs ride the limb decomposition `ops/agg.py` proves
  correct: the caller splits each 64-bit argument into w-bit i32
  limbs OUTSIDE the kernel (Mosaic has no 64-bit lanes), the kernel
  accumulates each limb column in an i32 tile (the f32 matmul block
  partial is exact while blk*(2^w-1) < 2^24, i.e. w <= 24-log2(blk);
  the per-group i32 accumulator is exact while
  max_group_rows*(2^w-1) < 2^31 — `limb_width` takes the min), and
  the caller recombines with `sum_j limbs[j] << (j*w)` in int64,
  whose wrapping IS int64 modular arithmetic — bit-identical to the
  XLA `_group_sum_i64_limbs` path. DECIMAL-exact q1/q3/q18 revenue
  sums are therefore eligible here.
- MIN/MAX slots are per-row masked reductions folded with
  minimum/maximum against +/-inf identities (no matmul).
- a REPMIN slot (i32 min of row id over onehot & sel) replaces the
  `group_rep_index` scatter for "any"-valued grouping columns.

Replaces (conceptually) the reference's generated hash-aggregation
kernels: colexecagg's *_hash.eg.go family.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .groupagg import BUILDS, FALLBACKS, LANES, MAX, MIN, ROWS  # noqa: F401

# group-domain tile (VMEM accumulator minor dim; multiple of 128 lanes)
GROUP_TILE = 512
# row-block size per grid step (bounds the one-hot tile and the f32
# matmul partial exactness window: blk*(2^w-1) < 2^24)
BLOCK_ROWS = 1024


def row_block(n: int, block_rows: int = BLOCK_ROWS) -> int:
    """Largest power-of-two row block that divides n (n % 128 == 0, so
    this is >= 128), capped by the block budget."""
    assert n % LANES == 0, "row count must be a multiple of 128"
    return min(block_rows, n & -n)


def limb_width(n: int, max_group_rows: int,
               block_rows: int = BLOCK_ROWS, cap: int = 22) -> int:
    """The widest limb w such that BOTH accumulations stay exact:
    the f32 matmul block partial (blk*(2^w-1) < 2^24) and the
    per-group i32 running sum (maxg*(2^w-1) < 2^31). Mirrors
    agg._group_sum_i64_limbs' bound, tightened by the block term.
    `cap` (autotuned, ops/pallas/autotune.py) may only narrow the
    width below the exactness bound — results stay bit-identical for
    any cap in [1, 22], a narrower cap just trades more limb columns
    for a denser matmul."""
    blk = row_block(n, block_rows)
    maxg = max_group_rows if max_group_rows and 0 < max_group_rows <= n else n
    maxg = max(1, maxg)
    w = int(math.floor(math.log2((2 ** 31 - 1) / maxg + 1)))
    w = min(w, 24 - int(math.log2(blk)), 22, cap)
    return max(1, w)


def _kernel(gid_ref, sel_ref, mat_ref, *refs, n_mat_f: int, n_mat: int,
            mm_ops: tuple, want_rep: bool, group_tile: int, blk: int,
            n: int, nf: int, ni: int):
    mm_refs = refs[:len(mm_ops)]
    outs = list(refs[len(mm_ops):])
    acc_f_ref, acc_i_ref = outs[:2]
    acc_mm_ref = outs[2] if mm_ops else None
    acc_rep_ref = outs[-1] if want_rep else None
    j = pl.program_id(0)   # group tile (outer)
    i = pl.program_id(1)   # row block (inner: output tile revisited)
    n_mat_i = n_mat - n_mat_f

    @pl.when(i == 0)
    def _init():
        acc_f_ref[:, :] = jnp.zeros((nf, group_tile), jnp.float32)
        acc_i_ref[:, :] = jnp.zeros((ni, group_tile), jnp.int32)
        for r, op in enumerate(mm_ops):
            ident = np.float32(np.inf if op == MIN else -np.inf)
            acc_mm_ref[:, r:r + 1] = jnp.full(
                (group_tile, 1), ident, jnp.float32)
        if want_rep:
            acc_rep_ref[:, :] = jnp.full(
                (group_tile, 1), np.int32(n), jnp.int32)

    # rows ride the LANE axis: every per-row input is a (1, blk) or
    # (n_mat, blk) block of a lane-dense array, and the one-hot is
    # built transposed, [GT, blk] (group ids down the sublanes)
    ids = j * group_tile + jax.lax.broadcasted_iota(
        jnp.int32, (group_tile, blk), 0)
    onehot = gid_ref[:, :] == ids  # (1, blk) == (GT, blk) -> broadcast

    # the whole block's segment partial as ONE [n_mat, GT] MXU matmul
    # (contracting the row axis of both operands). HIGHEST: the
    # exactness argument in the module docstring needs the f32
    # contraction at full precision — a bf16 pass would round any limb
    # wider than 8 bits.
    part = jax.lax.dot_general(
        mat_ref[:, :], onehot.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    if n_mat_f:
        acc_f_ref[0:n_mat_f, :] += part[0:n_mat_f, :]
    if n_mat_i:
        # limb/count columns are small non-negative ints: the f32
        # partial is exact by the limb_width bound, so the i32 cast
        # is lossless
        acc_i_ref[0:n_mat_i, :] += part[n_mat_f:n_mat, :].astype(jnp.int32)

    # MIN/MAX and REPMIN reduce along the lanes, so their accumulators
    # are [GT, 1] columns of outputs laid out [G, slots]
    for r, op in enumerate(mm_ops):
        ident = np.float32(np.inf if op == MIN else -np.inf)
        v = jnp.where(onehot, mm_refs[r][:, :], ident)
        fold = jnp.min if op == MIN else jnp.max
        red = fold(v, axis=1, keepdims=True)
        comb = jnp.minimum if op == MIN else jnp.maximum
        acc_mm_ref[:, r:r + 1] = comb(acc_mm_ref[:, r:r + 1], red)

    if want_rep:
        sel = sel_ref[:, :] != 0
        rid = i * blk + jax.lax.broadcasted_iota(
            jnp.int32, (group_tile, blk), 1)
        rv = jnp.where(jnp.logical_and(onehot, sel), rid, np.int32(n))
        acc_rep_ref[:, :] = jnp.minimum(
            acc_rep_ref[:, :], jnp.min(rv, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=(
    "num_groups", "mat_int", "mm_ops", "want_rep", "group_tile",
    "block_rows", "interpret"))
def large_group_aggregate(gid, sel, mat_values: tuple, mm_values: tuple,
                          num_groups: int, mat_int: tuple,
                          mm_ops: tuple = (), want_rep: bool = False,
                          group_tile: int = GROUP_TILE,
                          block_rows: int = BLOCK_ROWS,
                          interpret: bool = False):
    """One-pass large-G grouped aggregation.

    gid: int32[n] dense ids (0..num_groups-1); rows outside [0, G) or
    with sel False simply match no one-hot column, so the caller folds
    `sel` into the matmul columns (pre-masked to 0) and the kernel
    only consults `sel` for the REPMIN slot. mat_values: one [n]
    column per matmul slot, f32-valued; the first columns accumulate
    in f32 rows, the `mat_int[k]` == True tail in i32 rows (limb and
    count columns — small non-negative ints). mm_values/mm_ops:
    MIN/MAX slots, pre-masked to their +/-inf identities. Returns
    (f32[NF, num_groups], i32[NI, num_groups]) where
    NF = max(1, n_f + len(mm_ops)) (f sums first, then MIN/MAX rows)
    and NI = max(1, n_i + want_rep) (i sums first, then the rep row:
    min selected row id, n when the group is empty).

    Every operand reaches the kernel lane-dense — per-row vectors as
    [1, n], the matmul columns stacked [n_mat, n]. A per-row [n, 1]
    operand is tiled (8, 128) in HBM, 128x its size: at n = 2^23 one
    such column is 4 GB, and XLA refused TPC-H Q1's 61 of them at SF1
    ("Used 208.14G of 15.75G hbm").
    """
    n = gid.shape[0]
    BUILDS.bump("large")
    ROWS.bump("large", n)
    n_mat = len(mat_values)
    assert n_mat >= 1 and len(mat_int) == n_mat
    n_mat_i = sum(bool(b) for b in mat_int)
    n_mat_f = n_mat - n_mat_i
    # f columns first, then i columns — the kernel slices `part` once
    assert all(not b for b in mat_int[:n_mat_f]) and \
        all(bool(b) for b in mat_int[n_mat_f:])
    blk = row_block(n, block_rows)
    gtiles = -(-num_groups // group_tile)
    gp = gtiles * group_tile
    nf = max(1, n_mat_f)
    ni = max(1, n_mat_i)
    n_mm = len(mm_ops)

    def kernel(gid_ref, sel_ref, mat_ref, *refs):
        _kernel(gid_ref, sel_ref, mat_ref, *refs, n_mat_f=n_mat_f,
                n_mat=n_mat, mm_ops=mm_ops, want_rep=want_rep,
                group_tile=group_tile, blk=blk, n=n, nf=nf, ni=ni)

    # i32 index-map coordinates: under the engine's jax_enable_x64 a
    # literal 0 traces as i64, which Mosaic rejects
    row1 = pl.BlockSpec((1, blk), lambda j, i: (jnp.int32(0), i),
                        memory_space=pltpu.VMEM)
    matspec = pl.BlockSpec((n_mat, blk), lambda j, i: (jnp.int32(0), i),
                           memory_space=pltpu.VMEM)

    def by_group(rows):   # [rows, G] sums: group ids along the lanes
        return pl.BlockSpec((rows, group_tile),
                            lambda j, i: (jnp.int32(0), j),
                            memory_space=pltpu.VMEM)

    def by_slot(slots):   # [G, slots] lane-reduced MIN/MAX/REPMIN
        return pl.BlockSpec((group_tile, slots),
                            lambda j, i: (j, jnp.int32(0)),
                            memory_space=pltpu.VMEM)

    out_shape = [jax.ShapeDtypeStruct((nf, gp), jnp.float32),
                 jax.ShapeDtypeStruct((ni, gp), jnp.int32)]
    out_specs = [by_group(nf), by_group(ni)]
    if n_mm:
        out_shape.append(jax.ShapeDtypeStruct((gp, n_mm), jnp.float32))
        out_specs.append(by_slot(n_mm))
    if want_rep:
        out_shape.append(jax.ShapeDtypeStruct((gp, 1), jnp.int32))
        out_specs.append(by_slot(1))

    # the operand matrix is written out in full before the kernel
    # reads it: a phase of its own to a profile (`operands`). The
    # kernel's own scope is the caller's: XLA names the custom call by
    # the last component of its path, this function's name
    with jax.named_scope("operands"):
        args = (gid.astype(jnp.int32).reshape(1, n),
                sel.astype(jnp.int32).reshape(1, n),
                jnp.stack([v.astype(jnp.float32) for v in mat_values],
                          axis=0),
                *[v.astype(jnp.float32).reshape(1, n) for v in mm_values])
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            kernel,
            out_shape=tuple(out_shape),
            grid=(gtiles, n // blk),
            in_specs=[row1, row1, matspec] + [row1] * n_mm,
            out_specs=tuple(out_specs),
            interpret=interpret,
        )(*args)
    acc_f, acc_i = outs[0][:, :num_groups], outs[1][:, :num_groups]
    if n_mm:
        mm = outs[2][:num_groups, :].T
        acc_f = jnp.concatenate([acc_f[:n_mat_f], mm], axis=0)
    if want_rep:
        rep = outs[-1][:num_groups, :].T
        acc_i = jnp.concatenate([acc_i[:n_mat_i], rep], axis=0)
    return acc_f, acc_i
