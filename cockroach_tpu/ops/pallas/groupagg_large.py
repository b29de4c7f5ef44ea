"""Pallas TPU kernel: one-pass LARGE-G dense grouped aggregation.

The engine's one grouped-aggregation kernel: it serves dense group
domains from TPC-H Q1's twelve groups up to LARGE_G_MAX
(exec/compile.py) by tiling the group domain and turning the
segment sum into MXU matmuls: for each row block,

    one_hot(gid)[blk, G_tile].T @ values[blk, A]  ->  [G_tile, A]

folds the whole block into a VMEM accumulator tile with no scatters
anywhere (in the kernel the operands are laid out transposed, rows
along the lanes — see large_group_aggregate). The grid is sequential
on TPU — (group_tiles, row_blocks)
with the row-block dimension innermost, so each output tile is
revisited across consecutive steps (the standard Pallas reduction
pattern; the accumulator is initialised under `pl.when(i == 0)`).

The contraction's geometry comes from the plan. The group tile is the
group count rounded up to whole 128-lane vregs, at most the tile
parameter (effective_group_tile): TPC-H Q1's 12 groups take 128 lanes,
not 512. And every row whose values are exact in bf16 — limbs of at
most 8 bits, count bits, the liveness bit, the three bf16 pieces of a
shadow — is contracted against the one-hot (0 and 1: exact too) in ONE
bf16 MXU pass with f32 accumulation, not the six passes of an f32
matmul at `Precision.HIGHEST`; only float-sum rows keep such a
contraction, of their own.

Dtype envelope:

- f32 value columns accumulate in a f32 [NF, G_tile] tile. A block
  partial is exact for integer-valued columns while
  blk * max|value| < 2^24 (f32's integer range).
- exact int64 SUMs ride the limb decomposition `ops/agg.py` proves
  correct: each 64-bit argument reaches the kernel as its two 32-bit
  words (Mosaic has no 64-bit lanes), the kernel cuts the w-bit limbs
  out of them per row block, in VMEM, and
  accumulates each limb column in an i32 tile. Three bounds keep that
  exact, and `limb_width` takes the min: w <= 8, so a limb is an
  integer in [0, 255] and exact in bf16, as is its product with the
  one-hot; the MXU's f32 block partial is exact while
  blk*(2^w-1) < 2^24, i.e. w <= 24-log2(blk) (never the tighter one
  for a block of at most 2^16 rows); the per-group i32 accumulator is
  exact while max_group_rows*(2^w-1) < 2^31. The caller recombines
  with `sum_j limbs[j] << (j*w)` in int64,
  whose wrapping IS int64 modular arithmetic — bit-identical to the
  XLA `_group_sum_i64_limbs` path. DECIMAL-exact q1/q3/q18 revenue
  sums are therefore eligible here.
- the f32 shadow of an exact sum, which only feeds the overflow
  sentinel, is cut into its hi, mid and lo bf16 pieces (the split
  HIGHEST itself makes of an f32 operand, 24 significant bits) and
  rides the same pass as three rows, summed again in f32.
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
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128

# op kinds of a MIN/MAX slot
MIN, MAX = 0, 1


class _KernelTally:
    """Thread-safe per-kernel counter.

    The trace-time tallies are bumped inside jit-traced bodies; the
    pipelined data plane, per-mesh dispatcher threads and concurrent
    pgwire sessions can trace simultaneously, so a bare
    ``global x; x += 1`` read-modify-write races. One lock per tally,
    keyed by kind (``large`` for the kernel's builds, ``agg`` for an
    aggregation that fell back) so the engine can expose per-kind and
    total func-metrics.
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


# Trace-time tallies, read by the engine's exec.pallas.* func-metrics.
# large_group_aggregate's body runs once per (shape, static args)
# jit-cache entry, so they count kernel BUILDS, the honest metric for
# a jitted kernel (executions happen inside XLA where host counters
# cannot see them).
BUILDS = _KernelTally()      # kernel (re)builds
ROWS = _KernelTally()        # rows offered to the kernel at trace time
FALLBACKS = _KernelTally()   # aggregations compiled on the XLA segment
                             # path while the kernel was enabled
OPERAND_BYTES = _KernelTally()   # bytes of the HBM arrays a build hands
                                 # the kernel (what XLA writes for it)
LIMB_BITS = _KernelTally()       # limb width of a build's exact sums,
                                 # summed over builds (/ builds)
MATMUL_ROWS = _KernelTally()     # rows of a build's matmul operands,
                                 # summed over builds
GROUP_TILE_LANES = _KernelTally()    # lanes of the group tile a build
                                     # took, summed over builds
MXU_PASSES = _KernelTally()      # bf16 MXU passes of a build's
                                 # exact-rows contraction, over builds
OPERAND_WORDS = _KernelTally()   # [1, n] 32-bit arrays a build hands
                                 # the kernel, summed over builds
PROVED_SUMS = _KernelTally()     # a build's exact sums and avgs whose
                                 # argument the plan proved narrow
                                 # (BoundAgg.arg_bits), over builds

# group-domain tile (VMEM accumulator minor dim; multiple of 128
# lanes): the UPPER bound of the tile a build takes, which is sized by
# the plan's group count (effective_group_tile)
GROUP_TILE = 512
# row-block size per grid step (bounds the one-hot tile and the f32
# matmul partial exactness window: blk*(2^w-1) < 2^24, which an 8-bit
# limb keeps up to 2^16 rows). On the v5e a step's fixed cost and its
# one [1, blk] DMA an operand are most of a 1,024-row step once the
# contraction is one bf16 pass: 4,096 rows is 1.7-2.4x faster than
# 1,024 at 128 and at 512 lanes, and 8,192 rows of TPC-H Q1's thirteen
# operands pass the 16 MB of scoped VMEM (PERF.md, PR 29)
BLOCK_ROWS = 4096
# the widest limb: an 8-bit limb, a count bit and the one-hot are all
# exact in bf16, so the exact rows' contraction is ONE bf16 MXU pass
MAX_LIMB_BITS = 8


def effective_group_tile(num_groups: int,
                         group_tile: int = GROUP_TILE) -> int:
    """Lanes of the group tile a build over `num_groups` dense groups
    takes: the domain rounded up to whole 128-lane vregs, at most
    `group_tile`. TPC-H Q1's 12 groups take 128 lanes, not 512; a
    domain past group_tile - 128 sees the upper bound."""
    return min(group_tile, -(-max(1, num_groups) // LANES) * LANES)


def row_block(n: int, block_rows: int = BLOCK_ROWS) -> int:
    """Largest power-of-two row block that divides n (n % 128 == 0, so
    this is >= 128), capped by the block budget."""
    assert n % LANES == 0, "row count must be a multiple of 128"
    return min(block_rows, n & -n)


def limb_width(n: int, max_group_rows: int,
               block_rows: int = BLOCK_ROWS) -> int:
    """The widest limb w such that ALL THREE steps stay exact: the
    bf16 operand (w <= 8: a limb is an integer in [0, 255]), the MXU's
    f32 block partial (blk*(2^w-1) < 2^24) and the per-group i32
    running sum (maxg*(2^w-1) < 2^31). Mirrors
    agg._group_sum_i64_limbs' bound, tightened by the first two."""
    blk = row_block(n, block_rows)
    maxg = max_group_rows if max_group_rows and 0 < max_group_rows <= n else n
    maxg = max(1, maxg)
    w = int(math.floor(math.log2((2 ** 31 - 1) / maxg + 1)))
    w = min(w, 24 - int(math.log2(blk)), MAX_LIMB_BITS)
    return max(1, w)


def limb_rows(src: int, bits: int, w: int) -> tuple:
    """The layout rows that cover the low `bits` bits of source `src`
    in w-bit limbs, least significant first."""
    return tuple(("limb", src, j * w, w) for j in range(-(-bits // w)))


def _srl(x, s: int):
    return jax.lax.shift_right_logical(x, np.int32(s)) if s else x


def _limb(lo, hi, shift: int, width: int):
    """Bits [shift, shift + width) of the 64-bit value (hi, lo) as
    f32: one shift inside a word, two where the limb straddles the
    32-bit boundary. Logical shifts, so bits past 63 read 0."""
    if shift >= 32:
        assert hi is not None, "a one-word source has no bits past 31"
        x = _srl(hi, shift - 32)
    else:
        x = _srl(lo, shift)
        if hi is not None and shift + width > 32:
            x = x | (hi << np.int32(32 - shift))
    return (x & np.int32((1 << width) - 1)).astype(jnp.float32)


def _shadow(lo, hi):
    """f32 approximation of the 64-bit value (hi, lo): it only feeds
    the overflow sentinel, which tolerates 1e-2 relative."""
    if hi is None:
        return lo.astype(jnp.float32)  # one word: 0 <= v < 2^31
    ulo = _srl(lo, 16).astype(jnp.float32) * np.float32(1 << 16) \
        + (lo & np.int32(0xFFFF)).astype(jnp.float32)
    return hi.astype(jnp.float32) * np.float32(2.0 ** 32) + ulo


def _bf16_pieces(v):
    """f32 v as three f32 pieces, hi + mid + lo == v exactly, each of
    at most 8 significant bits and so exact in bf16: the split
    Precision.HIGHEST makes of an f32 operand (24 significant bits),
    by truncation."""
    def top8(x):    # sign, exponent and the 7 stored bits bf16 keeps
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.int32)
            & np.int32(-65536), jnp.float32)
    hi = top8(v)
    mid = top8(v - hi)
    return hi, mid, v - hi - mid


def _kernel(gid_ref, *refs, i_rows: tuple, split: tuple, f_rows: tuple,
            src_words: tuple, n_mwords: int, n_words: int, n_f: int,
            mm_ops: tuple, want_rep: bool, group_tile: int, blk: int,
            n: int, nf: int, ni: int):
    mask_refs, refs = refs[:n_mwords], refs[n_mwords:]
    word_refs, refs = refs[:n_words], refs[n_words:]
    f_refs, refs = refs[:n_f], refs[n_f:]
    mm_refs, refs = refs[:len(mm_ops)], refs[len(mm_ops):]
    n_mat_i, n_split = len(i_rows), len(split)
    n_x = n_mat_i + 3 * n_split
    n_out = 2 + bool(mm_ops) + want_rep
    # scratch: the [rows, blk] f32 operands of the bf16 pass (x) and of
    # the f32 one (f), each only where the layout has such rows
    outs, scratch = refs[:n_out], iter(refs[n_out:])
    x_ref = next(scratch) if n_x else None
    f_ref = next(scratch) if f_rows else None
    acc_f_ref, acc_i_ref = outs[:2]
    acc_mm_ref = outs[2] if mm_ops else None
    acc_rep_ref = outs[-1] if want_rep else None
    j = pl.program_id(0)   # group tile (outer)
    i = pl.program_id(1)   # row block (inner: output tile revisited)

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

    # the matmul operands of this row block, built here from the
    # aggregates' ARGUMENTS: each source's words and each mask word are
    # loaded once, every limb/count/shadow row derived on the VPU and
    # written to the scratch. The [rows, n] matrix exists nowhere else
    words = [ref[:, :] for ref in word_refs]
    srcs = [(words[lo], None if hi is None else words[hi])
            for lo, hi in src_words]
    mwords = [ref[:, :] for ref in mask_refs]

    def mask_bit(k):
        return _srl(mwords[k // 32], k % 32) & np.int32(1)

    for r, row in enumerate(i_rows):
        kind = row[0]
        if kind == "limb":
            v = _limb(*srcs[row[1]], row[2], row[3])
        elif kind == "count":      # bit 0 is sel, mask k rides bit k + 1
            v = mask_bit(row[1] + 1).astype(jnp.float32)
        else:                      # "live"
            v = mask_bit(0).astype(jnp.float32)
        x_ref[r:r + 1, :] = v
    # a shadow rides the same pass as its hi, mid and lo rows, the
    # three blocks after the i rows
    for k, (_, src) in enumerate(split):
        for p, piece in enumerate(_bf16_pieces(_shadow(*srcs[src]))):
            r = n_mat_i + p * n_split + k
            x_ref[r:r + 1, :] = piece
    for k, (_, col) in enumerate(f_rows):
        f_ref[k:k + 1, :] = f_refs[col][:, :]

    # rows ride the LANE axis: every per-row input is a (1, blk) block
    # of a lane-dense array, and the one-hot is built transposed,
    # [GT, blk] (group ids down the sublanes)
    ids = j * group_tile + jax.lax.broadcasted_iota(
        jnp.int32, (group_tile, blk), 0)
    onehot = gid_ref[:, :] == ids  # (1, blk) == (GT, blk) -> broadcast
    onehot_f32 = onehot.astype(jnp.float32)
    contract_rows = (((1,), (1,)), ((), ()))

    if n_x:
        # the block's segment partial of every exact row as ONE
        # [n_x, GT] MXU matmul, in ONE bf16 pass: a limb of at most 8
        # bits, a count bit, a shadow piece and the one-hot's 0 and 1
        # are all exact in bf16, so is every product, and the MXU
        # accumulates them in f32 — exact for the integer rows by the
        # limb_width bound (blk * 255 < 2^24)
        part = jax.lax.dot_general(
            x_ref[:, :].astype(jnp.bfloat16),
            onehot_f32.astype(jnp.bfloat16), contract_rows,
            preferred_element_type=jnp.float32)
        if n_mat_i:
            # the f32 partial of a limb/count row is an exact integer
            # under 2^24, so the i32 cast is lossless
            acc_i_ref[0:n_mat_i, :] += part[0:n_mat_i, :].astype(jnp.int32)
        for k, (at, _) in enumerate(split):
            hi, mid, lo = (part[r:r + 1, :] for r in (
                n_mat_i + p * n_split + k for p in range(3)))
            acc_f_ref[at:at + 1, :] += lo + mid + hi
    if f_rows:
        # float sums are not bf16-exact: their own small contraction,
        # six bf16 passes (HIGHEST), f32 precision
        part = jax.lax.dot_general(
            f_ref[:, :], onehot_f32, contract_rows,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        for k, (at, _) in enumerate(f_rows):
            acc_f_ref[at:at + 1, :] += part[k:k + 1, :]

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
        sel = mask_bit(0) != 0
        rid = i * blk + jax.lax.broadcasted_iota(
            jnp.int32, (group_tile, blk), 1)
        rv = jnp.where(jnp.logical_and(onehot, sel), rid, np.int32(n))
        acc_rep_ref[:, :] = jnp.minimum(
            acc_rep_ref[:, :], jnp.min(rv, axis=1, keepdims=True))


@functools.partial(jax.jit, static_argnames=(
    "num_groups", "layout", "mm_ops", "want_rep", "group_tile",
    "block_rows", "interpret", "proved_sums"))
def large_group_aggregate(gid, sel, sources: tuple, masks: tuple,
                          f_values: tuple, mm_values: tuple,
                          num_groups: int, layout: tuple,
                          mm_ops: tuple = (), want_rep: bool = False,
                          group_tile: int = GROUP_TILE,
                          block_rows: int = BLOCK_ROWS,
                          interpret: bool = False,
                          proved_sums: int = 0):
    """One-pass large-G grouped aggregation.

    gid: int32[n] dense ids (0..num_groups-1); rows outside [0, G)
    match no one-hot column. The kernel is handed the aggregates'
    ARGUMENTS and builds its matmul operands itself, per row block, in
    VMEM:

    - sources: one integer [n] array per distinct exact-sum argument,
      pre-masked to 0 where the row does not take part. int64 travels
      as its two 32-bit words; an int32 source (the caller proved
      0 <= v < 2^31) as one.
    - masks: bool[n], each already ANDed with `sel`; with `sel` they
      are packed 32 to an int32 word (bit 0 is `sel`).
    - f_values: f32[n] float-sum columns, pre-masked to 0.
    - layout: one entry per row of sums, f32-accumulated rows first —
      ("f", j) is f_values[j], ("shadow", s) an f32 approximation of
      source s — then the i32-accumulated ones: ("limb", s, shift,
      width) is bits [shift, shift + width <= 8) of source s
      (limb_rows), ("count", k) counts masks[k], ("live",) counts
      `sel`. The i rows and the shadows (three rows each) are one bf16
      MXU pass, the f rows an f32 contraction of their own.
    - group_tile: the tile's upper bound; the build takes
      effective_group_tile(num_groups, group_tile) lanes.
    - mm_values/mm_ops: MIN/MAX slots, pre-masked to their +/-inf
      identities.
    - proved_sums: for the PROVED_SUMS tally alone, which counts where
      builds are counted: the caller's sums and avgs whose layout
      rows were sized by a value-range proof.

    Returns (f32[NF, num_groups], i32[NI, num_groups]): the layout's
    f rows then the MIN/MAX rows (NF >= 1), its i rows then, with
    want_rep, the rep row: min selected row id, n when the group is
    empty (NI >= 1).

    Every operand reaches the kernel lane-dense, as [1, n]: a per-row
    [n, 1] operand is tiled (8, 128) in HBM, 128x its size. The
    [n_mat, n] f32 matrix of limb, count and shadow rows is never an
    HBM array (TPC-H Q1 at SF1: 61 rows, 2 GiB a statement): the
    kernel reads each source once and splits it on the VPU, under the
    matmul. With several group tiles (gtiles > 1) the split is redone
    for every tile, against an HBM read per tile that is the words',
    not the rows', size.
    """
    n = gid.shape[0]
    BUILDS.bump("large")
    ROWS.bump("large", n)
    n_mat = len(layout)
    assert n_mat >= 1
    n_mat_f = sum(row[0] in ("f", "shadow") for row in layout)
    # f rows first, then i rows
    i_rows = layout[n_mat_f:]
    assert all(row[0] in ("limb", "count", "live") for row in i_rows)
    # the one bf16 pass is exact only for limbs of at most 8 bits
    assert all(r[3] <= MAX_LIMB_BITS for r in i_rows if r[0] == "limb")
    # a shadow rides the bf16 pass as three rows; a float sum keeps a
    # HIGHEST contraction of its own. Each as (its row of the f
    # accumulator, its source or column)
    f_rows = tuple((r, row[1]) for r, row in enumerate(layout[:n_mat_f])
                   if row[0] == "f")
    split = tuple((r, row[1]) for r, row in enumerate(layout[:n_mat_f])
                  if row[0] == "shadow")
    n_mat_i = len(i_rows)
    n_x = n_mat_i + 3 * len(split)
    blk = row_block(n, block_rows)
    group_tile = effective_group_tile(num_groups, group_tile)
    gtiles = -(-num_groups // group_tile)
    gp = gtiles * group_tile
    nf = max(1, n_mat_f)
    ni = max(1, n_mat_i)
    n_mm = len(mm_ops)

    def row(x, dtype):
        return x.astype(dtype).reshape(1, n)

    # what XLA still writes for the kernel: the arguments evaluated and
    # masked, as 32-bit words (on TPU an int64 already lives as two)
    with jax.named_scope("operands"):
        words, src_words = [], []
        for s in sources:
            if s.dtype == jnp.int32:
                src_words.append((len(words), None))
                words.append(row(s, jnp.int32))
            else:
                s = s.astype(jnp.int64)
                src_words.append((len(words), len(words) + 1))
                words.append(row(s, jnp.int32))   # truncates: low word
                words.append(row(s >> jnp.int64(32), jnp.int32))
        bits = [sel] + list(masks)
        mwords = []
        for k0 in range(0, len(bits), 32):
            w = jnp.zeros((n,), jnp.uint32)
            for k, m in enumerate(bits[k0:k0 + 32]):
                w = w | (m.astype(jnp.uint32) << jnp.uint32(k))
            mwords.append(jax.lax.bitcast_convert_type(
                w, jnp.int32).reshape(1, n))
        args = (row(gid, jnp.int32), *mwords, *words,
                *[row(v, jnp.float32) for v in f_values],
                *[row(v, jnp.float32) for v in mm_values])
    # a count row past the masks handed in would read a zero bit
    assert all(r[1] < len(masks) for r in layout if r[0] == "count")
    OPERAND_BYTES.bump("large", sum(a.nbytes for a in args))
    OPERAND_WORDS.bump("large", len(args))
    PROVED_SUMS.bump("large", proved_sums)
    MATMUL_ROWS.bump("large", n_x + len(f_rows))
    LIMB_BITS.bump("large", max((r[3] for r in layout if r[0] == "limb"),
                                default=0))
    GROUP_TILE_LANES.bump("large", group_tile)
    MXU_PASSES.bump("large", int(n_x > 0))

    def kernel(gid_ref, *refs):
        _kernel(gid_ref, *refs, i_rows=i_rows, split=split, f_rows=f_rows,
                src_words=tuple(src_words),
                n_mwords=len(mwords), n_words=len(words),
                n_f=len(f_values), mm_ops=mm_ops,
                want_rep=want_rep, group_tile=group_tile, blk=blk, n=n,
                nf=nf, ni=ni)

    # i32 index-map coordinates: under the engine's jax_enable_x64 a
    # literal 0 traces as i64, which Mosaic rejects
    row1 = pl.BlockSpec((1, blk), lambda j, i: (jnp.int32(0), i),
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

    # the kernel's own scope is the caller's: XLA names the custom call
    # by the last component of its path, this function's name
    with jax.enable_x64(False):
        outs = pl.pallas_call(
            kernel,
            out_shape=tuple(out_shape),
            grid=(gtiles, n // blk),
            in_specs=[row1] * len(args),
            out_specs=tuple(out_specs),
            scratch_shapes=[pltpu.VMEM((rows, blk), jnp.float32)
                            for rows in (n_x, len(f_rows)) if rows],
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
