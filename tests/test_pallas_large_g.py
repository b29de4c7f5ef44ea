"""Large-G Pallas grouped-aggregation tests (interpret mode on CPU).

Three layers:

1. kernel-level fuzzed parity of ``large_group_aggregate`` against a
   numpy int64 oracle — the kernel is handed the ARGUMENTS and cuts
   the limbs itself: exact for counts and recombined int64 limb sums
   (negative values, both words, limbs astride the 32-bit boundary,
   one-word arguments, shared arguments, distinct validities),
   identity-filled for empty groups, tolerance-checked for f32 sums;
2. unit tests for the helpers (``row_block``, ``limb_width``) and the
   thread-safe ``_KernelTally``;
3. engine-level eligibility + parity: q18's inner GROUP BY rides the
   large kernel under the default ``auto`` mode, a sparse packed
   composite key does NOT (hash strategy -> fallback tally), the
   ``auto`` arm is bit-exact vs ``off`` (integer MIN/MAX included),
   the placement model asks the compile's own question, and the
   compiled HLO of the auto arm carries no aggregation scatters.
"""

import threading

import numpy as np
import pytest

from cockroach_tpu.ops.batch import read_ts_words
from cockroach_tpu.ops.pallas import groupagg_large as pg
from cockroach_tpu.ops.pallas.groupagg_large import (
    BLOCK_ROWS, GROUP_TILE, MAX, MIN, _KernelTally, effective_group_tile,
    large_group_aggregate, limb_rows, limb_width, row_block)


# ---------------------------------------------------------------- helpers

def _recombine(acc_i: np.ndarray, layout: tuple, src: int,
               rows=None) -> np.ndarray:
    """sum of (limb row << its shift) over source `src`'s limb rows
    (or the given subset) in mod-2^64 arithmetic (int64 wrap),
    matching both the XLA `_group_sum_i64_limbs` path and the engine's
    kernel-partial reconstruction."""
    i_rows = [r for r in layout if r[0] in ("limb", "count", "live")]
    total = np.zeros(acc_i.shape[1], np.uint64)
    for r in (rows if rows is not None else i_rows):
        if r[0] == "limb" and r[1] == src and r[2] < 64:
            total += acc_i[i_rows.index(r)].astype(np.uint64) \
                << np.uint64(r[2])
    return total.view(np.int64)


def _group_sum(gid, mask, vals, num_groups) -> np.ndarray:
    """Exact per-group int64 sums (wrapping, like the engine's)."""
    out = np.zeros(num_groups, np.int64)
    with np.errstate(over="ignore"):
        np.add.at(out, gid[mask], vals[mask])
    return out


def _group_count(gid, mask, num_groups) -> np.ndarray:
    return np.bincount(gid[mask], minlength=num_groups)


def _oracle(gid, sel, vals, mask, num_groups):
    """Per-group exact sums/counts/min/max/rep with numpy."""
    eff = sel & mask
    sums = np.zeros(num_groups, np.int64)
    cnts = np.zeros(num_groups, np.int64)
    mins = np.full(num_groups, np.inf, np.float32)
    maxs = np.full(num_groups, -np.inf, np.float32)
    reps = np.full(num_groups, len(gid), np.int64)
    for g in range(num_groups):
        gm = eff & (gid == g)
        cnts[g] = gm.sum()
        if gm.any():
            sums[g] = vals[gm].sum(dtype=np.int64)
            f = vals[gm].astype(np.float32)
            mins[g], maxs[g] = f.min(), f.max()
        sm = sel & (gid == g)
        if sm.any():
            reps[g] = np.flatnonzero(sm)[0]
    return sums, cnts, mins, maxs, reps


# ---------------------------------------------------------------- helpers'
# own unit tests

class TestRowBlock:
    def test_pow2_capped(self):
        assert row_block(1 << 16) == BLOCK_ROWS
        assert row_block(4096, block_rows=512) == 512

    def test_odd_multiple_of_128(self):
        # 384 = 128 * 3: largest pow2 divisor is 128
        assert row_block(384) == 128
        assert row_block(2048 * 3) == 2048  # the odd part caps it
        assert row_block(8192 * 3) == BLOCK_ROWS  # the budget does

    def test_rejects_unaligned(self):
        with pytest.raises(AssertionError):
            row_block(100)


class TestLimbWidth:
    @pytest.mark.parametrize("n,maxg,blk", [
        (4096, 1, 1024), (4096, 4096, 1024), (1 << 16, 1 << 16, 1024),
        (128, 128, 128), (1 << 20, 1000, 1024), (8192, 0, 256),
    ])
    def test_both_exactness_bounds(self, n, maxg, blk):
        w = limb_width(n, maxg, block_rows=blk)
        assert 1 <= w <= 8     # exact in bf16
        eff_blk = row_block(n, blk)
        eff_maxg = maxg if 0 < maxg <= n else n
        # f32 matmul block partial stays in f32's exact-integer range
        assert eff_blk * (2 ** w - 1) < 2 ** 24
        # i32 per-group running sum cannot wrap
        assert eff_maxg * (2 ** w - 1) < 2 ** 31

    def test_known_value(self):
        # one bf16 pass: never wider than 8, however small the group
        # (the f32 block bound alone would give 24-10=14 at blk=1024)
        assert limb_width(4096, 1, block_rows=1024) == 8
        # blk = 2^16 is the largest block an 8-bit limb allows
        assert limb_width(1 << 16, 1, block_rows=1 << 16) == 8
        assert limb_width(1 << 17, 1, block_rows=1 << 17) == 7


class TestKernelTally:
    def test_per_kind_and_total(self):
        t = _KernelTally()
        t.bump("a")
        t.bump("b", 5)
        assert t.value("a") == 1 and t.value("b") == 5
        assert t.value() == 6 and t.value("missing") == 0

    def test_thread_safety(self):
        t = _KernelTally()
        n_threads, per = 8, 10_000

        def work(k):
            for _ in range(per):
                t.bump(k)

        ts = [threading.Thread(target=work, args=("small" if i % 2 else
                                                  "large",))
              for i in range(n_threads)]
        for th in ts:
            th.start()
        for th in ts:
            th.join()
        assert t.value() == n_threads * per
        assert t.value("small") + t.value("large") == n_threads * per


# ---------------------------------------------------------------- kernel
# fuzzed parity vs numpy

CASES = [
    # (n, num_groups, sel_frac, mask_frac, seed) — G at/above/below the
    # (test-sized) tile boundary, empty groups via sparse occupancy
    (1024, 96, 0.8, 0.9, 0),
    (1024, 128, 0.9, 0.8, 1),    # G exactly at the tile boundary
    (2048, 129, 0.7, 0.95, 2),   # G one past a tile -> ragged last tile
    (4096, 700, 0.85, 0.9, 3),   # multi-tile, many empty groups
    (384, 40, 1.0, 1.0, 4),      # odd 128-multiple row count
]


I64 = np.iinfo(np.int64)

# what the in-kernel limb split has to get right, by the argument's
# values: name -> f(rng, n) -> int64[n]
SPLIT_VALUES = {
    # both 32-bit words populated, either sign
    "both_words": lambda rng, n: rng.integers(
        I64.min, I64.max, n, dtype=np.int64, endpoint=True),
    # negative only: the high word is all ones down to small magnitudes
    "negative": lambda rng, n: -rng.integers(
        1, 1 << rng.integers(1, 63), n, dtype=np.int64),
    # the ends of the range among small values: group sums wrap
    "extremes": lambda rng, n: rng.choice(
        np.array([I64.min, I64.max, -1, 0, 1, 1 << 32, -(1 << 32),
                  (1 << 31) - 1, 1 << 31], np.int64), n),
}


class TestLargeKernelParity:
    @pytest.mark.parametrize("n,G,sf,mf,seed", CASES)
    def test_int64_limb_sums_exact(self, n, G, sf, mf, seed):
        rng = np.random.default_rng(seed)
        gid = rng.integers(0, G, size=n).astype(np.int32)
        sel = rng.random(n) < sf
        mask = rng.random(n) < mf
        # negative values with populated high limbs: |v| up to 2^40
        vals = rng.integers(-(1 << 40), 1 << 40, size=n, dtype=np.int64)
        eff = sel & mask
        w = limb_width(n, max_group_rows=n, block_rows=256)
        layout = (("shadow", 0),) + limb_rows(0, 64, w) + (("count", 0),)
        mm = np.where(eff, vals, np.inf).astype(np.float32)
        mx = np.where(eff, vals, -np.inf).astype(np.float32)
        acc_f, acc_i = large_group_aggregate(
            gid, sel, (np.where(eff, vals, 0),), (eff,), (), (mm, mx),
            G, layout, mm_ops=(MIN, MAX), want_rep=True, group_tile=128,
            block_rows=256, interpret=True)
        acc_f, acc_i = np.asarray(acc_f), np.asarray(acc_i)
        sums, cnts, mins, maxs, reps = _oracle(gid, sel, vals, mask, G)
        k = len(layout) - 2
        np.testing.assert_array_equal(
            _recombine(acc_i, layout, 0), sums)  # bit-exact
        np.testing.assert_array_equal(acc_i[k], cnts)
        # MIN/MAX: identity fill survives for empty groups
        np.testing.assert_array_equal(acc_f[1], mins)
        np.testing.assert_array_equal(acc_f[2], maxs)
        # f32 shadow within block-accumulation tolerance
        tol = np.maximum(np.abs(sums).astype(np.float64) * 1e-2, 1e6)
        assert np.all(np.abs(acc_f[0].astype(np.float64) - sums) <= tol)
        # rep: min selected row id per group, n when none
        np.testing.assert_array_equal(acc_i[k + 1], reps)

    @pytest.mark.parametrize("w", [5, 6, 8])
    @pytest.mark.parametrize("kind", sorted(SPLIT_VALUES))
    def test_in_kernel_limb_split(self, kind, w):
        """The limbs the kernel cuts out of the argument's two words
        recombine to the numpy int64 group sums, and the f32 shadow
        row follows them."""
        n, G = 1024, 150
        rng = np.random.default_rng(w * 31 + len(kind))
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.85
        vals = SPLIT_VALUES[kind](rng, n)
        layout = (("shadow", 0),) + limb_rows(0, 64, w) + (("live",),)
        acc_f, acc_i = large_group_aggregate(
            gid, sel, (np.where(sel, vals, 0),), (), (), (), G, layout,
            group_tile=128, block_rows=256, interpret=True)
        want = _group_sum(gid, sel, vals, G)
        np.testing.assert_array_equal(
            _recombine(np.asarray(acc_i), layout, 0), want)
        exact = np.zeros(G)     # the unwrapped sum the shadow tracks
        np.add.at(exact, gid[sel], vals[sel].astype(np.float64))
        tol = np.maximum(np.abs(exact) * 1e-2, 1e12)
        assert np.all(np.abs(np.asarray(acc_f)[0] - exact) <= tol)

    @pytest.mark.parametrize("w", [5, 6, 8])
    def test_limbs_astride_the_word_boundary(self, w):
        """A 3-bit leading limb puts a limb of every width across bit
        32, where the kernel ORs the two words' shifts together."""
        n, G = 1024, 96
        rng = np.random.default_rng(w)
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.9
        vals = SPLIT_VALUES["both_words"](rng, n)
        limbs = (("limb", 0, 0, 3),) + tuple(
            ("limb", 0, s, w) for s in range(3, 64, w))
        assert any(s < 32 < s + wd for _, _, s, wd in limbs)
        layout = limbs + (("live",),)
        _, acc_i = large_group_aggregate(
            gid, sel, (np.where(sel, vals, 0),), (), (), (), G, layout,
            group_tile=128, block_rows=256, interpret=True)
        np.testing.assert_array_equal(
            _recombine(np.asarray(acc_i), layout, 0),
            _group_sum(gid, sel, vals, G))

    @pytest.mark.parametrize("w", [5, 6, 8])
    def test_one_word_argument(self, w):
        """A proven 31-bit argument travels as int32: one operand row,
        no high word, the same sums."""
        n, G = 1024, 96
        rng = np.random.default_rng(w)
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.9
        vals = rng.integers(0, 1 << 31, n, dtype=np.int64)
        vals[:2] = (1 << 31) - 1, 0
        layout = (("shadow", 0),) + limb_rows(0, 31, w) + (("live",),)
        before = pg.OPERAND_BYTES.value("large")
        acc_f, acc_i = large_group_aggregate(
            gid, sel, (np.where(sel, vals, 0).astype(np.int32),), (), (),
            (), G, layout, group_tile=128, block_rows=256,
            interpret=True)
        # gid, the packed masks, one word
        assert pg.OPERAND_BYTES.value("large") - before == 3 * 4 * n
        want = _group_sum(gid, sel, vals, G)
        np.testing.assert_array_equal(
            _recombine(np.asarray(acc_i), layout, 0), want)
        np.testing.assert_allclose(np.asarray(acc_f)[0], want, rtol=1e-5)

    @pytest.mark.parametrize("w", [5, 6, 8])
    def test_two_aggregates_share_one_argument(self, w):
        """sum(x) with a proven 13-bit bound and avg(x) without one
        read ONE source: the narrow sum's limbs are the wide one's
        first rows, and both recombine to the same answer."""
        n, G = 1024, 96
        rng = np.random.default_rng(w)
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.9
        vals = rng.integers(0, 1 << 13, n, dtype=np.int64)
        narrow, wide = limb_rows(0, 13, w), limb_rows(0, 64, w)
        assert set(narrow) <= set(wide)
        layout = (("shadow", 0),) + wide + (("count", 0), ("live",))
        _, acc_i = large_group_aggregate(
            gid, sel, (np.where(sel, vals, 0),), (sel,), (), (), G,
            layout, group_tile=128, block_rows=256, interpret=True)
        acc_i = np.asarray(acc_i)
        want = _group_sum(gid, sel, vals, G)
        np.testing.assert_array_equal(
            _recombine(acc_i, layout, 0, narrow), want)
        np.testing.assert_array_equal(
            _recombine(acc_i, layout, 0, wide), want)
        np.testing.assert_array_equal(acc_i[len(wide)],
                                      _group_count(gid, sel, G))

    @pytest.mark.parametrize("w", [5, 6, 8])
    def test_distinct_validities(self, w):
        """Two arguments with their own NULLs: each count row reads its
        own bit of the packed mask word, liveness reads `sel`."""
        n, G = 1024, 96
        rng = np.random.default_rng(w)
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.9
        va, vb = sel & (rng.random(n) < 0.7), sel & (rng.random(n) < 0.4)
        a = SPLIT_VALUES["both_words"](rng, n)
        b = rng.integers(0, 1 << 20, n, dtype=np.int64)
        layout = limb_rows(0, 64, w) + limb_rows(1, 20, w) \
            + (("count", 0), ("count", 1), ("live",))
        _, acc_i = large_group_aggregate(
            gid, sel, (np.where(va, a, 0),
                       np.where(vb, b, 0).astype(np.int32)),
            (va, vb), (), (), G, layout, group_tile=128, block_rows=256,
            interpret=True)
        acc_i = np.asarray(acc_i)
        np.testing.assert_array_equal(_recombine(acc_i, layout, 0),
                                      _group_sum(gid, va, a, G))
        np.testing.assert_array_equal(_recombine(acc_i, layout, 1),
                                      _group_sum(gid, vb, b, G))
        for r, m in ((-3, va), (-2, vb), (-1, sel)):
            np.testing.assert_array_equal(acc_i[r],
                                          _group_count(gid, m, G))

    def test_more_masks_than_one_word_holds(self):
        # 40 validities: mask 31 onward rides a second packed word
        n, G = 512, 40
        rng = np.random.default_rng(5)
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.9
        masks = tuple(sel & (rng.random(n) < 0.5) for _ in range(40))
        layout = tuple(("count", k) for k in range(40)) + (("live",),)
        _, acc_i = large_group_aggregate(
            gid, sel, (), masks, (), (), G, layout, group_tile=128,
            block_rows=256, interpret=True)
        for k, m in enumerate(masks + (sel,)):
            np.testing.assert_array_equal(np.asarray(acc_i)[k],
                                          _group_count(gid, m, G))

    def test_float_sum_column(self):
        # an f32 column (a capability no plan reaches: FLOAT arguments
        # are outside the engine's envelope) is copied into the operand
        # beside the rows the kernel derives
        n, G = 1024, 96
        rng = np.random.default_rng(6)
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.9
        x = rng.integers(-1000, 1000, n).astype(np.float32)
        acc_f, acc_i = large_group_aggregate(
            gid, sel, (), (), (np.where(sel, x, 0),), (), G,
            (("f", 0), ("live",)), group_tile=128, block_rows=256,
            interpret=True)
        want = np.zeros(G)
        np.add.at(want, gid[sel], x[sel])
        np.testing.assert_array_equal(np.asarray(acc_f)[0], want)
        np.testing.assert_array_equal(np.asarray(acc_i)[0],
                                      _group_count(gid, sel, G))

    def test_all_rows_masked(self):
        # empty state: every accumulator keeps its identity
        n, G = 1024, 200
        rng = np.random.default_rng(9)
        gid = rng.integers(0, G, size=n).astype(np.int32)
        sel = np.zeros(n, bool)
        zero = np.zeros(n, np.int64)
        inf = np.full(n, np.inf, np.float32)
        acc_f, acc_i = large_group_aggregate(
            gid, sel, (zero,), (), (), (inf, -inf), G,
            (("shadow", 0), ("live",)), mm_ops=(MIN, MAX), want_rep=True,
            group_tile=128, block_rows=256, interpret=True)
        acc_f, acc_i = np.asarray(acc_f), np.asarray(acc_i)
        assert np.all(acc_f[0] == 0.0)
        assert np.all(acc_f[1] == np.inf) and np.all(acc_f[2] == -np.inf)
        assert np.all(acc_i[0] == 0) and np.all(acc_i[1] == n)

    def test_counts_for_giant_group(self):
        # one group takes every row: the i32 count path at its densest
        n = 4096
        gid = np.zeros(n, np.int32)
        sel = np.ones(n, bool)
        _, acc_i = large_group_aggregate(
            gid, sel, (), (), (), (), 1, (("live",),), group_tile=128,
            block_rows=512, interpret=True)
        assert int(np.asarray(acc_i)[0, 0]) == n

    def test_default_tile_constants_sane(self):
        assert GROUP_TILE % 128 == 0 and BLOCK_ROWS % 128 == 0


# ---------------------------------------------------------------- the
# contraction's geometry comes from the plan

def _xla_limb_sums(vals, gid, G, n):
    import jax.numpy as jnp

    from cockroach_tpu.ops import agg
    return np.asarray(agg._group_sum_i64_limbs(
        jnp.asarray(vals), jnp.asarray(gid), G, n))


def _dots(jaxpr, inside_kernel=False):
    """Every dot_general of the kernel's body, as (operand dtypes,
    precision), found by walking the jaxpr down through the
    pallas_call (as TestNoScatterHLO reads HLO for scatters)."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and inside_kernel:
            out.append((tuple(str(v.aval.dtype) for v in eqn.invars),
                        eqn.params["precision"]))
        inner = inside_kernel or eqn.primitive.name == "pallas_call"
        for sub in eqn.params.values():
            sub = getattr(sub, "jaxpr", sub)
            if hasattr(sub, "eqns"):
                out.extend(_dots(sub, inner))
    return out


class TestPlanSizedContraction:
    @pytest.mark.parametrize("G,tile", [
        (1, 128), (12, 128), (128, 128), (129, 256), (600, GROUP_TILE)])
    def test_group_tile_from_num_groups(self, G, tile):
        """The tile is the group count rounded up to whole vregs, at
        most the parameter; whatever it is, the sums are the XLA limb
        path's, bit for bit. (n = 1,152 rows: a shape of this test's
        own, so the build is traced here and the tallies move.)"""
        assert effective_group_tile(G) == tile
        assert effective_group_tile(G, 256) == min(tile, 256)
        n = 1152
        rng = np.random.default_rng(G)
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.9
        vals = SPLIT_VALUES["both_words"](rng, n)
        w = limb_width(n, n)
        layout = (("shadow", 0),) + limb_rows(0, 64, w) \
            + (("count", 0), ("live",))
        before = {k: t.value("large") for k, t in (
            ("builds", pg.BUILDS), ("tile", pg.GROUP_TILE_LANES),
            ("passes", pg.MXU_PASSES))}
        _, acc_i = large_group_aggregate(
            gid, sel, (np.where(sel, vals, 0),), (sel,), (), (), G,
            layout, interpret=True)
        assert pg.BUILDS.value("large") - before["builds"] == 1
        assert pg.GROUP_TILE_LANES.value("large") - before["tile"] == tile
        assert pg.MXU_PASSES.value("large") - before["passes"] == 1
        acc_i = np.asarray(acc_i)
        assert acc_i.shape == (len(layout) - 1, G)
        np.testing.assert_array_equal(
            _recombine(acc_i, layout, 0),
            _xla_limb_sums(np.where(sel, vals, 0), gid, G, n))
        np.testing.assert_array_equal(acc_i[-1], _group_count(gid, sel, G))

    @pytest.mark.parametrize("blk", [BLOCK_ROWS, 1 << 16])
    @pytest.mark.parametrize("w", [6, 8])
    def test_one_pass_at_the_worst_case(self, w, blk):
        """Every row of every block in one group and every limb at its
        largest (255 or 63: the argument is -1, all ones): the MXU's
        f32 partial of a block is blk x 255, under 2^24 up to the
        largest block limb_width allows an 8-bit limb, 2^16."""
        n, G = 1 << 16, 12
        assert w <= limb_width(n, n, block_rows=blk) == 8
        assert blk * ((1 << w) - 1) < 1 << 24
        gid = np.full(n, 7, np.int32)
        sel = np.ones(n, bool)
        vals = np.full(n, -1, np.int64)
        layout = limb_rows(0, 64, w) + (("count", 0), ("live",))
        _, acc_i = large_group_aggregate(
            gid, sel, (vals,), (sel,), (), (), G, layout,
            block_rows=blk, interpret=True)
        acc_i = np.asarray(acc_i)
        for r, (_, _, shift, width) in enumerate(layout[:-2]):
            limb = (1 << min(width, 64 - shift)) - 1
            assert acc_i[r, 7] == n * limb and limb in (255, 63, 15)
        assert acc_i[-1, 7] == acc_i[-2, 7] == n
        assert not acc_i[:, np.arange(G) != 7].any()
        np.testing.assert_array_equal(_recombine(acc_i, layout, 0),
                                      _xla_limb_sums(vals, gid, G, n))

    def test_a_limb_past_eight_bits_is_refused(self):
        n = 1024
        with pytest.raises(AssertionError):
            large_group_aggregate(
                np.zeros(n, np.int32), np.ones(n, bool),
                (np.zeros(n, np.int64),), (), (), (), 12,
                limb_rows(0, 64, 9) + (("live",),), interpret=True)

    def test_float_sums_keep_f32_precision(self):
        """Two float sums with a shadow between them and limbs after:
        the f rows are summed at f32 precision (a bf16 pass would keep
        8 of these values' 15 bits), each comes back at its layout
        position, and the shadow follows its source."""
        n, G = 2048, 12
        rng = np.random.default_rng(11)
        gid = rng.integers(0, G, n).astype(np.int32)
        sel = rng.random(n) < 0.9
        x = (rng.integers(0, 4096, n) + 0.125).astype(np.float32)
        y = -(rng.integers(0, 4096, n) + 0.375).astype(np.float32)
        vals = SPLIT_VALUES["negative"](rng, n)
        layout = (("f", 0), ("shadow", 0), ("f", 1)) \
            + limb_rows(0, 64, 8) + (("live",),)
        acc_f, acc_i = large_group_aggregate(
            gid, sel, (np.where(sel, vals, 0),), (),
            (np.where(sel, x, 0), np.where(sel, y, 0)), (), G, layout,
            block_rows=256, interpret=True)
        acc_f = np.asarray(acc_f)
        for r, col in ((0, x), (2, y)):
            want = np.zeros(G)
            np.add.at(want, gid[sel], col[sel].astype(np.float64))
            assert np.abs(want).max() < 1 << 21   # exact in f32
            np.testing.assert_array_equal(acc_f[r], want)
        exact = np.zeros(G)
        np.add.at(exact, gid[sel], vals[sel].astype(np.float64))
        np.testing.assert_allclose(acc_f[1], exact, rtol=1e-5)
        np.testing.assert_array_equal(
            _recombine(np.asarray(acc_i), layout, 0),
            _group_sum(gid, sel, vals, G))

    def test_the_exact_dot_is_one_bf16_pass(self):
        """Read the kernel's jaxpr: the exact rows (limbs, counts,
        liveness, the shadows' pieces) are contracted with bf16
        operands at default precision into f32, the float-sum rows in
        a dot of their own at HIGHEST; a layout without float sums
        has no f32 dot at all."""
        import jax
        n, G = 1024, 12
        gid, sel = np.zeros(n, np.int32), np.ones(n, bool)
        src, x = np.zeros(n, np.int64), np.zeros(n, np.float32)
        exact = (("shadow", 0),) + limb_rows(0, 64, 8) \
            + (("count", 0), ("live",))

        def dots(layout, f_values):
            return _dots(jax.make_jaxpr(
                lambda *a: large_group_aggregate(
                    *a, (), G, layout, interpret=True))(
                gid, sel, (src,), (sel,), f_values).jaxpr)

        (dtypes, precision), = dots(exact, ())
        assert dtypes == ("bfloat16", "bfloat16")
        assert precision in (None, jax.lax.Precision.DEFAULT,
                             (jax.lax.Precision.DEFAULT,) * 2)
        both = sorted(dots((("f", 0),) + exact, (x,)))
        assert [d for d, _ in both] == [("bfloat16", "bfloat16"),
                                        ("float32", "float32")]
        assert both[1][1] in (jax.lax.Precision.HIGHEST,
                              (jax.lax.Precision.HIGHEST,) * 2)


# ---------------------------------------------------------------- engine
# eligibility + parity

SF = 0.005
N_ROWS = 8192


@pytest.fixture(scope="module")
def teng():
    from cockroach_tpu.exec.engine import Engine
    from cockroach_tpu.models import tpch
    e = Engine()
    tpch.load(e, SF, rows=N_ROWS,
              tables=("lineitem", "orders", "customer"))
    return e


def _local_session(eng):
    s = eng.session()
    s.vars.set("distsql", "off")
    return s


PARITY_SQL = ("SELECT l_orderkey, count(*) AS c, sum(l_quantity) AS q "
              "FROM lineitem GROUP BY l_orderkey")


class TestEngineEligibility:
    def test_q18_selects_large_kernel(self, teng):
        from cockroach_tpu.models import tpch
        s = _local_session(teng)
        before = pg.BUILDS.value("large")
        res = teng.execute(tpch.Q18_TEMPLATE.format(threshold=50),
                           session=s)
        assert pg.BUILDS.value("large") > before, \
            "q18's inner GROUP BY l_orderkey did not ride the kernel"
        # sanity vs the host-side reference implementation
        want = tpch.ref_q18(tpch.gen_lineitem(SF, rows=N_ROWS),
                            tpch.gen_orders(SF), tpch.gen_customer(SF),
                            threshold=50)
        assert len(res.rows) == len(want)

    def test_sparse_composite_stays_on_xla(self, teng):
        # packed composite keys (two wide-span INTs) force the hash
        # strategy: outside every kernel envelope -> fallback tally
        s = _local_session(teng)
        teng.execute("CREATE TABLE spk (a INT, b INT, v FLOAT)")
        rng = np.random.default_rng(11)
        rows = ", ".join(
            f"({int(a)}, {int(b)}, {float(v):.4f})"
            for a, b, v in zip(rng.integers(0, 10 ** 9, 300),
                               rng.integers(0, 10 ** 9, 300),
                               rng.random(300)))
        teng.execute(f"INSERT INTO spk VALUES {rows}")
        b_large = pg.BUILDS.value("large")
        fb = pg.FALLBACKS.value()
        teng.execute("SELECT a, b, count(*) FROM spk GROUP BY a, b",
                     session=s)
        assert pg.BUILDS.value("large") == b_large, \
            "sparse composite key must not route to the kernel"
        assert pg.FALLBACKS.value() > fb, \
            "XLA-path aggregation under auto must tally a fallback"

    def test_auto_matches_off_exactly(self, teng):
        s = _local_session(teng)
        s.vars.set("pallas_groupagg", "off")
        want = sorted(teng.execute(PARITY_SQL, session=s).rows)
        s.vars.set("pallas_groupagg", "auto")
        got = sorted(teng.execute(PARITY_SQL, session=s).rows)
        # counts and DECIMAL sums are exact in both arms -> identical
        assert got == want

    def test_auto_interpret_step_budget(self):
        # the cost guard that keeps CPU (interpret-mode) runs off
        # giant grids: a 300K-row/100K-group shape must NOT route
        # under auto off-TPU (it costs minutes interpreted), while
        # the tier-1 q3/q18 shapes and any on-chip shape pass
        from cockroach_tpu.exec import compile as C
        assert C._large_interpret_over_budget(True, 1 << 19, 100_000)
        assert not C._large_interpret_over_budget(True, 8192, 15_000)
        assert not C._large_interpret_over_budget(True, 4096, 15_000)
        assert not C._large_interpret_over_budget(False, 1 << 19,
                                                  100_000)

    def test_metrics_exported(self, teng):
        snap = teng.metrics.snapshot()
        for want in ("exec.pallas.kernel.builds",
                     "exec.pallas.kernel.builds.large",
                     "exec.pallas.kernel.fallbacks",
                     "exec.pallas.rows"):
            assert want in snap


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_kernel_tile_parity_fuzzed(seed):
    """Any valid (group_tile, block_rows) point gives bit-identical
    exact aggregates: limb sums recombine to the same int64s, counts
    match the numpy oracle. (The engine runs the module's constants;
    the points are a function's arguments, not a knob.)"""
    n, G, bits = 2048, 300, 40
    rng = np.random.default_rng(seed)
    gid = rng.integers(0, G, n).astype(np.int32)
    sel = rng.random(n) < 0.8
    vals = rng.integers(0, 1 << bits, n).astype(np.int64)
    mm = (np.where(sel, vals.astype(np.float32), np.float32(np.inf)),)
    for gt, br in ((512, 1024), (256, 512), (1024, 2048), (128, 256)):
        w = limb_width(n, n, block_rows=br)
        layout = limb_rows(0, bits, w) + (("live",),)
        _, acc_i = large_group_aggregate(
            gid, sel, (np.where(sel, vals, 0),), (), (), mm,
            num_groups=G, layout=layout, mm_ops=(MIN,), group_tile=gt,
            block_rows=br, interpret=True)
        acc_i = np.asarray(acc_i)
        np.testing.assert_array_equal(_recombine(acc_i, layout, 0),
                                      _group_sum(gid, sel, vals, G))
        np.testing.assert_array_equal(acc_i[-1], _group_count(gid, sel, G))


class TestIntMinMaxAndPlacement:
    """Integer MIN/MAX rides the kernel under `auto`, bit for bit; and
    the placement model (exec/scanplane.py) calls the predicate the
    compile calls, with the same inputs."""

    def test_int_minmax_rides_kernel_under_auto_bit_exact(self, teng):
        # adjacent giant int64 values: a plain f32 kernel MIN/MAX
        # would collapse them (2^40 + k all round to the same float),
        # so bit-parity here proves the hi-limb + dtype-preserving
        # refinement actually ran end to end
        teng.execute("CREATE TABLE mmx (g INT8 NOT NULL, v INT8)")
        rng = np.random.default_rng(77)
        n = 8192
        gk = rng.integers(0, 64, n).astype(np.int64)
        v = (np.int64(1) << 40) + rng.integers(
            -1000, 1000, n).astype(np.int64)
        v[rng.random(n) < 0.5] *= -1
        teng.store.insert_columns("mmx", {"g": gk, "v": v},
                                  teng.clock.now())
        sql = ("SELECT g, min(v) AS mn, max(v) AS mx FROM mmx "
               "GROUP BY g ORDER BY g")
        s = _local_session(teng)
        s.vars.set("pallas_groupagg", "off")
        want = teng.execute(sql, session=s).rows
        before = pg.BUILDS.value("large")
        s.vars.set("pallas_groupagg", "auto")
        got = teng.execute(sql, session=s).rows
        assert pg.BUILDS.value("large") > before, \
            "promoted int MIN/MAX did not route to the large kernel"
        assert got == want
        # spot-check one group against numpy to catch a both-arms bug
        g0 = int(got[0][0])
        m = gk == g0
        assert got[0][1:] == (int(v[m].min()), int(v[m].max()))

    @pytest.mark.parametrize("shape,takes", [
        ("q1", True), ("int_minmax", True), ("float_sum", False),
        ("hash", False)])
    def test_placement_agrees_with_compile(self, teng, shape, takes):
        """scanplane's model prices the kernel's words exactly when
        the program compile_plan builds bumps `builds.large`."""
        from cockroach_tpu.exec import compile as C
        from cockroach_tpu.exec.stmtutil import (_count_aggs,
                                                 _root_aggregate)
        from cockroach_tpu.models import tpch
        n = N_ROWS
        rng = np.random.default_rng(5)
        teng.execute(f"CREATE TABLE pl_{shape} (g INT8 NOT NULL, "
                     "h INT8 NOT NULL, v INT8, f FLOAT)")
        wide = shape == "hash"      # two wide-span keys: hash strategy
        teng.store.insert_columns(f"pl_{shape}", {
            "g": rng.integers(0, 10 ** 9 if wide else 48, n),
            "h": rng.integers(0, 10 ** 9 if wide else 2, n),
            "v": rng.integers(-(1 << 50), 1 << 50, n),
            "f": rng.random(n)}, teng.clock.now())
        sql = {
            "q1": tpch.Q1.replace("count(*) AS count_order",
                                  "count(*) AS count_order, count(*) AS c4"),
            "int_minmax": f"SELECT g, min(v), max(v), sum(v) "
                          f"FROM pl_{shape} GROUP BY g",
            "float_sum": f"SELECT g, sum(f), count(*) FROM pl_{shape} "
                         f"GROUP BY g",
            "hash": f"SELECT g, h, sum(v) FROM pl_{shape} GROUP BY g, h",
        }[shape]
        s = _local_session(teng)
        node, _ = teng._plan(teng._parse_cached(sql), s)
        agg = _root_aggregate(node)
        scatter = 16 * _count_aggs(node) * n
        modelled = teng._agg_temp_bytes(node, s, n)
        if takes:
            assert modelled == C.large_kernel_bytes(agg, n) < scatter
        else:
            assert modelled == scatter
        # builds are counted where the kernel is traced: forget what
        # another test of this process traced at this shape
        large_group_aggregate.clear_cache()
        before = pg.BUILDS.value("large")
        teng.execute(sql, session=s)
        assert (pg.BUILDS.value("large") > before) is takes
        s.vars.set("pallas_groupagg", "off")
        assert teng._agg_temp_bytes(node, s, n) == scatter


class TestNoScatterHLO:
    """The acceptance bar: under auto the compiled program for an
    eligible GROUP BY contains no input-width aggregation scatters;
    the off arm (XLA segment path) does."""

    def _lowered_text(self, eng, mode):
        s = _local_session(eng)
        s.vars.set("pallas_groupagg", mode)
        p = eng.prepare(PARITY_SQL, session=s)
        tsv = read_ts_words(eng._read_ts(s).to_int())
        return p.jfn.lower(p.scans, tsv, np.int32(1),
                           np.int32(0)).as_text()

    def test_off_arm_scatters_auto_arm_does_not(self, teng):
        off = self._lowered_text(teng, "off")
        auto = self._lowered_text(teng, "auto")
        assert "scatter" in off, \
            "oracle arm: the XLA segment path should lower scatters"
        assert "scatter" not in auto, \
            "auto arm still lowers aggregation scatters"


# ---------------------------------------------------------------- Q1: the
# aggregate reads its arguments once

def _q1_rows(eng, session, mode, sql=None):
    from cockroach_tpu.models import tpch
    session.vars.set("pallas_groupagg", mode)
    return eng.execute(sql or tpch.Q1, session=session).rows


Q1_AVGS = (6, 7, 8)     # avg_qty, avg_price, avg_disc: float8 quotients


def _assert_q1_equal(got, want):
    """Every exact column (group keys, DECIMAL sums, count) bit for
    bit; the avgs are float divisions of the same exact sums and
    counts, which the two arms order differently (a last-digit
    difference on the seed tree too)."""
    assert len(got) == len(want) >= 3
    for g, w in zip(got, want):
        for c, (x, y) in enumerate(zip(g, w)):
            if c in Q1_AVGS:
                assert x == pytest.approx(y, rel=1e-14)
            else:
                assert x == y, (c, x, y)


class TestQ1ReadsArgumentsOnce:
    """TPC-H Q1 (seven exact DECIMAL sums/avgs over five distinct
    arguments, count(*)) hands the kernel its arguments, not the limb
    matrix: what the `.scan` cell runs."""

    def test_auto_matches_off_bit_for_bit(self, teng):
        s = _local_session(teng)
        want = _q1_rows(teng, s, "off")
        tallies = (pg.BUILDS, pg.PROVED_SUMS, pg.OPERAND_WORDS,
                   pg.MATMUL_ROWS)
        before = [t.value("large") for t in tallies]
        got = _q1_rows(teng, s, "auto")
        builds, proved, words, rows = (
            t.value("large") - b for t, b in zip(tallies, before))
        assert builds == 1, "Q1 missed the kernel"
        # with the plan's value-range proofs on: all four sums and
        # three avgs proven, eight operand words, and at this table's
        # limb width of 8 fifteen limb rows, five counts, liveness and
        # no shadow
        assert (proved, words, rows) == (7, 8, 21)
        _assert_q1_equal(got, want)

    def test_operand_bytes_exported_and_small(self, teng, monkeypatch):
        from cockroach_tpu.models import tpch
        from cockroach_tpu.ops.pallas import groupagg_large as pgl
        assert "exec.pallas.kernel.operand_bytes" in teng.metrics.snapshot()
        seen = []
        orig = pgl.large_group_aggregate

        def spy(gid, sel, sources, *a, **kw):
            seen.append((gid.shape[0], len(kw["layout"]), len(sources)))
            return orig(gid, sel, sources, *a, **kw)

        monkeypatch.setattr(pgl, "large_group_aggregate", spy)
        # the limb width of TPC-H SF10 (this table's own is 8, which
        # an earlier test's build holds in the jit cache): any width
        # under the exactness bound gives the same answer
        monkeypatch.setattr(pgl, "limb_width", lambda *a, **kw: 6)
        s = _local_session(teng)
        want = _q1_rows(teng, s, "off")
        name = "exec.pallas.kernel.operand_bytes"
        before = teng.metrics.snapshot()[name]
        # one more item than tpch.Q1, so no cached plan answers
        sql = tpch.Q1.replace("count(*) AS count_order",
                              "count(*) AS count_order, count(*) AS c2")
        got = _q1_rows(teng, s, "auto", sql)
        _assert_q1_equal([r[:-1] for r in got], want)
        (n, n_mat, n_src), = seen
        # l_quantity and l_extendedprice serve a sum and an avg each
        assert n_src == 5
        handed = teng.metrics.snapshot()[name] - before
        # gid, one packed mask word, one word an argument the plan
        # proved under 2^31 (four of them), two for `charge` (37 bits)
        assert handed == (2 + 4 + 2) * 4 * n
        assert handed < 4 * n_mat * n / 2

    def test_overflow_sentinel_still_raises(self, teng):
        from cockroach_tpu.exec.engine import EngineError
        teng.execute("CREATE TABLE ovf (g INT8 NOT NULL, v INT8)")
        n = 8192
        g = (np.arange(n) % 3).astype(np.int64)
        v = np.full(n, np.iinfo(np.int64).max // 1000, np.int64)
        teng.store.insert_columns("ovf", {"g": g, "v": v},
                                  teng.clock.now())
        s = _local_session(teng)
        sql = "SELECT g, sum(v) FROM ovf GROUP BY g"
        for mode in ("off", "auto"):
            s.vars.set("pallas_groupagg", mode)
            with pytest.raises(EngineError, match="overflowed int64"):
                teng.execute(sql, session=s)


class TestOperandHLO:
    """Beside TestNoScatterHLO: Q1's program as the TPU would get it
    (lowered for that platform, Mosaic kernel and all) holds no
    row-major operand matrix, and the custom call reads eight
    [1, n] rows (a dozen before the plan proved four of the five
    arguments into one word each)."""

    def test_q1_has_no_operand_matrix(self, teng, monkeypatch):
        import re

        from cockroach_tpu.exec.engine import Engine
        from cockroach_tpu.models import tpch
        s = _local_session(teng)
        s.vars.set("pallas_groupagg", "auto")
        monkeypatch.setattr(Engine, "_pallas_interpret",
                            staticmethod(lambda: False))
        sql = tpch.Q1.replace("count(*) AS count_order",
                              "count(*) AS count_order, count(*) AS c3")
        p = teng.prepare(sql, session=s)
        tsv = read_ts_words(teng._read_ts(s).to_int())
        text = p.jfn.trace(p.scans, tsv, np.int32(1), np.int32(0),
                           p.params) \
            .lower(lowering_platforms=("tpu",)).as_text()
        n = N_ROWS
        wide = [int(k) for k in re.findall(rf"tensor<(\d+)x{n}xf32>", text)]
        assert not [k for k in wide if k > 1], \
            "an f32[k, n] array: the limb matrix is back in HBM"
        calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
        assert len(calls) == 1
        operands = re.findall(rf"tensor<1x{n}x[a-z0-9]+>",
                              calls[0].split("->")[0])
        # the group ids, five validities packed in one word, four
        # one-word arguments and `charge` in two
        assert len(operands) == 1 + 1 + 4 + 2
        assert sum("xi32>" in o for o in operands) == 8
        assert not re.search(rf"tensor<\d+x{n}x", calls[0].replace(
            f"tensor<1x{n}x", ""))


class TestMeshParity:
    """The path `tpch_sf1_mesh4.mixed` runs: Q1 under shard_map, the
    kernel on every shard's rows, i32 limb rows psummed."""

    def test_q1_sharded_equals_one_device(self):
        from cockroach_tpu.exec.engine import Engine
        from cockroach_tpu.models import tpch
        from cockroach_tpu.parallel.mesh import make_mesh
        eng = Engine(mesh=make_mesh(n=4))
        # 4 shards of 8,192 rows: over auto's row floor on each
        tpch.load(eng, SF, rows=4 * N_ROWS, tables=("lineitem",))
        local = _local_session(eng)
        want = _q1_rows(eng, local, "off")
        one = _q1_rows(eng, local, "auto")
        _assert_q1_equal(one, want)
        dist = eng.session()
        before = pg.BUILDS.value("large"), pg.PROVED_SUMS.value("large")
        got = _q1_rows(eng, dist, "auto")
        assert pg.BUILDS.value("large") > before[0], \
            "the sharded Q1 missed the kernel"
        # the shards' kernels are sized by the same proofs
        assert pg.PROVED_SUMS.value("large") - before[1] == 7
        # sharded against one device, both through the kernel: the
        # same limb sums and counts, so the avgs agree to the bit too
        assert got == one
