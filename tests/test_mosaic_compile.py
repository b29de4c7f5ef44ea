"""The large-G kernel, a Compact's two kernels (ops/pallas/compact.py,
through compile.compact_batch) and the bounded form of a composite-key
join (ops/join.py) compiled for the v5e here, without a chip: the
TPU's own compiler (XLA:TPU and Mosaic) is installed and compiles for
a topology that is described, not attached. Interpret mode cannot see
what these see: a contraction Mosaic refuses, a block that passes the
16 MB of scoped VMEM. Shapes are the benchmark's (TPC-H Q1's layout at
SF1's 2^23 and SF10's 2^26 rows) and a q18-class one (4,096 groups).
Nothing runs, so nothing here is a time or an answer.

The topology is described inside a fixture, never at import: only the
worker that is given this file loads the TPU's library.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from cockroach_tpu.ops.pallas import groupagg_large as pgl


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _q1_layout(w):
    """TPC-H Q1's kernel layout: five 64-bit arguments, a shadow and
    a validity each, liveness (66 rows at SF10's width 6)."""
    layout = tuple(("shadow", s) for s in range(5))
    for s in range(5):
        layout += pgl.limb_rows(s, 64, w)
    return layout + tuple(("count", k) for k in range(5)) + (("live",),)


Q1_BITS = (13, 24, 4, 30, 37)


def _q1_proven_layout(w, shadows=()):
    """Q1's layout as the plan's value-range proofs size it (PR 32):
    arguments of 13, 24, 4, 30 and 37 bits, a shadow only for the
    sources named (20 limb rows and none at SF10's width 6)."""
    layout = tuple(("shadow", s) for s in shadows)
    for s, bits in enumerate(Q1_BITS):
        layout += pgl.limb_rows(s, bits, w)
    return layout + tuple(("count", k) for k in range(5)) + (("live",),)


def _compile(one_chip, n, num_groups, layout, n_src, n_f=0, mm_ops=(),
             want_rep=False, src_dtypes=None, **kw):
    def spec(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    def fn(gid, sel, sources, masks, f_values, mm_values):
        # the engine traces under x64: index maps must stay i32
        return pgl.large_group_aggregate(
            gid, sel, sources, masks, f_values, mm_values,
            num_groups=num_groups, layout=layout, mm_ops=mm_ops,
            want_rep=want_rep, **kw)

    n_masks = 1 + max((r[1] for r in layout if r[0] == "count"),
                      default=-1)
    with jax.enable_x64(True):
        compiled = jax.jit(fn).trace(
            spec(jnp.int32), spec(jnp.bool_),
            tuple(spec(dt) for dt in
                  (src_dtypes or (jnp.int64,) * n_src)),
            tuple(spec(jnp.bool_) for _ in range(n_masks)),
            tuple(spec(jnp.float32) for _ in range(n_f)),
            tuple(spec(jnp.float32) for _ in mm_ops)).lower().compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("n,w", [(1 << 23, 8), (1 << 26, 6)])
def test_q1_layout_compiles_at_the_benchmarks_sizes(one_chip, n, w):
    """The bf16 transposed contraction over a 128-lane tile, at the
    default 4,096 rows a step: Q1 at SF1 (61 matmul rows) and at SF10
    (76), thirteen [1, n] operands."""
    _compile(one_chip, n, 12, _q1_layout(w), n_src=5)


@pytest.mark.parametrize("n,w,shadows", [
    (1 << 23, 8, ()), (1 << 26, 6, ()), (1 << 26, 5, (4,))])
def test_q1_proven_layout_compiles_at_the_benchmarks_sizes(one_chip, n, w,
                                                           shadows):
    """What the served Q1 hands the kernel since the plan proves its
    arguments' ranges: four one-word sources and one of two words,
    eight [1, n] operands, 21 matmul rows at SF1, 26 at SF10, and at
    width 5 with `charge`'s shadow kept (no group bound) 32; with no
    shadow at all the f accumulator is one unused row."""
    compiled = _compile(
        one_chip, n, 12, _q1_proven_layout(w, shadows), n_src=5,
        src_dtypes=tuple(jnp.int32 if b < 32 else jnp.int64
                         for b in Q1_BITS))
    call, = [ln for ln in compiled.as_text().splitlines()
             if "tpu_custom_call" in ln]
    assert call.count(f"s32[1,{n}]") == 8


def test_q18_class_shape_compiles_at_the_tile_parameter(one_chip):
    """4,096 groups: the tile is the parameter's 512 lanes, with the
    MIN and REPMIN slots such a plan has."""
    layout = (("shadow", 0),) + pgl.limb_rows(0, 64, 8) \
        + (("count", 0), ("live",))
    _compile(one_chip, 1 << 22, 4096, layout, n_src=1,
             mm_ops=(pgl.MIN,), want_rep=True)


def test_float_sums_beside_the_bf16_pass_compile(one_chip):
    """Two float-sum rows keep their own HIGHEST contraction beside
    the exact rows' pass."""
    layout = (("f", 0), ("shadow", 0), ("f", 1)) \
        + pgl.limb_rows(0, 64, 8) + (("count", 0), ("live",))
    _compile(one_chip, 1 << 20, 300, layout, n_src=1, n_f=2)


# -- a Compact's displacement network ----------------------------------------

@pytest.mark.parametrize("n,frac", [(1 << 23, 0.16), (1 << 23, 0.0508),
                                    (1343488, 0.04),
                                    (1 << 23, 0.3805), (3211264, 0.3211),
                                    (1 << 23, 0.5)])
def test_compact_batch_compiles_at_the_benchmarks_shapes(one_chip, n, frac):
    """The body the cells run, through Mosaic: SSB's first Compact at
    SF1 (2^23 rows, 5,248 of a block's 32,768 kept: 41 tile rows, not
    a multiple of 8), Q14's (1,664), and a second Compact over the
    first one's 1,343,488 rows; since PR 39 the larger capacities:
    Q4.1's first Compact (12,544 of a block) and its second over the
    3,211,264 rows that leaves (10,624), and a capacity of a half
    (TPC-H Q21's, 16,384); a 64-bit column as two words, a bool,
    a float, a float64 (gathered by packed row numbers: XLA:TPU cannot
    split one), a nullable column, and an inner Compact's flag."""
    from cockroach_tpu.exec.compile import compact_batch, plan_rows
    from cockroach_tpu.ops.batch import ColumnBatch
    from cockroach_tpu.sql import plan as P

    def spec(dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    def fn(sel, key, price, flag, ratio, wide, nulls, inner):
        b = ColumnBatch.from_dict(
            {"key": key.astype(jnp.int64), "price": price, "flag": flag,
             "ratio": ratio, "wide": wide, "__compact_overflow": inner},
            {"price": nulls}, sel=sel)
        return compact_batch(b, frac)

    with jax.enable_x64(True):
        compiled = jax.jit(fn).trace(
            spec(jnp.bool_), spec(jnp.int32), spec(jnp.int64),
            spec(jnp.bool_), spec(jnp.float32), spec(jnp.float64),
            spec(jnp.bool_), spec(jnp.bool_)).lower().compile()
    text = compiled.as_text()
    # route, five columns (each with its validity mask), the float64's
    # row numbers
    assert text.count('custom_call_target="tpu_custom_call"') == 7
    out = plan_rows(P.Compact(P.Scan("t", "t"), frac=frac), {"t": n})
    assert out < n and f"[{out}]" in text


# -- a composite-key join's bounded form --------------------------------------

def test_bounded_composite_join_compiles_at_the_benchmarks_shape(one_chip):
    """TPC-DS Q80's store_returns build (2^19 rows, a direct table over
    240,034 tickets widened to 2^18, k = 16) probed at store_sales' full
    2^22 rows: both
    branches of the build's lax.cond (two scatters, or k rounds of one)
    and the probe's gather of k candidates a row, through XLA:TPU."""
    from cockroach_tpu.ops.batch import ColumnBatch
    from cockroach_tpu.ops.join import hash_join, join_strategy

    n_build, n_probe, size = 1 << 19, 1 << 22, (1 << 18) + 1
    direct = ("bounded", 1, 0, size, 16, (0, 0), (20_480, 1 << 18))
    assert join_strategy(direct) == "bounded"

    def spec(n, dtype):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    def fn(psel, pitem, pticket, bsel, bitem, bticket, amt):
        probe = ColumnBatch.from_dict({"p.i": pitem, "p.t": pticket}, {},
                                      sel=psel)
        build = ColumnBatch.from_dict(
            {"b.i": bitem, "b.t": bticket, "b.amt": amt}, {}, sel=bsel)
        return hash_join(probe, build, ["p.i", "p.t"], ["b.i", "b.t"],
                         ["b.amt"], "left", direct=direct)

    with jax.enable_x64(True):
        compiled = jax.jit(fn).trace(
            spec(n_probe, jnp.bool_), spec(n_probe, jnp.int64),
            spec(n_probe, jnp.int64), spec(n_build, jnp.bool_),
            spec(n_build, jnp.int64), spec(n_build, jnp.int64),
            spec(n_build, jnp.int64)).lower().compile()
    assert "conditional" in compiled.as_text()
