"""Compile bound expressions to device computations.

The analogue of the reference's projection/selection operator planning
(pkg/sql/colexec/colbuilder/execplan.go planning render expressions +
the generated colexecproj/colexecsel kernels) — except one recursive
compiler covers all types, and XLA fuses the resulting elementwise
graph into the surrounding scan/aggregate (no per-operator batch
materialization at all).

``compile_expr(e)`` returns ``fn(ctx) -> (data, valid)`` where ctx maps
batch column names to (data, valid) pairs and carries aggregate results
for post-aggregation projections.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import kernels as K
from ..sql.bound import (BAggRef, BBetween, BBin, BCase, BCast, BCoalesce,
                         BCol, BConst, BDictGather, BDictLookup, BDictRemap,
                         BTableParam,
                         BExpr, BExtract, BFunc, BInList, BIsNull, BParam,
                         BUnary, BWinRef)
from ..sql.types import Family, SQLType


class ExprContext:
    """Evaluation context: column name -> (data, valid); agg results;
    runtime statement parameters (exec/planparam.py BParam values)."""

    def __init__(self, cols: dict, n: int, aggs: list | None = None,
                 params: tuple = ()):
        self.cols = cols
        self.n = n
        self.aggs = aggs or []
        self.params = params

    def col(self, name: str):
        return self.cols[name]


CompiledExpr = Callable[[ExprContext], tuple]


def _np_dtype(t: SQLType):
    return t.np_dtype


def compile_expr(e: BExpr) -> CompiledExpr:
    if isinstance(e, BConst):
        ty = e.type
        if e.value is None:
            def f_null(ctx):
                z = jnp.zeros((ctx.n,), dtype=_np_dtype(ty))
                return z, jnp.zeros((ctx.n,), dtype=jnp.bool_)
            return f_null
        val = e.value

        def f_const(ctx):
            d = jnp.full((ctx.n,), val, dtype=_np_dtype(ty))
            return d, jnp.ones((ctx.n,), dtype=jnp.bool_)
        return f_const

    if isinstance(e, BParam):
        idx, pty, nullable = e.index, e.type, e.nullable

        def f_param(ctx):
            # runtime scalar (statement-shape plan cache): same dtype
            # and broadcast semantics as the baked f_const above
            if nullable:
                # a subquery's result: (value, is-not-NULL)
                v, ok = ctx.params[idx]
                return (jnp.broadcast_to(
                            jnp.array(v, dtype=_np_dtype(pty)), (ctx.n,)),
                        jnp.broadcast_to(
                            jnp.array(ok, dtype=jnp.bool_), (ctx.n,)))
            v = jnp.array(ctx.params[idx], dtype=_np_dtype(pty))
            d = jnp.broadcast_to(v, (ctx.n,))
            return d, jnp.ones((ctx.n,), dtype=jnp.bool_)
        return f_param

    if isinstance(e, BCol):
        name = e.name

        def f_col(ctx):
            return ctx.col(name)
        return f_col

    if isinstance(e, BAggRef):
        i = e.index

        def f_agg(ctx):
            return ctx.aggs[i]
        return f_agg

    if isinstance(e, BWinRef):
        wname = f"__win{e.index}"

        def f_win(ctx):
            return ctx.col(wname)
        return f_win

    if isinstance(e, BBin):
        lf, rf = compile_expr(e.left), compile_expr(e.right)
        op = e.op
        if op in ("and", "or"):
            k = K.and_ if op == "and" else K.or_

            def f_logic(ctx):
                return k(lf(ctx), rf(ctx))
            return f_logic
        table = {"+": K.add, "-": K.sub, "*": K.mul, "/": K.div,
                 "%": K.mod, "//": None,
                 "=": K.eq, "!=": K.ne, "<": K.lt, "<=": K.le,
                 ">": K.gt, ">=": K.ge}
        if op == "//":
            def f_idiv(ctx):
                a, b = lf(ctx), rf(ctx)
                return a[0] // b[0], jnp.logical_and(a[1], b[1])
            return f_idiv
        k = table[op]
        out_ty = e.type

        def f_bin(ctx):
            a, b = lf(ctx), rf(ctx)
            d, v = k(a, b)
            if op in ("+", "-", "*") and out_ty.family in (
                    Family.INT, Family.DECIMAL, Family.DATE,
                    Family.TIMESTAMP):
                d = d.astype(_np_dtype(out_ty))
            return d, v
        return f_bin

    if isinstance(e, BUnary):
        xf = compile_expr(e.operand)
        op = e.op
        if op == "not":
            def f_not(ctx):
                return K.not_(xf(ctx))
            return f_not
        if op == "-":
            def f_neg(ctx):
                return K.neg(xf(ctx))
            return f_neg
        fn = {"abs": jnp.abs, "floor": jnp.floor, "ceil": jnp.ceil,
              "round": jnp.round, "sqrt": jnp.sqrt, "ln": jnp.log,
              "exp": jnp.exp}[op]

        def f_un(ctx):
            d, v = xf(ctx)
            return fn(d), v
        return f_un

    if isinstance(e, BBetween):
        xf = compile_expr(e.expr)
        lof, hif = compile_expr(e.lo), compile_expr(e.hi)
        neg = e.negated

        def f_between(ctx):
            r = K.between(xf(ctx), lof(ctx), hif(ctx))
            return K.not_(r) if neg else r
        return f_between

    if isinstance(e, BInList):
        xf = compile_expr(e.expr)
        vals = list(e.values)
        neg = e.negated

        def f_in(ctx):
            r = K.in_list(xf(ctx), vals)
            return K.not_(r) if neg else r
        return f_in

    if isinstance(e, BIsNull):
        xf = compile_expr(e.expr)
        k = K.is_not_null if e.negated else K.is_null

        def f_isnull(ctx):
            return k(xf(ctx))
        return f_isnull

    if isinstance(e, BCase):
        whenfs = [(compile_expr(c), compile_expr(v)) for c, v in e.whens]
        elsef = compile_expr(e.else_)

        def f_case(ctx):
            return K.case_when([(cf(ctx), vf(ctx)) for cf, vf in whenfs],
                               elsef(ctx))
        return f_case

    if isinstance(e, BCast):
        xf = compile_expr(e.expr)
        src, dst = e.expr.type, e.type

        def f_cast(ctx):
            d, v = xf(ctx)
            if dst.family == Family.FLOAT:
                out = d.astype(jnp.float64)
                if src.family == Family.DECIMAL:
                    out = out / (10.0 ** src.scale)
                return out, v
            if dst.family == Family.DECIMAL:
                if src.family == Family.FLOAT:
                    return jnp.round(d * 10.0 ** dst.scale).astype(jnp.int64), v
                return d.astype(jnp.int64), v
            if dst.family == Family.INT:
                if src.family == Family.DECIMAL:
                    # numeric -> int rounds half away from zero
                    div = 10 ** src.scale
                    mag = (jnp.abs(d) + div // 2) // div
                    d = jnp.where(d < 0, -mag, mag)
                elif src.family == Family.FLOAT:
                    d = jnp.rint(d)  # float -> int: half-even (pg)
                return d.astype(_np_dtype(dst)), v
            if dst.family == Family.BOOL:
                return d.astype(jnp.bool_), v
            raise NotImplementedError(f"cast {src} -> {dst}")
        return f_cast

    if isinstance(e, BCoalesce):
        fs = [compile_expr(a) for a in e.args]

        def f_coalesce(ctx):
            return K.coalesce(*[f(ctx) for f in fs])
        return f_coalesce

    if isinstance(e, BExtract):
        xf = compile_expr(e.expr)
        part = e.part
        fam = "timestamp" if e.expr.type.family == Family.TIMESTAMP else "date"

        def f_extract(ctx):
            d, v = xf(ctx)
            return K.extract_part(part, d, fam), v
        return f_extract

    if isinstance(e, BFunc):
        return _compile_func(e)

    if isinstance(e, (BDictGather, BDictLookup)) \
            and isinstance(e.table, BTableParam):
        # the table of a large dictionary, lifted out of the plan
        # (exec/planparam.py): a runtime argument, one gather
        xf = compile_expr(e.expr)
        tp, np_ = e.table, getattr(e, "null_table", None)

        def f_table_param(ctx):
            d, v = xf(ctx)
            codes = jnp.clip(d, 0, tp.size - 1)
            if np_ is not None:
                v = v & ctx.params[np_.index][codes]
            return ctx.params[tp.index][codes], v
        return f_table_param

    if isinstance(e, BDictGather):
        xf = compile_expr(e.expr)
        tbl = np.asarray(e.table)
        ntbl = (np.asarray(e.null_table, dtype=bool)
                if e.null_table is not None else None)

        def f_gather(ctx):
            d, v = xf(ctx)
            # jnp.array, not asarray: tbl can alias the dictionary's
            # live array, and an aliased trace constant is only safe
            # by a distant append-only argument (graftlint
            # no-aliasing-upload)
            lut = jnp.array(tbl)
            codes = jnp.clip(d, 0, tbl.shape[0] - 1)
            if ntbl is not None:
                v = v & _small_lut(ntbl, codes)
            return lut[codes], v
        return f_gather

    if isinstance(e, BDictLookup):
        xf = compile_expr(e.expr)
        tbl = np.asarray(e.table, dtype=bool)

        def f_dict(ctx):
            d, v = xf(ctx)
            codes = jnp.clip(d, 0, tbl.shape[0] - 1)
            return _small_lut(tbl, codes), v
        return f_dict

    if isinstance(e, BDictRemap):
        xf = compile_expr(e.expr)
        rtbl = np.asarray(e.table, dtype=np.int32)
        ntbl = (np.asarray(e.null_table, dtype=bool)
                if e.null_table is not None else None)

        def f_remap(ctx):
            d, v = xf(ctx)
            codes = jnp.clip(d, 0, rtbl.shape[0] - 1)
            if ntbl is not None:
                v = v & _small_lut(ntbl, codes)
            return _small_lut(rtbl, codes), v
        return f_remap

    raise NotImplementedError(f"cannot compile {e!r}")


# small-LUT gathers ride the MXU: TPU VPU dynamic gathers run ~100-200M
# lookups/s, while a one-hot matmul against a <=512-entry table is
# effectively free next to the surrounding streaming work (the MXU is
# idle in scan programs). Measured on v5e (round 3): 8.4M boolean
# lookups via gather +70ms, via one-hot matmul +0ms. f32 keeps integer
# remap values exact (<= 2^24); the dictionary LIKE/IN/= predicates
# TPC-H and SSB lean on are all <=512-entry LUTs.
_ONE_HOT_MAX = 512


def _small_lut(tbl: np.ndarray, codes):
    L = tbl.shape[0]
    if L > _ONE_HOT_MAX or (
            tbl.dtype != np.bool_ and L > 0
            and np.abs(tbl).max() >= (1 << 24)):
        # f32 holds integers exactly only below 2^24: big remap values
        # (SF100-class target dictionaries) stay on the gather path
        # (jnp.array: tbl is caller-owned, copy rather than alias —
        # graftlint no-aliasing-upload)
        return jnp.array(tbl)[codes]
    lp = max(128, 1 << (L - 1).bit_length())
    padded = np.zeros((lp,), dtype=np.float32)
    padded[:L] = tbl.astype(np.float32)
    oh = jax.nn.one_hot(codes, lp, dtype=jnp.float32)
    # graftlint: waive[no-aliasing-upload] padded is np.zeros allocated
    # two lines up, function-local and never written after this point
    out = oh @ jnp.asarray(padded)
    if tbl.dtype == np.bool_:
        return out > 0.5
    return jnp.round(out).astype(tbl.dtype)


# 1-arg elementwise builtin kernels (sql/builtins.py registry); all
# fuse into the surrounding scan program
_UNARY_KERNELS = {
    "sqrt": jnp.sqrt, "ln": jnp.log, "exp": jnp.exp,
    "log10": jnp.log10, "log2": jnp.log2, "cbrt": jnp.cbrt,
    "sin": jnp.sin, "cos": jnp.cos, "tan": jnp.tan,
    "cot": lambda x: 1.0 / jnp.tan(x),
    "asin": jnp.arcsin, "acos": jnp.arccos, "atan": jnp.arctan,
    "sinh": jnp.sinh, "cosh": jnp.cosh, "tanh": jnp.tanh,
    "degrees": jnp.degrees, "radians": jnp.radians,
    "floor": jnp.floor, "ceil": jnp.ceil, "ceiling": jnp.ceil,
    "trunc": jnp.trunc, "sign": jnp.sign,
    "erf": jax.scipy.special.erf,
    "erfc": jax.scipy.special.erfc,
    "sind": lambda x: jnp.sin(jnp.radians(x)),
    "cosd": lambda x: jnp.cos(jnp.radians(x)),
    "tand": lambda x: jnp.tan(jnp.radians(x)),
    "cotd": lambda x: 1.0 / jnp.tan(jnp.radians(x)),
    "asind": lambda x: jnp.degrees(jnp.arcsin(x)),
    "acosd": lambda x: jnp.degrees(jnp.arccos(x)),
    "atand": lambda x: jnp.degrees(jnp.arctan(x)),
}

_BINARY_KERNELS = {
    "pow": jnp.power, "power": jnp.power, "atan2": jnp.arctan2,
}


def _compile_func(e: BFunc) -> CompiledExpr:
    name = e.name
    fs = [compile_expr(a) for a in e.args]
    if name in _UNARY_KERNELS:
        fn = _UNARY_KERNELS[name]

        def f1(ctx):
            d, v = fs[0](ctx)
            return fn(d), v
        return f1
    if name in _BINARY_KERNELS:
        fn = _BINARY_KERNELS[name]

        def f2(ctx):
            (a, va), (b, vb) = fs[0](ctx), fs[1](ctx)
            return fn(a, b), jnp.logical_and(va, vb)
        return f2
    if name in ("round_n", "trunc_n"):
        ndigits = e.args[1].value
        scale = 10.0 ** ndigits
        op = jnp.round if name == "round_n" else jnp.trunc

        def f_round(ctx):
            d, v = fs[0](ctx)
            return op(d * scale) / scale, v
        return f_round
    if name == "mod":
        def f_mod(ctx):
            return K.mod(fs[0](ctx), fs[1](ctx))
        return f_mod
    if name == "logb":
        def f_logb(ctx):
            # args are [base, x] (pg's log(b, x))
            (b, vb), (x, vx) = fs[0](ctx), fs[1](ctx)
            ok = jnp.logical_and(b > 0, x > 0)
            d = jnp.log(jnp.where(ok, x, 1.0)) / \
                jnp.log(jnp.where(ok, b, 2.0))
            return d, jnp.logical_and(jnp.logical_and(vb, vx), ok)
        return f_logb
    if name == "div":
        def f_div(ctx):
            (a, va), (b, vb) = fs[0](ctx), fs[1](ctx)
            ok = b != 0
            q = jnp.trunc(a / jnp.where(ok, b, 1.0))
            return q, jnp.logical_and(jnp.logical_and(va, vb), ok)
        return f_div
    if name in ("greatest", "least"):
        pick = jnp.maximum if name == "greatest" else jnp.minimum

        def f_gl(ctx):
            # SQL GREATEST/LEAST ignore NULL arguments
            d, v = fs[0](ctx)
            for f in fs[1:]:
                d2, v2 = f(ctx)
                both = jnp.logical_and(v, v2)
                d = jnp.where(both, pick(d, d2), jnp.where(v, d, d2))
                v = jnp.logical_or(v, v2)
            return d, v
        return f_gl
    if name == "nullif":
        def f_nullif(ctx):
            (a, va), (b, vb) = fs[0](ctx), fs[1](ctx)
            eq = jnp.logical_and(a == b, jnp.logical_and(va, vb))
            return a, jnp.logical_and(va, jnp.logical_not(eq))
        return f_nullif
    if name == "isfinite":
        def f_isfinite(ctx):
            d, v = fs[0](ctx)
            return jnp.isfinite(d), v
        return f_isfinite
    if name == "width_bucket":
        def f_wb(ctx):
            (x, vx), (lo, vl), (hi, vh), (n, vn) = [f(ctx)
                                                    for f in fs]
            nb = n.astype(jnp.int64)
            frac = (x - lo) / jnp.where(hi != lo, hi - lo, 1.0)
            inner = jnp.floor(frac * nb).astype(jnp.int64) + 1
            d = jnp.where(x < lo, 0,
                          jnp.where(x >= hi, nb + 1, inner))
            ok = jnp.logical_and(jnp.logical_and(vx, vl),
                                 jnp.logical_and(vh, vn))
            return d, jnp.logical_and(ok, hi != lo)
        return f_wb
    if name == "isnan":
        def f_isnan(ctx):
            d, v = fs[0](ctx)
            return jnp.isnan(d), v
        return f_isnan
    if name in ("date_trunc_date", "date_trunc_ts"):
        part = e.args[0].value
        kern = (K.date_trunc_days if name == "date_trunc_date"
                else K.date_trunc_micros)

        def f_trunc(ctx):
            d, v = fs[1](ctx)
            return kern(part, d), v
        return f_trunc
    raise NotImplementedError(f"no kernel for builtin {name}")
