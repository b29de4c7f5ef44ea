#!/usr/bin/env python3
"""group_by_crossover.py: the two strategies of a plain GROUP BY past
the dense bound, timed against each other.

ops/hashtable.group_ids with the segment sums of a sum and a count (the
`hash` strategy) against exec/rollup.sorted_sets with one set (the
`sorted` strategy), over batches of several row counts, 3 and 6 key
columns drawn from TPC-DS Q89's key domains at SF1, a few thousand live
groups, 70 % of the rows selected and 2 % NULL values. Both must find
the same groups and the same total. exec/compile.py
SORTED_GROUP_MIN_ROWS cites what this prints on a TPU v5e.

Usage: python group_by_crossover.py [--small]
  --small  4,096 and 8,192 rows, 300 groups: a run on the CPU

One JSON line a (rows, keys) pair: milliseconds a call (the median of
15) and seconds of compile for each strategy; all of them also go to
chiprun_out/crossover.json.
"""

import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from cockroach_tpu.exec import rollup  # noqa: E402
from cockroach_tpu.exec.compile import _key_encode  # noqa: E402
from cockroach_tpu.ops import agg as aggops, hashtable  # noqa: E402
from cockroach_tpu.sql.bound import BoundAgg  # noqa: E402
from cockroach_tpu.sql.types import INT8  # noqa: E402

# Q89's keys at SF1: category, class, brand, store name, company, month
DIMS = [10, 99, 1530, 10, 1, 12]
CAPACITY = 1 << 17      # the table's slots (hash_group_capacity)
SLOTS = 1 << 13         # the sorted layout's (P.Aggregate.set_slots)


def data(n: int, k: int, groups: int, seed: int) -> tuple:
    """(keys, the summed value, its valid mask, sel) over n rows whose
    k keys take `groups` tuples."""
    rng = np.random.default_rng(seed)
    pool = np.stack([rng.integers(0, d, groups) for d in DIMS[:k]])
    pick = rng.integers(0, groups, n)
    keys = [(jnp.asarray(pool[j][pick].astype(np.int32)),
             jnp.ones((n,), jnp.bool_)) for j in range(k)]
    m = jnp.asarray(rng.integers(0, 30000, n).astype(np.int64))
    mv = jnp.asarray(rng.random(n) > 0.02)
    sel = jnp.asarray(rng.random(n) < 0.7)
    return keys, m, mv, sel


def by_table(keys, m, mv, sel):
    """(groups, the sums, the counts, the keys' columns) by the
    while-loop hash table."""
    cols = []
    for d, v in keys:
        cols += list(_key_encode(d, v))
    gid, ng, rep = hashtable.group_ids(tuple(cols), sel, CAPACITY)
    s = aggops.group_sum(m, gid, jnp.logical_and(sel, mv), CAPACITY,
                         acc_dtype=jnp.int64, arg_bits=15)
    c = aggops.group_count(gid, sel, CAPACITY)
    return ng, s, c, [d[rep] for d, _ in keys]


def by_sort(keys, m, mv, sel):
    """(groups, the states, more groups than SLOTS) by the sorted
    layout, one set."""
    k, n = len(keys), sel.shape[0]
    aggs = [BoundAgg("sum_int", None, INT8), BoundAgg("count", None, INT8)]
    _, st, live, _, _, short = rollup.sorted_sets(
        [tuple(range(k))], [(d, 0) for d in DIMS[:k]],
        [f"k{j}" for j in range(k)], keys,
        [(m, mv), (jnp.ones((n,), jnp.int64), sel)], aggs, sel, SLOTS,
        tally=False)
    return jnp.sum(live), st, short


def timed(f, args, reps: int) -> tuple:
    """(seconds to compile, median ms a call, the result)."""
    t = time.perf_counter()
    c = jax.jit(f).lower(*args).compile()
    compile_s = time.perf_counter() - t
    out = jax.block_until_ready(c(*args))
    ms = []
    for _ in range(reps):
        t = time.perf_counter()
        jax.block_until_ready(c(*args))
        ms.append((time.perf_counter() - t) * 1e3)
    return compile_s, float(np.median(ms)), out


def measure(rows: int, k: int, groups: int, reps: int) -> dict:
    """Both strategies over one batch; an AssertionError where they
    disagree."""
    args = data(rows, k, groups, rows + k)
    hc, hms, (hng, hsum, _, _) = timed(by_table, args, reps)
    sc, sms, (sng, ((ssum, svalid), _), short) = timed(by_sort, args, reps)
    assert not bool(short)
    assert int(hng) == int(sng), (int(hng), int(sng))
    assert int(jnp.sum(hsum)) == int(jnp.sum(jnp.where(svalid, ssum, 0)))
    return {"rows": rows, "keys": k, "groups": int(hng),
            "hash_ms": hms, "sorted_ms": sms,
            "hash_compile_s": hc, "sorted_compile_s": sc}


def main(argv) -> int:
    small = "--small" in argv
    rows = ([1 << 12, 1 << 13] if small
            else [1 << 15, 1 << 16, 1 << 17, 1 << 18, 313600])
    print(jax.devices(), flush=True)
    out = []
    for n in rows:
        for k in (3, 6):
            rec = measure(n, k, 300 if small else 3000, 15)
            out.append(rec)
            print(json.dumps(rec), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "crossover.json"), "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
