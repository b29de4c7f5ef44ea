"""The three branches of ops/join.hash_join beside `inner`: semi, anti
and left, on keys with NULLs on both sides and duplicate build keys,
against a nested-loop reference in plain Python; through the
direct-address table (payloads packed into one gather, and not) and
through the hash table; with and without a Compact of the output above
(an anti-join keeps the rows that found nothing, a left join keeps
all: what a Compact may follow is what it must not lose)."""

import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu.exec.compile import compact_batch
from cockroach_tpu.ops.batch import ColumnBatch
from cockroach_tpu.ops.join import hash_join

N_PROBE, N_BUILD, KEYS = 4096, 256, 300


def _tables(seed: int, unique_build: bool):
    rng = np.random.default_rng(seed)
    probe = {"k": rng.integers(0, KEYS + 40, size=N_PROBE),
             "k_null": rng.random(N_PROBE) < 0.1,
             "dead": rng.random(N_PROBE) < 0.2,
             "v": np.arange(N_PROBE)}
    bk = (rng.permutation(KEYS + 20)[:N_BUILD] if unique_build
          else rng.integers(0, KEYS, size=N_BUILD))
    build = {"k": bk, "k_null": rng.random(N_BUILD) < 0.1,
             "dead": rng.random(N_BUILD) < 0.1,
             "code": rng.integers(0, 50, size=N_BUILD).astype(np.int32),
             "code_null": rng.random(N_BUILD) < 0.2,
             "w": rng.integers(0, 1 << 40, size=N_BUILD)}
    return probe, build


def _batches(probe, build):
    p = ColumnBatch.from_dict(
        {"p.k": jnp.asarray(probe["k"]), "p.v": jnp.asarray(probe["v"])},
        {"p.k": jnp.asarray(~probe["k_null"]),
         "p.v": jnp.ones(N_PROBE, dtype=bool)},
        sel=jnp.asarray(~probe["dead"]))
    b = ColumnBatch.from_dict(
        {"b.k": jnp.asarray(build["k"]),
         "b.code": jnp.asarray(build["code"]),
         "b.w": jnp.asarray(build["w"])},
        {"b.k": jnp.asarray(~build["k_null"]),
         "b.code": jnp.asarray(~build["code_null"]),
         "b.w": jnp.ones(N_BUILD, dtype=bool)},
        sel=jnp.asarray(~build["dead"]))
    return p, b


def _nested_loop(probe, build, kind: str) -> list:
    """Rows (v, code | None, w | None) as SQL defines the join: a NULL
    key matches nothing, a dead row is not there; for a left join one
    row a match, NULLs where there is none."""
    live_build = [j for j in range(N_BUILD)
                  if not build["dead"][j] and not build["k_null"][j]]
    out = []
    for i in range(N_PROBE):
        if probe["dead"][i]:
            continue
        hits = [] if probe["k_null"][i] else [
            j for j in live_build if build["k"][j] == probe["k"][i]]
        v = int(probe["v"][i])
        if kind == "semi":
            if hits:
                out.append((v,))
        elif kind == "anti":
            if not hits:
                out.append((v,))
        else:
            for j in hits:
                out.append((v, None if build["code_null"][j]
                            else int(build["code"][j]),
                            int(build["w"][j])))
            if not hits:
                out.append((v, None, None))
    return sorted(out, key=lambda r: tuple(-1 if x is None else x
                                           for x in r))


def _rows(out: ColumnBatch, kind: str) -> list:
    sel = np.asarray(out.sel)
    cols = [(np.asarray(out.col("p.v")), np.asarray(out.col_valid("p.v")))]
    if kind == "left":
        cols += [(np.asarray(out.col(n)), np.asarray(out.col_valid(n)))
                 for n in ("b.code", "b.w")]
    rows = [tuple(int(d[i]) if ok[i] else None for d, ok in cols)
            for i in np.nonzero(sel)[0]]
    return sorted(rows, key=lambda r: tuple(-1 if x is None else x
                                            for x in r))


PATHS = {
    # direct-address table, every payload a gather of its own
    "direct": dict(direct=(0, KEYS + 41), pack_payload=()),
    # the int32 code folded with the match bit into one gather
    "packed": dict(direct=(0, KEYS + 41), pack_payload=("b.code",)),
    # no dense key domain: the while-loop hash table
    "hash": dict(direct=None, pack_payload=()),
}


@pytest.mark.parametrize("compact", [False, True],
                         ids=["plain", "compact_above"])
@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("kind", ["semi", "anti", "left"])
@pytest.mark.parametrize("seed", [3, 36])
def test_join_kind_equals_the_nested_loop(seed, kind, path, compact):
    # duplicate build keys everywhere but in a left join's direct
    # table, which holds one row a key (Engine._check_one_build sends
    # a duplicate-keyed build side through `expand`, below)
    probe, build = _tables(seed, unique_build=kind == "left")
    p, b = _batches(probe, build)
    payload = ["b.code", "b.w"] if kind == "left" else []
    out = hash_join(p, b, ["p.k"], ["b.k"], payload, kind,
                    **PATHS[path])
    if compact:
        # half the batch's width holds every survivor here (a fifth
        # of the probe is dead); no row may be lost, none invented
        out = compact_batch(out, 0.9, block=1024, interpret=True)
        assert not bool(jnp.any(out.col("__compact_overflow"))) \
            if out.has("__compact_overflow") else True
    assert _rows(out, kind) == _nested_loop(probe, build, kind)


@pytest.mark.parametrize("seed", [3, 36])
def test_left_join_expands_duplicate_build_keys(seed):
    probe, build = _tables(seed, unique_build=False)
    live = ~build["dead"] & ~build["k_null"]
    most = int(np.bincount(build["k"][live]).max())
    p, b = _batches(probe, build)
    out = hash_join(p, b, ["p.k"], ["b.k"], ["b.code", "b.w"], "left",
                    expand=most, direct=(0, KEYS + 41))
    assert out.n == N_PROBE * most
    assert _rows(out, "left") == _nested_loop(probe, build, "left")
