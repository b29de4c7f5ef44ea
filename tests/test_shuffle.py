"""The hash-partitioned all_to_all exchange (parallel/shuffle.py) vs a
numpy oracle on the 8-device virtual CPU mesh (round-3 VERDICT #3; the
HashRouter analogue, pkg/sql/colflow/routers.go:425)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cockroach_tpu.parallel import shuffle
from cockroach_tpu.parallel.mesh import (SHARD_AXIS, make_mesh,
                                         replicated_spec, shard_spec)

D = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(n=D)


def _run_exchange(mesh, keys, vals, valid, cap):
    """keys/vals/valid: [D, n_local] global arrays; returns per-shard
    received (keys, vals, valid, overflow) stacked [D, D*cap]."""

    def body(k, v, ok):
        k, v, ok = k[0], v[0], ok[0]
        dest = shuffle.dest_of((k,), D)
        recv, rvalid, ovf = shuffle.exchange(dest, ok, D, cap, [k, v])
        return (recv[0][None], recv[1][None], rvalid[None],
                jnp.asarray(ovf)[None])

    sh = shard_spec()
    f = jax.shard_map(body, mesh=mesh, in_specs=(sh, sh, sh),
                      out_specs=(sh, sh, sh, sh), check_vma=False)
    return f(keys, vals, valid)


class TestExchange:
    def test_rows_land_on_hash_owner(self, mesh):
        rng = np.random.default_rng(0)
        n_local = 64
        keys = rng.integers(0, 1000, size=(D, n_local)).astype(np.int64)
        vals = rng.integers(0, 10**6, size=(D, n_local)).astype(np.int64)
        valid = rng.random((D, n_local)) < 0.9
        rk, rv, rval, ovf = _run_exchange(
            mesh, jnp.asarray(keys), jnp.asarray(vals),
            jnp.asarray(valid), cap=n_local)
        assert not bool(np.asarray(ovf).any())
        rk, rv, rval = map(np.asarray, (rk, rv, rval))
        # oracle destination per key
        dest = np.asarray(shuffle.dest_of(
            (jnp.asarray(keys.reshape(-1)),), D)).reshape(D, n_local)
        # 1) every received row is on its hash owner
        for s in range(D):
            got = rk[s][rval[s]]
            if len(got):
                gd = np.asarray(shuffle.dest_of((jnp.asarray(got),), D))
                assert (gd == s).all()
        # 2) nothing lost, nothing duplicated: multiset of (key, val)
        sent = sorted((int(k), int(v)) for k, v, ok in
                      zip(keys.reshape(-1), vals.reshape(-1),
                          valid.reshape(-1)) if ok)
        recv_all = sorted(
            (int(k), int(v))
            for s in range(D)
            for k, v in zip(rk[s][rval[s]], rv[s][rval[s]]))
        assert recv_all == sent

    def test_overflow_flag_on_skew(self, mesh):
        # every row has the SAME key -> one destination gets them all
        n_local = 32
        keys = jnp.zeros((D, n_local), dtype=jnp.int64)
        vals = jnp.arange(D * n_local, dtype=jnp.int64).reshape(D, n_local)
        valid = jnp.ones((D, n_local), dtype=bool)
        _rk, _rv, _rval, ovf = _run_exchange(mesh, keys, vals, valid,
                                             cap=n_local // 4)
        assert bool(np.asarray(ovf).all())

    def test_empty_shards_ok(self, mesh):
        n_local = 16
        keys = jnp.arange(D * n_local, dtype=jnp.int64).reshape(D, n_local)
        vals = keys * 10
        valid = jnp.zeros((D, n_local), dtype=bool)
        _rk, _rv, rval, ovf = _run_exchange(mesh, keys, vals, valid,
                                            cap=n_local)
        assert not bool(np.asarray(ovf).any())
        assert not np.asarray(rval).any()


class TestShardedShardedJoin:
    def test_large_join_matches_oracle(self, mesh):
        """Both sides row-sharded; exchange each by its join key, then
        local direct-address join per shard — the sharded⋈sharded case
        the round-2 framework could not run at all."""
        from cockroach_tpu.ops.join import hash_join
        from cockroach_tpu.ops.batch import ColumnBatch

        rng = np.random.default_rng(1)
        n_l, n_r = 512, 256          # global rows, divisible by D
        lk = rng.integers(0, 200, size=n_l).astype(np.int64)
        lv = rng.integers(0, 10**6, size=n_l).astype(np.int64)
        rk = np.arange(n_r, dtype=np.int64)  # unique build keys (PK)
        rv = rng.integers(0, 10**6, size=n_r).astype(np.int64)
        cap = 2 * max(n_l, n_r) // D

        def body(lks, lvs, rks, rvs):
            lks, lvs = lks[0], lvs[0]
            rks, rvs = rks[0], rvs[0]
            ok_l = jnp.ones(lks.shape, bool)
            ok_r = jnp.ones(rks.shape, bool)
            dl = shuffle.dest_of((lks,), D)
            dr = shuffle.dest_of((rks,), D)
            (lk2, lv2), lval, o1 = shuffle.exchange(dl, ok_l, D, cap,
                                                    [lks, lvs])
            (rk2, rv2), rval, o2 = shuffle.exchange(dr, ok_r, D, cap,
                                                    [rks, rvs])
            ones_l = jnp.ones(lval.shape, bool)
            ones_r = jnp.ones(rval.shape, bool)
            probe = ColumnBatch(data=(lk2, lv2),
                                valid=(ones_l, ones_l),
                                sel=lval, names=("k", "v"))
            build = ColumnBatch(data=(rk2, rv2),
                                valid=(ones_r, ones_r),
                                sel=rval, names=("k", "w"))
            out = hash_join(probe, build, ["k"], ["k"], ["w"],
                            join_type="inner")
            # per-shard partial sum of v+w over matches: psum = oracle
            m = out.sel
            tot = jnp.sum(jnp.where(
                m, out.col("v") + out.col("w"), 0))
            cnt = jnp.sum(m.astype(jnp.int64))
            return (jax.lax.psum(tot, SHARD_AXIS)[None],
                    jax.lax.psum(cnt, SHARD_AXIS)[None],
                    jnp.asarray(jnp.logical_or(o1, o2))[None])

        sh = shard_spec()
        f = jax.shard_map(body, mesh=mesh, in_specs=(sh, sh, sh, sh),
                          out_specs=(sh, sh, sh), check_vma=False)
        tot, cnt, ovf = f(jnp.asarray(lk.reshape(D, -1)),
                          jnp.asarray(lv.reshape(D, -1)),
                          jnp.asarray(rk.reshape(D, -1)),
                          jnp.asarray(rv.reshape(D, -1)))
        assert not bool(np.asarray(ovf).any())
        # numpy oracle
        rmap = {int(k): int(v) for k, v in zip(rk, rv)}
        pairs = [(int(v) + rmap[int(k)]) for k, v in zip(lk, lv)
                 if int(k) in rmap]
        assert int(np.asarray(cnt)[0]) == len(pairs)
        assert int(np.asarray(tot)[0]) == sum(pairs)
