"""Subprocess body for the distributed-engine equivalence test.

Runs on 4 fake host devices (2 data x 2 model); compares the sharded
VERD tile step against the dense single-shard oracle.  Exits nonzero on
mismatch; tests/test_distributed_engine.py asserts the return code.
"""

import os
import warnings

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import verd as verd_mod
from repro.core.distributed_engine import (
    DistConfig, build_sharded_graph, make_sparse_index_build_step,
    make_sparse_walk_counts_step, make_verd_tile_step, make_walk_counts_step,
)
from repro.core.index import build_index, build_index_sharded, index_from_dense
from repro.core.power_iteration import exact_ppr_dense
from repro.graphs import synthetic

from repro.analysis.jaxpr import assert_no_replicated_index, iter_eqns


def densify_rows(values, indices, n):
    """Private copy of the conftest scatter oracle (plain subprocess)."""
    values = np.asarray(values)
    out = np.zeros((values.shape[0], n), np.float32)
    np.add.at(
        out, (np.arange(values.shape[0])[:, None], np.asarray(indices)),
        values,
    )
    return out


def check_sharded_build(mesh):
    """ISSUE 5 acceptance gate: build_index_sharded == single-device
    engine="sparse" build under the same per-chunk keys (same fold order),
    with identical drop_fraction; per-device jaxpr holds no replicated
    [n, L] index arrays; a sharded index serves through the query engine."""
    from repro.core.query import BatchQueryEngine, QueryConfig

    key = jax.random.PRNGKey(3)
    g = synthetic.erdos_renyi(64, 4.0, seed=21)   # n == n_pad: exact grid
    # walk shards = the 2-wide data axis -> single-device r_splits=2
    for respawn in (False, True):
        for l in (64, 6):                          # covering + truncating
            sharded, st_sh = build_index_sharded(
                g, r=64, l=l, key=key, mesh=mesh, source_batch=16,
                respawn=respawn,
            )
            single, st_si = build_index(
                g, r=64, l=l, key=key, source_batch=16, r_splits=2,
                respawn=respawn,
            )
            got = densify_rows(
                np.asarray(sharded.values)[: g.n],
                np.asarray(sharded.indices)[: g.n], g.n,
            )
            want = densify_rows(single.values, single.indices, g.n)
            l1 = np.abs(got - want).sum(axis=1)
            assert l1.max() <= 1e-5, (respawn, l, l1.max())
            ddf = abs(st_sh["drop_fraction"] - st_si["drop_fraction"])
            assert ddf <= 1e-6, (respawn, l, ddf)
    print("sharded build parity OK (covering + truncating, both modes)")

    # memory contract: inside the shard_map body every array's leading dim
    # stays the per-shard interval — a replicated [n, L] index block per
    # device (what the old host-driven build would produce) must not trace
    cfg = DistConfig(n=64, ep=2)
    step = make_sparse_index_build_step(
        cfg, mesh, r=64, l=16, sketch_l=48, real_n=64, source_batch=16,
    )
    rp = jnp.asarray(np.asarray(g.row_ptr))
    ci = jnp.asarray(np.asarray(g.col_idx))
    od = jnp.asarray(np.asarray(g.out_deg))
    jaxpr = jax.make_jaxpr(step)(rp, ci, od, key)
    # an index-shaped per-device block: >= n rows of >= l columns.  The
    # per-device sweep may hold flattened [q*w, 1] scatter intermediates
    # (row count is not vertex count there), but never a full-index [n, L]
    # tile.  The check is the auditor's no-replicated-index rule.
    assert_no_replicated_index(jaxpr, n=cfg.n, l=16)
    checked = sum(
        1 for eqn in iter_eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "shard_map"
    )
    assert checked > 0
    print(f"sharded build memory contract OK ({checked} shard_map eqns)")

    # serving: the model-sharded (and, on g2, row-padded) index answers
    # through the ordinary query engine without re-layout
    sharded, _ = build_index_sharded(
        g, r=64, l=16, key=key, mesh=mesh, source_batch=16,
    )
    single, _ = build_index(
        g, r=64, l=16, key=key, source_batch=16, r_splits=2, respawn=True,
    )
    qcfg = QueryConfig(mode="powerwalk", t_iterations=2, top_k=10)
    out_sh = BatchQueryEngine(g, sharded, qcfg).run([0, 5, 9, 33])
    out_si = BatchQueryEngine(g, single, qcfg).run([0, 5, 9, 33])
    np.testing.assert_allclose(
        out_sh["values"], out_si["values"], rtol=1e-5, atol=1e-7,
    )
    g2 = synthetic.erdos_renyi(60, 4.0, seed=11)   # n=60 -> n_pad=64
    sh2, st2 = build_index_sharded(
        g2, r=32, l=8, key=key, mesh=mesh, source_batch=16,
    )
    assert sh2.n == 64 and st2["pad_rows"] == 4
    assert float(np.abs(np.asarray(sh2.values)[g2.n:]).sum()) == 0.0
    si2, _ = build_index(
        g2, r=32, l=8, key=key, source_batch=16, r_splits=2, respawn=True,
    )
    got2 = densify_rows(
        np.asarray(sh2.values)[: g2.n], np.asarray(sh2.indices)[: g2.n],
        g2.n,
    )
    want2 = densify_rows(si2.values, si2.indices, g2.n)
    assert np.abs(got2 - want2).sum(axis=1).max() <= 1e-5
    out_p = BatchQueryEngine(g2, sh2, qcfg).run([0, 7, 59])
    out_q = BatchQueryEngine(g2, si2, qcfg).run([0, 7, 59])
    np.testing.assert_allclose(
        out_p["values"], out_q["values"], rtol=1e-5, atol=1e-7,
    )
    print("sharded index serving OK (incl. padded rows)")


def main():
    mesh = jax.make_mesh((2, 2), ("data", "model"))
    g = synthetic.erdos_renyi(60, 4.0, seed=11)
    n_pad = 64  # multiple of model axis
    # legacy dense-slab exchange (the sparse wire format is the default and
    # is covered by tests/parity_check.py)
    cfg = DistConfig(n=n_pad, ep=2, q_tile=8, t_iterations=2,
                     index_l=16, top_k=20, exchange="dense")
    slabs = build_sharded_graph(g, cfg)

    # dense oracle index from exact vectors (padded)
    exact = exact_ppr_dense(g)
    dense = np.zeros((n_pad, n_pad), np.float32)
    dense[: g.n, : g.n] = exact
    idx = index_from_dense(jnp.asarray(dense), l=cfg.index_l)
    ivals = idx.values.reshape(cfg.ep, cfg.n_shard, cfg.index_l)
    iidx = idx.indices.reshape(cfg.ep, cfg.n_shard, cfg.index_l)

    sources = jnp.asarray([0, 3, 7, 11, 19, 23, 31, 42], jnp.int32)
    step = make_verd_tile_step(cfg, mesh, kernel_interpret=True)
    with mesh:
        tv, ti = jax.jit(step)(slabs, sources, ivals, iidx)

    # oracle: dense verd on the unpadded graph with the same (padded) index
    idx_small = index_from_dense(jnp.asarray(dense[: g.n, : g.n]),
                                 l=cfg.index_l)
    want = verd_mod.verd_query(g, sources, idx_small, t=cfg.t_iterations)
    wv, wi = jax.lax.top_k(want, cfg.top_k)

    np.testing.assert_allclose(
        np.asarray(tv), np.asarray(wv), rtol=2e-4, atol=1e-5)
    # indices may tie-break differently: compare the score of chosen ids
    chosen = np.take_along_axis(np.asarray(want), np.asarray(ti), axis=1)
    np.testing.assert_allclose(
        chosen, np.asarray(wv), rtol=2e-4, atol=1e-5)
    print("verd tile OK")

    # deprecated compress_k on the dense path: still close (top-k tail small)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        cfg_c = DistConfig(n=n_pad, ep=2, q_tile=8, t_iterations=2,
                           index_l=16, top_k=20, exchange="dense",
                           compress_k=32)
    step_c = make_verd_tile_step(cfg_c, mesh, kernel_interpret=True)
    with mesh:
        cv, ci = jax.jit(step_c)(slabs, sources, ivals, iidx)
    np.testing.assert_allclose(
        np.asarray(cv), np.asarray(wv), rtol=5e-3, atol=1e-4)
    print("compressed exchange OK")

    # walk counts: estimator consistency on the sharded engine
    wcfg = DistConfig(n=n_pad, ep=2, q_tile=4, t_iterations=2)
    walk_step = make_walk_counts_step(wcfg, mesh, max_steps=64)
    r = 2000
    wsources = jnp.repeat(jnp.asarray([0, 3, 7, 11], jnp.int32), r)
    wrows = jnp.repeat(jnp.arange(4, dtype=jnp.int32), r)
    rp = jnp.asarray(np.asarray(g.row_ptr))
    ci_full = jnp.asarray(np.asarray(g.col_idx))
    od = jnp.asarray(np.asarray(g.out_deg))
    with mesh:
        fp, moves = jax.jit(walk_step)(
            rp, ci_full, od, wsources, wrows, jax.random.PRNGKey(0))
    est = np.asarray(fp)[:, : g.n] / np.asarray(moves)[:, None]
    err = np.abs(est - exact[[0, 3, 7, 11]]).sum(axis=1).mean()
    assert err < 0.15, f"walk L1 err too big: {err}"
    print(f"walk counts OK (L1={err:.4f})")

    # sharded compacted sparse-sketch walks: r splits over the 2 data
    # shards, sketches all_gather+merge — conservation must stay exact and
    # the merged estimate must converge like the single-device engine
    scfg = DistConfig(n=n_pad, ep=2, q_tile=4, t_iterations=2)
    sparse_step = make_sparse_walk_counts_step(scfg, mesh, r=r, l=g.n)
    ssources = jnp.asarray([0, 3, 7, 11], jnp.int32)
    with mesh:
        sv, si, smoves, swalks, sdrop = jax.jit(sparse_step)(
            rp, ci_full, od, ssources, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(swalks), float(r))
    # cross-shard conservation: kept mass + dropped ledger == moves; at
    # full width nothing is dropped
    np.testing.assert_allclose(
        np.asarray(sv).sum(axis=1) + np.asarray(sdrop),
        np.asarray(smoves), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sdrop), 0.0, atol=1e-6)
    # narrow sketch: the ledger must still close the conservation identity
    narrow_step = make_sparse_walk_counts_step(scfg, mesh, r=r, l=4)
    with mesh:
        nv, _, nmoves, _, ndrop = jax.jit(narrow_step)(
            rp, ci_full, od, ssources, jax.random.PRNGKey(0))
    assert float(np.asarray(ndrop).sum()) > 0.0
    np.testing.assert_allclose(
        np.asarray(nv).sum(axis=1) + np.asarray(ndrop),
        np.asarray(nmoves), rtol=1e-6)
    sest = np.zeros((4, g.n), np.float32)
    np.add.at(sest, (np.arange(4)[:, None], np.asarray(si)),
              np.asarray(sv) / np.asarray(smoves)[:, None])
    serr = np.abs(sest - exact[[0, 3, 7, 11]]).sum(axis=1).mean()
    assert serr < 0.15, f"sparse walk L1 err too big: {serr}"
    print(f"sparse walk counts OK (L1={serr:.4f})")

    check_sharded_build(mesh)


if __name__ == "__main__":
    main()
    print("ALL OK")
