"""Subprocess body for the cross-path parity suite (multi-shard half).

Runs on 4 fake host devices arranged as a (1 data x 4 model) mesh — the
acceptance gate's "4-shard CPU mesh" — and checks the three-path matrix
(docs/query_path.md):

* distributed-sparse == single-device-sparse to <= 1e-5 L1 when the widths
  cover the frontier support (incl. hub-split variants),
* both == the dense oracle at covering widths,
* truncated widths only *drop* mass (elementwise monotone) and the L1 drift
  is bounded by the dropped mass,
* the sparse exchange actually routed through the fused Pallas wrapper
  ``kernels.ops.sharded_frontier_push`` (trace-time invocation counter) —
  not a duplicated jnp path.

Exits nonzero on mismatch; tests/test_parity.py asserts the return code.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import verd as verd_mod
from repro.core.distributed_engine import (
    DistConfig, build_sharded_graph, make_verd_tile_step,
)
from repro.core.index import index_from_dense
from repro.core.power_iteration import exact_ppr_dense
from repro.graphs import synthetic

EP = 4
N_PAD = 128
TOP_K = N_PAD  # cover the full support so answers densify losslessly
QT = 8


def densify_answers(vals, idx, n):
    q = vals.shape[0]
    out = np.zeros((q, n), np.float32)
    np.add.at(out, (np.arange(q)[:, None], np.asarray(idx)), np.asarray(vals))
    return out


def run_distributed(cfg, slabs, sources, ivals, iidx, mesh):
    step = make_verd_tile_step(cfg, mesh, kernel_interpret=True)
    with mesh:
        tv, ti = jax.jit(step)(slabs, sources, ivals, iidx)
    return densify_answers(tv, ti, cfg.n)


def main():
    mesh = jax.make_mesh((1, EP), ("data", "model"))
    g = synthetic.erdos_renyi(120, 4.0, seed=3)
    cap = verd_mod.resolve_degree_cap(g)
    base = dict(n=N_PAD, ep=EP, q_tile=QT, t_iterations=2, index_l=16,
                top_k=TOP_K, degree_cap=cap)
    cfg = DistConfig(frontier_k=N_PAD, wire_k=0, combine_wire_k=0, **base)
    slabs = build_sharded_graph(g, cfg)

    exact = exact_ppr_dense(g)
    dense_pad = np.zeros((N_PAD, N_PAD), np.float32)
    dense_pad[: g.n, : g.n] = exact
    idx = index_from_dense(jnp.asarray(dense_pad), l=cfg.index_l)
    ivals = idx.values.reshape(EP, cfg.n_shard, cfg.index_l)
    iidx = idx.indices.reshape(EP, cfg.n_shard, cfg.index_l)
    idx_small = index_from_dense(jnp.asarray(dense_pad[: g.n, : g.n]),
                                 l=cfg.index_l)
    sources = jnp.asarray([0, 3, 7, 11, 19, 23, 31, 42], jnp.int32)

    # path 1: single-device sparse (covering K)
    sp = verd_mod.verd_query_sparse(
        g, sources, idx_small, t=cfg.t_iterations, k=g.n, out_k=TOP_K
    )
    single_sparse = np.zeros((QT, N_PAD), np.float32)
    single_sparse[:, : g.n] = np.asarray(sp.densify())

    # path 2: dense oracle
    dense_ans = np.zeros((QT, N_PAD), np.float32)
    dense_ans[:, : g.n] = np.asarray(verd_mod.verd_query(
        g, sources, idx_small, t=cfg.t_iterations))

    # path 3: distributed sparse exchange, with and without hub splitting;
    # the 4-shard run must invoke the fused kernel wrapper once per VERD
    # iteration (trace time), not fall back to a jnp push
    from repro.kernels import ops as kernel_ops

    kernel_ops.reset_kernel_invocations()
    got = run_distributed(cfg, slabs, sources, ivals, iidx, mesh)
    pushes = kernel_ops.kernel_invocations().get("sharded_frontier_push", 0)
    assert pushes == cfg.t_iterations, (
        f"engine bypassed the fused kernel wrapper: {pushes} invocations, "
        f"expected {cfg.t_iterations}"
    )
    l1 = np.abs(got - single_sparse).sum(axis=1)
    assert l1.max() <= 1e-5, f"dist-sparse vs single-sparse L1={l1.max()}"
    l1d = np.abs(got - dense_ans).sum(axis=1)
    assert l1d.max() <= 1e-5, f"dist-sparse vs dense oracle L1={l1d.max()}"
    print(
        f"4-shard sparse exchange parity OK (L1={l1.max():.2e}, "
        f"fused-kernel pushes={pushes})"
    )

    for h in (1, 3):
        cfg_h = DistConfig(frontier_k=N_PAD, hub_split_degree=h, **base)
        got_h = run_distributed(cfg_h, slabs, sources, ivals, iidx, mesh)
        np.testing.assert_allclose(got_h, got, atol=1e-6)
    print("hub-split parity OK")

    # legacy dense exchange still matches the oracle (its slabs carry the
    # edge_w slab the sparse build skips)
    cfg_d = DistConfig(exchange="dense", **base)
    slabs_d = build_sharded_graph(g, cfg_d)
    got_d = run_distributed(cfg_d, slabs_d, sources, ivals, iidx, mesh)
    l1 = np.abs(got_d - dense_ans).sum(axis=1)
    assert l1.max() <= 1e-4, f"dense exchange L1={l1.max()}"
    print("dense exchange parity OK")

    # truncated wire: only drops mass, drift bounded by the dropped mass
    cfg_t = DistConfig(frontier_k=4, wire_k=4, combine_wire_k=8, **base)
    got_t = run_distributed(cfg_t, slabs, sources, ivals, iidx, mesh)
    assert (got_t <= got + 1e-6).all(), "truncation must be monotone"
    dropped = got.sum(axis=1) - got_t.sum(axis=1)
    l1 = np.abs(got - got_t).sum(axis=1)
    assert (l1 <= dropped + 1e-5).all(), (l1, dropped)
    print("truncated exchange bounded OK")


if __name__ == "__main__":
    main()
    print("ALL OK")
