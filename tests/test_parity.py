"""Cross-path parity suite: distributed-sparse vs single-device-sparse vs
dense oracle (the three-path test matrix of docs/query_path.md).

The multi-shard half runs in a 4-fake-device subprocess
(``tests/parity_check.py``, marked ``slow``); the degenerate 1-shard case
and the wire-byte accounting run in-process on the single real CPU device.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import densify_rows
from repro.core import verd as verd_mod
from repro.core.distributed_engine import (
    DistConfig, build_sharded_graph, exchange_bytes_per_iteration,
    make_verd_tile_step,
)
from repro.core.index import index_from_dense
from repro.core.power_iteration import exact_ppr_dense
from repro.graphs import synthetic


@pytest.fixture(scope="module")
def setup():
    g = synthetic.erdos_renyi(60, 4.0, seed=9)
    exact = exact_ppr_dense(g)
    n_pad = 64
    dense = np.zeros((n_pad, n_pad), np.float32)
    dense[: g.n, : g.n] = exact
    return g, jnp.asarray(dense), n_pad


_densify = densify_rows


@pytest.mark.parametrize("hub_split_degree", [0, 2])
def test_one_shard_matches_single_device_sparse(setup, hub_split_degree):
    """Degenerate ep=1 mesh: the sharded engine *is* the sparse path."""
    g, dense, n_pad = setup
    cap = verd_mod.resolve_degree_cap(g)
    cfg = DistConfig(
        n=n_pad, ep=1, q_tile=4, t_iterations=2, index_l=16, top_k=n_pad,
        frontier_k=n_pad, degree_cap=cap, hub_split_degree=hub_split_degree,
    )
    slabs = build_sharded_graph(g, cfg)
    idx = index_from_dense(dense, l=cfg.index_l)
    ivals = idx.values.reshape(1, cfg.n_shard, cfg.index_l)
    iidx = idx.indices.reshape(1, cfg.n_shard, cfg.index_l)
    sources = jnp.asarray([0, 5, 17, 42], jnp.int32)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    step = make_verd_tile_step(cfg, mesh, kernel_interpret=True)
    with mesh:
        tv, ti = jax.jit(step)(slabs, sources, ivals, iidx)
    got = _densify(tv, ti, n_pad)

    idx_small = index_from_dense(dense[: g.n, : g.n], l=cfg.index_l)
    sp = verd_mod.verd_query_sparse(
        g, sources, idx_small, t=2, k=g.n, out_k=n_pad
    )
    want = np.zeros_like(got)
    want[:, : g.n] = np.asarray(sp.densify())
    assert np.abs(got - want).sum(axis=1).max() <= 1e-5

    # and the dense oracle agrees too (three-path closure)
    oracle = np.asarray(verd_mod.verd_query(g, sources, idx_small, t=2))
    assert np.abs(got[:, : g.n] - oracle).sum(axis=1).max() <= 1e-5


def test_one_shard_truncated_wire_bounded(setup):
    g, dense, n_pad = setup
    cap = verd_mod.resolve_degree_cap(g)
    base = dict(n=n_pad, ep=1, q_tile=4, t_iterations=2, index_l=16,
                top_k=n_pad, degree_cap=cap)
    idx = index_from_dense(dense, l=16)
    ivals = idx.values.reshape(1, n_pad, 16)
    iidx = idx.indices.reshape(1, n_pad, 16)
    sources = jnp.asarray([0, 5, 17, 42], jnp.int32)
    mesh = jax.make_mesh((1, 1), ("data", "model"))

    outs = {}
    for name, kw in [("exact", dict(frontier_k=n_pad)),
                     ("trunc", dict(frontier_k=4, wire_k=4))]:
        cfg = DistConfig(**base, **kw)
        slabs = build_sharded_graph(g, cfg)
        step = make_verd_tile_step(cfg, mesh, kernel_interpret=True)
        with mesh:
            tv, ti = jax.jit(step)(slabs, sources, ivals, iidx)
        outs[name] = _densify(tv, ti, n_pad)
    exact, trunc = outs["exact"], outs["trunc"]
    assert (trunc <= exact + 1e-6).all()
    dropped = exact.sum(axis=1) - trunc.sum(axis=1)
    l1 = np.abs(exact - trunc).sum(axis=1)
    assert (l1 <= dropped + 1e-5).all()


def test_wire_bytes_reduction_at_acceptance_point():
    """Acceptance gate: >= 5x fewer wire bytes/iteration than the dense
    exchange at n=100k, Q=256, K=512 (the bench_query report)."""
    cfg = DistConfig(n=100_000, ep=4, q_tile=256, frontier_k=512,
                     wire_k=512, degree_cap=1)
    bytes_ = exchange_bytes_per_iteration(cfg)
    assert bytes_["reduction"] >= 5.0, bytes_
    # dense slab: qt * n * 4B; sparse: qt * ep * wire_k * 8B
    assert bytes_["dense"] == 256 * 100_000 * 4
    assert bytes_["sparse"] == 256 * 4 * 512 * 8


def test_compress_k_is_deprecated():
    with pytest.warns(DeprecationWarning, match="compress_k"):
        cfg = DistConfig(n=64, ep=2, compress_k=16)
    # the knob now only feeds the sparse wire width when wire_k is unset
    assert cfg.resolved_wire_k == 16


def test_engine_routes_through_fused_kernel(setup):
    """Routing regression: the sparse tile step must go through the fused
    Pallas wrapper ``kernels.ops.sharded_frontier_push`` (once per VERD
    iteration at trace time), not a duplicated jnp path — while still
    matching the dense oracle."""
    from repro.kernels import ops as kernel_ops

    g, dense, n_pad = setup
    cap = verd_mod.resolve_degree_cap(g)
    cfg = DistConfig(
        n=n_pad, ep=1, q_tile=4, t_iterations=2, index_l=16, top_k=n_pad,
        frontier_k=n_pad, degree_cap=cap,
    )
    slabs = build_sharded_graph(g, cfg)
    idx = index_from_dense(dense, l=cfg.index_l)
    ivals = idx.values.reshape(1, cfg.n_shard, cfg.index_l)
    iidx = idx.indices.reshape(1, cfg.n_shard, cfg.index_l)
    sources = jnp.asarray([0, 5, 17, 42], jnp.int32)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    step = make_verd_tile_step(cfg, mesh, kernel_interpret=True)
    kernel_ops.reset_kernel_invocations()
    with mesh:
        tv, ti = jax.jit(step)(slabs, sources, ivals, iidx)
    counts = kernel_ops.kernel_invocations()
    assert counts.get("sharded_frontier_push", 0) == cfg.t_iterations, counts

    idx_small = index_from_dense(dense[: g.n, : g.n], l=cfg.index_l)
    oracle = np.asarray(verd_mod.verd_query(g, sources, idx_small, t=2))
    got = _densify(tv, ti, n_pad)
    assert np.abs(got[:, : g.n] - oracle).sum(axis=1).max() <= 1e-5


def test_sparse_exchange_requires_degree_cap():
    cfg = DistConfig(n=64, ep=2)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="degree_cap"):
        make_verd_tile_step(cfg, mesh)


def test_rejects_unknown_exchange():
    with pytest.raises(ValueError, match="exchange"):
        DistConfig(n=64, ep=2, exchange="bogus")


@pytest.mark.slow  # spawns a 4-device subprocess
def test_four_shard_parity_suite():
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    script = os.path.join(os.path.dirname(__file__), "parity_check.py")
    res = subprocess.run(
        [sys.executable, script], env=env, capture_output=True, text=True,
        timeout=900,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    assert "ALL OK" in res.stdout
