"""Pallas-kernel validation: interpret-mode sweeps vs pure-jnp oracles,
plus the HBM-residency kernel contract (no CSR/index whole-array VMEM
blocks; boundary cases the resident-block kernels never exercised)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import push_forward
from repro.graphs import formats, synthetic
from repro.kernels import frontier_push as push_mod
from repro.kernels import index_combine as comb_mod
from repro.kernels import ops, ref
from repro.kernels import walk_step as walk_mod
from repro.kernels.ell_spmm import ell_spmm, vmem_bytes
from repro.kernels.embedding_bag import embedding_bag as bag_kernel
from repro.kernels.index_combine import index_combine as comb_kernel

TOL = dict(
    float32=dict(rtol=1e-5, atol=1e-6),
    bfloat16=dict(rtol=2e-2, atol=2e-2),
)


def _tols(dtype):
    return TOL[jnp.dtype(dtype).name]


# ---------------------------------------------------------------------------
# ell_spmm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,rows,k,n", [
    (8, 256, 8, 64),
    (16, 512, 16, 128),
    (8, 256, 4, 32),
    (24, 768, 32, 200),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_ell_spmm_matches_ref(q, rows, k, n, dtype, rng):
    f = jnp.asarray(rng.random((q, n)), dtype)
    nbr = jnp.asarray(rng.integers(0, n, (rows, k)), jnp.int32)
    w = jnp.asarray(rng.random((rows, k)), dtype)
    got = ell_spmm(f, nbr, w, q_tile=8, r_tile=256, interpret=True)
    want = ref.ell_spmm_ref(f, nbr, w)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        **_tols(dtype),
    )


def test_ell_spmm_bf16(rng):
    f = jnp.asarray(rng.random((8, 64)), jnp.bfloat16)
    nbr = jnp.asarray(rng.integers(0, 64, (256, 8)), jnp.int32)
    w = jnp.asarray(rng.random((256, 8)), jnp.bfloat16)
    got = ell_spmm(f, nbr, w, q_tile=8, r_tile=256, interpret=True)
    want = ref.ell_spmm_ref(
        f.astype(jnp.float32), nbr, w.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), **TOL["bfloat16"]
    )


def test_ell_push_equals_graph_push(rng):
    """End-to-end: Pallas ELL push == edge-parallel push_forward."""
    g = synthetic.rmat(8, avg_deg=6.0, seed=5)
    ell = formats.to_ell_chunks(g, k=8)
    f = jnp.asarray(rng.random((5, g.n)), jnp.float32)
    got = ops.ell_push(f, ell, q_tile=8, r_tile=256, interpret=True)
    want = push_forward(g, f)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
    )


def test_ell_pull_pure_jnp_equals_push(rng):
    g = synthetic.erdos_renyi(100, 5.0, seed=4)
    ell = formats.to_ell_chunks(g, k=4)
    f = jnp.asarray(rng.random((3, g.n)), jnp.float32)
    got = formats.ell_pull(ell, f)
    want = push_forward(g, f)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
    )


def test_ell_hub_splitting():
    """A hub with in-degree >> k must fold correctly across chunk rows."""
    g = synthetic.star(50)  # every spoke points at vertex 0
    ell = formats.to_ell_chunks(g, k=4)
    f = jnp.ones((1, g.n), jnp.float32)
    got = ops.ell_push(f, ell, interpret=True)
    want = push_forward(g, f)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_vmem_budget_accounting():
    assert vmem_bytes(8, 256, 16, 4096) < 16 * 1024 * 1024


# ---------------------------------------------------------------------------
# index_combine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("q,n,l", [(8, 128, 8), (16, 256, 16), (4, 64, 4)])
def test_index_combine_matches_ref(q, n, l, rng):
    s = jnp.asarray(rng.random((q, n)), jnp.float32)
    f = jnp.asarray(rng.random((q, n)), jnp.float32)
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    got = comb_kernel(s, f, vals, idx, q_tile=4, v_tile=64, interpret=True)
    want = ref.index_combine_ref(s, f, vals, idx)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
    )


def test_index_combine_wrapper_pads(rng):
    q, n, l = 5, 100, 7  # deliberately unaligned
    s = jnp.asarray(rng.random((q, n)), jnp.float32)
    f = jnp.asarray(rng.random((q, n)), jnp.float32)
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    got = ops.index_combine(s, f, vals, idx, interpret=True)
    want = ref.index_combine_ref(s, f, vals, idx)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
    )


def test_index_combine_matches_core_combine(rng):
    """Kernel == the chunked-scan implementation in core.verd."""
    from repro.core.index import index_from_dense
    from repro.core.verd import combine_with_index

    q, n, l = 6, 96, 12
    s = jnp.asarray(rng.random((q, n)), jnp.float32)
    f = jnp.asarray(rng.random((q, n)), jnp.float32)
    dense = jnp.asarray(rng.random((n, n)), jnp.float32)
    idx = index_from_dense(dense, l=l)
    want = combine_with_index(s, f, idx, vertex_chunk=32)
    got = ops.index_combine(s, f, idx.values, idx.indices, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-4, atol=1e-5
    )


# ---------------------------------------------------------------------------
# frontier_push + index_combine_sparse (sparse online path)
# ---------------------------------------------------------------------------

def _frontier_fixture(rng, n=60, q=5):
    from repro.core import verd as verd_mod

    g = synthetic.erdos_renyi(n, 4.0, seed=11)
    srcs = jnp.asarray(rng.integers(0, n, q), jnp.int32)
    cap = verd_mod.resolve_degree_cap(g)
    return g, srcs, cap


def test_frontier_push_kernel_matches_ref(rng):
    from repro.core import frontier as F

    g, srcs, cap = _frontier_fixture(rng)
    f0 = F.from_sources(srcs, g.n)
    got = ops.frontier_push(
        f0, g, srcs, c=0.15, degree_cap=cap, k_out=16, interpret=True
    )
    rv, ri = ref.frontier_push_ref(
        f0.values, f0.indices, srcs, g.row_ptr, g.out_deg, g.col_idx,
        c=0.15, degree_cap=cap, k_out=16,
    )
    want = F.SparseFrontier(values=rv, indices=ri, k=16, n=g.n)
    np.testing.assert_allclose(
        np.asarray(got.densify()), np.asarray(want.densify()),
        rtol=1e-5, atol=1e-6,
    )


def test_frontier_push_kernel_two_iterations(rng):
    """Kernel iterated == verd_iterate_sparse's f after two pushes."""
    from repro.core import frontier as F
    from repro.core import verd as verd_mod

    g, srcs, cap = _frontier_fixture(rng)
    k = g.n
    f = F.from_sources(srcs, g.n)
    for _ in range(2):
        f = ops.frontier_push(
            f, g, srcs, c=0.15, degree_cap=cap, k_out=k, interpret=True
        )
    _, f_want = verd_mod.verd_iterate_sparse(g, srcs, t=2, k=k, c=0.15)
    np.testing.assert_allclose(
        np.asarray(f.densify()), np.asarray(f_want.densify()),
        rtol=1e-5, atol=1e-6,
    )


def test_index_combine_sparse_kernel_matches_ref(rng):
    from repro.core import frontier as F
    from repro.core import verd as verd_mod
    from repro.core.index import index_from_dense

    g, srcs, cap = _frontier_fixture(rng)
    dense = jnp.asarray(rng.random((g.n, g.n)), jnp.float32)
    idx = index_from_dense(dense, l=12)
    s, f = verd_mod.verd_iterate_sparse(g, srcs, t=2, k=g.n, degree_cap=cap)
    got = ops.index_combine_sparse(
        s, f, idx.values, idx.indices, k_out=10, interpret=True
    )
    rv, ri = ref.index_combine_sparse_ref(
        s.values, s.indices, f.values, f.indices, idx.values, idx.indices,
        k_out=10,
    )
    want = F.SparseFrontier(values=rv, indices=ri, k=10, n=g.n)
    np.testing.assert_allclose(
        np.asarray(got.densify()), np.asarray(want.densify()),
        rtol=1e-5, atol=1e-6,
    )
    # the fused sparse combine also equals the jnp core implementation
    core = verd_mod.combine_with_index_sparse(s, f, idx, out_k=10)
    np.testing.assert_allclose(
        np.asarray(got.densify()), np.asarray(core.densify()),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# sharded_frontier_push (distributed sparse-exchange half-iteration)
# ---------------------------------------------------------------------------

def _dens_buckets(vals, idx, ep, ns):
    """Scatter per-owner buckets back to dense [Q, ep, ns] for comparison
    (bucket top-k order may tie-break differently than the oracle's)."""
    from conftest import densify_rows

    return np.stack(
        [densify_rows(np.asarray(vals)[:, o], np.asarray(idx)[:, o], ns)
         for o in range(ep)],
        axis=1,
    )


@pytest.mark.parametrize("q,k,shards,hub_split_degree", [
    (5, 8, 1, 0),      # degenerate 1-shard case
    (5, 8, 1, 2),
    (8, 16, 2, 0),
    (8, 16, 2, 3),
    (3, 4, 4, 0),
    (3, 4, 4, 1),
])
def test_sharded_push_kernel_matches_ref(q, k, shards, hub_split_degree, rng):
    from repro.core import verd as verd_mod
    from repro.core.distributed_engine import DistConfig, build_sharded_graph

    g = synthetic.erdos_renyi(60, 4.0, seed=11)
    cap = verd_mod.resolve_degree_cap(g)
    n_pad = 64
    cfg = DistConfig(n=n_pad, ep=shards, degree_cap=cap)
    slabs = build_sharded_graph(g, cfg)
    ns = cfg.n_shard
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, ns, (q, k)), jnp.int32)
    for s in range(shards):
        got_v, got_i = ops.sharded_frontier_push(
            fv, fi, slabs.row_ptr[s], slabs.col_idx[s],
            c=0.15, degree_cap=cap, ep=shards, n_shard=ns, wire_k=ns,
            hub_split_degree=hub_split_degree, q_tile=1, interpret=True,
        )
        ref_v, ref_i = ref.sharded_push_ref(
            fv, fi, slabs.row_ptr[s], slabs.col_idx[s].reshape(-1),
            c=0.15, ep=shards, n_shard=ns, wire_k=ns,
        )
        np.testing.assert_allclose(
            _dens_buckets(got_v, got_i, shards, ns),
            _dens_buckets(ref_v, ref_i, shards, ns),
            rtol=1e-5, atol=1e-6,
        )


def test_sharded_push_truncated_wire_is_top_k(rng):
    """wire_k below the owner support keeps exactly the per-owner top-k."""
    from repro.core import verd as verd_mod
    from repro.core.distributed_engine import DistConfig, build_sharded_graph

    g = synthetic.erdos_renyi(60, 4.0, seed=11)
    cap = verd_mod.resolve_degree_cap(g)
    cfg = DistConfig(n=64, ep=2, degree_cap=cap)
    slabs = build_sharded_graph(g, cfg)
    ns = cfg.n_shard
    fv = jnp.asarray(rng.random((4, 8)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, ns, (4, 8)), jnp.int32)
    wire_k = 4
    got_v, _ = ops.sharded_frontier_push(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0],
        c=0.15, degree_cap=cap, ep=2, n_shard=ns, wire_k=wire_k,
        q_tile=4, interpret=True,
    )
    full_v, full_i = ref.sharded_push_ref(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0].reshape(-1),
        c=0.15, ep=2, n_shard=ns, wire_k=ns,
    )
    want = np.sort(np.asarray(full_v), axis=2)[:, :, ::-1][:, :, :wire_k]
    np.testing.assert_allclose(
        np.sort(np.asarray(got_v), axis=2)[:, :, ::-1], want,
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# embedding_bag
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,bag,v,d", [
    (64, 4, 100, 128),
    (128, 16, 50, 256),
    (64, 1, 10, 128),
])
def test_embedding_bag_matches_ref(b, bag, v, d, rng):
    ids = jnp.asarray(rng.integers(0, v, (b, bag)), jnp.int32)
    mask = jnp.asarray(rng.random((b, bag)) > 0.3, jnp.float32)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    got = bag_kernel(ids, mask, table, b_tile=64, d_tile=128, interpret=True)
    want = ref.embedding_bag_ref(ids, mask, table)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


def test_embedding_bag_wrapper_unaligned(rng):
    b, bag, v, d = 37, 3, 20, 48  # unaligned batch and dim
    ids = jnp.asarray(rng.integers(0, v, (b, bag)), jnp.int32)
    mask = jnp.ones((b, bag), jnp.float32)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    got = ops.embedding_bag(ids, mask, table, interpret=True)
    want = ref.embedding_bag_ref(ids, mask, table)
    assert got.shape == (b, d)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5
    )


# ---------------------------------------------------------------------------
# HBM-residency kernel contract (the DMA-gather rewrite)
#
# Two halves: (a) a mechanical memory contract — tracing each DMA kernel
# and asserting that no CSR/index array enters as a whole-array VMEM block
# (only `pl.ANY`/HBM refs + tile-sized VMEM blocks), (b) the boundary
# cases the old resident-block kernels never exercised: ragged last q_tile,
# k_out wider than the candidate set, empty frontiers, all-dangling rows,
# single-row grids.
# ---------------------------------------------------------------------------

# The jaxpr-walking logic lives in repro.analysis.jaxpr (PR 10) — the same
# engine `python -m repro.analysis` runs; these aliases keep the test bodies
# unchanged while guaranteeing the contract logic cannot drift across copies.
from repro.analysis.jaxpr import (  # noqa: E402
    assert_hbm_contract as _assert_hbm_contract,
    pallas_block_specs as _pallas_block_specs,
)


def _contract_fixture(rng, n=2048, avg_deg=6.0, q=16, k=8):
    from repro.core import verd as verd_mod

    g = synthetic.erdos_renyi(n, avg_deg, seed=7)
    cap = verd_mod.resolve_degree_cap(g)
    srcs = jnp.asarray(rng.integers(0, n, q), jnp.int32)
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, n, (q, k)), jnp.int32)
    return g, srcs, cap, fv, fi


@pytest.mark.parametrize("hub_split_degree", [0, 2])
def test_frontier_push_memory_contract(rng, hub_split_degree):
    """CSR arrays never enter the kernel as VMEM blocks: col_idx is an
    ANY/HBM ref, row_ptr/out_deg only feed O(Q*K) offset gathers outside,
    and every VMEM block is tile-sized (independent of n and m)."""
    from repro.core import verd as verd_mod

    g, srcs, cap, fv, fi = _contract_fixture(rng, n=16384)
    q_tile, k_out = 1, 16
    blocks = _pallas_block_specs(
        push_mod.frontier_push, fv, fi, srcs,
        g.row_ptr, g.out_deg, g.col_idx,
        c=0.15, degree_cap=cap, k_out=k_out, q_tile=q_tile,
        hub_split_degree=hub_split_degree, interpret=True,
    )
    h, s = verd_mod.resolve_hub_splits(cap, hub_split_degree)
    budget = push_mod.window_step_rows(q_tile * fv.shape[1] * s, h) * 128
    assert budget < g.m and budget < g.n  # the assertion below is meaningful
    _assert_hbm_contract(
        blocks, hbm_shapes={push_mod.lane_rows_shape(g.m)},
        vmem_budget=budget,
    )
    # and the CSR arrays specifically never appear as VMEM blocks
    for csr_shape in [(g.n + 1,), (g.n,), (g.m,)]:
        assert all(
            space == "any" for shape, space in blocks if shape == csr_shape
        )


def test_sharded_push_memory_contract(rng):
    from repro.core import verd as verd_mod
    from repro.core.distributed_engine import DistConfig, build_sharded_graph

    g, _, cap, fv, fi = _contract_fixture(rng, n=16384)
    cfg = DistConfig(n=16384, ep=2, degree_cap=cap)
    slabs = build_sharded_graph(g, cfg)
    ns = cfg.n_shard
    fi_local = jnp.clip(fi, 0, ns - 1)
    q_tile, wire_k = 1, 8
    m_shard = slabs.col_idx[0].size     # the slab, stored as lane rows
    blocks = _pallas_block_specs(
        push_mod.sharded_frontier_push, fv, fi_local,
        slabs.row_ptr[0], slabs.col_idx[0],
        c=0.15, degree_cap=cap, ep=2, n_shard=ns, wire_k=wire_k,
        q_tile=q_tile, interpret=True,
    )
    h, s = verd_mod.resolve_hub_splits(cap, 0)
    budget = push_mod.window_step_rows(q_tile * fv.shape[1] * s, h) * 128
    assert budget < m_shard and budget < ns
    _assert_hbm_contract(
        blocks, hbm_shapes={slabs.col_idx.shape[1:]},
        vmem_budget=budget,
    )


def test_index_combine_sparse_memory_contract(rng):
    n, l, q, k, s_w = 600, 16, 16, 8, 8
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    sv = jnp.asarray(rng.random((q, s_w)), jnp.float32)
    si = jnp.asarray(rng.integers(0, n, (q, s_w)), jnp.int32)
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, n, (q, k)), jnp.int32)
    q_tile, k_out = 8, 16
    blocks = _pallas_block_specs(
        comb_mod.index_combine_sparse, sv, si, fv, fi, vals, idx,
        k_out=k_out, q_tile=q_tile, interpret=True,
    )
    budget = comb_mod.row_step_rows(q_tile * k, l) * 128
    assert budget < n * l
    padded = comb_mod.padded_index_shape(n, l)
    _assert_hbm_contract(blocks, hbm_shapes={padded}, vmem_budget=budget)
    # both [n, L] index arrays must be HBM refs
    assert sum(
        1 for shape, space in blocks if shape == padded and space == "any"
    ) == 2


# -- boundary cases vs the dense oracles ------------------------------------

def _push_vs_ref(f0, g, srcs, *, k_out, q_tile=4, threshold=0.0, c=0.15,
                 hub_split_degree=0):
    from repro.core import frontier as F
    from repro.core import verd as verd_mod

    cap = verd_mod.resolve_degree_cap(g)
    got = ops.frontier_push(
        f0, g, srcs, c=c, degree_cap=cap, k_out=k_out, q_tile=q_tile,
        threshold=threshold, hub_split_degree=hub_split_degree,
        interpret=True,
    )
    rv, ri = ref.frontier_push_ref(
        f0.values, f0.indices, srcs, g.row_ptr, g.out_deg, g.col_idx,
        c=c, degree_cap=cap, k_out=k_out, threshold=threshold,
    )
    want = F.SparseFrontier(values=rv, indices=ri, k=k_out, n=g.n)
    np.testing.assert_allclose(
        np.asarray(got.densify()), np.asarray(want.densify()),
        rtol=1e-5, atol=1e-6,
    )
    return got


@pytest.mark.parametrize("q", [1, 3, 5, 7])
def test_frontier_push_ragged_last_tile(q, rng):
    """Q not a multiple of q_tile: the wrapper pads, pad rows stay empty."""
    from repro.core import frontier as F

    g = synthetic.erdos_renyi(60, 4.0, seed=11)
    srcs = jnp.asarray(rng.integers(0, g.n, q), jnp.int32)
    f0 = F.from_sources(srcs, g.n)
    got = _push_vs_ref(f0, g, srcs, k_out=12, q_tile=4)
    assert got.values.shape == (q, 12)


def test_frontier_push_k_out_wider_than_candidates(rng):
    """k_out beyond the candidate width: right-padded with empty slots."""
    from repro.core import frontier as F

    g = synthetic.erdos_renyi(30, 3.0, seed=2)
    srcs = jnp.asarray(rng.integers(0, g.n, 4), jnp.int32)
    f0 = F.from_sources(srcs, g.n)  # width-1 frontier: few candidates
    got = _push_vs_ref(f0, g, srcs, k_out=g.n, q_tile=4)
    # the padded tail obeys the empty-slot convention (0.0 at index 0)
    tail_mask = np.asarray(got.values) == 0
    assert (np.asarray(got.indices)[tail_mask] == 0).all()


def test_frontier_push_empty_frontier(rng):
    """All-zero frontier rows push nothing — not even dangling mass."""
    from repro.core import frontier as F

    g = synthetic.erdos_renyi(40, 4.0, seed=3)
    q, k = 5, 6
    f0 = F.SparseFrontier(
        values=jnp.zeros((q, k), jnp.float32),
        indices=jnp.zeros((q, k), jnp.int32), k=k, n=g.n,
    )
    srcs = jnp.asarray(rng.integers(0, g.n, q), jnp.int32)
    got = _push_vs_ref(f0, g, srcs, k_out=8)
    assert float(jnp.abs(got.values).max()) == 0.0
    assert int(jnp.abs(got.indices).max()) == 0


def test_frontier_push_all_dangling_rows(rng):
    """Frontier entirely on dangling vertices: every row's mass returns to
    its source as one (1-c)-weighted entry."""
    from repro.core import frontier as F

    # vertices 0..3 have edges; 4..9 are dangling
    src_e = np.array([0, 0, 1, 2, 3], np.int32)
    dst_e = np.array([1, 2, 3, 0, 1], np.int32)
    from repro.core.graph import Graph

    g = Graph.from_edges(src_e, dst_e, n=10)
    q = 3
    srcs = jnp.asarray([4, 5, 6], jnp.int32)
    fi = jnp.asarray(rng.integers(4, 10, (q, 4)), jnp.int32)
    fv = jnp.asarray(rng.random((q, 4)), jnp.float32)
    f0 = F.SparseFrontier(values=fv, indices=fi, k=4, n=g.n)
    got = _push_vs_ref(f0, g, srcs, k_out=6)
    dense = np.asarray(got.densify())
    want = np.zeros_like(dense)
    want[np.arange(q), np.asarray(srcs)] = 0.85 * np.asarray(fv).sum(axis=1)
    np.testing.assert_allclose(dense, want, rtol=1e-5, atol=1e-6)


def test_frontier_push_single_row_grid(rng):
    """Q == q_tile == 1: a one-step grid with a one-query tile."""
    from repro.core import frontier as F

    g = synthetic.erdos_renyi(50, 4.0, seed=5)
    srcs = jnp.asarray([7], jnp.int32)
    f0 = F.from_sources(srcs, g.n)
    got = _push_vs_ref(f0, g, srcs, k_out=10, q_tile=1)
    assert got.values.shape == (1, 10)


def test_sharded_push_ragged_and_empty(rng):
    """Sharded push: ragged Q + an all-zero frontier row in the same run."""
    from repro.core import verd as verd_mod
    from repro.core.distributed_engine import DistConfig, build_sharded_graph

    g = synthetic.erdos_renyi(60, 4.0, seed=11)
    cap = verd_mod.resolve_degree_cap(g)
    cfg = DistConfig(n=64, ep=2, degree_cap=cap)
    slabs = build_sharded_graph(g, cfg)
    ns = cfg.n_shard
    q, k = 5, 8  # ragged vs q_tile=4
    fv = jnp.asarray(rng.random((q, k)), jnp.float32).at[2].set(0.0)
    fi = jnp.asarray(rng.integers(0, ns, (q, k)), jnp.int32)
    got_v, got_i = ops.sharded_frontier_push(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0],
        c=0.15, degree_cap=cap, ep=2, n_shard=ns, wire_k=ns,
        q_tile=4, interpret=True,
    )
    ref_v, ref_i = ref.sharded_push_ref(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0].reshape(-1),
        c=0.15, ep=2, n_shard=ns, wire_k=ns,
    )
    np.testing.assert_allclose(
        _dens_buckets(got_v, got_i, 2, ns),
        _dens_buckets(ref_v, ref_i, 2, ns),
        rtol=1e-5, atol=1e-6,
    )
    assert got_v.shape == (q, 2, ns)
    assert float(jnp.abs(got_v[2]).max()) == 0.0  # empty row stays empty


def test_sharded_push_wire_k_above_owner_support(rng):
    """wire_k > n_shard: buckets are right-padded, never truncated."""
    from repro.core import verd as verd_mod
    from repro.core.distributed_engine import DistConfig, build_sharded_graph

    g = synthetic.erdos_renyi(24, 3.0, seed=4)
    cap = verd_mod.resolve_degree_cap(g)
    cfg = DistConfig(n=24, ep=2, degree_cap=cap)
    slabs = build_sharded_graph(g, cfg)
    ns = cfg.n_shard
    fv = jnp.asarray(rng.random((4, 4)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, ns, (4, 4)), jnp.int32)
    wire_k = ns + 5
    got_v, got_i = ops.sharded_frontier_push(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0],
        c=0.15, degree_cap=cap, ep=2, n_shard=ns, wire_k=wire_k,
        q_tile=4, interpret=True,
    )
    ref_v, ref_i = ref.sharded_push_ref(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0].reshape(-1),
        c=0.15, ep=2, n_shard=ns, wire_k=wire_k,
    )
    np.testing.assert_allclose(
        _dens_buckets(got_v, got_i, 2, ns),
        _dens_buckets(ref_v, ref_i, 2, ns),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.parametrize("q,q_tile,k_out", [(3, 4, 40), (1, 1, 5), (6, 4, 7)])
def test_index_combine_sparse_boundaries(q, q_tile, k_out, rng):
    """Ragged Q, single-row grid, and k_out beyond the candidate width."""
    from repro.core import frontier as F

    n, l, k, s_w = 30, 6, 4, 5
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    sv = jnp.asarray(rng.random((q, s_w)), jnp.float32)
    si = jnp.asarray(rng.integers(0, n, (q, s_w)), jnp.int32)
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, n, (q, k)), jnp.int32)
    s = F.SparseFrontier(values=sv, indices=si, k=s_w, n=n)
    f = F.SparseFrontier(values=fv, indices=fi, k=k, n=n)
    got = ops.index_combine_sparse(
        s, f, vals, idx, k_out=k_out, q_tile=q_tile, interpret=True
    )
    rv, ri = ref.index_combine_sparse_ref(
        sv, si, fv, fi, vals, idx, k_out=k_out
    )
    want = F.SparseFrontier(values=rv, indices=ri, k=k_out, n=n)
    np.testing.assert_allclose(
        np.asarray(got.densify()), np.asarray(want.densify()),
        rtol=1e-5, atol=1e-6,
    )
    assert got.values.shape == (q, k_out)


def test_index_combine_sparse_empty_frontier(rng):
    """Zero frontier: the combine degenerates to compacting s alone."""
    from repro.core import frontier as F

    n, l, q, k = 20, 4, 4, 3
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    sv = jnp.asarray(rng.random((q, 5)), jnp.float32)
    si = jnp.asarray(rng.integers(0, n, (q, 5)), jnp.int32)
    s = F.SparseFrontier(values=sv, indices=si, k=5, n=n)
    f = F.SparseFrontier(
        values=jnp.zeros((q, k), jnp.float32),
        indices=jnp.zeros((q, k), jnp.int32), k=k, n=n,
    )
    got = ops.index_combine_sparse(s, f, vals, idx, k_out=8, interpret=True)
    from repro.core.frontier import compact

    want = compact(sv, si, 8, n)
    np.testing.assert_allclose(
        np.asarray(got.densify()), np.asarray(want.densify()),
        rtol=1e-6, atol=1e-7,
    )


@pytest.mark.parametrize("hub_split_degree", [0, 3])
def test_frontier_push_window_clip_at_csr_end(rng, hub_split_degree):
    """A hub whose row *closes* col_idx forces the last gather window past
    ``m - h``: the clip-shift path (``d > 0`` in masked_push_from_windows)
    must still deliver exactly the dense oracle's push.  (Hypothesis sweeps
    this with random hub placements in test_properties.py; this is the
    deterministic in-container regression.)"""
    from repro.core import frontier as F
    from repro.core.graph import Graph

    n, hub_deg = 12, 7
    src_e = np.concatenate([
        np.array([0, 1, 2, 3], np.int32),
        np.full(hub_deg, n - 1, np.int32),   # hub row ends the edge array
    ])
    dst_e = np.concatenate([
        np.array([1, 2, 3, 0], np.int32),
        np.arange(hub_deg, dtype=np.int32),
    ])
    g = Graph.from_edges(src_e, dst_e, n=n)
    q = 3
    fv = jnp.asarray(rng.random((q, 2)), jnp.float32)
    fi = jnp.asarray([[n - 1, 0], [1, n - 1], [n - 1, n - 1]], jnp.int32)
    srcs = jnp.asarray(rng.integers(0, n, q), jnp.int32)
    f0 = F.SparseFrontier(values=fv, indices=fi, k=2, n=n)
    _push_vs_ref(
        f0, g, srcs, k_out=n, q_tile=1, hub_split_degree=hub_split_degree
    )


# -- offsets past one call's SMEM: split over several pallas_calls ----------

def _pallas_calls(fn, *args) -> int:
    eqns = jax.make_jaxpr(fn)(*args).jaxpr.eqns
    return sum(e.primitive.name == "pallas_call" for e in eqns)


def test_gather_windows_splits_past_smem_offsets(rng):
    """More window pieces than one call's scalar prefetch holds: the
    offsets split over several pallas_calls and the result is still the
    plain gather (windows of two 128-wide pieces, capped step rows)."""
    m, h = 4096, 200
    col = jnp.asarray(rng.integers(0, 1000, m), jnp.int32)
    starts = jnp.asarray(
        rng.integers(0, m - h, push_mod.SMEM_OFFSETS // 2 + 300), jnp.int32)

    def gather(c, st):
        return push_mod.gather_windows(
            push_mod.lane_rows(c), st, h=h, step_windows=1 << 16,
            interpret=True)

    assert _pallas_calls(gather, col, starts) >= 2
    np.testing.assert_array_equal(
        np.asarray(gather(col, starts)),
        np.asarray(jnp.take(col, starts[:, None] + jnp.arange(h))))


def test_gather_index_rows_splits_past_smem_offsets(rng):
    """More touched rows than one call's scalar prefetch holds, from an
    index whose width is not a multiple of 128."""
    n, l = 600, 200
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    rows = jnp.asarray(
        rng.integers(0, n, push_mod.SMEM_OFFSETS + 500), jnp.int32)

    def gather(v, i, r):
        return comb_mod.gather_index_rows(
            v, i, r, step_rows=1 << 16, interpret=True)

    assert _pallas_calls(gather, vals, idx, rows) >= 2
    got_v, got_i = gather(vals, idx, rows)
    np.testing.assert_array_equal(np.asarray(got_v), np.asarray(vals[rows]))
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(idx[rows]))


def test_frontier_push_past_smem_offsets_matches_ref(rng):
    """The fused push at a window count past one call's SMEM (Q * K * s >
    SMEM_OFFSETS, hub-split sub-slots) still matches its oracle."""
    from repro.core import frontier as F
    from repro.core import verd as verd_mod

    g = synthetic.erdos_renyi(2048, 6.0, seed=7)
    cap = verd_mod.resolve_degree_cap(g)
    q, k, split = 128, 256, 4
    _, s = verd_mod.resolve_hub_splits(cap, split)
    assert q * k * s > push_mod.SMEM_OFFSETS
    srcs = jnp.asarray(rng.integers(0, g.n, q), jnp.int32)
    f = F.SparseFrontier(
        values=jnp.asarray(rng.random((q, k)), jnp.float32),
        indices=jnp.asarray(rng.integers(0, g.n, (q, k)), jnp.int32),
        k=k, n=g.n)
    k_out = k * cap + 1                   # covers every row's support
    got = ops.frontier_push(
        f, g, srcs, c=0.15, degree_cap=cap, k_out=k_out,
        hub_split_degree=split, interpret=True)
    rv, ri = ref.frontier_push_ref(
        f.values, f.indices, srcs, g.row_ptr, g.out_deg, g.col_idx,
        c=0.15, degree_cap=cap, k_out=k_out)
    np.testing.assert_allclose(
        np.asarray(got.densify()),
        np.asarray(F.SparseFrontier(
            values=rv, indices=ri, k=k_out, n=g.n).densify()),
        rtol=1e-5, atol=1e-6)


# -- VMEM accounting + compiled-mode (real TPU) gates -----------------------

def test_push_vmem_accounting_independent_of_graph_size():
    """HBM-resident per-step VMEM must not grow with n or m; the legacy
    accounting (whole-array CSR blocks) must."""
    small = push_mod.vmem_bytes(8, 64, degree_cap=16)
    assert small == push_mod.vmem_bytes(8, 64, degree_cap=16)
    legacy_small = push_mod.vmem_bytes_legacy(
        8, 64, n=1_000, m=8_000, degree_cap=16
    )
    legacy_big = push_mod.vmem_bytes_legacy(
        8, 64, n=1_000_000, m=8_000_000, degree_cap=16
    )
    assert legacy_big > legacy_small > small
    # the per-step block is capped: a cap-4096 gather, split into width-64
    # sub-slots or not, stays within the 16 MiB scoped VMEM
    assert push_mod.vmem_bytes(
        8, 64, degree_cap=4096, hub_split_degree=64
    ) == push_mod.vmem_bytes(8, 64, degree_cap=4096) < 16 * 1024 * 1024
    comb_small = comb_mod.sparse_vmem_bytes(8, 64, 32)
    comb_legacy = comb_mod.sparse_vmem_bytes_legacy(
        8, 64, 32, n=1_000_000
    )
    assert comb_legacy > comb_small
    assert comb_mod.sparse_vmem_bytes(8, 512, 667) < 16 * 1024 * 1024


@pytest.mark.tpu
def test_frontier_push_compiled(rng):
    """interpret=False compile + run — the real-TPU gate for the DMA path."""
    from repro.core import frontier as F

    g = synthetic.erdos_renyi(60, 4.0, seed=11)
    srcs = jnp.asarray(rng.integers(0, g.n, 8), jnp.int32)
    f0 = F.from_sources(srcs, g.n)
    from repro.core import verd as verd_mod

    cap = verd_mod.resolve_degree_cap(g)
    got = ops.frontier_push(
        f0, g, srcs, c=0.15, degree_cap=cap, k_out=16, interpret=False
    )
    rv, ri = ref.frontier_push_ref(
        f0.values, f0.indices, srcs, g.row_ptr, g.out_deg, g.col_idx,
        c=0.15, degree_cap=cap, k_out=16,
    )
    np.testing.assert_allclose(
        np.asarray(got.densify()),
        np.asarray(F.SparseFrontier(
            values=rv, indices=ri, k=16, n=g.n).densify()),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.tpu
def test_sharded_push_compiled(rng):
    from repro.core import verd as verd_mod
    from repro.core.distributed_engine import DistConfig, build_sharded_graph

    g = synthetic.erdos_renyi(60, 4.0, seed=11)
    cap = verd_mod.resolve_degree_cap(g)
    cfg = DistConfig(n=64, ep=2, degree_cap=cap)
    slabs = build_sharded_graph(g, cfg)
    ns = cfg.n_shard
    fv = jnp.asarray(rng.random((8, 8)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, ns, (8, 8)), jnp.int32)
    got_v, got_i = ops.sharded_frontier_push(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0],
        c=0.15, degree_cap=cap, ep=2, n_shard=ns, wire_k=ns,
        interpret=False,
    )
    ref_v, ref_i = ref.sharded_push_ref(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0].reshape(-1),
        c=0.15, ep=2, n_shard=ns, wire_k=ns,
    )
    np.testing.assert_allclose(
        _dens_buckets(got_v, got_i, 2, ns),
        _dens_buckets(ref_v, ref_i, 2, ns),
        rtol=1e-5, atol=1e-6,
    )


@pytest.mark.tpu
def test_index_combine_sparse_compiled(rng):
    from repro.core import frontier as F

    n, l, q, k = 64, 8, 8, 4
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    s = F.SparseFrontier(
        values=jnp.asarray(rng.random((q, 4)), jnp.float32),
        indices=jnp.asarray(rng.integers(0, n, (q, 4)), jnp.int32),
        k=4, n=n,
    )
    f = F.SparseFrontier(
        values=jnp.asarray(rng.random((q, k)), jnp.float32),
        indices=jnp.asarray(rng.integers(0, n, (q, k)), jnp.int32),
        k=k, n=n,
    )
    got = ops.index_combine_sparse(s, f, vals, idx, k_out=8, interpret=False)
    rv, ri = ref.index_combine_sparse_ref(
        s.values, s.indices, f.values, f.indices, vals, idx, k_out=8
    )
    np.testing.assert_allclose(
        np.asarray(got.densify()),
        np.asarray(F.SparseFrontier(
            values=rv, indices=ri, k=8, n=n).densify()),
        rtol=1e-5, atol=1e-6,
    )


# ---------------------------------------------------------------------------
# walk_step: the offline walk engine's fused bulk advance
# ---------------------------------------------------------------------------

def _walk_fixture(rng, n=512, avg_deg=5.0, w=256):
    g = synthetic.erdos_renyi(n, avg_deg, seed=13)
    cur = jnp.asarray(rng.integers(0, n, w), jnp.int32)
    src = jnp.asarray(rng.integers(0, n, w), jnp.int32)
    u = jnp.asarray(rng.random(w), jnp.float32)
    return g, cur, src, u


@pytest.mark.parametrize("w", [128, 256, 384])
def test_walk_step_matches_ref_bitwise(w, rng):
    """int outputs: the kernel must equal the oracle exactly, not approx."""
    g, cur, src, u = _walk_fixture(rng, w=w)
    got = walk_mod.walk_step(
        cur, src, u, g.row_ptr, g.out_deg, g.col_idx, interpret=True
    )
    want = ref.walk_step_ref(cur, src, u, g.row_ptr, g.out_deg, g.col_idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("w", [1, 5, 130])
def test_walk_step_wrapper_pads_ragged(w, rng):
    """W not a multiple of w_tile: ops.walk_step pads and slices."""
    g, cur, src, u = _walk_fixture(rng, w=max(w, 1))
    cur, src, u = cur[:w], src[:w], u[:w]
    got = ops.walk_step(
        cur, src, u, g.row_ptr, g.out_deg, g.col_idx, interpret=True
    )
    want = ref.walk_step_ref(cur, src, u, g.row_ptr, g.out_deg, g.col_idx)
    assert got.shape == (w,)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_walk_step_wrapper_keeps_2d_shape(rng):
    g, cur, src, u = _walk_fixture(rng, w=96)
    cur2 = cur.reshape(8, 12)
    src2 = src.reshape(8, 12)
    u2 = u.reshape(8, 12)
    got = ops.walk_step(
        cur2, src2, u2, g.row_ptr, g.out_deg, g.col_idx, interpret=True
    )
    assert got.shape == (8, 12)
    want = ref.walk_step_ref(cur, src, u, g.row_ptr, g.out_deg, g.col_idx)
    np.testing.assert_array_equal(np.asarray(got).reshape(-1),
                                  np.asarray(want))


def test_walk_step_dangling_rows_jump_home(rng):
    """Dangling cursors must land on their walk's source, not a gather."""
    from repro.core.graph import Graph

    # vertices 3, 4 dangling; 0-2 form a cycle
    g = Graph.from_edges([0, 1, 2], [1, 2, 0], n=5)
    cur = jnp.asarray([3, 4, 0, 3] * 32, jnp.int32)
    src = jnp.asarray([1, 2, 4, 0] * 32, jnp.int32)
    u = jnp.asarray(np.linspace(0, 0.999, 128), jnp.float32)
    got = np.asarray(ops.walk_step(
        cur, src, u, g.row_ptr, g.out_deg, g.col_idx, interpret=True
    ))
    np.testing.assert_array_equal(got[0::4], 1)   # dangling -> source
    np.testing.assert_array_equal(got[1::4], 2)
    np.testing.assert_array_equal(got[2::4], 1)   # 0's only edge -> 1
    np.testing.assert_array_equal(got[3::4], 0)


def test_walk_step_clip_at_csr_end(rng):
    """The last CSR row's sampled address must stay inside col_idx even at
    u -> 1 (the clipped-window boundary the DMA reads)."""
    from repro.core.graph import Graph

    g = Graph.from_edges([0, 1, 1, 1], [1, 0, 0, 0], n=2)
    cur = jnp.full((128,), 1, jnp.int32)          # the last row, deg 3
    src = jnp.zeros((128,), jnp.int32)
    u = jnp.full((128,), 0.999999, jnp.float32)   # samples the last edge
    got = np.asarray(ops.walk_step(
        cur, src, u, g.row_ptr, g.out_deg, g.col_idx, interpret=True
    ))
    np.testing.assert_array_equal(got, 0)


def test_walk_step_edgeless_fallback(rng):
    from repro.core.graph import Graph

    g = Graph.from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), n=4)
    cur = jnp.asarray([0, 1, 2, 3], jnp.int32)
    src = jnp.asarray([3, 2, 1, 0], jnp.int32)
    u = jnp.zeros((4,), jnp.float32)
    got = ops.walk_step(
        cur, src, u, g.row_ptr, g.out_deg, g.col_idx, interpret=True
    )
    np.testing.assert_array_equal(np.asarray(got), [3, 2, 1, 0])


def test_walk_step_memory_contract(rng):
    """col_idx must ride as an ANY/HBM ref; every VMEM block stays O(w_tile)
    — independent of n and nnz (the DMA-gather discipline)."""
    g, cur, src, u = _walk_fixture(rng, n=4096, w=256)
    blocks = _pallas_block_specs(
        walk_mod.walk_step, cur, src, u, g.row_ptr, g.out_deg, g.col_idx,
        w_tile=128, interpret=True,
    )
    budget = walk_mod.step_walks(128)  # one packed int32 output lane each
    assert budget < g.m and budget < g.n
    _assert_hbm_contract(
        blocks, hbm_shapes={push_mod.lane_rows_shape(g.m)},
        vmem_budget=budget,
    )
    for csr_shape in [(g.n + 1,), (g.n,), (g.m,)]:
        assert all(
            space == "any" for shape, space in blocks if shape == csr_shape
        )


def test_walk_step_vmem_accounting():
    assert walk_mod.vmem_bytes(128) < 64 * 1024
    assert walk_mod.vmem_bytes(128) == walk_mod.vmem_bytes(1024)


@pytest.mark.tpu
def test_walk_step_compiled(rng):
    """interpret=False compile + run — the real-TPU gate for the DMA path."""
    g, cur, src, u = _walk_fixture(rng, w=256)
    got = walk_mod.walk_step(
        cur, src, u, g.row_ptr, g.out_deg, g.col_idx, interpret=False
    )
    want = ref.walk_step_ref(cur, src, u, g.row_ptr, g.out_deg, g.col_idx)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
