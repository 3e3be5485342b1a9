"""Sparse-frontier pipeline: primitives, sparse==dense equivalence, bounded
truncation drift, and the engine/serving routing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import frontier as F
from repro.core import verd as verd_mod
from repro.core.graph import Graph
from repro.core.index import build_index, index_from_dense
from repro.core.query import AUTO_SPARSE_MIN_N, BatchQueryEngine, QueryConfig
from repro.graphs import synthetic


@pytest.fixture(scope="module")
def graph():
    # ER keeps a mix of dangling and multi-out-degree vertices
    return synthetic.erdos_renyi(48, 4.0, seed=7)


@pytest.fixture(scope="module")
def index(graph):
    idx, _ = build_index(graph, r=100, l=16, key=jax.random.PRNGKey(0))
    return idx


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_merge_duplicates_matches_numpy(rng):
    q, w, n = 5, 40, 12
    vals = jnp.asarray(rng.random((q, w)), jnp.float32)
    idxs = jnp.asarray(rng.integers(0, n, (q, w)), jnp.int32)
    mv, mi = F.merge_duplicates(vals, idxs)
    # densified mass per column must be preserved exactly
    got = np.zeros((q, n), np.float32)
    np.add.at(got, (np.arange(q)[:, None], np.asarray(mi)), np.asarray(mv))
    want = np.zeros((q, n), np.float32)
    np.add.at(want, (np.arange(q)[:, None], np.asarray(idxs)), np.asarray(vals))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    # and each column appears in at most one nonzero slot per row
    for r in range(q):
        nz = np.asarray(mi[r])[np.asarray(mv[r]) > 0]
        assert len(nz) == len(set(nz.tolist()))


def test_topk_compact_pads_and_truncates(rng):
    vals = jnp.asarray([[0.5, 0.0, 0.9]], jnp.float32)
    idxs = jnp.asarray([[3, 7, 1]], jnp.int32)
    v, i = F.topk_compact(vals, idxs, 5)  # pad
    assert v.shape == (1, 5)
    np.testing.assert_allclose(np.asarray(v[0, :2]), [0.5, 0.9][::-1])
    assert int(i[0, 1]) == 3 and int(i[0, 0]) == 1
    assert float(v[0, 4]) == 0.0 and int(i[0, 4]) == 0
    v, i = F.topk_compact(vals, idxs, 2)  # truncate
    np.testing.assert_allclose(np.asarray(v[0]), [0.9, 0.5])


def test_densify_sparsify_roundtrip(rng):
    dense = jnp.asarray(rng.random((4, 30)), jnp.float32)
    sf = F.from_dense(dense, 30)
    np.testing.assert_allclose(
        np.asarray(sf.densify()), np.asarray(dense), rtol=1e-6
    )
    # truncating keeps exactly the top-k mass
    sf5 = F.from_dense(dense, 5)
    want = np.sort(np.asarray(dense), axis=1)[:, -5:].sum(axis=1)
    np.testing.assert_allclose(np.asarray(sf5.mass()), want, rtol=1e-6)


def test_from_sources_one_hot(graph):
    srcs = jnp.asarray([0, 5, 11], jnp.int32)
    sf = F.from_sources(srcs, graph.n)
    d = np.asarray(sf.densify())
    assert d.sum() == 3.0
    assert (d[np.arange(3), np.asarray(srcs)] == 1.0).all()


# ---------------------------------------------------------------------------
# sparse VERD == dense VERD when K covers the support
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("t", [0, 1, 2, 3])
def test_iterate_sparse_equals_dense(graph, t):
    srcs = jnp.asarray([0, 5, 11, 40], jnp.int32)
    s_d, f_d = verd_mod.verd_iterate(graph, srcs, t=t)
    s_s, f_s = verd_mod.verd_iterate_sparse(graph, srcs, t=t, k=graph.n)
    np.testing.assert_allclose(
        np.asarray(s_s.densify()), np.asarray(s_d), atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(f_s.densify()), np.asarray(f_d), atol=1e-6
    )


def test_query_sparse_equals_dense(graph, index):
    """Acceptance gate: sparse == dense to <= 1e-5 L1 at covering K."""
    srcs = jnp.asarray([0, 5, 11, 40], jnp.int32)
    dense = verd_mod.verd_query(graph, srcs, index, t=2)
    sparse = verd_mod.verd_query_sparse(graph, srcs, index, t=2, k=graph.n)
    l1 = np.abs(np.asarray(sparse.densify()) - np.asarray(dense)).sum(axis=1)
    assert l1.max() <= 1e-5, l1
    # and the served top-k agrees with the dense top-k
    sp = verd_mod.verd_query_sparse(
        graph, srcs, index, t=2, k=graph.n, out_k=10
    )
    dv, _ = jax.lax.top_k(dense, 10)
    np.testing.assert_allclose(np.asarray(sp.values), np.asarray(dv), atol=1e-6)


def test_query_sparse_no_index_equals_dense(graph):
    srcs = jnp.asarray([3, 17], jnp.int32)
    dense = verd_mod.verd_query(graph, srcs, None, t=4)
    sparse = verd_mod.verd_query_sparse(graph, srcs, None, t=4, k=graph.n)
    np.testing.assert_allclose(
        np.asarray(sparse.densify()), np.asarray(dense), atol=1e-6
    )


def test_sparse_push_dangling_mass_returns_to_source():
    # 0 -> 1, 1 dangling: pushing from 1 must return mass to the source
    g = Graph.from_edges([0], [1], n=3)
    srcs = jnp.asarray([0], jnp.int32)
    s, f = verd_mod.verd_iterate_sparse(g, srcs, t=2, k=3)
    s_d, f_d = verd_mod.verd_iterate(g, srcs, t=2)
    np.testing.assert_allclose(np.asarray(f.densify()), np.asarray(f_d),
                               atol=1e-6)
    # total mass conserved: s + f carries the full unit of probability
    np.testing.assert_allclose(
        np.asarray(s.mass() + f.mass()), 1.0, rtol=1e-6
    )


def test_degree_cap_below_max_drops_only_tail_edges(graph):
    """cap < max out-degree loses at most the capped-away edge fraction."""
    srcs = jnp.asarray([0, 5], jnp.int32)
    cap = verd_mod.resolve_degree_cap(graph)
    s_e, f_e = verd_mod.verd_iterate_sparse(
        graph, srcs, t=2, k=graph.n, degree_cap=cap)
    s_c, f_c = verd_mod.verd_iterate_sparse(
        graph, srcs, t=2, k=graph.n, degree_cap=max(cap // 2, 1))
    full = np.asarray(f_e.densify())
    capped = np.asarray(f_c.densify())
    assert (capped <= full + 1e-6).all()          # monotone: only drops mass
    deficit = (full - capped).sum(axis=1)
    assert (deficit >= -1e-6).all()


# ---------------------------------------------------------------------------
# truncation drift is bounded by the dropped mass
# ---------------------------------------------------------------------------

def test_truncation_drift_bounded_by_dropped_mass(graph, index):
    """Small K answers are elementwise <= exact and lose exactly the
    un-accumulated mass (every op is monotone non-negative, index rows are
    sub-stochastic)."""
    srcs = jnp.asarray([0, 5, 11, 40], jnp.int32)
    k_small = 4
    s_e, f_e = verd_mod.verd_iterate_sparse(graph, srcs, t=3, k=graph.n)
    s_s, f_s = verd_mod.verd_iterate_sparse(graph, srcs, t=3, k=k_small)
    exact = verd_mod.combine_with_index_sparse(s_e, f_e, index)
    trunc = verd_mod.combine_with_index_sparse(s_s, f_s, index)
    ex_d = np.asarray(exact.densify())
    tr_d = np.asarray(trunc.densify())
    assert (tr_d <= ex_d + 1e-6).all()
    l1 = np.abs(ex_d - tr_d).sum(axis=1)
    dropped = np.asarray(
        (s_e.mass() - s_s.mass()) + (f_e.mass() - f_s.mass())
    )
    assert (l1 <= dropped + 1e-5).all(), (l1, dropped)


def test_threshold_loses_at_most_thresholded_mass(graph, index):
    """Satellite: dense verd_query with threshold>0 drifts by at most the
    frontier mass the epsilon-sparsification dropped."""
    from repro.core.graph import transition_with_dangling

    eps = 2e-3
    srcs = jnp.asarray([0, 5, 11], jnp.int32)
    t = 3
    p0 = np.asarray(verd_mod.verd_query(graph, srcs, index, t=t))
    pe = np.asarray(
        verd_mod.verd_query(graph, srcs, index, t=t, threshold=eps)
    )
    # replay the thresholded iteration, accounting the dropped frontier mass
    q = srcs.shape[0]
    f = jnp.zeros((q, graph.n)).at[jnp.arange(q), srcs].set(1.0)
    dropped = np.zeros(q)
    for _ in range(t):
        f = 0.85 * transition_with_dangling(graph, f, srcs)
        f_cut = jnp.where(f >= eps, f, 0.0)
        dropped += np.asarray(jnp.sum(f - f_cut, axis=1))
        f = f_cut
    assert (pe <= p0 + 1e-6).all()
    l1 = np.abs(p0 - pe).sum(axis=1)
    assert (l1 <= dropped + 1e-5).all(), (l1, dropped)


# ---------------------------------------------------------------------------
# combine_with_index chunking (satellite)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("vertex_chunk", [7, 17, 33])
def test_combine_chunking_unaligned(graph, rng, vertex_chunk):
    """n=48 not divisible by the chunk: padding must not change the result."""
    l = 8
    dense = jnp.asarray(rng.random((graph.n, graph.n)), jnp.float32)
    idx = index_from_dense(dense, l=l)
    s = jnp.asarray(rng.random((3, graph.n)), jnp.float32)
    f = jnp.asarray(rng.random((3, graph.n)), jnp.float32)
    want = verd_mod.combine_with_index(s, f, idx, vertex_chunk=graph.n)
    got = verd_mod.combine_with_index(s, f, idx, vertex_chunk=vertex_chunk)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6
    )


# ---------------------------------------------------------------------------
# engine routing
# ---------------------------------------------------------------------------

def test_engine_sparse_path_matches_dense(graph, index):
    srcs = np.arange(12, dtype=np.int32)
    kw = dict(mode="powerwalk", t_iterations=2, top_k=8)
    dense = BatchQueryEngine(
        graph, index, QueryConfig(frontier_path="dense", **kw)).run(srcs)
    sparse = BatchQueryEngine(
        graph, index, QueryConfig(frontier_path="sparse", **kw)).run(srcs)
    np.testing.assert_allclose(
        dense["values"], sparse["values"], atol=1e-6
    )


def test_engine_sparse_path_top_k_wider_than_candidates(graph):
    """top_k exceeding the sparse candidate width (s + K*L) must pad, not
    shrink the answer buffer."""
    idx, _ = build_index(graph, r=20, l=4, key=jax.random.PRNGKey(1))
    eng = BatchQueryEngine(graph, idx, QueryConfig(
        mode="powerwalk", top_k=40, frontier_k=4, frontier_path="sparse"))
    out = eng.run(np.arange(3, dtype=np.int32))
    assert out["values"].shape == (3, 40)
    assert (out["values"][:, -1] == 0.0).all()  # padded tail slots


def test_engine_auto_rule(graph, index):
    eng = BatchQueryEngine(graph, index, QueryConfig(mode="powerwalk"))
    assert not eng.uses_sparse_path()  # n=48 is far below the auto floor
    assert AUTO_SPARSE_MIN_N > graph.n
    eng2 = BatchQueryEngine(
        graph, index, QueryConfig(mode="fppr", frontier_path="sparse"))
    assert not eng2.uses_sparse_path()  # only VERD modes have a frontier
    with pytest.raises(ValueError):    # and query_sparse refuses them too
        eng2.query_sparse(jnp.asarray([0], jnp.int32))


def test_engine_auto_avoids_hub_graphs():
    """Unsplit hub graphs must stay dense: the [Q, K, degree_cap] gather
    would dwarf the [Q, n] state sparse is meant to replace.  (With
    ``hub_split_degree`` set the guard relaxes to the split width — see
    ``tests/test_golden_auto.py::GOLDEN_SPLIT`` — backed by the streamed
    push below.)"""
    n = AUTO_SPARSE_MIN_N
    hub = synthetic.star(n)  # max out-degree = n - 1
    eng = BatchQueryEngine(hub, None, QueryConfig(mode="verd"))
    assert eng.degree_cap() == n - 1
    assert not eng.uses_sparse_path()
    flat = synthetic.cycle(n)  # max out-degree 1: sparse is safe
    eng2 = BatchQueryEngine(flat, None, QueryConfig(mode="verd"))
    assert eng2.uses_sparse_path()


@pytest.mark.parametrize("hub_split_degree,threshold", [
    (0, 0.0), (3, 0.0), (0, 1e-3),
])
def test_streamed_push_equals_one_shot(graph, hub_split_degree, threshold):
    """sparse_push_compact with a tiny stream target (many slot-chunk
    folds) must match the one-shot gather+compact at covering k_out."""
    rng = np.random.default_rng(4)
    q, k = 3, 10
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, graph.n, (q, k)), jnp.int32)
    srcs = jnp.asarray(rng.integers(0, graph.n, q), jnp.int32)
    cap = verd_mod.resolve_degree_cap(graph)
    kw = dict(
        c=0.15, degree_cap=cap, k_out=graph.n,
        hub_split_degree=hub_split_degree, threshold=threshold,
    )
    one_shot = verd_mod.sparse_push_compact(graph, fv, fi, srcs, **kw)
    streamed = verd_mod.sparse_push_compact(
        graph, fv, fi, srcs, stream_width=1, **kw
    )
    np.testing.assert_allclose(
        np.asarray(streamed.densify()), np.asarray(one_shot.densify()),
        rtol=1e-5, atol=1e-6,
    )


def test_streamed_push_truncation_is_monotone(graph):
    """Mid-stream folds only drop mass: a truncated k_out under-counts
    elementwise vs the covering run, drift bounded by the dropped mass."""
    rng = np.random.default_rng(5)
    q, k = 2, 12
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, graph.n, (q, k)), jnp.int32)
    srcs = jnp.asarray(rng.integers(0, graph.n, q), jnp.int32)
    cap = verd_mod.resolve_degree_cap(graph)
    kw = dict(c=0.15, degree_cap=cap, stream_width=1)
    full = verd_mod.sparse_push_compact(
        graph, fv, fi, srcs, k_out=graph.n, **kw
    ).densify()
    trunc = verd_mod.sparse_push_compact(
        graph, fv, fi, srcs, k_out=4, **kw
    ).densify()
    full, trunc = np.asarray(full), np.asarray(trunc)
    assert (trunc <= full + 1e-6).all()
    dropped = full.sum(axis=1) - trunc.sum(axis=1)
    l1 = np.abs(full - trunc).sum(axis=1)
    assert (l1 <= dropped + 1e-5).all()


def test_hub_graph_sparse_query_streams_bounded(monkeypatch):
    """The relaxed hub routing end to end: a star-hub graph with
    hub_split_degree set routes sparse, the push streams (never the
    [Q, K*degree_cap] one-shot tensor), and the answers match dense."""
    n = 4096
    hub = synthetic.star(n)                  # one vertex with n-1 out-edges
    srcs = jnp.asarray([0, 1, 17], jnp.int32)
    cfg = QueryConfig(
        mode="verd", top_k=8, frontier_k=16, frontier_path="sparse",
        hub_split_degree=64,
    )
    eng = BatchQueryEngine(hub, None, cfg)
    # guard the guard: one-shot would be K*cap ~ 65k wide; the streamed
    # fold keeps live width at the stream target
    seen = {}

    def spy(name):
        orig = getattr(verd_mod, name)

        def wrapped(*args, **kwargs):
            out = orig(*args, **kwargs)
            seen.setdefault(name, []).append(out[0].shape[1])
            return out

        monkeypatch.setattr(verd_mod, name, wrapped)

    spy("gather_push_edges")
    spy("gather_packed_edges")
    vals, idx = eng.query_topk(srcs)
    assert seen.get("gather_packed_edges"), f"push never streamed: {seen}"
    widths = [w for ws in seen.values() for w in ws]
    assert max(widths) < 16 * eng.degree_cap(), seen  # chunked, not one-shot
    dense_eng = BatchQueryEngine(
        hub, None, QueryConfig(mode="verd", top_k=8, frontier_path="dense")
    )
    dv, di = dense_eng.query_topk(srcs)
    np.testing.assert_allclose(
        np.asarray(vals), np.asarray(dv), rtol=1e-5, atol=1e-6
    )


@pytest.mark.parametrize("budgets, first, lanes", [
    ([[0, 2, 0, 0, 3, 0]], 0, 8),                      # zero budgets
    ([[3, 4, 2]], 2, 4),                               # block before a boundary
    ([[3, 4, 2]], 3, 4),                               # block on a boundary
    ([[3, 4, 2]], 4, 4),                               # block after a boundary
    ([[3, 4, 2]], 12, 4),                              # past the row's total
    ([[3, 0, 4, 2]], 3, 1),                            # one lane
    ([[3, 4, 2], [1, 1, 0]], 0, 64),                   # lanes over every row
    ([[5], [0]], 2, 6),                                # one slot
    ([[1, 0, 7, 2], [0, 0, 0, 1], [4, 4, 4, 4]], 3, 5),  # unequal totals
], ids=["zero-budgets", "before-boundary", "on-boundary", "after-boundary",
        "past-total", "one-lane", "wide-lanes", "one-slot", "unequal-rows"])
def test_packed_lane_slots_equal_searchsorted(budgets, first, lanes):
    """The boundary prefix sum gives each lane the slot a right-sided
    binary search over the cumulative budgets gives, clamped to K - 1."""
    ends = np.cumsum(np.asarray(budgets), axis=1).astype(np.int32)
    got = verd_mod.packed_lane_slots(jnp.asarray(ends), jnp.int32(first),
                                     lanes)
    e = first + np.arange(lanes)
    want = np.stack([np.searchsorted(r, e, side="right") for r in ends])
    np.testing.assert_array_equal(
        np.asarray(got), np.minimum(want, ends.shape[1] - 1))


def _bisection_gather_packed_edges(fv, start, deg, ends, col_idx, *, c,
                                   first, lanes):
    """The streamed push's lane gather as a per-lane binary search over the
    cumulative budgets, reading each slot's fields at every lane: the
    reference :func:`verd.gather_packed_edges` must equal bit for bit."""
    k = fv.shape[1]
    e = first + jnp.arange(lanes, dtype=jnp.int32)
    slot = jax.vmap(
        lambda row: jnp.searchsorted(row, e, side="right"))(ends)
    slot = jnp.minimum(slot.astype(jnp.int32), k - 1)
    valid = e[None, :] < ends[:, -1:]
    before = jnp.where(
        slot > 0, jnp.take_along_axis(ends, jnp.maximum(slot - 1, 0), axis=1),
        0)
    at = lambda x: jnp.take_along_axis(x, slot, axis=1)
    m = col_idx.shape[0]
    nbrs = jnp.take(
        col_idx, jnp.clip(at(start) + e[None, :] - before, 0, m - 1))
    inv = 1.0 / jnp.maximum(at(deg).astype(jnp.float32), 1.0)
    push_v = jnp.where(valid, (1.0 - c) * at(fv) * inv, 0.0)
    return push_v, jnp.where(valid, nbrs, 0)


def _assert_bitwise(got, want):
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        np.testing.assert_array_equal(g.view(np.uint32), w.view(np.uint32))


@pytest.mark.parametrize("hub_split_degree", [0, 8])
@pytest.mark.parametrize("name", ["rmat", "star"])
def test_packed_gather_bitwise_equals_bisection(monkeypatch, name,
                                                hub_split_degree):
    """Prefix-sum slots and per-slot tables give the bisection's candidates
    bit for bit, block by block and through the whole streamed push, with
    dangling and empty slots; and no bisection loop is left to trace."""
    from repro.analysis.jaxpr import iter_eqns

    g = synthetic.rmat(7, 6.0, seed=3) if name == "rmat" else synthetic.star(
        96)
    c, q, k, lanes = 0.15, 4, 12, 7
    rng = np.random.default_rng(12)
    deg_h = np.asarray(g.out_deg)
    fi_h = rng.integers(0, g.n, (q, k))
    fi_h[:, 0] = np.argmax(deg_h)                  # the hub in every row
    if name == "rmat":
        fi_h[:, 1] = np.flatnonzero(deg_h == 0)[0]  # a dangling slot
    fv_h = rng.random((q, k)).astype(np.float32)
    fv_h[rng.random((q, k)) < 0.25] = 0.0          # empty slots
    fv_h[0, :] = 0.0                               # a row with no edges
    fv, fi = jnp.asarray(fv_h), jnp.asarray(fi_h, jnp.int32)
    srcs = jnp.asarray(rng.integers(0, g.n, q), jnp.int32)
    cap = verd_mod.resolve_degree_cap(g)
    start = jnp.take(g.row_ptr, fi)
    deg = jnp.take(g.out_deg, fi)
    ends = jnp.cumsum(jnp.where(fv > 0, jnp.minimum(deg, cap), 0),
                      axis=1).astype(jnp.int32)
    d, w = verd_mod.packed_slot_tables(fv, start, deg, ends, c=c)
    blocks = -(-int(jnp.max(ends[:, -1])) // lanes)
    assert blocks >= 3
    for b in range(blocks + 1):
        first = jnp.int32(b * lanes)
        _assert_bitwise(
            verd_mod.gather_packed_edges(d, w, ends, g.col_idx, first=first,
                                         lanes=lanes),
            _bisection_gather_packed_edges(fv, start, deg, ends, g.col_idx,
                                           c=c, first=first, lanes=lanes))

    kw = dict(c=c, degree_cap=cap, k_out=24, stream_width=lanes,
              hub_split_degree=hub_split_degree)
    new = verd_mod.sparse_push_compact(g, fv, fi, srcs, **kw)
    monkeypatch.setattr(
        verd_mod, "gather_packed_edges",
        lambda d_, w_, ends_, col_idx, *, first, lanes:
        _bisection_gather_packed_edges(fv, start, deg, ends_, col_idx, c=c,
                                       first=first, lanes=lanes))
    old = verd_mod.sparse_push_compact(g, fv, fi, srcs, **kw)
    _assert_bitwise((new.values, new.indices), (old.values, old.indices))
    monkeypatch.undo()

    jaxpr = jax.make_jaxpr(
        lambda *a: verd_mod.gather_packed_edges(*a[:4], first=a[4],
                                                lanes=lanes))(
        d, w, ends, g.col_idx, jnp.int32(0))
    prims = {eqn.primitive.name for eqn in iter_eqns(jaxpr)}
    assert not prims & {"while", "scan"}, prims   # searchsorted's loop


def test_engine_auto_k_covers_expected_support():
    """Auto K must scale with mean_degree**t so auto-routed sparse answers
    aren't silently truncated below the typical frontier support."""
    g = synthetic.erdos_renyi(1000, 6.0, seed=2)
    shallow = BatchQueryEngine(
        g, None, QueryConfig(mode="verd", t_iterations=1, top_k=10))
    assert shallow.frontier_k == 256          # support ~6 « floor
    deep = BatchQueryEngine(
        g, None, QueryConfig(mode="verd", t_iterations=4, top_k=10))
    assert deep.frontier_k == g.n             # support ~6**4 > n: full width
    explicit = BatchQueryEngine(
        g, None, QueryConfig(mode="verd", t_iterations=4, frontier_k=64))
    assert explicit.frontier_k == 64          # user override wins


def test_ops_frontier_push_edgeless_graph():
    """m == 0 must take the jnp dangling path, matching the core op."""
    from repro.kernels import ops

    g = Graph.from_edges([], [], n=8)
    srcs = jnp.asarray([2, 5], jnp.int32)
    f0 = F.from_sources(srcs, g.n)
    got = ops.frontier_push(
        f0, g, srcs, c=0.15, degree_cap=1, k_out=4, interpret=True)
    cv, ci = verd_mod.sparse_push_candidates(
        g, f0.values, f0.indices, srcs, c=0.15, degree_cap=1)
    want = F.compact(cv, ci, 4, g.n)
    np.testing.assert_allclose(
        np.asarray(got.densify()), np.asarray(want.densify()), atol=1e-7)


def test_engine_rejects_bad_path(graph, index):
    with pytest.raises(ValueError):
        BatchQueryEngine(
            graph, index, QueryConfig(frontier_path="bogus"))


def test_service_sparse_path_and_pad_stats(graph, index):
    from repro.serving.batching import BatchingConfig
    from repro.serving.engine import PPRService, ServiceConfig

    t = [0.0]
    cfg = ServiceConfig(
        query=QueryConfig(mode="powerwalk", t_iterations=2, top_k=5,
                          frontier_path="sparse"),
        batching=BatchingConfig(max_batch=16, max_wait_s=0.0),
    )
    svc = PPRService(graph, index, cfg, clock=lambda: t[0])
    for v in range(5):
        svc.submit(v)
    answers = svc.poll(force=True)
    assert len(answers) == 5                 # pad rows never surface
    assert svc.stats["pad_rows"] == 3        # padded 5 -> 8
    assert svc.stats["served"] == 5
    answers2, stats = svc.run_closed_loop(range(7))
    assert stats["served"] == 12
    assert 0.0 <= stats["pad_fraction"] < 1.0
