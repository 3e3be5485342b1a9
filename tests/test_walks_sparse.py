"""Compacted sparse-sketch walk engine vs the legacy oracle.

Four layers (ISSUE 4's property checklist):

* exact conservation — walk counts and move counts must close to the unit,
  including under schedule-overflow truncation and sketch truncation;
* estimator parity — MCFP/MCEP from the compacted engine match the legacy
  ``simulate_walks`` estimates to Monte-Carlo tolerance at a matched walk
  budget (and both match exact PPR);
* the ``sample_walk_lengths`` geometric(c) law holds for the compacted
  engine's realized lengths;
* memory contract — the sparse index-build chunk traces with no
  ``f32[rows, n]`` intermediate (the acceptance gate that legacy
  ``build_index`` fails by construction).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import mcep, mcfp, metrics, walks
from repro.core.graph import Graph
from repro.core.index import build_index, sparse_chunk_estimates
from repro.core.power_iteration import exact_ppr_dense
from repro.graphs import synthetic


@pytest.fixture(scope="module")
def small_graph():
    return synthetic.erdos_renyi(48, 4.0, seed=7)


@pytest.fixture(scope="module")
def exact_small(small_graph):
    return exact_ppr_dense(small_graph)


# ---------------------------------------------------------------------------
# schedule
# ---------------------------------------------------------------------------

def test_schedule_shape_and_monotonicity():
    for r in (1, 7, 32, 100, 3000):
        sched = walks.compaction_schedule(r, max_steps=64, compact_every=8)
        assert len(sched) == 8
        assert sched[0] == r                     # every walk launches
        assert all(w <= r for w in sched)
        assert all(a >= b for a, b in zip(sched, sched[1:]))  # nonincreasing
        assert all(w >= 1 for w in sched)


def test_schedule_tracks_decay():
    sched = walks.compaction_schedule(
        3000, max_steps=64, compact_every=8, margin=1.35
    )
    for j, w in enumerate(sched):
        live = 3000 * 0.85 ** (8 * j)
        assert w >= min(3000, live)              # never below the mean
        assert w <= max(16, 2.0 * live + 8)      # tracks the decay


def test_schedule_rejects_bad_r():
    with pytest.raises(ValueError):
        walks.compaction_schedule(0)


# ---------------------------------------------------------------------------
# respawn-mode scheduling (ISSUE 5)
# ---------------------------------------------------------------------------

def _device_slots(widths, total_steps, compact_every=8):
    """Walk-slot positions one pass processes (the device-work unit)."""
    t0, slots = 0, 0
    for w in widths:
        steps = min(compact_every, total_steps - t0)
        slots += w * steps
        t0 += steps
    return slots


def test_respawn_schedule_shape():
    for r in (1, 8, 16, 100, 3000):
        widths, total = walks.respawn_schedule(r)
        assert widths, r
        assert all(1 <= w <= max(r, 4) for w in widths)
        assert widths[0] <= max(r, 4)
        # fixed-width launch plateau, then non-increasing drain
        assert all(a >= b for a, b in zip(widths, widths[1:]))
        assert total >= 8
    with pytest.raises(ValueError):
        walks.respawn_schedule(0)


def test_respawn_schedule_halves_device_work():
    """The perf contract behind the >= 2x positions/sec bench gate: at the
    floor-dominated small-R regime, respawn processes <= half the walk-slot
    positions of the decay schedule for the same R walks (and stays well
    ahead as R grows)."""
    sched16 = _device_slots(walks.compaction_schedule(16), 64)
    widths, total = walks.respawn_schedule(16)
    assert 2 * _device_slots(widths, total) <= sched16
    for r, margin in ((32, 1.5), (100, 1.3)):
        decay = _device_slots(walks.compaction_schedule(r), 64)
        widths, total = walks.respawn_schedule(r)
        assert margin * _device_slots(widths, total) <= decay, r


@pytest.mark.parametrize("r,l", [(40, 48), (40, 4), (257, 16)])
def test_respawn_conservation_exact(small_graph, key, r, l):
    sources = jnp.asarray([0, 5, 11], jnp.int32)
    counts = walks.simulate_walks_sparse(
        small_graph, sources, r, key, l=l, respawn=True
    )
    # every walk finishes exactly once, respawns and flushes included
    np.testing.assert_allclose(np.asarray(counts.walks), float(r))
    np.testing.assert_allclose(
        np.asarray(counts.fp.mass() + counts.fp_dropped),
        np.asarray(counts.moves), rtol=1e-6,
    )
    np.testing.assert_allclose(
        np.asarray(counts.ep.mass() + counts.ep_dropped),
        np.asarray(counts.walks), rtol=1e-6,
    )
    assert (np.asarray(counts.moves) >= r).all()


def test_respawn_quota_flush_still_conserves(small_graph, key):
    """A pass too short to launch the whole quota flushes the remainder as
    length-1 walks: walks == R must still hold exactly, with the flush
    ledgered in ``truncated``."""
    sources = jnp.asarray([0, 5, 11], jnp.int32)
    counts = walks.simulate_walks_sparse(
        small_graph, sources, 257, key, l=48, respawn=True,
        respawn_width=4, max_steps=8,
    )
    np.testing.assert_allclose(np.asarray(counts.walks), 257.0)
    assert float(np.asarray(counts.truncated).sum()) > 0.0
    np.testing.assert_allclose(
        np.asarray(counts.fp.mass() + counts.fp_dropped),
        np.asarray(counts.moves), rtol=1e-6,
    )


def test_respawn_matches_schedule_mode_in_distribution(
    small_graph, exact_small, key
):
    sources = jnp.asarray([0, 1, 2, 3], jnp.int32)
    r = 3000
    ests = {}
    for respawn in (False, True):
        counts = walks.simulate_walks_sparse(
            small_graph, sources, r, key, l=small_graph.n, respawn=respawn
        )
        ests[respawn] = np.asarray(counts.fp.densify()) / np.asarray(
            counts.moves
        )[:, None]
        # realized mean length follows the same geometric(c) law
        mean_len = float(counts.moves.sum() / counts.walks.sum())
        assert abs(mean_len - 1 / 0.15) < 0.4, respawn
    ex = np.asarray(exact_small[:4])
    for est in ests.values():
        assert np.abs(est - ex).sum(axis=1).mean() < 0.06
    # and the two modes agree to within twice the MC noise
    diff = np.abs(ests[True] - ests[False]).sum(axis=1).mean()
    assert diff < 0.12


# ---------------------------------------------------------------------------
# conservation (exact, not statistical)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("r,l", [(40, 48), (40, 4), (257, 16)])
def test_conservation_exact(small_graph, key, r, l):
    sources = jnp.asarray([0, 5, 11], jnp.int32)
    counts = walks.simulate_walks_sparse(small_graph, sources, r, key, l=l)
    # every walk finishes exactly once: terminated + truncated == R
    np.testing.assert_allclose(np.asarray(counts.walks), float(r))
    # every counted position is in the sketch or in the dropped ledger
    np.testing.assert_allclose(
        np.asarray(counts.fp.mass() + counts.fp_dropped),
        np.asarray(counts.moves), rtol=1e-6,
    )
    # every endpoint likewise
    np.testing.assert_allclose(
        np.asarray(counts.ep.mass() + counts.ep_dropped),
        np.asarray(counts.walks), rtol=1e-6,
    )
    assert (np.asarray(counts.moves) >= r).all()   # >= one position per walk
    assert (np.asarray(counts.truncated) >= 0).all()


def test_ragged_max_steps_respects_cap(small_graph, key):
    """max_steps not a multiple of compact_every: the last round is ragged
    and no walk may take more than max_steps positions."""
    sources = jnp.asarray([0, 5, 11], jnp.int32)
    counts = walks.simulate_walks_sparse(
        small_graph, sources, 40, key, l=48, max_steps=12, compact_every=8
    )
    np.testing.assert_allclose(np.asarray(counts.walks), 40.0)
    assert (np.asarray(counts.moves) <= 40 * 12).all()
    np.testing.assert_allclose(
        np.asarray(counts.fp.mass() + counts.fp_dropped),
        np.asarray(counts.moves), rtol=1e-6,
    )


def test_narrow_sketch_drops_mass(small_graph, key):
    # (40, 48) / (40, 4) reuse the compiled engines of the test above
    sources = jnp.asarray([0, 5, 11], jnp.int32)
    wide = walks.simulate_walks_sparse(small_graph, sources, 40, key, l=48)
    narrow = walks.simulate_walks_sparse(small_graph, sources, 40, key, l=4)
    assert float(narrow.fp_dropped.sum()) > float(wide.fp_dropped.sum())
    # same walks either way: the sketch width is a memory knob, not a
    # sampling knob
    np.testing.assert_allclose(
        np.asarray(wide.moves), np.asarray(narrow.moves)
    )


def test_dangling_walks_return_to_source(key):
    # 0 -> 1, 1 dangling: all non-teleport mass stays on {0, 1}
    g = Graph.from_edges([0], [1], n=3)
    counts = walks.simulate_walks_sparse(
        g, jnp.asarray([0], jnp.int32), 50, key, l=3
    )
    dense = np.asarray(counts.fp.densify())[0]
    assert dense[2] == 0.0
    assert dense.sum() == float(counts.moves[0])


def test_edgeless_graph(key):
    g = Graph.from_edges(np.zeros(0, np.int64), np.zeros(0, np.int64), n=4)
    counts = walks.simulate_walks_sparse(
        g, jnp.asarray([1, 2], jnp.int32), 50, key, l=4
    )
    np.testing.assert_allclose(np.asarray(counts.walks), 50.0)
    # every position is the source itself
    dense = np.asarray(counts.fp.densify())
    assert dense[0, 1] == float(counts.moves[0])
    assert dense[1, 2] == float(counts.moves[1])


# ---------------------------------------------------------------------------
# estimator parity vs the legacy oracle (matched walk budget)
# ---------------------------------------------------------------------------

def test_mcfp_matches_legacy_to_mc_tolerance(small_graph, exact_small, key):
    sources = jnp.asarray([0, 1, 2, 3], jnp.int32)
    r = 3000
    legacy = mcfp.estimate_ppr(small_graph, sources, r=r, key=key)
    sparse = mcfp.estimate_ppr_sparse(
        small_graph, sources, r=r, key=key, l=small_graph.n
    ).densify()
    ex = jnp.asarray(exact_small[:4], jnp.float32)
    # both estimators converge to exact PPR at the same MC rate
    for est in (legacy, sparse):
        assert metrics.mean_rag(ex, est, k=10) > 0.97
        assert float(metrics.l1_error(ex, est).mean()) < 0.06
    # and to each other within twice the MC noise
    diff = float(jnp.abs(legacy - sparse).sum(axis=1).mean())
    assert diff < 0.12


def test_mcep_matches_legacy_to_mc_tolerance(small_graph, exact_small, key):
    # same (rows, r, l) as the MCFP test: both engines are already compiled
    sources = jnp.asarray([0, 1, 2, 3], jnp.int32)
    r = 3000
    legacy = mcep.estimate_ppr(small_graph, sources, r=r, key=key)
    sparse = mcep.estimate_ppr_sparse(
        small_graph, sources, r=r, key=key, l=small_graph.n
    ).densify()
    ex = jnp.asarray(exact_small[:4], jnp.float32)
    l1_legacy = float(metrics.l1_error(ex, legacy).mean())
    l1_sparse = float(metrics.l1_error(ex, sparse).mean())
    assert l1_sparse < max(2.0 * l1_legacy, 0.2)
    diff = float(jnp.abs(legacy - sparse).sum(axis=1).mean())
    assert diff < 0.25


def test_realized_lengths_follow_geometric_law(small_graph, key):
    """moves/walks is the mean realized walk length: 1/c up to truncation
    bias — the same law ``sample_walk_lengths`` certifies.  (Shapes chosen
    to reuse the MCFP parity test's compiled engine.)"""
    sources = jnp.arange(4, dtype=jnp.int32)
    counts = walks.simulate_walks_sparse(
        small_graph, sources, 3000, key, l=small_graph.n
    )
    mean_len = float(counts.moves.sum() / counts.walks.sum())
    assert abs(mean_len - 1 / 0.15) < 0.4
    lens = np.asarray(
        walks.sample_walk_lengths(key, 20000, c=0.15, max_steps=200)
    )
    assert abs(mean_len - lens.mean()) < 0.5


def test_kernel_routed_engine_is_bitwise_identical(key):
    g = synthetic.erdos_renyi(200, 4.0, seed=3)
    sources = jnp.asarray([0, 5, 9], jnp.int32)
    a = walks.simulate_walks_sparse(g, sources, 64, key, l=64)
    b = walks.simulate_walks_sparse(
        g, sources, 64, key, l=64, use_kernel=True, kernel_interpret=True
    )
    for x, y in (
        (a.fp.values, b.fp.values), (a.fp.indices, b.fp.indices),
        (a.ep.values, b.ep.values), (a.ep.indices, b.ep.indices),
        (a.moves, b.moves), (a.walks, b.walks),
    ):
        assert bool((x == y).all())


def test_compact_slots_preserves_live_walks():
    cursors = jnp.asarray([[7, 3, 9, 4, 6, 2]], jnp.int32)
    alive = jnp.asarray([[False, True, False, True, True, True]])
    new_c, new_a, ov_w, ov_i = walks._compact_slots(cursors, alive, 3)
    # survivors packed into the low slots in order
    np.testing.assert_array_equal(np.asarray(new_c)[0], [3, 4, 6])
    np.testing.assert_array_equal(np.asarray(new_a)[0], [True, True, True])
    # the 4th survivor (cursor 2) overflows
    assert float(ov_w.sum()) == 1.0
    assert int(np.asarray(ov_i)[0, np.asarray(ov_w)[0] > 0][0]) == 2


def test_fold_width_only_changes_truncation_order(small_graph, key):
    """Fold batching is a perf knob: with a full-support sketch the result
    is independent of the fold cadence."""
    sources = jnp.asarray([0, 1], jnp.int32)
    a = walks.simulate_walks_sparse(
        small_graph, sources, 64, key, l=small_graph.n, fold_width=64
    )
    b = walks.simulate_walks_sparse(
        small_graph, sources, 64, key, l=small_graph.n, fold_width=4096
    )
    np.testing.assert_allclose(
        np.asarray(a.fp.densify()), np.asarray(b.fp.densify()), rtol=1e-6
    )


# ---------------------------------------------------------------------------
# index build: streaming sparse path + memory contract
# ---------------------------------------------------------------------------

def test_build_index_sparse_matches_legacy_quality(
    small_graph, exact_small, key
):
    idx_s, stats_s = build_index(small_graph, r=100, l=16, key=key)
    idx_l, stats_l = build_index(
        small_graph, r=100, l=16, key=key, engine="legacy"
    )
    assert stats_s["engine"] == "sparse" and stats_l["engine"] == "legacy"
    assert abs(stats_s["drop_fraction"] - stats_l["drop_fraction"]) < 0.05
    ex = jnp.asarray(exact_small, jnp.float32)
    verts = jnp.arange(12, dtype=jnp.int32)
    rag_s = metrics.mean_rag(ex[:12], idx_s.lookup_dense(verts), k=10)
    rag_l = metrics.mean_rag(ex[:12], idx_l.lookup_dense(verts), k=10)
    assert rag_s > rag_l - 0.03
    assert rag_s > 0.9


def test_build_index_rejects_unknown_engine(small_graph, key):
    with pytest.raises(ValueError):
        build_index(small_graph, r=10, l=4, key=key, engine="nope")


@pytest.mark.parametrize("engine", ["sparse", "legacy"])
def test_build_index_empty_sources(small_graph, key, engine):
    idx, stats = build_index(
        small_graph, r=10, l=4, key=key, engine=engine,
        sources=np.zeros(0, np.int32),
    )
    assert idx.values.shape == (small_graph.n, 4)
    np.testing.assert_allclose(np.asarray(idx.values), 0.0)
    assert stats["kept_mass"] == 0.0 and stats["dropped_mass"] == 0.0


def test_build_index_dedups_duplicate_sources(small_graph, key):
    """Regression (ISSUE 5): a repeated source id used to last-writer-win in
    the subset scatter and double-count the kept/dropped ledger; the builder
    now dedups up front and reports the count."""
    dup = np.asarray([3, 17, 3, 40, 17, 3], np.int32)
    uniq = np.asarray([3, 17, 40], np.int32)
    idx_d, st_d = build_index(
        small_graph, r=50, l=8, key=key, sources=dup, source_batch=2
    )
    idx_u, st_u = build_index(
        small_graph, r=50, l=8, key=key, sources=uniq, source_batch=2
    )
    assert st_d["duplicate_sources"] == 3
    assert st_u["duplicate_sources"] == 0
    np.testing.assert_array_equal(
        np.asarray(idx_d.values), np.asarray(idx_u.values)
    )
    np.testing.assert_array_equal(
        np.asarray(idx_d.indices), np.asarray(idx_u.indices)
    )
    # the mass ledger counts each source once, not once per duplicate
    assert st_d["kept_mass"] == pytest.approx(st_u["kept_mass"])
    assert st_d["dropped_mass"] == pytest.approx(st_u["dropped_mass"])


def test_build_index_legacy_reports_duplicates(small_graph, key):
    _, st = build_index(
        small_graph, r=10, l=4, key=key, engine="legacy",
        sources=np.asarray([1, 1, 2], np.int32),
    )
    assert st["duplicate_sources"] == 1


def test_build_index_r_splits_deterministic(small_graph, exact_small, key):
    """r_splits replays the sharded builder's per-chunk key fold on one
    device: deterministic, conservation intact, quality unchanged."""
    idx_a, st_a = build_index(small_graph, r=100, l=16, key=key, r_splits=2)
    idx_b, _ = build_index(small_graph, r=100, l=16, key=key, r_splits=2)
    np.testing.assert_array_equal(
        np.asarray(idx_a.values), np.asarray(idx_b.values)
    )
    idx_1, st_1 = build_index(small_graph, r=100, l=16, key=key)
    assert abs(st_a["drop_fraction"] - st_1["drop_fraction"]) < 0.05
    ex = jnp.asarray(exact_small, jnp.float32)
    verts = jnp.arange(12, dtype=jnp.int32)
    assert metrics.mean_rag(ex[:12], idx_a.lookup_dense(verts), k=10) > 0.9
    with pytest.raises(ValueError):
        build_index(small_graph, r=100, l=16, key=key, r_splits=3)


def test_build_index_respawn_matches_schedule_quality(
    small_graph, exact_small, key
):
    idx_r, st_r = build_index(small_graph, r=100, l=16, key=key, respawn=True)
    idx_s, st_s = build_index(small_graph, r=100, l=16, key=key)
    assert st_r["respawn"] and not st_s["respawn"]
    assert abs(st_r["drop_fraction"] - st_s["drop_fraction"]) < 0.05
    ex = jnp.asarray(exact_small, jnp.float32)
    verts = jnp.arange(12, dtype=jnp.int32)
    rag_r = metrics.mean_rag(ex[:12], idx_r.lookup_dense(verts), k=10)
    rag_s = metrics.mean_rag(ex[:12], idx_s.lookup_dense(verts), k=10)
    assert rag_r > rag_s - 0.03
    assert rag_r > 0.9


def test_build_index_sparse_subset_sources(small_graph, key):
    subset = np.asarray([3, 17, 40], np.int32)
    idx, stats = build_index(
        small_graph, r=50, l=8, key=key, sources=subset, source_batch=2
    )
    assert stats["pad_rows"] == 1              # 3 sources -> 2 chunks of 2
    row_mass = np.asarray(idx.values.sum(axis=1))
    assert (row_mass[subset] > 0).all()
    others = np.setdiff1d(np.arange(small_graph.n), subset)
    np.testing.assert_allclose(row_mass[others], 0.0)


def test_build_index_sparse_memory_contract(key):
    """The acceptance gate: the sparse build's per-chunk computation holds
    no ``f32[rows, n]``-sized intermediate — peak device memory is
    O(rows * sketch_l), independent of ``n`` beyond the CSR itself."""
    g = synthetic.rmat(12, avg_deg=6.0, seed=5)      # n = 4096
    rows, r, l = 64, 16, 32
    sketch_l = max(2 * l, l + 32)
    chunk = jnp.arange(rows, dtype=jnp.int32)
    fn = functools.partial(
        sparse_chunk_estimates, r=r, l=l, sketch_l=sketch_l
    )
    jaxpr = jax.make_jaxpr(fn)(g, chunk, key)
    # widest fold candidate row: sketch + a full pending buffer + the last
    # event segment that tipped it over (<= compact_every * r wide).  The
    # check itself is the auditor's dense-state-bound rule (repro.analysis);
    # the same budget/floor pair also runs under `make lint-contracts`.
    from repro.analysis.jaxpr import assert_dense_state_bound

    budget = rows * (sketch_l + max(4 * sketch_l, 512) + 8 * r + 8)
    assert_dense_state_bound(jaxpr, budget=budget, floor=rows * g.n)


@pytest.mark.slow
def test_build_index_sparse_smoke_4k():
    """End-to-end smoke on a 4k-vertex power-law graph: the new sparse path
    builds a working index whose truncation cost matches the legacy
    builder's (ISSUE 4 satellite)."""
    g = synthetic.rmat(12, avg_deg=8.0, seed=5)      # n = 4096
    key = jax.random.PRNGKey(9)
    idx_s, stats_s = build_index(g, r=16, l=32, key=key, source_batch=512)
    idx_l, stats_l = build_index(
        g, r=16, l=32, key=key, source_batch=512, engine="legacy"
    )
    assert idx_s.values.shape == (g.n, 32)
    assert abs(stats_s["drop_fraction"] - stats_l["drop_fraction"]) < 0.03
    # respawn-mode sweep: same estimator in distribution — its truncation
    # cost must match the schedule-mode build's at the smoke point
    idx_r, stats_r = build_index(
        g, r=16, l=32, key=key, source_batch=512, respawn=True
    )
    assert abs(stats_r["drop_fraction"] - stats_s["drop_fraction"]) < 0.03
    # spot-check quality parity on a few vertices (PI ground truth: the
    # dense 4096^2 solve would dwarf the builds under test)
    from repro.core.power_iteration import power_iteration

    verts = jnp.asarray([1, 100, 2000], jnp.int32)
    ex_rows = power_iteration(g, verts, n_iter=100)
    rag_s = metrics.mean_rag(ex_rows, idx_s.lookup_dense(verts), k=10)
    rag_l = metrics.mean_rag(ex_rows, idx_l.lookup_dense(verts), k=10)
    rag_r = metrics.mean_rag(ex_rows, idx_r.lookup_dense(verts), k=10)
    assert rag_s > rag_l - 0.1
    assert rag_r > rag_s - 0.1
