"""Incremental index maintenance (core/updates.py) — fast tier.

Four layers:

* edge-update mechanics — ``apply_edge_updates`` insert/delete semantics,
  strict-delete errors, and the determinism contract (untouched sources'
  CSR windows byte-identical after an update);
* the walks-through touch sketch — hash determinism, and the no-false-
  negative guarantee (every fingerprint-support vertex of a row is a
  member of that row's Bloom filter);
* repair parity — after a random edge batch, ``apply_updates`` on the old
  index equals a from-scratch ``build_index`` on the mutated graph
  *bitwise*, single-device and sharded/padded (the chunk-keyed repair
  replays the build's exact RNG streams);
* the respawn-aware cost model — ``walk_state_cost`` prices the same
  slot-area formula ``test_respawn_schedule_halves_device_work`` pins,
  and ``plan_for_budget`` charges it against the budget.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import updates, walks
from repro.core.graph import Graph, apply_edge_updates
from repro.core.index import (build_index, build_index_sharded,
                              plan_for_budget, preprocessing_cost_model,
                              walk_state_cost)
from repro.graphs import synthetic


def _edges(g: Graph) -> np.ndarray:
    return np.stack(
        [np.asarray(g.src, np.int64), np.asarray(g.col_idx, np.int64)],
        axis=1,
    )


def _sample_batch(g, rng, n_del=3, n_ins=3):
    """A random update batch: deletes of distinct existing edge rows
    (deduped so strict-delete multiplicity always holds) + random inserts."""
    e = _edges(g)
    dels = np.unique(e[rng.choice(len(e), size=n_del, replace=False)], axis=0)
    ins = rng.integers(0, g.n, size=(n_ins, 2), dtype=np.int64)
    return ins, dels


# ---------------------------------------------------------------------------
# apply_edge_updates mechanics
# ---------------------------------------------------------------------------

def test_apply_edge_updates_insert_delete():
    g = synthetic.erdos_renyi(64, 3.0, seed=1)
    e = _edges(g)
    dels = np.unique(e[[3, 10, 25]], axis=0)
    ins = np.array([[0, 63], [5, 7]], dtype=np.int64)
    g2, touched = apply_edge_updates(g, inserts=ins, deletes=dels)
    assert g2.n == g.n
    assert g2.m == g.m + len(ins) - len(dels)
    before = collections.Counter(map(tuple, e))
    after = collections.Counter(map(tuple, _edges(g2)))
    for s, d in ins:
        assert after[(s, d)] == before[(s, d)] + 1
    for s, d in dels:
        assert after[(s, d)] == before[(s, d)] - 1
    expect = np.unique(np.concatenate([ins[:, 0], dels[:, 0]]))
    np.testing.assert_array_equal(touched, expect)


def test_apply_edge_updates_strict_delete_raises():
    g = synthetic.erdos_renyi(32, 2.0, seed=4)
    missing = None
    have = set(map(tuple, _edges(g)))
    for s in range(32):
        for d in range(32):
            if (s, d) not in have:
                missing = (s, d)
                break
        if missing:
            break
    with pytest.raises(ValueError, match="not present"):
        apply_edge_updates(g, deletes=np.array([missing]))
    # deleting one more occurrence than exists is also strict
    e0 = tuple(_edges(g)[0])
    k = sum(1 for x in map(tuple, _edges(g)) if x == e0)
    with pytest.raises(ValueError):
        apply_edge_updates(g, deletes=np.array([e0] * (k + 1)))


def test_apply_edge_updates_untouched_csr_windows_identical():
    """The determinism contract repair relies on: sources outside
    ``touched`` keep byte-identical CSR adjacency windows."""
    g = synthetic.erdos_renyi(64, 3.0, seed=2)
    rng = np.random.default_rng(0)
    ins, dels = _sample_batch(g, rng)
    g2, touched = apply_edge_updates(g, inserts=ins, deletes=dels)
    rp, ci = np.asarray(g.row_ptr), np.asarray(g.col_idx)
    rp2, ci2 = np.asarray(g2.row_ptr), np.asarray(g2.col_idx)
    tset = set(int(t) for t in touched)
    assert tset  # batch really touched something
    for v in range(g.n):
        if v in tset:
            continue
        np.testing.assert_array_equal(
            ci[rp[v]:rp[v + 1]], ci2[rp2[v]:rp2[v + 1]],
            err_msg=f"untouched source {v} window changed")


def test_apply_edge_updates_rejects_out_of_range():
    g = synthetic.erdos_renyi(16, 2.0, seed=0)
    with pytest.raises(ValueError):
        apply_edge_updates(g, inserts=np.array([[0, 16]]))
    with pytest.raises(ValueError):
        apply_edge_updates(g, inserts=np.array([[-1, 0]]))


# ---------------------------------------------------------------------------
# touch sketch
# ---------------------------------------------------------------------------

def test_touch_hash_bits_deterministic_in_range():
    v = jnp.arange(200, dtype=jnp.int32)
    b1 = np.asarray(walks.touch_hash_bits(v, 512))
    b2 = np.asarray(walks.touch_hash_bits(v, 512))
    np.testing.assert_array_equal(b1, b2)
    assert b1.shape == (200, walks.TOUCH_HASHES)
    assert b1.min() >= 0 and b1.max() < 512
    # the k hash functions are distinct (not all columns identical)
    assert any(
        not np.array_equal(b1[:, 0], b1[:, j])
        for j in range(1, walks.TOUCH_HASHES)
    )


def test_default_touch_bits_sizing():
    assert updates.default_touch_bits(1) == 1024
    assert updates.default_touch_bits(16) == 4096
    assert updates.default_touch_bits(10 ** 6) == 65536
    b = updates.default_touch_bits(100)
    assert b & (b - 1) == 0  # power of two


def test_touch_sketch_covers_fingerprint_support(key):
    """No false negatives: every vertex a row's fingerprint puts mass on
    was a counted walk position, so it must hit that row's filter."""
    g = synthetic.erdos_renyi(128, 3.0, seed=2)
    m, _ = updates.build_maintainable_index(
        g, r=4, l=8, key=key, touch_bits=2048, source_batch=32, c=0.25)
    vals = np.asarray(m.index.values)
    idxs = np.asarray(m.index.indices)
    for row in range(0, g.n, 7):
        support = np.unique(idxs[row][vals[row] > 0])
        if not support.size:
            continue
        for v in support:
            dirty = m.touch.dirty_rows([int(v)])
            assert row in dirty, (row, int(v))


def test_plan_repair_includes_touched_sources(key):
    g = synthetic.erdos_renyi(128, 3.0, seed=2)
    m, _ = updates.build_maintainable_index(
        g, r=4, l=8, key=key, touch_bits=2048, source_batch=32, c=0.25)
    plan = updates.plan_repair(m, [5, 77, 5])
    assert {5, 77} <= set(plan["dirty_rows"].tolist())
    sb = m.params.source_batch
    covered = set()
    for ch in plan["chunks"]:
        covered |= set(range(int(ch) * sb, (int(ch) + 1) * sb))
    assert set(plan["dirty_rows"].tolist()) <= covered
    empty = updates.plan_repair(m, [])
    assert empty["dirty_rows"].size == 0 and empty["chunks"].size == 0


# ---------------------------------------------------------------------------
# repair parity vs from-scratch rebuild
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_repair_matches_rebuild_single_device(seed):
    """Property: after a random edge batch, chunk-keyed repair equals a
    from-scratch build on the mutated graph bitwise — dirty rows because
    the repair replays the build's exact per-chunk RNG streams, untouched
    rows because their CSR windows (and streams) never changed."""
    g = synthetic.erdos_renyi(512, 3.0, seed=3)
    key = jax.random.PRNGKey(seed)
    m, _ = updates.build_maintainable_index(
        g, r=2, l=4, key=key, touch_bits=512, source_batch=8, c=0.25)
    rng = np.random.default_rng(seed)
    ins, dels = _sample_batch(g, rng)
    g2, m2, report = updates.apply_updates(m, g, inserts=ins, deletes=dels)
    assert report["rows_replaced"] >= report["dirty_rows"] > 0
    # the invalidation is partial: repair swept strictly fewer chunks
    assert 0 < report["repaired_chunks"] < report["total_chunks"]
    assert report["resample_ratio"] > 1.0
    assert report["resampled_positions"] < report["rebuild_positions"]
    ref, _ = build_index(
        g2, r=2, l=4, key=key, engine="sparse", source_batch=8, c=0.25)
    assert jnp.array_equal(m2.index.values, ref.values)
    assert jnp.array_equal(m2.index.indices, ref.indices)
    # inputs not mutated: the old maintainable still matches the old graph
    old_ref, _ = build_index(
        g, r=2, l=4, key=key, engine="sparse", source_batch=8, c=0.25)
    assert jnp.array_equal(m.index.values, old_ref.values)


def test_repair_matches_rebuild_sharded_padded():
    """Same parity through the sharded build path: the index carries pad
    rows (n=100 -> 112 at source_batch=16) and P(model, None) sharding;
    repair sweeps the padded grid with the build's keys."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    g = synthetic.erdos_renyi(100, 3.0, seed=5)
    key = jax.random.PRNGKey(0)
    m, stats = updates.build_maintainable_index(
        g, r=4, l=8, key=key, mesh=mesh, touch_bits=1024,
        source_batch=16, c=0.25, respawn=True)
    assert m.index.n > g.n  # padded
    rng = np.random.default_rng(7)
    ins, dels = _sample_batch(g, rng, n_del=2, n_ins=2)
    g2, m2, report = updates.apply_updates(m, g, inserts=ins, deletes=dels)
    assert report["dirty_rows"] > 0
    # dirty_row_ids never name pad rows (the cache-invalidation contract)
    assert report["dirty_row_ids"].max() < g.n
    ref, ref_stats = build_index_sharded(
        g2, r=4, l=8, key=key, mesh=mesh, source_batch=16, c=0.25,
        respawn=True, touch_bits=1024)
    assert jnp.array_equal(m2.index.values, ref.values)
    assert jnp.array_equal(m2.index.indices, ref.indices)
    # the repaired touch sketch matches the rebuild's too, so a second
    # update on the repaired index plans from the same filters
    assert jnp.array_equal(m2.touch.bits, ref_stats["touch"])


def test_replace_rows_keeps_mesh_sharding():
    """replace_rows on a sharded index (from a mesh built with the default
    Explicit axes) scatters and stays on the index's mesh sharding."""
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    g = synthetic.erdos_renyi(100, 3.0, seed=5)
    index, _ = build_index_sharded(
        g, r=4, l=8, key=jax.random.PRNGKey(0), mesh=mesh, source_batch=16,
        c=0.25, respawn=True)
    rows = np.asarray([3, 40, 99])
    new_v = np.full((3, 8), 0.5, np.float32)
    new_i = np.tile(np.arange(8, dtype=np.int32), (3, 1))
    out = index.replace_rows(rows, new_v, new_i)
    assert out.values.sharding == index.values.sharding
    assert out.indices.sharding == index.indices.sharding
    want_v = np.asarray(index.values).copy()
    want_i = np.asarray(index.indices).copy()
    want_v[rows], want_i[rows] = new_v, new_i
    np.testing.assert_array_equal(np.asarray(out.values), want_v)
    np.testing.assert_array_equal(np.asarray(out.indices), want_i)


def test_apply_updates_noop_returns_same_index(key):
    g = synthetic.erdos_renyi(64, 3.0, seed=1)
    m, _ = updates.build_maintainable_index(
        g, r=2, l=4, key=key, touch_bits=512, source_batch=16, c=0.25)
    g2, m2, report = updates.apply_updates(m, g)
    assert m2 is m
    assert report["repaired_chunks"] == 0
    assert report["dirty_rows"] == 0
    assert g2.m == g.m


def test_apply_updates_rejects_wrong_graph(key):
    g = synthetic.erdos_renyi(64, 3.0, seed=1)
    other = synthetic.erdos_renyi(65, 3.0, seed=1)
    m, _ = updates.build_maintainable_index(
        g, r=2, l=4, key=key, touch_bits=512, source_batch=16, c=0.25)
    with pytest.raises(ValueError, match="built on"):
        updates.apply_updates(m, other, inserts=np.array([[0, 1]]))


# ---------------------------------------------------------------------------
# respawn-aware cost model
# ---------------------------------------------------------------------------

def _device_slots(widths, total_steps, compact_every=8):
    """Same oracle as test_walks_sparse.py: slot positions one pass runs."""
    t0, slots = 0, 0
    for w in widths:
        steps = min(compact_every, total_steps - t0)
        slots += w * steps
        t0 += steps
    return slots


def test_walk_state_cost_prices_actual_schedules():
    r = 16
    decay = walk_state_cost(r, c=0.25, respawn=False)
    resp = walk_state_cost(r, c=0.25, respawn=True)
    assert decay["slot_area"] == _device_slots(
        walks.compaction_schedule(r, c=0.25), 64)
    widths, total = walks.respawn_schedule(r, c=0.25)
    assert resp["slot_area"] == _device_slots(widths, total)
    assert resp["max_width"] == max(widths)
    assert decay["max_width"] == r
    # the contract test_respawn_schedule_halves_device_work pins, now
    # visible to the planner
    assert 2 * resp["slot_area"] <= decay["slot_area"]
    assert resp["walk_state_bytes"] < decay["walk_state_bytes"]
    zero = walk_state_cost(0)
    assert zero["walk_state_bytes"] == 0 and zero["slot_area"] == 0


def test_plan_for_budget_charges_walk_state():
    p = plan_for_budget(n=100_000, budget_bytes=1 << 24)
    assert p.index_bytes + p.walk_state_bytes <= p.budget_bytes
    assert p.walk_state_bytes > 0 and p.respawn
    # respawn's narrower slots afford at least as wide an index
    p_decay = plan_for_budget(n=100_000, budget_bytes=1 << 24, respawn=False)
    assert p_decay.index_bytes + p_decay.walk_state_bytes <= p.budget_bytes
    assert p.l >= p_decay.l
    # degenerate budgets stay sane
    assert plan_for_budget(n=100, budget_bytes=0).l == 0


def test_preprocessing_cost_model_respawn_fields():
    base = preprocessing_cost_model(10_000, 16, respawn=False)
    resp = preprocessing_cost_model(10_000, 16, respawn=True)
    # walk-position totals are schedule-independent...
    assert base["walk_positions"] == resp["walk_positions"]
    # ...but device slot-work and occupancy are not
    assert resp["slot_positions"] < base["slot_positions"]
    assert resp["slot_occupancy"] > base["slot_occupancy"]
    assert resp["max_slot_width"] < base["max_slot_width"]
