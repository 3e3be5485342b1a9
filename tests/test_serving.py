"""Serving-pipeline suite: batching tiers/padding, the async completion
queue, answer parity across pipeline depths, and the open-loop harness.

The parity tests are the PR's contract: pipelining changes *when* answers
materialize, never *what* they are — any depth must produce the same
arrays as the depth=1 blocking path, on the dense and sparse frontier
routes and against a padded (sharded-build-shaped) index.
"""

import types
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import query as query_mod
from repro.core.index import PPRIndex
from repro.core.query import BatchQueryEngine, QueryConfig
from repro.graphs import synthetic
from repro.serving import (PipelineConfig, PPRService, ServiceConfig,
                           run_closed_loop, run_open_loop)
from repro.serving.batching import (BatchingConfig, BufferOverloadError,
                                    RequestBuffer, TierPolicy)
from repro.serving.pipeline import CompletionQueue, PendingBatch, ServingPipeline


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def graph():
    return synthetic.rmat(11, avg_deg=8.0, seed=2)  # n = 2048


def _random_index(n: int, l: int, seed: int) -> PPRIndex:
    kv, ki = jax.random.split(jax.random.PRNGKey(seed))
    vals = jax.random.uniform(kv, (n, l), jnp.float32)
    vals = jnp.sort(vals / vals.sum(axis=1, keepdims=True), axis=1)[:, ::-1]
    idxs = jax.random.randint(ki, (n, l), 0, n, jnp.int32)
    return PPRIndex(values=vals, indices=idxs, l=l, n=n)


@pytest.fixture(scope="module")
def index(graph):
    return _random_index(graph.n, 16, seed=4)


@pytest.fixture(scope="module")
def padded_index(graph, index):
    """Sharded-build-shaped index: zeroed pad rows beyond graph.n."""
    pad = 37
    vals = jnp.concatenate(
        [index.values, jnp.zeros((pad, index.l), jnp.float32)])
    idxs = jnp.concatenate(
        [index.indices, jnp.zeros((pad, index.l), jnp.int32)])
    return PPRIndex(values=vals, indices=idxs, l=index.l, n=graph.n + pad)


def _service(graph, index, *, depth=1, dispatch="fused", frontier_path="sparse",
             max_batch=64, clock=None, **batching):
    cfg = ServiceConfig(
        query=QueryConfig(mode="powerwalk", t_iterations=2, top_k=32,
                          frontier_k=128, frontier_path=frontier_path),
        batching=BatchingConfig(max_batch=max_batch, **batching),
        pipeline=PipelineConfig(depth=depth, dispatch=dispatch),
    )
    return PPRService(graph, index, cfg, clock=clock)


def _serve_all(svc, vertices):
    """Submit everything, then flush; answers stacked by request id."""
    for v in vertices:
        svc.submit(int(v))
    answers = svc.poll(force=True)
    assert len(answers) == len(vertices)
    answers.sort(key=lambda a: a.request_id)
    return (np.stack([a.top_scores for a in answers]),
            np.stack([a.top_vertices for a in answers]))


# ---------------------------------------------------------------------------
# satellite: bucketed padding clamped to max_batch
# ---------------------------------------------------------------------------

def test_pad_clamped_to_max_batch():
    # regression: max_batch=3000 used to pad a full drain to 4096, a jit
    # shape wider than the configured limit
    buf = RequestBuffer(BatchingConfig(max_batch=3000), clock=lambda: 0.0)
    for v in range(3000):
        buf.submit(v)
    reqs, padded = buf.drain()
    assert len(reqs) == 3000 and padded == 3000
    # partial drains round up to the next pad_quantum multiple (the old
    # pow2 rule padded 2500 all the way to the 3000 clamp)
    for v in range(2500):
        buf.submit(v)
    reqs, padded = buf.drain()
    assert len(reqs) == 2500 and padded == 2560


def test_bucketed_padding_reduces_pad_fraction():
    """Regression for the PR 6 open-loop histogram: drains in the
    (quantum, 2*quantum] .. (max/2, max] bands used to double to the next
    power of two; bucketing pads them to the next multiple of 64."""
    cfg = BatchingConfig(max_batch=256)
    for n, want in [(1, 1), (2, 2), (33, 64), (64, 64), (65, 128),
                    (128, 128), (129, 192), (200, 256), (256, 256)]:
        assert cfg.pad_width(n) == want, (n, want, cfg.pad_width(n))
    # the shape a saturated service lives at: 129..192 real rows used to
    # pad to 256; bucketing halves the wasted pad rows (127 -> 63) and
    # drops pad_fraction from ~0.50 to ~0.33 at the worst point
    old_pow2 = 256
    assert cfg.pad_width(129) - 129 <= (old_pow2 - 129) / 2
    pad_old = (old_pow2 - 129) / old_pow2
    pad_new = (cfg.pad_width(129) - 129) / cfg.pad_width(129)
    assert pad_new < pad_old
    # closed shape set: pow2 up to the quantum, then quantum multiples —
    # with the serving bench's min_pad=64 floor the set is 4 shapes
    assert cfg.padded_shapes() == [1, 2, 4, 8, 16, 32, 64, 128, 192, 256]
    bench = BatchingConfig(max_batch=256, min_pad=64)
    assert bench.padded_shapes() == [64, 128, 192, 256]


def test_padding_disabled_passthrough():
    cfg = BatchingConfig(max_batch=256, pad_to_power_of_two=False)
    assert cfg.pad_width(129) == 129


def test_pad_min_floor():
    buf = RequestBuffer(BatchingConfig(max_batch=256, min_pad=64),
                        clock=lambda: 0.0)
    for v in range(5):
        buf.submit(v)
    reqs, padded = buf.drain()
    assert len(reqs) == 5 and padded == 64
    # the floor itself is clamped to max_batch
    buf2 = RequestBuffer(BatchingConfig(max_batch=8, min_pad=64),
                         clock=lambda: 0.0)
    buf2.submit(0)
    _, padded = buf2.drain()
    assert padded == 8


# ---------------------------------------------------------------------------
# satellite: tiers and deadlines with an injected clock
# ---------------------------------------------------------------------------

def test_tier_drain_interactive_first():
    buf = RequestBuffer(BatchingConfig(max_batch=16), clock=lambda: 0.0)
    b0 = buf.submit(10, tier="bulk")
    b1 = buf.submit(11, tier="bulk")
    i0 = buf.submit(20, tier="interactive")
    i1 = buf.submit(21, tier="interactive")
    reqs, _ = buf.drain()
    assert [r.request_id for r in reqs] == [i0, i1, b0, b1]
    assert [r.tier for r in reqs] == ["interactive"] * 2 + ["bulk"] * 2


def test_tier_deadline_with_empty_opposite_tier():
    t = [0.0]
    cfg = BatchingConfig(
        max_batch=100, max_wait_s=10.0,
        interactive=TierPolicy(max_wait_s=0.01),
        bulk=TierPolicy(max_wait_s=1.0),
    )
    buf = RequestBuffer(cfg, clock=lambda: t[0])
    buf.submit(1, tier="bulk")      # interactive tier stays empty
    assert not buf.ready()
    t[0] = 0.5
    assert not buf.ready()          # bulk deadline (1.0s) not yet crossed
    t[0] = 1.01
    assert buf.ready()              # fires on bulk's own deadline
    reqs, _ = buf.drain()
    assert len(reqs) == 1 and reqs[0].tier == "bulk"
    # and the interactive deadline fires alone too
    buf.submit(2, tier="interactive")
    assert not buf.ready()
    t[0] = 1.03
    assert buf.ready()


def test_ready_honors_oldest_request_per_tier():
    t = [0.0]
    buf = RequestBuffer(BatchingConfig(max_batch=100, max_wait_s=0.01),
                        clock=lambda: t[0])
    buf.submit(1)
    t[0] = 0.008
    buf.submit(2)                   # young request must not reset the clock
    assert not buf.ready()
    t[0] = 0.0101                   # oldest crossed its deadline
    assert buf.ready()


def test_tier_batch_limit_applies_per_tier():
    cfg = BatchingConfig(max_batch=16, interactive=TierPolicy(max_batch=2))
    buf = RequestBuffer(cfg, clock=lambda: 0.0)
    ids = [buf.submit(v) for v in range(3)]                  # interactive
    bids = [buf.submit(v, tier="bulk") for v in (7, 8)]
    assert buf.ready()              # interactive tier hit its batch size
    reqs, _ = buf.drain()
    # 2 interactive (tier cap) + bulk fills the remaining global room
    assert [r.request_id for r in reqs] == [ids[0], ids[1], bids[0], bids[1]]
    assert len(buf) == 1            # third interactive waits for next batch


def test_submit_rejects_unknown_tier():
    buf = RequestBuffer(BatchingConfig(), clock=lambda: 0.0)
    with pytest.raises(ValueError):
        buf.submit(0, tier="batch")


def test_bulk_aging_bound_prevents_starvation():
    """Satellite bugfix: the interactive-first drain used to starve bulk —
    under sustained interactive load every drain filled with interactive
    requests and the bulk request aged in the buffer forever.  A fired bulk
    deadline now outranks fresher interactive traffic (oldest-deadline-
    first), so ``max_wait_s`` is an aging bound."""
    t = [0.0]
    cfg = BatchingConfig(
        max_batch=4, max_wait_s=0.01,
        bulk=TierPolicy(max_wait_s=0.045),
        pad_to_power_of_two=False,
    )
    buf = RequestBuffer(cfg, clock=lambda: t[0])
    b0 = buf.submit(99, tier="bulk")          # deadline: t = 0.045
    bulk_served_round = None
    for rnd in range(5):
        for v in range(4):                    # sustained: a full batch of
            buf.submit(v)                     # interactive every round
        t[0] += 0.02
        reqs, _ = buf.drain()
        if any(r.request_id == b0 for r in reqs):
            bulk_served_round = rnd
            # the fired bulk deadline outranked interactive traffic that
            # was itself past deadline — pre-fix, interactive always won
            assert reqs[0].request_id == b0
            assert len(buf) > 0               # interactive left waiting
            break
    # served within one drain period of its 0.045s deadline (round 2 ends
    # at t=0.06), not starved through all 5 rounds
    assert bulk_served_round == 2
    # latency bound: deadline + one drain period, not 5 rounds
    assert t[0] - 0.0 <= cfg.tier_policy("bulk")[1] + 0.02


def test_drain_order_keeps_interactive_first_when_nothing_fired():
    t = [0.0]
    cfg = BatchingConfig(max_batch=4, max_wait_s=10.0)
    buf = RequestBuffer(cfg, clock=lambda: t[0])
    buf.submit(9, tier="bulk")
    buf.submit(1, tier="interactive")
    t[0] = 0.001                              # no deadline fired
    reqs, _ = buf.drain()
    assert [r.tier for r in reqs] == ["interactive", "bulk"]


# ---------------------------------------------------------------------------
# satellite: admission control — bounded queue depth, shed counter,
# rejected-answer path (injected clock throughout)
# ---------------------------------------------------------------------------

def test_buffer_admission_control_sheds_at_depth():
    buf = RequestBuffer(BatchingConfig(max_batch=16, max_queue_depth=2),
                        clock=lambda: 0.0)
    buf.submit(0)
    buf.submit(1)
    with pytest.raises(BufferOverloadError):
        buf.submit(2)
    assert buf.stats["shed"] == 1
    assert len(buf) == 2                # the overload submit enqueued nothing
    reqs, _ = buf.drain()
    assert [r.vertex for r in reqs] == [0, 1]
    buf.submit(3)                       # drain freed the queue: admitted again
    assert len(buf) == 1
    # unbounded by default: no depth configured, nothing ever sheds
    unb = RequestBuffer(BatchingConfig(max_batch=4), clock=lambda: 0.0)
    for v in range(100):
        unb.submit(v)
    assert unb.stats["shed"] == 0 and len(unb) == 100


def test_service_sheds_overload_with_rejected_answers(graph, index):
    t = [0.0]
    svc = _service(graph, index, clock=lambda: t[0], max_batch=16,
                   max_wait_s=10.0, max_queue_depth=3)
    rids = [svc.submit(v) for v in range(5)]       # last 2 shed
    assert len(set(rids)) == 5                     # shed requests keep an id
    assert svc.stats["shed"] == 2 and len(svc.buffer) == 3
    t[0] = 0.25
    answers = svc.poll(force=True)
    assert len(answers) == 5
    rej = {a.request_id: a for a in answers if a.rejected}
    assert set(rej) == set(rids[3:])
    for a in rej.values():
        # empty top-k, never dispatched, latency still measured from arrival
        assert a.top_vertices.size == 0 and a.top_scores.size == 0
        assert a.latency_s == pytest.approx(0.25)
    served = [a for a in answers if not a.rejected]
    assert {a.request_id for a in served} == set(rids[:3])
    assert all(a.top_scores.size > 0 for a in served)
    s = svc.snapshot_stats()
    # shed traffic never occupied a batch row: out of the served ledger
    assert s["served"] == 3
    assert s["shed"] == 2 and s["buffer_shed"] == 2
    assert s["max_queue_depth"] == 3
    # the drain freed the buffer: traffic is admitted again
    svc.submit(7)
    assert svc.stats["shed"] == 2 and len(svc.buffer) == 1


# ---------------------------------------------------------------------------
# pipeline mechanics (stub engine: no device work)
# ---------------------------------------------------------------------------

class _StubEngine:
    """Returns recognizable host arrays; numpy has no ``is_ready`` so every
    ticket reports ready immediately."""

    def __init__(self, k=4):
        self.k = k
        self.calls = 0

    def dispatch_key(self, seq):
        return seq

    def query_topk_async(self, verts, *, key=None):
        self.calls += 1
        q = len(verts)
        vals = np.full((q, self.k), float(self.calls), np.float32)
        idx = np.tile(np.asarray(verts, np.int32)[:, None], (1, self.k))
        return vals, idx


def test_completion_queue_is_bounded():
    q = CompletionQueue(depth=2)
    mk = lambda s: PendingBatch(s, [], 0, np.zeros(1), np.zeros(1), 0.0)
    q.push(mk(0)), q.push(mk(1))
    assert q.full()
    with pytest.raises(RuntimeError):
        q.push(mk(2))
    assert q.pop().seq == 0         # FIFO
    q.push(mk(2))
    assert [q.pop(block=True).seq for _ in range(2)] == [1, 2]


def test_queue_pop_waits_for_unready_head():
    class NotReady:
        def is_ready(self):
            return False

    q = CompletionQueue(depth=2)
    q.push(PendingBatch(0, [], 0, NotReady(), NotReady(), 0.0))
    assert q.pop(block=False) is None   # head not finished, nothing harvested
    assert len(q) == 1


def test_pipeline_depth_bound_and_backpressure():
    buf = RequestBuffer(BatchingConfig(max_batch=4, pad_to_power_of_two=False),
                        clock=lambda: 0.0)
    pl = ServingPipeline(_StubEngine(), buf, PipelineConfig(depth=2),
                         clock=lambda: 0.0)
    for v in range(20):
        buf.submit(v)
    completed = pl.dispatch(force=True)          # 5 batches through depth 2
    completed += pl.harvest(drain=True)
    assert pl.stats["dispatched"] == 5 and pl.stats["harvested"] == 5
    assert pl.stats["in_flight_peak"] == 2       # never exceeded depth
    assert pl.stats["queue_full_stalls"] == 3    # batches 3..5 had to wait
    served = [r.vertex for b in completed for r in b.requests]
    assert sorted(served) == list(range(20))
    # completion order preserved dispatch order (FIFO stream semantics)
    assert [b.seq for b in completed] == [0, 1, 2, 3, 4]


def test_pipeline_batch_histogram():
    buf = RequestBuffer(BatchingConfig(max_batch=8), clock=lambda: 0.0)
    pl = ServingPipeline(_StubEngine(), buf, PipelineConfig(depth=1),
                         clock=lambda: 0.0)
    for v in range(13):
        buf.submit(v)
    pl.flush()
    assert dict(pl.batch_hist) == {8: 2}         # 8 full + 5 padded to 8


def test_deadline_dispatch_deferred_while_busy():
    """A deadline-fired partial batch must not launch behind an in-flight
    batch (it would start no sooner and its pad rows burn capacity); it
    launches once the pipeline drains.  Size-fired batches always launch."""
    buf = RequestBuffer(BatchingConfig(max_batch=8, max_wait_s=0.0),
                        clock=lambda: 1.0)
    pl = ServingPipeline(_StubEngine(), buf, PipelineConfig(depth=2),
                         clock=lambda: 1.0)
    for v in range(3):
        buf.submit(v)
    assert buf.ready() and not buf.size_ready()
    pl.dispatch()                                # idle -> deadline batch goes
    assert pl.stats["dispatched"] == 1 and pl.in_flight == 1
    for v in range(3):
        buf.submit(v)
    pl.dispatch()                                # busy -> deferred, fills up
    assert pl.stats["dispatched"] == 1 and len(buf) == 3
    for v in range(8):
        buf.submit(v)                            # one tier hits max_batch
    pl.dispatch()                                # size-fired: launches anyway
    assert pl.stats["dispatched"] == 2 and len(buf) == 3
    pl.harvest(drain=True)
    pl.dispatch()                                # idle again -> deferred goes
    assert pl.stats["dispatched"] == 3 and len(buf) == 0


# ---------------------------------------------------------------------------
# satellite: stuck-ticket watchdog (injected clock, never-ready tickets)
# ---------------------------------------------------------------------------

class _NeverReady:
    """Device-array stand-in whose ticket never reports ready — a wedged
    device stream as far as the completion queue can tell."""

    def is_ready(self):
        return False


class _StuckEngine:
    def dispatch_key(self, seq):
        return seq

    def query_topk_async(self, verts, *, key=None, out=None):
        return _NeverReady(), _NeverReady()


def test_stall_watchdog_counts_and_warns_once():
    t = [0.0]
    buf = RequestBuffer(BatchingConfig(max_batch=4, pad_to_power_of_two=False),
                        clock=lambda: t[0])
    pl = ServingPipeline(_StuckEngine(), buf,
                         PipelineConfig(depth=2, stall_timeout_s=1.0),
                         clock=lambda: t[0])
    for v in range(4):
        buf.submit(v)
    pl.dispatch()
    assert pl.in_flight == 1
    # young ticket: harvest returns nothing and stays silent
    t[0] = 0.5
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert pl.harvest() == []
    assert pl.stats["stalled"] == 0
    # past the deadline: counted + warned
    t[0] = 1.5
    with pytest.warns(RuntimeWarning, match="in flight for"):
        assert pl.harvest() == []
    assert pl.stats["stalled"] == 1
    # detection only — the ticket stays in flight, and each stuck batch
    # warns exactly once however often harvest polls it
    assert pl.in_flight == 1
    t[0] = 50.0
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert pl.harvest() == []
    assert pl.stats["stalled"] == 1


def test_stall_watchdog_disabled_by_default():
    t = [0.0]
    buf = RequestBuffer(BatchingConfig(max_batch=2, pad_to_power_of_two=False),
                        clock=lambda: t[0])
    pl = ServingPipeline(_StuckEngine(), buf, PipelineConfig(depth=2),
                         clock=lambda: t[0])
    buf.submit(0), buf.submit(1)
    pl.dispatch()
    t[0] = 1e6                          # ancient ticket, watchdog off
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        assert pl.harvest() == []
    assert pl.stats["stalled"] == 0
    with pytest.raises(ValueError, match="stall_timeout_s"):
        PipelineConfig(stall_timeout_s=0.0)


def test_stalled_counter_in_service_snapshot(graph, index):
    svc = _service(graph, index)
    s = svc.snapshot_stats()
    assert s["pipeline_stalled"] == 0


# ---------------------------------------------------------------------------
# satellite: apply_updates is atomic — failure leaves the service untouched
# ---------------------------------------------------------------------------

def test_apply_updates_rolls_back_on_repair_failure(graph, index, monkeypatch):
    from repro.core import updates as updates_mod

    svc = _service(graph, index)
    svc.maintainer = object()           # sentinel; repair fails before use

    def boom(*a, **k):
        raise RuntimeError("injected repair failure")

    monkeypatch.setattr(updates_mod, "apply_updates", boom)
    before = (svc.graph, svc.engine, svc.maintainer, svc.pipeline.engine)
    with pytest.raises(RuntimeError, match="injected repair failure"):
        svc.apply_updates(inserts=[[0, 1]])
    # nothing swapped: same graph, same engine, same maintainer
    assert (svc.graph, svc.engine, svc.maintainer,
            svc.pipeline.engine) == before
    assert svc.stats["update_rollbacks"] == 1
    assert svc.stats["updates_applied"] == 0
    assert svc.cache.epoch == 0         # no invalidation happened either
    # the rolled-back service still serves
    svc.submit(3)
    answers = svc.poll(force=True)
    assert len(answers) == 1 and not answers[0].rejected


def test_apply_updates_rolls_back_on_engine_failure(graph, index, monkeypatch):
    """Repair succeeds but the replacement engine fails to construct —
    the dangerous half-applied window (new graph, old engine) must not
    exist: everything is built before anything is assigned."""
    import repro.serving.engine as engine_mod
    from repro.core import updates as updates_mod

    svc = _service(graph, index)
    old_maintainer = object()
    svc.maintainer = old_maintainer
    fake_m = types.SimpleNamespace(index=index)
    monkeypatch.setattr(
        updates_mod, "apply_updates",
        lambda *a, **k: (graph, fake_m,
                         dict(dirty_rows=0, dirty_row_ids=[])))

    def bad_engine(*a, **k):
        raise ValueError("injected engine failure")

    monkeypatch.setattr(engine_mod, "BatchQueryEngine", bad_engine)
    old_engine = svc.engine
    with pytest.raises(ValueError, match="injected engine failure"):
        svc.apply_updates(inserts=[[0, 1]])
    assert svc.maintainer is old_maintainer     # not fake_m
    assert svc.engine is old_engine
    assert svc.pipeline.engine is old_engine
    assert svc.stats["update_rollbacks"] == 1
    assert svc.stats["updates_applied"] == 0


# ---------------------------------------------------------------------------
# satellite: answer parity at every depth, both routes, padded index
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("frontier_path", ["sparse", "dense"])
@pytest.mark.parametrize("depth", [2, 4])
def test_async_depth_parity(graph, index, frontier_path, depth):
    rng = np.random.default_rng(3)
    verts = rng.integers(0, graph.n, size=165)   # 64 + 64 + 37(pad 64)
    base = _service(graph, index, depth=1, frontier_path=frontier_path)
    v0, i0 = _serve_all(base, verts)
    svc = _service(graph, index, depth=depth, frontier_path=frontier_path)
    v1, i1 = _serve_all(svc, verts)
    # identical arrays, not merely close: same fused computation, same
    # per-dispatch keys, only the harvest timing differs
    np.testing.assert_array_equal(v0, v1)
    np.testing.assert_array_equal(i0, i1)
    assert svc.pipeline.stats["in_flight_peak"] <= depth


@pytest.mark.parametrize("depth", [1, 2, 4])
def test_async_parity_padded_index(graph, index, padded_index, depth):
    rng = np.random.default_rng(5)
    verts = rng.integers(0, graph.n, size=100)
    ref = _service(graph, index, depth=1)
    v0, i0 = _serve_all(ref, verts)
    svc = _service(graph, padded_index, depth=depth)
    v1, i1 = _serve_all(svc, verts)
    # pad rows carry no mass, so a sharded-shaped index serves the same
    # answers at any depth
    np.testing.assert_allclose(v0, v1, rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(i0, i1)


@pytest.mark.parametrize("frontier_path", ["sparse", "dense"])
def test_fused_matches_legacy_blocking(graph, index, frontier_path):
    rng = np.random.default_rng(7)
    verts = rng.integers(0, graph.n, size=130)
    leg = _service(graph, index, depth=1, dispatch="legacy",
                   frontier_path=frontier_path)
    v0, i0 = _serve_all(leg, verts)
    fus = _service(graph, index, depth=1, dispatch="fused",
                   frontier_path=frontier_path)
    v1, i1 = _serve_all(fus, verts)
    np.testing.assert_allclose(v0, v1, rtol=1e-6, atol=1e-7)
    # equal scores can permute within ties; compare the score multisets and
    # the (vertex -> score) maps instead of raw index order
    for r in range(len(verts)):
        m0 = dict(zip(i0[r].tolist(), v0[r].tolist()))
        m1 = dict(zip(i1[r].tolist(), v1[r].tolist()))
        for k in set(m0) | set(m1):
            assert abs(m0.get(k, 0.0) - m1.get(k, 0.0)) < 1e-6


def test_service_matches_engine_rows(graph, index):
    """A full no-pad batch through the service equals the engine's own
    fused answers row for row."""
    verts = np.arange(64)
    svc = _service(graph, index, depth=2)
    v_srv, i_srv = _serve_all(svc, verts)
    eng = svc.engine
    v_ref, i_ref = eng.query_topk_async(
        jnp.asarray(verts, jnp.int32), key=eng.dispatch_key(0))
    np.testing.assert_array_equal(v_srv, np.asarray(v_ref))
    np.testing.assert_array_equal(i_srv, np.asarray(i_ref))


# ---------------------------------------------------------------------------
# satellite: per-dispatch result buffers ring instead of allocating
# ---------------------------------------------------------------------------

def test_buffer_ring_no_allocation_growth(graph, index):
    """Satellite bugfix: each fused dispatch used to allocate a fresh
    [padded, k] result pair.  With the buffer ring, a long run at a fixed
    shape set allocates at most ``depth`` pairs per shape and re-donates
    them forever after — allocation count plateaus, reuse count grows."""
    svc = _service(graph, index, depth=2, max_batch=16, min_pad=16)
    rng = np.random.default_rng(11)
    _, s = run_closed_loop(svc, rng.integers(0, graph.n, 16 * 12).tolist())
    assert s["served"] == 16 * 12
    dispatched = s["pipeline_dispatched"]
    assert dispatched >= 10                   # long run, many dispatches
    # single padded shape (min_pad == max_batch == 16): the ring bounds
    # allocations by pipeline depth, everything else reuses
    assert set(s["batch_hist"]) == {16}
    assert s["pipeline_buffers_allocated"] <= 2
    assert s["pipeline_buffers_reused"] == dispatched - s["pipeline_buffers_allocated"]


def test_buffer_ring_reuses_device_memory(graph, index):
    """The ring actually re-donates device buffers: a dispatch that pops a
    ringed pair writes its answer into the same device memory."""
    eng = BatchQueryEngine(graph, index, QueryConfig(
        mode="powerwalk", t_iterations=2, top_k=32, frontier_k=128,
        frontier_path="sparse"))
    verts = jnp.arange(8, dtype=jnp.int32)
    v0, i0 = eng.query_topk_async(verts)
    v0.block_until_ready()
    ptr_v = v0.unsafe_buffer_pointer()
    ref_vals = np.asarray(v0).copy()
    v1, i1 = eng.query_topk_async(verts + 1, out=(v0, i0))
    v1.block_until_ready()
    assert v1.unsafe_buffer_pointer() == ptr_v   # same device memory
    # and the answers are the fresh query's, not the donor's
    v_ref, _ = eng.query_topk_async(jnp.arange(8, dtype=jnp.int32) + 1)
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v_ref))
    assert not np.array_equal(np.asarray(v1), ref_vals)


def test_buffer_ring_disabled_never_reuses(graph, index):
    svc = _service(graph, index, depth=1, max_batch=16, min_pad=16)
    svc.cfg.pipeline.reuse_buffers = False
    rng = np.random.default_rng(12)
    _, s = run_closed_loop(svc, rng.integers(0, graph.n, 48).tolist())
    assert s["pipeline_buffers_reused"] == 0


# ---------------------------------------------------------------------------
# scatter-combine routing + parity (the fused path's perf lever)
# ---------------------------------------------------------------------------

def test_scatter_combine_routing(graph, index, monkeypatch):
    eng = BatchQueryEngine(graph, index, QueryConfig(
        mode="powerwalk", frontier_k=128, frontier_path="sparse"))
    assert eng.uses_scatter_combine(64)          # fits the default budget
    monkeypatch.setattr(query_mod, "SCATTER_COMBINE_BUDGET_BYTES", 100)
    assert not eng.uses_scatter_combine(64)      # auto respects the budget
    eng.config.combine_path = "scatter"
    assert eng.uses_scatter_combine(64)          # explicit overrides budget
    eng.config.combine_path = "sparse"
    assert not eng.uses_scatter_combine(1)
    # only the powerwalk sparse route has an index combine
    dense_eng = BatchQueryEngine(graph, index, QueryConfig(
        mode="powerwalk", frontier_path="dense"))
    assert not dense_eng.uses_scatter_combine(1)


def test_scatter_combine_matches_sparse_combine(graph, index):
    verts = jnp.arange(48, dtype=jnp.int32)
    answers = {}
    for path in ("scatter", "sparse"):
        eng = BatchQueryEngine(graph, index, QueryConfig(
            mode="powerwalk", t_iterations=2, top_k=32, frontier_k=128,
            frontier_path="sparse", combine_path=path))
        answers[path] = eng.query_topk_async(verts)
    np.testing.assert_allclose(
        np.asarray(answers["scatter"][0]), np.asarray(answers["sparse"][0]),
        rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(
        np.asarray(answers["scatter"][1]), np.asarray(answers["sparse"][1]))


# ---------------------------------------------------------------------------
# open-loop harness
# ---------------------------------------------------------------------------

def _virtual_clock_service(graph, index, **kw):
    t = [0.0]
    svc = _service(graph, index, clock=lambda: t[0], **kw)
    return svc, t


def test_open_loop_latency_from_scheduled_arrival(graph, index):
    svc, t = _virtual_clock_service(graph, index, max_batch=16,
                                    max_wait_s=1.0)
    def sleep(dt):
        t[0] += dt
    answers, stats = run_open_loop(
        svc, list(range(16)), qps=100.0, sleep=sleep)
    assert len(answers) == 16
    # all 16 complete in one final batch at the same (virtual) instant, so
    # latency must decrease with request id: arrival was backdated to the
    # *scheduled* offer time i/qps, not the submit time
    by_id = sorted(answers, key=lambda a: a.request_id)
    lats = [a.latency_s for a in by_id]
    assert all(lats[i] > lats[i + 1] for i in range(len(lats) - 1))
    np.testing.assert_allclose(lats[0] - lats[-1], 15 / 100.0, rtol=1e-6)
    assert stats["offered_qps"] == 100.0
    assert stats["latency_p99"] >= stats["latency_p50"] > 0


def test_open_loop_tiered_workload(graph, index):
    svc, t = _virtual_clock_service(graph, index, max_batch=16)
    work = [(5, "bulk"), (6, "interactive"), (7, "bulk"), (8, "interactive")]
    answers, _ = run_open_loop(svc, work, qps=None)
    got = {a.vertex: a.tier for a in answers}
    assert got == {5: "bulk", 6: "interactive", 7: "bulk", 8: "interactive"}


def test_closed_loop_wrapper_keeps_stats_contract(graph, index):
    svc = _service(graph, index, depth=2, max_batch=16)
    answers, s = svc.run_closed_loop(list(range(40)))
    assert len(answers) == 40
    for key in ("served", "batches", "pad_rows", "wall_s", "qps",
                "mean_latency", "pad_fraction", "frontier_path", "answer_k",
                "index_rows", "index_sharded", "latency_p50",
                "latency_p99", "pipeline_depth", "batch_hist",
                "pipeline_buffer_wait_s"):
        assert key in s, key
    assert s["served"] == 40
    assert 0.0 <= s["pad_fraction"] < 1.0
    # every request waits in the buffer for its dispatch: a cold service's
    # first batch counts like any other, so qps is answers over the wall
    assert s["pipeline_buffer_wait_s"] >= 0.0
    assert s["qps"] == pytest.approx(40 / s["wall_s"])


def test_poll_without_traffic_is_empty(graph, index):
    svc = _service(graph, index)
    assert svc.poll() == []
    assert svc.poll(force=True) == []


def test_buffer_wait_sums_arrival_to_dispatch_on_the_clock(graph, index):
    svc, t = _virtual_clock_service(graph, index, max_batch=4,
                                    max_wait_s=10.0)
    for v, at in [(1, 0.0), (2, 0.5), (3, 1.5)]:
        t[0] = at
        svc.submit(v)
    t[0] = 2.0
    assert len(svc.poll(force=True)) == 3  # dispatched at 2.0
    waits = 2.0 + 1.5 + 0.5
    assert svc.pipeline.stats["buffer_wait_s"] == pytest.approx(waits)
    # an arrival backdated to its schedule waits from the schedule
    svc.submit(4, arrival=1.0)
    t[0] = 3.0
    assert len(svc.poll(force=True)) == 1
    assert svc.snapshot_stats()["pipeline_buffer_wait_s"] == pytest.approx(6.0)
    svc.reset_stats()
    assert svc.pipeline.stats["buffer_wait_s"] == 0


def test_host_spans_name_each_batch(graph, index, tmp_path):
    """Under a profiler trace the service's host work shows as ``ppr.``
    spans; the dispatch and harvest of one batch carry its ``seq``."""
    import collections
    import glob

    from jax.profiler import ProfileData

    svc = _service(graph, index, depth=2, max_batch=8)
    with jax.profiler.trace(str(tmp_path)):
        for v in range(20):
            svc.submit(v)
        assert len(svc.poll(force=True)) == 20
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    spans = [(e.name, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("ppr.")]
    count = collections.Counter(name for name, _ in spans)
    assert count["ppr.submit"] == 20 and count["ppr.absorb"] >= 1
    dispatch = {a["seq"]: a for name, a in spans if name == "ppr.dispatch"}
    harvest = sorted(a["seq"] for name, a in spans if name == "ppr.harvest")
    assert sorted(dispatch) == harvest == [0, 1, 2]
    assert sum(a["rows"] for a in dispatch.values()) == 20
    assert all(a["padded"] >= a["rows"] for a in dispatch.values())


# ---------------------------------------------------------------------------
# slow end-to-end: real clock, sparse route, pipelined vs blocking
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_e2e_pipelined_serving_matches_blocking(graph, index):
    rng = np.random.default_rng(13)
    verts = rng.integers(0, graph.n, size=400).tolist()

    leg = _service(graph, index, depth=1, dispatch="legacy", max_batch=128)
    _, s_leg = run_closed_loop(leg, verts)
    pip = _service(graph, index, depth=4, dispatch="fused", max_batch=128)
    answers, s_pip = run_open_loop(pip, verts, qps=2000.0)

    assert s_leg["served"] == s_pip["served"] == 400
    assert len({a.request_id for a in answers}) == 400
    # batching differs between the two runs, but per-vertex answers are a
    # pure function of the vertex on the powerwalk route — collect by
    # vertex and compare across serving stacks
    leg_by_vertex = {}
    leg2 = _service(graph, index, depth=1, dispatch="legacy", max_batch=128)
    for a in run_closed_loop(leg2, sorted(set(verts)))[0]:
        leg_by_vertex[a.vertex] = (a.top_scores, a.top_vertices)
    for a in answers:
        v_ref, i_ref = leg_by_vertex[a.vertex]
        np.testing.assert_allclose(a.top_scores, v_ref, rtol=1e-5, atol=1e-6)
    assert s_pip["pipeline_in_flight_peak"] >= 1
