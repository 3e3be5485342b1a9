"""The online PPR service: buffer -> shared decomposition -> top-k answers.

End-to-end serving loop for the paper's product: clients submit query
vertices; the service batches them (Section 3.3), runs the VERD shared
decomposition against the PPR index, and returns top-k (vertex, score)
lists.  Collects the latency/throughput metrics the paper's Table 3
reports.

Since PR 6 the service is pipelined: ``poll()`` *dispatches* ready batches
without syncing (JAX async dispatch keeps up to ``pipeline.depth`` batches
in flight on the device stream) and *harvests* whichever in-flight batches
have finished — see ``serving/pipeline.py`` and docs/serving_path.md.
``pipeline.depth=1`` reproduces the old blocking poll exactly.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np
from jax.profiler import annotate_function

from repro.core.graph import Graph
from repro.core.index import PPRIndex
from repro.core.query import BatchQueryEngine, QueryConfig
from repro.serving.batching import (BatchingConfig, BufferOverloadError,
                                    RequestBuffer)
from repro.serving.cache import AnswerCache, CacheConfig, canonicalize_seed_set
from repro.serving.pipeline import CompletedBatch, PipelineConfig, ServingPipeline


@dataclasses.dataclass
class ServiceConfig:
    query: QueryConfig = dataclasses.field(default_factory=QueryConfig)
    batching: BatchingConfig = dataclasses.field(default_factory=BatchingConfig)
    pipeline: PipelineConfig = dataclasses.field(default_factory=PipelineConfig)
    cache: CacheConfig = dataclasses.field(default_factory=CacheConfig)


@dataclasses.dataclass
class Answer:
    request_id: int
    vertex: int
    top_vertices: np.ndarray
    top_scores: np.ndarray
    latency_s: float
    tier: str = "interactive"
    cached: bool = False          # served from the answer cache (no dispatch)
    rejected: bool = False        # shed by admission control: empty top-k,
                                  # never dispatched (client should back off)


class PPRService:
    """Serves PPR answers against a :class:`PPRIndex`.

    The index may be the output of ``index.build_index_sharded``: its
    ``values/indices`` arrays stay device-sharded over the model axis
    (``P("model", None)``) and may carry zeroed pad rows (``index.n >=
    graph.n``) — the query paths only ever gather real rows, so nothing is
    replicated or re-laid-out to serve from it.  Answer width is the
    engine's ``effective_top_k`` (``top_k`` clamped to the graph), so
    ``poll()`` rows always match the configured buffers.
    """

    def __init__(self, graph: Graph, index: Optional[PPRIndex],
                 cfg: Optional[ServiceConfig] = None, clock=None,
                 maintainer=None):
        self.cfg = cfg or ServiceConfig()
        # maintainer: a core.updates.MaintainableIndex — enables
        # apply_updates() (incremental index repair + exact cache
        # invalidation).  With index=None the maintainer's index serves.
        self.maintainer = maintainer
        if index is None and maintainer is not None:
            index = maintainer.index
        self.graph = graph
        self.engine = BatchQueryEngine(graph, index, self.cfg.query)
        self.buffer = RequestBuffer(self.cfg.batching, clock=clock)
        self.clock = clock or time.monotonic
        # the cache exists before the pipeline so dispatches can stamp its
        # epoch onto their tickets (invalidate-vs-in-flight fencing)
        self.cache = AnswerCache(self.cfg.cache)
        self.pipeline = ServingPipeline(
            self.engine, self.buffer, self.cfg.pipeline, clock=self.clock,
            epoch_fn=lambda: self.cache.epoch,
        )
        # which execution the engine routed to (docs/query_path.md): part of
        # the serving telemetry so capacity planning can see Q x K vs Q x n
        self.frontier_path = (
            "sparse" if self.engine.uses_sparse_path() else "dense"
        )
        self.answer_k = self.engine.effective_top_k
        # index layout telemetry: pad rows of a sharded build + whether the
        # backing arrays are device-sharded (capacity planning reads this)
        self.index_rows = index.n if index is not None else 0
        self.index_sharded = bool(
            index is not None
            and getattr(index.values, "sharding", None) is not None
            and not index.values.sharding.is_fully_replicated
        )
        self.stats: Dict[str, float] = dict(
            served=0, batches=0, total_latency=0.0, max_latency=0.0,
            pad_rows=0, cache_served=0,
            updates_applied=0, rows_repaired=0, cache_stale_drops=0,
            shed=0, update_rollbacks=0,
        )
        # answer cache (serving/cache.py): consulted at submit, filled at
        # absorb.  _pending_cached holds hit answers awaiting the next
        # poll(); _inflight_keys maps computed requests back to their
        # canonical key so their answers populate the cache.
        self._pending_cached: List[Tuple[int, int, str, float, Tuple]] = []
        self._inflight_keys: Dict[int, Tuple] = {}
        # requests shed by admission control awaiting their rejected answer
        self._pending_rejected: List[Tuple[int, int, str, float]] = []

    @classmethod
    def from_checkpoint(cls, graph: Graph, checkpoint_dir: str,
                        cfg: Optional[ServiceConfig] = None,
                        clock=None) -> "PPRService":
        """Boot a service from a *complete* committed build checkpoint.

        The crash-safe restart path: after a (possibly resumed) build, the
        final ``complete=True`` step under ``checkpoint_dir`` holds the
        assembled index, so a server restart reloads it without
        re-simulating any walks.  A maintainable build (touch sketch in
        the checkpoint) reloads with a full ``maintainer`` — so
        ``apply_updates`` keeps working across the restart; a plain build
        serves read-only.  Mid-build partial steps, ``.tmp`` dirs, and
        checksum-corrupted steps are never booted from
        (:func:`repro.core.index.load_index_checkpoint`).
        """
        from repro.core.index import load_index_checkpoint
        from repro.core.updates import load_maintainable_index

        try:
            m, _ = load_maintainable_index(checkpoint_dir)
        except ValueError:  # no touch sketch: not a maintainable build
            index, _ = load_index_checkpoint(checkpoint_dir)
            return cls(graph, index, cfg, clock=clock)
        if m.real_n != graph.n:
            raise ValueError(
                f"checkpoint was built on {m.real_n} vertices but the "
                f"graph has {graph.n}")
        return cls(graph, None, cfg, clock=clock, maintainer=m)

    # -- client API ----------------------------------------------------------
    @functools.partial(annotate_function, name="ppr.submit")
    def submit(self, vertex: Optional[int] = None, tier: str = "interactive",
               arrival: Optional[float] = None,
               seeds: Optional[Sequence[int]] = None,
               weights: Optional[Sequence[float]] = None) -> int:
        """Enqueue a query: a single ``vertex`` or a weighted seed set
        (``seeds``/``weights``, uniform when weights omitted; at most
        ``query.max_seeds`` seeds).  With the answer cache enabled, a
        request whose canonical seed set is cached never reaches the
        request buffer — its answer is delivered by the next ``poll()``.

        Under admission control (``batching.max_queue_depth``) a submit
        against a full buffer is *shed*: it still gets a request id, but
        the next ``poll()`` delivers an empty answer with
        ``rejected=True`` instead of queueing the request into a latency
        cliff.  Cache hits bypass the buffer and are never shed.
        """
        if seeds is not None:
            # contract: allow(host-sync): validates a host-side seed list
            # at submit time, before anything touches the device
            s_arr = np.asarray(seeds, dtype=np.int64).reshape(-1)
            if s_arr.size > self.cfg.query.max_seeds:
                raise ValueError(
                    f"seed set of {s_arr.size} exceeds "
                    f"query.max_seeds={self.cfg.query.max_seeds}"
                )
        if self.cache.enabled:
            key = canonicalize_seed_set(
                [vertex] if seeds is None else seeds,
                None if seeds is None else weights,
                weight_quantum=self.cfg.cache.weight_quantum,
            )
            if key[0]:  # non-degenerate seed set: cacheable
                primary = (
                    int(vertex) if seeds is None
                    # contract: allow(host-sync): host-side seed list
                    else int(np.asarray(seeds).reshape(-1)[0])
                )
                hit = self.cache.get(key)
                if hit is not None:
                    rid = self.buffer.allocate_id()
                    t = self.clock() if arrival is None else arrival
                    self._pending_cached.append((rid, primary, tier, t, hit))
                    return rid
                # miss: dispatch the *canonical* spelling (sorted seeds,
                # quantized normalized weights) — every spelling of this
                # key then computes byte-identical answers, so the cached
                # answer is exact for all of them, not just the first
                quantum = self.cfg.cache.weight_quantum
                try:
                    rid = self.buffer.submit(
                        primary, tier=tier, arrival=arrival,
                        seeds=list(key[0]),
                        weights=[q * quantum for q in key[1]],
                    )
                except BufferOverloadError:
                    return self._reject(primary, tier, arrival)
                self._inflight_keys[rid] = key
                return rid
        try:
            return self.buffer.submit(
                vertex, tier=tier, arrival=arrival, seeds=seeds,
                weights=weights,
            )
        except BufferOverloadError:
            primary = (
                int(vertex) if seeds is None
                # contract: allow(host-sync): host-side seed list
                else int(np.asarray(seeds).reshape(-1)[0])
            )
            return self._reject(primary, tier, arrival)

    def _reject(self, vertex: int, tier: str, arrival: Optional[float]) -> int:
        """Record a shed request; its ``rejected=True`` answer (empty
        top-k) is delivered by the next ``poll()``."""
        rid = self.buffer.allocate_id()
        t = self.clock() if arrival is None else arrival
        self._pending_rejected.append((rid, int(vertex), tier, t))
        self.stats["shed"] += 1
        return rid

    def invalidate(self, vertices: Iterable[int]) -> int:
        """Drop cached answers whose seed sets touch ``vertices`` (the hook
        an index/graph update calls); returns entries removed.  Also bumps
        the cache epoch, so in-flight batches dispatched before this call
        are not absorbed into the cache when harvested."""
        return self.cache.invalidate(vertices)

    def apply_updates(self, inserts=None, deletes=None) -> dict:
        """Apply an edge-update batch to the live graph + index.

        Requires the service to have been constructed with a
        ``maintainer`` (``core.updates.build_maintainable_index``).  Runs
        incremental repair (``core.updates.apply_updates``), swaps the
        engine onto the updated graph/index, then invalidates exactly the
        dirtied fingerprint rows in the answer cache — which also bumps
        the cache epoch, fencing out any batch still in flight on the old
        index.  Returns the repair report plus ``cache_invalidated``.

        The swap is atomic: every piece of replacement state (repaired
        index, new engine) is constructed *before* any service attribute
        changes, so a failure anywhere — repair or engine construction —
        leaves the service exactly as it was, still serving the old
        graph/index (``stats["update_rollbacks"]`` counts these).  A
        half-applied update (new graph, old engine) would silently serve
        wrong answers, which is strictly worse than failing the update.
        """
        if self.maintainer is None:
            raise ValueError(
                "apply_updates requires a maintainer "
                "(build the index via core.updates.build_maintainable_index "
                "and pass it to PPRService(..., maintainer=...))")
        from repro.core import updates as updates_mod

        try:
            new_graph, new_m, report = updates_mod.apply_updates(
                self.maintainer, self.graph, inserts=inserts, deletes=deletes)
            new_engine = BatchQueryEngine(
                new_graph, new_m.index, self.cfg.query)
        except BaseException:
            self.stats["update_rollbacks"] += 1
            raise
        # commit point: plain attribute assignments only — nothing below
        # this line can raise halfway through the swap
        self.graph = new_graph
        self.maintainer = new_m
        self.engine = new_engine
        self.pipeline.engine = new_engine
        self.frontier_path = (
            "sparse" if self.engine.uses_sparse_path() else "dense")
        self.answer_k = self.engine.effective_top_k
        self.index_rows = new_m.index.n
        # exact invalidation: an answer is stale iff one of its seeds' rows
        # was repaired.  Always runs (even for an empty dirty set) so the
        # epoch bump fences in-flight batches computed on the old index.
        report["cache_invalidated"] = self.cache.invalidate(
            report["dirty_row_ids"])
        self.stats["updates_applied"] += 1
        self.stats["rows_repaired"] += report["dirty_rows"]
        return report

    @property
    def in_flight(self) -> int:
        return self.pipeline.in_flight

    def poll(self, force: bool = False) -> List[Answer]:
        """Advance the pipeline; returns completed answers.

        Dispatches every ready batch (``force`` drains the buffer
        regardless of deadlines) and harvests finished ones.  At
        ``pipeline.depth=1`` — or with ``force`` — the harvest blocks, so
        every dispatched batch's answers come back from the same call,
        matching the pre-pipeline blocking ``poll()``.  Cache-hit answers
        pending since ``submit`` are always delivered, pipeline or not.
        """
        cached = self._drain_cached() + self._drain_rejected()
        if (not len(self.buffer) or not (self.buffer.ready() or force)) \
                and not self.pipeline.in_flight:
            return cached
        drain = force or self.cfg.pipeline.depth <= 1
        completed = self.pipeline.dispatch(force=force)
        completed.extend(self.pipeline.harvest(drain=drain))
        # harvesting freed pipeline slots; a deadline-fired batch deferred
        # while the device was busy can launch now instead of next poll
        more = self.pipeline.dispatch(force=force)
        if more or (drain and self.pipeline.in_flight):
            completed.extend(more)
            completed.extend(self.pipeline.harvest(drain=drain))
        if not completed:  # absorb (and its span) only what came back
            return cached
        return cached + self._absorb(completed)

    # -- bookkeeping ---------------------------------------------------------
    def _drain_cached(self) -> List[Answer]:
        """Materialize answers for cache hits recorded at submit time.
        Latency runs from the (possibly backdated) arrival to *now* — a hit
        still pays its queueing delay in the metrics, it just skips the
        device."""
        if not self._pending_cached:
            return []
        out: List[Answer] = []
        now = self.clock()
        for rid, vertex, tier, arrival, (tv, ts) in self._pending_cached:
            lat = now - arrival
            out.append(Answer(rid, vertex, tv, ts, lat, tier, cached=True))
            self.stats["served"] += 1
            self.stats["cache_served"] += 1
            self.stats["total_latency"] += lat
            self.stats["max_latency"] = max(self.stats["max_latency"], lat)
        self._pending_cached.clear()
        return out

    def _drain_rejected(self) -> List[Answer]:
        """Materialize ``rejected=True`` answers for shed requests.  Shed
        traffic never occupied a batch row, so it stays out of the
        served/latency metrics — ``stats["shed"]`` is its ledger."""
        if not self._pending_rejected:
            return []
        out: List[Answer] = []
        now = self.clock()
        empty_v = np.zeros(0, dtype=np.int64)
        empty_s = np.zeros(0, dtype=np.float32)
        for rid, vertex, tier, arrival in self._pending_rejected:
            out.append(Answer(
                rid, vertex, empty_v, empty_s, now - arrival, tier,
                rejected=True,
            ))
        self._pending_rejected.clear()
        return out

    @functools.partial(annotate_function, name="ppr.absorb")
    def _absorb(self, completed: List[CompletedBatch]) -> List[Answer]:
        out: List[Answer] = []
        for batch in completed:
            self.stats["pad_rows"] += batch.padded - len(batch.requests)
            self.stats["batches"] += 1
            for i, r in enumerate(batch.requests):
                lat = batch.completed_at - r.arrival
                out.append(Answer(
                    r.request_id, r.vertex, batch.indices[i],
                    batch.values[i], lat, r.tier,
                ))
                key = self._inflight_keys.pop(r.request_id, None)
                if key is not None:
                    # invalidate-vs-in-flight fence: a batch dispatched
                    # before an invalidate/apply_updates carries an older
                    # cache epoch — its answer was computed on the old
                    # index, so it is returned to the client (the request
                    # predates the update) but never written into the
                    # cache, where it would outlive the invalidation.
                    if batch.epoch == self.cache.epoch:
                        self.cache.put(key, batch.indices[i], batch.values[i])
                    else:
                        self.stats["cache_stale_drops"] += 1
                self.stats["served"] += 1
                self.stats["total_latency"] += lat
                self.stats["max_latency"] = max(self.stats["max_latency"], lat)
        return out

    def reset_stats(self) -> None:
        """Zero counters (e.g. after warmup dispatches in a benchmark)."""
        for k in self.stats:
            self.stats[k] = 0 if isinstance(self.stats[k], int) else 0.0
        for k in self.pipeline.stats:
            self.pipeline.stats[k] = 0
        self.pipeline.batch_hist.clear()
        for k in self.buffer.stats:
            self.buffer.stats[k] = 0
        for k in self.cache.stats:  # counters only; cached entries persist
            self.cache.stats[k] = 0

    def snapshot_stats(self) -> dict:
        """Service + pipeline telemetry as one flat dict (JSON-safe)."""
        s = dict(self.stats)
        s["frontier_path"] = self.frontier_path
        s["answer_k"] = self.answer_k
        s["index_rows"] = self.index_rows
        s["index_sharded"] = self.index_sharded
        s["pipeline_depth"] = self.cfg.pipeline.depth
        s["dispatch_path"] = self.cfg.pipeline.dispatch
        s["max_queue_depth"] = self.cfg.batching.max_queue_depth
        s["buffer_shed"] = self.buffer.stats["shed"]
        s["combine_path"] = (
            "scatter" if self.engine.uses_scatter_combine(
                self.cfg.batching.max_batch) else "sparse"
        ) if self.frontier_path == "sparse" else "dense"
        s.update({f"pipeline_{k}": v for k, v in self.pipeline.stats.items()})
        s["batch_hist"] = {
            int(k): int(v) for k, v in sorted(self.pipeline.batch_hist.items())
        }
        s["mean_latency"] = s["total_latency"] / max(s["served"], 1)
        # pad_fraction is a *batch* occupancy metric: cache-served answers
        # never occupied a batch row, so they stay out of the denominator
        computed = s["served"] - s["cache_served"]
        s["pad_fraction"] = s["pad_rows"] / max(computed + s["pad_rows"], 1)
        s.update({f"cache_{k}": v for k, v in self.cache.stats.items()})
        s["cache_size"] = len(self.cache)
        s["cache_capacity"] = self.cfg.cache.capacity
        s["cache_hit_rate"] = self.cache.stats["hits"] / max(
            self.cache.stats["hits"] + self.cache.stats["misses"], 1
        )
        s["cache_epoch"] = self.cache.epoch
        s["cache_reverse_entries"] = self.cache.reverse_index_entries()
        # eviction/invalidation hygiene: the reverse index must exactly
        # mirror the live entries — asserts here so any churn regression
        # surfaces in every stats snapshot, not just dedicated tests
        self.cache.check_integrity()
        return s

    def run_closed_loop(self, vertices: Sequence[int]) -> Tuple[List[Answer], dict]:
        """Serve a fixed workload to completion (benchmark mode).

        Thin wrapper over the open-loop harness at unbounded offer rate —
        see ``serving/loadgen.py`` for the rate-controlled version.
        """
        from repro.serving import loadgen
        return loadgen.run_closed_loop(self, vertices)
