"""Online batch-query engine (paper Section 3.3).

Buffers incoming PPR queries, executes them as one shared decomposition, and
returns top-k answers.  All four strategies of the paper's Table 3 are
selectable:

* ``powerwalk`` — VERD iterations + index combine (the contribution),
* ``verd``      — VERD with no index (the paper's R = 0 column),
* ``fppr``      — direct index lookup (Fogaras-style full precomputation),
* ``mcfp``      — online Monte-Carlo (no index),
* ``pi``        — power iteration (accuracy reference; impractical at scale).
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mcfp as mcfp_mod
from repro.core import power_iteration as pi_mod
from repro.core import verd as verd_mod
from repro.core.graph import Graph
from repro.core.index import PPRIndex
from repro.core.walks import DEFAULT_C


# Auto path selection: below this vertex count the dense [Q, n] frontier is
# cheap enough that the sparse bookkeeping (sort-based compaction) isn't
# worth it; above it the dense path's Q*n*8 bytes of state dominates.  See
# docs/query_path.md for the memory formulas.  Retuned 1<<15 -> 1<<14 from
# the recorded bench_query sparse sweep (docs/query_path.md): the sparse
# path already wins 6-8x at n = 16k-20k with L1 within the truncation
# bound, so the old threshold left a 2x band of graphs on the slow path.
AUTO_SPARSE_MIN_N = 1 << 14

# Serving fast path: the *final* index combine may scatter its candidates
# into a dense [Q, n] f32 scratch and lax.top_k it instead of running the
# sort-based sparse compaction (verd.combine_with_index_scatter).  The
# scratch is transient and only exists at the combine — the iterations stay
# Q x K — so "auto" takes it whenever Q * n * 4 bytes fits this budget and
# falls back to the n-independent sparse combine beyond it.
SCATTER_COMBINE_BUDGET_BYTES = 256 * 1024 * 1024


def auto_frontier_floor(top_k: int) -> int:
    """Minimum auto-derived sparse frontier width K: 4x the answer size
    with an absolute floor.  Shared by the engine selector below and
    ``DistConfig.resolved_frontier_k`` so the single-device and distributed
    paths derive the same K at the same config (retune it here once)."""
    return max(4 * top_k, 256)


def normalize_seed_weights(weights: jax.Array) -> jax.Array:
    """Seed-set weights normalized to sum 1 per row (f32).

    The engine's one normalization point: queries are scale-invariant in
    their seed weights (PPR restarts at a *distribution*), so the engine
    divides by the row sum before anything downstream sees the weights —
    which is also what lets ``serving.cache`` canonicalize rescaled seed
    sets onto one cache entry.  Weight-0 pad slots stay 0.  All-zero rows
    (nothing real in the row — pad queries) degrade to all-zero weights
    rather than NaN.
    """
    w = jnp.asarray(weights, jnp.float32)
    return w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-30)


def _fppr_lookup(
    index: PPRIndex, sources: jax.Array, seed_w: Optional[jax.Array]
) -> jax.Array:
    """fppr dense answers: a plain row lookup, or — for seed sets — the
    weighted sum of each seed's index row (fppr has no iterate to seed, but
    PPR linearity makes the lookup combine exact at index precision)."""
    if seed_w is None:
        return index.lookup_dense(sources)
    q, s = sources.shape
    rows = index.lookup_dense(sources.reshape(-1)).reshape(q, s, -1)
    return jnp.sum(seed_w[:, :, None] * rows, axis=1)


@dataclasses.dataclass
class QueryConfig:
    mode: str = "powerwalk"       # powerwalk | verd | fppr | mcfp | pi
    t_iterations: int = 2          # VERD iterations (paper: 2 at R=100)
    c: float = DEFAULT_C
    top_k: int = 200               # answer size (paper evaluates k<=200)
    r_online: int = 2000           # walks for online-MCFP baseline
    pi_iterations: int = 100
    threshold: float = 0.0         # VERD frontier sparsification epsilon
    max_batch: int = 4096          # shared-decomposition batch size
    frontier_k: int = 0            # sparse frontier width (0 = auto-derive)
    frontier_path: str = "auto"    # dense | sparse | auto
    combine_path: str = "auto"     # sparse | scatter | auto — how the sparse
                                   # route merges its final combine candidates
                                   # (auto: scatter while Q*n*4 bytes fits
                                   # SCATTER_COMBINE_BUDGET_BYTES)
    hub_split_degree: int = 0      # ELL row-split width for the sparse push
                                   # (0 = no splitting; see verd.gather_push_edges)
    max_seeds: int = 1             # seed-set width S_max: queries may carry up
                                   # to this many weighted seed vertices per
                                   # row, padded with weight-0 slots to one
                                   # stable jit shape (1 = classic
                                   # single-vertex queries)
    seed: int = 0                  # base PRNG seed for the Monte-Carlo
                                   # modes (mcfp); distinct per process so
                                   # replicas don't share MC noise


class BatchQueryEngine:
    """Executes batches of PPR queries with a shared decomposition."""

    def __init__(
        self,
        graph: Graph,
        index: Optional[PPRIndex] = None,
        config: Optional[QueryConfig] = None,
    ):
        self.graph = graph
        self.index = index
        self.config = config or QueryConfig()
        if self.config.mode in ("powerwalk", "fppr") and index is None:
            raise ValueError(f"mode {self.config.mode} requires a PPR index")
        if index is not None and index.n < graph.n:
            raise ValueError(
                f"index covers {index.n} rows < graph.n={graph.n}"
            )
        if self.config.frontier_path not in ("dense", "sparse", "auto"):
            raise ValueError(
                f"unknown frontier_path {self.config.frontier_path!r}"
            )
        if self.config.combine_path not in ("sparse", "scatter", "auto"):
            raise ValueError(
                f"unknown combine_path {self.config.combine_path!r}"
            )
        if self.config.max_seeds > 1 and self.config.mode in ("mcfp", "pi"):
            raise ValueError(
                f"mode {self.config.mode!r} does not support seed-set "
                "queries (max_seeds > 1): it is not linear in a start "
                "vector the engine can combine"
            )
        # base key is pure config (seed), so a rebuilt engine replays the
        # same MC noise; the stateful split below serves direct query_dense
        # calls, while run() folds chunk offsets for per-chunk determinism
        self._base_key = jax.random.PRNGKey(self.config.seed)
        self._key = self._base_key
        self._degree_cap: Optional[int] = None  # resolved lazily, host-side

    # -- sparse-path plumbing ------------------------------------------------
    @property
    def frontier_k(self) -> int:
        """Effective sparse-frontier width K (cfg.frontier_k or auto).

        The auto width covers the *expected* frontier support after ``t``
        pushes (~ mean_degree**t) so that auto-routed sparse answers are not
        silently truncated; graphs whose support estimate forces K near n
        then fail the ``uses_sparse_path`` guards and stay dense/exact.
        An explicit ``cfg.frontier_k`` overrides the estimate.
        """
        cfg = self.config
        n = self.graph.n
        if cfg.frontier_k > 0:
            return min(cfg.frontier_k, n)
        mean_deg = self.graph.m / max(n, 1)
        # log space: mean_deg ** t overflows float at absurd t; saturate at n.
        # A seed-set query starts from up to max_seeds vertices, so its
        # frontier support scales the single-vertex estimate by S_max.
        log_support = (
            cfg.t_iterations * math.log(max(mean_deg, 1.0))
            + math.log(max(cfg.max_seeds, 1))
        )
        if log_support >= math.log(max(n, 1)):
            # contract: allow(host-sync): n is a static python int
            support = float(n)
        else:
            support = math.exp(log_support)
        return min(
            n, max(auto_frontier_floor(cfg.top_k), int(math.ceil(support)))
        )

    def uses_sparse_path(self) -> bool:
        """Route decision: does query_topk hold Q x K instead of Q x n?

        Only the VERD modes have a frontier; ``auto`` picks sparse once the
        dense state (Q*n*8 bytes/query-pair) dwarfs the sparse state
        (~Q*K*8), i.e. on large graphs where K << n — AND the push's gather
        tile (Q*K*gather-width entries) stays below the dense row width it
        replaces.  The gather width is :meth:`effective_gather_width`: the
        max out-degree, or ``hub_split_degree`` once ELL splitting is on —
        so hub-heavy graphs route sparse as soon as a split width is set,
        because every gather axis (and the kernels' per-step VMEM) is then
        bounded by ``h`` regardless of how large the hubs are.
        """
        cfg = self.config
        if cfg.mode not in ("powerwalk", "verd"):
            return False
        if cfg.frontier_path == "sparse":
            return True
        if cfg.frontier_path == "dense":
            return False
        return (
            self.graph.n >= AUTO_SPARSE_MIN_N
            and 8 * self.frontier_k <= self.graph.n
            and self.frontier_k * self.effective_gather_width() <= self.graph.n
        )

    def uses_scatter_combine(self, q: int) -> bool:
        """Route decision for the sparse route's *final* combine: scatter
        into a transient dense ``[q, n]`` scratch (fast ``lax.top_k``) or
        keep the n-independent sort-based sparse compaction.

        Only the ``powerwalk`` sparse route has an index combine; ``auto``
        scatters while the scratch (``q * n * 4`` bytes) fits
        :data:`SCATTER_COMBINE_BUDGET_BYTES`.  Exact either way — this is a
        cost knob, not an accuracy knob."""
        cfg = self.config
        if cfg.mode != "powerwalk" or not self.uses_sparse_path():
            return False
        if cfg.combine_path == "scatter":
            return True
        if cfg.combine_path == "sparse":
            return False
        return q * self.graph.n * 4 <= SCATTER_COMBINE_BUDGET_BYTES

    def degree_cap(self) -> int:
        """Max out-degree (cached): the exact-mode edge budget per slot."""
        if self._degree_cap is None:
            self._degree_cap = verd_mod.resolve_degree_cap(self.graph)
        return self._degree_cap

    def effective_gather_width(self) -> int:
        """Widest gather axis of one sparse push: ``degree_cap`` unsplit,
        ``hub_split_degree`` once ELL hub splitting bounds every sub-slot
        (``verd.resolve_hub_splits``)."""
        h, _ = verd_mod.resolve_hub_splits(
            self.degree_cap(), self.config.hub_split_degree
        )
        return h

    @property
    def effective_top_k(self) -> int:
        """Served answer width: ``cfg.top_k`` clamped to the graph.

        The one place the clamp lives — an unclamped ``top_k > n`` would
        make ``lax.top_k`` reject the dense rows and the sparse route
        return a different width than the preallocated host buffers in
        :meth:`run` / ``PPRService.poll`` expect."""
        return max(1, min(self.config.top_k, self.graph.n))

    def query_sparse(
        self,
        sources: jax.Array,
        out_k: Optional[int] = None,
        weights: Optional[jax.Array] = None,
    ):
        """Sparse-path answers as a SparseFrontier (never builds [Q, n]).

        ``weights f32[Q, S]`` switches ``sources`` to seed-set rows
        ``int32[Q, S]`` (weights are normalized to sum 1 per row first)."""
        cfg = self.config
        if cfg.mode not in ("powerwalk", "verd"):
            raise ValueError(
                f"mode {cfg.mode!r} has no frontier; query_sparse supports "
                "the VERD modes (powerwalk, verd) only"
            )
        index = self.index if cfg.mode == "powerwalk" else None
        seed_w = None if weights is None else normalize_seed_weights(weights)
        return verd_mod.verd_query_sparse(
            self.graph, sources, index,
            t=cfg.t_iterations, k=self.frontier_k, c=cfg.c,
            threshold=cfg.threshold, out_k=out_k or self.effective_top_k,
            degree_cap=self.degree_cap(),
            hub_split_degree=cfg.hub_split_degree,
            seed_weights=seed_w,
        )

    # -- dense answers -----------------------------------------------------
    def query_dense(
        self,
        sources: jax.Array,
        *,
        key: Optional[jax.Array] = None,
        weights: Optional[jax.Array] = None,
    ) -> jax.Array:
        """Dense [Q, n] answers.  ``key`` overrides the Monte-Carlo stream
        of the ``mcfp`` mode (``run()`` passes a chunk-offset fold of the
        config seed so reruns are reproducible chunk by chunk); without it
        the engine's stateful key advances.  ``weights`` switches to
        seed-set rows (linear modes only — mcfp/pi raise)."""
        cfg = self.config
        g = self.graph
        seed_w = None if weights is None else normalize_seed_weights(weights)
        if seed_w is not None and cfg.mode in ("mcfp", "pi"):
            raise ValueError(
                f"mode {cfg.mode!r} does not support seed-set queries"
            )
        if cfg.mode == "powerwalk":
            return verd_mod.verd_query(
                g, sources, self.index, t=cfg.t_iterations, c=cfg.c,
                threshold=cfg.threshold, seed_weights=seed_w,
            )
        if cfg.mode == "verd":
            return verd_mod.verd_query(
                g, sources, None, t=cfg.t_iterations, c=cfg.c,
                threshold=cfg.threshold, seed_weights=seed_w,
            )
        if cfg.mode == "fppr":
            return _fppr_lookup(self.index, sources, seed_w)
        if cfg.mode == "mcfp":
            if key is None:
                self._key, key = jax.random.split(self._key)
            return mcfp_mod.estimate_ppr(g, sources, cfg.r_online, key, c=cfg.c)
        if cfg.mode == "pi":
            return pi_mod.power_iteration(
                g, sources, n_iter=cfg.pi_iterations, c=cfg.c
            )
        raise ValueError(f"unknown mode {cfg.mode!r}")

    # -- top-k answers (the served product) ---------------------------------
    def query_topk(
        self,
        sources: jax.Array,
        *,
        key: Optional[jax.Array] = None,
        weights: Optional[jax.Array] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        k = self.effective_top_k
        if self.uses_sparse_path():
            sf = self.query_sparse(sources, out_k=k, weights=weights)
            vals, idx = sf.values, sf.indices
        else:
            dense = self.query_dense(sources, key=key, weights=weights)
            vals, idx = jax.lax.top_k(dense, k)
        # static-shape width contract (trace time): every route must hand
        # back exactly the clamped width the host buffers were sized for
        assert vals.shape[-1] == k and idx.shape[-1] == k, (
            vals.shape, idx.shape, k,
        )
        return vals, idx

    # -- async dispatch (the serving pipeline's entry point) -----------------
    def dispatch_key(self, seq: int) -> jax.Array:
        """Per-dispatch PRNG key: the config-seed base key with the
        dispatch sequence number folded in, so Monte-Carlo answers are
        reproducible for a given (seed, dispatch order) at any pipeline
        depth — the async path never advances the stateful key."""
        return jax.random.fold_in(self._base_key, seq)

    def query_topk_async(
        self,
        sources: jax.Array,
        *,
        key: Optional[jax.Array] = None,
        weights: Optional[jax.Array] = None,
        out: Optional[Tuple[jax.Array, jax.Array]] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        """Top-k answers as *unmaterialized* device arrays.

        The whole query — iterate, combine, top-k — is one jitted
        computation, so this returns as soon as the work is enqueued on the
        device stream (JAX async dispatch): no host sync, no per-op Python
        dispatch between stages.  ``serving.pipeline`` launches several of
        these back to back and harvests them through a completion queue;
        callers that want a blocking answer can ``block_until_ready()`` the
        result, which is bit-identical to :meth:`query_topk` on the same
        route/combine.  ``key`` seeds the ``mcfp`` mode (ignored elsewhere);
        default is the engine's base key — pass :meth:`dispatch_key` for
        distinct, replayable noise per dispatch.

        ``weights f32[Q, S]`` switches ``sources`` to seed-set rows
        ``int32[Q, S]``.  ``out = (vals f32[Q, k], idx int32[Q, k])``
        optionally *donates* a pair of result buffers: the answer is written
        into their device memory instead of a fresh allocation (the passed
        arrays are consumed — use the returned ones), which is how the
        serving pipeline's buffer ring avoids a per-dispatch allocation.
        """
        sources = jnp.asarray(sources, jnp.int32)
        q = int(sources.shape[0])
        cfg = self.config
        if key is None:
            key = self._base_key
        if weights is not None and cfg.mode in ("mcfp", "pi"):
            raise ValueError(
                f"mode {cfg.mode!r} does not support seed-set queries"
            )
        sparse_route = self.uses_sparse_path()
        statics = dict(
            mode=cfg.mode,
            t=cfg.t_iterations,
            c=cfg.c,
            top_k=self.effective_top_k,
            r_online=cfg.r_online,
            pi_iterations=cfg.pi_iterations,
            threshold=cfg.threshold,
            frontier_k=self.frontier_k,
            degree_cap=self.degree_cap() if sparse_route else 0,
            hub_split_degree=cfg.hub_split_degree,
            sparse_route=sparse_route,
            scatter_combine=self.uses_scatter_combine(q),
        )
        index = self.index if cfg.mode in ("powerwalk", "fppr") else None
        if out is None:
            return _fused_topk(
                self.graph, index, sources, key, weights, **statics
            )
        return _fused_topk_into(
            self.graph, index, sources, key, out[0], out[1], weights,
            **statics,
        )

    # -- batched driver ------------------------------------------------------
    def run(self, sources, weights=None) -> dict:
        """Execute a (possibly large) query set in max_batch chunks.

        Returns answers + timing; mirrors the paper's Table 3 measurements.
        The Monte-Carlo mode folds each chunk's offset into the config-seed
        key, so rerunning the same engine (or a rebuilt one with the same
        seed) reproduces every chunk bit for bit.  ``weights f32[N, S]``
        switches ``sources int32[N, S]`` to seed-set rows.
        """
        # contract: allow(host-sync): run() is the offline batched driver —
        # it normalizes host inputs and materializes every chunk by design
        sources = np.asarray(sources, dtype=np.int32)
        weights = (
            # contract: allow(host-sync): host input normalization
            None if weights is None else np.asarray(weights, dtype=np.float32)
        )
        k = self.effective_top_k
        vals = np.zeros((len(sources), k), dtype=np.float32)
        idxs = np.zeros((len(sources), k), dtype=np.int32)
        start = time.perf_counter()
        for i in range(0, len(sources), self.config.max_batch):
            chunk = jnp.asarray(sources[i : i + self.config.max_batch])
            w_chunk = (
                None if weights is None
                else jnp.asarray(weights[i : i + self.config.max_batch])
            )
            v, ix = self.query_topk(
                chunk, key=jax.random.fold_in(self._base_key, i),
                weights=w_chunk,
            )
            v.block_until_ready()  # contract: allow(host-sync): offline driver
            vals[i : i + len(chunk)] = np.asarray(v)  # contract: allow(host-sync): offline driver
            idxs[i : i + len(chunk)] = np.asarray(ix)  # contract: allow(host-sync): offline driver
        elapsed = time.perf_counter() - start
        return dict(
            values=vals,
            indices=idxs,
            seconds=elapsed,
            queries=len(sources),
            qps=len(sources) / max(elapsed, 1e-9),
            mode=self.config.mode,
            top_k=k,
        )


# ---------------------------------------------------------------------------
# Fused top-k query: one jitted computation covering every mode/route, so a
# serving dispatch is a single async XLA launch.  Module-level (not a bound
# method) so the jit cache is shared across engines over the same
# graph/index pytrees and keyed only by the static route arguments.
# ---------------------------------------------------------------------------

_FUSED_STATICS = (
    "mode", "t", "c", "top_k", "r_online", "pi_iterations", "threshold",
    "frontier_k", "degree_cap", "hub_split_degree", "sparse_route",
    "scatter_combine",
)


def _fused_topk_impl(
    graph: Graph,
    index: Optional[PPRIndex],
    sources: jax.Array,
    key: jax.Array,
    weights: Optional[jax.Array],
    *,
    mode: str,
    t: int,
    c: float,
    top_k: int,
    r_online: int,
    pi_iterations: int,
    threshold: float,
    frontier_k: int,
    degree_cap: int,
    hub_split_degree: int,
    sparse_route: bool,
    scatter_combine: bool,
) -> Tuple[jax.Array, jax.Array]:
    seed_w = None if weights is None else normalize_seed_weights(weights)
    if sparse_route:
        if scatter_combine and mode == "powerwalk":
            s, f = verd_mod.verd_iterate_sparse(
                graph, sources, seed_w,
                t=t, k=frontier_k, c=c, threshold=threshold,
                degree_cap=degree_cap, hub_split_degree=hub_split_degree,
            )
            vals, idx = verd_mod.combine_with_index_scatter(
                s, f, index, out_k=top_k,
            )
        else:
            sf = verd_mod.verd_query_sparse(
                graph, sources, index if mode == "powerwalk" else None,
                t=t, k=frontier_k, c=c, threshold=threshold, out_k=top_k,
                degree_cap=degree_cap, hub_split_degree=hub_split_degree,
                seed_weights=seed_w,
            )
            vals, idx = sf.values, sf.indices
    else:
        if mode in ("powerwalk", "verd"):
            dense = verd_mod.verd_query(
                graph, sources, index if mode == "powerwalk" else None,
                t=t, c=c, threshold=threshold, seed_weights=seed_w,
            )
        elif mode == "fppr":
            dense = _fppr_lookup(index, sources, seed_w)
        elif mode == "mcfp":
            dense = mcfp_mod.estimate_ppr(graph, sources, r_online, key, c=c)
        elif mode == "pi":
            dense = pi_mod.power_iteration(graph, sources, n_iter=pi_iterations, c=c)
        else:
            raise ValueError(f"unknown mode {mode!r}")
        with jax.named_scope("ppr.topk"):
            vals, idx = jax.lax.top_k(dense, top_k)
            idx = idx.astype(jnp.int32)
    # same static-shape width contract as query_topk
    assert vals.shape[-1] == top_k and idx.shape[-1] == top_k, (
        vals.shape, idx.shape, top_k,
    )
    return vals, idx


@functools.partial(jax.jit, static_argnames=_FUSED_STATICS)
def _fused_topk(
    graph: Graph,
    index: Optional[PPRIndex],
    sources: jax.Array,
    key: jax.Array,
    weights: Optional[jax.Array] = None,
    **statics,
) -> Tuple[jax.Array, jax.Array]:
    return _fused_topk_impl(graph, index, sources, key, weights, **statics)


@functools.partial(
    jax.jit, static_argnames=_FUSED_STATICS, donate_argnums=(4, 5)
)
def _fused_topk_into(
    graph: Graph,
    index: Optional[PPRIndex],
    sources: jax.Array,
    key: jax.Array,
    out_v: jax.Array,
    out_i: jax.Array,
    weights: Optional[jax.Array] = None,
    **statics,
) -> Tuple[jax.Array, jax.Array]:
    """:func:`_fused_topk` writing into *donated* result buffers.

    ``out_v``/``out_i`` (f32/int32 ``[Q, top_k]``) are donated to XLA: the
    answer lands in their device memory, so a steady-state serving loop that
    rings a fixed pool of buffers through dispatch -> harvest -> redispatch
    performs no per-dispatch result allocation at all.
    """
    vals, idx = _fused_topk_impl(graph, index, sources, key, weights, **statics)
    return out_v.at[:].set(vals), out_i.at[:].set(idx.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Contract-auditor entry points (repro.analysis).
#
# dense-state-bound: the sparse query path must hold Q x K state, never an
# f32[Q, n] dense frontier (the scatter combine is budget-gated separately,
# so the audit pins the comparator combine path).  The widest legal f32
# intermediate is the combine candidate row (~K*L wide) plus the push
# gather area (~K*degree_cap), far under the dense floor Q*n.
#
# retrace-guard: the fused serving jit must compile exactly one cache entry
# per bucketed pad width — a weak-type or dtype wobble in the dispatch path
# (e.g. a python-int seed list vs an np.int32 array) would silently double
# compile time and jit-cache footprint in production.
# ---------------------------------------------------------------------------

from repro.analysis.registry import register_entry_point as _register_ep


def _contract_spec_sparse_query():
    import numpy as np

    from repro.graphs import synthetic

    n, q, l = 1 << 14, 8, 16
    g = synthetic.erdos_renyi(n, 3.0, seed=7)
    rng = np.random.default_rng(0)
    index = PPRIndex(
        values=jnp.asarray(rng.random((n, l)), jnp.float32),
        indices=jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32),
        l=l, n=n,
    )
    engine = BatchQueryEngine(g, index, QueryConfig(
        mode="powerwalk", t_iterations=2, top_k=32, frontier_k=128,
        frontier_path="sparse", combine_path="sparse",
    ))
    cap = engine.degree_cap()   # primed outside the trace (host-side max)
    k = engine.frontier_k
    sources = jnp.arange(q, dtype=jnp.int32)
    jaxpr = jax.make_jaxpr(lambda s: engine.query_topk_async(s))(sources)
    budget = q * (k * (cap + l + 8) + 1024)
    return dict(jaxpr=jaxpr, budget=budget, floor=q * n)


def _retrace_spec_fused_topk():
    import numpy as np

    from repro.graphs import synthetic
    from repro.serving.batching import BatchingConfig

    n, l = 256, 8
    g = synthetic.erdos_renyi(n, 4.0, seed=3)
    rng = np.random.default_rng(1)
    index = PPRIndex(
        values=jnp.asarray(rng.random((n, l)), jnp.float32),
        indices=jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32),
        l=l, n=n,
    )
    engine = BatchQueryEngine(
        g, index, QueryConfig(mode="powerwalk", t_iterations=1, top_k=8)
    )
    widths = BatchingConfig(max_batch=64).padded_shapes()

    def call(width: int, variant: int) -> None:
        # three spellings of the same batch a production dispatcher might
        # produce; all must normalize to one (shape, dtype) cache entry
        if variant == 0:
            srcs = np.zeros(width, np.int32)
        elif variant == 1:
            srcs = jnp.zeros(width, jnp.int32)
        else:
            srcs = [0] * width
        engine.query_topk_async(srcs, key=engine.dispatch_key(0))

    return dict(jit_fn=_fused_topk, widths=widths, variants=3, call=call)


_register_ep("sparse-query-path", "dense-state-bound",
             "src/repro/core/query.py", _contract_spec_sparse_query)
_register_ep("fused-topk-serving", "retrace-guard",
             "src/repro/core/query.py", _retrace_spec_fused_topk)
