"""Vectorized random-walk engine (the TPU rewrite of DrunkardMob).

DrunkardMob advances billions of walks by streaming the graph from disk and
moving the in-memory (vertex -> walks) map.  On TPU the same insight —
*advance all walks in bulk, never chase one walk* — becomes a dense cursor
array ``int32[W]`` advanced by a ``lax.scan``: one gather for the degrees,
one gather for the sampled out-edge, one scatter-add for the visit counts.
Walk state never leaves the device.

Termination follows the paper: at every position the walk teleports
(terminates) with probability ``c``; a walk sitting on a dangling vertex
jumps back to its personalization source (paper Section 2.1).  Walks are
capped at ``max_steps`` positions; the lost tail mass is ``(1-c)^max_steps``
(3e-5 at the default 64), far below Monte-Carlo noise at practical ``R``.

A single pass produces both estimators:

* **MCFP** (Algorithm 1): counts every visited position; normalize by total
  moves.
* **MCEP** (Algorithm 2, Fogaras et al.): counts only the final position;
  normalize by the number of walks.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import frontier as frontier_mod
from repro.core.graph import Graph

DEFAULT_C = 0.15


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class WalkCounts:
    """Aggregated walk statistics grouped into ``rows`` source rows.

    fp_counts: f32[rows, n] full-path visit counts (MCFP numerator).
    ep_counts: f32[rows, n] end-point counts (MCEP numerator).
    moves:     f32[rows]    total counted positions per row (MCFP denom).
    walks:     f32[rows]    number of walks per row (MCEP denominator).
    """

    fp_counts: jax.Array
    ep_counts: jax.Array
    moves: jax.Array
    walks: jax.Array


def _one_step(
    graph: Graph, key: jax.Array, cursors: jax.Array, sources: jax.Array
) -> jax.Array:
    """Advance every walk one edge (dangling vertices jump to source)."""
    deg = jnp.take(graph.out_deg, cursors)
    lo = jnp.take(graph.row_ptr, cursors)
    off = jax.random.randint(
        key, cursors.shape, 0, jnp.maximum(deg, 1), dtype=jnp.int32
    )
    nxt = jnp.take(graph.col_idx, lo + off)
    return jnp.where(deg == 0, sources, nxt)


@functools.partial(
    jax.jit, static_argnames=("n_rows", "max_steps", "unroll")
)
def simulate_walks(
    graph: Graph,
    walk_sources: jax.Array,
    walk_rows: jax.Array,
    key: jax.Array,
    *,
    n_rows: int,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    unroll: int = 1,
) -> WalkCounts:
    """Run one walk per entry of ``walk_sources`` and aggregate counts.

    walk_sources: int32[W] start (= personalization) vertex of each walk.
    walk_rows:    int32[W] output row each walk accumulates into (so ``R``
                  walks of one source share a row).
    """
    w = walk_sources.shape[0]
    n = graph.n

    def body(carry, t):
        cursors, active, fp, ep, moves, walks_done = carry
        step_key = jax.random.fold_in(key, t)
        k_move, k_term = jax.random.split(step_key)
        af = active.astype(fp.dtype)
        # count current position (MCFP numerator + move counter)
        fp = fp.at[walk_rows, cursors].add(af)
        moves = moves.at[walk_rows].add(af)
        # teleport draw at this position
        terminate = active & (
            jax.random.uniform(k_term, cursors.shape) < c
        )
        tf = terminate.astype(ep.dtype)
        ep = ep.at[walk_rows, cursors].add(tf)
        walks_done = walks_done.at[walk_rows].add(tf)
        active = active & ~terminate
        cursors = _one_step(graph, k_move, cursors, walk_sources)
        return (cursors, active, fp, ep, moves, walks_done), ()

    init = (
        walk_sources,
        jnp.ones((w,), dtype=bool),
        jnp.zeros((n_rows, n), dtype=jnp.float32),
        jnp.zeros((n_rows, n), dtype=jnp.float32),
        jnp.zeros((n_rows,), dtype=jnp.float32),
        jnp.zeros((n_rows,), dtype=jnp.float32),
    )
    (cursors, active, fp, ep, moves, walks_done), _ = jax.lax.scan(
        body, init, jnp.arange(max_steps), unroll=unroll
    )
    # Walks still active after the cap: their current position is the
    # endpoint (truncation; tail mass (1-c)^max_steps).
    af = active.astype(ep.dtype)
    ep = ep.at[walk_rows, cursors].add(af)
    walks_done = walks_done.at[walk_rows].add(af)
    return WalkCounts(fp_counts=fp, ep_counts=ep, moves=moves, walks=walks_done)


def walks_for_sources(
    sources: jax.Array, r: int
) -> Tuple[jax.Array, jax.Array]:
    """Expand ``sources[int32[S]]`` into (walk_sources, walk_rows) with ``r``
    walks per source."""
    s = sources.shape[0]
    walk_sources = jnp.repeat(sources, r)
    walk_rows = jnp.repeat(jnp.arange(s, dtype=jnp.int32), r)
    return walk_sources, walk_rows


def sample_walk_lengths(
    key: jax.Array, w: int, c: float = DEFAULT_C, max_steps: int = 64
) -> jax.Array:
    """Walk lengths only (positions per walk) — used by property tests to
    check the geometric(c) law the theory relies on."""
    u = jax.random.uniform(key, (w, max_steps))
    alive = jnp.cumprod((u >= c).astype(jnp.int32), axis=1)
    return 1 + alive.sum(axis=1)


# ---------------------------------------------------------------------------
# Compacted sparse-sketch walk engine (the scalable offline path).
#
# Two structural fixes over ``simulate_walks``:
#
# * **Live-walk compaction**: walk length is geometric(c) with mean ``1/c``
#   (~6.7 at the default), so after ``t`` steps only ``(1-c)^t`` of the walk
#   slots are alive — a fixed-width scan over ``W`` slots for ``max_steps``
#   rounds spends >85% of its device steps moving dead walks.  Here the slot
#   array shrinks through a *static bucket schedule* derived from
#   ``(1-c)^t``: every ``compact_every`` steps the surviving cursors are
#   compacted into the low slots (``jnp.cumsum`` over the active mask — the
#   same compaction idiom as ``frontier.py``) and the working width drops to
#   the next bucket.  Device work tracks live walks, not ``W x max_steps``.
#
# * **Sparse count sketches**: the ``f32[rows, n]`` fp/ep accumulators
#   become per-row fixed-width top-``L`` sketches (the ``SparseFrontier``
#   idiom ``PPRIndex`` already uses): each round's visit events are folded
#   into the running sketch by sort-by-(row, vertex) + segment-sum
#   (:func:`repro.core.frontier.fold_topk`), so memory is ``O(rows * L)``
#   and the truncated mass is tracked exactly per row.
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseWalkCounts:
    """Sketched walk statistics grouped into ``rows`` source rows.

    fp: SparseFrontier[rows, L]  top-L visit-count sketch (MCFP numerator).
    ep: SparseFrontier[rows, Lp] top-Lp end-point sketch (MCEP numerator).
    moves:      f32[rows] counted positions per row (MCFP denominator).
    walks:      f32[rows] finished walks per row (MCEP denominator) —
                terminated + truncated; always exactly ``R`` per row.
    truncated:  f32[rows] walks cut short by the schedule (compaction
                overflow or the max_steps cap); their current position is
                counted as the endpoint, like the legacy engine's cap.
    fp_dropped: f32[rows] visit mass truncated out of the fp sketch.
    ep_dropped: f32[rows] endpoint mass truncated out of the ep sketch.

    Conservation (tested): ``fp.mass() + fp_dropped == moves`` and
    ``ep.mass() + ep_dropped == walks == R`` per row, exactly.
    """

    fp: frontier_mod.SparseFrontier
    ep: frontier_mod.SparseFrontier
    moves: jax.Array
    walks: jax.Array
    truncated: jax.Array
    fp_dropped: jax.Array
    ep_dropped: jax.Array
    # bool[rows, touch_bits] per-row "walks-through" Bloom filter over every
    # *counted* position (None unless ``touch_bits > 0``): the row's walks
    # only ever step *from* counted positions, so if no member vertex's
    # out-neighborhood changed, the row re-simulates bit-identically on the
    # updated graph — the invalidation sketch of ``core/updates.py``.
    touch: Optional[jax.Array] = None


def compaction_schedule(
    r: int,
    *,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    compact_every: int = 8,
    margin: float = 1.35,
    floor: int = 8,
    lane: int = 8,
) -> Tuple[int, ...]:
    """Static per-round slot widths for the compacted engine.

    Round ``j`` covers steps ``[j * compact_every, (j+1) * compact_every)``
    and runs at width ``w_j = min(r, max(floor, margin * r * (1-c)^t_j))``
    rounded up to a ``lane`` multiple — the expected live-walk count at the
    round's first step with a safety margin.  Widths are non-increasing and
    start at exactly ``r`` (every walk launches in round 0).  Survivors that
    exceed a round's width (a ``margin`` tail event) are truncated to their
    endpoint and reported, so the schedule is a performance knob, never a
    correctness one.
    """
    if r <= 0:
        raise ValueError(f"r must be positive, got {r}")
    widths = []
    t = 0
    while t < max_steps:
        live = r * (1.0 - c) ** t
        w = int(math.ceil(margin * live))
        w = ((w + lane - 1) // lane) * lane
        w = min(r, max(floor, w)) if t else r
        widths.append(w)
        t += compact_every
    return tuple(widths)


def respawn_schedule(
    r: int,
    *,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    compact_every: int = 8,
    margin: float = 1.35,
    width: int = 0,
    slack: float = 1.15,
    floor: int = 4,
    lane: int = 4,
    drain_eps: float = 0.02,
) -> Tuple[Tuple[int, ...], int]:
    """Static rounds for respawn-mode scheduling: ``(widths, total_steps)``.

    Instead of tracking the ``(1-c)^t`` decay with ever-narrower buckets
    (:func:`compaction_schedule`), respawn mode runs a *narrow fixed-width*
    slot array at ~100% occupancy: every step, slots freed by termination
    are refilled with fresh walks from each row's remaining quota (the
    DrunkardMob slot-reuse idea).  The schedule is then

    * ``launch`` rounds at the fixed width ``w0`` — enough rounds that the
      expected launches (``c * w0`` per step) cover the quota ``r - w0``
      with ``slack``; stragglers keep respawning into the drain, and any
      quota still unspent at the very end is flushed as length-1 walks
      (ledgered in ``truncated``), so every row still finishes exactly
      ``r`` walks;
    * a ``drain`` tail — :func:`compaction_schedule` decay from ``w0``,
      truncated once ``(1-c)^t`` falls below ``drain_eps`` (the same
      truncate-to-endpoint semantics as the ``max_steps`` cap).

    Device slots processed — and with them the engine's two real costs,
    scan steps and sketch-fold event columns — drop from ``sum_j w_j *
    compact_every`` (which the floor of the decay schedule dominates at
    small ``r``) to roughly ``slack * r / c`` plus one short drain
    staircase — the ≥2x positions/sec win
    ``benchmarks/bench_preprocess.py`` records.  ``width=0`` auto-derives
    ``w0 ~ r / 3`` (lane-rounded): wide enough that the quota launches in
    one or two rounds (fewer scan steps), narrow enough that the drain
    staircase stays a fraction of the launch area.  ``floor``/``lane``
    default to 4 — narrower than the decay schedule's 8 because the drain
    cohort here is one fixed-width slot row, not the full launch width
    (set ``lane=8`` on sublane-sensitive backends).
    """
    if r <= 0:
        raise ValueError(f"r must be positive, got {r}")
    w0 = width if width > 0 else int(math.ceil(r / 3))
    w0 = ((w0 + lane - 1) // lane) * lane
    w0 = min(r, max(floor, w0))
    quota = r - w0
    if quota > 0:
        per_round = max(c * w0 * compact_every, 1e-9)
        launch_rounds = int(math.ceil(slack * quota / per_round))
        # trace-size bound: the unrolled round loop must stay O(max_steps)
        # even under an explicitly narrow ``width`` (launch otherwise grows
        # as ~r/width rounds).  Quota the capped launch can't place mops up
        # during the drain or flushes as length-1 walks — ledgered, exact.
        launch_rounds = min(
            launch_rounds,
            int(math.ceil(4 * max_steps / max(compact_every, 1))),
        )
    else:
        launch_rounds = 0
    drain_target = int(math.ceil(math.log(drain_eps) / math.log(1.0 - c))) \
        if 0.0 < c < 1.0 else max_steps
    drain_steps = min(
        max_steps,
        ((max(drain_target, 1) + compact_every - 1) // compact_every)
        * compact_every,
    )
    drain = compaction_schedule(
        w0, c=c, max_steps=drain_steps, compact_every=compact_every,
        margin=margin, floor=floor, lane=lane,
    )
    widths = (w0,) * launch_rounds + drain
    return widths, launch_rounds * compact_every + drain_steps


def schedule_slot_area(
    widths: Tuple[int, ...], total_steps: int, compact_every: int = 8
) -> int:
    """Device slot-steps one source row spends on one pass of a schedule.

    Round ``j`` runs at width ``w_j`` for ``min(compact_every, total_steps -
    t0_j)`` steps (the last round may be ragged), so the area is
    ``sum_j w_j * steps_j`` — the quantity
    ``test_respawn_schedule_halves_device_work`` pins and the respawn-aware
    cost model (``index.preprocessing_cost_model``) prices walk state with.
    """
    area, t0 = 0, 0
    for w in widths:
        steps = min(compact_every, total_steps - t0)
        if steps <= 0:
            break
        area += w * steps
        t0 += steps
    return area


TOUCH_HASHES = 4


def touch_hash_bits(
    vertices: jax.Array, n_bits: int, k: int = TOUCH_HASHES
) -> jax.Array:
    """Bloom bit positions of each vertex id: ``vertices.shape + (k,)`` int32.

    ``k`` independent streams of a uint32 avalanche mix (fmix32 over the id
    xor a per-hash odd constant), reduced mod ``n_bits``.  Pure jnp so the
    walk engine can record bits on-device and ``core/updates.py`` can query
    membership with the *same* function on host arrays.
    """
    v = jnp.asarray(vertices).astype(jnp.uint32)
    outs = []
    for j in range(k):
        h = v ^ jnp.uint32((2 * j + 1) * 0x9E3779B9 & 0xFFFFFFFF)
        h = (h ^ (h >> 16)) * jnp.uint32(0x85EBCA6B)
        h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE35)
        h = h ^ (h >> 16)
        outs.append((h % jnp.uint32(n_bits)).astype(jnp.int32))
    return jnp.stack(outs, axis=-1)


def sample_edge_offsets(u: jax.Array, deg: jax.Array) -> jax.Array:
    """Edge offset ``~ Uniform{0..deg-1}`` from ``u ~ U[0, 1)``.

    ``floor(u * deg)`` clipped into range — the one sampling law the jnp
    step, the Pallas ``walk_step`` launcher, and its oracle all share, so
    the kernel-routed engine is bit-identical to the jnp engine under the
    same key."""
    off = jnp.floor(u * deg.astype(jnp.float32)).astype(jnp.int32)
    return jnp.clip(off, 0, jnp.maximum(deg - 1, 0))


def advance_cursors(
    graph: Graph,
    cursors: jax.Array,
    sources: jax.Array,
    u: jax.Array,
    *,
    use_kernel: bool = False,
    kernel_interpret: bool = False,
) -> jax.Array:
    """Advance every cursor one edge (dangling vertices jump to ``sources``).

    ``u`` is the pre-drawn uniform for the edge choice (see
    :func:`sample_edge_offsets`).  ``sources`` must broadcast against
    ``cursors``.  With ``use_kernel`` the degree-gather + edge-sample +
    dangling-fix run fused through the HBM-resident Pallas kernel
    (``repro.kernels.ops.walk_step``), bit-identical to the jnp path.
    """
    if graph.m == 0:  # every vertex dangling: all walks jump home
        return jnp.broadcast_to(sources, cursors.shape).astype(cursors.dtype)
    if use_kernel:
        from repro.kernels import ops as kernel_ops

        return kernel_ops.walk_step(
            cursors, jnp.broadcast_to(sources, cursors.shape), u,
            graph.row_ptr, graph.out_deg, graph.col_idx,
            interpret=kernel_interpret,
        )
    deg = jnp.take(graph.out_deg, cursors)
    lo = jnp.take(graph.row_ptr, cursors)
    addr = jnp.clip(lo + sample_edge_offsets(u, deg), 0, graph.m - 1)
    nxt = jnp.take(graph.col_idx, addr)
    return jnp.where(deg == 0, sources, nxt)


def _compact_slots(
    cursors: jax.Array, alive: jax.Array, w_new: int
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Compact surviving cursors into the low slots of a width-``w_new`` row.

    Per row: rank survivors with ``cumsum`` over the active mask and scatter
    rank ``j`` into slot ``j`` (the ``frontier.py`` compaction idiom applied
    to walk state).  Survivors ranked past ``w_new`` overflow; their
    ``(weight, cursor)`` events are returned so the caller can truncate them
    to endpoints.  Returns ``(cursors[rows, w_new], alive[rows, w_new],
    overflow_w[rows, w_old], overflow_i[rows, w_old])``.
    """
    rows, w = cursors.shape
    rank = jnp.cumsum(alive.astype(jnp.int32), axis=1)       # 1-based
    keep = alive & (rank <= w_new)
    # park dropped/dead slots at a sentinel column that is sliced away
    tgt = jnp.where(keep, rank - 1, w_new)
    packed = jnp.zeros((rows, w_new + 1), cursors.dtype).at[
        jnp.arange(rows)[:, None], tgt
    ].set(jnp.where(keep, cursors, 0), mode="drop")
    n_kept = jnp.minimum(rank[:, -1], w_new)                 # [rows]
    new_alive = jnp.arange(w_new, dtype=jnp.int32)[None, :] < n_kept[:, None]
    over = alive & (rank > w_new)
    return (
        packed[:, :w_new],
        new_alive,
        over.astype(jnp.float32),
        jnp.where(over, cursors, 0),
    )


class _EventSketch:
    """Running top-``k`` sketch fed by buffered event segments.

    Folding (sort + segment-sum + top-k, :func:`frontier.fold_topk`) is the
    expensive primitive on every backend, so event segments queue in a
    pending list and one fold runs whenever the pending width reaches
    ``fold_width`` — the same stream-width batching idea as
    ``verd.sparse_push_compact``, applied across rounds.  Deferring folds is
    only ever *more* accurate (fewer intermediate truncations); the pending
    buffer bounds live memory at ``O(rows * (k + fold_width + one round's
    events))``.  With ``enabled=False`` nothing is sketched and every event
    lands in ``dropped`` (the MCFP-only builds skip the ep sketch this way).
    A trace-time helper: plain Python state, jnp math.
    """

    def __init__(self, rows: int, k: int, fold_width: int, enabled: bool = True):
        self.k = k
        self.enabled = enabled
        self.fold_width = fold_width
        self.values = jnp.zeros((rows, k), jnp.float32)
        self.indices = jnp.zeros((rows, k), jnp.int32)
        self.dropped = jnp.zeros((rows,), jnp.float32)
        self._pend_v: list = []
        self._pend_i: list = []
        self._pend_w = 0

    def add(self, ev_w: jax.Array, ev_i: jax.Array) -> None:
        """Queue an event segment ``[rows, w]`` (zero-weight slots fine)."""
        if not self.enabled:
            self.dropped = self.dropped + jnp.sum(ev_w, axis=1)
            return
        self._pend_v.append(ev_w)
        self._pend_i.append(ev_i)
        self._pend_w += ev_w.shape[1]
        if self._pend_w >= self.fold_width:
            self.flush()

    def flush(self) -> None:
        if not self._pend_w:
            return
        self.values, self.indices, d = frontier_mod.fold_topk(
            self.values, self.indices,
            jnp.concatenate(self._pend_v, axis=1),
            jnp.concatenate(self._pend_i, axis=1),
            self.k,
        )
        self.dropped = self.dropped + d
        self._pend_v, self._pend_i, self._pend_w = [], [], 0


@functools.partial(
    jax.jit,
    static_argnames=(
        "r", "l", "ep_l", "c", "max_steps", "compact_every", "margin",
        "fold_width", "use_kernel", "kernel_interpret", "respawn",
        "respawn_width", "touch_bits",
    ),
)
def simulate_walks_sparse(
    graph: Graph,
    sources: jax.Array,
    r: int,
    key: jax.Array,
    *,
    l: int,
    ep_l: Optional[int] = None,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    compact_every: int = 8,
    margin: float = 1.35,
    fold_width: int = 0,
    use_kernel: bool = False,
    kernel_interpret: bool = False,
    respawn: bool = False,
    respawn_width: int = 0,
    touch_bits: int = 0,
) -> SparseWalkCounts:
    """Run ``r`` walks per source through the compacted sparse-sketch engine.

    sources: int32[rows] personalization vertex of each output row (every
    walk of a row starts there — the :func:`walks_for_sources` layout, made
    structural).  ``l``/``ep_l`` are the fp/ep sketch widths; ``l >=``
    distinct visited vertices per row makes the fp sketch exact (an MCFP
    row from ``r`` walks has support ``<= moves ~ r/c``).  ``ep_l=0``
    disables endpoint sketching entirely (the MCFP-only index build), and
    symmetrically ``l=0`` disables the visit sketch (the MCEP-only
    estimate): the disabled sketch comes back width-1 empty and its whole
    event mass lands in the ``*_dropped`` ledger, so conservation still
    closes.  ``fold_width`` batches
    visit events across rounds before each sketch fold (0 = auto,
    ``max(4 * l, 512)``): larger folds cost fewer sorts *and* truncate less;
    live event memory stays ``O(rows * fold_width)``.

    One jit compilation per (shapes, schedule): the round loop is unrolled
    into a single device computation — per round one ``lax.scan`` of
    ``compact_every`` steps at that round's static width and one slot
    compaction, with sketch folds on the ``fold_width`` cadence.  Walks
    surviving ``max_steps`` total positions are truncated to endpoints
    exactly like the legacy engine's cap.

    ``respawn=True`` switches to respawn-mode scheduling
    (:func:`respawn_schedule`): a narrow fixed-width slot array (width
    ``respawn_width``, 0 = auto) runs at ~100% occupancy — every step,
    slots freed by termination refill with fresh walks from a per-row
    quota counter until all ``r`` walks of the row have launched, then the
    array drains through the usual decay/compaction tail.  Quota still
    unspent when the pass ends is flushed as length-1 walks (one counted
    position at the source — ledgered in ``truncated``), so the
    conservation identities close exactly in both modes.  In respawn mode
    ``max_steps`` caps the *drain* tail (the per-walk cap is enforced by
    the pass length rather than per slot; the geometric tail beyond it is
    the same ``(1-c)^t`` mass either way).

    ``touch_bits > 0`` additionally records a per-row Bloom filter
    (``bool[rows, touch_bits]``, :func:`touch_hash_bits` with
    ``TOUCH_HASHES`` hashes) over every counted position — the reverse
    "walks-through" sketch incremental index maintenance queries to find
    the rows an edge update invalidates.  Bloom membership has no false
    negatives, so a row whose filter misses every touched vertex is
    provably bit-stable under the update; false positives only cause
    harmless extra repair.
    """
    rows = sources.shape[0]
    n = graph.n
    l = min(l, n)
    ep_l = min(ep_l if ep_l is not None else l, n)
    track_fp = l > 0
    track_ep = ep_l > 0
    if fold_width <= 0:
        fold_width = max(4 * l, 512)
    if respawn:
        schedule, total_steps = respawn_schedule(
            r, c=c, max_steps=max_steps, compact_every=compact_every,
            margin=margin, width=respawn_width,
        )
    else:
        schedule = compaction_schedule(
            r, c=c, max_steps=max_steps, compact_every=compact_every,
            margin=margin,
        )
        total_steps = max_steps
    src32 = sources.astype(jnp.int32)
    src2d = src32[:, None]

    launched0 = min(r, schedule[0])
    track_touch = touch_bits > 0
    # named scopes: walk state and steps under ppr.walk.step, slot
    # compaction under ppr.slot_compact, event sketches under
    # ppr.sketch_fold (each fold inside it under ppr.fold)
    with jax.named_scope("ppr.walk.step"):
        cursors = jnp.broadcast_to(
            src2d, (rows, schedule[0])).astype(jnp.int32)
        alive = jnp.broadcast_to(
            jnp.arange(schedule[0], dtype=jnp.int32)[None, :] < launched0,
            (rows, schedule[0]),
        )
        quota = jnp.full((rows,), r - launched0, jnp.int32)
        moves = jnp.zeros((rows,), jnp.float32)
        walks_done = jnp.zeros((rows,), jnp.float32)
        truncated = jnp.zeros((rows,), jnp.float32)
    with jax.named_scope("ppr.sketch_fold"):
        fp = _EventSketch(rows, max(l, 1), fold_width, enabled=track_fp)
        ep = _EventSketch(rows, max(ep_l, 1), fold_width, enabled=track_ep)
        touch = jnp.zeros((rows, touch_bits), bool) if track_touch else None
    _touch_rows = jnp.arange(rows, dtype=jnp.int32)[:, None, None]

    def record_touch(tch, ev_i, ev_live):
        # set the k bloom bits of every live event's vertex; dead events are
        # parked at bit index ``touch_bits`` and dropped by the scatter
        bits = touch_hash_bits(ev_i, touch_bits)
        bits = jnp.where(ev_live[..., None], bits, touch_bits)
        return tch.at[_touch_rows, bits].set(True, mode="drop")

    def step_body(carry, xs):
        cursors, alive, quota, moves, walks_done = carry
        u_term, u_move = xs
        if respawn:
            # refill freed slots from the row quota: rank dead slots with a
            # cumsum (the _compact_slots idiom) and respawn the first
            # ``quota`` of them at the source — occupancy stays ~100%
            dead = ~alive
            rank = jnp.cumsum(dead.astype(jnp.int32), axis=1)  # 1-based
            spawn = dead & (rank <= quota[:, None])
            quota = quota - jnp.sum(spawn.astype(jnp.int32), axis=1)
            cursors = jnp.where(spawn, src2d, cursors)
            alive = alive | spawn
        af = alive.astype(jnp.float32)
        pos = cursors                      # position counted this step
        moves = moves + jnp.sum(af, axis=1)
        terminate = alive & (u_term < c)
        tf = terminate.astype(jnp.float32)
        walks_done = walks_done + jnp.sum(tf, axis=1)
        alive = alive & ~terminate
        nxt = advance_cursors(
            graph, cursors, src2d, u_move,
            use_kernel=use_kernel, kernel_interpret=kernel_interpret,
        )
        cursors = jnp.where(alive, nxt, cursors)
        return (cursors, alive, quota, moves, walks_done), (af, pos, tf)

    def per_row(ev):
        # [steps, rows, w] -> per-row event columns [rows, steps * w]
        return ev.transpose(1, 0, 2).reshape(rows, -1)

    def round_uniforms(t0, steps, w):
        """Pre-draw the round's step uniforms ``[steps, rows, w]`` in one
        batched RNG call: per step one (term, move) pair from the split of
        ``fold_in(key, t)`` — hoisting the threefry chains out of the scan
        body halves the fixed per-step cost the narrow respawn widths would
        otherwise be dominated by."""
        step_keys = jax.vmap(
            lambda t: jax.random.split(jax.random.fold_in(key, t))
        )(t0 + jnp.arange(steps))
        draw = jax.vmap(
            lambda k: jax.random.uniform(k, (rows, w))
        )
        return draw(step_keys[:, 0]), draw(step_keys[:, 1])

    t0 = 0
    for w in schedule:
        if w < cursors.shape[1]:
            with jax.named_scope("ppr.slot_compact"):
                cursors, alive, ov_w, ov_i = _compact_slots(
                    cursors, alive, w)
                # overflow walks: truncate to endpoint (schedule tail event)
                n_over = jnp.sum(ov_w, axis=1)
                walks_done = walks_done + n_over
                truncated = truncated + n_over
            with jax.named_scope("ppr.sketch_fold"):
                ep.add(ov_w, ov_i)
        # the last round may be ragged: never run past the step budget
        steps = min(compact_every, total_steps - t0)
        with jax.named_scope("ppr.walk.step"):
            u_move, u_term = round_uniforms(t0, steps, w)
            carry, (vis_w, vis_i, term_w) = jax.lax.scan(
                step_body, (cursors, alive, quota, moves, walks_done),
                (u_term, u_move),
            )
            cursors, alive, quota, moves, walks_done = carry
        with jax.named_scope("ppr.sketch_fold"):
            fp.add(per_row(vis_w), per_row(vis_i))
            ep.add(per_row(term_w), per_row(vis_i))
            if track_touch:
                touch = record_touch(
                    touch, per_row(vis_i), per_row(vis_w) > 0)
        t0 += steps

    with jax.named_scope("ppr.sketch_fold"):
        # step-budget cap: survivors' current position is the endpoint (the
        # same truncation as the legacy engine; tail mass ~ (1-c)^max_steps)
        af = alive.astype(jnp.float32)
        n_trunc = jnp.sum(af, axis=1)
        walks_done = walks_done + n_trunc
        truncated = truncated + n_trunc
        ep.add(af, jnp.where(alive, cursors, 0))
        if respawn:
            # quota the pass never got to launch: flush as length-1 walks
            # (one counted position at the source) so walks == R stays
            # exact; a slack-tail event, ledgered like any other truncation
            q_rem = quota.astype(jnp.float32)
            moves = moves + q_rem
            walks_done = walks_done + q_rem
            truncated = truncated + q_rem
            fp.add(q_rem[:, None], src2d)
            ep.add(q_rem[:, None], src2d)
            if track_touch:
                touch = record_touch(touch, src2d, q_rem[:, None] > 0)
        fp.flush()
        ep.flush()
    return SparseWalkCounts(
        fp=frontier_mod.SparseFrontier(
            values=fp.values, indices=fp.indices, k=max(l, 1), n=n
        ),
        ep=frontier_mod.SparseFrontier(
            values=ep.values, indices=ep.indices, k=max(ep_l, 1), n=n
        ),
        moves=moves,
        walks=walks_done,
        truncated=truncated,
        fp_dropped=fp.dropped,
        ep_dropped=ep.dropped,
        touch=touch,
    )


# ---------------------------------------------------------------------------
# Conservation-ledger export (crash-safe index builds)
# ---------------------------------------------------------------------------


class BuildLedger:
    """Host-side conservation ledger of a streaming index build.

    The builders (``index._build_index_sparse`` and the sharded segment
    loop) accumulate one kept/dropped estimate-mass entry per swept chunk
    and sum them once at the end.  Checkpointed builds additionally need
    the ledger *exportable* mid-sweep — committed with the partial index
    rows so a resumed run reproduces the uninterrupted run's final sums
    bitwise (same per-chunk f32 entries, same order, same one reduction).

    Entries may be device scalars (``jnp.sum`` per chunk), device vectors
    (per-row ledgers of a sharded segment), or restored numpy arrays — the
    export normalizes everything to one flat f32 host array per side.
    """

    def __init__(self):
        self._kept = []
        self._dropped = []

    def append(self, kept, dropped) -> None:
        self._kept.append(kept)
        self._dropped.append(dropped)

    def __len__(self) -> int:
        return len(self._kept)

    @property
    def empty(self) -> bool:
        return not self._kept

    def _flat(self, parts) -> jnp.ndarray:
        return jnp.concatenate(
            [jnp.asarray(p, jnp.float32).reshape(-1) for p in parts]
        )

    def export(self):
        """``(kept f32[entries], dropped f32[entries])`` host arrays — the
        checkpoint payload.  Exact: f32 values round-trip ``np.save``
        bit-for-bit."""
        import numpy as np
        if self.empty:
            z = np.zeros(0, np.float32)
            return z, z
        return (np.asarray(self._flat(self._kept)),  # contract: allow(host-sync): ledger totals, end of build
                np.asarray(self._flat(self._dropped)))  # contract: allow(host-sync): ledger totals, end of build

    @classmethod
    def restore(cls, kept, dropped) -> "BuildLedger":
        """Rebuild from exported arrays: one vector entry per side, so a
        resumed ledger's flattened stream equals the uninterrupted one."""
        led = cls()
        led.append(kept, dropped)
        return led

    def totals(self):
        """``(kept, dropped)`` floats: one ``jnp.sum`` over the flattened
        entry stream per side, a single host sync."""
        if self.empty:
            return 0.0, 0.0
        # contract: allow(host-sync): single end-of-build conservation sync
        kept, dropped = jax.device_get(
            (jnp.sum(self._flat(self._kept)),
             jnp.sum(self._flat(self._dropped)))
        )
        # contract: allow(host-sync): kept/dropped already on host (above)
        return float(kept), float(dropped)
