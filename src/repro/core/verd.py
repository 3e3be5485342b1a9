"""Vertex-Centric Decomposition (paper Algorithm 4 + Section 3.3 batching).

A batch of queries ``S`` keeps two row vectors per query; stacked they form
dense matrices ``F, S in R^{Q x n}`` and one VERD iteration is

    S <- S + c * F
    F <- (1 - c) * (F @ A)        (dangling rows of A -> each query's source)

i.e. one shared sparse-matrix product per iteration for the *whole batch* —
exactly the paper's "shared decomposition" that amortizes graph access
across queries, here realized as a single segment-sum push (or the Pallas
``ell_spmm`` kernel).  After ``T`` iterations the refined answer is

    p~ = S + F @ P_hat                     (P_hat = the top-L PPR index)

which is Algorithm 4 line 10.  ``recursive_decomp`` (Algorithm 3) is kept as
the oracle for the Theorem 2.3 equivalence tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import frontier
from repro.core.graph import (Graph, transition_with_dangling,
                              transition_with_dangling_seeds)
from repro.core.index import PPRIndex
from repro.core.walks import DEFAULT_C


# ---------------------------------------------------------------------------
# Weighted seed sets.  VERD is linear in its start vector, so a *seed-set*
# query (the shape real PPR consumers issue: personalize over a weighted set
# of vertices, not one source) is the same iterate seeded with a weighted
# one-hot row instead of a single 1.0.  Everywhere below, ``sources`` may be
#
# * ``int32[Q]``            — the classic single-vertex batch (weights None),
# * ``int32[Q, S]`` + ``seed_weights f32[Q, S]`` — one weighted seed set per
#   query row, padded to a stable width ``S`` with weight-0 slots.
#
# Dangling convention: a single-vertex query returns dangling mass to its
# source (paper Section 2.1); a seed-set query returns it to the query's
# *normalized seed distribution* (restart-vector semantics).  On supports
# that reach no dangling vertex the seed-set answer is exactly the weighted
# sum of the single-vertex answers (the linearity the serving cache relies
# on); with dangling flow the two differ only in where the reclaimed mass
# restarts, bounded by the per-seed dangling-mass variation.
# ---------------------------------------------------------------------------

def dangling_seed_candidates(
    dm: jax.Array,
    sources: jax.Array,
    seed_weights: Optional[jax.Array],
    *,
    c: float,
) -> Tuple[jax.Array, jax.Array]:
    """Sparse candidates returning dangling mass ``dm f32[Q]`` to the seeds.

    Single-vertex (``seed_weights is None``): one ``(1-c)*dm`` candidate at
    each query's source — the historical last slot.  Seed sets: ``S``
    candidates splitting ``(1-c)*dm`` proportionally to the normalized
    weights (weight-0 pad slots emit weight-0 candidates, which compact
    away).  Shared by every sparse push so the one-shot and streamed paths
    stay bit-identical.
    """
    if seed_weights is None:
        return (
            (1.0 - c) * dm[:, None],
            sources.reshape(-1, 1).astype(jnp.int32),
        )
    wsum = jnp.maximum(jnp.sum(seed_weights, axis=1, keepdims=True), 1e-30)
    share = dm[:, None] * (seed_weights / wsum)
    return (1.0 - c) * share, sources.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("t", "c", "threshold"))
def verd_iterate(
    graph: Graph,
    sources: jax.Array,
    seed_weights: Optional[jax.Array] = None,
    *,
    t: int,
    c: float = DEFAULT_C,
    threshold: float = 0.0,
) -> Tuple[jax.Array, jax.Array]:
    """Run ``t`` VERD iterations for a batch of query vertices.

    Returns ``(s, f)``, both ``f32[Q, n]``.  ``threshold`` optionally drops
    tiny frontier entries (the paper's epsilon sparsification) — exactness
    tests use 0.0.  With ``seed_weights`` (see the seed-set note above),
    ``sources int32[Q, S]`` seeds each row with its weighted one-hot
    combination and dangling mass restarts at the seed distribution.
    """
    q = sources.shape[0]

    def body(carry, _):
        s, f = carry
        s = s + c * f
        if seed_weights is None:
            f = (1.0 - c) * transition_with_dangling(graph, f, sources)
        else:
            f = (1.0 - c) * transition_with_dangling_seeds(
                graph, f, sources, seed_weights
            )
        if threshold > 0.0:
            f = jnp.where(f >= threshold, f, 0.0)
        return (s, f), ()

    with jax.named_scope("ppr.push"):
        f = jnp.zeros((q, graph.n), dtype=jnp.float32)
        if seed_weights is None:
            f = f.at[jnp.arange(q), sources].set(1.0)
        else:
            # .add, not .set: duplicate seeds within a row sum their weights
            f = f.at[jnp.arange(q)[:, None], sources].add(seed_weights)
        s = jnp.zeros_like(f)
        (s, f), _ = jax.lax.scan(body, (s, f), None, length=t)
    return s, f


def combine_with_index(
    s: jax.Array,
    f: jax.Array,
    index: PPRIndex,
    *,
    vertex_chunk: int = 4096,
) -> jax.Array:
    """Algorithm 4 line 10: ``p~ = s + sum_v f(v) * p_hat_v``.

    Chunked over index rows so the ``[Q, chunk*L]`` scatter intermediate
    stays bounded; the Pallas ``index_combine`` kernel is the fused
    equivalent.
    """
    with jax.named_scope("ppr.combine"):
        q, n = f.shape
        l = index.l
        n_chunks = (n + vertex_chunk - 1) // vertex_chunk
        pad_n = n_chunks * vertex_chunk
        # a sharded/padded index may carry extra all-zero rows (index.n >=
        # n); the dense frontier can only touch the first n, so slice before
        # padding
        vals = jnp.pad(index.values[:n], ((0, pad_n - n), (0, 0)))
        idxs = jnp.pad(index.indices[:n], ((0, pad_n - n), (0, 0)))
        f_pad = jnp.pad(f, ((0, 0), (0, pad_n - n)))
        vals = vals.reshape(n_chunks, vertex_chunk, l)
        idxs = idxs.reshape(n_chunks, vertex_chunk, l)
        f_chunks = f_pad.reshape(q, n_chunks, vertex_chunk).transpose(1, 0, 2)

        def body(acc, args):
            v, ix, fc = args  # [chunk, L], [chunk, L], [Q, chunk]
            contrib = fc[:, :, None] * v[None, :, :]      # [Q, chunk, L]
            acc = acc.at[:, ix.reshape(-1)].add(
                contrib.reshape(q, -1)
            )
            return acc, ()

        out, _ = jax.lax.scan(body, s, (vals, idxs, f_chunks))
        return out


def verd_query(
    graph: Graph,
    sources: jax.Array,
    index: Optional[PPRIndex],
    *,
    t: int,
    c: float = DEFAULT_C,
    threshold: float = 0.0,
    seed_weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Full online query: iterate then combine (index=None -> return s,
    the paper's R=0 mode).  ``seed_weights`` switches ``sources`` to
    weighted seed-set rows (see the seed-set note at the top)."""
    s, f = verd_iterate(
        graph, sources, seed_weights, t=t, c=c, threshold=threshold
    )
    if index is None:
        return s
    return combine_with_index(s, f, index)


# ---------------------------------------------------------------------------
# Sparse-frontier path: Q x K state instead of Q x n (see core/frontier.py).
# ---------------------------------------------------------------------------

def resolve_degree_cap(graph: Graph) -> int:
    """Max out-degree — the per-slot edge budget that makes the sparse push
    exact.  Must run outside jit (it materializes a device scalar)."""
    if graph.n == 0 or graph.m == 0:
        return 1
    # contract: allow(host-sync): one-time per-graph scalar, cached by every
    # caller (BatchQueryEngine.degree_cap) — never on the per-query path
    return max(int(jax.device_get(jnp.max(graph.out_deg))), 1)


def resolve_hub_splits(degree_cap: int, hub_split_degree: int) -> Tuple[int, int]:
    """ELL-style row-splitting geometry for the sparse push.

    Returns ``(h, s)``: each frontier slot expands into ``s`` sub-slots of
    gather width ``h`` (``s * h >= degree_cap``, so the split push is exact).
    ``hub_split_degree <= 0`` (or ``>= degree_cap``) disables splitting
    (``s == 1``, ``h == degree_cap``).
    """
    if hub_split_degree <= 0 or hub_split_degree >= degree_cap:
        return degree_cap, 1
    h = hub_split_degree
    return h, (degree_cap + h - 1) // h


def push_window_starts(
    start: jax.Array,
    *,
    degree_cap: int,
    hub_split_degree: int = 0,
    m: int,
) -> jax.Array:
    """Clipped per-sub-slot gather-window starts, ``int32[Q, K, s]``.

    Sub-slot ``j`` of a frontier slot owns edges ``[j*h, (j+1)*h)`` of its
    CSR row, so its fixed-width-``h`` gather window starts at ``start +
    j*h``.  Windows are clipped to ``[0, m - h]`` so that reading ``h``
    consecutive entries — a ``jnp.take`` on the jnp path, an HBM DMA in the
    Pallas kernels — never leaves ``col_idx``; every in-budget edge still
    lands inside its (possibly shifted) window, and
    :func:`masked_push_from_windows` compensates for the shift when masking.
    These are exactly the scalar-prefetched offsets the DMA kernels consume.
    Requires ``h <= m`` (guaranteed once ``degree_cap <= m``; no row has
    more than ``m`` edges, so clamping the cap to ``m`` is a no-op).
    """
    h, s = resolve_hub_splits(degree_cap, hub_split_degree)
    st = start[..., None] + h * jnp.arange(s, dtype=jnp.int32)
    return jnp.clip(st, 0, max(m - h, 0))


def masked_push_from_windows(
    fv: jax.Array,
    deg: jax.Array,
    start: jax.Array,
    windows: jax.Array,
    gathered: jax.Array,
    *,
    c: float,
    degree_cap: int,
    hub_split_degree: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Mask fixed-width gather windows into push candidates.

    ``windows int32[Q, K, s]`` are the clipped starts from
    :func:`push_window_starts`; ``gathered int32[Q, K, s, h]`` holds
    ``col_idx[windows + j]`` however it was read (jnp gather or kernel DMA —
    this function is the math both share).  Element ``j`` of a window whose
    clip shifted it down by ``d = start + s_i*h - window`` corresponds to
    edge offset ``s_i*h + (j - d)`` of the row; it is a real pushed edge iff
    ``j >= d`` and that offset is within ``budget = min(deg, degree_cap)``
    (the same tail-truncation as the unsplit gather).  For untouched windows
    ``d == 0`` and this reduces to the plain ``eoff < budget`` mask.

    Returns ``(push_v, nbrs)`` of width ``K * s * h``; weights are
    ``(1 - c) * fv / deg`` on valid lanes, empty slots ``(0.0, 0)``.
    """
    q, k = fv.shape
    h, s = resolve_hub_splits(degree_cap, hub_split_degree)
    sub = h * jnp.arange(s, dtype=jnp.int32)                  # [s]
    d = (start[..., None] + sub - windows)[..., None]         # [Q, K, s, 1]
    j = jnp.arange(h, dtype=jnp.int32)[None, None, None, :]   # [1, 1, 1, h]
    eoff = sub[None, None, :, None] + (j - d)                 # [Q, K, s, h]
    budget = jnp.minimum(deg, degree_cap)[..., None, None]
    valid = (j >= d) & (eoff < budget)
    nbrs = jnp.where(valid, gathered, 0)
    inv = 1.0 / jnp.maximum(deg[..., None, None].astype(jnp.float32), 1.0)
    push_v = jnp.where(valid, (1.0 - c) * fv[..., None, None] * inv, 0.0)
    return push_v.reshape(q, k * s * h), nbrs.reshape(q, k * s * h)


def gather_push_edges(
    fv: jax.Array,
    fi: jax.Array,
    start: jax.Array,
    deg: jax.Array,
    col_idx: jax.Array,
    *,
    c: float,
    degree_cap: int,
    hub_split_degree: int = 0,
    window_gather=None,
) -> Tuple[jax.Array, jax.Array]:
    """Edge gather shared by the single-device and sharded pushes.

    ``start``/``deg`` are the per-slot CSR offsets and out-degrees
    (``[Q, K]``, already gathered by the caller — the single-device path
    reads the global CSR, the sharded path its local slab).  With hub
    splitting (``hub_split_degree > 0``) each frontier slot becomes ``s =
    ceil(degree_cap / h)`` ELL-style sub-slots of gather width ``h``: a hub
    vertex simply occupies several sub-slots (sub-slot ``j`` owns edges
    ``[j*h, (j+1)*h)`` of its row), so no single gather axis is ever wider
    than ``h``.  Splitting moves mass between sub-slots only — the flat
    candidate multiset is identical to the unsplit gather (tested in
    ``test_properties.py``).

    Implemented as :func:`push_window_starts` + a window gather +
    :func:`masked_push_from_windows`.  The gather is a ``jnp.take`` unless
    ``window_gather(col_idx, starts, h) -> int32[R, h]`` is given — the DMA
    kernel ``repro.kernels.frontier_push.gather_windows`` plugs in there.

    Returns ``(push_v, nbrs)`` of width ``K * s * h``; ``nbrs`` are the
    ``col_idx`` destination ids, weights ``(1-c) * fv / deg``.
    """
    m = col_idx.shape[0]
    degree_cap = min(degree_cap, max(m, 1))  # no row has more than m edges
    h, _ = resolve_hub_splits(degree_cap, hub_split_degree)
    windows = push_window_starts(
        start, degree_cap=degree_cap, hub_split_degree=hub_split_degree, m=m
    )
    if window_gather is None:
        eidx = windows[..., None] + jnp.arange(h, dtype=jnp.int32)
        gathered = jnp.take(col_idx, eidx)                    # [Q, K, s, h]
    else:
        gathered = window_gather(
            col_idx, windows.reshape(-1), h
        ).reshape(windows.shape + (h,))
    return masked_push_from_windows(
        fv, deg, start, windows, gathered,
        c=c, degree_cap=degree_cap, hub_split_degree=hub_split_degree,
    )


def gather_push_candidates(
    fv: jax.Array,
    fi: jax.Array,
    sources: jax.Array,
    row_ptr: jax.Array,
    out_deg: jax.Array,
    col_idx: jax.Array,
    *,
    c: float,
    degree_cap: int,
    hub_split_degree: int = 0,
    seed_weights: Optional[jax.Array] = None,
    window_gather=None,
) -> Tuple[jax.Array, jax.Array]:
    """Array-level gather push shared by the core op and the Pallas kernel
    wrapper (``kernels/frontier_push.py``, which passes its DMA
    ``window_gather``); see :func:`sparse_push_candidates` for semantics.
    Requires ``col_idx`` non-empty."""
    start = jnp.take(row_ptr, fi)                     # [Q, K]
    deg = jnp.take(out_deg, fi)                       # [Q, K]
    push_v, nbrs = gather_push_edges(
        fv, fi, start, deg, col_idx,
        c=c, degree_cap=degree_cap, hub_split_degree=hub_split_degree,
        window_gather=window_gather,
    )
    dm = jnp.sum(jnp.where(deg == 0, fv, 0.0), axis=1)  # dangling mass [Q]
    dang_v, dang_i = dangling_seed_candidates(dm, sources, seed_weights, c=c)
    cand_v = jnp.concatenate([push_v, dang_v], axis=1)
    cand_i = jnp.concatenate([nbrs, dang_i], axis=1)
    return cand_v, cand_i


def sparse_push_candidates(
    graph: Graph,
    fv: jax.Array,
    fi: jax.Array,
    sources: jax.Array,
    *,
    c: float = DEFAULT_C,
    degree_cap: int,
    hub_split_degree: int = 0,
    seed_weights: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """One VERD push ``(1-c) * f @ A`` in sparse form, uncompacted.

    For each frontier slot ``(q, j)`` holding mass ``fv`` at vertex ``fi``,
    gathers up to ``degree_cap`` out-edges from CSR and emits one candidate
    per edge; dangling mass returns to each query's source (last slot) —
    or, with ``seed_weights``, to the query's weighted seed set (last ``S``
    slots, :func:`dangling_seed_candidates`).
    Returns ``(cand_v, cand_i)`` of width ``K * degree_cap + 1`` (``+ S``
    for seed sets; ``K * s * h`` with hub splitting, see
    :func:`gather_push_edges`) — callers dedup + top-K compact
    (``frontier.compact``).

    ``degree_cap`` below the max out-degree of any *frontier* vertex drops
    the tail edges of that vertex (mass ``fv * (deg - cap) / deg``); with
    ``degree_cap >= max out-degree`` the push is exact.  ``hub_split_degree``
    changes only the gather geometry (hub rows split across sub-slots), not
    the pushed mass.
    """
    with jax.named_scope("ppr.push.gather"):
        if graph.m == 0:  # every vertex dangling: all mass returns home
            dm = jnp.sum(fv, axis=1)
            return dangling_seed_candidates(dm, sources, seed_weights, c=c)
        return gather_push_candidates(
            fv, fi, sources, graph.row_ptr, graph.out_deg, graph.col_idx,
            c=c, degree_cap=degree_cap, hub_split_degree=hub_split_degree,
            seed_weights=seed_weights,
        )


def packed_lane_slots(
    ends: jax.Array, first: jax.Array, lanes: int
) -> jax.Array:
    """Frontier slot of each lane ``e`` in ``[first, first + lanes)``.

    ``ends`` ``[Q, K]`` holds each row's inclusive cumulative slot budgets;
    lane ``e`` belongs to the first slot whose end lies past it, i.e.
    ``searchsorted(ends[q], e, side="right")`` clamped to ``K - 1``.  The
    map is monotone along the lanes and steps only at the slot ends, so it
    is the count of ends at or before ``first`` plus a prefix sum of the
    ends that fall inside the block: one scatter of ``Q * K`` boundaries and
    one ``[Q, lanes]`` prefix sum instead of a bisection per lane.
    """
    q, k = ends.shape
    base = jnp.sum(ends <= first, axis=1, dtype=jnp.int32)          # [Q]
    pos = ends - first
    # an end at lane p > 0 starts the next slot there; ends at or before
    # `first` are in `base`, and ends past the block are dropped
    pos = jnp.where(pos > 0, pos, lanes)
    steps = jnp.zeros((q, lanes), jnp.int32).at[
        jnp.arange(q)[:, None], pos].add(1, mode="drop")
    return jnp.minimum(base[:, None] + jnp.cumsum(steps, axis=1), k - 1)


def packed_slot_tables(
    fv: jax.Array,
    start: jax.Array,
    deg: jax.Array,
    ends: jax.Array,
    *,
    c: float,
) -> Tuple[jax.Array, jax.Array]:
    """Per-slot ``(d, w)`` for :func:`gather_packed_edges`, built once per
    push: lane ``e`` of slot ``j`` reads CSR edge ``e + d[j]`` (``d = start
    - (ends - budget)``) and pushes weight ``w[j] = (1 - c) * fv / deg``."""
    with jax.named_scope("ppr.push.gather"):
        before = jnp.pad(ends[:, :-1], ((0, 0), (1, 0)))
        d = (start - before).astype(jnp.int32)
        inv = 1.0 / jnp.maximum(deg.astype(jnp.float32), 1.0)
        return d, (1.0 - c) * fv * inv


def gather_packed_edges(
    d: jax.Array,
    w: jax.Array,
    ends: jax.Array,
    col_idx: jax.Array,
    *,
    first: jax.Array,
    lanes: int,
) -> Tuple[jax.Array, jax.Array]:
    """Edges ``[first, first + lanes)`` of each row's frontier, packed.

    Each row's pushed edges are numbered slot after slot: slot ``j`` owns
    ``[ends[j] - budget_j, ends[j])`` (``ends`` is the inclusive cumulative
    sum of the per-slot edge budgets).  Lane ``e`` finds its slot by a
    prefix sum over the slot boundaries (:func:`packed_lane_slots`) and
    reads the slot's two tables from :func:`packed_slot_tables`: CSR edge
    ``e + d[slot]`` and weight ``w[slot]``, so no lane is spent on a slot's
    unused ``degree_cap`` padding.  Returns ``(push_v, nbrs)`` of width
    ``lanes``: weights ``(1 - c) * fv / deg``, empty lanes ``(0.0, 0)`` —
    the same candidates, in another grouping, as :func:`gather_push_edges`.
    """
    with jax.named_scope("ppr.push.gather"):
        e = first + jnp.arange(lanes, dtype=jnp.int32)
        slot = packed_lane_slots(ends, first, lanes)
        valid = e[None, :] < ends[:, -1:]
        at = lambda x: jnp.take_along_axis(x, slot, axis=1)
        m = col_idx.shape[0]
        nbrs = jnp.take(col_idx, jnp.clip(e[None, :] + at(d), 0, m - 1))
        push_v = jnp.where(valid, at(w), 0.0)
        return push_v, jnp.where(valid, nbrs, 0)


def sparse_push_compact(
    graph: Graph,
    fv: jax.Array,
    fi: jax.Array,
    sources: jax.Array,
    *,
    c: float = DEFAULT_C,
    degree_cap: int,
    k_out: int,
    hub_split_degree: int = 0,
    threshold: float = 0.0,
    stream_width: int = 0,
    seed_weights: Optional[jax.Array] = None,
) -> frontier.SparseFrontier:
    """One VERD push + compaction with bounded live candidate width, under
    the named scope ``ppr.push`` (the candidate gather under
    ``ppr.push.gather``, each merge into the frontier under ``ppr.fold``).

    Semantically :func:`sparse_push_candidates` followed by
    :func:`frontier.compact`, but when the one-shot candidate tensor
    (width ``K * s * h`` ~= ``K * degree_cap``) would dwarf the compacted
    result, the frontier's real edges are streamed in fixed-width lane
    blocks (:func:`gather_packed_edges`), each folded into a running
    top-``k_out`` state — live width stays ``O(stream target + k_out)``
    instead of ``O(K * degree_cap)``, and the number of blocks follows the
    edges the frontier actually has (the largest row's), not ``K *
    degree_cap``: on a power-law graph almost every slot's degree is far
    below the hubs' ``degree_cap``.  Exact (equal to the one-shot path, up
    to f32 merge rounding) whenever ``k_out`` covers the merged row
    support; below that, every fold truncates by rank like any other
    top-K here, so mass is only dropped and the drift stays bounded by the
    dropped mass.  ``stream_width`` overrides the lane-block width
    (0 = auto: ``max(4 * k_out, 16384)``).
    """
    with jax.named_scope("ppr.push"):
        q, k = fv.shape
        m = graph.m
        # seed-set queries emit S dangling candidates instead of 1 (see
        # dangling_seed_candidates) — the one-shot width grows accordingly
        s_width = 1 if seed_weights is None else int(seed_weights.shape[1])
        if m == 0:  # all-dangling: S candidates per row, nothing to stream
            cv, ci = sparse_push_candidates(
                graph, fv, fi, sources, c=c, degree_cap=degree_cap,
                seed_weights=seed_weights,
            )
            with jax.named_scope("ppr.fold"):
                return frontier.compact(
                    cv, ci, min(k_out, cv.shape[1]), graph.n,
                    threshold=threshold,
                )
        cap = min(degree_cap, max(m, 1))
        h, s = resolve_hub_splits(cap, hub_split_degree)
        slot_w = s * h
        out_w = min(k_out, k * slot_w + s_width)  # the one-shot path's width
        lanes = stream_width if stream_width > 0 else max(4 * out_w, 16384)
        if k * slot_w + s_width <= 2 * max(lanes, slot_w):  # narrow: one-shot
            cv, ci = sparse_push_candidates(
                graph, fv, fi, sources, c=c, degree_cap=degree_cap,
                hub_split_degree=hub_split_degree, seed_weights=seed_weights,
            )
            with jax.named_scope("ppr.fold"):
                return frontier.compact(
                    cv, ci, out_w, graph.n, threshold=threshold)
        start = jnp.take(graph.row_ptr, fi)
        deg = jnp.take(graph.out_deg, fi)
        # empty slots (fv == 0) push nothing: they get no lanes
        budget = jnp.where(fv > 0, jnp.minimum(deg, cap), 0)
        ends = jnp.cumsum(budget, axis=1).astype(jnp.int32)
        # dangling mass seeds the running state (the one-shot path's last
        # slot(s)); duplicate seed candidates dedup-merge on the first fold
        dm = jnp.sum(jnp.where(deg == 0, fv, 0.0), axis=1)
        dang_v, dang_i = dangling_seed_candidates(
            dm, sources, seed_weights, c=c)
        run_v, run_i = frontier.topk_compact(dang_v, dang_i, out_w)
        d, w = packed_slot_tables(fv, start, deg, ends, c=c)

        def fold(b, carry):
            pv, nb = gather_packed_edges(
                d, w, ends, graph.col_idx, first=b * lanes, lanes=lanes)
            # mid-stream compaction truncates by rank only; the epsilon
            # threshold applies once at the end, like the one-shot path
            rv, ri, _ = frontier.fold_topk(*carry, pv, nb, out_w)
            return rv, ri

        blocks = (jnp.max(ends[:, -1]) + lanes - 1) // lanes
        run_v, run_i = jax.lax.fori_loop(0, blocks, fold, (run_v, run_i))
        if threshold > 0.0:
            run_v = frontier.threshold_values(run_v, threshold)
            run_v, run_i = frontier.topk_compact(run_v, run_i, out_w)
        return frontier.SparseFrontier(
            values=run_v, indices=run_i, k=out_w, n=graph.n
        )


@functools.partial(
    jax.jit,
    static_argnames=(
        "t", "k", "c", "threshold", "degree_cap", "hub_split_degree"
    ),
)
def _verd_iterate_sparse(
    graph: Graph,
    sources: jax.Array,
    seed_weights: Optional[jax.Array] = None,
    *,
    t: int,
    k: int,
    c: float,
    threshold: float,
    degree_cap: int,
    hub_split_degree: int,
) -> Tuple[frontier.SparseFrontier, frontier.SparseFrontier]:
    # each iteration's S <- S + c * F is part of the push, as in the
    # dense verd_iterate
    with jax.named_scope("ppr.push"):
        q = sources.shape[0]
        if seed_weights is None:
            f = frontier.from_sources(sources, graph.n)
        else:
            f = frontier.from_seed_sets(sources, seed_weights, graph.n)
        s_vals, s_idxs = [], []
        for _ in range(t):
            s_vals.append(c * f.values)
            s_idxs.append(f.indices)
            f = sparse_push_compact(
                graph, f.values, f.indices, sources, c=c, k_out=k,
                degree_cap=degree_cap, hub_split_degree=hub_split_degree,
                threshold=threshold, seed_weights=seed_weights,
            )
        if s_vals:
            sv = jnp.concatenate(s_vals, axis=1)
            si = jnp.concatenate(s_idxs, axis=1)
            s = frontier.compact(sv, si, min(sv.shape[1], graph.n), graph.n)
        else:  # t == 0: s is empty
            s = frontier.SparseFrontier(
                values=jnp.zeros((q, 1), jnp.float32),
                indices=jnp.zeros((q, 1), jnp.int32),
                k=1, n=graph.n,
            )
        return s, f


def verd_iterate_sparse(
    graph: Graph,
    sources: jax.Array,
    seed_weights: Optional[jax.Array] = None,
    *,
    t: int,
    k: int,
    c: float = DEFAULT_C,
    threshold: float = 0.0,
    degree_cap: Optional[int] = None,
    hub_split_degree: int = 0,
) -> Tuple[frontier.SparseFrontier, frontier.SparseFrontier]:
    """Sparse-frontier VERD: ``t`` iterations holding ``Q x K`` state.

    Per iteration: one ``col_idx`` gather + segment-sum over ``Q * K *
    degree_cap`` candidate edges instead of the dense ``[Q, n] @ A`` — the
    win is ``O(Q * K * deg)`` vs ``O(Q * m)`` work and ``Q*K*8`` vs ``Q*n*8``
    bytes of state.  Exact (equal to :func:`verd_iterate` densified) whenever
    ``k`` covers the frontier support and ``degree_cap`` covers the max
    out-degree; truncation drops at most the compacted-away mass per
    iteration.  ``hub_split_degree > 0`` splits hub adjacency rows across
    ELL-style sub-slots of width ``<= hub_split_degree`` (same result,
    regular gather tiles — see :func:`gather_push_edges`).

    Returns ``(s, f)`` as :class:`~repro.core.frontier.SparseFrontier`; the
    accumulated ``s`` keeps its natural (un-truncated) width ``<= 1 +
    (t-1)*k``.  ``seed_weights`` switches ``sources`` to weighted seed-set
    rows ``int32[Q, S]`` (see the seed-set note at the top): the initial
    frontier is the width-``S`` weighted seed frontier and dangling mass
    restarts at the seed distribution.
    """
    if degree_cap is None:
        degree_cap = resolve_degree_cap(graph)
    return _verd_iterate_sparse(
        graph, sources, seed_weights, t=t, k=k, c=c, threshold=threshold,
        degree_cap=degree_cap, hub_split_degree=hub_split_degree,
    )


def combine_candidates_from_rows(
    sv: jax.Array,
    si: jax.Array,
    fv: jax.Array,
    iv: jax.Array,
    ii: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Sparse-combine math on already-gathered index rows (``iv/ii [Q, K,
    L]``): scale by frontier mass, stack with the ``s`` entries.  Shared by
    the jnp gather below and the DMA kernel body (which reads the rows via
    HBM copies instead of ``jnp.take``).  Uncompacted width ``S + K*L``."""
    q = fv.shape[0]
    contrib = fv[..., None] * iv
    cand_v = jnp.concatenate([sv, contrib.reshape(q, -1)], axis=1)
    cand_i = jnp.concatenate([si, ii.reshape(q, -1)], axis=1)
    return cand_v, cand_i


def gather_combine_candidates(
    sv: jax.Array,
    si: jax.Array,
    fv: jax.Array,
    fi: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """Array-level sparse combine shared by the core op and the Pallas
    kernel oracle path: gather the touched index rows, scale by frontier
    mass, stack with the ``s`` entries.  Uncompacted width ``S + K*L``."""
    iv = jnp.take(vals, fi, axis=0)                    # [Q, K, L]
    ii = jnp.take(idx, fi, axis=0)                     # [Q, K, L]
    return combine_candidates_from_rows(sv, si, fv, iv, ii)


def combine_with_index_sparse(
    s: frontier.SparseFrontier,
    f: frontier.SparseFrontier,
    index: PPRIndex,
    *,
    out_k: Optional[int] = None,
) -> frontier.SparseFrontier:
    """Algorithm 4 line 10 on sparse state: contract ``f[Q, K]`` against only
    the ``K`` touched index rows (named scope ``ppr.combine``; the final
    compaction under ``ppr.topk``).

    Gathers ``index`` rows at ``f.indices`` (``[Q, K, L]``), scales by the
    frontier mass, merges with the ``s`` entries, and compacts to ``out_k``
    (default: exact, no truncation).  Work is ``O(Q * K * L)`` — independent
    of ``n``.
    """
    with jax.named_scope("ppr.combine"):
        cand_v, cand_i = gather_combine_candidates(
            s.values, s.indices, f.values, f.indices,
            index.values, index.indices,
        )
        # compact pads narrow rows, so a requested out_k is always honored
        if out_k is None:
            out_k = min(cand_v.shape[1], index.n)
        with jax.named_scope("ppr.topk"):
            return frontier.compact(cand_v, cand_i, out_k, index.n)


def combine_with_index_scatter(
    s: frontier.SparseFrontier,
    f: frontier.SparseFrontier,
    index: PPRIndex,
    *,
    out_k: int,
    n_cols: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Final combine via a dense ``[Q, n]`` scatter-add + ``lax.top_k``
    (named scope ``ppr.combine``; the selection under ``ppr.topk``).

    Same candidate set as :func:`combine_with_index_sparse`, but duplicates
    are merged by scattering into one zeroed ``[Q, n]`` scratch instead of
    the sort-based ``frontier.compact`` — ``lax.top_k`` is a fast custom
    call while the compaction's comparator sorts dominate the whole query
    at serving widths (``S + K*L`` in the tens of thousands).  Exact:
    scatter-add merges duplicates just like the segment-sum, and slots the
    scatter never touched stay 0 and are masked to the ``(0.0, 0)`` empty
    convention.  The scratch costs ``Q * n * 4`` bytes *once* at the final
    combine only (iterations stay ``Q x K``), so callers gate on a memory
    budget (``query.SCATTER_COMBINE_BUDGET_BYTES``) and keep the
    n-independent sparse combine beyond it.
    """
    with jax.named_scope("ppr.combine"):
        cand_v, cand_i = gather_combine_candidates(
            s.values, s.indices, f.values, f.indices,
            index.values, index.indices,
        )
        q = cand_v.shape[0]
        n = index.n if n_cols is None else n_cols
        dense = jnp.zeros((q, n), jnp.float32).at[
            jnp.arange(q)[:, None], cand_i
        ].add(cand_v, mode="drop")
        with jax.named_scope("ppr.topk"):
            vals, idx = jax.lax.top_k(dense, min(out_k, n))
            idx = jnp.where(vals > 0, idx, 0).astype(jnp.int32)
            if out_k > n:  # honor the requested width like compact does
                pad = out_k - n
                vals = jnp.pad(vals, ((0, 0), (0, pad)))
                idx = jnp.pad(idx, ((0, 0), (0, pad)))
            return vals, idx


def verd_query_sparse(
    graph: Graph,
    sources: jax.Array,
    index: Optional[PPRIndex],
    *,
    t: int,
    k: int,
    c: float = DEFAULT_C,
    threshold: float = 0.0,
    out_k: Optional[int] = None,
    degree_cap: Optional[int] = None,
    hub_split_degree: int = 0,
    seed_weights: Optional[jax.Array] = None,
) -> frontier.SparseFrontier:
    """Full online query on the sparse path; answers come back as a
    :class:`~repro.core.frontier.SparseFrontier` of width ``out_k`` with
    entries sorted descending — exactly the served top-k shape, no ``[Q, n]``
    materialization anywhere.  ``seed_weights`` switches ``sources`` to
    weighted seed-set rows (see the seed-set note at the top)."""
    s, f = verd_iterate_sparse(
        graph, sources, seed_weights, t=t, k=k, c=c, threshold=threshold,
        degree_cap=degree_cap, hub_split_degree=hub_split_degree,
    )
    if index is None:
        if out_k is not None:
            with jax.named_scope("ppr.topk"):
                return frontier.compact(s.values, s.indices, out_k, graph.n)
        return s
    return combine_with_index_sparse(s, f, index, out_k=out_k)


# ---------------------------------------------------------------------------
# Algorithm 3 (recursive decomposition) — oracle for Theorem 2.3 tests.
# ---------------------------------------------------------------------------

def recursive_decomp(
    graph: Graph,
    u: int,
    t: int,
    base_vectors: np.ndarray,
    c: float = DEFAULT_C,
) -> np.ndarray:
    """Literal Algorithm 3 on host numpy.

    ``base_vectors[v]`` plays the role of the precomputed ``p_hat_v``; pass
    exact PPR vectors to check Theorem 2.2, or index rows for Theorem 2.3.
    Dangling vertices follow the paper's convention O(u) = {u}'s source --
    i.e. an artificial edge back to the *queried* vertex; since recursion
    re-roots at each vertex, the artificial edge of a dangling v points at
    the recursion root v itself (p_v = e_v for dangling v).
    """
    if t == 0:
        # contract: allow(host-sync): recursive_decomp is the float64 host
        # oracle the device paths are tested against
        return np.asarray(base_vectors[u], dtype=np.float64)
    out_nbrs = graph.out_neighbors(u)
    n = graph.n
    e_u = np.zeros(n, dtype=np.float64)
    e_u[u] = 1.0
    if len(out_nbrs) == 0:
        # dangling: artificial self-edge => p_u solves p = c e_u + (1-c) p
        return e_u
    acc = np.zeros(n, dtype=np.float64)
    for v in out_nbrs:
        acc += recursive_decomp(graph, int(v), t - 1, base_vectors, c)
    return c * e_u + (1.0 - c) / len(out_nbrs) * acc
