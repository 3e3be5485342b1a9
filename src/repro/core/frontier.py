"""Fixed-width sparse frontiers for the online VERD path.

After ``T`` VERD iterations the residual frontier of a query touches only a
small neighborhood of its source — the whole point of the paper's epsilon
sparsification (Section 3.3).  The dense ``f32[Q, n]`` row-vector layout of
:mod:`repro.core.verd` throws that away: a 4096-query batch on a 1M-vertex
graph needs 16 GB of frontier alone.

This module is the TPU-native sparse alternative: the same fixed-width top-K
idiom :class:`repro.core.index.PPRIndex` already uses, applied to the query
state.  A :class:`SparseFrontier` holds ``values f32[Q, K]`` + ``indices
int32[Q, K]`` — dense, regular, batchable — with the convention (shared with
``PPRIndex``) that empty slots carry ``value == 0`` at ``index == 0``, which
is harmless because every consumer multiplies by the value.

The two primitives everything else is built from:

* :func:`merge_duplicates` — a push or an index-combine may hit the same
  column from several slots; per-row sort + segment-sum folds duplicate hits
  into one slot so a subsequent top-K cannot under-count split mass.
* :func:`topk_compact` — fixed-width re-compaction after each push.  Exact
  whenever ``K`` covers the row support; otherwise the dropped mass bounds
  the L1 drift (tested in ``tests/test_frontier.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseFrontier:
    """Batch of fixed-width sparse row vectors.

    values:  f32[Q, K] nonnegative entries, 0 on empty slots.
    indices: int32[Q, K] column of each entry (0 on empty slots).
    k: static width; n: static column-space size.
    """

    values: jax.Array
    indices: jax.Array
    k: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def nbytes(self) -> int:
        return self.values.shape[0] * self.k * 8  # f32 + int32

    def mass(self) -> jax.Array:
        """Total mass per row, f32[Q]."""
        return jnp.sum(self.values, axis=1)

    def densify(self) -> jax.Array:
        """Scatter back to ``f32[Q, n]`` (oracle path / error measurement)."""
        q = self.values.shape[0]
        out = jnp.zeros((q, self.n), dtype=self.values.dtype)
        rows = jnp.arange(q)[:, None]
        return out.at[rows, self.indices].add(self.values)


def from_sources(sources: jax.Array, n: int) -> SparseFrontier:
    """Width-1 one-hot frontier: each query starts at its source vertex."""
    fv = jnp.ones((sources.shape[0], 1), dtype=jnp.float32)
    fi = sources.reshape(-1, 1).astype(jnp.int32)
    return SparseFrontier(values=fv, indices=fi, k=1, n=n)


def from_seed_sets(
    seeds: jax.Array, weights: jax.Array, n: int
) -> SparseFrontier:
    """Width-``S`` weighted frontier: each query starts at its seed set.

    ``seeds int32[Q, S]`` / ``weights f32[Q, S]`` — pad slots carry weight
    0 (the shared empty-slot convention), so a padded seed set is exactly
    the unpadded one.  Duplicate seeds within a row are fine: they sit in
    separate slots here and every downstream push/combine dedup-merges
    colliding columns, so the state never widens past ``S``.
    """
    return SparseFrontier(
        values=weights.astype(jnp.float32),
        indices=seeds.astype(jnp.int32),
        k=int(seeds.shape[1]),
        n=n,
    )


def from_dense(dense: jax.Array, k: int) -> SparseFrontier:
    """Top-K sparsification of dense rows (drops everything below rank K)."""
    n = dense.shape[1]
    k = min(k, n)
    vals, idxs = jax.lax.top_k(dense, k)
    vals = jnp.maximum(vals, 0.0)
    idxs = jnp.where(vals > 0, idxs, 0)
    return SparseFrontier(
        values=vals, indices=idxs.astype(jnp.int32), k=k, n=n
    )


def merge_duplicates(
    values: jax.Array, indices: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Fold duplicate column hits within each row into a single slot.

    Per row: sort by column id, segment-sum runs of equal ids into the run
    leader, zero the rest.  Width is preserved; empty slots stay
    ``(0.0, 0)``.  O(Q * W log W) — W is the candidate width, not ``n``.
    """
    q, w = values.shape
    order = jnp.argsort(indices, axis=1)
    si = jnp.take_along_axis(indices, order, axis=1)
    sv = jnp.take_along_axis(values, order, axis=1)
    is_new = jnp.concatenate(
        [jnp.ones((q, 1), bool), si[:, 1:] != si[:, :-1]], axis=1
    )
    pos = jnp.broadcast_to(jnp.arange(w), (q, w))
    leader = jax.lax.cummax(jnp.where(is_new, pos, 0), axis=1)
    # flat segment-sum: row-offset the leader positions so rows don't mix
    seg = (leader + jnp.arange(q)[:, None] * w).reshape(-1)
    summed = jax.ops.segment_sum(
        sv.reshape(-1), seg, num_segments=q * w
    ).reshape(q, w)
    out_v = jnp.where(is_new, summed, 0.0)
    out_i = jnp.where(is_new & (out_v > 0), si, 0)
    return out_v, out_i


def topk_compact(
    values: jax.Array, indices: jax.Array, k: int
) -> Tuple[jax.Array, jax.Array]:
    """Keep the top-``k`` entries of each row, sorted descending (no dedup —
    see ``compact``).  Rows narrower than ``k`` are right-padded with empty
    slots so the result width is always exactly ``k``."""
    w = values.shape[1]
    vals, sel = jax.lax.top_k(values, min(k, w))
    idxs = jnp.take_along_axis(indices, sel, axis=1)
    idxs = jnp.where(vals > 0, idxs, 0)
    if w < k:
        pad = ((0, 0), (0, k - w))
        return jnp.pad(vals, pad), jnp.pad(idxs, pad)
    return vals, idxs


def compact_arrays(
    values: jax.Array, indices: jax.Array, k: int, *, threshold: float = 0.0
) -> Tuple[jax.Array, jax.Array]:
    """Dedup -> epsilon-threshold -> top-K: the one re-compaction sequence
    every sparse push and combine applies (core ops and Pallas kernel
    bodies alike — keep them in sync by calling this, not by inlining).

    Merging *before* the threshold/top-K is what makes truncation honest: a
    column hit from several slots competes with its full mass, so the kept
    set is the true per-row top-K and the dropped mass bounds the error.
    """
    v, i = merge_duplicates(values, indices)
    v = threshold_values(v, threshold)
    return topk_compact(v, i, k)


def compact(
    values: jax.Array, indices: jax.Array, k: int, n: int,
    *, threshold: float = 0.0,
) -> SparseFrontier:
    """:func:`compact_arrays` wrapped into a :class:`SparseFrontier`."""
    v, i = compact_arrays(values, indices, k, threshold=threshold)
    return SparseFrontier(values=v, indices=i, k=v.shape[1], n=n)


def fold_topk(
    run_v: jax.Array,
    run_i: jax.Array,
    add_v: jax.Array,
    add_i: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fold a batch of candidate columns into a running top-``k`` sketch.

    The streaming-accumulation primitive shared by
    :func:`repro.core.verd.sparse_push_compact` (packed edge blocks) and
    the offline walk engine's visit-count sketches
    (:func:`repro.core.walks.simulate_walks_sparse`): concatenate the new
    candidates onto the running rows, dedup-merge, keep the top-``k``.
    Returns ``(values, indices, dropped)`` where ``dropped`` is the per-row
    mass truncated away by *this* fold — the exact error-budget increment a
    sketch consumer accumulates (dropped mass only ever leaves, so the
    running total bounds the sketch's L1 understatement).  Its device work
    carries the named scope ``ppr.fold``.
    """
    with jax.named_scope("ppr.fold"):
        cand_v = jnp.concatenate([run_v, add_v], axis=1)
        cand_i = jnp.concatenate([run_i, add_i], axis=1)
        out_v, out_i = compact_arrays(cand_v, cand_i, k)
        dropped = jnp.sum(cand_v, axis=1) - jnp.sum(out_v, axis=1)
        return out_v, out_i, jnp.maximum(dropped, 0.0)


def merge_sketch_parts(
    values: jax.Array,
    indices: jax.Array,
    dropped: jax.Array,
    k: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dedup-merge concatenated sketch parts back to width ``k``, folding
    this merge's own truncation into the running ``dropped`` ledger.

    The one merge law shared by the single-device ``r_splits`` chunk
    estimate (``index.sparse_chunk_estimates``) and the distributed sketch
    merge (``distributed_engine._merge_sparse_counts``) — the sharded /
    single-device row-for-row parity gate depends on the two staying
    bit-identical, so both call here instead of inlining the sequence.
    ``values/indices [rows, parts * k']`` are the parts concatenated along
    the width axis (split order == gather order); ``dropped`` carries the
    per-part truncation already accumulated.
    """
    out_v, out_i = compact_arrays(values, indices, k)
    dropped = dropped + jnp.maximum(
        jnp.sum(values, axis=1) - jnp.sum(out_v, axis=1), 0.0
    )
    return out_v, out_i, dropped


def threshold_values(values: jax.Array, threshold: float) -> jax.Array:
    """Epsilon sparsification (paper Section 3.3): zero entries below eps."""
    if threshold <= 0.0:
        return values
    return jnp.where(values >= threshold, values, 0.0)


def bucket_by_owner(
    values: jax.Array,
    indices: jax.Array,
    ep: int,
    n_shard: int,
    k: int,
    *,
    to_local: bool = True,
) -> Tuple[jax.Array, jax.Array]:
    """Per-(row, owner) top-``k`` buckets: the distributed wire format.

    ``indices`` are global column ids in ``[0, ep * n_shard)`` partitioned
    into ``ep`` contiguous owner intervals of width ``n_shard``.  For each
    owner the candidates falling into its interval are dedup-merged and
    compacted (:func:`compact_arrays`) so the bucket carries the true
    per-owner top-``k`` — exactly what one ``all_to_all`` step exchanges.

    Returns ``(vals f32[Q, ep, k], idx int32[Q, ep, k])``; with
    ``to_local`` (default) indices are owner-local (``global - owner *
    n_shard``), the form the receiving shard consumes directly.  Empty
    slots are ``(0.0, 0)`` as everywhere else.

    Exact whenever ``k >= n_shard`` (an owner can receive at most
    ``n_shard`` distinct columns after the merge); a smaller ``k`` drops
    the per-owner tail mass, bounding the drift like every other top-K
    truncation in this module.
    """
    # one global merge (the expensive sort), then a cheap per-owner top-k:
    # after the merge each column appears in at most one slot per row, so
    # masking + topk_compact yields the same buckets as a per-owner
    # compact_arrays without re-sorting ep times
    values, indices = merge_duplicates(values, indices)
    out_v, out_i = [], []
    for owner in range(ep):
        mask = (indices // n_shard) == owner
        v = jnp.where(mask, values, 0.0)
        # park masked-out slots at the owner's local vertex 0: value 0
        # entries are the shared empty-slot convention
        i = jnp.where(mask, indices, owner * n_shard)
        cv, ci = topk_compact(v, i, k)
        if to_local:
            ci = jnp.where(cv > 0, ci - owner * n_shard, 0)
        out_v.append(cv)
        out_i.append(ci)
    return (
        jnp.stack(out_v, axis=1),
        jnp.stack(out_i, axis=1).astype(jnp.int32),
    )
