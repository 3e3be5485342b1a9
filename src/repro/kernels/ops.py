"""Jit'd public wrappers around the Pallas kernels.

These handle tile padding, fold hub-split ELL rows back to vertices, and
expose drop-in replacements for the pure-jnp core ops:

* :func:`ell_push`      <-> :func:`repro.graphs.formats.ell_pull`
* :func:`index_combine` <-> :func:`repro.core.verd.combine_with_index`
* :func:`frontier_push` <-> :func:`repro.core.verd.sparse_push_candidates`
  (+ :func:`repro.core.frontier.compact`)
* :func:`sharded_frontier_push` <-> :func:`repro.core.verd.gather_push_edges`
  (+ :func:`repro.core.frontier.bucket_by_owner`) — the distributed wire step
* :func:`index_combine_sparse` <-> :func:`repro.core.verd.combine_with_index_sparse`
* :func:`walk_step` <-> :func:`repro.core.walks.advance_cursors` (jnp path) —
  the offline walk engine's fused bulk advance
* :func:`embedding_bag` <-> :func:`repro.models.recsys.embedding` bag path

Kernels compile for the TPU by default; ``interpret=True`` runs the kernel
bodies through the Pallas interpreter instead (the CPU test mode).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from repro.core import frontier as _frontier
from repro.core import verd as _verd
from repro.core.frontier import SparseFrontier
from repro.core.graph import Graph
from repro.graphs.formats import EllChunks
from repro.kernels import ell_spmm as _ell
from repro.kernels import embedding_bag as _bag
from repro.kernels import frontier_push as _push
from repro.kernels import index_combine as _comb
from repro.kernels import walk_step as _walk


# Trace-time invocation counts per wrapper: incremented when a wrapper body
# runs, i.e. once per jit trace (cached re-executions of a traced graph do
# not re-count).  "Did this path go through the fused kernel?" is exactly a
# trace-time question, which is what the engine-routing regression in
# tests/test_parity.py asserts.
_invocations: collections.Counter = collections.Counter()


def kernel_invocations() -> dict:
    """Snapshot of the per-wrapper trace-time invocation counts."""
    return dict(_invocations)


def reset_kernel_invocations() -> None:
    _invocations.clear()


def _pad_to(x: jax.Array, axis: int, multiple: int, value=0):
    size = x.shape[axis]
    target = ((size + multiple - 1) // multiple) * multiple
    if target == size:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - size)
    return jnp.pad(x, pads, constant_values=value)


@functools.partial(
    jax.jit, static_argnames=("q_tile", "r_tile", "interpret")
)
def ell_push(
    frontier: jax.Array,
    ell: EllChunks,
    *,
    q_tile: int = 8,
    r_tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """``frontier @ A0`` via the Pallas kernel; f32[Q, n] -> f32[Q, n].

    Pads Q and the ELL rows to tile multiples, then folds hub chunks with a
    segment-sum keyed by ``row2vertex``.
    """
    q, n = frontier.shape
    f = _pad_to(frontier, 0, q_tile)
    nbr = _pad_to(ell.nbr, 0, r_tile)
    w = _pad_to(ell.weight, 0, r_tile)
    r2v = _pad_to(ell.row2vertex, 0, r_tile)  # pad rows -> vertex 0, weight 0
    partial = _ell.ell_spmm(
        f, nbr, w, q_tile=q_tile, r_tile=r_tile, interpret=interpret
    )
    out = jax.ops.segment_sum(partial.T, r2v, num_segments=n).T
    return out[:q]


@functools.partial(
    jax.jit, static_argnames=("q_tile", "v_tile", "interpret")
)
def index_combine(
    s: jax.Array,
    f: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    *,
    q_tile: int = 8,
    v_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Fused ``s + f @ P_hat``; pads Q and the vertex axis to tiles."""
    q, n = s.shape
    s_p = _pad_to(s, 0, q_tile)
    f_p = _pad_to(_pad_to(f, 0, q_tile), 1, v_tile)
    vals_p = _pad_to(vals, 0, v_tile)
    idx_p = _pad_to(idx, 0, v_tile)
    out = _comb.index_combine(
        s_p, f_p, vals_p, idx_p, q_tile=q_tile, v_tile=v_tile,
        interpret=interpret,
    )
    return out[:q]


def frontier_push(
    f: SparseFrontier,
    graph: Graph,
    sources: jax.Array,
    *,
    c: float,
    degree_cap: int,
    k_out: int,
    threshold: float = 0.0,
    q_tile: int = 8,
    hub_split_degree: int = 0,
    interpret: bool = False,
) -> SparseFrontier:
    """One fused sparse VERD push via the Pallas kernel.

    Drop-in for ``verd.sparse_push_candidates`` + ``frontier.compact``:
    returns the new frontier, compacted to ``k_out``.
    """
    if graph.m == 0:  # edgeless graph: nothing to gather, pure jnp path
        cv, ci = _verd.sparse_push_candidates(
            graph, f.values, f.indices, sources, c=c, degree_cap=degree_cap
        )
        return _frontier.compact(
            cv, ci, k_out, graph.n, threshold=threshold
        )
    _invocations["frontier_push"] += 1  # counted only when the kernel runs
    ov, oi = _push.frontier_push(
        f.values, f.indices, sources.astype(jnp.int32),
        graph.row_ptr, graph.out_deg, graph.col_idx,
        c=c, degree_cap=degree_cap, k_out=k_out, threshold=threshold,
        q_tile=q_tile, hub_split_degree=hub_split_degree,
        interpret=interpret,
    )
    return SparseFrontier(values=ov, indices=oi, k=ov.shape[1], n=graph.n)


def sharded_frontier_push(
    fv: jax.Array,
    fi: jax.Array,
    row_ptr: jax.Array,
    col_rows: jax.Array,
    *,
    c: float,
    degree_cap: int,
    ep: int,
    n_shard: int,
    wire_k: int,
    hub_split_degree: int = 0,
    q_tile: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """One shard's local push + per-owner exchange buckets.

    Drop-in for the pre-``all_to_all`` compute of the distributed sparse
    wire format (``verd.gather_push_edges`` + ``frontier.bucket_by_owner``);
    returns ``(vals f32[Q, ep, wire_k], idx int32[Q, ep, wire_k])`` with
    owner-local indices.  ``col_rows`` is the shard's ``col_idx`` as lane
    rows (``ShardedGraph.col_idx[shard]``).
    """
    _invocations["sharded_frontier_push"] += 1
    return _push.sharded_frontier_push(
        fv, fi, row_ptr, col_rows,
        c=c, degree_cap=degree_cap, ep=ep, n_shard=n_shard, wire_k=wire_k,
        hub_split_degree=hub_split_degree, q_tile=q_tile,
        interpret=interpret,
    )


def index_combine_sparse(
    s: SparseFrontier,
    f: SparseFrontier,
    vals: jax.Array,
    idx: jax.Array,
    *,
    k_out: int,
    q_tile: int = 8,
    interpret: bool = False,
) -> SparseFrontier:
    """Fused sparse ``s + f @ P_hat`` + top-k via the Pallas kernel.

    Drop-in for ``verd.combine_with_index_sparse`` at ``out_k=k_out``.
    """
    _invocations["index_combine_sparse"] += 1
    ov, oi = _comb.index_combine_sparse(
        s.values, s.indices, f.values, f.indices, vals, idx, k_out=k_out,
        q_tile=q_tile, interpret=interpret,
    )
    return SparseFrontier(
        values=ov, indices=oi, k=ov.shape[1], n=vals.shape[0])


def walk_step(
    cursors: jax.Array,
    sources: jax.Array,
    u: jax.Array,
    row_ptr: jax.Array,
    out_deg: jax.Array,
    col_idx: jax.Array,
    *,
    w_tile: int = _walk.TILE_WALKS,
    interpret: bool = False,
) -> jax.Array:
    """One fused bulk walk advance via the Pallas kernel.

    Drop-in for the jnp path of :func:`repro.core.walks.advance_cursors`
    (bit-identical under the same uniforms): accepts any cursor shape,
    flattens, advances, and restores the shape.
    """
    if col_idx.shape[0] == 0:  # edgeless graph: every walk jumps home
        return jnp.broadcast_to(sources, cursors.shape).astype(jnp.int32)
    _invocations["walk_step"] += 1
    shape = cursors.shape
    out = _walk.walk_step(
        cursors.reshape(-1), jnp.broadcast_to(sources, shape).reshape(-1),
        u.reshape(-1), row_ptr, out_deg, col_idx,
        w_tile=w_tile, interpret=interpret,
    )
    return out.reshape(shape)


@functools.partial(
    jax.jit, static_argnames=("b_tile", "d_tile", "interpret")
)
def embedding_bag(
    ids: jax.Array,
    mask: jax.Array,
    table: jax.Array,
    *,
    b_tile: int = 64,
    d_tile: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Bag-sum lookup; pads batch and embedding dims to tiles."""
    b, _ = ids.shape
    v, d = table.shape
    ids_p = _pad_to(ids, 0, b_tile)
    mask_p = _pad_to(mask, 0, b_tile)
    d_t = min(d_tile, d) if d % min(d_tile, d) == 0 else d
    table_p = _pad_to(table, 1, d_t)
    out = _bag.embedding_bag(
        ids_p, mask_p, table_p, b_tile=b_tile, d_tile=d_t,
        interpret=interpret,
    )
    return out[:b, :d]
