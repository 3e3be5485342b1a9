"""Distributed PPR engine: PowerWalk at pod scale (the paper's system).

At twitter-2010 scale (N = 41.65M) the dense ``[Q, N]`` frontier of
:mod:`repro.core.verd` is impossible; this module is the vertex-sharded,
query-tiled engine:

* **Graph layout**: vertices partitioned into ``model``-axis intervals
  (paper Section 3.1's master/slave intervals, static here).  Each shard
  owns the *out-edges of its vertices* (local CSR rows, global column ids).
* **VERD iteration** (push mode): each shard pushes its local frontier
  mass through its local edges, bucketing contributions by destination
  owner -> one ``all_to_all`` over the model axis per iteration -> sum
  received partials.  This is PowerGraph's scatter phase turned into a
  single bulk collective — exactly the paper's "small packets multiplexed
  into large payloads", now in hardware.
* **Sparse-frontier exchange** (default, ``exchange="sparse"``): the wire
  format is the fixed-width :class:`~repro.core.frontier.SparseFrontier`
  idiom — each shard holds its local ``[Q, K]`` frontier slice, pushes it
  through its local CSR rows (ELL-style hub splitting keeps the gather
  width ``<= hub_split_degree``), buckets candidates by destination owner
  as per-owner top-``wire_k`` ``(values, local-index)`` pairs
  (:func:`repro.core.frontier.bucket_by_owner`), and one ``all_to_all``
  moves O(Q x shards x wire_k) bytes per iteration instead of the dense
  O(Q x N) slab.  Received partials are dedup-merged + re-compacted with
  the same ``frontier.py`` machinery as the single-device sparse path, so
  the two paths agree to <= 1e-5 L1 when the widths cover the frontier
  support (``tests/test_parity.py``).  The legacy dense slab exchange is
  kept under ``exchange="dense"`` as the oracle; its ``compress_k`` knob
  is deprecated (subsumed by ``wire_k``).
* **MCFP walk step**: walk cursors shard over the data axes (embarrassing
  parallelism over sources, as in the paper); every (data, model) shard
  scatters visits of its walks that land in its vertex interval — visit
  counting needs no communication at all.
* **Index combine + top-k**: local combine against the vertex-sharded
  top-L index, bucket/exchange once, then a local+gathered top-k.

Everything is shard_map'd so the collective schedule is explicit and
auditable in the compiled HLO.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import frontier as frontier_mod
from repro.core.graph import Graph
from repro.core.walks import DEFAULT_C


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Distributed engine configuration."""
    n: int                      # padded global vertex count (multiple of ep)
    ep: int                     # model-axis shards (vertex intervals)
    q_tile: int = 32            # queries per shared-decomposition tile
    c: float = DEFAULT_C
    t_iterations: int = 2
    index_l: int = 667
    top_k: int = 200
    exchange: str = "sparse"    # sparse (SparseFrontier wire) | dense (oracle)
    frontier_k: int = 0         # per-shard local frontier width (0 = derive)
    wire_k: int = 0             # per-owner exchange width (0 = frontier_k)
    combine_wire_k: int = 0     # index-combine exchange width (0 = derive)
    degree_cap: int = 0         # max out-degree; required for sparse exchange
    hub_split_degree: int = 0   # ELL row-split threshold for the sparse push
    kernel_q_tile: int = 8      # query-tile of the fused Pallas push kernel
    compress_k: int = 0         # DEPRECATED: top-k'd *dense* exchange; use
                                # exchange="sparse" + wire_k instead
    edge_chunk: int = 1 << 22   # local edge-scan chunk
    wire_dtype: Any = jnp.float32   # bf16 halves exchange buffers + bytes
    model_axis: str = "model"
    batch_axes: Tuple[str, ...] = ("data",)

    def __post_init__(self):
        if self.exchange not in ("sparse", "dense"):
            raise ValueError(f"unknown exchange {self.exchange!r}")
        if self.compress_k:
            warnings.warn(
                "DistConfig.compress_k is deprecated: set wire_k instead. "
                "On the default exchange='sparse' path compress_k is only "
                "honored as the wire_k fallback when wire_k is unset; on "
                "the legacy exchange='dense' oracle path it still selects "
                "the compressed slab exchange.",
                DeprecationWarning,
                stacklevel=2,
            )

    @property
    def n_shard(self) -> int:
        return self.n // self.ep

    @property
    def resolved_frontier_k(self) -> int:
        """Local frontier width K (same auto floor as the engine selector)."""
        from repro.core.query import auto_frontier_floor

        if self.frontier_k > 0:
            return min(self.frontier_k, self.n)
        return min(self.n, auto_frontier_floor(self.top_k))

    @property
    def resolved_wire_k(self) -> int:
        """Per-owner exchange width; ``n_shard`` always fully covers (an
        owner sees at most ``n_shard`` distinct columns after the merge)."""
        k = self.wire_k if self.wire_k > 0 else (
            self.compress_k if self.compress_k > 0
            else self.resolved_frontier_k
        )
        return min(k, self.n_shard)

    @property
    def resolved_combine_wire_k(self) -> int:
        k = self.combine_wire_k if self.combine_wire_k > 0 else max(
            self.resolved_wire_k, self.top_k
        )
        return min(k, self.n_shard)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ShardedGraph:
    """Per-shard CSR slabs, stacked on a leading shard dim.

    row_ptr: int32[ep, n_shard + 1]   local rows (offsets into col_idx row)
    col_idx: int32[ep, m_shard]       global destination ids (padded); for
                                      exchange="sparse" stored as the push
                                      kernel's lane rows, int32[ep,
                                      m_shard // 128 + 2, 1, 128] (the same
                                      ids, zero-padded), so no iteration
                                      relays the slab out
    edge_w:  f32[ep, m_shard]         1/out_deg(src), 0 on padding — only
                                      materialized for exchange="dense";
                                      the sparse step re-derives 1/deg from
                                      row lengths, so it gets a [ep, 1] stub
    dangling: f32[ep, n_shard]        1.0 where the local vertex is dangling
    """

    row_ptr: Any
    col_idx: Any
    edge_w: Any
    dangling: Any

    @staticmethod
    def specs(cfg: DistConfig, m_shard: int) -> "ShardedGraph":
        from repro.kernels.frontier_push import lane_rows_shape

        sds = jax.ShapeDtypeStruct
        dense = cfg.exchange == "dense"
        m_w = m_shard if dense else 1
        col = (m_shard,) if dense else lane_rows_shape(m_shard)
        return ShardedGraph(
            row_ptr=sds((cfg.ep, cfg.n_shard + 1), jnp.int32),
            col_idx=sds((cfg.ep,) + col, jnp.int32),
            edge_w=sds((cfg.ep, m_w), jnp.float32),
            dangling=sds((cfg.ep, cfg.n_shard), jnp.float32),
        )

    @staticmethod
    def shardings(cfg: DistConfig, mesh: Mesh) -> "ShardedGraph":
        s = NamedSharding(mesh, P(cfg.model_axis, None))
        return ShardedGraph(row_ptr=s, col_idx=s, edge_w=s, dangling=s)


def build_sharded_graph(graph: Graph, cfg: DistConfig) -> ShardedGraph:
    """Host-side partitioning of a real graph into per-shard slabs."""
    n, ep, ns = cfg.n, cfg.ep, cfg.n_shard
    row_ptr = np.asarray(graph.row_ptr).astype(np.int64)
    col = np.asarray(graph.col_idx).astype(np.int32)
    deg = np.asarray(graph.out_deg).astype(np.float32)
    inv = np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0)
    m_shard = 0
    slabs = []
    for s in range(ep):
        lo_v, hi_v = s * ns, min((s + 1) * ns, graph.n)
        lo_e, hi_e = row_ptr[lo_v] if lo_v <= graph.n else row_ptr[-1], \
            row_ptr[hi_v] if hi_v <= graph.n else row_ptr[-1]
        local_rp = (row_ptr[lo_v:hi_v + 1] - row_ptr[lo_v]).astype(np.int32)
        # pad vertex rows of the last shard
        if len(local_rp) < ns + 1:
            local_rp = np.concatenate(
                [local_rp,
                 np.full(ns + 1 - len(local_rp), local_rp[-1], np.int32)])
        lc = col[lo_e:hi_e]
        if cfg.exchange == "dense":
            lw = np.repeat(inv[lo_v:hi_v],
                           np.diff(row_ptr[lo_v:hi_v + 1]).astype(np.int64))
        else:
            lw = np.zeros(0, np.float32)
        dang = np.zeros(ns, np.float32)
        real = min(hi_v, graph.n) - lo_v
        if real > 0:
            dang[:real] = (deg[lo_v:lo_v + real] == 0).astype(np.float32)
        slabs.append((local_rp, lc, lw.astype(np.float32), dang))
        m_shard = max(m_shard, len(lc))
    m_shard = max(m_shard, 1)
    rp = np.stack([s[0] for s in slabs])
    if cfg.exchange == "dense":
        ci = np.stack([np.pad(s[1], (0, m_shard - len(s[1]))) for s in slabs])
        ew = np.stack([np.pad(s[2], (0, m_shard - len(s[2]))) for s in slabs])
    else:
        # the push kernel's lane rows, laid out once here
        from repro.kernels.frontier_push import lane_rows_shape

        shape = lane_rows_shape(m_shard)
        width = shape[0] * shape[2]
        ci = np.stack([np.pad(s[1], (0, width - len(s[1]))) for s in slabs])
        ci = ci.reshape((ep,) + shape)
        # the sparse step re-derives 1/deg; skip the O(m) f32 slab entirely
        ew = np.zeros((cfg.ep, 1), np.float32)
    dg = np.stack([s[3] for s in slabs])
    return ShardedGraph(
        row_ptr=jnp.asarray(rp), col_idx=jnp.asarray(ci),
        edge_w=jnp.asarray(ew), dangling=jnp.asarray(dg),
    )


# ---------------------------------------------------------------------------
# one VERD iteration, per shard
# ---------------------------------------------------------------------------

def _push_local(cfg: DistConfig, g_row_ptr, g_col, g_w, f_local):
    """Local push: [qt, ns] -> contributions [qt, ep, ns] by dest owner."""
    qt = f_local.shape[0]
    m = g_col.shape[0]
    chunk = min(cfg.edge_chunk, m)
    n_chunks = (m + chunk - 1) // chunk
    pad = n_chunks * chunk - m
    col_c = jnp.pad(g_col, (0, pad)).reshape(n_chunks, chunk)
    w_c = jnp.pad(g_w, (0, pad)).reshape(n_chunks, chunk)

    def body(acc, args):
        ci, col_k, w_k = args
        # per-chunk source-row recovery keeps the [m]-sized index arrays
        # out of live memory (only [chunk] at a time)
        e_ids = ci * chunk + jnp.arange(chunk, dtype=jnp.int32)
        sr_k = jnp.clip(
            jnp.searchsorted(g_row_ptr, e_ids, side="right") - 1,
            0, cfg.n_shard - 1,
        )
        vals = jnp.take(f_local, sr_k, axis=1) * w_k[None, :]   # [qt, chunk]
        # destination bucket = owner * n_shard + local id == global id
        acc = acc + jax.ops.segment_sum(
            vals.T, col_k, num_segments=cfg.n,
        ).T
        return acc, ()

    acc0 = jnp.zeros((qt, cfg.n), jnp.float32)
    acc, _ = jax.lax.scan(
        body, acc0, (jnp.arange(n_chunks, dtype=jnp.int32), col_c, w_c))
    return acc.reshape(qt, cfg.ep, cfg.n_shard)


def _compress_bucket(contrib, k):
    """Top-k per (query, owner-bucket): values + local ids (fixed shape)."""
    vals, idx = jax.lax.top_k(contrib, k)            # [qt, ep, k]
    return vals, idx.astype(jnp.int32)


def make_verd_tile_step(
    cfg: DistConfig, mesh: Mesh, *, kernel_interpret: bool = False
):
    """Returns jit-able fn(graph_slabs, sources[qt], index_vals, index_idx)
    -> (topk_vals [qt, top_k], topk_idx [qt, top_k]).

    One full query tile: T iterations of shared decomposition + index
    combine + distributed top-k.  ``index_vals/idx``: [ep, n_shard, L].
    Dispatches on ``cfg.exchange``: the default ``"sparse"`` wire format
    exchanges per-owner top-``wire_k`` (value, index) pairs; ``"dense"``
    keeps the legacy full-slab exchange as the oracle.  The sparse step's
    push kernel is compiled for the TPU; ``kernel_interpret=True`` runs it
    through the Pallas interpreter (CPU tests).
    """
    if cfg.exchange == "sparse":
        return _make_verd_tile_step_sparse(cfg, mesh, kernel_interpret)
    return _make_verd_tile_step_dense(cfg, mesh)


def _make_verd_tile_step_dense(cfg: DistConfig, mesh: Mesh):
    """Legacy dense-slab exchange: O(Q x N) wire bytes per iteration."""
    model = cfg.model_axis

    def local_fn(rp, col, w, dang, sources, ivals, iidx):
        # slabs arrive with leading shard dim of size 1
        rp, col, w, dang = rp[0], col[0], w[0], dang[0]
        ivals, iidx = ivals[0], iidx[0]
        qt = sources.shape[0]
        me = jax.lax.axis_index(model)
        lo = me * cfg.n_shard

        # frontier: local slice of one-hot(sources)
        cols0 = jnp.clip(sources - lo, 0, cfg.n_shard - 1)
        hit0 = (sources >= lo) & (sources < lo + cfg.n_shard)
        src_onehot = jnp.zeros((qt, cfg.n_shard), jnp.float32).at[
            jnp.arange(qt), cols0].add(hit0.astype(jnp.float32))
        f = src_onehot
        s = jnp.zeros_like(f)

        def iteration(carry, _):
            s, f = carry
            s = s + cfg.c * f
            # dangling mass returns to each query's source vertex
            dm = jnp.sum(f * dang[None, :], axis=1)          # [qt]
            dm = jax.lax.psum(dm, model)
            contrib = _push_local(cfg, rp, col, w, f)        # [qt, ep, ns]
            if cfg.compress_k:
                vals, idx = _compress_bucket(contrib, cfg.compress_k)
                vals = jax.lax.all_to_all(
                    vals.astype(cfg.wire_dtype), model,
                    split_axis=1, concat_axis=1, tiled=False)
                idx = jax.lax.all_to_all(
                    idx, model, split_axis=1, concat_axis=1, tiled=False)
                # vals/idx: [qt, ep, k] received from every peer
                new_f = jnp.zeros((qt, cfg.n_shard), jnp.float32)
                qi = jnp.broadcast_to(
                    jnp.arange(qt)[:, None, None], vals.shape)
                new_f = new_f.at[qi.reshape(-1), idx.reshape(-1)].add(
                    vals.reshape(-1).astype(jnp.float32))
            else:
                recv = jax.lax.all_to_all(
                    contrib.astype(cfg.wire_dtype), model,
                    split_axis=1, concat_axis=1, tiled=False)
                new_f = recv.astype(jnp.float32).sum(axis=1)  # [qt, ns]
            new_f = (1.0 - cfg.c) * new_f
            # dangling mass jumps back to each query's source (Section 2.1)
            new_f = new_f + (1.0 - cfg.c) * dm[:, None] * src_onehot
            return (s, new_f), ()

        (s, f), _ = jax.lax.scan(
            iteration, (s, f), None, length=cfg.t_iterations)

        # combine with the local index rows: out columns are global ->
        # bucket by owner and exchange once.  Chunked over local vertices so
        # the [qt, chunk, L] expansion stays bounded (dense fw at twitter
        # scale is 66 GB).
        v_chunk = min(65536, cfg.n_shard)
        n_chunks = (cfg.n_shard + v_chunk - 1) // v_chunk
        pad_v = n_chunks * v_chunk - cfg.n_shard
        f_p = jnp.pad(f, ((0, 0), (0, pad_v)))
        iv_p = jnp.pad(ivals, ((0, pad_v), (0, 0)))
        ii_p = jnp.pad(iidx, ((0, pad_v), (0, 0)))
        fc = f_p.reshape(qt, n_chunks, v_chunk).transpose(1, 0, 2)
        ivc = iv_p.reshape(n_chunks, v_chunk, -1)
        iic = ii_p.reshape(n_chunks, v_chunk, -1)

        def combine_chunk(acc, args):
            f_k, iv_k, ii_k = args
            fw = f_k[:, :, None] * iv_k[None, :, :].astype(jnp.float32)
            acc = acc.at[:, ii_k.reshape(-1)].add(fw.reshape(qt, -1))
            return acc, ()

        contrib, _ = jax.lax.scan(
            combine_chunk, jnp.zeros((qt, cfg.n), jnp.float32),
            (fc, ivc, iic))
        contrib = contrib.reshape(qt, cfg.ep, cfg.n_shard)
        recv = jax.lax.all_to_all(
            contrib.astype(cfg.wire_dtype), model,
            split_axis=1, concat_axis=1, tiled=False)
        p_local = s + recv.astype(jnp.float32).sum(axis=1)    # [qt, ns]

        # distributed top-k: local top-k then gather + re-select
        k = min(cfg.top_k, cfg.n_shard)
        lv, li = jax.lax.top_k(p_local, k)
        gi = (li + lo).astype(jnp.int32)
        av = jax.lax.all_gather(lv, model, axis=1, tiled=True)  # [qt, ep*k]
        ai = jax.lax.all_gather(gi, model, axis=1, tiled=True)
        fv, fi = jax.lax.top_k(av, cfg.top_k)
        out_idx = jnp.take_along_axis(ai, fi, axis=1)
        return fv, out_idx

    in_specs = (
        P(model, None), P(model, None), P(model, None), P(model, None),
        P(),                                  # sources replicated
        P(model, None, None), P(model, None, None),
    )
    out_specs = (P(), P())
    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )

    def step(slabs: ShardedGraph, sources, index_vals, index_idx):
        return fn(slabs.row_ptr, slabs.col_idx, slabs.edge_w, slabs.dangling,
                  sources, index_vals, index_idx)

    return step


def _make_verd_tile_step_sparse(
    cfg: DistConfig, mesh: Mesh, interpret: bool
):
    """SparseFrontier wire format: O(Q x shards x wire_k) bytes/iteration.

    Per shard, per iteration: gather-push the local ``[Q, K]`` frontier
    slice through the local CSR rows via the fused HBM-resident Pallas
    kernel ``kernels.ops.sharded_frontier_push`` (hub rows split ELL-style
    so no gather axis exceeds ``hub_split_degree``; the kernel emits the
    per-owner top-``wire_k`` (value, local-index) buckets directly), one
    ``all_to_all``, then dedup-merge + re-compact the received partials back
    to the ``[Q, K]`` slice.  The
    accumulated ``s`` and the index-combine contributions stay sparse end to
    end; only the final per-shard top-k is gathered.
    """
    from repro.kernels import ops as kernel_ops

    if cfg.degree_cap <= 0:
        raise ValueError(
            "exchange='sparse' requires cfg.degree_cap > 0 (the max "
            "out-degree; resolve it host-side with "
            "repro.core.verd.resolve_degree_cap)"
        )
    model = cfg.model_axis
    ns = cfg.n_shard
    k_front = min(cfg.resolved_frontier_k, ns)   # local slice: <= ns distinct
    kw = cfg.resolved_wire_k
    kc = cfg.resolved_combine_wire_k

    def a2a(x):
        return jax.lax.all_to_all(
            x, model, split_axis=1, concat_axis=1, tiled=False
        )

    def local_fn(rp, col, dang, sources, ivals, iidx):
        # no edge_w input: 1/deg weights are re-derived from the local row
        # lengths, so the O(m) f32 slab never enters the sparse step
        rp, col, dang = rp[0], col[0], dang[0]
        ivals, iidx = ivals[0], iidx[0]
        qt = sources.shape[0]
        me = jax.lax.axis_index(model)
        lo = me * ns

        # local slice of one-hot(sources), in sparse (width-1) form
        hit0 = ((sources >= lo) & (sources < lo + ns)).astype(jnp.float32)
        src_local = jnp.clip(sources - lo, 0, ns - 1).astype(jnp.int32)
        fv = hit0[:, None]
        fi = src_local[:, None]

        s_vals, s_idxs = [], []
        for _ in range(cfg.t_iterations):
            s_vals.append(cfg.c * fv)
            s_idxs.append(fi)
            # dangling mass returns to each query's source (Section 2.1)
            dm = jax.lax.psum(
                jnp.sum(fv * jnp.take(dang, fi), axis=1), model
            )
            # fused local gather push + per-owner top-k buckets (the
            # HBM-resident Pallas kernel) -> one all_to_all of fixed-width
            # (value, local-index) pairs
            bv, bi = kernel_ops.sharded_frontier_push(
                fv, fi, rp, col,
                c=cfg.c, degree_cap=cfg.degree_cap, ep=cfg.ep, n_shard=ns,
                wire_k=kw, hub_split_degree=cfg.hub_split_degree,
                q_tile=cfg.kernel_q_tile, interpret=interpret,
            )
            bv = a2a(bv.astype(cfg.wire_dtype)).astype(jnp.float32)
            bi = a2a(bi)
            cand_v = jnp.concatenate(
                [bv.reshape(qt, -1), ((1.0 - cfg.c) * dm * hit0)[:, None]],
                axis=1,
            )
            cand_i = jnp.concatenate(
                [bi.reshape(qt, -1), src_local[:, None]], axis=1
            )
            fv, fi = frontier_mod.compact_arrays(cand_v, cand_i, k_front)

        # index combine on the sparse slice: gather only the K touched local
        # rows, bucket the (global-column) contributions by owner, exchange
        # once.  ivals/iidx: [ns, L] with global column ids.
        iv = jnp.take(ivals, fi, axis=0).astype(jnp.float32)  # [qt, K, L]
        ii = jnp.take(iidx, fi, axis=0)
        contrib = (fv[..., None] * iv).reshape(qt, -1)
        cv, ci = frontier_mod.bucket_by_owner(
            contrib, ii.reshape(qt, -1), cfg.ep, ns, kc
        )
        cv = a2a(cv.astype(cfg.wire_dtype)).astype(jnp.float32)
        ci = a2a(ci)

        # local p~ entries: accumulated s + received combine partials; both
        # hold local indices, so one compaction yields the local top-k
        p_v = jnp.concatenate(s_vals + [cv.reshape(qt, -1)], axis=1)
        p_i = jnp.concatenate(s_idxs + [ci.reshape(qt, -1)], axis=1)
        lv, li = frontier_mod.compact_arrays(p_v, p_i, cfg.top_k)
        gi = (li + lo).astype(jnp.int32)

        # distributed top-k: gather every shard's local top-k, re-select
        av = jax.lax.all_gather(lv, model, axis=1, tiled=True)
        ai = jax.lax.all_gather(gi, model, axis=1, tiled=True)
        fv_out, sel = jax.lax.top_k(av, cfg.top_k)
        out_idx = jnp.take_along_axis(ai, sel, axis=1)
        return fv_out, out_idx

    in_specs = (
        P(model, None), P(model, None), P(model, None),
        P(),                                  # sources replicated
        P(model, None, None), P(model, None, None),
    )
    out_specs = (P(), P())
    fn = jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )

    def step(slabs: ShardedGraph, sources, index_vals, index_idx):
        return fn(slabs.row_ptr, slabs.col_idx, slabs.dangling,
                  sources, index_vals, index_idx)

    return step


def exchange_bytes_per_iteration(cfg: DistConfig) -> Dict[str, float]:
    """Wire bytes one shard sends per VERD iteration, per exchange format.

    ``dense``: the full ``[q_tile, n]`` slab in ``wire_dtype``.  ``sparse``:
    ``q_tile * ep * wire_k`` (value, int32 index) pairs.  ``reduction`` is
    dense/sparse — the headline number ``benchmarks/bench_query.py`` reports
    (>= 5x at the acceptance point n=100k, Q=256, K=512).
    """
    item = jnp.dtype(cfg.wire_dtype).itemsize
    dense = float(cfg.q_tile * cfg.n * item)
    sparse = float(cfg.q_tile * cfg.ep * cfg.resolved_wire_k * (item + 4))
    return dict(
        dense=dense, sparse=sparse, reduction=dense / max(sparse, 1.0)
    )


# ---------------------------------------------------------------------------
# distributed MCFP walk step (offline indexing)
# ---------------------------------------------------------------------------

def _walk_graph(row_ptr, col_idx, out_deg) -> Graph:
    """Wrap replicated CSR slabs for the walk engine.

    The walk engine never reads the COO ``src`` field; poison it so any
    future consumer gathers index -1 instead of silently using
    destinations as sources (DCE'd while unused)."""
    m = col_idx.shape[0]
    return Graph(
        row_ptr=row_ptr, col_idx=col_idx,
        src=jnp.broadcast_to(jnp.int32(-1), (m,)),
        out_deg=out_deg, n=out_deg.shape[0], m=m,
    )


def _merge_sparse_counts(counts, axes, l: int):
    """Cross-shard sketch merge: one ``all_gather`` of the per-shard
    ``[rows, l]`` sketches along the width axis + one dedup-merge back to
    ``l``, plus the psum'd ``moves`` and the full ``dropped`` ledger
    (per-shard sketch truncation + whatever this merge compacts away), so
    ``fp_v.sum(1) + dropped == moves`` holds exactly for any ``l``.  The
    one communication step of both the sharded walk-counts step and the
    sharded index build."""
    av = jax.lax.all_gather(counts.fp.values, axes, axis=1, tiled=True)
    ai = jax.lax.all_gather(counts.fp.indices, axes, axis=1, tiled=True)
    moves = jax.lax.psum(counts.moves, axes)
    fp_v, fp_i, dropped = frontier_mod.merge_sketch_parts(
        av, ai, jax.lax.psum(counts.fp_dropped, axes), l
    )
    return fp_v, fp_i, moves, dropped

def make_walk_counts_step(cfg: DistConfig, mesh: Mesh, *, max_steps: int = 64):
    """Returns fn(row_ptr, col_idx, out_deg, sources[S], key) ->
    (fp_counts [S, n] vertex-sharded, moves [S]).

    Graph arrays are replicated (fits for twitter-2010-class graphs);
    walks shard over the batch axes; every (data, model) shard counts the
    visits that land in its vertex interval — no communication until the
    final psum of ``moves`` over data.
    """
    model = cfg.model_axis

    def local_fn(row_ptr, col_idx, out_deg, sources, rows, key):
        w = sources.shape[0]
        me = jax.lax.axis_index(model)
        lo = me * cfg.n_shard
        n_rows = cfg.q_tile  # count rows per tile

        def body(carry, t):
            cursors, active, fp, moves = carry
            k = jax.random.fold_in(key, t)
            for ax in cfg.batch_axes:  # distinct stream per data shard
                k = jax.random.fold_in(k, jax.lax.axis_index(ax))
            k_move, k_term = jax.random.split(k)
            af = active.astype(jnp.float32)
            local = (cursors >= lo) & (cursors < lo + cfg.n_shard)
            fp = fp.at[rows, jnp.clip(cursors - lo, 0, cfg.n_shard - 1)].add(
                af * local.astype(jnp.float32))
            moves = moves.at[rows].add(af)
            term = active & (jax.random.uniform(k_term, (w,)) < cfg.c)
            active = active & ~term
            deg = jnp.take(out_deg, cursors)
            base = jnp.take(row_ptr, cursors)
            off = jax.random.randint(k_move, (w,), 0, jnp.maximum(deg, 1))
            nxt = jnp.take(col_idx, base + off)
            cursors = jnp.where(deg == 0, sources, nxt)
            return (cursors, active, fp, moves), ()

        init = (
            sources,
            jnp.ones((w,), bool),
            jnp.zeros((n_rows, cfg.n_shard), jnp.float32),
            jnp.zeros((n_rows,), jnp.float32),
        )
        (c, a, fp, moves), _ = jax.lax.scan(
            body, init, jnp.arange(max_steps))
        fp = jax.lax.psum(fp, cfg.batch_axes)
        moves = jax.lax.psum(moves, cfg.batch_axes + (model,)) / cfg.ep
        return fp, moves

    in_specs = (
        P(None), P(None), P(None),            # graph replicated
        P(cfg.batch_axes), P(cfg.batch_axes), # walk sources/rows sharded
        P(),
    )
    out_specs = (P(None, model), P())
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def make_sparse_walk_counts_step(
    cfg: DistConfig,
    mesh: Mesh,
    *,
    r: int,
    l: int,
    max_steps: int = 64,
    compact_every: int = 8,
):
    """Sharded compacted sparse-sketch walk engine (offline indexing).

    Returns fn(row_ptr, col_idx, out_deg, sources[rows], key) ->
    ``(fp_vals f32[rows, l], fp_idx int32[rows, l], moves f32[rows],
    walks f32[rows], dropped f32[rows])``, replicated.  ``dropped`` is the
    full cross-shard ledger — per-shard sketch truncation plus anything the
    final merge compacts away — so the engine's conservation contract
    ``fp_vals.sum(1) + dropped == moves`` holds exactly for any ``l``.

    Walks are embarrassingly parallel, so the ``r`` walks of every source
    split evenly over *every* mesh axis (batch and model alike — a model
    replica would otherwise recompute identical walks): each shard runs
    ``r / n_shards`` walks per row through
    :func:`repro.core.walks.simulate_walks_sparse` on the replicated graph
    with a per-shard key, entirely communication-free.  The only cross-shard
    step is the final sketch merge: one ``all_gather`` of the per-shard
    ``[rows, l]`` sketches along the width axis plus one
    :func:`repro.core.frontier.compact_arrays` dedup-merge back to ``l``
    (O(rows * n_shards * l) wire bytes total — independent of ``n`` and of
    the walk count), and a psum of the scalar ``moves``/``walks``/
    ``dropped`` counters.  Requires ``r`` divisible by the mesh size.
    """
    from repro.core.walks import simulate_walks_sparse

    axes = tuple(cfg.batch_axes) + (cfg.model_axis,)
    n_shards = 1
    for ax in axes:
        n_shards *= mesh.shape[ax]
    if r % n_shards != 0:
        raise ValueError(
            f"r={r} must divide evenly over the {n_shards} mesh shards"
        )
    r_local = r // n_shards

    def local_fn(row_ptr, col_idx, out_deg, sources, key):
        for ax in axes:  # distinct walk stream per shard
            key = jax.random.fold_in(key, jax.lax.axis_index(ax))
        g = _walk_graph(row_ptr, col_idx, out_deg)
        counts = simulate_walks_sparse(
            g, sources, r_local, key, l=l, ep_l=0, c=cfg.c,
            max_steps=max_steps, compact_every=compact_every,
        )
        fp_v, fp_i, moves, dropped = _merge_sparse_counts(counts, axes, l)
        walks = jax.lax.psum(counts.walks, axes)
        return fp_v, fp_i, moves, walks, dropped

    in_specs = (
        P(None), P(None), P(None),            # graph replicated
        P(),                                  # sources replicated (r splits)
        P(),
    )
    out_specs = (P(), P(), P(), P(), P())
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def make_sparse_index_build_step(
    cfg: DistConfig,
    mesh: Mesh,
    *,
    r: int,
    l: int,
    sketch_l: int,
    real_n: int,
    max_steps: int = 64,
    compact_every: int = 8,
    source_batch: int = 256,
    respawn: bool = False,
    touch_bits: int = 0,
    chunk_start: int = 0,
    chunk_count: Optional[int] = None,
):
    """The whole offline index build as one sharded device computation.

    Returns fn(row_ptr, col_idx, out_deg, key) -> ``(values f32[n, l],
    indices int32[n, l], kept f32[n], dropped f32[n])`` with the index
    arrays sharded ``P(model, None)`` — each model shard sweeps the source
    chunks of its own vertex interval with a ``lax.scan`` (so the sweep is
    device-side, not a host chunk loop) and emits only its ``[n_shard, l]``
    block; no device ever holds a replicated ``[n, l]`` index (the jaxpr
    gate in ``tests/dist_engine_check.py``).  Graph arrays arrive
    replicated and padded to ``cfg.n`` rows.

    Per chunk this drives the :func:`make_sparse_walk_counts_step`
    machinery restricted to the batch axes: each data replica runs
    ``r / n_data`` walks per row (:func:`repro.core.walks
    .simulate_walks_sparse`, respawn-mode when ``respawn``) and the
    sketches merge through the same one-``all_gather`` dedup
    (``_merge_sparse_counts``), then normalize/truncate via
    ``index.normalize_sketch_to_index_rows``.  Key discipline: chunk at
    global source offset ``o`` uses ``fold_in(key, o)``; data replica
    ``s`` (the linear index over ``cfg.batch_axes``) folds ``s`` on top —
    the exact fold order of the single-device ``engine="sparse"`` build at
    ``r_splits = n_data``, which is what makes the two builders agree row
    for row under one key.

    Requires ``cfg.n_shard`` divisible by ``source_batch`` (so shard
    intervals align with the single-device chunk grid) and ``r`` divisible
    by the batch-axis shard count.

    ``touch_bits > 0`` appends a fifth output: the per-row walks-through
    Bloom filter ``bool[n, touch_bits]`` (``P(model, None)`` like the index
    rows), OR-merged across data replicas with a psum and zeroed on pad
    rows — the invalidation sketch ``core/updates.py`` consumes.

    ``chunk_start``/``chunk_count`` restrict the sweep to a contiguous
    *per-shard* chunk range (defaults: the whole grid) — the checkpointed
    ``build_index_sharded`` segments the scan at commit boundaries with
    these, and because each chunk's key is positional
    (``fold_in(key, offset)``) a segmented sweep reproduces the full sweep
    bit for bit.  Outputs then cover ``chunk_count * source_batch`` rows
    per shard (``P(model, None)`` as before).
    """
    from repro.core.index import normalize_sketch_to_index_rows
    from repro.core.walks import simulate_walks_sparse

    model = cfg.model_axis
    ns = cfg.n_shard
    axes = tuple(cfg.batch_axes)
    n_split = 1
    for ax in axes:
        n_split *= mesh.shape[ax]
    if r % n_split != 0:
        raise ValueError(
            f"r={r} must divide evenly over the {n_split} walk shards"
        )
    if ns % source_batch != 0:
        raise ValueError(
            f"n_shard={ns} must be a multiple of source_batch={source_batch}"
        )
    r_local = r // n_split
    n_chunks = ns // source_batch
    if chunk_count is None:
        chunk_count = n_chunks - chunk_start
    if not (0 <= chunk_start
            and chunk_count >= 1
            and chunk_start + chunk_count <= n_chunks):
        raise ValueError(
            f"chunk range [{chunk_start}, {chunk_start + chunk_count}) "
            f"outside the [0, {n_chunks}) per-shard chunk grid"
        )
    rows_out = chunk_count * source_batch

    def local_fn(row_ptr, col_idx, out_deg, key):
        me = jax.lax.axis_index(model)
        lo = me * ns
        # linear data-replica id: the split index the single-device
        # r_splits emulation folds (row-major over cfg.batch_axes)
        split = jnp.int32(0)
        for ax in axes:
            split = split * mesh.shape[ax] + jax.lax.axis_index(ax)
        g = _walk_graph(row_ptr, col_idx, out_deg)

        def chunk_body(carry, j):
            offset = lo + j * source_batch
            sources = offset + jnp.arange(source_batch, dtype=jnp.int32)
            chunk_key = jax.random.fold_in(key, offset)
            sub_key = (
                chunk_key if n_split == 1
                else jax.random.fold_in(chunk_key, split)
            )
            counts = simulate_walks_sparse(
                g, sources, r_local, sub_key, l=sketch_l, ep_l=0, c=cfg.c,
                max_steps=max_steps, compact_every=compact_every,
                respawn=respawn, touch_bits=touch_bits,
            )
            if n_split > 1:
                fp_v, fp_i, moves, dropped = _merge_sparse_counts(
                    counts, axes, sketch_l
                )
            else:
                fp_v, fp_i = counts.fp.values, counts.fp.indices
                moves, dropped = counts.moves, counts.fp_dropped
            vals, idxs, kept, dropped_est = normalize_sketch_to_index_rows(
                fp_v, fp_i, moves, dropped, l
            )
            # pad vertices (>= real_n): dangling rows that walked in place —
            # zero them so the sharded index carries no phantom mass
            realm = sources < real_n
            vals = jnp.where(realm[:, None], vals, 0.0)
            idxs = jnp.where(realm[:, None], idxs, 0)
            kept = jnp.where(realm, kept, 0.0)
            dropped_est = jnp.where(realm, dropped_est, 0.0)
            out = (vals, idxs, kept, dropped_est)
            if touch_bits:
                touch = counts.touch
                if n_split > 1:   # OR-merge the replicas' bloom filters
                    touch = jax.lax.psum(
                        touch.astype(jnp.int32), axes) > 0
                touch = jnp.where(realm[:, None], touch, False)
                out = out + (touch,)
            return carry, out

        _, scanned = jax.lax.scan(
            chunk_body, 0,
            chunk_start + jnp.arange(chunk_count, dtype=jnp.int32),
        )
        vals, idxs, kept, dropped = scanned[:4]
        out = (
            vals.reshape(rows_out, l), idxs.reshape(rows_out, l),
            kept.reshape(rows_out), dropped.reshape(rows_out),
        )
        if touch_bits:
            out = out + (scanned[4].reshape(rows_out, touch_bits),)
        return out

    in_specs = (P(None), P(None), P(None), P())   # graph + key replicated
    out_specs = (
        P(model, None), P(model, None), P(model), P(model),
    ) + ((P(model, None),) if touch_bits else ())
    return jax.shard_map(
        local_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


# ---------------------------------------------------------------------------
# Contract-auditor entry point (repro.analysis): inside the sharded build's
# shard_map bodies no per-device array may cover the full [n, L] index —
# the index stays model-sharded, never replicated.  Meaningful only on a
# multi-device mesh (with ep=1 a shard's legal block IS [n, L]), so the
# builder skips when the process has a single device; the auditor CLI
# forces a 4-way host-platform split before importing jax.
# ---------------------------------------------------------------------------

from repro.analysis.registry import register_entry_point as _register_ep


def _contract_spec_sharded_build_step():
    if jax.device_count() < 2:
        return dict(skip="needs >= 2 devices for a sharded mesh (run via "
                         "`python -m repro.analysis`, which forces a 4-way "
                         "host-platform split)")
    from repro.graphs import synthetic

    data = 2 if jax.device_count() >= 4 else 1
    mesh = jax.make_mesh((data, 2), ("data", "model"))
    g = synthetic.erdos_renyi(64, 4.0, seed=21)
    cfg = DistConfig(n=64, ep=2)
    l = 16
    step = make_sparse_index_build_step(
        cfg, mesh, r=64, l=l, sketch_l=48, real_n=64, source_batch=16,
    )
    jaxpr = jax.make_jaxpr(step)(
        g.row_ptr, g.col_idx, g.out_deg, jax.random.PRNGKey(3)
    )
    return dict(jaxpr=jaxpr, n=cfg.n, l=l)


_register_ep("sparse-index-build-step", "no-replicated-index",
             "src/repro/core/distributed_engine.py",
             _contract_spec_sharded_build_step)
