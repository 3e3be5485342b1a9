"""Incremental index maintenance for evolving graphs.

Production graphs mutate under the service; rebuilding the whole
fingerprint index per edge batch is ``O(n * R / c)`` resampled walk
positions.  Per-vertex fingerprints are independent Monte-Carlo sketches,
so an edge update only invalidates the rows whose walks *could* have
crossed the touched vertices (the incremental scheme of Hou et al. 2022,
PAPERS.md).  This module finds that set and repairs only it:

* **Invalidation.** ``build_maintainable_index`` records, per fingerprint
  row, a "walks-through" Bloom filter over every counted walk position
  (``walks.simulate_walks_sparse(touch_bits=...)``).  A walk only ever
  steps *from* counted positions, so a row whose filter misses every
  touched vertex re-simulates **bit-identically** on the updated graph —
  Bloom false positives cause harmless extra repair, never a stale row.
  The dirty set is the filter hits plus the touched sources themselves.

* **Repair granularity.** The walk engine draws its uniforms per source
  *chunk* (``[rows, w]`` from ``fold_in(key, chunk_offset)``), so a row's
  random stream depends on its position in the chunk — repairing a row
  subset under fresh keys would decorrelate it from a rebuild.  Repair
  therefore recomputes whole *chunks* of the original build grid through
  :func:`repro.core.index.sparse_chunk_estimates` with the build's exact
  per-chunk keys: the repaired index equals a from-scratch
  ``build_index`` on the mutated graph row for row (bitwise on a
  single-device grid; the sharded grid repairs through the documented
  ``r_splits`` emulation, ≤1e-5 L1 on dirty rows).

* **Accounting.** Work is measured in resampled walk positions — chunk
  slots swept times the expected positions per slot (``r / c``), the same
  unit as ``index.preprocessing_cost_model`` — so the headline gate
  (``benchmarks/bench_updates.py``) is simply dirty-chunks over
  total-chunks.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import walks as walks_mod
from repro.core.graph import Graph, apply_edge_updates
from repro.core.index import (PPRIndex, build_index, build_index_sharded,
                              set_rows, sparse_chunk_estimates)

DEFAULT_C = walks_mod.DEFAULT_C


def default_touch_bits(r: int, c: float = DEFAULT_C) -> int:
    """Bloom width for ``r`` walks/row: a row's filter holds ~``r/c``
    distinct positions under ``TOUCH_HASHES`` hashes, so ``bits ~ 256 * r``
    keeps the per-(row, vertex) false-positive rate ~1e-4 — small enough
    that FP-dirty rows stay a rounding error next to truly-dirty ones.
    Power-of-two, clamped to [1024, 65536]."""
    bits = 1024
    while bits < 256 * max(r, 1) and bits < 65536:
        bits *= 2
    return bits


@dataclasses.dataclass(frozen=True)
class TouchSketch:
    """Per-row walks-through Bloom filters: ``bits bool[rows, n_bits]``."""

    bits: jax.Array
    hashes: int = walks_mod.TOUCH_HASHES

    @property
    def rows(self) -> int:
        return int(self.bits.shape[0])

    @property
    def n_bits(self) -> int:
        return int(self.bits.shape[1])

    @property
    def nbytes(self) -> int:
        return self.rows * self.n_bits  # bool storage

    def dirty_rows(self, touched) -> np.ndarray:
        """Rows whose filter contains *any* touched vertex (host query).

        Conservative by construction: no false negatives, so every row
        missing from the result is bit-stable under the update."""
        t = np.unique(np.asarray(touched, np.int64).reshape(-1))
        if t.size == 0:
            return np.zeros(0, dtype=np.int64)
        bits = np.asarray(self.bits)
        hb = np.asarray(walks_mod.touch_hash_bits(
            jnp.asarray(t, jnp.int32), self.n_bits, self.hashes))
        dirty = np.zeros(bits.shape[0], dtype=bool)
        # chunk the touched set so the [rows, chunk, k] gather stays small
        chunk = max(1, (1 << 22) // max(bits.shape[0], 1))
        for i in range(0, t.size, chunk):
            sel = bits[:, hb[i:i + chunk]]          # [rows, tc, k]
            dirty |= sel.all(axis=2).any(axis=1)
        return np.nonzero(dirty)[0].astype(np.int64)

    def replace_rows(self, rows, new_bits) -> "TouchSketch":
        """Functionally replace rows (sharding-preserving, like
        ``PPRIndex.replace_rows``)."""
        return TouchSketch(
            bits=set_rows(self.bits, rows, new_bits), hashes=self.hashes)


@dataclasses.dataclass(frozen=True)
class BuildParams:
    """Everything a repair needs to replay the build's chunk grid."""

    r: int
    l: int
    sketch_l: int
    c: float
    max_steps: int
    compact_every: int
    source_batch: int
    r_splits: int
    respawn: bool
    engine: str          # "sparse" | "sparse-sharded"


@dataclasses.dataclass(frozen=True)
class MaintainableIndex:
    """A ``PPRIndex`` plus what incremental repair needs: the build key,
    the chunk-grid parameters, and the per-row touch sketch."""

    index: PPRIndex
    touch: TouchSketch
    key: jax.Array
    params: BuildParams
    real_n: int          # graph vertices (index.n may be padded above it)

    @property
    def n_chunks(self) -> int:
        sb = self.params.source_batch
        grid_n = self.index.n if self.params.engine == "sparse-sharded" \
            else self.real_n
        return -(-grid_n // sb)


def build_maintainable_index(
    graph: Graph,
    r: int,
    l: int,
    key: jax.Array,
    *,
    touch_bits: int = 0,
    mesh=None,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    source_batch: int = 256,
    compact_every: int = 8,
    r_splits: int = 1,
    respawn: bool = False,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
    resume: bool = False,
    checkpoint_keep: int = 3,
    fault_plan=None,
    **sharded_kwargs,
) -> Tuple[MaintainableIndex, dict]:
    """Full-sweep index build that also records the maintenance state.

    Single-device (``mesh=None``, via :func:`repro.core.index.build_index`)
    or sharded (via :func:`repro.core.index.build_index_sharded`, which
    forces ``respawn`` to its own default unless overridden here).
    ``touch_bits=0`` auto-sizes the Bloom width from ``r``
    (:func:`default_touch_bits`).  Returns ``(maintainable, stats)`` with
    the touch filter popped out of ``stats`` into the result.

    ``checkpoint_dir``/``checkpoint_every``/``resume`` make the build
    crash-safe (see :func:`repro.core.index.build_index`); the touch
    filter rides in every commit, so an index resumed from a checkpoint
    repairs identically to an uninterrupted one
    (:func:`load_maintainable_index` is the reload path).
    """
    if touch_bits <= 0:
        touch_bits = default_touch_bits(r, c)
    ckpt_kwargs = dict(
        checkpoint_dir=checkpoint_dir, checkpoint_every=checkpoint_every,
        resume=resume, checkpoint_keep=checkpoint_keep,
        fault_plan=fault_plan,
    )
    if mesh is None:
        index, stats = build_index(
            graph, r, l, key, c=c, max_steps=max_steps,
            source_batch=source_batch, engine="sparse",
            compact_every=compact_every, r_splits=r_splits,
            respawn=respawn, touch_bits=touch_bits, **ckpt_kwargs,
        )
    else:
        index, stats = build_index_sharded(
            graph, r, l, key, mesh=mesh, c=c, max_steps=max_steps,
            source_batch=source_batch, compact_every=compact_every,
            respawn=respawn, touch_bits=touch_bits,
            **ckpt_kwargs, **sharded_kwargs,
        )
    touch = TouchSketch(bits=stats.pop("touch"))
    params = BuildParams(
        r=r, l=stats["l"], sketch_l=stats["sketch_l"], c=c,
        max_steps=max_steps, compact_every=compact_every,
        source_batch=stats["source_batch"], r_splits=stats["r_splits"],
        respawn=bool(stats["respawn"]), engine=stats["engine"],
    )
    m = MaintainableIndex(
        index=index, touch=touch, key=key, params=params, real_n=graph.n)
    return m, stats


def load_maintainable_index(checkpoint_dir: str) -> Tuple[
        MaintainableIndex, dict]:
    """Rebuild a :class:`MaintainableIndex` from a *complete* build
    checkpoint — no walk is re-simulated.

    The final ``complete=True`` step a checkpointed
    :func:`build_maintainable_index` commits carries everything repair
    needs: the index rows, the touch Bloom filter, and (in the build
    signature) the PRNG key plus the exact chunk-grid parameters.  The
    reloaded index therefore repairs bit-identically to the one the build
    returned in-process.  Requires the build to have run with
    ``touch_bits > 0`` (``build_maintainable_index`` always does).
    """
    from repro.core.index import load_index_checkpoint
    from repro.distributed.checkpoint import Checkpointer, deserialize_key

    index, stats = load_index_checkpoint(checkpoint_dir)
    if "touch" not in stats:
        raise ValueError(
            f"checkpoint under {checkpoint_dir} has no touch sketch — not "
            "a maintainable-index build")
    ckpt = Checkpointer(checkpoint_dir)
    hit = ckpt.restore_latest(
        predicate=lambda extra: bool(extra.get("complete")))
    assert hit is not None  # load_index_checkpoint already found it
    sig = hit[2]["signature"]
    key = deserialize_key(sig["key"])
    params = BuildParams(
        r=int(sig["r"]), l=int(stats["l"]), sketch_l=int(sig["sketch_l"]),
        c=float(sig["c"]), max_steps=int(sig["max_steps"]),
        compact_every=int(sig["compact_every"]),
        source_batch=int(sig["source_batch"]),
        r_splits=int(sig["r_splits"]), respawn=bool(sig["respawn"]),
        engine=str(stats["engine"]),
    )
    touch = TouchSketch(bits=stats.pop("touch"))
    m = MaintainableIndex(
        index=index, touch=touch, key=key, params=params,
        real_n=int(sig["n"]))
    return m, stats


def plan_repair(m: MaintainableIndex, touched_sources) -> dict:
    """Invalidation plan for a touched-source set: the dirty rows (touch
    hits ∪ touched sources) and the build-grid chunks covering them."""
    touched = np.unique(np.asarray(touched_sources, np.int64).reshape(-1))
    touched = touched[(touched >= 0) & (touched < m.real_n)]
    dirty = m.touch.dirty_rows(touched)
    dirty = np.union1d(dirty, touched)
    dirty = dirty[dirty < m.real_n]
    sb = m.params.source_batch
    chunks = np.unique(dirty // sb) if dirty.size else np.zeros(0, np.int64)
    return dict(
        touched=touched,
        dirty_rows=dirty,
        chunks=chunks,
        n_chunks_total=m.n_chunks,
    )


def _padded_walk_graph(graph: Graph, n_pad: int) -> Graph:
    """Pad the graph to the sharded index's vertex count: pad vertices are
    dangling, exactly as ``build_index_sharded`` pads its CSR slabs."""
    if n_pad == graph.n:
        return graph
    rp = np.asarray(graph.row_ptr, np.int32)
    od = np.asarray(graph.out_deg, np.int32)
    rp = np.concatenate([rp, np.full(n_pad - graph.n, rp[-1], np.int32)])
    od = np.concatenate([od, np.zeros(n_pad - graph.n, np.int32)])
    return Graph(
        row_ptr=jnp.asarray(rp), col_idx=graph.col_idx,
        src=graph.src, out_deg=jnp.asarray(od),
        n=n_pad, m=graph.m,
    )


def apply_updates(
    m: MaintainableIndex,
    graph: Graph,
    inserts=None,
    deletes=None,
) -> Tuple[Graph, MaintainableIndex, dict]:
    """Apply an edge-update batch and repair exactly the dirtied rows.

    ``graph`` must be the graph ``m`` was built (or last repaired) on.
    Returns ``(new_graph, new_maintainable, report)``; the inputs are not
    mutated.  ``report["dirty_row_ids"]`` is the vertex set serving-layer
    caches must invalidate; the ``resampled_*``/``rebuild_*`` fields carry
    the walk-position accounting the update bench gates on.
    """
    if graph.n != m.real_n:
        raise ValueError(
            f"graph has {graph.n} vertices but the index was built on "
            f"{m.real_n}")
    new_graph, touched = apply_edge_updates(graph, inserts, deletes)
    plan = plan_repair(m, touched)
    p = m.params
    sb = p.source_batch
    n_ins = len(np.asarray(inserts).reshape(-1, 2)) if inserts is not None \
        and np.asarray(inserts).size else 0
    n_del = len(np.asarray(deletes).reshape(-1, 2)) if deletes is not None \
        and np.asarray(deletes).size else 0
    # Work accounting, in walk positions (the preprocessing_cost_model
    # unit): every swept chunk slot expects r/c counted positions, and a
    # rebuild sweeps the full grid including its pad slots.
    pos_per_slot = p.r / p.c
    resampled_slots = int(len(plan["chunks"])) * sb
    rebuild_slots = plan["n_chunks_total"] * sb
    report = dict(
        edges_inserted=int(n_ins),
        edges_deleted=int(n_del),
        touched_sources=int(plan["touched"].size),
        dirty_rows=int(plan["dirty_rows"].size),
        dirty_row_ids=plan["dirty_rows"],
        repaired_chunks=int(len(plan["chunks"])),
        total_chunks=int(plan["n_chunks_total"]),
        resampled_positions=resampled_slots * pos_per_slot,
        rebuild_positions=rebuild_slots * pos_per_slot,
        resample_ratio=rebuild_slots / max(resampled_slots, 1),
    )
    if not len(plan["chunks"]):
        return new_graph, m, report

    walk_g = _padded_walk_graph(new_graph, m.index.n)
    sharded = p.engine == "sparse-sharded"
    rows_parts, vals_parts, idxs_parts, touch_parts = [], [], [], []
    for chunk in plan["chunks"]:
        start = int(chunk) * sb
        if sharded:
            # the sharded grid covers the padded vertex range; pad rows are
            # swept (their key position matters) then zeroed like the build
            src_np = np.arange(start, start + sb, dtype=np.int32)
            real = int(np.sum(src_np < m.real_n))
        else:
            # the single-device grid pads the ragged tail with source 0
            real = min(sb, m.real_n - start)
            src_np = np.concatenate([
                np.arange(start, start + real, dtype=np.int32),
                np.zeros(sb - real, np.int32),
            ])
        out = sparse_chunk_estimates(
            walk_g, jnp.asarray(src_np), jax.random.fold_in(m.key, start),
            r=p.r, l=p.l, sketch_l=p.sketch_l, c=p.c,
            max_steps=p.max_steps, compact_every=p.compact_every,
            r_splits=p.r_splits, respawn=p.respawn,
            touch_bits=m.touch.n_bits,
        )
        vals, idxs, _, _, touch = out
        if sharded:
            realm = jnp.asarray(src_np) < m.real_n
            vals = jnp.where(realm[:, None], vals, 0.0)
            idxs = jnp.where(realm[:, None], idxs, 0)
            touch = jnp.where(realm[:, None], touch, False)
            rows_parts.append(np.arange(start, start + sb, dtype=np.int64))
        else:
            vals, idxs, touch = vals[:real], idxs[:real], touch[:real]
            rows_parts.append(
                np.arange(start, start + real, dtype=np.int64))
        vals_parts.append(vals)
        idxs_parts.append(idxs)
        touch_parts.append(touch)

    rows = np.concatenate(rows_parts)
    new_index = m.index.replace_rows(
        rows, jnp.concatenate(vals_parts, axis=0),
        jnp.concatenate(idxs_parts, axis=0))
    new_touch = m.touch.replace_rows(
        rows, jnp.concatenate(touch_parts, axis=0))
    new_m = MaintainableIndex(
        index=new_index, touch=new_touch, key=m.key, params=p,
        real_n=m.real_n)
    report["rows_replaced"] = int(rows.size)
    return new_graph, new_m, report
