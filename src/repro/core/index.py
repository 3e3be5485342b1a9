"""The PPR index: top-L truncated fingerprints (paper Section 3.1/3.3).

The paper stores each approximate vector sparsely (hash tables / sorted
vectors) and discards entries below a threshold.  The TPU-native analogue is
a *fixed-width* top-L representation: ``values f32[n, L]`` + ``indices
int32[n, L]`` — dense, regular, vertex-shardable over the ``model`` mesh
axis.  An MCFP run with ``R`` walks yields at most ``~R/c`` nonzeros per
vertex, so ``L ~ R/c`` loses nothing; smaller ``L`` trades memory for the
truncated tail (bounded by the dropped mass, reported by the builder).

The memory-budget planner implements the paper's core knob: "the computation
can be shifted to the offline stage as much as the memory budget allows".
"""

from __future__ import annotations

import dataclasses
import functools
import math
import warnings
import zlib
from typing import Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import AxisType, NamedSharding

from repro.core import frontier as frontier_mod
from repro.core import mcfp
from repro.core.graph import Graph, graph_fingerprint
from repro.core.walks import (DEFAULT_C, BuildLedger, compaction_schedule,
                              respawn_schedule, schedule_slot_area,
                              simulate_walks_sparse)
from repro.distributed.checkpoint import (Checkpointer, serialize_key)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PPRIndex:
    """Top-L truncated PPR fingerprints for every vertex.

    values:  f32[n, L] PPR estimates, descending within a row, 0-padded.
    indices: int32[n, L] target vertex of each value (0 at padding).
    l: static width; n: static vertex count.
    """

    values: jax.Array
    indices: jax.Array
    l: int = dataclasses.field(metadata=dict(static=True))
    n: int = dataclasses.field(metadata=dict(static=True))

    @property
    def nbytes(self) -> int:
        return self.n * self.l * 8  # f32 + int32

    def lookup_dense(self, vertices: jax.Array) -> jax.Array:
        """Densify rows: f32[len(vertices), n] (FPPR-style direct answer)."""
        vals = jnp.take(self.values, vertices, axis=0)
        idxs = jnp.take(self.indices, vertices, axis=0)
        out = jnp.zeros((vertices.shape[0], self.n), dtype=vals.dtype)
        rows = jnp.arange(vertices.shape[0])[:, None]
        return out.at[rows, idxs].add(vals)

    def replace_rows(
        self, rows: jax.Array, values: jax.Array, indices: jax.Array
    ) -> "PPRIndex":
        """Functionally replace the fingerprint rows ``rows`` — the repair
        primitive of incremental maintenance (``core/updates.py``).

        Sharded-aware: if this index lives model-sharded (the
        ``build_index_sharded`` ``P(model, None)`` layout) the result keeps
        that sharding (:func:`set_rows`), so a repaired index keeps the
        serving path's layout instead of gathering to one device.
        """
        return PPRIndex(
            values=set_rows(self.values, rows, values),
            indices=set_rows(self.indices, rows, indices),
            l=self.l, n=self.n,
        )


def set_rows(dst: jax.Array, rows, src) -> jax.Array:
    """``dst.at[rows].set(src)`` that keeps ``dst``'s mesh sharding: the
    scattered result is put back onto it.  Every sharded producer here
    (:func:`build_index_sharded`, checkpoint loads) lays arrays out on a
    mesh with Auto axes, where the scatter itself needs no sharding."""
    out = dst.at[jnp.asarray(rows, jnp.int32)].set(jnp.asarray(src, dst.dtype))
    sh = getattr(dst, "sharding", None)
    return jax.device_put(out, sh) if isinstance(sh, NamedSharding) else out


def truncate_topl(estimates: jax.Array, l: int) -> Tuple[jax.Array, jax.Array]:
    """Keep the top-``l`` entries of each dense row. Returns (vals, idxs)."""
    vals, idxs = jax.lax.top_k(estimates, l)
    vals = jnp.maximum(vals, 0.0)
    # zero-value slots point at vertex 0 but carry weight 0 -> harmless
    idxs = jnp.where(vals > 0, idxs, 0)
    return vals, idxs.astype(jnp.int32)


def normalize_sketch_to_index_rows(
    fp_v: jax.Array,
    fp_i: jax.Array,
    moves: jax.Array,
    dropped_counts: jax.Array,
    l: int,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Sketch counts -> truncated index rows: the one normalization both
    the single-device chunk (:func:`sparse_chunk_estimates`) and the
    sharded build step (``distributed_engine.make_sparse_index_build_step``)
    apply, so the two builders agree bitwise under the same keys.

    ``fp_v/fp_i [rows, sketch_l]`` is a (merged) visit-count sketch sorted
    descending, ``moves`` the MCFP denominator, ``dropped_counts`` the
    count-domain dropped-mass ledger.  Returns ``(vals, idxs, kept,
    dropped)`` in estimate units, ``vals/idxs`` sliced to width ``l``.
    Its device work carries the named scope ``ppr.topl``.
    """
    with jax.named_scope("ppr.topl"):
        inv_moves = 1.0 / jnp.maximum(moves[:, None], 1.0)
        est_v = fp_v * inv_moves                      # sorted descending
        vals, idxs = est_v[:, :l], fp_i[:, :l]
        idxs = jnp.where(vals > 0, idxs, 0)
        kept = jnp.sum(vals, axis=1)
        dropped = (
            jnp.sum(est_v[:, l:], axis=1)
            + dropped_counts * inv_moves[:, 0]
        )
        return vals, idxs, kept, dropped


@functools.partial(
    jax.jit,
    static_argnames=(
        "r", "l", "sketch_l", "c", "max_steps", "compact_every", "r_splits",
        "respawn", "touch_bits",
    ),
)
def sparse_chunk_estimates(
    graph: Graph,
    chunk_sources: jax.Array,
    key: jax.Array,
    *,
    r: int,
    l: int,
    sketch_l: int,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    compact_every: int = 8,
    r_splits: int = 1,
    respawn: bool = False,
    touch_bits: int = 0,
) -> Tuple[jax.Array, ...]:
    """One source chunk of the sparse index build, entirely on device.

    Runs the compacted sparse-sketch walk engine at width ``sketch_l``,
    normalizes to MCFP estimates, and truncates to the index width ``l``
    (the sketch is already sorted descending, so truncation is a slice).
    Returns ``(vals f32[rows, l], idxs int32[rows, l], kept f32[rows],
    dropped f32[rows])`` — the per-row kept/dropped *estimate* mass, left on
    device so the builder syncs once at the end, never per chunk.  The
    traced computation holds no ``f32[rows, n]`` array (the memory contract
    ``tests/test_walks_sparse.py`` asserts on this function's jaxpr).

    ``r_splits > 1`` runs the chunk as that many independent sub-passes of
    ``r / r_splits`` walks (keys ``fold_in(key, split)``) whose sketches are
    concatenated in split order and dedup-merged back to ``sketch_l`` — the
    exact per-chunk key/fold discipline of the sharded builder, so a
    single-device build at ``r_splits = <mesh size>`` reproduces
    :func:`build_index_sharded` row for row.  ``respawn`` selects
    respawn-mode walk scheduling (see
    :func:`repro.core.walks.respawn_schedule`).

    ``touch_bits > 0`` appends a fifth output — the per-row
    "walks-through" Bloom filter ``bool[rows, touch_bits]`` (OR-merged
    across ``r_splits`` sub-passes) that incremental maintenance
    (``core/updates.py``) uses to find the rows an edge update dirties.
    With ``touch_bits=0`` the signature and traced computation are
    unchanged (the jaxpr memory contract in ``tests/test_walks_sparse.py``
    keeps holding as-is).
    """
    if r % r_splits != 0:
        raise ValueError(f"r={r} must divide over r_splits={r_splits}")
    touch = None
    if r_splits > 1:
        vs, is_ = [], []
        moves = jnp.zeros((chunk_sources.shape[0],), jnp.float32)
        dropped = jnp.zeros_like(moves)
        for s in range(r_splits):
            counts = simulate_walks_sparse(
                graph, chunk_sources, r // r_splits,
                jax.random.fold_in(key, s), l=sketch_l, ep_l=0, c=c,
                max_steps=max_steps, compact_every=compact_every,
                respawn=respawn, touch_bits=touch_bits,
            )
            vs.append(counts.fp.values)
            is_.append(counts.fp.indices)
            moves = moves + counts.moves
            dropped = dropped + counts.fp_dropped
            if touch_bits:
                touch = counts.touch if touch is None else touch | counts.touch
        with jax.named_scope("ppr.sketch_fold"):
            fp_v, fp_i, dropped = frontier_mod.merge_sketch_parts(
                jnp.concatenate(vs, axis=1), jnp.concatenate(is_, axis=1),
                dropped, sketch_l,
            )
    else:
        counts = simulate_walks_sparse(
            graph, chunk_sources, r, key, l=sketch_l, ep_l=0, c=c,
            max_steps=max_steps, compact_every=compact_every,
            respawn=respawn, touch_bits=touch_bits,
        )
        fp_v, fp_i = counts.fp.values, counts.fp.indices
        moves, dropped = counts.moves, counts.fp_dropped
        touch = counts.touch
    out = normalize_sketch_to_index_rows(fp_v, fp_i, moves, dropped, l)
    return out + (touch,) if touch_bits else out


def build_index(
    graph: Graph,
    r: int,
    l: int,
    key: jax.Array,
    *,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    source_batch: int = 256,
    sources: Optional[np.ndarray] = None,
    engine: str = "sparse",
    compact_every: int = 8,
    r_splits: int = 1,
    respawn: bool = False,
    touch_bits: int = 0,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
    resume: bool = False,
    checkpoint_keep: int = 3,
    fault_plan=None,
) -> Tuple[PPRIndex, dict]:
    """Offline preprocessing: MCFP for every vertex, truncated to top-L.

    ``engine="sparse"`` (default) streams the compacted sparse-sketch walk
    engine straight into the fixed-width index: peak device memory is
    ``O(source_batch * sketch_l)`` per chunk plus the ``[n, L]`` index
    itself — no ``f32[rows, n]`` accumulator, no host numpy round-trip, so
    the build runs at the graph sizes the online sparse path already
    handles.  ``engine="legacy"`` keeps the dense-accumulator oracle.
    ``r_splits``/``respawn`` (sparse engine only) select the sharded
    builder's per-chunk walk decomposition and respawn-mode scheduling —
    see :func:`sparse_chunk_estimates` and :func:`build_index_sharded`.

    Duplicate ``sources`` entries are deduplicated up front (a repeated id
    would otherwise last-writer-win in the subset scatter *and*
    double-count the kept/dropped mass ledger); the count is reported as
    ``stats["duplicate_sources"]`` and the build runs over the sorted
    unique set.

    **Crash safety** (sparse engine only): with ``checkpoint_dir`` set the
    build commits, every ``checkpoint_every`` source chunks, the partial
    index rows, the conservation ledger, the touch filters, and the
    completed-chunk frontier through
    :class:`repro.distributed.checkpoint.Checkpointer` (atomic rename,
    per-shard checksums).  ``resume=True`` restores the newest *committed*
    step — mid-write ``.tmp`` dirs are ignored, checksum-corrupted steps
    fall back to the prior commit — verifies the build signature (graph
    topology, key, chunk grid), and continues from the first incomplete
    chunk.  Because per-chunk keys are positional (``fold_in(key, chunk
    offset)``), a resumed build equals an uninterrupted one **bitwise**
    (``tests/test_checkpoint_resume.py``).  ``fault_plan`` is the testing
    seam of :mod:`repro.testing.faults`.

    Returns (index, stats) where stats reports the truncated tail mass —
    the accuracy cost of the memory budget.  All host syncs are deferred to
    one ``device_get`` at the end.
    """
    n = graph.n
    l = min(l, n)  # a row holds at most n entries (both engines rely on it)
    if checkpoint_dir is not None and engine != "sparse":
        raise ValueError("checkpointing requires engine='sparse'")
    if sources is None:
        # the default full sweep is unique by construction: skip the
        # O(n log n) host sort + copies the dedup would cost at scale
        sources = np.arange(n, dtype=np.int32)
        duplicate_sources = 0
    else:
        with TraceAnnotation("ppr.build.prepare"):
            sources = np.asarray(sources, dtype=np.int32)
            unique_sources = np.unique(sources)  # sorted unique set
            duplicate_sources = len(sources) - len(unique_sources)
            sources = unique_sources
    if engine == "sparse":
        index, stats = _build_index_sparse(
            graph, r, l, key, c=c, max_steps=max_steps,
            source_batch=source_batch, sources=sources,
            compact_every=compact_every, r_splits=r_splits, respawn=respawn,
            touch_bits=touch_bits, checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, resume=resume,
            checkpoint_keep=checkpoint_keep, fault_plan=fault_plan,
        )
        stats["duplicate_sources"] = duplicate_sources
        return index, stats
    if engine != "legacy":
        raise ValueError(f"unknown engine {engine!r}")
    if r_splits != 1 or respawn or touch_bits:
        raise ValueError(
            "r_splits/respawn/touch_bits apply to the sparse engine only"
        )

    values = np.zeros((n, l), dtype=np.float32)
    indices = np.zeros((n, l), dtype=np.int32)
    # per-chunk (total, kept) stay device scalars; one sync at the end so
    # the host never blocks the dispatch pipeline mid-stream
    totals = []
    kepts = []

    @jax.jit
    def trunc(e):
        vals, idxs = truncate_topl(e, l)
        return vals, idxs, jnp.sum(e), jnp.sum(vals)

    stats: dict = {}
    for chunk_ids, est in mcfp.estimate_ppr_batched(
        graph, sources, r, key, c=c, max_steps=max_steps,
        source_batch=source_batch, stats=stats,
    ):
        real = est.shape[0]
        if real < source_batch:  # re-pad the ragged tail: trunc compiles
            est = jnp.pad(est, ((0, source_batch - real), (0, 0)))  # once
        vals, idxs, total, k = trunc(est)
        values[chunk_ids] = np.asarray(vals[:real])
        indices[chunk_ids] = np.asarray(idxs[:real])
        totals.append(total)  # pad rows are all-zero: sums unaffected
        kepts.append(k)
    if totals:
        total, kept = jax.device_get(
            (jnp.sum(jnp.stack(totals)), jnp.sum(jnp.stack(kepts)))
        )
    else:  # empty sources: a valid all-zero index
        total = kept = 0.0
    dropped = float(total) - float(kept)
    stats.update(
        r=r,
        l=l,
        engine="legacy",
        duplicate_sources=duplicate_sources,
        kept_mass=float(kept),
        dropped_mass=dropped,
        drop_fraction=dropped / max(float(total), 1e-12),
        nbytes=n * l * 8,
    )
    return (
        PPRIndex(
            values=jnp.asarray(values), indices=jnp.asarray(indices), l=l, n=n
        ),
        stats,
    )


def _make_build_checkpointer(
    checkpoint_dir: Optional[str], checkpoint_every: int,
    checkpoint_keep: int, fault_plan,
) -> Optional[Checkpointer]:
    if checkpoint_dir is None:
        return None
    if checkpoint_every < 1:
        raise ValueError(f"checkpoint_every must be >= 1, got {checkpoint_every}")
    return Checkpointer(
        checkpoint_dir, keep=checkpoint_keep,
        pre_commit=None if fault_plan is None else fault_plan.pre_commit,
    )


def _resume_build_state(
    ckpt: Checkpointer, signature: dict,
) -> Optional[Tuple[int, dict, dict]]:
    """Restore the newest committed, checksum-verified build checkpoint.

    Returns ``(next_chunk, tree, extra)`` or ``None`` (no usable step:
    start from scratch).  A committed step whose *signature* differs is an
    error — resuming a different build into this directory would splice
    incompatible RNG streams; corrupted/mid-write steps were already
    filtered by ``restore_latest``.
    """
    hit = ckpt.restore_latest()
    if hit is None:
        return None
    step, tree, extra = hit
    if extra.get("signature") != signature:
        raise ValueError(
            f"checkpoint at {ckpt.root} step {step} was written by a "
            "different build (graph/key/chunk-grid signature mismatch); "
            "refusing to resume"
        )
    return int(extra["next_chunk"]), tree, extra


def _complete_stats(extra: dict, tree: dict, touch_bits: int) -> dict:
    """Stats of a restored *complete* build checkpoint (json round-trip of
    the floats is exact in Python 3)."""
    stats = dict(extra["stats"])
    stats["resumed_complete"] = True
    if touch_bits:
        stats["touch"] = jnp.asarray(tree["touch"])
        stats["touch_bits"] = touch_bits
    return stats


@functools.partial(jax.jit, static_argnames=("n",))
def _assemble_rows(parts, rows, *, n: int) -> jax.Array:
    """The index rows of a sweep's chunks (named scope
    ``ppr.index_assemble``): concatenated in order, and for a subset build
    (``rows`` the sorted source ids) scattered into ``n`` zero rows."""
    with jax.named_scope("ppr.index_assemble"):
        out = jnp.concatenate(parts, axis=0)
        if rows is None:
            return out
        return jnp.zeros((n,) + out.shape[1:], out.dtype).at[rows].set(out)


def sketch_width(n: int, l: int) -> int:
    """Visit-count sketch width of the sparse builders: headroom over the
    index width keeps the running top-L honest (entries near rank ``l``
    compete inside the sketch before the final slice)."""
    return min(n, max(2 * l, l + 32))


def _build_index_sparse(
    graph: Graph,
    r: int,
    l: int,
    key: jax.Array,
    *,
    c: float,
    max_steps: int,
    source_batch: int,
    sources: np.ndarray,
    compact_every: int,
    r_splits: int = 1,
    respawn: bool = False,
    touch_bits: int = 0,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
    resume: bool = False,
    checkpoint_keep: int = 3,
    fault_plan=None,
) -> Tuple[PPRIndex, dict]:
    """Streaming sparse build: ``SparseWalkCounts -> PPRIndex`` on device.

    ``sources`` must be unique (``build_index`` dedups before dispatch).
    ``touch_bits > 0`` additionally returns the per-row walks-through Bloom
    filter as ``stats["touch"]`` (``bool[n, touch_bits]``, zero rows for
    unswept sources) — the invalidation sketch of ``core/updates.py``.

    With ``checkpoint_dir`` the chunk loop commits its partial state every
    ``checkpoint_every`` chunks (step number == chunks completed) and a
    final ``complete=True`` step holding the assembled index; see
    :func:`build_index` for the resume contract.
    """
    n = graph.n
    l = min(l, n)
    sketch_l = sketch_width(n, l)
    with TraceAnnotation("ppr.build.prepare"):
        sources = np.asarray(sources, dtype=np.int32)
        n_src = len(sources)
        pad_rows = (-n_src) % source_batch
        padded = np.concatenate(
            [sources, np.zeros(pad_rows, np.int32)]
        ) if pad_rows else sources
        n_chunks = len(padded) // source_batch

    ckpt = _make_build_checkpointer(
        checkpoint_dir, checkpoint_every, checkpoint_keep, fault_plan)
    signature = None
    if ckpt is not None:
        signature = dict(
            kind="build_index_sparse",
            r=int(r), l=int(l), sketch_l=int(sketch_l), c=float(c),
            max_steps=int(max_steps), compact_every=int(compact_every),
            r_splits=int(r_splits), respawn=bool(respawn),
            touch_bits=int(touch_bits), source_batch=int(source_batch),
            n=int(n), n_src=int(n_src),
            sources_crc=zlib.crc32(sources.tobytes()) & 0xFFFFFFFF,
            graph_crc=graph_fingerprint(graph),
            key=serialize_key(key),
        )

    vals_chunks: List = []
    idxs_chunks: List = []
    touch_chunks: List = []
    ledger = BuildLedger()
    start_chunk = 0
    commits = 0
    if ckpt is not None and resume:
        restored = _resume_build_state(ckpt, signature)
        if restored is not None:
            start_chunk, tree, extra = restored
            if extra.get("complete"):
                index = PPRIndex(
                    values=jnp.asarray(tree["vals"]),
                    indices=jnp.asarray(tree["idxs"]), l=l, n=n,
                )
                return index, _complete_stats(extra, tree, touch_bits)
            vals_chunks.append(tree["vals"])
            idxs_chunks.append(tree["idxs"])
            ledger = BuildLedger.restore(tree["kept"], tree["dropped"])
            if touch_bits:
                touch_chunks.append(tree["touch"])

    def commit_partial(done: int) -> None:
        nonlocal ledger, commits
        kept_arr, dropped_arr = ledger.export()
        tree = dict(
            vals=np.asarray(jnp.concatenate(vals_chunks, axis=0)),
            idxs=np.asarray(jnp.concatenate(idxs_chunks, axis=0)),
            kept=kept_arr, dropped=dropped_arr,
        )
        if touch_bits:
            tree["touch"] = np.asarray(jnp.concatenate(touch_chunks, axis=0))
        ckpt.save(done, tree, dict(
            signature=signature, complete=False,
            next_chunk=done, n_chunks=n_chunks,
        ))
        commits += 1
        # consolidate: the committed host arrays replace the per-chunk
        # device arrays (concatenation is pure layout, so the final
        # assembly stays bitwise identical) and cap the lists' growth
        vals_chunks[:] = [tree["vals"]]
        idxs_chunks[:] = [tree["idxs"]]
        if touch_bits:
            touch_chunks[:] = [tree["touch"]]
        ledger = BuildLedger.restore(kept_arr, dropped_arr)

    for ci in range(start_chunk, n_chunks):
        if fault_plan is not None:
            fault_plan.chunk_boundary(ci)
        with TraceAnnotation("ppr.build.chunk", chunk=ci):
            i = ci * source_batch
            chunk = jnp.asarray(padded[i : i + source_batch])
            real = min(source_batch, n_src - i)
            sub_key = jax.random.fold_in(key, i)
            out = sparse_chunk_estimates(
                graph, chunk, sub_key, r=r, l=l, sketch_l=sketch_l, c=c,
                max_steps=max_steps, compact_every=compact_every,
                r_splits=r_splits, respawn=respawn, touch_bits=touch_bits,
            )
            vals, idxs, kept, dropped = out[:4]
            # device-level slicing of the ragged tail: no host sync, pad
            # rows never reach the index or the stats
            vals_chunks.append(vals[:real])
            idxs_chunks.append(idxs[:real])
            ledger.append(jnp.sum(kept[:real]), jnp.sum(dropped[:real]))
            if touch_bits:
                touch_chunks.append(out[4][:real])
            done = ci + 1
            if ckpt is not None and done < n_chunks \
                    and done % checkpoint_every == 0:
                commit_partial(done)

    with TraceAnnotation("ppr.build.assemble"):
        touch = None
        if not n_src:  # empty sources: a valid all-zero index
            values = jnp.zeros((n, l), jnp.float32)
            indices = jnp.zeros((n, l), jnp.int32)
            if touch_bits:
                touch = jnp.zeros((n, touch_bits), bool)
        else:
            # a full sweep in order is the index itself; a subset build
            # scatters its rows into the zero index
            rows = None if n_src == n and np.array_equal(
                sources, np.arange(n, dtype=np.int32)
            ) else jnp.asarray(sources)
            values = _assemble_rows(vals_chunks, rows, n=n)
            indices = _assemble_rows(idxs_chunks, rows, n=n)
            if touch_bits:
                touch = _assemble_rows(touch_chunks, rows, n=n)
    with TraceAnnotation("ppr.build.finish"):
        kept, dropped = ledger.totals()
        stats = dict(
            r=r,
            l=l,
            engine="sparse",
            sketch_l=sketch_l,
            r_splits=r_splits,
            respawn=bool(respawn),
            source_batch=source_batch,
            pad_rows=pad_rows,
            pad_fraction=pad_rows / max(n_src + pad_rows, 1),
            kept_mass=kept,
            dropped_mass=dropped,
            drop_fraction=dropped / max(kept + dropped, 1e-12),
            nbytes=n * l * 8,
        )
        if ckpt is not None:
            stats["checkpoint_commits"] = commits
            stats["resumed_at_chunk"] = start_chunk
            kept_arr, dropped_arr = ledger.export()
            tree = dict(
                vals=np.asarray(values), idxs=np.asarray(indices),
                kept=kept_arr, dropped=dropped_arr,
            )
            if touch_bits:
                tree["touch"] = np.asarray(touch)
            ckpt.save(n_chunks, tree, dict(
                signature=signature, complete=True,
                next_chunk=n_chunks, n_chunks=n_chunks,
                stats={k: v for k, v in stats.items() if k != "touch"},
            ))
    if touch_bits:
        stats["touch"] = touch
        stats["touch_bits"] = touch_bits
    return PPRIndex(values=values, indices=indices, l=l, n=n), stats


@functools.lru_cache(maxsize=32)
def _cached_sharded_build_step(
    cfg, mesh, r, l, sketch_l, real_n, max_steps, compact_every,
    source_batch, respawn, touch_bits=0, chunk_start=0, chunk_count=None,
):
    """Jitted sharded-build step, memoized on its static config so repeated
    :func:`build_index_sharded` calls (benchmark sweeps, rebuild loops)
    reuse one compilation instead of re-tracing the whole sweep.
    ``chunk_start``/``chunk_count`` select a per-shard chunk segment (the
    checkpointed build); the defaults sweep the whole grid."""
    from repro.core.distributed_engine import make_sparse_index_build_step

    return jax.jit(make_sparse_index_build_step(
        cfg, mesh, r=r, l=l, sketch_l=sketch_l, real_n=real_n,
        max_steps=max_steps, compact_every=compact_every,
        source_batch=source_batch, respawn=respawn, touch_bits=touch_bits,
        chunk_start=chunk_start, chunk_count=chunk_count,
    ))


def build_index_sharded(
    graph: Graph,
    r: int,
    l: int,
    key: jax.Array,
    *,
    mesh,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    source_batch: int = 256,
    compact_every: int = 8,
    respawn: bool = True,
    model_axis: str = "model",
    batch_axes: Tuple[str, ...] = ("data",),
    touch_bits: int = 0,
    checkpoint_dir: Optional[str] = None,
    checkpoint_every: int = 8,
    resume: bool = False,
    checkpoint_keep: int = 3,
    fault_plan=None,
) -> Tuple[PPRIndex, dict]:
    """Pod-scale offline preprocessing: the full-index build under a mesh.

    The single-device :func:`build_index` drives every source chunk from
    the host on one device; here the whole sweep is one device-side
    computation (``distributed_engine.make_sparse_index_build_step``):

    * **sources shard over the model axis** — each shard sweeps the source
      chunks of its own vertex interval with a ``lax.scan``, so the
      resulting ``PPRIndex`` ``values/indices [n, L]`` come back sharded
      ``P(model, None)`` and no device ever holds (or builds) the full
      index;
    * **walks split over the batch axes** — each data replica runs
      ``r / n_data`` walks per row with a per-replica key and the sketches
      dedup-merge through one ``all_gather`` (the
      ``make_sparse_walk_counts_step`` merge);
    * **respawn-mode scheduling** (default on) keeps walk-slot occupancy
      ~100% through the sweep instead of re-entering the ``(1-c)^t``
      schedule tail for every chunk
      (:func:`repro.core.walks.respawn_schedule`).

    Key discipline: chunk at global source offset ``o`` uses
    ``fold_in(key, o)`` and data-replica ``s`` folds ``s`` on top — exactly
    :func:`build_index` with ``engine="sparse", r_splits=n_data`` over the
    same chunk grid, so the sharded and single-device builds agree row for
    row (the ``tests/dist_engine_check.py`` parity gate).

    The vertex count pads up to ``ep * ceil_to(source_batch)`` so shard
    intervals align with the chunk grid; pad vertices are dangling, their
    rows are zeroed device-side, and the returned index has ``n = n_pad``
    (consumers only ever gather real rows; ``BatchQueryEngine`` accepts
    ``index.n >= graph.n``).  Stats mirror :func:`build_index` plus
    ``n``/``n_pad``/``shards``/``r_splits``.

    **Crash safety.**  With ``checkpoint_dir`` the one-scan sweep is
    segmented at per-shard chunk granularity: every ``checkpoint_every``
    chunks the completed shard blocks (index rows, kept/dropped ledgers,
    touch Bloom filters) commit atomically through
    :class:`repro.distributed.checkpoint.Checkpointer`, and a final
    ``complete=True`` step stores the assembled ``[n_pad, l]`` index.
    Because chunk keys are positional (``fold_in(key, offset)``), the
    segmented sweep — and any ``resume=True`` restart from the newest
    committed, checksum-verified step — reproduces the uninterrupted build
    bit for bit.  Restarting onto a different graph/key/mesh/chunk-grid is
    refused (signature mismatch).  ``fault_plan`` fires its
    ``chunk_boundary`` hook at each segment's first chunk index.
    """
    from repro.core.distributed_engine import DistConfig

    # the index is served and repaired by plain jnp code, so its layout
    # must not enter the array types (jax.make_mesh defaults to Explicit)
    mesh = mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))
    ep = int(mesh.shape[model_axis])
    n_split = 1
    for ax in batch_axes:
        n_split *= int(mesh.shape[ax])
    if r % n_split != 0:
        raise ValueError(
            f"r={r} must divide evenly over the {n_split} walk shards"
        )
    n = graph.n
    l = min(l, n)
    sketch_l = sketch_width(n, l)  # same headroom as single-device
    ns = -(-n // ep)
    if source_batch > ns:
        # clamping changes the chunk grid — and with it the per-chunk keys.
        # Row-for-row parity with the single-device build then requires
        # passing the *effective* batch (stats["source_batch"]) to
        # build_index, not the requested one.  (Rounding the shard interval
        # up to the requested batch instead would sweep r walks for every
        # phantom pad row — worse than the narrower grid.)
        warnings.warn(
            f"source_batch={source_batch} exceeds the per-shard interval; "
            f"clamped to {ns} — single-device parity comparisons must use "
            "the effective batch from stats['source_batch']",
            stacklevel=2,
        )
    source_batch = max(1, min(source_batch, ns))
    ns = -(-ns // source_batch) * source_batch
    n_pad = ns * ep
    cfg = DistConfig(
        n=n_pad, ep=ep, c=c, model_axis=model_axis,
        batch_axes=tuple(batch_axes),
    )
    # pad the graph arrays host-side: pad vertices are dangling, so their
    # (discarded) rows walk in place and never touch real rows' streams
    rp = np.asarray(graph.row_ptr, np.int32)
    od = np.asarray(graph.out_deg, np.int32)
    if n_pad > n:
        rp = np.concatenate([rp, np.full(n_pad - n, rp[-1], np.int32)])
        od = np.concatenate([od, np.zeros(n_pad - n, np.int32)])
    rp_j = jnp.asarray(rp)
    col_j = jnp.asarray(np.asarray(graph.col_idx, np.int32))
    od_j = jnp.asarray(od)
    n_chunks = ns // source_batch
    ckpt = _make_build_checkpointer(
        checkpoint_dir, checkpoint_every, checkpoint_keep, fault_plan)
    extra_stats: dict = {}
    if ckpt is None:
        step = _cached_sharded_build_step(
            cfg, mesh, r, l, sketch_l, n, max_steps, compact_every,
            source_batch, respawn, touch_bits,
        )
        with mesh:
            out = step(rp_j, col_j, od_j, key)
        values, indices, kept_rows, dropped_rows = out[:4]
        touch = out[4] if touch_bits else None
        kept, dropped = jax.device_get(
            (jnp.sum(kept_rows), jnp.sum(dropped_rows))
        )
        kept, dropped = float(kept), float(dropped)
    else:
        signature = dict(
            kind="build_index_sharded",
            r=int(r), l=int(l), sketch_l=int(sketch_l), c=float(c),
            max_steps=int(max_steps), compact_every=int(compact_every),
            source_batch=int(source_batch), respawn=bool(respawn),
            touch_bits=int(touch_bits), n=int(n), n_pad=int(n_pad),
            shards=int(ep), r_splits=int(n_split),
            model_axis=str(model_axis), batch_axes=list(batch_axes),
            mesh_shape={str(ax): int(sz) for ax, sz in mesh.shape.items()},
            graph_crc=graph_fingerprint(graph),
            key=serialize_key(key),
        )
        from jax.sharding import NamedSharding, PartitionSpec
        sh_rows = NamedSharding(mesh, PartitionSpec(model_axis, None))
        # per-shard-major blocks: [ep, done * source_batch, ...] host arrays
        seg_vals: List = []
        seg_idxs: List = []
        seg_kept: List = []
        seg_dropped: List = []
        seg_touch: List = []
        start_chunk = 0
        commits = 0
        if resume:
            restored = _resume_build_state(ckpt, signature)
            if restored is not None:
                start_chunk, tree, extra = restored
                if extra.get("complete"):
                    stats = dict(extra["stats"])
                    stats["resumed_complete"] = True
                    values = jax.device_put(np.asarray(tree["vals"]), sh_rows)
                    indices = jax.device_put(np.asarray(tree["idxs"]), sh_rows)
                    if touch_bits:
                        stats["touch"] = jax.device_put(
                            np.asarray(tree["touch"]), sh_rows)
                        stats["touch_bits"] = touch_bits
                    return (
                        PPRIndex(values=values, indices=indices,
                                 l=l, n=n_pad),
                        stats,
                    )
                seg_vals.append(np.asarray(tree["vals"]))
                seg_idxs.append(np.asarray(tree["idxs"]))
                seg_kept.append(np.asarray(tree["kept"]))
                seg_dropped.append(np.asarray(tree["dropped"]))
                if touch_bits:
                    seg_touch.append(np.asarray(tree["touch"]))
        ci = start_chunk
        while ci < n_chunks:
            if fault_plan is not None:
                fault_plan.chunk_boundary(ci)
            cnt = min(checkpoint_every, n_chunks - ci)
            seg_step = _cached_sharded_build_step(
                cfg, mesh, r, l, sketch_l, n, max_steps, compact_every,
                source_batch, respawn, touch_bits, ci, cnt,
            )
            with mesh:
                out = seg_step(rp_j, col_j, od_j, key)
            rows = cnt * source_batch
            host = jax.device_get(out)
            seg_vals.append(np.asarray(host[0]).reshape(ep, rows, l))
            seg_idxs.append(np.asarray(host[1]).reshape(ep, rows, l))
            seg_kept.append(np.asarray(host[2]).reshape(ep, rows))
            seg_dropped.append(np.asarray(host[3]).reshape(ep, rows))
            if touch_bits:
                seg_touch.append(
                    np.asarray(host[4]).reshape(ep, rows, touch_bits))
            ci += cnt
            if ci < n_chunks:
                tree = dict(
                    vals=np.concatenate(seg_vals, axis=1),
                    idxs=np.concatenate(seg_idxs, axis=1),
                    kept=np.concatenate(seg_kept, axis=1),
                    dropped=np.concatenate(seg_dropped, axis=1),
                )
                if touch_bits:
                    tree["touch"] = np.concatenate(seg_touch, axis=1)
                ckpt.save(ci, tree, dict(
                    signature=signature, complete=False,
                    next_chunk=ci, n_chunks=n_chunks,
                ))
                commits += 1
                seg_vals[:] = [tree["vals"]]
                seg_idxs[:] = [tree["idxs"]]
                seg_kept[:] = [tree["kept"]]
                seg_dropped[:] = [tree["dropped"]]
                if touch_bits:
                    seg_touch[:] = [tree["touch"]]
        # shard-major reassembly: concat segments per shard, then stack
        # shards — exactly the [n_pad, l] row order of the one-scan sweep
        vals_h = np.concatenate(seg_vals, axis=1).reshape(n_pad, l)
        idxs_h = np.concatenate(seg_idxs, axis=1).reshape(n_pad, l)
        kept_h = np.concatenate(seg_kept, axis=1).reshape(n_pad)
        dropped_h = np.concatenate(seg_dropped, axis=1).reshape(n_pad)
        values = jax.device_put(vals_h, sh_rows)
        indices = jax.device_put(idxs_h, sh_rows)
        touch = None
        if touch_bits:
            touch_h = np.concatenate(
                seg_touch, axis=1).reshape(n_pad, touch_bits)
            touch = jax.device_put(touch_h, sh_rows)
        kept = float(jnp.sum(jnp.asarray(kept_h)))
        dropped = float(jnp.sum(jnp.asarray(dropped_h)))
        extra_stats = dict(
            checkpoint_commits=commits, resumed_at_chunk=start_chunk)
    stats = dict(
        r=r,
        l=l,
        engine="sparse-sharded",
        sketch_l=sketch_l,
        r_splits=n_split,
        respawn=bool(respawn),
        n=n,
        n_pad=n_pad,
        shards=ep,
        source_batch=source_batch,
        pad_rows=n_pad - n,
        pad_fraction=(n_pad - n) / max(n_pad, 1),
        duplicate_sources=0,
        kept_mass=kept,
        dropped_mass=dropped,
        drop_fraction=dropped / max(kept + dropped, 1e-12),
        nbytes=n_pad * l * 8,
    )
    stats.update(extra_stats)
    if ckpt is not None:
        tree = dict(vals=vals_h, idxs=idxs_h,
                    kept=kept_h, dropped=dropped_h)
        if touch_bits:
            tree["touch"] = touch_h
        ckpt.save(n_chunks, tree, dict(
            signature=signature, complete=True,
            next_chunk=n_chunks, n_chunks=n_chunks,
            stats={k: v for k, v in stats.items() if k != "touch"},
        ))
    if touch_bits:
        stats["touch"] = touch
        stats["touch_bits"] = touch_bits
    return PPRIndex(values=values, indices=indices, l=l, n=n_pad), stats


def load_index_checkpoint(
    checkpoint_dir: str,
) -> Tuple[PPRIndex, dict]:
    """Load the newest *complete* committed index from a build checkpoint.

    The serving boot path: after a (possibly resumed) build finishes, its
    final ``complete=True`` step holds the assembled index rows plus the
    json-safe build stats — a server restart reloads them without
    re-simulating a single walk.  Partial (mid-build) steps and ``.tmp``
    dirs are never candidates; corrupted steps fall back to the prior
    complete step; no usable step raises ``FileNotFoundError``.
    """
    ckpt = Checkpointer(checkpoint_dir)
    hit = ckpt.restore_latest(
        predicate=lambda extra: bool(extra.get("complete")))
    if hit is None:
        raise FileNotFoundError(
            f"no complete committed index checkpoint under {checkpoint_dir}"
        )
    _, tree, extra = hit
    stats = dict(extra["stats"])
    values = jnp.asarray(tree["vals"])
    indices = jnp.asarray(tree["idxs"])
    n, l = values.shape
    if "touch" in tree:
        touch = jnp.asarray(tree["touch"])
        stats["touch"] = touch
        stats["touch_bits"] = int(touch.shape[1])
    return PPRIndex(values=values, indices=indices,
                    l=int(l), n=int(n)), stats


def index_from_dense(estimates: jax.Array, l: int) -> PPRIndex:
    """Build an index from precomputed dense vectors (tests/baselines)."""
    vals, idxs = truncate_topl(estimates, l)
    return PPRIndex(
        values=vals, indices=idxs, l=l, n=int(estimates.shape[1])
    )


# ---------------------------------------------------------------------------
# Memory-budget planning (paper Section 3: offline/online trade-off knob)
# ---------------------------------------------------------------------------

# Paper Figure 5 / Section 4.2: iterations needed for RAG > 0.99 at R.
_PAPER_T_FOR_R = ((0, 7), (10, 5), (100, 2))


@dataclasses.dataclass(frozen=True)
class IndexPlan:
    r: int              # walks per vertex offline
    l: int              # index width (top-L)
    t_online: int       # VERD iterations online
    index_bytes: int
    budget_bytes: int
    walk_state_bytes: int = 0   # per-chunk walk/event state priced in
    respawn: bool = True        # scheduling mode the plan was priced for


# Walk-state pricing per slot: a live slot holds its cursor (int32) + alive
# flag (bool); each scan round additionally materializes, per slot-step, the
# two pre-drawn uniforms (2 x f32) and the stacked (af, pos, tf) event
# columns (f32 + int32 + f32) the sketch folds consume.
_SLOT_BYTES = 5
_SLOT_STEP_BYTES = 20


def walk_state_cost(
    r: int,
    *,
    c: float = DEFAULT_C,
    max_steps: int = 64,
    compact_every: int = 8,
    source_batch: int = 256,
    respawn: bool = True,
) -> dict:
    """Schedule-derived device cost of one source chunk's walk pass.

    Prices the *actual* static schedule the engine would run — respawn mode
    (``respawn_schedule``: narrow fixed-width slots at ~100% occupancy) vs
    decay mode (``compaction_schedule``: width starts at ``r``) — via
    :func:`repro.core.walks.schedule_slot_area`, the formula
    ``test_respawn_schedule_halves_device_work`` pins.  Returns per-row
    ``slot_area`` (device slot-steps), the peak ``max_width``, the pass
    ``total_steps``, and ``walk_state_bytes`` for a ``source_batch``-row
    chunk.
    """
    if r <= 0:
        return dict(max_width=0, slot_area=0, total_steps=0,
                    walk_state_bytes=0)
    if respawn:
        widths, total_steps = respawn_schedule(
            r, c=c, max_steps=max_steps, compact_every=compact_every)
    else:
        widths = compaction_schedule(
            r, c=c, max_steps=max_steps, compact_every=compact_every)
        total_steps = max_steps
    area = schedule_slot_area(widths, total_steps, compact_every)
    w_max = max(widths)
    per_slot = _SLOT_BYTES + _SLOT_STEP_BYTES * min(compact_every,
                                                    total_steps)
    return dict(
        max_width=w_max,
        slot_area=area,
        total_steps=total_steps,
        walk_state_bytes=int(source_batch * w_max * per_slot),
    )


def plan_for_budget(
    n: int,
    budget_bytes: int,
    *,
    c: float = DEFAULT_C,
    bytes_per_entry: int = 8,
    max_steps: int = 64,
    compact_every: int = 8,
    source_batch: int = 256,
    respawn: bool = True,
) -> IndexPlan:
    """Choose (R, L, T) for a memory budget.

    An MCFP vector from ``R`` walks has ``<= R/c`` support, so ``R =
    floor(c * L)`` saturates the width; the online iteration count
    interpolates the paper's measured (R -> T) table.  ``L`` is the largest
    width whose *total* device footprint fits: index bytes ``n * L * 8``
    plus the walk-state bytes of one build chunk at the schedule the engine
    would actually run (:func:`walk_state_cost`) — respawn mode's narrow
    fixed-width slots (the default) afford a larger ``R`` at the same
    budget than decay-mode pricing, which scales with ``w_max = R``.
    """
    def state_bytes(l: int) -> int:
        return walk_state_cost(
            int(c * l), c=c, max_steps=max_steps,
            compact_every=compact_every, source_batch=source_batch,
            respawn=respawn,
        )["walk_state_bytes"]

    def fits(l: int) -> bool:
        return n * bytes_per_entry * l + state_bytes(l) <= budget_bytes

    # both cost terms are monotone in l: binary-search the largest feasible
    # width, starting from the index-only cap
    lo, hi = 0, max(int(budget_bytes // (max(n, 1) * bytes_per_entry)), 0)
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid - 1
    l = lo
    r = int(c * l)
    t = 7
    for r_ref, t_ref in _PAPER_T_FOR_R:
        if r >= r_ref:
            t = t_ref
    return IndexPlan(
        r=r, l=l, t_online=t,
        index_bytes=n * l * bytes_per_entry, budget_bytes=budget_bytes,
        walk_state_bytes=state_bytes(l), respawn=bool(respawn),
    )


def preprocessing_cost_model(
    n: int,
    r: int,
    *,
    c: float = DEFAULT_C,
    step_rate: float = 5e8,
    max_steps: int = 64,
    compact_every: int = 8,
    source_batch: int = 256,
    respawn: bool = True,
) -> dict:
    """Analytic preprocessing cost (paper Table 2 extrapolation).

    Total walk positions ~ n*R/c; ``step_rate`` is positions/sec for the
    bulk engine (fitted from measured small-graph runs by the benchmark).
    Index size is n*min(R/c, L)*8 bytes before compression.  Device-side
    cost is additionally priced at the *schedule* the engine runs
    (:func:`walk_state_cost`): ``slot_positions`` are the device slot-steps
    of the full sweep, ``slot_occupancy`` how many of those slot-steps move
    a live walk (respawn mode ~doubles it), ``walk_state_bytes`` the
    per-chunk walk/event state the memory planner charges.
    """
    positions = n * r / c
    sc = walk_state_cost(
        r, c=c, max_steps=max_steps, compact_every=compact_every,
        source_batch=source_batch, respawn=respawn,
    )
    slot_positions = n * sc["slot_area"]
    return dict(
        walk_positions=positions,
        est_seconds=positions / step_rate,
        index_bytes_uncapped=int(n * (r / c) * 8),
        respawn=bool(respawn),
        max_slot_width=sc["max_width"],
        slot_positions=slot_positions,
        slot_occupancy=positions / max(slot_positions, 1),
        walk_state_bytes=sc["walk_state_bytes"],
    )


# ---------------------------------------------------------------------------
# Contract-auditor entry point (repro.analysis): the sparse build's
# per-chunk computation holds no f32[rows, n] intermediate — peak device
# memory is O(rows * sketch_l), independent of n beyond the CSR itself.
# Mirrors tests/test_walks_sparse.py::test_build_index_sparse_memory_contract.
# ---------------------------------------------------------------------------

from repro.analysis.registry import register_entry_point as _register_ep


def _contract_spec_sparse_walk_chunk():
    from repro.graphs import synthetic

    g = synthetic.rmat(12, avg_deg=6.0, seed=5)      # n = 4096
    rows, r, l = 64, 16, 32
    sketch_l = max(2 * l, l + 32)
    chunk = jnp.arange(rows, dtype=jnp.int32)
    fn = functools.partial(
        sparse_chunk_estimates, r=r, l=l, sketch_l=sketch_l
    )
    jaxpr = jax.make_jaxpr(fn)(g, chunk, jax.random.PRNGKey(0))
    # widest fold candidate row: sketch + a full pending buffer + the last
    # event segment that tipped it over (<= compact_every * r wide)
    budget = rows * (sketch_l + max(4 * sketch_l, 512) + 8 * r + 8)
    return dict(jaxpr=jaxpr, budget=budget, floor=rows * g.n)


_register_ep("sparse-walk-chunk", "dense-state-bound",
             "src/repro/core/index.py", _contract_spec_sparse_walk_chunk)
