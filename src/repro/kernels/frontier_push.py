"""Pallas TPU kernels: HBM-resident sparse-frontier gather-push.

The one irregular memory access of a sparse VERD push is the edge gather:
every frontier slot reads a fixed-width window of ``col_idx`` starting at
its row's CSR offset.  :func:`gather_windows` is that gather as a DMA
kernel; :func:`frontier_push` (single-device fused push) and
:func:`sharded_frontier_push` (the distributed half-iteration used by
``core/distributed_engine.py``'s sparse wire format) wrap it with the same
masking, dedup and top-k math the jnp path runs, inside one jit.  Both
support ELL hub splitting (``hub_split_degree``).

Memory layout (the PowerWalk discipline: one iteration touches only the
frontier's out-edges, never the graph):

* ``col_idx`` stays in ``pl.ANY`` (HBM) — it is never blocked into VMEM.
  The kernel reads it as ``[rows, 1, 128]`` lane rows (:func:`lane_rows`):
  a TPU DMA moves whole layout tiles, so each window is read as the two
  lane rows that cover it and rotated into place in VMEM (``pltpu.roll``).
  The distributed engine's slabs are stored in this layout once
  (``distributed_engine.build_sharded_graph``), so its per-iteration push
  never copies the graph; the single-device :func:`frontier_push` takes a
  flat ``Graph`` and builds the lane rows in its own call.
* The CSR ``row_ptr``/``out_deg`` arrays never enter the kernel: the
  launcher turns them into per-slot ``start``/``deg`` via two O(Q*K)
  gathers, and the per-window starts
  (:func:`repro.core.verd.push_window_starts`) ride in as the
  ``PrefetchScalarGridSpec`` scalar-prefetch argument, in SMEM before the
  kernel body runs.  SMEM holds 1 MiB, so a long window list is split
  over several ``pallas_call``s.
* Dedup, threshold and top-k (sorts) run in jnp after the ``pallas_call``:
  Mosaic has no sort.

VMEM per grid step is O(rows per step * 128) — independent of ``n`` and
``nnz`` (see :func:`vmem_bytes`).  ``interpret=True`` runs the same DMA
schedule through the Pallas interpreter (the CPU test mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import frontier as frontier_mod
from repro.core import verd as verd_mod

LANES = 128
# scalar-prefetched offsets per pallas_call: 256 KiB of the 1 MiB SMEM
SMEM_OFFSETS = 1 << 16
# output rows per grid step: a [4096, 128] int32 block is 2 MiB (x2 buffers)
STEP_ROWS_CAP = 4096
# DMAs in flight per grid step
DMA_DEPTH = 8


def round_up(x: int, multiple: int) -> int:
    return -(-x // multiple) * multiple


def dma_pipeline(rows: int, make_dmas, on_row):
    """Pipelined DMA drain: the one schedule every gather kernel here
    shares.

    ``make_dmas(r)`` returns the async copies of row ``r`` (each into buffer
    slot ``r % DMA_DEPTH``); ``on_row(r)`` consumes that slot once the
    copies have landed.  Up to ``DMA_DEPTH`` rows are in flight, so HBM
    latency overlaps the rows before it.
    """
    for r in range(min(DMA_DEPTH, rows)):
        for dma in make_dmas(r):
            dma.start()

    def body(r, carry):
        for dma in make_dmas(r):
            dma.wait()
        on_row(r)

        @pl.when(r + DMA_DEPTH < rows)
        def _start_next():
            for dma in make_dmas(r + DMA_DEPTH):
                dma.start()

        return carry

    jax.lax.fori_loop(0, rows, body, 0)


def lane_rows_shape(m: int) -> tuple[int, int, int]:
    """Shape of :func:`lane_rows` for an ``m``-edge ``col_idx``."""
    return (m // LANES + 2, 1, LANES)


def lane_rows_reach(col_rows: jax.Array) -> int:
    """Edge count that windows over the lane rows ``col_rows`` may be
    clipped to: every window starting below it has both of its covering
    rows (the spare row included), and entries past the real edges are
    padding that the push's degree mask drops."""
    return (col_rows.shape[0] - 1) * LANES


def lane_rows(col_idx: jax.Array) -> jax.Array:
    """``col_idx`` as ``int32[rows, 1, 128]`` lane rows plus one spare row,
    so that the two rows covering any in-range window exist.  Built as
    whole rows + a padded two-row tail: padding before the reshape costs
    the TPU compiler ~20 s at m = 2**24."""
    col = col_idx.astype(jnp.int32)
    m = col.shape[0]
    full = m // LANES * LANES
    tail = jnp.pad(col[full:], (0, 2 * LANES - (m - full)))
    return jnp.concatenate([
        col[:full].reshape(-1, 1, LANES), tail.reshape(2, 1, LANES),
    ])


def chunked_prefetch_call(offsets: jax.Array, step_rows: int, call):
    """Run ``call(offsets_chunk)`` over ``offsets`` in chunks SMEM can hold
    and concatenate the results along axis 0.  ``offsets`` must be a
    multiple of ``step_rows`` long."""
    per_call = max(step_rows, SMEM_OFFSETS // step_rows * step_rows)
    total = offsets.shape[0]
    outs = [call(offsets[i:i + per_call]) for i in range(0, total, per_call)]
    if len(outs) == 1:
        return outs[0]
    return jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)


def _window_gather_kernel(win_ref, col_hbm, out_ref, buf, sem, *, rows):
    """``out[r, :] <- col_idx[win[r] : win[r] + 128]``: DMA the two lane
    rows that cover the window, rotate both left by the window's lane
    offset, and splice."""
    base = pl.program_id(0) * rows

    def make_dmas(r):
        return (pltpu.make_async_copy(
            col_hbm.at[pl.ds(win_ref[base + r] // LANES, 2)],
            buf.at[r % DMA_DEPTH],
            sem.at[r % DMA_DEPTH],
        ),)

    def on_row(r):
        lane = win_ref[base + r] % LANES
        shift = (LANES - lane) % LANES
        lo = pltpu.roll(buf[r % DMA_DEPTH, 0], shift, 1)
        hi = pltpu.roll(buf[r % DMA_DEPTH, 1], shift, 1)
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        out_ref[pl.ds(r, 1), :] = jnp.where(ids < LANES - lane, lo, hi)

    dma_pipeline(rows, make_dmas, on_row)


def window_step_rows(step_windows: int, h: int) -> int:
    """Output rows per grid step of :func:`gather_windows`: one per
    128-wide piece of each window, capped at :data:`STEP_ROWS_CAP`."""
    pieces = step_windows * (-(-h // LANES))
    return round_up(min(max(pieces, 1), STEP_ROWS_CAP), 8)


def gather_windows(
    col_rows: jax.Array,
    starts: jax.Array,
    *,
    h: int,
    step_windows: int,
    interpret: bool = False,
) -> jax.Array:
    """``out[r, j] = col_idx[starts[r] + j]`` for ``j < h``: int32[R, h],
    read out of ``col_rows``, the :func:`lane_rows` of ``col_idx``.

    The DMA kernel behind every sparse push here.  Requires ``0 <= starts``
    and ``starts + h <= lane_rows_reach(col_rows)`` (what
    :func:`repro.core.verd.push_window_starts` guarantees when given that
    ``m``).  Windows wider than 128 are read as several 128-wide pieces;
    ``step_windows`` windows share a grid step.
    """
    (r_total,) = starts.shape
    pieces = -(-h // LANES)
    flat = (
        starts.astype(jnp.int32)[:, None]
        + LANES * jnp.arange(pieces, dtype=jnp.int32)
    ).reshape(-1)
    rows = window_step_rows(step_windows, h)
    flat = jnp.pad(flat, (0, round_up(flat.shape[0], rows) - flat.shape[0]))

    def call(offsets):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                 # the window starts
            grid=(offsets.shape[0] // rows,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # col_idx: HBM
            out_specs=pl.BlockSpec((rows, LANES), lambda i, w: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((DMA_DEPTH, 2, 1, LANES), jnp.int32),
                pltpu.SemaphoreType.DMA((DMA_DEPTH,)),
            ],
        )
        return pl.pallas_call(
            functools.partial(_window_gather_kernel, rows=rows),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (offsets.shape[0], LANES), jnp.int32),
            interpret=interpret,
        )(offsets, col_rows)

    out = chunked_prefetch_call(flat, rows, call)
    return out[: r_total * pieces].reshape(r_total, pieces * LANES)[:, :h]


def vmem_bytes(
    q_tile: int, k: int, *, degree_cap: int, hub_split_degree: int = 0,
) -> int:
    """Per-grid-step VMEM of the push's window gather: the double-buffered
    output block plus the in-flight lane rows.  Independent of ``n`` and
    ``nnz``."""
    h, s = verd_mod.resolve_hub_splits(degree_cap, hub_split_degree)
    rows = window_step_rows(q_tile * k * s, h)
    # a [2, 1, 128] int32 buffer pads to two (8, 128) tiles in VMEM
    return 2 * rows * LANES * 4 + DMA_DEPTH * 2 * 8 * LANES * 4


def vmem_bytes_legacy(
    q_tile: int, k: int, *,
    n: int, m: int, degree_cap: int, hub_split_degree: int = 0,
) -> int:
    """What a kernel holding the whole CSR (``row_ptr``/``out_deg``/
    ``col_idx``) as resident whole-array blocks would need per step — the
    O(nnz) VMEM that makes compiling at scale impossible."""
    csr = (n + 1) * 4 + n * 4 + m * 4
    return vmem_bytes(
        q_tile, k, degree_cap=degree_cap, hub_split_degree=hub_split_degree,
    ) + csr


def _window_gather(q_tile: int, k: int, s: int, interpret: bool):
    """The :func:`gather_windows` callable the jnp push math takes in place
    of its ``jnp.take`` (``verd.gather_push_edges(window_gather=...)``)."""
    def gather(col_idx, starts, h):
        return gather_windows(
            lane_rows(col_idx), starts, h=h, step_windows=q_tile * k * s,
            interpret=interpret,
        )
    return gather


@functools.partial(
    jax.jit,
    static_argnames=("c", "degree_cap", "threshold", "k_out", "q_tile",
                     "hub_split_degree", "interpret"),
)
def frontier_push(
    fv: jax.Array,
    fi: jax.Array,
    sources: jax.Array,
    row_ptr: jax.Array,
    out_deg: jax.Array,
    col_idx: jax.Array,
    *,
    c: float,
    degree_cap: int,
    k_out: int,
    threshold: float = 0.0,
    q_tile: int = 8,
    hub_split_degree: int = 0,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused sparse push: kernel edge gather, then mask, dangling mass and
    dedup + threshold + top-``k_out`` in jnp.  ``q_tile`` queries' windows
    share a grid step.  ``hub_split_degree`` bounds the per-sub-slot
    gather width (ELL hub splitting) without changing the result.
    Requires ``col_idx`` non-empty (the edgeless case is the wrapper's jnp
    fallback)."""
    q, k = fv.shape
    assert fi.shape == (q, k) and sources.shape[0] == q
    _, s = verd_mod.resolve_hub_splits(
        min(degree_cap, max(col_idx.shape[0], 1)), hub_split_degree)
    cand_v, cand_i = verd_mod.gather_push_candidates(
        fv, fi.astype(jnp.int32), sources, row_ptr, out_deg, col_idx,
        c=c, degree_cap=degree_cap, hub_split_degree=hub_split_degree,
        window_gather=_window_gather(q_tile, k, s, interpret),
    )
    return frontier_mod.compact_arrays(
        cand_v, cand_i, k_out, threshold=threshold
    )


@functools.partial(
    jax.jit,
    static_argnames=("c", "degree_cap", "hub_split_degree", "ep", "n_shard",
                     "wire_k", "q_tile", "interpret"),
)
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
    """One shard's half-iteration of the distributed sparse exchange.

    ``fv/fi f32|int32[Q, K]``: the shard's local frontier slice (indices are
    local row ids).  ``row_ptr int32[n_shard + 1]`` / ``col_rows``: the
    shard's CSR slab, destination ids global, its ``col_idx`` stored as
    :func:`lane_rows` (``int32[rows, 1, 128]``, the layout
    ``build_sharded_graph`` keeps), so no call copies the slab.  The kernel
    gathers the edge windows out of the HBM-resident lane rows; the
    per-owner top-``wire_k`` bucketing runs in jnp after it.  Emits the exchange
    buckets ``(vals f32[Q, ep, wire_k], idx int32[Q, ep, wire_k])`` with
    owner-local indices — exactly what ``all_to_all`` puts on the wire.
    Dangling mass is the caller's business (it needs a cross-shard psum).
    """
    q, k = fv.shape
    assert fi.shape == (q, k) and col_rows.shape[1:] == (1, LANES)
    m = lane_rows_reach(col_rows)
    degree_cap = min(degree_cap, m)
    h, s = verd_mod.resolve_hub_splits(degree_cap, hub_split_degree)
    fi32 = fi.astype(jnp.int32)
    local_deg = row_ptr[1:] - row_ptr[:-1]
    start = jnp.take(row_ptr, fi32).astype(jnp.int32)
    deg = jnp.take(local_deg, fi32).astype(jnp.int32)
    # verd.gather_push_edges' three steps, the gather reading the stored
    # lane rows
    windows = verd_mod.push_window_starts(
        start, degree_cap=degree_cap, hub_split_degree=hub_split_degree, m=m,
    )
    gathered = gather_windows(
        col_rows, windows.reshape(-1), h=h, step_windows=q_tile * k * s,
        interpret=interpret,
    ).reshape(windows.shape + (h,))
    push_v, nbrs = verd_mod.masked_push_from_windows(
        fv, deg, start, windows, gathered,
        c=c, degree_cap=degree_cap, hub_split_degree=hub_split_degree,
    )
    return frontier_mod.bucket_by_owner(push_v, nbrs, ep, n_shard, wire_k)


# ---------------------------------------------------------------------------
# Contract-auditor entry points (repro.analysis): register both push kernels
# under the hbm-residency rule.  The builders are lazy — they construct tiny
# synthetic fixtures only when `python -m repro.analysis` runs the rule —
# and mirror tests/test_kernels.py's memory-contract parameters.
# ---------------------------------------------------------------------------

from repro.analysis.registry import register_entry_point as _register_ep


def _contract_spec_frontier_push():
    import numpy as np
    from repro.core import verd as verd_mod
    from repro.graphs import synthetic

    rng = np.random.default_rng(0)
    n, q, k, q_tile, k_out = 2048, 16, 8, 8, 16
    g = synthetic.erdos_renyi(n, 6.0, seed=7)
    cap = verd_mod.resolve_degree_cap(g)
    srcs = jnp.asarray(rng.integers(0, n, q), jnp.int32)
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, n, (q, k)), jnp.int32)
    h, s = verd_mod.resolve_hub_splits(cap, 0)
    return dict(
        fn=functools.partial(
            frontier_push, c=0.15, degree_cap=cap, k_out=k_out,
            q_tile=q_tile, interpret=True,
        ),
        args=(fv, fi, srcs, g.row_ptr, g.out_deg, g.col_idx),
        hbm_shapes=[lane_rows_shape(g.m)],
        vmem_budget=window_step_rows(q_tile * k * s, h) * LANES,
    )


def _contract_spec_sharded_push():
    import numpy as np
    from repro.core import verd as verd_mod
    from repro.core.distributed_engine import DistConfig, build_sharded_graph
    from repro.graphs import synthetic

    rng = np.random.default_rng(0)
    n, q, k, q_tile, wire_k = 2048, 16, 8, 4, 8
    g = synthetic.erdos_renyi(n, 6.0, seed=7)
    cap = verd_mod.resolve_degree_cap(g)
    cfg = DistConfig(n=n, ep=2, degree_cap=cap)
    slabs = build_sharded_graph(g, cfg)
    ns = cfg.n_shard
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.clip(jnp.asarray(rng.integers(0, n, (q, k)), jnp.int32), 0, ns - 1)
    h, s = verd_mod.resolve_hub_splits(cap, 0)
    return dict(
        fn=functools.partial(
            sharded_frontier_push, c=0.15, degree_cap=cap, ep=2, n_shard=ns,
            wire_k=wire_k, q_tile=q_tile, interpret=True,
        ),
        args=(fv, fi, slabs.row_ptr[0], slabs.col_idx[0]),
        hbm_shapes=[slabs.col_idx.shape[1:]],
        vmem_budget=window_step_rows(q_tile * k * s, h) * LANES,
    )


_register_ep("frontier-push", "hbm-residency",
             "src/repro/kernels/frontier_push.py", _contract_spec_frontier_push)
_register_ep("sharded-frontier-push", "hbm-residency",
             "src/repro/kernels/frontier_push.py", _contract_spec_sharded_push)
