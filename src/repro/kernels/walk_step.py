"""Pallas TPU kernel: fused bulk walk advance with HBM-resident ``col_idx``.

One device step of the offline walk engine advances every cursor one edge:
gather the degree and CSR offset of each cursor, sample an out-edge, read its
destination, and send dangling walks back to their personalization source.
The jnp path does this with three ``jnp.take`` gathers; at billion-edge
scale the ``col_idx`` gather is the one that matters — it must not require
``col_idx`` resident in VMEM.

Same memory discipline as ``frontier_push.py`` (and its DMA machinery):

* ``col_idx`` stays in ``pl.ANY`` (HBM) as ``[rows, 1, 128]`` lane rows,
  never blocked into VMEM.  Each walk DMAs the one lane row holding its
  sampled edge and rotates that edge into its own lane of the output.
* ``row_ptr``/``out_deg`` never enter the kernel: the launcher turns the
  cursors into per-walk ``deg`` + *sampled* edge addresses via two O(W)
  gathers and :func:`repro.core.walks.sample_edge_offsets` (the same
  edge-sampling law as the jnp engine, so kernel == jnp bit-for-bit under
  one key).  The clipped flat addresses ride in as the
  ``PrefetchScalarGridSpec`` scalar-prefetch argument.
* The dangling fix is one ``jnp.where`` after the ``pallas_call``.

VMEM per grid step is O(w_tile) — independent of ``n`` and ``nnz`` (see
:func:`vmem_bytes`).  ``interpret=True`` runs the same DMA schedule through
the Pallas interpreter (the CPU test mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import walks as walks_mod
from repro.kernels.frontier_push import (DMA_DEPTH, LANES,
                                         chunked_prefetch_call, dma_pipeline,
                                         lane_rows, lane_rows_shape, round_up)

# walks per output tile: one (8, 128) int32 tile
TILE_WALKS = 8 * LANES


def step_walks(w_tile: int) -> int:
    """Walks per grid step: ``w_tile`` rounded up to whole output tiles."""
    return round_up(max(w_tile, 1), TILE_WALKS)


def vmem_bytes(w_tile: int) -> int:
    """Per-grid-step VMEM of the fused walk advance: the double-buffered
    packed output block plus the in-flight lane rows.  Independent of ``n``
    and ``nnz``."""
    return 2 * step_walks(w_tile) * 4 + DMA_DEPTH * 8 * LANES * 4


def _element_gather_kernel(addr_ref, col_hbm, out_ref, buf, sem, *, walks):
    """``out.flat[r] <- col_idx[addr[r]]``: DMA the lane row holding the
    edge and rotate it from its lane to lane ``r % 128`` of output row
    ``r // 128``."""
    base = pl.program_id(0) * walks

    def make_dmas(r):
        return (pltpu.make_async_copy(
            col_hbm.at[pl.ds(addr_ref[base + r] // LANES, 1)],
            buf.at[r % DMA_DEPTH],
            sem.at[r % DMA_DEPTH],
        ),)

    def on_row(r):
        lane = addr_ref[base + r] % LANES
        dst = r % LANES
        moved = pltpu.roll(buf[r % DMA_DEPTH, 0], (dst - lane) % LANES, 1)
        ids = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
        row = pl.ds(r // LANES, 1)
        out_ref[row, :] = jnp.where(ids == dst, moved, out_ref[row, :])

    dma_pipeline(walks, make_dmas, on_row)


@functools.partial(jax.jit, static_argnames=("w_tile", "interpret"))
def walk_step(
    cursors: jax.Array,
    sources: jax.Array,
    u: jax.Array,
    row_ptr: jax.Array,
    out_deg: jax.Array,
    col_idx: jax.Array,
    *,
    w_tile: int = TILE_WALKS,
    interpret: bool = False,
) -> jax.Array:
    """Fused degree-gather + edge-sample + dangling-fix for ``W`` walks.

    cursors/sources: int32[W]; u: f32[W] uniform edge-choice draws.  Any
    ``W``: the walk axis is padded to whole grid steps of
    :func:`step_walks` ``(w_tile)`` walks.  Requires ``col_idx`` non-empty
    (the edgeless case is the wrapper's jnp fallback).  Returns the next
    cursors, int32[W] — equal to :func:`repro.core.walks.advance_cursors`
    bit-for-bit.
    """
    (w,) = cursors.shape
    assert sources.shape == (w,) and u.shape == (w,)
    m = col_idx.shape[0]
    cur32 = cursors.astype(jnp.int32)
    deg = jnp.take(out_deg, cur32).astype(jnp.int32)
    start = jnp.take(row_ptr, cur32).astype(jnp.int32)
    # the edge-sample: same law as the jnp engine (bitwise parity); dangling
    # rows get a clipped dummy address, overwritten by the dangling fix
    addr = jnp.clip(
        start + walks_mod.sample_edge_offsets(u, deg), 0, m - 1
    )
    walks = step_walks(w_tile)
    addr = jnp.pad(addr, (0, round_up(w, walks) - w))
    col = lane_rows(col_idx)

    def call(offsets):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                 # the sampled addresses
            grid=(offsets.shape[0] // walks,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # col_idx: HBM
            out_specs=pl.BlockSpec(
                (walks // LANES, LANES), lambda i, a: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((DMA_DEPTH, 1, 1, LANES), jnp.int32),
                pltpu.SemaphoreType.DMA((DMA_DEPTH,)),
            ],
        )
        return pl.pallas_call(
            functools.partial(_element_gather_kernel, walks=walks),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(
                (offsets.shape[0] // LANES, LANES), jnp.int32),
            interpret=interpret,
        )(offsets, col)

    nxt = chunked_prefetch_call(addr, walks, call).reshape(-1)[:w]
    return jnp.where(deg == 0, sources.astype(jnp.int32), nxt)


# ---------------------------------------------------------------------------
# Contract-auditor entry point (repro.analysis): col_idx rides as an
# ANY/HBM ref and every VMEM block stays O(w_tile).
# ---------------------------------------------------------------------------

from repro.analysis.registry import register_entry_point as _register_ep


def _contract_spec_walk_step():
    import functools

    import numpy as np
    from repro.graphs import synthetic

    rng = np.random.default_rng(0)
    n, w, w_tile = 4096, 256, 128
    g = synthetic.erdos_renyi(n, 5.0, seed=13)
    cur = jnp.asarray(rng.integers(0, n, w), jnp.int32)
    src = jnp.asarray(rng.integers(0, n, w), jnp.int32)
    u = jnp.asarray(rng.random(w), jnp.float32)
    return dict(
        fn=functools.partial(walk_step, w_tile=w_tile, interpret=True),
        args=(cur, src, u, g.row_ptr, g.out_deg, g.col_idx),
        hbm_shapes=[lane_rows_shape(g.m)],
        vmem_budget=step_walks(w_tile),
    )


_register_ep("walk-step", "hbm-residency",
             "src/repro/kernels/walk_step.py", _contract_spec_walk_step)
