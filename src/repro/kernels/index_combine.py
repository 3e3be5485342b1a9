"""Pallas TPU kernel: fused VERD index combine (Algorithm 4 line 10).

    out[q, :] = s[q, :] + sum_v f[q, v] * scatter(vals[v, :] at idx[v, :])

The vertex dimension is the reduction axis: the grid is ``(q_blocks,
v_blocks)`` with ``v`` innermost, and the output block (a full ``[q_tile, n]``
slab) is revisited across ``v`` steps — initialized from ``s`` at ``v == 0``
and accumulated in place afterwards (the standard Pallas reduction pattern).

Per grid step the kernel expands the ``[v_tile, L]`` index block against the
``[q_tile, v_tile]`` frontier block and scatter-adds ``q_tile`` rows at
``v_tile * L`` dynamic columns.  VMEM: q_tile*n*4 (out) + q_tile*n*4 (s,
v==0 only) + q_tile*v_tile*4 + v_tile*L*8 bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import frontier as frontier_mod
from repro.core import verd as verd_mod
from repro.kernels.frontier_push import (DMA_DEPTH, LANES,
                                         chunked_prefetch_call, dma_pipeline,
                                         round_up)


def _index_combine_kernel(s_ref, f_ref, vals_ref, idx_ref, o_ref):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        o_ref[...] = s_ref[...]

    f = f_ref[...]                        # [q_tile, v_tile]
    vals = vals_ref[...]                  # [v_tile, L]
    idx = idx_ref[...]                    # [v_tile, L]
    q_tile = f.shape[0]
    contrib = f[:, :, None] * vals[None, :, :]        # [q_tile, v_tile, L]
    acc = o_ref[...]
    acc = acc.at[:, idx.reshape(-1)].add(
        contrib.reshape(q_tile, -1).astype(acc.dtype)
    )
    o_ref[...] = acc


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
    """Fused combine; inputs must be tile-aligned (see ops.index_combine).

    ``f``'s column axis (vertices, length nv) and ``s``'s column axis (output
    vertex ids, length n) are distinct: nv may be padded past n.
    """
    q, nv = f.shape
    n = s.shape[1]
    l = vals.shape[1]
    assert s.shape[0] == q and idx.shape == (nv, l) and vals.shape == (nv, l)
    assert q % q_tile == 0 and nv % v_tile == 0, (q, nv, q_tile, v_tile)
    grid = (q // q_tile, nv // v_tile)
    return pl.pallas_call(
        _index_combine_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_tile, n), lambda i, j: (i, 0)),
            pl.BlockSpec((q_tile, v_tile), lambda i, j: (i, j)),
            pl.BlockSpec((v_tile, l), lambda i, j: (j, 0)),
            pl.BlockSpec((v_tile, l), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((q_tile, n), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((q, n), s.dtype),
        interpret=interpret,
    )(s, f, vals, idx)


# ---------------------------------------------------------------------------
# Sparse-frontier variant: contracts f[Q, K] against only the K touched index
# rows — DMA-gathered from the HBM-resident index, no [q_tile, n] slab and no
# whole-array index blocks anywhere.
# ---------------------------------------------------------------------------

# a TPU DMA moves whole (8, 128) tiles of a 2-D array
SUBLANES = 8
# output block budget per grid step (both arrays, one buffer)
STEP_BYTES_CAP = 2 << 20


def padded_index_shape(n: int, l: int) -> tuple[int, int]:
    """The tile-aligned ``[n, L]`` shape the row-gather kernel reads."""
    return round_up(n, SUBLANES), round_up(l, LANES)


def row_step_rows(step_rows: int, l: int) -> int:
    """Gathered rows per grid step: ``step_rows`` capped so both output
    blocks stay within :data:`STEP_BYTES_CAP`."""
    cap = max(SUBLANES, STEP_BYTES_CAP // (round_up(l, LANES) * 8))
    return round_up(min(max(step_rows, 1), cap), SUBLANES)


def _row_gather_kernel(
    row_ref, vals_hbm, idx_hbm, ov_ref, oi_ref, vbuf, ibuf, sem, *, rows,
):
    """``out[r] <- index[row[r]]``: DMA the 8-row tile holding the row out
    of each HBM array, then copy the row into the output block."""
    base = pl.program_id(0) * rows

    def make_dmas(r):
        tile = pl.multiple_of(row_ref[base + r] // SUBLANES * SUBLANES,
                              SUBLANES)
        slot = r % DMA_DEPTH
        return (
            pltpu.make_async_copy(
                vals_hbm.at[pl.ds(tile, SUBLANES)], vbuf.at[slot],
                sem.at[0, slot]),
            pltpu.make_async_copy(
                idx_hbm.at[pl.ds(tile, SUBLANES)], ibuf.at[slot],
                sem.at[1, slot]),
        )

    def on_row(r):
        sub = pl.ds(row_ref[base + r] % SUBLANES, 1)
        ov_ref[pl.ds(r, 1), :] = vbuf[r % DMA_DEPTH, sub, :]
        oi_ref[pl.ds(r, 1), :] = ibuf[r % DMA_DEPTH, sub, :]

    dma_pipeline(rows, make_dmas, on_row)


def gather_index_rows(
    vals: jax.Array,
    idx: jax.Array,
    rows: jax.Array,
    *,
    step_rows: int,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """``(vals[rows], idx[rows])`` via the DMA kernel: f32|int32[R, L].

    The index is padded to :func:`padded_index_shape` first: Mosaic DMAs
    only whole tiles, and XLA stores an ``[n, 667]`` array in a layout the
    kernel cannot take, so this copy happens in either case.  It is a copy
    of the whole index per call (a no-op only for an index whose shape is
    already aligned), which is why no served or build path calls this
    kernel yet.
    """
    n, l = vals.shape
    (r_total,) = rows.shape
    n_p, l_p = padded_index_shape(n, l)
    vals_p = jnp.pad(vals, ((0, n_p - n), (0, l_p - l)))
    idx_p = jnp.pad(idx.astype(jnp.int32), ((0, n_p - n), (0, l_p - l)))
    step = row_step_rows(step_rows, l)
    flat = jnp.clip(rows.astype(jnp.int32), 0, n - 1)
    flat = jnp.pad(flat, (0, round_up(r_total, step) - r_total))

    def call(offsets):
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,                 # the touched row ids
            grid=(offsets.shape[0] // step,),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),   # index values: HBM
                pl.BlockSpec(memory_space=pl.ANY),   # index columns: HBM
            ],
            out_specs=[
                pl.BlockSpec((step, l_p), lambda i, r: (i, 0)),
                pl.BlockSpec((step, l_p), lambda i, r: (i, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((DMA_DEPTH, SUBLANES, l_p), vals.dtype),
                pltpu.VMEM((DMA_DEPTH, SUBLANES, l_p), jnp.int32),
                pltpu.SemaphoreType.DMA((2, DMA_DEPTH)),
            ],
        )
        return pl.pallas_call(
            functools.partial(_row_gather_kernel, rows=step),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((offsets.shape[0], l_p), vals.dtype),
                jax.ShapeDtypeStruct((offsets.shape[0], l_p), jnp.int32),
            ],
            interpret=interpret,
        )(offsets, vals_p, idx_p)

    ov, oi = chunked_prefetch_call(flat, step, call)
    return ov[:r_total, :l], oi[:r_total, :l]


@functools.partial(
    jax.jit, static_argnames=("k_out", "q_tile", "interpret")
)
def index_combine_sparse(
    sv: jax.Array,
    si: jax.Array,
    fv: jax.Array,
    fi: jax.Array,
    vals: jax.Array,
    idx: jax.Array,
    *,
    k_out: int,
    q_tile: int = 8,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused sparse combine + top-k: the kernel gathers the ``K`` touched
    index rows of each query out of the HBM-resident ``[n, L]`` arrays
    (``q_tile`` queries' rows per grid step, capped by VMEM), then the
    combine math and dedup + top-``k_out`` run in jnp."""
    q, k = fv.shape
    n, l = vals.shape
    assert si.shape == sv.shape and fi.shape == (q, k)
    assert idx.shape == (n, l)
    iv, ii = gather_index_rows(
        vals, idx, fi.reshape(-1), step_rows=q_tile * k, interpret=interpret,
    )
    # same array-level math as the jnp core op — single source of truth
    cand_v, cand_i = verd_mod.combine_candidates_from_rows(
        sv, si, fv, iv.reshape(q, k, l), ii.reshape(q, k, l)
    )
    return frontier_mod.compact_arrays(cand_v, cand_i, k_out)


def sparse_vmem_bytes(q_tile: int, k: int, l: int) -> int:
    """Per-grid-step VMEM of the HBM-resident sparse combine: the
    double-buffered output blocks plus the in-flight index tiles."""
    l_p = round_up(l, LANES)
    rows = row_step_rows(q_tile * k, l)
    return 2 * rows * l_p * 8 + DMA_DEPTH * SUBLANES * l_p * 8


def sparse_vmem_bytes_legacy(q_tile: int, k: int, l: int, *, n: int) -> int:
    """What a kernel holding both whole ``[n, L]`` index arrays as resident
    blocks would need per step."""
    return sparse_vmem_bytes(q_tile, k, l) + 2 * n * l * 4


# ---------------------------------------------------------------------------
# Contract-auditor entry point (repro.analysis): the sparse combine's two
# [n, L] index arrays must ride as HBM refs, never as VMEM blocks.
# ---------------------------------------------------------------------------

from repro.analysis.registry import register_entry_point as _register_ep


def _contract_spec_index_combine():
    import functools

    import numpy as np

    rng = np.random.default_rng(0)
    n, l, q, k, s_w = 600, 16, 16, 8, 8
    q_tile, k_out = 8, 16
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    sv = jnp.asarray(rng.random((q, s_w)), jnp.float32)
    si = jnp.asarray(rng.integers(0, n, (q, s_w)), jnp.int32)
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(rng.integers(0, n, (q, k)), jnp.int32)
    return dict(
        fn=functools.partial(
            index_combine_sparse, k_out=k_out, q_tile=q_tile, interpret=True,
        ),
        args=(sv, si, fv, fi, vals, idx),
        hbm_shapes=[padded_index_shape(n, l)],
        vmem_budget=row_step_rows(q_tile * k, l) * round_up(l, LANES),
    )


_register_ep("index-combine-sparse", "hbm-residency",
             "src/repro/kernels/index_combine.py", _contract_spec_index_combine)
