"""Pallas TPU kernel: chunked-ELL frontier push (one VERD iteration's SpMM).

The VERD hot loop is ``F @ A`` for a dense query-frontier ``F[Q, n]`` and the
sparse transition ``A``.  In chunked-ELL form (see
:mod:`repro.graphs.formats`) each ELL row holds up to ``K`` in-edges of one
destination vertex, so the kernel computes

    partial[q, r] = sum_k  w[r, k] * F[q, nbr[r, k]]

a gather + multiply + K-reduction; duplicate rows of hub vertices are folded
outside with a segment-sum (``ops.ell_spmm_apply``).

TPU adaptation notes (vs. the paper's PowerGraph scatter):
* PowerGraph scatters tiny ``f_map`` packets per edge over Ethernet; here one
  VMEM-resident tile of ``F`` serves an entire block of destinations — the
  "bulk transfer" insight implemented as tiling instead of message batching.
* BlockSpec keeps a ``(q_tile, n)`` slab of ``F`` in VMEM: the gather never
  leaves the chip.  VMEM budget = q_tile*n*4 + r_tile*K*8 + q_tile*r_tile*4
  bytes; the wrapper asserts it fits a 16 MiB budget.  At n beyond ~4e5 the
  vertex-sharded distributed path splits ``F`` columns over the mesh first
  (each shard pulls only its local columns), so the kernel bound binds per
  *shard*, not per graph.
* The K-reduction is laid out so the compiler sees a static inner loop
  (K is a compile-time constant, typically 16/32) that vectorizes on the VPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

VMEM_BUDGET_BYTES = 16 * 1024 * 1024


def _ell_spmm_kernel(f_ref, nbr_ref, w_ref, o_ref):
    f = f_ref[...]                      # [q_tile, n]
    nbr = nbr_ref[...]                  # [r_tile, K]
    w = w_ref[...]                      # [r_tile, K]
    q_tile = f.shape[0]
    r_tile, k = nbr.shape
    gathered = jnp.take(f, nbr.reshape(-1), axis=1)       # [q_tile, r_tile*K]
    gathered = gathered.reshape(q_tile, r_tile, k)
    o_ref[...] = jnp.sum(gathered * w[None, :, :], axis=-1).astype(o_ref.dtype)


def vmem_bytes(q_tile: int, r_tile: int, k: int, n: int) -> int:
    return q_tile * n * 4 + r_tile * k * 8 + q_tile * r_tile * 4


@functools.partial(
    jax.jit, static_argnames=("q_tile", "r_tile", "interpret")
)
def ell_spmm(
    f: jax.Array,
    nbr: jax.Array,
    w: jax.Array,
    *,
    q_tile: int = 8,
    r_tile: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Raw partials ``f32[Q, rows]``; inputs must already be tile-aligned."""
    q, n = f.shape
    rows, k = nbr.shape
    assert q % q_tile == 0 and rows % r_tile == 0, (q, rows, q_tile, r_tile)
    grid = (q // q_tile, rows // r_tile)
    return pl.pallas_call(
        _ell_spmm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((q_tile, n), lambda i, j: (i, 0)),
            pl.BlockSpec((r_tile, k), lambda i, j: (j, 0)),
            pl.BlockSpec((r_tile, k), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((q_tile, r_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((q, rows), f.dtype),
        interpret=interpret,
    )(f, nbr, w)
