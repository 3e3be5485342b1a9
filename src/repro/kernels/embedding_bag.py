"""Pallas TPU kernel: embedding-bag (multi-hot gather + in-bag reduce).

JAX has no native ``nn.EmbeddingBag``; the framework's production path is
``jnp.take`` + mask + sum (see :mod:`repro.models.recsys.embedding`), and
this kernel is the fused VMEM-tiled version for the *sharded* case: after
row-sharding a 10^6..10^9-row table over the ``model`` axis each shard holds
a few thousand rows — small enough to pin in VMEM — and looks up only
locally-resident ids (non-local slots arrive masked-out; partial bags are
summed with a psum by the caller).

    out[b, :] = sum_i mask[b, i] * table[ids[b, i], :]

Grid: ``(bag_blocks, d_blocks)``; the table is blocked over the embedding
dim only (``(V_local, d_tile)``), so VMEM = V_local*d_tile*4 +
b_tile*bag*8 + b_tile*d_tile*4 bytes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _embedding_bag_kernel(ids_ref, mask_ref, table_ref, o_ref):
    ids = ids_ref[...]                    # [b_tile, bag]
    mask = mask_ref[...]                  # [b_tile, bag]
    table = table_ref[...]                # [v_local, d_tile]
    b_tile, bag = ids.shape
    rows = jnp.take(table, ids.reshape(-1), axis=0)      # [b_tile*bag, d_tile]
    rows = rows.reshape(b_tile, bag, -1) * mask[:, :, None]
    o_ref[...] = rows.sum(axis=1).astype(o_ref.dtype)


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
    """Fused bag-sum ``f32[B, D]``; inputs must be tile-aligned."""
    b, bag = ids.shape
    v, d = table.shape
    assert mask.shape == (b, bag)
    assert b % b_tile == 0 and d % d_tile == 0, (b, d, b_tile, d_tile)
    grid = (b // b_tile, d // d_tile)
    return pl.pallas_call(
        _embedding_bag_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((b_tile, bag), lambda i, j: (i, 0)),
            pl.BlockSpec((b_tile, bag), lambda i, j: (i, 0)),
            pl.BlockSpec((v, d_tile), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((b_tile, d_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((b, d), table.dtype),
        interpret=interpret,
    )(ids, mask, table)
