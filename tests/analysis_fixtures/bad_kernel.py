"""hbm-residency violation: a Pallas kernel that stages the whole CSR
``col_idx`` array into VMEM (default BlockSpec, no ``pl.ANY``) — the
exact layout the DMA-gather rebuild removed."""

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _copy_kernel(col_ref, out_ref):
    out_ref[...] = col_ref[...]


def vmem_resident_gather(col_idx: jax.Array) -> jax.Array:
    """Pulls the full edge array through VMEM: both the operand and the
    result block are whole-array VMEM blocks of shape ``(m,)``."""
    return pl.pallas_call(
        _copy_kernel,
        out_shape=jax.ShapeDtypeStruct(col_idx.shape, col_idx.dtype),
        interpret=True,
    )(col_idx)


def make_args(m: int = 4096):
    return (jnp.arange(m, dtype=jnp.int32),)
