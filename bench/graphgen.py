"""Graph500 R-MAT graphs, generated on the device.

The generator follows the Graph500 specification's Kronecker generator:
``edge_factor * 2**scale`` edges, each placed by ``scale`` independent
quadrant choices with initiator probabilities A, B, C and D = 1 - A - B - C,
then every vertex label replaced through one random permutation.  The
benchmark drops self-loops and merges duplicate edges, keeping the graph
directed.

The structure (the edges before labelling) comes from one key and the
labels from another: runs that share the structure key and differ in the
label key serve the same graph, with the same work and the same number of
edges, under labels in another order, so every compiled program serves
every seed.  All of it runs in one jitted call; the program's
``Graph.from_edges`` then runs on the host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int, purpose: int) -> jax.Array:
    """A PRNG key for one purpose of one run.  ``seed`` may exceed 32 bits:
    its two 32-bit halves are folded in apart."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, purpose)


@functools.partial(jax.jit, static_argnames=("scale", "edge_factor"))
def rmat_edges(key, label_key, *, scale: int, edge_factor: int,
               a: float, b: float, c: float):
    """``(src, dst, simple, perm)``: int32 ``[edge_factor << scale]``
    endpoints whose first ``simple`` are the distinct non-loop edges, under
    their labels and sorted by ``(src, dst)``, the rest (self-loops and
    repeats) after them; and the labelling, ``perm[v]`` the label of
    structure vertex ``v``."""
    m = edge_factor << scale
    n = 1 << scale

    def level(i, carry):
        src, dst = carry
        u = jax.random.uniform(jax.random.fold_in(key, i), (m,))
        # quadrants in the order A (0,0), B (0,1), C (1,0), D (1,1)
        row = u >= a + b
        col = ((u >= a) & (u < a + b)) | (u >= a + b + c)
        return (src * 2 + row.astype(jnp.int32),
                dst * 2 + col.astype(jnp.int32))

    zeros = jnp.zeros((m,), jnp.int32)
    src, dst = jax.lax.fori_loop(0, scale, level, (zeros, zeros))
    src, dst = jax.lax.sort((src, dst), num_keys=2)
    first = jnp.concatenate([
        jnp.ones((1,), bool), (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
    simple = first & (src != dst)
    perm = jax.random.permutation(label_key, n).astype(jnp.int32)
    # label n sorts every edge that is not simple after the simple ones
    src, dst = jax.lax.sort((jnp.where(simple, perm[src], n),
                             jnp.where(simple, perm[dst], n)), num_keys=2)
    return src, dst, jnp.sum(simple), perm


def graph500_edges(key, label_key, *, scale: int, edge_factor: int,
                   a: float, b: float, c: float):
    """Host ``(src, dst, perm)``: the distinct non-loop edges as int32
    arrays sorted by source then destination, and the labelling."""
    src, dst, simple, perm = jax.device_get(rmat_edges(
        key, label_key, scale=scale, edge_factor=edge_factor, a=a, b=b, c=c))
    return src[:simple], dst[:simple], perm
