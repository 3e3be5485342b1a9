"""The plain reference: exact personalized PageRank, and the comparison of
served answers or index rows with it.

Personalized PageRank of a source ``s`` with teleport probability ``c`` is
``x_s = c * sum_t (1 - c)**t * e_s P**t``, where ``P`` is the row-stochastic
out-edge matrix whose dangling rows (no out-edge) jump back to ``s``.  The
reference runs ``iterations`` steps of ``x <- c e_s + (1 - c) x P`` from
``x = c e_s`` in float32, pull form, for a block of sources at a time: one
gather of ``x / out_degree`` over the edges sorted by destination, a running
sum over them, and the difference of that sum at consecutive destination
boundaries.  (XLA's scatter-add, the plainer segment sum, takes seconds an
iteration on the chip at scale 20.)  The running sum goes over the edges in
slices of ``edge_chunk``, so that a block fits beside nothing else on the
device.  It uses nothing of the program: only the benchmark's own edge list.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


class Reference:
    """Exact PPR over a fixed edge list (host int arrays ``src``, ``dst``)."""

    def __init__(self, src, dst, n: int, *, c: float, iterations: int,
                 block: int, edge_chunk: int = 1 << 22):
        src = np.asarray(src, np.int32)
        dst = np.asarray(dst, np.int32)
        order = np.argsort(dst, kind="stable")
        self.src_by_dst = jnp.asarray(src[order])
        # bounds[v] = first edge (in destination order) into v; bounds[n] = m
        self.bounds = jnp.asarray(np.searchsorted(
            dst[order], np.arange(n + 1), side="left").astype(np.int32))
        deg = np.bincount(src, minlength=n).astype(np.float32)
        self.inv_deg = jnp.asarray(
            np.where(deg > 0, 1.0 / np.maximum(deg, 1.0), 0.0), jnp.float32)
        self.dangling = jnp.asarray(deg == 0)
        self.n, self.c, self.iterations, self.block = n, c, iterations, block
        self.edge_chunk = edge_chunk

    def ppr(self, sources) -> jax.Array:
        """``f32[len(sources), n]`` exact PPR rows (one block)."""
        with jax.default_matmul_precision("highest"):
            return _ppr_block(
                self.src_by_dst, self.bounds, self.inv_deg, self.dangling,
                jnp.asarray(sources, jnp.int32), c=self.c,
                iterations=self.iterations, edge_chunk=self.edge_chunk)

    def compare(self, sources, vertices, scores) -> np.ndarray:
        """Per row of ``(vertices, scores)`` ``[Q, W]``, the squared L2
        distance of the row, scattered into a dense vector (entries of
        score <= 0 absent), from the exact PPR of its source in ``sources``
        ``[Q]``, over the exact vector's squared norm."""
        sources = np.asarray(sources, np.int32)
        vertices = np.asarray(vertices, np.int32)
        scores = np.asarray(scores, np.float32)
        out = []
        for lo in range(0, len(sources), self.block):
            hi = min(lo + self.block, len(sources))
            rows = np.zeros(self.block, np.int32)
            rows[: hi - lo] = sources[lo:hi]
            v = np.zeros((self.block, vertices.shape[1]), np.int32)
            s = np.zeros((self.block, scores.shape[1]), np.float32)
            v[: hi - lo], s[: hi - lo] = vertices[lo:hi], scores[lo:hi]
            err = _sq_err(self.ppr(rows), jnp.asarray(v), jnp.asarray(s))
            out.append(np.asarray(err)[: hi - lo])
        return np.concatenate(out)


def _pull(x, src_by_dst, bounds, edge_chunk):
    """``[n, b]``: for each vertex the sum of ``x`` over its in-edges'
    sources, by a running sum over the edges in destination order."""
    m = src_by_dst.shape[0]
    at_bounds = jnp.zeros((bounds.shape[0], x.shape[1]), jnp.float32)
    carry = jnp.zeros((x.shape[1],), jnp.float32)
    for lo in range(0, m, edge_chunk):
        hi = min(lo + edge_chunk, m)
        run = carry + jnp.cumsum(x[src_by_dst[lo:hi]], axis=0)
        # the running sum before edge p is run[p - lo - 1] (carry at p = lo)
        inside = (bounds > lo) & (bounds <= hi)
        pick = run[jnp.clip(bounds - lo - 1, 0, hi - lo - 1)]
        at_bounds = jnp.where(inside[:, None], pick, at_bounds)
        carry = run[-1]
    return at_bounds[1:] - at_bounds[:-1]


@functools.partial(jax.jit, static_argnames=("c", "iterations", "edge_chunk"))
def _ppr_block(src_by_dst, bounds, inv_deg, dangling, sources, *, c,
               iterations, edge_chunk):
    n = inv_deg.shape[0]
    b = sources.shape[0]
    cols = jnp.arange(b)
    restart = jnp.zeros((n, b), jnp.float32).at[sources, cols].set(c)

    def step(_, x):
        pulled = _pull(x * inv_deg[:, None], src_by_dst, bounds, edge_chunk)
        lost = jnp.sum(jnp.where(dangling[:, None], x, 0.0), axis=0)
        return (restart + (1.0 - c) * pulled).at[sources, cols].add(
            (1.0 - c) * lost)

    return jax.lax.fori_loop(0, iterations, step, restart).T


@jax.jit
def _sq_err(exact, vertices, scores):
    rows = jnp.arange(exact.shape[0])[:, None]
    scores = jnp.where(scores > 0, scores, 0.0)
    dense = jnp.zeros(exact.shape, jnp.float32).at[rows, vertices].add(scores)
    return jnp.sum((dense - exact) ** 2, axis=1) / jnp.maximum(
        jnp.sum(exact ** 2, axis=1), 1e-30)
