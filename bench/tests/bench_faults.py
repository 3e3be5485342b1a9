"""Faults planted under the timed path, for the test that sees ``correct``
come out false.  Each is a context manager that patches one program
function where its output is produced, and clears JAX's caches on entry and
exit so that no program traced with or without it is reused."""

import contextlib

import bench_paths  # noqa: F401  (puts src/ on the path)
import jax
import jax.numpy as jnp

from repro.core import frontier, index, query, verd, walks


@contextlib.contextmanager
def patched(module, name, replacement):
    original = getattr(module, name)
    jax.clear_caches()
    setattr(module, name, replacement(original))
    try:
        yield
    finally:
        setattr(module, name, original)
        jax.clear_caches()


def _pad(x, width):
    if x.shape[1] >= width:
        return x[:, :width]
    return jnp.pad(x, ((0, 0), (0, width - x.shape[1])))


def push_state_unchanged():
    """Every online push returns the frontier it was given."""
    def make(original):
        def push(graph, fv, fi, sources, *, k_out, **kwargs):
            return frontier.SparseFrontier(
                values=_pad(fv, k_out), indices=_pad(fi, k_out),
                k=k_out, n=graph.n)
        return push
    return patched(verd, "sparse_push_compact", make)


def walk_state_unchanged():
    """Every walk step leaves each walk where it is."""
    return patched(walks, "advance_cursors",
                   lambda original: lambda graph, cursors, *a, **k: cursors)


def serve_half_batch():
    """The second half of every batch is left out: its answers come back
    empty (all scores 0)."""
    def make(original):
        def impl(*args, **kwargs):
            vals, idx = original(*args, **kwargs)
            half = vals.shape[0] // 2
            return vals.at[half:].set(0.0), idx
        return impl
    return patched(query, "_fused_topk_impl", make)


def build_half_batch():
    """The second half of every build chunk is left out: its rows come back
    all zero."""
    def make(original):
        def chunk(*args, **kwargs):
            vals, idxs, *rest = original(*args, **kwargs)
            half = vals.shape[0] // 2
            return (vals.at[half:].set(0.0), idxs, *rest)
        return chunk
    return patched(index, "sparse_chunk_estimates", make)


def serve_answer_altered():
    """Every eighth answer of every batch names the wrong vertices: each of
    its vertex ids is shifted by one (the check compares a sample of the
    answers, which one altered answer in a batch would escape)."""
    def make(original):
        def impl(graph, *args, **kwargs):
            vals, idx = original(graph, *args, **kwargs)
            return vals, idx.at[::8].set((idx[::8] + 1) % graph.n)
        return impl
    return patched(query, "_fused_topk_impl", make)


def build_row_altered():
    """Every eighth row of every build chunk names the wrong vertices (the
    check compares a sample of rows, which one altered row in a chunk would
    escape)."""
    def make(original):
        def chunk(graph, *args, **kwargs):
            vals, idxs, *rest = original(graph, *args, **kwargs)
            return (vals, idxs.at[::8].set((idxs[::8] + 1) % graph.n), *rest)
        return chunk
    return patched(index, "sparse_chunk_estimates", make)


SERVE_FAULTS = dict(state_unchanged=push_state_unchanged,
                    half_batch=serve_half_batch,
                    answer_altered=serve_answer_altered)
BUILD_FAULTS = dict(state_unchanged=walk_state_unchanged,
                    half_batch=build_half_batch,
                    answer_altered=build_row_altered)
