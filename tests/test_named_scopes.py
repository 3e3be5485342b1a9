"""The program's named scopes (``ppr.*``) add metadata and nothing else:
lowered without debug info, the serving and index-build programs are the
same text with the scopes and with every scope taken out."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import index as index_mod
from repro.core.index import PPRIndex
from repro.core.query import BatchQueryEngine, QueryConfig
from repro.graphs import synthetic


@pytest.fixture(scope="module")
def graph():
    return synthetic.rmat(10, avg_deg=8.0, seed=3)


def _lowered(fn, *args, scoped: bool):
    jax.clear_caches()  # nested jits would reuse a scoped trace
    with pytest.MonkeyPatch.context() as mp:
        if not scoped:
            mp.setattr(jax, "named_scope",
                       lambda name: contextlib.nullcontext())
        return jax.jit(fn).lower(*args)


def _assert_metadata_only(fn, args, scopes):
    with_scopes = _lowered(fn, *args, scoped=True)
    without = _lowered(fn, *args, scoped=False)
    assert with_scopes.as_text(debug_info=False) == \
        without.as_text(debug_info=False)
    debug = with_scopes.as_text(debug_info=True)
    assert all(scope in debug for scope in scopes)
    assert "ppr." not in without.as_text(debug_info=True)


@pytest.mark.parametrize("combine_path", ["sparse", "scatter"])
def test_serving_program_scopes_are_metadata_only(graph, combine_path):
    n, l, q = graph.n, 16, 8
    rng = np.random.default_rng(0)
    index = PPRIndex(
        values=jnp.asarray(rng.random((n, l), np.float32)),
        indices=jnp.asarray(rng.integers(0, n, (n, l), np.int32)), l=l, n=n)
    engine = BatchQueryEngine(graph, index, QueryConfig(
        mode="powerwalk", t_iterations=2, top_k=32, frontier_k=128,
        frontier_path="sparse", combine_path=combine_path))
    k = engine.effective_top_k
    engine.degree_cap()  # a host read, cached before tracing

    def serve(sources, out_v, out_i):
        return engine.query_topk_async(
            sources, key=engine.dispatch_key(0), out=(out_v, out_i))

    args = (jnp.arange(q, dtype=jnp.int32), jnp.zeros((q, k)),
            jnp.zeros((q, k), jnp.int32))
    _assert_metadata_only(serve, args, ["ppr.push", "ppr.push.gather",
                                        "ppr.fold", "ppr.combine",
                                        "ppr.topk"])


def test_build_chunk_program_scopes_are_metadata_only(graph):
    def chunk(sources, key):
        return index_mod.sparse_chunk_estimates(
            graph, sources, key, r=32, l=16, sketch_l=32, max_steps=16,
            compact_every=4)

    args = (jnp.arange(64, dtype=jnp.int32), jax.random.PRNGKey(0))
    _assert_metadata_only(chunk, args, ["ppr.walk.step", "ppr.sketch_fold",
                                        "ppr.fold", "ppr.slot_compact",
                                        "ppr.topl"])
