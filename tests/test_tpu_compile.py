"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: unaligned DMAs,
primitives Mosaic cannot lower (sort), more VMEM than a kernel may use,
programs larger than the chip's 16 GB.  These tests compile each
DMA-gather kernel at the widths of an R-MAT scale-20 deployment (n = 2**20,
m = 2**24, Q = 256, K = 512, L = 667) and one served batch against that
index, so such a regression fails here instead of on the chip.  Nothing
runs: the arguments are shapes.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import query as query_mod
from repro.core.graph import Graph
from repro.core.index import PPRIndex
from repro.kernels import frontier_push as push_mod
from repro.kernels import index_combine as comb_mod
from repro.kernels import walk_step as walk_mod

N, M, Q, K, L = 1 << 20, 1 << 24, 256, 512, 667
HBM_BYTES = 16 * 10**9            # one v5e chip
DEGREE_CAP = 128                  # gather width of the kernel compiles


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _fits(compiled) -> int:
    """Bytes the program needs on the device; asserts they fit one chip."""
    ma = compiled.memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert need < HBM_BYTES, need
    return need


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def test_frontier_push_compiles(spec):
    compiled = push_mod.frontier_push.lower(
        spec((Q, K), jnp.float32), spec((Q, K)), spec((Q,)),
        spec((N + 1,)), spec((N,)), spec((M,)),
        c=0.15, degree_cap=DEGREE_CAP, k_out=K,
    ).compile()
    assert _has_kernel(compiled)
    _fits(compiled)


def test_sharded_frontier_push_compiles(spec):
    ep = 4
    compiled = push_mod.sharded_frontier_push.lower(
        spec((Q, K), jnp.float32), spec((Q, K)),
        spec((N // ep + 1,)), spec(push_mod.lane_rows_shape(M // ep)),
        c=0.15, degree_cap=DEGREE_CAP, ep=ep, n_shard=N // ep, wire_k=K,
    ).compile()
    assert _has_kernel(compiled)
    _fits(compiled)


def test_index_combine_sparse_compiles(spec):
    compiled = comb_mod.index_combine_sparse.lower(
        spec((Q, K), jnp.float32), spec((Q, K)),
        spec((Q, K), jnp.float32), spec((Q, K)),
        spec((N, L), jnp.float32), spec((N, L)),
        k_out=K,
    ).compile()
    assert _has_kernel(compiled)
    _fits(compiled)


def test_walk_step_compiles(spec):
    w = 1024 * 100                # one build chunk: 1,024 sources x r=100
    compiled = walk_mod.walk_step.lower(
        spec((w,)), spec((w,)), spec((w,), jnp.float32),
        spec((N + 1,)), spec((N,)), spec((M,)),
    ).compile()
    assert _has_kernel(compiled)
    _fits(compiled)


def test_served_batch_fits_one_chip(spec):
    """One max_batch of the sparse route against the full [2**20, 667]
    index (5.6 GB) — what chip_smoke.py serves — fits 16 GB."""
    m = 15_500_000                # R-MAT scale 20 after dedup
    graph = Graph(row_ptr=spec((N + 1,)), col_idx=spec((m,)),
                  src=spec((m,)), out_deg=spec((N,)), n=N, m=m)
    index = PPRIndex(values=spec((N, L), jnp.float32),
                     indices=spec((N, L)), l=L, n=N)
    compiled = query_mod._fused_topk.lower(
        graph, index, spec((Q,)), spec((2,), jnp.uint32), None,
        mode="powerwalk", t=2, c=0.15, top_k=200, r_online=2000,
        pi_iterations=100, threshold=0.0, frontier_k=800,
        degree_cap=40_000, hub_split_degree=1024, sparse_route=True,
        scatter_combine=False,
    ).compile()
    need = _fits(compiled)
    assert need > index.n * index.l * 8   # the index itself is counted
