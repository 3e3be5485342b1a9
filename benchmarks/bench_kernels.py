"""Kernel micro-benchmarks: Pallas (interpret) vs pure-jnp reference.

On CPU the Pallas interpreter is a correctness tool, not a speed tool, so
the timing signal here is the *jnp* path (what the XLA CPU backend does
with the same math) plus a correctness gate on the kernel.  On TPU the
same harness times the compiled kernels (interpret=False).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import bench_graph, emit, timeit
from repro.core.graph import push_forward
from repro.graphs import formats
from repro.kernels import frontier_push as push_mod
from repro.kernels import index_combine as comb_mod
from repro.kernels import ops, ref


def run(fast: bool = False) -> dict:
    g = bench_graph("tiny")
    ell = formats.to_ell_chunks(g, k=16, pad_rows_to=256)
    rng = np.random.default_rng(0)
    q = 8
    f = jnp.asarray(rng.random((q, g.n)), jnp.float32)
    out = {}

    # frontier push: edge-parallel segment-sum vs chunked-ELL pull
    t_edge = timeit(lambda: push_forward(g, f))
    t_ell = timeit(lambda: formats.ell_pull(ell, f))
    emit("kernel_push_edge_parallel", t_edge * 1e6, f"n={g.n};m={g.m}")
    emit("kernel_push_ell_jnp", t_ell * 1e6, f"rows={ell.rows};k={ell.k}")

    got = ops.ell_push(f, ell, interpret=True)
    want = push_forward(g, f)
    err = float(jnp.abs(got - want).max())
    emit("kernel_push_pallas_interpret", 0.0, f"max_err={err:.2e}")
    out["push_err"] = err

    # index combine
    n, l = g.n, 32
    vals = jnp.asarray(rng.random((n, l)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, n, (n, l)), jnp.int32)
    s = jnp.asarray(rng.random((q, n)), jnp.float32)
    t_ref = timeit(lambda: ref.index_combine_ref(s, f, vals, idx))
    emit("kernel_combine_jnp", t_ref * 1e6, f"n={n};L={l}")
    got = ops.index_combine(s, f, vals, idx, interpret=True)
    err = float(jnp.abs(got - ref.index_combine_ref(s, f, vals, idx)).max())
    emit("kernel_combine_pallas_interpret", 0.0, f"max_err={err:.2e}")
    out["combine_err"] = err

    # embedding bag
    b, bag, v, d = 256, 8, 4096, 128
    ids = jnp.asarray(rng.integers(0, v, (b, bag)), jnp.int32)
    mask = jnp.ones((b, bag), jnp.float32)
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    t_ref = timeit(lambda: ref.embedding_bag_ref(ids, mask, table))
    emit("kernel_bag_jnp", t_ref * 1e6, f"b={b};bag={bag};v={v};d={d}")
    got = ops.embedding_bag(ids, mask, table, interpret=True)
    err = float(jnp.abs(got - ref.embedding_bag_ref(ids, mask, table)).max())
    emit("kernel_bag_pallas_interpret", 0.0, f"max_err={err:.2e}")
    out["bag_err"] = err
    out.update(run_vmem_report(fast=fast))
    return out


# ---------------------------------------------------------------------------
# per-grid-step VMEM: whole-array-block kernels (pre-HBM-residency) vs the
# DMA-gather kernels (CSR/index arrays stay in HBM, only tiles in VMEM)
# ---------------------------------------------------------------------------

def run_vmem_report(fast: bool = False) -> dict:
    """Per-step VMEM bytes of the sparse-path kernels, before/after HBM
    residency.

    ``before`` is what a kernel holding the whole CSR / index arrays as
    input blocks would need per grid step (O(nnz)); ``after`` is the
    DMA-gather layout (output block + in-flight tiles, capped per step —
    independent of n and nnz).  Analytic from the block shapes (exact: the
    buffers are fixed width), so the report also covers configs too large
    to allocate here.  The 16 MB line is the scoped VMEM budget the
    compiled (interpret=False) kernels must fit.
    """
    vmem_budget = 16 * 1024 * 1024
    # (label, n, m, q_tile, K, degree_cap, hub_split)
    points = [("tiny", 4_096, 32_768, 8, 256, 64, 0)]
    if not fast:
        points += [
            ("wiki", 100_000, 1_000_000, 8, 512, 48, 0),
            ("hub", 1_000_000, 16_000_000, 1, 512, 16_384, 128),
        ]
    out = {}
    for label, n, m, q_tile, k, cap, split in points:
        after = push_mod.vmem_bytes(
            q_tile, k, degree_cap=cap, hub_split_degree=split
        )
        before = push_mod.vmem_bytes_legacy(
            q_tile, k, n=n, m=m, degree_cap=cap, hub_split_degree=split,
        )
        out[("push_vmem", label)] = dict(before=before, after=after)
        emit(
            f"kernel_push_vmem_{label}",
            float(after),
            f"n={n};m={m};before_B={before:.3e};after_B={after:.3e};"
            f"reduction={before / after:.1f}x;"
            f"fits_16MB={'yes' if after <= vmem_budget else 'NO'}",
        )
        l = 32
        c_after = comb_mod.sparse_vmem_bytes(q_tile, k, l)
        c_before = comb_mod.sparse_vmem_bytes_legacy(q_tile, k, l, n=n)
        out[("combine_vmem", label)] = dict(before=c_before, after=c_after)
        emit(
            f"kernel_combine_vmem_{label}",
            float(c_after),
            f"n={n};L={l};before_B={c_before:.3e};after_B={c_after:.3e};"
            f"reduction={c_before / c_after:.1f}x;"
            f"fits_16MB={'yes' if c_after <= vmem_budget else 'NO'}",
        )
    return out


if __name__ == "__main__":
    run()
