"""Bring-up smoke run of PowerWalk's build-and-serve path on a TPU.

    python chip_smoke.py [--seed 0] [--r 100]       # one chip
    python chip_smoke.py --chips 4                  # the multi-chip paths

One chip: an R-MAT graph with the Graph500 parameters at scale 20
(n = 1,048,576, edge factor 16), the paper's engine defaults (c = 0.15,
r = 100, L = 667, T = 2, top-200 answers), the full index build, 1,024
single-vertex queries through ``PPRService.run_closed_loop``, answers checked
against ``power_iteration``, and each DMA-gather kernel compiled and checked
against its oracle in ``repro.kernels.ref`` on this graph and index at the
engine's widths (Q = 256 queries, K = 512 frontier slots).

``--chips 4``: the sharded build on a four-chip mesh against the single-chip
build (bitwise), a repair of the sharded index against the same repair of
the single-chip index, and the distributed VERD tile step with the compiled
push kernel against the single-device sparse query (R-MAT scale 12, widths
that cover the frontier, so the two must agree to float rounding).

Every phase prints one line with its seconds.  Timings here are smoke
timings, not benchmark numbers.  Any failed check raises; the last line,
printed only when every phase passed, is the JSON device stamp.  Without a
TPU the script exits non-zero before doing any work.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

SCALE = 20                # R-MAT scale: n = 2**20
EDGE_FACTOR = 16          # Graph500 edge factor
INDEX_L = 667             # PowerWalkEngineConfig.index_l
TOP_K = 200               # PowerWalkEngineConfig.top_k
C = 0.15                  # PowerWalkEngineConfig.c
T_ITER = 2                # PowerWalkEngineConfig.t_online
SOURCE_BATCH = 1024       # build chunk: sources per device call
QUERIES = 1024
MAX_BATCH = 256
RAG_SEEDS = 8
RAG_BOUND = 0.97          # tests/test_core_ppr.py's RAG bound
# the compiled kernels' checks: the engine's batch and frontier widths
KERNEL_Q = 256
KERNEL_K = 512
KERNEL_HUB_SPLIT = 64     # two sub-slots per degree-128 frontier slot
ORACLE_ROWS = 32          # queries per oracle call
# the sparse route's per-sub-slot gather width: R-MAT hubs have ~40k
# out-edges, so without a split the engine routes to the dense path, whose
# [Q, m] push intermediate does not fit 16 GB at this size
HUB_SPLIT = 1024


def phase(name: str, t0: float, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{name}] {time.perf_counter() - t0:.3f}s {parts}", flush=True)


def require_tpu(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU (jax sees {devs[0].platform}); refusing to "
            "fall back"
        )
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} chips, found {len(devs)}")
    return devs


def memory_line(devs) -> str:
    return " ".join(
        f"dev{i}={(d.memory_stats() or {}).get('bytes_in_use', 0)}"
        for i, d in enumerate(devs)
    )


def make_graph(seed: int):
    from repro.graphs import synthetic

    t0 = time.perf_counter()
    g = synthetic.rmat(SCALE, avg_deg=float(EDGE_FACTOR), seed=seed)
    g.col_idx.block_until_ready()
    deg = np.asarray(g.out_deg)
    phase("graph", t0, n=g.n, m=g.m, max_out_degree=int(deg.max()),
          dangling=int((deg == 0).sum()))
    return g


def build(g, r: int, seed: int, devs):
    import jax
    import jax.numpy as jnp

    from repro.core.index import build_index, sketch_width, sparse_chunk_estimates

    key = jax.random.PRNGKey(seed)
    # compile the build's one chunk program apart from the sweep
    t0 = time.perf_counter()
    out = sparse_chunk_estimates(
        g, jnp.arange(SOURCE_BATCH, dtype=jnp.int32), key, r=r, l=INDEX_L,
        sketch_l=sketch_width(g.n, INDEX_L), c=C, max_steps=64,
        compact_every=8, r_splits=1, respawn=False, touch_bits=0,
    )
    jax.block_until_ready(out)
    phase("build_compile", t0, note="compile + one chunk")
    del out
    t0 = time.perf_counter()
    index, stats = build_index(
        g, r=r, l=INDEX_L, key=key, c=C, max_steps=64,
        source_batch=SOURCE_BATCH, compact_every=8,
    )
    index.values.block_until_ready()
    peak = (devs[0].memory_stats() or {}).get("peak_bytes_in_use", 0)
    phase("build", t0, r=r, l=INDEX_L, index_bytes=stats["nbytes"],
          drop_fraction=f"{stats['drop_fraction']:.6f}",
          peak_bytes_in_use=peak)
    vals = np.asarray(index.values[:4096])
    if not np.isfinite(vals).all() or (vals < 0).any():
        raise AssertionError("index values not finite and nonnegative")
    row_mass = vals.sum(axis=1)
    if not (row_mass <= 1.0 + 1e-3).all():
        raise AssertionError(f"index row mass above 1: {row_mass.max()}")
    return index


def serve(g, index, seed: int):
    from repro.core.query import QueryConfig
    from repro.serving import PPRService, ServiceConfig
    from repro.serving.batching import BatchingConfig

    cfg = ServiceConfig(
        query=QueryConfig(
            mode="powerwalk", t_iterations=T_ITER, c=C, top_k=TOP_K,
            hub_split_degree=HUB_SPLIT,
        ),
        # batches fill to max_batch: one jit shape, compiled in the warmup
        batching=BatchingConfig(max_batch=MAX_BATCH, max_wait_s=5.0),
    )
    svc = PPRService(g, index, cfg)
    rng = np.random.default_rng(seed)
    t0 = time.perf_counter()
    # two one-batch loops: the second dispatch reuses the first's result
    # buffers, which is the other of the two programs a batch can run
    for _ in range(2):
        svc.run_closed_loop(rng.integers(0, g.n, MAX_BATCH).tolist())
    phase("serve_compile", t0, route=svc.frontier_path,
          frontier_k=svc.engine.frontier_k, note="compile + two batches")
    workload = rng.integers(0, g.n, QUERIES)
    batches0 = svc.stats["batches"]
    t0 = time.perf_counter()
    answers, stats = svc.run_closed_loop(workload.tolist())
    phase("serve", t0, served=len(answers), qps=f"{stats['qps']:.3f}",
          p99_ms=f"{stats['latency_p99'] * 1e3:.3f}",
          batches=int(stats["batches"] - batches0), note="smoke timings")
    if len(answers) != QUERIES:
        raise AssertionError(f"{len(answers)} answers for {QUERIES} queries")
    by_vertex = {}
    for a in answers:
        if a.rejected or len(a.top_vertices) != TOP_K:
            raise AssertionError(
                f"query {a.vertex}: rejected={a.rejected}, "
                f"{len(a.top_vertices)} vertices")
        if not np.isfinite(a.top_scores).all():
            raise AssertionError(f"query {a.vertex}: non-finite scores")
        by_vertex[a.vertex] = a
    return svc, workload, by_vertex


def check_rag(g, workload, by_vertex):
    import jax.numpy as jnp

    from repro.core import metrics
    from repro.core.power_iteration import power_iteration

    t0 = time.perf_counter()
    seeds = [int(v) for v in workload[:RAG_SEEDS]]
    exact = power_iteration(g, jnp.asarray(seeds, jnp.int32), n_iter=100, c=C)
    approx = np.zeros((len(seeds), g.n), np.float32)
    for row, v in enumerate(seeds):
        a = by_vertex[v]
        approx[row, a.top_vertices] = a.top_scores
    rag = np.asarray(metrics.rag_at_k(exact, jnp.asarray(approx), TOP_K))
    phase("rag", t0, mean_rag=f"{rag.mean():.6f}", min_rag=f"{rag.min():.6f}",
          bound=RAG_BOUND)
    if rag.mean() < RAG_BOUND:
        raise AssertionError(f"mean RAG@{TOP_K} {rag.mean()} < {RAG_BOUND}")


def assert_close(name, got, want):
    """``got`` within rtol 1e-5 / atol 1e-6 of ``want`` (the kernels'
    interpret-mode test tolerances), compared on the device."""
    import jax.numpy as jnp

    err = jnp.abs(got - want)
    bad = int(jnp.sum(err > 1e-6 + 1e-5 * jnp.abs(want)))
    if bad:
        raise AssertionError(
            f"{name}: {bad} entries differ, max |diff| {float(err.max())}")


def chunked_oracle(name, got_rows, oracle, q: int) -> None:
    """Compare the kernel's densified rows with ``oracle(lo, hi)`` (the
    densified oracle rows ``lo:hi``), ``ORACLE_ROWS`` queries at a time so
    that the oracles' ``[rows, m]`` intermediates fit beside the index."""
    for lo in range(0, q, ORACLE_ROWS):
        hi = min(q, lo + ORACLE_ROWS)
        assert_close(f"{name} rows {lo}:{hi}", got_rows(lo, hi),
                     oracle(lo, hi))


def check_kernels(g, index, seed: int):
    """Each DMA-gather kernel compiled, on this graph and index, against
    its oracle in ``repro.kernels.ref``, at the engine's batch widths
    (Q = 256 queries, K = 512 frontier slots, the L = 667 index): every
    call splits its offsets over several ``pallas_call``s and fills the
    capped step rows.  The push frontiers sit on vertices of out-degree
    <= 128, so ``degree_cap`` (exact for them) keeps the candidate width
    (K x degree_cap) where an exact oracle can cover it."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import frontier as F
    from repro.core.distributed_engine import DistConfig, build_sharded_graph
    from repro.kernels import frontier_push as push_mod
    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed + 1)
    deg = np.asarray(g.out_deg)
    low = np.flatnonzero((deg >= 1) & (deg <= 128))
    q, k = KERNEL_Q, KERNEL_K
    ops.reset_kernel_invocations()

    t0 = time.perf_counter()
    fi_np = rng.choice(low, (q, k))
    cap = int(deg[fi_np].max())
    srcs = jnp.asarray(rng.integers(0, g.n, q), jnp.int32)
    f = F.SparseFrontier(
        values=jnp.asarray(rng.random((q, k)), jnp.float32),
        indices=jnp.asarray(fi_np, jnp.int32), k=k, n=g.n)
    k_out = k * cap + 1                       # covers every row's support
    got = ops.frontier_push(
        f, g, srcs, c=C, degree_cap=cap, k_out=k_out,
        hub_split_degree=KERNEL_HUB_SPLIT,
    ).densify()
    push_ref = jax.jit(functools.partial(
        ref.frontier_push_ref, c=C, degree_cap=cap, k_out=k_out))

    def push_oracle(lo, hi):
        rv, ri = push_ref(f.values[lo:hi], f.indices[lo:hi], srcs[lo:hi],
                          g.row_ptr, g.out_deg, g.col_idx)
        return F.SparseFrontier(values=rv, indices=ri, k=k_out,
                                n=g.n).densify()

    chunked_oracle("frontier_push", lambda lo, hi: got[lo:hi], push_oracle, q)
    windows = q * k * -(-cap // KERNEL_HUB_SPLIT)
    phase("kernel_frontier_push", t0, q=q, k=k, degree_cap=cap,
          hub_split=KERNEL_HUB_SPLIT, windows=windows,
          pallas_calls=-(-windows // push_mod.SMEM_OFFSETS))
    del got

    t0 = time.perf_counter()
    ep = 4
    cfg = DistConfig(n=g.n, ep=ep)
    slabs = build_sharded_graph(g, cfg)
    ns = cfg.n_shard
    local_low = low[low < ns]
    fi_np = rng.choice(local_low, (q, k))
    cap = int(deg[fi_np].max())
    wire_k = k * cap                          # covers each owner's support
    fv = jnp.asarray(rng.random((q, k)), jnp.float32)
    fi = jnp.asarray(fi_np, jnp.int32)
    got_v, got_i = ops.sharded_frontier_push(
        fv, fi, slabs.row_ptr[0], slabs.col_idx[0], c=C, degree_cap=cap,
        ep=ep, n_shard=ns, wire_k=wire_k,
    )
    col0 = slabs.col_idx[0].reshape(-1)
    sharded_ref = jax.jit(functools.partial(
        ref.sharded_push_ref, c=C, ep=ep, n_shard=ns, wire_k=wire_k))

    def owner_rows(v, i):                     # [rows, ep, w] -> [rows, n]
        dense = jax.vmap(
            lambda vo, io: F.SparseFrontier(
                values=vo, indices=io, k=wire_k, n=ns).densify(),
            in_axes=1, out_axes=1)(v, i)
        return dense.reshape(v.shape[0], ep * ns)

    def sharded_oracle(lo, hi):
        return owner_rows(*sharded_ref(fv[lo:hi], fi[lo:hi],
                                       slabs.row_ptr[0], col0))

    chunked_oracle(
        "sharded_frontier_push",
        lambda lo, hi: owner_rows(got_v[lo:hi], got_i[lo:hi]),
        sharded_oracle, q)
    del slabs, col0, got_v, got_i
    phase("kernel_sharded_frontier_push", t0, shard=0, ep=ep, q=q, k=k,
          degree_cap=cap, wire_k=wire_k, windows=q * k)

    t0 = time.perf_counter()
    s = F.SparseFrontier(
        values=jnp.asarray(rng.random((q, k)), jnp.float32),
        indices=jnp.asarray(rng.integers(0, g.n, (q, k)), jnp.int32),
        k=k, n=g.n)
    f = F.SparseFrontier(
        values=jnp.asarray(rng.random((q, k)), jnp.float32),
        indices=jnp.asarray(rng.integers(0, g.n, (q, k)), jnp.int32),
        k=k, n=g.n)
    k_cmb = k + k * INDEX_L               # covers the combine's support
    got = ops.index_combine_sparse(
        s, f, index.values, index.indices, k_out=k_cmb,
    ).densify()

    @jax.jit
    def combine_oracle_rows(sv, si, fv, fi, vals, idx):
        # the dense oracle, one query at a time over its K touched rows
        def one(sv, si, fv, fi):
            s_row = F.SparseFrontier(values=sv[None], indices=si[None],
                                     k=k, n=g.n).densify()
            return ref.index_combine_ref(
                s_row, fv[None], jnp.take(vals, fi, axis=0),
                jnp.take(idx, fi, axis=0))[0]
        return jax.vmap(one)(sv, si, fv, fi)

    chunked_oracle(
        "index_combine_sparse", lambda lo, hi: got[lo:hi],
        lambda lo, hi: combine_oracle_rows(
            s.values[lo:hi], s.indices[lo:hi], f.values[lo:hi],
            f.indices[lo:hi], index.values, index.indices), q)
    del got
    phase("kernel_index_combine_sparse", t0, q=q, k=k, l=INDEX_L,
          touched_rows=q * k)

    t0 = time.perf_counter()
    w = 1 << 20
    key = jax.random.PRNGKey(seed)
    cur = jax.random.randint(key, (w,), 0, g.n, jnp.int32)
    src = jax.random.randint(jax.random.fold_in(key, 1), (w,), 0, g.n,
                             jnp.int32)
    u = jax.random.uniform(jax.random.fold_in(key, 2), (w,))
    got = ops.walk_step(cur, src, u, g.row_ptr, g.out_deg, g.col_idx)
    want = ref.walk_step_ref(cur, src, u, g.row_ptr, g.out_deg, g.col_idx)
    if not np.array_equal(np.asarray(got), np.asarray(want)):
        raise AssertionError("walk_step differs from its oracle")
    phase("kernel_walk_step", t0, walks=w)

    counts = ops.kernel_invocations()
    print(f"[kernel_invocations] {json.dumps(counts, sort_keys=True)}",
          flush=True)
    for name in ("frontier_push", "sharded_frontier_push",
                 "index_combine_sparse", "walk_step"):
        if counts.get(name, 0) < 1:
            raise AssertionError(f"kernel {name} never ran")


def print_cut(args) -> None:
    if args.r != 100:
        print(f"[cut] r={args.r} (paper default 100)", flush=True)


def run_one_chip(args) -> None:
    devs = require_tpu(1)
    print(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    print_cut(args)
    g = make_graph(args.seed)
    index = build(g, args.r, args.seed, devs)
    svc, workload, by_vertex = serve(g, index, args.seed)
    check_rag(g, workload, by_vertex)
    del svc
    check_kernels(g, index, args.seed)


def run_four_chips(args) -> None:
    devs = require_tpu(4)
    print(f"[device] platform={devs[0].platform} kind={devs[0].device_kind} "
          f"count={len(devs)}", flush=True)
    print_cut(args)
    import jax
    import jax.numpy as jnp

    from repro.core import verd as verd_mod
    from repro.core.distributed_engine import (DistConfig,
                                               build_sharded_graph,
                                               make_verd_tile_step)
    from repro.core.index import build_index, build_index_sharded
    from repro.graphs import synthetic
    from repro.kernels import ops

    from repro.core.index import PPRIndex

    g = make_graph(args.seed)
    key = jax.random.PRNGKey(args.seed)
    mesh = jax.make_mesh((1, 4), ("data", "model"))

    # the single-chip build first, alone on device 0 (it peaks near 14 GB
    # at r = 100); only host copies are kept while the sharded build runs
    t0 = time.perf_counter()
    single, ss = build_index(
        g, r=args.r, l=INDEX_L, key=key, c=C, source_batch=SOURCE_BATCH,
        r_splits=1, respawn=True,
    )
    host_v, host_i = np.asarray(single.values), np.asarray(single.indices)
    del single
    phase("single_build", t0, r=args.r,
          drop_fraction=f"{ss['drop_fraction']:.6f}", note="includes compile")

    t0 = time.perf_counter()
    sharded, st = build_index_sharded(
        g, r=args.r, l=INDEX_L, key=key, mesh=mesh, c=C,
        source_batch=SOURCE_BATCH, respawn=True,
    )
    sharded.values.block_until_ready()
    phase("sharded_build", t0, r=args.r, shards=st["shards"],
          n_pad=st["n_pad"], source_batch=st["source_batch"],
          drop_fraction=f"{st['drop_fraction']:.6f}", note="includes compile")
    print(f"[bytes_in_use] {memory_line(devs)}", flush=True)
    per_dev = [s.data.nbytes for s in sharded.values.addressable_shards]
    if len(set(s.device for s in sharded.values.addressable_shards)) != 4 \
            or max(per_dev) * 4 != sum(per_dev):
        raise AssertionError(f"index not split over 4 chips: {per_dev}")
    if st["source_batch"] != SOURCE_BATCH:
        raise AssertionError("the two builds ran different chunk grids")
    for name, want in (("values", host_v), ("indices", host_i)):
        a = np.asarray(getattr(sharded, name))[: g.n]
        if not np.array_equal(a, want):
            raise AssertionError(
                f"sharded build {name} differ from the single-chip build in "
                f"{int((a != want).any(axis=1).sum())} rows")
    print("[build_parity] bitwise equal", flush=True)

    # repair: the same rows replaced in both layouts, one after the other
    t0 = time.perf_counter()
    rng = np.random.default_rng(args.seed + 2)
    rows = np.sort(rng.choice(g.n, 4096, replace=False))
    new_v, new_i = host_v[rows[::-1]], host_i[rows[::-1]]
    rep_sh = sharded.replace_rows(rows, new_v, new_i)
    if rep_sh.values.sharding != sharded.values.sharding:
        raise AssertionError("repair changed the index sharding")
    print(f"[bytes_in_use] {memory_line(devs)}", flush=True)
    got_v = np.asarray(rep_sh.values)[: g.n]
    got_i = np.asarray(rep_sh.indices)[: g.n]
    del rep_sh, sharded
    single = PPRIndex(values=jnp.asarray(host_v), indices=jnp.asarray(host_i),
                      l=INDEX_L, n=g.n)
    rep_si = single.replace_rows(rows, new_v, new_i)
    if not (np.array_equal(got_v, np.asarray(rep_si.values))
            and np.array_equal(got_i, np.asarray(rep_si.indices))):
        raise AssertionError("sharded repair differs from the single-chip "
                             "repair")
    phase("repair_parity", t0, rows=rows.size)
    del rep_si, single, got_v, got_i, host_v, host_i

    # the distributed VERD tile step: covering widths, so that it must
    # agree with the single-device sparse query to float rounding
    t0 = time.perf_counter()
    gs = synthetic.rmat(12, avg_deg=float(EDGE_FACTOR), seed=args.seed)
    cap = verd_mod.resolve_degree_cap(gs)
    cfg = DistConfig(
        n=gs.n, ep=4, q_tile=8, t_iterations=T_ITER, index_l=64,
        top_k=gs.n, frontier_k=gs.n, degree_cap=cap,
    )
    small, _ = build_index(gs, r=args.r, l=64, key=key, c=C)
    slabs = build_sharded_graph(gs, cfg)
    ivals = small.values.reshape(4, cfg.n_shard, 64)
    iidx = small.indices.reshape(4, cfg.n_shard, 64)
    sources = jnp.asarray(rng.integers(0, gs.n, 8), jnp.int32)
    ops.reset_kernel_invocations()
    step = make_verd_tile_step(cfg, mesh)
    with mesh:
        tv, ti = jax.jit(step)(slabs, sources, ivals, iidx)
    got = np.zeros((8, gs.n), np.float32)
    np.add.at(got, (np.arange(8)[:, None], np.asarray(ti)), np.asarray(tv))
    want = np.asarray(verd_mod.verd_query_sparse(
        gs, sources, small, t=T_ITER, k=gs.n, out_k=gs.n, c=C,
    ).densify())
    l1 = np.abs(got - want).sum(axis=1).max()
    pushes = ops.kernel_invocations().get("sharded_frontier_push", 0)
    phase("verd_tile_step", t0, n=gs.n, l1=f"{l1:.3e}",
          kernel_pushes=pushes)
    if pushes != T_ITER:
        raise AssertionError(f"tile step ran {pushes} kernel pushes")
    if l1 > 1e-5:
        raise AssertionError(f"tile step vs single-device L1 {l1} > 1e-5")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--r", type=int, default=100,
                    help="walks per vertex of the index (cut only if the "
                         "build does not fit the time limit)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 1:
        run_one_chip(args)
    else:
        run_four_chips(args)
    import jax

    devs = jax.devices()
    phase("total", t0)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
