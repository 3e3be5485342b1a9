"""PPR serving launcher (the paper's online phase as a process).

    PYTHONPATH=src python -m repro.launch.serve \
        [--n-log2 11] [--r 100] [--t 2] [--queries 2000] [--mode powerwalk]

Builds (or loads) the index, starts the batched service, and runs a
closed-loop workload, printing Table-3-style latency/throughput numbers.
"""

from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.core.index import build_index
from repro.core.query import QueryConfig
from repro.graphs import synthetic
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import PPRService, ServiceConfig
from repro.serving.batching import BatchingConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n-log2", type=int, default=11)
    ap.add_argument("--r", type=int, default=100)
    ap.add_argument("--t", type=int, default=2)
    ap.add_argument("--mode", default="powerwalk",
                    choices=["powerwalk", "verd", "fppr", "mcfp", "pi"])
    ap.add_argument("--queries", type=int, default=2000)
    ap.add_argument("--max-batch", type=int, default=256)
    ap.add_argument("--top-k", type=int, default=50)
    args = ap.parse_args()

    enable_compile_cache()
    g = synthetic.rmat(args.n_log2, avg_deg=10.0, seed=0)
    print(f"graph n={g.n} m={g.m}; building index R={args.r}")
    index = None
    if args.mode in ("powerwalk", "fppr"):
        index, stats = build_index(
            g, r=args.r, l=max(32, int(args.r / 0.15)),
            key=jax.random.PRNGKey(0), source_batch=512)
        print(f"index: {stats['nbytes'] >> 20} MiB "
              f"(dropped {stats['drop_fraction']:.3f})")

    svc = PPRService(
        g, index,
        ServiceConfig(
            query=QueryConfig(mode=args.mode, t_iterations=args.t,
                              top_k=args.top_k),
            batching=BatchingConfig(max_batch=args.max_batch),
        ),
    )
    workload = np.random.default_rng(0).integers(0, g.n, size=args.queries)
    _, stats = svc.run_closed_loop(workload)
    print(f"mode={args.mode}: {stats['served']:.0f} queries "
          f"{stats['wall_s']:.2f}s  {stats['qps']:.0f} q/s  "
          f"mean_latency {stats['mean_latency'] * 1e3:.1f}ms")


if __name__ == "__main__":
    main()
