"""End-to-end serving driver: the paper's product as a running service.

    PYTHONPATH=src python examples/serve_ppr.py

Simulates an online workload against :class:`repro.serving.PPRService`:
requests arrive one by one, the buffer batches them (paper Section 3.3),
the VERD shared decomposition answers them through the async pipeline
(docs/serving_path.md), and latency/throughput stats are reported — the
Table 3 scenario as a live loop, first closed-loop (capacity) then
open-loop at a fixed offered rate with an interactive/bulk traffic mix.
"""

import jax
import numpy as np

from repro.core.index import build_index
from repro.core.query import QueryConfig
from repro.graphs import synthetic
from repro.serving import (PipelineConfig, PPRService, ServiceConfig,
                           run_open_loop)
from repro.serving.batching import BatchingConfig, TierPolicy


def make_service(g, index, depth=4):
    return PPRService(
        g, index,
        ServiceConfig(
            query=QueryConfig(mode="powerwalk", t_iterations=2, top_k=20),
            batching=BatchingConfig(
                max_batch=256, max_wait_s=0.005,
                # bulk traffic may wait longer so interactive stays snappy
                interactive=TierPolicy(max_wait_s=0.005),
                bulk=TierPolicy(max_wait_s=0.050),
            ),
            pipeline=PipelineConfig(depth=depth),
        ),
    )


def main():
    print("== PowerWalk serving demo ==")
    g = synthetic.rmat(11, avg_deg=10.0, seed=0)
    index, _ = build_index(g, r=100, l=256, key=jax.random.PRNGKey(0),
                           source_batch=512)
    rng = np.random.default_rng(1)

    # -- closed loop: capacity ------------------------------------------------
    svc = make_service(g, index)
    workload = rng.integers(0, g.n, size=2000)
    answers, stats = svc.run_closed_loop(workload)
    print(f"closed loop: served {stats['served']:.0f} requests in "
          f"{stats['wall_s']:.2f}s ({stats['qps']:.0f} q/s), "
          f"{stats['batches']:.0f} batches, depth={stats['pipeline_depth']}")
    print(f"  latency mean={stats['mean_latency'] * 1e3:.1f}ms "
          f"p99={stats['latency_p99'] * 1e3:.1f}ms")
    a = answers[0]
    print(f"  sample answer: query v{a.vertex} -> "
          f"top vertices {a.top_vertices[:5].tolist()}")
    assert stats["served"] == len(workload)

    # -- open loop: offered-rate workload with a tier mix ---------------------
    svc2 = make_service(g, index)
    mixed = [(int(v), "bulk" if i % 4 == 0 else "interactive")
             for i, v in enumerate(rng.integers(0, g.n, size=1000))]
    offered = 0.5 * stats["qps"]
    answers2, s2 = run_open_loop(svc2, mixed, qps=offered)
    by_tier = {"interactive": [], "bulk": []}
    for a in answers2:
        by_tier[a.tier].append(a.latency_s)
    print(f"open loop @ {offered:.0f} q/s offered: achieved "
          f"{s2['qps']:.0f} q/s, p50={s2['latency_p50'] * 1e3:.1f}ms "
          f"p99={s2['latency_p99'] * 1e3:.1f}ms, "
          f"in_flight_peak={s2['pipeline_in_flight_peak']:.0f}")
    for tier, lats in by_tier.items():
        print(f"  {tier}: {len(lats)} answers, "
              f"mean={np.mean(lats) * 1e3:.1f}ms")
    assert s2["served"] == len(mixed)
    print("OK")


if __name__ == "__main__":
    main()
