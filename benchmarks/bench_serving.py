"""Serving-path benchmark: open-loop QPS sweep over the async pipeline.

Drives ``PPRService`` with the open-loop load generator at a grid of
offered rates and records what clients would see: p50/p99 latency vs
offered QPS, the saturation knee (highest offered rate the service still
sustains), the batch-size histogram the batcher actually formed, and a
pipeline-depth sweep.  ``depth=1, dispatch=legacy`` reproduces the PR-5
blocking ``poll()`` and is the baseline; the acceptance gate is sustained
knee throughput >= 2x that baseline at the n=100k / K=512 reference point
(same reference as bench_query's sparse sweep).

Warmup dispatches cover every padded jit shape the batcher can form
(``min_pad .. max_batch`` powers of two) before any measurement, so
trajectories are never dominated by compile time.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks.bench_query import _random_index
from benchmarks.common import emit
from repro.core.query import QueryConfig
from repro.graphs import synthetic
from repro.serving import PPRService, PipelineConfig, ServiceConfig
from repro.serving.batching import BatchingConfig
from repro.serving.loadgen import run_closed_loop, run_open_loop

# sustained = achieved within this fraction of offered (open-loop knee rule)
SUSTAIN_FRACTION = 0.92

FULL = dict(n=100_000, avg_deg=8.0, L=32, K=512, top_k=100, t=2,
            max_batch=256, min_pad=64, max_wait_s=0.010, requests=2048,
            depths=(1, 2, 4), rate_grid=(0.6, 0.9, 1.1, 1.4))
FAST = dict(n=8_192, avg_deg=8.0, L=16, K=128, top_k=50, t=2,
            max_batch=32, min_pad=16, max_wait_s=0.005, requests=160,
            depths=(1, 2), rate_grid=(0.8, 1.2))


def _make_service(g, idx, p: dict, depth: int, dispatch: str) -> PPRService:
    cfg = ServiceConfig(
        query=QueryConfig(
            mode="powerwalk", t_iterations=p["t"], top_k=p["top_k"],
            frontier_k=p["K"], frontier_path="sparse",
        ),
        batching=BatchingConfig(
            max_batch=p["max_batch"], max_wait_s=p["max_wait_s"],
            min_pad=p["min_pad"],
        ),
        pipeline=PipelineConfig(depth=depth, dispatch=dispatch),
    )
    return PPRService(g, idx, cfg)


def _warmup(svc: PPRService, p: dict) -> None:
    """Compile every padded batch shape the buffer can form, then zero the
    counters so measurements see a warm service only.  Iterates the
    batcher's own closed shape set (``BatchingConfig.padded_shapes``) —
    the old pow2 walk missed the bucketed quantum-multiple widths (e.g.
    192 at max_batch=256), so those compiled mid-measurement."""
    for shape in svc.cfg.batching.padded_shapes():
        for v in range(shape):
            svc.submit(v % svc.engine.graph.n)
        svc.poll(force=True)
    svc.reset_stats()


def _point(stats: dict) -> dict:
    """The per-measurement slice of stats the JSON trajectory keeps."""
    return dict(
        offered_qps=stats["offered_qps"], qps=stats["qps"],
        latency_p50=stats["latency_p50"], latency_p99=stats["latency_p99"],
        mean_latency=stats["mean_latency"], served=stats["served"],
        batches=stats["batches"], pad_fraction=stats["pad_fraction"],
        batch_hist=stats["batch_hist"],
        in_flight_peak=stats["pipeline_in_flight_peak"],
        queue_full_stalls=stats["pipeline_queue_full_stalls"],
    )


def _knee(points: list) -> dict:
    """Highest sustained point of one open-loop sweep: the largest offered
    rate where achieved throughput kept up (SUSTAIN_FRACTION), else the
    best achieved rate (fully saturated sweep)."""
    sustained = [p for p in points
                 if p["qps"] >= SUSTAIN_FRACTION * p["offered_qps"]]
    pool = sustained or points
    best = max(pool, key=lambda p: p["qps"])
    return dict(knee_qps=best["qps"], offered_qps=best["offered_qps"],
                latency_p99=best["latency_p99"], sustained=bool(sustained))


def run(fast: bool = False) -> dict:
    p = FAST if fast else FULL
    g = synthetic.erdos_renyi(p["n"], p["avg_deg"], seed=5)
    idx = _random_index(g.n, p["L"], jax.random.PRNGKey(7))
    rng = np.random.default_rng(11)
    workload = rng.integers(0, g.n, size=p["requests"]).tolist()

    configs = [("legacy_d1", 1, "legacy")]
    configs += [(f"fused_d{d}", d, "fused") for d in p["depths"]]

    out: dict = dict(
        reference=dict(n=p["n"], K=p["K"], L=p["L"], top_k=p["top_k"],
                       t=p["t"], max_batch=p["max_batch"],
                       max_wait_s=p["max_wait_s"], requests=p["requests"]),
        closed_loop={}, open_loop={}, knee={}, depth_sweep={},
    )

    # -- closed-loop capacity per config (sets each open-loop rate grid) ----
    capacity = {}
    services = {}
    for name, depth, dispatch in configs:
        svc = _make_service(g, idx, p, depth, dispatch)
        _warmup(svc, p)
        _, stats = run_closed_loop(svc, workload)
        # the service is warm (all jit shapes compiled by _warmup), so the
        # plain wall-clock qps is the honest capacity
        capacity[name] = stats["qps"]
        services[name] = svc
        out["closed_loop"][name] = _point(stats)
        if dispatch == "fused":
            out["depth_sweep"][str(depth)] = stats["qps"]
        emit(f"serving_closed_{name}", 1e6 / max(stats["qps"], 1e-9),
             f"qps={stats['qps']:.1f};p99={stats['latency_p99']*1e3:.1f}ms")

    # -- open-loop sweep: offered rate grid scaled to each config's own
    # closed-loop capacity so every sweep brackets its knee -----------------
    for name, depth, dispatch in configs:
        svc = services[name]
        points = []
        for frac in p["rate_grid"]:
            offered = frac * capacity[name]
            svc.reset_stats()
            _, stats = run_open_loop(svc, workload, qps=offered)
            points.append(_point(stats))
            emit(f"serving_open_{name}_r{frac:g}",
                 1e6 / max(stats["qps"], 1e-9),
                 f"offered={offered:.1f};qps={stats['qps']:.1f};"
                 f"p99={stats['latency_p99']*1e3:.1f}ms")
        out["open_loop"][name] = points
        out["knee"][name] = _knee(points)

    # -- the acceptance gate: pipelined knee vs blocking-baseline knee ------
    base = out["knee"]["legacy_d1"]["knee_qps"]
    best_name = max((n for n, _, d in configs if d == "fused"),
                    key=lambda n: out["knee"][n]["knee_qps"])
    best = out["knee"][best_name]["knee_qps"]
    out["knee_speedup_vs_blocking"] = best / max(base, 1e-9)
    out["knee_best_config"] = best_name
    emit("serving_knee_speedup", 0.0,
         f"best={best_name};{best:.1f}qps_vs_{base:.1f}qps;"
         f"x{out['knee_speedup_vs_blocking']:.2f}")
    return out
