"""Open-loop load generation for the PPR service.

A closed-loop driver (submit, wait, submit) hides saturation: when the
service slows down, the offered load slows down with it, so measured
latency stays flat right past the capacity cliff.  The open-loop harness
offers request ``i`` at its *scheduled* time ``t0 + i/qps`` regardless of
service backpressure, backdates the request's arrival to that schedule,
and measures latency from it — so queueing delay under overload shows up
in p99 exactly as clients would see it.  ``qps=None`` degenerates to the
closed-loop mode (offer as fast as the loop runs), which is what
``PPRService.run_closed_loop`` wraps.

The clock comes from the service (injectable for deterministic tests);
``sleep`` is injectable the same way.
"""

from __future__ import annotations

import time
from typing import Callable, List, Optional, Sequence, Tuple, Union

import numpy as np

# a workload item is a vertex, an explicit (vertex, tier) pair, or a
# seed-set dict: {"seeds": [...], "weights": [...], "tier": "..."}
# (weights/tier optional — uniform weights, interactive tier)
WorkItem = Union[int, Tuple[int, str], dict]


def _percentile(xs: List[float], p: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), p)) if xs else 0.0


def _submit(service, item: WorkItem, arrival: Optional[float] = None) -> None:
    """Submit one work item of any spelling."""
    if isinstance(item, dict):
        service.submit(
            tier=item.get("tier", "interactive"), arrival=arrival,
            seeds=item["seeds"], weights=item.get("weights"),
        )
        return
    v, tier = item if isinstance(item, tuple) else (item, "interactive")
    service.submit(v, tier=tier, arrival=arrival)


def zipf_seed_workload(
    n_vertices: int,
    n_requests: int,
    *,
    skew: float = 1.1,
    max_seeds: int = 4,
    pool: int = 1024,
    singles_fraction: float = 0.0,
    tier: str = "interactive",
    seed: int = 0,
) -> List[WorkItem]:
    """Zipf-skewed hot-seed traffic: the cache benchmark's arrival stream.

    Draws a ``pool`` of distinct weighted seed sets once, then samples each
    request's set from a Zipf(``skew``) rank distribution over the pool —
    the classic hot-key shape of real personalization traffic (a few hot
    users/communities dominate), which is what gives an answer cache
    something to hit.  Repeated picks are spelled with *permuted* seeds and
    *rescaled* weights, so cache hit rate exercises canonicalization, not
    memcmp.  ``singles_fraction`` of requests degrade to plain single-vertex
    items (the set's primary seed) for mixed single/seed-set traffic.
    """
    rng = np.random.default_rng(seed)
    pool = max(1, pool)
    sizes = rng.integers(1, max_seeds + 1, pool)
    pool_seeds = [
        rng.integers(0, n_vertices, int(sz)).tolist() for sz in sizes
    ]
    pool_weights = [
        (rng.random(int(sz)) + 0.1).tolist() for sz in sizes
    ]
    ranks = np.arange(1, pool + 1, dtype=np.float64) ** (-skew)
    picks = rng.choice(pool, size=n_requests, p=ranks / ranks.sum())
    items: List[WorkItem] = []
    for j in picks:
        s = pool_seeds[j]
        w = pool_weights[j]
        if singles_fraction > 0 and rng.random() < singles_fraction:
            items.append(int(s[0]))
            continue
        perm = rng.permutation(len(s))
        scale = float(rng.uniform(0.5, 2.0))
        items.append(dict(
            seeds=[s[i] for i in perm],
            weights=[w[i] * scale for i in perm],
            tier=tier,
        ))
    return items


def run_open_loop(
    service,
    vertices: Sequence[WorkItem],
    qps: Optional[float] = None,
    *,
    sleep: Callable[[float], None] = time.sleep,
    max_sleep_s: float = 0.002,
) -> Tuple[list, dict]:
    """Offer ``vertices`` at ``qps`` (None = as fast as possible); returns
    ``(answers, stats)`` once every request has been served.

    While waiting for the next scheduled arrival the loop keeps polling, so
    in-flight batches are harvested (and deadline-expired buffers flushed)
    even when no new request shows up — the pipeline never idles on offered
    gaps.  Per-request latency is measured from the *scheduled* offer time.
    """
    clock = service.clock
    answers: list = []
    t0 = clock()
    i = 0
    while i < len(vertices):
        if qps:
            now = clock()
            if now < t0 + i / qps:  # next arrival not due yet: keep serving
                answers.extend(service.poll())
                now = clock()
                t_sched = t0 + i / qps
                if now < t_sched:
                    sleep(min(t_sched - now, max_sleep_s))
                continue
            # submit *every* request already due before polling again: an
            # open-loop arrival process doesn't wait for the server, so when
            # the service falls behind, due requests land in its queue as a
            # group (and batch up) instead of trickling one per poll
            while i < len(vertices) and t0 + i / qps <= now:
                _submit(service, vertices[i], arrival=t0 + i / qps)
                i += 1
        else:
            _submit(service, vertices[i])
            i += 1
        answers.extend(service.poll())
    answers.extend(service.poll(force=True))
    wall = clock() - t0

    s = service.snapshot_stats()
    lat = [a.latency_s for a in answers]
    s["wall_s"] = wall
    s["offered_qps"] = float(qps) if qps else 0.0
    s["qps"] = len(answers) / max(wall, 1e-9)
    s["latency_p50"] = _percentile(lat, 50)
    s["latency_p99"] = _percentile(lat, 99)
    return answers, s


def run_closed_loop(service, vertices: Sequence[WorkItem]) -> Tuple[list, dict]:
    """Serve a fixed workload to completion with no rate control."""
    return run_open_loop(service, vertices, qps=None)
