"""The one traffic generator: it reads a traffic mix (``bench/traffic/*.json``)
and drives the program through a measured window.

A mix of ``"kind": "serve"`` sends single-vertex queries to a
``PPRService``:

* ``queries``: ``uniform_linked`` (uniform over vertices with an out-edge),
  ``out_degree`` (in proportion to out-degree) or ``zipf`` (a Zipf law of
  exponent ``zipf_s`` over a seeded ranking of the linked vertices);
* ``arrivals``: ``closed`` (``clients`` clients, each sending its next query
  when its answer is back) or ``poisson`` (an open loop at ``rate_per_s``,
  each request backdated to its scheduled time, so latency counts the wait a
  stall imposes on later requests).

Offering stops when the window closes; requests in flight then drain, for
at most ``drain_s`` seconds.  Each latency runs from the request's
submission (closed loop) or its scheduled time (open loop) to the poll that
returned its answer.

A mix of ``"kind": "build"`` calls ``build_index`` on successive slices of
``sources_per_call`` sources from a seeded offset; the call in flight when
the window closes completes.
"""

from __future__ import annotations

import time

import numpy as np

import traces

QUERY_LAWS = ("uniform_linked", "out_degree", "zipf")


def draw_queries(rng, out_deg: np.ndarray, spec: dict, count: int):
    """``count`` query vertices drawn by the mix's ``queries`` law."""
    law = spec["queries"]
    linked = np.flatnonzero(out_deg > 0)
    if law == "uniform_linked":
        return rng.choice(linked, count)
    if law == "out_degree":
        p = out_deg[linked] / out_deg[linked].sum()
        return rng.choice(linked, count, p=p)
    if law == "zipf":
        ranked = rng.permutation(linked)
        w = np.arange(1, len(ranked) + 1, dtype=np.float64) ** -spec["zipf_s"]
        return ranked[rng.choice(len(ranked), count, p=w / w.sum())]
    raise ValueError(f"unknown query law {law!r}; expected one of {QUERY_LAWS}")


def shuffle_blocks(rng, queries: np.ndarray, block: int) -> np.ndarray:
    """``queries`` with each run of ``block`` shuffled in place of itself:
    the same set in each block, in another order."""
    return np.concatenate([rng.permutation(queries[i:i + block])
                           for i in range(0, len(queries), block)])


def warm_queries(rng, out_deg: np.ndarray, count: int):
    """Queries whose frontier stays small: dangling vertices where the graph
    has them, else the vertices of least out-degree."""
    dangling = np.flatnonzero(out_deg == 0)
    pool = dangling if len(dangling) else np.argsort(out_deg)[:count]
    return rng.choice(pool, count)


class ServeWindow:
    """What a serving window produced: one record per request."""

    def __init__(self):
        self.vertex, self.sent, self.done = [], [], []
        self.top_v, self.top_s = [], []
        self.unanswered = 0
        self.start = self.close = self.end = 0.0

    @property
    def answered(self) -> int:
        return len(self.done)


def serve(svc, queries, spec: dict, seconds, rng=None,
          clock=time.perf_counter):
    """Drive ``svc`` with ``queries`` for ``seconds``, then drain.  In a
    closed loop, ``seconds=None`` sends every query and stops.  An open
    loop draws its arrival gaps from ``rng``."""
    out = ServeWindow()
    queries = list(queries)
    nxt = 0
    pending = {}
    out.start = clock()
    out.close = out.start + seconds if seconds is not None else float("inf")
    poisson = spec["arrivals"] == "poisson"
    if poisson:
        rate = float(spec["rate_per_s"])
        gaps = rng.exponential(1.0 / rate, len(queries))
        due = out.start + np.cumsum(gaps)
    elif spec["arrivals"] != "closed":
        raise ValueError(f"unknown arrivals {spec['arrivals']!r}")

    def submit(at=None):
        nonlocal nxt
        v = int(queries[nxt])
        nxt += 1
        with traces.span("submit"):
            rid = svc.submit(v, arrival=at)
        pending[rid] = (v, at if at is not None else clock())

    if not poisson:
        for _ in range(min(int(spec["clients"]), len(queries))):
            submit()
    drain_until = None
    while True:
        now = clock()
        if poisson:
            while nxt < len(queries) and due[nxt] <= now < out.close:
                submit(at=float(due[nxt]))
            if nxt >= len(queries) and now < out.close:
                raise RuntimeError("the open loop ran out of queries")
        if now >= out.close and drain_until is None:
            drain_until = now + float(spec.get("drain_s", 60.0))
        if not pending and (now >= out.close or (
                not poisson and nxt >= len(queries))):
            break
        if drain_until is not None and now > drain_until:
            out.unanswered = len(pending)
            break
        with traces.span("poll"):
            answers = svc.poll()
        t = clock()
        for a in answers:
            v, sent = pending.pop(a.request_id)
            out.vertex.append(v)
            out.sent.append(sent)
            out.done.append(t)
            out.top_v.append(np.asarray(a.top_vertices))
            out.top_s.append(
                np.asarray(a.top_scores) if not a.rejected else None)
            if not poisson and t < out.close and nxt < len(queries):
                submit()
        if not answers:
            time.sleep(0.0005)
    out.end = clock()
    return out


class BuildWindow:
    def __init__(self):
        self.calls = 0
        self.rows = 0
        self.sources = []
        self.sample_rows = []
        self.start = self.close = self.end = 0.0


def build(call, n: int, rng, spec: dict, seconds: float, sample_per_call: int,
          clock=time.perf_counter):
    """Call ``call(sources)`` (which returns the built ``PPRIndex``) on
    successive slices until ``seconds`` have passed; keeps
    ``sample_per_call`` rows of each call, drawn from ``rng``."""
    import jax
    import jax.numpy as jnp

    out = BuildWindow()
    per_call = int(spec["sources_per_call"])
    offset = int(rng.integers(n))
    out.start = clock()
    out.close = out.start + seconds
    kept = []
    while True:
        sources = (offset + np.arange(per_call)) % n
        offset = (offset + per_call) % n
        with traces.span("build_index"):
            index = call(sources)
            index.values.block_until_ready()
        out.calls += 1
        out.rows += per_call
        pick = rng.choice(sources, sample_per_call, replace=False)
        rows = jnp.asarray(pick, jnp.int32)
        kept.append((pick, index.values[rows], index.indices[rows]))
        del index
        if clock() >= out.close:
            break
    out.end = clock()
    for pick, vals, idxs in kept:
        vals, idxs = jax.device_get((vals, idxs))
        out.sources.append(pick)
        out.sample_rows.append((vals, idxs))
    return out
