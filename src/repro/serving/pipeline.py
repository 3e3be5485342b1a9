"""Async serving pipeline: multi-batch in-flight dispatch + completion queue.

JAX dispatch is asynchronous — a jitted call returns device arrays as soon
as the work is *enqueued* on the device stream.  The old ``poll()`` threw
that away by calling ``block_until_ready()`` per batch, so host buffering,
device compute, and top-k readout ran strictly in series.  This module
splits serving into two phases:

* **dispatch** — drain the request buffer, pad to a stable jit shape,
  launch ``engine.query_topk_async`` (one fused XLA computation, no sync),
  and push a :class:`PendingBatch` ticket holding the device arrays plus
  request metadata onto a bounded :class:`CompletionQueue`;
* **harvest** — pop tickets whose arrays report ready
  (``jax.Array.is_ready``), slice off the pad rows, and materialize only
  the ``n_real`` top-k rows to the host.

The queue depth bounds how many batches are in flight at once (device
memory for ``depth`` result buffers plus their transient scratch); when
the queue is full the dispatcher harvests the head *blocking* before
launching more, which is the natural backpressure.  ``depth=1`` makes
every dispatch wait for the previous batch — exactly the old blocking
behavior — and is the baseline the serving benchmark compares against.

``dispatch="legacy"`` additionally routes through the eager
``engine.query_topk`` + ``block_until_ready`` path (today's code), so the
benchmark can separate the fused-dispatch win from the pipelining win.
"""

from __future__ import annotations

import collections
import dataclasses
import time
import warnings
from typing import Callable, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.batching import Request, RequestBuffer


@dataclasses.dataclass
class PipelineConfig:
    depth: int = 4                # max batches in flight (1 = blocking)
    dispatch: str = "fused"       # fused (query_topk_async) | legacy
                                  # (eager query_topk + block, PR-5 behavior)
    reuse_buffers: bool = True    # ring harvested result buffers back into
                                  # dispatch (donated to the fused query),
                                  # so a steady-state loop allocates no new
                                  # per-dispatch result arrays
    stall_timeout_s: Optional[float] = None  # stuck-ticket watchdog: a
                                  # head-of-queue batch still not ready this
                                  # long after dispatch counts as stalled
                                  # (stats["stalled"] + one warning per
                                  # ticket).  None disables the watchdog.

    def __post_init__(self):
        if self.depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {self.depth}")
        if self.dispatch not in ("fused", "legacy"):
            raise ValueError(f"unknown dispatch {self.dispatch!r}")
        if self.stall_timeout_s is not None and self.stall_timeout_s <= 0:
            raise ValueError(
                f"stall_timeout_s must be positive, got {self.stall_timeout_s}"
            )


@dataclasses.dataclass
class PendingBatch:
    """One in-flight batch: device arrays + the metadata needed to turn
    them into answers later.  Holding this ticket is what keeps the result
    buffers alive; nothing here has synced with the device."""
    seq: int
    requests: List[Request]
    padded: int
    values: jax.Array             # [padded, k] f32, possibly unfinished
    indices: jax.Array            # [padded, k] i32
    dispatched_at: float
    # cache epoch at dispatch time (AnswerCache.epoch via the pipeline's
    # epoch_fn).  A ticket computed under an older epoch predates some
    # invalidate()/apply_updates() and must not be absorbed into the cache
    # — the invalidate-vs-in-flight race fix.  Answers are still correct to
    # *return* (the request was accepted before the update).
    epoch: int = 0
    stall_warned: bool = False    # watchdog fired for this ticket (each
                                  # stuck batch counts/warns exactly once)

    def is_ready(self) -> bool:
        """Non-blocking completion probe via ``jax.Array.is_ready``."""
        try:
            return bool(self.values.is_ready() and self.indices.is_ready())
        except AttributeError:  # plain numpy (stub engines in tests)
            return True


@dataclasses.dataclass
class CompletedBatch:
    """A harvested batch: host arrays sliced to the real rows."""
    seq: int
    requests: List[Request]
    padded: int
    values: np.ndarray            # [n_real, k]
    indices: np.ndarray           # [n_real, k]
    dispatched_at: float
    completed_at: float
    epoch: int = 0                # cache epoch stamped at dispatch


class CompletionQueue:
    """Bounded FIFO of in-flight batches.  On a single device stream XLA
    completes computations in dispatch order, so harvesting from the head
    only is both correct and optimal."""

    def __init__(self, depth: int):
        self.depth = depth
        self._q: Deque[PendingBatch] = collections.deque()

    def __len__(self) -> int:
        return len(self._q)

    def full(self) -> bool:
        return len(self._q) >= self.depth

    def push(self, ticket: PendingBatch) -> None:
        if self.full():
            raise RuntimeError(
                f"completion queue full (depth={self.depth}); harvest first"
            )
        self._q.append(ticket)

    def pop(self, block: bool = False) -> Optional[PendingBatch]:
        """Pop the head ticket if finished (or unconditionally when
        ``block``); returns ``None`` when nothing is harvestable."""
        if not self._q:
            return None
        head = self._q[0]
        if not block and not head.is_ready():
            return None
        self._q.popleft()
        return head

    def head(self) -> Optional[PendingBatch]:
        """Peek the oldest in-flight ticket (watchdog probe; no pop)."""
        return self._q[0] if self._q else None


class ServingPipeline:
    """Glue between a :class:`RequestBuffer` and a query engine.

    Owns the dispatch sequence counter (folded into the engine's config
    seed key so Monte-Carlo answers replay identically at any depth), the
    completion queue, and the pipeline telemetry the benchmark reads.
    """

    def __init__(self, engine, buffer: RequestBuffer, cfg: PipelineConfig,
                 clock: Optional[Callable[[], float]] = None,
                 epoch_fn: Optional[Callable[[], int]] = None):
        self.engine = engine
        self.buffer = buffer
        self.cfg = cfg
        self.clock = clock or time.monotonic
        # reads the cache epoch at dispatch time (None = always epoch 0)
        self.epoch_fn = epoch_fn
        self.queue = CompletionQueue(cfg.depth)
        self._seq = 0
        # buffer_wait_s: summed over dispatched requests, the time from
        # arrival to the dispatch that drained it (on ``clock``)
        self.stats: Dict[str, float] = dict(
            dispatched=0, harvested=0, queue_full_stalls=0, in_flight_peak=0,
            buffers_allocated=0, buffers_reused=0, stalled=0,
            buffer_wait_s=0.0,
        )
        # padded batch width -> count; the benchmark's batch-size histogram
        self.batch_hist: Dict[int, int] = collections.Counter()
        # per-shape ring of harvested result buffers, re-donated to the
        # next dispatch of the same padded width: once every shape has been
        # seen ``depth`` times the steady state performs no per-dispatch
        # result allocation at all (only jax arrays ring — stub engines
        # returning numpy never populate it)
        self._ring: Dict[int, Deque] = {}

    @property
    def in_flight(self) -> int:
        return len(self.queue)

    # -- dispatch phase ------------------------------------------------------
    def _should_dispatch(self, force: bool) -> bool:
        if not len(self.buffer):
            return False
        if force or self.buffer.size_ready():
            return True
        # Deadline-fired batches only launch into an *idle* pipeline: on a
        # serialized device stream a partial batch dispatched behind another
        # batch starts no sooner, but its pad rows burn capacity.  Deferring
        # it lets the buffer keep filling while the device works, so the
        # next dispatch carries more real rows per launch.
        return self.in_flight == 0 and self.buffer.ready()

    def dispatch(self, force: bool = False) -> List[CompletedBatch]:
        """Drain-and-launch until the buffer is quiet.  Returns any batches
        that had to be harvested to make room (queue-full backpressure) —
        callers must not drop them."""
        out: List[CompletedBatch] = []
        while self._should_dispatch(force):
            out.extend(self._dispatch_one())
        return out

    def _batch_arrays(self, requests: List[Request], padded: int):
        """Marshal a drained batch into the engine's input arrays.

        With the engine configured for seed sets (``config.max_seeds > 1``)
        every request — single-vertex or not — becomes one ``[S_max]`` row
        of (seeds, weights), weight-0 padded; pad *rows* are all-zero
        weights, which the engine's normalization turns into all-zero
        answers.  Single-vertex engines keep the historical 1-D vertex
        vector (stub engines in tests rely on that call shape).
        """
        max_seeds = getattr(
            getattr(self.engine, "config", None), "max_seeds", 1
        )
        if max_seeds <= 1:
            # contract: allow(host-sync): marshals host-side Request
            # objects into the dispatch batch; nothing here is a device
            # array yet
            verts = np.array([r.vertex for r in requests], dtype=np.int32)
            if padded > len(requests):  # pad with vertex 0
                verts = np.concatenate(
                    [verts, np.zeros(padded - len(requests), np.int32)]
                )
            return verts, None
        seeds = np.zeros((padded, max_seeds), np.int32)
        weights = np.zeros((padded, max_seeds), np.float32)
        for j, r in enumerate(requests):
            if r.seeds is not None:
                s = r.seeds[:max_seeds]
                seeds[j, : len(s)] = s
                weights[j, : len(s)] = r.weights[: len(s)]
            else:
                seeds[j, 0] = r.vertex
                weights[j, 0] = 1.0
        return seeds, weights

    def _dispatch_one(self) -> List[CompletedBatch]:
        out: List[CompletedBatch] = []
        if self.queue.full():  # backpressure: block on the oldest batch
            self.stats["queue_full_stalls"] += 1
            out.append(self._complete(self.queue.pop(block=True)))
        requests, padded = self.buffer.drain()
        with TraceAnnotation("ppr.dispatch", seq=self._seq,
                             rows=len(requests), padded=padded):
            now = self.clock()
            self.stats["buffer_wait_s"] += sum(now - r.arrival
                                               for r in requests)
            ticket = self._launch(requests, padded)
        self._seq += 1
        self.queue.push(ticket)
        self.stats["dispatched"] += 1
        self.stats["in_flight_peak"] = max(
            self.stats["in_flight_peak"], len(self.queue)
        )
        self.batch_hist[padded] += 1
        return out

    def _launch(self, requests: List[Request], padded: int) -> PendingBatch:
        """Launch one drained batch on the engine; returns its ticket."""
        verts, weights = self._batch_arrays(requests, padded)
        if self.cfg.dispatch == "legacy":
            if weights is None:
                vals, idx = self.engine.query_topk(jnp.asarray(verts))
            else:
                vals, idx = self.engine.query_topk(
                    jnp.asarray(verts), weights=jnp.asarray(weights)
                )
            # contract: allow(host-sync): the legacy dispatch mode IS the
            # blocking baseline the async pipeline is benchmarked against
            vals.block_until_ready()
        else:
            kwargs = {}
            if weights is not None:
                kwargs["weights"] = jnp.asarray(weights)
            if self.cfg.reuse_buffers:
                ring = self._ring.get(padded)
                if ring:
                    kwargs["out"] = ring.popleft()
                    self.stats["buffers_reused"] += 1
                else:
                    self.stats["buffers_allocated"] += 1
            vals, idx = self.engine.query_topk_async(
                verts, key=self.engine.dispatch_key(self._seq), **kwargs
            )
        return PendingBatch(
            self._seq, requests, padded, vals, idx, self.clock(),
            epoch=self.epoch_fn() if self.epoch_fn is not None else 0,
        )

    # -- completion phase ----------------------------------------------------
    def harvest(self, drain: bool = False) -> List[CompletedBatch]:
        """Pop finished batches from the queue head.  ``drain`` blocks until
        *everything* in flight has completed (flush semantics); otherwise
        only ready batches are taken and the call never syncs."""
        out: List[CompletedBatch] = []
        while len(self.queue):
            ticket = self.queue.pop(block=drain)
            if ticket is None:
                self._watch_stall()
                break
            out.append(self._complete(ticket))
        return out

    def _watch_stall(self) -> None:
        """Stuck-ticket watchdog: the head batch has had the device stream
        to itself since dispatch, so an age past ``stall_timeout_s`` means
        the stream is wedged (deadlocked collective, runaway kernel, host
        callback hang) — surface it instead of polling forever silently.
        Detection only: the ticket stays in flight (harvest with
        ``drain=True`` still blocks on it), but the counter/warning give
        load harnesses and operators a tripwire."""
        if self.cfg.stall_timeout_s is None:
            return
        head = self.queue.head()
        if head is None or head.stall_warned:
            return
        age = self.clock() - head.dispatched_at
        if age >= self.cfg.stall_timeout_s:
            head.stall_warned = True
            self.stats["stalled"] += 1
            warnings.warn(
                f"serving pipeline batch seq={head.seq} "
                f"({len(head.requests)} requests) has been in flight for "
                f"{age:.3f}s (stall_timeout_s="
                f"{self.cfg.stall_timeout_s}) — device stream may be stuck",
                RuntimeWarning,
                stacklevel=3,
            )

    def flush(self) -> List[CompletedBatch]:
        """Dispatch whatever is buffered, then block for all of it."""
        out = self.dispatch(force=True)
        out.extend(self.harvest(drain=True))
        return out

    def _complete(self, ticket: PendingBatch) -> CompletedBatch:
        with TraceAnnotation("ppr.harvest", seq=ticket.seq):
            n_real = len(ticket.requests)
            # pad rows never reach answers or stats: slice them off on
            # device so only the real rows' top-k is materialized on the host
            # contract: allow(host-sync): post-is_ready harvest — the
            # ticket's arrays are already resident when _complete runs, so
            # these copies never stall the dispatch thread
            vals = np.asarray(ticket.values[:n_real])
            # contract: allow(host-sync): post-is_ready harvest (see above)
            idx = np.asarray(ticket.indices[:n_real])
            self.stats["harvested"] += 1
            if (
                self.cfg.reuse_buffers
                and self.cfg.dispatch == "fused"
                and hasattr(ticket.values, "is_ready")  # jax arrays only
            ):
                # the host copies above are independent of the device
                # buffers, so the full-width result arrays go back in the
                # ring to be donated to the next dispatch of this width
                self._ring.setdefault(
                    ticket.padded, collections.deque()
                ).append((ticket.values, ticket.indices))
            return CompletedBatch(
                ticket.seq, ticket.requests, ticket.padded, vals, idx,
                ticket.dispatched_at, self.clock(), epoch=ticket.epoch,
            )
