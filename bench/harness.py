"""The benchmark harness: finds a cell by name in ``BENCHMARK.json``, sets it
up from its seed, drives its measured window, checks what the window
produced against the plain reference, and assembles the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by name:
``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
``bench/metrics/<metric>.py`` (a ``read(record)`` function returning a
number, or ``None`` where the run has nothing for it to read).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# seed purposes: each input of a run is drawn from its own stream
GRAPH, INDEX, QUERIES, WARM, SAMPLE, ARRIVALS, LABELS = range(7)


# -- the cell, as data ---------------------------------------------------------
def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(name: str, root: str = ROOT) -> dict:
    """The workload ``name`` of ``root/BENCHMARK.json`` with its
    configuration, traffic mix and metrics resolved."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    return dict(
        name=name, chips=int(w["chips"]),
        config=load_json(os.path.join(root, configs[w["config"]]["file"])),
        traffic=load_json(os.path.join(
            root, "bench", "traffic", w["traffic"] + ".json")),
        end_to_end=[m for m in bench["end_to_end"]
                    if name in m.get("workloads", [name])],
        per_layer=[m for m in bench["per_layer"]
                   if name in m.get("workloads", [name])],
        metrics_dir=os.path.join(root, "bench", "metrics"),
    )


def metric_reader(metrics_dir: str, name: str):
    path = os.path.join(metrics_dir, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# -- the device -------------------------------------------------------------------
def require_accelerator(chips: int):
    """The devices of a TPU with at least ``chips`` chips; exits non-zero
    otherwise, before any work."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench: no TPU (JAX sees {devs[0].platform}); refusing to run")
    if len(devs) < chips:
        raise SystemExit(f"bench: needs {chips} chips, found {len(devs)}")
    return devs


def peaks(device_kind: str) -> dict:
    """The device's published peaks from ``bench/peaks.json``; a kind the
    table lacks is an error."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return table[device_kind]


def device_stamp(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs), memory_peak_bytes=int(peak))


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` at the checkout's root.  Every
    program is cached, however fast it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# -- set-up -----------------------------------------------------------------------
def make_graph(config: dict):
    """The Graph500 graph of the configuration's ``graph_seed``, structure
    and labels: host ``(src, dst)`` and the program's ``Graph``."""
    import graphgen
    from repro.core.graph import Graph

    src, dst, _ = graphgen.graph500_edges(
        graphgen.seed_key(config["graph_seed"], GRAPH),
        graphgen.seed_key(config["graph_seed"], LABELS),
        scale=config["scale"], edge_factor=config["edge_factor"],
        a=config["rmat_a"], b=config["rmat_b"], c=config["rmat_c"])
    return src, dst, Graph.from_edges(src, dst, n=1 << config["scale"])


def build_call(graph, config: dict, key):
    """``sources -> PPRIndex`` with the configuration's build arguments
    (``sources=None`` builds every row)."""
    from repro.core.index import build_index

    def call(sources=None, call_key=key):
        index, _ = build_index(
            graph, r=config["r"], l=config["index_l"], key=call_key,
            c=config["c"], max_steps=config["build_max_steps"],
            source_batch=config["build_source_batch"],
            compact_every=config["build_compact_every"], sources=sources)
        return index

    return call


def make_service(graph, index, config: dict):
    from repro.core.query import QueryConfig
    from repro.serving import PPRService, ServiceConfig
    from repro.serving.batching import BatchingConfig

    cfg = ServiceConfig(
        query=QueryConfig(
            mode="powerwalk", t_iterations=config["t_online"],
            c=config["c"], top_k=config["top_k"],
            frontier_k=config["frontier_k"],
            frontier_path=config["frontier_path"],
            hub_split_degree=config["hub_split_degree"]),
        batching=BatchingConfig(max_batch=config["max_batch"],
                                max_wait_s=config["max_wait_s"]))
    return PPRService(graph, index, cfg)


def with_control(config: dict, kind: str) -> dict:
    """The configuration with the control's one broken guarantee."""
    return {**config, **config["control"][kind]}


# -- the run ----------------------------------------------------------------------
def run(cell: dict, seed: int, seconds: float, trace: bool, t_start: float,
        devs, control: bool = False, log=sys.stderr) -> dict:
    """One run of ``cell``: set-up from ``seed``, the window of ``seconds``,
    the check against the reference.  Returns the result line's object.
    ``control`` runs the configuration's control in the program's place."""
    import jax

    import graphgen
    import traces
    import loadgen

    config, traffic = cell["config"], cell["traffic"]
    kind = traffic["kind"]
    if control:
        config = with_control(config, kind)
    rng = lambda purpose: np.random.default_rng([seed, purpose])
    t_phase = [time.perf_counter()]

    def phase(name: str, **fields) -> None:
        now = time.perf_counter()
        extra = "".join(f" {k}={v}" for k, v in fields.items())
        print(f"phase {name} {now - t_phase[0]:.3f}s{extra}", file=log,
              flush=True)
        t_phase[0] = now

    src, dst, graph = make_graph(config)
    phase("graph", n=graph.n, m=graph.m)
    out_deg = np.asarray(graph.out_deg)
    index_key = graphgen.seed_key(seed, INDEX)
    call = build_call(graph, config, index_key)
    compiles = traces.CompileCounter()

    if kind == "serve":
        index = call()
        index.values.block_until_ready()
        phase("build_index", rows=graph.n)
        svc = make_service(graph, index, config)
        # a closed loop fills every batch to its clients; an open loop may
        # dispatch any padded width, so each is warmed.  The service rings
        # its donated result buffers back into dispatch, so a width's
        # second batch runs a second program: two rounds of each.
        widths = ([traffic["clients"]] if traffic["arrivals"] == "closed"
                  else svc.cfg.batching.padded_shapes())
        for width in widths:
            loadgen.serve(
                svc, loadgen.warm_queries(rng(WARM), out_deg, 2 * width),
                {**traffic, "arrivals": "closed", "clients": width}, None)
        phase("warm", widths=len(widths), route=svc.frontier_path,
              frontier_k=svc.engine.frontier_k)
        # the graph's queries, each batch-sized block in an order of the
        # seed's own: every seed does the same work
        queries = loadgen.draw_queries(
            np.random.default_rng([config["graph_seed"], QUERIES]),
            out_deg, traffic, traffic["queries_per_run"])
        queries = loadgen.shuffle_blocks(rng(QUERIES), queries,
                                         config["max_batch"])
        batches0 = svc.stats["batches"]
        drive = lambda: loadgen.serve(svc, queries, traffic, seconds,
                                      rng=rng(ARRIVALS))
    elif kind == "build":
        sample = int(traffic["sample_rows_per_call"])
        loadgen.build(lambda s: call(s, jax.random.fold_in(index_key, 0)),
                      graph.n, rng(WARM), traffic, 0.0, sample)
        phase("warm")
        calls = iter(range(1, 1 << 30))
        drive = lambda: loadgen.build(
            lambda s: call(s, jax.random.fold_in(index_key, next(calls))),
            graph.n, rng(QUERIES), traffic, seconds, sample)
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")

    tmp = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(tmp)
    setup_s = time.perf_counter() - t_start
    with compiles.counting():
        with traces.span("window"):
            win = drive()
    phase("window", compiles=compiles.count)
    record = dict(kind=kind, compiles_in_window=compiles.count)
    if trace:
        jax.profiler.stop_trace()
        record["trace"] = traces.reduce_trace(traces.find_trace(tmp))
        shutil.rmtree(tmp, ignore_errors=True)
        phase("trace")
    stamp = device_stamp(devs)

    if kind == "serve":
        record["batches"] = svc.stats["batches"] - batches0
        e2e = serve_metrics(win)
        attempted = win.answered + win.unanswered
        failed = win.unanswered + sum(s is None for s in win.top_s)
    else:
        record["chunks"] = win.calls * -(-int(traffic["sources_per_call"])
                                         // config["build_source_batch"])
        e2e = dict(index_rows_per_s=win.rows / (win.end - win.start))
        attempted, failed = win.rows, 0
    # free the program's state before the reference runs on the device
    svc = index = drive = call = graph = None
    gc.collect()
    e2e["setup_s"] = setup_s

    checks = check(config, kind, src, dst, win, rng(SAMPLE))
    phase("reference")
    correct = failed == 0 and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=log, flush=True)

    result = dict(correct=bool(correct), attempted=int(attempted),
                  failed=int(failed))
    if trace:
        metrics = {}
        for m in cell["per_layer"]:
            value = metric_reader(cell["metrics_dir"], m["name"])(record)
            if value is not None:
                metrics[m["name"]] = dict(value=value, unit=m["unit"])
        t = record["trace"]
        stamp.update(busy_s=t["busy_s"], window_s=t["window_s"])
        result["breakdown"] = dict(device_ops=t["device_ops"],
                                   idle_gaps=t["idle_gaps"])
    else:
        metrics = {m["name"]: dict(value=e2e[m["name"]], unit=m["unit"])
                   for m in cell["end_to_end"]}
    result.update(metrics=metrics, device=stamp, checks=checks)
    return result


def serve_metrics(win) -> dict:
    lat = np.asarray(win.done) - np.asarray(win.sent)
    if win.unanswered:  # an answer that never came counts at the drain's end
        lat = np.concatenate([lat, np.full(win.unanswered,
                                           win.end - win.close)])
    return dict(
        answers_per_s=win.answered / (win.end - win.start),
        latency_p95_ms=float(np.percentile(lat, 95)) * 1e3,
    )


def check(config: dict, kind: str, src, dst, win, rng) -> dict:
    """The window's output against the plain reference: per compared row
    the squared L2 distance from exact PPR over the exact vector's squared
    norm, and of those the mean (``sq_err_mean``) and the largest
    (``sq_err_max``), each compared where the configuration's ``limits``
    for this kind of traffic give it a limit."""
    import reference

    ref = reference.Reference(
        src, dst, 1 << config["scale"], c=config["c"],
        iterations=config["reference_iterations"],
        block=config["reference_block"])
    limits = config["limits"][kind]
    if kind == "serve":
        rows = [i for i, s in enumerate(win.top_s) if s is not None]
        take = int(config["reference_rows"] or len(rows))
        if take < len(rows):
            rows = sorted(rng.choice(rows, take, replace=False))
        if not rows:  # nothing came back to compare: no number passes
            return {name: dict(value=None, limit=lim)
                    for name, lim in limits.items()}
        sq = ref.compare(
            np.asarray(win.vertex)[rows], np.stack([win.top_v[i] for i in rows]),
            np.stack([win.top_s[i] for i in rows]))
    else:
        sq = ref.compare(
            np.concatenate(win.sources),
            np.concatenate([i for _, i in win.sample_rows]),
            np.concatenate([v for v, _ in win.sample_rows]))
    values = dict(sq_err_mean=float(np.mean(sq)), sq_err_max=float(np.max(sq)))
    return {name: dict(value=values[name], limit=lim)
            for name, lim in limits.items()}
