"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--only walks,...]

Output: ``name,us_per_call,derived`` CSV rows (one per measurement) on
stdout, plus one ``BENCH_<module>.json`` file per module whose ``run()``
returns a dict (positions/sec, peak state bytes, wall times, ...) — the
persisted perf trajectory, so speedups claimed in one PR are checkable in
the next.
Mapping to the paper:
  bench_accuracy   -> Figures 3-4 (MCFP vs MCEP)
  bench_verd       -> Figure 5    (VERD iterations vs index R)
  bench_preprocess -> Table 2     (offline indexing cost; analytic big rows)
  bench_query      -> Table 3 / Figure 6 (online batch-query latency)
  bench_walks      -> Section 3.1 (walk-engine throughput, legacy vs sparse)
  bench_kernels    -> Pallas kernel micro-benches + correctness gates
  bench_serving    -> Section 3.3 serving loop (open-loop QPS, pipeline depth)
  bench_cache      -> answer cache under Zipf hot-seed traffic (knee shift)
  bench_updates    -> evolving-graph maintenance (incremental vs rebuild)
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _json_safe(obj):
    """Coerce a benchmark result into JSON-serializable form (tuple keys
    become strings, arrays become lists, unknowns become repr strings)."""
    if isinstance(obj, dict):
        return {
            k if isinstance(k, str) else str(k): _json_safe(v)
            for k, v in obj.items()
        }
    if isinstance(obj, (list, tuple)):
        return [_json_safe(v) for v in obj]
    if isinstance(obj, (int, float, str, bool)) or obj is None:
        return obj
    if hasattr(obj, "tolist"):
        return _json_safe(obj.tolist())
    if hasattr(obj, "item"):
        return obj.item()
    return repr(obj)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller graphs / fewer points (CI mode)")
    ap.add_argument("--only", default=None,
                    help="comma-separated module suffixes to run")
    ap.add_argument("--json-dir", default=".",
                    help="directory for the BENCH_<module>.json files")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (bench_accuracy, bench_cache, bench_kernels,
                            bench_preprocess, bench_query, bench_serving,
                            bench_updates, bench_verd, bench_walks)
    modules = dict(
        accuracy=bench_accuracy, verd=bench_verd, preprocess=bench_preprocess,
        query=bench_query, walks=bench_walks, kernels=bench_kernels,
        serving=bench_serving, cache=bench_cache, updates=bench_updates,
    )
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("name,us_per_call,derived")
    t0 = time.time()
    failures = 0
    for name, mod in modules.items():
        print(f"# --- {name} ---", flush=True)
        t_mod = time.time()
        try:
            result = mod.run(fast=args.fast)
        except Exception as e:  # keep the suite going; report at the end
            failures += 1
            print(f"# FAILED {name}: {type(e).__name__}: {e}", flush=True)
            continue
        if isinstance(result, dict):
            import os

            payload = _json_safe(result)
            payload["_meta"] = dict(
                module=name, fast=bool(args.fast),
                wall_s=time.time() - t_mod,
            )
            # --fast measures CI-sized graphs: keep it from clobbering the
            # persisted full-size perf trajectory
            suffix = ".fast.json" if args.fast else ".json"
            path = os.path.join(args.json_dir, f"BENCH_{name}{suffix}")
            with open(path, "w") as f:
                json.dump(payload, f, indent=2, sort_keys=True)
            print(f"# wrote {path}", flush=True)
    print(f"# total_seconds={time.time() - t0:.1f} failures={failures}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
