"""Puts ``bench/`` and ``src/`` on the import path of the benchmark's tests,
and holds the tiny cells they drive on the CPU."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (os.path.join(ROOT, "src"), BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

import harness  # noqa: E402

# what a tiny cell keeps of its configuration: every width and guarantee,
# with the graph, the batch and the build slice cut to a CPU test's size.
# The engine's auto route serves a graph this small densely; the tiny cell
# holds the sparse route that the cells take at scale 20.
TINY_SCALE = 11
TINY_BATCH = 32
TINY_SLICE = 2048


def tiny_cell(name: str, root: str = ROOT) -> dict:
    cell = harness.find_cell(name, root)
    cell["config"].update(scale=TINY_SCALE, max_batch=TINY_BATCH,
                          reference_block=TINY_BATCH, frontier_path="sparse")
    traffic = cell["traffic"]
    if traffic["kind"] == "serve":
        traffic.update(clients=TINY_BATCH, queries_per_run=4096)
    else:
        traffic.update(sources_per_call=TINY_SLICE)
    return cell


def run_tiny(cell: dict, seed: int = 2**33 + 7, seconds: float = 1.0,
             **kwargs) -> dict:
    import time

    import jax

    return harness.run(cell, seed, seconds, False, time.perf_counter(),
                       jax.devices(), **kwargs)
