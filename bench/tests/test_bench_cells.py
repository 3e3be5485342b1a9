"""Rehearsal of every cell on the CPU at a tiny size, through the harness's
own functions: set-up, warm-up, window, reference check and result line.
Also: a cell added as data alone runs, and ``run.py`` refuses to run
without a TPU."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_paths
from bench_paths import BENCH, ROOT, harness, run_tiny, tiny_cell

CELLS = [w["name"] for w in harness.load_json(
    os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_rehearsal(name):
    cell = tiny_cell(name)
    result = run_tiny(cell)
    assert list(result)[:3] == ["correct", "attempted", "failed"]
    assert list(result)[-1] == "checks"
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert set(result["checks"]) == set(
        cell["config"]["limits"][cell["traffic"]["kind"]])
    assert result["device"]["count"] >= 1
    json.dumps(result)


def test_per_layer_readers_follow_the_run_kind():
    record = dict(kind="serve", compiles_in_window=0, batches=4,
                  trace=dict(busy_s=2.0, window_s=8.0, idle_share=0.75))
    cell = harness.find_cell("g500s20-r100.serve-uniform")
    read = {m["name"]: harness.metric_reader(cell["metrics_dir"], m["name"])
            for m in cell["per_layer"]}
    assert read["idle_share.serve"](record) == pytest.approx(75.0)
    assert read["device_ms_per_batch.serve"](record) == pytest.approx(500.0)
    assert read["compiles_in_window.serve"](record) == 0
    build = harness.metric_reader(cell["metrics_dir"], "idle_share.build")
    assert build(record) is None
    chunk = harness.metric_reader(cell["metrics_dir"],
                                  "device_ms_per_chunk.build")
    assert chunk(dict(record, kind="build", chunks=8)) == pytest.approx(250.0)


def test_cell_added_as_data_alone(tmp_path):
    """A new configuration and traffic mix, given as files and a
    ``workloads`` entry, run through the harness unchanged: here an open
    loop of queries drawn by out-degree."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    config = json.loads((root / "bench/configs/g500s20-r100.json").read_text())
    config.update(name="g500s12-r100", scale=12)
    (root / "bench/configs/g500s12-r100.json").write_text(json.dumps(config))
    (root / "bench/traffic/serve-degree-open.json").write_text(json.dumps(dict(
        kind="serve", arrivals="poisson", rate_per_s=200.0,
        queries="out_degree", queries_per_run=4096, drain_s=60)))
    bench["configs"].append(dict(
        name="g500s12-r100", source="test", file="bench/configs/g500s12-r100.json",
        reduced=["scale"], why="test"))
    name = "g500s12-r100.serve-degree-open"
    bench["workloads"].append(dict(
        name=name, config="g500s12-r100", traffic="serve-degree-open",
        chips=1, why="test"))
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "g500s20-r100.serve-uniform" in m.get("workloads", []):
            m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.find_cell(name, str(root))
    assert cell["traffic"]["arrivals"] == "poisson"
    cell["config"].update(max_batch=bench_paths.TINY_BATCH,
                          reference_block=bench_paths.TINY_BATCH,
                          frontier_path="sparse")
    result = run_tiny(cell)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {
        "answers_per_s", "latency_p95_ms", "setup_s"}


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "g500s20-r100.build", "--seed", str(2**33), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("law", ["uniform_linked", "out_degree", "zipf"])
def test_query_laws_draw_linked_vertices_from_the_seed(law):
    import numpy as np

    import loadgen

    out_deg = np.array([0, 3, 0, 1, 9, 0, 2, 5])
    spec = dict(queries=law, zipf_s=1.1)
    draw = lambda: loadgen.draw_queries(  # noqa: E731
        np.random.default_rng([2**33, 2]), out_deg, spec, 4000)
    q = draw()
    np.testing.assert_array_equal(q, draw())
    assert (out_deg[q] > 0).all()
    counts = np.bincount(q, minlength=len(out_deg))
    if law == "out_degree":
        assert counts[4] > counts[3] * 5
    if law == "uniform_linked":
        assert counts[np.flatnonzero(out_deg)].min() > 600
    with pytest.raises(ValueError, match="unknown query law"):
        loadgen.draw_queries(np.random.default_rng(0), out_deg,
                             dict(queries="nope"), 1)
