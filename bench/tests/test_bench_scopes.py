"""The reduction of a trace by the program's named scopes and host spans,
and the join from a device operation to its scopes through the HLO that
the trace carries."""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_paths
import scopes
import traces
from bench_paths import TINY_BATCH, harness
from repro.core import query as query_mod

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# instructions no program scope can name: the plumbing of values between
# operations, and what the compiler adds on its own (an instruction with no
# op_name of a JAX primitive, such as the CPU backend's passes of a cumsum
# or a zero buffer it materializes for an inlined call)
GLUE = {"parameter", "constant", "tuple", "get-tuple-element", "bitcast",
        "copy", "copy-start", "copy-done"}
_COMPUTATION = re.compile(r"^(ENTRY )?(%[\w.-]+) .*\{$")
_CALLED = re.compile(
    r"(?:body|condition|true_computation|false_computation)=(%[\w.-]+)")
_OPCODE = re.compile(r"\s([a-z][\w-]*)\(")


def executed_instructions(hlo_text: str):
    """``(name, opcode, op_name)`` of each instruction of the computations a
    device runs as its own operations: the entry, loop bodies and
    conditions, conditional branches and called computations (not fusion
    bodies or reducers)."""
    computations, entry, current = {}, None, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            current = computations.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif line.startswith("}"):
            current = None
        elif current is not None and " = " in line:
            current.append(line.strip().removeprefix("ROOT "))
    seen, todo, out = {entry}, [entry], []
    while todo:
        for line in computations[todo.pop()]:
            name, _, rest = line.partition(" = ")
            opcode = _OPCODE.search(" " + rest).group(1)
            op_name = re.search(r'op_name="([^"]*)"', rest)
            out.append((name, opcode, op_name and op_name.group(1)))
            called = _CALLED.findall(rest)
            branches = re.search(r"branch_computations=\{([^}]*)\}", rest)
            if branches:
                called += [b.strip() for b in branches.group(1).split(",")]
            if opcode == "call":
                called += re.findall(r"to_apply=(%[\w.-]+)", rest)
            for c in called:
                if c not in seen:
                    seen.add(c)
                    todo.append(c)
    return out


def is_glue(opcode: str, op_name) -> bool:
    """No scope can name it: plumbing, or no JAX primitive in its op_name
    (none at all, or only the frames of jitted calls)."""
    frames = (op_name or "").split("/")
    return opcode in GLUE or all(f.startswith("jit(") or not f
                                 for f in frames)


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A CPU trace of the tiny serve cell's two programs: a one-chunk
    subset build and one batch through the donated-buffer serving program,
    on the routes the chip takes at scale 20 (streamed push, sort-based
    combine)."""
    cell = bench_paths.tiny_cell("g500s20-r100.serve-uniform")
    config = cell["config"]
    _, _, graph = harness.make_graph(config)
    call = harness.build_call(graph, config, jax.random.PRNGKey(0))
    out = str(tmp_path_factory.mktemp("trace"))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(query_mod, "SCATTER_COMBINE_BUDGET_BYTES", 0)
        with jax.profiler.trace(out):
            index = call(np.arange(64))
            engine = harness.make_service(graph, index, config).engine
            verts = np.flatnonzero(np.asarray(graph.out_deg) > 0)
            k = engine.effective_top_k
            vals, _ = engine.query_topk_async(
                verts[:TINY_BATCH].astype(np.int32),
                key=engine.dispatch_key(0),
                out=(jnp.zeros((TINY_BATCH, k)),
                     jnp.zeros((TINY_BATCH, k), jnp.int32)))
            vals.block_until_ready()
    return traces.find_trace(out)


@pytest.mark.parametrize("program, expected", [
    ("jit__fused_topk_into",
     {"ppr.push", "ppr.push.gather", "ppr.fold", "ppr.combine",
      "ppr.topk"}),
    ("jit_sparse_chunk_estimates",
     {"ppr.walk.step", "ppr.sketch_fold", "ppr.fold", "ppr.slot_compact",
      "ppr.topl"}),
    ("jit__assemble_rows", {"ppr.index_assemble"}),
])
def test_every_operation_of_the_programs_maps_to_a_scope(
        cpu_trace, program, expected):
    texts = [text for name, text in scopes.hlo_modules(cpu_trace).items()
             if name.startswith(program + "(")]
    assert texts
    unscoped, seen = [], set()
    for text in texts:
        by_name = scopes.instruction_scopes(text)
        for name, opcode, op_name in executed_instructions(text):
            if is_glue(opcode, op_name):
                continue
            assert by_name[name] == scopes.scope_path(op_name)
            if not by_name[name]:
                unscoped.append(f"{name} {opcode} {op_name}")
            seen.update(by_name[name])
    assert not unscoped
    assert seen == expected


def test_scope_path_keeps_order_and_drops_repeats():
    assert scopes.scope_path(
        "jit(f)/jit(g)/ppr.push/ppr.push/while/body/ppr.push.gather/"
        "vmap(ppr.fold)/sort") == ("ppr.push", "ppr.push.gather",
                                   "ppr.fold")
    assert scopes.scope_path("jit(f)/add") == ()


def test_reduce_synthetic_events(monkeypatch):
    push, fold = ("ppr.push",), ("ppr.push", "ppr.fold")
    devices = {"/device:TPU:0": [
        (0, 40, "%while.1", push), (5, 20, "%sort.2", fold),
        (10, 30, "%fusion.3", fold), (50, 60, "%fusion.4", ("ppr.combine",)),
        (60, 70, "%copy.5", ()), (150, 160, "%fusion.4", ())]}
    spans = [(0, 100, "bench.window"), (0, 3, "ppr.submit"),
             (40, 48, "ppr.dispatch"), (80, 120, "ppr.harvest"),
             (75, 130, "bench.poll")]
    monkeypatch.setattr(scopes, "read_scoped_events",
                        lambda path: (devices, spans))
    r = scopes.reduce_scopes("unused")
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(60e-9)
    assert r["scopes"] == {
        "ppr.combine": pytest.approx(10e-9), "ppr.fold": pytest.approx(25e-9),
        "ppr.push": pytest.approx(40e-9),
        "ppr.push/ppr.fold": pytest.approx(25e-9)}
    assert r["unscoped_s"] == pytest.approx(10e-9)
    assert r["host_spans"] == {"ppr.dispatch": [pytest.approx(8e-9), 1],
                               "ppr.harvest": [pytest.approx(20e-9), 1],
                               "ppr.submit": [pytest.approx(3e-9), 1]}
    assert r["device_ops"][0] == ["ppr.push %while.1", pytest.approx(40e-9)]
    assert ["%copy.5", pytest.approx(10e-9)] in r["device_ops"]
    # the innermost span over each gap's middle: ppr. and bench. alike
    assert r["idle_gaps"] == [["ppr.harvest", pytest.approx(30e-9)],
                              ["ppr.dispatch", pytest.approx(10e-9)]]


def test_reduce_probe_without_scopes_matches_busy_time():
    """The committed probe ran before the program had scopes: every
    operation is unscoped and the busy time is ``reduce_trace``'s."""
    path = os.path.join(DATA, "tpu_probe.xplane.pb")
    r = scopes.reduce_scopes(path)
    base = traces.reduce_trace(path)
    assert r["busy_s"] == pytest.approx(base["busy_s"], rel=1e-12)
    assert r["window_s"] == pytest.approx(base["window_s"], rel=1e-12)
    assert r["scopes"] == {} and r["host_spans"] == {}
    assert r["unscoped_s"] == pytest.approx(r["busy_s"], rel=1e-12)
    assert list(scopes.hlo_modules(path)) == [
        "jit__lambda(14884802333909295914)"]


def test_reduce_recorded_scoped_tpu_trace():
    """A trace recorded on a TPU v5 lite: a jitted program running
    ``frontier.fold_topk`` (``ppr.fold``) and a scaling inside ``ppr.push``,
    then a ``lax.top_k`` under ``ppr.combine/ppr.topk`` and one unscoped
    gather, called three times inside ``bench.window``, each call inside
    ``ppr.dispatch`` and its ``device_get`` inside ``ppr.harvest``."""
    path = os.path.join(DATA, "tpu_scopes.xplane.pb")
    assert os.path.getsize(path) < 1 << 20
    assert [m.partition("(")[0] for m in scopes.hlo_modules(path)] == [
        "jit_probe"]
    r = scopes.reduce_scopes(path)
    base = traces.reduce_trace(path)
    assert r["busy_s"] == pytest.approx(base["busy_s"], rel=1e-12)
    # the reduction of this file, pinned
    assert r["busy_s"] == pytest.approx(0.031122922, rel=1e-9)
    assert r["window_s"] == pytest.approx(0.037441316, rel=1e-9)
    assert r["scopes"] == {
        "ppr.combine": pytest.approx(2.5436e-05, rel=1e-9),
        "ppr.combine/ppr.topk": pytest.approx(2.5295e-05, rel=1e-9),
        "ppr.fold": pytest.approx(0.030542528, rel=1e-9),
        "ppr.push": pytest.approx(0.030542528, rel=1e-9),
        "ppr.push/ppr.fold": pytest.approx(0.030542528, rel=1e-9),
        "ppr.topk": pytest.approx(2.5295e-05, rel=1e-9)}
    assert r["unscoped_s"] == pytest.approx(0.000554958, rel=1e-6)
    assert r["host_spans"] == {
        "ppr.dispatch": [pytest.approx(0.00112796, rel=1e-9), 3],
        "ppr.harvest": [pytest.approx(0.036274036, rel=1e-9), 3]}
    label, seconds = r["device_ops"][0]
    assert label.startswith("ppr.fold %fusion fusion kCustom")
    assert {g[0] for g in r["idle_gaps"]} <= {"ppr.harvest", "ppr.dispatch",
                                              "idle"}
    assert r["idle_gaps"][0][0] == "ppr.harvest"
