"""Jaxpr auditing primitives: recursive equation iteration, pallas_call
block-spec extraction, and the contract predicates behind the jaxpr rules.

This generalizes what ``tests/jaxpr_utils.py`` + per-suite helpers used to
hand-roll (``tests/test_kernels.py::_pallas_block_specs`` etc.) into one
importable engine, so the kernel contract logic cannot drift across
copies.  Functions here return :class:`~repro.analysis.registry.Finding`
lists (for the runner) with thin ``assert_*`` wrappers (for pytest).

Memory-space vocabulary: a block mapping whose
``transformed_block_aval.memory_space`` stringifies to ``"any"``
(``pl.ANY``) stays in HBM and is DMA'd manually by the kernel; anything
else (``"None"`` = the default VMEM, or an explicit ``"vmem"``) is staged
into VMEM by the pipeline — which is exactly what the CSR / ``[n, L]``
index operands must never do.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.extend.core as jcore

from repro.analysis.registry import Finding

Jaxpr = Any          # jax.extend.core.Jaxpr or ClosedJaxpr
BlockSpecs = List[Tuple[Tuple[Optional[int], ...], str]]


def iter_eqns(jaxpr) -> Iterator[Any]:
    """Yield every equation in ``jaxpr``, recursing into sub-jaxprs held in
    equation params (pjit bodies, scan/while bodies, shard_map bodies...).
    Accepts an open or closed jaxpr."""
    if isinstance(jaxpr, jcore.ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if isinstance(u, jcore.ClosedJaxpr):
                    yield from iter_eqns(u.jaxpr)
                elif isinstance(u, jcore.Jaxpr):
                    yield from iter_eqns(u)


def iter_outvars(jaxpr) -> Iterator[Tuple[Any, Any]]:
    """Yield ``(eqn, outvar)`` for every output var of every (nested) eqn —
    the provenance stream the dense-state rules scan for oversized arrays."""
    for eqn in iter_eqns(jaxpr):
        for var in eqn.outvars:
            yield eqn, var


def subjaxprs_of(jaxpr, primitive_name: str) -> List[Any]:
    """All sub-jaxprs belonging to equations of ``primitive_name`` (e.g.
    ``"shard_map"`` bodies: what runs *per device*)."""
    found: List[Any] = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != primitive_name:
            continue
        for v in eqn.params.values():
            vs = v if isinstance(v, (tuple, list)) else (v,)
            for u in vs:
                if isinstance(u, jcore.ClosedJaxpr):
                    found.append(u.jaxpr)
                elif isinstance(u, jcore.Jaxpr):
                    found.append(u)
    return found


def pallas_block_specs(fn, *args, **kwargs) -> BlockSpecs:
    """Trace ``fn(*args, **kwargs)`` and return every pallas_call operand /
    result block as ``(block_shape, memory_space_str)``.

    ``memory_space_str`` is ``"any"`` for HBM-resident operands the kernel
    DMAs manually, ``"None"`` for pipeline-staged VMEM blocks.
    """
    jaxpr = jax.make_jaxpr(functools.partial(fn, **kwargs))(*args)
    blocks: BlockSpecs = []
    for eqn in iter_eqns(jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        gm = eqn.params["grid_mapping"]
        for bm in gm.block_mappings:
            aval = bm.transformed_block_aval
            blocks.append((
                tuple(_block_dim(d) for d in bm.block_shape),
                str(aval.memory_space),
            ))
    return blocks


def _block_dim(d) -> Optional[int]:
    """One block-shape entry as an int (``None`` for a squeezed dim): the
    installed Pallas wraps sizes as ``Blocked(block_size=...)``."""
    d = getattr(d, "block_size", d)
    return d if isinstance(d, int) else None


def _block_elems(shape: Sequence[Optional[int]]) -> int:
    n = 1
    for d in shape:
        if isinstance(d, int):
            n *= d
    return n


def hbm_contract_findings(
    blocks: BlockSpecs,
    *,
    hbm_shapes: Iterable[Tuple[int, ...]],
    vmem_budget: int,
    rule: str = "hbm-residency",
    anchor: str = "",
) -> List[Finding]:
    """The kernel memory contract as findings:

    1. every shape in ``hbm_shapes`` must appear among the blocks with
       memory space ``"any"`` (HBM-resident, kernel-managed DMA);
    2. no ``hbm_shapes`` block may be staged into VMEM;
    3. every VMEM-staged block must hold <= ``vmem_budget`` elements.
    """
    findings: List[Finding] = []
    if not blocks:
        findings.append(Finding(
            rule=rule, file=anchor, line=0,
            message="no pallas_call found in traced entry point "
                    "(kernel contract cannot be audited)",
        ))
        return findings
    wanted = [tuple(s) for s in hbm_shapes]
    hbm_resident = [shape for shape, space in blocks if space == "any"]
    for shape in wanted:
        if shape not in hbm_resident:
            findings.append(Finding(
                rule=rule, file=anchor, line=0,
                message=f"operand block {shape} is not HBM-resident "
                        f"(expected memory_space=ANY; got blocks {blocks})",
            ))
    for shape, space in blocks:
        if space == "any":
            continue
        if tuple(shape) in wanted:
            findings.append(Finding(
                rule=rule, file=anchor, line=0,
                message=f"contract block {tuple(shape)} lowered into VMEM "
                        f"(memory_space={space!r}); must stay in HBM",
            ))
            continue
        elems = _block_elems(shape)
        if elems > vmem_budget:
            findings.append(Finding(
                rule=rule, file=anchor, line=0,
                message=f"VMEM block {tuple(shape)} holds {elems} elements, "
                        f"over the per-tile budget {vmem_budget}",
            ))
    return findings


def assert_hbm_contract(
    blocks: BlockSpecs,
    *,
    hbm_shapes: Iterable[Tuple[int, ...]],
    vmem_budget: int,
) -> None:
    """Pytest front door: raise AssertionError on any contract violation."""
    findings = hbm_contract_findings(
        blocks, hbm_shapes=hbm_shapes, vmem_budget=vmem_budget
    )
    if findings:
        raise AssertionError(
            "HBM residency contract violated:\n  "
            + "\n  ".join(f.message for f in findings)
        )


def replicated_index_findings(
    jaxpr,
    *,
    n: int,
    l: int,
    rule: str = "no-replicated-index",
    anchor: str = "",
) -> List[Finding]:
    """Scan every shard_map body (the per-device program) for an array of
    shape ``[..., >=n, >=l]`` — a replicated full-index block that would
    erase the sharded build's memory asymptotics.  ``n`` is the *global*
    vertex count; a legal per-shard block is ``[n/ep, L]``-sized."""
    findings: List[Finding] = []
    bodies = subjaxprs_of(jaxpr, "shard_map")
    if not bodies:
        findings.append(Finding(
            rule=rule, file=anchor, line=0,
            message="traced build step contains no shard_map "
                    "(sharded-build contract cannot be audited)",
        ))
        return findings
    for body in bodies:
        for eqn, var in iter_outvars(body):
            aval = var.aval
            shape = getattr(aval, "shape", ())
            if len(shape) < 2:
                continue
            if shape[-2] >= n and shape[-1] >= l:
                findings.append(Finding(
                    rule=rule, file=anchor, line=0,
                    message=f"per-device array {tuple(shape)} "
                            f"(primitive {eqn.primitive.name!r}) covers the "
                            f"full [{n}, {l}] index — replicated, not sharded",
                ))
    return findings


def assert_no_replicated_index(jaxpr, *, n: int, l: int) -> None:
    findings = replicated_index_findings(jaxpr, n=n, l=l)
    if findings:
        raise AssertionError(
            "replicated-index contract violated:\n  "
            + "\n  ".join(f.message for f in findings)
        )


def dense_state_findings(
    jaxpr,
    *,
    budget: int,
    floor: int,
    rule: str = "dense-state-bound",
    anchor: str = "",
    dtype_name: str = "float32",
) -> List[Finding]:
    """Flag any intermediate ``dtype_name`` array over ``budget`` elements.

    ``floor`` is the dense-state size the sparse path exists to avoid
    (``rows * n`` / ``Q * n``); the rule demands ``budget < floor`` so a
    budget inflation can never silently re-admit dense state ("teeth").
    """
    findings: List[Finding] = []
    if budget >= floor:
        findings.append(Finding(
            rule=rule, file=anchor, line=0,
            message=f"budget {budget} >= dense floor {floor}: the bound has "
                    f"no teeth (would admit a dense [rows, n] intermediate)",
        ))
        return findings
    for eqn, var in iter_outvars(jaxpr):
        aval = var.aval
        dt = getattr(aval, "dtype", None)
        if dt is None or dt.name != dtype_name:
            continue
        size = int(getattr(aval, "size", 0))
        if size > budget:
            findings.append(Finding(
                rule=rule, file=anchor, line=0,
                message=f"{dtype_name}{list(aval.shape)} intermediate "
                        f"({size} elements, primitive "
                        f"{eqn.primitive.name!r}) exceeds the sparse-state "
                        f"budget {budget} (dense floor {floor})",
            ))
    return findings


def assert_dense_state_bound(jaxpr, *, budget: int, floor: int) -> None:
    findings = dense_state_findings(jaxpr, budget=budget, floor=floor)
    if findings:
        raise AssertionError(
            "dense-state-bound contract violated:\n  "
            + "\n  ".join(f.message for f in findings)
        )
