"""The TL001–TL005 rule implementations.

Each rule is a function ``(EntryProbe) -> list[Finding]``; rules skip
entries their annotations don't apply to.  See
:mod:`repro.analysis.lint.findings` for the catalogue and
``docs/ARCHITECTURE.md`` ("Checked invariants") for the incidents behind
each rule.
"""

from __future__ import annotations

import jax
import numpy as np

from repro.analysis import hlo
from repro.analysis.lint.entries import EntryProbe
from repro.analysis.lint.findings import Finding
from repro.analysis.lint.jaxpr_utils import (
    aval_bytes,
    iter_eqns,
    iter_eqns_scoped,
    iter_loops,
    reaches_comparison,
    stray_chain_reads,
)
from repro.precision import x64

#: reductions with an ``axes`` param (TL003)
_REDUCE_PRIMS = frozenset(
    {"reduce_sum", "reduce_prod", "reduce_max", "reduce_min", "reduce_and", "reduce_or"}
)


def check_fma_seam(entry: EntryProbe) -> list:
    """TL001: the compiled latency chain must match op-by-op evaluation.

    LLVM contracts an unprotected mul→add into an FMA *only* when it sees
    the whole chain at once — i.e. in the jitted graph, never in op-by-op
    eager dispatch.  So a bitwise diff between the two evaluations is a
    direct detector for a missing seam: any mismatch means the §3 product
    reached ``task_finish_time`` contraction-exposed.
    """
    if entry.latency_probe is None:
        return []
    fn, batches = entry.latency_probe
    with x64():
        # wrap in a fresh function object: jax's executable cache is keyed
        # on identity, and a stale entry (e.g. traced before the seam was
        # edited out) would mask a real regression
        jitted = jax.jit(lambda *args: fn(*args))
        for i, args in enumerate(batches):
            compiled = np.asarray(jitted(*args))
            eager = np.asarray(fn(*args))
            mismatches = int(np.count_nonzero(compiled != eager))
            if mismatches:
                return [
                    Finding(
                        code="TL001",
                        entry=entry.name,
                        symbol=f"batch{i}",
                        message=(
                            f"compiled latency chain differs from op-by-op "
                            f"evaluation in {mismatches}/{compiled.size} "
                            f"elements — the §3 product reaches "
                            f"task_finish_time without a contraction-"
                            f"blocking seam (guarded_comp_latency)"
                        ),
                    )
                ]
    return []


def _hlo_copy_evidence(entry: EntryProbe) -> str:
    """Trip-weighted ``copy`` traffic from the entry's optimized HLO.

    Secondary evidence attached to a confirmed TL002 finding: compiles
    the entry once and sums copy-instruction bytes weighted by
    :func:`repro.analysis.hlo.loop_multiplicities` trip counts.
    """
    if entry.hlo_fn_args is None:
        return ""
    fn, args = entry.hlo_fn_args
    try:
        with x64():
            text = jax.jit(fn).lower(*args).compile().as_text()
        comps, hlo_entry = hlo.parse_computations(text)
        if hlo_entry is None:
            return ""
        mult = hlo.loop_multiplicities(comps, hlo_entry)
        copied = 0.0
        for name, m in mult.items():
            for inst in comps[name].instructions:
                if inst.op == "copy":
                    copied += hlo._shape_list_bytes(inst.type_str) * m
        return (
            f"; optimized HLO shows ~{copied / 1e6:.2f} MB of trip-weighted "
            f"copy traffic"
        )
    except Exception:  # evidence is best-effort; the jaxpr finding stands
        return ""


def check_carry_copy(entry: EntryProbe) -> list:
    """TL002: scatter-updated loop-carried tables must be write-only.

    For every loop carry that is a large float table produced by a pure
    scatter write-chain from its own carried input, any *other* consumer
    of a chain member (a gather, slice, arithmetic) forces XLA to
    materialize a pre-write copy of the whole table once per trip — the
    PR 4/5 "copy cliff".  Live values must instead be reconstructed from
    small read-only side tables (see ``fused._apply_cache_events_lb``).
    """
    if entry.jaxpr is None:
        return []
    findings = []
    evidence = None
    for loop in iter_loops(entry.jaxpr.jaxpr):
        # the cliff is about *nested* loops (the per-iteration rank loops):
        # a top-level batching scan reads and rewrites its carries once per
        # training iteration by design
        if loop.depth < 1:
            continue
        for invar, outvar in loop.carries:
            aval = invar.aval
            if getattr(aval, "ndim", 0) < 3:
                continue
            if getattr(aval, "dtype", None) is None or aval.dtype.kind != "f":
                continue
            strays = stray_chain_reads(loop.body, invar, outvar)
            if not strays:
                continue
            if evidence is None:
                evidence = _hlo_copy_evidence(entry)
            reads = ", ".join(sorted({p for p, _ in strays}))
            findings.append(
                Finding(
                    code="TL002",
                    entry=entry.name,
                    symbol=f"{loop.path}:{aval}",
                    message=(
                        f"scatter-carried table {aval} is also read inside "
                        f"its loop by [{reads}] — defeats in-place carry "
                        f"aliasing (one full-table copy per trip)"
                        f"{evidence}"
                    ),
                )
            )
    return findings


def check_pad_variant_reduce(entry: EntryProbe) -> list:
    """TL003: reductions over width-bucket padded axes need mask evidence.

    XLA reductions are NOT pad-length invariant (lane grouping changes
    with the static shape), so every reduction or matmul contraction over
    a ``width_bucket`` padded axis must consume data masked by an
    ``iota < widths``-style comparison — otherwise the pad rows' values
    (gather-clamped copies of real rows) silently enter the sum.
    """
    if entry.jaxpr is None or not entry.padded_axis_sizes:
        return []
    sizes = set(entry.padded_axis_sizes)
    findings = []
    for eqn, scope, path in iter_eqns_scoped(entry.jaxpr.jaxpr):
        name = eqn.primitive.name
        if name in _REDUCE_PRIMS:
            operand = eqn.invars[0]
            shape = getattr(operand.aval, "shape", ())
            padded = [
                ax
                for ax in eqn.params.get("axes", ())
                if ax < len(shape) and shape[ax] in sizes
            ]
            if padded and not reaches_comparison(scope, operand):
                findings.append(
                    Finding(
                        code="TL003",
                        entry=entry.name,
                        symbol=f"{path}/{name}:{operand.aval}",
                        message=(
                            f"{name} over padded axis "
                            f"{padded} of {operand.aval} has no mask "
                            f"evidence (no <=-style comparison upstream)"
                        ),
                    )
                )
        elif name == "dot_general":
            (lc, rc), _ = eqn.params["dimension_numbers"]
            lhs, rhs = eqn.invars[0], eqn.invars[1]
            lshape = getattr(lhs.aval, "shape", ())
            padded = [d for d in lc if d < len(lshape) and lshape[d] in sizes]
            if padded and not (
                reaches_comparison(scope, lhs) or reaches_comparison(scope, rhs)
            ):
                findings.append(
                    Finding(
                        code="TL003",
                        entry=entry.name,
                        symbol=f"{path}/{name}:{lhs.aval}",
                        message=(
                            f"matmul contraction over padded axis {padded} "
                            f"of {lhs.aval} has no mask evidence on either "
                            f"operand"
                        ),
                    )
                )
    return findings


def check_dtype_leak(entry: EntryProbe) -> list:
    """TL004: strong dtypes in loop carries / entry outputs + kernel contract.

    A weak-typed carry or output means a python-scalar-promoted value
    reached a persistent buffer — the next arithmetic against it can
    re-promote and silently change the iterate dtype.  Kernel entries
    additionally pin their traced output dtypes to the declared
    ``FusedKernels.value_dtype`` (the fused engine sizes its in-flight
    buffers with it).
    """
    if entry.jaxpr is None:
        return []
    findings = []
    for loop in iter_loops(entry.jaxpr.jaxpr):
        for invar, _ in loop.carries:
            aval = invar.aval
            if (
                getattr(aval, "ndim", 0) == 0
                and getattr(aval, "dtype", None) is not None
                and aval.dtype.kind in "iub"
            ):
                # fori_loop/while counters are weak int scalars by jax
                # construction; the leak class is float/array carries
                continue
            if getattr(invar.aval, "weak_type", False):
                findings.append(
                    Finding(
                        code="TL004",
                        entry=entry.name,
                        symbol=f"{loop.path}:carry:{invar.aval}",
                        message=(
                            f"loop carry {invar.aval} is weakly typed — "
                            f"initialize with an explicit dtype"
                        ),
                    )
                )
    for i, aval in enumerate(entry.jaxpr.out_avals):
        if getattr(aval, "weak_type", False):
            findings.append(
                Finding(
                    code="TL004",
                    entry=entry.name,
                    symbol=f"output[{i}]:{aval}",
                    message=f"entry output {i} ({aval}) is weakly typed",
                )
            )
    if entry.declared_output_dtypes is not None:
        outs = entry.jaxpr.out_avals
        for i, want in enumerate(entry.declared_output_dtypes):
            if i >= len(outs):
                break
            got = getattr(outs[i], "dtype", None)
            if got is not None and np.dtype(got) != np.dtype(want):
                findings.append(
                    Finding(
                        code="TL004",
                        entry=entry.name,
                        symbol=f"output[{i}]:{outs[i]}",
                        message=(
                            f"kernel output {i} is {got}, declared "
                            f"value_dtype is {np.dtype(want)} — a "
                            f"float64<->float32 leak into the engine's "
                            f"value buffers"
                        ),
                    )
                )
    return findings


def check_cond_capture(entry: EntryProbe, min_capture_bytes: int = 16384) -> list:
    """TL005: no ``lax.cond`` deep in rank loops capturing large buffers.

    Inside a loop, each ``cond`` branch invocation copies its operands on
    the CPU thunk runtime (~9 ms per event rank for the §5 value table in
    PR 4's first attempt).  Conds at the training-scan body level
    (``depth <= cond_depth_threshold``) are per-iteration branches and
    exempt; deeper conds must not take operands at or above
    ``min_capture_bytes``.
    """
    if entry.jaxpr is None:
        return []
    findings = []
    for eqn, path, depth in iter_eqns(entry.jaxpr.jaxpr):
        if eqn.primitive.name != "cond":
            continue
        if depth <= entry.cond_depth_threshold:
            continue
        big = [
            v.aval
            for v in eqn.invars[1:]
            if hasattr(v, "aval") and aval_bytes(v.aval) >= min_capture_bytes
        ]
        if big:
            largest = max(big, key=aval_bytes)
            findings.append(
                Finding(
                    code="TL005",
                    entry=entry.name,
                    symbol=f"{path}/cond:{largest}",
                    message=(
                        f"lax.cond at loop depth {depth} captures "
                        f"{len(big)} large buffer(s) (largest {largest}, "
                        f"{aval_bytes(largest)} bytes) — each trip copies "
                        f"them on the thunk runtime"
                    ),
                )
            )
    return findings


#: rule code -> implementation, in reporting order
ALL_RULES = (
    ("TL001", check_fma_seam),
    ("TL002", check_carry_copy),
    ("TL003", check_pad_variant_reduce),
    ("TL004", check_dtype_leak),
    ("TL005", check_cond_capture),
)
