"""Pass 1 — collective lint over jaxprs.

The coordinator in the reference exists to catch collectives submitted in
different orders or with mismatched shapes at *runtime* (stall inspector,
controller validation). Under XLA the whole collective schedule is visible
*before* execution: ``jax.make_jaxpr`` of a train step exposes every
``psum`` / ``all_gather`` / ``ppermute`` / ``all_to_all`` the step will
issue, including those buried inside ``pjit`` / ``scan`` / ``while`` /
``shard_map`` sub-jaxprs. This module walks that structure and checks:

 - every collective's axis names exist in the active mesh
   (:data:`RULE_UNKNOWN_AXIS`);
 - every ``ppermute`` permutation is a complete bijection over its axis —
   a duplicate source/destination is rejected, and a hole (a rank that
   never receives) is flagged unless every use of the result is masked
   through ``select_n`` (the guarded-partial-permute idiom the in-repo
   binomial-tree broadcast uses) (:data:`RULE_PPERMUTE`);
 - fused allreduce buckets (``concatenate`` feeding a ``psum``) stay
   within the fusion-buffer budget (:data:`RULE_FUSION_BUDGET`).

Cross-rank ordering (the deadlock lint) lives in ``analysis.ordering``:
SPMD jaxprs are order-identical across ranks by construction, so ordering
divergence is a property of the *eager named-op* path, linted by simulating
ranks against the tensor-name registry.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .findings import (
    Finding,
    RULE_FUSION_BUDGET,
    RULE_PPERMUTE,
    RULE_UNKNOWN_AXIS,
    SEVERITY_ERROR,
    SEVERITY_WARNING,
    apply_suppressions,
)

# Primitive-name vocabulary. jax names the replicated-tracing variants of
# psum/pbroadcast with a ``2`` suffix (shard_map check_rep/check_vma), and
# psum_scatter lowers to ``reduce_scatter``.
COLLECTIVE_PRIMITIVES = {
    "psum": "allreduce",
    "psum2": "allreduce",
    "pmax": "allreduce",
    "pmin": "allreduce",
    "ppermute": "ppermute",
    "pbroadcast": "broadcast",
    "all_gather": "allgather",
    "all_to_all": "alltoall",
    "reduce_scatter": "reducescatter",
    "axis_index": "axis_index",
}


@dataclass
class CollectiveSite:
    """One collective equation found in the (possibly nested) jaxpr."""

    primitive: str
    kind: str
    axes: Tuple[str, ...]
    params: Dict[str, Any]
    nbytes: int
    dtype: str
    path: str  # e.g. "pjit/shard_map/scan"
    # The jaxpr the equation lives in plus the equation itself, so checks
    # can inspect producers/consumers (fusion buckets, select_n guards).
    jaxpr: Any = None
    eqn: Any = None
    # Axis sizes visible at this site (from enclosing shard_map meshes).
    axis_sizes: Dict[str, int] = field(default_factory=dict)

    @property
    def location(self) -> str:
        return f"jaxpr:{self.path}/{self.primitive}"


def _axis_names(params: Dict[str, Any]) -> Tuple[str, ...]:
    axes = params.get("axes", params.get("axis_name", ()))
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def _aval_nbytes(aval: Any) -> int:
    try:
        size = int(math.prod(aval.shape)) if aval.shape else 1
        return size * aval.dtype.itemsize
    except Exception:  # noqa: BLE001 - abstract values without shape
        return 0


def _sub_jaxprs(value: Any) -> Iterable[Any]:
    """Yield any jaxpr-like objects inside an eqn param value (handles
    pjit's ClosedJaxpr, scan/shard_map's Jaxpr, cond's branch tuples)."""
    values = value if isinstance(value, (list, tuple)) else (value,)
    for item in values:
        if hasattr(item, "eqns"):
            yield item
        elif hasattr(item, "jaxpr") and hasattr(item.jaxpr, "eqns"):
            yield item.jaxpr


def collect_collectives(
    jaxpr: Any,
    path: str = "",
    axis_sizes: Optional[Dict[str, int]] = None,
) -> List[CollectiveSite]:
    """Recursively walk ``jaxpr`` (a Jaxpr or ClosedJaxpr) and return every
    collective equation, annotated with the axis sizes of any enclosing
    ``shard_map`` meshes."""
    if hasattr(jaxpr, "jaxpr"):  # ClosedJaxpr
        jaxpr = jaxpr.jaxpr
    axis_sizes = dict(axis_sizes or {})
    sites: List[CollectiveSite] = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMITIVES and name != "axis_index":
            invar = eqn.invars[0] if eqn.invars else None
            aval = getattr(invar, "aval", None)
            sites.append(
                CollectiveSite(
                    primitive=name,
                    kind=COLLECTIVE_PRIMITIVES[name],
                    axes=_axis_names(eqn.params),
                    params=dict(eqn.params),
                    nbytes=_aval_nbytes(aval) if aval is not None else 0,
                    dtype=str(getattr(aval, "dtype", "")),
                    path=path or "top",
                    jaxpr=jaxpr,
                    eqn=eqn,
                    axis_sizes=dict(axis_sizes),
                )
            )
        inner_sizes = axis_sizes
        mesh = eqn.params.get("mesh")
        if mesh is not None and hasattr(mesh, "shape"):
            inner_sizes = dict(axis_sizes)
            try:
                inner_sizes.update(
                    {str(k): int(v) for k, v in dict(mesh.shape).items()}
                )
            except Exception:  # noqa: BLE001 - AbstractMesh variants
                pass
        child_path = f"{path}/{name}" if path else name
        for sub in _sub_jaxprs_of_eqn(eqn):
            sites.extend(collect_collectives(sub, child_path, inner_sizes))
    return sites


def _sub_jaxprs_of_eqn(eqn: Any) -> Iterable[Any]:
    for value in eqn.params.values():
        yield from _sub_jaxprs(value)


def _mesh_axis_sizes(mesh: Any) -> Dict[str, int]:
    """Normalize a mesh spec — a jax ``Mesh``, a ``{name: size}`` dict, or
    None — into a name→size dict."""
    if mesh is None:
        return {}
    if isinstance(mesh, dict):
        return {str(k): int(v) for k, v in mesh.items()}
    shape = getattr(mesh, "shape", None)
    if shape is not None:
        try:
            return {str(k): int(v) for k, v in dict(shape).items()}
        except Exception:  # noqa: BLE001
            pass
    names = getattr(mesh, "axis_names", None)
    if names is not None:
        sizes = getattr(mesh, "axis_sizes", None) or ()
        return {
            str(n): int(s)
            for n, s in zip(names, sizes or [0] * len(names))
        }
    raise TypeError(f"cannot read axis sizes from mesh spec {mesh!r}")


def _check_axes(
    site: CollectiveSite, known: Dict[str, int]
) -> List[Finding]:
    out: List[Finding] = []
    for axis in site.axes:
        if axis not in known:
            out.append(
                Finding(
                    rule=RULE_UNKNOWN_AXIS,
                    severity=SEVERITY_ERROR,
                    message=(
                        f"{site.kind} over axis {axis!r} which is not an "
                        f"axis of the active mesh "
                        f"(known axes: {sorted(known) or 'none'})"
                    ),
                    location=site.location,
                    details={"axis": axis, "known_axes": sorted(known)},
                )
            )
    return out


def _is_select(eqn: Any) -> bool:
    """select_n, or a ``jit`` wrapper whose body is only select_n — how
    ``jnp.where`` appears in a jaxpr."""
    if eqn.primitive.name == "select_n":
        return True
    if eqn.primitive.name == "jit":
        for sub in _sub_jaxprs_of_eqn(eqn):
            if any(e.primitive.name != "select_n" for e in sub.eqns):
                return False
        return True
    return False


def _select_guarded(site: CollectiveSite) -> bool:
    """True when every consumer of the ppermute result in its jaxpr is a
    ``select_n`` — the masked-partial-permute idiom (e.g. the binomial
    broadcast), where holes cannot leak unreceived values."""
    outvars = {id(v) for v in site.eqn.outvars}
    consumed = False
    for eqn in site.jaxpr.eqns:
        if eqn is site.eqn:
            continue
        if any(id(v) in outvars for v in eqn.invars):
            consumed = True
            if not _is_select(eqn):
                return False
    # Unconsumed results also can't leak a hole into downstream values,
    # but an output-returned hole can — require at least one select_n
    # consumer OR no consumption at all with no jaxpr output.
    if not consumed:
        return not any(id(v) in outvars for v in site.jaxpr.outvars)
    return True


def _check_ppermute(
    site: CollectiveSite, known: Dict[str, int]
) -> List[Finding]:
    perm = site.params.get("perm") or ()
    pairs = [(int(s), int(d)) for s, d in perm]
    axis = site.axes[0] if site.axes else None
    n = site.axis_sizes.get(axis) or known.get(axis) or 0
    out: List[Finding] = []
    srcs = [s for s, _ in pairs]
    dsts = [d for _, d in pairs]
    problems: List[str] = []
    if len(set(srcs)) != len(srcs):
        dup = sorted({s for s in srcs if srcs.count(s) > 1})
        problems.append(f"duplicate source ranks {dup}")
    if len(set(dsts)) != len(dsts):
        dup = sorted({d for d in dsts if dsts.count(d) > 1})
        problems.append(f"duplicate destination ranks {dup}")
    if n:
        bad = sorted(
            {r for r in srcs + dsts if r < 0 or r >= n}
        )
        if bad:
            problems.append(f"ranks {bad} outside [0, {n})")
        holes = sorted(set(range(n)) - set(dsts))
        if holes and not problems and not _select_guarded(site):
            problems.append(
                f"ranks {holes} never receive (hole ⇒ silent hang on ICI) "
                "and the result is used unmasked"
            )
    if problems:
        out.append(
            Finding(
                rule=RULE_PPERMUTE,
                severity=SEVERITY_ERROR,
                message=(
                    f"ppermute over axis {axis!r} "
                    f"(size {n or 'unknown'}) is not a complete bijection: "
                    + "; ".join(problems)
                ),
                location=site.location,
                details={
                    "axis": axis or "",
                    "axis_size": n,
                    "perm": [list(p) for p in pairs],
                },
            )
        )
    return out


def _check_fusion_budget(
    site: CollectiveSite, threshold_bytes: Optional[int]
) -> List[Finding]:
    if not threshold_bytes or site.kind != "allreduce":
        return []
    # Only flag *fused buckets* (a concatenate feeding the psum): a single
    # large gradient legally owns an over-threshold bucket of its own.
    invar = site.eqn.invars[0] if site.eqn.invars else None
    producer = None
    for eqn in site.jaxpr.eqns:
        if invar is not None and any(v is invar for v in eqn.outvars):
            producer = eqn
            break
    if producer is None or producer.primitive.name != "concatenate":
        return []
    if site.nbytes <= threshold_bytes:
        return []
    return [
        Finding(
            rule=RULE_FUSION_BUDGET,
            severity=SEVERITY_WARNING,
            message=(
                f"fused allreduce bucket is {site.nbytes} bytes, over the "
                f"{threshold_bytes}-byte fusion-buffer budget "
                f"({len(producer.invars)} leaves concatenated)"
            ),
            location=site.location,
            details={
                "bucket_bytes": site.nbytes,
                "threshold_bytes": threshold_bytes,
                "leaves": len(producer.invars),
            },
        )
    ]


def lint_jaxpr(
    closed_jaxpr: Any,
    *,
    mesh: Any = None,
    fusion_threshold_bytes: Optional[int] = None,
    divergence: bool = True,
    suppress: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Lint an already-traced jaxpr (``jax.make_jaxpr(fn)(*args)`` output,
    or any Jaxpr/ClosedJaxpr). ``divergence=True`` folds the Pass 4
    rank-divergence analysis in; ``suppress`` takes call-site suppression
    specs (``"rule"`` or ``"rule@location-glob"``)."""
    known = _mesh_axis_sizes(mesh)
    sites = collect_collectives(closed_jaxpr)
    findings: List[Finding] = []
    for site in sites:
        # Enclosing shard_map meshes extend the known-axis set: an axis
        # bound by the traced fn itself is valid even if the caller's
        # mesh spec doesn't name it — unless a mesh WAS provided, in
        # which case the step's axes must be a subset of it.
        local_known = dict(site.axis_sizes)
        if mesh is not None:
            local_known = known
        else:
            local_known = {**known, **site.axis_sizes}
        findings.extend(_check_axes(site, local_known))
        if site.primitive == "ppermute":
            findings.extend(_check_ppermute(site, local_known))
        findings.extend(_check_fusion_budget(site, fusion_threshold_bytes))
    if divergence:
        from .divergence import analyze_divergence

        findings.extend(analyze_divergence(closed_jaxpr))
    return apply_suppressions(findings, suppress)


def lint_step(
    fn: Any,
    *args: Any,
    mesh: Any = None,
    fusion_threshold_bytes: Optional[int] = None,
    divergence: bool = True,
    suppress: Optional[Sequence[str]] = None,
) -> List[Finding]:
    """Trace ``fn(*args)`` to a jaxpr and lint it. A trace-time unbound
    axis (jax's own NameError) is converted into an ``unknown-axis``
    finding instead of propagating, so the CLI reports it uniformly.

    The trace also feeds the guard-skip-agreement rule: the streamed
    registration and skip-agreement-seam ledgers are drained before and
    consumed after, so a step using streamed overlap under
    ``HOROVOD_GUARD_NONFINITE=skip`` without the agreement collective is
    flagged (docs/fault_tolerance.md). The Pass 4 rank-divergence
    analysis runs over the same trace (``divergence=False`` opts out);
    ``suppress`` filters findings at this call site
    (docs/static_analysis.md "Suppressions")."""
    import jax

    from ..guard import nonfinite as _nf
    from ..ops import fusion as _fusion
    from .preflight import check_guard_skip_agreement

    # Drain stale ledgers so this trace's counts are its own.
    _fusion.take_stream_registrations()
    _nf.take_seam_registrations()
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except NameError as exc:
        return apply_suppressions([
            Finding(
                rule=RULE_UNKNOWN_AXIS,
                severity=SEVERITY_ERROR,
                message=(
                    f"tracing failed with an unbound axis name: {exc}"
                ),
                location="trace",
                details={"exception": str(exc)},
            )
        ], suppress)
    stream_calls = _fusion.take_stream_registrations()["calls"]
    seam_calls = _nf.take_seam_registrations()
    findings = lint_jaxpr(
        closed, mesh=mesh, fusion_threshold_bytes=fusion_threshold_bytes,
        divergence=divergence,
    )
    findings.extend(
        check_guard_skip_agreement(stream_calls, seam_calls)
    )
    return apply_suppressions(findings, suppress)
