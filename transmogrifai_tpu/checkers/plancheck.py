"""plancheck — jaxpr-level static cost analysis of fused programs.

Reference role: TransmogrifAI validates workflows *structurally* before any
data is touched (SURVEY §1, OpWorkflow.scala:265-323); the TM1xx-TM5xx
analyzers (opcheck.py, serve/validator.py) reproduce that.  This module adds
the *cost* half of the same guarantee for this port's fused programs: the
jit-fused device prefix of a :class:`~..workflow.plan.ColumnarTransformPlan`
or :class:`~..serve.plan.CompiledScoringPlan`, and the vmapped fold x grid
sweep programs — all of which are opaque XLA programs once traced.  Instead
of learning "this plan is memory-bound / recompile-happy / won't fit HBM" by
running it, the analyzer traces the program with ``jax.make_jaxpr`` on
zero-cost abstract specs (NO backend compile, NO device buffer beyond the
trace's baked constants) and walks the jaxpr to produce a
:class:`PlanCostReport`:

- **FLOPs** per primitive (dense contractions counted exactly from
  ``dot_general`` dimension numbers; solves/factorizations at their cubic
  counts; elementwise/reduction ops at one flop per element),
- **bytes read/written** per primitive (operand and result aval sizes — an
  upper bound: XLA fusion keeps many temporaries in registers, so the
  measured traffic is lower),
- **arithmetic intensity** per fused segment (the Pallas-kernel worklist:
  a segment under the threshold is bandwidth-bound on any accelerator),
- **peak live-buffer HBM estimate** per row bucket (linear-scan liveness
  over the jaxpr, constants included — the number the TM601 admission gate
  compares against the device budget),
- **collective / resharding op inventory** against the ambient mesh
  (``psum``/``all_gather``/``sharding_constraint``/... — TM603 under a
  single-host contract),
- a **recompile-hazard map**: input shapes the pow2/8192 bucket ladder
  cannot cover (data-dependent widths — TM602).

Diagnostics (TM6xx, checkers/diagnostics.py) surface through
``Workflow.validate(cost=True, hbm_budget=...)``,
``WorkflowModel.validate(serving=True, ...)``, ``cli lint --cost``, the
``train(hbm_budget=...)`` gate, and serving admission
(serve/validator.py:check_plan_admission).  Every entry point here runs
purely on abstract ``ShapeDtypeStruct`` specs: the whole pass adds ZERO
backend compiles (asserted in tests/test_plancheck.py with the compile
probe).
"""

from __future__ import annotations

import copy
import logging
import math
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..types import ColumnKind
from .diagnostics import Diagnostic, make_diagnostic

log = logging.getLogger(__name__)

#: memoized transform-plan reports keyed on (content fingerprint, bucket,
#: entry specs) — content-addressed, so stale entries are impossible and a
#: bounded FIFO is enough
_ANALYZE_MEMO: Dict[tuple, "PlanCostReport"] = {}
_ANALYZE_MEMO_LOCK = threading.Lock()
_ANALYZE_MEMO_MAX = 128

#: default arithmetic-intensity threshold (FLOPs per byte of HBM traffic)
#: below which a segment is reported memory-bound (TM604): the thin
#: tree-histogram path sits near 1 F/B, the batched matmul regime over 10.
MEMORY_BOUND_INTENSITY = 2.0

#: cross-device collective / resharding primitives (TM603 inventory)
_COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pbroadcast", "pvary",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "axis_index", "sharding_constraint",
})

#: float accumulations whose result depends on reduction order under a
#: sharded/layout-varying execution (the PR 2 BLAS-summation class)
_ORDER_ACCUM_PRIMS = frozenset({
    "reduce_sum", "dot_general", "cumsum", "cumlogsumexp", "add_any",
    "reduce_window_sum", "reduce_prod",
})

#: float sorts — order/implementation-dependent for equal/NaN keys and under
#: GSPMD sharding (the PR 4 sort-miscompile class)
_ORDER_SORT_PRIMS = frozenset({"sort", "top_k", "approx_top_k"})

#: call-like primitives to recurse into: primitive name -> params key(s)
_CALL_JAXPR_KEYS = ("jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr",
                    "fun_jaxpr")

_ELEMENTWISE_PRIMS = frozenset({
    "add", "sub", "mul", "div", "rem", "max", "min", "and", "or", "xor",
    "not", "neg", "sign", "abs", "floor", "ceil", "round", "exp", "exp2",
    "expm1", "log", "log1p", "tanh", "logistic", "sin", "cos", "tan",
    "asin", "acos", "atan", "atan2", "sinh", "cosh", "sqrt", "rsqrt",
    "cbrt", "pow", "integer_pow", "erf", "erfc", "erf_inv", "is_finite",
    "eq", "ne", "lt", "le", "gt", "ge", "select_n", "clamp", "nextafter",
    "square", "sigmoid",
})

_REDUCE_PRIMS = frozenset({
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "reduce_precision",
})

#: aliasing/placement primitives whose output shares its input's buffer —
#: no traffic, no flops, and the "output" must not inflate the live set
#: (make_jaxpr inserts an aliasing ``device_put`` per baked constant)
_ALIAS_PRIMS = frozenset({"device_put", "copy", "stop_gradient"})


# ---------------------------------------------------------------------------
# aval helpers
# ---------------------------------------------------------------------------

def _aval_nelems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 1
    out = 1
    for d in shape:
        out *= int(d)
    return out


def _aval_bytes(aval) -> int:
    dtype = getattr(aval, "dtype", None)
    itemsize = np.dtype(dtype).itemsize if dtype is not None else 4
    return _aval_nelems(aval) * itemsize


def _is_float(aval) -> bool:
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and np.issubdtype(np.dtype(dtype), np.floating)


# ---------------------------------------------------------------------------
# per-primitive FLOP model
# ---------------------------------------------------------------------------

def _dot_general_flops(eqn) -> int:
    """2 * |out| * |contracted| — exact for dense contractions."""
    (lhs_c, _rhs_c), _batch = eqn.params["dimension_numbers"]
    lhs_shape = eqn.invars[0].aval.shape
    contracted = 1
    for d in lhs_c:
        contracted *= int(lhs_shape[d])
    out_elems = sum(_aval_nelems(v.aval) for v in eqn.outvars)
    return 2 * out_elems * contracted


def _eqn_flops(eqn) -> int:
    name = eqn.primitive.name
    if name == "dot_general":
        return _dot_general_flops(eqn)
    out_elems = sum(_aval_nelems(v.aval) for v in eqn.outvars)
    in_elems = sum(_aval_nelems(v.aval) for v in eqn.invars
                   if hasattr(v, "aval"))
    if name in _ELEMENTWISE_PRIMS:
        return out_elems
    if name in _REDUCE_PRIMS or name.startswith("cum"):
        return in_elems
    if name in _ORDER_SORT_PRIMS:
        n = max(in_elems, 2)
        return int(n * math.log2(n))
    if name == "lu":
        n = int(eqn.invars[0].aval.shape[-1])
        batch = _aval_nelems(eqn.invars[0].aval) // max(n * n, 1)
        return int((2 / 3) * n ** 3 * max(batch, 1))
    if name == "cholesky":
        n = int(eqn.invars[0].aval.shape[-1])
        batch = _aval_nelems(eqn.invars[0].aval) // max(n * n, 1)
        return int((1 / 3) * n ** 3 * max(batch, 1))
    if name == "triangular_solve":
        n = int(eqn.invars[0].aval.shape[-1])
        return n * _aval_nelems(eqn.invars[1].aval)
    return 0


# ---------------------------------------------------------------------------
# jaxpr walk
# ---------------------------------------------------------------------------

@dataclass
class _Tally:
    flops: int = 0
    bytes_read: int = 0
    bytes_written: int = 0
    op_counts: Dict[str, int] = field(default_factory=dict)
    collectives: Dict[str, int] = field(default_factory=dict)
    #: modeled cross-device traffic (bytes) of the collective/resharding ops:
    #: full operand bytes for true collectives and fully-replicating
    #: constraints (a potential all-gather), zero for constraints that keep a
    #: dimension sharded (layout-preserving pins move nothing) — the TM608
    #: scalability evidence
    collective_bytes: int = 0
    order_accums: int = 0
    order_sorts: int = 0
    notes: List[str] = field(default_factory=list)

    def merge_scaled(self, other: "_Tally", times: int) -> None:
        self.flops += other.flops * times
        self.bytes_read += other.bytes_read * times
        self.bytes_written += other.bytes_written * times
        for k, v in other.op_counts.items():
            self.op_counts[k] = self.op_counts.get(k, 0) + v * times
        for k, v in other.collectives.items():
            self.collectives[k] = self.collectives.get(k, 0) + v * times
        self.collective_bytes += other.collective_bytes * times
        self.order_accums += other.order_accums * times
        self.order_sorts += other.order_sorts * times
        for n in other.notes:
            if n not in self.notes:
                self.notes.append(n)


def _collective_volume(eqn) -> int:
    """Modeled cross-device byte volume of one collective/resharding eqn.

    ``sharding_constraint`` charges its operand bytes only when the target
    sharding is FULLY REPLICATED — the shape a GSPMD all-gather materializes
    (the eval sweeps' metric pin is exactly this, deliberately); a constraint
    that keeps any dimension sharded is a layout pin and moves nothing by
    itself.  True collectives (psum/all_gather/...) always charge operand
    bytes.  An upper bound either way — XLA may fuse or elide."""
    in_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars
                   if hasattr(v, "aval"))
    if eqn.primitive.name != "sharding_constraint":
        return in_bytes
    sh = eqn.params.get("sharding")
    if sh is None or bool(getattr(sh, "is_fully_replicated", False)):
        return in_bytes
    return 0


def _sub_jaxprs(eqn) -> List[Tuple[Any, int]]:
    """(closed/open sub-jaxpr, trip multiplier) list for call-like eqns."""
    out: List[Tuple[Any, int]] = []
    name = eqn.primitive.name
    params = eqn.params
    if name == "scan":
        out.append((params["jaxpr"], max(int(params.get("length", 1)), 1)))
        return out
    if name == "while":
        # trip count is dynamic: count the body once and note the bound
        out.append((params["body_jaxpr"], 1))
        out.append((params["cond_jaxpr"], 1))
        return out
    if name == "cond":
        branches = params.get("branches", ())
        out.extend((b, 1) for b in branches)
        return out
    for key in _CALL_JAXPR_KEYS:
        if key in params:
            out.append((params[key], 1))
            return out
    return out


def _open_jaxpr(j):
    """The inner Jaxpr of a ClosedJaxpr (or ``j`` itself when already open).
    A ClosedJaxpr's constants are bound to ``jaxpr.constvars``, so their
    bytes are accounted exactly once through the constvar avals."""
    return getattr(j, "jaxpr", j)


def _const_bytes(j) -> int:
    """Bytes of constants baked at ANY nesting level of the jaxpr tree.

    A jit-wrapped program stages as ONE pjit eqn whose consts live in the
    sub-ClosedJaxpr — the top-level constvars are empty — and every real
    caller hands analyze_program/trace_cost a jit-wrapped fn, so the TM609
    replication evidence must see through call boundaries.  Counted once per
    binding site (residency, not traffic), summed across sites: an upper
    bound when branches share a constant."""
    jaxpr = _open_jaxpr(j)
    total = sum(_aval_bytes(v.aval) for v in jaxpr.constvars)
    for eqn in jaxpr.eqns:
        for sub, _times in _sub_jaxprs(eqn):
            total += _const_bytes(sub)
    return total


def _walk_jaxpr(j, tally: _Tally, depth: int = 0) -> int:
    """Accumulate costs of ``j`` into ``tally``; return the jaxpr's peak live
    bytes (inputs + constants + liveness-scanned temporaries).

    The peak is a linear-scan liveness estimate: at each equation the live
    set is the jaxpr's constants, still-needed inputs/temporaries, and the
    equation's outputs; call-like equations contribute their own internal
    peak beyond their operands.  An upper bound — XLA's buffer assignment
    reuses dead buffers at least this well.
    """
    jaxpr = _open_jaxpr(j)
    if depth > 32:  # defensive: pathological nesting
        return 0

    # constants + inputs resident for the whole program; a ClosedJaxpr's
    # consts ARE its constvars, counted here exactly once
    var_bytes: Dict[Any, int] = {}
    base = 0
    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        b = _aval_bytes(v.aval)
        var_bytes[v] = b
        base += b

    # last-use index per var (outvars live to the end)
    last_use: Dict[Any, int] = {}
    n_eqns = len(jaxpr.eqns)
    for idx, eqn in enumerate(jaxpr.eqns):
        for v in eqn.invars:
            if hasattr(v, "aval") and not _is_literal(v):
                last_use[v] = idx
    for v in jaxpr.outvars:
        if hasattr(v, "aval") and not _is_literal(v):
            last_use[v] = n_eqns
    # an alias output shares its source's buffer: the source stays live as
    # long as the alias does (reverse pass resolves alias-of-alias chains)
    for idx in range(n_eqns - 1, -1, -1):
        eqn = jaxpr.eqns[idx]
        if eqn.primitive.name not in _ALIAS_PRIMS:
            continue
        alias_end = max((last_use.get(v, idx) for v in eqn.outvars),
                        default=idx)
        for v in eqn.invars:
            if hasattr(v, "aval") and not _is_literal(v):
                last_use[v] = max(last_use.get(v, idx), alias_end)

    # entry buffers (non-donated inputs + baked constants) are held by the
    # caller for the whole XLA call — they are never freed by the walk below
    entry = set(var_bytes)
    live = dict(var_bytes)
    live_bytes = base
    peak = base
    for idx, eqn in enumerate(jaxpr.eqns):
        name = eqn.primitive.name
        subs = _sub_jaxprs(eqn)
        inner_extra = 0
        if subs:
            if name == "while":
                tally.notes.append("while-loop: dynamic trip count "
                                   "(body cost counted once)")
            for sub, times in subs:
                sub_tally = _Tally()
                sub_peak = _walk_jaxpr(sub, sub_tally, depth + 1)
                tally.merge_scaled(sub_tally, times)
                sub_jaxpr = _open_jaxpr(sub)
                sub_io = sum(_aval_bytes(v.aval) for v in sub_jaxpr.invars)
                inner_extra = max(inner_extra, max(sub_peak - sub_io, 0))
        elif name in _ALIAS_PRIMS:
            tally.op_counts[name] = tally.op_counts.get(name, 0) + 1
        else:
            tally.op_counts[name] = tally.op_counts.get(name, 0) + 1
            tally.flops += _eqn_flops(eqn)
            tally.bytes_read += sum(_aval_bytes(v.aval) for v in eqn.invars
                                    if hasattr(v, "aval"))
            tally.bytes_written += sum(_aval_bytes(v.aval)
                                       for v in eqn.outvars)
            if name in _COLLECTIVE_PRIMS:
                tally.collectives[name] = tally.collectives.get(name, 0) + 1
                tally.collective_bytes += _collective_volume(eqn)
            any_float = any(_is_float(v.aval) for v in eqn.invars
                            if hasattr(v, "aval"))
            if any_float and name in _ORDER_ACCUM_PRIMS:
                tally.order_accums += 1
            if any_float and name in _ORDER_SORT_PRIMS:
                tally.order_sorts += 1

        out_bytes = 0
        aliasing = name in _ALIAS_PRIMS
        for v in eqn.outvars:
            if v not in live:
                b = 0 if aliasing else _aval_bytes(v.aval)
                live[v] = b
                out_bytes += b
        live_bytes += out_bytes
        peak = max(peak, live_bytes + inner_extra)
        # free vars whose last use was this equation
        for v in list(eqn.invars) + list(eqn.outvars):
            if _is_literal(v) or v in entry:
                continue
            if v in live and last_use.get(v, n_eqns) <= idx:
                live_bytes -= live.pop(v)
    return peak


def _is_literal(v) -> bool:
    return type(v).__name__ == "Literal"


# ---------------------------------------------------------------------------
# public dataclasses
# ---------------------------------------------------------------------------

@dataclass
class SegmentCost:
    """Static cost of one fused segment (a whole program or one stage)."""

    name: str
    flops: int
    bytes_read: int
    bytes_written: int
    peak_live_bytes: int = 0
    op_counts: Dict[str, int] = field(default_factory=dict)
    collectives: Dict[str, int] = field(default_factory=dict)
    #: modeled cross-device traffic of the collective/resharding ops (TM608)
    collective_bytes: int = 0
    #: per-host-replicated entry bytes: the program's baked constants, which
    #: every host holds in full regardless of mesh size (TM609 evidence)
    replicated_bytes: int = 0
    order_accums: int = 0
    order_sorts: int = 0
    notes: List[str] = field(default_factory=list)

    @property
    def bytes_total(self) -> int:
        return self.bytes_read + self.bytes_written

    @property
    def intensity(self) -> float:
        """Arithmetic intensity: FLOPs per byte of modeled HBM traffic."""
        return self.flops / max(self.bytes_total, 1)

    @property
    def memory_bound(self) -> bool:
        return self.intensity < MEMORY_BOUND_INTENSITY

    def to_dict(self) -> Dict[str, Any]:
        return {
            "name": self.name, "flops": self.flops,
            "bytesRead": self.bytes_read, "bytesWritten": self.bytes_written,
            "peakLiveBytes": self.peak_live_bytes,
            "intensity": round(self.intensity, 4),
            "memoryBound": self.memory_bound,
            "collectives": dict(self.collectives),
            "collectiveBytes": self.collective_bytes,
            "orderSensitiveOps": {"accumulations": self.order_accums,
                                  "sorts": self.order_sorts},
            "notes": list(self.notes),
        }


@dataclass
class BucketCost:
    """Whole-program totals at one row bucket of the padding ladder."""

    bucket: int
    flops: int
    bytes_read: int
    bytes_written: int
    peak_hbm_bytes: int
    #: modeled cross-device collective traffic per step at this bucket —
    #: the TM608 scalability evidence (rows-proportional growth across the
    #: ladder means the program cannot scale past one host)
    collective_bytes: int = 0

    @property
    def intensity(self) -> float:
        return self.flops / max(self.bytes_read + self.bytes_written, 1)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "bucket": self.bucket, "flops": self.flops,
            "bytesRead": self.bytes_read, "bytesWritten": self.bytes_written,
            "peakHbmBytes": self.peak_hbm_bytes,
            "collectiveBytes": self.collective_bytes,
            "intensity": round(self.intensity, 4),
        }


@dataclass
class RecompileHazard:
    """One input shape the pow2/8192 bucket ladder cannot cover."""

    kind: str            # "data_dependent_width" | "over_max_bucket" | ...
    detail: str
    stage_uid: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "detail": self.detail,
                "stageUid": self.stage_uid}


@dataclass
class PlanCostReport:
    """Full static cost report of one fused plan."""

    plan: str                                  # label + fingerprint prefix
    segments: List[SegmentCost] = field(default_factory=list)
    buckets: List[BucketCost] = field(default_factory=list)
    hazards: List[RecompileHazard] = field(default_factory=list)
    collectives: Dict[str, int] = field(default_factory=dict)
    #: per-host-replicated entry bytes (baked constants) at the reference
    #: bucket — the operands adding hosts cannot shard away (TM609)
    replicated_bytes: int = 0
    #: order/layout-sensitive op counts (TM605 evidence): float accumulations
    #: and float sorts in the traced program
    order_accums: int = 0
    order_sorts: int = 0
    mesh: Optional[str] = None
    notes: List[str] = field(default_factory=list)

    @property
    def total_flops(self) -> int:
        return self.buckets[-1].flops if self.buckets else 0

    @property
    def total_bytes(self) -> int:
        b = self.buckets[-1] if self.buckets else None
        return (b.bytes_read + b.bytes_written) if b else 0

    @property
    def peak_hbm_bytes(self) -> int:
        return max((b.peak_hbm_bytes for b in self.buckets), default=0)

    @property
    def collective_bytes_per_step(self) -> int:
        """Modeled cross-device collective traffic of one dispatch at the
        largest analyzed bucket."""
        return self.buckets[-1].collective_bytes if self.buckets else 0

    def memory_bound_segments(self) -> List[SegmentCost]:
        return [s for s in self.segments if s.memory_bound and s.bytes_total]

    def to_dict(self) -> Dict[str, Any]:
        return {
            "plan": self.plan,
            "totalFlops": self.total_flops,
            "totalBytes": self.total_bytes,
            "peakHbmBytes": self.peak_hbm_bytes,
            "collectiveBytesPerStep": self.collective_bytes_per_step,
            "replicatedBytes": self.replicated_bytes,
            "buckets": [b.to_dict() for b in self.buckets],
            "segments": [s.to_dict() for s in self.segments],
            "recompileHazards": [h.to_dict() for h in self.hazards],
            "collectives": dict(self.collectives),
            "orderSensitiveOps": {"accumulations": self.order_accums,
                                  "sorts": self.order_sorts},
            "mesh": self.mesh,
            "notes": list(self.notes),
        }

    def pretty(self) -> str:
        lines = [f"PlanCostReport [{self.plan}]"]
        if self.mesh:
            lines.append(f"  mesh: {self.mesh}")
        if self.buckets:
            lines.append("  bucket      FLOPs        bytes        peak HBM     AI")
            for b in self.buckets:
                lines.append(
                    f"  {b.bucket:<10d}  {b.flops:<11.3e}  "
                    f"{b.bytes_read + b.bytes_written:<11.3e}  "
                    f"{_fmt_bytes(b.peak_hbm_bytes):<11s}  "
                    f"{b.intensity:.3f}")
        if self.segments:
            lines.append(f"  segments @ bucket "
                         f"{self.buckets[-1].bucket if self.buckets else '?'}:")
            for s in self.segments:
                tag = "  [memory-bound]" if s.memory_bound else ""
                lines.append(
                    f"    {s.name}: flops={s.flops:.3e} "
                    f"bytes={s.bytes_total:.3e} AI={s.intensity:.3f}{tag}")
        if self.collectives:
            inv = ", ".join(f"{k} x{v}" for k, v in
                            sorted(self.collectives.items()))
            lines.append(f"  collectives/resharding: {inv} "
                         f"({_fmt_bytes(self.collective_bytes_per_step)}"
                         f"/step)")
        else:
            lines.append("  collectives/resharding: none")
        if self.replicated_bytes:
            lines.append(f"  per-host replicated operands: "
                         f"{_fmt_bytes(self.replicated_bytes)}")
        if self.order_accums or self.order_sorts:
            lines.append(f"  order-sensitive ops: "
                         f"{self.order_accums} float accumulation(s), "
                         f"{self.order_sorts} float sort(s)")
        for h in self.hazards:
            lines.append(f"  recompile hazard [{h.kind}]: {h.detail}")
        for n in self.notes:
            lines.append(f"  note: {n}")
        return "\n".join(lines)


def _fmt_bytes(b: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(b) < 1024 or unit == "GiB":
            return f"{b:.1f}{unit}" if unit != "B" else f"{b}B"
        b /= 1024
    return f"{b}B"


# ---------------------------------------------------------------------------
# tracing entry points (all abstract: make_jaxpr only, zero backend compiles)
# ---------------------------------------------------------------------------

def trace_cost(fn, *specs, name: str = "program") -> SegmentCost:
    """Trace ``fn`` on abstract specs (ShapeDtypeStructs or arrays, whose
    avals are used) and return its :class:`SegmentCost`.  Pure trace: no
    lowering, no backend compile, no device dispatch."""
    import jax

    closed = jax.make_jaxpr(fn)(*specs)
    tally = _Tally()
    peak = _walk_jaxpr(closed, tally)
    # baked constants at every nesting level (a jit-wrapped fn binds them in
    # its pjit sub-jaxpr, not the top-level constvars): the operands every
    # host replicates in full regardless of mesh size (TM609 evidence)
    replicated = _const_bytes(closed)
    return SegmentCost(
        name=name, flops=tally.flops, bytes_read=tally.bytes_read,
        bytes_written=tally.bytes_written, peak_live_bytes=peak,
        op_counts=tally.op_counts, collectives=tally.collectives,
        collective_bytes=tally.collective_bytes, replicated_bytes=replicated,
        order_accums=tally.order_accums, order_sorts=tally.order_sorts,
        notes=tally.notes)


def _mesh_label() -> Optional[str]:
    from ..parallel.mesh import current_mesh

    mesh = current_mesh()
    if mesh is None:
        return None
    shape = "x".join(str(s) for s in np.asarray(mesh.devices).shape)
    return f"{'/'.join(mesh.axis_names)}:{shape}"


def _bucket_ladder(min_bucket: int, max_bucket: int, limit: int = 6
                   ) -> List[int]:
    """Power-of-two ladder [min, max], geometrically subsampled to ``limit``
    entries (endpoints always kept) — each bucket costs one abstract trace."""
    ladder, b = [], max(int(min_bucket), 1)
    while b <= max_bucket:
        ladder.append(b)
        b *= 2
    if max_bucket not in ladder:
        ladder.append(int(max_bucket))
    if len(ladder) <= limit:
        return ladder
    idx = np.unique(np.linspace(0, len(ladder) - 1, limit).astype(int))
    return [ladder[i] for i in idx]


def _segment_costs(wiring, entry_specs_for) -> List[SegmentCost]:
    """Per-stage SegmentCosts of a fused plan's wiring at the reference
    bucket: propagate abstract specs stage by stage (eval_shape), tracing
    each stage's ``device_transform`` in isolation."""
    import jax

    env: Dict[str, Any] = {}
    segments: List[SegmentCost] = []
    for runner, srcs, out_uid in wiring:
        ops = []
        for tag, key in srcs:
            ops.append(env[key] if tag == "env" else entry_specs_for(key))
        try:
            seg = trace_cost(runner.device_transform, *ops,
                             name=f"{type(runner).__name__}({runner.uid})")
            traced = jax.eval_shape(runner.device_transform, *ops)
        except Exception as e:  # noqa: BLE001 — per-stage cost is best-effort
            log.debug("segment trace failed for %s: %s", runner.uid, e)
            env[out_uid] = None
            continue
        segments.append(seg)
        env[out_uid] = jax.ShapeDtypeStruct(traced.shape, traced.dtype) \
            if hasattr(traced, "shape") else traced
    return segments


def _analyze_fused(fused_fn, specs_per_bucket, wiring, label: str,
                   hazards: Sequence[RecompileHazard] = ()) -> PlanCostReport:
    """Shared core: trace ``fused_fn`` at every bucket's specs, per-stage
    segments at the largest bucket."""
    report = PlanCostReport(plan=label, mesh=_mesh_label(),
                            hazards=list(hazards))
    largest_specs = None
    for bucket, specs in specs_per_bucket:
        seg = trace_cost(fused_fn, *specs, name=f"bucket{bucket}")
        report.buckets.append(BucketCost(
            bucket=bucket, flops=seg.flops, bytes_read=seg.bytes_read,
            bytes_written=seg.bytes_written,
            peak_hbm_bytes=seg.peak_live_bytes,
            collective_bytes=seg.collective_bytes))
        report.replicated_bytes = max(report.replicated_bytes,
                                      seg.replicated_bytes)
        for k, v in seg.collectives.items():
            report.collectives[k] = max(report.collectives.get(k, 0), v)
        for n in seg.notes:
            if n not in report.notes:
                report.notes.append(n)
        largest_specs = specs
        report.order_accums = max(report.order_accums, seg.order_accums)
        report.order_sorts = max(report.order_sorts, seg.order_sorts)
    if wiring and largest_specs is not None:
        spec_by_index = dict(enumerate(largest_specs))
        report.segments = _segment_costs(
            wiring, lambda key: spec_by_index[key])
    return report


def analyze_scoring_plan(plan, buckets: Optional[Sequence[int]] = None
                         ) -> PlanCostReport:
    """Cost-analyze a :class:`~..serve.plan.CompiledScoringPlan` across its
    padding-bucket ladder.  Abstract specs come from the plan's own entry
    table — the exact operands its executables are compiled for."""
    import jax

    if buckets is None:
        buckets = _bucket_ladder(plan.min_bucket, plan.max_bucket)

    def specs_at(bucket: int):
        return [jax.ShapeDtypeStruct((bucket,) + tuple(trailing),
                                     np.dtype(dtype))
                for trailing, dtype in plan._entry_specs]

    specs_per_bucket = [(b, specs_at(b)) for b in buckets]
    label = f"scoring/{len(plan.device_stage_uids)}stages/" \
            f"{plan.fingerprint[:12]}"
    report = _analyze_fused(plan._fused, specs_per_bucket, plan._wiring,
                            label, hazards=scoring_hazards(plan))
    if not plan._prefix:
        report.notes.append("empty device prefix: every stage runs on host")
    return report


def _width_hazards(runners) -> List[RecompileHazard]:
    """Data-dependent-width recompile hazards among ``runners``: a raw
    OPVector feature feeding a device-capable stage — the row-bucket ladder
    amortizes rows only, so every new width compiles a fresh executable.
    (ONE rule shared by the fitted scoring-plan path and the unfitted
    workflow path, so the two reports cannot drift.)"""
    from ..features.generator import FeatureGeneratorStage
    from ..workflow.plan import device_slots

    hazards: List[RecompileHazard] = []
    seen: set = set()
    for runner in runners:
        if not callable(getattr(runner, "device_transform", None)):
            continue
        for slot in device_slots(runner):
            if slot >= len(runner.inputs):
                continue
            f = runner.inputs[slot]
            if isinstance(f.origin_stage, FeatureGeneratorStage) \
                    and f.ftype.kind is ColumnKind.VECTOR \
                    and f.uid not in seen:
                seen.add(f.uid)
                hazards.append(RecompileHazard(
                    kind="data_dependent_width",
                    detail=f"raw feature {f.name!r} is an OPVector whose "
                           f"width is only known from the data; the row "
                           f"bucket ladder cannot cover it — every new "
                           f"width compiles a fresh executable",
                    stage_uid=runner.uid))
    return hazards


def scoring_hazards(plan) -> List[RecompileHazard]:
    """Recompile-hazard map of a scoring plan: raw feature shapes the bucket
    ladder cannot amortize (widths only known from the data)."""
    return _width_hazards(list(plan._prefix) + list(plan._remainder))


def analyze_transform_plan(plan, dataset) -> PlanCostReport:
    """Cost-analyze a :class:`~..workflow.plan.ColumnarTransformPlan` at the
    dataset's row bucket.  Entry specs derive from column kinds/widths — the
    columns themselves are never lifted."""
    import jax

    from ..workflow.plan import mesh_aligned_tile

    n = dataset.n_rows
    # the DISPATCH tile, not the bare pow2/8192 bucket: under a mesh whose
    # data axis does not divide the bucket, _place pads up to the mesh
    # multiple — the admission gate must certify the program that runs
    bucket = mesh_aligned_tile(n)

    def spec_for(key, rows: int):
        if key[0] == "lift":
            col = dataset[plan._entry_names[key]]
            if col.kind is ColumnKind.VECTOR:
                trailing: tuple = (int(col.data.shape[1]),)
            elif col.kind is ColumnKind.GEO:
                trailing = (3,)
            else:
                trailing = ()
            return jax.ShapeDtypeStruct((rows,) + trailing,
                                        np.dtype("float32"))
        runner, slot, _name = plan._entry_encoders[key]
        trailing, dtype = runner.device_input_spec(slot)
        return jax.ShapeDtypeStruct((rows,) + tuple(trailing),
                                    np.dtype(dtype))

    specs = [spec_for(k, bucket) for k in plan._entry_keys]
    label = f"transform/{len(plan.device_stage_uids)}stages/" \
            f"{plan.fingerprint[:12]}"
    # content-addressed memo: the report is deterministic per (fingerprint,
    # bucket, entry specs), and the armed train()/CV budget gate re-analyzes
    # the same plan at every fused dispatch — trace once, hand out copies
    key = (plan.fingerprint, bucket,
           tuple((tuple(s.shape), str(s.dtype)) for s in specs))
    with _ANALYZE_MEMO_LOCK:
        cached = _ANALYZE_MEMO.get(key)
    if cached is None:
        cached = _analyze_fused(plan._fused, [(bucket, specs)],
                                plan._wiring, label)
        with _ANALYZE_MEMO_LOCK:
            _ANALYZE_MEMO[key] = cached
            while len(_ANALYZE_MEMO) > _ANALYZE_MEMO_MAX:
                _ANALYZE_MEMO.pop(next(iter(_ANALYZE_MEMO)))
    report = copy.deepcopy(cached)  # callers may append notes/mutate
    if n > 8192:
        report.notes.append(
            "rows > 8192: buckets grow in 8192-multiples — a steady table "
            "shape reuses one executable, a drifting row count compiles one "
            "per multiple")
    return report


def analyze_program(fn, specs_per_bucket, label: str = "program"
                    ) -> PlanCostReport:
    """Static cost report of an arbitrary (jit-wrapped or plain) program
    across a row-bucket ladder — the sweep-program twin of
    :func:`analyze_transform_plan`.

    ``specs_per_bucket`` is ``[(bucket, [specs...]), ...]``; statics bind
    via ``functools.partial``/lambda before the call.  This is the entry the
    TM608/TM609 scalability tests (tests/test_multihost.py) use to cost the
    sharded fold x grid sweep programs (collective bytes per step,
    replicated operand bytes) at ZERO backend compiles."""
    return _analyze_fused(fn, list(specs_per_bucket), None, label)


#: TM608 threshold: per-step collective volume counted as rows-proportional
#: when its growth across the bucket ladder is at least this fraction of the
#: row growth (1.0 = exactly linear; 0.5 tolerates a constant component)
ROWS_PROPORTIONAL_FRACTION = 0.5

#: TM609 threshold: fraction of the armed per-host HBM budget that
#: replicated (per-host, non-shardable) operands may occupy
REPLICATED_HBM_SHARE = 0.5


def scalability_diagnostics(report: PlanCostReport,
                            hbm_budget: Optional[float] = None
                            ) -> List[Diagnostic]:
    """TM608/TM609: the static scalability gate (pod-scale readiness at zero
    hardware).  Mesh-scoped by construction — an unmeshed trace has no
    collectives and its baked constants are not *replicas* of anything, so
    both checks are quiet off-mesh and CI plans analyzed without a mesh
    never churn."""
    diags: List[Diagnostic] = []
    if report.mesh is None:
        return diags

    if len(report.buckets) >= 2:
        ladder = sorted(report.buckets, key=lambda b: b.bucket)
        lo, hi = ladder[0], ladder[-1]
        if hi.bucket > lo.bucket and hi.collective_bytes > 0:
            rows_ratio = hi.bucket / lo.bucket
            vol_ratio = hi.collective_bytes / max(lo.collective_bytes, 1)
            if vol_ratio >= ROWS_PROPORTIONAL_FRACTION * rows_ratio:
                diags.append(make_diagnostic(
                    "TM608",
                    f"plan {report.plan}: per-step collective volume grows "
                    f"with global rows ({_fmt_bytes(lo.collective_bytes)} at "
                    f"bucket {lo.bucket} -> {_fmt_bytes(hi.collective_bytes)} "
                    f"at bucket {hi.bucket}, x{vol_ratio:.1f} for x"
                    f"{rows_ratio:.0f} rows) — the program moves row-shaped "
                    f"data over the mesh and will not scale past one host"))

    if hbm_budget is not None and report.replicated_bytes > \
            REPLICATED_HBM_SHARE * hbm_budget:
        diags.append(make_diagnostic(
            "TM609",
            f"plan {report.plan}: {_fmt_bytes(report.replicated_bytes)} of "
            f"per-host replicated operands (baked constants) exceed "
            f"{REPLICATED_HBM_SHARE:.0%} of the {_fmt_bytes(int(hbm_budget))} "
            f"per-host budget — replication cannot be sharded away by "
            f"adding hosts"))
    return diags


# ---------------------------------------------------------------------------
# TM6xx diagnostics
# ---------------------------------------------------------------------------

def cost_diagnostics(report: PlanCostReport,
                     hbm_budget: Optional[float] = None,
                     single_host: bool = False,
                     intensity_threshold: float = MEMORY_BOUND_INTENSITY
                     ) -> List[Diagnostic]:
    """Map a :class:`PlanCostReport` to TM601-TM605 diagnostics."""
    diags: List[Diagnostic] = []

    if hbm_budget is not None and report.buckets:
        worst = max(report.buckets, key=lambda b: b.peak_hbm_bytes)
        if worst.peak_hbm_bytes > hbm_budget:
            diags.append(make_diagnostic(
                "TM601",
                f"plan {report.plan}: peak live-buffer HBM estimate "
                f"{_fmt_bytes(worst.peak_hbm_bytes)} at bucket "
                f"{worst.bucket} exceeds the device budget "
                f"{_fmt_bytes(int(hbm_budget))}"))

    for h in report.hazards:
        diags.append(make_diagnostic(
            "TM602",
            f"plan {report.plan}: {h.detail}",
            stage_uid=h.stage_uid))

    if report.collectives:
        inv = ", ".join(f"{k} x{v}" for k, v in
                        sorted(report.collectives.items()))
        if single_host:
            diags.append(make_diagnostic(
                "TM603",
                f"plan {report.plan} contains cross-device "
                f"collective/resharding ops ({inv}) but was validated as "
                f"single-host"))

    slow = [s for s in report.segments
            if s.bytes_total and s.intensity < intensity_threshold]
    if slow:
        names = ", ".join(f"{s.name} (AI={s.intensity:.2f})" for s in slow)
        diags.append(make_diagnostic(
            "TM604",
            f"plan {report.plan}: {len(slow)} memory-bound segment(s) below "
            f"{intensity_threshold:.1f} FLOPs/byte — Pallas fused-kernel "
            f"candidates: {names}"))

    # TM608/TM609: the static scalability pass (mesh-scoped; quiet off-mesh)
    diags.extend(scalability_diagnostics(report, hbm_budget=hbm_budget))

    sorts, accums = report.order_sorts, report.order_accums
    if sorts or (accums and report.mesh is not None):
        what = []
        if sorts:
            what.append(f"{sorts} float sort(s)")
        if accums and report.mesh is not None:
            what.append(f"{accums} float accumulation(s) under mesh "
                        f"{report.mesh}")
        diags.append(make_diagnostic(
            "TM605",
            f"plan {report.plan}: {', '.join(what)} — results depend on "
            f"reduction order/layout; bitwise parity across backends and "
            f"meshes is not guaranteed"))
    return diags


class _ModelShim:
    """Minimal (result_features, fitted) carrier for CompiledScoringPlan."""

    def __init__(self, result_features, fitted):
        self.result_features = list(result_features)
        self.fitted = dict(fitted)


def check_plan_cost(result_features, fitted=None,
                    hbm_budget: Optional[float] = None,
                    single_host: bool = False,
                    intensity_threshold: float = MEMORY_BOUND_INTENSITY,
                    min_bucket: int = 8, max_bucket: int = 1024
                    ) -> Tuple[Optional[PlanCostReport], List[Diagnostic]]:
    """TM6xx entry point for ``validate(cost=True, ...)`` / ``cli lint --cost``.

    With a complete ``fitted`` mapping the scoring plan is partitioned and
    traced exactly as serving would compile it.  Without one (an untrained
    Workflow) only the recompile-hazard map is computable — the device
    prefix's kernels and widths are properties of the fitted stages.
    """
    from ..stages.base import Estimator
    from ..workflow.dag import all_stages

    stages = all_stages(result_features)
    unfitted = [s for s in stages if isinstance(s, Estimator)
                and (fitted is None or s.uid not in fitted)]
    if unfitted:
        # hazard map only: raw data-dependent widths feeding device consumers
        report = PlanCostReport(plan="unfitted-workflow", mesh=_mesh_label(),
                                hazards=_width_hazards(stages))
        report.notes.append(
            f"{len(unfitted)} unfitted estimator(s): fused-prefix cost is a "
            "property of the fitted stages — train (or pass a fitted model) "
            "for FLOPs/bytes/HBM analysis")
        diags = cost_diagnostics(report, hbm_budget=None,
                                 single_host=False,
                                 intensity_threshold=intensity_threshold)
        if hbm_budget is not None or single_host:
            # fail CLOSED: an armed admission contract that cannot be
            # evaluated must not read as a pass (the lint_gate keys on
            # error severity, and a silent green here would admit anything)
            what = [w for w, on in
                    (("hbm_budget", hbm_budget is not None),
                     ("single_host", single_host)) if on]
            diags.append(make_diagnostic(
                "TM606",
                f"{'/'.join(what)} contract requested but the plan cost "
                f"cannot be computed: {len(unfitted)} unfitted "
                f"estimator(s) in the DAG "
                f"({', '.join(s.uid for s in unfitted[:3])}"
                f"{', ...' if len(unfitted) > 3 else ''})"))
        return report, diags

    from ..serve.plan import CompiledScoringPlan

    plan = CompiledScoringPlan(_ModelShim(result_features, fitted or {}),
                               min_bucket=min_bucket, max_bucket=max_bucket,
                               strict=False)
    report = analyze_scoring_plan(plan)
    return report, cost_diagnostics(report, hbm_budget=hbm_budget,
                                    single_host=single_host,
                                    intensity_threshold=intensity_threshold)


# ---------------------------------------------------------------------------
# TM607: static host-DRAM residency estimate (ISSUE 13 satellite)
# ---------------------------------------------------------------------------

@dataclass
class HostResidencyReport:
    """Static host-DRAM residency estimate of one fitted plan at a row count.

    Two modes are modeled: the IN-MEMORY path materializes the whole table
    (raw + every produced column) at once; the CHUNKED out-of-core path
    (data/chunked.py + workflow/ooc.py) holds only the prefetch-depth chunk
    tiles, the resident (non-spillable) output columns, and — transiently,
    one estimator at a time — that estimator's input columns.  The TM607
    gate compares the CHUNKED peak against the budget: it is the smallest
    working set any ingestion mode can achieve, so exceeding it cannot be
    fixed by spilling harder.
    """

    n_rows: int
    chunk_rows: int
    table_bytes: int = 0              #: full materialized table (in-memory mode)
    chunk_buffer_bytes: int = 0       #: prefetch-depth chunk tiles
    resident_bytes: int = 0           #: non-spillable outputs (predictions)
    fit_sets: List[Dict[str, Any]] = field(default_factory=list)
    notes: List[str] = field(default_factory=list)

    @property
    def max_fit_set_bytes(self) -> int:
        return max((int(f["bytes"]) for f in self.fit_sets), default=0)

    @property
    def peak_in_memory_bytes(self) -> int:
        return self.table_bytes

    @property
    def peak_chunked_bytes(self) -> int:
        return (self.chunk_buffer_bytes + self.resident_bytes
                + self.max_fit_set_bytes)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "nRows": self.n_rows, "chunkRows": self.chunk_rows,
            "tableBytes": self.table_bytes,
            "chunkBufferBytes": self.chunk_buffer_bytes,
            "residentBytes": self.resident_bytes,
            "peakInMemoryBytes": self.peak_in_memory_bytes,
            "peakChunkedBytes": self.peak_chunked_bytes,
            "fitSets": list(self.fit_sets),
            "notes": list(self.notes),
        }

    def pretty(self) -> str:
        lines = [f"HostResidencyReport @ {self.n_rows} rows "
                 f"(chunks of {self.chunk_rows})",
                 f"  in-memory table: {_fmt_bytes(self.table_bytes)}",
                 f"  chunked peak:    {_fmt_bytes(self.peak_chunked_bytes)} "
                 f"(buffers {_fmt_bytes(self.chunk_buffer_bytes)} + "
                 f"resident {_fmt_bytes(self.resident_bytes)} + "
                 f"largest fit set {_fmt_bytes(self.max_fit_set_bytes)})"]
        for f in self.fit_sets:
            lines.append(f"    fit {f['stageUid']}: "
                         f"{_fmt_bytes(int(f['bytes']))} "
                         f"({', '.join(f['columns'])})")
        for n in self.notes:
            lines.append(f"  note: {n}")
        return "\n".join(lines)


def _col_row_bytes(col) -> int:
    """Per-row host bytes of a (zero-row template) column."""
    data = col.data
    if data.dtype == object:
        per = 64  # object refs + smallish payloads: a rough floor
    else:
        per = data.dtype.itemsize * int(np.prod(data.shape[1:])) \
            if data.ndim > 1 else data.dtype.itemsize
    return per + (1 if col.mask is not None or col.is_numeric else 0)


def estimate_host_residency(result_features, fitted,
                            n_rows: int,
                            chunk_rows: Optional[int] = None,
                            schema_dataset=None) -> HostResidencyReport:
    """Zero-row replay of the fitted DAG → per-column row bytes → the
    :class:`HostResidencyReport` at ``n_rows``.  Touches no data and
    compiles nothing: every fitted runner transforms a ZERO-ROW dataset
    (metadata/width are functions of fitted state only, the same principle
    the fused planner's metadata replay rests on).

    ``schema_dataset`` supplies raw-column widths/dtypes when available (a
    real or chunked dataset); without one the raw schema derives from the
    feature generators' declared types (raw OPVector widths then unknown —
    noted, counted at zero).
    """
    from ..data.chunked import DEFAULT_CHUNK_ROWS
    from ..data.dataset import Column, Dataset
    from ..readers.prefetch import prefetch_depth
    from ..workflow.dag import compute_dag
    from ..workflow.fit import _resolve
    from ..workflow.workflow import dedup_raw_features

    chunk_rows = int(chunk_rows or DEFAULT_CHUNK_ROWS)
    report = HostResidencyReport(n_rows=int(n_rows), chunk_rows=chunk_rows)

    empty = np.zeros(0, dtype=np.intp)
    cols: Dict[str, Any] = {}
    if schema_dataset is not None:
        for name in schema_dataset.names:
            cols[name] = schema_dataset[name].take(empty)
    else:
        for f in dedup_raw_features(result_features):
            gen = f.origin_stage
            cols[f.name] = Column.from_values(gen.ftype, [])
            if gen.ftype.kind is ColumnKind.VECTOR:
                report.notes.append(
                    f"raw vector column {f.name!r}: width unknown without a "
                    f"schema dataset — counted at zero bytes")
    ds0 = Dataset(cols)

    from ..stages.base import Estimator

    per_row: Dict[str, int] = {n: _col_row_bytes(c) for n, c in cols.items()}
    resident_per_row = 0
    stages = [s for layer in compute_dag(result_features) for s in layer]
    for stage in stages:
        runner = _resolve(stage, dict(fitted))
        if runner is None:
            raise ValueError(
                f"stage {stage.uid} is unfitted: the residency estimate "
                "needs the fitted widths")
        # the estimator's fit-time working set: its input columns (plus the
        # sample-weight column when the schema carries one) at n_rows
        if isinstance(stage, Estimator):
            names = [f.name for f in stage.inputs if f.name in per_row]
            if "__sample_weight__" in per_row:
                names.append("__sample_weight__")
            report.fit_sets.append({
                "stageUid": stage.uid,
                "columns": names,
                "bytes": int(n_rows) * sum(per_row[n] for n in names)})
        ds0 = runner.transform(ds0)
        out = ds0[runner.output_name]
        per_row[runner.output_name] = _col_row_bytes(out)
        if type(out) is not Column:
            # non-spillable output (PredictionColumn): resident in chunked
            # mode too
            resident_per_row += _col_row_bytes(out)

    row_total = sum(per_row.values())
    report.table_bytes = int(n_rows) * row_total
    # ingest buffers: the prefetch queue's staged chunks + the one being
    # consumed + the output tile being spilled — all at full-table row width
    report.chunk_buffer_bytes = (prefetch_depth() + 2) * chunk_rows * row_total
    report.resident_bytes = int(n_rows) * resident_per_row
    return report


def host_residency_diagnostics(report: HostResidencyReport,
                               host_budget: Optional[float]
                               ) -> List[Diagnostic]:
    """TM607 when even the chunked out-of-core working set exceeds the
    armed budget (the in-memory overage alone is only a note: spilling —
    ``train(host_budget=)`` / ``maybe_chunk`` — resolves it)."""
    diags: List[Diagnostic] = []
    if host_budget is None:
        return diags
    if report.peak_chunked_bytes > host_budget:
        worst = max(report.fit_sets, key=lambda f: f["bytes"], default=None)
        detail = ""
        if worst is not None and worst["bytes"] == report.max_fit_set_bytes \
                and worst["bytes"] > 0:
            detail = (f"; largest fit set: stage {worst['stageUid']} "
                      f"({', '.join(worst['columns'])} = "
                      f"{_fmt_bytes(int(worst['bytes']))})")
        diags.append(make_diagnostic(
            "TM607",
            f"host-DRAM residency estimate "
            f"{_fmt_bytes(report.peak_chunked_bytes)} at {report.n_rows} "
            f"rows exceeds the armed host budget "
            f"{_fmt_bytes(int(host_budget))} even in chunked out-of-core "
            f"mode{detail}"))
    elif report.peak_in_memory_bytes > host_budget:
        report.notes.append(
            f"in-memory table ({_fmt_bytes(report.peak_in_memory_bytes)}) "
            f"exceeds the budget but the chunked out-of-core path fits "
            f"({_fmt_bytes(report.peak_chunked_bytes)}) — "
            f"train(host_budget=)/TMOG_HOST_BUDGET spills automatically")
    return diags


def check_host_residency(result_features, fitted=None,
                         host_budget: Optional[float] = None,
                         n_rows: Optional[int] = None,
                         chunk_rows: Optional[int] = None,
                         schema_dataset=None
                         ) -> Tuple[Optional[HostResidencyReport],
                                    List[Diagnostic]]:
    """TM607 entry point for ``validate(host_budget=...)`` and
    ``cli lint --cost --host-budget``.  Fails CLOSED (TM606) when the armed
    contract cannot be evaluated: unfitted estimators (no widths) or a
    missing row count (residency is linear in rows — without one there is
    nothing to compare)."""
    if host_budget is None:
        return None, []
    if not n_rows:
        return None, [make_diagnostic(
            "TM606",
            "host_budget contract requested but no row count provided "
            "(pass rows=/--rows: the residency estimate is linear in rows "
            "and a gate evaluated at zero rows would admit anything)")]
    try:
        report = estimate_host_residency(result_features, fitted or {},
                                         n_rows=n_rows,
                                         chunk_rows=chunk_rows,
                                         schema_dataset=schema_dataset)
    except Exception as e:  # noqa: BLE001 — fail closed, never silently green
        return None, [make_diagnostic(
            "TM606",
            f"host_budget contract requested but the residency estimate "
            f"could not be computed ({type(e).__name__}: {e})")]
    return report, host_residency_diagnostics(report, host_budget)
