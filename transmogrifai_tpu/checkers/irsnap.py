"""irsnap — IR golden corpus + semantic program differ (TM7xx).

Reference role: the reference validates workflows before data moves
(OpWorkflow.scala:265-323, SURVEY §1); this port adds a second semantic layer
the reference never had — the lowered XLA programs themselves.  A jax/jaxlib
bump (or an innocent-looking kernel edit) can change the MEANING of a fused
program with every Python-level test still green: the GSPMD sort miscompile
(sharded sort dim + replicated batch dims, fixed in PR 4) produced auPR
values near ``-n`` with no exception anywhere.  One tier-1 metric test pins
that single bug; this module pins ALL of them structurally.

For every program family the framework emits — the fold x grid sweep programs
in models/{logistic,svm,linear,trees}.py, the fused transform-plan prefix
from workflow/plan.py, the scoring-plan device prefix from serve/plan.py —
the program is lowered ON ABSTRACT SPECS to StableHLO text
(``jax.jit(...).lower()``: trace + MLIR lowering only, ZERO backend compiles,
the same discipline as plancheck), canonicalized (locations stripped, SSA
names renumbered, large constant payloads content-hashed), fingerprinted,
and persisted as a checked-in golden corpus under ``tests/goldens/ir/``.

A differ classifies corpus deltas into typed diagnostics:

- **TM700** info — corpus membership drift (program family added/removed);
- **TM701** info — benign text drift (canonical text changed, every semantic
  feature — op histogram, dtypes, collectives, sort signatures — identical);
- **TM702** warning — fusion/layout change (op histogram shifted);
- **TM703** warning — collectives/resharding added or removed;
- **TM704** error — dtype or widening drift (element-type inventory changed);
- **TM705** error — the known-miscompile hazard class: a sort whose sort
  dimension is sharded while its batch dimensions stay replicated (the exact
  pre-PR-4 GSPMD pattern), newly present relative to the golden.

Entry points: ``cli lint --ir`` (compare against goldens),
``cli lint --ir --update-goldens`` (re-golden after a reviewed upgrade), and
``tools/ir_gate.py`` (CI: rc flips only on NEW TM7xx errors — the
lint_gate.py contract).  Every snapshot here is keyed alongside the existing
content fingerprints (``perf.programs.cache_key_fingerprint`` for sweep
programs, ``ColumnarTransformPlan.fingerprint`` / scoring-plan fingerprints
for plans), so BENCH artifacts and cache stats can be correlated with the
exact IR that ran.

Goldens are the **CPU lowering** (the tier-1 environment): StableHLO is
platform-portable for these programs, but re-goldening on an accelerator
would churn the corpus — the index records jax version and platform so a
mismatch is visible, and ``tools/ir_gate.py`` pins the environment.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import re
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .diagnostics import Diagnostic, make_diagnostic

log = logging.getLogger(__name__)

#: corpus file-format version (bump on incompatible index/layout changes)
CORPUS_VERSION = 1

#: StableHLO/CHLO collective + resharding markers (the TM703 inventory);
#: custom_call targets count via their ``@Target`` name
_COLLECTIVE_OPS = frozenset({
    "stablehlo.all_reduce", "stablehlo.all_gather", "stablehlo.all_to_all",
    "stablehlo.reduce_scatter", "stablehlo.collective_permute",
    "stablehlo.collective_broadcast", "stablehlo.partition_id",
    "stablehlo.replica_id",
})
_COLLECTIVE_CUSTOM_CALLS = frozenset({
    "Sharding", "SPMDFullToShardShape", "SPMDShardToFullShape",
})

#: constant payloads longer than this are replaced by a content hash — the
#: "changed" signal survives, the corpus stays reviewable (fitted constants
#: and iota tables would otherwise dominate the text)
_CONST_HASH_THRESHOLD = 48


# ---------------------------------------------------------------------------
# canonicalization
# ---------------------------------------------------------------------------

_LOC_RE = re.compile(r"\s*loc\((?:[^()]|\([^()]*\))*\)")
_LOC_LINE_RE = re.compile(r"^#loc.*$", re.MULTILINE)
_SSA_RE = re.compile(r"%[A-Za-z0-9_]+")
_DENSE_RE = re.compile(r"dense<([^<>]*)>")
_MODULE_RE = re.compile(r"module @[A-Za-z0-9_.$-]+")
#: serialized kernel payloads (the Mosaic module a Pallas ``tpu_custom_call``
#: carries in ``backend_config``) are NOT byte-stable across processes — the
#: canonical form elides them entirely; the kernel's semantics stay pinned
#: by the interpret-mode family of the same kernel, and the custom_call's
#: presence/target/operand types stay in this family's text
_BACKEND_CONFIG_RE = re.compile(r'backend_config = "((?:[^"\\]|\\.)*)"')


def _hash_payload(payload: str) -> str:
    h = hashlib.blake2b(payload.encode(), digest_size=6).hexdigest()
    return f"dense<#blake2b:{h}/{len(payload)}>"


def canonicalize_stablehlo(text: str) -> str:
    """Canonical form of a StableHLO module: location metadata stripped, SSA
    value names renumbered in order of first appearance, constant payloads
    above the size threshold replaced by content hashes.

    Two lowerings of the same program canonicalize identically even when the
    MLIR printer numbers values differently; the fingerprint is a hash of
    this text.  Deliberately NOT stripped: dtype/shape signatures, op
    attributes, sharding annotations, private function names — those carry
    the semantics the differ classifies.
    """
    text = text.replace("\r\n", "\n")
    text = _LOC_LINE_RE.sub("", text)
    text = _LOC_RE.sub("", text)
    text = _MODULE_RE.sub("module @m", text)
    text = _BACKEND_CONFIG_RE.sub(
        lambda m: 'backend_config = "#elided"'
        if len(m.group(1)) > _CONST_HASH_THRESHOLD else m.group(0),
        text)
    text = _DENSE_RE.sub(
        lambda m: _hash_payload(m.group(1))
        if len(m.group(1)) > _CONST_HASH_THRESHOLD else m.group(0),
        text)

    mapping: Dict[str, str] = {}

    def rename(m: re.Match) -> str:
        name = m.group(0)
        if name not in mapping:
            mapping[name] = f"%v{len(mapping)}"
        return mapping[name]

    text = _SSA_RE.sub(rename, text)
    lines = [ln.rstrip() for ln in text.split("\n")]
    return "\n".join(ln for ln in lines if ln.strip()) + "\n"


def ir_fingerprint(canonical_text: str) -> str:
    return hashlib.blake2b(canonical_text.encode(),
                           digest_size=16).hexdigest()


# ---------------------------------------------------------------------------
# semantic feature extraction (pure text analysis — goldens reload from disk)
# ---------------------------------------------------------------------------

_OP_RE = re.compile(r'"?((?:stablehlo|chlo|vhlo|mhlo|sdy)\.[A-Za-z_0-9]+)"?')
#: custom_call target in the PRETTY printer form (``custom_call @Target``)
_CUSTOM_CALL_RE = re.compile(r"custom_call @([A-Za-z0-9_]+)")
#: ... and in the GENERIC printer form (``"stablehlo.custom_call"(...)
#: <{call_target_name = "Target", ...}>``) — Pallas kernels lower to
#: ``tpu_custom_call`` and must count by their target name, not lump under
#: one opaque ``stablehlo.custom_call`` entry, whichever form the MLIR
#: printer of the day emits
_CALL_TARGET_RE = re.compile(r'call_target_name\s*=\s*"([A-Za-z0-9_.$-]+)"')
_TENSOR_DTYPE_RE = re.compile(
    r"tensor<(?:[0-9?]+x)*([a-z][a-z0-9]*(?:<[^<>]*>)?)>")
_SHARDING_ATTR_RE = re.compile(r'mhlo\.sharding = "([^"]*)"')
_FUNC_RE = re.compile(r"func\.func (?:public |private )?@([A-Za-z0-9_]+)\(")
_DEF_RE = re.compile(r"^\s*(%[A-Za-z0-9_]+)(?::\d+)?\s*=\s*(.*)$")
_CALL_RE = re.compile(r"\bcall @([A-Za-z0-9_]+)\(([^)]*)\)")
_SORT_DIM_RE = re.compile(r"dimension = (\d+)")
#: op name at the head of a def line, in pretty (`stablehlo.negate %v0`) OR
#: generic (`"stablehlo.negate"(%v0)`) printer form — the sharding
#: pass-through walk must survive an MLIR printer-form change
_OP_NAME_RE = re.compile(r'^\s*"?([A-Za-z_][A-Za-z0-9_$.]*)"?')
_OPERANDS_RE = re.compile(r"\(([^)]*)\)")
_ARG_SHARD_RE = re.compile(
    r'(%[A-Za-z0-9_]+): tensor<([^>]*)>\s*(\{[^}]*mhlo\.sharding = '
    r'"([^"]*)"[^}]*\})?')
_TENSOR_RE = re.compile(r"tensor<([^<>]*)>")


def _op_histogram(text: str) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for m in _OP_RE.finditer(text):
        name = m.group(1)
        counts[name] = counts.get(name, 0) + 1
    # per-target custom_call counts, across both printer forms (one op
    # prints EITHER ``@Target`` or ``call_target_name = "Target"``, never
    # both, so summing the two never double-counts)
    for regex in (_CUSTOM_CALL_RE, _CALL_TARGET_RE):
        for m in regex.finditer(text):
            key = f"custom_call@{m.group(1)}"
            counts[key] = counts.get(key, 0) + 1
    return counts


#: a collective's device-id table (``replica_groups = dense<...> :
#: tensor<2x4xi64>``): an attribute, not the element type of any value
_DEVICE_TABLE_RE = re.compile(
    r"(?:replica_groups|source_target_pairs) = dense<[^>]*> : tensor<[^>]*>")


def _dtype_histogram(text: str) -> Dict[str, int]:
    counts: Dict[str, int] = {}
    for m in _TENSOR_DTYPE_RE.finditer(_DEVICE_TABLE_RE.sub("", text)):
        dt = m.group(1)
        counts[dt] = counts.get(dt, 0) + 1
    return counts


def _collectives(op_counts: Dict[str, int]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for op, n in op_counts.items():
        if op in _COLLECTIVE_OPS:
            out[op] = n
        elif op.startswith("custom_call@") \
                and op.split("@", 1)[1] in _COLLECTIVE_CUSTOM_CALLS:
            out[op] = n
        elif op.startswith("sdy."):
            out[op] = n
    return out


def _parse_sharding(spec: str, rank: int) -> Optional[List[int]]:
    """Per-dimension tile counts of a GSPMD sharding string for a tensor of
    ``rank`` dims: ``{replicated}`` -> all ones; ``{devices=[a,b,...]<=[N]
    ...}`` -> leading ``rank`` entries of the tile assignment (trailing
    entries — ``last_tile_dim_replicate`` and friends — are replication
    tiles).  None when the string is not understood (``{manual}``, ...)."""
    spec = spec.strip()
    if spec in ("{replicated}", "{maximal}") or spec.startswith("{maximal"):
        return [1] * rank
    m = re.search(r"devices=\[([0-9,]+)\]", spec)
    if not m:
        return None
    tiles = [int(t) for t in m.group(1).split(",") if t]
    if len(tiles) < rank:
        return None
    return tiles[:rank]


@dataclass
class SortSignature:
    """One sort op in the lowered program, with its sharding context."""

    dimension: int
    rank: int
    shape: str                       # "3x64xf32"
    sharding: Optional[str] = None   # GSPMD string reaching the operand
    #: True when the sort DIMENSION is sharded while every batch dim is
    #: replicated — the GSPMD miscompile hazard class (TM705)
    sharded_sort_dim: bool = False

    def key(self) -> tuple:
        return (self.dimension, self.rank, self.shape,
                self.sharded_sort_dim)

    def to_dict(self) -> Dict[str, Any]:
        return {"dimension": self.dimension, "rank": self.rank,
                "shape": self.shape, "sharding": self.sharding,
                "shardedSortDim": self.sharded_sort_dim}


#: shape-preserving elementwise ops GSPMD propagates sharding through — the
#: detector follows them backwards from a sort operand to the annotation
#: (the metrics sort ``-scores``: a negate sits between the constraint and
#: the sort in the real pre-PR-4 program)
_SHARDING_PASSTHROUGH = frozenset({
    "stablehlo.negate", "stablehlo.convert", "stablehlo.abs",
    "stablehlo.multiply", "stablehlo.add", "stablehlo.subtract",
    "stablehlo.divide", "stablehlo.maximum", "stablehlo.minimum",
    "stablehlo.select", "stablehlo.compare", "stablehlo.clamp",
    "stablehlo.and", "stablehlo.or", "stablehlo.xor", "stablehlo.not",
    "stablehlo.exponential", "stablehlo.log", "stablehlo.logistic",
    "stablehlo.tanh", "stablehlo.sqrt", "stablehlo.rsqrt",
    "stablehlo.sign", "stablehlo.floor", "stablehlo.ceil",
    "stablehlo.copy", "stablehlo.optimization_barrier",
})


class _Module:
    """Light per-function SSA view of a canonical StableHLO module, just
    deep enough to resolve which sharding annotation reaches a sort operand:
    one GSPMD ``custom_call @Sharding`` def, followed backwards through
    shape-preserving elementwise ops and private-function call boundaries —
    the shapes jax's lowering actually emits."""

    def __init__(self, text: str):
        self.funcs: Dict[str, Dict[str, str]] = {}       # fn -> var -> line
        #: fn -> [(arg name, tensor shape, sharding-or-None), ...]
        self.func_args: Dict[str, List[Tuple[str, str, Optional[str]]]] = {}
        self.calls: Dict[str, List[Tuple[str, List[str]]]] = {}
        current = None
        for line in text.split("\n"):
            fm = _FUNC_RE.search(line)
            if fm:
                current = fm.group(1)
                self.funcs.setdefault(current, {})
                args = []
                sig = line[fm.end() - 1:]
                for am in _ARG_SHARD_RE.finditer(sig):
                    args.append((am.group(1), am.group(2), am.group(4)))
                self.func_args[current] = args
                continue
            if current is None:
                continue
            dm = _DEF_RE.match(line)
            if dm:
                self.funcs[current][dm.group(1)] = dm.group(2)
            for cm in _CALL_RE.finditer(line):
                ops = [o.strip() for o in cm.group(2).split(",") if o.strip()]
                self.calls.setdefault(cm.group(1), []).append((current, ops))

    def type_of(self, fn: str, var: str) -> Optional[str]:
        """Tensor shape string (e.g. ``2x2x64xf32``) of ``var`` in ``fn``:
        from its def line's result type (the last ``tensor<...>`` printed —
        the ``-> type`` of a call-like op, the trailing ``: type``
        otherwise) or its function-arg annotation."""
        defline = self.funcs.get(fn, {}).get(var)
        if defline is not None:
            types = _TENSOR_RE.findall(defline)
            return types[-1] if types else None
        for name, shape, _shard in self.func_args.get(fn, []):
            if name == var:
                return shape
        return None

    def sharding_of(self, fn: str, var: str, depth: int = 0) -> Optional[str]:
        """GSPMD sharding string reaching ``var`` inside ``fn``, or None."""
        if depth > 24:
            return None
        defline = self.funcs.get(fn, {}).get(var)
        if defline is not None:
            if "custom_call @Sharding" in defline:
                sm = _SHARDING_ATTR_RE.search(defline)
                return sm.group(1) if sm else None
            om = _OP_NAME_RE.match(defline)
            op = om.group(1) if om else ""
            if op in _SHARDING_PASSTHROUGH:
                rhs = defline.split(" : ", 1)[0]
                for tok in _SSA_RE.findall(rhs):
                    found = self.sharding_of(fn, tok, depth + 1)
                    if found is not None:
                        return found
            return None
        # a block argument: entry sharding attr, else resolve at call sites
        for idx, (name, _shape, shard) in enumerate(
                self.func_args.get(fn, [])):
            if name != var:
                continue
            if shard is not None:
                return shard
            for caller, ops in self.calls.get(fn, []):
                if idx < len(ops):
                    found = self.sharding_of(caller, ops[idx], depth + 1)
                    if found is not None:
                        return found
        return None


def _sort_signatures(text: str) -> List[SortSignature]:
    mod = _Module(text)
    out: List[SortSignature] = []
    current = None
    for line in text.split("\n"):
        fm = _FUNC_RE.search(line)
        if fm:
            current = fm.group(1)
        if '"stablehlo.sort"' not in line and "stablehlo.sort(" not in line:
            continue
        dim_m = _SORT_DIM_RE.search(line)
        dimension = int(dim_m.group(1)) if dim_m else 0
        ops_m = _OPERANDS_RE.search(line)
        operands = [o.strip().split("#")[0] for o in ops_m.group(1).split(",")
                    if o.strip().startswith("%")] if ops_m else []
        # operand shape via the def/arg type map (the sort's own type
        # signature prints after its comparator region, lines away)
        shape = next((t for t in
                      (mod.type_of(current or "main", v) for v in operands)
                      if t), "?")
        rank = len(re.findall(r"(?:\d+|\?)x", shape))
        sig = SortSignature(dimension=dimension, rank=rank, shape=shape)
        for var in operands:
            shard = mod.sharding_of(current or "main", var)
            if shard is None:
                continue
            sig.sharding = shard
            tiles = _parse_sharding(shard, rank)
            if tiles is None:
                continue
            if dimension < len(tiles) and tiles[dimension] > 1 \
                    and all(t == 1 for i, t in enumerate(tiles)
                            if i != dimension):
                sig.sharded_sort_dim = True
                break
        out.append(sig)
    return out


# ---------------------------------------------------------------------------
# snapshots
# ---------------------------------------------------------------------------

@dataclass
class IRSnapshot:
    """Canonical IR of one program family + its extracted semantic features.

    Every feature derives from ``text`` alone (``from_text``), so goldens
    reload from disk with full differ fidelity — and a reviewer can tamper a
    golden file to see exactly which class fires.
    """

    key: str
    text: str
    ir_fingerprint: str
    op_counts: Dict[str, int] = field(default_factory=dict)
    dtype_counts: Dict[str, int] = field(default_factory=dict)
    collectives: Dict[str, int] = field(default_factory=dict)
    sorts: List[SortSignature] = field(default_factory=list)
    #: content fingerprint of the program source/state (perf.programs /
    #: workflow.plan identity) — correlates the IR with executable-cache and
    #: BENCH records; NOT part of the diff classification
    content_fingerprint: Optional[str] = None
    min_devices: int = 1

    @classmethod
    def from_text(cls, key: str, text: str,
                  content_fingerprint: Optional[str] = None,
                  min_devices: int = 1) -> "IRSnapshot":
        canonical = canonicalize_stablehlo(text)
        ops = _op_histogram(canonical)
        return cls(
            key=key, text=canonical,
            ir_fingerprint=ir_fingerprint(canonical),
            op_counts=ops,
            dtype_counts=_dtype_histogram(canonical),
            collectives=_collectives(ops),
            sorts=_sort_signatures(canonical),
            content_fingerprint=content_fingerprint,
            min_devices=min_devices)

    def sharded_sort_hazards(self) -> List[SortSignature]:
        """Sort ops matching the GSPMD miscompile class (TM705 evidence)."""
        return [s for s in self.sorts if s.sharded_sort_dim]

    def to_index_entry(self) -> Dict[str, Any]:
        return {
            "irFingerprint": self.ir_fingerprint,
            "contentFingerprint": self.content_fingerprint,
            "minDevices": self.min_devices,
            "sorts": [s.to_dict() for s in self.sorts],
            "collectives": dict(self.collectives),
        }


def snapshot_lowered(key: str, lowered, content_fingerprint=None,
                     min_devices: int = 1) -> IRSnapshot:
    """Snapshot an already-``.lower()``-ed jax computation."""
    return IRSnapshot.from_text(key, lowered.as_text(),
                                content_fingerprint=content_fingerprint,
                                min_devices=min_devices)


def snapshot_program(key: str, fn, specs: Sequence[Any],
                     statics: Optional[Dict[str, Any]] = None,
                     min_devices: int = 1) -> IRSnapshot:
    """Lower a jitted program on abstract specs and snapshot it.

    ``fn`` must already be ``jax.jit``-wrapped (the module-level sweep
    programs are); ``statics`` are its static_argnames kwargs.  Pure
    trace+lower: zero backend compiles, no device buffers beyond baked
    constants.  The content fingerprint is the executable cache's stable key
    (``perf.programs.cache_key_fingerprint``) so corpus entries line up with
    cache stats and BENCH records.
    """
    from ..perf.programs import cache_key_fingerprint

    statics = statics or {}
    lowered = fn.lower(*specs, **statics)
    return snapshot_lowered(
        key, lowered,
        content_fingerprint=cache_key_fingerprint(fn, *specs,
                                                  statics=statics),
        min_devices=min_devices)


def snapshot_scoring_plan(plan, bucket: Optional[int] = None,
                          key: str = "serve.plan.scoring_prefix"
                          ) -> IRSnapshot:
    """Snapshot the fused device prefix of a
    :class:`~..serve.plan.CompiledScoringPlan` at one padding bucket
    (default: its max bucket) — the exact program its executables compile."""
    import jax

    if bucket is None:
        bucket = plan.max_bucket
    specs = [jax.ShapeDtypeStruct((bucket,) + tuple(trailing),
                                  np.dtype(dtype))
             for trailing, dtype in plan._entry_specs]
    lowered = jax.jit(plan._fused).lower(*specs)  # opcheck: allow(TM303) lower-only snapshot path, zero backend compiles
    return snapshot_lowered(key, lowered,
                            content_fingerprint=plan.fingerprint)


def snapshot_transform_plan(plan, dataset=None, bucket: Optional[int] = None,
                            key: str = "workflow.plan.transform_prefix"
                            ) -> IRSnapshot:
    """Snapshot the fused prefix of a
    :class:`~..workflow.plan.ColumnarTransformPlan` at one row bucket.

    Entry specs derive from the plan's entry table exactly as
    ``plancheck.analyze_transform_plan`` builds them; ``dataset`` is only
    needed when a lifted entry is an OPVector column (width known from the
    data)."""
    import jax

    from ..types import ColumnKind
    from ..workflow.plan import _transform_bucket

    if bucket is None:
        bucket = _transform_bucket(dataset.n_rows) if dataset is not None \
            else 64

    def spec_for(k):
        if k[0] == "lift":
            name = plan._entry_names[k]
            trailing: tuple = ()
            if dataset is not None and name in dataset:
                col = dataset[name]
                if col.kind is ColumnKind.VECTOR:
                    trailing = (int(col.data.shape[1]),)
                elif col.kind is ColumnKind.GEO:
                    trailing = (3,)
            return jax.ShapeDtypeStruct((bucket,) + trailing,
                                        np.dtype("float32"))
        runner, slot, _name = plan._entry_encoders[k]
        trailing, dtype = runner.device_input_spec(slot)
        return jax.ShapeDtypeStruct((bucket,) + tuple(trailing),
                                    np.dtype(dtype))

    specs = [spec_for(k) for k in plan._entry_keys]
    lowered = jax.jit(plan._fused).lower(*specs)  # opcheck: allow(TM303) lower-only snapshot path, zero backend compiles
    return snapshot_lowered(key, lowered,
                            content_fingerprint=plan.fingerprint)


# ---------------------------------------------------------------------------
# the semantic differ (TM700-TM705)
# ---------------------------------------------------------------------------

def diff_snapshots(old: Optional[IRSnapshot], new: Optional[IRSnapshot],
                   key: Optional[str] = None) -> List[Diagnostic]:
    """Classify the delta between a golden and a current snapshot.

    Exactly one snapshot may be None (corpus membership drift, TM700).  For
    a changed program the MOST severe applicable class wins per dimension:
    dtype drift (TM704) and a newly introduced sharded-sort hazard (TM705)
    are errors and may co-fire; collective drift (TM703) and op-histogram
    drift (TM702) are warnings; a canonical-text change with every semantic
    feature equal is TM701 info.  Equal fingerprints yield no diagnostics.
    """
    key = key or (new.key if new is not None else old.key)

    def _d(code: str, message: str) -> Diagnostic:
        # the corpus key rides as the location: baseline keys in
        # tools/ir_gate.py become "TM70x @ <family>", stable per family
        return make_diagnostic(code, message, location=key)

    def _tm705(s: SortSignature) -> Diagnostic:
        return _d(
            "TM705",
            f"IR of {key!r}: sort over tensor<{s.shape}> has its sort "
            f"dimension {s.dimension} SHARDED ({s.sharding}) while batch "
            f"dimensions stay replicated — the GSPMD sort-miscompile "
            f"pattern (pre-PR-4 eval sweeps returned metrics near -n under "
            f"a 4x2 mesh with no error raised)")
    if old is None and new is None:
        return []
    if old is None:
        # a brand-new family has no golden to diff against, but the hazard
        # scan must still run: the miscompile class shipping inside a new
        # program is exactly as wrong as appearing in an old one
        return [_d(
            "TM700", f"IR corpus: new program family {key!r} has no golden "
                     f"snapshot yet — record it with "
                     f"`cli lint --ir --update-goldens`")] \
            + [_tm705(s) for s in new.sharded_sort_hazards()]
    if new is None:
        return [_d(
            "TM700", f"IR corpus: golden program family {key!r} is no "
                     f"longer emitted (or was skipped in this environment) "
                     f"— refresh the corpus if intentional")]
    if old.ir_fingerprint == new.ir_fingerprint:
        return []

    diags: List[Diagnostic] = []

    # TM705 — the miscompile hazard class, newly introduced vs the golden
    old_hazards = {s.key() for s in old.sharded_sort_hazards()}
    diags.extend(_tm705(s) for s in new.sharded_sort_hazards()
                 if s.key() not in old_hazards)

    # TM704 — element-type inventory drift (dtype appears/disappears, or
    # counts migrate between float widths: silent widening/narrowing)
    old_dt, new_dt = old.dtype_counts, new.dtype_counts
    if set(old_dt) != set(new_dt):
        appeared = sorted(set(new_dt) - set(old_dt))
        vanished = sorted(set(old_dt) - set(new_dt))
        what = []
        if appeared:
            what.append(f"appeared: {', '.join(appeared)}")
        if vanished:
            what.append(f"vanished: {', '.join(vanished)}")
        diags.append(_d(
            "TM704",
            f"IR of {key!r}: element-type inventory changed "
            f"({'; '.join(what)}) — numeric semantics (precision, "
            f"accumulation grade) may have silently shifted"))
    else:
        floats = [d for d in old_dt if d.startswith(("f", "bf"))]
        shifted = [d for d in floats if old_dt[d] != new_dt[d]]
        if len(shifted) >= 2:
            moves = ", ".join(f"{d}: {old_dt[d]} -> {new_dt[d]}"
                              for d in sorted(shifted))
            diags.append(_d(
                "TM704",
                f"IR of {key!r}: tensor counts migrated between float "
                f"widths ({moves}) — a widening/narrowing drift"))

    # TM703 — collectives / resharding drift
    if old.collectives != new.collectives:
        def inv(c):
            return ", ".join(f"{k} x{v}" for k, v in sorted(c.items())) \
                or "none"
        diags.append(_d(
            "TM703",
            f"IR of {key!r}: collective/resharding inventory changed "
            f"({inv(old.collectives)} -> {inv(new.collectives)}) — "
            f"cross-device communication (and its numerics) moved"))

    # TM702 — fusion/layout drift (op histogram shifted beyond collectives)
    if old.op_counts != new.op_counts:
        changed = sorted(set(old.op_counts) | set(new.op_counts))
        deltas = [f"{op}: {old.op_counts.get(op, 0)} -> "
                  f"{new.op_counts.get(op, 0)}"
                  for op in changed
                  if old.op_counts.get(op, 0) != new.op_counts.get(op, 0)]
        shown = "; ".join(deltas[:6]) + (
            f"; ... {len(deltas) - 6} more" if len(deltas) > 6 else "")
        diags.append(_d(
            "TM702",
            f"IR of {key!r}: op histogram changed ({shown}) — "
            f"fusion/layout structure drifted; verify perf and parity "
            f"expectations still hold"))

    if not diags:
        # text changed, every semantic feature identical: benign drift
        diags.append(_d(
            "TM701",
            f"IR of {key!r}: canonical text drifted "
            f"({old.ir_fingerprint[:12]} -> {new.ir_fingerprint[:12]}) with "
            f"identical op/dtype/collective/sort signatures — benign; "
            f"refresh the corpus at leisure"))
    return diags


def diff_corpus(goldens: Dict[str, IRSnapshot],
                current: Dict[str, IRSnapshot],
                skipped: Sequence[str] = ()) -> List[Diagnostic]:
    """Diff a whole corpus.  ``skipped`` keys (families this environment
    cannot build, e.g. mesh variants without enough devices) are exempt from
    the TM700 missing-family report."""
    diags: List[Diagnostic] = []
    for key in sorted(set(goldens) | set(current)):
        if key in skipped and key not in current:
            continue
        diags.extend(diff_snapshots(goldens.get(key), current.get(key),
                                    key=key))
    return diags


# ---------------------------------------------------------------------------
# program-family registry
# ---------------------------------------------------------------------------

@dataclass
class CorpusEntry:
    """One program family: a builder returning its IRSnapshot on demand."""

    key: str
    build: Callable[[], IRSnapshot]
    min_devices: int = 1


def _spec(*shape, dtype="float32"):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))


def _binary_metric():
    from ..evaluators import metrics as M

    return M.METRICS_BINARY["auPR"]


def _sweep_entries() -> List[CorpusEntry]:
    """The fold x grid sweep program families, on tiny abstract shapes.

    Shapes are deliberately small (n=64, d=4, k=2 folds, g=2 grid points,
    short loops): the IR structure — op mix, dtypes, collectives, sort
    shapes — is what the corpus pins; row counts only scale tensor dims.
    """
    n, d, k, g = 64, 4, 2, 2

    def irls():
        from ..models.logistic import _irls_sweep

        return snapshot_program(
            "models.logistic.irls_sweep", _irls_sweep,
            [_spec(n, d + 1), _spec(n), _spec(k, n), _spec(g)],
            statics=dict(max_iter=3, has_intercept=True))

    def fista():
        from ..models.logistic import _fista_sweep

        return snapshot_program(
            "models.logistic.fista_sweep", _fista_sweep,
            [_spec(n, d + 1), _spec(n), _spec(k, n), _spec(g), _spec(g)],
            statics=dict(max_iter=3, has_intercept=True))

    def ridge():
        from ..models.linear import _ridge_sweep

        return snapshot_program(
            "models.linear.ridge_sweep", _ridge_sweep,
            [_spec(n, d + 1), _spec(n), _spec(k, n), _spec(g)],
            statics=dict(has_intercept=True))

    def svc():
        from ..models.svm import _svc_cv_program

        return snapshot_program(
            "models.svm.svc_cv_program", _svc_cv_program,
            [_spec(n, d), _spec(n), _spec(n), _spec(k, n), _spec(k, n),
             _spec(g)],
            statics=dict(max_iter=3, has_intercept=True,
                         metric_fn=_binary_metric()))

    def gbt():
        from ..models.trees import _gbt_cv_program

        scalars = dict(eta=_spec(), reg_lambda=_spec(), alpha=_spec(),
                       gamma=_spec(), min_child_weight=_spec(),
                       scale_pos_weight=_spec(), max_delta_step=_spec())
        return snapshot_program(
            "models.trees.gbt_cv_program", _gbt_cv_program,
            [_spec(n, d, dtype="int8"), _spec(n), _spec(k, n), _spec(k, n),
             _spec(2, dtype="uint32")],
            statics=dict(n_rounds=2, max_depth=2, n_bins=8,
                         objective="binary:logistic", num_class=1,
                         subsample=1.0, colsample_bytree=1.0,
                         colsample_bylevel=1.0,
                         metric_fn=_binary_metric(), **scalars))

    def forest():
        from ..models.trees import _forest_cv_program

        t = 3
        return snapshot_program(
            "models.trees.forest_cv_program", _forest_cv_program,
            [_spec(n, d, dtype="int8"), _spec(n), _spec(n, 1), _spec(k, n),
             _spec(k, n), _spec(t, d), _spec(t, n)],
            statics=dict(max_depth=2, n_bins=8, reg_lambda=_spec(),
                         min_child_weight=_spec(), classification=True,
                         metric_fn=_binary_metric(), int_exact=False))

    def eval_linear():
        from ..models.base import _eval_linear_sweep_for

        return snapshot_program(
            "models.base.eval_linear_sweep", _eval_linear_sweep_for(None),
            [_spec(n, d + 1), _spec(n), _spec(g, k, d + 1), _spec(k, n)],
            statics=dict(metric_fn=_binary_metric(), link="sigmoid"))

    def eval_softmax():
        from ..evaluators import metrics as M
        from ..models.base import _eval_softmax_sweep_for

        c = 3
        return snapshot_program(
            "models.base.eval_softmax_sweep", _eval_softmax_sweep_for(None),
            [_spec(n, d + 1), _spec(n), _spec(g, k, d + 1, c), _spec(k, n)],
            statics=dict(metric_fn=M.multiclass_error))

    def eval_linear_meshed():
        """The FIXED eval-sweep form under a 4x2 mesh: the per-mesh closure
        deals the lanes over the devices inside a shard_map region, each
        sorting whole rows — the corpus proof that the sharded-sort-dim
        hazard stays absent from the shipped program."""
        from ..models.base import _eval_linear_sweep_for
        from ..parallel.mesh import make_mesh

        mesh = make_mesh(4, 2)
        return snapshot_program(
            "models.base.eval_linear_sweep@mesh4x2",
            _eval_linear_sweep_for(mesh),
            [_spec(n, d + 1), _spec(n), _spec(g, k, d + 1), _spec(k, n)],
            statics=dict(metric_fn=_binary_metric(), link="sigmoid"),
            min_devices=8)

    def _fresh_jit(jitted, static_argnames):
        """A FRESH-IDENTITY jit wrapper around a module-level sweep program.

        The sweep programs read the ambient mesh at trace time (their
        ``constrain_*`` annotations), but jax's tracing caches key on the
        underlying CALLABLE plus avals/shardings — and the corpus lowers on
        abstract specs with NO shardings, so lowering the meshed variant
        through the same program object that already lowered the unmeshed
        family silently reuses the unmeshed trace (a fresh ``jax.jit`` of
        the same function does too).  A new wrapper function per snapshot
        defeats the cache by identity.  (Real dispatch never hits this:
        meshed operands carry NamedShardings that key the trace apart, and
        the AOT cache keys on the mesh token besides.)
        """
        import functools

        import jax as _jax

        inner = jitted.__wrapped__

        @functools.wraps(inner)
        def _mesh_variant(*args, **kwargs):
            return inner(*args, **kwargs)

        return _jax.jit(_mesh_variant, static_argnames=static_argnames)

    def irls_meshed():
        """The dp x mp SHARDED IRLS sweep: since PR 39 one shard_map region,
        a chip's rows in, the row sums psum'd over data, the grid dealt over
        the model axis — the corpus pins the sharded lowering across jax
        bumps, and the TM705 scan proves the sharded-sort hazard stays
        absent."""
        from ..models.logistic import _irls_sweep
        from ..parallel.mesh import make_mesh, use_mesh

        with use_mesh(make_mesh(4, 2)):
            return snapshot_program(
                "models.logistic.irls_sweep@mesh4x2",
                _fresh_jit(_irls_sweep, ("max_iter", "has_intercept")),
                [_spec(n, d + 1), _spec(n), _spec(k, n), _spec(g)],
                statics=dict(max_iter=3, has_intercept=True),
                min_devices=8)

    def svc_meshed():
        """The sharded SVC CV program under the 4x2 mesh — the sort-based
        metric runs inside, so this family is the standing TM705 regression
        surface for the sharded sweep path."""
        from ..models.svm import _svc_cv_program
        from ..parallel.mesh import make_mesh, use_mesh

        with use_mesh(make_mesh(4, 2)):
            return snapshot_program(
                "models.svm.svc_cv_program@mesh4x2",
                _fresh_jit(_svc_cv_program,
                           ("max_iter", "has_intercept", "metric_fn")),
                [_spec(n, d), _spec(n), _spec(n), _spec(k, n), _spec(k, n),
                 _spec(g)],
                statics=dict(max_iter=3, has_intercept=True,
                             metric_fn=_binary_metric()),
                min_devices=8)

    return [
        CorpusEntry("models.logistic.irls_sweep", irls),
        CorpusEntry("models.logistic.fista_sweep", fista),
        CorpusEntry("models.linear.ridge_sweep", ridge),
        CorpusEntry("models.svm.svc_cv_program", svc),
        CorpusEntry("models.trees.gbt_cv_program", gbt),
        CorpusEntry("models.trees.forest_cv_program", forest),
        CorpusEntry("models.base.eval_linear_sweep", eval_linear),
        CorpusEntry("models.base.eval_softmax_sweep", eval_softmax),
        CorpusEntry("models.base.eval_linear_sweep@mesh4x2",
                    eval_linear_meshed, min_devices=8),
        CorpusEntry("models.logistic.irls_sweep@mesh4x2", irls_meshed,
                    min_devices=8),
        CorpusEntry("models.svm.svc_cv_program@mesh4x2", svc_meshed,
                    min_devices=8),
    ]


def _plan_fixture_runners():
    """Deterministic fitted runner DAG for the plan families — built from
    hand-set fitted state (no training, no data, no RNG): two Real features
    through a NumericVectorizerModel with fixed fills, a Binary feature
    through a BinaryVectorizer, both into a VectorsCombiner.  Exercises the
    canonical-lift entries, multi-stage fusion across DAG layers, and the
    interleave/concat kernels the real prep prefix compiles."""
    from ..features.builder import FeatureBuilder
    from ..ops.combiner import VectorsCombiner
    from ..ops.numeric import BinaryVectorizer, NumericVectorizerModel
    from ..serve.plan import resolve_scoring_stages

    x1 = FeatureBuilder.Real("x1").extract_field().as_predictor()
    x2 = FeatureBuilder.Real("x2").extract_field().as_predictor()
    b1 = FeatureBuilder.Binary("b1").extract_field().as_predictor()
    vec = x1.transform_with(
        NumericVectorizerModel(fills=np.array([0.5, -1.25]),
                               track_nulls=True), x2)
    bvec = b1.transform_with(BinaryVectorizer())
    out = vec.transform_with(VectorsCombiner(), bvec)
    return [out], resolve_scoring_stages([out], {})


def _plan_entries() -> List[CorpusEntry]:
    def transform_prefix():
        from ..workflow.plan import ColumnarTransformPlan

        _features, runners = _plan_fixture_runners()
        plan = ColumnarTransformPlan(runners,
                                     frozenset({"x1", "x2", "b1"}))
        return snapshot_transform_plan(plan, bucket=64)

    def transform_prefix_chunk():
        # the chunked-epoch program (ISSUE 13): workflow/ooc.py derives its
        # plan through plan_for over the chunk's column names and dispatches
        # it at the fixed chunk tile — build it the same way here and
        # snapshot at a 64-row tile.  The corpus pins that this entry dedups
        # BIT-IDENTICALLY (same irFingerprint) with the in-memory
        # transform_prefix family above: chunking must not fork the program
        # surface (asserted in tests/test_chunked_ingest.py).
        from ..workflow.plan import plan_for

        _features, runners = _plan_fixture_runners()
        plan, _remainder = plan_for(runners, frozenset({"x1", "x2", "b1"}))
        return snapshot_transform_plan(
            plan, bucket=64, key="workflow.plan.transform_prefix@chunk")

    def scoring_prefix():
        from ..serve.plan import CompiledScoringPlan

        features, _runners = _plan_fixture_runners()
        plan = CompiledScoringPlan(_Shim(features, {}), min_bucket=8,
                                   max_bucket=64, strict=False)
        return snapshot_scoring_plan(plan, bucket=64)

    def scoring_prefix_bf16():
        # the reduced-precision scoring class (ISSUE 19): the same fused
        # prefix with bf16 boundary casts folded in.  Pinned as its own
        # family so a jax bump that changes how the casts lower (or
        # silently drops them) diffs against THIS golden instead of
        # perturbing the f32 family — whose bit-identity with production
        # f32 plans is itself a pinned invariant.
        from ..serve.plan import CompiledScoringPlan

        features, _runners = _plan_fixture_runners()
        plan = CompiledScoringPlan(_Shim(features, {}), min_bucket=8,
                                   max_bucket=64, strict=False,
                                   precision="bf16")
        return snapshot_scoring_plan(
            plan, bucket=64, key="serve.plan.scoring_prefix@bf16")

    def transform_prefix_meshed():
        """The dp x mp SHARDED transform prefix (ISSUE 15): every entry row
        block constrained to the data axis — pinned so the pod-scale
        transform program form (and its collective inventory: layout pins
        only, NO all-gathers) survives jax bumps.  Built under the mesh, so
        the plan fingerprint carries the mesh token (distinct from the
        unmeshed family by design)."""
        from ..parallel.mesh import make_mesh, use_mesh
        from ..workflow.plan import ColumnarTransformPlan

        with use_mesh(make_mesh(4, 2)):
            _features, runners = _plan_fixture_runners()
            plan = ColumnarTransformPlan(runners,
                                         frozenset({"x1", "x2", "b1"}))
            return snapshot_transform_plan(
                plan, bucket=64, key="workflow.plan.transform_prefix@mesh4x2")

    return [
        CorpusEntry("workflow.plan.transform_prefix", transform_prefix),
        CorpusEntry("workflow.plan.transform_prefix@chunk",
                    transform_prefix_chunk),
        CorpusEntry("workflow.plan.transform_prefix@mesh4x2",
                    transform_prefix_meshed, min_devices=8),
        CorpusEntry("serve.plan.scoring_prefix", scoring_prefix),
        CorpusEntry("serve.plan.scoring_prefix@bf16", scoring_prefix_bf16),
    ]


class _Shim:
    """Minimal (result_features, fitted) carrier for CompiledScoringPlan."""

    def __init__(self, result_features, fitted):
        self.result_features = list(result_features)
        self.fitted = dict(fitted)


class CorpusUnavailable(RuntimeError):
    """Raised by a family builder when this environment cannot lower it
    (e.g. no TPU cross-lowering support in the jax build) — build_corpus
    records the family as skipped instead of failing the whole snapshot."""


def _kernel_entries() -> List[CorpusEntry]:
    """The Pallas kernel program families (perf/kernels/, ISSUE 10).

    Two pins per design: the ``@interpret`` families lower the emulation on
    CPU — plain StableHLO, the kernel BODY's full semantics golden — and
    ``hist@tpu`` cross-lowers the compiled form, pinning the
    ``custom_call @tpu_custom_call`` interface (operand layout, dtypes,
    call count; the volatile Mosaic payload is elided by
    ``canonicalize_stablehlo``).  All lower-only: zero backend compiles.
    """
    import jax

    from ..perf.programs import cache_key_fingerprint

    L, n, two_k, d, nn, n_bins = 2, 256, 2, 4, 2, 8
    B = n_bins + 1

    def _hist_fn(interpret: bool):
        from ..perf.kernels.histogram import hist_level_pallas

        def hist_program(local, ghT, binned):
            return hist_level_pallas(local, ghT, binned, nn, n_bins,
                                     int_exact=True, interpret=interpret,
                                     chunk=128)

        return hist_program

    _hist_specs = [_spec(L, n, dtype="int32"),
                   _spec(L, two_k, n, dtype="int8"),
                   _spec(n, d, dtype="int32")]

    def hist_interpret():
        fn = jax.jit(_hist_fn(True))  # opcheck: allow(TM303) lower-only snapshot path, zero backend compiles
        return snapshot_lowered(
            "perf.kernels.hist@interpret", fn.lower(*_hist_specs),
            content_fingerprint=cache_key_fingerprint(fn, *_hist_specs))

    def hist_tpu():
        fn = jax.jit(_hist_fn(False))  # opcheck: allow(TM303) lower-only snapshot path, zero backend compiles
        try:
            lowered = fn.trace(*_hist_specs).lower(
                lowering_platforms=("tpu",))
        except Exception as e:  # noqa: BLE001 — env-dependent cross-lowering
            raise CorpusUnavailable(
                f"TPU cross-lowering unavailable: {type(e).__name__}: {e}")
        return snapshot_lowered(
            "perf.kernels.hist@tpu", lowered,
            content_fingerprint=cache_key_fingerprint(fn, *_hist_specs))

    def split_interpret():
        from ..perf.kernels.splitscan import split_scan_pallas

        def split_program(hg, hh, G, H, mask, reg_lambda, alpha, gamma, mcw):
            return split_scan_pallas(hg, hh, G, H, mask, n_bins, reg_lambda,
                                     alpha, gamma, mcw, interpret=True)

        fn = jax.jit(split_program)  # opcheck: allow(TM303) lower-only snapshot path, zero backend compiles
        specs = [_spec(L, nn, 1, d, B), _spec(L, nn, 1, d, B),
                 _spec(L, nn, 1), _spec(L, nn, 1), _spec(L, d),
                 _spec(), _spec(), _spec(), _spec()]
        return snapshot_lowered(
            "perf.kernels.split_scan@interpret", fn.lower(*specs),
            content_fingerprint=cache_key_fingerprint(fn, *specs))

    def encode_interpret():
        import jax.numpy as jnp

        from ..perf.kernels.encode import bucketize_right_encode, onehot_codes

        def encode_program(x, splits, codes):
            buckets = bucketize_right_encode(x, splits, True, False,
                                             interpret=True)
            levels = onehot_codes(codes, 7, interpret=True)
            return jnp.concatenate([buckets, levels], axis=1)

        fn = jax.jit(encode_program)  # opcheck: allow(TM303) lower-only snapshot path, zero backend compiles
        specs = [_spec(n), _spec(5), _spec(n, dtype="int32")]
        return snapshot_lowered(
            "perf.kernels.encode@interpret", fn.lower(*specs),
            content_fingerprint=cache_key_fingerprint(fn, *specs))

    return [
        CorpusEntry("perf.kernels.hist@interpret", hist_interpret),
        CorpusEntry("perf.kernels.hist@tpu", hist_tpu),
        CorpusEntry("perf.kernels.split_scan@interpret", split_interpret),
        CorpusEntry("perf.kernels.encode@interpret", encode_interpret),
    ]


def corpus_entries() -> List[CorpusEntry]:
    """Every builtin program family, in stable key order."""
    return _sweep_entries() + _plan_entries() + _kernel_entries()


def build_corpus(families: Optional[Sequence[str]] = None
                 ) -> Tuple[Dict[str, IRSnapshot], List[str]]:
    """Build snapshots for every (matching) family this environment can
    lower.  Returns ``(snapshots, skipped_keys)``; ``families`` filters by
    substring match on the key.  Zero backend compiles by construction —
    asserted with the compile probe in tests/test_irsnap.py.
    """
    import jax

    n_dev = jax.device_count()
    snaps: Dict[str, IRSnapshot] = {}
    skipped: List[str] = []
    for entry in corpus_entries():
        if families and not any(f in entry.key for f in families):
            skipped.append(entry.key)
            continue
        if entry.min_devices > n_dev:
            log.info("irsnap: skipping %s (needs %d devices, have %d)",
                     entry.key, entry.min_devices, n_dev)
            skipped.append(entry.key)
            continue
        try:
            snap = entry.build()
        except CorpusUnavailable as e:
            log.info("irsnap: skipping %s (%s)", entry.key, e)
            skipped.append(entry.key)
            continue
        snap.min_devices = entry.min_devices
        snaps[snap.key] = snap
    return snaps, skipped


# ---------------------------------------------------------------------------
# golden-corpus persistence
# ---------------------------------------------------------------------------

def default_goldens_dir() -> str:
    """``tests/goldens/ir`` of the repo checkout holding this package."""
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), "tests", "goldens", "ir")


def _slug(key: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.-]", "_", key)


def save_corpus(snaps: Dict[str, IRSnapshot], goldens_dir: str) -> str:
    """Write canonical IR text files + the index (fingerprints, content
    fingerprints, environment provenance).  Returns the index path."""
    import jax

    os.makedirs(goldens_dir, exist_ok=True)
    index = {
        "version": CORPUS_VERSION,
        "jaxVersion": jax.__version__,
        "platform": jax.default_backend(),
        "deviceCount": jax.device_count(),
        "entries": {},
    }
    for key in sorted(snaps):
        snap = snaps[key]
        fname = f"{_slug(key)}.stablehlo.txt"
        with open(os.path.join(goldens_dir, fname), "w") as fh:
            fh.write(snap.text)
        index["entries"][key] = {"file": fname, **snap.to_index_entry()}
    # drop stale text files for families no longer in the corpus
    keep = {f"{_slug(k)}.stablehlo.txt" for k in snaps} | {"index.json"}
    for f in os.listdir(goldens_dir):
        if f.endswith(".stablehlo.txt") and f not in keep:
            os.remove(os.path.join(goldens_dir, f))
    index_path = os.path.join(goldens_dir, "index.json")
    with open(index_path, "w") as fh:
        json.dump(index, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return index_path


def load_corpus(goldens_dir: str) -> Tuple[Dict[str, IRSnapshot], Dict]:
    """Reload a golden corpus: snapshots are re-derived from the canonical
    text files (the differ never trusts stale index features), the index
    supplies provenance + content fingerprints.  Raises FileNotFoundError
    when the corpus (or a referenced text file) is absent — a gate must not
    silently pass on a missing corpus."""
    index_path = os.path.join(goldens_dir, "index.json")
    with open(index_path) as fh:
        index = json.load(fh)
    snaps: Dict[str, IRSnapshot] = {}
    for key, meta in index.get("entries", {}).items():
        path = os.path.join(goldens_dir, meta["file"])
        with open(path) as fh:
            text = fh.read()
        snap = IRSnapshot.from_text(
            key, text, content_fingerprint=meta.get("contentFingerprint"),
            min_devices=int(meta.get("minDevices", 1)))
        snaps[key] = snap
    return snaps, index


@dataclass
class CorpusDiff:
    """Result of one corpus comparison (the ``irDiff`` JSONL payload)."""

    compared: int
    changed: List[str]
    skipped: List[str]
    diagnostics: List[Diagnostic]
    golden_jax_version: Optional[str] = None
    current_jax_version: Optional[str] = None
    golden_platform: Optional[str] = None
    current_platform: Optional[str] = None

    def to_dict(self) -> Dict[str, Any]:
        return {
            "compared": self.compared,
            "changed": list(self.changed),
            "skipped": list(self.skipped),
            "counts": _count_by_code(self.diagnostics),
            "goldenJaxVersion": self.golden_jax_version,
            "currentJaxVersion": self.current_jax_version,
            "goldenPlatform": self.golden_platform,
            "currentPlatform": self.current_platform,
        }


def _count_by_code(diags: Sequence[Diagnostic]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for d in diags:
        out[d.code] = out.get(d.code, 0) + 1
    return out


def check_ir_corpus(goldens_dir: Optional[str] = None,
                    families: Optional[Sequence[str]] = None
                    ) -> Tuple[CorpusDiff, Dict[str, IRSnapshot]]:
    """Snapshot the current program families and diff them against the
    golden corpus.  The main ``cli lint --ir`` entry point; returns the
    structured diff plus the freshly built snapshots (for
    --update-goldens)."""
    import jax

    goldens_dir = goldens_dir or default_goldens_dir()
    # goldens first: a missing/typo'd corpus dir must refuse BEFORE paying
    # for eleven program lowerings
    goldens, index = load_corpus(goldens_dir)
    current, skipped = build_corpus(families=families)
    if families:
        goldens = {k: v for k, v in goldens.items()
                   if any(f in k for f in families)}
    # mesh variants this environment cannot lower are also exempt
    n_dev = jax.device_count()
    skipped = list(skipped) + [k for k, s in goldens.items()
                               if s.min_devices > n_dev]
    diags = diff_corpus(goldens, current, skipped=skipped)
    changed = sorted({
        k for k in set(goldens) & set(current)
        if goldens[k].ir_fingerprint != current[k].ir_fingerprint})
    diff = CorpusDiff(
        compared=len(set(goldens) & set(current)),
        changed=changed, skipped=sorted(set(skipped)), diagnostics=diags,
        golden_jax_version=index.get("jaxVersion"),
        current_jax_version=jax.__version__,
        golden_platform=index.get("platform"),
        current_platform=jax.default_backend())
    if diff.golden_platform and diff.golden_platform != diff.current_platform:
        diff.diagnostics.append(make_diagnostic(
            "TM700",
            f"IR corpus was goldened on platform "
            f"{diff.golden_platform!r} but this run lowers for "
            f"{diff.current_platform!r} — text drift below may be "
            f"platform lowering, not a jax upgrade",
            severity=None))
    return diff, current
