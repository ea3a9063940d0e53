"""Histogram-based tree ensembles on TPU — the XGBoost/RandomForest capability.

Reference capabilities replaced (SURVEY §2.9): OpXGBoostClassifier/Regressor (XGBoost4J
0.81 — C++ histogram GBT with Rabit allreduce, param surface in
core/src/main/scala/ml/dmlc/xgboost4j/scala/spark/XGBoostParams.scala:1-111),
OpRandomForestClassifier/Regressor, OpGBTClassifier/Regressor,
OpDecisionTreeClassifier/Regressor (Spark MLlib trees; multiclass handled natively,
MultiClassificationModelSelector.scala:49-76).

TPU-first design (not a port of either C++ codebase):
- Features are quantile-binned ON HOST once into small ints; everything after lives on
  device with static shapes.  A reserved bin (index ``n_bins``) holds missing values and
  gets a learned default direction per split (XGBoost's sparsity-aware algorithm).
- Trees are MULTI-OUTPUT: the grower takes per-class gradient/hessian columns
  (n, K) and leaves carry a (K,) value vector, so ONE tree structure serves binary
  (K=1), regression (K=1), and multiclass (K = num_class) problems.  This is the
  `multi_strategy="multi_output_tree"` design of modern XGBoost rather than
  K-trees-per-round: one growth pass per round regardless of K, which keeps the
  round loop a single ``lax.scan`` and the histogram contraction one big matmul.
- Trees grow LEVEL-WISE over a dense complete binary tree of static size
  ``2^(max_depth+1)-1``: per level, the (node, class, feature, bin) gradient/hessian
  histograms build as scatter-free MXU matmuls — a one-hot(node) x [grad|hess]
  activation contracted against a joint (feature, bin) one-hot (TPU lowers
  scatters to slow sorts, matmuls fly), row-chunked under ``lax.scan`` so the
  live activation stays a few MB per CV vmap lane at any row count, with
  sibling subtraction (right child = parent - left) and a totals-only deepest
  level cutting ~4x of the work.  Row routing and per-node table lookups are
  fused compare-multiply-reduces, never TPU gathers; a level's look-up reads
  the 2^l nodes that level can reach, not the heap.  When rows are sharded
  over the ``data`` mesh axis the histogram contraction IS the Rabit
  allreduce, inserted by XLA as a psum.
- Split gain is the XGBoost second-order formula with L2 ``reg_lambda``, L1 ``alpha``
  (soft-threshold on G), complexity ``gamma``, and ``min_child_weight``; leaves take
  ``-T_alpha(G)/(H+lambda) * eta`` clipped to ``max_delta_step``.  Multi-output gain
  sums the per-class terms (min_child_weight applies to the mean hessian across
  classes so K=1 reduces exactly to the scalar formula).
- GBT boosts under ``lax.scan`` (carry = margins), so the entire ensemble fit is ONE
  XLA program; per-round ``subsample`` / ``colsample_bytree`` masks derive from a
  folded-in PRNG key inside the scan.  RandomForest vmaps the same grower over
  per-tree Poisson bootstrap weights and per-tree feature masks.
- CV sweeps vmap the whole fit over the fold-weight axis and evaluate the metric on
  device, so a (grids x folds) selector sweep is one XLA program per grid config
  (the reference's per-fold Futures thread pool, OpCrossValidation.scala:114-134).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..data.dataset import Column
from ..perf.kernels import dispatch as _kdispatch
from ..perf.kernels import histogram as _khist
from ..perf.kernels import routing as _krout
from ..perf.kernels import splitscan as _ksplit
from ..perf.timers import activity
from ..stages.base import Param
from .base import PredictionEstimatorBase, PredictionModelBase
from .prediction import PredictionColumn

#: Default histogram resolution, matching the reference's Spark tree default
#: (RandomForestParams/GBTParams maxBins = 32, OpRandomForestClassifier.scala /
#: OpGBTClassifier.scala inherit it).  The XGBoost-flavored estimators expose
#: ``n_bins`` for callers that want max_bin-style resolution (up to 256).
#: Histogram cost scales linearly with the bin count on the TPU one-hot
#: formulation, so the reference default is also the fast default.
DEFAULT_BINS = 32

#: histogram-accumulation row-chunk size (see _grow_tree); module-level so
#: tests can shrink it to exercise the chunked path on small data
#: (``kernel_provenance()`` reports the bound value and ``chip_smoke.py``
#: prints it).
#: 2048 measured 3.8x faster than 8192 on v5e at 1M x 128 (64 bins): the
#: per-step (chunk, B*d) bin one-hot operand is small enough for XLA to keep
#: the one-hot -> matmul pipeline on-chip instead of spilling through HBM.
#: Re-measured at the 32-bin default (r4): 2048 and 4096 tie (RF cv 3.4s,
#: GBT cv 2.4s) while 8192 still regresses GBT 3.4x — 2048 stands.
_HIST_CHUNK = _kdispatch.HIST_CHUNK_DEFAULT

#: boosting reuses ONE materialized int8 bin one-hot across all rounds and
#: levels instead of regenerating it per histogram pass — GBT's measured
#: cost is ~100% one-hot construction (r5: ~29us/chunk rebuilt 150x for a
#: 50-round depth-3 fit; an int8 read is ~11us/chunk).  An estimator builds
#: it once a fit (``_GBTBase._shared_bin_onehot``) and every boosting program of
#: the fit reads that array: it is RESIDENT from the fit's first boosting
#: program to the fit's end and shows in ``peak_bytes_in_use``.  Until PR 32
#: each program built its own as a temporary, which that counter never
#: showed (1.70 GB read at 2^20 x 128 with 4.43 GB of one-hot live).  A
#: direct caller of ``_fit_gbt`` that hands none in gets the per-pass
#: rebuild.  Capped so the operand (n_padded * (bins+1) * d int8) never
#: risks HBM.
_BINOH_MAT_MAX_BYTES = 6_000_000_000


def _hist_admit(L: int, nn: int, K: int, B: int, d: int, elem_bytes: int,
                chunk: int, counted: bool = True):
    """THE histogram-kernel admission call (perf/kernels/dispatch.hist_mode)
    with the working-set formula written once: ``_level_hist`` consults it
    per level, ``_deep_hist_mode`` (at dispatch, uncounted: a dispatch is
    not a trace) for the deepest level, to decide whether the premade
    mat-binoh operand is needed — the two decisions must never diverge."""
    return _kdispatch.hist_mode(
        L * nn * 2 * K, B * d, chunk,
        lanes_bytes_per_row=4 * (L + L * 2 * K + d),
        elem_bytes=elem_bytes, counted=counted)


def _deepest_fresh_nodes(max_depth: int) -> int:
    """Nodes whose histograms the deepest level of a tree builds afresh:
    its 2^(max_depth-2) left children (their siblings come by subtraction)."""
    return max(1, 2 ** max(max_depth - 2, 0))


def _deep_hist_mode(L: int, K: int, max_depth: int, n_bins: int, d: int):
    """What a trace's ``_hist_admit`` will answer at the DEEPEST
    fresh-histogram level of a boosted tree (the largest per-level working
    set, nn = 2^(max_depth-2) left children): the Pallas kernel's mode, or
    None for the XLA scan.  The kernel builds its one-hots in VMEM per
    chunk, so the premade operand is moot only where it is admitted THERE:
    if VMEM admission routes the deep levels back to the XLA scan, the
    operand must exist or those levels lose the measured mat-binoh win."""
    return _hist_admit(L, _deepest_fresh_nodes(max_depth), K, n_bins + 1, d,
                       jnp.dtype(_hist_dtype()).itemsize, _HIST_CHUNK,
                       counted=False)


def _binoh_bytes(n: int, d: int, n_bins: int) -> int:
    """Bytes of the int8 bin one-hot ``_materialize_bin_oh`` builds for an
    (n, d) code block: padded rows x (bins + 1) x d, or 0 where it declines
    (the unchunked path of a small block, or an operand over the cap)."""
    if n <= 2 * _HIST_CHUNK:
        return 0
    nbytes = (n + (-n) % _HIST_CHUNK) * (n_bins + 1) * d
    return nbytes if nbytes <= _BINOH_MAT_MAX_BYTES else 0


def _materialize_bin_oh(binned: jnp.ndarray, n_bins: int):
    """(n_chunks, CHUNK, B*d) int8 bin one-hot for chunk-scanned growth, or
    None when the row count takes the unchunked path / exceeds the cap."""
    n, d = binned.shape
    B = n_bins + 1
    if not _binoh_bytes(n, d, n_bins):
        return None
    pad = (-n) % _HIST_CHUNK
    if pad:
        binned = jnp.pad(binned, ((0, pad), (0, 0)))
    bc = binned.reshape(-1, _HIST_CHUNK, d)

    def one_chunk(bc_i):
        # per-chunk construction: a single full-table broadcast compare made
        # XLA materialize an int32 (chunks, CHUNK, B, d) intermediate — 17 GB
        # at 1M x 128 x B33 (r5); the lax.map body's live temp is ~1 MB
        return (bc_i[:, None, :] ==
                jnp.arange(B, dtype=bc_i.dtype)[None, :, None]
                ).astype(jnp.int8).reshape(_HIST_CHUNK, B * d)

    return jax.lax.map(one_chunk, bc)


@partial(jax.jit, static_argnames=("n_bins",))
def _bin_onehot(binned, n_bins: int):
    """``_materialize_bin_oh`` as a program of its own: the operand an
    estimator builds once a fit and hands to every boosting program of it
    (``_GBTBase._shared_bin_onehot``)."""
    from ..parallel.mesh import constrain_rows

    with jax.named_scope("binoh_build"):
        return _materialize_bin_oh(constrain_rows(binned), n_bins)


def _hist_dtype():
    """MXU input dtype for histogram matmuls: bf16 on TPU (one-hots are exact,
    gradients tolerate the 8-bit mantissa; accumulation stays f32), full f32
    elsewhere so CPU tests are exact.

    The risky regime — large-magnitude regression gradients (~1e5) with
    near-tied split gains — is pinned by tests/test_trees.py's forced-bf16
    parity cases: bf16's exponent range carries the magnitude and the f32
    accumulation amortizes mantissa noise, so no gradient pre-scaling is
    needed (measured R² parity to ~1e-4 at grad 2e5)."""
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


# ---------------------------------------------------------------------------
# Host-side quantile binning
# ---------------------------------------------------------------------------

#: rows used for quantile-edge estimation on large tables — the XGBoost
#: approx-sketch tradeoff (exact quantiles cost O(n log n) per feature on
#: host; a 64k sample pins each edge to ~0.4% quantile error, far below the
#: 1/n_bins bucket width)
_QUANTILE_SAMPLE = 65536


def quantile_bin(x: np.ndarray, n_bins: int = DEFAULT_BINS
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """Bin (n, d) float features into int32 codes; NaN -> reserved bin ``n_bins``.

    Returns (binned (n, d) int32 in [0, n_bins], edges (d, n_bins-1) float32).
    Edges are per-feature quantile boundaries: value v falls in bin
    ``searchsorted(edges, v, side='right')``.  Above ``_QUANTILE_SAMPLE`` rows
    the edges come from a fixed-seed row sample (exact below it).
    """
    n, d = x.shape
    edges = quantile_edges(x, n_bins)
    # column-contiguous copy: per-column searchsorted on the row-major layout
    # pays a d-element stride per access and is ~4x slower
    xt = np.ascontiguousarray(x.T)
    binned_t = np.full((d, n), n_bins, dtype=np.int32)
    for j in range(d):
        col = xt[j]
        # NaNs sort past the last edge; the where() reroutes them to the
        # reserved missing bin without a masked scatter
        idx_j = np.searchsorted(edges[j], col, side="right").astype(np.int32)
        binned_t[j] = np.where(np.isfinite(col), idx_j, n_bins)
    return np.ascontiguousarray(binned_t.T), edges


def quantile_edges(x: np.ndarray, n_bins: int = DEFAULT_BINS) -> np.ndarray:
    """Per-feature quantile edges (d, n_bins-1) — the sketch half of
    quantile_bin (sampled above _QUANTILE_SAMPLE rows, fixed seed)."""
    n, d = x.shape
    if n > _QUANTILE_SAMPLE:
        idx = np.random.default_rng(0).choice(n, _QUANTILE_SAMPLE,
                                              replace=False)
        idx.sort()
        xt_q = np.ascontiguousarray(x[idx].T)  # row-gather first: rows are
    else:                                      # contiguous, columns are not
        xt_q = np.ascontiguousarray(x.T)
    edges = np.zeros((d, n_bins - 1), dtype=np.float32)
    qs = np.linspace(0.0, 1.0, n_bins + 1)[1:-1]
    for j in range(d):
        colq = xt_q[j]
        okq = np.isfinite(colq)
        if okq.sum() == 0:
            continue
        e = np.quantile(colq[okq], qs).astype(np.float32)
        edges[j] = np.maximum.accumulate(e)  # enforce monotone (ties collapse)
    return edges


# Shared binning across tree families: RF and GBT in one selector sweep the
# SAME feature block at the same resolution, so the host quantile sketch and
# the device digitization each need to run once, not once per family
# (VERDICT r2 weak #2).  Keyed on the content stamp of the raw block; bounded
# FIFO so device codes don't accumulate across selector fits.
_EDGE_CACHE: "dict[tuple, np.ndarray]" = {}
_BINNED_CACHE: "dict[tuple, Any]" = {}
_BIN_CACHE_MAX = 8


def _shared_binned(x32: np.ndarray, xd, n_bins: int) -> Tuple[Any, np.ndarray]:
    """(device bin codes, host edges) for ``x32`` (already placed as ``xd``)
    at ``n_bins``, cached so every tree family in a selector — and the final
    best-model refit — shares one quantile sketch + one device digitize."""
    from ..parallel.mesh import _content_stamp

    with activity("bin", n_bins=int(n_bins), rows=int(x32.shape[0])) as span:
        stamp = (x32.shape, _content_stamp(x32), int(n_bins))
        edges = _EDGE_CACHE.get(stamp)
        span.note(edges_hit=edges is not None)
        if edges is None:
            edges = quantile_edges(x32, int(n_bins))
            _EDGE_CACHE[stamp] = edges
            while len(_EDGE_CACHE) > _BIN_CACHE_MAX:
                _EDGE_CACHE.pop(next(iter(_EDGE_CACHE)))
        # the entry holds xd itself, so its id cannot be recycled while
        # cached (and the binned codes are guaranteed to live on xd's own
        # mesh/sharding)
        bkey = (id(xd), stamp)
        hit = _BINNED_CACHE.get(bkey)
        span.note(codes_hit=hit is not None)
        if hit is None:
            binned = _digitize_device(xd, jnp.asarray(edges), int(n_bins))
            _BINNED_CACHE[bkey] = (xd, binned)
            while len(_BINNED_CACHE) > _BIN_CACHE_MAX:
                _BINNED_CACHE.pop(next(iter(_BINNED_CACHE)))
            return binned, edges
        return hit[1], edges


@partial(jax.jit, static_argnames=("n_bins",))
def _digitize_device(x: jnp.ndarray, edges: jnp.ndarray, n_bins: int
                     ) -> jnp.ndarray:
    """Device digitization against fitted edges; non-finite -> missing bin.

    Lets CV sweeps bin from the SHARED raw device placement instead of
    transferring a second (n, d) int32 block per tree family.

    Counting compares instead of searchsorted: binary search lowers to a
    serialized per-column gather loop on TPU (measured ~39 s on (1M, 128)
    with 63 edges); the equivalent count of edges <= x is E streaming
    (n, d) compares on the VPU (~tens of ms), exactly
    searchsorted(side="right") for monotone edge rows.
    """
    def count_step(e, acc):
        return acc + (edges[None, :, e] <= x).astype(jnp.int32)

    binned = jax.lax.fori_loop(
        0, edges.shape[1], count_step,
        jnp.zeros(x.shape, jnp.int32), unroll=True)
    return jnp.where(jnp.isfinite(x), binned, n_bins).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Device tree grower (multi-output)
# ---------------------------------------------------------------------------

class Tree(NamedTuple):
    """Dense complete binary tree, node i has children 2i+1 / 2i+2."""

    feat: jnp.ndarray          # (m,) int32 split feature (0 when leaf)
    thr_bin: jnp.ndarray       # (m,) int32 split bin: go left if bin <= thr_bin
    miss_left: jnp.ndarray     # (m,) bool missing-value default direction
    is_leaf: jnp.ndarray       # (m,) bool
    value: jnp.ndarray         # (m, K) float32 leaf value vector (eta-scaled)


#: XGBoost L1 shrinkage on the gradient sum — ONE definition shared with the
#: split-scan kernels (perf/kernels/splitscan.py) so leaf values and split
#: gains can never drift apart across dispatch modes
_soft_threshold = _ksplit.soft_threshold


def _node_lookup(tbl: jnp.ndarray, node: jnp.ndarray) -> jnp.ndarray:
    """Leaf values tbl[..., node, :] as a fused compare-reduce over the WHOLE
    heap: tbl (..., m, K), node (..., n) -> (..., n, K).

    Same rationale as the walk's row select (perf/kernels/routing.py): a
    (n,) gather from a small table per lane serializes on TPU; the compare
    against iota fuses into a VPU streaming reduce.  A row's final node may sit at any level, so this one read —
    once a tree, after the walk — compares with all m = 2^(depth+1)-1 nodes
    (n * m * K multiply-adds).  The walk itself never does: see
    ``_level_lookup``.
    """
    m = tbl.shape[-2]
    oh = node[..., None] == jnp.arange(m, dtype=node.dtype)          # (.., n, m)
    return (oh[..., None] * tbl[..., None, :, :]).sum(axis=-2)       # (.., n, K)


def _lookup_nodes(max_depth: int) -> int:
    """Node-table entries one row is compared with while one tree grows (or
    is walked) and its leaf value is read: one ``_level_lookup`` over the
    2^l nodes of each level l < max_depth, then the whole heap once for the
    value: 63 + 127 = 190 at depth 6, 7 + 15 = 22 at depth 3."""
    return (2 ** max_depth - 1) + (2 ** (max_depth + 1) - 1)


def _level_lookup(feat, thr_bin, miss_left, is_leaf, local, d: int,
                  n_bins: int):
    """(feat, thr_bin, miss_left, is_leaf) of each row's node, read in the
    slice of the heap its LEVEL can reach: the four tables (..., nodes) hold
    the level's 2^l nodes, ``local`` (..., n) is the row's node id less the
    level's first.

    The tables are packed into ONE int32 word a node (``feat`` in the low
    bits, ``thr_bin`` above it, then the two flags; widths from ``d`` and
    ``n_bins`` at trace time: feat < d, thr_bin <= n_bins), so a level costs
    one compare-select-reduce over 2^l entries — a masked sum of one int32
    entry is exact — not four over the heap's 2^(depth+1)-1.  A row stuck at
    a leaf of an earlier level has ``local < 0``, matches nothing and reads
    zeros, which the caller discards (it stays where it is).  Level 0 has
    one node and every row is at it: a broadcast."""
    fbits, tbits = (d - 1).bit_length(), int(n_bins).bit_length()
    flags = fbits + tbits
    assert flags + 2 <= 31, (
        f"a node word of 31 bits cannot hold d={d} features and "
        f"n_bins={n_bins}: {fbits} + {tbits} + 2 bits")
    words = (feat.astype(jnp.int32)
             | (thr_bin.astype(jnp.int32) << fbits)
             | (miss_left.astype(jnp.int32) << flags)
             | (is_leaf.astype(jnp.int32) << (flags + 1)))
    nn = words.shape[-1]
    if nn == 1:
        w = jnp.broadcast_to(words, local.shape)
    else:
        oh = local[..., None] == jnp.arange(nn, dtype=local.dtype)
        w = jnp.where(oh, words[..., None, :], 0).sum(axis=-1)
    return (w & ((1 << fbits) - 1), (w >> fbits) & ((1 << tbits) - 1),
            ((w >> flags) & 1).astype(bool),
            ((w >> (flags + 1)) & 1).astype(bool))


def _route_level(binned, feat, thr_bin, miss_left, is_leaf, node, first: int,
                 n_bins: int):
    """One level of a walk of L lanes over the level's tables (L, 2^l), node
    (L, n): rows at its split nodes move to a child, rows at a leaf — of
    this level or of an earlier one — stay.

    The row's bin code at its node's split column comes through the one
    entry of perf/kernels/routing.py (``level_select_lanes``, counted as
    ``route:xla``), which picks its form from the shapes: while the level
    has fewer nodes than the table has columns, the level's 2^l columns are
    gathered on the MXU and the row's own picked among them; from there on
    the row's column index is compared with all d columns.  Both are exact,
    so ``node`` is the same to the last bit; a row with ``local < 0`` reads
    a code that the last line discards."""
    local = node - first
    nf, thr, go_miss, leaf_here = _level_lookup(
        feat, thr_bin, miss_left, is_leaf, local, binned.shape[-1], n_bins)
    nb = _krout.level_select_lanes(binned, feat, local, nf, n_bins,
                                   _HIST_CHUNK)
    go_left = jnp.where(nb == n_bins, go_miss, nb <= thr)
    child = jnp.where(go_left, 2 * node + 1, 2 * node + 2)
    return jnp.where((local < 0) | leaf_here, node, child)


def _leaf_value(G, H, reg_lambda, alpha, eta, max_delta_step):
    raw = -_soft_threshold(G, alpha) / (H + reg_lambda + 1e-12)
    clipped = jnp.where(max_delta_step > 0.0,
                        jnp.clip(raw, -max_delta_step, max_delta_step), raw)
    return clipped * eta


def _grow_trees(binned: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
                feat_mask: jnp.ndarray, key, max_depth: int, n_bins: int,
                reg_lambda, alpha, gamma, min_child_weight, eta, max_delta_step,
                colsample_bylevel: float = 1.0, int_exact: bool = False,
                bin_oh_c=None):
    """Level-wise histogram growth of L trees JOINTLY; static shapes, jit-safe.

    binned: (n, d) int32 in [0, n_bins] (n_bins = missing) — SHARED by lanes.
    grad/hess: (L, n, K) per-lane per-class — zero-weight rows contribute 0.
    feat_mask: (L, d) float 1/0 — colsample_bytree support per lane.
    key: PRNG key for colsample_bylevel (ignored when colsample_bylevel >= 1;
    the per-level draw is shared by all lanes, matching the former per-lane
    vmap which closed over one key).

    The lane axis L — the (fold x tree) lanes of a CV sweep — folds into the
    M dimension of ONE histogram GEMM per row chunk, so the (chunk, B*d) bin
    one-hot operand is built once per chunk and shared by every lane.  Under
    the former per-lane ``vmap`` formulation XLA regenerated that operand
    inside each lane's batched matmul: measured growth cost scaled linearly
    with L and was INDEPENDENT of the bin count — the one-hot construction
    floor paid L times over (r5 profiling; chunk size and scan unroll moved
    nothing, ruling out step overhead).

    ``int_exact=True`` runs the histogram GEMMs in int8 x int8 -> int32 —
    EXACT (not quantized) whenever grad/hess values are integers in
    [-127, 127], which is precisely the forest-CV case: grad = -fold_w *
    poisson_boot * onehot_target, hess = fold_w * poisson_boot, with 0/1
    fold weights (P[poisson(1) >= 128] ~ 1e-216 makes overflow a
    non-event).  The MXU runs int8 at twice the bf16 rate on v5e, and
    per-(node, feat, bin) partial sums stay below 2^24 so the int32 -> f32
    histogram conversion is lossless.  Callers must verify integerness
    (host-side weight check) before setting it.

    Returns (Tree with leading L axis, node (L, n)): ``node`` is each row's
    FINAL leaf assignment per lane — callers that need in-sample predictions
    (boosting margin updates, forest training-set votes) read ``value[node]``
    directly instead of re-traversing.
    """
    L, n, K = grad.shape
    d = binned.shape[1]
    n_orig = n
    m = 2 ** (max_depth + 1) - 1
    B = n_bins + 1  # + missing slot

    # Row-chunk the histogram accumulation: the per-level activation
    # one_hot(node) x [grad|hess] is (rows, nodes*2K), and under the fold x
    # tree CV vmap it multiplies by every lane — at 1M rows x 50 trees x 3
    # folds that is tens of GB and blows HBM.  Chunking turns it into a
    # lax.scan whose live temporary is (CHUNK, nodes*2K) per lane (a few MB)
    # while each step stays an MXU matmul of the same total FLOPs.  Padded
    # rows carry zero grad/hess so every histogram is exact.
    CHUNK = _HIST_CHUNK
    if n > 2 * CHUNK:
        pad = (-n) % CHUNK
        if pad:
            binned = jnp.pad(binned, ((0, pad), (0, 0)))
            grad = jnp.pad(grad, ((0, 0), (0, pad), (0, 0)))
            hess = jnp.pad(hess, ((0, 0), (0, pad), (0, 0)))
            n = n + pad
        n_chunks = n // CHUNK
        binned_c = binned.reshape(n_chunks, CHUNK, d)
    else:
        n_chunks = 0
        binned_c = None

    feat = jnp.zeros((L, m), dtype=jnp.int32)
    thr_bin = jnp.full((L, m), n_bins, dtype=jnp.int32)
    miss_left = jnp.zeros((L, m), dtype=bool)
    is_leaf = jnp.zeros((L, m), dtype=bool)
    value = jnp.zeros((L, m, K), dtype=jnp.float32)

    node = jnp.zeros((L, n), dtype=jnp.int32)  # current node id per row/lane
    hdt = jnp.int8 if int_exact else _hist_dtype()
    acc_t = jnp.int32 if int_exact else jnp.float32
    # gh pre-transposed ONCE to (L, 2K, n): the per-chunk GEMM lhs
    # (L*nn*2K, rows) is then a pure fused broadcast-multiply — no per-chunk
    # transpose of a lane-folded tensor (the r5 first cut paid one per chunk
    # per level and regressed deep forests ~20%)
    ghT = jnp.concatenate([grad, hess], axis=-1).swapaxes(1, 2)   # (L, 2K, n)
    ghT = ghT.astype(hdt) if int_exact else ghT
    # chunk axis leads for the scan; per-step element is (L, 2K, CHUNK)
    gh_c = ghT.reshape(L, 2 * K, n_chunks, CHUNK).transpose(2, 0, 1, 3) \
        if n_chunks else None

    # per-(node, class, feat, bin) grad/hess histograms as ONE MXU matmul per
    # row block: scatter-free — TPU lowers segment_sum to slow sorts, but
    # contracting the one-hot(node) x [grad|hess] activation against a joint
    # one-hot over the (feature, bin) axis is pure matmul work of shape
    # (L*nodes*2K, rows) @ (rows, d*B) — the lane axis folded into M so the
    # bin one-hot is ONE shared rhs per chunk (a per-lane vmap regenerates it
    # per lane: r5 measured growth cost linear in L, independent of B).
    # Inputs go through the MXU in ``hdt`` (bfloat16 on TPU — the one-hot is
    # exact in bf16 and gradients tolerate 8-bit mantissas, cf. LightGBM's
    # quantized histograms; EXACT int8 when ``int_exact``) with f32/int32
    # accumulation.
    #
    # Two classic halvings on top (together ~4x less histogram work):
    # - sibling subtraction: at depth > 0 only LEFT children get a fresh
    #   histogram (one-hot over the parent index); the right sibling is
    #   parent_hist - left_hist.  Children of nodes that already became
    #   leaves inherit the parent's mass through the subtraction, but those
    #   nodes are unreachable (routing and prediction stop at leaves), so
    #   their garbage gains/values never surface.
    # - the final level's leaf values derive from the last split's left/right
    #   sums (already in the cumulative histograms) — no deepest-level data
    #   pass at all.

    def _hist_block(local_blk, ghT_blk, binned_blk, nn, premade):
        # local_blk: (L, rows); ghT_blk: (L, 2K, rows); binned_blk is the
        # (rows, d) codes — or, when ``premade``, the already-materialized
        # (rows, B*d) int8 one-hot slice (boosting reuse, _bin_onehot)
        rows = binned_blk.shape[0]
        # node one-hot generated DIRECTLY in (L, nn, rows) layout — broadcast
        # compare; no transpose anywhere on the lane-folded lhs
        node_oh = (local_blk[:, None, :] ==
                   jnp.arange(nn, dtype=local_blk.dtype)[None, :, None]
                   ).astype(hdt)                                # (L, nn, rows)
        acc = (node_oh[:, :, None, :] * ghT_blk[:, None, :, :].astype(hdt)
               ).reshape(L * nn * 2 * K, rows)
        if premade:
            bin_oh = binned_blk.astype(hdt)
        else:
            # (rows, B, d) layout — NOT (rows, d, B): the innermost axis must
            # be the 128-lane-aligned feature dim; with B=65 innermost, bf16
            # tiles pad 65 -> 128 and half the one-hot bandwidth is wasted
            # (profiled: these chunk scans are ~100% of GBT fit time)
            bin_oh = (binned_blk[:, None, :] ==
                      jnp.arange(B, dtype=binned_blk.dtype)[None, :, None]
                      ).astype(hdt).reshape(rows, B * d)
        return jax.lax.dot_general(
            acc, bin_oh, (((1,), (0,)), ((), ())),
            preferred_element_type=acc_t)                # (L*nn*2K, B*d)

    def _level_hist(local, nn):
        """(L, nn, 2K, d, B) histograms; negative ``local`` rows contribute 0."""
        kmode = _hist_admit(L, nn, K, B, d, jnp.dtype(hdt).itemsize, CHUNK)
        if kmode is not None:
            # fused Pallas build: row chunks stream through VMEM, the
            # (M, B*d) accumulator stays resident across the whole pass —
            # the premade bin one-hot (_bin_onehot) is unnecessary here,
            # the kernel constructs its one-hots in VMEM per chunk
            hist = _khist.hist_level_pallas(
                local, ghT, binned, nn, n_bins, int_exact=int_exact,
                mxu_dtype=hdt, interpret=kmode == "interpret", chunk=CHUNK)
        elif n_chunks:
            local_c = local.reshape(L, n_chunks, CHUNK).swapaxes(0, 1)
            premade = bin_oh_c is not None

            def chunk_step(hacc, blk):
                lb, gb, bb = blk
                return hacc + _hist_block(lb, gb, bb, nn, premade), None

            hist0 = jnp.zeros((L * nn * 2 * K, B * d), acc_t)
            hist, _ = jax.lax.scan(
                chunk_step, hist0,
                (local_c, gh_c, bin_oh_c if premade else binned_c))
        else:
            hist = _hist_block(local, ghT, binned, nn, False)
        # int_exact: per-(node, feat, bin) partial sums stay far below 2^24,
        # so the int32 -> f32 conversion is lossless
        hist = hist.astype(jnp.float32)
        # tiny per-level tensor: back to the (…, d, B) convention
        return jnp.swapaxes(hist.reshape(L, nn, 2 * K, B, d), -1, -2)

    def _leaf_all(G, H):
        return _leaf_value(G, H, reg_lambda, alpha, eta, max_delta_step)

    if max_depth == 0:
        with jax.named_scope("tree_hist"):
            hist = _level_hist(node, 1)                  # root totals only
        G = hist[:, :, :K, 0, :].sum(-1)
        H = hist[:, :, K:, 0, :].sum(-1)
        value = value.at[:, 0:1].set(_leaf_all(G, H))
        is_leaf = is_leaf.at[:, 0].set(True)
        return Tree(feat, thr_bin, miss_left, is_leaf, value), node[:, :n_orig]

    prev_hist = None
    for depth in range(max_depth):
        first = 2 ** depth - 1
        n_nodes = 2 ** depth
        local = node - first  # (L, n) in [0, n_nodes) for active rows

        if depth == 0:
            with jax.named_scope("tree_hist"):
                hist = _level_hist(local, 1)
        else:
            # leaf-stuck rows have local < 0 after the parent shift; sending
            # them (and right-child rows) to index -1 zeroes their one-hot row
            with jax.named_scope("tree_hist"):
                is_left = (local % 2 == 0) & (local >= 0)
                left_local = jnp.where(is_left, local // 2, -1)
                left = _level_hist(left_local, n_nodes // 2)
                right = prev_hist - left
                hist = jnp.stack([left, right], axis=2).reshape(
                    L, n_nodes, 2 * K, d, B)
        prev_hist = hist
        hist_g, hist_h = hist[:, :, :K], hist[:, :, K:]          # (L,nodes,K,d,B)

        G = hist_g[:, :, :, 0, :].sum(-1)  # (L, nodes, K) totals (feature 0 covers all)
        H = hist_h[:, :, :, 0, :].sum(-1)
        node_val = _leaf_all(G, H)

        # split search: left = bins [0..b]; missing tried on both sides.
        # The cumsum + gain + argmax math lives in perf/kernels/splitscan.py
        # — ONE definition shared by the XLA reference and the fused Pallas
        # kernel, dispatched there (TMOG_PALLAS / VMEM admission).
        level_mask = feat_mask                       # (L, d)
        if colsample_bylevel < 1.0:
            # salt 3 keeps level draws independent of the subsample (salt 1)
            # and colsample_bytree (salt 2) draws made from the same round key;
            # ONE draw shared by all lanes (parity with the former vmap, which
            # closed every lane over the same key)
            level_key = jax.random.fold_in(jax.random.fold_in(key, 3), depth)
            level_mask = feat_mask * _colsample_mask(level_key, d,
                                                     colsample_bylevel)[None, :]
        with jax.named_scope("tree_split"):
            best, best_gain, bml = _ksplit.split_scan(
                hist_g, hist_h, G, H, level_mask, n_bins,
                reg_lambda, alpha, gamma, min_child_weight)  # (L, nodes) each
        bf = (best // (n_bins - 1)).astype(jnp.int32)
        bb = (best % (n_bins - 1)).astype(jnp.int32)

        # nodes with no positive gain (or no rows) become leaves now
        leaf_now = (best_gain <= 0.0) | (H.mean(-1) <= 0.0)
        sl = slice(first, first + n_nodes)
        lvl_feat = jnp.where(leaf_now, 0, bf)                # (L, nodes) each
        lvl_thr = jnp.where(leaf_now, n_bins, bb)
        lvl_miss = jnp.where(leaf_now, False, bml)
        feat = feat.at[:, sl].set(lvl_feat)
        thr_bin = thr_bin.at[:, sl].set(lvl_thr)
        miss_left = miss_left.at[:, sl].set(lvl_miss)
        is_leaf = is_leaf.at[:, sl].set(leaf_now)
        value = value.at[:, sl].set(node_val)

        if depth == max_depth - 1:
            # FINAL level: the children's G/H totals are exactly the chosen
            # split's left/right sums, already sitting in the cumulative
            # histograms — deriving leaf values from them eliminates the
            # former deepest-level totals pass over the data entirely
            # (one full (n, d) scan per tree per round saved).  The cumsums
            # rebuild here on the tiny per-level tensors: on the XLA split
            # path they CSE with split_scan's own, on the Pallas path they
            # are the only HBM-visible copy.
            gl = jnp.cumsum(hist_g[..., :n_bins], axis=-1)[..., :-1]
            hl = jnp.cumsum(hist_h[..., :n_bins], axis=-1)[..., :-1]
            g_miss = hist_g[..., n_bins][..., None]
            h_miss = hist_h[..., n_bins][..., None]
            bidx = jnp.broadcast_to(best[:, :, None, None],
                                    (L, n_nodes, K, 1))
            gl_best = jnp.take_along_axis(
                gl.reshape(L, n_nodes, K, -1), bidx, -1)[..., 0]
            hl_best = jnp.take_along_axis(
                hl.reshape(L, n_nodes, K, -1), bidx, -1)[..., 0]
            fidx = jnp.broadcast_to(bf[:, :, None, None], (L, n_nodes, K, 1))
            gm_best = jnp.take_along_axis(g_miss[..., 0], fidx, -1)[..., 0]
            hm_best = jnp.take_along_axis(h_miss[..., 0], fidx, -1)[..., 0]
            G_l = gl_best + jnp.where(bml[..., None], gm_best, 0.0)
            H_l = hl_best + jnp.where(bml[..., None], hm_best, 0.0)
            lv = _leaf_all(G_l, H_l)
            rv = _leaf_all(G - G_l, H - H_l)
            child_vals = jnp.stack([lv, rv], axis=2).reshape(
                L, 2 * n_nodes, K)
            csl = slice(first + n_nodes, first + 3 * n_nodes)
            # children of leaf-now parents get garbage values here — they are
            # unreachable (routing stops at leaves), same as the former
            # sibling-subtraction garbage
            value = value.at[:, csl].set(child_vals)
            is_leaf = is_leaf.at[:, csl].set(True)

        # route rows over the level's own tables: a row is at one of its
        # 2^depth nodes or stuck at an earlier leaf
        with jax.named_scope("tree_route"):
            node = _route_level(binned, lvl_feat, lvl_thr, lvl_miss,
                                leaf_now, node, first, n_bins)

    return Tree(feat, thr_bin, miss_left, is_leaf, value), node[:, :n_orig]


def _grow_tree(binned: jnp.ndarray, grad: jnp.ndarray, hess: jnp.ndarray,
               feat_mask: jnp.ndarray, key, max_depth: int, n_bins: int,
               reg_lambda, alpha, gamma, min_child_weight, eta, max_delta_step,
               colsample_bylevel: float = 1.0):
    """Single-lane convenience wrapper over ``_grow_trees`` (grad/hess (n, K),
    feat_mask (d,)); returns (Tree without lane axis, node (n,))."""
    tree, node = _grow_trees(binned, grad[None], hess[None], feat_mask[None],
                             key, max_depth, n_bins, reg_lambda, alpha, gamma,
                             min_child_weight, eta, max_delta_step,
                             colsample_bylevel)
    return Tree(*(a[0] for a in tree)), node[0]


# ---------------------------------------------------------------------------
# Ensemble fitters
# ---------------------------------------------------------------------------

def _colsample_mask(key, d: int, frac: float) -> jnp.ndarray:
    """Exact-k column subsampling mask via rank of uniforms (no dynamic shapes)."""
    k_keep = max(1, int(round(frac * d)))
    u = jax.random.uniform(key, (d,))
    rank = jnp.argsort(jnp.argsort(u))
    return (rank < k_keep).astype(jnp.float32)


def _base_score_device(y, w, objective: str, num_class: int, scale_pos_weight):
    """(K,) prior margin from the TRAINING weights, on device — the same formula
    the host ``_resolved`` uses, so fold-swept models match ``_fit_arrays`` exactly
    (fold weights zero out validation rows: no label leakage into the prior)."""
    if objective == "binary:logistic":
        we = w * jnp.where(y == 1.0, scale_pos_weight, 1.0)
        p = jnp.clip((we * (y == 1.0)).sum() / jnp.maximum(we.sum(), 1e-12),
                     1e-6, 1 - 1e-6)
        return jnp.log(p / (1 - p))[None]
    if objective == "multi:softmax":
        counts = (w[:, None] * jax.nn.one_hot(y.astype(jnp.int32), num_class)).sum(0)
        p = jnp.clip(counts / jnp.maximum(counts.sum(), 1e-12), 1e-6, 1.0)
        return jnp.log(p)
    return ((w * y).sum() / jnp.maximum(w.sum(), 1e-12))[None]


def _fit_gbt_lanes(binned, y, w_lanes, key, n_rounds: int, max_depth: int,
                   n_bins: int, objective: str, num_class: int,
                   subsample: float, colsample_bytree: float,
                   colsample_bylevel: float, eta, reg_lambda, alpha, gamma,
                   min_child_weight, scale_pos_weight, max_delta_step,
                   base_score, bin_oh_c=None):
    """Boosting of L lanes jointly under lax.scan; carry = (L, n, K) margins.

    w_lanes: (L, n) per-lane row weights (CV fold weights — validation rows
    zeroed); base_score: (L, K) per-lane prior margin.  Every lane's tree of
    round r grows in ONE ``_grow_trees`` call, so the fold lanes share the
    histogram GEMM's one-hot operand (r5).  ``subsample`` row masks and
    ``colsample_bytree`` feature masks draw once per round, shared by lanes
    (parity with the former per-fold vmap over a closed-over key).
    ``bin_oh_c``: the materialised bin one-hot (``_bin_onehot``) or None.
    Returns (final margins (L, n, K), stacked Trees (rounds, L, ...)).
    """
    L, n = w_lanes.shape
    d = binned.shape[1]
    K = num_class

    if objective == "multi:softmax":
        y_onehot = jax.nn.one_hot(y.astype(jnp.int32), K, dtype=jnp.float32)

    # bin_oh_c: one int8 bin one-hot shared by every round x level, built
    # by the estimator that dispatched this program (_GBTBase._shared_bin_onehot:
    # None for a small block, one over the cap, or where the Pallas
    # histogram kernel is admitted at the DEEPEST fresh-histogram level and
    # builds its one-hots in VMEM per chunk).  Without it the XLA scan
    # rebuilds the one-hot at every pass.

    def round_fn(margin, r):
        rkey = jax.random.fold_in(key, r)
        wt = w_lanes
        if subsample < 1.0:
            wt = wt * jax.random.bernoulli(
                jax.random.fold_in(rkey, 1), subsample,
                (n,)).astype(jnp.float32)[None, :]
        feat_mask = jnp.ones(d, dtype=jnp.float32)
        if colsample_bytree < 1.0:
            feat_mask = _colsample_mask(jax.random.fold_in(rkey, 2), d,
                                        colsample_bytree)
        fm_l = jnp.broadcast_to(feat_mask[None, :], (L, d))

        with jax.named_scope("boost_grad"):
            if objective == "binary:logistic":
                wp = wt * jnp.where(y == 1.0, scale_pos_weight, 1.0)[None, :]
                p = jax.nn.sigmoid(margin[..., 0])
                grad = (wp * (p - y[None, :]))[..., None]
                hess = (wp * jnp.maximum(p * (1 - p), 1e-16))[..., None]
            elif objective == "multi:softmax":
                p = jax.nn.softmax(margin, axis=-1)
                grad = wt[..., None] * (p - y_onehot[None])
                hess = wt[..., None] * jnp.maximum(p * (1 - p), 1e-16)
            else:  # reg:squarederror
                grad = (wt * (margin[..., 0] - y[None, :]))[..., None]
                hess = wt[..., None] * jnp.ones((1, 1, 1), jnp.float32)
        tree, node = _grow_trees(binned, grad, hess, fm_l, rkey, max_depth,
                                 n_bins, reg_lambda, alpha, gamma,
                                 min_child_weight, eta, max_delta_step,
                                 colsample_bylevel, bin_oh_c=bin_oh_c)
        # the grower already routed every row to its leaf — no re-traversal
        with jax.named_scope("boost_margin"):
            new_margin = margin + _node_lookup(tree.value, node)
        return new_margin, tree

    margin0 = jnp.broadcast_to(base_score.astype(jnp.float32)[:, None, :],
                               (L, n, K))
    final_margin, trees = jax.lax.scan(round_fn, margin0, jnp.arange(n_rounds))
    return final_margin, trees


def _fit_gbt_impl(binned, y, w, key, n_rounds: int, max_depth: int, n_bins: int,
                  objective: str, num_class: int, subsample: float,
                  colsample_bytree: float, colsample_bylevel: float,
                  eta, reg_lambda, alpha, gamma, min_child_weight,
                  scale_pos_weight, max_delta_step, base_score, bin_oh=None):
    """Single-lane boosting (the refit path).  base_score: (K,) margin offset.
    Returns (final margins (n, K), stacked Trees (rounds, ...)) — identical
    PRNG stream and semantics to one lane of ``_fit_gbt_lanes``."""
    margin, trees = _fit_gbt_lanes(
        binned, y, w[None, :], key, n_rounds, max_depth, n_bins, objective,
        num_class, subsample, colsample_bytree, colsample_bylevel, eta,
        reg_lambda, alpha, gamma, min_child_weight, scale_pos_weight,
        max_delta_step, jnp.reshape(jnp.asarray(base_score, jnp.float32),
                                    (1, -1)), bin_oh_c=bin_oh)
    return margin[0], Tree(*(a[:, 0] for a in trees))


_GBT_STATICS = ("n_rounds", "max_depth", "n_bins", "objective", "num_class",
                "subsample", "colsample_bytree", "colsample_bylevel")


@partial(jax.jit, static_argnames=_GBT_STATICS)
def _fit_gbt(binned, y, w, key, n_rounds, max_depth, n_bins, objective, num_class,
             subsample, colsample_bytree, colsample_bylevel,
             eta, reg_lambda, alpha, gamma, min_child_weight,
             scale_pos_weight, max_delta_step, base_score, bin_oh=None):
    return _fit_gbt_impl(binned, y, w, key, n_rounds, max_depth, n_bins, objective,
                         num_class, subsample, colsample_bytree, colsample_bylevel,
                         eta, reg_lambda, alpha, gamma, min_child_weight,
                         scale_pos_weight, max_delta_step, base_score, bin_oh)


def _fit_forest_impl(binned, y_cols, w, max_depth: int, n_bins: int,
                     reg_lambda, min_child_weight, feat_masks, boot_w,
                     int_exact: bool = False):
    """Random forest: grow all (bootstrap weights, feature masks) lanes in one
    joint ``_grow_trees`` call — the T tree lanes fold into the histogram
    GEMM's M dimension instead of a per-tree vmap (r5).

    y_cols: (n, K) regression targets — one-hot class indicators for classification,
    so leaf values are per-class probability vectors; variance-reduction splits on
    one-hot targets equal Gini-gain splits up to a constant factor.

    int_exact: histogram GEMMs in int8 (EXACT — see _grow_trees); valid only
    when w is 0/1 and y_cols is one-hot (callers verify host-side).
    """
    key = jax.random.PRNGKey(0)  # unused (no bylevel sampling in forests)
    wt = w[None, :] * boot_w                                     # (T, n)
    # squared loss around 0 => leaf = weighted mean of targets
    grad = -wt[:, :, None] * y_cols[None]                        # (T, n, K)
    hess = wt[:, :, None] * jnp.ones((1, 1, y_cols.shape[1]), jnp.float32)
    return _grow_trees(binned, grad, hess, feat_masks, key, max_depth, n_bins,
                       reg_lambda, 0.0, 0.0, min_child_weight, 1.0, 0.0,
                       int_exact=int_exact)


@partial(jax.jit, static_argnames=("max_depth", "n_bins", "int_exact"))
def _fit_forest(binned, y_cols, w, max_depth, n_bins,
                reg_lambda, min_child_weight, feat_masks, boot_w,
                int_exact=False):
    return _fit_forest_impl(binned, y_cols, w, max_depth, n_bins,
                            reg_lambda, min_child_weight, feat_masks, boot_w,
                            int_exact=int_exact)[0]


@partial(jax.jit, static_argnames=("max_depth", "n_bins"))
def _predict_trees_sum(trees: Tree, binned, max_depth, n_bins):
    """(n, K) sum of leaf value vectors over T stacked trees: the grower's
    level walk with the trees as its lanes, over static slices of the heap
    (level l reads its 2^l nodes; the row select sees how many trees there
    are, and chunks the rows where 50 trees x 32 nodes x n would not fit),
    then the value of each row's final node from the whole heap."""
    node = jnp.zeros((trees.feat.shape[0], binned.shape[0]), dtype=jnp.int32)
    for level in range(max_depth):
        first = 2 ** level - 1
        sl = slice(first, first + 2 ** level)
        node = _route_level(binned, trees.feat[:, sl], trees.thr_bin[:, sl],
                            trees.miss_left[:, sl], trees.is_leaf[:, sl],
                            node, first, n_bins)
    return _node_lookup(trees.value, node).sum(axis=0)


# ---------------------------------------------------------------------------
# Fold-vmapped CV sweep programs (one XLA program per grid config)
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=_GBT_STATICS + ("metric_fn",))
def _gbt_cv_program(binned, y, train_w, val_w, key, n_rounds, max_depth, n_bins,
                    objective, num_class, subsample, colsample_bytree,
                    colsample_bylevel, eta, reg_lambda, alpha, gamma,
                    min_child_weight, scale_pos_weight, max_delta_step,
                    metric_fn, bin_oh=None):
    """All folds of one GBT grid point in one program: the boosted margins over the
    full row block already contain the validation predictions (fold membership only
    zeroes training weights), so fit + eval fuse with no second predict pass.
    The prior margin is recomputed per fold from the fold's training weights —
    exactly what ``_fit_arrays`` would produce on that fold.  Folds are LANES
    of one joint boosting run (``_fit_gbt_lanes``): each round grows all
    folds' trees in one histogram GEMM sharing the one-hot operand (r5).

    dp x mp sharding rides ambient row annotations (identity off-mesh): the
    (n, d) bin codes and the per-fold weight rows pin to the data axis, so
    the histogram GEMMs reduce shard-locally and the psums carry only the
    (lanes, bins x features) histogram blocks — per-host rows, never global
    rows.  Metric payloads keep their fold-vmapped layout (the watch-item
    test pins that form bitwise; see test_use_mesh.py)."""
    from ..parallel.mesh import constrain_fold_rows, constrain_rows

    binned, y = constrain_rows(binned), constrain_rows(y)
    train_w = constrain_fold_rows(train_w)
    val_w = constrain_fold_rows(val_w)
    base = jax.vmap(lambda w_: _base_score_device(
        y, w_, objective, num_class, scale_pos_weight))(train_w)     # (k, K)
    margin, _ = _fit_gbt_lanes(
        binned, y, train_w, key, n_rounds, max_depth, n_bins, objective,
        num_class, subsample, colsample_bytree, colsample_bylevel, eta,
        reg_lambda, alpha, gamma, min_child_weight, scale_pos_weight,
        max_delta_step, base, bin_oh_c=bin_oh)                   # (k, n, K)
    if objective == "binary:logistic":
        payload = jax.nn.sigmoid(margin[..., 0])
    elif objective == "multi:softmax":
        payload = jax.nn.softmax(margin, axis=-1)
    else:
        payload = margin[..., 0]
    with jax.named_scope("eval_sort"):
        return jax.vmap(lambda pf, vw_: metric_fn(pf, y, vw_))(payload, val_w)


@partial(jax.jit, static_argnames=("max_depth", "n_bins", "classification",
                                  "metric_fn", "int_exact"))
def _forest_cv_program(binned, y, y_cols, train_w, val_w, feat_masks, boot_w,
                       max_depth, n_bins, reg_lambda, min_child_weight,
                       classification, metric_fn, int_exact=False):
    """All folds of one forest grid point (fit + predict + metric) in one
    program.  The (fold x tree) grid flattens into k*T lanes of ONE joint
    ``_grow_trees`` call — every lane shares the histogram GEMM's one-hot
    operand instead of regenerating it per fold per tree (r5).

    dp x mp row annotations as in :func:`_gbt_cv_program` (identity
    off-mesh): bin codes, targets, fold weights, and the per-tree bootstrap
    rows pin to the data axis; the small (T, d) feature masks replicate."""
    from ..parallel.mesh import constrain_fold_rows, constrain_rows

    binned, y = constrain_rows(binned), constrain_rows(y)
    y_cols = constrain_rows(y_cols)
    train_w = constrain_fold_rows(train_w)
    val_w = constrain_fold_rows(val_w)
    boot_w = constrain_fold_rows(boot_w)
    k, n = train_w.shape
    n_trees, _ = feat_masks.shape
    K = y_cols.shape[1]
    # all k*T (fold, tree) lanes grow in ONE channel-batched GEMM
    wt = (train_w[:, None, :] * boot_w[None, :, :]).reshape(k * n_trees, n)
    grad = -wt[:, :, None] * y_cols[None]
    hess = wt[:, :, None] * jnp.ones((1, 1, K), jnp.float32)
    masks = jnp.tile(feat_masks, (k, 1))
    trees, nodes = _grow_trees(
        binned, grad, hess, masks, jax.random.PRNGKey(0), max_depth,
        n_bins, reg_lambda, 0.0, 0.0, min_child_weight, 1.0, 0.0,
        int_exact=int_exact)
    # in-sample votes read each lane's final row->leaf assignment from
    # the grower — no re-traversal of the whole forest
    vals = _node_lookup(trees.value, nodes)              # (k*T, n, K)
    mean = vals.reshape(k, n_trees, n, K).sum(axis=1) / n_trees
    if classification:
        if K == 1:
            payload = mean[..., 0]
        else:
            cl = jnp.clip(mean, 0.0, 1.0)
            payload = cl / jnp.maximum(cl.sum(-1, keepdims=True), 1e-12)
    else:
        payload = mean[..., 0]
    return jax.vmap(lambda pf, vw_: metric_fn(pf, y, vw_))(payload, val_w)


# ---------------------------------------------------------------------------
# Model stages
# ---------------------------------------------------------------------------

class _TreeEnsembleModelBase(PredictionModelBase):
    def __init__(self, trees: Tree, edges: np.ndarray, max_depth: int, n_bins: int,
                 base_score=0.0, **kw):
        super().__init__(**kw)
        # numpy dict storage so the model round-trips through the array-store serde
        self.trees = {k: np.asarray(v) for k, v in
                      (trees._asdict() if isinstance(trees, Tree) else trees).items()}
        self.edges = np.asarray(edges, dtype=np.float32)
        self.max_depth = int(max_depth)
        self.n_bins = int(n_bins)
        self.base_score = np.asarray(base_score, dtype=np.float64).reshape(-1)

    def _tree_batch(self) -> Tree:
        return Tree(**{k: jnp.asarray(v) for k, v in self.trees.items()})

    #: batches at or below this row count predict on HOST numpy — a device
    #: dispatch per record is the wrong trade for ms-grade local serving
    #: (the reference's MLeap role)
    _HOST_PREDICT_MAX_ROWS = 512

    def _margin(self, x: np.ndarray) -> np.ndarray:
        """(n, K) summed leaf values + base score."""
        # re-normalize here too: serde restores attrs via setattr, bypassing
        # the __init__ reshape (a loaded model may hold a plain float)
        base = np.asarray(self.base_score, dtype=np.float64).reshape(-1)
        x = np.asarray(x, dtype=np.float32)
        if x.shape[0] <= self._HOST_PREDICT_MAX_ROWS:
            return self._margin_host(x) + base[None, :]
        # go through the shared content-keyed placement: predicting on the
        # block the model was just fit on (the selector's train-eval pass,
        # model.score right after train) must NOT re-transfer the (n, d)
        # matrix — a second host->device copy of the block costs more than
        # the traversal it feeds
        from ..parallel.mesh import place_rows_bucketed_cached

        xd, n0 = place_rows_bucketed_cached(x, insert=False)
        binned = _digitize_device(xd, jnp.asarray(self.edges), self.n_bins)
        s = _predict_trees_sum(self._tree_batch(), binned, self.max_depth,
                               self.n_bins)
        return np.asarray(s[:n0], dtype=np.float64) + base[None, :]

    def _margin_host(self, x: np.ndarray) -> np.ndarray:
        """Pure-numpy traversal (exact parity with the device path).

        Node lookups go through flat 1-D fancy indexing on raveled tree
        arrays — identical arithmetic to the per-axis ``take_along_axis``
        formulation but ~3x cheaper per step, which matters because this is
        the serving hot path for tree winners (micro-batches stay on host,
        _HOST_PREDICT_MAX_ROWS).
        """
        n, d = x.shape
        binned = np.empty((n, d), np.int32)
        for j in range(d):
            binned[:, j] = np.searchsorted(self.edges[j], x[:, j], side="right")
        binned[~np.isfinite(x)] = self.n_bins
        feat = self.trees["feat"]          # (T, m)
        T, m = feat.shape
        featf = np.ascontiguousarray(feat).ravel()
        thrf = np.ascontiguousarray(self.trees["thr_bin"]).ravel()
        missf = np.ascontiguousarray(self.trees["miss_left"]).ravel()
        leaff = np.ascontiguousarray(self.trees["is_leaf"]).ravel()
        value = self.trees["value"]        # (T, m, K)
        valuef = np.ascontiguousarray(value).reshape(T * m, -1)
        off = (np.arange(T, dtype=np.int32) * m)[:, None]      # (T, 1)
        binnedf = binned.ravel()
        rowsd = np.arange(n, dtype=np.int32) * d               # (n,)
        node = np.zeros((T, n), np.int32)
        for _ in range(self.max_depth):
            g = off + node                                     # (T, n) global
            nb = binnedf[rowsd + featf[g]]
            go_left = np.where(nb == self.n_bins, missf[g], nb <= thrf[g])
            node = np.where(leaff[g], node,
                            np.where(go_left, 2 * node + 1, 2 * node + 2))
        # (T, n, K) leaf values summed over trees
        vals = valuef[off + node]
        return vals.sum(axis=0).astype(np.float64)

    @property
    def n_trees(self) -> int:
        return int(self.trees["feat"].shape[0])

    @property
    def n_outputs(self) -> int:
        return int(self.trees["value"].shape[-1])

    def feature_importances(self, d: int) -> np.ndarray:
        """Split-count importances per feature (XGBoost 'weight' type)."""
        feats = np.asarray(self.trees["feat"]).ravel()
        leaves = np.asarray(self.trees["is_leaf"]).ravel()
        counts = np.bincount(feats[~leaves], minlength=d).astype(np.float64)
        tot = counts.sum()
        return counts / tot if tot > 0 else counts

    def _margin_device(self, x32: np.ndarray):
        """(margins (n_padded, K) on device, base (K,)) over the shared
        placement — no host fetch; selector train-eval fast path."""
        from ..parallel.mesh import place_rows_bucketed_cached

        xd, _ = place_rows_bucketed_cached(np.asarray(x32, np.float32),
                                           insert=False)
        binned = _digitize_device(xd, jnp.asarray(self.edges), self.n_bins)
        m = _predict_trees_sum(self._tree_batch(), binned, self.max_depth,
                               self.n_bins)
        base = np.asarray(self.base_score, dtype=np.float64).reshape(-1)
        return m, base


class GBTClassifierModel(_TreeEnsembleModelBase):
    def predict_column(self, vec: Column) -> PredictionColumn:
        m = self._margin(vec.data)
        if m.shape[1] == 1:  # binary: single logistic margin
            z = m[:, 0]
            p1 = 1.0 / (1.0 + np.exp(-z))
            return PredictionColumn.classification(
                np.column_stack([-z, z]), np.column_stack([1 - p1, p1]))
        from .base import softmax_probs

        return PredictionColumn.classification(m, softmax_probs(m))

    def eval_payload_device(self, x32):
        if self.n_outputs != 1:
            return None  # multiclass eval is host-side (confusion matrices)
        m, base = self._margin_device(x32)
        z = m[:, 0] + jnp.float32(base[0])
        return jax.nn.sigmoid(z), (z > 0).astype(jnp.float32)


class GBTRegressorModel(_TreeEnsembleModelBase):
    def predict_column(self, vec: Column) -> PredictionColumn:
        return PredictionColumn.regression(self._margin(vec.data)[:, 0])


class ForestClassifierModel(_TreeEnsembleModelBase):
    def predict_column(self, vec: Column) -> PredictionColumn:
        mean = self._margin(vec.data) / self.n_trees
        if mean.shape[1] == 1:  # binary: leaf mean of y IS P(class 1)
            p1 = np.clip(mean[:, 0], 0.0, 1.0)
            prob = np.column_stack([1 - p1, p1])
        else:  # multiclass: leaf mean of one-hot labels IS the class distribution
            prob = np.clip(mean, 0.0, 1.0)
            prob = prob / np.maximum(prob.sum(axis=1, keepdims=True), 1e-12)
        return PredictionColumn.classification(prob * self.n_trees, prob)

    def eval_payload_device(self, x32):
        if self.n_outputs != 1:
            return None
        m, base = self._margin_device(x32)
        b = jnp.float32(base[0] if len(base) else 0.0)
        p1 = jnp.clip((m[:, 0] + b) / self.n_trees, 0.0, 1.0)
        return p1, (p1 > 0.5).astype(jnp.float32)


class ForestRegressorModel(_TreeEnsembleModelBase):
    def predict_column(self, vec: Column) -> PredictionColumn:
        return PredictionColumn.regression(self._margin(vec.data)[:, 0] / self.n_trees)


def _weights_binary(train_w) -> bool:
    """Every fold train weight is 0 or 1."""
    if isinstance(train_w, jax.Array):
        from .tuning import folds_of

        folds = folds_of(train_w)
        if folds is not None:
            return folds.binary
    return bool(np.all((train_w == 0.0) | (train_w == 1.0)))


class _TreeEstimatorBase(PredictionEstimatorBase):
    max_depth = Param(default=5)
    n_bins = Param(default=DEFAULT_BINS)
    reg_lambda = Param(default=1.0)
    min_child_weight = Param(default=1.0)
    seed = Param(default=42)

    def _binned(self, x: np.ndarray):
        """(device bin codes (padded rows), edges, n_valid) — bins ON DEVICE
        from the shared raw placement, so a final refit after CV re-uses the
        block the sweep already transferred (no second (n, d) host->device
        copy).  Padded rows carry zero weight downstream."""
        x32 = np.asarray(x, np.float32)
        from ..parallel.mesh import place_rows_bucketed_cached

        xd, n0 = place_rows_bucketed_cached(x32)
        binned, edges = _shared_binned(x32, xd, int(self.n_bins))
        return binned, edges, n0

    @staticmethod
    def _pad_rows(n_padded: int, *arrays):
        """Zero-pad 1-D/2-D row-aligned host arrays to the padded row count."""
        out = []
        for a in arrays:
            a = np.asarray(a)
            pad = n_padded - a.shape[-1]
            width = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
            out.append(np.pad(a, width) if pad else a)
        return out

    def _cv_sweep_device(self, x, y, train_w, val_w,
                         grids: List[Dict[str, Any]], metric_fn):
        """Fold-vmapped sweep: bins ON DEVICE from the shared raw placement,
        dispatches one async program per grid point; the validator gathers all
        families' metrics in one fetch at the end (VERDICT r1 #2 / r2 #1b)."""
        from ..parallel.mesh import ensure_fit_placements
        from .base import sweep_placements

        x32 = np.asarray(x, np.float32)
        # 0/1 fold weights (the unweighted/unbalanced case) let forests run
        # the EXACT int8 histogram path — verified host-side, decided per fit
        # (of device blocks the validator derived, the folds know: an (n,)
        # test of the base weights)
        int01 = _weights_binary(train_w)
        xd, _, tw, vw, n0 = sweep_placements(x32, [], train_w, val_w)
        binned, _ = _shared_binned(x32, xd, int(self.n_bins))
        pad = int(xd.shape[0]) - n0
        y_p = np.pad(np.asarray(y, np.float64), (0, pad))
        # family-specific model-axis resharding happens ONCE here, not per
        # grid point (GBT shards the fold axis; forests shard their per-tree
        # batch inside _sweep_folds instead and keep folds as-placed)
        tw, vw = self._reshard_fold_weights(tw, vw)
        pending = []
        # what the grid points share on the device (boosting's bin one-hot)
        # is built once: in the selector's fit table, or one of this sweep's
        with ensure_fit_placements():
            for at, grid in enumerate(grids):
                est = self.copy().set_params(**grid)
                # a grid point that changes the binning resolution needs its
                # own codes
                b = binned if int(est.n_bins) == int(self.n_bins) else \
                    _shared_binned(x32, xd, int(est.n_bins))[0]
                pending.append(est._sweep_folds(b, x, y_p, tw, vw, metric_fn,
                                                weights01=int01,
                                                grid_at=(at, len(grids))))
        return pending

    def _reshard_fold_weights(self, tw, vw):
        """Family-specific model-axis layout for the fold weight matrices."""
        return tw, vw

    def _sweep_folds(self, binned, x, y, train_w, val_w, metric_fn,
                     weights01=False, grid_at=(0, 1)):
        """One grid point's program, dispatched; ``grid_at`` says which of
        how many points of the sweep it is."""
        raise NotImplementedError


class _GBTBase(_TreeEstimatorBase):
    """Shared GBT/XGBoost fitting (objective set by subclass).

    Full XGBoost4J param surface (XGBoostParams.scala:1-111): eta, gamma,
    reg_lambda, alpha, min_child_weight, subsample, colsample_bytree,
    colsample_bylevel, scale_pos_weight, max_delta_step, num_class.
    """

    num_rounds = Param(default=100)
    eta = Param(default=0.3)            # XGBoost learning_rate
    gamma = Param(default=0.0)          # min split loss
    alpha = Param(default=0.0)          # L1 on leaf weights
    subsample = Param(default=1.0)      # per-round row subsampling
    colsample_bytree = Param(default=1.0)
    colsample_bylevel = Param(default=1.0)
    scale_pos_weight = Param(default=1.0)
    max_delta_step = Param(default=0.0)
    objective: str = "binary:logistic"

    def _resolved(self, y, w):
        """(objective, num_class, base_score (K,)) for this label column."""
        return self.objective, 1, np.zeros(1)

    def _fit_config(self):
        return dict(
            n_rounds=int(self.num_rounds), max_depth=int(self.max_depth),
            n_bins=int(self.n_bins), subsample=float(self.subsample),
            colsample_bytree=float(self.colsample_bytree),
            colsample_bylevel=float(self.colsample_bylevel),
        )

    def _fit_dynamics(self):
        return dict(
            eta=jnp.float32(self.eta), reg_lambda=jnp.float32(self.reg_lambda),
            alpha=jnp.float32(self.alpha), gamma=jnp.float32(self.gamma),
            min_child_weight=jnp.float32(self.min_child_weight),
            scale_pos_weight=jnp.float32(self.scale_pos_weight),
            max_delta_step=jnp.float32(self.max_delta_step),
        )

    def _launch_counts(self, binned, lanes: int, num_class: int
                       ) -> Dict[str, Any]:
        """What one boosting program is about to do, from shapes at dispatch
        (the counts of its ``host.launch`` span): the lanes it boosts
        jointly, rounds, levels a tree, the bytes of the int8 bin one-hot it
        reads at every level (0 where there is none: a small block, one
        over the cap, or the Pallas kernel admitted), what builds the
        deepest level's histogram, what routes the rows, the walks of the
        bin operand it makes (one a level of every round, whether the
        one-hot is resident or rebuilt), the rows M of the histogram GEMM
        at the deepest fresh level (lanes x 2^(depth-2) left children x
        gradient and hessian of each class), the node-table entries one
        row is compared with in one round of one lane (``_lookup_nodes``)
        and the columns its code is compared with while that tree is routed
        (``routing.select_cols``: 2^l at a level that gathers its columns
        on the MXU, d at one that compares with all of them): what a grid
        of points costs is the sum of these over its launches."""
        n, d = (int(v) for v in binned.shape)
        rounds, depth = int(self.num_rounds), int(self.max_depth)
        kmode = _deep_hist_mode(lanes, num_class, depth, int(self.n_bins), d)
        return dict(
            lanes=lanes, rounds=rounds, levels=depth,
            binoh_bytes=0 if kmode else _binoh_bytes(n, d, int(self.n_bins)),
            hist_kernel=_khist.hist_level_pallas.__name__ if kmode else "xla",
            route_kernel=_krout.ROUTE_KERNEL,
            binoh_walks=rounds * depth,
            hist_rows_deepest=lanes * _deepest_fresh_nodes(depth)
            * 2 * num_class,
            lookup_nodes=_lookup_nodes(depth),
            select_cols=_krout.select_cols(depth, d))

    def _shared_bin_onehot(self, binned, counts: Dict[str, Any]
                           ) -> Dict[str, Any]:
        """``{"bin_oh": the int8 bin one-hot of ``binned``}`` for a boosting
        program whose ``counts`` say it reads one, else ``{}``.  One a fit:
        every grid point's sweep and the winner's refit share it
        (``fit_shared``), so it is resident from the first boosting program
        of a fit to the fit's end, not a temporary rebuilt by each."""
        from ..parallel.mesh import fit_shared
        from ..perf.programs import run_cached

        if not counts["binoh_bytes"]:
            return {}
        n_bins = int(self.n_bins)
        return {"bin_oh": fit_shared(
            ("bin_onehot", n_bins, _HIST_CHUNK), binned, lambda: run_cached(
                _bin_onehot, binned, statics=dict(n_bins=n_bins),
                key_extras=dict(hist_chunk=_HIST_CHUNK),
                label=f"{type(self).__name__}/bin_onehot"))}

    def _fit_arrays(self, x, y, w):
        from ..parallel.mesh import DATA_AXIS, place_cached

        binned, edges, n0 = self._binned(x)
        objective, num_class, base = self._resolved(y, w)
        y_p, w_p = self._pad_rows(int(binned.shape[0]), y, w)
        yd = place_cached(np.asarray(y_p, np.float32), (DATA_AXIS,))
        wd = place_cached(np.asarray(w_p, np.float32), (DATA_AXIS,))
        counts = self._launch_counts(binned, 1, num_class)
        shared = self._shared_bin_onehot(binned, counts)
        with activity("launch", label=f"{type(self).__name__}/gbt_refit",
                      **counts):
            _, trees = _fit_gbt(
                binned, yd, wd,
                jax.random.PRNGKey(int(self.seed)), objective=objective,
                num_class=num_class, base_score=jnp.asarray(base, jnp.float32),
                **self._fit_config(), **self._fit_dynamics(), **shared,
            )
        cls = GBTRegressorModel if objective == "reg:squarederror" \
            else GBTClassifierModel
        return cls(trees=trees, edges=edges, max_depth=self.max_depth,
                   n_bins=self.n_bins, base_score=base)

    def _reshard_fold_weights(self, tw, vw):
        # folds shard over the model axis: each model-axis slice boosts its
        # folds on its own row shard, histogram psums ride the data axis only
        # (degrades to replication when folds don't divide the model axis)
        from ..parallel.mesh import DATA_AXIS, MODEL_AXIS
        from .base import place_spec

        return (place_spec(tw, (MODEL_AXIS, DATA_AXIS)),
                place_spec(vw, (MODEL_AXIS, DATA_AXIS)))

    def _sweep_folds(self, binned, x, y, train_w, val_w, metric_fn,
                     weights01=False, grid_at=(0, 1)):
        from ..parallel.mesh import DATA_AXIS, place_cached
        from ..perf.programs import run_cached

        objective, num_class, _ = self._resolved(y, np.ones_like(y))
        yd = place_cached(np.asarray(y, np.float32), (DATA_AXIS,))
        counts = dict(
            self._launch_counts(binned, int(train_w.shape[0]), num_class),
            grid_point=grid_at[0], grid_points=grid_at[1])
        return run_cached(
            _gbt_cv_program,
            binned, yd, train_w, val_w, jax.random.PRNGKey(int(self.seed)),
            kwargs={**self._fit_dynamics(),
                    **self._shared_bin_onehot(binned, counts)},
            statics=dict(objective=objective, num_class=num_class,
                         metric_fn=metric_fn, **self._fit_config()),
            key_extras=dict(hist_chunk=_HIST_CHUNK),
            label=f"{type(self).__name__}/cv_program", counts=counts)


def _class_count(y: np.ndarray, declared) -> int:
    if declared:
        return int(declared)
    return max(2, int(y.max()) + 1) if len(y) else 2


def _log_priors(y: np.ndarray, w: np.ndarray, k: int) -> np.ndarray:
    counts = np.zeros(k)
    for c in range(k):
        counts[c] = float(w[y == c].sum())
    p = np.clip(counts / max(counts.sum(), 1e-12), 1e-6, 1.0)
    return np.log(p)


class GradientBoostedTreesClassifier(_GBTBase):
    """OpGBTClassifier / OpXGBoostClassifier capability.

    Binary labels boost a single logistic margin; K>2 labels switch to the
    multi:softmax objective with (K,)-output trees
    (OpXGBoostClassifier.scala:47-375 num_class handling).
    """

    num_class = Param(default=None, doc="None = infer from labels")

    def _resolved(self, y, w):
        k = _class_count(y, self.num_class)
        if k <= 2:
            # prior log-odds under the EFFECTIVE weights (scale_pos_weight folded
            # in), so spw=s on unit weights == unit spw on s-weighted positives
            we = w * np.where(y == 1.0, float(self.scale_pos_weight), 1.0)
            sw = max(float(we.sum()), 1e-12)
            p = float(np.clip((we * (y == 1.0)).sum() / sw, 1e-6, 1 - 1e-6))
            return "binary:logistic", 1, np.array([np.log(p / (1 - p))])
        return "multi:softmax", k, _log_priors(y, w, k)


class GradientBoostedTreesRegressor(_GBTBase):
    """OpGBTRegressor / OpXGBoostRegressor capability (squared-error boosting)."""

    objective = "reg:squarederror"

    def _resolved(self, y, w):
        sw = max(float(w.sum()), 1e-12)
        return "reg:squarederror", 1, np.array([float((w * y).sum() / sw)])


# XGBoost-named aliases (parity with OpXGBoostClassifier/Regressor param surface)
class XGBoostClassifier(GradientBoostedTreesClassifier):
    pass


class XGBoostRegressor(GradientBoostedTreesRegressor):
    pass


class _ForestBase(_TreeEstimatorBase):
    num_trees = Param(default=50)
    # forests use the UNregularized leaf mean (Spark/sklearn semantics); the XGBoost
    # L2 default would bias small-leaf probabilities toward zero
    reg_lambda = Param(default=0.0)
    subsample = Param(default=1.0)          # Poisson bootstrap rate
    feature_subset = Param(default="sqrt")  # sqrt | all | float fraction
    classification: bool = True

    def _masks(self, d: int):
        rng = np.random.default_rng(self.seed)
        fs = self.feature_subset
        if fs == "all":
            k = d
        elif fs == "sqrt":
            k = max(1, int(np.sqrt(d)))
        elif fs == "onethird":
            k = max(1, d // 3)
        else:
            k = max(1, int(float(fs) * d))
        masks = np.zeros((self.num_trees, d), dtype=np.float32)
        for t in range(self.num_trees):
            masks[t, rng.choice(d, size=k, replace=False)] = 1.0
        return jnp.asarray(masks)

    def _boot(self, n: int):
        # Poisson bootstrap drawn ON DEVICE: a host draw of (trees, n) costs
        # seconds at 1M rows plus a multi-hundred-MB transfer per grid point;
        # the device draw is async and transfer-free.  Keyed on the estimator
        # seed so cv_sweep and _fit_arrays share the identical stream.
        return jax.random.poisson(
            jax.random.PRNGKey(int(self.seed) + 1), float(self.subsample),
            (int(self.num_trees), n)).astype(jnp.float32)

    def _y_cols(self, y: np.ndarray) -> np.ndarray:
        """Per-class regression targets: (n, 1) raw for regression/binary, one-hot
        (n, K) for multiclass so leaves become class distributions."""
        if not self.classification:
            return y[:, None].astype(np.float32)
        k = _class_count(y, getattr(self, "num_class", None))
        if k <= 2:
            return y[:, None].astype(np.float32)
        return np.eye(k, dtype=np.float32)[y.astype(np.int32)]

    def _fit_forest_trees(self, x, y, w):
        from ..parallel.mesh import DATA_AXIS, place_cached

        binned, edges, n0 = self._binned(x)
        n_pad = int(binned.shape[0])
        y_cols, w_p = self._pad_rows(n_pad, self._y_cols(y).T, w)
        boot = self._boot(x.shape[0])
        if n_pad > n0:
            boot = jnp.pad(jnp.asarray(boot), ((0, 0), (0, n_pad - n0)))
        trees = _fit_forest(
            binned,
            place_cached(np.ascontiguousarray(y_cols.T), (DATA_AXIS,)),
            place_cached(np.asarray(w_p, np.float32), (DATA_AXIS,)),
            int(self.max_depth), int(self.n_bins),
            jnp.float32(self.reg_lambda), jnp.float32(self.min_child_weight),
            self._masks(x.shape[1]), boot,
            int_exact=bool(self.classification
                           and np.all((np.asarray(w) == 0.0)
                                      | (np.asarray(w) == 1.0))),
        )
        return trees, edges

    def _sweep_folds(self, binned, x, y, train_w, val_w, metric_fn,
                     weights01=False, grid_at=(0, 1)):
        from ..parallel.mesh import DATA_AXIS, MODEL_AXIS, place_cached
        from .base import place_spec

        # bootstrap weights draw at the ORIGINAL row count so the PRNG stream
        # (and thus every tree) matches _fit_arrays exactly; bucket-padded
        # rows get zero weight
        boot = self._boot(int(x.shape[0]))
        pad = int(binned.shape[0]) - int(x.shape[0])
        if pad:
            boot = jnp.pad(jnp.asarray(boot), ((0, 0), (0, pad)))
        # the per-tree batch shards over the model axis (SURVEY §2.10): each
        # model slice grows its trees against the shared row-sharded codes
        masks = place_spec(np.asarray(self._masks(x.shape[1])),
                           (MODEL_AXIS, None))
        boot = place_spec(boot, (MODEL_AXIS, DATA_AXIS))
        from ..perf.programs import run_cached

        return run_cached(
            _forest_cv_program,
            binned, place_cached(np.asarray(y, np.float32), (DATA_AXIS,)),
            place_cached(self._y_cols(y), (DATA_AXIS,)),
            train_w, val_w, masks, boot,
            kwargs=dict(reg_lambda=jnp.float32(self.reg_lambda),
                        min_child_weight=jnp.float32(self.min_child_weight)),
            statics=dict(max_depth=int(self.max_depth),
                         n_bins=int(self.n_bins),
                         classification=self.classification,
                         metric_fn=metric_fn,
                         # grad/hess = fold_w x poisson counts x one-hot
                         # targets: exact int8 when fold weights are 0/1 and
                         # targets are class indicators
                         int_exact=weights01 and self.classification),
            key_extras=dict(hist_chunk=_HIST_CHUNK),
            label=f"{type(self).__name__}/cv_program")


class RandomForestClassifier(_ForestBase):
    """OpRandomForestClassifier capability — K classes natively
    (OpRandomForestClassifier.scala; leaves carry class distributions)."""

    num_class = Param(default=None, doc="None = infer from labels")
    classification = True

    def _fit_arrays(self, x, y, w):
        trees, edges = self._fit_forest_trees(x, y, w)
        return ForestClassifierModel(trees=trees, edges=edges,
                                     max_depth=self.max_depth, n_bins=self.n_bins)


class RandomForestRegressor(_ForestBase):
    """OpRandomForestRegressor capability (Spark 'auto' = one-third feature subset)."""

    feature_subset = Param(default="onethird")
    classification = False

    def _fit_arrays(self, x, y, w):
        trees, edges = self._fit_forest_trees(x, y, w)
        return ForestRegressorModel(trees=trees, edges=edges,
                                    max_depth=self.max_depth, n_bins=self.n_bins)


class DecisionTreeClassifier(RandomForestClassifier):
    """OpDecisionTreeClassifier capability: a 1-tree forest on all rows/features."""

    def __init__(self, **kw):
        kw.setdefault("num_trees", 1)
        kw.setdefault("feature_subset", "all")
        kw.setdefault("subsample", 1.0)
        super().__init__(**kw)

    def _boot(self, n: int):
        # deterministic: every row in every tree (no bootstrap)
        return jnp.ones((self.num_trees, n), dtype=jnp.float32)


class DecisionTreeRegressor(RandomForestRegressor):
    """OpDecisionTreeRegressor capability."""

    def __init__(self, **kw):
        kw.setdefault("num_trees", 1)
        kw.setdefault("feature_subset", "all")
        kw.setdefault("subsample", 1.0)
        super().__init__(**kw)

    def _boot(self, n: int):
        return jnp.ones((self.num_trees, n), dtype=jnp.float32)
