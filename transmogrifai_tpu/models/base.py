"""Model stage bases: (label RealNN, features OPVector) -> Prediction.

Reference: core/.../sparkwrappers/specific/OpPredictorWrapper.scala — every model estimator
takes (label, features) and emits a Prediction map.  Here models are pure JAX: fit produces
a param pytree; predict is a jitted batched function.  Estimators that implement
``cv_sweep`` run the whole (fold x grid) sweep as one vmapped XLA program.
"""

from __future__ import annotations

import contextvars
import functools
from functools import partial
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..data.dataset import Column, Dataset
from ..stages.base import Estimator, Transformer
from ..types import OPVector, Prediction, RealNN
from .prediction import PredictionColumn


def softmax_probs(raw: np.ndarray) -> np.ndarray:
    """Numerically-stable row softmax over logits/log-likelihoods (shared by all
    multiclass models)."""
    m = raw.max(axis=1, keepdims=True)
    e = np.exp(raw - m)
    return e / e.sum(axis=1, keepdims=True)


def sweep_placements(x32: np.ndarray, extras, train_w, val_w):
    """Shared device placement for a fold-vmapped CV sweep.

    Places the raw feature block ONCE per selector fit (cached on the source
    array identity — every family receives the same object from the
    validator), bucket/mesh-pads and places the row-aligned ``extras``
    (labels, one-hots, ...) through ``place_fit_rows``, and takes the fold
    weight matrices as they are when the validator derived them on the
    device; host fold weights are padded and placed as before.

    Returns (xd, [extra_devs...], tw_dev, vw_dev, n_valid).
    """
    from ..parallel.mesh import (
        DATA_AXIS, pad_host, place_cached, place_fit_rows,
        place_rows_bucketed_cached)

    xd, n0 = place_rows_bucketed_cached(x32)
    n_padded = int(xd.shape[0])
    # inside a selector fit the labels are placed once and every family's
    # request after the first is answered by identity; what a family derives
    # afresh (one-hots) is content-cached like every host array
    extra_devs = [place_fit_rows(e, n_padded) for e in extras]
    if isinstance(train_w, jax.Array):
        tw, vw = place_fit_rows(train_w, n_padded), \
            place_fit_rows(val_w, n_padded)
    else:
        # content-cached: every family pads the caller's identical fold
        # weights, so the (k, n) transfers happen once per fit, not per family
        tw, vw = (place_cached(pad_host(np.asarray(w, np.float32),
                                        [(0, 0), (0, n_padded - n0)]),
                               (None, DATA_AXIS))
                  for w in (train_w, val_w))
    return xd, extra_devs, tw, vw, n0


def derive_on_device(fn, *args, axes, statics=None, label: str):
    """What a fit computes on the device from inputs it has placed, in place
    of a host array to build, pad, hash and place: one small program through
    ``run_cached`` like every other, its outputs laid out as ``place(...,
    axes)`` lays out a host array (to the letter: the layout is part of the
    consuming programs' cache keys), counted in ``placement_stats()``."""
    from ..parallel.mesh import count_derived, place
    from ..perf.programs import run_cached

    out = jax.tree.map(lambda o: place(o, axes),
                       run_cached(fn, *args, statics=statics, label=label))
    count_derived(*jax.tree.leaves(out))
    return out


@partial(jax.jit, static_argnames=("n_padded",))
def _unit_weights(n_valid, n_padded: int):
    """1 on the first ``n_valid`` rows of the padded block, 0 after: what
    zero-padded host ones read."""
    from ..parallel.mesh import constrain_rows, row_mask

    return constrain_rows(row_mask(n_padded, n_valid))


def unit_weights(n_valid: int, n_padded: int):
    """Placed unit weights over the padded row block, made on the device."""
    from ..parallel.mesh import DATA_AXIS

    return derive_on_device(_unit_weights, jnp.int32(n_valid),
                            axes=(DATA_AXIS,), statics=dict(n_padded=n_padded),
                            label="ModelSelector/unit_weights")


def host_fold_weights(train_w, val_w, n: int):
    """The (k, n) numpy form of fold weights for a family with no device
    path at these grids: host blocks as they are; for device blocks the host
    form of the folds they were derived from, else a fetch cut to ``n``."""
    if not isinstance(train_w, jax.Array):
        return train_w, val_w
    from .tuning import folds_of

    folds = folds_of(train_w)
    if folds is not None:
        return folds.host()
    return tuple(np.asarray(w)[:, :n] for w in (train_w, val_w))


def place_spec(arr, axes):
    """Place (or re-shard in place, for on-device arrays) with a
    PartitionSpec over the ambient mesh — ``mesh.place`` with its graceful
    unknown-axis / non-divisible degradation (see parallel/mesh.py)."""
    from ..parallel.mesh import place

    return place(arr, tuple(axes))


def place_grid(arr):
    """Place a per-grid parameter vector sharded over the mesh's MODEL axis.

    This is what makes a CV sweep two-dimensionally parallel (SURVEY §2.10):
    rows reduce over the ``data`` axis (psum) while the hyperparameter grid
    partitions over ``model`` — each model-axis slice fits its grid points on
    its own row shard, with no collective between grid points.  No-op without
    an ambient mesh; a 1-sized model axis degenerates to replication.
    """
    from ..parallel.mesh import MODEL_AXIS

    arr = np.asarray(arr)
    return place_spec(arr, (MODEL_AXIS,) + (None,) * (arr.ndim - 1))


#: whose sweep ``gather_scores`` is about to wait for: the estimator's class
#: name, set by the estimator's own gather round the call (the function keeps
#: its one argument: callers and tests wrap it)
_GATHER_FAMILY: "contextvars.ContextVar[Optional[str]]" = \
    contextvars.ContextVar("transmogrifai_tpu_gather_family", default=None)


def _gather_for(family: str, pending) -> np.ndarray:
    """``gather_scores(pending)`` with its wait labelled ``<family>/cv_gather``."""
    token = _GATHER_FAMILY.set(family)
    try:
        return gather_scores(pending)
    finally:
        _GATHER_FAMILY.reset(token)


def gather_scores(pending) -> np.ndarray:
    """Host-fetch a pending sweep result: a (g, k) device array or a list of
    per-grid (k,) device arrays (one async fetch either way).

    The ``device_sync`` fault point fires before the blocking fetch — this
    is where transient device errors from the in-flight sweep surface on
    the host, so an injected fault here models exactly that (the resilient
    sweep wrapper in models/tuning.py re-dispatches through its retry
    ladder)."""
    from ..perf.timers import activity
    from ..serve.faults import fault_point

    fault_point("device_sync",
                programs=len(pending)
                if isinstance(pending, (list, tuple)) else 1)
    family = _GATHER_FAMILY.get()
    labelled = {"label": f"{family}/cv_gather"} if family else {}
    with activity("device_wait", **labelled):
        if isinstance(pending, (list, tuple)):
            return np.stack(jax.device_get(list(pending)))
        return np.asarray(jax.device_get(pending))


@partial(jax.jit, static_argnames=("metric_fn",))
def eval_metric(payload, y, w, *, metric_fn):
    """One jitted metric evaluation, cached on the metric's identity.

    Metric functions come from module-level registries (Evaluator.metric_fn),
    so their identity is stable across cv_sweep calls — WITHOUT this wrapper,
    every sweep re-traces the metric eagerly (or re-jits a fresh closure) and
    pays a full backend compile per call.  Sort-based AUC programs are among
    the slower ones to compile, so this caching is load-bearing for selector
    throughput, not a micro-optimization.
    """
    return metric_fn(payload, y, w)


def _replicator(mesh):
    """Constraint replicating an operand over ``mesh`` (identity when None).

    The sort-based AUC metrics miscompile under GSPMD when the sort dimension
    is sharded over a mesh axis while the batch dimensions stay replicated
    (observed on a (data=4, model=2) mesh: auPR values near -n instead of
    [0, 1]).  A program that leaves its metric to the partitioner therefore
    pins the metric's inputs to replicated: every device receives them whole
    (an all-gather; the bytes are counted at dispatch,
    ``count_eval_replicas``) and computes every lane itself.  The multiclass
    eval program still does (its metric holds no sort and its lanes are
    cheap); the linear one deals its lanes out instead, :func:`_lane_dealer`.
    """
    if mesh is None:
        return lambda a: a
    from jax.sharding import NamedSharding, PartitionSpec

    rep = NamedSharding(mesh, PartitionSpec())
    return lambda a: jax.lax.with_sharding_constraint(a, rep)


def _dealt_over(mesh):
    """The mesh axes the eval lanes are dealt over, model-major, and the
    devices that makes."""
    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    over = tuple(a for a in (MODEL_AXIS, DATA_AXIS) if a in mesh.axis_names)
    return over, int(np.prod([mesh.shape[a] for a in over]))


def _lane_dealer(mesh):
    """The linear eval program's body under ``mesh``: the g x k lanes are
    independent, and a sort-based metric needs a lane's whole row axis on ONE
    device, not on all of them.  So the lanes are flattened, padded with
    zero coefficients to a multiple of the devices and dealt out: a model
    slice scores its share of the lanes on its row shard, an all-to-all over
    the data axis trades the lane axis for the row axis, and each device runs
    ``metric_fn`` over ``lanes / devices`` whole lanes (with fewer lanes than
    devices some take padding only).  Only the labels and the (k, n)
    validation weights are gathered to every device.  All of it is one
    ``shard_map`` region, so the metric's sort is a local one that GSPMD never
    partitions: the miscompile :func:`_replicator` dodges cannot occur.
    What it moves is counted at dispatch (``count_eval_replicas``), what it
    costs on four chips is in PERF.md (``mesh_eval_device_s``).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import DATA_AXIS, MODEL_AXIS

    over, devices = _dealt_over(mesh)
    model = MODEL_AXIS if MODEL_AXIS in over else None

    def dealt(xd, yd, betas, vw, metric_fn, link):
        g, k, d = betas.shape
        lanes = g * k
        padded = lanes + (-lanes) % devices
        flat = jnp.pad(betas.reshape(lanes, d), ((0, padded - lanes), (0, 0)))
        folds = np.arange(padded, dtype=np.int32) % k

        def local(x, y, b, w, fold):
            margins = jnp.einsum("nd,ld->ln", x, b)
            scores = jax.nn.sigmoid(margins) if link == "sigmoid" else margins
            scores = jax.lax.all_to_all(scores, DATA_AXIS, 0, 1, tiled=True)
            y = jax.lax.all_gather(y, DATA_AXIS, tiled=True)
            w = jax.lax.all_gather(w, DATA_AXIS, axis=1, tiled=True)
            with jax.named_scope("eval_sort"):
                return jax.vmap(lambda s, f: metric_fn(s, y, w[f]))(
                    scores, fold)

        metrics = shard_map(
            local, mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS), P(model),
                      P(None, DATA_AXIS), P(over)),
            out_specs=P(over))(xd, yd, flat, vw, folds)
        return metrics[:lanes].reshape(g, k)

    return dealt


@functools.lru_cache(maxsize=None)
def _eval_linear_sweep_for(mesh):
    """Per-mesh jitted linear eval program.

    One closure per mesh: the ``shard_map`` region bakes the mesh into the
    trace, so sharing one jitted function across meshes would poison the jit
    trace cache; ``run_cached`` keys on the ambient mesh already, and the
    per-mesh function identity keeps the plain jit cache honest too.
    """
    dealt = None if mesh is None else _lane_dealer(mesh)

    @partial(jax.jit, static_argnames=("metric_fn", "link"))
    def eval_linear_sweep(xd, yd, betas, vw, *, metric_fn, link="identity"):
        """Metric per (grid, fold) for linear-family sweeps — one cached
        program.  betas: (g, k, d); vw: (k, n).  ``link`` maps margins to
        scores ("identity" for regression/SVM margins, "sigmoid" for logistic
        probs).  Under a mesh the lanes are dealt over its devices."""
        if dealt is not None:
            return dealt(xd, yd, betas, vw, metric_fn, link)
        margins = jnp.einsum("nd,gkd->gkn", xd, betas)
        scores = jax.nn.sigmoid(margins) if link == "sigmoid" else margins
        per_fold = jax.vmap(lambda s, w_: metric_fn(s, yd, w_), in_axes=(0, 0))
        with jax.named_scope("eval_sort"):
            return jax.vmap(lambda ps: per_fold(ps, vw), in_axes=0)(scores)

    return eval_linear_sweep


@functools.lru_cache(maxsize=None)
def _eval_softmax_sweep_for(mesh):
    """Per-mesh jitted multiclass eval program (see _eval_linear_sweep_for)."""
    rep = _replicator(mesh)

    @partial(jax.jit, static_argnames=("metric_fn",))
    def eval_softmax_sweep(xd, yd, bs, vw, *, metric_fn):
        """Metric per (grid, fold) for multiclass sweeps — one cached
        program.  bs: (g, k, d, C) per-(grid, fold) softmax weights; the
        metric receives the (n, C) probability matrix."""
        logits = jnp.einsum("nd,gkdc->gknc", xd, bs)
        probs = jax.nn.softmax(logits, axis=-1)
        probs, yr, vwr = rep(probs), rep(yd), rep(vw)
        per_fold = jax.vmap(lambda p, w_: metric_fn(p, yr, w_), in_axes=(0, 0))
        return jax.vmap(lambda ps: per_fold(ps, vwr), in_axes=0)(probs)

    return eval_softmax_sweep


def count_eval_replicas(xd, yd, coefs, vw) -> None:
    """Count, at dispatch, what the ambient mesh's eval program does with its
    metric inputs.  Both programs pin the labels and the validation weights
    to every device.  The linear one (``coefs`` (g, k, d)) lays its scores
    out split: ``xd``'s rows by the g x k lanes, padded to a multiple of the
    devices they are dealt over (:func:`_lane_dealer`).  The multiclass one
    (``coefs`` (g, k, d, C)) pins its (g, k, n, C) probabilities to every
    device too (:func:`_replicator`).  From shapes; nothing without a mesh."""
    from ..parallel.mesh import count_replicated, count_sharded, current_mesh

    mesh = current_mesh()
    if mesh is None:
        return
    lanes, n = int(np.prod(coefs.shape[:2])), xd.shape[0]
    count_replicated(mesh, yd, vw)
    if coefs.ndim == 3:
        lanes += (-lanes) % _dealt_over(mesh)[1]
        count_sharded(mesh, jax.ShapeDtypeStruct((lanes, n), jnp.float32))
    else:
        count_replicated(mesh, jax.ShapeDtypeStruct(
            (lanes, n) + tuple(coefs.shape[3:]), jnp.float32))


def eval_linear_sweep_program():
    """The linear eval-sweep program specialized to the ambient mesh."""
    from ..parallel.mesh import current_mesh

    return _eval_linear_sweep_for(current_mesh())


def eval_softmax_sweep_program():
    """The multiclass eval-sweep program specialized to the ambient mesh."""
    from ..parallel.mesh import current_mesh

    return _eval_softmax_sweep_for(current_mesh())


@partial(jax.jit, static_argnames=("link",))
def _linear_eval_payload(xd, coef, intercept, *, link):
    """(score, pred) on device for a linear head over the padded row block."""
    z = xd @ coef + intercept
    if link == "sigmoid":
        return jax.nn.sigmoid(z), (z > 0).astype(jnp.float32)
    return z, (z > 0).astype(jnp.float32)


class PredictionModelBase(Transformer):
    """Fitted model transformer: scores the feature vector; label input is optional."""

    input_types = (RealNN, OPVector)
    output_type = Prediction
    allow_label_as_input = True

    def _is_label_slot(self, feature, features) -> bool:
        return feature is features[0]

    def predict_column(self, vec: Column) -> PredictionColumn:
        raise NotImplementedError

    def eval_payload_device(self, x32: np.ndarray):
        """Device fast path for the selector's train/holdout evaluation.

        Returns ``(score_dev, pred_dev)`` — 1-D device arrays over the
        BUCKET-PADDED row block of the shared content-keyed placement
        (padded rows are masked by zero weights in the evaluator) — or
        ``None`` when this model has no device scoring path (the selector
        then falls back to host ``predict_column``).  Scores are computed
        in float32, matching the evaluator's documented f32-grade metric
        precision; serving (`predict_column`) keeps float64 semantics."""
        return None

    def transform(self, dataset: Dataset) -> Dataset:
        # label may be absent at scoring time — only the feature vector is required
        vec = dataset[self.inputs[1].name]
        return dataset.with_column(self.output_name, self.predict_column(vec))

    def transform_columns(self, cols, dataset):
        return self.predict_column(cols[-1])


class PredictionEstimatorBase(Estimator):
    input_types = (RealNN, OPVector)
    output_type = Prediction
    allow_label_as_input = True

    #: hyperparameter grid axes that can be vmapped on device (dynamic scalars)
    sweepable_params: tuple = ()

    def _is_label_slot(self, feature, features) -> bool:
        return feature is features[0]

    def fit_columns(self, cols, dataset):
        label, vec = cols
        # asarray keeps object identity on float32 blocks -> stamp-memo hit
        x = np.asarray(vec.data, np.float32)
        y = np.asarray(label.data, np.float32)
        w = np.asarray(dataset["__sample_weight__"].data, np.float32) \
            if "__sample_weight__" in dataset else np.ones_like(y)
        return self._fit_arrays(x, y, w)

    def _fit_arrays(self, x: np.ndarray, y: np.ndarray, w: np.ndarray
                    ) -> PredictionModelBase:
        raise NotImplementedError

    # --- sweep protocol (overridden by device-sweepable estimators) ----------
    def _cv_sweep_device(
        self,
        x: np.ndarray,
        y: np.ndarray,
        train_w: np.ndarray,
        val_w: np.ndarray,
        grids: List[Dict[str, Any]],
        metric_fn,
    ):
        """Dispatch this family's whole (grid x fold) sweep WITHOUT blocking.

        Returns the pending (g, k) device array — or a list of per-grid (k,)
        pending arrays — or ``None`` when this family (or this particular
        grid) has no vectorized device path and must take the generic loop.
        Device dispatch is async in JAX, so the validator can launch EVERY
        family's program before fetching any metrics (the reference's
        all-model all-fold concurrency, OpCrossValidation.scala:114-134,
        without its Futures pool).
        """
        return None

    def cv_sweep(
        self,
        x: np.ndarray,
        y: np.ndarray,
        train_w: np.ndarray,   # (k, n) fold train weights
        val_w: np.ndarray,     # (k, n) fold validation weights
        grids: List[Dict[str, Any]],
        metric_fn,             # device fn (scores, y, w) -> metric
    ) -> np.ndarray:
        """Metric per (grid, fold).  Blocking: device path when available,
        else python loops (generic estimators)."""
        pending = self._cv_sweep_device(x, y, train_w, val_w, grids, metric_fn)
        if pending is not None:
            return _gather_for(type(self).__name__, pending)
        return self._cv_sweep_generic(
            x, y, *host_fold_weights(train_w, val_w, len(y)), grids, metric_fn)

    def takes_device_folds(self) -> bool:
        """Whether the validator may hand this estimator fold weights it
        derived on the device: only the sweep protocol as this base class
        runs it knows what to do with them."""
        cls, base = type(self), PredictionEstimatorBase
        return (cls.cv_sweep is base.cv_sweep
                and cls.cv_sweep_async is base.cv_sweep_async
                and cls._cv_sweep_device is not base._cv_sweep_device)

    def cv_sweep_async(self, x, y, train_w, val_w, grids, metric_fn):
        """Dispatch and return a zero-arg gather -> (g, k) metric ndarray.

        Families with a device sweep return while their XLA program is still
        running; generic families compute eagerly (the gather is then a no-op).
        """
        if type(self).cv_sweep is not PredictionEstimatorBase.cv_sweep:
            # subclass overrode the blocking entry point itself — honor it
            # (custom estimators predate the async protocol)
            scores = self.cv_sweep(x, y, train_w, val_w, grids, metric_fn)
            return lambda: scores
        pending = self._cv_sweep_device(x, y, train_w, val_w, grids, metric_fn)
        if pending is not None:
            return lambda: _gather_for(type(self).__name__, pending)
        scores = self._cv_sweep_generic(
            x, y, *host_fold_weights(train_w, val_w, len(y)), grids, metric_fn)
        return lambda: scores

    def _cv_sweep_generic(self, x, y, train_w, val_w,
                          grids: List[Dict[str, Any]], metric_fn) -> np.ndarray:
        k = train_w.shape[0]
        out = np.zeros((len(grids), k))
        yd = jnp.asarray(y, jnp.float32)
        for gi, grid in enumerate(grids):
            est = self.copy().set_params(**grid)
            for f in range(k):
                model = est._fit_arrays(x, y, train_w[f])
                col = model.predict_column(Column.vector(x))
                # multiclass metrics take the (n, C) probability matrix; binary and
                # regression metrics take the 1-D score
                if col.prob is not None and col.prob.shape[1] > 2:
                    payload = col.prob
                else:
                    payload = col.score
                out[gi, f] = float(eval_metric(
                    jnp.asarray(payload, jnp.float32), yd,
                    jnp.asarray(val_w[f]), metric_fn=metric_fn))
        return out
