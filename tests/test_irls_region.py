"""The IRLS sweep under a mesh (``models/logistic._irls_region``): each chip
runs the one-chip step on its own rows and only the step's row sums cross
the chips.  On the suite's 8 virtual CPU devices: betas equal to the
unmeshed sweep's, the region as the jaxpr holds it (one ``shard_map``, a
chip's share of the rows in every row product, ``psum``s over the data axis
of the stated shapes), the unmeshed program without either, and the launch
span's ``irls_allreduce_bytes``.  CPU only: nothing here is a time."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import metrics as M
from transmogrifai_tpu.models import logistic as lg
from transmogrifai_tpu.parallel.mesh import make_mesh, place, use_mesh
from transmogrifai_tpu.perf.timers import record_phases

N, D, K, ITERS = 1024, 8, 3, 10
D1 = D + 1
#: (data, model): a model axis of two deals the grid over it, and pads 3
MESHES = [(4, 1), (4, 2)]


def _mesh(shape):
    return make_mesh(*shape, devices=jax.devices()[:shape[0] * shape[1]])


def _table(g, seed=0):
    """(x with its ones column, y, train_w (K, N), regs (g,))."""
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(N, D)).astype(np.float32)
    beta = rng.normal(size=D).astype(np.float32)
    y = (rng.random(N) < 1 / (1 + np.exp(-(x @ beta)))).astype(np.float32)
    folds = rng.integers(0, K, size=N)
    train_w = np.stack([(folds != f) for f in range(K)]).astype(np.float32)
    x1 = np.concatenate([x, np.ones((N, 1), np.float32)], axis=1)
    return x1, y, train_w, np.geomspace(1e-3, 1e-1, g).astype(np.float32)


def _placed(x, y, train_w, regs):
    """The operands as the sweep's dispatch places them under the mesh."""
    return (place(x, ("data", None)), place(y, ("data",)),
            place(train_w, (None, "data")), place(regs, ("model",)))


def _eqns(jaxpr, in_loop=False):
    """Every equation of ``jaxpr`` and of the jaxprs inside it, with whether
    it sits in a loop."""
    for e in jaxpr.eqns:
        yield e, in_loop
        loop = in_loop or e.primitive.name in ("scan", "while")
        for v in e.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, loop)


def _jaxpr(args):
    return jax.make_jaxpr(partial(lg._irls_sweep, max_iter=ITERS))(*args).jaxpr


@pytest.mark.parametrize("g", [2, 3])
@pytest.mark.parametrize("shape", MESHES)
def test_meshed_sweep_returns_the_unmeshed_betas(shape, g):
    x, y, train_w, regs = _table(g)
    plain = np.asarray(lg._irls_sweep(x, y, train_w, regs, max_iter=ITERS))
    with use_mesh(_mesh(shape)):
        meshed = lg._irls_sweep(*_placed(x, y, train_w, regs), max_iter=ITERS)
    assert meshed.shape == (g, K, D1)
    assert np.isfinite(plain).all()
    np.testing.assert_allclose(np.asarray(meshed), plain, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.parametrize("shape", MESHES)
def test_the_region_sees_a_chips_rows_and_all_reduces_only_its_sums(shape):
    g = 3
    x, y, train_w, regs = _table(g)
    with use_mesh(_mesh(shape)):
        args = _placed(x, y, train_w, regs)
        eqns = list(_eqns(_jaxpr(args)))
        counted = lg.irls_allreduce_bytes(K, g, D1, ITERS)
    regions = [e for e, _ in eqns if e.primitive.name == "shard_map"]
    assert len(regions) == 1
    inside = list(_eqns(regions[0].params["jaxpr"]))
    rows = N // shape[0]
    dims = [[v.aval.shape for v in e.invars]
            for e, _ in inside if e.primitive.name == "dot_general"]
    assert not any(N in s for d in dims for s in d)
    # the margin, the gradient and the Hessian contract a chip's rows
    assert sum(any(rows in s for s in d) for d in dims) >= 3

    psums = [(e, loop) for e, loop in inside if e.primitive.name == "psum"]
    assert {e.params["axes"] for e, _ in psums} == {("data",)}
    assert sum(e.primitive.name == "psum" for e, _ in eqns) == len(psums)
    lanes = -(-g // shape[1]) * K         # a model slice's lanes
    sizes = sorted((int(np.prod(v.aval.shape)), loop)
                   for e, loop in psums for v in e.invars)
    assert sizes == sorted([(K, False), (lanes * D1, True),
                            (lanes * D1 * D1, True)])
    per_slice = sum(n * 4 * (ITERS if loop else 1) for n, loop in sizes)
    assert counted == per_slice * shape[1]


def test_off_the_mesh_the_program_holds_no_region_and_no_psum():
    x, y, train_w, regs = _table(3)
    names = {e.primitive.name for e, _ in _eqns(_jaxpr((x, y, train_w,
                                                        regs)))}
    assert "dot_general" in names
    assert not names & {"shard_map", "psum"}
    assert lg.irls_allreduce_bytes(K, 3, D1, ITERS) == 0


@pytest.mark.parametrize("shape", [None] + MESHES)
def test_the_launch_span_counts_the_bytes_the_region_all_reduces(shape):
    """``irls_allreduce_bytes`` on the sweep's ``host.launch``: the formula
    from shapes at dispatch on a mesh (grid padded to the model axis), 0 off
    it."""
    x, y, train_w, _ = _table(3)
    x = x[:, :D]
    val_w = 1.0 - train_w
    grids = [{"reg_param": r} for r in (0.001, 0.01, 0.1)]
    est = lg.LogisticRegression(max_iter=ITERS)

    def sweep():
        with record_phases() as rec:
            est._cv_sweep_device(x, y, train_w, val_w, grids,
                                 M.METRICS_BINARY["auPR"])
        return [s.counts["irls_allreduce_bytes"] for s in rec.spans
                if s.path == "host.launch"
                and s.counts.get("label") == "LogisticRegression/irls_sweep"]

    if shape is None:
        assert sweep() == [0]
        return
    with use_mesh(_mesh(shape)):
        got = sweep()
    slices = shape[1]
    g_pad = -(-3 // slices) * slices
    assert got == [(ITERS * g_pad * K * (D1 * D1 + D1) + slices * K) * 4]


def test_the_region_under_jit_keys_apart_from_the_unmeshed_trace():
    """A process that ran the sweep off the mesh, then under two meshes,
    gets each program: the meshed operands key jax's trace apart."""
    x, y, train_w, regs = _table(2, seed=3)
    plain = np.asarray(lg._irls_sweep(x, y, train_w, regs, max_iter=ITERS))
    for shape in MESHES:
        with use_mesh(_mesh(shape)):
            args = _placed(x, y, train_w, regs)
            text = lg._irls_sweep.lower(*args, max_iter=ITERS).as_text()
            got = lg._irls_sweep(*args, max_iter=ITERS)
        assert "all_reduce" in text
        np.testing.assert_allclose(np.asarray(got), plain, rtol=1e-5,
                                   atol=1e-6)
    again = lg._irls_sweep.lower(jnp.asarray(x), jnp.asarray(y),
                                 jnp.asarray(train_w), jnp.asarray(regs),
                                 max_iter=ITERS).as_text()
    assert "all_reduce" not in again
