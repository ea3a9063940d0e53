"""The row-sharded sweep as a deployment (``binsel_lr_mesh4_d128``): a selector
fit of its families under ``use_mesh(make_mesh(4, 1))`` on the 8 virtual CPU
devices, at a small size, held to the benchmark's plain reference; what the
mesh did with the bytes (``placement_stats()["mesh"]``, the fit recorder's
``mesh``); a degradation seen where one happens; and nothing of it with no
mesh.  CPU only: nothing here is a time or a device number."""


import jax
import numpy as np
import pytest

from chipbench import run as harness
from chipbench import traffic
from chipbench.entries import selector_fit, selector_fit_mesh
from transmogrifai_tpu.parallel import mesh as M

CONFIG = "binsel_lr_mesh4_d128"
SEED = 2**31 + 77


def _config(rows):
    """The deployment's configuration with one limit widened for the size.

    Limits, and why they hold at a few thousand rows: ``choice_regret``,
    ``refit_score_gap`` and ``train_eval_gap`` are the configuration's own
    (the refit and its evaluation are float32 programs on either side, and a
    sharded sum only adds in another order: 1e-6 and under).  ``cv_metric_gap``
    is the configuration's plus 4 / rows: the program's auPR under fold
    weights loses its (0, 1) start point when the top-ranked row lies outside
    the fold and then sits 0.5 / n_pos under the plain one (PERF.md, Open
    questions) — about 3 / rows here, nothing at the timed size."""
    cfg = harness.load_config(harness.load_benchmark(), CONFIG)
    limits = dict(cfg["limits"],
                  cv_metric_gap=cfg["limits"]["cv_metric_gap"] + 4.0 / rows)
    return {**cfg, "limits": limits}


@pytest.fixture(autouse=True)
def _interpret(monkeypatch):
    monkeypatch.setenv("TMOG_PALLAS", "interpret")


#: 4 divides the first; the second pads to 8192 rows, 4093 of weight 0
@pytest.fixture(scope="module", params=[4096, 4099])
def fitted(request):
    """One warm-up and one timed fit through the benchmark's entry."""
    rows = request.param
    cfg = _config(rows)
    table = traffic.generate({**traffic.load("postprep_16m"), "rows": rows},
                             SEED)
    state = selector_fit_mesh.setup(cfg, table)
    rec = selector_fit_mesh.step(state)
    selector_fit_mesh.collect(state, [rec], table, SEED)
    return rows, cfg, table, state, rec


def test_fit_under_a_4x1_mesh_agrees_with_the_plain_reference(fitted):
    rows, cfg, table, _, rec = fitted
    assert rec["failed"] == 0 and rec["why_failed"] == [], rec["why_failed"]
    assert rec["attempted"] == 15
    compared, detail = selector_fit_mesh.compare(cfg, table, [rec], SEED)
    assert set(compared) == set(cfg["limits"])
    for name, (value, limit) in compared.items():
        assert value <= limit, (name, value, limit, detail["cv_gaps"])


def test_each_of_the_four_devices_holds_a_quarter_of_the_padded_rows(fitted):
    rows, cfg, table, state, rec = fitted
    padded = M.bucket_size(rows)
    with M.use_mesh(state.mesh):
        placed, n_valid = M.place_rows_bucketed_cached(table.x)
    assert n_valid == rows and placed.shape == (padded, 128)
    shards = placed.addressable_shards
    assert len({s.device for s in shards}) == 4
    assert [s.data.shape for s in shards] == [(padded // 4, 128)] * 4
    assert selector_fit_mesh.shard_faults(placed, 4) == []
    assert rec["mesh"]["shape"] == {"data": 4, "model": 1}
    assert rec["mesh"]["rows_per_shard"] == padded // 4
    # the same array laid out whole on every device is a fault
    whole = jax.device_put(np.zeros((8, 2), np.float32), M.replicated(
        state.mesh))
    assert selector_fit_mesh.shard_faults(whole, 4)


def test_the_fit_shards_and_replicates_what_its_shapes_give(fitted):
    """A warm fit's placements, by row count n of the padded block: the two
    (3, n) fold-weight blocks and the evaluators' unit weights are made on
    the device and laid out sharded (28 n bytes), and so are the eval
    program's scores, its 15 lanes padded to 16 and dealt four to a chip
    (64 n bytes); the eval program pins the labels and the (3, n) validation
    weights to every device (16 n bytes), and the seven grid scalars ride a
    model axis of one (28 bytes).  The table, labels, base weights and fold
    ids are cache hits: nothing is placed for them.  Nothing degrades."""
    rows, _, _, state, rec = fitted
    n = M.bucket_size(rows)
    moved = rec["counters"]
    assert moved["mesh_degraded"] == 0 and moved["mesh_bytes_degraded"] == 0
    assert moved["mesh_bytes_sharded"] == (2 * 3 * 4 + 4) * n + 16 * 4 * n
    assert moved["mesh_bytes_replicated"] == (1 + 3) * 4 * n + 7 * 4
    # the recorder of the fit says the same
    for name in ("degraded", "bytes_degraded", "bytes_sharded",
                 "bytes_replicated"):
        assert rec["mesh"][name] == moved[f"mesh_{name}"]


@pytest.mark.parametrize("precision", ["bfloat16", "float8"])
def test_lower_precision_control_is_not_correct_through_the_mesh_entry(
        precision):
    rows = 4096
    cfg = _config(rows)
    assert precision in cfg["controls"]
    table = traffic.generate({**traffic.load("postprep_16m"), "rows": rows},
                             5)
    sharded = selector_fit_mesh.shard_table(table, 4)
    assert len(sharded.x.sharding.device_set) == 4      # the reference's own
    low, _ = selector_fit_mesh.compare(cfg, table, [], 5,
                                       precision=precision, control=True)
    assert any(v > lim for v, lim in low.values()), (precision, low)


def test_float32_control_reads_zero_through_the_mesh_entry():
    cfg = _config(4096)
    table = traffic.generate({**traffic.load("postprep_16m"), "rows": 4096},
                             5)
    same, _ = selector_fit_mesh.compare(cfg, table, [], 5, control=True)
    assert all(v == 0.0 for v, _ in same.values()), same


def _mesh_counts():
    return M.placement_stats()["mesh"]


def test_a_degradation_is_counted_from_the_floor_up():
    mesh = M.make_mesh(4, 2)
    big = np.zeros(M.DEGRADED_MIN_BYTES // 4 + 1, np.float32)  # 4 ∤ rows
    with M.use_mesh(mesh):
        before = _mesh_counts()
        # legal and unseen: a one-point grid over a two-way model axis, and
        # a row-aligned vector under the floor
        M.place(np.zeros(1, np.float32), (M.MODEL_AXIS,))
        M.place(np.zeros(1001, np.float32), (M.DATA_AXIS,))
        assert _mesh_counts()["degraded"] == before["degraded"]
        placed = M.place(big, (M.DATA_AXIS,))
        after = _mesh_counts()
    assert after["degraded"] == before["degraded"] + 1
    assert after["bytes_degraded"] == before["bytes_degraded"] + big.nbytes
    # it became one replica a device, and is counted as that
    assert len(placed.addressable_shards) == 8
    assert all(s.data.shape == big.shape for s in placed.addressable_shards)
    assert after["bytes_replicated"] - before["bytes_replicated"] \
        == big.nbytes + 4 + 4004
    # inside a program: counted when it is traced
    with M.use_mesh(mesh):
        jax.jit(lambda a: M.constrain_rows(a) * 2.0).lower(
            jax.ShapeDtypeStruct(big.shape, big.dtype))
    assert _mesh_counts()["degraded"] == after["degraded"] + 1
    # an array that divides is sharded, not degraded
    with M.use_mesh(mesh):
        even = M.place(np.zeros((2, 1 << 18), np.float32),
                       (M.MODEL_AXIS, M.DATA_AXIS))
    assert _mesh_counts()["degraded"] == after["degraded"] + 1
    assert even.addressable_shards[0].data.shape == (1, 1 << 16)


def _sweep_irs():
    from transmogrifai_tpu.checkers.irsnap import build_corpus

    snaps, _ = build_corpus(["models.logistic", "models.base"])
    return {k: s.ir_fingerprint for k, s in snaps.items() if "@mesh" not in k}


def test_with_no_mesh_every_counter_stays_zero_and_the_programs_are_the_same(
        fitted):
    """A fit with no mesh moves no ``mesh`` counter and its recorder's
    ``mesh`` stays None; and the unmeshed sweep programs lower to the same
    bytes after meshed fits ran in the process (``fitted``) as the checked-in
    snapshots' own test (tests/test_irsnap.py) holds them to."""
    cfg = {**_config(2048), "mesh": None, "entry": "selector_fit"}
    table = traffic.generate({**traffic.load("postprep_16m"), "rows": 2048},
                             SEED + 1)
    irs = _sweep_irs()
    assert len(irs) >= 4
    before = _mesh_counts()
    state = selector_fit.setup(cfg, table)
    rec = selector_fit.step(state)
    assert rec["failed"] == 0, rec["why_failed"]
    assert _mesh_counts() == before
    assert state.selector.last_fit_profile.mesh is None
    M.count_replicated(None, np.zeros(4, np.float32))
    assert _mesh_counts() == before
    assert _sweep_irs() == irs
