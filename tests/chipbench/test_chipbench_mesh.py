"""Guards on the four-chip cell ``lr_sweep_mesh4_16m`` and the files it
brings: the configuration is ``binsel_lr_d128``'s but for the mesh, the
traffic ``postprep_4m``'s but for the rows, the work file divides by the
mesh's chips, each new reader returns None where there is nothing of its own
to read (an older program, a CPU run), and the entry counts a fit as failed
when the program's degradation counter moves or the table does not lie in
equal shares.  CPU only; nothing here is a time or a device number."""

import importlib

import numpy as np
import pytest

from chipbench import layerlib
from chipbench import run as harness
from chipbench import traffic
from chipbench.entries import selector_fit, selector_fit_mesh

CELL = "lr_sweep_mesh4_16m"
BENCH = harness.load_benchmark()
READERS = ["lr_mesh_device_s", "mesh_eval_device_s", "mesh_roofline",
           "replicated_gb", "mesh_host_lead_s"]


def _cell():
    return next(w for w in BENCH["workloads"] if w["name"] == CELL)


def _config(name="binsel_lr_mesh4_d128"):
    return harness.load_config(BENCH, name)


def test_the_cell_is_the_one_four_chip_cell_and_its_files_resolve():
    cell = _cell()
    assert cell["chips"] == 4 and cell["traffic"] == "postprep_16m"
    assert [w["name"] for w in BENCH["workloads"] if w["chips"] == 4] == [CELL]
    cfg = _config(cell["config"])
    assert cfg["entry"] == "selector_fit_mesh" and cfg["mesh"] == [4, 1]
    entry = importlib.import_module(f"chipbench.entries.{cfg['entry']}")
    for fn in ("enable_cache", "setup", "step", "collect", "release",
               "compare"):
        assert callable(getattr(entry, fn)), fn
    work = importlib.import_module(f"chipbench.work.{cfg['name']}")
    assert callable(work.work)
    for name in READERS:
        m = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "fold_models_per_s"
    # every metric without a list is read in this cell as it is, and the
    # six of set-up since PR 40 listed it
    from test_chipbench_setup_spans import METRICS as SETUP

    assert {m["name"] for m in harness.cell_metrics(BENCH, "per_layer", CELL)
            } == set(READERS) | set(SETUP) | {
        "cv_dispatch_s", "tail_s", "fit_mfu", "window_compiles",
        "setup_cache_loads", "device_idle_share", "peak_hbm_gb"}


def test_nothing_is_cut_from_the_one_chip_configuration():
    mesh, single = _config(), _config("binsel_lr_d128")
    for key in ("selector", "width", "cv", "precision", "controls",
                "reduced"):
        assert mesh[key] == single[key], key
    assert set(mesh["limits"]) == set(single["limits"])
    assert len(mesh["families"]) == len(single["families"]) == 1
    fam, was = mesh["families"][0], single["families"][0]
    assert {k: v for k, v in fam.items() if k != "eval_modules"} == was
    assert set(fam["eval_modules"]) <= set(fam["modules"])
    for key, note in single["reduced_notes"].items():
        assert mesh["reduced_notes"][key] == note
    assert mesh["assumed"][:len(single["assumed"])] == single["assumed"]
    mix, was = traffic.load("postprep_16m"), traffic.load("postprep_4m")
    assert mix == {**was, "rows": 1 << 24}
    # float32 x 128 columns: more than a chip's 16 GB holds with its copy
    assert mix["rows"] * traffic.width(mix) * 4 == 8 * (1 << 30)


def test_work_is_the_whole_fits_divided_by_the_meshs_chips():
    from chipbench.work import binsel_lr_d128, binsel_lr_mesh4_d128

    cfg, mix = _config(), traffic.load("postprep_16m")
    whole = binsel_lr_d128.work(cfg, mix, traffic.width(mix))
    share = binsel_lr_mesh4_d128.work(cfg, mix, traffic.width(mix))
    assert set(share) == set(whole) == {"lr"}
    for kind in ("flops", "bytes"):
        assert share["lr"][kind] == whole["lr"][kind] / 4
    two = binsel_lr_mesh4_d128.work({**cfg, "mesh": [2, 1]}, mix,
                                    traffic.width(mix))
    assert two["lr"]["bytes"] == whole["lr"]["bytes"] / 2
    # a chip's quarter at 2^24 rows is the one-chip cell's whole at 2^22
    small = traffic.load("postprep_4m")
    assert share == binsel_lr_d128.work(cfg, small, traffic.width(small))


def _read(name, ctx):
    return importlib.import_module(f"chipbench.per_layer.{name}").read(ctx)


def _ctx(**over):
    ctx = {"config": _config(), "traffic": traffic.load("postprep_16m"),
           "records": [], "trace": None, "traced_calls": 0, "peaks": None,
           "notes": {}}
    ctx.update(over)
    return ctx


@pytest.mark.parametrize("name", READERS)
def test_a_reader_with_nothing_of_its_own_to_read_returns_none(
        name, monkeypatch):
    from transmogrifai_tpu.perf import timers

    monkeypatch.setattr(timers, "recent_fit_profiles", lambda: [])
    # no trace, no record
    assert _read(name, _ctx()) is None
    # an older program's record (no mesh counter) and a trace of other
    # modules than the configuration's
    rec = {"seconds": 1.0, "counters": {"compiles": 0}, "spans": {}}
    other = {"modules": {"jit_something_else": 1.0}, "window_s": 2.0,
             "busy_s": 1.0}
    peaks = harness.load_peaks("TPU v5 lite")
    assert _read(name, _ctx(records=[rec], trace=other, traced_calls=1,
                            peaks=peaks)) is None


def test_readers_read_what_the_record_and_the_trace_hold():
    peaks = harness.load_peaks("TPU v5 lite")
    trace = {"modules": {"jit__irls_sweep": 2.0, "jit__fista_sweep": 4.0,
                         "jit_eval_linear_sweep": 6.0, "jit__irls_core": 9.0},
             "window_s": 30.0, "busy_s": 21.0}
    recs = [{"seconds": 5.0, "spans": {},
             "counters": {"compiles": 0, "mesh_bytes_replicated": b}}
            for b in (1.0e9, 2.0e9)]
    ctx = _ctx(records=recs, trace=trace, traced_calls=2, peaks=peaks)
    assert _read("lr_mesh_device_s", ctx) == pytest.approx(6.0)
    assert _read("mesh_eval_device_s", ctx) == pytest.approx(3.0)
    assert _read("replicated_gb", ctx) == pytest.approx(1.5)
    least = layerlib.least_seconds(ctx, ["lr"])[0]
    assert _read("mesh_roofline", ctx) == pytest.approx(100.0 * least / 6.0)
    # one chip's share is held against one chip's peaks: under 100% as long
    # as the sweeps take the 0.94 s the one-chip cell's whole work needs
    assert least == pytest.approx(0.94, abs=0.01)


# ---------------------------------------------------------------------------
# The entry's failure rule, on a real tiny fit
# ---------------------------------------------------------------------------

ROWS = 2048


@pytest.fixture(scope="module")
def state():
    table = traffic.generate({**traffic.load("postprep_16m"), "rows": ROWS},
                             2**31 + 31)
    return selector_fit_mesh.setup(_config(), table)


def test_a_clean_fit_is_not_failed_and_carries_selector_fits_keys(state):
    rec = selector_fit_mesh.step(state)
    assert rec["failed"] == 0 and rec["why_failed"] == []
    assert {"seconds", "attempted", "failed", "why_failed", "counters",
            "spans", "cv", "best", "train_eval"} <= set(rec)
    assert {"compiles", "cache_loads", "aot_fallbacks", "planner_fallbacks",
            "mesh_degraded", "mesh_bytes_replicated"} <= set(rec["counters"])


def test_a_fit_is_failed_whole_when_the_degradation_counter_moves(
        state, monkeypatch):
    from transmogrifai_tpu.parallel import mesh as M

    real = selector_fit.step

    def degrading(st, may_compile=False):
        # inside the entry's use_mesh: a vector 4 does not divide, placed as
        # a fit would place a row-aligned input
        M.place(np.zeros((1 << 18) + 1, np.float32), (M.DATA_AXIS,))
        return real(st, may_compile)

    monkeypatch.setattr(selector_fit, "step", degrading)
    rec = selector_fit_mesh.step(state)
    assert rec["failed"] == rec["attempted"] == 15
    assert any("mesh_degraded moved by 1" in w for w in rec["why_failed"])


def test_a_fit_is_failed_whole_when_the_table_lies_in_unequal_shares(
        state, monkeypatch):
    import jax
    from transmogrifai_tpu.parallel import mesh as M

    clean = selector_fit_mesh.step(state)
    assert clean["failed"] == 0
    x = np.asarray(state.dataset["features"].data)
    whole = jax.device_put(x, M.replicated(state.mesh))
    monkeypatch.setattr(M, "place_rows_bucketed_cached",
                        lambda arr, *a, **k: (whole, arr.shape[0]))
    # an older program has no counter: the shares alone decide
    monkeypatch.setattr(selector_fit_mesh, "_mesh_counters", lambda: {})
    monkeypatch.setattr(
        selector_fit, "step", lambda st, may_compile=False: {
            **clean, "counters": dict(clean["counters"]), "why_failed": []})
    rec = selector_fit_mesh.step(state)
    assert rec["failed"] == rec["attempted"] == 15
    assert any("padded rows lie as" in w for w in rec["why_failed"])
