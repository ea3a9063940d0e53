"""``chipbench/reduce.py`` on a small trace recorded on a v5e
(``chipbench/testdata/small.xplane.pb``: two named programs run three times
each, 10 ms of host sleep between them) and on hand-made planes.  The
expected numbers were added up by hand from the file's own events."""

import os

import pytest

from chipbench import reduce as R

TRACE = os.path.join(os.path.dirname(os.path.abspath(R.__file__)),
                     "testdata", "small.xplane.pb")
NS = 1e-9


@pytest.fixture(scope="module")
def reduced():
    return R.reduce_file(TRACE)


def test_window_is_the_host_span_and_busy_the_union_of_ops(reduced):
    assert reduced["devices"] == 1
    assert reduced["window_s"] == pytest.approx(37842609 * NS, abs=1e-12)
    # the first program ran 1 ms before the host span opened (the host's and
    # the device's clocks differ by that much) and is clipped away; the ops
    # of the other five add up to:
    by_hand = (276772 + 277239 + 277665) + (17784 + 17732)
    assert reduced["busy_s"] == pytest.approx(by_hand * NS, abs=1e-12)
    assert reduced["busy_s"] < reduced["window_s"]


def test_per_module_sums_and_runs(reduced):
    assert reduced["modules"] == pytest.approx({
        "jit_beta_program": (276785 + 277252 + 277678) * NS,
        "jit_alpha_program": (17790 + 17738) * NS}, abs=1e-12)
    assert reduced["module_runs"] == {"jit_beta_program": 3,
                                      "jit_alpha_program": 2}


def test_top_ops_and_longest_gaps(reduced):
    name, seconds = reduced["device_ops"][0]
    assert name.startswith("%sort.6 = ")
    assert seconds == pytest.approx((266154 + 266825 + 267005) * NS,
                                    abs=1e-12)
    assert len(reduced["device_ops"]) <= R.TOP
    label, gap = reduced["idle_gaps"][0]
    assert label == "jit_alpha_program -> jit_beta_program"
    assert gap == pytest.approx((80987795 - (69106362 + 17738)) * NS,
                                abs=1e-12)
    gaps = [g for _, g in reduced["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    # the sleeps between the programs are what the device waited for
    assert sum(gaps) == pytest.approx(
        reduced["window_s"] - sum(reduced["modules"].values()), rel=1e-6)


def test_union_and_gaps_on_hand_made_intervals():
    assert R.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.8)]) == 4
    assert R.union_seconds([]) == 0
    got = R.gaps([(1, 2, "a"), (4, 7, "b"), (5, 6, "c")], (0, 10))
    assert got == [("b -> window_end", 3), ("a -> b", 2),
                   ("window_start -> a", 1)]
    assert R.module_name("jit__irls_sweep(8423042300)") == "jit__irls_sweep"


def test_two_chips_average_and_a_cpu_trace_is_refused():
    planes = {
        "/host:CPU": {"python": [(R.WINDOW_SPAN, 0.0, 10.0)]},
        "/device:TPU:0": {R.MODULE_LINE: [("jit_f(1)", 1.0, 4.0)],
                          R.OP_LINE: [("%a", 1.0, 2.0), ("%b", 2.5, 2.5)]},
        "/device:TPU:1": {R.MODULE_LINE: [("jit_f(1)", 1.0, 2.0)],
                          R.OP_LINE: [("%a", 1.0, 2.0)]},
        "/device:TPU:0 SparseCore": {R.OP_LINE: [("%x", 0.0, 9.0)]},
    }
    out = R.reduce_planes(planes)
    assert out["devices"] == 2 and out["window_s"] == 10.0
    assert out["busy_s"] == pytest.approx((4.0 + 2.0) / 2)
    assert out["modules"] == {"jit_f": pytest.approx(3.0)}
    assert out["module_runs"] == {"jit_f": 1}
    assert out["device_ops"][0] == ["%a", pytest.approx(2.0)]
    with pytest.raises(R.NoDevicePlane):
        R.reduce_planes({"/host:CPU": planes["/host:CPU"]})
