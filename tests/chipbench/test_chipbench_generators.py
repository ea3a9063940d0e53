"""Traffic generators found by name (``chipbench/traffic.py``,
``chipbench/generators/``): the post-prep table byte for byte as it was made
before it moved into a module of its own, every traffic file naming a
generator that is there, a generator added as a file alone reached through
``traffic`` and the harness, and an unknown name refused.  CPU only."""

import hashlib
import importlib
import json
import os
import sys

import numpy as np
import pytest

from chipbench import generators, traffic
from chipbench import run as harness

#: sha256 of ``x.tobytes()`` and ``y.tobytes()`` of the post-prep table at
#: the traffic files' parameters, computed on PR 39's ``chipbench/traffic.py``
#: before the move (the three files differ in ``rows`` alone): one block, and
#: three blocks with a short last one through the thread pool
DIGESTS = {
    (1 << 18, 2**31 + 40): (
        "361c4d85e539e34492ff5fa9e00bbd943278b6801d7b9fdfc9d2722be0a8d152",
        "263f953751a526fb7e748080aa234d0430d7e04d1b0b85d37b61efe1d01840e3"),
    (1 << 18, 3000000040): (
        "84b231b00513a5c41f263027b3fe60cbf5facfed85617a96e15e7015b7cbcd1f",
        "66dd90663126c6e277ec223ae8ef33c05dbaf36e11aa51ab60db57a9a48baafa"),
    ((1 << 19) + 12345, 2**31 + 40): (
        "88cb24e68efbaca834dac5c755ab66b603dc38df6aca3fc9ce447681eaae73d9",
        "dc4e229a5f5887968543913b3adff5c52ef4baf9076e4d000757f1fb47547217"),
    ((1 << 19) + 12345, 3000000040): (
        "6c068cd4e2cb39cebe02835a3aa53f80977be8911fa8c7959d9acb096e1d773a",
        "3c5f7791aeacaf67993307183676efc027490efb252b3bd8a626f6498223676a"),
}
MIXES = sorted(name[:-len(".json")] for name in os.listdir(
    os.path.join(harness.ROOT, "chipbench", "traffic")))


@pytest.mark.parametrize("rows,seed", sorted(DIGESTS))
def test_the_moved_table_is_the_parents_byte_for_byte(rows, seed):
    for mix in ("postprep_1m", "postprep_4m", "postprep_16m"):
        params = traffic.load(mix)
        assert {**params, "rows": rows} == {
            **traffic.load("postprep_1m"), "rows": rows}, mix
    t = traffic.generate({**traffic.load("postprep_1m"), "rows": rows}, seed)
    assert (hashlib.sha256(t.x.tobytes()).hexdigest(),
            hashlib.sha256(t.y.tobytes()).hexdigest()) == DIGESTS[rows, seed]


@pytest.mark.parametrize("mix", MIXES)
def test_every_traffic_file_names_a_generator_that_is_there(mix):
    params = traffic.load(mix)
    module = traffic.generator(params)
    assert module.__name__ == f"chipbench.generators.{params['generator']}"
    assert callable(module.generate) and callable(module.width)
    # the width the work models are handed, from the parameters alone
    assert traffic.width(params) == 128


def test_an_unknown_generator_is_refused_with_what_is_there():
    params = {**traffic.load("postprep_1m"), "generator": "no_such_table"}
    with pytest.raises(ValueError, match="no_such_table") as exc:
        traffic.generate(params, 1)
    assert "postprep_table" in str(exc.value)
    for name in (None, "..traffic", "postprep_table.generate"):
        with pytest.raises(ValueError, match="postprep_table"):
            traffic.width({**params, "generator": name})


#: a generator as a later PR would add it, as a file alone: the post-prep
#: table with its label turned over, so that a run shows whose table it fit
THROWAWAY = '''
from chipbench.generators import postprep_table
from chipbench.traffic import Table

CALLS = []


def width(params):
    return postprep_table.width(params)


def generate(params, seed):
    CALLS.append(seed)
    t = postprep_table.generate(params, seed)
    return Table(t.x, 1.0 - t.y)
'''


@pytest.fixture
def throwaway(tmp_path, monkeypatch):
    """``throwaway_table`` in a directory of its own on the package's path,
    and a traffic mix naming it in a directory of its own: no file of the
    repo is written or edited."""
    name = "throwaway_table"
    (tmp_path / "gen").mkdir()
    (tmp_path / "gen" / f"{name}.py").write_text(THROWAWAY)
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "throwaway_mix.json").write_text(json.dumps(
        {**traffic.load("postprep_4m"), "generator": name}))
    monkeypatch.setattr(generators, "__path__",
                        [*generators.__path__, str(tmp_path / "gen")])
    monkeypatch.setattr(traffic, "HERE", str(tmp_path))
    importlib.invalidate_caches()
    yield f"{generators.__name__}.{name}"
    sys.modules.pop(f"{generators.__name__}.{name}", None)


def test_a_generator_added_as_a_file_is_reached_by_name(throwaway,
                                                        monkeypatch):
    from test_chipbench_run import ROWS, _tiny

    monkeypatch.setenv("TMOG_PALLAS", "interpret")
    params = {**traffic.load("throwaway_mix"), "rows": ROWS}
    assert traffic.width(params) == 128
    t = traffic.generate(params, 2**31 + 40)
    base = traffic.generate({**params, "generator": "postprep_table"},
                            2**31 + 40)
    assert np.array_equal(t.x, base.x) and np.array_equal(t.y, 1.0 - base.y)
    # a staged cell on the new mix, through the harness as the driver runs it
    bench = harness.load_benchmark()
    bench["workloads"] = bench["workloads"] + [{
        "name": "throwaway_cell", "config": "binsel_svc_d128",
        "traffic": "throwaway_mix", "chips": 1, "why": "a test's"}]
    result = harness.run("throwaway_cell", 2**31 + 41, 0.3, False,
                         require_tpu=False, overrides=_tiny("svc_sweep_4m"),
                         free_device=False, bench=bench)
    assert sys.modules[throwaway].CALLS[-1] == 2**31 + 41
    assert result["correct"] is True, result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"fold_models_per_s", "setup_s"}
