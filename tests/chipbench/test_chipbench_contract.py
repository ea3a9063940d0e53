"""Guards on ``BENCHMARK.json`` and the files it names: the contract's
character sets, one file per configuration / traffic mix / metric, a harness
that names none of them, and a table of peaks that refuses a device it does
not know.  CPU only; nothing here is a time or a device number."""

import importlib
import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=["committed", "with_staged_cells"])
def bench(request):
    """BENCHMARK.json as committed, and as it will read once the staged
    cells move into it: both have to keep the contract."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if request.param == "with_staged_cells":
        with open(os.path.join(REPO, "chipbench", "staged_cells.json")) as f:
            staged = json.load(f)
        for key in ("configs", "workloads", "per_layer"):
            bench[key] = bench[key] + staged[key]
    return bench


def _metrics(bench):
    return bench["end_to_end"] + bench["per_layer"]


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert 1 <= len(bench["workloads"]) <= 24
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert all(os.path.isdir(os.path.join(REPO, p)) for p in bench["paths"])
    assert not any(w.startswith("/") or ".." in w for w in bench["command"])


def test_every_name_and_unit_is_in_the_allowed_characters(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for w in bench["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    names += [m["name"] for m in _metrics(bench)]
    for n in names:
        assert NAME.match(n), n
    for m in _metrics(bench):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    metric_names = [m["name"] for m in _metrics(bench)]
    assert len(set(metric_names)) == len(metric_names)
    assert "setup_s" in metric_names


def test_every_entry_has_exactly_the_contracts_keys(bench):
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
        assert len(c["reduced"]) <= 16
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_cells_configurations_and_metrics_resolve_to_files(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    used = set()
    for w in bench["workloads"]:
        used.add(w["config"])
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "traffic", w["traffic"] + ".json"))
    for c in bench["configs"]:
        assert c["name"] in used
        path = os.path.join(REPO, c["file"])
        assert c["file"].startswith("chipbench/") and os.path.isfile(path)
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert os.path.isfile(os.path.join(
            REPO, "chipbench", "entries", cfg["entry"] + ".py"))
        for fam in cfg["families"]:
            assert os.path.isfile(os.path.join(
                REPO, "chipbench", "reference", fam["reference"] + ".py"))
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            mod = importlib.import_module(f"chipbench.{kind}.{m['name']}")
            assert callable(mod.read)
            assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e


def test_the_harness_names_no_cell_configuration_family_or_metric(bench):
    with open(os.path.join(REPO, "chipbench", "run.py")) as f:
        source = f.read()
    names = {m["name"] for m in _metrics(bench)}
    names |= {c["name"] for c in bench["configs"]}
    for w in bench["workloads"]:
        names |= {w["name"], w["traffic"]}
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        names |= {cfg["entry"]}
        for fam in cfg["families"]:
            names |= {fam["reference"], fam["estimator"].rsplit(".", 1)[-1]}
    for n in sorted(names):
        assert not re.search(rf"\b{re.escape(n)}\b", source), n


def test_nothing_in_the_benchmark_imports_the_old_scripts():
    pat = re.compile(r"^\s*(import|from)\s+(bench|chip_smoke)\b", re.M)
    for base, _, files in os.walk(os.path.join(REPO, "chipbench")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    assert not pat.search(f.read()), name


def test_references_import_nothing_of_the_program():
    for base, _, files in os.walk(os.path.join(REPO, "chipbench",
                                               "reference")):
        for name in files:
            if name.endswith(".py"):
                with open(os.path.join(base, name)) as f:
                    assert "transmogrifai_tpu" not in f.read(), name


def test_peaks_refuse_an_unknown_device_kind():
    from chipbench import run

    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in chipbench/peaks.json"):
        run.load_peaks("TPU v9 imaginary")
