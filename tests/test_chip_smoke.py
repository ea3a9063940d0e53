"""Tier-1 guards for ``chip_smoke.py`` (ISSUE 21): the script the driver runs
on the chip must not rot between chip runs, and "a kernel cannot be lowered
for TPU" must be caught without a chip.

CPU only.  The smoke body runs at a tiny size with the kernels in interpret
mode; nothing here is a time, a rate or a device number.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


class TestSmokeBody:
    def test_body_runs_end_to_end_at_tiny_size(self, monkeypatch):
        monkeypatch.setenv("TMOG_PALLAS", "interpret")
        out = chip_smoke.run(rows=2048, rf_trees=6, gbt_rounds=6,
                             rf_depths=(2, 3), require_tpu=False)
        assert out["ok"] is True and out["claim"] is None
        assert out["device"]["platform"] == "cpu"
        assert out["width"] == chip_smoke.WIDTH == 128
        facts = out["setup_facts"]
        assert facts["second_train_backend_compiles"] == 0
        assert facts["serve_vs_score_max_delta"] == 0.0   # bitwise on CPU
        # the interpret-mode Pallas kernels really ran, and matched; the
        # grower's routing is the XLA compare-reduce in every mode (PR 33)
        picked = out["kernels_selected"]
        for kernel in ("split", "encode", "hist"):
            assert picked.get(f"{kernel}:interpret", 0) > 0, picked
        assert picked.get("route:xla", 0) > 0, picked
        assert picked.get("route:interpret", 0) == 0, picked
        assert len(out["kernels"]) == 4
        assert set(out["kernels"].values()) == {"matches"}
        # the verdict the driver parses: these keys and no others
        verdict = json.loads(chip_smoke.verdict_line(out))
        assert verdict == {"ok": True, "device": {
            "platform": "cpu", "kind": jax.devices()[0].device_kind,
            "count": len(jax.devices())}}

    def test_last_stdout_line_is_the_bare_verdict(self, monkeypatch, capsys):
        """The driver refuses a last line with any key besides ``ok`` and
        ``device``; the summary (``"claim": null``) is the line before it."""
        canned = {"ok": True, "rows": 7, "setup_facts": {"total_wall_s": 1.0},
                  "device": {"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}, "claim": None}
        monkeypatch.setattr(chip_smoke, "run", lambda **kw: dict(canned))
        assert chip_smoke.main([]) == 0
        lines = capsys.readouterr().out.splitlines()
        assert lines[-1] == ('{"ok": true, "device": {"platform": "tpu", '
                             '"kind": "TPU v5 lite", "count": 1}}')
        summary = json.loads(lines[-2])
        assert summary["claim"] is None and list(summary)[-1] == "claim"

    def test_refuses_to_run_without_a_chip(self):
        with pytest.raises(SystemExit) as exc:
            chip_smoke.run(rows=64)
        assert "no chip" in str(exc.value)

    def test_mesh_form_fails_on_too_few_devices(self, monkeypatch):
        # device check ON, backend check satisfied: 8 virtual devices < 16
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        with pytest.raises(SystemExit) as exc:
            chip_smoke.run(rows=64, mesh_shape=(4, 4))
        assert "needs 16 TPU devices" in str(exc.value)

    def test_broken_family_fails_the_script(self, monkeypatch):
        """A family that dies in the sweep must fail the smoke — the
        robust-to-failing-models path (models/tuning.py) would otherwise
        let LR win and the process exit 0."""
        from transmogrifai_tpu.serve.faults import FaultHarness

        monkeypatch.setenv("TMOG_PALLAS", "interpret")
        harness = FaultHarness().fail_when(
            "sweep_dispatch",
            lambda ctx: ctx.get("family") == "RandomForestClassifier",
            lambda: RuntimeError("scripted: RF sweep refused"))
        with harness, pytest.raises(SystemExit) as exc:
            chip_smoke.run(rows=1024, rf_trees=4, gbt_rounds=4,
                           rf_depths=(2,), require_tpu=False)
        assert "failed_models" in str(exc.value)

    def test_no_accelerator_exit_code_and_no_result_line(self):
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "chip_smoke.py")],
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
            capture_output=True, text=True, timeout=120)
        assert out.returncode != 0
        assert "no chip" in out.stderr
        assert '"ok"' not in out.stdout


# ---------------------------------------------------------------------------
# Every kernel ``auto`` can select on a TPU cross-lowers for TPU at the
# smoke's real shapes (jaxpr -> Mosaic MLIR; no chip, seconds on the CPU)
# ---------------------------------------------------------------------------

N, D, BINS = chip_smoke.ROWS, chip_smoke.WIDTH, chip_smoke.BINS
B = BINS + 1
S = jax.ShapeDtypeStruct
F32, I32 = jnp.float32, jnp.int32


def _split(lanes, nodes):
    from transmogrifai_tpu.perf.kernels.splitscan import split_scan_pallas

    hist = S((lanes, nodes, 1, D, B), F32)
    tot = S((lanes, nodes, 1), F32)
    return (lambda hg, hh, g, h, m: split_scan_pallas(
        hg, hh, g, h, m, BINS, 1.0, 0.0, 0.0, 1.0),
        (hist, hist, tot, tot, S((lanes, D), F32)))


def _hist(int_exact):
    from transmogrifai_tpu.perf.kernels.histogram import hist_level_pallas

    gh = S((3, 2, 8192), jnp.int8 if int_exact else F32)
    return (lambda lo, g, b: hist_level_pallas(
        lo, g, b, 2, BINS, int_exact=int_exact, mxu_dtype=jnp.bfloat16,
        chunk=512),
        (S((3, 8192), I32), gh, S((8192, D), I32)))


def _onehot():
    from transmogrifai_tpu.perf.kernels.encode import onehot_codes

    return (lambda c: onehot_codes(c, chip_smoke.PICKLISTS[0] + 2),
            (S((N,), I32),))


def _bucketize():
    from transmogrifai_tpu.perf.kernels.encode import bucketize_right_encode

    return (lambda x, s: bucketize_right_encode(x, s, True, True),
            (S((N,), F32), S((6,), F32)))


def _hinge():
    from transmogrifai_tpu.perf.kernels import descent
    from transmogrifai_tpu.perf.kernels.dispatch import descent_tile

    def step(x, y, w, beta):
        block = descent.hinge_block(x, y, w, descent_tile(D))
        return descent.hinge_grad_pallas(block, beta, True)

    return step, (S((N, D + 1), F32), S((N,), F32), S((N,), F32),
                  S((D + 1,), F32))


TPU_KERNELS = {
    "split_scan@gbt": lambda: _split(chip_smoke.FOLDS, 4),
    # the deepest level of a depth-6 boosted tree (the grid cell, PR 34)
    "split_scan@gbt-depth6": lambda: _split(chip_smoke.FOLDS, 32),
    "split_scan@rf": lambda: _split(chip_smoke.FOLDS * 50, 32),
    "hist_level@stream-bf16": lambda: _hist(False),
    "hist_level@stream-int8": lambda: _hist(True),
    "onehot_codes": _onehot,
    # the SVC refit's squared-hinge step, the ones column last
    "hinge_grad@refit": _hinge,
    "bucketize_right_encode": _bucketize,
}


@pytest.mark.parametrize("name", sorted(TPU_KERNELS))
def test_kernel_cross_lowers_for_tpu(name):
    fn, specs = TPU_KERNELS[name]()
    text = jax.jit(fn).trace(*specs).lower(  # opcheck: allow(TM303) test
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel in the IR"


# ---------------------------------------------------------------------------
# The walk's routing entry is NOT among them (PR 33): with compiled Pallas
# forced it lowers for TPU to plain XLA at the boosted cell's shapes (2^20 x
# 128 codes; the sweep's three lanes, the refit's one) and at the 128 lanes
# the kernel used to be admitted for, and counts itself as ``route:xla`` —
# at a level that gathers its columns (32 nodes: a ``dot_general``, PR 37)
# and at one that compares with all of them (128 nodes) alike.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nodes", [32, 128])
@pytest.mark.parametrize("lanes", [chip_smoke.FOLDS, 1, 128])
def test_grower_routing_lowers_to_plain_xla_for_tpu(lanes, nodes):
    from functools import partial

    from transmogrifai_tpu.perf.kernels import dispatch as KD
    from transmogrifai_tpu.perf.kernels.routing import level_select_lanes

    rows = S((lanes, 2 ** 20), I32)
    specs = (S((2 ** 20, D), I32), S((lanes, nodes), I32), rows, rows)
    before = KD.kernel_selections()
    with KD.force_kernel_mode("pallas"):
        text = jax.jit(partial(level_select_lanes, n_bins=32, chunk=2048)  # opcheck: allow(TM303) test
                       ).trace(*specs).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" not in text
    assert ("dot_general" in text) == (nodes < D)
    moved = {k: v - before.get(k, 0) for k, v in KD.kernel_selections().items()
             if k.startswith("route:") and v != before.get(k, 0)}
    assert moved == {"route:xla": 1}


# ---------------------------------------------------------------------------
# ... and the TPU compiler itself accepts the kernels of TPU_KERNELS.  libtpu
# can compile for a v5e without one (a compile-only topology), which is where
# Mosaic's refusals surface: the int8 multiply, the scoped-VMEM limit.  A
# child process, so libtpu never loads into the test session; skipped where
# libtpu cannot describe the topology.
# ---------------------------------------------------------------------------

_MOSAIC_SCRIPT = r"""
import json, os, sys
repo = sys.argv[1]
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["TMOG_PERSISTENT_CACHE"] = "0"   # compile-only executables do not load
sys.path[:0] = [repo, os.path.join(repo, "tests")]
import jax
from jax.experimental import topologies
try:
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
except Exception as e:
    print("RESULT " + json.dumps({"skip": f"{type(e).__name__}: {e}"[:300]}))
    sys.exit(0)
import test_chip_smoke as t
chip = jax.sharding.SingleDeviceSharding(topo.devices[0])
failed = {}
for name in sorted(t.TPU_KERNELS):
    fn, specs = t.TPU_KERNELS[name]()
    specs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip) for s in specs]
    try:
        jax.jit(fn).lower(*specs).compile()
    except Exception as e:
        failed[name] = f"{type(e).__name__}: {e}"[:600]
print("RESULT " + json.dumps({"failed": failed, "n": len(t.TPU_KERNELS)}))
"""


def test_kernels_compile_for_v5e_without_a_chip():
    out = subprocess.run(
        [sys.executable, "-c", _MOSAIC_SCRIPT, REPO],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=REPO,
        capture_output=True, text=True, timeout=300)
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    assert out.returncode == 0 and lines, out.stderr[-2000:]
    result = json.loads(lines[-1][len("RESULT "):])
    if "skip" in result:
        pytest.skip(f"no compile-only TPU topology here: {result['skip']}")
    assert result["n"] == len(TPU_KERNELS)
    assert result["failed"] == {}
