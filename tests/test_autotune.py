"""Persistent kernel autotuner tests (perf/autotune.py, ISSUE 19).

Pins the store contracts end to end: sweep-once-then-cache-hit (including
under first-contact thread races), verified-before-eligible, corrupt /
schema-drifted entries falling back to defaults instead of crashing, the
``tune=<digest>`` cache-token component riding ``dispatch.cache_token()``
exactly when a non-default winner is adopted, and the kernel dispatchers
actually consuming a planted winner at trace time.  The ``tuning_int``
env-knob funnel's log-and-fall-back discipline rides along (satellite 1).
"""

import json
import logging
import os
import threading

import pytest

from transmogrifai_tpu.perf import autotune
from transmogrifai_tpu.perf.kernels import dispatch


@pytest.fixture()
def store(tmp_path, monkeypatch):
    """A throwaway winner store wired in as THE process store, with clean
    in-process adoption state on both sides of the test."""
    root = str(tmp_path / "autotune")
    monkeypatch.setenv("TMOG_AUTOTUNE_DIR", root)
    autotune.reset()
    yield root
    autotune.reset()


def _plant_winner(store_root, family, cls, params, *, schema=None,
                  verified=True):
    """Write a store entry the way a prior process's sweep would have."""
    entry = {
        "schema": autotune.SCHEMA_VERSION if schema is None else schema,
        "device_kind": autotune.device_kind(), "family": family,
        "shape_class": cls, "params": params, "verified": verified,
        "candidates": 5, "eligible": 5, "best_seconds": 1e-4,
        "default_seconds": 2e-4, "swept_unix": 0.0,
    }
    path = autotune._entry_path(autotune.device_kind(), family, cls,
                                store_root)
    os.makedirs(store_root, exist_ok=True)
    with open(path, "w") as fh:
        json.dump(entry, fh)
    return path


class TestTuningIntFallback:
    def test_non_integer_logs_and_falls_back(self, monkeypatch, caplog):
        monkeypatch.setenv("TMOG_HIST_CHUNK", "banana")
        with caplog.at_level(logging.WARNING,
                             logger="transmogrifai_tpu.perf.kernels"):
            assert dispatch.tuning_int("TMOG_HIST_CHUNK", 2048) == 2048
        assert any("banana" in r.message and "not an integer" in r.message
                   for r in caplog.records)

    def test_below_minimum_logs_and_falls_back(self, monkeypatch, caplog):
        monkeypatch.setenv("TMOG_HIST_CHUNK", "0")
        with caplog.at_level(logging.WARNING,
                             logger="transmogrifai_tpu.perf.kernels"):
            assert dispatch.tuning_int("TMOG_HIST_CHUNK", 2048,
                                       minimum=1) == 2048
        assert any("below the minimum" in r.message for r in caplog.records)

    def test_valid_value_passes_through_silently(self, monkeypatch, caplog):
        monkeypatch.setenv("TMOG_HIST_CHUNK", "512")
        with caplog.at_level(logging.WARNING,
                             logger="transmogrifai_tpu.perf.kernels"):
            assert dispatch.tuning_int("TMOG_HIST_CHUNK", 2048) == 512
        assert not caplog.records


class TestStoreRobustness:
    def test_corrupt_entry_reads_as_defaults(self, store):
        cls = autotune.shape_class("encode", "xla", rows=4096, width=16)
        path = autotune._entry_path(autotune.device_kind(), "encode", cls,
                                    store)
        os.makedirs(store, exist_ok=True)
        with open(path, "w") as fh:
            fh.write('{"schema": 1, "params": {"blo')  # torn write
        dec = autotune.ensure_tuned("encode", sweep_on_miss=False,
                                    store=store)
        assert dec.source == "default"
        assert dec.params == autotune.family_defaults("encode", cls)
        assert autotune.winners(store) == []

    def test_schema_mismatch_reads_as_defaults(self, store):
        cls = autotune.shape_class("encode", "xla", rows=4096, width=16)
        _plant_winner(store, "encode", cls, {"block": 256},
                      schema=autotune.SCHEMA_VERSION + 1)
        dec = autotune.ensure_tuned("encode", sweep_on_miss=False,
                                    store=store)
        assert dec.source == "default"
        assert autotune.winners(store) == []

    def test_unverified_entry_is_ignored(self, store):
        cls = autotune.shape_class("encode", "xla", rows=4096, width=16)
        _plant_winner(store, "encode", cls, {"block": 256}, verified=False)
        dec = autotune.ensure_tuned("encode", sweep_on_miss=False,
                                    store=store)
        assert dec.source == "default"

    def test_clear_removes_entries_and_resets_adoption(self, store):
        cls = autotune.shape_class("encode", "xla", rows=4096, width=16)
        _plant_winner(store, "encode", cls, {"block": 256})
        assert len(autotune.winners(store)) == 1
        assert autotune.clear(store) == 1
        assert autotune.winners(store) == []
        assert autotune.ensure_tuned("encode", sweep_on_miss=False,
                                     store=store).source == "default"


class TestSweepOnce:
    @pytest.mark.parametrize("family", sorted(autotune.FAMILIES))
    def test_sweep_persists_then_fresh_state_reads_cached(self, store,
                                                          family):
        swept = autotune.sweep(family, store=store, reps=1)
        assert swept.source == "swept" and swept.verified
        assert swept.candidates >= 1
        assert autotune.sweep_count() == 1
        autotune.reset()
        dec = autotune.ensure_tuned(family, sweep_on_miss=False,
                                    store=store)
        assert dec.source == "cached"
        assert dec.params == swept.params
        assert autotune.sweep_count() == 0  # the warm store swept NOTHING

    def test_one_store_answers_every_family_after_one_sweep_each(self,
                                                                 store):
        """Every kernel family sweeps ONCE into one store, every winner is
        verified, and a fresh adoption state answers all of them from the
        store at zero further sweeps."""
        assert set(autotune.FAMILIES) == {"hist", "split", "encode", "route"}
        decisions = {f: autotune.sweep(f, store=store, reps=1)
                     for f in autotune.FAMILIES}
        assert autotune.sweep_count() == len(autotune.FAMILIES)
        assert all(d.verified for d in decisions.values()), decisions
        assert len(autotune.winners(store)) == len(autotune.FAMILIES)
        autotune.reset()
        warm = {f: autotune.ensure_tuned(f, store=store, sweep_on_miss=False)
                for f in autotune.FAMILIES}
        assert autotune.sweep_count() == 0
        for f, dec in warm.items():
            assert dec.source == "cached", (f, dec)
            assert dec.params == decisions[f].params

    def test_concurrent_first_contact_sweeps_once(self, store):
        barrier = threading.Barrier(2)
        results, errors = [], []

        def contact():
            try:
                barrier.wait(timeout=30)
                results.append(autotune.ensure_tuned(
                    "encode", sweep_on_miss=True, store=store))
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append(e)

        threads = [threading.Thread(target=contact) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors
        assert autotune.sweep_count() == 1, \
            "two racing first contacts must produce exactly ONE sweep"
        assert results[0].params == results[1].params
        # the store entry the race produced is whole (no torn writes)
        entries = autotune.winners(store)
        assert len(entries) == 1 and entries[0]["verified"] is True

    def test_ensure_tuned_unarmed_never_sweeps(self, store):
        dec = autotune.ensure_tuned("encode", sweep_on_miss=False,
                                    store=store)
        assert dec.source == "default"
        assert autotune.sweep_count() == 0


class TestCacheToken:
    def test_untuned_token_is_empty(self, store):
        assert autotune.tuning_token() == ""
        assert "tune=" not in dispatch.cache_token()

    def test_default_winner_does_not_move_the_token(self, store):
        cls = autotune.shape_class("encode", "xla", rows=4096, width=16)
        _plant_winner(store, "encode", cls,
                      autotune.family_defaults("encode", cls))
        assert autotune.tuning_token() == ""

    def test_non_default_winner_rides_cache_token(self, store):
        baseline = dispatch.cache_token()
        cls = autotune.shape_class("encode", "xla", rows=4096, width=16)
        _plant_winner(store, "encode", cls, {"block": 512})
        autotune.reset()  # a fresh process adopting the warm store
        token = autotune.tuning_token()
        assert token.startswith("tune=")
        assert dispatch.cache_token() == f"{baseline}:{token}"
        # tokens are content-addressed: a different winner, different token
        _plant_winner(store, "encode", cls, {"block": 256})
        autotune.reset()
        assert autotune.tuning_token() not in ("", token)

    def test_provenance_names_the_adopted_winners(self, store):
        cls = autotune.shape_class("encode", "xla", rows=4096, width=16)
        _plant_winner(store, "encode", cls, {"block": 512})
        autotune.reset()
        prov = autotune.provenance()
        assert prov["store"] == store
        assert prov["token"].startswith("tune=")
        assert prov["winners"][f"encode/{cls}"] == {
            "params": {"block": 512}, "source": "cached"}


class TestKernelsConsumeWinners:
    def test_encode_resolves_planted_winner_block(self, store, monkeypatch):
        monkeypatch.delenv("TMOG_ENCODE_BLOCK", raising=False)
        from transmogrifai_tpu.perf.kernels import encode as KE

        n, width = 300, 7
        cls = autotune.shape_class("encode", "interpret", rows=n,
                                   width=width)
        _plant_winner(store, "encode", cls, {"block": 160})
        autotune.reset()
        assert KE._resolve_block(None, n, width, True) == 160
        # explicit arg and env knob both outrank the winner
        assert KE._resolve_block(64, n, width, True) == 64
        monkeypatch.setenv("TMOG_ENCODE_BLOCK", "96")
        assert KE._resolve_block(None, n, width, True) == 96

    def test_winner_applies_only_to_its_shape_class(self, store):
        from transmogrifai_tpu.perf.kernels import encode as KE

        cls = autotune.shape_class("encode", "interpret", rows=300, width=7)
        _plant_winner(store, "encode", cls, {"block": 160})
        autotune.reset()
        # a different width is a different class: module default applies
        assert KE._resolve_block(None, 300, 9, True) == KE._ENCODE_BLOCK


class TestCliTune:
    def test_show_run_clear_roundtrip(self, store, capsys):
        from transmogrifai_tpu.cli.gen import main

        assert main(["tune", "show", "--store", store]) == 0
        assert "no verified winners" in capsys.readouterr().out
        assert main(["tune", "run", "--family", "encode", "--reps", "1",
                     "--store", store, "--format", "json"]) == 0
        lines = [json.loads(ln) for ln
                 in capsys.readouterr().out.strip().splitlines()]
        assert lines[0]["sweep"]["family"] == "encode"
        assert lines[0]["sweep"]["verified"] is True
        assert main(["tune", "show", "--store", store,
                     "--format", "json"]) == 0
        lines = [json.loads(ln) for ln
                 in capsys.readouterr().out.strip().splitlines()]
        assert lines[0]["winner"]["family"] == "encode"
        assert lines[-1]["count"] == 1
        assert main(["tune", "clear", "--store", store]) == 0
        assert autotune.winners(store) == []

    def test_run_refuses_unknown_family(self, store):
        from transmogrifai_tpu.cli.gen import main

        with pytest.raises(SystemExit, match="unknown family"):
            main(["tune", "run", "--family", "nope", "--store", store])
