"""Out-of-core chunked ingestion tests (ISSUE 13): the memory-mapped chunk
store, chunk-local gather (peak-RSS pins), the double-buffered prefetch
pipeline, bitwise chunked-vs-in-memory fit/score parity, crash-and-resume of
a chunked epoch via OffsetCheckpoint, the zero-new-compile guarantee across
chunk boundaries, the TM607 host-residency gate, and the IR-corpus pin that
chunking does not fork the program surface.
"""

import os

import numpy as np
import pytest

from transmogrifai_tpu import (
    BinaryClassificationModelSelector,
    Evaluators,
    FeatureBuilder,
    Workflow,
    transmogrify,
)
from transmogrifai_tpu.data.chunked import (
    ChunkedDataset,
    ChunkedDatasetWriter,
    ChunkStore,
    dataset_nbytes,
    maybe_chunk,
)
from transmogrifai_tpu.data.dataset import Column, Dataset, _gather_rows
from transmogrifai_tpu.models.logistic import LogisticRegression
from transmogrifai_tpu.perf import measure_compiles
from transmogrifai_tpu.perf.programs import program_cache_entries
from transmogrifai_tpu.readers import OffsetCheckpoint
from transmogrifai_tpu.readers.prefetch import ChunkPrefetcher, PrefetchStats
from transmogrifai_tpu.types import OPVector, PickList, Real, RealNN
from transmogrifai_tpu.workflow.fit import transform_dag
from transmogrifai_tpu.workflow.ooc import EpochStats, chunked_transform_epoch


def _fixture(n=2000, seed=12):
    rng = np.random.default_rng(seed)
    cols = {}
    for i in range(4):
        cols[f"num{i}"] = Column(Real, rng.normal(size=n),
                                 rng.random(n) > 0.1)
    levels = [f"lv{j}" for j in range(8)]
    for i in range(2):
        data = np.array(
            [None if rng.random() < 0.05
             else levels[rng.integers(0, len(levels))] for _ in range(n)],
            dtype=object)
        cols[f"cat{i}"] = Column(PickList, data)
    z = cols["num0"].data - cols["num1"].data
    y = (rng.random(n) < 1.0 / (1.0 + np.exp(-z))).astype(np.float64)
    cols["label"] = Column(RealNN, y, np.ones(n, dtype=np.bool_))
    return Dataset(cols)


def _features(with_selector=False, folds=2):
    label = FeatureBuilder.of("label", RealNN).extract_field().as_response()
    feats = [FeatureBuilder.of(f"num{i}", Real).extract_field()
             .as_predictor() for i in range(4)] + \
        [FeatureBuilder.of(f"cat{i}", PickList).extract_field()
         .as_predictor() for i in range(2)]
    checked = label.sanity_check(transmogrify(feats))
    if not with_selector:
        return label, checked
    sel = BinaryClassificationModelSelector.with_cross_validation(
        models=[(LogisticRegression(),
                 [{"reg_param": 0.01}, {"reg_param": 0.1}])],
        num_folds=folds)
    pred = label.transform_with(sel, checked)
    return label, pred


def _rss_bytes():
    try:
        with open("/proc/self/statm") as fh:
            return int(fh.read().split()[1]) * os.sysconf("SC_PAGE_SIZE")
    except (OSError, ValueError, IndexError):  # pragma: no cover — non-linux
        return None


needs_proc = pytest.mark.skipif(_rss_bytes() is None,
                                reason="needs /proc/self/statm")


class TestChunkedStore:
    def test_roundtrip_and_chunk_local_take(self):
        ds = _fixture(1111)
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=256)
        assert cds.n_rows == 1111 and cds.n_chunks == 5
        # full materialize round-trips bitwise (masks, objects, metadata)
        back = cds.materialize()
        for name in ds.names:
            np.testing.assert_array_equal(back[name].data, ds[name].data)
            if ds[name].mask is not None:
                np.testing.assert_array_equal(back[name].mask, ds[name].mask)
        # chunk-local gather == plain fancy indexing, any order/duplicates
        rng = np.random.default_rng(0)
        idx = rng.integers(-1111, 1111, size=400)
        got = cds.take(idx)
        want = ds.take(idx % 1111)
        for name in ds.names:
            np.testing.assert_array_equal(got[name].data, want[name].data)
        # empty take
        assert cds.take(np.zeros(0, np.intp)).n_rows == 0
        with pytest.raises(IndexError):
            cds["num0"].take(np.array([1111]))

    def test_select_split_and_resident_columns(self):
        ds = _fixture(600)
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=128)
        sub = cds.select(["num0", "label"])
        assert sub.names == ["num0", "label"]
        tr, te = cds.split(0.25, seed=3)
        tr2, te2 = ds.split(0.25, seed=3)
        np.testing.assert_array_equal(tr["num1"].data, tr2["num1"].data)
        np.testing.assert_array_equal(te["cat0"].data, te2["cat0"].data)
        # a resident column rides along and slices per chunk
        extra = Column(Real, np.arange(600, dtype=np.float64),
                       np.ones(600, np.bool_))
        cds2 = cds.with_resident_column("extra", extra)
        c1 = cds2.chunk(1)
        np.testing.assert_array_equal(c1["extra"].data,
                                      np.arange(128, 256, dtype=np.float64))

    def test_writer_streaming_and_schema_enforcement(self):
        ds = _fixture(500)
        w = ChunkedDatasetWriter(chunk_rows=200)
        for lo in range(0, 500, 200):
            w.append(ds.take(np.arange(lo, min(lo + 200, 500))))
        cds = w.finish()
        np.testing.assert_array_equal(cds.materialize()["num2"].data,
                                      ds["num2"].data)
        w2 = ChunkedDatasetWriter(chunk_rows=200)
        w2.append(ds.take(np.arange(100)))  # partial first chunk
        with pytest.raises(ValueError, match="final appended chunk"):
            w2.append(ds.take(np.arange(100, 200)))

    def test_maybe_chunk_budget(self, monkeypatch):
        ds = _fixture(400)
        assert maybe_chunk(ds) is ds  # no budget: fast path
        assert maybe_chunk(ds, budget=dataset_nbytes(ds) + 1) is ds
        spilled = maybe_chunk(ds, budget=1024)
        assert isinstance(spilled, ChunkedDataset)
        monkeypatch.setenv("TMOG_HOST_BUDGET", "1024")
        assert isinstance(maybe_chunk(ds), ChunkedDataset)
        # a malformed budget fails CLOSED (raises), never silently disarms
        monkeypatch.setenv("TMOG_HOST_BUDGET", "16MB")
        with pytest.raises(ValueError, match="TMOG_HOST_BUDGET"):
            maybe_chunk(ds)

    def test_open_restores_store_and_data_token(self, tmp_path):
        ds = _fixture(500)
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=128,
                                          spill_dir=str(tmp_path / "s"))
        assert cds.data_token  # every ingestion stamps an identity
        back = ChunkedDataset.open(str(tmp_path / "s"))
        assert back.data_token == cds.data_token
        assert back.n_rows == 500 and back.chunk_rows == 128
        np.testing.assert_array_equal(back.materialize()["num1"].data,
                                      ds["num1"].data)


class TestChunkLocalGatherRss:
    @needs_proc
    def test_memmap_take_does_not_materialize_column(self, tmp_path):
        """Satellite pin: fancy-indexing a memory-mapped column reads slabs
        in ascending order — peak RSS stays far under the column's size."""
        n = 6_000_000  # 48 MB of float64
        path = tmp_path / "big.npy"
        np.save(path, np.arange(n, dtype=np.float64))
        mm = np.load(path, mmap_mode="r")
        col = Column(Real, mm, None)
        rng = np.random.default_rng(1)
        idx = rng.integers(0, n, size=2_000)
        before = _rss_bytes()
        out = col.take(idx)
        delta = _rss_bytes() - before
        np.testing.assert_array_equal(out.data, np.asarray(idx, np.float64))
        assert delta < 24 * 1024 * 1024, \
            f"take materialized the column: RSS grew {delta} bytes"

    @needs_proc
    def test_spilled_column_take_rss_is_chunk_bounded(self, tmp_path):
        """ChunkedColumn.take reads one chunk at a time: peak RSS on a
        spilled column is ~one chunk + the output, never the column."""
        chunk_rows = 262_144  # 2 MB float64 chunks
        n = chunk_rows * 24   # 48 MB column
        store = ChunkStore(str(tmp_path / "store"))
        from transmogrifai_tpu.data.chunked import ColumnChunkWriter

        w = ColumnChunkWriter(store, "big", chunk_rows)
        for ci in range(24):
            lo = ci * chunk_rows
            w.write(ci, Column(Real, np.arange(lo, lo + chunk_rows,
                                               dtype=np.float64), None))
        col = w.finish()
        rng = np.random.default_rng(2)
        idx = rng.integers(0, n, size=2_000)
        before = _rss_bytes()
        out = col.take(idx)
        delta = _rss_bytes() - before
        np.testing.assert_array_equal(out.data, np.asarray(idx, np.float64))
        assert delta < 24 * 1024 * 1024, \
            f"spilled take held more than ~a chunk: RSS grew {delta} bytes"

    def test_gather_rows_matches_fancy_index(self, tmp_path):
        np.save(tmp_path / "a.npy",
                np.arange(40_000, dtype=np.float32).reshape(20_000, 2))
        mm = np.load(tmp_path / "a.npy", mmap_mode="r")
        rng = np.random.default_rng(3)
        for idx in (rng.integers(-20_000, 20_000, size=777),
                    np.zeros(0, np.intp),
                    rng.random(20_000) > 0.7):
            np.testing.assert_array_equal(_gather_rows(mm, np.asarray(idx)),
                                          np.asarray(mm)[np.asarray(idx)])
        # out-of-range raises like the plain-array path (no silent wrap)
        for bad in (np.array([-20_005]), np.array([20_000])):
            with pytest.raises(IndexError):
                _gather_rows(mm, bad)


class TestPrefetch:
    def test_overlap_and_order(self):
        import time

        def loader(ci):
            time.sleep(0.002)
            return ci * 10

        stats = PrefetchStats()
        got = []
        with ChunkPrefetcher(loader, 8, stats=stats) as it:
            for ci, item in it:
                time.sleep(0.004)  # consumer slower than loader
                got.append((ci, item))
        assert got == [(i, i * 10) for i in range(8)]
        assert stats.chunks == 8
        # loads hidden behind the consumer: overlap well above the gate
        assert stats.overlap_fraction > 0.5, stats.to_dict()

    def test_loader_error_propagates_at_position(self):
        def loader(ci):
            if ci == 3:
                raise RuntimeError("disk gone")
            return ci

        seen = []
        with pytest.raises(RuntimeError, match="disk gone"):
            with ChunkPrefetcher(loader, 8) as it:
                for ci, _item in it:
                    seen.append(ci)
        assert seen == [0, 1, 2]

    def test_early_close_stops_worker(self):
        it = ChunkPrefetcher(lambda ci: ci, 1000, depth=2)
        next(it)
        it.close()
        assert list(it) == []


class TestChunkedFitParity:
    def test_train_score_evaluate_bitwise(self):
        ds = _fixture(2000)
        l1, p1 = _features(with_selector=True)
        m1 = (Workflow().set_input_dataset(ds)
              .set_result_features(l1, p1)).train()
        l2, p2 = _features(with_selector=True)
        # a budget one byte under the table guarantees the spill (the fit
        # sets — estimator inputs only — are far smaller, so no TM607)
        m2 = (Workflow().set_input_dataset(ds)
              .set_result_features(l2, p2)).train(
                  host_budget=dataset_nbytes(ds) - 1)
        # same winner, bitwise-equal CV metric values
        assert m1.summary().best_model_name == m2.summary().best_model_name
        v1 = [tuple(r.metric_values) for r in m1.summary().validation_results]
        v2 = [tuple(r.metric_values) for r in m2.summary().validation_results]
        assert v1 == v2
        # bitwise-equal evaluation through the chunked score path
        ev = Evaluators.binary_classification()
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=512)
        assert m1.evaluate(ev, ds) == m2.evaluate(ev, cds)
        # chunked score materializes to the same prediction block
        s1 = m1.score(ds, keep_intermediate=True)
        s2 = m2.score(cds, keep_intermediate=True)
        c1, c2 = s1[p1.name], s2[p2.name]
        if hasattr(c2, "materialize"):
            c2 = c2.materialize()
        np.testing.assert_array_equal(c1.data, c2.data)

    def test_workflow_cv_parity(self):
        ds = _fixture(1500, seed=5)
        l1, p1 = _features(with_selector=True)
        m1 = (Workflow().with_workflow_cv().set_input_dataset(ds)
              .set_result_features(l1, p1)).train()
        l2, p2 = _features(with_selector=True)
        m2 = (Workflow().with_workflow_cv().set_input_dataset(ds)
              .set_result_features(l2, p2)).train(
                  host_budget=dataset_nbytes(ds) - 1)
        v1 = [tuple(r.metric_values) for r in m1.summary().validation_results]
        v2 = [tuple(r.metric_values) for r in m2.summary().validation_results]
        assert v1 == v2

    def test_transform_parity_including_padded_tail(self):
        ds = _fixture(2000)
        label, checked = _features()
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, checked)).train()
        ref = transform_dag(ds, m.result_features, m.fitted)
        # 512-row chunks: 3 full tiles + one padded 464-row tail
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=512)
        out = transform_dag(cds, m.result_features, m.fitted)
        np.testing.assert_array_equal(ref[checked.name].data,
                                      out[checked.name].materialize().data)

    def test_two_epochs_over_one_table_do_not_alias(self):
        """Two epochs with DIFFERENT fitted stages over the same chunked
        table must not clobber each other's spill files: epoch outputs are
        namespaced by runner content."""
        ds = _fixture(700, seed=41)
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=256)
        label1, checked1 = _features()
        m1 = (Workflow().set_input_dataset(ds.take(np.arange(400)))
              .set_result_features(label1, checked1)).train()
        label2, checked2 = _features()
        m2 = (Workflow().set_input_dataset(ds.take(np.arange(400, 700)))
              .set_result_features(label2, checked2)).train()
        out1 = transform_dag(cds, m1.result_features, m1.fitted)
        v1_before = out1[checked1.name].materialize().data.copy()
        # second epoch over the SAME table with different fitted content
        transform_dag(cds, m2.result_features, m2.fitted)
        np.testing.assert_array_equal(
            out1[checked1.name].materialize().data, v1_before,
            err_msg="a second epoch clobbered the first epoch's spill files")

    def test_fused_false_argument_forces_host_path(self):
        """transform_dag(cds, ..., fused=False) must honor the flag on the
        chunked path (not only the env var): bitwise parity at zero use of
        the fused planner's executables."""
        ds = _fixture(600, seed=13)
        label, checked = _features()
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, checked)).train()
        ref = transform_dag(ds, m.result_features, m.fitted, fused=False)
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=256)
        hits0 = sum(s.hits for s in program_cache_entries().values())
        out = transform_dag(cds, m.result_features, m.fitted, fused=False)
        assert sum(s.hits for s in program_cache_entries().values()) == hits0
        np.testing.assert_array_equal(ref[checked.name].data,
                                      out[checked.name].materialize().data)

    def test_interpreted_fallback_parity(self, monkeypatch):
        """TMOG_FUSED_TRANSFORM=0: the chunked epoch runs the per-stage host
        loop per chunk and still matches bitwise."""
        monkeypatch.setenv("TMOG_FUSED_TRANSFORM", "0")
        ds = _fixture(900, seed=9)
        label, checked = _features()
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, checked)).train()
        ref = transform_dag(ds, m.result_features, m.fitted, fused=False)
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=256)
        out = transform_dag(cds, m.result_features, m.fitted)
        np.testing.assert_array_equal(ref[checked.name].data,
                                      out[checked.name].materialize().data)


class TestZeroCompileAcrossChunks:
    def test_chunked_epoch_reuses_the_in_memory_executable(self):
        """Acceptance: the chunked path must not fork the program surface —
        after an in-memory dispatch at the chunk-tile shape, a whole chunked
        epoch performs ZERO backend compiles and adds ZERO executable-cache
        keys (cache keys unchanged), one cache hit per chunk."""
        ds = _fixture(2000)
        label, checked = _features()
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, checked)).train()
        transform_dag(ds.take(np.arange(512)), m.result_features, m.fitted)
        before = set(program_cache_entries())
        hits0 = sum(s.hits for s in program_cache_entries().values())
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=512)
        with measure_compiles() as c:
            transform_dag(cds, m.result_features, m.fitted)
        assert c.backend_compiles == 0, \
            f"chunk boundary recompiled {c.backend_compiles} programs"
        entries = program_cache_entries()
        assert set(entries) == before, "chunking forked the executable cache"
        assert sum(s.hits for s in entries.values()) - hits0 == cds.n_chunks


    def test_streamed_table_epoch_spills_every_chunk_at_zero_compiles(self):
        """A table streamed into the chunk store and never resident whole:
        after one warm dispatch at the chunk tile, the chunked epoch visits
        every chunk once, spills the vector column out-of-core, and compiles
        nothing across the chunk boundaries (padded tail included)."""
        from transmogrifai_tpu.workflow.dag import compute_dag

        n, chunk_rows = 1900, 512
        ds = _fixture(n)
        w = ChunkedDatasetWriter(chunk_rows=chunk_rows)
        for lo in range(0, n, chunk_rows):
            w.append(ds.take(np.arange(lo, min(lo + chunk_rows, n))))
        cds = w.finish()
        assert cds.n_chunks == 4 and cds.n_rows == n
        label, checked = _features()
        head = cds.take(np.arange(chunk_rows))
        m = (Workflow().set_input_dataset(head)
             .set_result_features(label, checked)).train()
        # warm the chunk-tile executable
        transform_dag(head, m.result_features, m.fitted)
        runners = [m.fitted.get(s.uid, s)
                   for layer in compute_dag(m.result_features)
                   for s in layer]
        stats = EpochStats()
        with measure_compiles() as c:
            out = chunked_transform_epoch(cds, runners, stats=stats)
        assert c.backend_compiles == 0
        assert stats.chunks_total == stats.chunks_processed == cds.n_chunks
        assert stats.chunks_skipped == 0
        assert stats.prefetch["chunks"] == cds.n_chunks
        assert checked.name in out.spilled_names
        assert stats.bytes_spilled > 0
        assert out.n_rows == n


class TestCrashAndResume:
    def _prep(self, tmp_path, n=1500):
        ds = _fixture(n, seed=21)
        label, checked = _features()
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, checked)).train()
        from transmogrifai_tpu.workflow.dag import compute_dag

        runners = [m.fitted.get(s.uid, s)
                   for layer in compute_dag(m.result_features)
                   for s in layer]
        cds = ChunkedDataset.from_dataset(
            ds, chunk_rows=256, spill_dir=str(tmp_path / "store"))
        return ds, m, runners, cds, checked

    def test_epoch_resumes_from_committed_chunk(self, tmp_path):
        ds, m, runners, cds, checked = self._prep(tmp_path)
        ckpt = OffsetCheckpoint(str(tmp_path / "offsets.json"))

        # crash mid-epoch: the spill store dies on the 3rd chunk's writes
        store = cds.store
        real_write = store.write_chunk

        def dying_write(name, ci, data, mask):
            # epoch output files are namespaced "<column>@<fingerprint>"
            if ci >= 2 and name.startswith(checked.name):
                raise OSError("simulated crash during spill")
            return real_write(name, ci, data, mask)

        store.write_chunk = dying_write
        with pytest.raises(OSError, match="simulated crash"):
            chunked_transform_epoch(cds, runners, checkpoint=ckpt)
        store.write_chunk = real_write

        # resume: committed chunks are skipped, outputs complete + bitwise
        stats = EpochStats()
        with measure_compiles() as c:
            out = chunked_transform_epoch(cds, runners, checkpoint=ckpt,
                                          stats=stats)
        assert stats.chunks_skipped == 2, stats
        assert stats.chunks_processed == cds.n_chunks - 2
        assert c.backend_compiles == 0
        ref = transform_dag(ds, m.result_features, m.fitted)
        np.testing.assert_array_equal(ref[checked.name].data,
                                      out[checked.name].materialize().data)

    def test_reingest_invalidates_the_resume_key(self, tmp_path):
        """A re-ingest into the SAME spill dir stamps a new data token, so
        the old run's committed offsets (and its stale output chunks) are
        never resumed over — the whole epoch recomputes."""
        ds, m, runners, cds, checked = self._prep(tmp_path, n=700)
        ckpt = OffsetCheckpoint(str(tmp_path / "offsets.json"))
        chunked_transform_epoch(cds, runners, checkpoint=ckpt)
        # same rows, same dir, NEW ingest (different data identity)
        cds2 = ChunkedDataset.from_dataset(
            ds, chunk_rows=256, spill_dir=str(tmp_path / "store"))
        assert cds2.data_token != cds.data_token
        stats = EpochStats()
        chunked_transform_epoch(cds2, runners, checkpoint=ckpt, stats=stats)
        assert stats.chunks_skipped == 0
        assert stats.chunks_processed == cds2.n_chunks

    def test_missing_spill_files_rewind_the_offset(self, tmp_path):
        """A checkpoint ahead of the store (wiped spill dir) must rewind to
        the first chunk whose files are actually present, not trust the
        offset blindly."""
        import glob

        ds, m, runners, cds, checked = self._prep(tmp_path, n=700)
        ckpt = OffsetCheckpoint(str(tmp_path / "offsets.json"))
        out1 = chunked_transform_epoch(cds, runners, checkpoint=ckpt)
        # wipe one committed output chunk file from disk (epoch outputs are
        # namespaced "<column>@<fingerprint>"; the store slug maps '@'->'_')
        hits = glob.glob(os.path.join(
            cds.store.root, cds.store._slug(checked.name) + "_*",
            "c000001.npy"))
        assert hits, "expected a namespaced spill file for chunk 1"
        os.remove(hits[0])
        stats = EpochStats()
        out2 = chunked_transform_epoch(cds, runners, checkpoint=ckpt,
                                       stats=stats)
        assert stats.chunks_skipped <= 1
        np.testing.assert_array_equal(
            out1[checked.name].materialize().data,
            out2[checked.name].materialize().data)

    def test_sigkill_mid_epoch_resumes_in_fresh_process(self, tmp_path):
        """PR 20 satellite: a REAL SIGKILL (no atexit, no finally, no
        in-process monkeypatch) lands at the start of chunk 2; a FRESH
        process reopens the spill store (same data token), skips the two
        committed chunks, and completes bitwise-equal to the in-memory
        reference."""
        import json
        import signal
        import subprocess
        import sys
        import textwrap

        script = tmp_path / "chunk_e2e.py"
        script.write_text(textwrap.dedent("""\
            import json, os, signal, sys

            import numpy as np

            mode, spill, offsets, out = sys.argv[1:5]

            from test_chunked_ingest import _features, _fixture
            from transmogrifai_tpu import Workflow
            from transmogrifai_tpu.data.chunked import ChunkedDataset
            from transmogrifai_tpu.readers import OffsetCheckpoint
            from transmogrifai_tpu.workflow.dag import compute_dag
            from transmogrifai_tpu.workflow.fit import transform_dag
            from transmogrifai_tpu.workflow.ooc import (
                EpochStats, chunked_transform_epoch)

            ds = _fixture(700, seed=21)
            label, checked = _features()
            m = (Workflow().set_input_dataset(ds)
                 .set_result_features(label, checked)).train()
            runners = [m.fitted.get(s.uid, s)
                       for layer in compute_dag(m.result_features)
                       for s in layer]
            ckpt = OffsetCheckpoint(offsets)

            if mode == "kill":
                from transmogrifai_tpu.serve.faults import FaultHarness

                cds = ChunkedDataset.from_dataset(
                    ds, chunk_rows=256, spill_dir=spill)
                h = FaultHarness()
                # chunks 0 and 1 process + commit; the kill fires at the
                # ingest_chunk fault point as chunk 2 begins
                h.script("ingest_chunk", [None, None, lambda ctx: os.kill(
                    os.getpid(), signal.SIGKILL)])
                with h:
                    chunked_transform_epoch(cds, runners, checkpoint=ckpt)
                raise SystemExit("unreachable: SIGKILL should have landed")

            cds = ChunkedDataset.open(spill)  # same data token -> resumable
            stats = EpochStats()
            out_ds = chunked_transform_epoch(cds, runners, checkpoint=ckpt,
                                             stats=stats)
            ref = transform_dag(ds, m.result_features, m.fitted)
            bitwise = bool(np.array_equal(
                ref[checked.name].data,
                out_ds[checked.name].materialize().data))
            with open(out, "w") as fh:
                json.dump({"skipped": stats.chunks_skipped,
                           "processed": stats.chunks_processed,
                           "n_chunks": cds.n_chunks,
                           "bitwise": bitwise}, fh)
        """))
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "PYTHONPATH": os.pathsep.join(
                   [repo, os.path.join(repo, "tests"),
                    os.environ.get("PYTHONPATH", "")])}
        spill = tmp_path / "store"
        offsets = tmp_path / "offsets.json"
        out = tmp_path / "resume.json"

        killed = subprocess.run(
            [sys.executable, str(script), "kill", str(spill), str(offsets),
             str(out)],
            capture_output=True, text=True, env=env, timeout=300)
        assert killed.returncode == -signal.SIGKILL, killed.stderr
        # the fsync'd offset commit survived the kill: chunks 0 and 1 landed
        assert os.path.exists(offsets)

        resumed = subprocess.run(
            [sys.executable, str(script), "run", str(spill), str(offsets),
             str(out)],
            capture_output=True, text=True, env=env, timeout=300)
        assert resumed.returncode == 0, resumed.stderr
        report = json.loads(out.read_text())
        assert report["skipped"] == 2, report
        assert report["processed"] == report["n_chunks"] - 2
        assert report["bitwise"] is True


class TestHostResidencyGate:
    def test_static_tm607_over_and_under_budget(self):
        ds = _fixture(1200)
        label, pred = _features(with_selector=True)
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, pred)).train()
        # generous budget: clean, report attached
        rep = m.validate(host_budget=1 << 30, rows=1_000)
        assert not [d for d in rep if d.code in ("TM606", "TM607")]
        assert rep.host_residency is not None
        assert rep.host_residency.peak_chunked_bytes > 0
        assert rep.host_residency.fit_sets  # estimator working sets listed
        # tiny budget at huge rows: TM607 fires (fail closed)
        rep2 = m.validate(host_budget=1_000_000, rows=50_000_000)
        assert [d for d in rep2 if d.code == "TM607"], rep2.pretty()
        # armed without a row count: TM606 (cannot evaluate -> fail closed)
        rep3 = m.validate(host_budget=1_000_000)
        assert [d for d in rep3 if d.code == "TM606"]

    def test_unfitted_workflow_fails_closed(self):
        label, pred = _features(with_selector=True)
        wf = Workflow().set_result_features(label, pred)
        rep = wf.validate(host_budget=1_000_000, rows=10_000)
        assert [d for d in rep if d.code == "TM606"]

    def test_runtime_gate_refuses_oversized_fit_set(self):
        from transmogrifai_tpu.checkers.diagnostics import OpCheckError

        ds = _fixture(1200)
        label, pred = _features(with_selector=True)
        wf = (Workflow().set_input_dataset(ds)
              .set_result_features(label, pred))
        with pytest.raises(OpCheckError) as ei:
            wf.train(host_budget=4_096)  # forces chunking AND refuses fits
        assert any(d.code == "TM607" for d in ei.value.report)

    def test_workflow_cv_materialization_is_gated_too(self):
        """The CV fold loop's label/vector materialization must hit the
        same TM607 gate as estimator fits — not assemble first, gate later."""
        from transmogrifai_tpu.checkers.diagnostics import OpCheckError

        ds = _fixture(1200)
        label, pred = _features(with_selector=True)
        wf = (Workflow().with_workflow_cv().set_input_dataset(ds)
              .set_result_features(label, pred))
        with pytest.raises(OpCheckError) as ei:
            wf.train(host_budget=4_096)
        assert any(d.code == "TM607" for d in ei.value.report)

    def test_cli_lint_host_budget(self):
        from transmogrifai_tpu.cli.gen import main

        with pytest.raises(SystemExit):
            # --host-budget without --rows refuses (fail closed)
            main(["lint", "--workflow", "tests.test_chunked_ingest:_wf",
                  "--host-budget", "1000000"])


def _wf():
    """cli lint --workflow target used by TestHostResidencyGate."""
    label, pred = _features(with_selector=True)
    return Workflow().set_result_features(label, pred)


def _nested_x(r):
    """Module-level custom extract (importable, for serde) used by the
    score_dataset refusal test."""
    return r["payload"]["x"]


class TestProgramSurfaceUnforked:
    def test_ir_corpus_chunk_family_dedups_bit_identical(self):
        """Satellite pin: the chunked-epoch fused-prefix family in the IR
        golden corpus carries the SAME canonical-IR fingerprint as the
        in-memory transform_prefix family — chunking does not fork the
        program surface."""
        import json

        from transmogrifai_tpu.checkers.irsnap import (build_corpus,
                                                       default_goldens_dir)

        with open(os.path.join(default_goldens_dir(), "index.json")) as fh:
            entries = json.load(fh)["entries"]
        base = entries["workflow.plan.transform_prefix"]
        chunk = entries["workflow.plan.transform_prefix@chunk"]
        assert chunk["irFingerprint"] == base["irFingerprint"]
        # and a FRESH build agrees (not just the recorded goldens)
        snaps, _skipped = build_corpus(families=["transform_prefix"])
        fresh = {k: s.ir_fingerprint for k, s in snaps.items()}
        assert fresh["workflow.plan.transform_prefix@chunk"] == \
            fresh["workflow.plan.transform_prefix"]


class TestChunkedReaderAndServe:
    def test_reader_generate_chunked_matches_generate_dataset(self):
        from transmogrifai_tpu.readers.base import CustomReader

        rng = np.random.default_rng(7)
        records = [{"num0": float(rng.normal()), "label": float(i % 2),
                    "cat0": f"lv{i % 5}"} for i in range(700)]
        label = FeatureBuilder.of("label", RealNN).extract_field() \
            .as_response()
        num = FeatureBuilder.of("num0", Real).extract_field().as_predictor()
        cat = FeatureBuilder.of("cat0", PickList).extract_field() \
            .as_predictor()
        raw = [label, num, cat]
        reader = CustomReader(lambda: iter(records))
        ref = reader.generate_dataset(raw)
        cds = CustomReader(lambda: iter(records)).generate_chunked(
            raw, chunk_rows=256)
        assert isinstance(cds, ChunkedDataset) and cds.n_chunks == 3
        got = cds.materialize()
        for f in raw:
            np.testing.assert_array_equal(got[f.name].data, ref[f.name].data)

    def test_compiled_plan_score_dataset_chunked(self):
        ds = _fixture(800, seed=31)
        label, pred = _features(with_selector=True)
        m = (Workflow().set_input_dataset(ds)
             .set_result_features(label, pred)).train()
        plan = m.serving_plan(min_bucket=8, max_bucket=256, strict=False)
        records = plan._records_of(ds)
        ref = plan.score(records)
        cds = ChunkedDataset.from_dataset(ds, chunk_rows=256)
        got = plan.score_dataset(cds)
        assert got == ref
        assert plan.last_prefetch["chunks"] == cds.n_chunks
        # streaming sink: bounded output residency, same rows, count return
        sunk = []
        n = plan.score_dataset(cds, sink=sunk.extend)
        assert n == len(ref) and sunk == ref

    def test_score_dataset_refuses_custom_extracts(self):
        """A custom extract fn's record shape cannot be rebuilt from
        columns — dataset scoring must refuse loudly, not re-run the lambda
        over the wrong dict."""
        rng = np.random.default_rng(3)
        n = 300
        records = [{"payload": {"x": float(rng.normal())},
                    "label": float(i % 2)} for i, _ in enumerate(range(n))]
        label = FeatureBuilder.of("label", RealNN).extract_field() \
            .as_response()
        x = FeatureBuilder.of("x", Real).extract(
            _nested_x).as_predictor()
        checked = label.sanity_check(transmogrify([x]))
        sel = BinaryClassificationModelSelector.with_cross_validation(
            models=[(LogisticRegression(), [{"reg_param": 0.1}])],
            num_folds=2)
        pred = label.transform_with(sel, checked)
        from transmogrifai_tpu.readers.base import CustomReader, \
            rows_to_dataset

        m = (Workflow().set_reader(CustomReader(lambda: iter(records)))
             .set_result_features(label, pred)).train()
        plan = m.serving_plan(min_bucket=8, max_bucket=256, strict=False)
        assert plan.score(records[:4])  # raw-record path still works
        ds = rows_to_dataset(records, [label, x])
        with pytest.raises(ValueError, match="custom extract"):
            plan.score_dataset(ds)

    def test_aggregate_reader_refuses_generate_chunked(self):
        from transmogrifai_tpu.readers.base import (AggregateReader,
                                                    CustomReader)

        reader = AggregateReader(CustomReader(lambda: iter([])),
                                 key_fn=lambda r: "k",
                                 time_fn=lambda r: 0)
        with pytest.raises(NotImplementedError, match="per-event"):
            reader.generate_chunked([])
