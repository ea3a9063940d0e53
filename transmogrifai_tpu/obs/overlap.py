"""Shared producer/consumer overlap accounting (ingest + serve pipelines).

Reference: the Reader layer's streaming ingestion (DataReader.scala
generateDataFrame :173-188) leans on Spark to overlap IO with execution;
this repo makes the overlap explicit in two places — the chunk prefetcher
(readers/prefetch.py, PR 13) and the pipelined serving flush loop
(serve/pipeline.py, PR 18) — and both report the SAME metric with the SAME
locking discipline, which this one class provides:

- ``load_seconds``  — total producer time spent staging work,
- ``wait_seconds``  — total consumer time blocked on the hand-off buffer,
- ``overlap_fraction`` — the share of producer time hidden behind the
  consumer's own work (``1 - wait/load``); the chunk prefetcher and the
  serve pipeline both report it.

Two threads read-modify-write these fields (TM312) and the overlap ratio
reads two of them together (TM314: a torn read of ``wait`` against a newer
``load`` would fabricate a ratio no moment in time ever had) — so every
update goes through the one lock and the report paths (``to_dict``,
``overlap_fraction``) snapshot under the same lock.
"""

from __future__ import annotations

import threading


class OverlapStats:
    """Lock-disciplined counters of one producer/consumer pipeline run.

    ``chunks`` counts consumer hand-offs (ingest: chunks; serve: batches).
    The producer thread accumulates ``load_seconds`` while the consumer
    thread accumulates ``wait_seconds``/``stalls``/``chunks``, and the
    report paths may be read mid-run (the fleet console polls them)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.chunks = 0
        self.load_seconds = 0.0
        self.wait_seconds = 0.0
        self.stalls = 0

    def add_load(self, seconds: float) -> None:
        """Producer-side: one item's staging time."""
        with self._lock:
            self.load_seconds += seconds

    def add_wait(self, seconds: float, stalled: bool = False) -> None:
        """Consumer-side: one hand-off's buffer wait (+ stall count)."""
        with self._lock:
            self.wait_seconds += seconds
            if stalled:
                self.stalls += 1

    def add_chunk(self) -> None:
        with self._lock:
            self.chunks += 1

    def _overlap_locked(self) -> float:
        if self.load_seconds <= 0.0:
            return 1.0
        return max(0.0, min(1.0, 1.0 - self.wait_seconds / self.load_seconds))

    @property
    def overlap_fraction(self) -> float:
        """Fraction of total producer time hidden behind the consumer's
        work: 1.0 = every item was already staged when asked for; 0.0 =
        the consumer waited out every load (no overlap)."""
        with self._lock:
            return self._overlap_locked()

    def to_dict(self) -> dict:
        with self._lock:
            return {"chunks": self.chunks,
                    "load_seconds": round(self.load_seconds, 4),
                    "wait_seconds": round(self.wait_seconds, 4),
                    "stalls": self.stalls,
                    "overlap_fraction": round(self._overlap_locked(), 4)}
