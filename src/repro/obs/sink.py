"""JSONL export: interval-flushed metrics snapshots and trace spans.

One :class:`JsonlSink` owns a directory and two append-only files:

* ``metrics.jsonl`` — one registry snapshot per flush interval, each
  line ``{"ts": ..., "counters": {...}, "gauges": {...},
  "histograms": {name: {count, sum, mean, p50, p95, p99}}}``.
* ``spans.jsonl`` — every drained trace span, one JSON object per line
  (``stage``, ``trace_id``, ``parent``, ``start_ns``, ``end_ns``, extra
  fields; the clock is described in :mod:`repro.obs.trace`).

The flush thread is a daemon on a short interval; ``stop()`` performs a
final flush so short runs (tests, bench smokes) never lose the tail.
Files are line-buffered appends — a crashed run leaves valid JSONL up to
the last flush, which is exactly what ``repro.obs.report`` consumes.
"""

from __future__ import annotations

import json
import os
import threading
import time

from .metrics import MetricsRegistry
from .trace import Tracer

METRICS_FILE = "metrics.jsonl"
SPANS_FILE = "spans.jsonl"


class JsonlSink:
    """Interval flusher for one registry + tracer pair into a directory."""

    def __init__(self, directory: str, registry: MetricsRegistry,
                 tracer: Tracer | None = None, flush_s: float = 1.0):
        self.directory = directory
        self._registry = registry
        self._tracer = tracer
        self._flush_s = flush_s
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._lock = threading.Lock()  # serialize flush() vs stop()-flush
        os.makedirs(directory, exist_ok=True)
        self._metrics_path = os.path.join(directory, METRICS_FILE)
        self._spans_path = os.path.join(directory, SPANS_FILE)

    def start(self) -> "JsonlSink":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="obs-sink", daemon=True)
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout=5.0)
            self._thread = None
        self.flush()  # final flush catches everything after the last tick

    def flush(self) -> None:
        with self._lock:
            snap = self._registry.snapshot()
            snap["ts"] = time.time()
            with open(self._metrics_path, "a", encoding="utf-8") as f:
                f.write(json.dumps(snap) + "\n")
            if self._tracer is not None:
                spans = self._tracer.drain()
                if spans:
                    with open(self._spans_path, "a", encoding="utf-8") as f:
                        for span in spans:
                            f.write(json.dumps(span) + "\n")

    def _run(self) -> None:
        while not self._stop.wait(self._flush_s):
            try:
                self.flush()
            except OSError:
                # a full/vanished disk should degrade telemetry, not
                # kill the run; the next tick retries.
                pass
