"""Process-local metrics registry: counters, gauges, latency histograms.

The paper's pipeline lives or dies on balance — actors must out-generate
the learner, write-backs must keep eviction honest — and the four stats
dataclasses (``ServiceStats``/``SourceStats``/``GatewayStats``/
``InferenceStats``) only expose *counts* plus lossy 1-in-8-sampled latency
EMAs. This module is the measurement substrate under all of them: every
plane records into one shared :class:`MetricsRegistry`, and the dataclass
fields become derived views (see ``ServiceStats``'s ``*_us``), so nothing
downstream breaks while percentiles become available.

Design constraints, in order:

* **Lock-cheap on the hot path.** Counters and gauges take one
  uncontended per-instrument lock (tens of ns in CPython — far below the
  cost of the queue ops they sit next to); histograms additionally touch
  one bucket slot. Nothing allocates per record.
* **Fixed-bucket histograms.** Geometric buckets (factor ``2**0.25`` ≈
  1.19) spanning 1µs .. ~70min cover every latency this system produces
  with ≤ ~19% relative quantization error — percentiles interpolate
  inside the bucket, so p50/p95/p99 are honest to within one bucket
  ratio (property-tested against ``numpy.quantile``).
* **Create-or-get instruments.** ``registry.counter(name)`` etc. return
  the existing instrument for a name, so independent components (shards,
  connection handlers) share instruments by naming convention
  (``shard0/add_us``, ``gateway/blocks_in``) without passing handles.

``snapshot()`` is the export surface: a plain-dict view of every
instrument, cheap enough for an interval flush thread
(:mod:`repro.obs.sink`) to call once a second.
"""

from __future__ import annotations

import math
import threading

# Geometric bucket ladder: factor 2**0.25 from 1µs up. 128 buckets reach
# 2**(128/4) µs ≈ 4.3e9 µs ≈ 72 minutes — beyond any latency this system
# can produce while the run is still alive.
_BUCKET_FACTOR = 2.0 ** 0.25
_NUM_BUCKETS = 128
_LOG_FACTOR = math.log(_BUCKET_FACTOR)

# Bucket i spans [_FACTOR**i, _FACTOR**(i+1)); values below 1.0 clamp into
# bucket 0, values beyond the ladder clamp into the last bucket.
_BUCKET_EDGES = [_BUCKET_FACTOR ** i for i in range(_NUM_BUCKETS + 1)]


def bucket_index(value: float) -> int:
    """Bucket for ``value``; clamped to the fixed ladder."""
    if value < _BUCKET_FACTOR:
        return 0
    i = int(math.log(value) / _LOG_FACTOR)
    if i >= _NUM_BUCKETS:  # beyond the ladder: clamp before indexing edges
        return _NUM_BUCKETS - 1
    # float log can land one off the true bucket at edges; nudge.
    if value >= _BUCKET_EDGES[i + 1]:
        i += 1
    elif value < _BUCKET_EDGES[i]:
        i -= 1
    return min(max(i, 0), _NUM_BUCKETS - 1)


class Counter:
    """Monotone event count (blocks routed, starved polls, retries)."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0

    def inc(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class Gauge:
    """Last-write-wins level (queue depth, replay size, param version)."""

    __slots__ = ("name", "_lock", "_value")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._value = 0.0

    def set(self, v: float) -> None:
        with self._lock:
            self._value = float(v)

    @property
    def value(self) -> float:
        return self._value


class Histogram:
    """Fixed geometric-bucket latency histogram with interpolated
    percentiles. Values are microseconds by convention (any positive unit
    works — the ladder is relative)."""

    __slots__ = ("name", "_lock", "_counts", "_count", "_sum", "_min", "_max")

    def __init__(self, name: str):
        self.name = name
        self._lock = threading.Lock()
        self._counts = [0] * _NUM_BUCKETS
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = 0.0

    def record(self, value: float) -> None:
        i = bucket_index(value)
        with self._lock:
            self._counts[i] += 1
            self._count += 1
            self._sum += value
            if value < self._min:
                self._min = value
            if value > self._max:
                self._max = value

    @property
    def count(self) -> int:
        return self._count

    @property
    def sum(self) -> float:
        return self._sum

    @property
    def mean(self) -> float:
        return self._sum / self._count if self._count else 0.0

    def bucket_counts(self) -> list[int]:
        """A copy of the per-bucket counts (bucket ``i`` spans
        ``[2**(i/4), 2**((i+1)/4))``): subtract two copies for the
        histogram of what was recorded in between."""
        with self._lock:
            return list(self._counts)

    def percentile(self, q: float) -> float:
        """Interpolated q-th percentile (q in [0, 100]). Exact to within
        one bucket ratio (~19%): the true quantile lives in the bucket the
        cumulative count selects, and we interpolate the value linearly by
        rank position inside that bucket, clamped to the observed
        min/max so single-bucket histograms return honest values."""
        with self._lock:
            count = self._count
            if count == 0:
                return 0.0
            counts = list(self._counts)
            lo_seen, hi_seen = self._min, self._max
        rank = (q / 100.0) * (count - 1)  # numpy 'linear' convention
        cum = 0
        for i, c in enumerate(counts):
            if c == 0:
                continue
            if cum + c > rank:
                # rank falls inside bucket i: interpolate by position.
                frac = (rank - cum) / c
                lo = max(_BUCKET_EDGES[i], lo_seen)
                hi = min(_BUCKET_EDGES[i + 1], hi_seen)
                if hi < lo:
                    lo = hi = max(min(_BUCKET_EDGES[i + 1], hi_seen),
                                  min(_BUCKET_EDGES[i], lo_seen))
                return lo + frac * (hi - lo)
            cum += c
        return hi_seen  # q == 100 (or float dust): the observed max

    def summary(self) -> dict:
        """Plain-dict export: count/sum/mean plus p50/p95/p99."""
        return {"count": self._count, "sum": self._sum, "mean": self.mean,
                "p50": self.percentile(50.0), "p95": self.percentile(95.0),
                "p99": self.percentile(99.0)}


class MetricsRegistry:
    """Create-or-get instrument store; one per process (or per test)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._counters: dict[str, Counter] = {}
        self._gauges: dict[str, Gauge] = {}
        self._histograms: dict[str, Histogram] = {}

    def counter(self, name: str) -> Counter:
        with self._lock:
            inst = self._counters.get(name)
            if inst is None:
                inst = self._counters[name] = Counter(name)
            return inst

    def gauge(self, name: str) -> Gauge:
        with self._lock:
            inst = self._gauges.get(name)
            if inst is None:
                inst = self._gauges[name] = Gauge(name)
            return inst

    def histogram(self, name: str) -> Histogram:
        with self._lock:
            inst = self._histograms.get(name)
            if inst is None:
                inst = self._histograms[name] = Histogram(name)
            return inst

    def snapshot(self) -> dict:
        """One consistent-enough view of every instrument (instruments are
        individually locked; cross-instrument skew is bounded by the walk)."""
        with self._lock:
            counters = list(self._counters.values())
            gauges = list(self._gauges.values())
            hists = list(self._histograms.values())
        return {
            "counters": {c.name: c.value for c in counters},
            "gauges": {g.name: g.value for g in gauges},
            "histograms": {h.name: h.summary() for h in hists},
        }
