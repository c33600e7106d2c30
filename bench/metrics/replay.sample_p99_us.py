"""99th percentile of the replay shards' sample latency over the window,
from the shards' ``sample_us`` histograms (every 8th sample op, waited for;
``runtime/service.py``). The histograms' bucket counts at the window's two
ends are subtracted and merged over shards; the percentile interpolates
inside the bucket, as ``repro.obs.metrics.Histogram`` does. Moves
``consume_tps``."""

NAME, UNIT, LAYER, MOVES = "replay.sample_p99_us", "us", "Replay shards", "consume_tps"

_FACTOR = 2.0 ** 0.25


def read(record: dict):
    start, end = record.get("shards_start"), record.get("shards_end")
    if not start or not end:
        return None
    counts = [0] * len(end[0]["sample_hist"])
    for s, e in zip(start, end):
        for i, (a, b) in enumerate(zip(s["sample_hist"], e["sample_hist"])):
            counts[i] += b - a
    n = sum(counts)
    if n == 0:
        return None
    rank = 0.99 * (n - 1)
    cum = 0
    for i, c in enumerate(counts):
        if c and cum + c > rank:
            lo, hi = _FACTOR ** i, _FACTOR ** (i + 1)
            return lo + (rank - cum) / c * (hi - lo)
        cum += c
    return None
