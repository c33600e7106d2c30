"""99th percentile of the lockstep iteration time over the traced run's
window, each iteration waited for on the host (the two in which the
profiler started or stopped left out). Shows host stalls between
iterations. Reported where the run timed iterations. Moves
``consume_tps``."""

import statistics

NAME, UNIT, LAYER, MOVES = "lockstep.iter_p99_ms", "ms", "Lockstep host loop", "consume_tps"


def read(record: dict):
    ms = record.get("iter_ms")
    if not ms or len(ms) < 100:
        return None
    return statistics.quantiles(ms, n=100)[98]
