"""Share of the traced window in which no operation ran on the device: 1
minus the union of device-op intervals over the ``bench_window`` span,
averaged over the chips used (``bench/trace_reduce.py``). Moves
``consume_tps``."""

NAME, UNIT, LAYER, MOVES = "device_idle_share", "%", "Device", "consume_tps"


def read(record: dict):
    red = record.get("trace")
    if not red or not red.get("window_s") or not red.get("busy_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
