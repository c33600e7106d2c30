"""Where one traced run of a cell spends its device time and its threads'
time, read from the run's own profiler span.

    python3 bench/tools/layer_shares.py --workload <cell> --seed <n> --seconds 45 [--gaps 3]

It runs the cell as ``bench/run.py --trace 1`` does (``run.run_cell``,
the same window and the same ``bench_window`` span), and adds, outside the
window:

* ``device``: the span's device time per layer of the program
  (``bench/program_trace.py``: actor, replay, learner, other) and its idle
  time, each as a share of the span, in %; they sum to 100.
* ``host_spans``: for each runtime span name (``actor/*``, ``shard/*``,
  ``learner/*``, ``fabric/*``; the runtime's ``Tracer.span``s are
  annotations on the trace's host plane), how many fall in the span and
  their seconds inside it, summed over threads.
* ``waits`` (decoupled runtime): the registry read as the span opens and
  as it closes, through ``RuntimeHandles.telemetry``. The learner's share
  of the span waiting for a batch (``learner/batch_wait_us``) and the
  shard owners' mean share blocked on device results
  (``shardN/sync_wait_us``), in %.
* ``gaps`` (with ``--gaps N``): the N longest idle stretches of the device
  in the span, each with the host events over at least 5% of it and the
  garbage collections that overlap it.

It hooks the benchmark from outside (the trace's reduction, the profile
window's ticks and ``run_async``'s handles) and edits none of its files.
The last line of standard output is run_cell's result line with these
under ``layers``.
"""

from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]

from bench import harness, program_trace, run, trace_reduce  # noqa: E402

HOST_LAYERS = ("actor/", "shard/", "learner/", "fabric/")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--gaps", type=int, default=0)
    args = ap.parse_args(argv)
    result, lines = measure(args.workload, args.seed, args.seconds, args.gaps)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


def measure(workload: str, seed: int, seconds: float, gaps: int = 0,
            checkout: pathlib.Path = CHECKOUT, require_chip: bool = True):
    """One traced run of the cell: run_cell's (result, check lines), the
    result with the readings above under ``layers``."""
    out: dict = {}
    handles: list = []
    snaps: list = []
    pauses: list = []
    undo = _hook(out, handles, snaps, gaps, pauses)
    try:
        result, lines = run.run_cell(workload, seed, seconds, True, checkout=checkout,
                                     require_chip=require_chip)
    finally:
        undo()
        if _on_gc in gc.callbacks:
            gc.callbacks.remove(_on_gc)
    if len(snaps) == 2:
        out["waits"] = _waits(snaps)
    result["layers"] = out
    return result, lines


def _hook(out: dict, handles: list, snaps: list, gaps: int, pauses: list):
    """Install the three hooks, which fill ``out``, ``handles``, ``snaps``
    and ``pauses``; returns the function that takes them out again."""
    import repro.runtime
    reduce = trace_reduce.reduce
    run_async = repro.runtime.run_async
    tick = harness.ProfileWindow.tick

    def reduce_and_read(path, annotation="bench_window", top=10):
        if path and not out:
            out.update(_read_trace(path, annotation, gaps, pauses))
        return reduce(path, annotation, top)

    def run_async_keeping_handles(*a, on_handles=None, **k):
        def keep(h):
            handles.append(h)
            if on_handles is not None:
                on_handles(h)
        return run_async(*a, on_handles=keep, **k)

    def tick_and_snapshot(self, elapsed, mark=None):
        if self.state == "on" and elapsed >= self.offset + self.SECONDS:
            _snapshot(handles, snaps)
            if gaps:
                gc.callbacks.remove(_on_gc)
        changed = tick(self, elapsed, mark)
        if changed and self.state == "on":
            _snapshot(handles, snaps)
            if gaps:
                _on_gc.pauses = pauses
                gc.callbacks.append(_on_gc)
        return changed

    trace_reduce.reduce = reduce_and_read
    repro.runtime.run_async = run_async_keeping_handles
    harness.ProfileWindow.tick = tick_and_snapshot

    def undo():
        trace_reduce.reduce = reduce
        repro.runtime.run_async = run_async
        harness.ProfileWindow.tick = tick
    return undo


def _snapshot(handles: list, snaps: list) -> None:
    tel = getattr(handles[0], "telemetry", None) if handles else None
    if tel is not None:
        snaps.append((time.perf_counter(), tel.registry.snapshot()))


def _on_gc(phase, info):
    """Garbage collections inside the span, on the clock of host events."""
    if phase == "start":
        _on_gc.t = time.time_ns()
    elif getattr(_on_gc, "t", None) is not None:
        _on_gc.pauses.append((_on_gc.t, time.time_ns(), info.get("generation")))
        _on_gc.t = None


def _waits(snaps: list) -> dict:
    """Wait counters' change over the span, as shares of it, in %."""
    (t0, a), (t1, b) = snaps
    us = (t1 - t0) * 1e6
    before, after = a["counters"], b["counters"]
    delta = {k: v - before.get(k, 0) for k, v in after.items() if k.endswith("_wait_us")}
    shards = [v for k, v in delta.items() if k.startswith("shard")]
    out = {"span_s": t1 - t0}
    if "learner/batch_wait_us" in delta:
        out["learner_batch_wait"] = 100.0 * delta["learner/batch_wait_us"] / us
    if shards:
        out["owner_sync"] = 100.0 * sum(shards) / (len(shards) * us)
    return out


def _read_trace(path: str, annotation: str, gaps: int, pauses: list) -> dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window, host, ops = None, [], []
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if plane.name.startswith("/host:"):
                    if ev.name == annotation:
                        window = (s, e)
                    host.append((s, e, line.name, ev.name))
                elif plane.name.startswith("/device:") and line.name == "XLA Ops":
                    ops.append((s, e, ev.name.split(" = ")[0]))
    if window is None:
        return {}
    w0, w1 = window
    spans: dict = {}
    for s, e, _, name in host:
        d = min(e, w1) - max(s, w0)
        if d > 0 and name.startswith(HOST_LAYERS):
            row = spans.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += d / 1e9
    out = {"host_spans": spans}
    red = program_trace.reduce(path, annotation)
    if red is not None:
        w = red["window_s"]
        out["device"] = {k: 100.0 * v / w for k, v in red["layers"].items()}
        out["device"]["idle"] = 100.0 * (1.0 - red["busy_s"] / w)
        out.update(window_s=w, named_s=red["named_s"])
    if gaps and ops:
        env = pd.find_plane_with_name("Task Environment")
        t0 = dict(env.stats)["profile_start_time"] if env is not None else 0
        out["gaps"] = _idle_gaps(sorted(ops), host, w0, w1, gaps,
                                 [(a - t0, b - t0, g) for a, b, g in pauses])
    return out


def _idle_gaps(ops, host, w0, w1, n, pauses) -> list:
    """The ``n`` longest stretches of the span with no device op running."""
    idle, reach = [], w0
    for s, e, _ in ops:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        if s > reach:
            idle.append((reach, s))
        reach = max(reach, e)
    if w1 > reach:
        idle.append((reach, w1))
    idle.sort(key=lambda g: g[0] - g[1])
    out = []
    for g0, g1 in idle[:n]:
        over = sorted(((min(e, g1) - max(s, g0)) / 1e6, line, name, (e - s) / 1e6, (s - g0) / 1e6)
                      for s, e, line, name in host
                      if min(e, g1) - max(s, g0) > 0.05 * (g1 - g0) and name != "bench_window")
        out.append({"at_ms": (g0 - w0) / 1e6, "ms": (g1 - g0) / 1e6,
                    "before": [o[2] for o in ops if o[1] <= g0][-2:],
                    "after": [o[2] for o in ops if o[0] >= g1][:2],
                    "host": [{"overlap_ms": o, "line": ln, "name": nm, "ms": d, "start_ms": r}
                             for o, ln, nm, d, r in sorted(over, reverse=True)[:12]],
                    "gc": [{"start_ms": (a - g0) / 1e6, "ms": (b - a) / 1e6, "generation": g}
                           for a, b, g in pauses if a < g1 and b > g0]})
    return out


if __name__ == "__main__":
    sys.exit(main())
