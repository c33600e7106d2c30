"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time,
its top operations and its longest idle gaps.

* The window is the span of the host annotation ``bench_window`` (the
  benchmark's own, around the measured window).
* Device operations are the events of the device planes (``/device:...``)
  on their ``XLA Ops`` line, or, where a plane has no such line, on every
  line but the step and module lines. Busy time is the union of their
  intervals clipped to the window, averaged over the device planes.
* Top operations count leaf events only: an event that encloses others on
  its line (a ``while`` or ``conditional`` around its body) is left out of
  them, so no device time is counted twice; busy time is unaffected.
* Each operation is named by its short HLO name and its program:
  ``fusion.53 @ jit_step``, from the event's ``hlo_op`` and ``hlo_module``
  stats, or else from the event name and the module event that encloses it
  on the same plane.
* An idle gap is a stretch of the window with no device operation. It is
  named by the host event (any host thread, the window annotation and
  profiler bookkeeping left out) that overlaps it the most.
"""

from __future__ import annotations

import bisect
import collections

_SKIP_LINES = ("Steps", "XLA Modules", "Framework Ops", "Framework Name Scope",
               "Source code", "XLA TraceMe", "SparseCore")
_SKIP_HOST = ("bench_window", "ThreadpoolListener", "$profiler")


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _stats(ev) -> dict:
    try:
        return dict(ev.stats)
    except (TypeError, ValueError):
        return {}


def _short(name: str) -> str:
    return name.split(" = ")[0].split("(")[0].strip().lstrip("%")


def reduce(path: str, annotation: str = "bench_window", top: int = 10) -> dict | None:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    host_events = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                    if ev.name == annotation:
                        window = (s, e) if window is None else (min(window[0], s), max(window[1], e))
                    elif not ev.name.startswith(_SKIP_HOST):
                        host_events.append((s, e, ev.name))
        elif plane.name.startswith("/device:"):
            devices.append(plane)
    if window is None:
        return None
    w0, w1 = window
    busy_per_plane, ops, all_busy = [], collections.Counter(), []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_lines = [lines["XLA Ops"]] if "XLA Ops" in lines else [
            ln for ln in plane.lines if not ln.name.startswith(_SKIP_LINES)]
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in (lines["XLA Modules"].events if "XLA Modules" in lines else []))
        ivals = []
        for ln in op_lines:
            events = sorted(ln.events, key=lambda ev: (ev.start_ns, -ev.duration_ns))
            parent = _parents([(ev.start_ns, ev.start_ns + ev.duration_ns) for ev in events])
            for ev, is_parent in zip(events, parent):
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                ivals.append((s, e))
                if is_parent:  # a loop or branch: its body's ops are counted
                    continue
                st = _stats(ev)
                mod = st.get("hlo_module") or _enclosing(modules, ev.start_ns)
                ops[f"{_short(str(st.get('hlo_op') or ev.name))} @ {_short(str(mod))}"] += (e - s) / 1e9
        merged = _union(ivals)
        if ivals:
            busy_per_plane.append(sum(e - s for s, e in merged))
            all_busy.append(merged)
    if not busy_per_plane:
        return None
    busy_s = sum(busy_per_plane) / len(busy_per_plane) / 1e9
    gaps = []
    merged = all_busy[0]
    prev = w0
    for s, e in merged + [[w1, w1]]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    named = []
    for g0, g1 in gaps[:top]:
        best, best_ov = "no host event", 0
        for s, e, name in host_events:
            ov = min(e, g1) - max(s, g0)
            if ov > best_ov:
                best, best_ov = name, ov
        named.append([best, (g1 - g0) / 1e9])
    return {"busy_s": busy_s, "window_s": (w1 - w0) / 1e9,
            "top_ops": [[k, v] for k, v in ops.most_common(top)],
            "idle_gaps": named, "device_planes": len(busy_per_plane)}


def _parents(intervals) -> list[bool]:
    """For intervals of one line sorted by start (the longer first on a
    tie), which enclose the next one: on a line, events either nest (a
    ``while`` or ``conditional`` around its body's ops) or are disjoint."""
    return [i + 1 < len(intervals) and intervals[i + 1][0] < e
            for i, (_, e) in enumerate(intervals)]


def _enclosing(modules, t) -> str:
    i = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if i >= 0 and modules[i][0] <= t < modules[i][1]:
        return modules[i][2]
    return "?"
