"""Run-report surface: ``python -m repro.obs.report <metrics-dir>``.

Renders the JSONL a :class:`repro.obs.sink.JsonlSink` wrote during a run
into a per-stage bottleneck table:

* **stage table** — for each pipeline stage (the runtime's span names,
  ``actor/rollout`` through ``shard/writeback``, plus the gateway and
  sample-source stages): span count, sustained rate over the observed
  window, and p50/p95/p99 of the stage's own duration
  (``end_ns - start_ns``).
* **inter-stage gaps** — for consecutive stages of the same trace id,
  the time from the first stage's end to the next one's start
  (actor add → shard add, learner write-back → shard write-back, ...):
  this is where a bottleneck shows up as queue time that no single
  stage's duration explains.
* **queue depths** — last-seen gauge values (shard add/sample queues,
  staged prefetch depth, replay size).
* **wait shares** — each ``*_wait_us`` counter (one thread's time
  blocked: ``learner/batch_wait_us``, each shard owner's
  ``shardN/sync_wait_us``) as a share of that thread's time between the
  first and the last snapshot.
* **stall counters** — starvation and backpressure totals (learner
  starved polls, actor add-blocked, gateway add retries).
* **recovery events** — the fault-tolerance plane's counters (actor
  restarts, transport reconnects, snapshots saved): a run that survived
  faults shows its scars here.

The tool reads only what the sink wrote — run it offline, long after
the run, on a copied directory.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

from .sink import METRICS_FILE, SPANS_FILE

# Canonical pipeline order; report rows render in this order with any
# unknown stages appended (future planes report in without edits here).
STAGE_ORDER = ["actor/param_refresh", "actor/rollout", "actor/add", "gateway",
               "shard/add", "sample", "learner/get_batch", "fabric/merge",
               "learner/step", "learner/write_back", "fabric/route_writeback",
               "shard/writeback", "shard/sync", "learner/publish"]

# Consecutive same-trace-id stage pairs whose gap (the first one's end to
# the second one's start) is queue time between planes. (A block's add id
# and a batch's sample id are different traces by design, so no pair
# crosses from ingest to consume.)
GAP_PAIRS = [("actor/rollout", "actor/add"), ("actor/add", "shard/add"),
             ("gateway", "shard/add"), ("sample", "learner/step"),
             ("learner/step", "learner/write_back"),
             ("learner/write_back", "shard/writeback")]

_STALL_TOKENS = ("starved", "backpressure", "blocked", "retries", "dropped")

# A counter of microseconds one thread spent blocked, reported as a share
# of that thread's time.
_WAIT_SUFFIX = "_wait_us"

# Counters whose names carry these tokens are recovery events: the
# fault-tolerance plane reporting restarts, reconnects, and snapshots.
_RECOVERY_TOKENS = ("restart", "reconnect", "snapshot", "proc_exits")


def _read_jsonl(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    out = []
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn tail line from a crashed run
    return out


def _percentile(values: list[float], q: float) -> float:
    """numpy-style 'linear' percentile on a raw sample, stdlib only."""
    if not values:
        return 0.0
    vals = sorted(values)
    rank = (q / 100.0) * (len(vals) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(vals) - 1)
    frac = rank - lo
    return vals[lo] + frac * (vals[hi] - vals[lo])


def load_report(directory: str) -> dict:
    """Aggregate a metrics dir into the report's data model."""
    metrics = _read_jsonl(os.path.join(directory, METRICS_FILE))
    spans = [s for s in _read_jsonl(os.path.join(directory, SPANS_FILE))
             if "start_ns" in s and "end_ns" in s]

    # --- stage table -----------------------------------------------------
    by_stage: dict[str, list[dict]] = {}
    for span in spans:
        by_stage.setdefault(span.get("stage", "?"), []).append(span)
    window_s = (max((max(s["end_ns"] for s in spans)
                     - min(s["start_ns"] for s in spans)) / 1e9, 1e-9)
                if spans else 0.0)
    stages = {}
    for stage, group in by_stage.items():
        durs = [(s["end_ns"] - s["start_ns"]) / 1e3 for s in group]
        stages[stage] = {
            "count": len(group),
            "rate_hz": len(group) / window_s if window_s else 0.0,
            "p50_us": _percentile(durs, 50.0),
            "p95_us": _percentile(durs, 95.0),
            "p99_us": _percentile(durs, 99.0),
        }

    # --- inter-stage gaps ------------------------------------------------
    # per trace id and stage, the earliest span: the gap measures when the
    # stage first touched this trace (a batch's write-back fans out to
    # every shard).
    by_tid: dict[int, dict[str, tuple[int, int]]] = {}
    for span in spans:
        tid = span.get("trace_id")
        if tid:
            st = by_tid.setdefault(tid, {})
            stage = span.get("stage", "?")
            if stage not in st or span["start_ns"] < st[stage][0]:
                st[stage] = (span["start_ns"], span["end_ns"])
    gaps = {}
    for src, dst in GAP_PAIRS:
        deltas = [(st[dst][0] - st[src][1]) / 1e3 for st in by_tid.values()
                  if src in st and dst in st and st[dst][0] >= st[src][1]]
        if deltas:
            gaps[f"{src}->{dst}"] = {
                "count": len(deltas),
                "p50_us": _percentile(deltas, 50.0),
                "p95_us": _percentile(deltas, 95.0),
                "p99_us": _percentile(deltas, 99.0),
            }

    # --- last-seen gauges / stall counters -------------------------------
    last = metrics[-1] if metrics else {}
    gauges = dict(last.get("gauges", {}))
    counters = dict(last.get("counters", {}))
    stalls = {k: v for k, v in counters.items()
              if any(tok in k for tok in _STALL_TOKENS)}
    recovery = {k: v for k, v in counters.items()
                if any(tok in k for tok in _RECOVERY_TOKENS)}
    # snapshot/last_step is a gauge, but it belongs with the recovery
    # story (what a resume would continue from).
    for name, val in gauges.items():
        if any(tok in name for tok in _RECOVERY_TOKENS):
            recovery[name] = val

    # --- wait shares: microseconds blocked per microsecond of the span
    # between the first and the last snapshot ----------------------------
    waits = {}
    first = metrics[0] if metrics else {}
    span_s = last.get("ts", 0.0) - first.get("ts", 0.0)
    if span_s > 0:
        before = first.get("counters", {})
        for name, val in counters.items():
            if name.endswith(_WAIT_SUFFIX):
                waits[name] = (val - before.get(name, 0)) / (span_s * 1e6)

    # --- inference plane -------------------------------------------------
    # The wave scheduler's padding tax, made honest: lifetime fraction of
    # dispatched lanes that were replicated padding (recomputed duplicate
    # rollouts, dropped on return). A slot-scheduled run pads nothing.
    inference = {}
    lanes = counters.get("inference/wave_lanes", 0)
    if lanes:
        inference["wave_lanes"] = lanes
        inference["padded_lanes"] = counters.get("inference/padded_lanes", 0)
        inference["pad_fraction"] = inference["padded_lanes"] / lanes
        if "inference/slot_occupancy" in gauges:
            inference["slot_occupancy"] = gauges["inference/slot_occupancy"]

    return {"directory": directory, "window_s": window_s,
            "num_spans": len(spans), "num_snapshots": len(metrics),
            "stages": stages, "gaps": gaps, "gauges": gauges,
            "counters": counters, "waits": waits, "stalls": stalls,
            "recovery": recovery,
            "inference": inference,
            "histograms": dict(last.get("histograms", {}))}


def _fmt_row(cols, widths):
    return "  ".join(str(c).rjust(w) for c, w in zip(cols, widths))


def render(report: dict) -> str:
    lines = []
    lines.append(f"run report: {report['directory']}")
    lines.append(f"  spans={report['num_spans']}"
                 f" snapshots={report['num_snapshots']}"
                 f" window={report['window_s']:.2f}s")

    stages = report["stages"]
    if stages:
        order = [s for s in STAGE_ORDER if s in stages]
        order += sorted(s for s in stages if s not in STAGE_ORDER)
        widths = (22, 8, 10, 10, 10, 10)
        lines.append("")
        lines.append("stage durations (traced spans)")
        lines.append(_fmt_row(
            ("stage", "count", "rate/s", "p50_us", "p95_us", "p99_us"),
            widths))
        for stage in order:
            row = stages[stage]
            lines.append(_fmt_row(
                (stage, row["count"], f"{row['rate_hz']:.1f}",
                 f"{row['p50_us']:.1f}", f"{row['p95_us']:.1f}",
                 f"{row['p99_us']:.1f}"), widths))

    gaps = report["gaps"]
    if gaps:
        widths = (40, 8, 12, 12, 12)
        lines.append("")
        lines.append("inter-stage gaps (same trace id, one stage's end "
                     "to the next one's start)")
        lines.append(_fmt_row(
            ("edge", "count", "p50_us", "p95_us", "p99_us"), widths))
        for edge, row in gaps.items():
            lines.append(_fmt_row(
                (edge, row["count"], f"{row['p50_us']:.1f}",
                 f"{row['p95_us']:.1f}", f"{row['p99_us']:.1f}"), widths))

    if report["gauges"]:
        lines.append("")
        lines.append("queue depths / levels (last snapshot)")
        for name in sorted(report["gauges"]):
            lines.append(f"  {name} = {report['gauges'][name]:g}")

    if report.get("waits"):
        lines.append("")
        lines.append("wait shares (time blocked over the snapshots' span)")
        for name in sorted(report["waits"]):
            lines.append(f"  {name} = {100.0 * report['waits'][name]:.1f}%")

    if report["stalls"]:
        lines.append("")
        lines.append("starvation / backpressure counters")
        for name in sorted(report["stalls"]):
            lines.append(f"  {name} = {report['stalls'][name]}")

    if report.get("inference"):
        inf = report["inference"]
        lines.append("")
        lines.append("inference plane (shared batched engine)")
        lines.append(f"  dispatched lanes = {inf['wave_lanes']:g}")
        lines.append(f"  padded lanes     = {inf['padded_lanes']:g}  "
                     f"(pad fraction {inf['pad_fraction']:.3f} — wasted "
                     "duplicate rollouts under wave coalescing)")
        if "slot_occupancy" in inf:
            lines.append(f"  slot occupancy   = {inf['slot_occupancy']:.3f}"
                         "  (last dispatch, live/max slots)")

    if report.get("recovery"):
        lines.append("")
        lines.append("recovery events (restarts / reconnects / snapshots)")
        for name in sorted(report["recovery"]):
            lines.append(f"  {name} = {report['recovery'][name]:g}")

    hists = report["histograms"]
    if hists:
        widths = (28, 8, 10, 10, 10, 10)
        lines.append("")
        lines.append("latency histograms (full run)")
        lines.append(_fmt_row(
            ("name", "count", "mean_us", "p50_us", "p95_us", "p99_us"),
            widths))
        for name in sorted(hists):
            h = hists[name]
            if not h.get("count"):
                continue
            lines.append(_fmt_row(
                (name, h["count"], f"{h['mean']:.1f}", f"{h['p50']:.1f}",
                 f"{h['p95']:.1f}", f"{h['p99']:.1f}"), widths))

    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.obs.report",
        description="Render a run's metrics/span JSONL into a per-stage "
                    "bottleneck table.")
    ap.add_argument("metrics_dir",
                    help="directory passed as --metrics-dir to the run")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw aggregated report as JSON")
    args = ap.parse_args(argv)
    if not os.path.isdir(args.metrics_dir):
        print(f"error: {args.metrics_dir} is not a directory",
              file=sys.stderr)
        return 2
    report = load_report(args.metrics_dir)
    if args.json:
        print(json.dumps(report, indent=2, sort_keys=True))
    else:
        print(render(report))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
