"""Run one benchmark cell and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by its name: ``BENCHMARK.json`` names its
configuration, traffic and chips; ``bench/workloads/<cell>.json`` the limits
of its comparison; ``bench/traffic/<traffic>.json`` the runtime that drives
it (``bench/runtimes/<runtime>.py``); each metric is read by
``bench/metrics/<metric>.py``.

One process per run: set-up (imports, device, state built from the seed,
warm-up) until the window opens, the window of ``--seconds``, then the
check of what the window produced against the plain reference. The last
line of standard output is one JSON object; the numbers compared, each
beside its limit, are the last lines of standard error and the last key of
that object. The run exits non-zero, and prints no result, when JAX finds
no TPU, fewer chips than the cell asks for, or a device kind that
``bench/peaks.json`` lacks.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


class NoChip(SystemExit):
    pass


def find_device(cell, peaks: dict):
    """The cell's devices; refuses the CPU, too few chips and an unknown
    device kind."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator: {e}")
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX runs on {devices[0].platform}")
    if len(devices) < cell.chips:
        raise NoChip(f"the cell asks for {cell.chips} chips, JAX finds {len(devices)}")
    if kind not in peaks:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return devices[:cell.chips], kind


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             checkout: pathlib.Path = CHECKOUT, require_chip: bool = True,
             calibrate: tuple = ()) -> tuple[dict, list[str]]:
    """One run; returns (result object, check lines). ``require_chip``
    False skips the look for a chip (the harness's own tests on the CPU).
    ``calibrate`` names further modes whose numbers the result carries
    under ``calibration`` (``bench/calibrate.py``): "control" puts the
    reference at bfloat16 in the program's place, a fault name plants that
    fault in the float32 reference put in its place."""
    for p in (str(checkout / "src"), str(checkout)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import harness
    cell = harness.load_cell(workload, checkout)
    peaks = harness.load_json(checkout / "bench" / "peaks.json")
    import jax
    if require_chip:
        devices, kind = find_device(cell, peaks)
        peak = peaks[kind]
    else:
        devices, kind, peak = jax.devices()[:cell.chips], jax.devices()[0].device_kind, None
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clock = {"start": T_START, "device": time.perf_counter(),
             "compile_counter": harness.CompileCounter(),
             "trace_dir": str(checkout / ".bench_trace")}
    runtime = harness.load_module(
        checkout / "bench" / "runtimes" / f"{cell.traffic['runtime']}.py",
        f"bench_runtime_{cell.traffic['runtime']}")
    record = runtime.run(cell, seed, seconds, trace, clock)
    setup_s = clock["fill"] - T_START
    memory_peak = harness.memory_peak_bytes(devices)
    record.pop("_state", None)
    gc.collect()

    t_check = time.perf_counter()
    numbers = runtime.check(cell, record, "program")
    calibration = {m: runtime.check(cell, record, m) for m in calibrate}
    limits = cell.workload["limits"]
    checks = {k: {"value": float(numbers[k]) if k in numbers else None, "limit": v}
              for k, v in limits.items()}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values()) and not record.get("capture_errors")

    record.update(chips=cell.chips, device_kind=kind, peak=peak,
                  config=cell.config, setup_s=setup_s)
    e2e = {"setup_s": setup_s,
           "generate_tps": record["generated"] / record["window_s"],
           "consume_tps": record["consumed"] / record["window_s"]}
    record["e2e"] = e2e
    bench = cell.benchmark
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": bool(correct), "attempted": 1, "failed": 0 if correct else 1}
    metrics = {}
    breakdown = None
    if not trace:
        for m in bench["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace_reduce
        red = (trace_reduce.reduce(record["trace_path"], harness.ProfileWindow.ANNOTATION)
               if record.get("trace_path") else None)
        shutil.rmtree(clock["trace_dir"], ignore_errors=True)
        record["trace"] = red
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            breakdown = {"device_ops": red["top_ops"], "idle_gaps": red["idle_gaps"]}
        for m in bench["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            reader = harness.load_module(checkout / "bench" / "metrics" / f"{m['name']}.py",
                                         "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result.update(metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    if calibrate:
        result["calibration"] = dict(calibration, program=numbers)
    result["checks"] = checks

    fill = record["fill"]
    info = {"setup_phases_s": {
                "imports_and_device": clock["device"] - T_START,
                "state_build": clock.get("build", clock["device"]) - clock["device"],
                "fill_and_warm": clock["fill"] - clock.get("build", clock["device"])},
            "setup_compiles": clock["compile_counter"].counts["setup"],
            "window_s": record["window_s"],
            "window_compiles": clock["compile_counter"].counts["window"],
            "generated": record["generated"], "consumed": record["consumed"],
            "fill": fill, "check_s": time.perf_counter() - t_check,
            "in_flight_at_deadline": record.get("in_flight_at_deadline"),
            "iterations": record.get("iterations"),
            "capture_errors": record.get("capture_errors", [])}
    lines = ["[run] " + json.dumps(info)]
    lines += [f"[check] {k} = {c['value']!r} limit {c['limit']!r}" for k, c in checks.items()]
    return result, lines


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result, lines = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"[run] {e}", file=sys.stderr)
        return 3
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
