"""What tracing costs when it is on.

    python3 bench/tools/trace_cost.py <on|off> <cell> <seed> <seconds>
    python3 bench/tools/trace_cost.py span

With a cell: one window of the cell, as ``bench/run.py`` sets it up, with
the profiler on from the window's first tick to its end (``on``) or never
(``off``); prints the window's rates. Run both with one seed for a pair.
With ``span``: the mean cost of one ``Tracer.span`` around nothing, in us,
unsampled with the profiler off, on, and off again, and sampled (buffered)
with the profiler off.
"""

import time

T0 = time.perf_counter()

import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(CHECKOUT / "src"), str(CHECKOUT)]
OUT = CHECKOUT / ".bench_trace_cost"


def spans(n: int = 200_000) -> dict:
    import jax
    from repro.obs import Tracer
    tr = Tracer(0.0)
    out = {}
    for mode in ("off", "on", "off"):
        if mode == "on":
            jax.profiler.start_trace(str(OUT))
        t = time.perf_counter()
        for _ in range(n):
            with tr.span("bench/span"):
                pass
        out[mode] = 1e6 * (time.perf_counter() - t) / n
        if mode == "on":
            jax.profiler.stop_trace()
            shutil.rmtree(OUT, ignore_errors=True)
    sampled = Tracer(1.0)
    t = time.perf_counter()
    for _ in range(n):
        with sampled.span("bench/span", 1):
            pass
    out["sampled_off"] = 1e6 * (time.perf_counter() - t) / n
    return {"span_us": out}


def window(mode: str, workload: str, seed: int, seconds: float) -> dict:
    import jax
    from bench import harness

    class WholeWindow(harness.ProfileWindow):
        """The profiler on from the window's first tick to its end."""

        def due(self):
            return [0.0]

        def tick(self, elapsed, mark=None):
            if self.enabled and self.state == "before":
                self.start()
                self.state, self.marks = "on", []
                return True
            return False

        def finish(self, mark=None):
            if self.state == "on":
                jax.profiler.stop_trace()
                self.state = "done"

    harness.ProfileWindow = WholeWindow
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    cell = harness.load_cell(workload)
    clock = {"start": T0, "device": time.perf_counter(),
             "compile_counter": harness.CompileCounter(), "trace_dir": str(OUT)}
    runtime = harness.load_module(harness.BENCH / "runtimes" / f"{cell.traffic['runtime']}.py",
                                  "bench_runtime")
    rec = runtime.run(cell, seed, seconds, mode == "on", clock)
    shutil.rmtree(OUT, ignore_errors=True)
    w = rec["window_s"]
    return {"mode": mode, "cell": workload, "seed": seed, "window_s": w,
            "generate_tps": rec["generated"] / w, "consume_tps": rec["consumed"] / w,
            "window_compiles": clock["compile_counter"].counts["window"]}


if __name__ == "__main__":
    if sys.argv[1] == "span":
        print(json.dumps(spans()))
    else:
        print(json.dumps(window(sys.argv[1], sys.argv[2], int(sys.argv[3]), float(sys.argv[4]))))
