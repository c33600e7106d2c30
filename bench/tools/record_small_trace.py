"""Record a small profiler trace on the chip for the trace-reduction test.

    python bench/tools/record_small_trace.py <out_dir>

Runs two small jitted programs five times inside a ``bench_window``
annotation, with a 10 ms host sleep between rounds, and writes the
``.xplane.pb`` file to ``<out_dir>``. It also prints every plane, its lines
and the number of events on each, so a reader can see how the trace names
devices, host threads and programs.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    g = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones((1024, 1024), jnp.float32)
    g(f(x)).block_until_ready()
    tmp = tempfile.mkdtemp(dir=out_dir)
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(5):
            g(f(x)).block_until_ready()
            time.sleep(0.01)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(tmp)
    pd = jax.profiler.ProfileData.from_file(os.path.join(out_dir, "small.xplane.pb"))
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            names = sorted({e.name for e in evs})[:12]
            print("   line", repr(line.name), len(evs), names)
            for e in evs[:3]:
                print("      ev", repr(e.name), e.start_ns, e.duration_ns,
                      dict(list(e.stats)[:8]) if hasattr(e, "stats") else "")
    d = jax.devices()[0]
    print("device", d.platform, d.device_kind, len(jax.devices()))
    print("memory_stats", d.memory_stats())


if __name__ == "__main__":
    main(sys.argv[1])
