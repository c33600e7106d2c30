"""Record a small profiler trace of the program's named device programs on
the chip, for the test of ``bench/program_trace.py``.

    python bench/tools/record_program_trace.py <out_dir>

With the reduced DQN preset at a small replay, it warms and then runs,
three rounds inside a ``bench_window`` annotation with a 10 ms host sleep
before each: a replay shard's add, sample, write-back and min-fill check,
the fabric's merge and routing of two shards, the runner's rollout and
learner step, the lockstep step (one program of every phase, told apart by
its scopes), and an anonymous program. It writes
``<out_dir>/programs_v5e.xplane.pb`` and prints the two reductions of it,
``trace_reduce`` and ``program_trace``, and each program's device seconds
in the window, which the test holds the reduction to.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

from bench import program_trace, trace_reduce  # noqa: E402
from repro.configs import apex_dqn  # noqa: E402
from repro.core import apex, replay as replay_lib  # noqa: E402
from repro.runtime import fabric, phases, runner, service  # noqa: E402


def main(out_dir: str) -> None:
    # op names stay in the recorded HLO; source locations would only
    # make the file larger
    jax.config.update("jax_traceback_in_locations_limit", 0)
    preset = apex_dqn.reduced()
    cfg = dataclasses.replace(
        preset.apex, replay=dataclasses.replace(preset.apex.replay, capacity=4096,
                                                soft_capacity=3584, min_fill=256))
    env, agent, opt = preset.env, preset.agent, preset.make_optimizer()
    key = jax.random.key(0)
    batch = cfg.batch_size

    rollout, learn, _ = runner.make_runner_fns(cfg, env, agent, opt)
    aslice = phases.initial_actor_slice(cfg, env, 0, 0)
    params = agent.init(key, aslice.obs[:1])
    lslice = phases.LearnerSlice(params=params, target_params=params,
                                 opt_state=opt.init(params),
                                 learner_step=jnp.zeros((), jnp.int32))
    fns = service.make_shard_fns(cfg, batch // 2)
    item = phases.item_example(env, aslice.obs, cfg.compress_obs)
    merge = fabric._merge_fn(cfg.replay.beta, cfg.replay.capacity)
    route = fabric._partition_fn(2, cfg.replay.capacity)
    init_fn, step_fn = apex.make_train_fn(cfg, env, agent, opt)
    anonymous = jax.jit(lambda x: x * 2.0 + 1.0)
    state = {"replay": replay_lib.init(cfg.replay, item), "apex": init_fn(key)}

    def one_round():
        _, block, _ = rollout(params, aslice, jnp.int32(0))
        rep = fns.add(state["replay"], block)
        sub = fns.sample(rep, fns.split(key)[0])
        fns.can_sample(rep)
        idx, _, w = merge((sub, sub))
        learn(lslice, phases.learner_batch_example(cfg, item)[0], w)
        slots, prios, _ = route(idx, w)
        state["replay"] = fns.writeback(rep, slots[:batch // 2], prios[:batch // 2], 1, key)
        state["apex"], _ = step_fn(state["apex"])
        jax.block_until_ready((state, anonymous(w)))

    one_round()
    os.makedirs(out_dir, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=out_dir)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0  # as the benchmark's own profiler window
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench_window"):
        for _ in range(3):
            time.sleep(0.01)
            one_round()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    out = os.path.join(out_dir, "programs_v5e.xplane.pb")
    shutil.copy(path, out)
    shutil.rmtree(tmp)

    red = trace_reduce.reduce(out)
    if red is None:
        raise SystemExit(f"no device operations in the window of {out}")
    ran = {name.split(" @ ")[1] for name, _ in trace_reduce.reduce(out, top=10 ** 6)["top_ops"]}
    with open(out, "rb") as f:
        raw = f.read()
    with open(out, "wb") as f:
        f.write(_keep_hlo_of(raw, ran))
    print("trace_reduce", json.dumps({k: red[k] for k in ("busy_s", "window_s")}))
    print("program_trace", json.dumps(program_trace.reduce(out)))
    print("ops", json.dumps(red["top_ops"]))
    pd = jax.profiler.ProfileData.from_file(out)
    w0, w1 = next((ev.start_ns, ev.start_ns + ev.duration_ns) for p in pd.planes
                  if p.name.startswith("/host:") for ln in p.lines for ev in ln.events
                  if ev.name == "bench_window")
    per_module = collections.Counter()
    for p in pd.planes:
        if p.name.startswith("/device:"):
            for ln in p.lines:
                if ln.name == "XLA Modules":
                    for ev in ln.events:
                        s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                        if e > s:
                            per_module[trace_reduce._short(ev.name)] += (e - s) / 1e9
    print("modules", json.dumps(per_module.most_common()))
    print("device", jax.devices()[0].device_kind, "bytes", os.path.getsize(out))


def _keep_hlo_of(raw: bytes, programs: set) -> bytes:
    """The trace with the ``/host:metadata`` plane's recorded HLO kept only
    for ``programs`` (names without their id), the others (every program
    the process had loaded) dropped to keep the file small."""
    buf = memoryview(raw)
    out = bytearray()
    for field, plane, (s, e) in program_trace._fields(buf):
        if field == 1 and program_trace._str(plane, 2) == "/host:metadata":
            kept = bytearray()
            for f, entry, (es, ee) in program_trace._fields(plane):
                if f == 4:
                    meta = next(program_trace._get(entry, 2))
                    if trace_reduce._short(program_trace._str(meta, 2)) not in programs:
                        continue
                kept += plane[es:ee]
            out += _varint_bytes(1 << 3 | 2) + _varint_bytes(len(kept)) + kept
        else:
            out += buf[s:e]
    return bytes(out)


def _varint_bytes(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


if __name__ == "__main__":
    main(sys.argv[1])
