"""What every cell shares: its files, the preset it runs, device copies,
a compile counter, and the profiler window.

A cell is found by name. ``BENCHMARK.json`` names its configuration and
traffic; ``bench/workloads/<cell>.json`` holds the limits of its
comparison; ``bench/configs/<config>.json`` the configuration as it is run;
``bench/traffic/<traffic>.json`` the runtime and its geometry, and the
cadence (``cadence``: rollout length, learner steps per iteration, actor
sync period) where its runtime drives the preset at another than the
configuration's. Nothing here names a cell.
"""

from __future__ import annotations

import dataclasses
import glob
import importlib
import importlib.util
import json
import os
import pathlib
import shutil
import threading
import time

BENCH = pathlib.Path(__file__).resolve().parent
CHECKOUT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict          # bench/configs/<config>.json, with the traffic's cadence
    traffic: dict         # bench/traffic/<traffic>.json
    workload: dict        # bench/workloads/<cell>.json
    benchmark: dict       # BENCHMARK.json


def load_cell(name: str, checkout: pathlib.Path = CHECKOUT) -> Cell:
    bench = load_json(checkout / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"no workload named {name!r} in BENCHMARK.json")
    cfg_entry = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    root = checkout / "bench"
    workload = load_json(root / "workloads" / f"{name}.json")
    drift = [k for k in ("config", "traffic", "chips", "why") if workload.get(k) != entry[k]]
    if drift:
        raise SystemExit(f"bench/workloads/{name}.json and BENCHMARK.json differ in {drift}")
    config = load_json(checkout / cfg_entry["file"])
    traffic = load_json(root / "traffic" / f"{entry['traffic']}.json")
    # a traffic mix may set the cadence its runtime drives the preset at
    cadence = traffic.get("cadence", {})
    if cadence:
        config = dict(config, **cadence,
                      set_on_preset=sorted(set(config.get("set_on_preset", [])) | set(cadence)))
    return Cell(
        name=name, chips=int(entry["chips"]), config=config, traffic=traffic,
        workload=workload, benchmark=bench)


# ---------------------------------------------------------------------------
# The preset the configuration file names, checked against the file
# ---------------------------------------------------------------------------

def _preset_values(preset) -> dict:
    """The numbers of a built preset, under the configuration file's keys."""
    cfg, env, agent = preset.apex, preset.env, preset.agent
    r = cfg.replay
    out = {
        "num_actors": cfg.num_actors, "rollout_len": cfg.rollout_len,
        "n_step": cfg.n_step, "batch_size": cfg.batch_size,
        "learner_steps_per_iter": cfg.learner_steps_per_iter,
        "param_sync_period": cfg.param_sync_period,
        "target_update_period": cfg.target_update_period,
        "evict_interval": cfg.evict_interval, "evict_num": cfg.evict_num,
        "eviction": cfg.eviction, "eps_base": cfg.eps_base,
        "eps_alpha": cfg.eps_alpha, "compress_obs": cfg.compress_obs,
        "replicate_k": cfg.replicate_k, "eps_mode": cfg.eps_mode,
        "replay_capacity": r.capacity, "soft_capacity": r.soft_cap,
        "alpha": r.alpha, "beta": r.beta, "evict_alpha": r.evict_alpha,
        "min_fill": r.min_fill,
        "env": {k: getattr(env, k) for k in dataclasses.asdict(env)},
        "learning_rate": preset.learning_rate,
    }
    if hasattr(agent, "net"):
        net = agent.net
        out.update(agent="dqn", torso=net.torso,
                   mlp_hidden=list(net.mlp_hidden), head_hidden=net.head_hidden,
                   grad_clip=agent.grad_clip)
        out["env"]["num_actions"] = env.num_actions
    else:
        out.update(agent="dpg", actor_hidden=list(agent.actor_net.hidden),
                   critic_hidden=list(agent.critic_net.hidden),
                   sigma=agent.sigma, action_grad_clip=agent.action_grad_clip)
        out["env"]["action_dim"] = env.action_dim
    return out


def build_preset(config: dict):
    """Call the preset the file names, set on its ``ApexConfig`` the fields
    the file lists under ``set_on_preset``, and refuse a preset whose
    numbers then differ from the file's: the file is the configuration as
    it is run."""
    mod_name, fn_name = config["preset"].split(":")
    preset = getattr(importlib.import_module(mod_name), fn_name)(
        **config.get("preset_kwargs", {}))
    # fields of the preset's ApexConfig that the file sets itself
    over = {k: config[k] for k in config.get("set_on_preset", [])}
    if over:
        preset = dataclasses.replace(preset, apex=dataclasses.replace(preset.apex, **over))
    have = _preset_values(preset)
    diffs = []
    for k, v in have.items():
        want = config["optimizer"]["learning_rate"] if k == "learning_rate" else config.get(k)
        if isinstance(v, dict):
            diffs += [f"{k}.{kk}: program {vv!r} file {want.get(kk)!r}"
                      for kk, vv in v.items() if want is None or want.get(kk) != vv]
        elif want != v:
            diffs.append(f"{k}: program {v!r} file {want!r}")
    if diffs:
        raise SystemExit("configuration file and preset differ: " + "; ".join(diffs))
    return preset


# ---------------------------------------------------------------------------
# Device copies, compile counting, profiler window
# ---------------------------------------------------------------------------

_copy_fn = None


def device_copy(tree):
    """A fresh device copy of ``tree``, dispatched without waiting. Taken
    of a step's input before the step runs, it survives the step donating
    that input."""
    global _copy_fn
    import jax
    import jax.numpy as jnp
    if _copy_fn is None:
        _copy_fn = jax.jit(lambda t: jax.tree.map(jnp.copy, t))
    return _copy_fn(tree)


class CompileCounter:
    """Counts traces and backend compiles by phase: ``setup`` until the
    runtime sets ``active`` at the window's start, ``window`` while it is
    set, ``after`` once it is cleared. The window must see none."""

    def __init__(self):
        import jax.monitoring
        self._phase = "setup"
        self.counts = {p: {"traces": 0, "compiles": 0, "compile_s": 0.0}
                       for p in ("setup", "window", "after")}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def active(self) -> bool:
        return self._phase == "window"

    @active.setter
    def active(self, on: bool) -> None:
        self._phase = "window" if on else ("after" if self._phase == "window" else self._phase)

    def _on(self, name, duration, **_kw):
        with self._lock:
            c = self.counts[self._phase]
            if name == "/jax/core/compile/jaxpr_trace_duration":
                c["traces"] += 1
            elif name == "/jax/core/compile/backend_compile_duration":
                c["compiles"] += 1
                c["compile_s"] += float(duration)


class ProfileWindow:
    """``jax.profiler`` trace of a ``SECONDS``-long span in the middle of
    the window, annotated ``bench_window`` so the reduction can clip device
    time to it. The profiler starts ``LEAD`` seconds before the span, so
    its own start-up (a slow first dispatch under the profiler) falls
    outside it. A trace of the whole window would take minutes to write and
    read back for the decoupled runtime's many small programs."""

    ANNOTATION = "bench_window"
    SECONDS = 5.0
    LEAD = 1.0

    def __init__(self, enabled: bool, out_dir: str, window_s: float = 0.0):
        self.enabled = enabled
        self.out_dir = out_dir
        self.offset = max(self.LEAD, (window_s - self.SECONDS) / 2)
        self._ann = None
        self.state = "before"

    def due(self) -> list[float]:
        """Window times at which ``tick`` has work: profiler start, span
        start, span end."""
        return [self.offset - self.LEAD, self.offset, self.offset + self.SECONDS]

    def tick(self, elapsed: float, mark=None) -> bool:
        """Start the profiler, open the span and close it as the window's
        clock passes each time of ``due()``; ``mark()`` is read just after
        the span opens and just before it closes (the span's counters).
        True when this tick changed the state."""
        if not self.enabled:
            return False
        if self.state == "before" and elapsed >= self.offset - self.LEAD:
            self.start()
            self.state = "tracing"
            return True
        if self.state == "tracing" and elapsed >= self.offset:
            self._ann = _annotation(self.ANNOTATION)
            self._ann.__enter__()
            self.marks = [(time.perf_counter(), mark() if mark else None)]
            self.state = "on"
            return True
        if self.state == "on" and elapsed >= self.offset + self.SECONDS:
            self.marks.append((time.perf_counter(), mark() if mark else None))
            self.path = self.stop()
            self.state = "done"
            return True
        return False

    def finish(self, mark=None) -> None:
        """Close whatever the window's end left open."""
        while self.state in ("tracing", "on"):
            self.tick(float("inf"), mark)

    def start(self):
        if not self.enabled:
            return
        import jax
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # host spans of JAX and the runtime only
        jax.profiler.start_trace(self.out_dir, profiler_options=opts)

    def stop(self) -> str | None:
        if not self.enabled or self.state == "before":
            return None
        import jax
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        return paths[0] if paths else None


def _annotation(name):
    import jax
    return jax.profiler.TraceAnnotation(name)


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None
