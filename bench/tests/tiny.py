"""A copy of the benchmark whose configurations are the program's reduced
presets, for the harness's own tests on the CPU.

``make_checkout(dst)`` copies ``BENCHMARK.json`` and ``bench/`` into
``dst``, links ``src``, and rewrites each configuration file to the reduced
preset of the same agent, so that every cell runs in seconds on the CPU
through the same files, runtimes, check and readers.
"""

from __future__ import annotations

import json
import os
import pathlib
import shutil

REPO = pathlib.Path(__file__).resolve().parents[2]

TINY = {
    "dqn": {
        "preset": "repro.configs.apex_dqn:reduced", "preset_kwargs": {"num_shards": 1},
        "env": {"length": 8, "max_steps": 32, "gamma": 0.99, "slip_prob": 0.05,
                "distractor_reward": 0.01, "num_actions": 4},
        "mlp_hidden": [64], "head_hidden": 64, "num_actors": 16, "rollout_len": 24,
        "n_step": 3, "batch_size": 64, "learner_steps_per_iter": 2,
        "param_sync_period": 2, "target_update_period": 100, "evict_interval": 50,
        "replay_capacity": 4096, "soft_capacity": 3584, "min_fill": 256,
        "optimizer": {"kind": "centered_rmsprop", "learning_rate": 1e-3,
                      "decay": 0.95, "eps": 1.5e-07},
    },
    "dpg": {
        "preset": "repro.configs.apex_dpg:reduced", "preset_kwargs": {"num_shards": 1},
        "env": {"max_steps": 60, "gamma": 0.99, "dt": 0.05, "drag": 0.1, "action_dim": 2},
        "actor_hidden": [32, 32], "critic_hidden": [32, 32], "num_actors": 8,
        "rollout_len": 20, "n_step": 5, "batch_size": 32, "learner_steps_per_iter": 2,
        "param_sync_period": 2, "target_update_period": 50, "evict_interval": 25,
        "evict_num": 64, "replay_capacity": 2048, "soft_capacity": 1792, "min_fill": 128,
        "optimizer": {"kind": "adam", "learning_rate": 1e-3, "b1": 0.9, "b2": 0.999,
                      "eps": 1e-08},
    },
}


def make_checkout(dst: pathlib.Path) -> pathlib.Path:
    dst = pathlib.Path(dst)
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "src", dst / "src")
    # every workload file is a cell here, also one that BENCHMARK.json
    # leaves out, so that its runtime and reference stay tested
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    names = {w["name"] for w in spec["workloads"]}
    configs = {c["name"] for c in spec["configs"]}
    for path in sorted((dst / "bench" / "workloads").glob("*.json")):
        w = json.loads(path.read_text())
        if w["name"] not in names:
            spec["workloads"].append({k: w[k] for k in ("name", "config", "traffic", "chips", "why")})
        if w["config"] not in configs:
            cfg = json.loads((dst / "bench" / "configs" / f"{w['config']}.json").read_text())
            spec["configs"].append({"name": w["config"], "source": cfg["source"],
                                    "file": f"bench/configs/{w['config']}.json",
                                    "reduced": cfg["reduced"], "why": w["why"]})
            configs.add(w["config"])
    (dst / "BENCHMARK.json").write_text(json.dumps(spec, indent=2))
    for path in (dst / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg.update(TINY[cfg["agent"]])
        path.write_text(json.dumps(cfg, indent=2))
    for path in (dst / "bench" / "traffic").glob("*.json"):
        tr = json.loads(path.read_text())
        tr["warm_timeout_s"] = 120
        path.write_text(json.dumps(tr, indent=2))
    return dst
