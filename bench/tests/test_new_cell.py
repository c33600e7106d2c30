"""A later cell comes as files alone: a workload, a traffic mix and a
metric reader dropped into a copy of the benchmark, with their entries in
``BENCHMARK.json``, run with no other file edited."""

import hashlib
import json
import shutil


def _digest(root):
    return {p.relative_to(root).as_posix(): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_cell_by_files_alone(tiny_checkout, run_cell, tmp_path):
    dst = tmp_path / "checkout"
    dst.mkdir()
    shutil.copy(tiny_checkout / "BENCHMARK.json", dst)
    shutil.copytree(tiny_checkout / "bench", dst / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (dst / "src").symlink_to((tiny_checkout / "src").resolve())
    before = _digest(dst / "bench")

    bench = dst / "bench"
    (bench / "traffic" / "lockstep_sync1.json").write_text(json.dumps(
        {"runtime": "lockstep", "sync_every": 1}))
    lock = json.loads((bench / "workloads" / "dqn.lockstep.json").read_text())
    entry = {"name": "dqn.lockstep_sync1", "config": "apex_dqn_chainworld_mlp",
             "traffic": "lockstep_sync1", "chips": 1,
             "why": "the lockstep cell with the host waiting on every iteration"}
    (bench / "workloads" / "dqn.lockstep_sync1.json").write_text(
        json.dumps(dict(entry, limits=lock["limits"])))
    (bench / "metrics" / "window_iterations.py").write_text(
        'NAME, UNIT, LAYER, MOVES = "window_iterations", "1", "Lockstep host loop", "consume_tps"\n'
        "def read(record):\n    return record.get('iterations')\n")
    spec = json.loads((dst / "BENCHMARK.json").read_text())
    spec["workloads"].append(entry)
    spec["per_layer"].append({"name": "window_iterations", "unit": "1", "better": "higher",
                              "source": "host_clock", "layer": "Lockstep host loop",
                              "moves": "consume_tps", "workloads": ["dqn.lockstep_sync1"]})
    (dst / "BENCHMARK.json").write_text(json.dumps(spec))

    result, _ = run_cell("dqn.lockstep_sync1", trace=True, checkout=dst)
    assert result["correct"]
    assert result["metrics"]["window_iterations"]["value"] > 0
    after = _digest(bench)
    assert {k: v for k, v in after.items() if k in before} == before
