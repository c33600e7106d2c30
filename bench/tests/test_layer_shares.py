"""``bench/tools/layer_shares.py``: a traced run of a cell with the layer
and wait shares read from its own span, on the CPU."""

import importlib.util
import pathlib

import pytest

TOOL = pathlib.Path(__file__).resolve().parents[1] / "tools" / "layer_shares.py"


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location("layer_shares", TOOL)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_decoupled_run_reads_waits_and_runtime_spans(tool, tiny_checkout, monkeypatch):
    from bench import harness, trace_reduce
    # a short span: a CPU trace of the decoupled runtime's many small
    # programs takes minutes to write and read back at the chip's 5 s
    monkeypatch.setattr(harness.ProfileWindow, "SECONDS", 0.5)
    monkeypatch.setattr(harness.ProfileWindow, "LEAD", 0.2)
    tick, reduce = harness.ProfileWindow.tick, trace_reduce.reduce
    result, _ = tool.measure("dqn.async", 7, 1.0, gaps=1, checkout=tiny_checkout,
                             require_chip=False)
    assert result["correct"]
    layers = result["layers"]
    waits = layers["waits"]
    assert waits["span_s"] > 0
    for key in ("learner_batch_wait", "owner_sync"):
        assert 0.0 <= waits[key] <= 100.0 + 1e-6, key
    for name in ("actor/rollout", "shard/add", "learner/step", "learner/write_back"):
        count, seconds = layers["host_spans"][name]
        assert count > 0 and seconds > 0.0, name
    # the hooks are gone after the run
    assert (harness.ProfileWindow.tick, trace_reduce.reduce) == (tick, reduce)


def test_idle_gaps_are_the_longest_stretches_without_an_op(tool):
    ops = [(10, 20, "a"), (15, 30, "b"), (60, 70, "c"), (75, 90, "d")]
    host = [(25, 65, "python", "learner/step"), (0, 100, "python", "bench_window"),
            (40, 41, "python", "tiny")]
    gaps = tool._idle_gaps(ops, host, 0, 100, 2, [(35, 45, 2)])
    assert [(g["at_ms"] * 1e6, g["ms"] * 1e6) for g in gaps] == [(30, 30), (0, 10)]
    first = gaps[0]
    assert first["before"] == ["a", "b"] and first["after"] == ["c", "d"]
    assert [h["name"] for h in first["host"]] == ["learner/step"]
    assert first["gc"][0]["generation"] == 2
