"""The reduction of a profiler trace to device seconds per layer of the
program (``bench/program_trace.py``): by program name, and inside the
fused lockstep step by the phase scopes the program's ops carry."""

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from bench import program_trace, trace_reduce  # noqa: E402

# Recorded on a TPU v5e by bench/tools/record_program_trace.py: three rounds
# of the named programs and the lockstep step inside ``bench_window``.
PROGRAMS = REPO / "bench" / "tests" / "data" / "programs_v5e.xplane.pb"


def test_innermost_event_takes_each_instant():
    """A loop (replay) around a body op (learner) and a lone op: the loop's
    uncovered time stays its own, the body's goes to the body, overlaps
    count once, and unnamed time is other."""
    out = program_trace._innermost([(0, 10, "replay"), (2, 5, "learner"),
                                    (4, 12, None), (20, 25, "actor")])
    assert out == {"replay": 2, "learner": 2, "other": 8, "actor": 5, "named": 9}
    assert sum(v for k, v in out.items() if k != "named") == 12 + 5  # the union


def test_scope_of_takes_innermost_phase():
    """An op's layer is the innermost phase scope of its op name, looked up
    by its program's name with or without the trace's id; an op with no
    scope, or of a program whose HLO is unknown, has none."""
    scopes = {"jit_apex_step": {
        "fusion.1": "jit(apex_step)/act/while/body/add",
        "fusion.3": "jit(apex_step)/while/body/cond/branch_1_fun/learn/mul",
        "fusion.4": "jit(apex_step)/replay_writeback/learn_rate/mul",
        "copy.2": "jit(apex_step)/while/body/copy"}}
    assert program_trace._scope_of(scopes, "jit_apex_step(77)", "fusion.1") == "actor"
    assert program_trace._scope_of(scopes, "jit_apex_step(77)", "fusion.3") == "learner"
    assert program_trace._scope_of(scopes, "jit_apex_step", "fusion.4") == "replay"
    assert program_trace._scope_of(scopes, "jit_apex_step(77)", "copy.2") is None
    assert program_trace._scope_of(scopes, "jit_apex_step(77)", "fusion.9") is None
    assert program_trace._scope_of(scopes, "jit__lambda(5)", "fusion.1") is None


def test_program_names_give_layers():
    assert program_trace._prefix_layer("jit_replay_add(123)") == "replay"
    assert program_trace._prefix_layer("jit_actor_rollout(1)") == "actor"
    assert program_trace._prefix_layer("jit_learner_step_many(1)") == "learner"
    assert program_trace._prefix_layer("jit_apex_step(1)") is None
    assert program_trace._prefix_layer("jit__lambda(1)") is None


def test_recorded_trace_splits_into_layers():
    """On the recorded trace the layers partition the busy time that
    trace_reduce reports; each layer holds at least its leaf operations
    (a loop's own time goes to the loop's layer), and the replay and
    learner ops, which run in no loop there, add up exactly."""
    red = program_trace.reduce(str(PROGRAMS))
    busy = trace_reduce.reduce(str(PROGRAMS), top=10 ** 6)
    layers = red["layers"]
    assert red["window_s"] == pytest.approx(busy["window_s"], abs=1e-12)
    assert sum(layers.values()) == pytest.approx(busy["busy_s"], abs=1e-12)
    assert red["named_s"] == pytest.approx(busy["busy_s"] - layers["other"], abs=1e-12)
    assert layers == pytest.approx({"actor": 0.001609722, "replay": 0.001035528,
                                    "learner": 0.000172719, "other": 0.000211448}, abs=1e-12)

    scopes = {trace_reduce._short(k): v
              for k, v in program_trace._scopes_from_trace(str(PROGRAMS)).items()}
    leaf = dict.fromkeys(layers, 0.0)
    for name, seconds in busy["top_ops"]:
        op, module = name.split(" @ ")
        layer = (program_trace._prefix_layer(module)
                 or program_trace._scope_of(scopes, module, op) or "other")
        leaf[layer] += seconds
    for layer in layers:
        assert leaf[layer] <= layers[layer] + 1e-12, layer
    assert leaf["replay"] == pytest.approx(layers["replay"], abs=1e-12)
    assert leaf["learner"] == pytest.approx(layers["learner"], abs=1e-12)


def test_recorded_step_carries_every_phase_scope():
    """The HLO the trace records for jit_apex_step gives its ops the five
    phase scopes; the trimmed trace keeps only the programs that ran."""
    scopes = program_trace._scopes_from_trace(str(PROGRAMS))
    (step,) = [v for k, v in scopes.items() if k.startswith("jit_apex_step(")]
    found = {part for name in step.values() for part in name.split("/")}
    assert set(program_trace.SCOPES) <= found
    assert not any(k.startswith("jit_apex_init(") for k in scopes)


def test_program_without_names_is_all_other():
    """The older trace's programs are all ``jit__lambda``: everything is
    other, and nothing is named."""
    old = REPO / "bench" / "tests" / "data" / "small_v5e.xplane.pb"
    red = program_trace.reduce(str(old))
    assert red["named_s"] == 0.0
    assert red["layers"]["other"] == pytest.approx(trace_reduce.reduce(str(old))["busy_s"])
    assert program_trace.reduce(str(old), annotation="absent") is None
