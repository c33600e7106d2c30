"""The trace reduction against hand counts, on a small trace recorded on a
TPU v5e (``bench/tools/record_small_trace.py``): two programs run five
times each inside the ``bench_window`` annotation. The device clock reads
about 1 ms ahead of the annotation, so the first round of both programs
falls before the window and is left out."""

import pathlib

import pytest

from bench import trace_reduce

TRACE = pathlib.Path(__file__).with_name("data") / "small_v5e.xplane.pb"

# device durations (ns) of the XLA Ops events, in order, from the trace
FUSION = [42575, 42502, 42590, 42960, 42533]
MULADD = [12795, 13133, 13035, 12553, 13200]
WINDOW_NS = 57397266


def test_busy_window_and_ops():
    red = trace_reduce.reduce(str(TRACE))
    busy = sum(FUSION[1:]) + sum(MULADD[1:])
    assert red["busy_s"] == pytest.approx(busy / 1e9, abs=1e-12)
    assert red["window_s"] == pytest.approx(WINDOW_NS / 1e9, abs=1e-12)
    assert red["device_planes"] == 1
    ops = dict(red["top_ops"])
    assert set(ops) == {"fusion @ jit__lambda", "multiply_add_fusion @ jit__lambda"}
    assert ops["fusion @ jit__lambda"] == pytest.approx(sum(FUSION[1:]) / 1e9, abs=1e-12)
    assert ops["multiply_add_fusion @ jit__lambda"] == pytest.approx(sum(MULADD[1:]) / 1e9, abs=1e-12)


def test_idle_gaps_are_named_by_host_activity():
    red = trace_reduce.reduce(str(TRACE))
    gaps = red["idle_gaps"]
    # the five 10 ms host sleeps between rounds are the longest gaps
    assert [name for name, _ in gaps[:5]] == ["$time sleep"] * 5
    assert all(0.0105 < s < 0.012 for _, s in gaps[:5])
    assert sum(s for _, s in gaps) <= red["window_s"] - red["busy_s"] + 1e-9


def test_union_merges_overlaps():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [[0, 3], [5, 9]]


def test_enclosing_events_are_parents():
    # a loop (0-10) around two ops, then a lone op; a lone op alone
    assert trace_reduce._parents([(0, 10), (1, 4), (5, 9), (12, 13)]) == [True, False, False, False]
    assert trace_reduce._parents([(0, 10)]) == [False]


def test_no_window_gives_nothing():
    assert trace_reduce.reduce(str(TRACE), annotation="absent") is None
