"""Telemetry plane: histogram percentile math (property-tested against
numpy order statistics), deterministic trace sampling, trace-id
propagation across a loopback gateway round trip (tcp AND shm), the
JSONL sink, the structured log emitter, and the run report end to end."""

import glob
import json
import math
import threading
import time

import jax

import numpy as np
import pytest
from _apex_helpers import make_block, tiny_preset
from _hypothesis_fallback import given, settings, st

from repro.net import transport, wire
from repro.net.gateway import ReplayGateway
from repro.net.learner_client import RemoteFabricSource
from repro.obs import MetricsRegistry, Telemetry, Tracer, log as obslog
from repro.obs.metrics import (_BUCKET_EDGES, _BUCKET_FACTOR, _NUM_BUCKETS,
                               Histogram, bucket_index)
from repro.obs import report as report_lib
from repro.obs.sink import METRICS_FILE, SPANS_FILE, JsonlSink
from repro.runtime import AsyncConfig, ParamStore, run_async


# --- histogram ---------------------------------------------------------------

def test_bucket_index_edges_and_clamps():
    for i in (0, 1, 17, _NUM_BUCKETS - 1):
        lo, hi = _BUCKET_EDGES[i], _BUCKET_EDGES[i + 1]
        assert bucket_index(lo) == i
        assert bucket_index(hi * 0.999999) == i
    assert bucket_index(0.0) == 0
    assert bucket_index(1e-9) == 0
    assert bucket_index(1e30) == _NUM_BUCKETS - 1


def test_single_value_histogram_is_honest():
    """Clamping to observed min/max: one sample must come back exactly,
    not smeared across its bucket."""
    h = Histogram("t")
    h.record(42.0)
    for q in (0.0, 50.0, 95.0, 99.0, 100.0):
        assert h.percentile(q) == pytest.approx(42.0)
    assert h.mean == pytest.approx(42.0)


@settings(max_examples=60, deadline=None)
@given(values=st.lists(st.floats(min_value=1.0, max_value=1e8,
                                 allow_nan=False, allow_infinity=False),
                       min_size=1, max_size=200),
       q=st.floats(min_value=0.0, max_value=100.0))
def test_histogram_percentile_tracks_numpy_order_stats(values, q):
    """Property (acceptance): the interpolated percentile lies within one
    geometric bucket ratio of the order statistic numpy's 'linear'
    convention anchors on — i.e. the histogram is exact up to its
    documented quantization, for any data shape (uniform, bimodal, spiky).
    """
    h = Histogram("t")
    for v in values:
        h.record(v)
    got = h.percentile(q)
    rank = (q / 100.0) * (len(values) - 1)
    v_sorted = np.sort(np.asarray(values))
    v_floor = v_sorted[int(math.floor(rank))]
    v_ceil = v_sorted[int(math.ceil(rank))]
    # the true numpy quantile lies in [v_floor, v_ceil]; ours lives in
    # v_floor's bucket (clamped to the observed range)
    tol = _BUCKET_FACTOR * 1.0001
    assert got >= v_floor / tol
    assert got <= max(v_floor * tol, v_ceil)
    assert np.quantile(v_sorted, q / 100.0) <= v_ceil * tol


def test_registry_create_or_get_and_snapshot():
    reg = MetricsRegistry()
    assert reg.counter("a") is reg.counter("a")
    assert reg.histogram("h") is reg.histogram("h")
    reg.counter("a").inc(3)
    reg.gauge("g").set(7.5)
    reg.histogram("h").record(100.0)
    snap = reg.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 7.5
    assert snap["histograms"]["h"]["count"] == 1
    assert snap["histograms"]["h"]["p50"] == pytest.approx(100.0)


def test_histogram_concurrent_records_lose_nothing():
    h = Histogram("t")
    n, threads = 2000, 8

    def work():
        for i in range(n):
            h.record(10.0 + (i % 50))

    ts = [threading.Thread(target=work) for _ in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert h.count == n * threads


# --- tracer ------------------------------------------------------------------

def test_tracer_rate_validation_and_determinism():
    with pytest.raises(ValueError, match="sample rate"):
        Tracer(1.5)
    with pytest.raises(ValueError, match="sample rate"):
        Tracer(-0.1)
    off = Tracer(0.0)
    assert not off.enabled
    assert all(off.sample() == 0 for _ in range(10))
    full = Tracer(1.0)
    ids = [full.sample() for _ in range(10)]
    assert all(ids) and len(set(ids)) == 10  # every call, all distinct
    half = Tracer(0.5)
    assert [bool(half.sample()) for i in range(8)] == [True, False] * 4


def test_tracer_record_drops_untraced_and_drains_in_order():
    tr = Tracer(1.0)
    tr.record("actor", 0, 123.0)          # untraced: must no-op
    assert tr.peek() == []
    tid = tr.new_id()
    tr.record("actor", tid, 10.0, actor=3)
    tr.record("add", tid, 20.0, shard=0)
    spans = tr.drain()
    assert [s["stage"] for s in spans] == ["actor", "add"]
    assert all(s["trace_id"] == tid for s in spans)
    assert spans[0]["actor"] == 3 and spans[1]["shard"] == 0
    assert tr.drain() == []               # drained means drained


def test_span_lands_in_the_profiler_trace_on_its_clock(tmp_path):
    """A span is a host event of a ``jax.profiler`` trace under its name;
    its buffered start is the event's start on the profiler's clock
    (``profile_start_time + start_ns``), and its parent is the enclosing
    span on the same thread, whose id it inherits."""
    tr = Tracer(1.0)
    tid = tr.new_id()
    with jax.profiler.trace(str(tmp_path)):
        with tr.span("test/outer", tid, k=1):
            with tr.span("test/inner"):
                time.sleep(0.005)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    start = dict(pd.find_plane_with_name("Task Environment").stats)[
        "profile_start_time"]
    events = {ev.name: ev for plane in pd.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events}
    spans = {s["stage"]: s for s in tr.peek()}
    for name in ("test/outer", "test/inner"):
        ev = events[name]
        assert abs(start + ev.start_ns - spans[name]["start_ns"]) < 1e6
        assert abs(ev.duration_ns - (spans[name]["end_ns"]
                                     - spans[name]["start_ns"])) < 1e6
    assert spans["test/inner"]["parent"] == "test/outer"
    assert spans["test/outer"]["parent"] is None
    assert spans["test/inner"]["trace_id"] == tid
    assert spans["test/outer"]["k"] == 1


def test_untraced_spans_buffer_nothing():
    """At sample rate 0 a span still annotates, but nothing is buffered;
    an id set inside the block is buffered."""
    off = Tracer(0.0)
    with off.span("a", off.sample()):
        with off.span("b"):
            pass
    assert off.peek() == []
    with off.span("late") as sp:
        sp.trace_id = 5
    assert [s["stage"] for s in off.peek()] == ["late"]


# --- sink + log --------------------------------------------------------------

def test_jsonl_sink_writes_metrics_and_spans(tmp_path):
    reg = MetricsRegistry()
    tr = Tracer(1.0)
    reg.counter("c").inc(5)
    tr.record("actor", tr.new_id(), 11.0)
    sink = JsonlSink(str(tmp_path), reg, tr, flush_s=30.0)  # manual flushes
    sink.start()
    sink.stop()  # final flush on stop even if the interval never fired
    metrics = [json.loads(line) for line in
               (tmp_path / METRICS_FILE).read_text().splitlines()]
    spans = [json.loads(line) for line in
             (tmp_path / SPANS_FILE).read_text().splitlines()]
    assert metrics[-1]["counters"]["c"] == 5
    assert metrics[-1]["ts"] > 0
    assert spans[0]["stage"] == "actor"
    assert spans[0]["end_ns"] - spans[0]["start_ns"] == 11000


def test_log_format_line_is_machine_parseable():
    line = obslog.format_line("async", t=12.34, generated=4096,
                              note="two words")
    assert line == "[async] t=12.3 generated=4096 note=two_words"
    fields = dict(tok.split("=", 1) for tok in line.split()[1:])
    assert fields["generated"] == "4096"


# --- trace-id propagation over the wire --------------------------------------

@pytest.mark.parametrize("kind", ["tcp", "shm"])
def test_trace_id_rides_the_frame_header(kind):
    """The id survives both byte paths: the shm ring (bulk data frames)
    and the socket (small/control frames), and absent ids read back 0."""
    lst = transport.listen("127.0.0.1", 0, accept_shm=True,
                           ring_bytes=1 << 16)
    box = {}

    def srv():
        conn = lst.accept(timeout=10.0)
        box["server"] = conn
        if kind != "tcp":
            conn.recv(timeout=1.0)  # serve the shm upgrade handshake

    th = threading.Thread(target=srv, daemon=True)
    th.start()
    client = transport.connect("127.0.0.1", lst.port, kind,
                               ring_bytes=1 << 16)
    th.join(timeout=10.0)
    server = box["server"]
    try:
        assert client.kind == kind
        rng = np.random.default_rng(0)
        big = wire.encode_tree({"x": rng.random(8000).astype(np.float32)})
        client.send(wire.ADD_BLOCK, big, trace_id=0xABC1)   # ring on shm
        assert server.recv(timeout=5.0)[0] == wire.ADD_BLOCK
        assert server.last_trace_id == 0xABC1
        client.send(wire.HELLO, wire.encode_json({"hi": 1}))  # untraced
        assert server.recv(timeout=5.0)[0] == wire.HELLO
        assert server.last_trace_id == 0
        small = wire.encode_tree({"y": np.arange(4, dtype=np.int32)})
        client.send(wire.PRIORITY_UPDATE, small, trace_id=0xABC2)  # socket
        assert server.recv(timeout=5.0)[0] == wire.PRIORITY_UPDATE
        assert server.last_trace_id == 0xABC2
    finally:
        for c in (client, server, lst):
            try:
                c.close()
            except Exception:
                pass


class _TraceRecordingFabric:
    """SampleSource-shaped fake that records the trace ids the gateway
    hands to add/write_back."""

    def __init__(self, batch=None):
        self.add_tids = []
        self.writeback_tids = []
        self._batch = batch

    def add(self, block, timeout=None, trace_id=0):
        self.add_tids.append(trace_id)
        return True

    def get_batch(self, timeout=None):
        return self._batch

    def write_back(self, indices, priorities, trace_id=0):
        self.writeback_tids.append(trace_id)


@pytest.mark.parametrize("kind", ["tcp", "shm"])
def test_trace_id_propagates_through_gateway_round_trip(kind):
    """Acceptance (satellite): a traced block's id crosses the wire into
    the gateway's span and the fabric's add; a traced learner round's id
    crosses back inside the coalesced PRIORITY_UPDATE into write_back —
    over tcp AND shm."""
    preset = tiny_preset()
    block = make_block(preset.apex, preset.env, preset.agent)
    from repro.core.sampling import LearnerBatch
    rng = np.random.default_rng(0)
    batch = LearnerBatch(rng.integers(0, 99, 8).astype(np.int32),
                         {"obs": rng.random((8, 4)).astype(np.float32)},
                         np.ones(8, np.float32))
    fabric = _TraceRecordingFabric(batch)
    gw_tel = Telemetry(tracer=Tracer(0.0))  # gateway records, never samples
    gw = ReplayGateway(fabric, ParamStore({}), telemetry=gw_tel).start()

    # ingest plane: actor-side frame header -> gateway span -> fabric.add
    conn = transport.connect(gw.host, gw.port, kind)
    try:
        assert conn.kind == kind
        conn.send(wire.HELLO, wire.encode_json(
            {"actor_id": 0, "protocol": wire.PROTOCOL_VERSION}))
        conn.send(wire.ADD_BLOCK, wire.encode_block_iov(block),
                  trace_id=0xBEEF)
        assert conn.recv(timeout=10.0)[0] == wire.ADD_ACK
    finally:
        conn.close()
    assert fabric.add_tids == [0xBEEF]
    gw_spans = gw_tel.tracer.peek()
    assert [s["stage"] for s in gw_spans] == ["gateway"]
    assert gw_spans[0]["trace_id"] == 0xBEEF

    # consume plane: client samples its own id; the coalesced
    # PRIORITY_UPDATE carries it back to the fabric's write_back
    src_tel = Telemetry(tracer=Tracer(1.0))
    src = RemoteFabricSource(gw.host, gw.port, transport=kind,
                             telemetry=src_tel).start()
    try:
        got = src.get_batch(timeout=5.0)
        assert got is not None
        tid = src.last_trace_id
        assert tid != 0
        src.write_back(got.indices, np.ones(8, np.float32), trace_id=tid)
        src.get_batch(timeout=5.0)  # flushes the parked round
        deadline = [None] * 100
        for _ in deadline:
            if fabric.writeback_tids:
                break
            threading.Event().wait(0.05)
        assert fabric.writeback_tids == [tid]
        sample_spans = [s for s in src_tel.tracer.peek()
                        if s["stage"] == "sample"]
        assert sample_spans and sample_spans[0]["trace_id"] == tid
        assert sample_spans[0]["transport"] == kind
    finally:
        src.stop()
        gw.stop()
    assert gw.error is None


# --- end to end: traced run + report (acceptance) ----------------------------

def test_traced_run_report_shows_every_stage(tmp_path):
    """A tiny traced async run must yield a report where every local
    pipeline stage (actor rollout and add, shard add, sample, learner
    fetch, step and write-back, shard write-back) shows nonzero counts,
    rates, and latency percentiles, plus queue-depth gauges, the derived
    *_us views still feeding ServiceStats, and the queue gaps between
    stages."""
    preset = tiny_preset()
    acfg = AsyncConfig(actor_threads=2, total_learner_steps=6,
                       max_seconds=60.0, seed=3,
                       metrics_dir=str(tmp_path), trace_sample_rate=1.0)
    res = run_async(preset.apex, acfg, preset.env, preset.agent,
                    preset.make_optimizer())
    assert res.stats["learner_steps"] >= 6
    assert res.service_stats.add_us > 0.0  # derived view still populated

    rep = report_lib.load_report(str(tmp_path))
    stages = ("actor/rollout", "actor/add", "shard/add", "sample",
              "learner/get_batch", "learner/step", "learner/write_back",
              "shard/writeback")
    for stage in stages:
        row = rep["stages"][stage]
        assert row["count"] > 0, stage
        assert row["rate_hz"] > 0.0, stage
        assert row["p50_us"] > 0.0, stage
    for edge in ("actor/add->shard/add", "learner/write_back->shard/writeback"):
        assert rep["gaps"][edge]["count"] > 0, edge
        assert rep["gaps"][edge]["p50_us"] >= 0.0, edge
    assert "shard0/replay_size" in rep["gauges"]
    assert rep["histograms"]["shard0/add_us"]["count"] > 0
    # the rendered table carries every stage row
    text = report_lib.render(rep)
    for stage in stages:
        assert stage in text
    # the CLI entry point renders the same directory (exit code 0)
    assert report_lib.main([str(tmp_path)]) == 0
    assert report_lib.main([str(tmp_path / "nope")]) == 2


def test_report_gives_each_wait_counter_as_a_share_of_its_thread(tmp_path):
    """``*_wait_us`` counters read as the share of the first-to-last
    snapshot span their thread sat blocked; other counters do not."""
    snaps = [{"ts": 100.0, "counters": {"learner/batch_wait_us": 1e6,
                                        "shard0/sync_wait_us": 0,
                                        "learner/steps": 10}},
             {"ts": 104.0, "counters": {"learner/batch_wait_us": 2e6,
                                        "shard0/sync_wait_us": 3e6,
                                        "shard1/sync_wait_us": 2e6,
                                        "learner/steps": 50}}]
    with open(tmp_path / METRICS_FILE, "w") as f:
        for snap in snaps:
            f.write(json.dumps(snap) + "\n")
    rep = report_lib.load_report(str(tmp_path))
    assert rep["waits"] == pytest.approx({"learner/batch_wait_us": 0.25,
                                          "shard0/sync_wait_us": 0.75,
                                          "shard1/sync_wait_us": 0.5})
    text = report_lib.render(rep)
    assert "learner/batch_wait_us = 25.0%" in text
    assert "shard0/sync_wait_us = 75.0%" in text
    # one snapshot spans no time: no share
    with open(tmp_path / METRICS_FILE, "w") as f:
        f.write(json.dumps(snaps[1]) + "\n")
    assert report_lib.load_report(str(tmp_path))["waits"] == {}


def test_trace_sample_rate_validated_by_async_config():
    preset = tiny_preset()
    acfg = AsyncConfig(trace_sample_rate=1.5)
    with pytest.raises(ValueError, match="trace_sample_rate"):
        run_async(preset.apex, acfg, preset.env, preset.agent,
                  preset.make_optimizer())
