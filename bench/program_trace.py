"""Device seconds per layer of the program inside the ``bench_window`` span
of a profiler trace (``.xplane.pb``).

* Device operations and the window are those of ``bench/trace_reduce.py``
  (the same lines, clipped to the same span), so the layers and the
  device's idle time partition the window: where events nest (a ``while``
  around its body), each instant of busy time goes to the innermost event
  that covers it.
* An operation's layer is its program's name: ``jit_actor_*`` is
  ``actor``, ``jit_replay_*`` is ``replay``, ``jit_learner_*`` is
  ``learner``. In a program that fuses several phases (the lockstep
  ``jit_apex_step``) it is the innermost phase scope in the operation's
  metadata (``runtime/phases.py``): ``act`` is ``actor``; ``replay_add``,
  ``replay_sample`` and ``replay_writeback`` are ``replay``; ``learn`` is
  ``learner``. Everything else (the benchmark's own copies, eager ops, a
  fused program's ops outside every phase) is ``other``.
* The scopes come from the optimised HLO the profiler records for each
  program in the trace's ``/host:metadata`` plane (an ``Hlo Proto`` stat),
  read with a small protobuf decoder.

``named_s`` is the device time of operations whose layer came from a name
or a scope: a program without names reads 0 there, and its layers say
nothing.

No per-layer metric calls it yet: a ``--trace 1`` run deletes its trace
before the per-layer readers run, so a runtime (``bench/runtimes/*.py``)
has to reduce its trace into the run's record first (PERF.md, Open
questions). ``bench/tools/layer_shares.py`` calls it on a traced run of a
cell; ``bench/tools/record_program_trace.py`` records the trace its test
reads.

It shares four private helpers of ``bench/trace_reduce.py`` (``_SKIP_LINES``,
``_stats``, ``_enclosing``, ``_short``) so that both reductions see the same
operations in the same window; ``tests/test_program_trace.py`` holds the two
to the same busy time on a recorded trace, so a change to one that the
other does not follow fails there.
"""

from __future__ import annotations

import collections
import heapq

from bench import trace_reduce

LAYERS = ("actor", "replay", "learner", "other")
PREFIXES = {"jit_actor_": "actor", "jit_replay_": "replay", "jit_learner_": "learner"}
SCOPES = {"act": "actor", "replay_add": "replay", "replay_sample": "replay",
          "replay_writeback": "replay", "learn": "learner"}


def reduce(path: str, annotation: str = "bench_window") -> dict | None:
    """``{"window_s", "busy_s", "named_s", "layers": {layer: seconds}}``,
    device seconds averaged over the device planes; None without the
    window or device operations."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == annotation:
                        s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                        window = (s, e) if window is None else (min(window[0], s), max(window[1], e))
        elif plane.name.startswith("/device:"):
            devices.append(plane)
    if window is None:
        return None
    w0, w1 = window
    scopes = None
    per_plane = []
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        op_lines = [lines["XLA Ops"]] if "XLA Ops" in lines else [
            ln for ln in plane.lines if not ln.name.startswith(trace_reduce._SKIP_LINES)]
        modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                         for ev in (lines["XLA Modules"].events if "XLA Modules" in lines else []))
        events = []
        for ln in op_lines:
            for ev in ln.events:
                s, e = max(ev.start_ns, w0), min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                st = trace_reduce._stats(ev)
                module = str(st.get("hlo_module") or trace_reduce._enclosing(modules, ev.start_ns))
                layer = _prefix_layer(module)
                if layer is None:
                    if scopes is None:
                        scopes = _scopes_from_trace(path) or {}
                    op = trace_reduce._short(str(st.get("hlo_op") or ev.name))
                    layer = _scope_of(scopes, module, op)
                events.append((s, e, layer))
        if events:
            per_plane.append(_innermost(events))
    if not per_plane:
        return None
    n = len(per_plane)
    layers = {k: sum(p.get(k, 0.0) for p in per_plane) / n / 1e9 for k in LAYERS + ("named",)}
    named = layers.pop("named")
    return {"window_s": (w1 - w0) / 1e9, "busy_s": sum(layers.values()),
            "named_s": named, "layers": layers}


def _prefix_layer(module: str):
    for prefix, layer in PREFIXES.items():
        if module.startswith(prefix):
            return layer
    return None


def _scope_of(scopes: dict, module: str, op: str):
    """The op's layer from the innermost phase scope of its op name, or
    None where its program's HLO is not known or the op has no scope."""
    ops = scopes.get(module) or scopes.get(trace_reduce._short(module))
    if not ops or op not in ops:
        return None
    for part in reversed(ops[op].split("/")):
        if part in SCOPES:
            return SCOPES[part]
    return None


def _innermost(events) -> collections.Counter:
    """Nanoseconds per layer of the union of ``(start, end, layer)``, each
    instant going to the latest-starting (on a tie, the shortest) event
    that covers it. A layer of None counts as ``other``; time whose layer
    came from a name or scope is also counted under ``named``."""
    events.sort(key=lambda ev: (ev[0], ev[1]))
    points = sorted({p for s, e, _ in events for p in (s, e)})
    out = collections.Counter()
    live: list = []
    j = 0
    for a, b in zip(points, points[1:]):
        while j < len(events) and events[j][0] <= a:
            s, e, layer = events[j]
            heapq.heappush(live, (-s, e, j, layer))
            j += 1
        while live and live[0][1] <= a:
            heapq.heappop(live)
        if live:
            layer = live[0][3]
            out[layer or "other"] += b - a
            if layer is not None:
                out["named"] += b - a
    return out


# ---------------------------------------------------------------------------
# Op names from the HLO the trace records
# ---------------------------------------------------------------------------

def _scopes_from_trace(path: str) -> dict:
    """``{program name as in the trace: {instruction: op_name}}`` from the
    ``Hlo Proto`` stats of the trace's ``/host:metadata`` plane. Field
    numbers: xplane.proto (XSpace.planes 1; XPlane.name 2, event_metadata
    4, stat_metadata 5; XEventMetadata.name 2, stats 5; XStat.metadata_id
    1, bytes_value 6; XStatMetadata.name 2) and hlo.proto (HloProto
    .hlo_module 1; HloModuleProto.computations 3; HloComputationProto
    .instructions 2; HloInstructionProto.name 1, metadata 7; OpMetadata
    .op_name 2)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for plane in _get(buf, 1):
        if _str(plane, 2) != "/host:metadata":
            continue
        stat_names = {_int(meta, 1): _str(meta, 2)
                      for entry in _get(plane, 5) for meta in _get(entry, 2)}
        for entry in _get(plane, 4):
            for meta in _get(entry, 2):
                for stat in _get(meta, 5):
                    if stat_names.get(_int(stat, 1)) == "Hlo Proto":
                        for proto in _get(stat, 6):
                            out[_str(meta, 2)] = _op_names(proto)
    return out


def _op_names(hlo_proto) -> dict:
    ops = {}
    for module in _get(hlo_proto, 1):
        for comp in _get(module, 3):
            for inst in _get(comp, 2):
                for meta in _get(inst, 7):
                    if op_name := _str(meta, 2):
                        ops[_str(inst, 1)] = op_name
    return ops


def _varint(buf, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value, (start, end)) of one protobuf message: the
    value is an int for a varint, a memoryview for a length-delimited
    field, None for fixed widths; ``buf[start:end]`` is the whole field,
    key included."""
    i, n = 0, len(buf)
    while i < n:
        start = i
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value, (start, i)


def _get(buf, field: int):
    return (v for f, v, _ in _fields(buf) if f == field)


def _int(buf, field: int) -> int:
    return next((v for f, v, _ in _fields(buf) if f == field and isinstance(v, int)), 0)


def _str(buf, field: int) -> str:
    return bytes(next(_get(buf, field), b"")).decode()
