"""Pipeline tracing: follow one transition block across the planes.

A traced block picks up a compact 64-bit id at the actor, and every
stage that touches it — actor rollout and add, gateway decode/route,
shard add, sample, learner step, priority write-back — records a *span*
into a bounded in-process buffer:
``{stage, trace_id, parent, start_ns, end_ns, **fields}``. Between
processes the id rides a dedicated header field in the v3 wire frame
(:mod:`repro.net.wire`), so a block that crosses the gateway keeps its
identity without payload changes; ``trace_id == 0`` means untraced and
costs one integer compare on the hot path.

Sampling is deterministic, not random: the id source keeps a sequence
counter and traces every ``round(1/rate)``-th call. Determinism matters
here — tests can set rate 1.0 and assert exact propagation, and two runs
at the same rate trace the same block positions, making run-to-run span
diffs meaningful.

Two ways to record:

* ``with tracer.span(name, trace_id, **fields):`` around the work. The
  span always enters ``jax.profiler.TraceAnnotation(name)``: a TraceMe
  that costs next to nothing while no profiler runs and that puts the
  span, under its name, on the host plane of a ``jax.profiler`` trace,
  so an idle stretch of the device is named by the runtime stage that
  overlaps it. The span is buffered only when its id is nonzero (at
  sample rate 0, never). An id of 0 inherits the enclosing span's id,
  and ``parent`` is the enclosing span's name on the same thread. The id
  may be set inside the block (``sp.trace_id = ...``), for a stage that
  learns its id from the work it wraps.
* ``tracer.record(stage, trace_id, dur_us)`` after the fact, for a
  duration measured elsewhere; it ends at the call.

Clock: ``start_ns`` and ``end_ns`` are ``time.time_ns()``, the wall
clock (``CLOCK_REALTIME``) that the profiler's host events read
(TraceMe's ``GetCurrentTimeNanos``). ``jax.profiler.ProfileData`` gives
a host event's ``start_ns`` relative to the ``profile_start_time`` stat
of the trace's ``Task Environment`` plane, so a span's ``start_ns`` is
``profile_start_time + event.start_ns``, up to the few microseconds
between the two clock reads.

Span semantics per plane:

* **ingest**: actor → gateway → add share one id (the block's), so
  inter-stage gaps in :mod:`repro.obs.report` measure queue time between
  planes.
* **consume**: each sampled batch draws a fresh id at the sample stage;
  learn and write-back inherit it via ``SampleSource.last_trace_id``, so
  the sample → learn → writeback chain is linked per batch.

Durations for jitted stages are honest only under a device sync; traced
ops force ``block_until_ready`` (see ``ReplayShard._timed``), which is
why the sample rate default is 0 and the overhead bench gates the
enabled path at >= 0.98x disabled.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque

from jax.profiler import TraceAnnotation

# Bounded span buffer: at the default 1s sink flush interval even a
# rate-1.0 smoke run produces a few thousand spans/s; 64k absorbs sink
# stalls without unbounded growth. Overflow drops oldest (deque maxlen).
_DEFAULT_BUFFER_CAP = 65536


class Tracer:
    """Deterministic-sampled trace-id source plus a bounded span buffer."""

    def __init__(self, sample_rate: float = 0.0,
                 buffer_cap: int = _DEFAULT_BUFFER_CAP):
        if not 0.0 <= sample_rate <= 1.0:
            raise ValueError(
                f"trace sample rate must be in [0, 1], got {sample_rate}")
        self.sample_rate = float(sample_rate)
        # every N-th sample() call draws a real id; rate 0 disables.
        self._period = 0 if sample_rate <= 0.0 else max(
            1, round(1.0 / sample_rate))
        self._lock = threading.Lock()
        self._seq = 0
        self._next_id = 1
        # pid in the top bits keeps ids unique across actor processes
        # without coordination; 48 bits of counter is inexhaustible.
        self._id_prefix = (os.getpid() & 0xFFFF) << 48
        self._spans: deque[dict] = deque(maxlen=buffer_cap)

    @property
    def enabled(self) -> bool:
        return self._period > 0

    def new_id(self) -> int:
        """A fresh nonzero trace id, unconditionally (no sampling)."""
        with self._lock:
            tid = self._id_prefix | self._next_id
            self._next_id = (self._next_id + 1) & ((1 << 48) - 1) or 1
        return tid

    def sample(self) -> int:
        """A trace id for this event if it is sampled, else 0."""
        if self._period == 0:
            return 0
        with self._lock:
            seq = self._seq
            self._seq += 1
        if seq % self._period:
            return 0
        return self.new_id()

    def span(self, name: str, trace_id: int = 0, **fields) -> "Span":
        """Context manager around one stage's work (see module docstring)."""
        return Span(self, name, trace_id, fields)

    def record(self, stage: str, trace_id: int, dur_us: float,
               **fields) -> None:
        """Append one span of ``dur_us`` ending now. No-op for trace_id 0
        so call sites can pass the id through unconditionally."""
        if not trace_id:
            return
        end = time.time_ns()
        self._append(stage, trace_id, _enclosing_name(),
                     end - int(dur_us * 1e3), end, fields)

    def _append(self, stage, trace_id, parent, start_ns, end_ns,
                fields) -> None:
        span = {"stage": stage, "trace_id": trace_id, "parent": parent,
                "start_ns": start_ns, "end_ns": end_ns}
        if fields:
            span.update(fields)
        self._spans.append(span)  # deque.append is atomic under the GIL

    def drain(self) -> list[dict]:
        """Remove and return all buffered spans (sink flush path)."""
        out = []
        while True:
            try:
                out.append(self._spans.popleft())
            except IndexError:
                return out

    def peek(self) -> list[dict]:
        """Non-destructive copy of the buffer (test assertions)."""
        return list(self._spans)


# Per-thread stack of open spans: a span's parent is the enclosing one on
# its own thread.
_open = threading.local()


def _stack() -> list:
    try:
        return _open.stack
    except AttributeError:
        _open.stack = []
        return _open.stack


def _enclosing_name() -> str | None:
    stack = _stack()
    return stack[-1].name if stack else None


class Span:
    """One open span; see :meth:`Tracer.span`."""

    __slots__ = ("_tracer", "name", "trace_id", "_fields", "_ann",
                 "_parent", "_start")

    def __init__(self, tracer: Tracer, name: str, trace_id: int,
                 fields: dict):
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self._fields = fields

    def __enter__(self) -> "Span":
        stack = _stack()
        parent = stack[-1] if stack else None
        if parent is not None and not self.trace_id:
            self.trace_id = parent.trace_id
        self._parent = parent.name if parent is not None else None
        stack.append(self)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._start = time.time_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.time_ns()
        self._ann.__exit__(*exc)
        _stack().pop()
        if self.trace_id:
            self._tracer._append(self.name, self.trace_id, self._parent,
                                 self._start, end, self._fields)
