"""Shared actor inference server: K clients, one device dispatch.

The paper's FPS economics (§4.1) rest on actors being nearly free relative to
the learner — Ape-X runs 360 actors at ~1/139th of the learner's FPS each —
which in practice requires *batching* actor policy evaluation so the device
is dispatched once per wave of actors, not once per actor. Clients submit
their ``ActorSlice`` to a server thread that runs **one** jitted
``vmap(act_phase)`` call over the stacked slices (parameters broadcast),
then hands each client its own slice of the stacked results.

Two scheduling modes share the engine:

* ``mode="wave"`` — classic wave coalescing: after the first pending
  request the server waits up to ``coalesce_s`` for the rest of the wave,
  then pads short waves to ``max_batch`` by replicating the last request
  (one compiled shape forever; padding lanes recompute a duplicate rollout
  and are dropped). The padding tax is recorded honestly:
  ``inference/pad_fraction`` gauge plus ``inference/padded_lanes`` /
  ``inference/wave_lanes`` lifetime counters.
* ``mode="slots"`` — slot-scheduled continuous batching: no coalesce
  window. Pending requests are admitted from a deque into the compiled
  step's ``max_batch`` slots the moment the previous dispatch returns, and
  every slot is freed the step its request finishes (actor rollouts are
  one-step requests, so admission latency is the only scheduling variable
  — there is no batch-wide barrier for a straggler to stretch).
  ``inference/slot_occupancy`` gauges how full the step runs.

Semantics vs per-actor dispatch (both modes):

* Numerics are identical per actor — ``act_phase`` is pure and the vmap axis
  is the actor axis, so each actor's rollout uses its own rng/env state and
  its shard's slice of the exploration ladder. A full wave dispatches the
  exact same stacked content in either mode, so per-actor results are
  bit-identical between them (property-tested).
* Parameter staleness: wave mode refreshes its ``ParamStore`` snapshot
  every ``param_sync_period`` *dispatches* (a dispatch is one rollout per
  participating actor). Slot mode refreshes at every dispatch boundary —
  the hot-swap contract: a request finishes on the snapshot current when
  its dispatch was admitted, no request is ever dropped for a version
  change, and ``InferenceStats.hot_swaps`` counts the swaps taken.

Stop/error propagation is event-driven: a parked ``act()`` wakes the
instant ``stop()`` runs or the server thread dies — there is no poll
quantum between a failure and the client seeing it.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.obs import Telemetry
from repro.runtime import phases
from repro.runtime.params import ParamStore


@dataclasses.dataclass
class InferenceStats:
    requests: int = 0        # act() calls served
    dispatches: int = 0      # jitted batched calls issued
    full_waves: int = 0      # dispatches that batched max_batch requests
    param_refreshes: int = 0
    hot_swaps: int = 0       # slot mode: dispatch-boundary param swaps taken
                             # with requests in flight elsewhere (zero drops)


class _Request:
    __slots__ = ("aslice", "shard_id", "event", "result")

    def __init__(self, aslice: phases.ActorSlice, shard_id: int):
        self.aslice = aslice
        self.shard_id = shard_id
        self.event = threading.Event()
        self.result = None


class InferenceServer:
    """Batches ``act_phase`` across clients into one jitted call."""

    def __init__(self, cfg, env, agent, store: ParamStore, *,
                 max_batch: int, param_sync_period: int | None = None,
                 coalesce_s: float = 0.002, mode: str = "wave",
                 telemetry: Telemetry | None = None):
        if mode not in ("wave", "slots"):
            raise ValueError(
                f"InferenceServer mode must be 'wave' or 'slots', got "
                f"{mode!r}")
        self._cfg = cfg
        self._mode = mode
        self._tel = telemetry if telemetry is not None else Telemetry.local()
        # Wave *issue* latency (stack + jit dispatch, not synced — syncing
        # would serialize the pipeline this server exists to keep full)
        # and wave occupancy, for the obs report's inference row.
        self._h_wave = self._tel.histogram("inference/wave_us")
        self._g_wave = self._tel.gauge("inference/wave_size")
        # The padding tax, made visible (wave mode replicates the last
        # request into idle lanes): instantaneous fraction plus lifetime
        # lane counters so the report can state a run-wide pad fraction.
        self._g_pad = self._tel.gauge("inference/pad_fraction")
        self._g_occupancy = self._tel.gauge("inference/slot_occupancy")
        self._c_wave_lanes = self._tel.counter("inference/wave_lanes")
        self._c_padded = self._tel.counter("inference/padded_lanes")
        self._store = store
        self._max_batch = max_batch
        self._sync_period = (param_sync_period if param_sync_period is not None
                             else cfg.param_sync_period)
        self._coalesce_s = coalesce_s
        self._snap = store.get()

        def actor_rollout_batched(params, slices, sids):
            return jax.vmap(lambda sl, sid: phases.act_phase(
                cfg, env, agent, params, sl, sid))(slices, sids)

        self._fn = jax.jit(actor_rollout_batched)

        self._pending: collections.deque[_Request] = collections.deque()
        self._cond = threading.Condition()
        self._stop = threading.Event()
        self._stats_lock = threading.Lock()
        self.stats = InferenceStats()
        self.error: BaseException | None = None
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="inference-server")

    # -- lifecycle ----------------------------------------------------------

    def start(self) -> "InferenceServer":
        self._thread.start()
        return self

    def stop(self, join: bool = True) -> None:
        self._stop.set()
        with self._cond:
            # Wake parked clients directly: their requests will never be
            # taken, and act() must not sit out a poll quantum to notice.
            for req in self._pending:
                req.event.set()
            self._pending.clear()
            self._cond.notify_all()
        if join and self._thread.is_alive():
            self._thread.join()

    def warm(self, aslice: phases.ActorSlice) -> int:
        """Compile the full-wave batched call before the clock starts;
        returns the measured per-actor transitions-per-block."""
        slices = jax.tree.map(
            lambda x: jnp.stack([x] * self._max_batch), aslice)
        sids = jnp.arange(self._max_batch, dtype=jnp.int32)
        _, blocks, _ = jax.block_until_ready(
            self._fn(self._snap.params, slices, sids))
        return int(blocks.priorities.shape[1])

    def snapshot(self) -> InferenceStats:
        with self._stats_lock:
            return dataclasses.replace(self.stats)

    # -- client side ---------------------------------------------------------

    def act(self, aslice: phases.ActorSlice, shard_id: int,
            ) -> tuple[phases.ActorSlice, phases.TransitionBlock, dict] | None:
        """Submit one rollout request and wait for its slice of the batched
        result. Returns None when the server (or runtime) is stopping."""
        req = _Request(aslice, shard_id)
        with self._cond:
            # Registration and the stop/error check share the lock, so a
            # request is either appended while the server is live (stop()
            # or the death path will wake it) or refused here — it can
            # never slip into a queue nobody will drain.
            if self.error is not None:
                raise RuntimeError("inference server died") from self.error
            if self._stop.is_set():
                return None
            self._pending.append(req)
            self._cond.notify_all()
        req.event.wait()
        if req.result is None:
            if self.error is not None:
                raise RuntimeError("inference server died") from self.error
            return None  # stopped before (or during) this request's dispatch
        return req.result

    # -- server loop --------------------------------------------------------

    def _take_wave(self) -> list[_Request]:
        with self._cond:
            while not self._pending and not self._stop.is_set():
                self._cond.wait(timeout=0.05)
            if self._stop.is_set():
                return []
            if self._mode == "wave":
                # Coalesce: wait out the window for the rest of the wave.
                deadline = time.monotonic() + self._coalesce_s
                while (len(self._pending) < self._max_batch
                       and not self._stop.is_set()):
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
            # Slot admission: whatever is pending right now fills free
            # slots, nothing waits for stragglers.
            wave = [self._pending.popleft()
                    for _ in range(min(len(self._pending), self._max_batch))]
            return wave

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                wave = self._take_wave()
                if not wave:
                    continue
                self._dispatch(wave)
        except BaseException as e:  # noqa: BLE001
            self.error = e
        finally:
            with self._cond:  # unblock any client still parked on a request
                for req in self._pending:
                    req.event.set()
                self._pending.clear()

    def _refresh_params(self) -> None:
        """Dispatch-boundary snapshot policy (caller holds _stats_lock).
        Wave mode: every ``param_sync_period`` dispatches. Slot mode: every
        dispatch — requests admitted into this dispatch complete on the
        snapshot taken here, so a version change never drops an in-flight
        request; it just bounds staleness at one dispatch."""
        if self._mode == "slots":
            snap = self._store.get()
            if snap.version != self._snap.version:
                self._snap = snap
                self.stats.param_refreshes += 1
                self.stats.hot_swaps += 1
        elif self.stats.dispatches % self._sync_period == 0:
            self._snap = self._store.get()
            self.stats.param_refreshes += 1

    def _dispatch(self, wave: list[_Request]) -> None:
        with self._stats_lock:
            self._refresh_params()
            self.stats.dispatches += 1
            self.stats.requests += len(wave)
            self.stats.full_waves += int(len(wave) == self._max_batch)
        try:
            # Pad short waves to max_batch by replicating the last request:
            # one compiled shape forever instead of one trace per wave size
            # (padding lanes recompute a duplicate rollout and are dropped
            # — counted below so the tax is visible in the obs report).
            pad = self._max_batch - len(wave)
            reqs = wave + [wave[-1]] * pad
            self._g_pad.set(pad / self._max_batch)
            self._g_occupancy.set(len(wave) / self._max_batch)
            self._c_wave_lanes.inc(self._max_batch)
            self._c_padded.inc(pad)
            t0 = time.perf_counter()
            slices = jax.tree.map(lambda *xs: jnp.stack(xs),
                                  *[r.aslice for r in reqs])
            sids = jnp.asarray([r.shard_id for r in reqs], jnp.int32)
            out = self._fn(self._snap.params, slices, sids)
            self._h_wave.record(1e6 * (time.perf_counter() - t0))
            self._g_wave.set(len(wave))
            for i, req in enumerate(wave):
                req.result = jax.tree.map(lambda x: x[i], out)
        except BaseException as e:  # noqa: BLE001
            self.error = e  # recorded *before* clients wake, so act() raises
            raise
        finally:
            # Whatever failed above, a taken wave must never park its
            # clients forever: wake them (result stays None; act()
            # re-raises).
            for req in wave:
                req.event.set()
