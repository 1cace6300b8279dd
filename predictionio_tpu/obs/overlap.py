"""Overlap accounting for the staged serving pipeline (ISSUE 9).

The whole point of splitting the serving batch path into assemble →
dispatch → readback stages is that the device computes WHILE the host
parses/supplements the next batch and serializes the previous one. A
claim like that needs a number, not an architecture diagram:
:class:`OverlapTracker` accrues wall-clock into per-track busy counters
and into an overlap counter whenever the device track and at least one
host track are simultaneously active. The engine server exports the
fractions as ``pio_pipeline_device_idle_fraction`` and
``pio_pipeline_overlap_fraction`` (docs/observability.md): stages
run one after another show overlap ≈ 0; the pipeline under load must
not.

Beside the tracks runs the **starvation clock** (ISSUE 24): every
batch in the pipeline is in one of four places (:data:`STATES` less
``empty``), and the wall time since the first batch accrues into
exactly ONE state, the first of :data:`STATES` that holds a batch —
so "the device had nothing queued" splits into who kept it waiting:
the launch (``launching``), the dispatch thread (``staged``), parse
and supplement (``assembling``) or the traffic (``empty``). Exported
as ``pio_pipeline_state_seconds_total{state}``.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional

#: the accelerator track; every other track name counts as host work
DEVICE_TRACK = "device"

#: the starvation clock's states in priority order: ``enqueued`` (a
#: batch's executable is queued on the device and its results are not
#: back: the device has work as far as the host can know), else
#: ``launching`` (a dispatch call is in progress), else ``staged`` (an
#: assembled batch waits for the dispatch thread), else ``assembling``
#: (a picked-up batch is being parsed and supplemented), else ``empty``
#: (no batch anywhere: nothing arrived, or nothing was picked up)
STATES = ("enqueued", "launching", "staged", "assembling", "empty")
_HOLDING, _EMPTY = STATES[:-1], STATES[-1]


class OverlapTracker:
    """O(1)-per-transition wall-clock accounting over named activity
    tracks. ``enter(track)``/``exit(track)`` bracket activity (tracks
    are counted, so concurrent batches nest); between any two
    transitions the elapsed time accrues into every active track's
    busy counter, and into the overlap counter when ``"device"`` and
    any host track were both active. The wall-clock origin is the
    FIRST ``enter`` — idle time before traffic ever arrived does not
    dilute the fractions.

    :meth:`step` is the general transition the staged pipeline uses:
    one lock, any of leave-track / enter-track / leave-state /
    enter-state, at a time the CALLER stamped (the batch's own record,
    so the tracker reads no clock of its own there). Stamps from
    different threads can arrive a few microseconds out of order; the
    tracker's clock only moves forward, so the states always sum to
    the wall time."""

    def __init__(self, time_fn=time.monotonic):
        self._time = time_fn
        self._lock = threading.Lock()
        self._active: Dict[str, int] = {}
        self._busy: Dict[str, float] = {}
        self._overlap = 0.0
        self._t0 = None
        self._last = None
        self._holding: Dict[str, int] = dict.fromkeys(_HOLDING, 0)
        self._state_sec: Dict[str, float] = dict.fromkeys(STATES, 0.0)

    # ptpu: guarded-by[_lock] — internal accrual step, only ever called
    # with self._lock held by step/snapshot
    def _accrue(self, now: float) -> None:
        if self._last is None:
            self._last = now
            return
        dt = now - self._last
        if dt <= 0:
            return
        self._last = now
        device = self._active.get(DEVICE_TRACK, 0) > 0
        host = any(n > 0 for t, n in self._active.items()
                   if t != DEVICE_TRACK)
        for t, n in self._active.items():
            if n > 0:
                self._busy[t] = self._busy.get(t, 0.0) + dt
        if device and host:
            self._overlap += dt
        for state in _HOLDING:
            if self._holding[state] > 0:
                break
        else:
            state = _EMPTY
        self._state_sec[state] += dt

    def step(self, now: Optional[float] = None, *,
             exit: Optional[str] = None, enter: Optional[str] = None,
             leave: Optional[str] = None,
             join: Optional[str] = None) -> int:
        """One transition at ``now`` (the tracker's clock when None):
        ``exit``/``enter`` a track, and move one batch out of state
        ``leave`` and/or into state ``join``. Returns the PRIOR active
        count of the entered track (0 when none was entered)."""
        with self._lock:
            if now is None:
                now = self._time()
            if self._t0 is None:
                self._t0 = now
            self._accrue(now)
            if exit is not None:
                self._active[exit] = max(
                    self._active.get(exit, 0) - 1, 0)
            prev = 0
            if enter is not None:
                prev = self._active.get(enter, 0)
                self._active[enter] = prev + 1
            if leave is not None:
                self._holding[leave] = max(self._holding[leave] - 1, 0)
            if join is not None:
                self._holding[join] += 1
            return prev

    def enter(self, track: str) -> int:
        """Mark ``track`` active; returns the PRIOR active count (a
        dispatch stage uses ``enter("device") > 0`` as "this launch
        overlapped an in-flight batch")."""
        return self.step(enter=track)

    def exit(self, track: str) -> None:
        self.step(exit=track)

    def active(self, track: str) -> int:
        with self._lock:
            return self._active.get(track, 0)

    def snapshot(self) -> dict:
        """Cumulative view: wall seconds since first activity, per-track
        busy seconds, device busy/idle fractions, the overlap fraction
        (device ∧ host active) and the starvation clock's seconds per
        state (they sum to the wall seconds). In-progress intervals
        are folded in up to now."""
        with self._lock:
            wall = 0.0
            if self._t0 is not None:
                self._accrue(self._time())
                wall = self._last - self._t0
            busy = dict(self._busy)
            overlap = self._overlap
            states = dict(self._state_sec)
        device_busy = busy.get(DEVICE_TRACK, 0.0)
        return {
            "wall_sec": wall,
            "busy_sec": busy,
            "device_busy_sec": device_busy,
            "device_busy_fraction": (device_busy / wall) if wall > 0
            else 0.0,
            "device_idle_fraction": (1.0 - device_busy / wall)
            if wall > 0 else 1.0,
            "overlap_sec": overlap,
            "overlap_fraction": (overlap / wall) if wall > 0 else 0.0,
            "state_sec": states,
        }

    def device_idle_fraction(self) -> float:
        return self.snapshot()["device_idle_fraction"]

    def overlap_fraction(self) -> float:
        return self.snapshot()["overlap_fraction"]

    def state_seconds(self, state: str) -> float:
        return self.snapshot()["state_sec"][state]
