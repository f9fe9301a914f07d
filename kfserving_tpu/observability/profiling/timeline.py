"""Engine event timeline: a bounded, allocation-light ring of
device-path events.

PRs 2-3 instrumented the *request* path (spans, stage histograms, the
flight recorder); the engine's wave/chunk/preemption machinery stayed
invisible at runtime — a p99 outlier pin showed the request's stages
but not *which* decode wave, prefill chunk, growth-HOLD window, or
preemption produced them.  This ring records every generator/engine
event with wall-clock start + duration, a track (host / device /
per-slot), and the owning request's trace id, so:

- `GET /debug/profile` renders it as a Chrome-trace/Perfetto timeline
  (trace_export.py);
- pinned flight-recorder entries embed the engine events overlapping
  the request's span (monitoring/__init__.py);
- bench runs derive HOLD / suppressed-wave summaries from it
  (trace_export.summarize).

`span()` is the one way to record a block the engine is in: on exit
it records the ring event above, and while a profiler capture is
active (`tracing.ProfilerControl` installs `annotate`) the same block
is also held in the profiler's own trace, on the profiler's clock and
on the thread that did the work, so device idle gaps can be put to an
engine phase.  This module imports no JAX: the factory is handed in.

Hot-path contract (the generator records from its scheduler loop and
its enqueue/fetch executor threads):

- **never blocks**: `record()` does O(1) work — one tuple build and a
  ring-slot store under a lock held for two statements.  No I/O, no
  resizing, no iteration.
- **bounded memory**: the ring is preallocated at `capacity` slots and
  overwrites oldest-first; a sustained event storm changes *which*
  events survive, never how much memory the ring holds.
- **reader-safe**: `snapshot()`/`window()` copy the slot references
  under the same lock; concurrent writers keep rotating underneath
  without invalidating the copy (events are immutable tuples).

Knobs: `KFS_TIMELINE_EVENTS` sizes the process ring (default 8192;
one decode wave records ~2 + active-slot events, so the default holds
minutes of steady decode).
"""

import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

DEFAULT_CAPACITY = 8192

# Track names.  "host" (the scheduler loop and everything recorded
# after the fact), "launch" (the generator's one launching thread)
# and "fetch" (its fetch workers) are the host-side tracks, "device"
# is shared; slot events carry track="slot" plus the slot index;
# "counter" events are point-in-time occupancy samples the exporter
# renders as Chrome counter series.
HOST, DEVICE, SLOT, COUNTER = "host", "device", "slot", "counter"
LAUNCH, FETCH = "launch", "fetch"

# Event tuple layout (immutable — readers copy references, writers
# never mutate a published event):
#   (start_epoch_s, dur_s, track, name, trace_id, slot, attrs)
Event = Tuple[float, float, str, str, Optional[str], int,
              Optional[Dict[str, Any]]]


class EngineTimeline:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = max(16, int(capacity))
        self._ring: List[Optional[Event]] = [None] * self.capacity
        self._next = 0          # total events ever recorded
        self._lock = threading.Lock()
        # `annotate(name, **attrs)` -> context manager that writes the
        # block into the profiler's trace; None outside a capture
        # (ProfilerControl.start/stop set and clear it).
        self.annotate: Optional[Callable[..., Any]] = None

    @classmethod
    def from_env(cls) -> "EngineTimeline":
        try:
            cap = int(os.environ.get("KFS_TIMELINE_EVENTS",
                                     DEFAULT_CAPACITY))
        except ValueError:
            cap = DEFAULT_CAPACITY
        return cls(cap)

    # -- writing (hot path) ------------------------------------------------
    def record(self, track: str, name: str, dur_s: float = 0.0,
               trace_id: Optional[str] = None, slot: int = -1,
               t_end: Optional[float] = None,
               attrs: Optional[Dict[str, Any]] = None) -> None:
        """Record one event ending at `t_end` (default: now) that ran
        for `dur_s` seconds (0 = instant).  `attrs` is stored by
        reference and must not be mutated after the call."""
        end = time.time() if t_end is None else t_end
        event: Event = (end - dur_s, dur_s, track, name, trace_id,
                        int(slot), attrs)
        with self._lock:
            self._ring[self._next % self.capacity] = event
            self._next += 1

    def span(self, track: str, name: str,
             trace_id: Optional[str] = None, slot: int = -1,
             **attrs: Any) -> "_Span":
        """Context manager around a block of engine work: records the
        ring event (start, duration) on exit and, during a profiler
        capture, holds the profiler's annotation for the block.  With
        no capture active it costs one small object and two clock
        reads over `record()`.  A failing annotation never reaches the
        engine: the ring event is recorded all the same."""
        return _Span(self, track, name, trace_id, slot, attrs)

    def counter(self, name: str, values: Dict[str, Any]) -> None:
        """Point-in-time occupancy sample (free blocks, active slots,
        pending depth) — rendered as a Chrome counter track."""
        self.record(COUNTER, name, attrs=values)

    # -- reading -----------------------------------------------------------
    @property
    def recorded(self) -> int:
        return self._next

    def snapshot(self, window_s: Optional[float] = None,
                 now: Optional[float] = None) -> List[Event]:
        """Events oldest-first, optionally only those whose span ends
        inside the trailing `window_s` seconds."""
        with self._lock:
            n = self._next
            if n <= self.capacity:
                events = [e for e in self._ring[:n]]
            else:
                head = n % self.capacity
                events = self._ring[head:] + self._ring[:head]
        events = [e for e in events if e is not None]
        if window_s is not None:
            cutoff = (now if now is not None else time.time()) \
                - float(window_s)
            events = [e for e in events if e[0] + e[1] >= cutoff]
        return events

    def window(self, t0: float, t1: float, limit: int = 64
               ) -> List[Dict[str, Any]]:
        """Events overlapping [t0, t1] as dicts (newest `limit`), for
        embedding in flight-recorder entries.  Tuples are filtered and
        sliced BEFORE dict conversion — this runs on every pin, and
        dict-ifying a full ring to keep 64 would tax exactly the
        tail-latency storms pins exist for."""
        limit = max(0, int(limit))
        if limit == 0:
            return []
        hits = [e for e in self.snapshot()
                if e[0] <= t1 and e[0] + e[1] >= t0]
        return [self.event_dict(e) for e in hits[-limit:]]

    @staticmethod
    def event_dict(event: Event) -> Dict[str, Any]:
        start, dur, track, name, trace_id, slot, attrs = event
        out: Dict[str, Any] = {
            "t": round(start, 6),
            "dur_ms": round(dur * 1000.0, 3),
            "track": track,
            "name": name,
        }
        if trace_id is not None:
            out["trace_id"] = trace_id
        if slot >= 0:
            out["slot"] = slot
        if attrs:
            out["attrs"] = dict(attrs)
        return out

    def clear(self) -> None:
        with self._lock:
            self._ring = [None] * self.capacity
            self._next = 0


class _Span:
    """One `EngineTimeline.span()` block (a class, not a generator:
    it sits on the engine's hot path)."""

    __slots__ = ("_timeline", "_track", "_name", "_trace_id", "_slot",
                 "_attrs", "_annotation", "_t0")

    def __init__(self, timeline, track, name, trace_id, slot, attrs):
        self._timeline = timeline
        self._track = track
        self._name = name
        self._trace_id = trace_id
        self._slot = slot
        self._attrs = attrs
        self._annotation = None

    def __enter__(self) -> "_Span":
        factory = self._timeline.annotate
        if factory is not None:
            try:
                # The profiler's annotation takes scalars; a list (a
                # launch's trace ids) stays in the ring event alone.
                annotation = factory(self._name, **{
                    k: v for k, v in self._attrs.items()
                    if isinstance(v, (int, float, str))})
                annotation.__enter__()
                self._annotation = annotation
            except Exception:  # tracing must never fail the engine
                self._annotation = None
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur_s = time.perf_counter() - self._t0
        self._timeline.record(self._track, self._name, dur_s=dur_s,
                              trace_id=self._trace_id, slot=self._slot,
                              attrs=self._attrs or None)
        if self._annotation is not None:
            try:
                self._annotation.__exit__(exc_type, exc, tb)
            except Exception:  # as above
                pass
        return False


# The process timeline: one serving process = one device path = one
# event ring (the same singleton shape as tracing.tracer).
TIMELINE = EngineTimeline.from_env()
