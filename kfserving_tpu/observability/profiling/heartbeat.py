"""The process's one heartbeat: for how long was the process held, and
by whom.

One daemon thread a process, alive while anything watches a loop
(`HEARTBEAT.watch`: an engine whose pipeline runs on the loop, the
sanitizer's stall watchdog).  Eight times a second it

- posts a tick to every watched event loop (`call_soon_threadsafe`) and
  observes, when the tick has run, how late the loop ran it:
  `kfserving_tpu_process_held_ms{what="loop"}`.  A handler that holds the
  loop shows here and nowhere else;
- observes how late it woke itself against its own interval:
  `{what="interpreter"}`.  This thread only sleeps, so what delays it
  holds every thread: a collection, a C call that keeps the interpreter
  lock;
- takes up the collections `gc.callbacks` stamped since the last beat:
  `kfserving_tpu_process_gc_pause_ms{generation}`;
- calls each watcher's `beat` (`InflightTable.check`: the oldest-age
  gauge and the stall report, made *while* a loop is held, since this
  thread does not run on it).

A pause of `PAUSE_MS` or more logs one WARNING line, a line of JSON after
`process paused:`, pinned into the flight recorder where one is attached:
`what` (`gc | loop | interpreter`), `ms`, for a collection `generation`
and `collected`, for a held loop the loop thread's innermost frames
taken by this thread while the loop was still held, and every watcher's
in-flight rows.  One pause gives one line, under its most specific name:
a collection also delays this thread and the loop's tick, and the time a
named pause covers is taken off what a later name would claim.  Reading
them beside `generator_program_inflight_ms`: a long round trip with a
`gc` line was the collector; with a `loop` line, the handler in its
frames; with an `interpreter` line, native code that kept the
interpreter lock; with none of the three, the runtime or the device.

The collector's callbacks run on whichever thread allocates, possibly
inside a metric's lock, so they take no lock and call nothing of the
registry: two clock reads and a deque append a collection.  Everything
else happens on the heartbeat thread.  This module imports no JAX and
nothing of `engine/` or `reliability/`: watchers hand their callables in.
"""

import asyncio
import gc
import json
import logging
import sys
import threading
import time
import traceback
from collections import deque
from typing import Any, Callable, Dict, List, Optional

from kfserving_tpu.observability import metrics as obs

logger = logging.getLogger("kfserving_tpu.observability.heartbeat")

INTERVAL_S = 0.125
PAUSE_MS = 250.0
REPORT_PREFIX = "process paused:"
REPORT_FRAMES = 16   # innermost frames of the held loop's thread
PIN = "process_paused"


def stack_lines(frame, limit: int = REPORT_FRAMES) -> List[str]:
    """A thread's Python stack as a report lists it, innermost frame
    last."""
    return [f"{f.filename}:{f.lineno} {f.name}"
            for f in traceback.extract_stack(frame, limit)]


def _running_loop():
    try:
        return asyncio.get_running_loop()
    except RuntimeError:
        return None


class Watch:
    """One `HEARTBEAT.watch()`: what a watcher wants of each beat."""

    __slots__ = ("loop", "beat", "rows", "held")

    def __init__(self, loop, beat, rows, held):
        self.loop, self.beat, self.rows, self.held = loop, beat, rows, held


class _Loop:
    """One watched event loop: the tick that is out, and its thread."""

    __slots__ = ("sent_t", "landed", "ident", "frames")

    def __init__(self):
        self.sent_t: Optional[float] = None
        self.landed: deque = deque()   # (sent, ran) of ticks that ran
        self.ident: Optional[int] = None
        self.frames: Optional[List[str]] = None   # taken while held


class Heartbeat:
    def __init__(self):
        self._lock = threading.Lock()   # watches and the thread's life
        self._watches: List[Watch] = []
        self._loops: Dict[Any, _Loop] = {}
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # Collections: stamped by the collector's callbacks, taken up by
        # the heartbeat thread.
        self._gc_t0: Optional[float] = None
        self._collections: deque = deque()
        # The latest pauses that have a line, as (start, end): what a
        # later name may not claim again.
        self._named: deque = deque(maxlen=8)
        # A FlightRecorder, attached by the server that owns one.
        self.recorder = None

    # -- watchers ------------------------------------------------------------
    def watch(self, loop, beat: Optional[Callable[[], Any]] = None,
              rows: Optional[Callable[[], List[Dict[str, Any]]]] = None,
              held: Optional[Callable[[float], None]] = None) -> Watch:
        """Heartbeat `loop` until `unwatch`.  Each beat calls `beat()`,
        and `held(ms)` with how late the loop ran a tick, or is running
        the one that is out; `rows()` is listed in a pause's line.  The
        first watch starts the thread."""
        watch = Watch(loop, beat, rows, held)
        with self._lock:
            self._watches.append(watch)
            state = self._loops.setdefault(loop, _Loop())
            if state.ident is None and _running_loop() is loop:
                state.ident = threading.get_ident()
            if self._thread is None:
                if self._on_collection not in gc.callbacks:
                    gc.callbacks.append(self._on_collection)
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=self._run, args=(self._stop,),
                    name="kfs-heartbeat", daemon=True)
                self._thread.start()
        return watch

    def unwatch(self, watch: Optional[Watch]) -> None:
        """The last watch to go stops the thread."""
        with self._lock:
            if watch in self._watches:
                self._watches.remove(watch)
            thread = self._sweep()
        if thread is not None and thread is not threading.current_thread():
            thread.join(timeout=2.0)

    def _sweep(self) -> Optional[threading.Thread]:
        """Under the lock: forget loops nobody watches; with none left,
        stop the thread and give it, to be joined."""
        watched = {w.loop for w in self._watches}
        for loop in [l for l in self._loops if l not in watched]:
            del self._loops[loop]
        if self._watches or self._thread is None:
            return None
        if self._on_collection in gc.callbacks:
            gc.callbacks.remove(self._on_collection)
        self._gc_t0 = None
        self._stop.set()
        thread, self._thread = self._thread, None
        return thread

    def watching(self) -> int:
        with self._lock:
            return len(self._watches)

    # -- the collector's thread, whichever it is -------------------------------
    def _on_collection(self, phase: str, info: Dict[str, int]) -> None:
        now = time.perf_counter()
        if phase == "start":
            self._gc_t0 = now
        elif self._gc_t0 is not None:
            self._collections.append(
                (info["generation"], self._gc_t0, now, info["collected"]))
            self._gc_t0 = None

    # -- a watched loop's thread -------------------------------------------------
    @staticmethod
    def _tick(state: _Loop, sent_t: float) -> None:
        state.ident = threading.get_ident()
        state.landed.append((sent_t, time.perf_counter()))
        state.sent_t = None

    # -- the heartbeat thread ------------------------------------------------
    def _run(self, stop: threading.Event) -> None:
        for what in ("loop", "interpreter"):
            obs.process_held_ms().labels(what=what)
        for generation in range(3):
            obs.process_gc_pause_ms().labels(generation=generation)
        due = time.perf_counter() + INTERVAL_S
        while not stop.wait(max(0.0, due - time.perf_counter())):
            try:
                self._beat(due, time.perf_counter())
            except Exception:  # the heartbeat outlives a broken watcher
                logger.exception("heartbeat failed")
            # From the beat's end: a long report is not a late wake.
            due = time.perf_counter() + INTERVAL_S

    def _beat(self, due: float, now: float) -> None:
        self._collections_seen()
        late_ms = max(0.0, now - due) * 1e3
        obs.process_held_ms().labels(what="interpreter").observe(late_ms)
        # A collection whose stop has not been stamped yet is the
        # collector's all the same.
        collecting = self._gc_t0
        if collecting is not None and collecting < now:
            self._named.append((collecting, now))
        if self._unnamed_ms(due, now) >= PAUSE_MS:
            self._paused("interpreter", late_ms, due, now)
        with self._lock:
            watches = list(self._watches)
            loops = list(self._loops.items())
        for loop, state in loops:
            self._look_at(loop, state, watches, now)
        for watch in watches:
            if watch.beat is not None:
                watch.beat()

    def _collections_seen(self) -> None:
        while self._collections:
            generation, t0, t1, collected = self._collections.popleft()
            ms = (t1 - t0) * 1e3
            obs.process_gc_pause_ms().labels(
                generation=generation).observe(ms)
            if ms >= PAUSE_MS:
                self._paused("gc", ms, t0, t1, generation=generation,
                             collected=collected)

    def _look_at(self, loop, state: _Loop, watches: List[Watch],
                 now: float) -> None:
        """One loop's ticks: those that ran since the last beat, the
        one that is out, and the next."""
        held = [w.held for w in watches
                if w.loop is loop and w.held is not None]
        while state.landed:
            sent_t, ran_t = state.landed.popleft()
            ms = (ran_t - sent_t) * 1e3
            obs.process_held_ms().labels(what="loop").observe(ms)
            for tell in held:
                tell(ms)
            frames, state.frames = state.frames, None
            if self._unnamed_ms(sent_t, ran_t) >= PAUSE_MS:
                self._paused("loop", ms, sent_t, ran_t, frames=frames)
        if loop.is_closed():
            with self._lock:
                self._watches = [w for w in self._watches
                                 if w.loop is not loop]
                self._sweep()
            return
        if state.sent_t is not None:
            # Out for a whole interval: where the loop's thread is now,
            # should this come to a pause's line.
            state.frames = self._frames_of(state.ident)
            for tell in held:
                tell((now - state.sent_t) * 1e3)
            return
        state.sent_t = now
        try:
            loop.call_soon_threadsafe(self._tick, state, now)
        except RuntimeError:   # closed since the look above
            state.sent_t = None

    def _unnamed_ms(self, t0: float, t1: float) -> float:
        """Of the interval, what no pause with a line covers."""
        covered = sum(max(0.0, min(t1, b) - max(t0, a))
                      for a, b in self._named)
        return (t1 - t0 - covered) * 1e3

    @staticmethod
    def _frames_of(ident: Optional[int]) -> Optional[List[str]]:
        frame = sys._current_frames().get(ident)
        return None if frame is None else stack_lines(frame)

    def _paused(self, what: str, ms: float, t0: float, t1: float,
                **more: Any) -> None:
        self._named.append((t0, t1))
        report = {"what": what, "ms": round(ms, 1), **more,
                  "inflight": [row for w in list(self._watches)
                               if w.rows is not None for row in w.rows()]}
        logger.warning("%s %s", REPORT_PREFIX,
                       json.dumps(report, default=str))
        recorder = self.recorder
        if recorder is not None:
            recorder.record(report, pin=PIN)


# The process heartbeat: one serving process, one thread (the same
# singleton shape as TIMELINE).
HEARTBEAT = Heartbeat()
