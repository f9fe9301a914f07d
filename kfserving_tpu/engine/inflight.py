"""The programs a generator has launched and whose results are not yet
on the host: one numbered row each, from the launch to the fetch.

A row is made on the launching thread when an `engine.launch.<program>`
span opens (`InflightTable.launch`): `seq` (monotone per engine), the
program (`decode | prefill | insert | feed | chunk | spec | spec_draft`),
the span's own `rows / steps / bucket`, and `state`: `launching` while the
jitted call has not returned (a compile, or a device queue that is full,
sits here and is no stall), then `in_flight`.  It retires on a fetch
worker, in a `finally`, when the fetch that joins it returns
(`InflightTable.fetch`, around `_fetch_wave` and `_fetch_spec`).  `insert`,
`feed` and `spec_draft` have no fetch of their own: they retire with the
first later-launched program that is fetched, because the device runs
programs in launch order, and what is left when the engine comes to rest
(nothing more will be fetched) goes with `settle()`.  A launch that raises
dispatched nothing and leaves no row.

Both spans carry `seq` (and `engine.fetch` its `program`) in the ring
event and in the profiler's annotation, so `/debug/profile` and a traced
run pair a fetch with its launch; while a worker is inside a fetch,
`fetching()` says for which `seq` and since when.

Two clocks are read where the work happens.  In-flight time, from the
launch call's return to the fetch's return, is observed at retirement as
`generator_program_inflight_ms{program}`: under a pipeline it holds the
wait behind earlier programs, so it is the round trip a token rides.  The
fetch's return is stamped (`fetch().done_t`) and the scheduler loop
observes from it `generator_deliver_lag_ms` when it takes the result up.

A third clock runs while no row is in flight: the device then has
nothing from this engine.  The interval from the retirement (or the
`settle()`) that leaves no row `in_flight` to the *return* of the next
launch call is added to
`generator_device_starved_seconds_total{model,cause}`: `cause="no_work"`
for the part in which the scheduler loop stood in `engine.wait.request`
(`waiting()`: no slot active, nothing pending; a loop that ended for
want of work stands there until it starts again), `cause="host"` for the
rest: the loop admitting, growing or delivering, the launching thread in
`engine.prep.*` or inside a launch call with nothing behind it on the
device.  A lower bound of the device's idle time (a fetch returns a
transfer later than its program ended), exact in what it attributes.
The launch that ends an interval over a millisecond records one
`engine.starved` slice on the ring's `device` track (`cause`, the larger
part; `seconds`, `no_work_s`, the `seq` that ended it), so
`/debug/profile` shows the hole where the waves are; `stats()` has both
totals under `device_starved_s`.

`check()` is the process heartbeat's
(`observability/profiling/heartbeat.py`), eight times a second from the
pipeline's start to the engine's `close()`, on a thread that is not the
loop's.  It keeps `generator_inflight_oldest_age_s`, brings the starved
clock of a waiting loop up to date, and finds a
**stall**: the oldest row in flight of a fetched program whose age has
passed `max(STALL_FLOOR_S, STALL_FACTOR x the running mean in-flight time
of its program)`.  (A hang inside `insert` or `feed` stalls the fetched
program behind it; the report lists every row, the oldest first.)  A `seq`
is counted and reported once: `generator_program_stalls_total`, one
instant `engine.stall` in the ring, and one ERROR record, a line of JSON
after `engine stalled:`, built without a call into JAX or the device: a
hung chip would hang the report.  Nothing is cancelled and no request's
outcome changes: this observes.  Reading one: a worker inside the
stalled `seq`'s fetch (`fetching`, and its stack in `np.asarray`) is a
device or runtime that has not answered; `fetching` empty with idle
worker stacks is a scheduler loop that is held and can neither submit
the fetch nor take results up: the report is made while it is held, and
the loop thread's stack, under `stacks["loop"]`, says by what.

Cost with no capture: a dict store and one comparison a launch, a pop
and a pass over the few rows left a fetch.  The launching thread takes
no lock; readers copy the rows with `list(dict.values())`, one C call
under the interpreter lock.
"""

import itertools
import json
import logging
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from kfserving_tpu.observability import metrics as obs
from kfserving_tpu.observability.profiling import TIMELINE
from kfserving_tpu.observability.profiling.heartbeat import stack_lines
from kfserving_tpu.observability.profiling.timeline import (
    DEVICE,
    FETCH,
    HOST,
    LAUNCH,
)

logger = logging.getLogger("kfserving_tpu.engine.inflight")

LAUNCHING, IN_FLIGHT = "launching", "in_flight"
# Programs whose results a fetch brings to the host; the rest retire
# with the next of these.
FETCHED = frozenset(("decode", "prefill", "chunk", "spec"))

# No program of a served configuration is in flight for seconds: the
# slowest seen on the chip, an (8, 1024) prefill behind two 16-step
# waves, comes back in about half a second.  Five seconds is ten times
# that and still well inside a client's patience.
STALL_FLOOR_S = 5.0
# Above the floor the threshold follows the program: a wave that
# usually takes a second is not stalled at six.  Twenty means of a
# quantity whose spread in a healthy pipeline is a factor of two or
# three is far outside it.
STALL_FACTOR = 20.0
REPORT_PREFIX = "engine stalled:"
REPORT_EVENTS = 64   # ring events in a report
REPORT_FRAMES = 16   # innermost frames of each thread's stack
# A starved interval over this long leaves a slice in the ring.
STARVED_EVENT_S = 0.001
BY_HOST, NO_WORK = "host", "no_work"   # a starved second's cause


class Row:
    __slots__ = ("seq", "program", "rows", "steps", "bucket", "state",
                 "launched_t")

    def __init__(self, seq: int, program: str, rows, steps, bucket):
        self.seq = seq
        self.program = program
        self.rows, self.steps, self.bucket = rows, steps, bucket
        self.state = LAUNCHING
        # When the launch call was made and, once in flight, when it
        # returned: a row's age is the time in its state, so a compile
        # ages a launch and never the flight after it.
        self.launched_t = time.perf_counter()

    def as_dict(self, now: float) -> Dict[str, Any]:
        return {"seq": self.seq, "program": self.program,
                "rows": self.rows, "steps": self.steps,
                "bucket": self.bucket, "state": self.state,
                "age_s": round(now - self.launched_t, 4)}


class _Launch:
    """`InflightTable.launch()`: the row and the span around one jitted
    call (a class, not a generator: it is on the launching thread)."""

    __slots__ = ("_table", "_row", "_span")

    def __init__(self, table, row, span):
        self._table, self._row, self._span = table, row, span

    def __enter__(self) -> Row:
        self._table._rows[self._row.seq] = self._row
        self._span.__enter__()
        return self._row

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._span.__exit__(exc_type, exc, tb)
        row, table = self._row, self._table
        if exc_type is None:
            row.launched_t = time.perf_counter()
            row.state = IN_FLIGHT
            # After the state, as `_starve` looks again after its stamp:
            # one of the two sees the other.
            if table._starved_t is not None:
                table._fed(row)
        else:
            table._rows.pop(row.seq, None)
        return False


class _Fetch:
    """`InflightTable.fetch()`: a fetch worker inside one D2H join."""

    __slots__ = ("_table", "_seq", "_span", "done_t")

    def __init__(self, table, seq, span):
        self._table, self._seq, self._span = table, seq, span
        self.done_t = 0.0

    def __enter__(self) -> "_Fetch":
        self._table._fetching[threading.get_ident()] = (
            self._seq, time.time())
        self._span.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._span.__exit__(exc_type, exc, tb)
        self._table._fetching.pop(threading.get_ident(), None)
        self.done_t = self._table._retire(self._seq)
        return False


class InflightTable:
    def __init__(self, model: str, thread_prefixes: Tuple[str, ...] = ()):
        self.model = model
        # Names of the launching thread and the fetch workers begin
        # with these: whose stacks a report holds.
        self._thread_prefixes = tuple(thread_prefixes)
        self._seq = itertools.count(1)
        self._rows: Dict[int, Row] = {}   # launch order
        self._fetching: Dict[int, Tuple[int, float]] = {}
        # Fetch workers and the loop's check; never the launching thread.
        self._lock = threading.Lock()
        self._mean: Dict[str, List[float]] = {}   # program: [n, seconds]
        self._stalled: set = set()   # seqs reported and not yet retired
        # The scheduler loop's thread, once its pipeline has started: a
        # report holds its stack too.
        self.loop_ident: Optional[int] = None
        # The starved clock.  Since when no row is in flight (None while
        # one is), up to when that interval is in the counter, and the
        # part of it the loop stood waiting for a request; since when
        # the loop stands there (None while it does not).
        self._starved_t: Optional[float] = None
        self._booked_t = 0.0
        self._no_work_s = 0.0
        self._waiting_t: Optional[float] = None
        self._starved_s = {BY_HOST: 0.0, NO_WORK: 0.0}
        # The series read 0 from the start: a scrape that lacks them is
        # a server without this table, not one without a stall.
        for program in sorted(FETCHED):
            obs.generator_program_stalls_total().labels(
                model=model, program=program)
        for cause in self._starved_s:
            obs.generator_device_starved_seconds_total().labels(
                model=model, cause=cause)
        obs.generator_inflight_oldest_age_s().labels(model=model).set(0.0)

    # -- launching thread ----------------------------------------------------
    def launch(self, program: str, trace_id: Optional[str] = None,
               slot: int = -1, rows: Optional[int] = None,
               steps: Optional[int] = None, bucket: Optional[int] = None,
               **attrs: Any) -> _Launch:
        """Around one jitted call: the row, and the
        `engine.launch.<program>` span that carries its `seq`; `with
        ... as row` gives the row."""
        seq = next(self._seq)
        shape = {k: v for k, v in (("rows", rows), ("steps", steps),
                                   ("bucket", bucket)) if v is not None}
        return _Launch(
            self, Row(seq, program, rows, steps, bucket),
            TIMELINE.span(LAUNCH, "engine.launch." + program,
                          trace_id=trace_id, slot=slot, seq=seq,
                          **shape, **attrs))

    # -- fetch workers -------------------------------------------------------
    def fetch(self, seq: int, program: str) -> _Fetch:
        """Around the fetch that joins `seq`: the `engine.fetch` span,
        this worker's entry in `fetching()`, and in a `finally` the
        row's retirement; `.done_t` is then the fetch's return."""
        return _Fetch(self, seq, TIMELINE.span(
            FETCH, "engine.fetch", seq=seq, program=program))

    def _retire(self, seq: int) -> float:
        now = time.perf_counter()
        joined = self._rows.pop(seq, None)
        # Two workers may retire the same `insert` or `feed`: one pops it.
        gone = [joined] + [
            self._rows.pop(row.seq, None)
            for row in list(self._rows.values())
            if row.seq < seq and row.program not in FETCHED]
        took = None if joined is None else now - joined.launched_t
        if took is not None:
            obs.generator_program_inflight_ms().labels(
                program=joined.program).observe(took * 1000.0)
        with self._lock:
            if took is not None:
                mean = self._mean.setdefault(joined.program, [0, 0.0])
                mean[0] += 1
                mean[1] += took
            over = [row for row in gone
                    if row is not None and row.seq in self._stalled]
            self._stalled.difference_update(row.seq for row in over)
            self._starve(now)
        for row in over:
            logger.info("engine stall over: seq %d (%s) after %.3f s",
                        row.seq, row.program, now - row.launched_t)
        return now

    # -- the starved clock ---------------------------------------------------
    def _starve(self, now: float) -> None:
        """Under the lock, rows having gone: with none left in flight
        the device has nothing from this engine from `now` on."""
        if self._starved_t is not None or self._any_in_flight():
            return
        self._booked_t, self._no_work_s = now, 0.0
        self._starved_t = now
        if self._any_in_flight():
            # A launch returned between the look and the stamp and saw
            # no interval to end: there is none.
            self._starved_t = None

    def _any_in_flight(self) -> bool:
        return any(row.state == IN_FLIGHT
                   for row in list(self._rows.values()))

    def _fed(self, row: Row) -> None:
        """The launching thread, its call returned (`row.launched_t`)
        with an interval open: the interval ends, and what of it is not
        in the counter yet was the host's.  No lock: a waiting loop's
        part is booked before the loop can have asked for this launch,
        and `_starve` only opens an interval, or takes back the one it
        has just opened."""
        since, self._starved_t = self._starved_t, None
        if since is None:
            return
        now = row.launched_t
        self._book(BY_HOST, now - self._booked_t)
        seconds = now - since
        if seconds > STARVED_EVENT_S:
            TIMELINE.record(DEVICE, "engine.starved", dur_s=seconds,
                            t_end=time.time(), attrs={
                "cause": (NO_WORK if 2 * self._no_work_s > seconds
                          else BY_HOST),
                "seconds": round(seconds, 6),
                "no_work_s": round(self._no_work_s, 6),
                "seq": row.seq})

    def _book(self, cause: str, seconds: float) -> None:
        if seconds > 0.0:
            self._starved_s[cause] += seconds
            obs.generator_device_starved_seconds_total().labels(
                model=self.model, cause=cause).inc(seconds)

    def _book_waiting(self, now: float) -> None:
        """Under the lock: where the loop stands waiting for a request
        with an interval open, bring the counter up to `now`; what came
        before the wait was the host's."""
        if self._waiting_t is None or self._starved_t is None:
            return
        began = max(self._waiting_t, self._booked_t)
        self._book(BY_HOST, began - self._booked_t)
        self._book(NO_WORK, now - began)
        self._no_work_s += max(0.0, now - began)
        self._booked_t = max(began, now)

    # -- scheduler loop ------------------------------------------------------
    def waiting(self, for_a_request: bool) -> None:
        """The loop enters `engine.wait.request` (no slot active,
        nothing pending), or has left it.  A loop that ends there for
        want of work says nothing, and has left it when it starts
        again."""
        now = time.perf_counter()
        with self._lock:
            self._book_waiting(now)
            self._waiting_t = now if for_a_request else None

    def settle(self) -> None:
        """The engine is at rest (no slot active, no fetch awaited):
        what `insert` or `feed` launched after the last fetched program
        will be joined by nothing, and goes now."""
        for row in list(self._rows.values()):
            if row.program not in FETCHED and row.state == IN_FLIGHT:
                self._rows.pop(row.seq, None)
        with self._lock:
            self._starve(time.perf_counter())

    def stall_after(self, program: str) -> float:
        with self._lock:
            n, seconds = self._mean.get(program, (0, 0.0))
        return max(STALL_FLOOR_S, STALL_FACTOR * seconds / n if n else 0.0)

    # -- the heartbeat's thread ------------------------------------------------
    def check(self) -> bool:
        """Keep the oldest-age gauge and a waiting loop's starved clock,
        and report a stall, once a `seq`.  True while a fetched program
        is tabled."""
        now = time.perf_counter()
        with self._lock:
            self._book_waiting(now)
        tabled = [r for r in list(self._rows.values())
                  if r.program in FETCHED]
        oldest = next((r for r in tabled if r.state == IN_FLIGHT), None)
        age = now - oldest.launched_t if oldest is not None else 0.0
        obs.generator_inflight_oldest_age_s().labels(
            model=self.model).set(age)
        after = self.stall_after(oldest.program) if oldest else 0.0
        if oldest is not None and age > after:
            with self._lock:
                fresh = (oldest.seq in self._rows
                         and oldest.seq not in self._stalled)
                if fresh:
                    self._stalled.add(oldest.seq)
            if fresh:
                self._report(oldest, age, after, now)
        return bool(tabled)

    def _report(self, row: Row, age: float, after: float,
                now: float) -> None:
        obs.generator_program_stalls_total().labels(
            model=self.model, program=row.program).inc()
        TIMELINE.record(HOST, "engine.stall", attrs={
            "seq": row.seq, "program": row.program, "rows": row.rows,
            "bucket": row.bucket, "age_s": round(age, 3)})
        rows = list(self._rows.values())
        report = {
            "model": self.model,
            "stalled": row.as_dict(now),
            "stall_after_s": round(after, 3),
            "inflight": [r.as_dict(now) for r in rows],
            "fetching": self.fetching(),
            "launching": [r.as_dict(now) for r in rows
                          if r.state == LAUNCHING],
            "events": [TIMELINE.event_dict(e)
                       for e in TIMELINE.snapshot()[-REPORT_EVENTS:]],
            "stacks": self._stacks(),
        }
        logger.error("%s %s", REPORT_PREFIX,
                     json.dumps(report, default=str))

    def _stacks(self) -> Dict[str, List[str]]:
        """The Python stacks of the launching thread, the fetch workers
        and, as `loop`, the scheduler loop's thread, innermost frame
        last."""
        frames = sys._current_frames()
        out = {}
        for thread in threading.enumerate():
            frame = frames.get(thread.ident)
            name = ("loop" if thread.ident == self.loop_ident
                    else thread.name)
            if frame is None or not (name == "loop" or name.startswith(
                    self._thread_prefixes)):
                continue
            out[name] = stack_lines(frame, REPORT_FRAMES)
        return out

    # -- anyone --------------------------------------------------------------
    def fetching(self) -> List[Dict[str, Any]]:
        """Each fetch worker that is inside a fetch: for which `seq`,
        and since when (epoch seconds)."""
        return [{"thread": ident, "seq": seq, "since": round(since, 6)}
                for ident, (seq, since)
                in sorted(self._fetching.copy().items())]

    def rows(self) -> List[Dict[str, Any]]:
        """`stats()["inflight"]`: the rows oldest first; empty at rest."""
        now = time.perf_counter()
        return [r.as_dict(now) for r in list(self._rows.values())]

    def starved_s(self) -> Dict[str, float]:
        """`stats()["device_starved_s"]`: the counter's seconds by cause
        since the engine's start."""
        return {cause: round(s, 6) for cause, s in self._starved_s.items()}
