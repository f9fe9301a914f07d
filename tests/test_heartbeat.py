"""The process heartbeat (ISSUE 56): how late the loop and the interpreter
ran and how long each collection took, one `process paused:` line a pause
under its most specific name, one thread however many watch.

Scripted beats first (no thread: `_beat(due, now)` with the times given),
then the thread itself against a loop that is really held and a collection
that really runs.
"""

import asyncio
import gc
import json
import logging
import threading
import time

import pytest

from kfserving_tpu.observability import REGISTRY
from kfserving_tpu.observability.monitoring.flight_recorder import (
    FlightRecorder,
)
from kfserving_tpu.observability.profiling import HEARTBEAT, heartbeat
from kfserving_tpu.observability.profiling.heartbeat import Heartbeat

HELD_MS = "kfserving_tpu_process_held_ms"
GC_MS = "kfserving_tpu_process_gc_pause_ms"
LOGGER = "kfserving_tpu.observability.heartbeat"


@pytest.fixture(autouse=True)
def _no_heartbeat_left():
    yield
    for watch in list(HEARTBEAT._watches):
        HEARTBEAT.unwatch(watch)
    HEARTBEAT.recorder = None
    assert beating() == []


def beating():
    return [t for t in threading.enumerate() if t.name == "kfs-heartbeat"]


def observed(name, at_least_ms=0.0, **labels):
    """Observations of a histogram's children with `labels` that fell in
    a bucket whose upper bound is over `at_least_ms`."""
    family = REGISTRY.family(name)
    total = 0
    for have, child in (family.samples() if family else []):
        if any(have.get(k) != str(v) for k, v in labels.items()):
            continue
        bounds = list(child.buckets) + [float("inf")]
        total += sum(n for bound, n in zip(bounds, child.counts)
                     if bound > at_least_ms)
    return total


def lines(caplog):
    out = []
    for record in caplog.records:
        message = record.getMessage()
        if message.startswith(heartbeat.REPORT_PREFIX):
            assert record.levelno == logging.WARNING
            assert "\n" not in message
            out.append(json.loads(message[len(heartbeat.REPORT_PREFIX):]))
    return out


async def until(condition, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < deadline, "waited in vain"
        await asyncio.sleep(0.01)


class Loop:
    """What the heartbeat asks of a loop, run by hand."""

    def __init__(self):
        self.posted, self.closed = [], False

    def call_soon_threadsafe(self, fn, *args):
        if self.closed:
            raise RuntimeError("Event loop is closed")
        self.posted.append((fn, args))

    def is_closed(self):
        return self.closed


# ---------------------------------------------------------- scripted beats


@pytest.mark.parametrize("late_s,line", [(0.0, False), (0.249, False),
                                         (0.4, True)])
def test_a_late_wake_is_the_interpreters(caplog, late_s, line):
    caplog.set_level(logging.WARNING, logger=LOGGER)
    hb = Heartbeat()
    before = observed(HELD_MS, what="interpreter")
    hb._beat(due=100.0, now=100.0 + late_s)
    assert observed(HELD_MS, what="interpreter") == before + 1
    assert observed(HELD_MS, at_least_ms=late_s * 1e3 - 1e-6,
                    what="interpreter") >= 1
    if not line:
        assert lines(caplog) == []
        return
    (report,) = lines(caplog)
    assert report == {"what": "interpreter", "ms": 400.0, "inflight": []}


def test_a_collection_is_named_once_though_it_held_everything(caplog):
    """A generation-2 collection of 330 ms held this thread and the
    loop's tick as well: one line, the collector's."""
    caplog.set_level(logging.WARNING, logger=LOGGER)
    hb, loop = Heartbeat(), Loop()
    hb._watches.append(heartbeat.Watch(
        loop, None, lambda: [{"seq": 7, "program": "decode"}], None))
    state = hb._loops.setdefault(loop, heartbeat._Loop())
    hb._beat(due=100.0, now=100.0)
    ((tick, args),) = loop.posted
    assert state.sent_t == 100.0
    hb._collections.append((2, 100.02, 100.35, 12345))
    state.landed.append((100.0, 100.36))   # the tick ran when it could
    state.sent_t = None
    before = observed(GC_MS, generation=2)
    hb._beat(due=100.125, now=100.37)
    assert observed(GC_MS, generation=2) == before + 1
    assert observed(GC_MS, at_least_ms=250.0, generation=2) >= 1
    assert observed(HELD_MS, at_least_ms=250.0, what="loop") >= 1
    (report,) = lines(caplog)
    assert report == {"what": "gc", "ms": 330.0, "generation": 2,
                      "collected": 12345,
                      "inflight": [{"seq": 7, "program": "decode"}]}
    # a loop held on beyond the collection is a pause of its own
    state.landed.append((100.3, 100.9))
    hb._beat(due=100.9, now=100.9)
    assert [r["what"] for r in lines(caplog)] == ["gc", "loop"]
    assert lines(caplog)[1]["ms"] == 600.0


def test_the_collectors_callbacks_stamp_and_take_no_lock():
    hb = Heartbeat()
    hb._on_collection("stop", {"generation": 0, "collected": 0})
    assert not hb._collections   # a stop without its start is nobody's
    with hb._lock:   # the thread that collects may hold any lock
        hb._on_collection("start", {"generation": 1})
        hb._on_collection("stop", {"generation": 1, "collected": 9,
                                   "uncollectable": 0})
    ((generation, t0, t1, collected),) = hb._collections
    assert (generation, collected) == (1, 9) and t1 >= t0
    assert hb._gc_t0 is None


def test_a_collection_under_way_is_not_the_interpreters(caplog):
    """The heartbeat woke before the collector's stop was stamped."""
    caplog.set_level(logging.WARNING, logger=LOGGER)
    hb = Heartbeat()
    hb._gc_t0 = 100.01
    hb._beat(due=100.0, now=100.4)
    assert lines(caplog) == []
    hb._collections.append((1, 100.01, 100.41, 3))
    hb._gc_t0 = None
    hb._beat(due=100.525, now=100.53)
    (report,) = lines(caplog)
    assert (report["what"], report["generation"]) == ("gc", 1)


def test_a_tick_that_is_out_tells_the_watchers_and_takes_the_frames():
    hb, loop, told = Heartbeat(), Loop(), []
    hb._watches.append(heartbeat.Watch(loop, None, None, told.append))
    state = hb._loops.setdefault(loop, heartbeat._Loop())
    state.ident = threading.get_ident()
    hb._beat(due=10.0, now=10.0)
    assert told == [] and len(loop.posted) == 1
    hb._beat(due=10.125, now=10.125)   # still out: nothing new is posted
    assert told == [pytest.approx(125.0)] and len(loop.posted) == 1
    assert any(frame.endswith(
        " test_a_tick_that_is_out_tells_the_watchers_and_takes_the_frames")
        for frame in state.frames)
    fn, args = loop.posted.pop()
    fn(*args)   # the loop runs it at last
    assert state.sent_t is None and len(state.landed) == 1
    hb._beat(due=10.25, now=10.25)
    assert len(told) == 2 and told[1] >= 0.0
    assert len(loop.posted) == 1   # and the next tick is out


def test_a_closed_loop_is_forgotten_with_its_watches():
    hb, loop, other = Heartbeat(), Loop(), Loop()
    hb._watches += [heartbeat.Watch(loop, None, None, None),
                    heartbeat.Watch(other, None, None, None)]
    hb._loops = {loop: heartbeat._Loop(), other: heartbeat._Loop()}
    loop.closed = True
    hb._beat(due=1.0, now=1.0)
    assert [w.loop for w in hb._watches] == [other]
    assert list(hb._loops) == [other]


def test_a_watcher_that_raises_does_not_stop_the_beats(caplog):
    beats = []
    hb, loop = Heartbeat(), Loop()

    def broken():
        beats.append(1)
        raise ValueError("no")

    watch = hb.watch(loop, beat=broken)
    try:
        deadline = time.monotonic() + 10.0
        while len(beats) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
    finally:
        hb.unwatch(watch)
    assert len(beats) >= 2 and hb._thread is None


# ------------------------------------------------------ the thread itself


async def test_one_thread_however_many_watch_and_none_after():
    loop = asyncio.get_running_loop()
    assert beating() == []
    first = HEARTBEAT.watch(loop)
    second = HEARTBEAT.watch(loop, beat=lambda: None)
    assert len(beating()) == 1 and HEARTBEAT.watching() == 2
    assert HEARTBEAT._on_collection in gc.callbacks
    HEARTBEAT.unwatch(first)
    assert len(beating()) == 1
    HEARTBEAT.unwatch(second)
    HEARTBEAT.unwatch(second)   # twice is once
    assert beating() == [] and HEARTBEAT.watching() == 0
    assert HEARTBEAT._on_collection not in gc.callbacks
    # both series read 0 from the first beat on: a scrape without them
    # is a parent
    families = REGISTRY.families()
    assert families[HELD_MS] == families[GC_MS] == "histogram"


async def test_a_loop_held_400_ms_is_seen_and_named_with_its_frames(caplog):
    caplog.set_level(logging.WARNING, logger=LOGGER)
    recorder = FlightRecorder()
    HEARTBEAT.recorder = recorder
    watch = HEARTBEAT.watch(
        asyncio.get_running_loop(),
        rows=lambda: [{"seq": 3, "program": "prefill"}])
    try:
        await asyncio.sleep(0.3)   # healthy beats: no line
        assert lines(caplog) == []
        assert observed(HELD_MS, what="loop") >= 1
        before = observed(HELD_MS, at_least_ms=250.0, what="loop")
        time.sleep(0.4)   # a handler that holds the loop
        await until(lambda: lines(caplog))
        await asyncio.sleep(0.3)   # later beats add no second line
    finally:
        HEARTBEAT.unwatch(watch)
    assert observed(HELD_MS, at_least_ms=250.0, what="loop") > before
    (report,) = lines(caplog)
    assert report["what"] == "loop" and 250.0 <= report["ms"] < 5000.0
    assert report["inflight"] == [{"seq": 3, "program": "prefill"}]
    # taken by the heartbeat thread while the loop was held: this
    # function, inside its sleep
    assert any(frame.endswith(
        " test_a_loop_held_400_ms_is_seen_and_named_with_its_frames")
        for frame in report["frames"])
    (pinned,) = [e for e in recorder.dump(10)["pinned"]
                 if e["pinned"] == heartbeat.PIN]
    assert pinned["what"] == "loop" and pinned["ms"] == report["ms"]


def cycles(n):
    graph = []
    for _ in range(n):
        a, b = [], []
        a.append(b)
        b.append(a)
        graph.append(a)
    return len(graph)


async def test_a_forced_collection_feeds_generation_2_and_one_line(
        caplog, monkeypatch):
    caplog.set_level(logging.WARNING, logger=LOGGER)
    # a graph of a quarter of a million cycles takes tens of
    # milliseconds to collect, not a quarter of a second
    monkeypatch.setattr(heartbeat, "PAUSE_MS", 10.0)
    loop = asyncio.get_running_loop()
    gc.collect()
    gc.disable()   # no collection but the one asked for
    watch = HEARTBEAT.watch(loop)
    try:
        await asyncio.sleep(0.15)
        before = observed(GC_MS, generation=2)
        assert await loop.run_in_executor(None, cycles, 250_000) == 250_000
        collected = await loop.run_in_executor(None, gc.collect)
        await until(lambda: observed(GC_MS, generation=2) > before)
        await asyncio.sleep(0.3)
    finally:
        gc.enable()
        HEARTBEAT.unwatch(watch)
    assert observed(GC_MS, generation=2) == before + 1
    (mine,) = [r for r in lines(caplog) if r["what"] == "gc"]
    assert mine["generation"] == 2 and mine["ms"] >= 10.0
    assert mine["collected"] == collected >= 500_000

