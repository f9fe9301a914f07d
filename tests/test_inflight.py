"""The in-flight table (ISSUE 39): every launch numbered and tabled
until its fetch returns, two clocks where the work happens, and a stall
counted once and reported once with what a person needs to name it.

The table alone first (no engine, no JAX), then the engine that owns
one: rows and spans paired by `seq`, a fetch held on an `Event` reported
with its program's real shape, a slow launch that is no stall.
"""

import asyncio
import json
import logging
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from kfserving_tpu.engine import inflight
from kfserving_tpu.engine.generator import GenerationEngine
from kfserving_tpu.engine.inflight import InflightTable
from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny
from kfserving_tpu.observability import REGISTRY
from kfserving_tpu.observability.profiling import TIMELINE, to_chrome_trace
from kfserving_tpu.tools import check_metrics

INFLIGHT_MS = "kfserving_tpu_generator_program_inflight_ms"
DELIVER_LAG_MS = "kfserving_tpu_generator_deliver_lag_ms"
STALLS = "kfserving_tpu_generator_program_stalls_total"
OLDEST_AGE = "kfserving_tpu_generator_inflight_oldest_age_s"
STARVED = "kfserving_tpu_generator_device_starved_seconds_total"
MAX_SEQ = 64


@pytest.fixture(autouse=True)
def _clear_timeline():
    TIMELINE.clear()
    yield
    TIMELINE.clear()
    TIMELINE.annotate = None


def children(name):
    family = REGISTRY.family(name)
    return list(family.samples()) if family else []


def observed(name, **labels):
    """Observations of a histogram, over the children with `labels`."""
    return sum(child.total for have, child in children(name)
               if all(have.get(k) == v for k, v in labels.items()))


def value(name, **labels):
    return sum(child.value for have, child in children(name)
               if all(have.get(k) == v for k, v in labels.items()))


def ring(name):
    return [e for e in TIMELINE.snapshot() if e[3] == name]


def reports(caplog):
    """The stall reports logged so far, parsed."""
    out = []
    for record in caplog.records:
        message = record.getMessage()
        if message.startswith(inflight.REPORT_PREFIX):
            assert record.levelno == logging.ERROR
            assert "\n" not in message
            out.append(json.loads(
                message[len(inflight.REPORT_PREFIX):]))
    return out


def launched(table, program, **shape):
    with table.launch(program, **shape) as row:
        pass
    return row


# ------------------------------------------------------ the table alone


def test_a_rows_life_from_launch_to_fetch():
    table = InflightTable("m")
    assert table.rows() == []
    with table.launch("decode", rows=4, steps=2) as row:
        (listed,) = table.rows()
        assert listed["state"] == "launching"
        opened = row.launched_t
    (listed,) = table.rows()
    assert listed == {"seq": row.seq, "program": "decode", "rows": 4,
                      "steps": 2, "bucket": None, "state": "in_flight",
                      "age_s": listed["age_s"]}
    assert listed["age_s"] >= 0
    with table.fetch(row.seq, "decode") as joined:
        assert [r["seq"] for r in table.rows()] == [row.seq]
    assert table.rows() == [] and table.fetching() == []
    assert joined.done_t >= row.launched_t >= opened


def test_seq_is_monotone_and_rows_are_listed_oldest_first():
    table = InflightTable("m")
    seqs = [launched(table, p).seq
            for p in ("prefill", "insert", "feed", "decode", "chunk")]
    assert seqs == sorted(seqs) and len(set(seqs)) == 5
    assert [r["seq"] for r in table.rows()] == seqs
    other = InflightTable("other")   # a count of its own an engine
    assert launched(other, "decode").seq == seqs[0]


def test_insert_and_feed_retire_with_the_next_fetched_program():
    table = InflightTable("m")
    prefill = launched(table, "prefill", rows=2, bucket=16)
    launched(table, "insert", rows=2)
    launched(table, "feed", rows=2)
    decode = launched(table, "decode", rows=4, steps=2)
    later = launched(table, "feed", rows=1)
    with table.fetch(prefill.seq, "prefill"):
        pass
    # launched after the prefill: its fetch says nothing of them
    assert [r["program"] for r in table.rows()] == [
        "insert", "feed", "decode", "feed"]
    with table.fetch(decode.seq, "decode"):
        pass
    assert [r["seq"] for r in table.rows()] == [later.seq]
    table.settle()   # at rest nothing will join it
    assert table.rows() == []


def test_a_draft_retires_with_its_verify_and_fetches_may_cross():
    table = InflightTable("m")
    first = launched(table, "decode", rows=4, steps=2)
    launched(table, "spec_draft", rows=3)
    spec = launched(table, "spec", rows=3, steps=4)
    # two workers: the later program's fetch may return first; the
    # earlier fetched row stays until its own does
    with table.fetch(spec.seq, "spec"):
        pass
    assert [r["seq"] for r in table.rows()] == [first.seq]
    with table.fetch(first.seq, "decode"):
        pass
    assert table.rows() == []


def test_a_launch_that_raises_leaves_no_row():
    table = InflightTable("m")
    with pytest.raises(RuntimeError):
        with table.launch("prefill", rows=8, bucket=64):
            raise RuntimeError("RESOURCE_EXHAUSTED")
    assert table.rows() == []
    (event,) = ring("engine.launch.prefill")   # the span is recorded
    assert event[6]["rows"] == 8


def test_a_fetch_that_raises_retires_its_row_all_the_same():
    table = InflightTable("m")
    row = launched(table, "decode", rows=4, steps=2)
    with pytest.raises(RuntimeError):
        with table.fetch(row.seq, "decode"):
            assert table.fetching()[0]["seq"] == row.seq
            raise RuntimeError("synthetic XLA failure")
    assert table.rows() == [] and table.fetching() == []
    assert observed(INFLIGHT_MS, program="decode") == 1


def test_a_worker_inside_a_fetch_says_for_which_seq_and_since_when():
    table = InflightTable("m")
    row = launched(table, "prefill", rows=1, bucket=16)
    before = time.time()
    with table.fetch(row.seq, "prefill"):
        (entry,) = table.fetching()
        assert entry["seq"] == row.seq
        assert entry["thread"] == threading.get_ident()
        assert before <= entry["since"] <= time.time()
    assert table.fetching() == []


def test_each_fetched_program_is_observed_once_by_its_program():
    table = InflightTable("m")
    for program in ("prefill", "insert", "feed", "decode", "decode",
                    "chunk", "spec_draft", "spec"):
        row = launched(table, program)
        if program in inflight.FETCHED:
            with table.fetch(row.seq, program):
                pass
    counts = {have["program"]: child.total
              for have, child in children(INFLIGHT_MS)}
    assert counts == {"prefill": 1, "decode": 2, "chunk": 1, "spec": 1}
    assert table.rows() == []


def test_inflight_time_runs_from_the_launch_calls_return():
    table = InflightTable("m")
    with table.launch("decode") as row:
        time.sleep(0.05)   # a compile: inside the launch, not in flight
    time.sleep(0.02)
    with table.fetch(row.seq, "decode"):
        pass
    ((_, child),) = children(INFLIGHT_MS)
    assert 20.0 <= child.sum < 50.0   # milliseconds


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation."""

    seen = []

    def __init__(self, name, **attrs):
        _Annotation.seen.append((name, attrs))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_launch_and_fetch_carry_the_same_seq_in_ring_and_annotation():
    _Annotation.seen = []
    TIMELINE.annotate = _Annotation
    table = InflightTable("m")
    row = launched(table, "prefill", rows=2, bucket=32,
                   trace_ids=["a", "b"])
    with table.fetch(row.seq, "prefill"):
        pass
    (launch,), (fetch,) = ring("engine.launch.prefill"), ring("engine.fetch")
    assert launch[2] == "launch" and fetch[2] == "fetch"
    assert launch[6] == {"seq": row.seq, "rows": 2, "bucket": 32,
                         "trace_ids": ["a", "b"]}
    assert fetch[6] == {"seq": row.seq, "program": "prefill"}
    # the profiler's annotation takes the scalars: seq among them
    assert _Annotation.seen == [
        ("engine.launch.prefill", {"seq": row.seq, "rows": 2,
                                   "bucket": 32}),
        ("engine.fetch", {"seq": row.seq, "program": "prefill"})]
    # and /debug/profile's rendering keeps them as the events' args
    rendered = [e for e in to_chrome_trace(TIMELINE.snapshot())[
        "traceEvents"] if e.get("name", "").startswith("engine.")]
    assert {e["args"]["seq"] for e in rendered} == {row.seq}


# ----------------------------------------------------- the starved clock


class Clock:
    """`time`, as the table reads it, moved by hand."""

    def __init__(self):
        self.t = 1000.0

    def perf_counter(self):
        return self.t

    def time(self):
        return 1.7e9 + self.t

    def pass_ms(self, ms):
        self.t += ms / 1e3


@pytest.fixture
def clock(monkeypatch):
    clock = Clock()
    monkeypatch.setattr(inflight, "time", clock)
    return clock


def starved_ms(table, cause):
    """By the counter and by `stats()`: they are one number."""
    counted = value(STARVED, model=table.model, cause=cause)
    assert table.starved_s()[cause] == pytest.approx(counted, abs=1e-6)
    return counted * 1e3


def fetched(table, row):
    with table.fetch(row.seq, row.program):
        pass


def test_the_starved_clock_reads_zero_from_the_start(clock):
    table = InflightTable("m")
    assert {(have["model"], have["cause"]): child.value
            for have, child in children(STARVED)} == {
        ("m", "host"): 0.0, ("m", "no_work"): 0.0}
    assert table.starved_s() == {"host": 0.0, "no_work": 0.0}
    # nothing ever launched: nothing was starved of this engine's work
    clock.pass_ms(500)
    first = launched(table, "decode")
    assert starved_ms(table, "host") == starved_ms(table, "no_work") == 0
    assert not ring("engine.starved")
    fetched(table, first)


def test_both_causes_to_the_millisecond(clock):
    table = InflightTable("m")
    first = launched(table, "decode", rows=4, steps=2)
    clock.pass_ms(10)
    fetched(table, first)        # the device has nothing from here on
    clock.pass_ms(3)             # the loop delivers, finds nothing to do
    table.waiting(True)
    clock.pass_ms(500)           # engine.wait.request
    table.waiting(False)
    clock.pass_ms(2)             # admits
    with table.launch("prefill", rows=1, bucket=16) as second:
        clock.pass_ms(5)         # prepares and launches, nothing behind it
    assert starved_ms(table, "no_work") == pytest.approx(500.0)
    assert starved_ms(table, "host") == pytest.approx(10.0)
    (event,) = ring("engine.starved")
    assert event[2] == "device" and event[1] == pytest.approx(0.510)
    assert event[0] + event[1] == pytest.approx(clock.time())
    assert event[6] == {"cause": "no_work", "seconds": 0.51,
                        "no_work_s": 0.5, "seq": second.seq}
    # with work in flight nothing is starved, however long it takes
    clock.pass_ms(700)
    third = launched(table, "decode")
    clock.pass_ms(700)
    fetched(table, second)
    clock.pass_ms(700)
    assert starved_ms(table, "host") == pytest.approx(10.0)
    # and the host's part alone: 4 ms from the last fetch to the next
    # launch's return, a hole that /debug/profile shows as the host's
    fetched(table, third)
    clock.pass_ms(1)
    with table.launch("decode") as fourth:
        clock.pass_ms(3)
    assert starved_ms(table, "host") == pytest.approx(14.0)
    assert starved_ms(table, "no_work") == pytest.approx(500.0)
    assert ring("engine.starved")[1][6] == {
        "cause": "host", "seconds": 0.004, "no_work_s": 0.0,
        "seq": fourth.seq}


def test_two_workers_retiring_out_of_order_starve_at_the_last(clock):
    table = InflightTable("m")
    prefill = launched(table, "prefill", rows=2, bucket=32)
    decode = launched(table, "decode", rows=4, steps=2)
    clock.pass_ms(20)
    fetched(table, decode)       # the later program's fetch returns first
    clock.pass_ms(30)            # the prefill is still out: not starved
    fetched(table, prefill)
    clock.pass_ms(6)
    launched(table, "decode")
    assert starved_ms(table, "host") == pytest.approx(6.0)


def test_insert_and_feed_keep_the_device_fed_until_they_go(clock):
    table = InflightTable("m")
    prefill = launched(table, "prefill", rows=1, bucket=16)
    launched(table, "insert", rows=1)
    launched(table, "feed", rows=1)
    clock.pass_ms(10)
    fetched(table, prefill)      # launched after it: still tabled
    clock.pass_ms(40)
    table.settle()               # at rest: they go, and the clock starts
    clock.pass_ms(2)
    table.waiting(True)
    clock.pass_ms(100)
    table.waiting(False)
    clock.pass_ms(1)
    decode = launched(table, "decode")
    assert starved_ms(table, "host") == pytest.approx(3.0)
    assert starved_ms(table, "no_work") == pytest.approx(100.0)
    # a feed behind a fetched wave goes with it, and the clock starts there
    launched(table, "feed", rows=1)
    clock.pass_ms(5)
    later = launched(table, "decode")
    fetched(table, decode)
    clock.pass_ms(5)
    fetched(table, later)
    assert table.rows() == []
    clock.pass_ms(7)
    launched(table, "decode")
    assert starved_ms(table, "host") == pytest.approx(10.0)


def test_a_launch_that_raises_feeds_nothing(clock):
    table = InflightTable("m")
    fetched(table, launched(table, "decode"))
    clock.pass_ms(2)
    with pytest.raises(RuntimeError):
        with table.launch("prefill", rows=1, bucket=16):
            clock.pass_ms(3)
            raise RuntimeError("out of memory")
    assert starved_ms(table, "host") == 0.0   # still open
    clock.pass_ms(4)
    with table.launch("prefill", rows=1, bucket=16):
        clock.pass_ms(1)
    assert starved_ms(table, "host") == pytest.approx(10.0)


def test_a_launch_under_way_when_the_last_row_retires_is_the_hosts(clock):
    table = InflightTable("m")
    first = launched(table, "decode")
    with table.launch("decode"):          # compiles, say
        clock.pass_ms(50)
        fetched(table, first)             # nothing is on the device now
        clock.pass_ms(200)
    assert starved_ms(table, "host") == pytest.approx(200.0)
    assert ring("engine.starved")[0][6]["cause"] == "host"


def test_a_waiting_loop_is_booked_beat_by_beat_and_a_dead_one_too(clock):
    """A scrape must not find, after a quiet hour, the hour arriving in
    one second: the heartbeat's look books a wait as it goes."""
    table = InflightTable("m")
    fetched(table, launched(table, "decode"))
    clock.pass_ms(1)
    table.waiting(True)
    for beat in range(1, 9):
        clock.pass_ms(125)
        assert not table.check()
        assert starved_ms(table, "no_work") == pytest.approx(125.0 * beat)
    assert starved_ms(table, "host") == pytest.approx(1.0)
    # the loop ends there for want of work (it does not say so), and
    # starts again with a request: it was waiting all the while
    clock.pass_ms(60_000)
    table.check()
    table.waiting(False)
    clock.pass_ms(2)
    launched(table, "prefill", rows=1, bucket=16)
    assert starved_ms(table, "no_work") == pytest.approx(61_000.0)
    assert starved_ms(table, "host") == pytest.approx(3.0)
    assert ring("engine.starved")[0][6]["cause"] == "no_work"
    # a look with work in flight books nothing
    clock.pass_ms(300)
    assert table.check()
    assert starved_ms(table, "host") == pytest.approx(3.0)


def test_the_launch_retire_pair_costs_microseconds():
    """What the starved clock adds to a launch and a retirement: printed
    for PERF.md, and held to a generous ceiling."""
    table = InflightTable("m")
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        fetched(table, launched(table, "decode"))
    pair_us = (time.perf_counter() - t0) / n * 1e6
    print(f"launch + fetch + retire, starved every time: {pair_us:.1f} us")
    assert value(STARVED, model="m", cause="host") > 0
    assert pair_us < 2000


# ------------------------------------------------------------- a stall


@pytest.fixture
def quick_stalls(monkeypatch):
    """A floor a test can wait for; the factor as it is."""
    monkeypatch.setattr(inflight, "STALL_FLOOR_S", 0.05)


def test_a_stall_is_counted_once_and_reported_once(quick_stalls, caplog):
    caplog.set_level(logging.INFO, logger="kfserving_tpu.engine.inflight")
    table = InflightTable("m")
    launched(table, "feed", rows=1)   # nothing is owed a fetch: no stall
    held = launched(table, "prefill", rows=2, bucket=32)
    behind = launched(table, "decode", rows=4, steps=2)
    assert table.check() and not reports(caplog)   # too young
    assert value(STALLS) == 0
    time.sleep(0.08)
    for _ in range(3):   # the same seq never twice
        assert table.check()
    assert value(STALLS) == 1
    assert value(STALLS, model="m", program="prefill") == 1
    assert value(OLDEST_AGE, model="m") >= 0.08
    (event,) = ring("engine.stall")
    assert event[1] == 0.0 and event[2] == "host"
    assert event[6]["seq"] == held.seq and event[6]["age_s"] >= 0.08
    assert (event[6]["program"], event[6]["rows"], event[6]["bucket"]) \
        == ("prefill", 2, 32)
    (report,) = reports(caplog)
    assert report["model"] == "m"
    assert report["stalled"]["seq"] == held.seq
    assert (report["stalled"]["program"], report["stalled"]["rows"],
            report["stalled"]["bucket"]) == ("prefill", 2, 32)
    assert report["stall_after_s"] == 0.05
    # every row in launch order, the oldest unfinished first
    assert [r["program"] for r in report["inflight"]] == [
        "feed", "prefill", "decode"]
    assert report["fetching"] == [] and report["launching"] == []
    assert [e["name"] for e in report["events"]][-1] == "engine.stall"
    # the row retires at last: one line, and the count stays
    with table.fetch(held.seq, "prefill"):
        pass
    over = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("engine stall over:")]
    assert len(over) == 1 and f"seq {held.seq} " in over[0]
    # a later seq that stalls counts again
    assert table.check()
    assert value(STALLS) == 2 and value(STALLS, program="decode") == 1
    assert reports(caplog)[1]["stalled"]["seq"] == behind.seq
    with table.fetch(behind.seq, "decode"):
        pass
    assert not table.check()   # nothing to look at: the timer lapses
    assert value(OLDEST_AGE, model="m") == 0.0
    assert value(STALLS) == 2 and len(ring("engine.stall")) == 2


def test_a_long_launch_is_not_a_stall(quick_stalls, caplog):
    table = InflightTable("m")
    with table.launch("prefill", rows=4, bucket=64):
        time.sleep(0.1)   # a compile, or a device queue that is full
        assert table.check()
        assert table.rows()[0]["state"] == "launching"
        assert table.rows()[0]["age_s"] >= 0.1
    assert table.check()   # in flight since a moment ago
    assert value(STALLS) == 0 and not reports(caplog)
    assert not ring("engine.stall")


def test_the_threshold_follows_the_programs_running_mean(monkeypatch):
    table = InflightTable("m")
    assert table.stall_after("decode") == inflight.STALL_FLOOR_S
    monkeypatch.setattr(inflight, "STALL_FLOOR_S", 0.001)
    for _ in range(2):
        row = launched(table, "decode")
        time.sleep(0.01)
        with table.fetch(row.seq, "decode"):
            pass
    ((_, child),) = children(INFLIGHT_MS)
    mean_s = child.sum / child.total / 1000.0
    assert table.stall_after("decode") == pytest.approx(
        inflight.STALL_FACTOR * mean_s, rel=1e-6)
    assert table.stall_after("prefill") == 0.001   # no mean yet


class _Raises:
    """In `jax`'s place: any use of it raises."""

    def __getattr__(self, name):
        if name.startswith("__"):   # a probe of the module object
            raise AttributeError(name)
        raise AssertionError(f"the report called into jax ({name})")


def test_the_report_calls_nothing_of_jax(quick_stalls, monkeypatch,
                                         caplog):
    assert not any(name == "jax" or name.startswith("jax.")
                   for name in vars(inflight))
    for name in [n for n in sys.modules
                 if n == "jax" or n.startswith(("jax.", "jaxlib"))]:
        monkeypatch.setitem(sys.modules, name, _Raises())
    table = InflightTable("m", (threading.current_thread().name,))
    row = launched(table, "decode", rows=4, steps=2)
    time.sleep(0.06)
    with table.fetch(row.seq, "decode"):   # a worker inside np.asarray
        table.check()
        (report,) = reports(caplog)
    assert report["fetching"][0]["seq"] == row.seq
    (stack,) = report["stacks"].values()   # this thread's, by its name
    assert stack[-1].endswith(" _stacks")   # innermost last
    assert any(frame.endswith(" test_the_report_calls_nothing_of_jax")
               for frame in stack)


def test_launches_fetches_and_looks_from_four_threads():
    """One launching thread, two fetch workers and the loop's look, on a
    short switch interval: every row retires once, nothing is lost."""
    table = InflightTable("m")
    n, queue, errors = 3000, [], []
    ready = threading.Semaphore(0)

    def launcher():
        for i in range(n):
            program = ("prefill", "insert", "feed", "decode")[i % 4]
            row = launched(table, program, rows=i)
            if program in inflight.FETCHED:
                queue.append(row)
                ready.release()
        for _ in range(2):
            queue.append(None)
            ready.release()

    def fetcher():
        try:
            while True:
                assert ready.acquire(timeout=30)
                row = queue.pop(0)
                if row is None:
                    return
                with table.fetch(row.seq, row.program):
                    pass
        except BaseException as e:  # reported below, on the main thread
            errors.append(e)

    def looker(stop):
        try:
            while not stop.is_set():
                table.check()
                table.rows()
                table.fetching()
        except BaseException as e:
            errors.append(e)

    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    stop = threading.Event()
    threads = [threading.Thread(target=f, args=a) for f, a in (
        (launcher, ()), (fetcher, ()), (fetcher, ()), (looker, (stop,)))]
    try:
        for t in threads:
            t.start()
        for t in threads[:3]:
            t.join(timeout=60)
        stop.set()
        threads[3].join(timeout=10)
    finally:
        sys.setswitchinterval(before)
        stop.set()
    assert not errors and not any(t.is_alive() for t in threads)
    table.settle()
    assert table.rows() == [] and table.fetching() == []
    assert observed(INFLIGHT_MS) == n // 2
    assert value(STALLS) == 0


def test_check_metrics_knows_the_new_series():
    InflightTable("m")
    families = REGISTRY.families()
    assert families[STALLS] == "counter"
    assert families[OLDEST_AGE] == "gauge"
    # at 0 from the start: a scrape without them is another server
    assert {have["program"] for have, _ in children(STALLS)} \
        == set(inflight.FETCHED)
    assert not check_metrics.lint_families({
        STALLS: "counter", OLDEST_AGE: "gauge",
        INFLIGHT_MS: "histogram", DELIVER_LAG_MS: "histogram"})
    assert not asyncio.run(check_metrics.smoke())
    linted = REGISTRY.families()
    assert linted[INFLIGHT_MS] == linted[DELIVER_LAG_MS] == "histogram"


def test_the_buckets_hold_a_stall_and_are_fine_under_a_second():
    table = InflightTable("m")
    row = launched(table, "decode")
    with table.fetch(row.seq, "decode"):
        pass
    ((_, child),) = children(INFLIGHT_MS)
    assert max(child.buckets) >= 60_000
    assert sum(1 for b in child.buckets if b < 1000) >= 10
    from kfserving_tpu.observability import metrics as obs
    lag = obs.generator_deliver_lag_ms().labels()
    assert max(lag.buckets) >= 60_000
    assert sum(1 for b in lag.buckets if b < 1000) >= 10


# --------------------------------------------- the engine that owns one


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder_tiny(num_layers=2, hidden_size=64, num_heads=2,
                       intermediate_size=128, max_seq=MAX_SEQ,
                       vocab_size=96)
    module = DecoderLM(cfg)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return module, variables


def make_engine(tiny, **kw):
    module, variables = tiny
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [16, 32, MAX_SEQ])
    kw.setdefault("steps_per_call", 2)
    return GenerationEngine(module, variables, **kw)


def prompt_of(n, stride=7):
    return [(i * stride) % 90 + 1 for i in range(n)]


async def until(condition, timeout=30.0):
    deadline = time.monotonic() + timeout
    while not condition():
        assert time.monotonic() < deadline, "waited in vain"
        await asyncio.sleep(0.01)


async def test_an_engines_fetches_pair_with_its_launches(tiny):
    eng = make_engine(tiny)
    try:
        assert eng.stats()["inflight"] == []
        await asyncio.gather(*[
            eng.complete(prompt_of(n), max_new_tokens=5)
            for n in (5, 9, 20)])
        await until(lambda: eng.stats()["inflight"] == [])
    finally:
        await eng.close()
    events = TIMELINE.snapshot()
    launches = {e[6]["seq"]: e[3].rsplit(".", 1)[1] for e in events
                if e[3].startswith("engine.launch.")}
    fetches = [e[6] for e in events if e[3] == "engine.fetch"]
    assert {"prefill", "insert", "feed", "decode"} <= set(launches.values())
    # launch order is ring order on the one launching thread
    assert list(launches) == sorted(launches)
    assert fetches and all(
        launches[f["seq"]] == f["program"] for f in fetches)
    assert len({f["seq"] for f in fetches}) == len(fetches)
    # every fetched program was fetched, and the loop waited by its seq
    assert {f["seq"] for f in fetches} == {
        seq for seq, program in launches.items()
        if program in inflight.FETCHED}
    assert {e[6]["seq"] for e in ring("engine.wait.fetch")} \
        == {f["seq"] for f in fetches}
    # each observed once on its worker, and once when the loop took it up
    assert observed(INFLIGHT_MS) == len(fetches) \
        == observed(DELIVER_LAG_MS)
    for program in ("prefill", "decode"):
        assert observed(INFLIGHT_MS, program=program) == sum(
            f["program"] == program for f in fetches)
    assert value(STALLS) == 0 and not ring("engine.stall")


async def test_a_held_fetch_is_reported_once_with_its_real_shape(
        tiny, monkeypatch, caplog):
    caplog.set_level(logging.INFO, logger="kfserving_tpu.engine.inflight")
    eng = make_engine(tiny)
    # The heartbeat looks from the pipeline's start, not from the loop's
    # first await of a fetch: a first request compiles `insert` with its
    # prefill in flight.  Every shape first, then a floor to wait for.
    await eng.complete(prompt_of(9), max_new_tokens=4)
    TIMELINE.clear()
    eng._inflight._mean.clear()   # a prefill does not take that long
    monkeypatch.setattr(inflight, "STALL_FLOOR_S", 0.05)
    hold, entered = threading.Event(), threading.Event()
    fetch_wave = eng._fetch_wave

    def held_fetch(toks_h, lp_h):
        if not entered.is_set():   # the first fetch: the prefill's
            entered.set()
            assert hold.wait(timeout=60)
        return fetch_wave(toks_h, lp_h)

    eng._fetch_wave = held_fetch
    try:
        answer = asyncio.ensure_future(
            eng.complete(prompt_of(9), max_new_tokens=4))
        await until(lambda: value(STALLS) >= 1)
        # no later look may find a second young wave stalled
        monkeypatch.setattr(inflight, "STALL_FLOOR_S", 60.0)
        assert entered.is_set() and not answer.done()
        listed = eng.stats()["inflight"]
        assert listed[0]["program"] == "prefill"
        assert listed[0]["state"] == "in_flight"
        assert value(OLDEST_AGE, model=eng.name) > 0.05
        await asyncio.sleep(0.1)   # some looks later: still one
        hold.set()
        tokens, _ = await answer
        assert len(tokens) == 4   # nothing cancelled, no outcome changed
        await until(lambda: eng.stats()["inflight"] == [])
        await until(lambda: value(OLDEST_AGE, model=eng.name) == 0.0)
    finally:
        hold.set()
        await eng.close()
    assert value(STALLS) == 1
    assert value(STALLS, model=eng.name, program="prefill") == 1
    (report,) = reports(caplog)
    stalled = report["stalled"]
    assert (stalled["program"], stalled["rows"], stalled["bucket"]) \
        == ("prefill", 1, 16)
    assert stalled["state"] == "in_flight" and stalled["age_s"] > 0.05
    (event,) = ring("engine.stall")
    assert event[6]["seq"] == stalled["seq"]
    # every row in launch order, the oldest unfinished program first
    # (the waves behind it were fetched by the other worker, and took
    # the insert and the feed with them)
    assert report["inflight"][0] == stalled
    assert [r["seq"] for r in report["inflight"]] \
        == sorted(r["seq"] for r in report["inflight"])
    # the worker that is inside the fetch, and since when
    assert stalled["seq"] in [f["seq"] for f in report["fetching"]]
    assert all(f["since"] <= time.time() for f in report["fetching"])
    # the ring's last events, the launch among them by its seq
    assert 0 < len(report["events"]) <= inflight.REPORT_EVENTS
    assert any(e["name"] == "engine.launch.prefill"
               and e["attrs"]["seq"] == stalled["seq"]
               for e in report["events"])
    # the stacks of the launching thread and the fetch workers
    names = sorted(report["stacks"])
    assert any(n.startswith(f"generator-enq-{eng.name}_") for n in names)
    # (held: inside the wrapper and waiting there; on a slow machine the
    # other worker can be passing through the wrapper with a wave's fetch)
    held_stacks = [s for n, s in report["stacks"].items()
                   if n.startswith(f"generator-{eng.name}_")
                   and any("held_fetch" in frame for frame in s)
                   and s[-1].endswith(" wait")]
    assert len(held_stacks) == 1
    over = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("engine stall over:")]
    assert len(over) == 1 and f"seq {stalled['seq']} " in over[0]


async def test_a_compile_inside_a_launch_is_no_stall(
        tiny, monkeypatch, caplog):
    monkeypatch.setattr(inflight, "STALL_FLOOR_S", 0.3)
    eng = make_engine(tiny)
    decode, slow = eng._decode, []
    seen = []

    def compiling(*args):
        if not slow:   # the first call of the shape
            slow.append(1)
            time.sleep(0.8)
        return decode(*args)

    eng._decode = compiling
    try:
        answer = asyncio.ensure_future(
            eng.complete(prompt_of(9), max_new_tokens=4))
        await until(lambda: bool(slow))
        for _ in range(40):
            seen.extend(r for r in eng.stats()["inflight"]
                        if r["program"] == "decode")
            await asyncio.sleep(0.01)
        tokens, _ = await answer
        assert len(tokens) == 4
    finally:
        await eng.close()
    assert seen and all(r["state"] == "launching" for r in seen)
    assert max(r["age_s"] for r in seen) > 0.3   # older than the floor
    assert value(STALLS) == 0 and not reports(caplog)
    assert not ring("engine.stall")


# ------------------------------------- the heartbeat that looks at the table


def beating():
    return [t for t in threading.enumerate() if t.name == "kfs-heartbeat"]


async def test_an_idle_engine_is_starved_of_work_not_by_the_host(tiny):
    eng = make_engine(tiny)
    try:
        assert eng.stats()["device_starved_s"] == {
            "host": 0.0, "no_work": 0.0}
        t0 = time.perf_counter()
        await eng.complete(prompt_of(9), max_new_tokens=4)
        await until(lambda: eng.stats()["inflight"] == [])
        await asyncio.sleep(0.4)            # no slot active, nothing pending
        await eng.complete(prompt_of(9), max_new_tokens=4)
        wall = time.perf_counter() - t0
        starved = eng.stats()["device_starved_s"]
    finally:
        await eng.close()
    assert starved["no_work"] >= 0.35
    assert 0.0 < starved["host"] < wall - starved["no_work"]
    # (the counter went on while the engine closed)
    assert value(STARVED, model=eng.name, cause="no_work") \
        >= starved["no_work"] - 1e-5
    # the hole is on the device track, where the waves are
    holes = [e for e in ring("engine.starved")
             if e[6]["cause"] == "no_work"]
    assert holes and holes[-1][2] == "device"
    assert holes[-1][1] >= 0.35
    launches = {e[6]["seq"] for e in TIMELINE.snapshot()
                if e[3].startswith("engine.launch.")}
    assert holes[-1][6]["seq"] in launches


async def test_one_heartbeat_for_two_engines_and_none_after_close(tiny):
    from kfserving_tpu.observability.profiling import HEARTBEAT

    assert beating() == [] and HEARTBEAT.watching() == 0
    one, two = make_engine(tiny), make_engine(tiny)
    try:
        assert beating() == []   # no pipeline has started on a loop yet
        await asyncio.gather(
            one.complete(prompt_of(9), max_new_tokens=3),
            two.complete(prompt_of(5), max_new_tokens=3))
        assert len(beating()) == 1 and HEARTBEAT.watching() == 2
        # an engine whose loop ended for want of work is watched still
        await until(lambda: one._loop_task.done(), timeout=10.0)
        await one.complete(prompt_of(9), max_new_tokens=3)
        assert len(beating()) == 1 and HEARTBEAT.watching() == 2
        await one.close()
        assert len(beating()) == 1 and HEARTBEAT.watching() == 1
    finally:
        await one.close()
        await two.close()
    assert beating() == [] and HEARTBEAT.watching() == 0


async def test_a_stall_is_reported_while_the_loop_is_still_held(
        tiny, monkeypatch, caplog):
    caplog.set_level(logging.INFO, logger="kfserving_tpu.engine.inflight")
    eng = make_engine(tiny)
    await eng.complete(prompt_of(9), max_new_tokens=4)   # every shape
    eng._inflight._mean.clear()   # whose compiles no mean should hold
    monkeypatch.setattr(inflight, "STALL_FLOOR_S", 0.05)
    hold, entered = threading.Event(), threading.Event()
    fetch_wave = eng._fetch_wave

    def held_fetch(toks_h, lp_h):
        if not entered.is_set():
            entered.set()
            assert hold.wait(timeout=60)
        return fetch_wave(toks_h, lp_h)

    eng._fetch_wave = held_fetch
    try:
        answer = asyncio.ensure_future(
            eng.complete(prompt_of(9), max_new_tokens=4))
        await until(entered.is_set)
        before = value(STALLS)
        time.sleep(0.5)   # a handler holds the loop; the fetch is out
        # not one await since: the look was another thread's
        counted, seen = value(STALLS), reports(caplog)
        monkeypatch.setattr(inflight, "STALL_FLOOR_S", 60.0)
        hold.set()
        tokens, _ = await answer
        assert len(tokens) == 4
    finally:
        hold.set()
        await eng.close()
    assert before == 0 and counted == 1
    (report,) = seen
    assert report["stalled"]["program"] == "prefill"
    assert any(frame.endswith(
        " test_a_stall_is_reported_while_the_loop_is_still_held")
        for frame in report["stacks"]["loop"])
