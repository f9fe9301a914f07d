"""Engine phases in the profiler's trace, time to first token split at
its hand-offs, and the counters the chip benchmark reads (ISSUE 24).

One way to record an engine span (`EngineTimeline.span`), two sinks:
the ring always, the profiler's trace while a capture is active.  The
tests hold the parts to each other: same ring event with and without
the profiler, spans on the threads that did the work, stage sums equal
to the whole, dispatch counts equal to the engine's own.
"""

import asyncio
import threading

import jax
import jax.numpy as jnp
import pytest

from kfserving_tpu.engine import compile_cache
from kfserving_tpu.engine.generator import GenerationEngine
from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny
from kfserving_tpu.observability import REGISTRY
from kfserving_tpu.observability.profiling import (
    TIMELINE,
    EngineTimeline,
    to_chrome_trace,
)
from kfserving_tpu.tracing import ProfilerControl, current_request_id
from tests.utils import engine_span_lines

MAX_SEQ = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder_tiny(num_layers=2, hidden_size=64, num_heads=2,
                       intermediate_size=128, max_seq=MAX_SEQ,
                       vocab_size=96)
    module = DecoderLM(cfg)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return module, variables


@pytest.fixture(autouse=True)
def _clear_timeline():
    TIMELINE.clear()
    yield
    TIMELINE.clear()
    TIMELINE.annotate = None


def make_engine(tiny, **kw):
    module, variables = tiny
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [16, 32, MAX_SEQ])
    kw.setdefault("steps_per_call", 2)
    return GenerationEngine(module, variables, **kw)


def prompt_of(n, stride=7):
    return [(i * stride) % 90 + 1 for i in range(n)]


def hist(name, **labels):
    """(count, sum) of one histogram child; zeros before its first
    observation."""
    family = REGISTRY.family(name)
    for child_labels, child in (family.samples() if family else ()):
        if child_labels == labels:
            return child.total, child.sum
    return 0, 0.0


def counter(name, **labels):
    family = REGISTRY.family(name)
    for child_labels, child in (family.samples() if family else ()):
        if child_labels == labels:
            return child.value
    return 0.0


# ------------------------------------------------------------ span()


class _Annotation:
    """Stands in for jax.profiler.TraceAnnotation."""

    seen = []

    def __init__(self, name, **attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        _Annotation.seen.append(("enter", self.name, self.attrs,
                                 threading.get_ident()))
        return self

    def __exit__(self, *exc):
        _Annotation.seen.append(("exit", self.name))
        return False


def _one_span(timeline):
    with timeline.span("launch", "engine.launch.decode",
                       trace_id="abc", slot=2, rows=3, steps=4):
        pass
    (event,) = timeline.snapshot()
    return event


def test_span_records_the_same_ring_event_with_and_without_a_factory():
    plain, annotated = EngineTimeline(16), EngineTimeline(16)
    _Annotation.seen = []
    annotated.annotate = _Annotation
    a, b = _one_span(plain), _one_span(annotated)
    # (start, dur, track, name, trace_id, slot, attrs): all but the
    # clock readings agree, and those are a start and a duration.
    assert a[2:] == b[2:] == ("launch", "engine.launch.decode", "abc",
                              2, {"rows": 3, "steps": 4})
    assert a[1] >= 0 and b[1] >= 0 and a[0] > 0
    assert [s[0] for s in _Annotation.seen] == ["enter", "exit"]
    assert _Annotation.seen[0][1:3] == ("engine.launch.decode",
                                        {"rows": 3, "steps": 4})


def test_span_without_attrs_records_none_like_record_does():
    tl = EngineTimeline(16)
    with tl.span("host", "engine.grow"):
        pass
    tl.record("host", "engine.grow")
    first, second = tl.snapshot()
    assert first[2:] == second[2:] == ("host", "engine.grow", None, -1,
                                       None)


class _Raises:
    def __init__(self, where):
        self.where = where

    def __call__(self, name, **attrs):
        if self.where == "factory":
            raise RuntimeError("no profiler session")
        return self

    def __enter__(self):
        if self.where == "enter":
            raise RuntimeError("enter failed")

    def __exit__(self, *exc):
        if self.where == "exit":
            raise RuntimeError("exit failed")


@pytest.mark.parametrize("where", ["factory", "enter", "exit"])
def test_span_never_raises_when_the_factory_does(where):
    tl = EngineTimeline(16)
    tl.annotate = _Raises(where)
    event = _one_span(tl)
    assert event[3] == "engine.launch.decode"
    # and the block's own exception is not swallowed
    with pytest.raises(KeyError):
        with tl.span("host", "engine.admit"):
            raise KeyError("the engine's own")
    assert [e[3] for e in tl.snapshot()] == ["engine.launch.decode",
                                             "engine.admit"]


def test_the_annotation_takes_scalars_and_the_ring_keeps_everything():
    tl = EngineTimeline(16)
    _Annotation.seen = []
    tl.annotate = _Annotation
    with tl.span("launch", "engine.launch.prefill", rows=2, bucket=32,
                 trace_ids=["t-a", "t-b"]):
        pass
    assert _Annotation.seen[0][2] == {"rows": 2, "bucket": 32}
    assert tl.snapshot()[0][6]["trace_ids"] == ["t-a", "t-b"]


def test_launch_and_fetch_tracks_render_as_lanes_of_their_own():
    tl = EngineTimeline(16)
    for track in ("host", "launch", "fetch", "device"):
        with tl.span(track, f"engine.on.{track}"):
            pass
    trace = to_chrome_trace(tl.snapshot())
    tids = {e["name"]: e["tid"] for e in trace["traceEvents"]
            if e["name"].startswith("engine.on.")}
    assert len(set(tids.values())) == 4
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("name") == "thread_name"}
    assert names == {"host", "launch", "fetch", "device"}


# ------------------------------------------- the profiler's control


def test_no_annotation_factory_outside_start_and_stop(tmp_path,
                                                      monkeypatch):
    """With no capture active the engine calls nothing of
    jax.profiler: the factory is installed by start, removed by stop,
    and a refused second start leaves it as it was."""
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: calls.append((a, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    control = ProfilerControl()
    assert TIMELINE.annotate is None
    assert control.start(str(tmp_path))
    assert TIMELINE.annotate is jax.profiler.TraceAnnotation
    assert not control.start(str(tmp_path / "again"))
    assert TIMELINE.annotate is jax.profiler.TraceAnnotation
    assert control.stop() == str(tmp_path)
    assert TIMELINE.annotate is None
    assert control.stop() is None
    assert len(calls) == 1


def test_python_tracer_false_reaches_start_trace(tmp_path, monkeypatch):
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **kw: calls.append((a, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    control = ProfilerControl()
    control.start(str(tmp_path))
    control.stop()
    control.start(str(tmp_path), python_tracer=False)
    control.stop()
    # The default call is what it was: the log directory alone.
    assert calls[0] == ((str(tmp_path),), {})
    options = calls[1][1]["profiler_options"]
    assert options.python_tracer_level == 0


async def test_a_failed_start_installs_no_factory(tmp_path, monkeypatch):
    def refuse(*a, **kw):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    control = ProfilerControl()
    with pytest.raises(RuntimeError):
        control.start(str(tmp_path))
    assert TIMELINE.annotate is None and control.active_dir is None


# ------------------------------------------ spans of a real capture


async def test_a_real_capture_holds_engine_spans_on_their_threads(
        tiny, tmp_path):
    """jax.profiler on the CPU around a tiny generator: the launching
    thread's prep and launch spans share one line of the trace, the
    fetch workers' another, the loop's deliver and waits a third."""
    control = ProfilerControl()
    eng = make_engine(tiny)
    try:
        # warm every program first, so that the capture holds no compile
        await eng.complete(prompt_of(9), max_new_tokens=4)
        assert control.start(str(tmp_path), python_tracer=False)
        try:
            await asyncio.gather(*[
                eng.complete(prompt_of(9, stride=s), max_new_tokens=6)
                for s in (3, 5)])
        finally:
            control.stop()
    finally:
        await eng.close()
    lines = engine_span_lines(str(tmp_path))
    for name in ("engine.launch.decode", "engine.prep.decode",
                 "engine.launch.prefill", "engine.prep.prefill",
                 "engine.launch.insert", "engine.launch.feed",
                 "engine.fetch", "engine.deliver", "engine.admit",
                 "engine.grow", "engine.wait.fetch"):
        assert name in lines, (name, sorted(lines))
    launcher = lines["engine.launch.decode"]
    assert len(launcher) == 1  # one launching thread
    for name in ("engine.prep.decode", "engine.prep.prefill",
                 "engine.launch.prefill", "engine.launch.insert"):
        assert lines[name] == launcher, name
    loop = lines["engine.deliver"]
    assert len(loop) == 1
    assert lines["engine.wait.fetch"] == loop == lines["engine.admit"]
    assert not lines["engine.fetch"] & (launcher | loop)
    assert launcher != loop


# ------------------------------------- time to first token, by stage

STAGES = ("queued", "dispatch", "delivery")


def _ttft_readings():
    whole = hist("kfserving_tpu_llm_ttft_ms")
    stages = [hist("kfserving_tpu_generator_ttft_stage_ms", stage=s)
              for s in STAGES]
    return whole, stages


async def test_ttft_stages_sum_to_ttft(tiny):
    (n0, sum0), before = _ttft_readings()
    eng = make_engine(tiny, max_slots=2)
    try:
        # five requests over two slots: some wait in the queue
        await asyncio.gather(*[
            eng.complete(prompt_of(8 + i), max_new_tokens=5)
            for i in range(5)])
    finally:
        await eng.close()
    (n1, sum1), after = _ttft_readings()
    assert n1 - n0 == 5
    for (c0, _), (c1, _) in zip(before, after):
        assert c1 - c0 == 5
    parts = [s1 - s0 for (_, s0), (_, s1) in zip(before, after)]
    assert all(p >= 0 for p in parts)
    assert sum(parts) == pytest.approx(sum1 - sum0, rel=1e-9, abs=1e-6)
    # with two slots the later arrivals waited: queued is not nothing
    assert parts[0] > 0


async def test_ttft_stages_of_a_chunked_admission(tiny):
    """A cold prompt is admitted by chunks: its dispatch stage ends
    when the first chunk is enqueued, and the stages still sum."""
    (n0, sum0), before = _ttft_readings()
    eng = make_engine(tiny, block_size=16, prefill_chunk_tokens=16)
    try:
        await eng.complete(prompt_of(40), max_new_tokens=3)
        assert eng.stats()["chunked_prefill"]["admissions"] == 1
    finally:
        await eng.close()
    (n1, sum1), after = _ttft_readings()
    assert n1 - n0 == 1
    parts = [s1 - s0 for (_, s0), (_, s1) in zip(before, after)]
    assert sum(parts) == pytest.approx(sum1 - sum0, rel=1e-9, abs=1e-6)


async def test_a_preempted_and_resumed_request_is_counted_once(tiny):
    """Pool pressure preempts running streams and re-admits them; a
    stream that had emitted is taken and enqueued a second time and
    observed in no stage again."""
    (n0, _), before = _ttft_readings()
    prompts = [[(i * 7 + j) % 90 + 1 for j in range(42)]
               for i in range(3)]
    eng = make_engine(tiny, block_size=16, cache_blocks=10,
                      prefill_buckets=[16, 32, 64], steps_per_call=1)
    try:
        await asyncio.wait_for(asyncio.gather(*[
            eng.complete(p, max_new_tokens=20) for p in prompts]),
            timeout=300)
        assert eng.stats()["paged"]["preemptions"] >= 1
    finally:
        await eng.close()
    (n1, _), after = _ttft_readings()
    assert n1 - n0 == 3
    for (c0, _), (c1, _) in zip(before, after):
        assert c1 - c0 == 3


# ------------------------------------------- host time per dispatch


async def test_dispatch_host_ms_counts_equal_the_engines_own(tiny):
    name = "kfserving_tpu_generator_dispatch_host_ms"
    before = {p: hist(name, program=p)[0]
              for p in ("decode", "prefill", "chunk", "spec")}
    eng = make_engine(tiny, block_size=16, prefill_chunk_tokens=32)
    try:
        await asyncio.gather(
            eng.complete(prompt_of(9), max_new_tokens=7),
            eng.complete(prompt_of(12, stride=3), max_new_tokens=5),
            eng.complete(prompt_of(50, stride=5), max_new_tokens=4))
        stats = eng.stats()
    finally:
        await eng.close()
    moved = {p: hist(name, program=p)[0] - n for p, n in before.items()}
    assert moved["decode"] == stats["decode_steps"] > 0
    assert moved["prefill"] == stats["prefills"] > 0
    assert moved["chunk"] == \
        stats["chunked_prefill"]["chunks_dispatched"] > 0
    assert moved["spec"] == 0
    assert hist(name, program="decode")[1] > 0


async def test_a_prefill_launch_carries_the_trace_ids_of_its_rows(tiny):
    eng = make_engine(tiny)
    try:
        pending = []
        for trace in ("trace-a", "trace-b"):
            current_request_id.set(trace)
            pending.append(asyncio.ensure_future(
                eng.complete(prompt_of(9), max_new_tokens=3)))
        await asyncio.gather(*pending)
    finally:
        current_request_id.set(None)
        await eng.close()
    launches = [e for e in TIMELINE.snapshot()
                if e[3] == "engine.launch.prefill"]
    carried = [t for e in launches for t in e[6]["trace_ids"]]
    assert sorted(carried) == ["trace-a", "trace-b"]
    assert all(e[2] == "launch" and e[6]["bucket"] == 16
               for e in launches)
    decode = [e for e in TIMELINE.snapshot()
              if e[3] == "engine.launch.decode"]
    # beside its shape a launch carries its number in the in-flight
    # table (ISSUE 39; tests/test_inflight.py holds it to its fetch)
    assert decode and all(
        e[6] == {"seq": e[6]["seq"], "rows": 4, "steps": 2}
        for e in decode)


# ------------------------------------- programs traced, by JAX itself


@pytest.fixture
def compile_counters(monkeypatch):
    """The process-wide listeners, for one test: taken off again, so
    that no later test finds their counts in its registry."""
    monkeypatch.setattr(compile_cache, "_counting", False)
    compile_cache.count_jax_compile_events()
    yield
    jax.monitoring.unregister_event_duration_listener(
        compile_cache._on_jax_event)
    jax.monitoring.unregister_event_listener(compile_cache._on_jax_event)


def test_a_retrace_after_warm_up_is_counted_and_a_warm_call_is_not(
        compile_counters):
    compile_cache.count_jax_compile_events()  # registered once

    def traced():
        return counter("kfserving_tpu_jax_compile_events_total",
                       event="trace")

    @jax.jit
    def double(x):
        return x * 2

    strong = jnp.ones((3,), jnp.float32)
    weak = jax.lax.full((3,), 1.0)  # float32[3] too, weakly typed
    assert weak.weak_type and weak.dtype == strong.dtype
    double(strong).block_until_ready()
    warm = traced()
    assert warm >= 1
    double(strong).block_until_ready()
    assert traced() == warm
    # the same name, shape and dtype: a retrace that a key set of
    # (program, shape) cannot tell from the warm call
    double(weak).block_until_ready()
    retraced = traced()
    assert retraced > warm  # the jitted function, and any it nests
    double(weak).block_until_ready()
    assert traced() == retraced
    lowered = counter("kfserving_tpu_jax_compile_events_total",
                      event="lower")
    compiled = counter("kfserving_tpu_jax_compile_events_total",
                       event="backend_compile")
    assert lowered >= 2 and compiled >= 2


# ------------------------------------------------ peak device memory


def test_the_device_record_carries_hbm_peak(monkeypatch):
    from kfserving_tpu import startup

    class _Device:
        def __init__(self, stats):
            self._stats = stats

        def memory_stats(self):
            return self._stats

    monkeypatch.setattr(startup, "_device", {"platform": "tpu"})
    monkeypatch.setattr(jax, "devices", lambda: [
        _Device({"bytes_in_use": 7, "peak_bytes_in_use": 15}),
        _Device(None)])
    record = startup.device()
    assert record["hbm_in_use"] == [7, None]
    assert record["hbm_peak"] == [15, None]
    assert record["platform"] == "tpu"


async def test_v2_carries_hbm_peak(monkeypatch, compile_counters):
    import aiohttp

    from kfserving_tpu import startup
    from kfserving_tpu.server.app import ModelServer

    monkeypatch.setattr(startup, "_device", None)
    startup.report_device()
    server = ModelServer(http_port=0)
    await server.start_async([], host="127.0.0.1")
    try:
        async with aiohttp.ClientSession() as s:
            async with s.get(
                    f"http://127.0.0.1:{server.http_port}/v2") as r:
                device = (await r.json())["device"]
    finally:
        await server.stop_async()
    # the CPU backend reports no memory statistics: None per device
    assert len(device["hbm_peak"]) == device["count"]
    assert len(device["hbm_in_use"]) == device["count"]
