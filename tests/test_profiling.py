"""Device-time observability (ISSUE 6): engine event timeline,
Chrome-trace/Perfetto export, live roofline gauges.

Acceptance bar: a replica that served a chunked-prefill generate run
answers `GET /debug/profile` with valid Chrome-trace JSON containing
wave, chunk, preemption, and device-dispatch slices correlated by
trace id — and the MFU / padding-waste / goodput gauges federate
through the router under a `replica` label, consistent with the
engine's own offline stats.
"""

import asyncio
import json
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from kfserving_tpu.engine.generator import GenerationEngine
from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny
from kfserving_tpu.observability.profiling import (
    TIMELINE,
    EngineTimeline,
    merge_traces,
    summarize,
    to_chrome_trace,
)

MAX_SEQ = 128
BS = 16
CHUNK = 32


@pytest.fixture(scope="module")
def tiny():
    cfg = decoder_tiny(num_layers=2, hidden_size=64, num_heads=2,
                       intermediate_size=128, max_seq=MAX_SEQ,
                       vocab_size=96)
    module = DecoderLM(cfg)
    variables = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    return module, variables, cfg


@pytest.fixture(autouse=True)
def _clear_timeline():
    TIMELINE.clear()
    yield
    TIMELINE.clear()


def make_engine(tiny, chunk=CHUNK, **kw):
    module, variables, _ = tiny
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [16, 32, 64, MAX_SEQ])
    kw.setdefault("block_size", BS)
    return GenerationEngine(module, variables,
                            prefill_chunk_tokens=chunk, **kw)


def prompt_of(n, stride=7):
    return [(i * stride) % 90 + 1 for i in range(n)]


# --------------------------------------------------------- ring bounds


def test_ring_bounded_under_event_storm():
    """A sustained storm changes WHICH events survive, never how much
    memory the ring holds."""
    tl = EngineTimeline(capacity=64)
    for i in range(64 * 10):
        tl.record("device", "decode.wave", dur_s=0.001,
                  attrs={"i": i})
    assert tl.recorded == 640
    events = tl.snapshot()
    assert len(events) == 64
    assert len(tl._ring) == 64  # preallocated, never grew
    # Oldest-first, and only the newest capacity survive.
    indices = [e[6]["i"] for e in events]
    assert indices == list(range(640 - 64, 640))


def test_record_hot_path_never_blocks():
    """record() is O(1) with no I/O: 50k events land in well under a
    second even with a reader hammering snapshots concurrently — the
    generator's scheduler loop can afford it per wave."""
    tl = EngineTimeline(capacity=256)
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            tl.snapshot(window_s=10.0)

    t = threading.Thread(target=reader)
    t.start()
    try:
        t0 = time.perf_counter()
        for i in range(50_000):
            tl.record("device", "decode.wave", dur_s=0.0001, slot=1)
        elapsed = time.perf_counter() - t0
    finally:
        stop.set()
        t.join()
    assert tl.recorded == 50_000
    assert elapsed < 5.0  # generous CI bound; typical is ~0.1 s


def test_concurrent_writer_exporter_race():
    """Writers rotating the ring under a live exporter: every export
    must remain valid JSON with schema-complete events (immutable
    event tuples make the copied snapshot torn-write-free)."""
    tl = EngineTimeline(capacity=128)
    errors = []
    stop = threading.Event()

    def writer(tid):
        i = 0
        while not stop.is_set():
            tl.record("slot", "decode", dur_s=0.001, slot=tid,
                      trace_id=f"t{tid}", attrs={"i": i})
            i += 1

    def exporter():
        while not stop.is_set():
            try:
                trace = to_chrome_trace(tl.snapshot())
                parsed = json.loads(json.dumps(trace))
                assert isinstance(parsed["traceEvents"], list)
            except Exception as e:  # pragma: no cover - failure path
                errors.append(e)
                return

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(3)]
    threads.append(threading.Thread(target=exporter))
    for t in threads:
        t.start()
    time.sleep(0.3)
    stop.set()
    for t in threads:
        t.join()
    assert errors == []


# ----------------------------------------------------- trace schema


def _validate_chrome_trace(trace):
    """Golden schema check: the invariants Perfetto/chrome://tracing
    require of the Trace Event JSON object form."""
    assert isinstance(trace, dict)
    assert isinstance(trace["traceEvents"], list)
    for ev in trace["traceEvents"]:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "C", "M")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "M":
            assert ev["name"] in ("process_name", "thread_name")
            assert isinstance(ev["args"]["name"], str)
            continue
        assert isinstance(ev["ts"], (int, float))
        if ev["ph"] == "X":
            assert isinstance(ev["dur"], (int, float))
            assert ev["dur"] >= 0
        if ev["ph"] == "i":
            assert ev["s"] in ("t", "p", "g")
        if ev["ph"] == "C":
            assert all(isinstance(v, (int, float))
                       for v in ev["args"].values())


def test_chrome_trace_export_schema():
    tl = EngineTimeline(capacity=64)
    t0 = 1000.0
    tl.record("device", "decode.wave", dur_s=0.010, t_end=t0,
              attrs={"steps": 4})
    tl.record("slot", "decode", dur_s=0.010, t_end=t0,
              trace_id="abc123", slot=2)
    tl.record("host", "preempt", t_end=t0, trace_id="abc123", slot=2,
              attrs={"phase": "prefill"})
    tl.counter("pool", {"active_slots": 2, "free_blocks": 5})
    trace = to_chrome_trace(tl.snapshot())
    _validate_chrome_trace(trace)
    json.loads(json.dumps(trace))  # round-trips
    events = trace["traceEvents"]
    # Tracks: device tid 2, slot 2 -> tid 12, host instant tid 1.
    wave = next(e for e in events if e["name"] == "decode.wave")
    assert (wave["ph"], wave["tid"]) == ("X", 2)
    assert wave["ts"] == pytest.approx((t0 - 0.010) * 1e6)
    assert wave["dur"] == pytest.approx(10_000.0)
    slot_ev = next(e for e in events if e["name"] == "decode")
    assert slot_ev["tid"] == 12
    assert slot_ev["args"]["trace_id"] == "abc123"
    preempt = next(e for e in events if e["name"] == "preempt")
    assert (preempt["ph"], preempt["tid"]) == ("i", 1)
    counter = next(e for e in events if e["ph"] == "C")
    assert counter["args"] == {"active_slots": 2, "free_blocks": 5}
    thread_names = {e["tid"]: e["args"]["name"] for e in events
                    if e["ph"] == "M" and e["name"] == "thread_name"}
    assert thread_names[2] == "device"
    assert thread_names[12] == "slot 2"


def test_merge_traces_repids_replicas():
    tl = EngineTimeline(capacity=8)
    tl.record("device", "decode.wave", dur_s=0.001)
    one = to_chrome_trace(tl.snapshot())
    merged = merge_traces([("h1:1", one), ("h2:2", one)])
    _validate_chrome_trace(merged)
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {1, 2}
    procs = [e["args"]["name"] for e in merged["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"]
    assert any(p.startswith("h1:1") for p in procs)
    assert any(p.startswith("h2:2") for p in procs)


def test_summarize_gaps_hold_suppressed():
    tl = EngineTimeline(capacity=64)
    # Device slices at 0-10ms, 15-25ms, 26-36ms -> gaps 5ms and 1ms.
    for start, dur in ((0.0, 0.010), (0.015, 0.010), (0.026, 0.010)):
        tl.record("device", "decode.wave", dur_s=dur,
                  t_end=100.0 + start + dur)
    tl.record("host", "hold", dur_s=0.040, t_end=100.2)
    tl.record("host", "wave.suppressed", t_end=100.3)
    tl.record("host", "preempt", t_end=100.3)
    s = summarize(tl.snapshot())
    assert s["decode_waves"] == 3
    # Gaps between the "device" track's slices are gaps between host
    # clocks: no summary of them is given (device idle time is read
    # from the profiler's trace, chipbench/engine_phases.py).
    assert not any(k.startswith("dispatch_gap") for k in s)
    assert s["hold_ms"] == pytest.approx(40.0, abs=0.01)
    assert s["suppressed_waves"] == 1
    assert s["suppressed_wave_ratio"] == 0.25
    assert s["preemptions"] == 1


def test_window_overlap_selects_span_events():
    tl = EngineTimeline(capacity=64)
    tl.record("device", "old", dur_s=0.01, t_end=100.0)
    tl.record("device", "in", dur_s=0.01, t_end=200.0)
    tl.record("device", "straddle", dur_s=5.0, t_end=201.0)
    tl.record("device", "late", dur_s=0.01, t_end=300.0)
    names = [e["name"] for e in tl.window(199.0, 202.0)]
    assert names == ["in", "straddle"]
    assert all("dur_ms" in e and "t" in e
               for e in tl.window(199.0, 202.0))
    assert tl.window(199.0, 202.0, limit=1) == [
        tl.window(199.0, 202.0)[-1]]
    assert tl.window(199.0, 202.0, limit=0) == []  # none, not all


# --------------------------------------------------- check_metrics


def test_ratio_gauge_lint_rule():
    from kfserving_tpu.tools.check_metrics import lint_exposition

    good = ("# TYPE kfserving_tpu_engine_goodput_ratio gauge\n"
            'kfserving_tpu_engine_goodput_ratio{model="m"} 0.97\n')
    assert lint_exposition(good) == []
    bad = ("# TYPE kfserving_tpu_engine_goodput_ratio gauge\n"
           'kfserving_tpu_engine_goodput_ratio{model="m"} 1.7\n')
    problems = lint_exposition(bad)
    assert any("outside [0, 1]" in p for p in problems)
    nan = ("# TYPE kfserving_tpu_engine_goodput_ratio gauge\n"
           'kfserving_tpu_engine_goodput_ratio{model="m"} nan\n')
    assert any("outside [0, 1]" in p for p in lint_exposition(nan))


def test_roofline_families_lint_and_clamp():
    """Every roofline family passes the house lint, and publish
    clamps ratio gauges into the unit the suffix declares."""
    from kfserving_tpu.observability import REGISTRY
    from kfserving_tpu.observability.profiling import roofline
    from kfserving_tpu.tools.check_metrics import (
        lint_exposition,
        lint_families,
    )

    consumed = roofline.publish_gauges("m", {
        "mfu": 0.4, "decode_mfu": 0.01, "prefill_mfu": 0.2,
        "achieved_tflops": 80.0, "achieved_decode_tflops": 2.0,
        "goodput_ratio": 1.2,           # broken accounting: clamped
        "hbm_bw_util": 0.5,
        "bucket_pad_waste": {"b8": 0.25},
        "prefill_bucket_pad_waste": {"s64": 0.1},
    })
    assert {"mfu", "goodput_ratio", "hbm_bw_util",
            "bucket_pad_waste",
            "prefill_bucket_pad_waste"} <= consumed
    fams = {n: k for n, k in REGISTRY.families().items()
            if "engine" in n}
    assert "kfserving_tpu_engine_mfu" in fams
    assert lint_families(fams) == []
    text = REGISTRY.render(exemplars=False)
    assert lint_exposition(text) == []
    assert 'kfserving_tpu_engine_goodput_ratio{model="m"} 1' in text
    assert ('kfserving_tpu_engine_padding_waste_ratio'
            '{bucket="b8",model="m"} 0.25') in text


# ------------------------------------------- engine e2e (the tentpole)


async def test_engine_timeline_and_roofline_stats(tiny, monkeypatch):
    """A chunked-prefill run under pool pressure leaves wave, chunk,
    AND preemption events on the timeline — trace-id correlated —
    and the engine's stats carry the roofline block the gauges
    promote."""
    from kfserving_tpu.tracing import current_request_id

    monkeypatch.setenv("KFS_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("KFS_PEAK_HBM_BW", "1e9")
    # Live prompt under prefill_chunk_tokens -> the BUCKETED prefill
    # path; the 96-token cold prompt -> the chunked path.  8 blocks:
    # live (2 + growth to 3) + cold (6) collide -> mid-prefill
    # preemption of the cold request.
    p_live = prompt_of(30, stride=5)
    p_cold = prompt_of(96, stride=3)
    eng = make_engine(tiny, max_slots=4, cache_blocks=8,
                      steps_per_call=1, pipeline_depth=1)
    try:
        current_request_id.set("trace-live")
        live = asyncio.ensure_future(
            eng.complete(p_live, max_new_tokens=10))
        for _ in range(100):
            await asyncio.sleep(0.005)
            if any(s is not None for s in eng._slots):
                break
        current_request_id.set("trace-cold")
        cold = asyncio.ensure_future(
            eng.complete(p_cold, max_new_tokens=8))
        await asyncio.wait_for(live, timeout=120)
        await asyncio.wait_for(cold, timeout=120)
        stats = eng.stats()
    finally:
        current_request_id.set(None)
        await eng.close()

    events = TIMELINE.snapshot()
    by_name = {}
    for e in events:
        by_name.setdefault(e[3], []).append(e)
    assert "decode.wave" in by_name          # wave slices
    assert "prefill.chunk" in by_name        # chunk slices
    assert "preempt" in by_name              # preemption marker
    assert "prefill.bucket" in by_name       # bucketed admission
    # Trace-id correlation: chunk slices belong to the cold request,
    # per-slot decode slices to the live one.
    assert any(e[4] == "trace-cold" for e in by_name["prefill.chunk"])
    assert any(e[4] == "trace-live" for e in events
               if e[2] == "slot" and e[3] == "decode")
    assert any(e[4] == "trace-cold" for e in by_name["preempt"])
    # Pool occupancy samples rode along.
    assert any(e[2] == "counter" for e in events)

    # Roofline block: present and sane with the env peaks set.
    assert 0 < stats["decode_mfu"] <= 1.0
    assert 0 < stats["prefill_mfu"]
    assert 0 < stats["goodput_ratio"] <= 1.0
    assert 0 < stats["hbm_bw_util"] <= 1.0
    assert stats["achieved_decode_tflops"] > 0
    waste = stats["prefill_bucket_pad_waste"]
    assert all(0.0 <= v <= 1.0 for v in waste.values())

    # The exported trace is schema-valid and carries the correlation.
    trace = to_chrome_trace(events)
    _validate_chrome_trace(trace)
    traced = {e["args"].get("trace_id") for e in trace["traceEvents"]
              if e["ph"] != "M"}
    assert {"trace-live", "trace-cold"} <= traced

    # summarize() sees the same run the trace renders.
    s = summarize(events)
    assert s["decode_waves"] >= 1
    assert s["prefill_chunks"] >= 1
    assert s["preemptions"] >= 1


# --------------------------------------------------- HTTP endpoints


def _write_gen_dir(tmp_path, name, extra=None):
    d = tmp_path / name
    d.mkdir()
    cfg = {
        "architecture": "decoder_tiny",
        "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                        "num_heads": 2, "intermediate_size": 128,
                        "max_seq": 128},
        "max_slots": 2, "max_seq": 128,
        "prefill_buckets": [16, 32, 64, 128],
        "max_new_tokens": 6, "tokenizer": "byte",
        "block_size": 16, "prefill_chunk_tokens": 32,
    }
    cfg.update(extra or {})
    (d / "config.json").write_text(json.dumps(cfg))
    return str(d)


async def test_debug_profile_endpoint(tmp_path, monkeypatch):
    """GET /debug/profile on a replica that served a chunked-prefill
    generate run returns valid Chrome-trace JSON with wave + chunk
    slices; ?format=events returns the raw ring; bad params 400."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel
    from kfserving_tpu.server.app import ModelServer

    monkeypatch.setenv("KFS_PEAK_FLOPS", "1e12")
    model = GenerativeModel("gen", _write_gen_dir(tmp_path, "gen"))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    prompt = "a cold prompt long enough to be chunked into pieces"
    try:
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v2/models/gen/generate",
                              json={"text_input": prompt}) as r:
                assert r.status == 200, await r.text()
            async with s.get(f"{base}/debug/profile?window_s=60"
                             ) as r:
                assert r.status == 200
                trace = await r.json()
            _validate_chrome_trace(trace)
            names = {e["name"] for e in trace["traceEvents"]}
            assert "decode.wave" in names
            assert "prefill.chunk" in names
            async with s.get(f"{base}/debug/profile?format=events"
                             ) as r:
                assert r.status == 200
                body = await r.json()
            assert body["recorded"] >= 1
            assert any(e["name"] == "decode.wave"
                       for e in body["events"])
            async with s.get(f"{base}/debug/profile?window_s=zap"
                             ) as r:
                assert r.status == 400
            async with s.get(f"{base}/debug/profile?format=pb"
                             ) as r:
                assert r.status == 400
            # Roofline gauges land on the replica's own /metrics.
            async with s.get(f"{base}/metrics") as r:
                text = await r.text()
            assert "kfserving_tpu_engine_mfu{" in text
            assert "kfserving_tpu_engine_goodput_ratio{" in text
            # Exactly one declaration per family in the merged
            # private+global exposition (the consumed-keys contract).
            types = [ln.split()[2] for ln in text.splitlines()
                     if ln.startswith("# TYPE ")]
            assert len(types) == len(set(types))
    finally:
        await server.stop_async()


async def test_profile_capture_window(tmp_path, monkeypatch):
    """POST /debug/profile/capture holds the profiler for the window
    and releases it on every path; concurrent captures 409.  The
    profiler is stubbed — real jax.profiler init costs ~25 s on this
    backend and belongs in the slow tier (below)."""
    import aiohttp

    import kfserving_tpu.tracing as tracing
    from kfserving_tpu.server.app import ModelServer

    class _StubProfiler:
        def __init__(self):
            self.active_dir = None
            self.stopped = 0
            self.python_tracer = []

        def start(self, log_dir, python_tracer=True):
            if self.active_dir is not None:
                return False
            self.active_dir = log_dir
            self.python_tracer.append(python_tracer)
            return True

        def stop(self):
            out, self.active_dir = self.active_dir, None
            self.stopped += 1
            return out

    stub = _StubProfiler()
    monkeypatch.setattr(tracing, "profiler", stub)
    server = ModelServer(http_port=0)
    await server.start_async([], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    log_dir = str(tmp_path / "capture")
    try:
        async with aiohttp.ClientSession() as s:
            first = asyncio.ensure_future(s.post(
                f"{base}/debug/profile/capture",
                json={"duration_s": 0.5, "log_dir": log_dir}))
            await asyncio.sleep(0.1)
            async with s.post(f"{base}/debug/profile/capture",
                              json={"duration_s": 0.1}) as r2:
                assert r2.status == 409
            r1 = await first
            assert r1.status == 200, await r1.text()
            out = await r1.json()
            assert out["captured"] is True
            assert out["log_dir"] == log_dir
            assert stub.stopped == 1  # released
            # A second capture works once the first released.
            async with s.post(f"{base}/debug/profile/capture",
                              json={"duration_s": 0.1,
                                    "log_dir": log_dir,
                                    "python_tracer": False}) as r3:
                assert r3.status == 200
            assert stub.stopped == 2
            # The Python tracer stays on unless the body says false.
            assert stub.python_tracer == [True, False]
            async with s.post(f"{base}/debug/profile/capture",
                              json={"duration_s": "zap"}) as r4:
                assert r4.status == 400
    finally:
        await server.stop_async()


@pytest.mark.slow
async def test_profile_capture_real_jax_profiler(tmp_path):
    """The unstubbed path: a real jax.profiler capture window writes
    a trace under log_dir and releases the control — and the trace
    holds the engine's phases, each on the thread that did the work
    (the launching thread's prep and launch spans on one line, the
    fetch on another, the loop's deliver on a third)."""
    import os

    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel
    from kfserving_tpu.server.app import ModelServer
    from kfserving_tpu.tracing import profiler
    from tests.utils import engine_span_lines

    model = GenerativeModel("gen", _write_gen_dir(tmp_path, "gen"))
    model.load()
    server = ModelServer(http_port=0)
    await server.start_async([model], host="127.0.0.1")
    base = f"http://127.0.0.1:{server.http_port}"
    log_dir = str(tmp_path / "capture")
    try:
        async with aiohttp.ClientSession() as s:
            async def generate():
                async with s.post(f"{base}/v2/models/gen/generate",
                                  json={"text_input": "short"}) as r:
                    assert r.status == 200, await r.text()

            await generate()  # warm: the capture holds no compile
            capture = asyncio.ensure_future(s.post(
                f"{base}/debug/profile/capture",
                json={"duration_s": 1.0, "log_dir": log_dir,
                      "python_tracer": False}))
            await asyncio.sleep(0.3)
            assert TIMELINE.annotate is not None
            await generate()
            r = await capture
            assert r.status == 200, await r.text()
            assert (await r.json())["captured"] is True
        assert os.path.isdir(log_dir)
        assert profiler.active_dir is None  # released
        assert TIMELINE.annotate is None
    finally:
        await server.stop_async()
    lines = engine_span_lines(log_dir)
    assert {"engine.launch.decode", "engine.prep.prefill",
            "engine.fetch", "engine.deliver"} <= set(lines)
    assert lines["engine.launch.decode"] == lines["engine.prep.prefill"]
    assert not lines["engine.fetch"] & lines["engine.launch.decode"]
    assert not lines["engine.deliver"] & (
        lines["engine.launch.decode"] | lines["engine.fetch"])


async def test_pinned_flightrecorder_embeds_engine_events(tmp_path):
    """A pinned (5xx) request's flight-recorder entry embeds the
    engine events overlapping its span — the wave/chunk evidence a
    p99 pin needs."""
    from kfserving_tpu.server.app import ModelServer

    server = ModelServer(http_port=0)
    TIMELINE.record("device", "decode.wave", dur_s=0.020)
    server.monitoring.record_request("m", "generate", 500, 50.0,
                                     trace_id="t1")
    dump = server.monitoring.dump_flightrecorder()
    pinned = dump["pinned"]
    assert pinned and pinned[0]["pinned"] == "error"
    embedded = pinned[0]["engine_events"]
    assert any(e["name"] == "decode.wave" for e in embedded)


# ------------------------------------------ router federation (CI)


async def test_router_federates_roofline_and_profile(tmp_path,
                                                     monkeypatch):
    """Acceptance: the roofline families scrape through the router
    under a `replica` label with values consistent with the engine's
    own stats, and /debug/profile federates the replica timeline as
    one merged Chrome trace."""
    import aiohttp

    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.orchestrator import (
        InProcessOrchestrator,
    )
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import (
        InferenceService,
        PredictorSpec,
    )
    from kfserving_tpu.tools.check_metrics import lint_exposition

    monkeypatch.setenv("KFS_PEAK_FLOPS", "1e12")
    model_dir = _write_gen_dir(tmp_path, "writer")
    orch = InProcessOrchestrator()
    controller = Controller(orch)
    router = IngressRouter(controller)
    await router.start_async()
    try:
        isvc = InferenceService(
            name="writer",
            predictor=PredictorSpec(framework="generative",
                                    storage_uri=model_dir))
        status = await controller.apply(isvc)
        assert status.ready
        base = f"http://127.0.0.1:{router.http_port}"
        prompt = "a cold prompt long enough to be chunked into pieces"
        async with aiohttp.ClientSession() as s:
            async with s.post(f"{base}/v1/models/writer:generate",
                              json={"prompt": prompt,
                                    "max_tokens": 6}) as r:
                assert r.status == 200, await r.text()
            async with s.get(f"{base}/metrics") as r:
                assert r.status == 200
                text = await r.text()
            async with s.get(f"{base}/debug/profile") as r:
                assert r.status == 200
                trace = await r.json()
        # Roofline families federated under the replica label.
        assert 'kfserving_tpu_engine_mfu{' in text
        mfu_lines = [ln for ln in text.splitlines()
                     if ln.startswith("kfserving_tpu_engine_mfu{")
                     and 'replica="' in ln]
        assert mfu_lines, "mfu must carry the replica label"
        good_lines = [
            ln for ln in text.splitlines()
            if ln.startswith("kfserving_tpu_engine_goodput_ratio{")
            and 'replica="' in ln]
        assert good_lines
        # Gauge value consistent (±10%) with the engine's own stats.
        comp = orch.state["default/writer/predictor"].replicas[0]
        stats = comp.handle.repository.get_model(
            "writer").engine_stats()
        scraped = float(good_lines[0].rsplit(" ", 1)[1])
        assert scraped == pytest.approx(stats["goodput_ratio"],
                                        rel=0.10)
        # The federated exposition passes the house lint (including
        # the new _ratio bounds rule).
        assert lint_exposition(text) == []
        # Merged fleet trace: replica process group with wave/chunk
        # slices.
        _validate_chrome_trace(trace)
        names = {e["name"] for e in trace["traceEvents"]}
        assert "decode.wave" in names
        assert "prefill.chunk" in names
        procs = [e["args"]["name"] for e in trace["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"]
        assert procs and all("·" in p for p in procs)
    finally:
        await router.stop_async()
        await orch.shutdown()
