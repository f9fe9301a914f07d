"""The five BASELINE.json benchmark configs, each driven end-to-end
through the real HTTP serving stack.

1. sklearn-iris SVC, V1 predict, fixed-rate sweep (CPU reference path;
   reference test/benchmark/README.md:58-66 table shape).
2. jaxserver ResNet-50, uint8 wire + dynamic batching (the headline
   req/s/chip number + engine MFU/latency breakdown).
3. jaxserver BERT fill-mask with seq-len bucketed batching.
4. multi-model serving: 8 Flax MLPs hot-swapped through the V2
   repository API on one chip.
5. transformer -> predictor chain through the ingress router
   (image preprocess + ViT classify).

Smoke mode (CPU backend) swaps the big models for tiny ones and cuts
request counts so the whole matrix runs in ~a minute hermetically.
"""

import asyncio
import contextlib
import json
import os
import tempfile
import time
from typing import Any, Dict, List

import numpy as np

from benchmarks.harness import closed_loop, np_json_body, open_loop

IRIS_ROWS = [[6.8, 2.8, 4.8, 1.4], [6.0, 3.4, 4.5, 1.6]]


def _write_jax_model_dir(arch: str, arch_kwargs: Dict[str, Any] = None,
                         **config) -> str:
    model_dir = tempfile.mkdtemp(prefix=f"bench-{arch}-")
    cfg = {"architecture": arch, "arch_kwargs": arch_kwargs or {}}
    cfg.update(config)
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(cfg, f)
    # No checkpoint: random init serves fine for throughput benchmarks.
    return model_dir


async def _serve(models, **server_kwargs):
    from kfserving_tpu.server.app import ModelServer

    server = ModelServer(http_port=0, **server_kwargs)
    await server.start_async(models, host="127.0.0.1")
    return server


def _reset_timeline() -> None:
    """Each generate config summarizes ITS OWN device timeline: the
    engine event ring is process-wide, and a previous config's waves
    leaking into this config's counts would corrupt the committed
    summary."""
    from kfserving_tpu.observability.profiling import TIMELINE

    TIMELINE.clear()


def _timeline_summary() -> Dict[str, Any]:
    """Device-timeline summary for the committed bench record
    (HOLD time, suppressed-wave ratio, slice counts) — the
    same events `GET /debug/profile` renders, so the BENCH JSON and
    the Perfetto view can never disagree.  Scope: the WHOLE config run
    since its `_reset_timeline()` (warmup and every interleaved A/B
    arm included) — per-arm comparisons stay with the bench's own gap
    measurements.  `ring_truncated` flags a wrapped ring: the counts
    then cover only the newest `ring_capacity` events, and the record
    says so instead of presenting a silent cap as full coverage."""
    from kfserving_tpu.observability.profiling import (
        TIMELINE,
        summarize,
    )

    out = summarize(TIMELINE.snapshot())
    out["events_recorded"] = TIMELINE.recorded
    out["ring_capacity"] = TIMELINE.capacity
    out["ring_truncated"] = TIMELINE.recorded > TIMELINE.capacity
    return out


def _cache_summary(model) -> Dict[str, Any]:
    """Cache economics block every generate* config commits alongside
    the PR-6 `timeline` block (ISSUE 13 bench discipline): prefix
    hit rate, tokens saved, eviction causes, and pool occupancy
    p50/p99 derived from the SAME timeline counter samples the
    Perfetto view renders — the committed JSON and /debug/profile can
    never disagree.  Dense engines commit {"paged": false} so the
    record says the cache was off instead of silently omitting it."""
    from kfserving_tpu.observability.profiling import TIMELINE

    stats = model.engine_stats()
    paged = stats.get("paged")
    if not paged:
        return {"paged": False}
    hits = paged.get("prefix_hits", 0)
    misses = paged.get("prefix_misses", 0)
    pool = paged.get("pool_blocks") or 0
    occupancy: List[float] = []
    for e in TIMELINE.snapshot():
        # (start, dur, track, name, trace_id, slot, attrs)
        if e[2] == "counter" and e[3] == "pool" and e[6] and pool:
            # Multi-engine benches (cold4k's chunked/monolithic pair)
            # share one process ring: only THIS engine's samples may
            # feed this model's occupancy ratio.
            if e[6].get("engine") not in (None, model.name):
                continue
            free = e[6].get("free_blocks")
            if free is None:
                continue
            reclaim = e[6].get("reclaimable_blocks", 0)
            occupancy.append(
                min(1.0, max(0.0, (pool - free - reclaim) / pool)))
    occ = np.asarray(occupancy or [0.0])
    return {
        "paged": True,
        "hit_rate": round(hits / max(1, hits + misses), 4),
        "prefix_hits": hits,
        "prefix_misses": misses,
        "tokens_saved": paged.get("prefill_tokens_saved", 0),
        "block_size": paged.get("block_size"),
        "index_entries": paged.get("index_entries"),
        "evictions": paged.get("evictions"),
        "occupancy_p50": round(float(np.percentile(occ, 50)), 4),
        "occupancy_p99": round(float(np.percentile(occ, 99)), 4),
        "occupancy_samples": len(occupancy),
    }


async def _sse_measure(session, url, body, gaps, ttfts,
                       stop_after_first=False):
    """POST a generate_stream and fold per-event arrival times into
    ttfts/gaps (ms) — the one SSE measurement loop the generative
    benches share (a read carrying "data: " counts as ONE event even
    if the transport coalesced several, so every config undercounts
    identically).  stop_after_first: record TTFT then drop the stream
    (the client disconnect cancels the slot server-side)."""
    t_post = time.perf_counter()
    last = None
    async with session.post(url, data=body) as r:
        assert r.status == 200, await r.text()
        async for chunk in r.content.iter_any():
            if b"data: " not in chunk:
                continue
            now = time.perf_counter()
            if last is None:
                ttfts.append((now - t_post) * 1e3)
                if stop_after_first:
                    return
            else:
                gaps.append((now - last) * 1e3)
            last = now


# -- config 1: sklearn iris --------------------------------------------------
async def bench_iris(smoke: bool) -> Dict[str, Any]:
    import joblib
    from sklearn import datasets, svm

    from kfserving_tpu.predictors.sklearnserver import SKLearnModel

    model_dir = tempfile.mkdtemp(prefix="bench-iris-")
    X, y = datasets.load_iris(return_X_y=True)
    joblib.dump(svm.SVC(gamma="scale").fit(X, y),
                os.path.join(model_dir, "model.joblib"))
    model = SKLearnModel("iris", model_dir)
    model.load()
    server = await _serve([model])
    body = json.dumps({"instances": IRIS_ROWS}).encode()
    path = "/v1/models/iris:predict"
    try:
        rates = [5, 50] if smoke else [5, 50, 500]
        duration = 2.0 if smoke else 4.0
        sweep = []
        for rate in rates:
            sweep.append(await open_loop(
                server.http_port, path, lambda i: body, rate, duration))
        peak = await closed_loop(server.http_port, path, body,
                                 num_requests=200 if smoke else 2000,
                                 concurrency=32)
        return {"sweep": sweep, "closed_loop": peak,
                # reference published p99 @500qps = 5.642ms
                # (test/benchmark/README.md:64)
                "reference_p99_ms_at_500qps": 5.642}
    finally:
        await server.stop_async()


# -- config 2: ResNet-50 (headline) ------------------------------------------
async def bench_resnet(smoke: bool) -> Dict[str, Any]:
    from kfserving_tpu.predictors.jax_model import JaxModel

    if smoke:
        model_dir = _write_jax_model_dir(
            "mlp", {"input_dim": 64, "features": [128], "num_classes": 10},
            max_batch_size=16, max_latency_ms=5.0, warmup=True,
            output="argmax")
        image = np.random.default_rng(0).normal(size=(64,)) \
            .astype(np.float32)
    else:
        # Serve big buckets and let the inflight-aware batcher fill
        # them; explicit buckets bound warmup compile count.
        model_dir = _write_jax_model_dir(
            "resnet50", max_batch_size=128,
            # Finer ladder + the batcher's bucket-aligned flushing keep
            # executed batches exactly bucket-sized (round-2 misaligned
            # flushes averaged 62% padding per batch, unweighted).  The 4/8 floor buckets
            # catch deadline flushes of a few stragglers that would
            # otherwise pad a b16 program half-empty — device FLOPs are
            # ~3% of wall here, but the padding metric should measure
            # batching quality, not the ladder floor.
            batch_buckets=[4, 8, 16, 32, 64, 128], pipeline_depth=3,
            max_latency_ms=15.0,
            warmup=True, input_dtype="uint8", scale=1.0 / 255.0,
            output="argmax")
        image = np.random.default_rng(0).integers(
            0, 256, size=(224, 224, 3)).astype(np.uint8)

    model = JaxModel("resnet", model_dir)
    t0 = time.perf_counter()
    model.load()
    compile_s = time.perf_counter() - t0
    server = await _serve([model])
    body = np_json_body("instances", image[None])
    path = "/v1/models/resnet:predict"
    try:
        peak = await closed_loop(
            server.http_port, path, body,
            num_requests=128 if smoke else 1536,
            concurrency=16 if smoke else 256)
        rate = 20 if smoke else 100
        fixed = await open_loop(server.http_port, path, lambda i: body,
                                rate, 2.0 if smoke else 8.0)
        # The V2 binary wire (raw tensor bytes + JSON header): on a
        # one-core host the JSON number parse dominates V1 intake, so
        # this is the native tensor path's peak.
        from kfserving_tpu.protocol import v2 as v2proto

        bin_body, hlen = v2proto.make_binary_request({"input_0": image[None]})
        binary = await closed_loop(
            server.http_port, "/v2/models/resnet/infer", bin_body,
            num_requests=128 if smoke else 2048,
            concurrency=16 if smoke else 256,
            headers={"Inference-Header-Content-Length": str(hlen)})
        # Raw-socket pipelined mode: the aiohttp client above shares the
        # single host core with the server (the reference ran vegeta on
        # a separate machine); this shows true server capacity.
        from benchmarks.harness import pipelined_closed_loop

        piped = await pipelined_closed_loop(
            server.http_port, "/v2/models/resnet/infer", bin_body,
            num_requests=256 if smoke else 4096,
            connections=4 if smoke else 8,
            headers={"Inference-Header-Content-Length": str(hlen)})
        grpc_res = await _grpc_closed_loop(
            server, "resnet", image[None],
            num_requests=128 if smoke else 1024,
            concurrency=16 if smoke else 64)
        stats = model.engine_stats()
        return {"closed_loop": peak, "fixed_rate": fixed,
                "binary_wire_closed_loop": binary,
                "binary_wire_pipelined": piped,
                "grpc_closed_loop": grpc_res,
                "tensorjson_parse": _tensorjson_parse_ab(body),
                "compile_s": round(compile_s, 1),
                "engine": {k: (round(v, 4) if isinstance(v, float) else v)
                           for k, v in stats.items()}}
    finally:
        await server.stop_async()


def _tensorjson_parse_ab(body: bytes) -> Dict[str, Any]:
    """Parse-throughput A/B for the V1 JSON intake (VERDICT r4 item 5):
    the classic i4 path vs the uint8 hint path on the same image body.
    Deterministic host-CPU measurement."""
    from kfserving_tpu.protocol import native

    if not native.available():
        return {"skipped": "native codec unavailable"}
    n = 30
    out: Dict[str, Any] = {"body_mb": round(len(body) / 1e6, 2)}
    for label, hint in (("i4_mb_s", None), ("u1_mb_s", "u1")):
        native.parse_v1(body, hint=hint)  # warm
        t0 = time.perf_counter()
        for _ in range(n):
            native.parse_v1(body, hint=hint)
        dt = time.perf_counter() - t0
        out[label] = round(n * len(body) / dt / 1e6, 1)
    if out.get("i4_mb_s"):
        out["u1_over_i4"] = round(out["u1_mb_s"] / out["i4_mb_s"], 3)
    return out


async def _grpc_closed_loop(server, model_name: str, arr,
                            num_requests: int, concurrency: int
                            ) -> Dict[str, Any]:
    """V2 gRPC ModelInfer with raw_input_contents (the native tensor
    wire over HTTP/2) — the protocol row's perf leg."""
    try:
        import grpc
    except ImportError:
        return {"skipped": "grpcio not installed"}
    from benchmarks.harness import summarize
    from kfserving_tpu.protocol.grpc import pb2
    from kfserving_tpu.protocol.v2 import datatype_of

    if getattr(server, "grpc_server", None) is None:
        from kfserving_tpu.server.grpc_server import GRPCServer

        server.grpc_server = GRPCServer(server.dataplane, port=0)
        await server.grpc_server.start()
    port = server.grpc_server.port
    req = pb2.ModelInferRequest(model_name=model_name)
    tensor = req.inputs.add()
    tensor.name = "input_0"
    tensor.datatype = datatype_of(arr)
    tensor.shape.extend(arr.shape)
    req.raw_input_contents.append(np.ascontiguousarray(arr).tobytes())
    payload = req.SerializeToString()

    channel = grpc.aio.insecure_channel(f"127.0.0.1:{port}")
    call = channel.unary_unary(
        "/inference.GRPCInferenceService/ModelInfer",
        request_serializer=lambda b: b,
        response_deserializer=pb2.ModelInferResponse.FromString)
    latencies: List[float] = []
    errors = 0
    first_error = None
    sem = asyncio.Semaphore(concurrency)

    async def one():
        nonlocal errors, first_error
        async with sem:
            t0 = time.perf_counter()
            try:
                await call(payload)
            except Exception as exc:
                errors += 1
                if first_error is None:
                    first_error = f"{type(exc).__name__}: {exc}"
                return
            latencies.append((time.perf_counter() - t0) * 1e3)

    t0 = time.perf_counter()
    await asyncio.gather(*[one() for _ in range(num_requests)])
    wall = time.perf_counter() - t0
    await channel.close()
    return summarize(latencies, wall, errors, first_error)


async def bench_overload(smoke: bool) -> Dict[str, Any]:
    """Overload with admission control on vs off (VERDICT r2 weak #6).

    The reference's benchmark concluded queue-proxy + containerConcurrency
    wins at overload: bounded queues keep accepted-request latency sane
    while the raw path melts down (reference test/benchmark/
    README.md:124-135: raw svc at 1000 QPS hit p99 20.3s / 93.7%
    success).  Same analysis for the TPU stack, with the reference's
    load model: OPEN-loop fixed-rate arrivals above capacity (vegeta's
    model — a closed loop self-limits to service rate and measures
    nothing but the epoch's capacity; an interleaved closed-loop A/B
    measured goodput_ratio 0.96 / p99 ratio 0.99, i.e. the gate is a
    no-op there, and the sequential version's '1.37x' was run-to-run
    drift).  Gateless: the queue absorbs the excess and latency grows
    with test duration.  Admission: the excess sheds as fast 503s and
    ACCEPTED requests keep bounded latency."""
    from kfserving_tpu.predictors.jax_model import JaxModel

    if smoke:
        arch_args = ("mlp", {"input_dim": 64, "features": [128],
                             "num_classes": 10})
        model_cfg = dict(max_batch_size=16, max_latency_ms=5.0,
                         warmup=True, output="argmax")
        image = np.random.default_rng(0).normal(size=(64,)) \
            .astype(np.float32)
        rate, duration, cc = 400, 2.0, 8
    else:
        arch_args = ("resnet50", None)
        model_cfg = dict(
            max_batch_size=128, batch_buckets=[16, 32, 64, 128],
            pipeline_depth=3, max_latency_ms=15.0, warmup=True,
            input_dtype="uint8", scale=1.0 / 255.0, output="argmax")
        image = np.random.default_rng(0).integers(
            0, 256, size=(224, 224, 3)).astype(np.uint8)
        # ~1.5x the V1-JSON capacity (~145 req/s measured across
        # epochs); the gate admits cc executing + cc queued and sheds
        # the rest.
        rate, duration, cc = 220, 8.0, 64
    body = np_json_body("instances", image[None])
    out: Dict[str, Any] = {"rate_qps": rate,
                           "round_duration_s": duration,
                           "container_concurrency": cc}
    # Open loop: shed 503s cost the generator nothing (no closed-loop
    # retry storm on the shared core).  Both modes serve at once and
    # ALTERNATE rounds — a sequential A/B once inverted purely from
    # drift between phases.
    rounds = 2 if smoke else 4
    out["rounds"] = rounds
    servers = {}
    results: Dict[str, list] = {"gateless": [], "admission": []}
    try:
        for mode, server_kwargs in (
                ("gateless", {}),
                ("admission", {"container_concurrency": cc,
                               "max_queue_depth": cc})):
            model_dir = _write_jax_model_dir(arch_args[0], arch_args[1],
                                             **model_cfg)
            model = JaxModel("resnet", model_dir)
            model.load()
            servers[mode] = await _serve([model], **server_kwargs)
        path = "/v1/models/resnet:predict"
        for server in servers.values():
            await closed_loop(server.http_port, path, body,
                              num_requests=4, concurrency=2)
        order = list(servers.items())
        for rnd in range(rounds):
            # Reverse phase order on alternate rounds: monotonic
            # drift within a round-pair would otherwise bias whichever
            # mode always ran second.
            for mode, server in (order if rnd % 2 == 0
                                 else list(reversed(order))):
                results[mode].append(await open_loop(
                    server.http_port, path, lambda i: body,
                    rate, duration))
    finally:
        for server in servers.values():
            await server.stop_async()

    from benchmarks.harness import aggregate_rounds

    out["gateless"] = aggregate_rounds(results["gateless"])
    out["admission"] = aggregate_rounds(results["admission"])
    gate, raw = out["admission"], out["gateless"]
    if gate.get("p99_ms_median") and raw.get("p99_ms_median"):
        out["accepted_p99_improvement"] = round(
            raw["p99_ms_median"] / gate["p99_ms_median"], 3)
        out["goodput_ratio"] = round(
            gate["req_per_s_median"] / raw["req_per_s_median"], 3)
    # Predictive SLO control loop (ISSUE 12): traffic-step A/B through
    # the full control plane, committed to BENCH_overload.json.
    out["traffic_step"] = await _overload_traffic_step(smoke)
    record = {
        "scenario": "overload_traffic_step",
        "smoke": smoke,
        "admission_ab": {k: out.get(k) for k in
                         ("gateless", "admission",
                          "accepted_p99_improvement", "goodput_ratio")},
        "traffic_step": out["traffic_step"],
    }
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCH_overload.json"), "w") as f:
        json.dump(record, f, indent=2)
    return out


class _SleepModel:
    """Deterministic-service-time model for the control-plane step
    bench: capacity per replica is exactly containerConcurrency /
    service_s, so the A/B measures the CONTROL LOOP, not model
    noise."""

    def __init__(self, name: str, service_s: float):
        from kfserving_tpu.model.model import Model

        class _M(Model):
            def load(self):
                self.ready = True
                return True

            async def predict(self, request):
                await asyncio.sleep(service_s)
                return {"predictions": [1]}

        self.model = _M(name)
        self.model.load()


async def _overload_traffic_step(smoke: bool) -> Dict[str, Any]:
    """Interleaved A/B at a fixed traffic step: REACTIVE (pre-ISSUE-12
    autoscaler, no brownout) vs PREDICTIVE (feed-forward sizing +
    standby pre-arm + brownout admission).  The step offers ~3x the
    component's max capacity; the latency SLO can only hold if the
    excess is shed selectively.  Per round, the step is split into a
    `settle` slice (detection + actuation transient, reported) and a
    `held` slice (steady state, gated on the SLO) — convergence time
    is evidence, not something to hide inside a tail percentile."""
    from kfserving_tpu.control.autoscaler import Autoscaler
    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.orchestrator import (
        InProcessOrchestrator,
    )
    from kfserving_tpu.control.predictive import PredictiveScaler
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import (
        InferenceService,
        PredictorSpec,
    )
    from kfserving_tpu.observability.monitoring.slo import SLOObjective
    from kfserving_tpu.reliability import (
        BrownoutController,
        PRIORITY_HEADER,
    )

    service_s = 0.25
    cc = 8
    max_replicas = 2
    objective_ms = 500.0  # on a histogram bucket bound (exact burn)
    base_rate, step_rate = 8, 96
    warm_s, settle_s, held_s = 1.5, 1.2, 3.5
    rounds = 2 if smoke else 4
    tick_s = 0.1
    out: Dict[str, Any] = {
        "service_ms": service_s * 1000.0, "container_concurrency": cc,
        "max_replicas": max_replicas,
        "capacity_req_per_s": max_replicas * cc / service_s,
        "latency_objective_ms": objective_ms,
        "base_rate_qps": base_rate, "step_rate_qps": step_rate,
        "rounds": rounds,
        "priority_mix": {"batch": 0.5, "normal": 0.4,
                         "critical": 0.1},
    }

    # i -> priority tier: 50% batch / 40% normal / 10% critical,
    # interleaved so every slice of the step carries the full mix.
    def tier_of(i: int) -> str:
        slot = i % 10
        if slot < 5:
            return "batch"
        if slot < 9:
            return "normal"
        return "critical"

    def headers_fn(i: int) -> Dict[str, str]:
        return {PRIORITY_HEADER: tier_of(i)}

    stacks: Dict[str, Dict[str, Any]] = {}
    results: Dict[str, Dict[str, list]] = {
        "reactive": {"settle": [], "held": []},
        "predictive": {"settle": [], "held": []},
    }
    try:
        for mode in ("reactive", "predictive"):
            orch = InProcessOrchestrator(
                model_factory=lambda cid, spec: _SleepModel(
                    "step", service_s).model)
            controller = Controller(orch)
            brownout = BrownoutController() \
                if mode == "predictive" else None
            router = IngressRouter(controller, brownout=brownout)
            predictive = None
            if mode == "predictive":
                predictive = PredictiveScaler(
                    controller, router,
                    objectives={"step": SLOObjective(
                        "step", latency_ms=objective_ms)},
                    windows_s=(0.6, 3.0), burn_alert=2.0,
                    burn_exit=1.0, exit_ticks=3, brownout=brownout)
            scaler = Autoscaler(controller, router,
                                tick_seconds=tick_s,
                                predictive=predictive)
            isvc = InferenceService(
                name="step",
                predictor=PredictorSpec(
                    framework="sklearn",
                    storage_uri="file:///dev/null",
                    min_replicas=1, max_replicas=max_replicas,
                    container_concurrency=cc))
            await controller.apply(isvc)
            await router.start_async()
            await scaler.start()
            stacks[mode] = dict(orch=orch, controller=controller,
                                router=router, scaler=scaler,
                                predictive=predictive,
                                brownout=brownout, isvc=isvc)

        body = json.dumps({"instances": [[1.0]]}).encode()
        path = "/v1/models/step:predict"
        order = list(stacks.items())
        for rnd in range(rounds):
            for mode, stack in (order if rnd % 2 == 0
                                else list(reversed(order))):
                # Round reset: back to 1 replica, fresh windows/levels.
                await stack["controller"].reconciler.scale(
                    stack["isvc"], "predictor", 1)
                stack["scaler"]._windows.clear()
                stack["scaler"]._idle.clear()
                if stack["brownout"] is not None:
                    stack["brownout"].set_level("step", 0)
                port = stack["router"].http_port
                await open_loop(port, path, lambda i: body,
                                base_rate, warm_s,
                                headers_fn=headers_fn)
                results[mode]["settle"].append(await open_loop(
                    port, path, lambda i: body, step_rate, settle_s,
                    headers_fn=headers_fn, label_fn=tier_of))
                results[mode]["held"].append(await open_loop(
                    port, path, lambda i: body, step_rate, held_s,
                    headers_fn=headers_fn, label_fn=tier_of))
                # Cool-down past the LONG burn window so the next arm
                # starts from a calm series — and so the predictive
                # arm's automatic brownout EXIT (burn recovered, gap
                # cleared) lands in the decision trail.
                await asyncio.sleep(3.2)
    finally:
        for stack in stacks.values():
            await stack["scaler"].stop()
            await stack["router"].stop_async()
            await stack["orch"].shutdown()

    from benchmarks.harness import aggregate_rounds

    for mode in results:
        out[mode] = {
            "settle": aggregate_rounds(results[mode]["settle"]),
            "held": aggregate_rounds(results[mode]["held"]),
            "held_rounds": results[mode]["held"],
        }
    reactive_p99 = out["reactive"]["held"].get("p99_ms_median")
    predictive_p99 = out["predictive"]["held"].get("p99_ms_median")
    out["slo"] = {
        "latency_objective_ms": objective_ms,
        "reactive_breached": (reactive_p99 is not None
                              and reactive_p99 > objective_ms),
        "predictive_held": (predictive_p99 is not None
                            and predictive_p99 <= objective_ms),
        "predictive_errors": out["predictive"]["held"]["errors"]
        + out["predictive"]["settle"]["errors"],
        "predictive_shed_retriable":
            out["predictive"]["held"]["shed_retriable"]
            + out["predictive"]["settle"]["shed_retriable"],
    }
    # The decision trail: every pre-arm/scale/brownout decision the
    # predictive loop pinned into the supervisor flight recorder
    # (federated live at /debug/flightrecorder, replica="supervisor").
    stack = stacks.get("predictive", {})
    recorder = getattr(stack.get("orch"), "flight_recorder", None)
    if recorder is not None:
        dump = recorder.dump(limit=64, pinned_only=True)
        out["decision_trail"] = dump.get("pinned", [])
    orch = stack.get("orch")
    if orch is not None:
        out["standby_adoptions"] = getattr(orch, "standby_adoptions",
                                           0)
    return out


def cpu_torch_resnet_baseline(smoke: bool) -> Dict[str, Any]:
    """Reference execution model: torch ResNet-50, per-request batch=1 on
    CPU (reference python/pytorchserver predicts per request, no
    batching).  transformers' default ResNetConfig IS ResNet-50."""
    if smoke:
        return {"req_per_s": None}
    try:
        import torch
        from transformers import ResNetConfig, ResNetForImageClassification
    except Exception:
        return {"req_per_s": None}
    model = ResNetForImageClassification(ResNetConfig())
    model.eval()
    x = torch.randn(1, 3, 224, 224)
    n = int(os.environ.get("BENCH_CPU_REQUESTS", "20"))
    lat = []
    with torch.no_grad():
        model(x)  # warm
        for _ in range(n):
            t0 = time.perf_counter()
            model(x)
            lat.append((time.perf_counter() - t0) * 1000.0)
    lat.sort()
    from benchmarks.harness import percentile

    return {"req_per_s": round(n / (sum(lat) / 1000.0), 2),
            "p50_ms": round(percentile(lat, 0.5), 1),
            "p99_ms": round(percentile(lat, 0.99), 1)}


# -- config 3: BERT seq-bucketed ---------------------------------------------
async def bench_bert(smoke: bool) -> Dict[str, Any]:
    from kfserving_tpu.predictors.jax_model import JaxModel

    arch = "bert_tiny" if smoke else "bert"
    # Full sequence range: BERT-base's max_position is 512, and the
    # 256/512 buckets are where the padding-aware flash path pays
    # (_FLASH_MIN_SEQ=512).  VERDICT r2 weak #7: buckets stopped at 128.
    seq_buckets = [32, 64, 128] if smoke else [32, 64, 128, 256, 512]
    # Explicit batch buckets bound warmup to (2 batch x 5 seq) compiles;
    # without the full grid, serve-time compiles turned first requests
    # into timeouts.
    # topk output: fill-mask serving returns top-5 ids/scores per
    # position, not the raw [seq, vocab] logits (a ~40MB JSON body per
    # 128-token instance for bert-base's 30k vocab).
    model_dir = _write_jax_model_dir(
        arch, {}, max_batch_size=8 if smoke else 16,
        # b1 floor: mixed-length traffic splits across 5 seq buckets,
        # so per-bucket arrival is sparse and deadline flushes are often
        # singletons — padding them to 4 slots showed 35-47% waste on
        # the b4 programs.  3 batch x 5 seq = 15 warmup compiles.
        batch_buckets=[8] if smoke else [1, 4, 16],
        # pipeline_depth stays at the default 2: measured depth 3 at
        # this concurrency left throughput flat (129.7 vs 128-145
        # req/s) and worsened p99 (426 vs 275 ms) — BERT here is
        # client-concurrency/latency-capped, not RTT-serialization-
        # bound like the 151KB-per-request ResNet wire.
        max_latency_ms=5.0, warmup=True, seq_buckets=seq_buckets,
        output="topk", topk=5)
    model = JaxModel("bert", model_dir)
    model.load()
    server = await _serve([model])
    rng = np.random.default_rng(0)
    vocab = 1000

    def body_for_len(length: int) -> bytes:
        ids = rng.integers(1, vocab, size=(1, length)).astype(np.int32)
        return np_json_body("instances", ids)

    # Pre-warm each seq bucket's executables (readiness would normally
    # gate on this; we keep the timed section post-compile).
    path = "/v1/models/bert:predict"
    # One traffic length per bucket so the mixed sweep exercises every
    # compiled program.
    lengths = [24, 48, 100] if smoke else [24, 48, 100, 200, 450]
    bodies = {L: body_for_len(L) for L in lengths}
    try:
        for L in bodies:
            await closed_loop(server.http_port, path, bodies[L],
                              num_requests=2, concurrency=1)
        peak = await closed_loop(
            server.http_port, path, bodies[48],
            num_requests=64 if smoke else 384,
            concurrency=8 if smoke else 32)
        # Mixed-length fixed-rate over ALL buckets, with per-length
        # latency classes (VERDICT r2 weak #7 deliverable).
        mixed = await open_loop(
            server.http_port, path,
            lambda i: bodies[lengths[i % len(lengths)]],
            10 if smoke else 25, 2.0 if smoke else 8.0,
            label_fn=lambda i: f"len{lengths[i % len(lengths)]}")
        # The 512 bucket on its own: p99 where flash+kv_lengths runs.
        long_tail = None
        if not smoke:
            long_tail = await closed_loop(
                server.http_port, path, bodies[450],
                num_requests=128, concurrency=16)
        # Native wire both ways: token ids in as raw int32, topk
        # values/indices back as raw bytes (binary_data_output) — the
        # heavy part of a fill-mask response is the output tensors.
        from kfserving_tpu.protocol import v2 as v2proto

        ids48 = rng.integers(1, vocab, size=(1, 48)).astype(np.int32)
        bin_body, hlen = v2proto.make_binary_request(
            {"input_0": ids48}, binary_output=True)
        binary = await closed_loop(
            server.http_port, "/v2/models/bert/infer", bin_body,
            num_requests=64 if smoke else 384,
            concurrency=8 if smoke else 32,
            headers={"Inference-Header-Content-Length": str(hlen)})
        # D2H profile: topk keeps the response at O(seq*k), not
        # O(seq*vocab) — response bytes per traffic length shows it.
        import aiohttp

        resp_bytes = {}
        async with aiohttp.ClientSession() as session:
            for L in lengths:
                async with session.post(
                        f"http://127.0.0.1:{server.http_port}{path}",
                        data=bodies[L]) as resp:
                    resp_bytes[f"len{L}"] = len(await resp.read())
        stats = model.engine_stats()
        return {"closed_loop": peak, "mixed_lengths_fixed_rate": mixed,
                "long_bucket_closed_loop": long_tail,
                "binary_wire_closed_loop": binary,
                "seq_buckets": seq_buckets,
                "response_bytes_by_length": resp_bytes,
                "engine": {k: (round(v, 4) if isinstance(v, float) else v)
                           for k, v in stats.items()}}
    finally:
        await server.stop_async()


async def bench_bert_flash_ab(smoke: bool) -> Dict[str, Any]:
    """Flash-vs-XLA A/B at the 512 bucket (VERDICT r2 weak #7: show the
    padding-aware flash path visibly helping at BERT's real sequence
    range).

    Where the kernel pays (measured, fori-chain device timing, D=64):
    NOT at BERT-base's 512 bucket — XLA is 3.1x faster there and the
    dispatcher now routes it to XLA (_FLASH_MIN_SEQ_HALF_LANE) — but at
    long context, scaled by the padding skipped: at L=4096, xla/flash =
    3.7x at 25% fill, 2.0x at 50%, 1.4x at 90%.  So the A/B serves a
    long-context model at a 4096 bucket with 25%-fill traffic.

    Drift-robust design: both variants (Pallas kernel eligible
    vs KFS_DISABLE_FLASH-forced XLA) load into ONE process, then run in
    ALTERNATING closed-loop rounds so host drift hits both
    equally; engines run with blocking stats so avg_device_ms carries
    the device delta — the primary signal (a sequential A/B once
    inverted from drift mid-config).  Off-TPU both variants take the XLA path,
    so the ratio is ~1."""
    import os as _os

    from kfserving_tpu.predictors.jax_model import JaxModel

    if smoke:
        arch_kwargs = {"num_layers": 2, "hidden_size": 64,
                       "num_heads": 2, "intermediate_size": 128,
                       "vocab_size": 512, "max_position": 256,
                       "seq_len": 256}
        seq, traffic_len, vocab = 256, 100, 512
        rounds, per_round = 2, 16
    else:
        arch_kwargs = {"num_layers": 8, "hidden_size": 512,
                       "num_heads": 8, "intermediate_size": 2048,
                       "vocab_size": 8192, "max_position": 4096,
                       "seq_len": 4096}
        seq, traffic_len, vocab = 4096, 1024, 8192
        rounds, per_round = 4, 24
    out: Dict[str, Any] = {"seq_bucket": seq, "traffic_len": traffic_len,
                           "rounds": rounds}
    rng = np.random.default_rng(1)
    ids = rng.integers(1, vocab, size=(1, traffic_len)).astype(np.int32)
    body = np_json_body("instances", ids)
    _os.environ["KFS_ENGINE_BLOCKING_STATS"] = "1"
    ambient_disable = _os.environ.pop("KFS_DISABLE_FLASH", None)
    models = {}
    try:
        for mode, disable in (("flash", None), ("xla", "1")):
            # Explicitly clear for the flash variant: an ambient
            # KFS_DISABLE_FLASH would otherwise bake the XLA path into
            # BOTH models and report a silent ~1.0 ratio.
            if disable is None:
                _os.environ.pop("KFS_DISABLE_FLASH", None)
            else:
                _os.environ["KFS_DISABLE_FLASH"] = disable
            try:
                model_dir = _write_jax_model_dir(
                    "bert", arch_kwargs, max_batch_size=4,
                    batch_buckets=[4], max_latency_ms=10.0, warmup=True,
                    seq_buckets=[seq], output="topk", topk=5)
                model = JaxModel(f"bert-{mode}", model_dir)
                model.load()
                models[mode] = model
            finally:
                _os.environ.pop("KFS_DISABLE_FLASH", None)
    finally:
        _os.environ.pop("KFS_ENGINE_BLOCKING_STATS", None)
        if ambient_disable is not None:
            _os.environ["KFS_DISABLE_FLASH"] = ambient_disable
    server = await _serve(list(models.values()))
    lat: Dict[str, list] = {"flash": [], "xla": []}
    try:
        for mode in models:
            await closed_loop(
                server.http_port, f"/v1/models/bert-{mode}:predict",
                body, num_requests=2, concurrency=1)
        for rnd in range(rounds):
            # Alternate phase order so monotonic drift within a
            # round-pair can't bias one variant (same pattern as
            # bench_overload).
            for mode in (("flash", "xla") if rnd % 2 == 0
                         else ("xla", "flash")):
                res = await closed_loop(
                    server.http_port,
                    f"/v1/models/bert-{mode}:predict", body,
                    num_requests=per_round, concurrency=8)
                lat[mode].append(res)
        from benchmarks.harness import aggregate_rounds

        for mode in ("flash", "xla"):
            stats = models[mode].engine_stats()
            out[mode] = aggregate_rounds(lat[mode])
            # device+fetch SUM, kept as recorded; whether
            # device_ms alone is the device's time on this chip is
            # ROADMAP A2's to establish.
            out[mode]["avg_sync_ms"] = round(
                stats.get("avg_device_ms", 0.0)
                + stats.get("avg_fetch_ms", 0.0), 3)
    finally:
        await server.stop_async()
    if out["flash"]["avg_sync_ms"] and out["xla"]["avg_sync_ms"]:
        out["xla_over_flash_sync"] = round(
            out["xla"]["avg_sync_ms"] / out["flash"]["avg_sync_ms"], 3)
    if out["flash"]["p50_ms_median"] and out["xla"]["p50_ms_median"]:
        out["xla_over_flash_p50"] = round(
            out["xla"]["p50_ms_median"] / out["flash"]["p50_ms_median"],
            3)
    return out


# -- config 4: 8-model hot-swap ----------------------------------------------
def _write_mms_catalog(n_models: int) -> str:
    root = tempfile.mkdtemp(prefix="bench-mms-")
    for i in range(n_models):
        d = os.path.join(root, f"m{i}")
        os.makedirs(d)
        json.dump({"architecture": "mlp",
                   "arch_kwargs": {"input_dim": 32, "features": [64],
                                   "num_classes": 8},
                   "max_latency_ms": 2.0, "warmup": True},
                  open(os.path.join(d, "config.json"), "w"))
    return root


@contextlib.contextmanager
def _bench_param_cache():
    """Hermetic mmap param cache for the multimodel configs: the
    warm-host measurements depend on cache state, so the bench owns
    its own directory instead of inheriting ~/.cache entries from
    earlier runs."""
    prior = os.environ.get("KFS_PARAM_CACHE")
    os.environ["KFS_PARAM_CACHE"] = tempfile.mkdtemp(
        prefix="bench-pcache-")
    try:
        yield
    finally:
        if prior is None:
            os.environ.pop("KFS_PARAM_CACHE", None)
        else:
            os.environ["KFS_PARAM_CACHE"] = prior


async def bench_multimodel(smoke: bool) -> Dict[str, Any]:
    """Repository hot-swap economics, with the swap cost SPLIT into
    its real components (the pre-ISSUE-15 `swap_cycle_ms` conflated
    param materialization with everything else, burying the residency
    win): registration (the declarative load/unload REST cycle),
    cold-materialize first predict (param init + store + compile), and
    warm-host first predict (mmap param hit)."""
    import aiohttp

    from kfserving_tpu.predictors.jaxserver import JaxModelRepository

    n_models = 8
    loop = asyncio.get_running_loop()
    # kfslint: disable=async-blocking — bench setup: one mkdtemp
    # before any server exists.
    with _bench_param_cache():
        root = await loop.run_in_executor(
            None, _write_mms_catalog, n_models)
        repo = JaxModelRepository(models_dir=root)
        server = await _serve([], registered_models=repo)
        x = np.random.default_rng(0).normal(
            size=(1, 32)).astype(np.float32)
        body = np_json_body("instances", x)
        base = f"http://127.0.0.1:{server.http_port}"
        try:
            async with aiohttp.ClientSession() as session:
                load_t0 = time.perf_counter()
                for i in range(n_models):
                    async with session.post(
                            f"{base}/v2/repository/models/m{i}/load"
                            ) as resp:
                        assert resp.status == 200, await resp.text()
                load_all_s = time.perf_counter() - load_t0

                # First predict per model: the COLD-materialize swap
                # half (random init + param-cache store + compile).
                cold_ms = []
                for i in range(n_models):
                    t0 = time.perf_counter()
                    async with session.post(
                            f"{base}/v1/models/m{i}:predict",
                            data=body) as resp:
                        assert resp.status == 200, await resp.text()
                    cold_ms.append(
                        (time.perf_counter() - t0) * 1000.0)

                # Hot-swap cycles on m0, now split: the REST
                # unload+load pair (registration) and the WARM-host
                # first predict (mmap param hit + engine rebuild).
                swaps = 2 if smoke else 6
                reg_ms, warm_ms = [], []
                for _ in range(swaps):
                    t0 = time.perf_counter()
                    for verb in ("unload", "load"):
                        async with session.post(
                                f"{base}/v2/repository/models/m0/"
                                f"{verb}") as resp:
                            assert resp.status == 200
                    t1 = time.perf_counter()
                    async with session.post(
                            f"{base}/v1/models/m0:predict",
                            data=body) as resp:
                        assert resp.status == 200
                    t2 = time.perf_counter()
                    reg_ms.append((t1 - t0) * 1000.0)
                    warm_ms.append((t2 - t1) * 1000.0)

            # round-robin inference across all 8 registered models
            results = await asyncio.gather(*[
                closed_loop(server.http_port,
                            f"/v1/models/m{i}:predict", body,
                            num_requests=32 if smoke else 128,
                            concurrency=4)
                for i in range(n_models)])
            total_reqs = sum(r["requests"] for r in results)
            req_per_s = sum(r["req_per_s"] for r in results)
            p99 = max(r["p99_ms"] for r in results)

            def med(v):
                return round(sorted(v)[len(v) // 2], 1)

            return {"models": n_models,
                    "load_all_s": round(load_all_s, 2),
                    # Total warm swap (registration + first predict):
                    # the like-for-like successor of the old
                    # swap_cycle_ms, minus the materialization it used
                    # to conflate in.
                    "swap_cycle_ms": med(
                        [r + w for r, w in zip(reg_ms, warm_ms)]),
                    "swap_registration_ms": med(reg_ms),
                    "swap_warm_host_ms": med(warm_ms),
                    "swap_cold_materialize_ms": med(cold_ms),
                    "round_robin_req_per_s": round(req_per_s, 1),
                    "round_robin_worst_p99_ms": p99,
                    "total_requests": total_reqs}
        finally:
            await server.stop_async()


# -- multimodel density: residency + affinity A/B (ISSUE 15) -----------------
async def bench_multimodel_density(smoke: bool) -> Dict[str, Any]:
    """The demand-paged residency evidence (ROADMAP item 4 done bar):

    Part A — N>=20 models on ONE replica under eviction pressure: the
    HBM budget fits ~40% of the catalog, every predict to an evicted
    model warm-faults it in off the mmap params, and the committed
    record proves fault-in swap p99 < 100 ms warm-host, evictions
    actually firing, and the admission-aware veto skipping a busy
    victim (deterministically driven).

    Part B — fixed-fleet router A/B: the same catalog behind R
    replicas, blind round-robin vs model-affinity ring at identical
    fleet size, judged on aggregate req/s and per-replica HBM eviction
    rate with the federated `hbm.resident` ledgers embedded as
    evidence.

    Committed to BENCH_multimodel.json.
    """
    import aiohttp

    from kfserving_tpu.engine.hbm import HBMManager
    from kfserving_tpu.predictors.jaxserver import JaxModelRepository

    n_models = 20 if smoke else 24
    resident_frac = 0.4
    reqs_per_model = 6 if smoke else 24
    out: Dict[str, Any] = {"scenario": "multimodel_density",
                           "smoke": smoke, "models": n_models}
    loop = asyncio.get_running_loop()
    # kfslint: disable=async-blocking — bench setup: one mkdtemp
    # before any server exists.
    with _bench_param_cache():
        root = await loop.run_in_executor(
            None, _write_mms_catalog, n_models)
        x = np.random.default_rng(0).normal(
            size=(1, 32)).astype(np.float32)
        body = np_json_body("instances", x)

        # ---- part A: one replica, eviction pressure ----------------
        hbm = HBMManager(budget_bytes=1 << 40)  # sized after probe
        repo = JaxModelRepository(models_dir=root, hbm=hbm)
        server = await _serve([], registered_models=repo)
        base = f"http://127.0.0.1:{server.http_port}"
        try:
            async with aiohttp.ClientSession() as session:
                t0 = time.perf_counter()
                for i in range(n_models):
                    async with session.post(
                            f"{base}/v2/repository/models/m{i}/load"
                            ) as resp:
                        assert resp.status == 200, await resp.text()
                register_all_s = time.perf_counter() - t0
                # Probe one cold fault to size the budget off the
                # model's REAL HBM bytes, then clamp the budget so
                # only ~resident_frac of the catalog fits.
                async with session.post(
                        f"{base}/v1/models/m0:predict",
                        data=body) as resp:
                    assert resp.status == 200
                per_model = max(1, hbm.used_bytes)
                hbm.budget_bytes = int(
                    per_model * n_models * resident_frac)
                # Cold-materialize the whole catalog (populates the
                # mmap param cache; evictions begin once the budget
                # saturates).
                for i in range(n_models):
                    async with session.post(
                            f"{base}/v1/models/m{i}:predict",
                            data=body) as resp:
                        assert resp.status == 200, await resp.text()
                cold_evictions = sum(hbm.evictions.values())

                # Steady state: W workers each round-robin the FULL
                # catalog (shuffled per worker) — every pass touches
                # models outside the resident set, so the measured
                # throughput INCLUDES continuous warm fault-ins and
                # evictions.  Bounded concurrency: the bench measures
                # the swap, not host-side event-loop saturation from
                # an unbounded client storm.
                async def rr_worker(w: int):
                    rng = np.random.default_rng(w)
                    order = list(range(n_models))
                    done = 0
                    for _ in range(reqs_per_model):
                        rng.shuffle(order)
                        for i in order:
                            async with session.post(
                                    f"{base}/v1/models/m{i}:predict",
                                    data=body) as resp:
                                assert resp.status == 200, \
                                    await resp.text()
                            done += 1
                    return done

                t0 = time.perf_counter()
                counts = await asyncio.gather(
                    *[rr_worker(w) for w in range(4)])
                wall_s = time.perf_counter() - t0
                total = sum(counts)

                # Admission-aware proof, deterministic: a LONG-RUNNING
                # request holds a model in flight while newer traffic
                # ages it back to the LRU head (touches move everyone
                # else up); the next fault-in's plan must SKIP the
                # busy head and evict the next candidate instead.
                victim = hbm.debug()["resident"][0]["model"]
                non_resident = next(
                    f"m{i}" for i in range(n_models)
                    if repo.residency.state_of(f"m{i}") == "host")
                skips_before = sum(hbm.eviction_skips.values())
                async with repo.residency.serving(victim):
                    for entry in hbm.debug()["resident"]:
                        if entry["model"] != victim:
                            hbm.touch(entry["model"])
                    async with session.post(
                            f"{base}/v1/models/{non_resident}:predict",
                            data=body) as resp:
                        assert resp.status == 200, await resp.text()
                skips = sum(hbm.eviction_skips.values()) - skips_before
                still_resident = victim in hbm.resident_models()

            res = repo.residency.debug()
            out["single_replica"] = {
                "register_all_s": round(register_all_s, 3),
                "budget_bytes": hbm.budget_bytes,
                "model_bytes": per_model,
                "resident_models": len(hbm.resident_models()),
                "steady_state": {
                    "requests": total,
                    "req_per_s": round(total / wall_s, 1),
                    "warm_fault_p50_ms":
                        res["fault_in_ms"]["warm_p50"],
                    "warm_fault_p99_ms":
                        res["fault_in_ms"]["warm_p99"],
                    "warm_faults": res["fault_in_ms"]["warm_count"],
                    "cold_fault_p50_ms":
                        res["fault_in_ms"]["cold_p50"],
                },
                "evictions_total": sum(hbm.evictions.values()),
                "evictions_during_cold_sweep": cold_evictions,
                "admission_aware": {
                    "busy_victim_skips": skips,
                    "busy_victim_stayed_resident": still_resident,
                },
            }
        finally:
            await server.stop_async()

        # ---- part B: fixed-fleet router A/B ------------------------
        out["router_ab"] = await _density_router_ab(
            root, n_models, resident_frac,
            reqs_per_model=max(8, reqs_per_model))

    out["warm_p99_under_100ms"] = bool(
        (out["single_replica"]["steady_state"]["warm_fault_p99_ms"]
         or 1e9) < 100.0)
    root_dir = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))

    def _commit():
        with open(os.path.join(root_dir, "BENCH_multimodel.json"),
                  "w") as f:
            json.dump(out, f, indent=2)

    await loop.run_in_executor(None, _commit)
    return out


async def _density_router_ab(root: str, n_models: int,
                             resident_frac: float,
                             reqs_per_model: int,
                             replicas: int = 2,
                             windows: int = 3) -> Dict[str, Any]:
    """Same catalog, same fleet size, two routing policies: blind
    round-robin (every replica eventually pages the whole catalog
    through its HBM) vs model-affinity ring (the fleet partitions the
    catalog).  Fresh fleet per arm so neither inherits the other's
    residency; the mmap param cache is shared (both arms' cold faults
    are materialization-free — the A/B isolates ROUTING, not cache
    luck).  Both fleets stay alive and the measured windows INTERLEAVE
    (RR, affinity, RR, affinity, ...) with the median taken per arm —
    the repo's bench discipline: a sequential pair would let machine
    noise drift between the arms and swamp the fault-cost signal."""
    import aiohttp

    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.orchestrator import (
        InProcessOrchestrator,
    )
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import (
        InferenceService,
        PredictorSpec,
        TrainedModel,
    )

    x = np.random.default_rng(1).normal(size=(1, 32)).astype(np.float32)
    body = np_json_body("instances", x)
    runtime: Dict[str, Dict[str, Any]] = {}
    try:
        for arm in ("round_robin", "affinity"):
            controller = Controller(InProcessOrchestrator())
            isvc = InferenceService(
                name="mms",
                predictor=PredictorSpec(
                    framework="jax", storage_uri=root,
                    multi_model=True,
                    min_replicas=replicas, max_replicas=replicas))
            await controller.apply(isvc)
            for i in range(n_models):
                await controller.apply_trained_model(TrainedModel(
                    name=f"m{i}", inference_service="mms",
                    storage_uri=os.path.join(root, f"m{i}")))
            router = IngressRouter(
                controller, http_port=0,
                affinity="model" if arm == "affinity" else "none",
                # The A/B isolates residency-vs-routing: a high spill
                # ceiling keeps the ring honest under the bench's
                # burst concurrency (spill-under-overload is proven in
                # tests).
                affinity_spill=64)
            await router.start_async()
            runtime[arm] = {"router": router, "controller": controller}
            cid = "default/mms/predictor"
            orch = controller.reconciler.orchestrator
            fleet = [r.handle for r in orch.replicas(cid)]
            runtime[arm]["fleet"] = fleet
            # Warm EVERY replica over the whole catalog DIRECTLY
            # (bypassing the router): the engine-build/compile cost is
            # identical in both arms and paid outside the measured
            # phase, so the A/B compares pure routing-driven HBM churn
            # — warm fault-ins and evictions — not compile luck.
            async with aiohttp.ClientSession() as session:
                per_model = None
                for s in fleet:
                    for i in range(n_models):
                        async with session.post(
                                f"http://127.0.0.1:{s.http_port}"
                                f"/v1/models/m{i}:predict",
                                data=body) as resp:
                            assert resp.status == 200, \
                                await resp.text()
                        if per_model is None:
                            # Clamp every replica's budget off the
                            # first REAL model footprint: ~70% of the
                            # catalog fits — capacity planning for a
                            # partitioned fleet: the expected arc
                            # share (1/replicas) PLUS slack for the
                            # binomial imbalance of hashing n_models
                            # keys onto the ring (a 20-model catalog
                            # on 2 replicas splits 13/7 in ~15% of
                            # draws).  A partitioned arc fits; the
                            # full catalog a blind spray pages through
                            # every replica does not.
                            per_model = max(
                                1, s.repository.hbm.used_bytes)
                            for srv in fleet:
                                srv.repository.hbm.budget_bytes = \
                                    int(per_model * n_models * 0.7)
            # Settle each arm to ITS OWN routing policy's steady-state
            # residency before measuring: the direct warmup above left
            # every replica with the same tail-of-catalog LRU state,
            # so without this the affinity arm would pay its one-time
            # re-partitioning fault-ins inside the measured window —
            # the A/B compares steady states, not transients.
            await asyncio.gather(*[
                closed_loop(router.http_port,
                            f"/v1/models/m{i}:predict", body,
                            num_requests=2, concurrency=1)
                for i in range(n_models)])
            for s in fleet:
                s.repository.hbm.evictions.clear()

        async def measure(arm: str) -> Dict[str, Any]:
            # One measured window: concurrent closed loops round-robin
            # the full catalog through the arm's router.
            router = runtime[arm]["router"]
            t0 = time.perf_counter()
            results = await asyncio.gather(*[
                closed_loop(router.http_port,
                            f"/v1/models/m{i}:predict", body,
                            num_requests=reqs_per_model,
                            concurrency=1)
                for i in range(n_models)])
            wall_s = time.perf_counter() - t0
            return {
                "requests": sum(r["requests"] for r in results),
                "errors": sum(r.get("errors", 0) for r in results),
                "req_per_s": round(sum(
                    r["requests"] for r in results) / wall_s, 1),
                "worst_p99_ms": max(r["p99_ms"] for r in results),
            }

        window_stats: Dict[str, list] = {a: [] for a in runtime}
        for _ in range(windows):
            for arm in ("round_robin", "affinity"):
                window_stats[arm].append(await measure(arm))

        arms: Dict[str, Any] = {}
        for arm, stats in window_stats.items():
            # Federated ledger evidence: per-replica resident sets +
            # eviction counts off GET /debug/cache (the PR 13 feed).
            router = runtime[arm]["router"]
            async with aiohttp.ClientSession() as session:
                async with session.get(
                        f"http://127.0.0.1:{router.http_port}"
                        f"/debug/cache") as resp:
                    fleet_view = await resp.json()
            ledgers = {}
            for host, snap in (fleet_view.get("replicas")
                               or {}).items():
                h = snap.get("hbm") or {}
                ledgers[host] = {
                    "resident": [r["model"]
                                 for r in h.get("resident", [])],
                    "evictions": sum(
                        (h.get("evictions") or {}).values()),
                }
            rates = sorted(w["req_per_s"] for w in stats)
            p99s = sorted(w["worst_p99_ms"] for w in stats)
            arms[arm] = {
                "requests": sum(w["requests"] for w in stats),
                "errors": sum(w["errors"] for w in stats),
                "windows": len(stats),
                "req_per_s_median": rates[len(rates) // 2],
                "req_per_s_windows": [w["req_per_s"] for w in stats],
                "worst_p99_ms_median": p99s[len(p99s) // 2],
                "evictions_measured_phase": sum(
                    led["evictions"] for led in ledgers.values()),
                "hbm_resident_ledgers": ledgers,
            }
    finally:
        for rt in runtime.values():
            await rt["router"].stop_async()
            await rt["controller"].reconciler.orchestrator.shutdown()
    rr, aff = arms["round_robin"], arms["affinity"]
    return {
        "replicas": replicas,
        "arms": arms,
        "affinity_over_rr_req_per_s": round(
            aff["req_per_s_median"] / rr["req_per_s_median"], 3)
        if rr["req_per_s_median"] else None,
        "eviction_rate_rr": rr["evictions_measured_phase"],
        "eviction_rate_affinity": aff["evictions_measured_phase"],
    }


# -- config 5: transformer -> predictor chain --------------------------------
async def bench_chain(smoke: bool) -> Dict[str, Any]:
    from examples.image_transformer import ImageTransformer
    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.orchestrator import (
        InProcessOrchestrator,
        default_model_factory,
    )
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import (
        InferenceService,
        PredictorSpec,
        TransformerSpec,
    )

    arch = "vit_tiny" if smoke else "vit_b16"
    size = 64 if smoke else 224
    model_dir = _write_jax_model_dir(
        arch, {"image_size": size},
        max_batch_size=8 if smoke else 16, max_latency_ms=5.0,
        warmup=True, output="argmax")

    def factory(component_id, spec):
        if isinstance(spec, TransformerSpec):
            name = component_id.split("/")[1]
            return ImageTransformer(name, predictor_host=None)
        return default_model_factory(component_id, spec)

    orch = InProcessOrchestrator(model_factory=factory)
    controller = Controller(orch)
    router = IngressRouter(controller)
    await router.start_async()
    try:
        isvc = InferenceService(
            name="vitchain",
            predictor=PredictorSpec(framework="jax",
                                    storage_uri=f"file://{model_dir}"),
            transformer=TransformerSpec())
        await controller.apply(isvc)
        # transformer proxies through the router's direct predictor lane
        for comp in orch.state["default/vitchain/transformer"].replicas:
            comp.handle.repository.get_model("vitchain").predictor_host = \
                f"127.0.0.1:{router.http_port}/direct/predictor"

        rng = np.random.default_rng(0)
        image = rng.integers(0, 256, size=(size, size, 3)).astype(np.uint8)
        body = np_json_body("instances", image[None])
        path = "/v1/models/vitchain:predict"
        peak = await closed_loop(router.http_port, path, body,
                                 num_requests=32 if smoke else 128,
                                 concurrency=4 if smoke else 16)
        fixed = await open_loop(router.http_port, path, lambda i: body,
                                5 if smoke else 20,
                                2.0 if smoke else 5.0)
        return {"closed_loop": peak, "fixed_rate": fixed,
                "chain": "transformer->predictor via ingress router"}
    finally:
        await router.stop_async()
        await orch.shutdown()


# -- config 6 (TPU-native addition): long-context serving --------------------
async def bench_generate(smoke: bool) -> Dict[str, Any]:
    """Generative decoder serving (VERDICT r4 item 1): KV-cache
    incremental decode + continuous batching through the real HTTP
    stack.  No reference counterpart — the reference has no generative
    serving at all.  Reports tokens/s/chip (aggregate over concurrent
    requests sharing decode steps), per-token inter-arrival p50/p99
    from a live SSE stream, and slot occupancy."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel

    if smoke:
        cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 128},
            "max_slots": 4, "max_seq": 128,
            "prefill_buckets": [32, 64],
        }
        arch, n_req, conc, max_tokens = "decoder_tiny", 12, 4, 8
    else:
        # GPT-2-small-class body; bf16; realistic vocab so the LM head
        # matmul is honest.  8 slots x 512 cache.
        cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 512},
            "max_slots": 8, "max_seq": 512,
            "prefill_buckets": [64, 512],
        }
        # 8 per wave x 4 rounds x 3 variants keeps all slots occupied
        # during each wave (occupancy is a headline stat — the r5
        # first pass split 64 requests three ways, 5/wave, and the
        # 0.38 occupancy capped aggregate tokens/s).
        arch, n_req, conc, max_tokens = "decoder", 96, 8, 64
    arch_kwargs = cfg.pop("arch_kwargs")
    # K A/B: steps_per_call=1 (token-granular streaming) vs K>1 (K
    # decode steps per device dispatch).  Both models live in one
    # process and alternate rounds.  K=16 is kept as it was; on this
    # chip it is not measured (ROADMAP A3 re-derives it).
    if smoke:
        k_hi = 2
    else:
        try:
            k_hi = int(os.environ.get("BENCH_GEN_K", "16"))
        except ValueError:
            raise ValueError(
                f"BENCH_GEN_K must be an integer >= 2, got "
                f"{os.environ['BENCH_GEN_K']!r}")
        if k_hi < 2:
            # The A/B needs a distinct second variant (K=1 is the
            # baseline side).
            raise ValueError(
                f"BENCH_GEN_K must be >= 2, got {k_hi}")
    # Three-way interleaved A/B (ROOFLINE methodology):
    #   k1    — steps_per_call=1, the token-granular baseline
    #   kKd1  — K steps/dispatch, pipeline_depth=1 (blocking fetch:
    #           wave wall = RTT + K device steps — the r4 shipped mode)
    #   kK    — K steps/dispatch, pipeline_depth=2 (device-resident
    #           feed chain: the fetch of wave N overlaps wave N+1, so
    #           wave wall -> max(RTT, K device steps)) — shipped mode
    variant_specs = [
        ("k1", {"steps_per_call": 1}),
        (f"k{k_hi}d1", {"steps_per_call": k_hi, "pipeline_depth": 1}),
        (f"k{k_hi}", {"steps_per_call": k_hi}),
    ]
    models = {}
    load_s = {}
    for label, extra in variant_specs:
        model_dir = _write_jax_model_dir(arch, arch_kwargs,
                                         **extra, **cfg)
        m = GenerativeModel(f"gen-{label}", model_dir)
        t0 = time.perf_counter()
        m.load()
        load_s[label] = round(time.perf_counter() - t0, 1)
        models[label] = m
    _reset_timeline()
    server = await _serve(list(models.values()))
    base = f"http://127.0.0.1:{server.http_port}"
    prompt = ("the quick brown fox jumps over the lazy dog "
              * (1 if smoke else 3))
    body = json.dumps({"prompt": prompt,
                       "max_tokens": max_tokens}).encode()
    variants = list(models)
    try:
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=600)) as s:
            # Warmup: compiles each variant's prefill bucket + decode
            # scan (and the insert scatter) before timing starts.
            compile_s = {}
            for label in variants:
                t0 = time.perf_counter()
                async with s.post(
                        f"{base}/v1/models/gen-{label}:generate",
                        data=body) as r:
                    assert r.status == 200, await r.text()
                compile_s[label] = round(time.perf_counter() - t0, 1)

            async def wave(label, n):
                sem = asyncio.Semaphore(conc)
                counts: List[int] = []

                async def one():
                    async with sem:
                        async with s.post(
                                f"{base}/v1/models/gen-{label}:generate",
                                data=body) as r:
                            out = await r.json()
                            counts.append(
                                out["details"]["token_count"])

                t0 = time.perf_counter()
                await asyncio.gather(*[one() for _ in range(n)])
                return sum(counts), time.perf_counter() - t0

            # Alternating rounds: each variant serves half of n_req in
            # interleaved waves so drift hits both equally.
            # Each round is ONE REPETITION of the A/B — the committed
            # record carries the per-rep values and their median, so a
            # single lucky round can never become the headline
            # (VERDICT r5 weak #1: round notes led with a best single
            # run the committed record contradicted).
            totals = {v: [0, 0.0] for v in variants}
            reps = {v: [] for v in variants}
            rounds = 4
            per_wave = max(1, n_req // (rounds * len(variants)))
            # Report what actually runs: integer division can shrink
            # the request count (smoke: 12 -> 8).
            n_req = rounds * len(variants) * per_wave
            for rnd in range(rounds):
                order = (variants if rnd % 2 == 0
                         else list(reversed(variants)))
                for label in order:
                    tok, wall = await wave(label, per_wave)
                    totals[label][0] += tok
                    totals[label][1] += wall
                    if wall > 0:
                        reps[label].append(round(tok / wall, 2))

            # Per-event latency: inter-event gaps on live SSE streams
            # (K=1: one token per gap; K=8: one K-chunk per gap).
            async def gaps_for(label):
                gaps: List[float] = []
                await _sse_measure(
                    s, f"{base}/v2/models/gen-{label}/generate_stream",
                    body, gaps, [])
                return np.asarray(gaps or [0.0])

            g1 = await gaps_for("k1")
            gk = await gaps_for(variants[2])
        out: Dict[str, Any] = {
            "requests": n_req, "concurrency": conc,
            "max_tokens": max_tokens,
            "steps_per_call_ab": {}, "load_s": load_s,
            "compile_s": compile_s,
        }
        for label in variants:
            tok, wall = totals[label]
            stats = models[label].engine_stats()
            rep_vals = reps[label]
            out["steps_per_call_ab"][label] = {
                # Headline per variant = MEDIAN of the interleaved
                # per-round repetitions; the reps + spread ride along
                # so the committed record shows its own variance.
                "tokens_per_s": (round(float(np.median(rep_vals)), 2)
                                 if rep_vals else None),
                "tokens_per_s_reps": rep_vals,
                "tokens_per_s_spread": (
                    [min(rep_vals), max(rep_vals)] if rep_vals
                    else None),
                "tokens_per_s_aggregate": (round(tok / wall, 2)
                                           if wall else None),
                "tokens_total": tok,
                "wall_s": round(wall, 2),
                "slot_occupancy": stats.get("slot_occupancy"),
                "decode_dispatches": stats.get("decode_steps"),
                "token_steps": stats.get("token_steps"),
                "decode_device_s": stats.get("decode_device_s"),
                "decode_wait_s": stats.get("decode_wait_s"),
                "wasted_token_steps": stats.get("wasted_token_steps"),
                "pipeline_depth": stats.get("pipeline_depth"),
                "adaptive_depth": stats.get("adaptive_depth"),
                "suppressed_waves": stats.get("suppressed_waves"),
            }
        k1 = out["steps_per_call_ab"]["k1"]["tokens_per_s"]
        kd1 = out["steps_per_call_ab"][variants[1]]["tokens_per_s"]
        khi = out["steps_per_call_ab"][variants[2]]["tokens_per_s"]
        if k1 and khi:
            out["k_speedup"] = round(khi / k1, 2)
        if kd1 and khi:
            # The pipelining dividend at equal K (median over median):
            # >1 means the fetch RTT is being hidden behind device
            # compute.  The kK side runs the ADAPTIVE governor, so
            # this is also the adaptive-vs-fixed-depth-1 criterion.
            out["depth_speedup"] = round(khi / kd1, 2)
        # Headline numbers come from the pipelined K variant (the
        # shipped default for this transport).
        out["tokens_per_s"] = khi
        out["token_p50_ms"] = round(float(np.percentile(g1, 50)), 2)
        out["token_p99_ms"] = round(float(np.percentile(g1, 99)), 2)
        out["chunk_p50_ms"] = round(float(np.percentile(gk, 50)), 2)
        out["slot_occupancy"] = out["steps_per_call_ab"][
            variants[2]]["slot_occupancy"]
        out["cache_bytes"] = models["k1"].engine_stats().get(
            "cache_bytes")
        out["timeline"] = _timeline_summary()
        out["cache"] = _cache_summary(models[variants[2]])
        return out
    finally:
        await server.stop_async()


async def bench_longctx(smoke: bool) -> Dict[str, Any]:
    """Long-context fill-mask: a 4096-token seq bucket served through
    the binary wire, suffix padding masked inside the flash kernel
    (kv_lengths).  No reference counterpart — the reference never
    touches model internals; this is the TPU-native long-sequence
    serving capability (SURVEY.md §5.7)."""
    from kfserving_tpu.predictors.jax_model import JaxModel
    from kfserving_tpu.protocol import v2 as v2proto

    if smoke:
        arch_kwargs = {"num_layers": 2, "hidden_size": 64,
                       "num_heads": 2, "intermediate_size": 128,
                       "vocab_size": 512, "max_position": 256,
                       "seq_len": 256}
        bucket, tokens, vocab = 256, 200, 512
    else:
        arch_kwargs = {"num_layers": 4, "hidden_size": 512,
                       "num_heads": 8, "intermediate_size": 2048,
                       "vocab_size": 8192, "max_position": 4096,
                       "seq_len": 4096}
        bucket, tokens, vocab = 4096, 3000, 8192
    model_dir = _write_jax_model_dir(
        "bert", arch_kwargs,
        seq_buckets=[bucket], batch_buckets=[4], max_batch_size=4,
        max_latency_ms=25.0, pipeline_depth=2, warmup=True,
        output="topk", topk=5)
    model = JaxModel("longctx", model_dir)
    t0 = time.perf_counter()
    model.load()
    compile_s = time.perf_counter() - t0
    server = await _serve([model])
    try:
        rng = np.random.default_rng(0)
        ids = rng.integers(1, vocab, size=(1, tokens)).astype(np.int32)
        body, hlen = v2proto.make_binary_request(
            {"input_0": ids}, binary_output=True)
        res = await closed_loop(
            server.http_port, "/v2/models/longctx/infer", body,
            num_requests=16 if smoke else 48,
            concurrency=4 if smoke else 8,
            headers={"Inference-Header-Content-Length": str(hlen)})
        res["tokens_per_request"] = tokens
        res["tokens_per_s"] = res["req_per_s"] * tokens
        return {"closed_loop": res, "seq_bucket": bucket,
                "compile_s": round(compile_s, 1)}
    finally:
        await server.stop_async()


async def bench_generate_poisson(smoke: bool) -> Dict[str, Any]:
    """Arrival-process generation bench (VERDICT r4 #5's measurement
    half): open-loop Poisson arrivals of MIXED-length prompts against
    live SSE streams, reporting inter-token gap percentiles and
    time-to-first-token.  The uniform-wave bench_generate never
    overlaps a prefill burst with steady-state decode, so the stall a
    512-bucket admission adds to every in-flight stream's inter-token
    latency is invisible there; Poisson arrivals expose it.  Done
    criterion: inter-token p99 <= ~1.5x steady-state p50 at equal
    throughput."""
    import random as _random

    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel

    if smoke:
        cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 128},
            "max_slots": 4, "max_seq": 128,
            "prefill_buckets": [32, 128],
            "steps_per_call": 2,
        }
        n_req, max_tokens = 10, 8
        short_len, long_len = 8, 60
    else:
        cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 512},
            "max_slots": 8, "max_seq": 512,
            "prefill_buckets": [64, 512],
            "steps_per_call": int(os.environ.get("BENCH_GEN_K", "16")),
        }
        n_req, max_tokens = 48, 48
        short_len, long_len = 30, 380  # 64-bucket vs 512-bucket
    arch_kwargs = cfg.pop("arch_kwargs")
    _reset_timeline()
    model_dir = _write_jax_model_dir(
        "decoder_tiny" if smoke else "decoder", arch_kwargs, **cfg)
    model = GenerativeModel("gen", model_dir)
    model.load()
    server = await _serve([model])
    base = f"http://127.0.0.1:{server.http_port}"
    rng = _random.Random(7)

    def prompt_of(n_tokens):
        # ~1 byte tokenizer char per token.
        return "x" * max(4, n_tokens - 1)

    try:
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=900)) as s:
            async def one_stream(length, gaps, ttfts):
                """Gap samples are per arriving CHUNK (transport
                read), not per SSE event: at K>1 a wave's K token
                events land in one read, and pretending they have
                individual latencies would make the percentiles
                meaningless (bench_generate's K=1 variant owns true
                per-token gaps).  Chunk cadence is exactly what an
                admission stall stretches — the p99/p50 criterion
                reads on it."""
                body = json.dumps({
                    "text_input": prompt_of(length),
                    "max_tokens": max_tokens}).encode()
                await _sse_measure(
                    s, f"{base}/v2/models/gen/generate_stream",
                    body, gaps, ttfts)

            # Warmup: compile both prefill buckets + decode scan, AND
            # the pow2 batched-prefill row buckets a burst compiles
            # (b2/b4) — the first capacity run here once ate a 20 s
            # b4-prefill compile and the arrival rate collapsed to the
            # floor.
            warm_gaps, warm_ttft = [], []
            await one_stream(short_len, warm_gaps, warm_ttft)
            await one_stream(long_len, warm_gaps, warm_ttft)
            # Row buckets b4 AND b2 for both length buckets: the
            # estimate's arrival order forms b2 groups, and a cold b2
            # trace inside est_wall collapses the rate to the floor.
            for n, length in ((4, short_len), (2, short_len),
                              (2, long_len)):
                await asyncio.gather(*[
                    one_stream(length, warm_gaps, warm_ttft)
                    for _ in range(n)])

            # Capacity estimate from a warm closed burst of the MIXED
            # length distribution (an all-short estimate once
            # overshot: short streams skip the long-bucket prefill
            # compute that dominates mixed load, the resulting 0.7x
            # rate exceeded true capacity, and the arrival queue
            # exploded to 32 s TTFTs).  Then Poisson at 0.6x so
            # stalls are attributable to admission interference, not
            # saturation.
            t0 = time.perf_counter()
            est_gaps, est_ttft = [], []
            await asyncio.gather(*[
                one_stream(short_len if i % 3 else long_len,
                           est_gaps, est_ttft)
                for i in range(6)])
            est_wall = time.perf_counter() - t0
            req_rate_capacity = 6 / est_wall if est_wall > 0 else 1.0
            rate = max(0.2, 0.6 * req_rate_capacity)

            # Median-of-N repetitions INSIDE one invocation (VERDICT
            # r5 weak #2: the committed Poisson record must carry its
            # own variance, not a single arrival-pattern roll).  Each
            # rep is an independent Poisson phase; the headline keys
            # are medians across reps and the per-rep values ride
            # along as *_reps.
            n_reps = 3
            per_rep = max(2, n_req // n_reps)
            n_req = n_reps * per_rep
            rep_records: List[Dict[str, Any]] = []
            prefills_total = 0
            wasted_total = 0
            for _rep in range(n_reps):
                pre = dict(model.engine_stats())
                gaps: List[float] = []
                ttfts: List[float] = []
                tasks = []
                t_start = time.perf_counter()
                for i in range(per_rep):
                    # 70% short-bucket, 30% long-bucket arrivals:
                    # long prefills land while short streams decode.
                    length = (short_len if rng.random() < 0.7
                              else long_len)
                    tasks.append(asyncio.ensure_future(
                        one_stream(length, gaps, ttfts)))
                    await asyncio.sleep(rng.expovariate(rate))
                await asyncio.gather(*tasks)
                wall = time.perf_counter() - t_start
                stats = model.engine_stats()
                g = np.asarray(gaps) if gaps else np.asarray([0.0])
                t = np.asarray(ttfts) if ttfts else np.asarray([0.0])
                rep_records.append({
                    "wall_s": round(wall, 2),
                    "tokens_per_s": round(
                        (stats.get("tokens_generated", 0)
                         - pre.get("tokens_generated", 0)) / wall, 2),
                    "chunk_gap_p50_ms": round(
                        float(np.percentile(g, 50)), 2),
                    "chunk_gap_p99_ms": round(
                        float(np.percentile(g, 99)), 2),
                    "ttft_p50_ms": round(
                        float(np.percentile(t, 50)), 2),
                    "ttft_p99_ms": round(
                        float(np.percentile(t, 99)), 2),
                })
                prefills_total += (stats.get("prefills", 0)
                                   - pre.get("prefills", 0))
                wasted_total += (stats.get("wasted_token_steps", 0)
                                 - pre.get("wasted_token_steps", 0))

        def med(key):
            return round(float(np.median(
                [r[key] for r in rep_records])), 2)

        p50 = med("chunk_gap_p50_ms")
        p99 = med("chunk_gap_p99_ms")
        return {
            "requests": n_req, "max_tokens": max_tokens,
            "timeline": _timeline_summary(),
            "cache": _cache_summary(model),
            "arrival_rate_req_s": round(rate, 3),
            "repetitions": n_reps,
            "wall_s": round(sum(r["wall_s"] for r in rep_records), 2),
            "tokens_per_s": med("tokens_per_s"),
            "chunk_gap_p50_ms": p50,
            "chunk_gap_p99_ms": p99,
            "chunk_gap_p99_ms_reps": [r["chunk_gap_p99_ms"]
                                      for r in rep_records],
            "tokens_per_s_reps": [r["tokens_per_s"]
                                  for r in rep_records],
            "p99_over_p50": round(p99 / p50, 2) if p50 else None,
            "ttft_p50_ms": med("ttft_p50_ms"),
            "ttft_p99_ms": med("ttft_p99_ms"),
            "reps": rep_records,
            "prefills": prefills_total,
            "wasted_token_steps": wasted_total,
        }
    finally:
        await server.stop_async()


async def bench_generate_4k(smoke: bool) -> Dict[str, Any]:
    """Long-context generation with the PAGED cache (VERDICT r4 #4's
    bench half): 4096-token context, flash-eligible prefill bucket,
    a shared long system prompt exercising prefix reuse at scale, and
    a pool sized well UNDER dense parity — the HBM the paging exists
    to save.  Reports tokens/s, TTFT, prefix-hit rate, and cache
    bytes vs the dense layout."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel

    if smoke:
        cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 256},
            "max_slots": 4, "max_seq": 256,
            "prefill_buckets": [64, 256],
            "block_size": 32, "cache_blocks": 20,
            "steps_per_call": 2,
        }
        n_req, conc, max_tokens = 6, 3, 8
        system_len, tail_len = 150, 12
    else:
        cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 4096},
            "max_slots": 8, "max_seq": 4096,
            "prefill_buckets": [512, 4096],
            # Dense parity would be 8 * (4096/128) = 256 blocks; 112
            # covers the shared prefix (23 blocks) + per-slot tails +
            # growth with ~2.3x headroom — 43.75% of dense HBM.
            "block_size": 128, "cache_blocks": 112,
            "steps_per_call": int(os.environ.get("BENCH_GEN_K", "16")),
        }
        n_req, conc, max_tokens = 16, 8, 48
        system_len, tail_len = 2980, 40
    arch_kwargs = cfg.pop("arch_kwargs")
    model_dir = _write_jax_model_dir(
        "decoder_tiny" if smoke else "decoder", arch_kwargs, **cfg)
    model = GenerativeModel("gen4k", model_dir)
    t0 = time.perf_counter()
    model.load()
    load_s = round(time.perf_counter() - t0, 1)
    _reset_timeline()
    server = await _serve([model])
    base = f"http://127.0.0.1:{server.http_port}"
    system = "the quick brown fox jumps over the lazy dog. " * 80
    system = system[:system_len]
    try:
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1800)) as s:
            async def one(i, ttfts):
                body = json.dumps({
                    "text_input": system + f" request {i:04d} " +
                                  "x" * (tail_len - 14),
                    "max_tokens": max_tokens}).encode()
                # Drains the stream fully (tokens_per_s needs the
                # whole decode) but keeps only the TTFT.
                await _sse_measure(
                    s, f"{base}/v2/models/gen4k/generate_stream",
                    body, [], ttfts)

            # Warmup: compiles the 4096 prefill bucket (flash path)
            # + decode scan + the pow2 batched-prefill ROW buckets a
            # concurrent burst forms (b8/b4/b2 — without this they
            # compile mid-measurement and pollute TTFT by seconds);
            # also seeds the prefix index.
            warm_ttft: List[float] = []
            t0 = time.perf_counter()
            await one(9999, warm_ttft)
            for burst in (8, 4, 2):
                if burst <= conc:
                    await asyncio.gather(*[
                        one(9000 + burst * 10 + j, warm_ttft)
                        for j in range(burst)])
            compile_s = round(time.perf_counter() - t0, 1)

            pre = dict(model.engine_stats())
            ttfts: List[float] = []
            sem = asyncio.Semaphore(conc)

            async def gated(i):
                async with sem:
                    await one(i, ttfts)

            t0 = time.perf_counter()
            await asyncio.gather(*[gated(i) for i in range(n_req)])
            wall = time.perf_counter() - t0
        stats = model.engine_stats()
        paged = stats.get("paged", {})
        hits = paged.get("prefix_hits", 0) - \
            pre.get("paged", {}).get("prefix_hits", 0)
        misses = paged.get("prefix_misses", 0) - \
            pre.get("paged", {}).get("prefix_misses", 0)
        dense_bytes = (cfg["max_slots"] * cfg["max_seq"]
                       * arch_kwargs.get("num_heads", 2)
                       * (arch_kwargs["hidden_size"]
                          // arch_kwargs.get("num_heads", 2))
                       * 2 * arch_kwargs.get("num_layers", 2)
                       * (2 if not smoke else 4))
        return {
            "requests": n_req, "concurrency": conc,
            "timeline": _timeline_summary(),
            "cache": _cache_summary(model),
            "context": cfg["max_seq"],
            "block_size": cfg["block_size"],
            "pool_blocks": cfg["cache_blocks"],
            "load_s": load_s, "compile_s": compile_s,
            "wall_s": round(wall, 2),
            "tokens_per_s": round(
                (stats.get("tokens_generated", 0)
                 - pre.get("tokens_generated", 0)) / wall, 2),
            "ttft_p50_ms": round(float(np.percentile(
                np.asarray(ttfts or [0.0]), 50)), 2),
            "prefix_hits": hits, "prefix_misses": misses,
            "prefix_hit_rate": round(hits / max(1, hits + misses), 3),
            "cache_bytes": stats.get("cache_bytes"),
            "dense_cache_bytes": dense_bytes,
            "hbm_vs_dense": round(
                stats.get("cache_bytes", 0) / max(1, dense_bytes), 3),
        }
    finally:
        await server.stop_async()


async def bench_generate_cold4k(smoke: bool) -> Dict[str, Any]:
    """COLD long-context prefill vs live decode streams (VERDICT r5
    weak #4's missing measurement): `generate_4k` runs at
    prefix_hit_rate 1.0, so the monolithic cold-prefill stall it would
    inject between two decode fetches was never measured.  Here every
    cold prompt is UNIQUE from its first block (a per-request salt
    defeats the chain-hash prefix index), cold arrivals come Poisson
    over live short-prompt decode streams, and the A/B is chunked
    prefill (prefill_chunk_tokens set) vs monolithic on otherwise
    identical paged models — interleaved reps, median-of-N, per-rep
    spread committed.  Headline: the decode streams' inter-chunk gap
    p99 with chunking strictly below without."""
    import random as _random

    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel

    if smoke:
        # The cold prompt must be long enough that the MONOLITHIC
        # stall clears host jitter by an order of magnitude (a
        # 200-token prompt on the 2-layer body stalled ~20-45 ms —
        # the same size as this box's scheduler noise, making the
        # A/B a coin flip): 900 tokens lands a one-to-few-hundred-ms
        # monolithic stall against ~10 ms decode gaps, while the
        # chunked side pays one ~128-token chunk at a time.
        base_cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 1024},
            "max_slots": 4, "max_seq": 1024,
            "prefill_buckets": [32, 1024],
            "block_size": 32, "cache_blocks": 96,
            "steps_per_call": 2,
        }
        chunk_tokens = 128
        # 5 reps: this box's scheduler occasionally steals >1s from a
        # rep (seen on BOTH variants), so the median needs room to
        # absorb two bad reps; streams sized for a stable per-rep p99.
        n_streams, n_cold, reps = 3, 3, 5
        stream_len, stream_tokens, cold_len, cold_tokens = 24, 36, 900, 4
    else:
        base_cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 4096},
            "max_slots": 8, "max_seq": 4096,
            "prefill_buckets": [64, 512, 4096],
            # Unique cold 4k prompts share nothing: budget 5 resident
            # 32-block prompts + short-stream tails + growth.
            "block_size": 128, "cache_blocks": 176,
            "steps_per_call": int(os.environ.get("BENCH_GEN_K", "16")),
        }
        # One chunk's device time ~ one K=16 decode wave for this
        # body on this transport.
        chunk_tokens = 512
        n_streams, n_cold, reps = 4, 5, 3
        stream_len, stream_tokens, cold_len, cold_tokens = 60, 128, 3900, 24
    arch_kwargs = base_cfg.pop("arch_kwargs")
    arch = "decoder_tiny" if smoke else "decoder"
    models = {}
    for label, extra in (("chunked",
                          {"prefill_chunk_tokens": chunk_tokens}),
                         ("monolithic", {})):
        d = _write_jax_model_dir(arch, arch_kwargs, **extra, **base_cfg)
        m = GenerativeModel(f"cold-{label}", d)
        m.load()
        models[label] = m
    _reset_timeline()
    server = await _serve(list(models.values()))
    base = f"http://127.0.0.1:{server.http_port}"
    rng = _random.Random(11)
    salt = {"n": 0}

    def cold_prompt():
        # The salt leads, so even the FIRST cache block differs
        # between requests — zero prefix reuse, a genuinely cold
        # prefill every time.
        salt["n"] += 1
        return f"cold{salt['n']:06d} " + "y" * (cold_len - 12)

    try:
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1800)) as s:
            async def stream(label, length, max_toks, gaps, ttfts):
                body = json.dumps({
                    "text_input": "s%04d " % rng.randrange(10_000)
                                  + "x" * max(1, length - 6),
                    "max_tokens": max_toks}).encode()
                await _sse_measure(
                    s, f"{base}/v2/models/cold-{label}/generate_stream",
                    body, gaps, ttfts)

            async def cold_one(label, ttfts):
                body = json.dumps({
                    "text_input": cold_prompt(),
                    "max_tokens": cold_tokens}).encode()
                # TTFT is the cold metric; dropping the stream after
                # the first token cancels the slot (client disconnect)
                # so cold DECODE doesn't crowd the live streams we're
                # measuring.
                await _sse_measure(
                    s, f"{base}/v2/models/cold-{label}/generate_stream",
                    body, [], ttfts, stop_after_first=True)

            async def rep(label):
                """One repetition: live decode streams measured while
                cold long prompts land Poisson."""
                gaps: List[float] = []
                st_ttft: List[float] = []
                cold_ttft: List[float] = []
                streams = [asyncio.ensure_future(
                    stream(label, stream_len, stream_tokens, gaps,
                           st_ttft)) for _ in range(n_streams)]
                # Let streams reach steady-state decode before the
                # first cold arrival.
                await asyncio.sleep(0.1 if smoke else 0.5)
                colds = []
                for _ in range(n_cold):
                    colds.append(asyncio.ensure_future(
                        cold_one(label, cold_ttft)))
                    await asyncio.sleep(rng.expovariate(
                        4.0 if smoke else 1.0))
                await asyncio.gather(*streams, *colds)
                g = np.asarray(gaps) if gaps else np.asarray([0.0])
                return {
                    "gap_p50_ms": round(float(np.percentile(g, 50)), 2),
                    "gap_p99_ms": round(float(np.percentile(g, 99)), 2),
                    "gap_max_ms": round(float(np.max(g)), 2),
                    "cold_ttft_p50_ms": round(float(np.percentile(
                        np.asarray(cold_ttft or [0.0]), 50)), 2),
                }

            # Warmup both variants: decode scan + stream bucket +
            # one full cold prefill (compiles the 4096 bucket on the
            # monolithic side and the chunk program on the chunked
            # side) — compiles must never land inside a measured rep.
            compile_s = {}
            for label in models:
                t0 = time.perf_counter()
                await stream(label, stream_len, 2, [], [])
                await cold_one(label, [])
                compile_s[label] = round(time.perf_counter() - t0, 1)

            pre = {lb: dict(m.engine_stats())
                   for lb, m in models.items()}
            rep_out = {lb: [] for lb in models}
            for r_i in range(reps):
                order = (list(models) if r_i % 2 == 0
                         else list(reversed(list(models))))
                for label in order:
                    rep_out[label].append(await rep(label))
        out: Dict[str, Any] = {
            "repetitions": reps, "decode_streams": n_streams,
            "cold_arrivals_per_rep": n_cold,
            "cold_prompt_tokens": cold_len,
            "chunk_tokens": chunk_tokens,
            "compile_s": compile_s,
        }
        for label, m in models.items():
            recs = rep_out[label]
            stats = m.engine_stats()

            def d(key):
                return stats.get(key, 0) - pre[label].get(key, 0)

            med = {k: round(float(np.median([r[k] for r in recs])), 2)
                   for k in recs[0]}
            out[label] = {
                **med,
                "gap_p99_ms_reps": [r["gap_p99_ms"] for r in recs],
                "prefills": d("prefills"),
                "wasted_token_steps": d("wasted_token_steps"),
                "suppressed_waves": d("suppressed_waves"),
            }
            chunked_stats = stats.get("chunked_prefill")
            if chunked_stats:
                out[label]["chunked_prefill"] = chunked_stats
            paged = stats.get("paged", {})
            out[label]["prefix_hits"] = (
                paged.get("prefix_hits", 0)
                - pre[label].get("paged", {}).get("prefix_hits", 0))
        # The tentpole criterion, computed from MEDIANS: chunking must
        # strictly lower the decode streams' gap p99 under cold load.
        c, mo = out["chunked"], out["monolithic"]
        if mo["gap_p99_ms"]:
            out["gap_p99_chunked_over_monolithic"] = round(
                c["gap_p99_ms"] / mo["gap_p99_ms"], 3)
        out["gap_p99_ms"] = c["gap_p99_ms"]
        out["gap_p99_ms_monolithic"] = mo["gap_p99_ms"]
        out["timeline"] = _timeline_summary()
        out["cache"] = {label: _cache_summary(m)
                        for label, m in models.items()}
        return out
    finally:
        await server.stop_async()


async def bench_generate_stream_wire(smoke: bool) -> Dict[str, Any]:
    """GenerationService.GenerateStream (gRPC/HTTP2) vs SSE on the
    SAME workload (VERDICT r5 missing #2 — the dropped r4
    done-criterion).  One model, interleaved repetitions alternating
    wire order, median-of-N: aggregate tokens/s, TTFT, and inter-read
    gap percentiles per wire."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel

    try:
        import grpc
    except ImportError:
        return {"skipped": "grpcio not installed"}
    from kfserving_tpu.protocol.grpc import kfs_generate_pb2 as gpb
    from kfserving_tpu.server.grpc_server import GRPCServer

    if smoke:
        cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 128},
            "max_slots": 4, "max_seq": 128,
            "prefill_buckets": [32, 64],
            "steps_per_call": 2,
        }
        n_streams, max_tokens, reps = 4, 8, 2
    else:
        cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 512},
            "max_slots": 8, "max_seq": 512,
            "prefill_buckets": [64, 512],
            "steps_per_call": int(os.environ.get("BENCH_GEN_K", "16")),
        }
        n_streams, max_tokens, reps = 8, 64, 3
    arch_kwargs = cfg.pop("arch_kwargs")
    model_dir = _write_jax_model_dir(
        "decoder_tiny" if smoke else "decoder", arch_kwargs, **cfg)
    model = GenerativeModel("wire", model_dir)
    model.load()
    _reset_timeline()
    server = await _serve([model])
    server.grpc_server = GRPCServer(server.dataplane, port=0)
    await server.grpc_server.start()
    base = f"http://127.0.0.1:{server.http_port}"
    prompt = "the quick brown fox jumps over the lazy dog"
    try:
        channel = grpc.aio.insecure_channel(
            f"127.0.0.1:{server.grpc_server.port}")
        stream_call = channel.unary_stream(
            "/kfserving.generate.GenerationService/GenerateStream",
            request_serializer=lambda b: b,
            response_deserializer=(
                gpb.GenerateStreamResponse.FromString))
        grpc_payload = gpb.GenerateRequest(
            model_name="wire", text_input=prompt,
            max_tokens=max_tokens).SerializeToString()
        sse_body = json.dumps({"text_input": prompt,
                               "max_tokens": max_tokens}).encode()

        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=900)) as s:
            async def one_sse(gaps, ttfts):
                await _sse_measure(
                    s, f"{base}/v2/models/wire/generate_stream",
                    sse_body, gaps, ttfts)

            async def one_grpc(gaps, ttfts):
                t_post = time.perf_counter()
                last = None
                async for _msg in stream_call(grpc_payload):
                    now = time.perf_counter()
                    if last is None:
                        ttfts.append((now - t_post) * 1e3)
                    else:
                        gaps.append((now - last) * 1e3)
                    last = now

            wires = {"sse": one_sse, "grpc": one_grpc}

            async def wave(fn, gaps, ttfts):
                pre = dict(model.engine_stats())
                t0 = time.perf_counter()
                await asyncio.gather(*[fn(gaps, ttfts)
                                       for _ in range(n_streams)])
                wall = time.perf_counter() - t0
                toks = (model.engine_stats().get("tokens_generated", 0)
                        - pre.get("tokens_generated", 0))
                return round(toks / wall, 2) if wall else None

            # Warmup both wires (compiles + HTTP2/TCP setup).
            await wave(one_sse, [], [])
            await wave(one_grpc, [], [])

            recs = {w: {"tokens_per_s": [], "gaps": [], "ttfts": []}
                    for w in wires}
            for r_i in range(reps):
                order = (list(wires) if r_i % 2 == 0
                         else list(reversed(list(wires))))
                for w in order:
                    tps = await wave(wires[w], recs[w]["gaps"],
                                     recs[w]["ttfts"])
                    recs[w]["tokens_per_s"].append(tps)
        out: Dict[str, Any] = {
            "streams_per_rep": n_streams, "max_tokens": max_tokens,
            "repetitions": reps,
        }
        for w in wires:
            tps = [v for v in recs[w]["tokens_per_s"]
                   if v is not None]
            g = np.asarray(recs[w]["gaps"] or [0.0])
            t = np.asarray(recs[w]["ttfts"] or [0.0])
            out[w] = {
                "tokens_per_s": (round(float(np.median(tps)), 2)
                                 if tps else None),
                "tokens_per_s_reps": tps,
                "gap_p50_ms": round(float(np.percentile(g, 50)), 2),
                "gap_p99_ms": round(float(np.percentile(g, 99)), 2),
                "ttft_p50_ms": round(float(np.percentile(t, 50)), 2),
            }
        if out["sse"]["tokens_per_s"] and out["grpc"]["tokens_per_s"]:
            out["grpc_over_sse"] = round(
                out["grpc"]["tokens_per_s"]
                / out["sse"]["tokens_per_s"], 3)
        out["timeline"] = _timeline_summary()
        out["cache"] = _cache_summary(model)
        return out
    finally:
        try:
            await channel.close()
        except Exception:
            pass
        await server.stop_async()


async def bench_cache(smoke: bool) -> Dict[str, Any]:
    """Shared-prefix cache & cost attribution A/B (ISSUE 13
    acceptance): the realistic multi-user prompt mix — one common
    system prompt + unique per-request tails — against a control arm
    of fully unique prompts on the SAME paged model, interleaved
    reps, median-of-N.  Evidence committed to BENCH_cache.json:
    hit-rate > 0 on the shared arm and ~0 on the unique arm,
    tokens-saved consistent with hit-blocks x block_size, the
    replica's /debug/cache snapshot (index census, hot chains, pool
    occupancy), and per-request attribution records showing the
    cache economics land in the cost feed."""
    import aiohttp

    from kfserving_tpu.observability import attribution
    from kfserving_tpu.predictors.llm import GenerativeModel

    if smoke:
        cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 256},
            "max_slots": 4, "max_seq": 256,
            "prefill_buckets": [64, 128, 256],
            "block_size": 32, "cache_blocks": 32,
            "steps_per_call": 2,
        }
        per_wave, reps, max_tokens = 3, 3, 6
        system_len, tail_len = 96, 16      # 3 shared blocks
    else:
        cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 4096},
            "max_slots": 8, "max_seq": 4096,
            "prefill_buckets": [512, 4096],
            "block_size": 128, "cache_blocks": 160,
            "steps_per_call": int(os.environ.get("BENCH_GEN_K", "16")),
        }
        per_wave, reps, max_tokens = 8, 3, 32
        system_len, tail_len = 2944, 96    # 23 shared blocks
    arch_kwargs = cfg.pop("arch_kwargs")
    bs = cfg["block_size"]
    model_dir = _write_jax_model_dir(
        "decoder_tiny" if smoke else "decoder", arch_kwargs, **cfg)
    model = GenerativeModel("cachebench", model_dir)
    model.load()
    _reset_timeline()
    attribution.clear()
    server = await _serve([model])
    base = f"http://127.0.0.1:{server.http_port}"
    # Byte tokenizer: ~1 token per char; the system prompt length is
    # block-aligned so every shared block is a FULL block (partial
    # trailing blocks never register in the prefix index).
    system = ("you are a careful serving assistant. " * 200)[:system_len]
    salt = {"n": 0}

    def shared_prompt():
        salt["n"] += 1
        return system + f" req {salt['n']:05d} " + \
            "t" * max(1, tail_len - 11)

    def unique_prompt():
        # Salt LEADS: even the first block differs per request — a
        # genuinely cold prompt of the same total length.
        salt["n"] += 1
        return f"u{salt['n']:06d} " + "u" * (system_len + tail_len - 8)

    try:
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1800)) as s:
            async def one(prompt, ttfts):
                body = json.dumps({"text_input": prompt,
                                   "max_tokens": max_tokens}).encode()
                await _sse_measure(
                    s, f"{base}/v2/models/cachebench/generate_stream",
                    body, [], ttfts)

            # Warmup: compile the prefill bucket + decode scan + pow2
            # prefill row buckets, and SEED the shared system prompt's
            # blocks into the prefix index (the steady-state a real
            # fleet serves from).
            for n in (1, 2, min(4, per_wave)):
                await asyncio.gather(*[
                    one(shared_prompt(), []) for _ in range(n)])

            arms = {"shared": shared_prompt, "unique": unique_prompt}
            rep_records = {a: [] for a in arms}
            for r_i in range(reps):
                order = (list(arms) if r_i % 2 == 0
                         else list(reversed(list(arms))))
                for arm in order:
                    pre = dict(model.engine_stats()).get("paged", {})
                    ttfts: List[float] = []
                    t0 = time.perf_counter()
                    await asyncio.gather(*[
                        one(arms[arm](), ttfts)
                        for _ in range(per_wave)])
                    wall = time.perf_counter() - t0
                    post = model.engine_stats().get("paged", {})
                    hits = (post.get("prefix_hits", 0)
                            - pre.get("prefix_hits", 0))
                    misses = (post.get("prefix_misses", 0)
                              - pre.get("prefix_misses", 0))
                    rep_records[arm].append({
                        "wall_s": round(wall, 3),
                        "prefix_hits": hits,
                        "prefix_misses": misses,
                        "hit_rate": round(
                            hits / max(1, hits + misses), 4),
                        "tokens_saved": (
                            post.get("prefill_tokens_saved", 0)
                            - pre.get("prefill_tokens_saved", 0)),
                        "ttft_p50_ms": round(float(np.percentile(
                            np.asarray(ttfts or [0.0]), 50)), 2),
                    })
            # The replica's own federable snapshot (the exact feed
            # prefix-affinity routing reads).
            async with s.get(f"{base}/debug/cache") as r:
                assert r.status == 200, await r.text()
                debug_cache = await r.json()

        out: Dict[str, Any] = {
            "requests_per_wave": per_wave, "repetitions": reps,
            "system_prompt_tokens": system_len,
            "shared_blocks": system_len // bs,
            "block_size": bs,
        }
        for arm in arms:
            recs = rep_records[arm]
            med = {k: round(float(np.median([r[k] for r in recs])), 4)
                   for k in ("hit_rate", "tokens_saved",
                             "ttft_p50_ms")}
            out[arm] = {
                **med,
                "hit_rate_reps": [r["hit_rate"] for r in recs],
                "prefix_hits_total": sum(r["prefix_hits"]
                                         for r in recs),
                "prefix_misses_total": sum(r["prefix_misses"]
                                           for r in recs),
                "tokens_saved_total": sum(r["tokens_saved"]
                                          for r in recs),
                "reps": recs,
            }
        # Acceptance arithmetic: tokens saved must equal hit blocks x
        # block_size on the shared arm, and the unique arm must not
        # have hit the index at all.
        out["hit_rate_shared"] = out["shared"]["hit_rate"]
        out["hit_rate_unique"] = out["unique"]["hit_rate"]
        out["tokens_saved_consistent"] = (
            out["shared"]["tokens_saved_total"]
            == out["shared"]["prefix_hits_total"] * bs)
        # Attribution evidence: one costed record per arm (the shared
        # arm's must carry cache_saved_tokens > 0, the unique arm's
        # 0) — proof the cache economics reach the per-request feed.
        samples = attribution.recent(limit=4 * per_wave * reps)
        out["attribution_samples"] = {
            "shared": next((r for r in reversed(samples)
                            if r.get("cache_saved_tokens", 0) > 0),
                           None),
            "unique": next((r for r in reversed(samples)
                            if r.get("cache_saved_tokens", 1) == 0),
                           None),
        }
        out["debug_cache"] = debug_cache
        out["timeline"] = _timeline_summary()
        out["cache"] = _cache_summary(model)
        record = {
            "scenario": "shared_prefix_cache_ab",
            "smoke": smoke,
            **{k: out[k] for k in
               ("requests_per_wave", "repetitions",
                "system_prompt_tokens", "shared_blocks", "block_size",
                "shared", "unique", "hit_rate_shared",
                "hit_rate_unique", "tokens_saved_consistent",
                "attribution_samples", "debug_cache", "cache")},
        }
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        with open(os.path.join(root, "BENCH_cache.json"), "w") as f:
            json.dump(record, f, indent=2)
        return out
    finally:
        await server.stop_async()


async def bench_kvtier(smoke: bool) -> Dict[str, Any]:
    """Tiered KV residency A/B (ISSUE 16 acceptance): conversational
    return traffic with Poisson-distributed gaps sized so the device
    block pool churns every conversation out between visits, but the
    host tier holds them all.  Two identical paged models on one
    server — one with the host tier, one drop-on-evict — interleaved
    reps with order flip, median-of-N.  Evidence committed to
    BENCH_kvtier.json: return-visit TTFT p50/p99 per arm, host-tier
    tokens saved vs the drop arm's zero, the tier telemetry families,
    and the consistency flag `host_tier_saved_tokens == (faulted +
    coalesced blocks) x block_size` — the credit ledger never invents
    a block nobody read back."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel

    if smoke:
        cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 256},
            "max_slots": 2, "max_seq": 256,
            "prefill_buckets": [32, 64, 128, 256],
            "block_size": 32, "cache_blocks": 14,
            "prefill_chunk_tokens": 32,
            "steps_per_call": 2,
        }
        n_convs, reps, max_tokens = 6, 3, 4
        ctx_len, host_tier_blocks, gap_mean_s = 96, 64, 0.005
    else:
        cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 4096},
            "max_slots": 4, "max_seq": 4096,
            "prefill_buckets": [512, 2048, 4096],
            "block_size": 128, "cache_blocks": 72,
            "prefill_chunk_tokens": 512,
            "steps_per_call": int(os.environ.get("BENCH_GEN_K", "16")),
        }
        n_convs, reps, max_tokens = 8, 3, 16
        ctx_len, host_tier_blocks, gap_mean_s = 1920, 256, 0.05
    arch_kwargs = cfg.pop("arch_kwargs")
    bs = cfg["block_size"]
    arch = "decoder_tiny" if smoke else "decoder"
    models = {}
    for arm, extra in (("tier", {"host_tier_blocks":
                                 host_tier_blocks}),
                       ("drop", {})):
        # kfslint: disable=async-blocking — bench setup: two tiny
        # config.json writes before any server exists.
        model_dir = _write_jax_model_dir(arch, arch_kwargs, **cfg,
                                         **extra)
        models[arm] = GenerativeModel(f"kvtier_{arm}", model_dir)
        models[arm].load()
    _reset_timeline()
    server = await _serve(list(models.values()))
    base = f"http://127.0.0.1:{server.http_port}"
    rng = np.random.default_rng(1234)

    # Byte tokenizer, conversation salt LEADING: every conversation's
    # context is its own block-aligned chain (no cross-conversation
    # prefix sharing — each return visit must find ITS OWN state).
    def context(conv):
        head = f"conversation {conv:04d} "
        return (head + "history " * 400)[:ctx_len]

    def prompt(conv, turn):
        return context(conv) + f" turn {turn:03d}"

    try:
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1800)) as s:
            async def one(arm, conv, turn, ttfts):
                body = json.dumps({
                    "text_input": prompt(conv, turn),
                    "max_tokens": max_tokens}).encode()
                await _sse_measure(
                    s, f"{base}/v2/models/kvtier_{arm}"
                       "/generate_stream", body, [], ttfts)

            # Warmup BOTH arms: compile chunk/decode programs, seed
            # every conversation's chains, and (tier arm) compile the
            # spill-gather and fault-back insert programs — the pool
            # starts churning inside this round already.
            for arm in models:
                for conv in range(n_convs):
                    await one(arm, conv, 0, [])
                for conv in range(n_convs):
                    await one(arm, conv, 1, [])

            def tier_stats(arm):
                st = models[arm].engine_stats()
                ht = dict(st.get("host_tier") or {})
                ht["tokens_saved"] = st.get("paged", {}).get(
                    "host_tier_tokens_saved", 0)
                return ht

            rep_records = {a: [] for a in models}
            turn = {a: 2 for a in models}
            for r_i in range(reps):
                order = (list(models) if r_i % 2 == 0
                         else list(reversed(list(models))))
                for arm in order:
                    pre = tier_stats(arm)
                    ttfts: List[float] = []
                    t0 = time.perf_counter()
                    # One full return cycle: by the time a
                    # conversation comes back around, n_convs-1
                    # others have churned the device pool past its
                    # capacity.  Gaps are Poisson (exponential
                    # inter-arrival), the regime the tier targets:
                    # too long for HBM residency, short enough that
                    # re-prefill is pure waste.
                    for conv in range(n_convs):
                        await asyncio.sleep(float(
                            rng.exponential(gap_mean_s)))
                        await one(arm, conv, turn[arm], ttfts)
                    turn[arm] += 1
                    wall = time.perf_counter() - t0
                    post = tier_stats(arm)
                    rep_records[arm].append({
                        "wall_s": round(wall, 3),
                        "ttft_p50_ms": round(float(np.percentile(
                            np.asarray(ttfts), 50)), 2),
                        "ttft_p99_ms": round(float(np.percentile(
                            np.asarray(ttfts), 99)), 2),
                        "tokens_saved": (post["tokens_saved"]
                                         - pre["tokens_saved"]),
                        "faulted_blocks": (
                            post.get("faulted_blocks", 0)
                            - pre.get("faulted_blocks", 0)),
                        "spills": (post.get("spills", 0)
                                   - pre.get("spills", 0)),
                    })
            async with s.get(f"{base}/debug/cache") as r:
                assert r.status == 200, await r.text()
                debug_cache = await r.json()

        out: Dict[str, Any] = {
            "conversations": n_convs, "repetitions": reps,
            "context_tokens": ctx_len, "context_blocks": ctx_len // bs,
            "block_size": bs, "host_tier_blocks": host_tier_blocks,
            "cache_blocks": cfg["cache_blocks"],
            "poisson_gap_mean_ms": gap_mean_s * 1e3,
        }
        for arm in models:
            recs = rep_records[arm]
            out[arm] = {
                **{k: round(float(np.median([r[k] for r in recs])), 2)
                   for k in ("ttft_p50_ms", "ttft_p99_ms",
                             "tokens_saved")},
                "tokens_saved_total": sum(r["tokens_saved"]
                                          for r in recs),
                "faulted_blocks_total": sum(r["faulted_blocks"]
                                            for r in recs),
                "spills_total": sum(r["spills"] for r in recs),
                "reps": recs,
            }
        ht = tier_stats("tier")
        out["host_tier"] = ht
        # The credit ledger's arithmetic bar: every saved token maps
        # to a block somebody physically faulted back (or rode in
        # on), times the block size — nothing invented, nothing lost.
        out["tokens_saved_consistent"] = (
            ht["tokens_saved"] == (ht.get("faulted_blocks", 0)
                                   + ht.get("coalesced_blocks", 0))
            * bs)
        out["drop_arm_saved_nothing"] = \
            out["drop"]["tokens_saved_total"] == 0
        out["ttft_p50_tier_over_drop"] = round(
            out["tier"]["ttft_p50_ms"]
            / max(1e-9, out["drop"]["ttft_p50_ms"]), 3)
        out["debug_cache"] = debug_cache
        out["timeline"] = _timeline_summary()
        out["cache"] = {a: _cache_summary(models[a]) for a in models}
        record = {
            "scenario": "tiered_kv_residency_ab",
            "smoke": smoke,
            **{k: out[k] for k in
               ("conversations", "repetitions", "context_tokens",
                "context_blocks", "block_size", "host_tier_blocks",
                "cache_blocks", "poisson_gap_mean_ms", "tier", "drop",
                "host_tier", "tokens_saved_consistent",
                "drop_arm_saved_nothing", "ttft_p50_tier_over_drop",
                "debug_cache", "cache")},
        }
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        # kfslint: disable=async-blocking — evidence commit after the
        # measured waves; the server is already torn down below.
        with open(os.path.join(root, "BENCH_kvtier.json"), "w") as f:
            # kfslint: disable=async-blocking — same write as above.
            json.dump(record, f, indent=2)
        return out
    finally:
        await server.stop_async()


async def bench_kvhandoff(smoke: bool) -> Dict[str, Any]:
    """Durable KV handoff A/B (ISSUE 19 acceptance): recycle a replica
    mid-conversation and measure the return visit.  Each rep of each
    arm is a full simulated recycle — serve, seed every conversation's
    context, tear the incumbent down, boot a successor, and time the
    conversations' return visits on the fresh process.  The "handoff"
    arm points `host_tier_dir` at a shared persistent directory and
    runs the SIGTERM drain parachute (`engine.export_kv`) before
    teardown, so the successor adopts the predecessor's generation and
    serves the returning conversations as tier fault-backs; the "cold"
    arm keeps the default ephemeral tier, which dies with the process,
    so every return visit is a full re-prefill.  The device pool is
    sized to hold all conversations, so the ONLY delta between arms is
    what survives the recycle.  Arms interleave with order flip,
    median-of-N.  Evidence committed to BENCH_kvhandoff.json:
    return-visit TTFT p50/p99 per arm, re-prefill tokens saved (cold
    arm must be exactly zero), adopted-block counts from the successor
    tier, and the honest export ledger — exported/dropped/failed
    straight from the drain, nothing smoothed over."""
    import shutil

    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel

    if smoke:
        cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 256},
            "max_slots": 2, "max_seq": 256,
            "prefill_buckets": [32, 64, 128, 256],
            "block_size": 32, "cache_blocks": 24,
            "prefill_chunk_tokens": 32,
            "steps_per_call": 2,
        }
        n_convs, reps, max_tokens = 4, 3, 4
        ctx_len, host_tier_blocks = 96, 64
    else:
        cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 4096},
            "max_slots": 4, "max_seq": 4096,
            "prefill_buckets": [512, 2048, 4096],
            "block_size": 128, "cache_blocks": 120,
            "prefill_chunk_tokens": 512,
            "steps_per_call": int(os.environ.get("BENCH_GEN_K", "16")),
        }
        n_convs, reps, max_tokens = 6, 3, 16
        ctx_len, host_tier_blocks = 1920, 256
    arch_kwargs = cfg.pop("arch_kwargs")
    bs = cfg["block_size"]
    arch = "decoder_tiny" if smoke else "decoder"
    export_budget_s = 10.0
    # kfslint: disable=async-blocking — bench setup: one tempdir
    # create before any server exists.
    kv_dir = tempfile.mkdtemp(prefix="bench_kvhandoff_")
    loop = asyncio.get_running_loop()

    # Same leading-salt convention as bench_kvtier: each conversation
    # owns its block-aligned chain, so a return visit must recover ITS
    # state — there is no cross-conversation prefix to hide behind.
    def context(conv):
        head = f"conversation {conv:04d} "
        return (head + "history " * 400)[:ctx_len]

    def prompt(conv, turn):
        return context(conv) + f" turn {turn:03d}"

    async def one(session, base, conv, turn, ttfts):
        body = json.dumps({"text_input": prompt(conv, turn),
                           "max_tokens": max_tokens}).encode()
        await _sse_measure(
            session, f"{base}/v2/models/kvhandoff/generate_stream",
            body, [], ttfts)

    async def incarnation(extra):
        """One replica process stand-in: fresh model + server."""
        # kfslint: disable=async-blocking — bench setup: one tiny
        # config.json write before the incarnation's server exists.
        model_dir = _write_jax_model_dir(
            arch, arch_kwargs, **cfg,
            host_tier_blocks=host_tier_blocks, **extra)
        model = GenerativeModel("kvhandoff", model_dir)
        model.load()
        server = await _serve([model])
        return model, server, f"http://127.0.0.1:{server.http_port}"

    async def run_rep(arm):
        extra = ({"host_tier_dir": kv_dir} if arm == "handoff"
                 else {})
        rec: Dict[str, Any] = {}
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1800)) as s:
            # Incumbent: seed every conversation, then recycle.
            model, server, base = await incarnation(extra)
            try:
                for conv in range(n_convs):
                    await one(s, base, conv, 0, [])
                if arm == "handoff":
                    # The drain parachute, exactly as the SIGTERM
                    # path runs it (off the async loop).
                    eng = model.engine
                    rec["export"] = await loop.run_in_executor(
                        None,
                        lambda: eng.export_kv(export_budget_s))
            finally:
                await server.stop_async()
                await model.close()

            # Successor: adopts the predecessor's generation (handoff
            # arm) or starts empty (cold arm), then serves the return
            # visits.
            model, server, base = await incarnation(extra)
            try:
                ttfts: List[float] = []
                t0 = time.perf_counter()
                for conv in range(n_convs):
                    await one(s, base, conv, 1, ttfts)
                rec["wall_s"] = round(time.perf_counter() - t0, 3)
                st = model.engine.stats()
                ht = dict(st.get("host_tier") or {})
                rec.update({
                    "ttft_p50_ms": round(float(np.percentile(
                        np.asarray(ttfts), 50)), 2),
                    "ttft_p99_ms": round(float(np.percentile(
                        np.asarray(ttfts), 99)), 2),
                    "tokens_saved": st.get("paged", {}).get(
                        "host_tier_tokens_saved", 0),
                    "adopted_blocks": (ht.get("handoff") or {}).get(
                        "adopted", 0),
                    "faulted_blocks": ht.get("faulted_blocks", 0),
                })
            finally:
                await server.stop_async()
                await model.close()
        return rec

    arms = ("handoff", "cold")
    rep_records: Dict[str, List[Dict[str, Any]]] = \
        {a: [] for a in arms}
    _reset_timeline()
    try:
        for r_i in range(reps):
            order = arms if r_i % 2 == 0 else tuple(reversed(arms))
            for arm in order:
                rep_records[arm].append(await run_rep(arm))
            # Wipe the shared tier directory between reps so every
            # rep's adoption starts from exactly one predecessor
            # generation (both incarnations are closed — no flocks).
            # kfslint: disable=async-blocking — between-rep cleanup
            # with every server torn down; nothing is being served.
            shutil.rmtree(kv_dir, ignore_errors=True)
            # kfslint: disable=async-blocking — same window as above.
            os.makedirs(kv_dir, exist_ok=True)

        out: Dict[str, Any] = {
            "conversations": n_convs, "repetitions": reps,
            "context_tokens": ctx_len, "context_blocks": ctx_len // bs,
            "block_size": bs, "host_tier_blocks": host_tier_blocks,
            "cache_blocks": cfg["cache_blocks"],
            "export_budget_s": export_budget_s,
        }
        for arm in arms:
            recs = rep_records[arm]
            out[arm] = {
                **{k: round(float(np.median([r[k] for r in recs])), 2)
                   for k in ("ttft_p50_ms", "ttft_p99_ms",
                             "tokens_saved")},
                "tokens_saved_total": sum(r["tokens_saved"]
                                          for r in recs),
                "adopted_blocks_total": sum(r["adopted_blocks"]
                                            for r in recs),
                "faulted_blocks_total": sum(r["faulted_blocks"]
                                            for r in recs),
                "reps": recs,
            }
        # The honest export ledger: what the drain actually shipped,
        # dropped on deadline, or failed — summed across reps.
        exp = [r.get("export") or {}
               for r in rep_records["handoff"]]
        out["export"] = {k: sum(e.get(k, 0) for e in exp)
                         for k in ("exported", "skipped", "dropped",
                                   "failed")}
        out["cold_arm_saved_nothing"] = \
            out["cold"]["tokens_saved_total"] == 0
        out["ttft_p50_handoff_over_cold"] = round(
            out["handoff"]["ttft_p50_ms"]
            / max(1e-9, out["cold"]["ttft_p50_ms"]), 3)
        out["timeline"] = _timeline_summary()
        record = {
            "scenario": "kv_handoff_recycle_ab",
            "smoke": smoke,
            **{k: out[k] for k in
               ("conversations", "repetitions", "context_tokens",
                "context_blocks", "block_size", "host_tier_blocks",
                "cache_blocks", "export_budget_s", "handoff", "cold",
                "export", "cold_arm_saved_nothing",
                "ttft_p50_handoff_over_cold")},
        }
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        # kfslint: disable=async-blocking — evidence commit after the
        # measured waves; every server is already torn down.
        with open(os.path.join(root, "BENCH_kvhandoff.json"),
                  "w") as f:
            # kfslint: disable=async-blocking — same write as above.
            json.dump(record, f, indent=2)
        return out
    finally:
        # kfslint: disable=async-blocking — final teardown; every
        # server is already stopped.
        shutil.rmtree(kv_dir, ignore_errors=True)


async def bench_specdec(smoke: bool) -> Dict[str, Any]:
    """Speculative decoding A/B (ISSUE 20 acceptance): three identical
    paged decoders on one server — speculation off, n-gram prompt-
    lookup proposer, and a registered draft model — interleaved reps
    with order flip, median-of-N.  The workload is repetitive prompts
    (the regime prompt-lookup targets) decoded greedily; the draft arm
    self-drafts (same architecture + param-cache content key as the
    target, windowed context), the honest upper bound for draft
    agreement on a random-init bench model.  Before the measured reps
    a probe prompt runs on ALL arms and the streamed token ids must
    be identical — speculation is a latency optimization, never a
    sampling change, and the committed record carries the proof.
    Evidence committed to BENCH_specdec.json: per-arm tokens/s and
    TTFT/gap percentiles, acceptance rate and accepted-length p50/p99
    straight from the engine's spec_debug (the same body `kfs cache`
    federates), and draft/verify overhead device-ms per rep."""
    import aiohttp

    from kfserving_tpu.predictors.llm import GenerativeModel

    if smoke:
        cfg = {
            "arch_kwargs": {"num_layers": 2, "hidden_size": 64,
                            "num_heads": 2, "intermediate_size": 128,
                            "max_seq": 256},
            "max_slots": 2, "max_seq": 256,
            "prefill_buckets": [32, 64, 128, 256],
            "block_size": 32, "cache_blocks": 24,
            "prefill_chunk_tokens": 32,
            "steps_per_call": 2,
        }
        n_prompts, reps, max_tokens = 4, 3, 24
        ctx_len, spec_k, draft_window = 96, 3, 32
    else:
        cfg = {
            "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                            "num_layers": 12, "num_heads": 12,
                            "intermediate_size": 3072,
                            "max_seq": 2048},
            "max_slots": 4, "max_seq": 2048,
            "prefill_buckets": [256, 1024, 2048],
            "block_size": 128, "cache_blocks": 96,
            "prefill_chunk_tokens": 256,
            "steps_per_call": int(os.environ.get("BENCH_GEN_K", "16")),
        }
        n_prompts, reps, max_tokens = 6, 3, 64
        ctx_len, spec_k, draft_window = 640, 4, 128
    arch_kwargs = cfg.pop("arch_kwargs")
    arch = "decoder_tiny" if smoke else "decoder"
    arm_extras = {
        "off": {},
        "ngram": {"speculative": {"tokens": spec_k}},
        "draft": {"speculative": {
            "tokens": spec_k,
            "draft": {"architecture": arch,
                      "arch_kwargs": arch_kwargs,
                      "window": draft_window}}},
    }
    models = {}
    for arm, extra in arm_extras.items():
        # kfslint: disable=async-blocking — bench setup: three tiny
        # config.json writes before any server exists.
        model_dir = _write_jax_model_dir(arch, arch_kwargs, **cfg,
                                         **extra)
        models[arm] = GenerativeModel(f"specdec_{arm}", model_dir)
        models[arm].load()
    _reset_timeline()
    server = await _serve(list(models.values()))
    base = f"http://127.0.0.1:{server.http_port}"

    # Repetitive prompts — the structure prompt-lookup exploits.  Each
    # prompt leads with its own salt so arms never share a prefix
    # chain across prompts, only across reps (symmetric per arm).
    def prompt(i):
        head = f"request {i:04d} "
        return (head + "alpha beta gamma delta epsilon " * 40)[
            :ctx_len]

    def spec_stats(arm):
        sp = models[arm].engine_stats().get("speculative")
        return dict(sp) if sp else {}

    try:
        async with aiohttp.ClientSession(
                timeout=aiohttp.ClientTimeout(total=1800)) as s:
            async def one(arm, i, ttfts, gaps):
                """One greedy stream; returns emitted token count
                (data-event count minus the terminal event — the
                same undercount-on-coalesce rule as _sse_measure,
                identical for every arm)."""
                body = json.dumps({
                    "text_input": prompt(i),
                    "max_tokens": max_tokens}).encode()
                t_post = time.perf_counter()
                last = None
                n_events = 0
                url = (f"{base}/v2/models/specdec_{arm}"
                       "/generate_stream")
                async with s.post(url, data=body) as r:
                    assert r.status == 200, await r.text()
                    async for chunk in r.content.iter_any():
                        if b"data: " not in chunk:
                            continue
                        now = time.perf_counter()
                        if last is None:
                            ttfts.append((now - t_post) * 1e3)
                        else:
                            gaps.append((now - last) * 1e3)
                        last = now
                        n_events += chunk.count(b"data: ")
                return max(0, n_events - 1)

            async def probe_ids(arm):
                """Full token-id transcript of the shared probe
                prompt — the cross-arm parity proof."""
                body = json.dumps({"text_input":
                                   "parity probe " + prompt(0),
                                   "max_tokens": max_tokens}).encode()
                buf = b""
                url = (f"{base}/v2/models/specdec_{arm}"
                       "/generate_stream")
                async with s.post(url, data=body) as r:
                    assert r.status == 200, await r.text()
                    async for chunk in r.content.iter_any():
                        buf += chunk
                ids = []
                for line in buf.split(b"\n"):
                    if not line.startswith(b"data: "):
                        continue
                    tok = (json.loads(line[6:]).get("token")
                           or {}).get("id")
                    if tok is not None:
                        ids.append(int(tok))
                return ids

            # Warmup every arm: prefill/chunk/decode programs plus
            # the spec_draft / spec_verify programs on the spec arms.
            for arm in models:
                for i in range(min(2, n_prompts)):
                    await one(arm, i, [], [])

            # Cross-arm parity on one probe prompt: identical greedy
            # token ids or the record says so.
            parity = {arm: await probe_ids(arm) for arm in models}
            parity_ok = (parity["off"] == parity["ngram"]
                         == parity["draft"]
                         and len(parity["off"]) > 0)

            rep_records = {a: [] for a in models}
            for r_i in range(reps):
                order = (list(models) if r_i % 2 == 0
                         else list(reversed(list(models))))
                for arm in order:
                    pre = spec_stats(arm)
                    ttfts: List[float] = []
                    gaps: List[float] = []
                    tokens = 0
                    t0 = time.perf_counter()
                    for i in range(n_prompts):
                        tokens += await one(arm, i, ttfts, gaps)
                    wall = time.perf_counter() - t0
                    post = spec_stats(arm)
                    rec = {
                        "wall_s": round(wall, 3),
                        "tokens": tokens,
                        "tokens_per_s": round(tokens / wall, 2),
                        "ttft_p50_ms": round(float(np.percentile(
                            np.asarray(ttfts), 50)), 2),
                        "ttft_p99_ms": round(float(np.percentile(
                            np.asarray(ttfts), 99)), 2),
                        "gap_p50_ms": round(float(np.percentile(
                            np.asarray(gaps or [0.0]), 50)), 2),
                        "gap_p99_ms": round(float(np.percentile(
                            np.asarray(gaps or [0.0]), 99)), 2),
                    }
                    if post:
                        rec.update({
                            "proposed_tokens": (
                                post.get("proposed_tokens", 0)
                                - pre.get("proposed_tokens", 0)),
                            "accepted_tokens": (
                                post.get("accepted_tokens", 0)
                                - pre.get("accepted_tokens", 0)),
                            "draft_overhead_device_ms": round(
                                (post.get("draft_device_s", 0.0)
                                 - pre.get("draft_device_s", 0.0))
                                * 1e3, 2),
                            "verify_device_ms": round(
                                (post.get("verify_device_s", 0.0)
                                 - pre.get("verify_device_s", 0.0))
                                * 1e3, 2),
                        })
                    rep_records[arm].append(rec)
            async with s.get(f"{base}/debug/cache") as r:
                assert r.status == 200, await r.text()
                debug_cache = await r.json()

        out: Dict[str, Any] = {
            "prompts": n_prompts, "repetitions": reps,
            "context_tokens": ctx_len, "max_tokens": max_tokens,
            "spec_tokens": spec_k, "draft_window": draft_window,
            "parity_all_arms": parity_ok,
            "parity_probe_tokens": len(parity["off"]),
        }
        for arm in models:
            recs = rep_records[arm]
            out[arm] = {
                **{k: round(float(np.median([r[k] for r in recs])),
                            2)
                   for k in ("tokens_per_s", "ttft_p50_ms",
                             "ttft_p99_ms", "gap_p50_ms",
                             "gap_p99_ms")},
                "reps": recs,
            }
            sp = spec_stats(arm)
            if sp:
                # The engine's own acceptance ledger (what `kfs
                # cache` and /debug/cache federate), cumulative over
                # warmup + probe + all reps.
                out[arm]["speculative"] = {
                    k: sp.get(k) for k in (
                        "proposer", "waves", "proposed_tokens",
                        "accepted_tokens", "emitted_tokens",
                        "acceptance_rate", "accepted_length_p50",
                        "accepted_length_p99", "draft_device_s",
                        "verify_device_s", "fallbacks")}
        for arm in ("ngram", "draft"):
            out[f"tokens_per_s_{arm}_over_off"] = round(
                out[arm]["tokens_per_s"]
                / max(1e-9, out["off"]["tokens_per_s"]), 3)
        out["debug_cache"] = debug_cache
        out["timeline"] = _timeline_summary()
        out["cache"] = {a: _cache_summary(models[a]) for a in models}
        record = {
            "scenario": "speculative_decoding_ab",
            "smoke": smoke,
            **{k: out[k] for k in
               ("prompts", "repetitions", "context_tokens",
                "max_tokens", "spec_tokens", "draft_window",
                "parity_all_arms", "parity_probe_tokens",
                "off", "ngram", "draft",
                "tokens_per_s_ngram_over_off",
                "tokens_per_s_draft_over_off", "cache")},
        }
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        # kfslint: disable=async-blocking — evidence commit after the
        # measured waves; the server is torn down below.
        with open(os.path.join(root, "BENCH_specdec.json"), "w") as f:
            # kfslint: disable=async-blocking — same write as above.
            json.dump(record, f, indent=2)
        return out
    finally:
        await server.stop_async()


async def bench_history(smoke: bool) -> Dict[str, Any]:
    """History sampler overhead A/B (ISSUE 17 acceptance): serving
    throughput on the same live server with the ring-TSDB sampler
    ticking vs stopped.

    A sub-0.1% effect cannot be resolved through scheduler/GC noise
    directly, so the bench amplifies it: the on-arm ticks at 20x the
    default rate (tick_s=0.05), the interleaved A/B measures the
    amplified delta, and the committed per-default-tick overhead is
    that delta / 20.  Noise discipline: many short alternating
    segments (drift spans both arms), the lead arm flips every pair
    (second-segment warmth cancels), gc.collect() + gc.disable()
    around each segment (collections land between, not inside,
    segments), identical idle gaps in both arms (the first request
    after an idle pause is ~10x the steady-state cost and must not
    bill to one arm), and the estimator is the median of per-pair
    process-CPU deltas (immune to external CPU contention — client
    and server share this process).  Evidence committed to
    BENCH_history.json, including the deterministic cross-check:
    mean tick wall-time x tick rate."""
    import gc

    import aiohttp

    from kfserving_tpu.model.model import Model
    from kfserving_tpu.observability.registry import REGISTRY

    class _Echo(Model):
        def load(self):
            self.ready = True
            return True

        async def predict(self, request):
            return {"predictions": [1]}

    seg_req = 600 if smoke else 1500  # requests per segment
    pairs = 24                        # alternating on/off segment pairs
    amplification = 20.0              # on-arm tick rate vs default
    tick_s = str(1.0 / amplification)
    prev_tick = os.environ.get("KFS_HISTORY_TICK_S")
    os.environ["KFS_HISTORY_TICK_S"] = tick_s
    try:
        model = _Echo("histbench")
        model.load()
        server = await _serve([model])
    finally:
        if prev_tick is None:
            os.environ.pop("KFS_HISTORY_TICK_S", None)
        else:
            os.environ["KFS_HISTORY_TICK_S"] = prev_tick
    url = (f"http://127.0.0.1:{server.http_port}"
           f"/v1/models/histbench:predict")
    payload = {"instances": [[1.0]]}
    try:
        async with aiohttp.ClientSession() as session:

            async def measure(n: int):
                """(wall seconds, process-CPU seconds) for n
                closed-loop requests, GC parked outside the segment."""
                gc.collect()
                gc.disable()
                try:
                    w0 = time.perf_counter()
                    c0 = time.process_time()
                    for _ in range(n):
                        async with session.post(url,
                                                json=payload) as r:
                            await r.read()
                            assert r.status == 200
                    return (time.perf_counter() - w0,
                            time.process_time() - c0)
                finally:
                    # kfslint: disable=async-blocking — stdlib
                    # gc.enable() (name-collides with the
                    # compile_cache.enable helper); it only flips a
                    # flag, nothing blocks.
                    gc.enable()

            await measure(2 * seg_req)  # warmup, discarded
            arms = {"history_on": [], "history_off": []}
            deltas_cpu, deltas_wall = [], []
            for pair in range(pairs):
                order = (("history_on", "history_off")
                         if pair % 2 == 0
                         else ("history_off", "history_on"))
                seg = {}
                for arm in order:
                    if arm == "history_on":
                        await server.history.start()
                    else:
                        await server.history.stop()
                    await asyncio.sleep(0.06)  # identical in both arms
                    seg[arm] = await measure(seg_req)
                    arms[arm].append(seg_req / seg[arm][0])
                await server.history.stop()
                on, off = seg["history_on"], seg["history_off"]
                deltas_wall.append((on[0] - off[0]) / off[0] * 100.0)
                deltas_cpu.append((on[1] - off[1]) / off[1] * 100.0)
        deltas_cpu.sort()
        deltas_wall.sort()
        stress_pct = deltas_cpu[len(deltas_cpu) // 2]
        overhead_pct = stress_pct / amplification
        med = {arm: sorted(v)[len(v) // 2] for arm, v in arms.items()}
        tick_hist = None
        fam = REGISTRY.family("kfserving_tpu_history_tick_ms")
        if fam is not None:
            for _, child in fam.samples():
                if child.total:
                    mean_ms = child.sum / child.total
                    tick_hist = {
                        "ticks": child.total,
                        "mean_ms": round(mean_ms, 4),
                        # Deterministic cross-check: the fraction of
                        # wall time the tick body consumes at the
                        # DEFAULT 1 s tick.
                        "direct_overhead_pct_at_default_tick": round(
                            mean_ms / 1000.0 * 100.0, 4)}
        out = {
            "scenario": "history_sampler_overhead_ab",
            "smoke": smoke,
            "stress_tick_s": float(tick_s),
            "amplification": amplification,
            "requests_per_segment": seg_req,
            "segment_pairs": pairs,
            "history_on": {
                "median_segment_req_per_s": round(
                    med["history_on"], 1)},
            "history_off": {
                "median_segment_req_per_s": round(
                    med["history_off"], 1)},
            "stress_overhead_pct": round(stress_pct, 3),
            "stress_overhead_wall_pct": round(
                deltas_wall[len(deltas_wall) // 2], 3),
            # The committed headline: the stress delta scaled back to
            # the shipping 1 s tick.
            "overhead_pct": round(overhead_pct, 4),
            "within_budget": overhead_pct < 1.0,
            "live_series": server.history.store.series_count(),
            "tick": tick_hist,
        }
        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        # kfslint: disable=async-blocking — evidence commit after the
        # measured waves; the server is torn down below.
        with open(os.path.join(root, "BENCH_history.json"), "w") as f:
            # kfslint: disable=async-blocking — same write as above.
            json.dump(out, f, indent=2)
        return out
    finally:
        await server.stop_async()
