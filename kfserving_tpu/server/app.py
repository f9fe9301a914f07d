"""ModelServer: the TPU-native model server with the V1/V2 route table.

Route table is a superset of the reference server's
(reference python/kfserving/kfserving/kfserver.py:61-87):

    GET  /                                  liveness ("Alive")
    GET  /v2/health/live                    V2 server live
    GET  /v2/health/ready                   V2 server ready (all models)
    GET  /v2                                V2 server metadata
    GET  /v1/models  /v2/models             list models
    GET  /v1/models/{name}                  model health
    GET  /v2/models/{name}                  V2 model metadata
    GET  /v2/models/{name}/status           model health (reference alias)
    GET  /v2/models/{name}/ready            V2 model ready
    POST /v1/models/{name}:predict          V1 predict
    POST /v2/models/{name}/infer            V2 infer
    POST /v1/models/{name}:explain          V1 explain
    POST /v2/models/{name}/explain          V2 explain
    POST /v2/repository/models/{name}/load  load (model repository ext.)
    POST /v2/repository/models/{name}/unload
    GET  /v2/repository/index               repository index
    GET  /metrics                           Prometheus metrics

Unlike the reference (tornado, forked workers, kfserver.py:89-108) this is a
single-process asyncio server: the TPU chip is owned by one runtime, requests
interleave on the event loop, and parallelism comes from batched XLA
execution rather than process forking.
"""

import argparse
import asyncio
import contextlib
import json
import logging
import os
import signal
import time
from typing import Any, Dict, List, Optional

from kfserving_tpu.model.model import Model
from kfserving_tpu.model.repository import ModelRepository
from kfserving_tpu.protocol import cloudevents, native
from kfserving_tpu.protocol.errors import ServingError
from kfserving_tpu.server.dataplane import DataPlane
from kfserving_tpu.server.http import HTTPServer, Request, Response, Router
from kfserving_tpu.server.metrics import Metrics

logger = logging.getLogger("kfserving_tpu.server")

DEFAULT_HTTP_PORT = 8080

# Same CLI surface as the reference parent parser (kfserver.py:34-43) so
# per-framework __main__ modules inherit it.
parser = argparse.ArgumentParser(add_help=False)
parser.add_argument("--http_port", default=DEFAULT_HTTP_PORT, type=int,
                    help="The HTTP port listened to by the model server.")
parser.add_argument("--workers", default=1, type=int,
                    help="Unused; kept for reference CLI compatibility "
                         "(single process owns the TPU).")
parser.add_argument("--max_latency_ms", default=5.0, type=float,
                    help="Dynamic batcher flush deadline in milliseconds.")
parser.add_argument("--max_batch_size", default=32, type=int,
                    help="Dynamic batcher max batch size.")
parser.add_argument("--container_concurrency", default=0, type=int,
                    help="Max concurrent inference calls per replica "
                         "(0 = unlimited; Knative containerConcurrency).")
parser.add_argument("--grpc_port", default=None, type=int,
                    help="V2 gRPC port (unset = gRPC disabled, 0 = "
                         "ephemeral).")


def _json(data: Any, status: int = 200) -> Response:
    fast = native.dump_response(data)
    if fast is not None:
        return Response(fast, status=status)
    return Response(json.dumps(data, default=_np_default).encode("utf-8"),
                    status=status)


def _python_tracer(body: Dict[str, Any]) -> bool:
    """The capture option both profiler endpoints take: only a JSON
    `"python_tracer": false` switches the Python tracer off."""
    return body.get("python_tracer") is not False


def _np_default(obj):
    tolist = getattr(obj, "tolist", None)
    if tolist is not None:
        return tolist()
    raise TypeError(
        f"Object of type {type(obj).__name__} is not JSON serializable")


@contextlib.contextmanager
def _staged(stages: Dict[str, float], stage: str):
    """Record a stage's wall time (ms) into `stages` for the access
    log — one shared helper instead of per-request timer classes."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        stages[stage] = round((time.perf_counter() - t0) * 1000.0, 3)


def _error(e: ServingError) -> Response:
    return _json({"error": e.reason}, status=e.status_code)


class _AdmissionGate:
    """FIFO concurrency gate with a bounded wait queue.

    Not an asyncio.Semaphore: Semaphore.locked() ignores waiters before
    Python 3.12 and acquire() permits barging, which would let newcomers
    starve queued requests and grow the queue past its bound.  This gate
    hands a finishing request's slot directly to the oldest waiter.
    """

    def __init__(self, limit: int, max_queue: int):
        self.limit = limit
        self.max_queue = max_queue
        self.active = 0
        self.queue = []  # FIFO of futures

    async def enter(self) -> bool:
        """True once a slot is held; False = queue full, reject."""
        if self.active < self.limit and not self.queue:
            self.active += 1
            return True
        if len(self.queue) >= self.max_queue:
            return False
        fut = asyncio.get_running_loop().create_future()
        self.queue.append(fut)
        try:
            await fut
        except asyncio.CancelledError:
            if fut.done() and not fut.cancelled():
                # granted between the cancel and now: pass the slot on
                self.exit()
            else:
                try:
                    self.queue.remove(fut)
                except ValueError:
                    pass
            raise
        return True

    def exit(self) -> None:
        while self.queue:
            fut = self.queue.pop(0)
            if not fut.done():
                fut.set_result(None)  # slot transferred; active unchanged
                return
        self.active -= 1


class ModelServer:
    def __init__(self, http_port: int = DEFAULT_HTTP_PORT,
                 registered_models: Optional[ModelRepository] = None,
                 enable_docs: bool = True,
                 container_concurrency: int = 0,
                 max_queue_depth: Optional[int] = None,
                 grpc_port: Optional[int] = None):
        self.repository = registered_models or ModelRepository()
        self.dataplane = DataPlane(self.repository)
        self.http_port = http_port
        # V2 gRPC front end over the same dataplane (None = disabled;
        # 0 = ephemeral port).
        self.grpc_port = grpc_port
        self.grpc_server = None
        self.metrics = Metrics()
        self.router = Router()
        self._register_routes()
        self.http_server = HTTPServer(self.router)
        self.request_hooks = []  # agent logger taps in here
        # Agent-style background services (logger, watcher, puller): objects
        # with async start()/stop(), run for the server's lifetime.
        self.services = []
        # The online monitoring loop (ISSUE 3): monitor bus tee off the
        # request hooks, SLO burn-rate engine over this server's
        # request series, flight recorder of recent timelines.
        # Construction is cheap (no tasks until start_async); the
        # SLO evaluation loop only runs when objectives are declared.
        from kfserving_tpu.observability.monitoring import Monitoring

        self.monitoring = Monitoring(self)
        self.services.append(self.monitoring)
        # Continuous telemetry history (ISSUE 17): the ring TSDB
        # sampler ticks every registry family — the process-wide
        # REGISTRY plus THIS server's private request registry — into
        # bounded rings, runs the scrape-time publishers so live
        # scrapes and history agree, and feeds the trend detector
        # whose change-points pin into this server's flight recorder.
        # KFS_HISTORY=0 disables the whole subsystem.
        from kfserving_tpu.observability.history import (
            HistorySampler,
            TrendDetector,
            history_enabled,
        )

        self.history: Optional[HistorySampler] = None
        if history_enabled():
            from kfserving_tpu.observability.registry import REGISTRY

            self.history = HistorySampler(
                registries=[self.metrics.registry, REGISTRY],
                fault_hook=self._history_tick_fault,
                publishers=[self.publish_engine_gauges])
            self.history.detector = TrendDetector(
                self.history.store,
                recorder=self.monitoring.flight_recorder)
            self.services.append(self.history)
        # Incident engine (ISSUE 18): the join over every detector —
        # SLO breach edges, trend change-points, sanitizer violations,
        # eviction/fault-back storms, failovers — diagnosed against
        # the additive decomposition with a cross-signal evidence
        # bundle, served at GET /debug/incidents.  Triggers tee off
        # the flight recorder's pin stream and the SLO engine's
        # breach edge; diagnosis runs on a background worker behind
        # the observability.incident_open fault site (injected hook —
        # observability/ never imports reliability/).  KFS_INCIDENTS=0
        # disables the subsystem.
        from kfserving_tpu.observability.incidents import (
            IncidentManager,
            incidents_enabled,
        )

        self.incidents: Optional[IncidentManager] = None
        if incidents_enabled():
            self.incidents = IncidentManager(
                history=(self.history.store
                         if self.history is not None else None),
                recorder=self.monitoring.flight_recorder,
                providers={"cache": self._incident_cache_snapshot},
                fault_hook=self._incident_open_fault)
            self.monitoring.flight_recorder.add_pin_listener(
                self.incidents.on_pin)
            self.monitoring.slo.transition_listeners.append(
                self.incidents.on_slo_transition)
            self.services.append(self.incidents)
        # Per-replica admission control (Knative containerConcurrency,
        # reference component.go:79-82): at most `container_concurrency`
        # inference calls execute at once; up to `max_queue_depth` more
        # wait (the queue-proxy buffer), the rest get 503 so the load
        # balancer retries another replica.  0 = unlimited.
        self.container_concurrency = container_concurrency
        self.max_queue_depth = (
            max_queue_depth if max_queue_depth is not None
            else max(2 * container_concurrency, 8))
        self._admission = (
            _AdmissionGate(container_concurrency, self.max_queue_depth)
            if container_concurrency > 0 else None)
        # Standby: a callable that performs the deferred (device-
        # touching) model load; set via standby_model().
        self._standby_fn = None
        self._standby_state = "none"  # none | armed | activating | done
        # Durable KV handoff (ISSUE 19): single-flight peer pulls —
        # the router's x-kfs-kv-peer retry hint can arrive on many
        # concurrent failover retries at once; one pull per
        # predecessor serves them all (the lock serializes, the set
        # dedups for the process life).
        self._kv_peer_lock = asyncio.Lock()
        self._kv_peers_pulled: set = set()

    def standby_model(self, activate_fn) -> None:
        """Arm standby mode: the server starts with NO model and
        `activate_fn` (blocking; returns the loaded Model) runs on the
        first POST /standby/activate.

        This is the chip-owner recycle fast-path: everything that does
        NOT need the TPU — interpreter start, jax/flax imports, artifact
        download, config parse — happens while the predecessor still
        owns the chip, so the swap gap shrinks to device init + cache-
        hot compile + warmup."""
        self._standby_fn = activate_fn
        self._standby_state = "armed"

    # -- routes ------------------------------------------------------------
    def _register_routes(self):
        r = self.router
        r.add("GET", "/", self._live)
        r.add("GET", "/v2/health/live", self._live)
        r.add("GET", "/v2/health/ready", self._server_ready)
        r.add("GET", "/v2", self._server_metadata)
        r.add("GET", "/v1/models", self._list_models)
        r.add("GET", "/v2/models", self._list_models)
        r.add("GET", "/v1/models/{name}", self._model_health)
        r.add("GET", "/v2/models/{name}/status", self._model_health)
        r.add("GET", "/v2/models/{name}/ready", self._model_ready)
        r.add("GET", "/v2/models/{name}", self._model_metadata)
        r.add("POST", "/v1/models/{name}:predict", self._predict_v1)
        r.add("POST", "/v2/models/{name}/infer", self._infer_v2)
        # Versioned forms (required_api.md:35-56 — the version segment
        # is optional for servers with one live version per name; these
        # accept any version and serve the registered model).
        r.add("GET", "/v2/models/{name}/versions/{version}/ready",
              self._model_ready)
        r.add("GET", "/v2/models/{name}/versions/{version}",
              self._model_metadata)
        r.add("POST", "/v2/models/{name}/versions/{version}/infer",
              self._infer_v2)
        r.add("POST", "/v1/models/{name}:explain", self._explain)
        r.add("POST", "/v2/models/{name}/explain", self._explain)
        # Generative routes (the v2 generate extension; no reference
        # counterpart — its server predates generative serving).  The
        # V1 spelling mirrors :predict; {"stream": true} upgrades to a
        # chunked token stream, as does the dedicated _stream route.
        r.add("POST", "/v1/models/{name}:generate", self._generate)
        r.add("POST", "/v2/models/{name}/generate", self._generate)
        r.add("POST", "/v2/models/{name}/generate_stream",
              self._generate_stream)
        r.add("POST", "/v2/repository/models/{name}/load", self._load)
        r.add("POST", "/v2/repository/models/{name}/unload", self._unload)
        r.add("GET", "/v2/repository/index", self._repository_index)
        r.add("GET", "/metrics", self._metrics)
        # Boot-phase breakdown (VERDICT r4 weak #4): cumulative
        # seconds-since-process-birth marks for interpreter+imports,
        # download, init, compile/warmup, serving — the recycling
        # orchestrator scrapes this to explain successor load time.
        r.add("GET", "/startup_phases", self._startup_phases)
        # Standby activation (recycle fast-swap): a successor process
        # boots with imports/download done but the device untouched;
        # the orchestrator POSTs here once the old chip owner exits.
        r.add("POST", "/standby/activate", self._standby_activate)
        # Durable KV handoff (ISSUE 19): the peer-transfer surface.
        # The chain index, single-chain payload pulls (digest header
        # verified by the receiver), and the re-attach trigger — a
        # bare POST re-scans the persistent tier dir for orphaned
        # predecessor generations; a body naming a peer pulls its
        # resident chains over HTTP instead (the disaggregation
        # substrate ROADMAP item 3 names).
        r.add("GET", "/kv/chains", self._kv_chains)
        r.add("GET", "/kv/chains/{chain}", self._kv_chain_payload)
        r.add("POST", "/kv/reattach", self._kv_reattach)
        # Online monitoring surface (ISSUE 3): SLO health the router
        # federates, and the flight recorder's recent/pinned request
        # timelines.
        r.add("GET", "/v2/health/slo", self._slo_health)
        r.add("GET", "/debug/flightrecorder", self._flightrecorder)
        # Tracing/profiling surface (SURVEY §5.1).
        r.add("GET", "/debug/traces", self._traces)
        r.add("POST", "/debug/profiler/start", self._profiler_start)
        r.add("POST", "/debug/profiler/stop", self._profiler_stop)
        # Device-time observability (ISSUE 6): the engine event
        # timeline as a Chrome-trace/Perfetto download, and a bounded
        # on-demand jax.profiler capture window for TPU-level
        # drill-down (start/sleep/stop in one call — the manual
        # start/stop pair above stays for long captures).
        r.add("GET", "/debug/profile", self._profile)
        r.add("POST", "/debug/profile/capture", self._profile_capture)
        # Cache & cost attribution (ISSUE 13): per-engine prefix-index
        # census + pool/HBM occupancy snapshot, federated by the
        # router under the `replica` label — the feed prefix-affinity
        # routing and the HBM residency manager will read.
        r.add("GET", "/debug/cache", self._cache)
        # Telemetry history (ISSUE 17): the replica's ring-TSDB query
        # surface, federated by the router under the `replica` label
        # with a fleet rollup.
        r.add("GET", "/debug/history", self._history)
        # Incident engine (ISSUE 18): diagnosed incident records —
        # list summaries, ?id= pulls one full record with its
        # evidence bundle, ?state=open filters.  Federated by the
        # router with fleet-level root-cause dedup.
        r.add("GET", "/debug/incidents", self._incidents)

    # -- handlers ----------------------------------------------------------
    async def _live(self, req: Request) -> Response:
        return Response(b"Alive", content_type="text/plain")

    async def _server_ready(self, req: Request) -> Response:
        ready = self.dataplane.server_ready()
        body = {"ready": ready}
        from kfserving_tpu.reliability import sanitizer

        if sanitizer.enabled():
            # Sanitize runs surface their discipline state where the
            # probe already looks: armed sources, violation counts
            # (all zero = the clean bill the smoke gate asserts).
            body["sanitizer"] = sanitizer.status()
        return _json(body, status=200 if ready else 503)

    async def _server_metadata(self, req: Request) -> Response:
        return _json(self.dataplane.server_metadata())

    async def _list_models(self, req: Request) -> Response:
        return _json(self.dataplane.list_models())

    async def _model_health(self, req: Request) -> Response:
        name = req.path_params["name"]
        try:
            model = self.dataplane.model_ready(name)
        except ServingError as e:
            return _error(e)
        return _json({"name": model.name, "ready": model.ready})

    async def _model_ready(self, req: Request) -> Response:
        try:
            self.dataplane.model_ready(req.path_params["name"])
        except ServingError as e:
            return _error(e)
        return Response(b"", status=200)

    async def _model_metadata(self, req: Request) -> Response:
        try:
            return _json(self.dataplane.model_metadata(req.path_params["name"]))
        except ServingError as e:
            return _error(e)

    async def _predict_v1(self, req: Request) -> Response:
        return await self._inference(req, "predict", self.dataplane.infer)

    async def _infer_v2(self, req: Request) -> Response:
        return await self._inference(req, "infer", self.dataplane.infer)

    async def _explain(self, req: Request) -> Response:
        return await self._inference(req, "explain", self.dataplane.explain)

    async def _inference(self, req: Request, verb: str, op) -> Response:
        from kfserving_tpu.reliability import Deadline
        from kfserving_tpu.tracing import (
            REQUEST_ID_HEADER,
            ensure_request_id,
        )

        name = req.path_params["name"]
        rid = ensure_request_id(req.headers)
        # Per-request budget (x-request-timeout-ms): minted here at
        # ingress, carried by contextvar through dataplane, batcher
        # queue, and engine dispatch — each stage sheds the request
        # with 504 the moment the budget is spent instead of wasting
        # device work on an answer nobody is waiting for.
        deadline = Deadline.from_headers(req.headers)
        start = time.perf_counter()
        if self._admission is not None:
            admitted = await self._enter_admission(deadline)
            if admitted is not True:
                status, error = self._shed_reason(admitted)
                latency_ms = (time.perf_counter() - start) * 1000.0
                resp = _json({"error": error}, status=status)
                self.metrics.observe_request(name, verb, status,
                                             latency_ms,
                                             trace_id=rid)
                # A shed is exactly what the flight recorder exists to
                # keep evidence of (504 pins as deadline_shed).
                self.monitoring.record_request(name, verb, status,
                                               latency_ms,
                                               trace_id=rid)
                # Shed requests still reach the hooks: the payload logger
                # must not go blind exactly during overload.
                for hook in self.request_hooks:
                    try:
                        hook(name, verb, req, resp, latency_ms)
                    except Exception:
                        logger.exception("request hook failed")
                resp.headers[REQUEST_ID_HEADER] = rid
                return resp
            try:
                resp = await self._inference_inner(
                    req, verb, op, name, start, deadline,
                    trace_id=rid)
            finally:
                self._admission.exit()
        else:
            resp = await self._inference_inner(req, verb, op, name,
                                               start, deadline,
                                               trace_id=rid)
        resp.headers[REQUEST_ID_HEADER] = rid
        return resp

    @staticmethod
    def _shed_reason(admitted: Optional[bool]):
        """Status + message for a failed admission.  False: queue full
        (503, the load balancer retries elsewhere).  None: the budget
        died while queued — 504 without ever holding a slot, so an
        engine batch slot is never consumed for it."""
        if admitted is False:
            return 503, "concurrency limit exceeded"
        return 504, "request deadline exceeded (admission queue)"

    async def _enter_admission(self, deadline) -> Optional[bool]:
        """Admission with a budget-bounded queue wait: True = slot
        held, False = queue full (503), None = deadline expired while
        queued (504).  wait_for's cancellation is safe against the
        grant race: _AdmissionGate.enter() hands an already-granted
        slot to the next waiter when cancelled."""
        if deadline is None:
            return await self._admission.enter()
        remaining = deadline.remaining_s()
        if remaining <= 0:
            return None
        try:
            return await asyncio.wait_for(self._admission.enter(),
                                          timeout=remaining)
        except asyncio.TimeoutError:
            return None

    async def _inference_inner(self, req: Request, verb: str, op,
                               name: str, start: float,
                               deadline=None,
                               trace_id: Optional[str] = None
                               ) -> Response:
        from kfserving_tpu.observability.accesslog import log_access
        from kfserving_tpu.reliability import deadline_scope
        from kfserving_tpu.tracing import tracer

        status = 200
        stages: Dict[str, float] = {}
        tokens_out = None
        try:
            if deadline is not None and deadline.expired:
                # Budget spent waiting for the admission slot: 504
                # without touching decode or the engine (the slot is
                # released by the caller's finally).
                from kfserving_tpu.reliability import DeadlineExceeded

                raise DeadlineExceeded("admission queue")
            with deadline_scope(deadline):
                with tracer.span("server.decode", model=name,
                                 verb=verb), _staged(stages, "decode"):
                    body = self.dataplane.decode_body(
                        req.headers, req.body,
                        dtype_hint=self.dataplane.wire_dtype_hint(name))
                with tracer.span("server.infer", model=name,
                                 verb=verb), _staged(stages, "infer"):
                    response = await op(name, body)
                with tracer.span("server.encode", model=name,
                                 verb=verb), _staged(stages, "encode"):
                    resp = self._encode_response(req, body, response)
                if isinstance(response, dict):
                    tokens_out = response.get("details", {}).get(
                        "token_count") if isinstance(
                            response.get("details"), dict) else None
        except ServingError as e:
            status = e.status_code
            resp = _error(e)
        except Exception as e:
            logger.exception("%s failed for model %s", verb, name)
            status = 500
            resp = _json({"error": str(e)}, status=500)
        latency_ms = (time.perf_counter() - start) * 1000.0
        self.metrics.observe_request(name, verb, status, latency_ms,
                                     trace_id=trace_id)
        # Flight-recorder capture AFTER the stage spans completed (the
        # tracer ring already holds this trace's batcher/engine spans)
        # and BEFORE the hooks, so a slow hook can't delay pin
        # evaluation past the next request.
        self.monitoring.record_request(name, verb, status, latency_ms,
                                       trace_id=trace_id,
                                       stages=stages or None)
        from kfserving_tpu.observability import attribution

        log_access("server", trace_id=trace_id, model=name, verb=verb,
                   status=status, latency_ms=round(latency_ms, 3),
                   stages=stages or None, tokens_out=tokens_out,
                   cost=attribution.lookup(trace_id))
        for hook in self.request_hooks:
            try:
                hook(name, verb, req, resp, latency_ms)
            except Exception:
                logger.exception("request hook failed")
        return resp

    def _encode_response(self, req: Request, body: Any, response: Any
                         ) -> Response:
        """Echo CloudEvents framing when the request was a CloudEvent
        (reference handlers/http.py:96-109); binary-extension responses
        when the V2 request asked for binary_data_output."""
        if isinstance(body, cloudevents.CloudEvent):
            event = cloudevents.CloudEvent(body.attributes, response)
            if cloudevents.is_structured(req.headers):
                headers, payload = cloudevents.to_structured(event)
            else:
                headers, payload = cloudevents.to_binary(event)
            return Response(payload, headers=headers)
        from kfserving_tpu.protocol.v2 import (
            InferRequest,
            encode_binary_response,
        )

        if (isinstance(body, InferRequest)
                and body.parameters.get("binary_data_output")
                and isinstance(response, dict)
                and response.get("outputs")):
            payload, hlen = encode_binary_response(response)
            return Response(
                payload,
                headers={
                    "content-type": "application/octet-stream",
                    "inference-header-content-length": str(hlen)})
        return _json(response)

    async def _generate(self, req: Request) -> Response:
        # Failover fetch hint (ISSUE 19): warm the tier from the
        # predecessor before dispatch — one single-flight pull per
        # peer; the set probe makes the steady-state cost zero.
        await self._maybe_peer_import(req.headers,
                                      req.path_params["name"])
        # Cheap pre-scan avoids a duplicate json.loads on the hot
        # non-streaming path (_inference decodes the body itself).
        if b'"stream"' in req.body:
            try:
                body = json.loads(req.body) if req.body else {}
            except ValueError:
                return _json({"error": "malformed JSON body"},
                             status=400)
            if isinstance(body, dict) and body.get("stream"):
                return await self._generate_stream(req, body=body)
        return await self._inference(req, "generate",
                                     self.dataplane.generate)

    async def _generate_stream(self, req: Request,
                               body: Any = None) -> Response:
        from kfserving_tpu.server.http import StreamingResponse
        from kfserving_tpu.tracing import (
            REQUEST_ID_HEADER,
            ensure_request_id,
        )

        name = req.path_params["name"]
        await self._maybe_peer_import(req.headers, name)
        rid = ensure_request_id(req.headers)
        # Budget applies to submission AND rides into the engine
        # request (captured at submit): a stream whose budget expires
        # mid-generation finishes with reason "timeout" instead of
        # holding its decode slot to the token budget.
        from kfserving_tpu.reliability import Deadline, deadline_scope

        deadline = Deadline.from_headers(req.headers)
        if body is None:
            try:
                body = json.loads(req.body) if req.body else {}
            except ValueError:
                return _json({"error": "malformed JSON body"},
                             status=400)
        # Streams go through the SAME admission gate as every other
        # inference verb — they are the longest-lived, slot-holding
        # requests in the system, exactly what containerConcurrency
        # exists to bound.  The slot is held until the stream ends
        # (released in sse()'s finally, since the body outlives this
        # handler).
        gated = False
        if self._admission is not None:
            admitted = await self._enter_admission(deadline)
            if admitted is not True:
                status, error = self._shed_reason(admitted)
                resp = _json({"error": error}, status=status)
                self.metrics.observe_request(name, "generate_stream",
                                             status, 0.0,
                                             trace_id=rid)
                resp.headers[REQUEST_ID_HEADER] = rid
                return resp
            gated = True
        try:
            with deadline_scope(deadline):
                events = await self.dataplane.generate_stream(name, body)
        except ServingError as e:
            if gated:
                self._admission.exit()
            resp = _error(e)
            resp.headers[REQUEST_ID_HEADER] = rid
            return resp
        except Exception:
            if gated:
                self._admission.exit()
            raise
        start = time.perf_counter()
        metrics, hooks = self.metrics, self.request_hooks
        admission = self._admission if gated else None
        state = {"status": 200}

        async def sse():
            try:
                async for event in events:
                    payload = json.dumps(event, default=_np_default)
                    yield f"data: {payload}\n\n".encode("utf-8")
            except Exception:
                logger.exception("generate stream for %s failed", name)
                state["status"] = 500
                raise

        async def on_close():
            # Runs exactly once on every exit path — including a
            # client that disconnected before the body was ever
            # iterated (a plain generator's finally never runs there,
            # which used to leak the containerConcurrency slot per
            # disconnect until the server wedged at all-503).
            if admission is not None:
                admission.exit()
            # Propagate the close to the model's event stream so the
            # engine frees the decode slot on abandonment.
            from kfserving_tpu.streams import aclose_quietly

            await aclose_quietly(events, "model event stream")
            latency_ms = (time.perf_counter() - start) * 1000.0
            metrics.observe_request(name, "generate_stream",
                                    state["status"], latency_ms,
                                    trace_id=rid)
            # Streams are flight-recorded at close: their generator
            # span (tokens, finish reason) exists only once the
            # stream ends.
            self.monitoring.record_request(name, "generate_stream",
                                           state["status"],
                                           latency_ms, trace_id=rid)
            from kfserving_tpu.observability import attribution
            from kfserving_tpu.observability.accesslog import (
                log_access,
            )

            # The stream's cost record exists by now: the engine
            # finalizes it at the terminal event, and on_close runs
            # after the event stream ended (or was abandoned — the
            # cancel path finalizes too).
            log_access("server", trace_id=rid, model=name,
                       verb="generate_stream",
                       status=state["status"],
                       latency_ms=round(latency_ms, 3),
                       cost=attribution.lookup(rid))
            # Hooks get a minimal response carrying the stream's REAL
            # outcome: a mid-stream failure must not reach the payload
            # logger / monitor bus stamped as a 200.  The body is
            # empty — the token stream was never buffered.
            stream_resp = Response(b"", status=state["status"])
            for hook in hooks:
                try:
                    hook(name, "generate_stream", req, stream_resp,
                         latency_ms)
                except Exception:
                    logger.exception("request hook failed")

        from kfserving_tpu.streams import GuardedStream

        return StreamingResponse(GuardedStream(sse(), on_close),
                                 headers={REQUEST_ID_HEADER: rid})

    async def _standby_activate(self, req: Request) -> Response:
        from kfserving_tpu import startup

        if self._standby_fn is None:
            return _json({"error": "server is not in standby mode"},
                         status=409)
        if self._standby_state == "done":
            return _json({"activated": True, "already": True})
        if self._standby_state == "activating":
            return _json({"error": "activation already in progress"},
                         status=409)
        self._standby_state = "activating"
        t0 = time.perf_counter()
        try:
            model = await asyncio.get_running_loop().run_in_executor(
                None, self._standby_fn)
            self.register_model(model)
            self._standby_state = "done"
        except Exception as e:
            self._standby_state = "armed"  # retryable
            logger.exception("standby activation failed")
            return _json({"error": f"activation failed: {e}"},
                         status=500)
        # kfslint: disable=async-blocking — mark()'s /proc read is
        # RAM-backed and runs once per process (birth time cached).
        startup.mark("standby_activate")
        # The orchestrator's swap breakdown attaches this: how long
        # the device-touching half took, and whether params came off
        # the mmap cache ("mmap") or paid full materialization.
        return _json({
            "activated": True, "model": model.name,
            "activate_s": round(time.perf_counter() - t0, 3),
            "param_source": getattr(model, "param_source", None),
            "phases": startup.phases(),
        })

    # -- durable KV handoff (ISSUE 19) -------------------------------------
    def _kv_tier_models(self, name: Optional[str] = None):
        """(model, engine, tier) triples for every registered model
        with a host KV tier (optionally filtered by model name)."""
        out = []
        for model in self.repository.get_models():
            if name is not None and model.name != name:
                continue
            engine = getattr(model, "engine", None)
            tier = getattr(engine, "kv_tier", None)
            if tier is not None:
                out.append((model, engine, tier))
        return out

    async def _kv_chains(self, req: Request) -> Response:
        """Peer-transfer index: every host-tier-resident chain digest
        per model, with the block geometry a puller needs to validate
        compatibility before moving payload bytes."""
        name = req.query.get("model")
        models: Dict[str, Any] = {}
        for model, _engine, tier in self._kv_tier_models(name):
            models[model.name] = {
                "block_bytes": tier.block_bytes,
                "chains": tier.chains(),
            }
        return _json({"models": models})

    async def _kv_chain_payload(self, req: Request) -> Response:
        """One chain's block payload, streamed to a pulling peer.
        The digest header lets the receiver verify the bytes before
        admission — a corrupted transfer is discarded there, never
        served."""
        from kfserving_tpu.engine.kv_tier import payload_digest

        chain_hex = req.path_params["chain"]
        try:
            chain = bytes.fromhex(chain_hex)
        except ValueError:
            return _json({"error": "chain must be a hex digest"},
                         status=400)
        name = req.query.get("model")
        loop = asyncio.get_running_loop()
        for model, _engine, tier in self._kv_tier_models(name):
            try:
                # Off-loop: the read copies one block's bytes out of
                # the tier mmap under its lock.
                payload = await loop.run_in_executor(
                    None, tier.read, chain)
            except KeyError:
                continue
            return Response(
                payload,
                headers={
                    "content-type": "application/octet-stream",
                    "x-kfs-kv-digest": payload_digest(payload),
                    "x-kfs-kv-block-bytes": str(tier.block_bytes),
                    "x-kfs-kv-model": model.name,
                })
        return _json({"error": f"chain {chain_hex} is not resident"},
                     status=404)

    async def _kv_reattach(self, req: Request) -> Response:
        """Re-attach conversation KV after a process boundary.  A
        bare POST re-scans the persistent tier dir and adopts any
        orphaned predecessor generation (digest-verified, per-entry);
        a body naming a `peer` base URL pulls that replica's resident
        chains over /kv/chains instead — the crash-failover path,
        where the predecessor's host died but a surviving replica
        still holds the conversation's blocks."""
        body: Dict[str, Any] = {}
        if req.body:
            try:
                parsed = json.loads(req.body)
                if isinstance(parsed, dict):
                    body = parsed
            except ValueError:
                return _json({"error": "malformed JSON body"},
                             status=400)
        peer = body.get("peer")
        name = body.get("model")
        try:
            budget_s = float(body.get(
                "budget_s",
                os.environ.get("KFS_KV_PEER_BUDGET_S", "2")))
        except (TypeError, ValueError):
            budget_s = 2.0
        if peer:
            results = await self._kv_pull_peer(
                str(peer).rstrip("/"), budget_s, name=name)
            return _json({"peer": peer, "models": results})
        loop = asyncio.get_running_loop()
        results = {}
        for model, _engine, tier in self._kv_tier_models(name):
            try:
                results[model.name] = await loop.run_in_executor(
                    None, tier.reattach)
            except Exception as e:
                logger.exception("kv reattach for %s failed",
                                 model.name)
                results[model.name] = {"error": str(e)}
        if results:
            self.monitoring.flight_recorder.record(
                {"kind": "kv_handoff_reattach", "models": results},
                pin="kv_handoff_reattach")
        return _json({"models": results})

    async def _kv_pull_peer(self, peer: str, budget_s: float,
                            name: Optional[str] = None
                            ) -> Dict[str, Any]:
        """Pull a peer's resident chains into the local tier:
        index fetch, per-chain payload pulls digest-verified on
        receipt, then one transactional engine.kv_import per model.
        Bounded by `budget_s` — a slow peer costs the returning
        conversation a re-prefill, never a stalled request."""
        from kfserving_tpu.observability import metrics as obs
        from kfserving_tpu.engine.kv_tier import payload_digest

        import aiohttp

        loop = asyncio.get_running_loop()
        deadline = loop.time() + max(0.1, budget_s)
        results: Dict[str, Any] = {}
        timeout = aiohttp.ClientTimeout(total=max(0.1, budget_s))
        try:
            async with aiohttp.ClientSession(timeout=timeout) as s:
                async with s.get(f"{peer}/kv/chains") as resp:
                    if resp.status != 200:
                        return {"error": f"peer index {resp.status}"}
                    index = await resp.json()
                for mname, info in (index.get("models")
                                    or {}).items():
                    if name is not None and mname != name:
                        continue
                    triples = self._kv_tier_models(mname)
                    if not triples:
                        continue
                    _model, engine, tier = triples[0]
                    if info.get("block_bytes") != tier.block_bytes:
                        results[mname] = {
                            "error": "block geometry mismatch"}
                        continue
                    pairs = []
                    mismatches = 0
                    failed = 0
                    for ch_hex in info.get("chains") or []:
                        if loop.time() >= deadline:
                            break
                        try:
                            chain = bytes.fromhex(ch_hex)
                        except ValueError:
                            continue
                        if tier.contains(chain):
                            continue
                        try:
                            async with s.get(
                                    f"{peer}/kv/chains/{ch_hex}",
                                    params={"model": mname}) as r:
                                if r.status != 200:
                                    failed += 1
                                    continue
                                payload = await r.read()
                                want = r.headers.get(
                                    "x-kfs-kv-digest")
                        except (aiohttp.ClientError,
                                asyncio.TimeoutError):
                            failed += 1
                            continue
                        if len(payload) != tier.block_bytes or (
                                want and payload_digest(payload)
                                != want):
                            # Wire corruption: discard, never admit.
                            mismatches += 1
                            continue
                        pairs.append((chain, payload))
                    res = dict(await loop.run_in_executor(
                        None, engine.kv_import, pairs))
                    if mismatches:
                        res["digest_mismatch"] = mismatches
                        obs.kv_handoff_peer_blocks_total().labels(
                            model=mname,
                            outcome="digest_mismatch").inc(
                                mismatches)
                    if failed:
                        res["failed"] = res.get("failed", 0) + failed
                        obs.kv_handoff_peer_blocks_total().labels(
                            model=mname, outcome="failed").inc(
                                failed)
                    results[mname] = res
        except (aiohttp.ClientError, asyncio.TimeoutError,
                OSError) as e:
            results.setdefault("error", f"peer pull failed: {e!r}")
        if results:
            self.monitoring.flight_recorder.record(
                {"kind": "kv_handoff_peer_pull", "peer": peer,
                 "models": {k: v for k, v in results.items()
                            if isinstance(v, dict)}},
                pin="kv_handoff_peer_pull")
        return results

    async def _maybe_peer_import(self, headers: Dict[str, str],
                                 name: str) -> None:
        """Honor the router's failover fetch hint: an x-kfs-kv-peer
        header names the predecessor replica this request was retried
        away from.  One bounded single-flight pull per peer warms the
        local tier before dispatch; any failure degrades to a plain
        re-prefill — the request itself never fails on the hint."""
        peer = None
        for k, v in headers.items():
            if k.lower() == "x-kfs-kv-peer":
                peer = v.strip()
                break
        if not peer:
            return
        peer = peer.rstrip("/")
        if peer in self._kv_peers_pulled:
            return
        if not self._kv_tier_models(name):
            return
        async with self._kv_peer_lock:
            if peer in self._kv_peers_pulled:
                return
            self._kv_peers_pulled.add(peer)
            try:
                budget = float(os.environ.get(
                    "KFS_KV_PEER_BUDGET_S", "2"))
            except ValueError:
                budget = 2.0
            if budget <= 0:
                return
            try:
                await self._kv_pull_peer(peer, budget, name=name)
            except Exception:
                logger.exception("kv peer pull from %s failed", peer)

    async def export_kv(self, budget_s: Optional[float] = None
                        ) -> Dict[str, Any]:
        """Drain parachute: export every engine's live-slot and hot
        prefix-chain KV into its PERSISTENT host tier (ephemeral
        tiers die with the process — exporting into one would be
        theater).  Runs on the SIGTERM drain path between drain()
        and stop_async(), bounded by KFS_KV_EXPORT_BUDGET_S so it
        can never stretch the orchestrator's swap window; 0
        disables."""
        if budget_s is None:
            try:
                budget_s = float(os.environ.get(
                    "KFS_KV_EXPORT_BUDGET_S", "2"))
            except ValueError:
                budget_s = 2.0
        results: Dict[str, Any] = {}
        if budget_s <= 0:
            return results
        loop = asyncio.get_running_loop()
        for model, engine, tier in self._kv_tier_models():
            fn = getattr(engine, "export_kv", None)
            if fn is None or not getattr(tier, "persistent", False):
                continue
            try:
                res = await loop.run_in_executor(None, fn, budget_s)
            except Exception:
                logger.exception("kv export for %s failed",
                                 model.name)
                continue
            results[model.name] = res
        if results:
            self.monitoring.flight_recorder.record(
                {"kind": "kv_handoff_export", "budget_s": budget_s,
                 "models": results},
                pin="kv_handoff_export")
        return results

    async def _load(self, req: Request) -> Response:
        name = req.path_params["name"]
        try:
            await self.dataplane.load(name)
        except ServingError as e:
            return _error(e)
        return _json({"name": name, "load": True})

    async def _unload(self, req: Request) -> Response:
        name = req.path_params["name"]
        try:
            await self.dataplane.unload(name)
        except ServingError as e:
            return _error(e)
        return _json({"name": name, "unload": True})

    async def _repository_index(self, req: Request) -> Response:
        return _json(self.dataplane.repository_index())

    async def _startup_phases(self, req: Request) -> Response:
        from kfserving_tpu import startup

        return _json(startup.phases())

    def publish_engine_gauges(self) -> None:
        """Refresh every scrape-time-published family (roofline MFU /
        padding / goodput / HBM bandwidth, pool occupancy and
        fragmentation ratios, generic per-key engine gauges) from the
        engines' stats dicts.  Runs at every `/metrics` scrape AND on
        the history sampler's tick — before ISSUE 17 these families
        were invisible between scrapes, so history and a live scrape
        could disagree about the same series."""
        from kfserving_tpu.observability.profiling import roofline

        for model in self.repository.get_models():
            engine_stats = getattr(model, "engine_stats", None)
            if engine_stats is None:
                continue
            try:
                stats = engine_stats()
                # Roofline families (MFU, padding-waste, goodput, HBM
                # bandwidth) publish into the process registry, where
                # the router federates them under a `replica` label;
                # consumed keys skip the generic per-key export below
                # so the merged exposition declares each family
                # exactly once.  The cache publisher adds the paged
                # pool's occupancy/fragmentation `_ratio` gauges
                # (ISSUE 13) without consuming the legacy
                # `kfserving_tpu_engine_paged{bucket=...}` export.
                consumed = roofline.publish_gauges(model.name, stats)
                from kfserving_tpu.observability import attribution

                consumed |= attribution.publish_cache_gauges(
                    model.name, stats)
                for key, value in stats.items():
                    if key in consumed:
                        continue
                    if isinstance(value, dict):
                        # Per-bucket stats (bucket_hits/..._pad_waste)
                        # export as labeled series.
                        for bucket, v in value.items():
                            if isinstance(v, (int, float)):
                                self.metrics.set_gauge(
                                    f"kfserving_tpu_engine_{key}",
                                    float(v),
                                    labels={"model": model.name,
                                            "bucket": str(bucket)})
                        continue
                    if isinstance(value, (int, float)):
                        self.metrics.set_gauge(
                            f"kfserving_tpu_engine_{key}", float(value),
                            labels={"model": model.name})
            except Exception:
                logger.exception("engine stats for %s failed", model.name)

    async def _metrics(self, req: Request) -> Response:
        # Engine gauges (device/host breakdown, MFU) refresh at scrape.
        self.publish_engine_gauges()
        # Content negotiation: exemplars are only legal under the
        # OpenMetrics content type; the classic text parser would
        # reject the suffix and drop the whole scrape.
        want_om = "application/openmetrics-text" in \
            req.headers.get("accept", "")
        body = self.metrics.render(exemplars=want_om)
        if want_om:
            body += "# EOF\n"
            ctype = ("application/openmetrics-text; version=1.0.0; "
                     "charset=utf-8")
        else:
            ctype = "text/plain; version=0.0.4"
        return Response(body.encode("utf-8"), content_type=ctype)

    async def _slo_health(self, req: Request) -> Response:
        """The SLO engine's last evaluation.  ?refresh=1 forces a
        fresh tick (tests / on-demand checks); the body always
        answers 200 — a breach is a *reported* state, not an endpoint
        failure (the router must still federate it)."""
        if req.query.get("refresh") == "1":
            return _json(self.monitoring.slo.tick())
        return _json(self.monitoring.slo.report())

    async def _flightrecorder(self, req: Request) -> Response:
        try:
            limit = int(req.query.get("limit", "100"))
        except ValueError:
            return _json({"error": "limit must be an integer"},
                         status=400)
        pinned_only = req.query.get("pinned", "0") == "1"
        # Pin-stream filters (ISSUE 18): ?pin_type= prefix-matches the
        # pin reason (trend / slo_ / sanitizer_...), ?since_ts= keeps
        # entries stamped at or after the wall-clock time — incident
        # bundles and humans pull just the detector evidence instead
        # of the whole ring.
        pin_type = req.query.get("pin_type") or None
        since_raw = req.query.get("since_ts")
        try:
            since_ts = float(since_raw) if since_raw else None
        except ValueError:
            return _json({"error": "since_ts must be a number"},
                         status=400)
        return _json(self.monitoring.dump_flightrecorder(
            limit=limit, pinned_only=pinned_only, pin_type=pin_type,
            since_ts=since_ts))

    async def _traces(self, req: Request) -> Response:
        from kfserving_tpu.tracing import tracer

        trace_id = req.query.get("trace_id")
        try:
            limit = int(req.query.get("limit", "100"))
        except ValueError:
            return _json({"error": "limit must be an integer"},
                         status=400)
        return _json({"spans": tracer.spans(trace_id, limit)})

    async def _profile(self, req: Request) -> Response:
        """The engine event timeline (decode waves, prefill chunks,
        preemptions, HOLD windows, device dispatch spans) rendered as
        Chrome-trace JSON — loadable directly in Perfetto.
        ?window_s= trims to the trailing window; ?format=events
        returns the raw event dicts instead."""
        from kfserving_tpu.observability.profiling import (
            TIMELINE,
            to_chrome_trace,
        )

        window = req.query.get("window_s")
        try:
            window_s = float(window) if window else None
        except ValueError:
            return _json({"error": "window_s must be a number"},
                         status=400)
        fmt = req.query.get("format", "trace_json")
        if fmt not in ("trace_json", "events"):
            return _json(
                {"error": "format must be trace_json or events"},
                status=400)
        events = TIMELINE.snapshot(window_s)
        if fmt == "events":
            return _json({
                "events": [TIMELINE.event_dict(e) for e in events],
                "recorded": TIMELINE.recorded,
            })
        return _json(to_chrome_trace(events))

    async def _profile_capture(self, req: Request) -> Response:
        """Bounded on-demand jax.profiler capture: start a TPU-level
        trace, hold it for duration_s (clamped to 60 s), stop, return
        the log dir.  409 while another capture (or a manual
        /debug/profiler/start) is active."""
        from kfserving_tpu.tracing import profiler

        try:
            body = json.loads(req.body) if req.body else {}
        except ValueError:
            body = {}
        try:
            duration_s = float(body.get("duration_s", 2.0))
        except (TypeError, ValueError):
            return _json({"error": "duration_s must be a number"},
                         status=400)
        duration_s = max(0.1, min(duration_s, 60.0))
        log_dir = body.get("log_dir", "/tmp/kfs-profile")
        try:
            started = profiler.start(
                log_dir, python_tracer=_python_tracer(body))
        except Exception as e:
            return _json({"error": f"profiler start failed: {e}"},
                         status=500)
        if not started:
            return _json({"error": "profiler already active",
                          "log_dir": profiler.active_dir}, status=409)
        try:
            await asyncio.sleep(duration_s)
        finally:
            profiler.stop()
        return _json({"captured": True, "log_dir": log_dir,
                      "duration_s": duration_s})

    async def _cache(self, req: Request) -> Response:
        """Replica cache snapshot: per generative model the prefix-
        index entry count, reuse-depth distribution, top-K hot chains
        by hit count, and the pool occupancy stats; plus the HBM
        accountant's residency ledger when one is wired.  ?top_k=
        bounds the hot-chain list (default 10); ?top_cost=K appends
        the attribution ring's top-K cost records (by device-ms and
        by held blocks — `kfs cache --top-cost`)."""
        try:
            top_k = int(req.query.get("top_k", "10"))
            top_cost = int(req.query.get("top_cost", "0"))
        except ValueError:
            return _json({"error": "top_k and top_cost must be "
                                   "integers"}, status=400)
        body = self.cache_snapshot(top_k=top_k)
        if top_cost > 0:
            from kfserving_tpu.observability import attribution

            window_raw = req.query.get("cost_window_s")
            try:
                window_s = float(window_raw) if window_raw else None
            except ValueError:
                return _json({"error": "cost_window_s must be a "
                                       "number"}, status=400)
            body["top_cost"] = {
                "by_device_ms": attribution.top(
                    top_cost, window_s=window_s, by="device_ms"),
                "by_held_blocks": attribution.top(
                    top_cost, window_s=window_s, by="held_blocks"),
            }
        return _json(body)

    def cache_snapshot(self, top_k: int = 10) -> Dict[str, Any]:
        """The /debug/cache body as a plain dict — shared by the
        handler and the incident engine's evidence provider (the
        bundle embeds exactly what the debug endpoint would have
        shown at open time)."""
        models: Dict[str, Any] = {}
        hbm = None
        residency = None
        host_tier: Dict[str, Any] = {}
        seen_managers = set()
        res_manager = getattr(self.repository, "residency", None)
        if res_manager is not None:
            try:
                # Demand-paged residency snapshot (states, fault-in
                # p50/p99, eviction/skip totals) beside the HBM ledger
                # it acts on — one scrape answers "who is resident,
                # how fast do faults land, is anything thrashing".
                residency = res_manager.debug()
            except Exception:
                logger.exception("residency debug failed")
        for model in self.repository.get_models():
            debug = getattr(getattr(model, "engine", None),
                            "cache_debug", None)
            if debug is not None:
                try:
                    models[model.name] = debug(top_k=top_k)
                except Exception:
                    logger.exception("cache debug for %s failed",
                                     model.name)
            tier = getattr(getattr(model, "engine", None),
                           "kv_tier", None)
            if tier is not None:
                try:
                    # Host KV tier beside the device pool it backs:
                    # occupancy, spill/fault-back outcomes, fault-back
                    # latency p50/p99 (ISSUE 16).
                    host_tier[model.name] = tier.debug()
                except Exception:
                    logger.exception("kv tier debug for %s failed",
                                     model.name)
            manager = getattr(model, "hbm", None)
            if manager is not None and id(manager) not in seen_managers:
                seen_managers.add(id(manager))
                try:
                    # One manager per device in practice; a second one
                    # (multi-mesh) appends its ledger.
                    snap = manager.debug()
                    if hbm is None:
                        hbm = snap
                    else:
                        hbm["resident"] += snap["resident"]
                        hbm["used_bytes"] += snap["used_bytes"]
                except Exception:
                    logger.exception("hbm debug failed")
        return {"models": models, "hbm": hbm,
                "residency": residency,
                "host_tier": host_tier or None}

    def _incident_cache_snapshot(self) -> Dict[str, Any]:
        """Evidence-bundle provider: the cache/residency/HBM state at
        incident-open time (bounded hot-chain census)."""
        return self.cache_snapshot(top_k=5)

    async def _incidents(self, req: Request) -> Response:
        """Diagnosed incident records (ISSUE 18).  `?id=` returns one
        full record, evidence bundle and ranked hypotheses included;
        the bare list returns newest-first summaries (`?state=open`
        filters, `?limit=` bounds).  Incidents off (KFS_INCIDENTS=0)
        answers 200 with `enabled: false` — the router must still
        federate the replica."""
        if self.incidents is None:
            return _json({"enabled": False, "open": 0,
                          "incidents": []})
        incident_id = req.query.get("id")
        if incident_id:
            record = self.incidents.get(incident_id)
            if record is None:
                return _json(
                    {"error": f"unknown incident {incident_id}"},
                    status=404)
            return _json(record)
        try:
            limit = int(req.query.get("limit", "50"))
        except ValueError:
            return _json({"error": "limit must be an integer"},
                         status=400)
        state = req.query.get("state") or None
        return _json(self.incidents.report(state=state, limit=limit))

    async def _incident_open_fault(self) -> None:
        """The incident worker's chaos seam: probes the
        `observability.incident_open` fault site before each queued
        trigger is diagnosed.  Lives HERE (not in observability/) so
        the incidents package never imports the reliability layer —
        the hook is injected at construction."""
        from kfserving_tpu.reliability import fault_sites
        from kfserving_tpu.reliability.faults import faults

        await faults.inject(fault_sites.OBSERVABILITY_INCIDENT_OPEN)

    async def _history_tick_fault(self) -> None:
        """The history sampler's chaos seam: probes the
        `observability.history_tick` fault site before every tick.
        Lives HERE (not in observability/) so the history package
        never imports the reliability layer — the hook is injected
        at construction."""
        from kfserving_tpu.reliability import fault_sites
        from kfserving_tpu.reliability.faults import faults

        await faults.inject(fault_sites.OBSERVABILITY_HISTORY_TICK)

    async def _history(self, req: Request) -> Response:
        """Replica telemetry history: aligned (ts, value) frames from
        the in-process ring TSDB.  `?series=` selects one family
        (omitted = every live series), `?labels=k=v,k2=v2` filters by
        label subset, `?window_s=` bounds the lookback (default
        600 s), `?step_s=` resamples onto an absolute epoch grid so
        the router can merge replicas by timestamp.  `?index=1`
        returns the series catalog instead of frames.  History off
        (KFS_HISTORY=0) answers 200 with `enabled: false` — the
        router must still federate the replica."""
        if self.history is None:
            return _json({"enabled": False, "series": []})
        if req.query.get("index") == "1":
            return _json({"enabled": True,
                          "tick_s": self.history.tick_s,
                          "tiers": self.history.store.tiers,
                          "series": self.history.store.index()})
        series = req.query.get("series") or None
        labels: Dict[str, str] = {}
        for pair in (req.query.get("labels") or "").split(","):
            if not pair:
                continue
            if "=" not in pair:
                return _json(
                    {"error": "labels must be k=v[,k2=v2...]"},
                    status=400)
            k, v = pair.split("=", 1)
            labels[k] = v
        try:
            window_s = float(req.query.get("window_s", "600"))
            step_raw = req.query.get("step_s")
            step_s = float(step_raw) if step_raw else None
        except ValueError:
            return _json(
                {"error": "window_s and step_s must be numbers"},
                status=400)
        if window_s <= 0 or (step_s is not None and step_s <= 0):
            return _json(
                {"error": "window_s and step_s must be positive"},
                status=400)
        return _json({
            "enabled": True,
            "tick_s": self.history.tick_s,
            "ticks": self.history.ticks,
            "series": self.history.store.query(
                series=series, labels=labels or None,
                window_s=window_s, step_s=step_s),
        })

    async def _profiler_start(self, req: Request) -> Response:
        from kfserving_tpu.tracing import profiler

        try:
            body = json.loads(req.body) if req.body else {}
        except ValueError:
            body = {}
        log_dir = body.get("log_dir", "/tmp/kfs-profile")
        if not profiler.start(log_dir,
                              python_tracer=_python_tracer(body)):
            return _json({"error": "profiler already active",
                          "log_dir": profiler.active_dir}, status=409)
        return _json({"profiling": True, "log_dir": log_dir})

    async def _profiler_stop(self, req: Request) -> Response:
        from kfserving_tpu.tracing import profiler

        log_dir = profiler.stop()
        if log_dir is None:
            return _json({"error": "profiler not active"}, status=409)
        return _json({"profiling": False, "log_dir": log_dir})

    # -- lifecycle ---------------------------------------------------------
    def register_model(self, model: Model) -> None:
        if not model.name:
            raise ValueError(
                "Failed to register model, model.name must be provided.")
        self.repository.update(model)
        logger.info("Registering model: %s", model.name)

    async def start_async(self, models: List[Model],
                          host: str = "0.0.0.0") -> None:
        for model in models:
            self.register_model(model)
        for service in self.services:
            await service.start()
        # Residency-managed repositories pin eviction storms into THIS
        # server's flight recorder (thrash evidence beside the request
        # evidence, federated at /debug/flightrecorder).
        residency = getattr(self.repository, "residency", None)
        if residency is not None:
            residency.attach_flight_recorder(
                self.monitoring.flight_recorder)
        # Host KV tiers pin fault-back storms the same way (the device
        # pool churning conversations through the tier faster than
        # they finish is thrash evidence an operator needs pinned).
        for model in self.repository.get_models():
            tier = getattr(getattr(model, "engine", None),
                           "kv_tier", None)
            if tier is not None:
                tier.attach_flight_recorder(
                    self.monitoring.flight_recorder)
        # A pause the process heartbeat names (`process paused:`) is
        # pinned here, by the first server of the process to ask.
        from kfserving_tpu.observability.profiling import HEARTBEAT

        if HEARTBEAT.recorder is None:
            HEARTBEAT.recorder = self.monitoring.flight_recorder
        # Device-discipline sanitizer (KFS_SANITIZE=1): violations
        # pin into this server's flight recorder, and the stall
        # watchdog heartbeats the serving loop.  Disabled: two env
        # reads, nothing armed.  Ownership matters: the watchdog is
        # process-global, so only the server that started it stops
        # it — a second in-process server must not tear down the
        # first one's on ITS stop.
        from kfserving_tpu.reliability import sanitizer

        self._owns_sanitizer_watchdog = False
        if sanitizer.enabled():
            self._owns_sanitizer_watchdog = (
                sanitizer.start_watchdog(
                    asyncio.get_running_loop()) is not None)
            if self._owns_sanitizer_watchdog:
                # Only the owning server wires the process-global
                # recorder attachment and armed gauge — a second
                # in-process server must not steal the first one's
                # pinned-violation feed or flip its telemetry.
                sanitizer.attach_flight_recorder(
                    self.monitoring.flight_recorder)
                from kfserving_tpu.observability import metrics as obs

                obs.sanitizer_armed().set(1)
        await self.http_server.start(host, self.http_port)
        self.http_port = self.http_server.port
        if self.grpc_port is not None:
            from kfserving_tpu.server.grpc_server import GRPCServer

            self.grpc_server = GRPCServer(
                self.dataplane, port=self.grpc_port, host=host,
                metrics=self.metrics, monitoring=self.monitoring)
            await self.grpc_server.start()
            self.grpc_port = self.grpc_server.port
        from kfserving_tpu import startup

        # kfslint: disable=async-blocking — mark()'s /proc read is
        # RAM-backed and runs once per process (birth time cached).
        startup.mark("serving")

    async def drain(self, budget_s: float) -> bool:
        """Wait for in-flight work — including live token streams,
        the longest-lived requests in the system — to finish, up to
        `budget_s`.  Returns True when fully drained.  Past the
        budget, stop_async() closes the engines, which delivers a
        terminal error event to every still-open stream (clients see
        a clean end-of-stream, not a dead socket) — the recycle
        contract for generative replicas."""
        loop = asyncio.get_running_loop()
        deadline = loop.time() + budget_s
        while loop.time() < deadline:
            busy = (self._admission is not None
                    and self._admission.active > 0)
            if not busy:
                for m in self.repository.get_models():
                    gauges = getattr(getattr(m, "engine", None),
                                     "load_gauges", None)
                    if gauges is None:
                        continue
                    g = gauges()
                    if g["active_slots"] + g["pending"] > 0:
                        busy = True
                        break
            if not busy:
                return True
            await asyncio.sleep(0.1)
        return False

    async def stop_async(self) -> None:
        from kfserving_tpu.observability.profiling import HEARTBEAT
        from kfserving_tpu.reliability import sanitizer

        if HEARTBEAT.recorder is self.monitoring.flight_recorder:
            HEARTBEAT.recorder = None
        if getattr(self, "_owns_sanitizer_watchdog", False):
            sanitizer.stop_watchdog()
            # Detach our recorder too: a stopped server's buffer has
            # no /debug surface left, and the global reference would
            # pin this server's object graph for the process life.
            sanitizer.attach_flight_recorder(None)
            self._owns_sanitizer_watchdog = False
            from kfserving_tpu.observability import metrics as obs

            obs.sanitizer_armed().set(0)
        if self.grpc_server is not None:
            await self.grpc_server.stop()
            self.grpc_server = None
        for model in self.repository.get_models():
            close = getattr(model, "close", None)
            if close is not None:
                await close()
        residency = getattr(self.repository, "residency", None)
        if residency is not None:
            residency.close()
        for service in reversed(self.services):
            await service.stop()
        await self.http_server.stop()

    def start(self, models: List[Model]) -> None:
        """Blocking entrypoint, reference kfserver.py:89-108 equivalent."""
        async def _main():
            await self.start_async(models)
            stop = asyncio.Event()
            loop = asyncio.get_running_loop()
            for sig in (signal.SIGINT, signal.SIGTERM):
                try:
                    loop.add_signal_handler(sig, stop.set)
                except NotImplementedError:
                    pass
            await stop.wait()
            # SIGTERM drain: let in-flight work (streams included)
            # finish inside the orchestrator's kill grace before the
            # engines close.  Default stays UNDER the orchestrator's
            # TERM_GRACE_S (10 s SIGKILL escalation): past this budget
            # streams get the engines' terminal error event, not the
            # SIGKILL dead socket.
            grace = float(os.environ.get("KFS_DRAIN_GRACE_S", "8"))
            if grace > 0:
                await self.drain(grace)
            # Drain parachute (ISSUE 19): whatever conversation KV is
            # still device-resident — live slots included — exports
            # into the persistent host tier before the engines close,
            # so the successor serves returning users via warm
            # fault-backs instead of full re-prefills.  Bounded by
            # KFS_KV_EXPORT_BUDGET_S; a no-op without a persistent
            # tier dir.
            await self.export_kv()
            await self.stop_async()

        logging.basicConfig(level=logging.INFO)
        asyncio.run(_main())
