"""Protocol-agnostic data-plane operations over a ModelRepository.

This is the glue between HTTP handlers and models, the analogue of the
reference's handler bodies (reference python/kfserving/kfserving/handlers/
http.py:53-112 and kfserver.py:118-196), factored so gRPC or in-process
callers reuse the same path.
"""

import json
from typing import Any, Dict, List

from kfserving_tpu import __version__ as SERVER_VERSION
from kfserving_tpu.model.model import Model
from kfserving_tpu.model.repository import ModelRepository, maybe_await
from kfserving_tpu.protocol import cloudevents, native, v1, v2
from kfserving_tpu.protocol.errors import (
    InvalidInput,
    ModelNotFound,
    ModelNotReady,
)
from kfserving_tpu.protocol.v2 import InferRequest
from kfserving_tpu.reliability.deadline import (
    check_deadline,
    deadline_scope,
)
from kfserving_tpu.reliability import fault_sites
from kfserving_tpu.reliability.faults import faults
from kfserving_tpu.tracing import tracer

SERVER_NAME = "kfserving-tpu"


class DataPlane:
    def __init__(self, repository: ModelRepository):
        self.repository = repository

    # -- health / metadata -------------------------------------------------
    def live(self) -> bool:
        return True

    def server_ready(self) -> bool:
        """V2 "server ready": all registered models ready (required_api.md)."""
        return all(m.ready for m in self.repository.get_models())

    def model_ready(self, name: str) -> Model:
        model = self.repository.get_model(name)
        if model is None:
            raise ModelNotFound(name)
        if not model.ready:
            raise ModelNotReady(name)
        return model

    def list_models(self) -> List[str]:
        return [m.name for m in self.repository.get_models()]

    def server_metadata(self) -> Dict[str, Any]:
        from kfserving_tpu import startup

        meta = {
            "name": SERVER_NAME,
            "version": SERVER_VERSION,
            "extensions": ["model_repository"],
        }
        device = startup.device()
        if device is not None:
            meta["device"] = device
        return meta

    def model_metadata(self, name: str) -> Dict[str, Any]:
        model = self.repository.get_model(name)
        if model is None:
            raise ModelNotFound(name)
        return model.metadata()

    # -- inference ---------------------------------------------------------
    async def get_model(self, name: str) -> Model:
        """Fetch a model, lazily loading on first use like the reference
        (handlers/http.py:32-41).

        The load runs OUTSIDE the request's deadline scope: a lazy
        load (download + compile grid, multi-second) is shared state
        benefiting every future request, so one short-budget client
        must not abort it mid-warmup — that would discard the compile
        work and make each budgeted request restart the same doomed
        load.  The triggering request's own budget is still enforced
        by the caller's check right after this returns."""
        model = self.repository.get_model(name)
        if model is None:
            raise ModelNotFound(name)
        if not model.ready:
            with deadline_scope(None):
                await maybe_await(model.load())
        return model

    def wire_dtype_hint(self, name: str) -> Any:
        """The served model's preferred wire dtype (e.g. "u1" for uint8
        image models), handed to the native parser so integer bodies
        land in the model's dtype directly."""
        model = self.repository.get_model(name)
        return getattr(model, "wire_dtype", None)

    def decode_body(self, headers: Dict[str, str], body: bytes,
                    dtype_hint: Any = None) -> Any:
        """Decode a request body: CloudEvent (binary or structured) or JSON.

        Dense numeric V1 bodies take the native tensorjson fast path
        (protocol/native.py): one C pass straight into a contiguous
        array — uint8 when `dtype_hint` says the model takes uint8 and
        the values fit, else int32/float32.  Everything else
        (CloudEvents, V2 tensor objects, dict instances, strings)
        decodes as before.
        """
        if cloudevents.has_ce_headers(headers) or cloudevents.is_structured(headers):
            try:
                return cloudevents.from_http(headers, body)
            except ValueError as e:
                raise InvalidInput(f"Cloud Event Exceptions: {e}")
        header_len = headers.get(v2.INFERENCE_HEADER_CONTENT_LENGTH)
        if header_len is not None:
            # V2 binary data extension: JSON header + raw tensor bytes.
            try:
                return InferRequest.from_binary(body, int(header_len))
            except ValueError as e:
                raise InvalidInput(str(e))
        if body[:1] == b"{" and b'"datatype"' not in body:
            fast = native.parse_v1(body, hint=dtype_hint)
            if fast is not None:
                arr, key = fast
                return {key: arr}
        try:
            return json.loads(body) if body else {}
        except ValueError as e:
            raise InvalidInput(f"Unrecognized request format: {e}")

    async def infer(self, name: str, body: Any) -> Any:
        # Stage-boundary deadline checks (InferLine discipline): a
        # request already over budget after a lazy model load or a
        # slow preprocess fails 504 HERE, before the model/batcher
        # spends a slot on it.
        model = await self.get_model(name)
        # Chaos hook (site `dataplane.infer`, `match` selects models):
        # injected latency/errors land INSIDE the request's measured
        # path, so the SLO engine, flight recorder, and monitors see
        # exactly what a real model-side slowdown would produce —
        # the knob tests/test_monitoring.py drives the alert loop
        # with.  configured() keeps the no-faults hot path at one
        # dict lookup.
        if faults.configured(fault_sites.DATAPLANE_INFER):
            await faults.inject(fault_sites.DATAPLANE_INFER, key=name)
        check_deadline("dataplane.infer")
        with tracer.span("dataplane.preprocess", model=name):
            request = await model.preprocess(body)
        request = self.validate(request)
        check_deadline("dataplane.infer preprocess")
        with tracer.span("dataplane.predict", model=name):
            response = await maybe_await(model.predict(request))
        with tracer.span("dataplane.postprocess", model=name):
            return await model.postprocess(response)

    async def explain(self, name: str, body: Any) -> Any:
        model = await self.get_model(name)
        check_deadline("dataplane.explain")
        request = await model.preprocess(body)
        request = self.validate(request)
        check_deadline("dataplane.explain preprocess")
        response = await maybe_await(model.explain(request))
        return await model.postprocess(response)

    async def generate(self, name: str, body: Any) -> Any:
        model = await self.get_model(name)
        check_deadline("dataplane.generate")
        generate = getattr(model, "generate", None)
        if generate is None:
            raise InvalidInput(
                f"model {name} does not support :generate")
        return await maybe_await(generate(body))

    async def generate_stream(self, name: str, body: Any):
        model = await self.get_model(name)
        stream = getattr(model, "generate_stream", None)
        if stream is None:
            raise InvalidInput(
                f"model {name} does not support streaming generation")
        # Awaiting runs validation + submission NOW: a bad request is a
        # 4xx before any streaming headers are committed.
        return await maybe_await(stream(body))

    def validate(self, request: Any) -> Any:
        if isinstance(request, dict) and "inputs" in request and isinstance(
                request.get("inputs"), list) and request["inputs"] and isinstance(
                request["inputs"][0], dict) and "datatype" in request["inputs"][0]:
            # Looks like a V2 tensor request; structural validation happens
            # in InferRequest.from_dict on the engine side.
            return request
        if isinstance(request, dict):
            return v1.validate_request(request)
        return request

    # -- repository --------------------------------------------------------
    async def load(self, name: str) -> None:
        try:
            ok = await self.repository.load(name)
        except Exception as e:
            raise ModelNotReady(name, f"Error type: {type(e)} error msg: {e}")
        if not ok or not self.repository.is_model_ready(name):
            raise ModelNotReady(name)

    async def unload(self, name: str) -> None:
        try:
            await self.repository.unload(name)
        except KeyError:
            raise ModelNotFound(name)

    def repository_index(self) -> List[Dict[str, Any]]:
        """V2 repository index extension (Triton-style)."""
        return [
            {"name": m.name, "state": "READY" if m.ready else "UNAVAILABLE"}
            for m in self.repository.get_models()
        ]
