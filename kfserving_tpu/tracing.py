"""Request tracing: span IDs through router -> server -> engine.

The reference delegates distributed tracing to the Istio/Knative mesh
(queue-proxy emits request traces, reference test/benchmark/
README.md:5-12); the TPU build is sidecar-free, so SURVEY §5.1 calls
for its own spans plus `jax.profiler` hooks around compile/execute.

Design: a process-wide ring buffer of completed spans plus a
contextvar carrying the current request id.  The request id enters at
the ingress router (or is minted at the server) via the
``x-request-id`` header, rides the contextvar through the asyncio
handler and — via ``contextvars.copy_context`` — into the engine's
worker threads, so engine sub-spans (prepare/transfer/compute/fetch)
attach to the request that caused them.  Spans are queryable at
``GET /debug/traces`` and logged at DEBUG.

The `jax.profiler` toggle (``POST /debug/profiler/start|stop``) wraps
``jax.profiler.start_trace`` for on-demand XLA-level traces.
"""

import contextlib
import contextvars
import logging
import re
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger("kfserving_tpu.tracing")

REQUEST_ID_HEADER = "x-request-id"
# W3C Trace Context (https://www.w3.org/TR/trace-context/): the
# cross-hop carrier.  `traceparent` wins over x-request-id when both
# arrive; x-request-id stays the echo/correlation header for clients
# that never adopted W3C.
TRACEPARENT_HEADER = "traceparent"

_HEX32 = re.compile(r"^[0-9a-f]{32}$")
_HEX16 = re.compile(r"^[0-9a-f]{16}$")

# Current request id; propagated into engine worker threads by running
# the executor callable under contextvars.copy_context().
current_request_id: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("kfs_request_id", default=None)


def mint_trace_id() -> str:
    return uuid.uuid4().hex


def mint_span_id() -> str:
    return uuid.uuid4().hex[:16]


def parse_traceparent(value: str) -> Optional[Tuple[str, str]]:
    """(trace_id, parent_span_id) from a `traceparent` header, or None
    when malformed (all-zero ids are invalid per spec)."""
    parts = value.strip().lower().split("-")
    if len(parts) < 4:
        return None
    _version, trace_id, span_id = parts[0], parts[1], parts[2]
    if not _HEX32.match(trace_id) or not _HEX16.match(span_id):
        return None
    if trace_id == "0" * 32 or span_id == "0" * 16:
        return None
    return trace_id, span_id


def format_traceparent(trace_id: str, span_id: str) -> str:
    return f"00-{trace_id}-{span_id}-01"


@dataclass
class TraceContext:
    """One hop's view of the request's trace: the shared trace id, the
    upstream hop's span id (None at the trace root), and this hop's
    own span id (forwarded downstream as the parent)."""

    trace_id: str
    parent_span_id: Optional[str] = None
    span_id: str = field(default_factory=mint_span_id)

    def forward_traceparent(self) -> Optional[str]:
        """The `traceparent` value to send downstream, or None when
        the trace id is not W3C-shaped (a client-supplied
        x-request-id keeps carrying context on its own header — never
        rewrite the id the client correlates by)."""
        if not _HEX32.match(self.trace_id):
            return None
        return format_traceparent(self.trace_id, self.span_id)


def ensure_trace_context(headers: Dict[str, str],
                         mint: str = "short") -> TraceContext:
    """Join (or start) the request's trace and set the contextvar.

    Precedence: a valid `traceparent` wins (its 32-hex trace id
    becomes THE id on every layer's spans); else `x-request-id` (any
    string — legacy correlation); else a fresh id is minted.
    ``mint="w3c"`` mints a full 32-hex id (the ingress router, which
    must emit a valid traceparent); ``"short"`` keeps the seed's
    16-hex x-request-id shape (replica-local minting)."""
    tp = headers.get(TRACEPARENT_HEADER)
    if tp:
        parsed = parse_traceparent(tp)
        if parsed is not None:
            ctx = TraceContext(parsed[0], parent_span_id=parsed[1])
            current_request_id.set(ctx.trace_id)
            return ctx
    rid = headers.get(REQUEST_ID_HEADER)
    if not rid:
        rid = mint_trace_id() if mint == "w3c" else uuid.uuid4().hex[:16]
    ctx = TraceContext(rid)
    current_request_id.set(ctx.trace_id)
    return ctx


@dataclass
class Span:
    trace_id: str
    name: str
    start: float          # time.time() epoch seconds
    duration_ms: float
    attrs: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "name": self.name,
                "start": self.start, "duration_ms": self.duration_ms,
                "attrs": self.attrs}


class Tracer:
    """Process-wide completed-span ring buffer (bounded, lock-guarded)."""

    def __init__(self, capacity: int = 512):
        self._spans: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self.enabled = True

    def record(self, span: Span) -> None:
        if not self.enabled:
            return
        with self._lock:
            self._spans.append(span)
        logger.debug("span %s %s %.2fms %s", span.trace_id, span.name,
                     span.duration_ms, span.attrs)

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        """Time a block; attaches to the current request id (or 'untraced').
        Yields a dict the block may add attributes to."""
        trace_id = current_request_id.get() or "untraced"
        start_wall = time.time()
        start = time.perf_counter()
        span_attrs: Dict[str, Any] = dict(attrs)
        try:
            yield span_attrs
        finally:
            self.record(Span(trace_id, name, start_wall,
                             (time.perf_counter() - start) * 1000.0,
                             span_attrs))

    def spans(self, trace_id: Optional[str] = None,
              limit: int = 100) -> List[Dict[str, Any]]:
        with self._lock:
            items = list(self._spans)
        if trace_id is not None:
            items = [s for s in items if s.trace_id == trace_id]
        return [s.to_dict() for s in items[-limit:]]

    def clear(self) -> None:
        with self._lock:
            self._spans.clear()


# The process tracer (one serving process = one trace sink).
tracer = Tracer()


def ensure_request_id(headers: Dict[str, str]) -> str:
    """Read (or mint) the request id for an incoming request and set
    the contextvar.  Returns the id so responses can echo it.  Joins a
    W3C trace when the request carries one (ensure_trace_context)."""
    return ensure_trace_context(headers).trace_id


class ProfilerControl:
    """On-demand jax.profiler trace capture (SURVEY §5.1).  Both
    debug endpoints (`/debug/profiler/start|stop`, the bounded
    `/debug/profile/capture`) go through this one object and share
    its options.

    While a capture is active the engine timeline's spans are also
    written into the profiler's trace: `start` hands
    `jax.profiler.TraceAnnotation` to `TIMELINE.annotate` and `stop`
    takes it away, so outside a capture the engines call nothing of
    `jax.profiler`.

    Both run on the caller's thread, for the debug endpoints the
    serving loop's, and on a chip `stop_trace` writes the capture for
    11-93 s with the loop held (the heartbeat's `process paused:` line
    names it, `what="loop"`, frames ending in `stop_trace`).  An
    executor thread does not mend that while the Python tracer is on:
    measured on the v5e (PERF.md §6, PR 56), a stop beside a serving
    loop shares the interpreter lock with it, opens with 2-3 s in
    which no thread runs, records on past the moment it was asked to
    end, and in `gpt2-large.chat` took over 120 s where the held loop
    gave it 84."""

    def __init__(self):
        self._active_dir: Optional[str] = None
        self._lock = threading.Lock()

    @property
    def active_dir(self) -> Optional[str]:
        return self._active_dir

    def start(self, log_dir: str, python_tracer: bool = True) -> bool:
        """`python_tracer=False` switches the profiler's Python
        tracer off (one event per Python call: most of a trace's
        events and of its cost to the host); engine spans, XLA's own
        host events and the device planes stay."""
        import jax

        from kfserving_tpu.observability.profiling import TIMELINE

        with self._lock:
            if self._active_dir is not None:
                return False
            if python_tracer:
                jax.profiler.start_trace(log_dir)
            else:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0
                jax.profiler.start_trace(log_dir,
                                         profiler_options=options)
            TIMELINE.annotate = jax.profiler.TraceAnnotation
            self._active_dir = log_dir
            logger.info("jax.profiler trace -> %s", log_dir)
            return True

    def stop(self) -> Optional[str]:
        import jax

        from kfserving_tpu.observability.profiling import TIMELINE

        with self._lock:
            if self._active_dir is None:
                return None
            TIMELINE.annotate = None
            jax.profiler.stop_trace()
            out, self._active_dir = self._active_dir, None
            logger.info("jax.profiler trace stopped (%s)", out)
            return out


profiler = ProfilerControl()
