"""Device-time observability: engine timeline, trace export, roofline.

The profiling layer turns ROADMAP item 1's "re-derive the arithmetic
at real step times" from a one-off offline exercise into something the
running server exposes continuously:

- `heartbeat`    — the process's one heartbeat thread: how late the
                   serving loop and the interpreter ran, each
                   collection's pause, one `process paused:` line a
                   pause of 250 ms or more, and the engines' stall
                   watch called from a thread that is not the loop's;
- `timeline`     — the bounded, allocation-light engine event ring
                   (decode waves, prefill chunks, preemptions,
                   growth-HOLD windows, compile-cache misses, device
                   dispatch spans, pool occupancy), trace-id
                   correlated with the PR-2 spans;
- `trace_export` — Chrome-trace/Perfetto rendering of the ring
                   (`GET /debug/profile?window_s=&format=trace_json`),
                   fleet merge for the router's federated view, and
                   the bench-side HOLD / suppressed-wave summary;
- `roofline`     — promotion of the engines' FLOP / bucket-waste /
                   bandwidth accounting into registry gauges
                   (`kfserving_tpu_engine_mfu`, padding-waste and
                   goodput ratios, decode HBM-bandwidth utilization),
                   federated through the router like all PR-2 series.

Import discipline (observability package contract): nothing from
`server/`, `control/`, `engine/`, or `reliability/` — the engines
record *into* this layer, never the reverse.
"""

from kfserving_tpu.observability.profiling.heartbeat import HEARTBEAT
from kfserving_tpu.observability.profiling.timeline import (
    TIMELINE,
    EngineTimeline,
)
from kfserving_tpu.observability.profiling.trace_export import (
    merge_traces,
    summarize,
    to_chrome_trace,
)

__all__ = ["HEARTBEAT", "TIMELINE", "EngineTimeline", "to_chrome_trace",
           "merge_traces", "summarize"]
