"""Chrome-trace / Perfetto export of the engine event timeline.

Renders `EngineTimeline` events as the Chrome Trace Event JSON format
(the `{"traceEvents": [...]}` object form) — loadable directly in
Perfetto (ui.perfetto.dev) or chrome://tracing:

- one *process* per replica (pid; the router's federated view re-pids
  each replica's trace and names the process after the replica host);
- *threads* are the timeline tracks: host (tid 1), device (tid 2),
  the generator's launching thread (tid 3) and fetch workers (tid 4),
  and one per engine slot (tid 10+slot) so concurrent streams render
  as parallel lanes;
- complete events (`ph: "X"`, microsecond ts/dur) for spans, instant
  events (`ph: "i"`) for zero-duration markers (preemptions,
  suppressed waves, compile-cache misses), counter events (`ph: "C"`)
  for pool-occupancy samples;
- every event's `args` carries its trace id (when the event belongs
  to a request), so a Perfetto search on the id from `/debug/traces`
  or a flight-recorder pin lands on the exact wave/chunk slices that
  served it.

`summarize()` reduces the same events the trace renders to total
growth-HOLD time, the suppressed-wave ratio and slice counts, so a
summary and the Perfetto view can never disagree.  It says nothing about device idle time: the
"device" track's slices are host clocks around dispatch→fetch, and
the gaps between them are not the device's (that is read from the
profiler's trace, where the engine's spans share the device's
clock).
"""

from typing import Any, Dict, List, Tuple

from kfserving_tpu.observability.profiling.timeline import (
    COUNTER,
    DEVICE,
    FETCH,
    HOST,
    LAUNCH,
    SLOT,
    Event,
)

_TID_HOST = 1
_TID_SLOT_BASE = 10
_TRACK_TIDS = {HOST: _TID_HOST, DEVICE: 2, LAUNCH: 3, FETCH: 4}
_TID_NAMES = {tid: track for track, tid in _TRACK_TIDS.items()}


def _tid(track: str, slot: int) -> int:
    if track == SLOT and slot >= 0:
        return _TID_SLOT_BASE + slot
    return _TRACK_TIDS.get(track, _TID_HOST)


def to_chrome_trace(events: List[Event], pid: int = 1,
                    process_name: str = "kfserving-tpu"
                    ) -> Dict[str, Any]:
    """Render timeline events as a Chrome Trace Event JSON object."""
    out: List[Dict[str, Any]] = [{
        "ph": "M", "name": "process_name", "pid": pid, "tid": 0,
        "args": {"name": process_name},
    }]
    tids_seen: Dict[int, str] = {}
    for start, dur, track, name, trace_id, slot, attrs in events:
        ts_us = start * 1e6
        if track == COUNTER:
            # Counter samples: numeric attrs become stacked series.
            vals = {k: v for k, v in (attrs or {}).items()
                    if isinstance(v, (int, float))}
            if vals:
                out.append({"ph": "C", "name": name, "pid": pid,
                            "tid": _TID_HOST, "ts": ts_us,
                            "args": vals})
            continue
        tid = _tid(track, slot)
        if tid not in tids_seen:
            tids_seen[tid] = _TID_NAMES.get(
                tid, f"slot {tid - _TID_SLOT_BASE}")
        args: Dict[str, Any] = dict(attrs) if attrs else {}
        if trace_id is not None:
            args["trace_id"] = trace_id
        if slot >= 0:
            args.setdefault("slot", slot)
        event: Dict[str, Any] = {
            "name": name, "cat": track, "pid": pid, "tid": tid,
            "ts": ts_us, "args": args,
        }
        if dur > 0:
            event["ph"] = "X"
            event["dur"] = dur * 1e6
        else:
            event["ph"] = "i"
            event["s"] = "t"  # thread-scoped instant
        out.append(event)
    for tid, tname in sorted(tids_seen.items()):
        out.append({"ph": "M", "name": "thread_name", "pid": pid,
                    "tid": tid, "args": {"name": tname}})
    return {"traceEvents": out, "displayTimeUnit": "ms"}


def merge_traces(traces: List[Tuple[str, Dict[str, Any]]]
                 ) -> Dict[str, Any]:
    """Merge per-replica Chrome traces into one: each replica becomes
    its own process (re-pid'd, process_name prefixed with the host) so
    Perfetto shows the fleet as parallel process groups."""
    merged: List[Dict[str, Any]] = []
    for idx, (host, trace) in enumerate(traces):
        pid = idx + 1
        for event in trace.get("traceEvents", []):
            event = dict(event, pid=pid)
            if event.get("ph") == "M" and \
                    event.get("name") == "process_name":
                inner = dict(event.get("args") or {})
                inner["name"] = f"{host} · {inner.get('name', '')}"
                event["args"] = inner
            merged.append(event)
    return {"traceEvents": merged, "displayTimeUnit": "ms"}


def summarize(events: List[Event]) -> Dict[str, Any]:
    """Timeline-derived device-path summary:

    - hold_ms: total growth-starvation HOLD window time;
    - suppressed_wave_ratio: waves the adaptive governor refused vs
      dispatched decode waves;
    - slice counts per kind (waves, chunks, prefills, preemptions).
    """
    waves = sum(1 for _, _, t, n, *_ in events
                if t == DEVICE and n == "decode.wave")
    chunks = sum(1 for _, _, t, n, *_ in events
                 if t == DEVICE and n == "prefill.chunk")
    prefills = sum(1 for _, _, t, n, *_ in events
                   if t == DEVICE and n == "prefill.bucket")
    preempts = sum(1 for _, _, t, n, *_ in events
                   if t == HOST and n == "preempt")
    suppressed = sum(1 for _, _, t, n, *_ in events
                     if t == HOST and n == "wave.suppressed")
    hold_ms = sum(dur for _, dur, t, n, *_ in events
                  if t == HOST and n == "hold") * 1000.0
    return {
        "decode_waves": waves,
        "prefill_chunks": chunks,
        "prefill_dispatches": prefills,
        "preemptions": preempts,
        "suppressed_waves": suppressed,
        "suppressed_wave_ratio": round(
            suppressed / (suppressed + waves), 4)
        if suppressed + waves else 0.0,
        "hold_ms": round(hold_ms, 3),
    }
