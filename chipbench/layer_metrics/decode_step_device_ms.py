"""decode_step_device_ms: device time of the decode program per decode step
(a call runs `steps_per_call` of them), from the trace's program events."""

UNIT, LAYER, SOURCE = "ms", "model step", "device_trace"
MOVES = "tpot_p50_ms"


def read(run):
    trace = run["trace_reduced"]
    if not trace:
        return None
    calls = [v for k, v in trace["programs"].items() if "decode_fn" in k]
    count = sum(v["count"] for v in calls)
    if not count:
        return None
    steps = count * run["config"]["serving"]["steps_per_call"]
    return 1000.0 * sum(v["seconds"] for v in calls) / steps
