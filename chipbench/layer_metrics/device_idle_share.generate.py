"""device_idle_share.generate: 1 - (union of device operations) / (traced
part of the window), from the profiler's trace."""

UNIT, LAYER, SOURCE = "%", "device", "device_trace"
MOVES = "tokens_per_s"


def read(run):
    trace = run["trace_reduced"]
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
