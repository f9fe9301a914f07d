"""prefill_device_ms_per_ktok: device time of the prefill programs in the
traced part of the window, per thousand prompt tokens of the requests whose
first token arrived there (a first token is what a prefill ends in)."""

from chipbench import stats

UNIT, LAYER, SOURCE = "ms", "model step", "device_trace"
MOVES = "request_mean_ms"


def read(run):
    trace = run["trace_reduced"]
    if not trace or not run["trace_window"]:
        return None
    seconds = sum(v["seconds"] for k, v in trace["programs"].items()
                  if "prefill_fn" in k)
    tokens = sum(r["prompt_tokens"] for r in run["records"]
                 if stats.in_window(r["first"], run["trace_window"]))
    return 1e6 * seconds / tokens if tokens and seconds else None
