"""prefill_busy_share: of the seconds the device was busy in the traced part
of the window, the share inside prefill programs.  A closed loop above the
knee completes tokens at the pace of its decode waves, and a prefill
dispatch between two waves delays every stream by its whole length: what
prefill takes of the device, decode does not have."""

UNIT, LAYER, SOURCE = "%", "model step", "device_trace"
MOVES = "tokens_per_s"


def read(run):
    trace = run["trace_reduced"]
    if not trace or not trace["busy_s"]:
        return None
    seconds = sum(v["seconds"] for k, v in trace["programs"].items()
                  if "prefill_fn" in k)
    return 100.0 * seconds / trace["busy_s"]
