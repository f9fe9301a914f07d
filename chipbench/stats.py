"""Metric arithmetic over the load generator's events.

A request record, as `loadgen` writes it (times are CLOCK_MONOTONIC seconds
of the generator process; None where it never happened):

    {"i", "phase", "prompt_tokens", "output_tokens", "due", "sent",
     "first", "last", "tokens": [arrival time of each token], "ok", "error"}

`due` is when the request should have been sent: its scheduled time in an
open loop, the moment its client became free in a closed loop.  Everything
here is a pure function of such records and a window [open, close).
"""

import math


def percentile(sorted_values, q: float) -> float:
    """Nearest-rank percentile of a pre-sorted list (as benchmarks/harness.py
    has it); NaN for an empty one."""
    if not sorted_values:
        return float("nan")
    idx = min(len(sorted_values) - 1,
              max(0, math.ceil(q * len(sorted_values)) - 1))
    return sorted_values[idx]


def median(values) -> float:
    return percentile(sorted(values), 0.5)


def in_window(t, window) -> bool:
    return t is not None and window[0] <= t < window[1]


def tokens_in_window(records, window) -> int:
    """Streamed tokens whose arrival falls in the window, whichever request
    they belong to and whether or not it completed there."""
    return sum(1 for r in records for t in r["tokens"] if in_window(t, window))


def due_in_window(records, window) -> list:
    return [r for r in records if in_window(r["due"], window)]


def first_answer_ms(records, window) -> list:
    """Sorted times from due to first byte of the answer, over requests due
    in the window.  A request with no answer is not in the list: the caller
    counts it as failed."""
    return sorted((r["first"] - r["due"]) * 1000.0
                  for r in due_in_window(records, window)
                  if r["first"] is not None)


def first_answer_quantile_ms(run: dict, q: float):
    """A reader's whole job for a quantile of time to first answer; None
    where no request due in the window was answered."""
    values = first_answer_ms(run["records"], run["window"])
    return percentile(values, q) if values else None


def tpot_ms(records, window) -> list:
    """Sorted (last token - first token) / (tokens - 1) over requests whose
    last token arrived in the window.  In a steady system the requests that
    end in a window are a fair sample of all requests, and none is cut short
    by the window's end, as the long ones among those due in it would be."""
    return sorted(
        (r["last"] - r["first"]) * 1000.0 / (len(r["tokens"]) - 1)
        for r in records
        if r["ok"] and in_window(r["last"], window) and len(r["tokens"]) > 1)


def request_ms(records, window) -> list:
    """Sorted times from due to last token, over requests whose last token
    arrived in the window: the same population as `tpot_ms`, for the same
    reason."""
    return sorted((r["last"] - r["due"]) * 1000.0 for r in records
                  if r["ok"] and in_window(r["last"], window))


def lateness_ms(records, window) -> list:
    """Sorted send time - due time over requests due in the window: how late
    the load generator ran."""
    return sorted((r["sent"] - r["due"]) * 1000.0
                  for r in due_in_window(records, window)
                  if r["sent"] is not None)


def live_context_tokens(records, window) -> float:
    """Time-weighted mean, over the window, of the summed context lengths
    (prompt + tokens streamed so far) of the requests that hold a decode
    slot: those between their first and their last token.  The paged decode
    kernel reads that many key and value rows per layer and step."""
    if window[1] <= window[0]:
        return float("nan")
    total = 0.0
    for r in records:
        times = r["tokens"]
        if not times:
            continue
        for n, (t0, t1) in enumerate(zip(times, times[1:]), start=1):
            lo, hi = max(t0, window[0]), min(t1, window[1])
            if hi > lo:
                total += (r["prompt_tokens"] + n) * (hi - lo)
    return total / (window[1] - window[0])
