"""The device's idle time put to the engine's phases.

    python -m chipbench.engine_phases <trace dir> <out.json>   (a CPU child)

While a profiler capture is active the served program writes its own spans
into the trace (`EngineTimeline.span`, kfserving_tpu/observability/profiling/
timeline.py): `engine.prep.<program>`, `engine.launch.<program>`,
`engine.spill` and `engine.faultback` on the thread that launches programs,
`engine.fetch` on the fetch workers, `engine.admit`, `engine.grow`,
`engine.deliver`, `engine.wait.fetch` and `engine.wait.request` on the
scheduler's loop.  They share the device's clock, so each idle gap of the
device (found as `trace.reduce` finds them) is put to the span that covers
it: the launching thread's first, then the loop thread's for what is left,
else `(no engine span)`.  A gap that several spans share is divided among
them by overlap, so the phases' seconds add up to the idle time exactly.
A span that was open when the capture began, or still open when it ended, is
not in the trace (the annotation is taken at a span's start and written at
its end), so before the launching thread's first span and after its last
the table says `(capture edge)` and not `(no engine span)`: with launches of
0.2 to 0.5 s, each edge of a 3-s capture is up to that long.
Threads are told apart by the `engine.*` events their lines hold, never by
a thread's name; names of Python frames play no part, so the table reads
the same with the Python tracer off.

`of(run)` computes the table once for a run (in a CPU child, as `trace.py`
is run), prints it as an observation line and keeps it in `run` for the
readers under layer_metrics/.  A trace with no `engine.*` event (a program
from before the spans) gives None, and so do the readers.
"""

import bisect
import json
import os
import subprocess
import sys

from chipbench import prom, trace
from chipbench.servers import ROOT, WORK, BenchFailure, child_env, log

NO_SPAN = "(no engine span)"
EDGE = "(capture edge)"
SMALL = f"(gaps under {trace.SMALL_GAP_NS // 1000} us)"
LAUNCHING = ("engine.launch.", "engine.prep.", "engine.spill",
             "engine.faultback")
LOOP = ("engine.admit", "engine.grow", "engine.deliver", "engine.wait.")


class Spans:
    """The `engine.*` events of some host threads, ordered for lookup."""

    def __init__(self, lines):
        self.events = sorted((s, s + d, n) for line in lines
                             for n, s, d in line["events"]
                             if n.startswith("engine.") and d > 0)
        self.starts = [e[0] for e in self.events]
        self.reach, high = [], 0
        for _, end, _ in self.events:
            high = max(high, end)
            self.reach.append(high)

    def claim(self, free: list, into: dict) -> list:
        """Credit to each span's name what it overlaps of the `free`
        intervals (sorted, disjoint); returns what no span covered.  Spans
        of one thread follow each other; should two overlap, the earlier
        one claims the shared part, so nothing is counted twice."""
        left = []
        for start, end in free:
            i = bisect.bisect_left(self.starts, end) - 1
            covering = []
            while i >= 0 and self.reach[i] > start:
                if self.events[i][1] > start:
                    covering.append(self.events[i])
                i -= 1
            pieces = [[start, end]]
            for s, e, name in reversed(covering):
                rest = []
                for a, b in pieces:
                    lo, hi = max(a, s), min(b, e)
                    if hi <= lo:
                        rest.append([a, b])
                        continue
                    into[name] = into.get(name, 0) + (hi - lo)
                    if a < lo:
                        rest.append([a, lo])
                    if hi < b:
                        rest.append([hi, b])
                pieces = rest
            left.extend(pieces)
        return left


def engine_lines(planes) -> tuple:
    """(launching lines, loop lines): the host lines holding the launching
    thread's spans, and those holding the scheduler loop's."""
    launching, loop = [], []
    for plane in planes:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            names = {n for n, _, _ in line["events"]
                     if n.startswith("engine.")}
            if any(n.startswith(LAUNCHING) for n in names):
                launching.append(line)
            elif any(n.startswith(LOOP) for n in names):
                loop.append(line)
    return launching, loop


def idle_gaps(planes) -> tuple:
    """(t0, t1, gaps at least SMALL_GAP_NS long, ns in shorter ones): the
    traced part and the first device's idle gaps, as `trace.reduce` has
    them."""
    device = next(p for p in planes if trace.DEVICE_PLANE.match(p["name"]))
    every = [(s, s + d) for p in planes for line in p["lines"]
             for _, s, d in line["events"]]
    t0, t1 = min(s for s, _ in every), max(e for _, e in every)
    ops = next((line["events"] for line in device["lines"]
                if line["name"] == trace.OPS_LINE), [])
    busy = trace.union_ns((s, s + d) for _, s, d in ops)
    edges = [[t0, t0]] + busy + [[t1, t1]]
    gaps, small = [], 0
    for (_, end), (start, _) in zip(edges, edges[1:]):
        if start - end >= trace.SMALL_GAP_NS:
            gaps.append([end, start])
        elif start - end > 0:
            small += start - end
    return t0, t1, gaps, small


def reduce(normalized: dict):
    """The table, from a normalized trace; None where the trace has no
    device plane or no `engine.*` event."""
    planes = normalized["planes"]
    launching, loop = engine_lines(planes)
    if not launching and not loop:
        return None
    if not any(trace.DEVICE_PLANE.match(p["name"]) for p in planes):
        return None
    t0, t1, gaps, small = idle_gaps(planes)
    phases = {}
    launched = Spans(launching)
    left = Spans(loop).claim(launched.claim(gaps, phases), phases)
    # What no span covers: at the capture's edges or inside it.
    first = launched.events[0][0] if launched.events else t0
    last = launched.reach[-1] if launched.events else t1
    edge = sum(max(0, min(e, first) - s) + max(0, e - max(s, last))
               for s, e in left)
    uncovered = sum(e - s for s, e in left) - edge
    for name, ns in ((EDGE, edge), (NO_SPAN, uncovered), (SMALL, small)):
        if ns:
            phases[name] = ns

    def seconds(*prefixes):
        return sum(v for k, v in phases.items()
                   if k.startswith(prefixes)) / 1e9

    spans = {}
    for line in launching:
        for name, _, dur in line["events"]:
            if name.startswith(("engine.launch.", "engine.prep.")):
                rec = spans.setdefault(name, [0, 0])
                rec[0] += 1
                rec[1] += dur
    idle_s = sum(phases.values()) / 1e9
    launch_s = seconds("engine.launch.")
    prep_s = seconds("engine.prep.", "engine.spill", "engine.faultback")
    return {
        "window_s": (t1 - t0) / 1e9,
        "idle_s": idle_s,
        "launch_s": launch_s,
        "prep_s": prep_s,
        # the launching thread in no span: the loop's phases, the capture's
        # edges, and what no engine span covers
        "loop_s": idle_s - launch_s - prep_s - small / 1e9,
        "edge_s": edge / 1e9,
        "no_span_s": uncovered / 1e9,
        "edges_s": [(first - t0) / 1e9, (t1 - last) / 1e9],
        "idle_by_phase": sorted(([k, v / 1e9] for k, v in phases.items()),
                                key=lambda kv: -kv[1]),
        "spans": {k: {"count": v[0], "mean_ms": v[1] / v[0] / 1e6}
                  for k, v in sorted(spans.items())},
    }


def of(run: dict):
    """The table of this run's trace, computed on first use."""
    if "engine_phases" in run:
        return run["engine_phases"]
    run["engine_phases"] = None
    if not run.get("trace_dir"):
        return None
    out = os.path.join(WORK, "runs",
                       f"{run['cell']['name']}.engine_phases.json")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.engine_phases", run["trace_dir"],
         out], cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=child_env(run["config"]["name"], JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        raise BenchFailure(f"engine_phases exited {proc.returncode}:\n"
                           f"{proc.stderr[-3000:]}")
    with open(out) as f:
        table = json.load(f)
    if table is None:
        log("engine phases: the trace holds no engine.* span")
    else:
        log("engine phases (s of device idle time by engine span; mean "
            "and count of the launching thread's spans): "
            + json.dumps(table))
    run["engine_phases"] = table
    return table


def share(run: dict, key: str):
    """`key` of the table as a share of the traced part, in percent."""
    table = of(run)
    if not table or table["window_s"] <= 0:
        return None
    return 100.0 * table[key] / table["window_s"]


def histogram_mean(run: dict, histogram: str, **labels):
    """What the engine's own clocks say of its phases (host time per
    dispatch, time to first token by stage): sum over count of one
    histogram child of /metrics, both differenced between the window's
    edges.  None where the program has no such histogram, or it did not
    move."""
    total = prom.delta(run["scrapes"], "open", "close", histogram + "_sum",
                       **labels)
    count = prom.delta(run["scrapes"], "open", "close",
                       histogram + "_count", **labels)
    return total / count if total is not None and count else None


def main(argv) -> int:
    normalized = trace.normalize(argv[1])
    with open(argv[2], "w") as f:
        json.dump(reduce(normalized), f)
    if len(argv) > 3:  # keep the normalized trace too, to record a test trace
        with open(argv[3], "w") as f:
            json.dump(normalized, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
