"""From the profiler's trace to numbers: device busy and idle time, time per
device operation and per program, and each idle gap attributed to what the
host was doing in it.

    python -m chipbench.trace <trace dir> <out.json>      (a CPU child)

`normalize` turns the profiler's .xplane.pb into plain lists, `reduce` works
on those lists alone, so the reduction is tested on a small recorded trace
(chipbench/tests/data/) without the profiler.

A normalized trace: {"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, duration_ns], ...]}]}]}.  Device planes are the ones named
/device:TPU:<n>; on each, the line "XLA Ops" holds one event per executed
HLO operation (nested inside `while` and `conditional` bodies, hence the
union for busy time) and "XLA Modules" one per executed program.
"""

import bisect
import glob
import json
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
SMALL_GAP_NS = 20_000  # gaps shorter than this are launch latency, summed


def normalize(trace_dir: str) -> dict:
    import jax

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {trace_dir}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[e.name, int(e.start_ns), int(e.duration_ns)]
                      for e in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union_ns(intervals) -> list:
    """Sorted, merged [start, end] intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


_RESULT_TYPE = re.compile(r"\w+\[[\d,]*\]")


def op_key(name: str) -> str:
    """The kind of an operation: the profiler names an event by its whole HLO
    instruction (`%fusion.12 = bf16[24,20,64]{...} fusion(...)`); its kind is
    the instruction's name without the instance number, and the type of its
    (first) result: `fusion bf16[24,20,64]`."""
    head, _, rest = name.partition(" = ")
    base = re.sub(r"[.\d]+$", "", head.strip().lstrip("%")) or head
    result = _RESULT_TYPE.search(rest)
    return f"{base} {result.group(0)}" if result else base


def module_key(name: str) -> str:
    """`jit_decode_fn(1234567)` -> `jit_decode_fn`."""
    return re.sub(r"\(\d+\)$", "", name)


class HostTimeline:
    """What the host did while the device was idle.  The device is idle
    because nothing was launched, so a gap is named from the threads that
    launch programs: the host lines that hold a `PjitFunction(...)` event."""

    def __init__(self, planes):
        self.threads = []
        for plane in planes:
            if not plane["name"].startswith("/host:"):
                continue
            for line in plane["lines"]:
                if not any(n.startswith("PjitFunction(")
                           for n, _, _ in line["events"]):
                    continue
                events = sorted((s, s + d, n) for n, s, d in line["events"]
                                if d > 0)
                reach, high = [], 0
                for _, end, _ in events:
                    high = max(high, end)
                    reach.append(high)
                self.threads.append(([e[0] for e in events], reach, events))

    def doing(self, start: int, end: int) -> str:
        """The innermost event of a launching thread that covers four fifths
        of [start, end]; where none covers that much, the innermost of those
        that overlap it about as much as any does."""
        found = []
        for starts, reach, events in self.threads:
            i = bisect.bisect_left(starts, end) - 1
            while i >= 0 and reach[i] > start:
                s, e, name = events[i]
                overlap = min(e, end) - max(s, start)
                if overlap > 0:
                    found.append((overlap, e - s, name))
                i -= 1
        if not found:
            return "(launching threads idle)"
        most = max(overlap for overlap, _, _ in found)
        enough = min(0.8 * (end - start), 0.9 * most)
        return min((dur, name) for overlap, dur, name in found
                   if overlap >= enough)[1]


def reduce(trace: dict) -> dict:
    planes = trace["planes"]
    device_planes = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    if not device_planes:
        raise ValueError(
            "the device ran nothing during the capture: the trace has no "
            "/device:TPU:<n> plane, only "
            + (", ".join(p["name"] for p in planes) or "no plane at all")
            + ".  A server that had no request left to answer in the "
            "window's last seconds gives such a trace")
    every = [(s, s + d) for p in planes for line in p["lines"]
             for _, s, d in line["events"]]
    t0, t1 = min(s for s, _ in every), max(e for _, e in every)

    busy_ns, ops, programs = [], {}, {}
    first_busy = None
    for plane in device_planes:
        lines = {line["name"]: line["events"] for line in plane["lines"]}
        op_events = lines.get(OPS_LINE, [])
        merged = union_ns((s, s + d) for _, s, d in op_events)
        busy_ns.append(sum(e - s for s, e in merged))
        if first_busy is None:
            first_busy = merged
        # Time per operation counts leaves only: an event that holds later
        # events of the same line is a `while` or `conditional` around them.
        ordered = sorted(op_events, key=lambda e: (e[1], -e[2]))
        for i, (name, start, dur) in enumerate(ordered):
            nxt = ordered[i + 1] if i + 1 < len(ordered) else None
            if nxt is not None and nxt[1] < start + dur and dur > 0 \
                    and nxt[1] + nxt[2] <= start + dur:
                continue
            rec = ops.setdefault(op_key(name), [0, 0])
            rec[0] += 1
            rec[1] += dur
        for name, _, dur in lines.get(MODULES_LINE, []):
            rec = programs.setdefault(module_key(name), [0, 0])
            rec[0] += 1
            rec[1] += dur

    host = HostTimeline(planes)
    gaps, small = {}, 0
    edges = [[t0, t0]] + first_busy + [[t1, t1]]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        if start - end <= 0:
            continue
        if start - end < SMALL_GAP_NS:
            small += start - end
            continue
        name = host.doing(end, start)
        gaps[name] = gaps.get(name, 0) + (start - end)
    if small:
        gaps[f"(gaps under {SMALL_GAP_NS // 1000} us)"] = small

    def ranked(table):
        return sorted(([k, v / 1e9] for k, v in table.items()),
                      key=lambda kv: -kv[1])

    return {
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "chips": len(device_planes),
        "device_ops": ranked({k: v[1] for k, v in ops.items()}),
        "idle_gaps": ranked(gaps),
        "ops": {k: {"count": v[0], "seconds": v[1] / 1e9}
                for k, v in ops.items()},
        "programs": {k: {"count": v[0], "seconds": v[1] / 1e9}
                     for k, v in programs.items()},
    }


def main(argv) -> int:
    trace = normalize(argv[1])
    with open(argv[2], "w") as f:
        json.dump(reduce(trace), f)
    if len(argv) > 3:  # keep the normalized trace too, to record a test trace
        with open(argv[3], "w") as f:
            json.dump(trace, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
