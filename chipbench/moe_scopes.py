"""Device time of the programs' operations by the `jax.named_scope` they were
traced under: what the routed expert layer costs inside a decode call.

    python -m chipbench.moe_scopes <trace dir> <out.json>      (a CPU child)

`trace.py` reads events through `jax.profiler.ProfileData`, which gives an
event's name (its HLO instruction) and nothing of where it came from.  The
profile itself knows: on a device plane every operation's metadata carries
`tf_op`, the `op_name` JAX gave the instruction, scopes and all
(`jit(decode_fn)/while/body/OlmoeLM/layer_3/experts/moe.experts/dot_general`),
or, for a kernel the compiler brings in itself, the kernel's own name
(`ragged-dot-none:`; KERNELS says which scope each belongs to).
Reading it takes the profile's own protobuf schema, which ships with
TensorFlow in this installation; hence a reducer of its own, as a child.

`normalize` turns the .xplane.pb into plain lists, `reduce` works on those
alone and is tested on a small recorded trace.  A normalized trace:
{"modules": [[name, start_ns, duration_ns], ...], "ops": [[tf_op, start_ns,
duration_ns], ...]} of the first device plane's "XLA Modules" and "XLA Ops"
lines.  An operation belongs to the program whose event holds its start; an
event that holds later events of its line (`while`, `conditional`) is not a
leaf and is not counted.  A program without such scopes (the dense decoder,
a parent commit) gives empty tables, and the readers give None.
"""

import glob
import json
import os
import re
import subprocess
import sys

from chipbench import trace
from chipbench.servers import ROOT, WORK, BenchFailure, child_env, log

SCOPES = ("moe.router", "moe.dispatch", "moe.experts", "moe.combine", "attn")
_SCOPE = re.compile(r"/(" + "|".join(re.escape(s) for s in SCOPES) + r")/")
# Kernels the compiler brings in under their own names, with no `op_name`:
# XLA's grouped matmul behind `jax.lax.ragged_dot` (ops/moe.py's only use of
# it is the experts) and the pass over the group sizes that precedes it; and
# ops/moe.py's own Pallas kernel, should a profile give it by name alone.
KERNELS = (("ragged-dot-metadata", "moe.dispatch"),
           ("ragged-dot", "moe.experts"),
           ("moe_experts_touched", "moe.experts"))


def normalize(trace_dir: str) -> dict:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {trace_dir}")
    space = xplane_pb2.XSpace()
    with open(paths[0], "rb") as f:
        space.ParseFromString(f.read())
    plane = next((p for p in space.planes
                  if trace.DEVICE_PLANE.match(p.name)), None)
    if plane is None:
        raise ValueError("the trace has no /device:TPU:<n> plane")
    tf_op_id = next((k for k, v in plane.stat_metadata.items()
                     if v.name == "tf_op"), None)

    def tf_op(metadata) -> str:
        for stat in metadata.stats:
            if stat.metadata_id == tf_op_id:
                return stat.str_value or plane.stat_metadata[
                    stat.ref_value].name
        return ""

    out = {"modules": [], "ops": []}
    for line in plane.lines:
        if line.name not in (trace.MODULES_LINE, trace.OPS_LINE):
            continue
        for event in line.events:
            metadata = plane.event_metadata[event.metadata_id]
            start = line.timestamp_ns + event.offset_ps // 1000
            if line.name == trace.MODULES_LINE:
                out["modules"].append(
                    [trace.module_key(metadata.name), start,
                     event.duration_ps // 1000])
            else:
                out["ops"].append([tf_op(metadata), start,
                                   event.duration_ps // 1000])
    return out


def scope_of(tf_op: str):
    """The innermost of SCOPES on the operation's path; None for none."""
    found = _SCOPE.findall(tf_op + "/")
    if found:
        return found[-1]
    return next((scope for kernel, scope in KERNELS
                 if tf_op.startswith(kernel)), None)


def reduce(normalized: dict) -> dict:
    """{program: {"calls", "seconds", "leaf_seconds", "scopes": {scope: s}}}
    over the programs that ran whole inside the trace."""
    modules = sorted(normalized["modules"], key=lambda m: m[1])
    ordered = sorted(normalized["ops"], key=lambda e: (e[1], -e[2]))
    programs = {}
    m = 0
    for i, (tf_op, start, dur) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is not None and dur > 0 and nxt[1] < start + dur \
                and nxt[1] + nxt[2] <= start + dur:
            continue  # holds later events: a `while` or `conditional`
        while m < len(modules) and modules[m][1] + modules[m][2] <= start:
            m += 1
        if m == len(modules) or modules[m][1] > start:
            continue  # outside every program of the trace
        rec = programs.setdefault(modules[m][0], {"leaf_ns": 0, "scopes": {}})
        rec["leaf_ns"] += dur
        scope = scope_of(tf_op)
        if scope:
            rec["scopes"][scope] = rec["scopes"].get(scope, 0) + dur
    out = {}
    for name, _, dur in modules:
        rec = out.setdefault(name, {"calls": 0, "seconds": 0.0})
        rec["calls"] += 1
        rec["seconds"] += dur / 1e9
    for name, rec in out.items():
        seen = programs.get(name, {"leaf_ns": 0, "scopes": {}})
        rec["leaf_seconds"] = seen["leaf_ns"] / 1e9
        rec["scopes"] = {k: v / 1e9 for k, v in sorted(seen["scopes"].items())}
    return out


def of(run: dict):
    """The table of this run's trace, computed on first use; None where the
    run has no trace."""
    if "moe_scopes" in run:
        return run["moe_scopes"]
    run["moe_scopes"] = None
    if not run.get("trace_dir"):
        return None
    out = os.path.join(WORK, "runs", f"{run['cell']['name']}.moe_scopes.json")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.moe_scopes", run["trace_dir"], out],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=child_env(run["config"]["name"], JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        raise BenchFailure(f"moe_scopes exited {proc.returncode}:\n"
                           f"{proc.stderr[-3000:]}")
    with open(out) as f:
        table = json.load(f)
    log("device seconds by scope, per program: " + json.dumps(
        {k: v for k, v in table.items() if v["scopes"]}))
    run["moe_scopes"] = table
    return table


def decode(run: dict):
    """The decode program's record, or None where the trace holds no decode
    call or no operation of it under one of SCOPES."""
    table = of(run) or {}
    rec = next((v for k, v in table.items() if "decode_fn" in k), None)
    return rec if rec and rec["scopes"] and rec["seconds"] > 0 else None


def main(argv) -> int:
    normalized = normalize(argv[1])
    with open(argv[2], "w") as f:
        json.dump(reduce(normalized), f)
    if len(argv) > 3:  # keep the normalized trace too, to record a test trace
        with open(argv[3], "w") as f:
            json.dump(normalized, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
