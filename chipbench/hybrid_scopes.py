"""Device time of a hybrid model's programs by `jax.named_scope`: what the
Mamba layers and the held experts cost inside a decode call.

    python -m chipbench.hybrid_scopes <trace dir> <out.json>     (a CPU child)

`moe_scopes.py`'s list of scopes is fixed (it is what `moe_step_share` and
`moe_experts_roofline` were accepted with), so the scopes that
models/nemotron_h.py and ops/ssm.py add get this reducer of their own: the
same normalized trace (`moe_scopes.normalize`), the same rules (an operation
belongs to the program whose event holds its start; an event that holds
later events of its line is not a leaf), another list.  A program without
such scopes (the other decoders, a parent commit) gives empty tables, and
the readers give None.
"""

import json
import os
import re
import subprocess
import sys

from chipbench import moe_scopes
from chipbench.servers import ROOT, WORK, BenchFailure, child_env, log

SCOPES = ("ssm.in_proj", "ssm.conv", "ssm.scan", "ssm.out", "moe.router",
          "moe.dispatch", "moe.experts", "moe.shared", "moe.combine", "attn")
_SCOPE = re.compile(r"/(" + "|".join(re.escape(s) for s in SCOPES) + r")/")


def scope_of(tf_op: str):
    """The innermost of SCOPES on the operation's path, else the scope of a
    kernel that comes under its own name (`moe_scopes.KERNELS`); None for
    none."""
    found = _SCOPE.findall(tf_op + "/")
    if found:
        return found[-1]
    return next((scope for kernel, scope in moe_scopes.KERNELS
                 if tf_op.startswith(kernel)), None)


def reduce(normalized: dict) -> dict:
    """{program: {"calls", "whole_calls", "seconds", "leaf_seconds",
    "scopes": {scope: s}}} over the programs of the trace.  A capture begins
    and ends inside a call, and with 7 or 8 decode calls of 160 ms in a 3-s
    trace a stub counted as a call would overstate a roofline share by up to
    a seventh: `whole_calls` is the program's seconds over its longest call
    (whole calls of one program take the same time), so a call cut by the
    capture's edge counts for the part of it that is in the trace."""
    modules = sorted(normalized["modules"], key=lambda m: m[1])
    ordered = sorted(normalized["ops"], key=lambda e: (e[1], -e[2]))
    out = {}
    for name, _, dur in modules:
        rec = out.setdefault(name, {"calls": 0, "whole_calls": 0.0,
                                    "seconds": 0.0, "longest": 0.0,
                                    "leaf_seconds": 0.0, "scopes": {}})
        rec["calls"] += 1
        rec["seconds"] += dur / 1e9
        rec["longest"] = max(rec["longest"], dur / 1e9)
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
        rec = out[modules[m][0]]
        rec["leaf_seconds"] += dur / 1e9
        scope = scope_of(tf_op)
        if scope:
            rec["scopes"][scope] = rec["scopes"].get(scope, 0.0) + dur / 1e9
    for rec in out.values():
        rec["scopes"] = dict(sorted(rec["scopes"].items()))
        longest = rec.pop("longest")
        rec["whole_calls"] = rec["seconds"] / longest if longest else 0.0
    return out


def of(run: dict):
    """The table of this run's trace, computed on first use; None where the
    run has no trace."""
    if "hybrid_scopes" in run:
        return run["hybrid_scopes"]
    run["hybrid_scopes"] = None
    if not run.get("trace_dir"):
        return None
    out = os.path.join(WORK, "runs",
                       f"{run['cell']['name']}.hybrid_scopes.json")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.hybrid_scopes", run["trace_dir"],
         out], cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=child_env(run["config"]["name"], JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        raise BenchFailure(f"hybrid_scopes exited {proc.returncode}:\n"
                           f"{proc.stderr[-3000:]}")
    with open(out) as f:
        table = json.load(f)
    log("device seconds by scope (hybrid), per program: " + json.dumps(
        {k: v for k, v in table.items() if v["scopes"]}))
    run["hybrid_scopes"] = table
    return table


def decode(run: dict):
    """The decode program's record, or None where the trace holds no decode
    call or no operation of it under an `ssm.*` scope."""
    table = of(run) or {}
    rec = next((v for k, v in table.items() if "decode_fn" in k), None)
    if not rec or rec["seconds"] <= 0 or not any(
            s.startswith("ssm.") for s in rec["scopes"]):
        return None
    return rec


def layer_steps(run: dict, kind: str, calls: float) -> float:
    """Layer-steps of layers of `kind` (a letter of the pattern) in `calls`
    decode calls (`whole_calls`: a fraction for a call cut by an edge)."""
    config = run["config"]
    return (calls * config["serving"]["steps_per_call"]
            * config["hybrid_override_pattern"].count(kind))


def main(argv) -> int:
    normalized = moe_scopes.normalize(argv[1])
    with open(argv[2], "w") as f:
        json.dump(reduce(normalized), f)
    if len(argv) > 3:  # keep the normalized trace too, to record a test trace
        with open(argv[3], "w") as f:
            json.dump(normalized, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
