"""Device time of a latent-attention model's programs by `jax.named_scope`:
what latent attention, the shared expert, the dense layer and the head cost
inside a decode call beside the routed experts.

    python -m chipbench.latent_scopes <trace dir> <out.json>     (a CPU child)

`moe_scopes.py`'s list of scopes is fixed (it is what `moe_step_share` and
`moe_experts_roofline` were accepted with), so the scopes that
models/deepseek_v3.py adds (`attn.latent`, under it `attn.latent.absorb` and
`attn.latent.expand`; `moe.shared`, `mlp`, `head`) get this reducer of their
own, in the manner of `window_scopes.py`: the same normalized trace
(`moe_scopes.normalize`), the same rules (an operation belongs to the
program whose event holds its start; an event that holds later events of its
line is not a leaf), another list.  A program without `attn.latent` (the
other decoders, a parent commit) gives no record, and the readers give None.
"""

import json
import os
import re
import subprocess
import sys

from chipbench import moe_scopes
from chipbench.servers import ROOT, WORK, BenchFailure, child_env, log

SCOPES = ("attn.latent.absorb", "attn.latent.expand", "attn.latent",
          "moe.router", "moe.dispatch", "moe.experts", "moe.shared",
          "moe.combine", "mlp", "head")
# The closing slash is looked at, not taken: `attn.latent.absorb` comes right
# after `attn.latent` on a path, and its opening slash is that one.
_SCOPE = re.compile(r"/(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?=/)")


def scope_of(tf_op: str):
    """The innermost of SCOPES on the operation's path, else the scope of a
    kernel that comes under its own name (`moe_scopes.KERNELS`)."""
    found = _SCOPE.findall(tf_op + "/")
    if found:
        return found[-1]
    return next((scope for kernel, scope in moe_scopes.KERNELS
                 if tf_op.startswith(kernel)), None)


def reduce(normalized: dict) -> dict:
    """{program: {"calls", "seconds", "scopes": {scope: s}}} over the
    programs of the trace."""
    modules = sorted(normalized["modules"], key=lambda m: m[1])
    ordered = sorted(normalized["ops"], key=lambda e: (e[1], -e[2]))
    out = {}
    for name, _, dur in modules:
        rec = out.setdefault(name, {"calls": 0, "seconds": 0.0, "scopes": {}})
        rec["calls"] += 1
        rec["seconds"] += dur / 1e9
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
        scope = scope_of(tf_op)
        if scope:
            scopes = out[modules[m][0]]["scopes"]
            scopes[scope] = scopes.get(scope, 0.0) + dur / 1e9
    for rec in out.values():
        rec["scopes"] = dict(sorted(rec["scopes"].items()))
    return out


def of(run: dict):
    """The table of this run's trace, computed on first use; None where the
    run has no trace."""
    if "latent_scopes" in run:
        return run["latent_scopes"]
    run["latent_scopes"] = None
    if not run.get("trace_dir"):
        return None
    out = os.path.join(WORK, "runs",
                       f"{run['cell']['name']}.latent_scopes.json")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.latent_scopes", run["trace_dir"],
         out], cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=child_env(run["config"]["name"], JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        raise BenchFailure(f"latent_scopes exited {proc.returncode}:\n"
                           f"{proc.stderr[-3000:]}")
    with open(out) as f:
        table = json.load(f)
    log("device seconds by scope (latent), per program: " + json.dumps(
        {k: v for k, v in table.items() if v["scopes"]}))
    run["latent_scopes"] = table
    return table


def decode(run: dict):
    """The decode program's record, or None where the trace holds no decode
    call or no operation of it under `attn.latent`."""
    table = of(run) or {}
    rec = next((v for k, v in table.items() if "decode_fn" in k), None)
    if not rec or rec["seconds"] <= 0 or not any(
            s.startswith("attn.latent") for s in rec["scopes"]):
        return None
    return rec


def main(argv) -> int:
    with open(argv[2], "w") as f:
        json.dump(reduce(moe_scopes.normalize(argv[1])), f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
