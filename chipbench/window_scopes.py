"""Device time of a window model's programs by `jax.named_scope`: what the
sliding-window layers and the whole-context layers cost inside a decode
call, and the paged decode kernel's own calls under each.

    python -m chipbench.window_scopes <trace dir> <out.json>     (a CPU child)

`moe_scopes.py`'s list of scopes is fixed (it is what `moe_step_share` and
`moe_experts_roofline` were accepted with), so the scopes that
models/mellum.py adds (`attn.window` and `attn.full` inside `attn`,
`rope.tables`) get this reducer of their own, in the manner of
`hybrid_scopes.py`: the same normalized trace (`moe_scopes.normalize`), the
same rules (an operation belongs to the program whose event holds its start;
an event that holds later events of its line is not a leaf), another list.
Beside each scope's seconds it keeps the seconds and the number of the
kernel's own calls under it (`KERNEL` in the operation's path: one call a
layer-step), which is what a roofline share divides by.  A program without
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

SCOPES = ("attn.window", "attn.full", "rope.tables", "moe.router",
          "moe.dispatch", "moe.experts", "moe.combine", "attn")
# The closing slash is looked at, not taken: `attn.window` comes right after
# `attn` on a path, and its opening slash is that one.
_SCOPE = re.compile(r"/(" + "|".join(re.escape(s) for s in SCOPES)
                    + r")(?=/)")
KERNEL = "jit(paged_attention_tpu)/pallas_call"


def scope_of(tf_op: str):
    """The innermost of SCOPES on the operation's path, else the scope of a
    kernel that comes under its own name (`moe_scopes.KERNELS`)."""
    found = _SCOPE.findall(tf_op + "/")
    if found:
        return found[-1]
    return next((scope for kernel, scope in moe_scopes.KERNELS
                 if tf_op.startswith(kernel)), None)


def reduce(normalized: dict) -> dict:
    """{program: {"calls", "whole_calls", "seconds", "scopes": {scope: s},
    "kernel": {scope: {"calls", "seconds"}}}} over the programs of the
    trace; `whole_calls` as `hybrid_scopes.reduce` has it."""
    modules = sorted(normalized["modules"], key=lambda m: m[1])
    ordered = sorted(normalized["ops"], key=lambda e: (e[1], -e[2]))
    out = {}
    for name, _, dur in modules:
        rec = out.setdefault(name, {"calls": 0, "whole_calls": 0.0,
                                    "seconds": 0.0, "longest": 0.0,
                                    "scopes": {}, "kernel": {}})
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
        scope = scope_of(tf_op)
        if not scope:
            continue
        rec["scopes"][scope] = rec["scopes"].get(scope, 0.0) + dur / 1e9
        if KERNEL in tf_op:
            kernel = rec["kernel"].setdefault(scope, {"calls": 0,
                                                      "seconds": 0.0})
            kernel["calls"] += 1
            kernel["seconds"] += dur / 1e9
    for rec in out.values():
        rec["scopes"] = dict(sorted(rec["scopes"].items()))
        longest = rec.pop("longest")
        rec["whole_calls"] = rec["seconds"] / longest if longest else 0.0
    return out


def of(run: dict):
    """The table of this run's trace, computed on first use; None where the
    run has no trace."""
    if "window_scopes" in run:
        return run["window_scopes"]
    run["window_scopes"] = None
    if not run.get("trace_dir"):
        return None
    out = os.path.join(WORK, "runs",
                       f"{run['cell']['name']}.window_scopes.json")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.window_scopes", run["trace_dir"],
         out], cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=child_env(run["config"]["name"], JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        raise BenchFailure(f"window_scopes exited {proc.returncode}:\n"
                           f"{proc.stderr[-3000:]}")
    with open(out) as f:
        table = json.load(f)
    log("device seconds by scope (window), per program: " + json.dumps(
        {k: v for k, v in table.items() if v["scopes"]}))
    run["window_scopes"] = table
    return table


def decode(run: dict):
    """The decode program's record, or None where the trace holds no decode
    call or no operation of it under `attn.window` or `attn.full`."""
    table = of(run) or {}
    rec = next((v for k, v in table.items() if "decode_fn" in k), None)
    if not rec or rec["seconds"] <= 0 or not any(
            s in rec["scopes"] for s in ("attn.window", "attn.full")):
        return None
    return rec


def kernel_roofline(run: dict, scope: str, cap):
    """100 x (the least time for the paged decode kernel's calls under
    `scope` in the traced part of the window) / (the time they took).  One
    call is one layer-step; what it needs is
    `opsbytes_window.grouped_decode_attention` at the rows the requests
    decoding then make it read (`cap`: the window, or None for the whole
    context), which the load generator knows from its own records.  None
    where the trace holds no such call."""
    from chipbench import opsbytes_window

    rec = decode(run)
    if rec is None or not run.get("trace_window") or "peaks" not in run:
        return None
    kernel = rec["kernel"].get(scope)
    if not kernel or kernel["seconds"] <= 0:
        return None
    config = run["config"]
    flops, nbytes = opsbytes_window.grouped_decode_attention(
        rows=opsbytes_window.live_rows(run["records"], run["trace_window"],
                                       cap),
        sequences=config["serving"]["max_slots"],
        query_heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"], head_dim=config["head_dim"],
        bytes_per_value=2)
    least = max(flops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * kernel["calls"] * least / kernel["seconds"]


def pool_block_fill(run: dict, pool: str):
    """100 x Δcontext tokens / (Δblocks walked x block_size) of one pool's
    decode walk between the window's edges (`paged_block_fill`, by pool).
    None for a program without the per-pool counters."""
    from chipbench import prom

    model = run["config"]["name"]
    tokens, blocks = (prom.delta(
        run["scrapes"], "open", "close",
        f"kfserving_tpu_generator_decode_kv_pool_{name}_total",
        model=model, pool=pool)
        for name in ("context_tokens", "blocks_walked"))
    if tokens is None or not blocks:
        return None
    return 100.0 * tokens / (blocks * run["config"]["serving"]["block_size"])


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
