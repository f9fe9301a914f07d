"""One run of one cell of BENCHMARK.json.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data and finds everything by name:

    a cell           an entry of BENCHMARK.json `workloads`: {config, traffic}
    a configuration  the `file` of its entry in `configs` (chipbench/configs/)
    a traffic mix    chipbench/traffic/<traffic>.json
    how it is served chipbench/kinds/<config's "kind">.py, `measure(run)`
    its reference    chipbench/references/<config's reference module>.py
    its limits       the configuration's `reference.tolerance`, or
                     chipbench/limits/<config>.json where there is one
    a metric         chipbench/end_to_end/<name>.py or
                     chipbench/layer_metrics/<name>.py, `read(run)`; a reader
                     that finds nothing to read returns None and the metric
                     is left out of the line

so a later PR adds a cell, a configuration, a mix or a metric by adding files
and manifest entries, and edits none.

Lines before the last are observations for whoever reads the run (set-up by
phase, throughput by slice, how late the generator ran).  The last line is
the result.  A run that cannot give one (no TPU, fewer chips than the cell
asks for, a child that dies, a directory that is not a checkout) prints none
and exits non-zero; a run whose answers are off the reference, or that
compiled inside its window, prints `"correct": false` and exits 0, as every
run that has a result does: what reads the line is told by the line.
Every result carries the numbers that decided `correct`, each beside its
limit: under `compared`, the line's last key, and as stderr's last lines.
"""

import argparse
import importlib.util
import json
import os
import subprocess
import sys
import time

from chipbench import schedule, stats
from chipbench.servers import ROOT, WORK, BenchFailure, child_env, log

HERE = os.path.dirname(os.path.abspath(__file__))


def load_by_path(directory: str, name: str):
    """The module chipbench/<directory>/<name>.py; names may hold dots and
    dashes, so it is loaded by its path.  None where there is no such file."""
    path = os.path.join(HERE, directory, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{directory}.{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: dict, workload: str):
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"no workload {workload!r} in BENCHMARK.json; "
                           f"there are {sorted(cells)}")
    cell = cells[workload]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = schedule.load_traffic(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(manifest: dict, group: str, directory: str, run: dict) -> dict:
    """Every metric of `group` that this cell reports, read by its reader."""
    out = {}
    for metric in manifest[group]:
        if run["cell"]["name"] not in metric.get(
                "workloads", [run["cell"]["name"]]):
            continue
        reader = load_by_path(directory, metric["name"])
        if reader is None:
            raise BenchFailure(f"metric {metric['name']!r} has no reader "
                               f"chipbench/{directory}/{metric['name']}.py")
        value = reader.read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def reduce_trace(run: dict):
    """The profiler's trace, reduced in a CPU child (reading it needs JAX,
    and this process stays off JAX)."""
    reduced = os.path.join(WORK, "runs", f"{run['cell']['name']}.trace.json")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.trace", run["trace_dir"], reduced],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=child_env(run["config"]["name"], JAX_PLATFORMS="cpu"))
    if proc.returncode != 0:
        raise BenchFailure(f"trace reduction exited {proc.returncode}:\n"
                           f"{proc.stderr[-3000:]}")
    with open(reduced) as f:
        return json.load(f)


def observations(run: dict) -> None:
    """The earlier lines: what explains a run that strays."""
    window = run["window"]
    log("setup by phase: " + json.dumps(
        {k: round(v, 2) for k, v in run["setup_phases"].items()})
        + f"; server's own marks {json.dumps(run['startup'])}")
    log("reference: " + ", ".join(
        f"{name} {c['value']:.5f} (limit {c['limit']})"
        for name, c in compared(run).items() if name.startswith("reference"))
        + f" over {run['reference']['prompts']} prompts, "
        f"{run['reference']['seconds']:.1f}s")
    kind = load_by_path("kinds", run["config"]["kind"])
    if hasattr(kind, "observe"):
        kind.observe(run)
    late = stats.lateness_ms(run["records"], window)
    log(f"generator lateness over {len(late)} requests due in the window: "
        f"p50 {stats.percentile(late, 0.5):.3f} ms, "
        f"p99 {stats.percentile(late, 0.99):.3f} ms, "
        f"max {late[-1] if late else float('nan'):.3f} ms")
    for note in run["notes"]:
        log(f"generator note: {note}")
    if run["compiles_in_window"]:
        log("compiled inside the window: "
            + "; ".join(c[:120] for c in run["compiles_in_window"]))


def compared(run: dict) -> dict:
    """Each number that decides `correct`, beside its limit.  Which numbers
    are compared with the reference, and their limits, the kind has read for
    the configuration (`kinds/generate.reference_limits`)."""
    reference = run["reference"]
    limits = reference.get("limits") or {
        "reference_gap": reference["tolerance"]}
    read = {"reference_gap": "gap", "reference_gap_median": "gap_median"}
    out = {name: {"value": reference[read[name]], "limit": limit}
           for name, limit in limits.items()}
    out["compiles_in_window"] = {"value": len(run["compiles_in_window"]),
                                 "limit": 0}
    return out


def outcome(run: dict) -> dict:
    """attempted / failed over requests due in the window whose outcome is
    known (those still running when the run ended are neither), and whether
    the run is correct."""
    due = stats.due_in_window(run["records"], run["window"])
    known = [r for r in due if r["error"] != "cut"
             or (run["traffic"]["loop"] == "open" and r["first"] is None)]
    failed = [r for r in known if not r["ok"]]
    for r in failed[:5]:
        log(f"failed request {r['i']}: {r['error']}")
    correct = (all(c["value"] <= c["limit"] for c in compared(run).values())
               and run["device"]["platform"] == run["platform"])
    return {"correct": bool(correct), "attempted": len(known),
            "failed": len(failed)}


def measure_cell(cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, platform: str = "tpu") -> dict:
    """Everything one run of `cell` observed, for the readers to reduce.  The
    command line fixes `platform` to "tpu" and takes cell, config and traffic
    from the manifest; tests rehearse cells at toy size with "cpu"."""
    t_start = time.monotonic()
    if not os.path.isdir(os.path.join(ROOT, "kfserving_tpu")):
        raise BenchFailure(f"{ROOT} is not a checkout: no kfserving_tpu/")
    run = {"cell": cell, "config": config, "traffic": traffic, "seed": seed,
           "seconds": seconds, "trace": trace, "platform": platform,
           "t_start": t_start}
    kind = load_by_path("kinds", config["kind"])
    run.update(kind.measure(run))
    if platform == "tpu":
        with open(os.path.join(HERE, "peaks.json")) as f:
            peaks = json.load(f)["devices"]
        if run["device"]["kind"] not in peaks:
            raise BenchFailure(f"no peaks for device kind "
                               f"{run['device']['kind']!r} in peaks.json")
        run["peaks"] = peaks[run["device"]["kind"]]
    run["trace_reduced"] = reduce_trace(run) if trace else None
    observations(run)
    return run


def result_of(manifest: dict, run: dict) -> dict:
    """The last line: end-to-end metrics of an untraced run, per-layer
    metrics of a traced one."""
    result = outcome(run)
    if run["trace"]:
        result["metrics"] = metrics_of(manifest, "per_layer",
                                       "layer_metrics", run)
    else:
        result["metrics"] = metrics_of(manifest, "end_to_end",
                                       "end_to_end", run)
    in_use = [max(s[1]) for s in run["device_samples"] if all(s[1])]
    after = run["device_after"]["hbm_in_use"]
    if all(after):
        in_use.append(max(after))
    result["device"] = {"platform": run["device"]["platform"],
                        "kind": run["device"]["kind"],
                        "count": run["device"]["count"],
                        "memory_peak_bytes": int(max(in_use, default=0))}
    if run["trace"]:
        reduced = run["trace_reduced"]
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["compared"] = compared(run)  # last in the line
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        run = measure_cell(*find_cell(manifest, args.workload), args.seed,
                           args.seconds, bool(args.trace))
        result = result_of(manifest, run)
    except BenchFailure as e:
        print(f"[chipbench] no result: {e}", file=sys.stderr, flush=True)
        return 1
    for name, c in result["compared"].items():  # stderr's last lines
        print(f"[chipbench] compared {name}: {c['value']:.6g} "
              f"(limit {c['limit']:.6g})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
