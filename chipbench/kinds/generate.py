"""How a `"kind": "generate"` configuration is served, checked, warmed and
driven: the llmserver child, streamed generation, the paged decoder.

`measure(run)` takes a cell from spawn to the end of its window and returns
what the metric readers reduce.  Phases, in order, each timed for the set-up
line: spawn (until the child names its device), load (until it answers
ready), check requests (the served log-probabilities that the plain reference
is compared with; its CPU child computes beside the next phase), programs
(every prefill shape the window can use, compiled or loaded from the cache),
then the load generator's warm traffic or lead-in.
"""

import hashlib
import json
import os
import random
import re
import shutil
import statistics
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from chipbench import prom, schedule, servers, stats
from chipbench.servers import BenchFailure, log

HERE = os.path.dirname(os.path.abspath(__file__))
BOS_ID = 256  # the served byte tokenizer: BOS, then one id per UTF-8 byte
CHECK_STEPS = 8  # decode steps compared per check prompt
CHECK_TOP = 5


def token_ids(prompt: str) -> list:
    return [BOS_ID] + list(prompt.encode("utf-8"))


# -- correctness: served log-probabilities against the plain reference -------
def check_prompts(traffic: dict, seed: int) -> list:
    """A seeded sample of three prompts: near the short end, the middle and
    the long end of the cell's prompt lengths."""
    rng = random.Random(f"check:{seed}")
    lengths = schedule.quantile_lengths(traffic["prompt_tokens"], 10)
    return [schedule.prompt_text(lengths[i], rng) for i in (1, 6, 9)]


def served_answers(server, prompts) -> list:
    cases = []
    for prompt in prompts:
        out = servers.post_json(
            f"{server.base}/v2/models/{server.name}/generate",
            {"text_input": prompt, "max_tokens": CHECK_STEPS,
             "temperature": 0.0, "logprobs": CHECK_TOP})
        records = out["details"].get("logprobs") or []
        if len(records) != CHECK_STEPS:
            raise BenchFailure(
                f"check prompt: asked {CHECK_STEPS} tokens, got "
                f"{len(records)} ({out['details'].get('finish_reason')})")
        cases.append({
            "prompt_ids": token_ids(prompt),
            "generated_ids": [r["id"] for r in records],
            "chosen": [r["logprob"] for r in records],
            "top_ids": [t["id"] for t in records[0]["top"]],
            "top": [t["logprob"] for t in records[0]["top"]],
        })
    return cases


def reference_answers(config: dict, cases: list) -> tuple:
    """(answers, seconds spent).  The reference's answers for these prompts
    and these generated ids are kept under WORK/refs, so a later run of the
    same configuration and seed reuses them; the served side is greedy and
    deterministic, so the key repeats."""
    ref = config["reference"]
    job = {"params_dir": servers.param_cache_dir(config["name"]),
           "n_layer": config["n_layer"],
           "layer_norm_epsilon": config["layer_norm_epsilon"],
           "cases": [{k: c[k] for k in
                      ("prompt_ids", "generated_ids", "top_ids")}
                     for c in cases]}
    entry = os.listdir(job["params_dir"])
    key = hashlib.sha256(json.dumps(
        [config["serving"]["arch_kwargs"], sorted(entry), job["cases"]],
        sort_keys=True).encode()).hexdigest()[:24]
    refs = os.path.join(servers.WORK, "refs")
    os.makedirs(refs, exist_ok=True)
    out_path = os.path.join(refs, f"{config['name']}-{key}.json")
    t0 = time.monotonic()
    if not os.path.exists(out_path):
        job_path = out_path + ".job"
        with open(job_path, "w") as f:
            json.dump(job, f)
        proc = subprocess.run(
            [sys.executable, "-m", f"chipbench.references.{ref['module']}",
             job_path, out_path + ".tmp"],
            cwd=servers.ROOT, capture_output=True, text=True, timeout=900,
            env=servers.child_env(config["name"], JAX_PLATFORMS="cpu"))
        if proc.returncode != 0:
            raise BenchFailure(f"reference child exited {proc.returncode}:\n"
                               f"{proc.stderr[-3000:]}")
        os.replace(out_path + ".tmp", out_path)
    with open(out_path) as f:
        return json.load(f)["cases"], time.monotonic() - t0


def reference_gaps(cases: list, answers: list) -> list:
    """|served - reference| log-probability of the chosen token of every
    compared step and of the first step's top tokens: every one of them."""
    return [abs(served - ref)
            for case, answer in zip(cases, answers)
            for served, ref in zip(case["chosen"] + case["top"],
                                   answer["chosen"] + answer["top"])]


def reference_limits(config: dict) -> dict:
    """{number compared with the reference: its limit}.  A configuration's
    own `reference.tolerance` bounds the widest gap.  Where the widest gap
    cannot tell a lower precision from the stated one (a router whose flipped
    near-tie moves one token by more than rounding moves them all),
    chipbench/limits/<configuration>.json names the numbers that can and
    their limits, this one's anew among them; PERF.md gives the readings."""
    path = os.path.join(os.path.dirname(HERE), "limits",
                        config["name"] + ".json")
    if not os.path.exists(path):
        return {"reference_gap": config["reference"]["tolerance"]}
    with open(path) as f:
        return {name: entry["limit"] for name, entry in json.load(f).items()}


# -- programs: every prefill shape the window can use ------------------------
_PREFILL = re.compile(r"jit\(prefill_fn\).*?int32\[(\d+),(\d+)\]")


def compiled_prefills(server) -> set:
    """(rows, bucket) of every prefill program JAX's log says it has traced."""
    found = set()
    for line in servers.compile_lines(server.log_text()):
        m = _PREFILL.match(line)
        if m:
            found.add((int(m.group(1)), int(m.group(2))))
    return found


def warm_programs(server, config: dict, seed: int) -> None:
    """The engine admits the front run of same-bucket arrivals as one
    prefill program of rows padded to a power of two, so the window can meet
    any (rows, bucket) pair up to `warm_rows`.  While one long request keeps
    the engine cycling (arrivals between two waves are admitted together), a
    burst of `rows` one-token requests per pair brings each program in; the
    server's own log says which programs exist."""
    url = f"{server.base}/v2/models/{server.name}/generate"
    rng = random.Random(f"warm:{seed}")
    buckets = sorted(config["serving"]["prefill_buckets"])
    done = threading.Event()

    def one(tokens: int, max_tokens: int):
        servers.post_json(url, {"text_input": schedule.prompt_text(tokens, rng),
                                "max_tokens": max_tokens, "temperature": 0.0})

    def background():
        while not done.is_set():
            one(buckets[0] // 2, 4 * config["serving"]["steps_per_call"])

    with ThreadPoolExecutor(max_workers=1 + max(config["warm_rows"])) as pool:
        keeper = pool.submit(background)
        try:
            for bucket in buckets:
                for rows in config["warm_rows"]:
                    for _ in range(8):
                        if (rows, bucket) in compiled_prefills(server):
                            break
                        burst = [pool.submit(one, bucket - 1, 1)
                                 for _ in range(rows)]
                        for f in burst:
                            f.result()
                    else:
                        raise BenchFailure(
                            f"could not bring in prefill program rows={rows} "
                            f"bucket={bucket}: have "
                            f"{sorted(compiled_prefills(server))}")
        finally:
            done.set()
            keeper.result()


# -- the cell ----------------------------------------------------------------
def plan_for(run: dict, server) -> dict:
    traffic, seconds = run["traffic"], run["seconds"]
    plan = {"url": server.base,
            "path": f"/v2/models/{server.name}/generate_stream",
            "loop": traffic["loop"], "seconds": seconds, "slice_s": 5.0,
            "server_log": server.log_path,
            "out": os.path.join(servers.WORK, "runs",
                                f"{run['cell']['name']}.json"),
            "trace": None}
    if traffic["loop"] == "closed":
        plan.update(clients=traffic["clients"],
                    stagger_s=traffic["stagger_s"],
                    warm_rounds=traffic["warm_rounds"],
                    # the generator draws `schedule.closed_stream` itself:
                    # the list has no end, so it cannot be written here
                    traffic=traffic, seed=run["seed"])
    else:
        plan.update(tail_s=traffic["tail_s"],
                    requests=schedule.open_requests(
                        traffic, run["seed"], seconds))
    if run["trace"]:
        trace_dir = os.path.join(servers.WORK, "trace", run["cell"]["name"])
        plan["trace"] = {"seconds": min(run["config"]["trace_s"], seconds),
                         "log_dir": trace_dir}
    os.makedirs(os.path.dirname(plan["out"]), exist_ok=True)
    return plan


def measure(run: dict) -> dict:
    """Spawn to end of window.  `run` holds cell, config, traffic, seed,
    seconds, trace, platform and t_start; returns the generator's output plus
    the server-side facts the readers need."""
    config = run["config"]
    phases = {}
    mark = run["t_start"]

    def phase(name: str) -> None:
        nonlocal mark
        t = time.monotonic()
        phases[name] = t - mark
        mark = t

    server = servers.Server(config["server_module"], config["name"],
                            config["serving"], config["name"])
    try:
        device = server.device(run["platform"], run["cell"]["chips"])
        phase("spawn_s")
        server.wait_ready()
        phase("load_s")
        startup = server.get_json("/startup_phases")

        cases = served_answers(server, check_prompts(run["traffic"],
                                                     run["seed"]))
        phase("check_requests_s")
        # The reference computes on the CPU while the programs are brought
        # in; both end before the load generator starts.
        with ThreadPoolExecutor(max_workers=1) as beside:
            reference = beside.submit(reference_answers, config, cases)
            warm_programs(server, config, run["seed"])
            phase("programs_s")
            answers, ref_s = reference.result()
        gaps = reference_gaps(cases, answers)
        phase("reference_wait_s")

        plan = plan_for(run, server)
        plan_path = plan["out"] + ".plan"
        with open(plan_path, "w") as f:
            json.dump(plan, f)
        if plan["trace"]:
            shutil.rmtree(plan["trace"]["log_dir"], ignore_errors=True)
        generator = subprocess.Popen(
            [sys.executable, "-m", "chipbench.loadgen", plan_path],
            cwd=servers.ROOT, env=servers.child_env(config["name"]))
        try:
            rc = generator.wait(timeout=run["seconds"] + 600)
        finally:
            servers.stop(generator)
        if rc != 0:
            raise BenchFailure(f"the load generator exited {rc}")
        with open(plan["out"]) as f:
            out = json.load(f)
        device_after = server.get_json("/v2")["device"]
        window_log = server.log_text(out["log_offsets"]["open"],
                                     out["log_offsets"]["close"])
    finally:
        server.close()

    phases["traffic_before_window_s"] = out["window"][0] - mark
    out.update(
        device=device, device_after=device_after, startup=startup,
        setup_phases=phases, setup_s=out["window"][0] - run["t_start"],
        reference={"gap": max(gaps), "gap_median": statistics.median(gaps),
                   "limits": reference_limits(config),
                   "tolerance": config["reference"]["tolerance"],
                   "seconds": ref_s, "prompts": len(cases)},
        compiles_in_window=servers.compile_lines(window_log),
        trace_dir=plan["trace"]["log_dir"] if plan["trace"] else None)
    return out


def observe(run: dict) -> None:
    """Earlier lines of a generate cell: throughput, slot occupancy and pool
    fill by slice of the window, which is where a straying run shows."""
    model = run["config"]["name"]
    scrapes = sorted([run["scrapes"]["open"], run["scrapes"]["close"]]
                     + run["slice_scrapes"], key=lambda s: s["t"])

    def counters(scrape):
        def read(name):
            return prom.sample(scrape["metrics"], name, model=model) or 0.0
        steps = read("kfserving_tpu_engine_token_steps")
        return (steps * read("kfserving_tpu_engine_slot_occupancy"), steps,
                read("kfserving_tpu_generator_pool_occupancy_ratio"))

    for a, b in zip(scrapes, scrapes[1:]):
        piece = (a["t"], b["t"])
        (occ_a, steps_a, _), (occ_b, steps_b, fill) = counters(a), counters(b)
        steps = steps_b - steps_a
        log(f"slice from {a['t'] - run['window'][0]:5.1f}s: "
            f"{stats.tokens_in_window(run['records'], piece) / (b['t'] - a['t']):8.2f} tokens/s, "
            f"{sum(1 for r in run['records'] if r['ok'] and stats.in_window(r['last'], piece))} finished, "
            f"{steps:.0f} steps, slots "
            f"{100 * (occ_b - occ_a) / steps if steps else float('nan'):.1f}% "
            f"occupied, pool {100 * fill:.1f}% full")
