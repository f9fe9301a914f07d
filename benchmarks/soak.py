"""Sustained-load soak with process recycling (VERDICT r2 weak #5).

Orchestrator-level process recycling bounds a replica whose RSS grows
under sustained load.  This drives the full stack end-to-end:

  load gen -> IngressRouter -> subprocess replica (owns the TPU) ->
  RecyclePolicy watchdog -> warm-standby swap (spawn -> mmap-param
  activate while the incumbent serves -> drain) -> router announced-
  swap holds carry any residual gap.

ISSUE 10 made the warm standby the DEFAULT lifecycle: the successor
activates off the mmap param cache while the incumbent still serves,
so the swap window is 0 by construction; `--exclusive` measures the
exclusive-device ordering (drain -> activate inside an announced
window the router bridges by holding, not shedding).

Success = RSS stays bounded by the policy across >=1 recycle, client
sees no failed requests, and the committed swap_breakdown shows where
every swap's milliseconds went (standby_spawn / activate / drain, plus
the successor's own boot marks — params_mmap on a cache hit).

Usage: python -m benchmarks.soak [--minutes 6] [--qps 60]
       [--max-rss-mb 4096] [--exclusive] [--smoke]
Writes SOAK.json.
"""

import argparse
import asyncio
import json
import os
import tempfile
import time


def _registry_series(substr: str) -> dict:
    """Samples of every registry series whose name contains `substr`
    (the soak runs router + orchestrator in-process, so their counters
    are readable without a scrape)."""
    from kfserving_tpu.observability import REGISTRY

    out = {}
    for line in REGISTRY.render_lines():
        if line.startswith("#") or substr not in line:
            continue
        try:
            series, value = line.rsplit(" ", 1)
            out[series] = float(value)
        except ValueError:
            continue
    return out


async def run_soak(minutes: float, qps: float, max_rss_mb: float,
                   smoke: bool, max_requests: int = None,
                   buffer_deadline_s: float = 15.0,
                   exclusive: bool = False) -> dict:
    import aiohttp
    import numpy as np

    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import InferenceService, PredictorSpec
    from kfserving_tpu.control.subprocess_orchestrator import (
        RecyclePolicy,
        SubprocessOrchestrator,
        _proc_rss_mb,
    )
    from kfserving_tpu.protocol import v2 as v2proto

    model_dir = tempfile.mkdtemp(prefix="soak-")
    if smoke:
        cfg = {"architecture": "mlp",
               "arch_kwargs": {"input_dim": 64, "features": [128],
                               "num_classes": 10},
               "max_batch_size": 16, "max_latency_ms": 5.0,
               "warmup": True, "output": "argmax"}
        image = np.random.default_rng(0).normal(size=(1, 64)) \
            .astype(np.float32)
    else:
        cfg = {"architecture": "resnet50", "max_batch_size": 128,
               "batch_buckets": [16, 32, 64, 128], "pipeline_depth": 3,
               "max_latency_ms": 15.0, "warmup": True,
               "input_dtype": "uint8", "scale": 1.0 / 255.0,
               "output": "argmax"}
        image = np.random.default_rng(0).integers(
            0, 256, size=(1, 224, 224, 3)).astype(np.uint8)
    with open(os.path.join(model_dir, "config.json"), "w") as f:
        json.dump(cfg, f)
    body, hlen = v2proto.make_binary_request({"input_0": image})

    env = {"JAX_PLATFORMS": "cpu"} if smoke else {}
    orch = SubprocessOrchestrator(
        env_overrides=env,
        recycle=RecyclePolicy(max_rss_mb=max_rss_mb,
                              max_requests=max_requests,
                              check_interval_s=2.0 if smoke else 5.0,
                              exclusive_device=exclusive,
                              min_age_s=10.0 if smoke else 30.0))
    controller = Controller(orch)
    router = IngressRouter(controller, upstream_timeout_s=180.0,
                           buffer_deadline_s=buffer_deadline_s)
    await router.start_async()
    results = {"ok": 0, "fail": 0, "statuses": {}}
    rss_samples = []
    lat = []

    async def one(session, sem):
        async with sem:
            t0 = time.perf_counter()
            try:
                async with session.post(
                        f"http://127.0.0.1:{router.http_port}"
                        "/v2/models/soak/infer", data=body,
                        headers={"Inference-Header-Content-Length":
                                 str(hlen)}) as resp:
                    await resp.read()
                    st = resp.status
            except Exception as e:
                st = f"exc:{type(e).__name__}"
            lat.append((time.perf_counter() - t0) * 1e3)
            key = str(st)
            results["statuses"][key] = results["statuses"].get(key, 0) + 1
            if st == 200:
                results["ok"] += 1
            else:
                results["fail"] += 1

    async def sampler():
        while True:
            await asyncio.sleep(5.0)
            reps = orch.replicas("default/soak/predictor")
            if reps and reps[0].handle:
                # kfslint: disable=async-blocking — /proc reads are
                # RAM-backed (same waiver as the recycle watchdog's).
                rss = _proc_rss_mb(reps[0].handle.process.pid)
                if rss is not None:
                    rss_samples.append(
                        {"t": round(time.perf_counter() - t_start, 1),
                         "rss_mb": round(rss, 0),
                         "recycles": orch.recycle_count})

    try:
        isvc = InferenceService(
            name="soak",
            predictor=PredictorSpec(framework="jax",
                                    storage_uri=f"file://{model_dir}"))
        await controller.apply(isvc)
        t_start = time.perf_counter()
        samp = asyncio.ensure_future(sampler())
        interval = 1.0 / qps
        deadline = t_start + minutes * 60.0
        tasks = []
        # Bounded client concurrency: during a swap window requests
        # buffer in the router; without a cap the open loop would pile
        # thousands of sockets.
        sem = asyncio.Semaphore(256)
        timeout = aiohttp.ClientTimeout(total=180.0)
        async with aiohttp.ClientSession(timeout=timeout) as session:
            i = 0
            while time.perf_counter() < deadline:
                tasks.append(asyncio.ensure_future(one(session, sem)))
                i += 1
                next_t = t_start + i * interval
                delay = next_t - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
            await asyncio.gather(*tasks)
        samp.cancel()
        lat.sort()
        from benchmarks.harness import percentile

        windows_ms = sorted(w * 1000.0 for w in orch.swap_windows_s)
        return {
            "minutes": minutes, "qps": qps, "max_rss_mb": max_rss_mb,
            "max_requests": max_requests,
            "buffer_deadline_s": buffer_deadline_s,
            "mode": "exclusive_standby" if exclusive
                    else "warm_standby",
            "requests": results["ok"] + results["fail"],
            "ok": results["ok"], "fail": results["fail"],
            "statuses": results["statuses"],
            "recycles": orch.recycle_count,
            "promotions": orch.promotions,
            "swap_failures": orch.swap_failures,
            # Unavailability gap per swap: warm-standby swaps are 0 by
            # construction (successor entered rotation before the
            # incumbent drained); the exclusive mode measures
            # chip-release -> successor-serving.
            "swap_windows_s": list(orch.swap_windows_s),
            "swap_window_p99_ms": (round(percentile(windows_ms, 0.99),
                                         1) if windows_ms else None),
            "swap_breakdown": list(orch.swap_breakdown),
            # Announced-swap holds the router absorbed instead of
            # shedding (and the param-cache outcomes of every replica
            # boot this run spawned, scraped from the successors).
            "router_swap_holds": _registry_series(
                "router_swap_held_total"),
            "p50_ms": round(percentile(lat, 0.5), 1) if lat else None,
            "p99_ms": round(percentile(lat, 0.99), 1) if lat else None,
            "max_ms": round(lat[-1], 1) if lat else None,
            "rss_timeline": rss_samples,
            "rss_peak_mb": max((s["rss_mb"] for s in rss_samples),
                               default=None),
        }
    finally:
        await router.stop_async()
        await orch.shutdown()


def main():
    import logging
    import sys

    # Recycle decisions and swap windows are INFO-level; the soak's
    # record must show them (a silent watchdog is indistinguishable
    # from a healthy no-trigger run otherwise).
    logging.basicConfig(level=logging.INFO, stream=sys.stderr)
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=6.0)
    ap.add_argument("--qps", type=float, default=60.0)
    ap.add_argument("--max-rss-mb", type=float, default=4096.0)
    ap.add_argument("--max-requests", type=int, default=None,
                    help="recycle every N served requests (deterministic "
                         ">=2 swaps per soak)")
    ap.add_argument("--buffer-deadline-s", type=float, default=15.0)
    ap.add_argument("--exclusive", action="store_true",
                    help="exclusive-device ordering: drain -> activate "
                         "inside an announced window the router holds "
                         "across (default: warm standby — activate "
                         "BEFORE drain, zero-gap)")
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    out = asyncio.run(run_soak(args.minutes, args.qps, args.max_rss_mb,
                               args.smoke, args.max_requests,
                               args.buffer_deadline_s,
                               exclusive=args.exclusive))
    with open("SOAK.json", "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out))


if __name__ == "__main__":
    # Direct-script invocation (`python benchmarks/soak.py`) puts
    # benchmarks/ itself on sys.path, breaking the in-function
    # `from benchmarks.harness import ...` — add the repo root so both
    # that and `python -m benchmarks.soak` work.
    import sys

    sys.path.insert(0, os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    main()
