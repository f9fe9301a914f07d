"""Device-only model step timing: what the chip does with dispatch,
transfer and the host taken out of the loop.

The engine stats measure dispatch->host-visible-result, which includes
a runtime round trip per batch — a floor on wall MFU but not a
statement about the silicon.  This tool measures the flagship models
the way the attention kernels were measured: K model steps chained
inside one on-device ``lax.fori_loop`` with an explicit data dependency
between iterations, timed at K=1 and K=N.  The per-step device time is

    (t_N - t_1) / (N - 1)

which cancels dispatch, transfer, and the single sync.

The chain dependency is a zero-scaled scalar folded back into the input
(x + 0*mean(logits)): XLA cannot DCE or reorder the steps, and the
added work is one reduction + broadcast per step (noise at these
FLOP counts).

Usage:  python -m benchmarks.device_roofline [--model resnet50|bert]
Prints one JSON line per (model, batch) with ms/step, TF/s, and MFU
against the chip's bf16 peak.
"""

import argparse
import json
import time

import numpy as np


def _flops_of(jitted, params, x) -> float:
    """XLA cost-model FLOPs for one step (same source the engine stats
    use, engine/jax_engine.py:303-321)."""
    lowered = jitted.lower(params, x)
    analysis = lowered.cost_analysis()
    if not analysis:
        analysis = lowered.compile().cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else {}
    return float((analysis or {}).get("flops", 0.0))


def _chain_dep(out, v):
    """Fold a model output into the next step's input without changing
    its value at runtime and without being eliminable at compile time.

    NOT `0.0 * sum(out)`: for integer inputs the int-cast zero is a
    valid strength reduction and XLA deletes the whole model (measured:
    a "4098 TF/s BERT" = 20x chip peak).  And not plain `sum(out)`
    either: a reduce-sum of a matmul factors through it
    (sum(A@B) = sum_k(sum_i A)_k (sum_j B)_k), which let XLA skip
    BERT's 96-GFLOP vocab projection (measured 105% "MFU").  The
    squared sum consumes every output element irreducibly; scaled by
    1e-30 it is a non-constant float the simplifier cannot prove zero —
    its int cast truncates to 0 and its float add is far below one ulp
    of any activation, both only at runtime."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(out)
    dep = sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
              for l in leaves) * 1e-30

    def inject(a):
        if jnp.issubdtype(a.dtype, jnp.floating):
            return a + dep.astype(a.dtype)
        return a + dep.astype(jnp.int32).astype(a.dtype)

    if isinstance(v, dict):
        return {k: inject(a) for k, a in v.items()}
    return inject(v)


def _fetch_probe(v):
    """Reduce a chain carry to one f32 scalar whose value depends on
    every element — fetching it joins the device timeline at ~zero
    transfer cost regardless of carry size."""
    import jax
    import jax.numpy as jnp

    leaves = jax.tree.leaves(v)
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
               for l in leaves)


def dispatch_chained_step_time(apply_fn, params, x, n: int = 24,
                               reps: int = 3) -> dict:
    """Host-chained variant for models whose fori_loop chain is too
    large a program to compile (BERT-base): issue K
    async dispatches where each step's input carries a data dependency
    on the previous output, sync once at the end.  The device executes
    the queue back-to-back, so (t_K - t_1)/(K-1) still cancels the
    single round trip and dispatch tail."""
    import jax

    def step(p, v):
        return _chain_dep(apply_fn(p, v), v)

    jstep = jax.jit(step)
    probe = jax.jit(_fetch_probe)

    def run(k):
        # Sync via a tiny scalar D2H fetch: it transfers 4 bytes and
        # depends on every chained step.
        v = x
        for _ in range(k):
            v = jstep(params, v)
        np.asarray(probe(v))

    run(2)  # compile + queue warm
    per_step = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run(1)
        t1 = time.perf_counter()
        run(n)
        t2 = time.perf_counter()
        per_step.append(((t2 - t1) - (t1 - t0)) / (n - 1))
    per_step.sort()
    return {"sec_per_step": per_step[len(per_step) // 2],
            "t1_sec": t1 - t0, "n": n, "method": "dispatch-chain"}


def chained_step_time(apply_fn, params, x, n: int = 12,
                      reps: int = 3) -> dict:
    """Median of `reps` (t_n - t_1)/(n-1) measurements, seconds/step."""
    import jax

    def chain(k):
        def body(_, carry):
            return _chain_dep(apply_fn(params, carry), carry)

        # Scalar-probe output: the fetch that times the run transfers 4
        # bytes but depends on every chained step.
        return jax.jit(
            lambda p, v: _fetch_probe(jax.lax.fori_loop(0, k, body, v)))

    f1 = chain(1)
    fn = chain(n)
    # compile both
    np.asarray(f1(params, x))
    np.asarray(fn(params, x))
    per_step = []
    for _ in range(reps):
        t0 = time.perf_counter()
        np.asarray(f1(params, x))
        t1 = time.perf_counter()
        np.asarray(fn(params, x))
        t2 = time.perf_counter()
        per_step.append(((t2 - t1) - (t1 - t0)) / (n - 1))
    per_step.sort()
    return {"sec_per_step": per_step[len(per_step) // 2],
            "t1_sec": t1 - t0, "n": n, "method": "fori-chain"}


def measure(model_name: str, batches, seq=None, method="auto") -> list:
    import jax

    from kfserving_tpu.engine.jax_engine import device_peak_flops
    from kfserving_tpu.models import registry

    if model_name == "resnet50":
        spec = registry.create_model("resnet50")
        make_x = lambda b: np.random.default_rng(0).normal(
            size=(b, 224, 224, 3)).astype(np.float32)
    elif model_name == "bert":
        spec = registry.create_model("bert")
        make_x = lambda b: np.random.default_rng(0).integers(
            1, 1000, size=(b, seq or 128)).astype(np.int32)
    else:
        raise SystemExit(f"unknown model {model_name}")
    params = registry.init_params(spec)
    apply_fn = registry.apply_fn_for(spec)
    jitted = jax.jit(apply_fn)
    peak = device_peak_flops()
    rows = []
    for b in batches:
        x = jax.device_put(make_x(b))
        flops = _flops_of(jitted, params, x)
        if method == "dispatch":
            t = dispatch_chained_step_time(apply_fn, params, x)
        else:
            try:
                t = chained_step_time(apply_fn, params, x)
            except Exception as exc:  # chain too big for remote compile
                print(f"# fori chain failed ({type(exc).__name__}); "
                      "falling back to dispatch chain", flush=True)
                t = dispatch_chained_step_time(apply_fn, params, x)
        sec = t["sec_per_step"]
        tf_s = flops / sec / 1e12 if sec > 0 else None
        row = {"model": model_name, "batch": b,
               "seq": seq if model_name == "bert" else None,
               "method": t.get("method", "fori-chain"),
               "ms_per_step": round(sec * 1e3, 3),
               "ms_per_item": round(sec * 1e3 / b, 4),
               "flops_per_step": flops,
               "tflops_per_s": round(tf_s, 2) if tf_s else None,
               "mfu": round(flops / sec / peak, 4) if peak and sec > 0
               else None,
               "t1_wall_ms": round(t["t1_sec"] * 1e3, 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="all",
                    choices=["resnet50", "bert", "all"])
    ap.add_argument("--batches", default="32,64,128,256")
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--method", default="auto",
                    choices=["auto", "dispatch"])
    args = ap.parse_args()
    batches = [int(b) for b in args.batches.split(",")]
    out = []
    if args.model in ("resnet50", "all"):
        out += measure("resnet50", batches, method=args.method)
    if args.model in ("bert", "all"):
        out += measure("bert", batches, seq=args.seq,
                       method=args.method)
    # Merge with prior invocations (partial runs build the table up).
    try:
        with open("DEVICE_ROOFLINE.json") as f:
            prior = json.load(f)
    except Exception:
        prior = []
    key = lambda r: (r["model"], r["batch"], r.get("seq"))
    merged = {key(r): r for r in prior}
    merged.update({key(r): r for r in out})
    with open("DEVICE_ROOFLINE.json", "w") as f:
        json.dump(sorted(merged.values(),
                         key=lambda r: (r["model"], r["batch"])), f, indent=2)


if __name__ == "__main__":
    main()
