"""chip_smoke.py — the quickest proof that the serving path still starts on
the chip.

    python chip_smoke.py                one chip: predict, generate, kernels
    python chip_smoke.py --four-chips   four chips: sharded model, replicas

This process never imports JAX: a chip belongs to one process at a time, so
the parent starts the real servers (`python -m ...jaxserver` / `...llmserver`)
as children, one at a time, each owning the chip and gone before the next
starts, and talks to them over HTTP as a client would.  Weights are the
loaders' seeded random init (a model directory holds `config.json` only).

Every phase checks answers, not status codes, and any failure — a non-200, a
wrong count, a child that exits, a mismatch, a timeout — raises: non-zero
exit, no result line.  The device in the last line is the one the serving
children named themselves (`startup.report_device`); a platform other than
the expected one fails the run before a model is loaded.

The phases are functions of (model config, expected platform); the command
line fixes them to full width and "tpu".  Tests rehearse them at toy size on
the CPU by calling the functions.  Lines before the last are observations of
a smoke run, never benchmark numbers.
"""

import argparse
import contextlib
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, ".chip_smoke")  # git-ignored: model dirs, logs
READY_TIMEOUT_S = 600.0

# -- the configurations the command line runs --------------------------------
# ResNet-50, BASELINE config 2; batch buckets cut to two so cold
# compile stays bounded, raw logits out for the check.
RESNET50 = {
    "architecture": "resnet50",
    "max_batch_size": 32, "batch_buckets": [8, 32],
    "pipeline_depth": 3, "max_latency_ms": 15.0, "warmup": True,
    "input_dtype": "uint8", "scale": 1.0 / 255.0, "output": "logits",
}
# GPT-2-small widths and 4,096 positions over a pool of 128-token blocks.
DECODER = {
    "architecture": "decoder",
    "arch_kwargs": {"vocab_size": 32000, "hidden_size": 768,
                    "num_layers": 12, "num_heads": 12,
                    "intermediate_size": 3072, "max_seq": 4096},
    "max_slots": 8, "max_seq": 4096, "prefill_buckets": [512, 4096],
    "block_size": 128, "cache_blocks": 112, "steps_per_call": 16,
    "tokenizer": "byte",
}
# bf16 compute against a float32 reference, as max|a-b| / max|b|.  ResNet-50
# measured 0.0065 bf16-vs-f32 on one backend (CPU); the bound leaves room for
# a second backend's rounding and stays below the spread between inputs.
LOGITS_REL_TOL = 0.03
# Kernel against its XLA formulation on bf16 inputs, max abs error.  Outputs
# are O(1) averages of unit-normal values; bf16 has 8 bits of mantissa.
KERNEL_ABS_TOL = 0.05
# First-step log-probability, tp=4 against one chip: sharding only reorders
# bf16 partial sums.
LOGPROB_ABS_TOL = 0.05


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(AssertionError):
    """A phase found something wrong; the run ends non-zero."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


# -- children ------------------------------------------------------------------
def child_env(**extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = (ROOT + os.pathsep
                         + env.get("PYTHONPATH", "")).rstrip(os.pathsep)
    # Seeded params are materialized once and mapped by every later child
    # (the CPU reference included): same bytes, by construction.
    env["KFS_PARAM_CACHE"] = os.path.join(WORK, "params")
    env.update(extra)
    return env


def write_model_dir(name: str, config: dict) -> str:
    path = os.path.join(WORK, "models", name)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f)
    return path


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def stop(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=20)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=20)


def run_child(code: str, args, env, timeout_s: float, tag: str) -> str:
    """Run `python -c code *args` from the repo root to its end; its
    stdout.  Non-zero exit fails the run with the end of its output."""
    proc = subprocess.run(
        [sys.executable, "-c", code, *args], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=timeout_s)
    if proc.returncode != 0:
        raise SmokeFailure(
            f"{tag} child exited {proc.returncode}:\n"
            f"{proc.stdout[-2000:]}\n{proc.stderr[-4000:]}")
    return proc.stdout


def http(method: str, url: str, body: bytes = None, headers: dict = None,
         timeout_s: float = 600.0):
    """(body bytes, headers).  urllib raises on any non-2xx status."""
    req = urllib.request.Request(url, data=body, method=method,
                                 headers=headers or {})
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        return resp.read(), resp.headers


def post_json(url: str, payload: dict) -> dict:
    body, _ = http("POST", url, json.dumps(payload).encode(),
                   {"content-type": "application/json"})
    return json.loads(body)


class Server:
    """One chip-owning server child: `python -m <module>` on a free port,
    its output in a log file the smoke reads back."""

    def __init__(self, module: str, name: str, config: dict, env: dict):
        self.name = name
        self.port = free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = os.path.join(WORK, "logs", f"{name}.log")
        os.makedirs(os.path.dirname(self.log_path), exist_ok=True)
        self._log = open(self.log_path, "w")
        self.started = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, "-m", module, "--model_name", name,
             "--model_dir", write_model_dir(name, config),
             "--http_port", str(self.port)],
            cwd=ROOT, env=env, stdout=self._log, stderr=subprocess.STDOUT)

    def log_text(self) -> str:
        with open(self.log_path, errors="replace") as f:
            return f.read()

    def _wait(self, what: str, probe, timeout_s: float):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeFailure(
                    f"{self.name} exited {self.proc.returncode} before "
                    f"{what}:\n{self.log_text()[-4000:]}")
            found = probe()
            if found is not None:
                return found
            time.sleep(0.25)
        raise SmokeFailure(f"{self.name}: no {what} within {timeout_s:.0f}s"
                           f":\n{self.log_text()[-4000:]}")

    def device(self, platform: str) -> dict:
        """The device the child named at start-up, before its model loads;
        another platform than `platform` fails here, in seconds."""
        def probe():
            for line in self.log_text().splitlines():
                _, mark, record = line.partition("kfserving_tpu.startup:device ")
                if mark:
                    return json.loads(record)
            return None

        device = self._wait("device report", probe, 120.0)
        check(device["platform"] == platform,
              f"{self.name} holds platform {device['platform']!r}, "
              f"expected {platform!r}: {device}")
        return device

    def wait_ready(self) -> float:
        """Seconds from spawn until the health route answers ready."""
        def probe():
            try:
                body, _ = http("GET", f"{self.base}/v1/models/{self.name}",
                               timeout_s=5.0)
            except OSError:
                return None
            return True if json.loads(body).get("ready") else None

        self._wait("ready", probe, READY_TIMEOUT_S)
        return time.monotonic() - self.started

    def metadata_device(self) -> dict:
        body, _ = http("GET", f"{self.base}/v2")
        return json.loads(body)["device"]

    def metrics(self) -> str:
        body, _ = http("GET", f"{self.base}/metrics")
        return body.decode()

    def close(self) -> None:
        stop(self.proc)
        self._log.close()


@contextlib.contextmanager
def serving(module: str, name: str, config: dict, platform: str,
            env: dict = None):
    """Start a server child, refuse a wrong platform, wait on its health
    route; always stop it on the way out."""
    server = Server(module, name, config, env or child_env())
    try:
        device = server.device(platform)
        ready_s = server.wait_ready()
        log(f"{name}: device {json.dumps(device)}")
        log(f"{name}: ready after {ready_s:.1f}s (load + cold compile, "
            f"smoke observation)")
        yield server, device
    finally:
        server.close()


def gauge(metrics_text: str, name: str, **labels) -> float:
    """One sample of a Prometheus text exposition."""
    for line in metrics_text.splitlines():
        if line.startswith(name) and all(
                f'{k}="{v}"' in line for k, v in labels.items()):
            return float(line.rsplit(" ", 1)[1])
    raise SmokeFailure(f"/metrics has no {name}{labels}")


def moved(before: str, after: str, metric: str, model: str) -> float:
    """How far a model's sample moved between two /metrics scrapes."""
    return gauge(after, metric, model=model) - gauge(before, metric,
                                                     model=model)


def attention_paths(log_text: str):
    """The dispatchers' once-per-program lines, as
    [(path, query shape, the line's shapes), ...]."""
    return [(m.group(1), tuple(int(n) for n in m.group(3).split(",")),
             m.group(2))
            for m in re.finditer(r"attention path=(\w+) (q=\(([^)]*)\).*)",
                                 log_text)]


def check_device_record(device: dict, platform: str) -> None:
    """On a chip the runtime must answer: memory and the engine's peaks."""
    if platform != "tpu":
        return
    check(all(device["hbm_bytes"]), f"memory_stats() gave no HBM size: {device}")
    check(device["peak_flops"], f"no peak FLOP/s for {device['kind']!r}")
    check(device["peak_hbm_bw"], f"no peak HBM bandwidth for {device['kind']!r}")


# -- phase: predict ------------------------------------------------------------
REFERENCE_CHILD = """
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from kfserving_tpu.engine import param_cache
from kfserving_tpu.models import ModelSpec, apply_fn_for, create_model

config, batch_path, out_path = json.loads(sys.argv[1]), sys.argv[2], sys.argv[3]
assert jax.devices()[0].platform == "cpu", jax.devices()
kwargs = config.get("arch_kwargs") or {}
served = create_model(config["architecture"], **kwargs)
variables, source = param_cache.load_or_materialize(
    config["architecture"], kwargs, served, "/nonexistent")
reference = ModelSpec(served.module.clone(dtype=jnp.float32), served.example)
x = jnp.asarray(np.load(batch_path)).astype(jnp.float32) * config["scale"]
with jax.default_matmul_precision("highest"):
    logits = apply_fn_for(reference)(variables, x)
np.save(out_path, np.asarray(logits, np.float32))
print("reference params:", source)
"""


def phase_predict(config: dict, platform: str, batch: np.ndarray) -> dict:
    """jaxserver: V1 JSON and V2 binary predicts, a coalesced burst, and
    logits against a float32 CPU forward of the same seeded parameters."""
    from kfserving_tpu.protocol import native, v2

    check(native.build(), "building csrc/tensorjson.c failed")
    name = "smoke-predict"
    os.makedirs(WORK, exist_ok=True)
    batch_path = os.path.join(WORK, "predict_batch.npy")
    ref_path = os.path.join(WORK, "predict_reference.npy")
    np.save(batch_path, batch)
    with ThreadPoolExecutor(max_workers=1) as pool, \
            serving("kfserving_tpu.predictors.jaxserver", name, config,
                    platform) as (server, device):
        # The reference runs beside the server, in a child pinned to the
        # CPU platform: it never asks for the chip.
        reference = pool.submit(
            run_child, REFERENCE_CHILD,
            [json.dumps(config), batch_path, ref_path],
            child_env(JAX_PLATFORMS="cpu"), 600.0, "reference")
        check_device_record(device, platform)

        v1_out = post_json(f"{server.base}/v1/models/{name}:predict",
                           {"instances": batch.tolist()})
        v1_logits = np.asarray(v1_out["predictions"], np.float32)

        body, header_len = v2.make_binary_request({"input_0": batch},
                                                  binary_output=True)
        raw, headers = http(
            "POST", f"{server.base}/v2/models/{name}/infer", body,
            {"Inference-Header-Content-Length": str(header_len)})
        v2_out = v2.decode_binary_response(
            raw, int(headers["Inference-Header-Content-Length"]))
        v2_logits = v2_out["outputs"][0]["data"]

        # A burst of single-instance requests: the batcher must coalesce.
        before = server.metrics()
        burst = 4 * len(batch)
        with ThreadPoolExecutor(max_workers=burst) as clients:
            answers = list(clients.map(
                lambda i: post_json(
                    f"{server.base}/v1/models/{name}:predict",
                    {"instances": [batch[i % len(batch)].tolist()]}),
                range(burst)))
        after = server.metrics()
        flushed = moved(before, after,
                        "kfserving_tpu_engine_batches_flushed", name)
        batched = moved(before, after,
                        "kfserving_tpu_engine_instances_batched", name)
        check(batched == burst and flushed < burst,
              f"burst of {burst} was not coalesced: {batched:.0f} instances "
              f"in {flushed:.0f} batches")
        burst_logits = np.asarray(
            [a["predictions"][0] for a in answers], np.float32)

        log_text = server.log_text()
        check("tensorjson codec=native" in log_text,
              "the native codec did not serve the V1 wire:\n"
              + "\n".join(l for l in log_text.splitlines()
                          if "tensorjson" in l))
        in_use = server.metadata_device()["hbm_in_use"]
        if platform == "tpu":
            check(all(in_use), f"memory_stats() gave no HBM in use: {in_use}")
        log(f"predict: {reference.result().strip()}")

    ref = np.load(ref_path)
    check(ref.shape == (len(batch), ref.shape[1]), f"reference {ref.shape}")
    scale = float(np.abs(ref).max())
    for wire, got in (("v1-json", v1_logits), ("v2-binary", v2_logits),
                      ("burst", burst_logits)):
        want = ref[np.arange(len(got)) % len(batch)]
        check(got.shape == want.shape and np.isfinite(got).all(),
              f"{wire}: logits {got.shape}, finite={np.isfinite(got).all()}")
        rel = float(np.abs(got - want).max()) / scale
        # Each answered row must sit nearest its own reference row: catches
        # a batcher that scrambles or pads rows into each other.
        nearest = np.abs(got[:, None, :] - ref[None, :, :]).max(-1).argmin(-1)
        check(rel <= LOGITS_REL_TOL
              and (nearest == np.arange(len(got)) % len(batch)).all(),
              f"{wire}: logits off the float32 reference: rel err {rel:.4f} "
              f"(bound {LOGITS_REL_TOL}), nearest rows {nearest.tolist()}")
        log(f"predict {wire}: {got.shape} logits, rel err {rel:.5f} vs "
            f"float32 CPU reference (bound {LOGITS_REL_TOL})")
    log(f"predict: codec=native; burst {burst} requests -> {flushed:.0f} "
        f"batches; HBM in use {in_use} bytes")
    return device


# -- phase: generate -----------------------------------------------------------
def generate(server: Server, prompt: str, max_tokens: int) -> dict:
    """One greedy /generate with logprobs; checks count and finiteness and
    returns {"ids": [...], "logprobs": [...], "top": [[...], ...]}."""
    out = post_json(
        f"{server.base}/v2/models/{server.name}/generate",
        {"text_input": prompt, "max_tokens": max_tokens,
         "temperature": 0.0, "logprobs": 5})
    details = out["details"]
    records = details.get("logprobs") or []
    check(details["token_count"] == max_tokens == len(records),
          f"asked {max_tokens} tokens, got {details['token_count']} "
          f"({details['finish_reason']}), {len(records)} logprob records")
    chosen = [r["logprob"] for r in records]
    check(all(math.isfinite(lp) and lp <= 0.0 for lp in chosen),
          f"log-probabilities not finite: {chosen}")
    return {"ids": [r["id"] for r in records], "logprobs": chosen,
            "top": [[t["logprob"] for t in r["top"]] for r in records]}


def smoke_prompts(config: dict):
    """A short prompt (smallest prefill bucket) and a long one (the
    largest; >= 1,024 tokens at full width).  Byte tokenizer: one token
    per character plus BOS."""
    buckets = sorted(config["prefill_buckets"])
    text = "the quick brown fox jumps over the lazy dog. " * 100
    return text[:buckets[0] // 2], text[:buckets[-1] // 2]


def phase_generate(config: dict, platform: str, env: dict = None) -> dict:
    """llmserver: short and long prompts, four concurrent generates, one
    stream, greedy repeatability; the attention path of each program read
    from the server's own log.  Returns the device and what the two
    single prompts produced (the four-chip phase compares them)."""
    name = "smoke-generate"
    short, long = smoke_prompts(config)
    buckets = sorted(config["prefill_buckets"])
    n_tokens = 2 * config["steps_per_call"] + 3  # crosses dispatch chunks
    with serving("kfserving_tpu.predictors.llmserver", name, config,
                 platform, env) as (server, device):
        check_device_record(device, platform)
        t0 = time.monotonic()
        first = generate(server, short, n_tokens)
        short_s = time.monotonic() - t0
        t0 = time.monotonic()
        long_out = generate(server, long, n_tokens)
        long_s = time.monotonic() - t0
        log(f"generate: short prompt ({len(short) + 1} tokens) {short_s:.1f}s,"
            f" long prompt ({len(long) + 1} tokens) {long_s:.1f}s — first "
            f"calls, compile included (smoke observation)")

        again = generate(server, short, n_tokens)
        check(again["ids"] == first["ids"],
              f"two identical greedy requests differ:\n{first['ids']}\n"
              f"{again['ids']}")

        # Four at once must share decode waves: more tokens out than
        # device steps taken.
        before = server.metrics()
        with ThreadPoolExecutor(max_workers=4) as clients:
            list(clients.map(
                lambda i: generate(server, f"request {i}: " + short,
                                   n_tokens), range(4)))
        after = server.metrics()
        tokens = moved(before, after,
                       "kfserving_tpu_engine_tokens_generated", name)
        steps = moved(before, after, "kfserving_tpu_engine_token_steps",
                      name)
        check(tokens == 4 * n_tokens and steps < tokens,
              f"four concurrent generates did not share slots: "
              f"{tokens:.0f} tokens in {steps:.0f} device steps")

        events = []
        raw, headers = http(
            "POST", f"{server.base}/v2/models/{name}/generate_stream",
            json.dumps({"text_input": short, "max_tokens": n_tokens,
                        "temperature": 0.0}).encode(),
            {"content-type": "application/json"})
        check(headers.get("content-type", "").startswith(
            "text/event-stream"), f"stream content-type {headers}")
        for line in raw.decode().splitlines():
            if line.startswith("data: "):
                events.append(json.loads(line[6:]))
        streamed = [e["token"]["id"] for e in events
                    if e.get("token") and e["token"].get("id") is not None]
        check(streamed == first["ids"]
              and events[-1].get("finish_reason") == "length",
              f"stream gave {len(streamed)} tokens, finish "
              f"{events[-1].get('finish_reason')}; greedy ids "
              f"{'match' if streamed == first['ids'] else 'differ'}")

        paths = attention_paths(server.log_text())
        in_use = server.metadata_device()["hbm_in_use"]
        pool_bytes = gauge(server.metrics(),
                           "kfserving_tpu_engine_cache_bytes", model=name)

    # Which attention ran is read from the dispatchers' trace-time lines.
    decode = {path for path, _, _ in paths if path.endswith("_paged")}
    # The kernel reads whole lane tiles: a block, and one heads shard's
    # row of the pool (H*D over tp), are multiples of 128.
    shard_width = (config["arch_kwargs"]["hidden_size"]
                   // config.get("mesh", {}).get("tp", 1))
    want = "pallas_paged" if (platform == "tpu"
                              and config["block_size"] % 128 == 0
                              and shard_width % 128 == 0) \
        else "xla_paged"
    check(decode == {want}, f"decode attention {decode}, expected {want}")
    prefill = [(path, shapes) for path, q, shapes in paths
               if not path.endswith("_paged") and q[1] == buckets[-1]]
    check(prefill, f"no prefill program at bucket {buckets[-1]} in {paths}")
    log(f"generate: decode attention path={want} (in the program that ran)")
    for path, shapes in prefill:
        log(f"generate: prefill[{buckets[-1]}] attention path={path} {shapes}")
    log(f"generate: {n_tokens} tokens per request, finite log-probs, greedy "
        f"repeatable, 1 stream; 4 concurrent: {tokens:.0f} tokens in "
        f"{steps:.0f} device steps; HBM in use {in_use} bytes")
    return {"device": device, "short": first, "long": long_out,
            "hbm_in_use": in_use, "pool_bytes": pool_bytes, "paths": paths}


# -- phase: kernels ------------------------------------------------------------
KERNELS_CHILD = """
import json, sys
import numpy as np
import jax, jax.numpy as jnp
from kfserving_tpu.ops.attention import _xla_attention
from kfserving_tpu.ops.paged_attention import (
    paged_attention_tpu, paged_attention_xla, paged_write,
    paged_write_sharded, pool_shape)
from kfserving_tpu.ops.pallas_attention import flash_attention

shapes, platform = json.loads(sys.argv[1]), sys.argv[2]
dev = jax.devices()
assert dev[0].platform == platform, dev
interpret = platform != "tpu"
if interpret:  # rehearsal off the chip: Pallas interpret mode
    import functools
    from jax.experimental import pallas as pl
    pl.pallas_call = functools.partial(pl.pallas_call, interpret=True)
rng = np.random.default_rng(0)
def normal(*shape):
    return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)
def err(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert a.shape == b.shape and np.isfinite(a).all(), (a.shape, b.shape)
    return float(np.abs(a - b).max())
out = {}
b, h, d = shapes["slots"], shapes["heads"], shapes["head_dim"]
nb, bs, mb = shapes["blocks"], shapes["block_size"], shapes["blocks_per_slot"]
pool = pool_shape(nb, bs, h, d)  # one minor dimension of all heads
q, pk, pv = normal(b, 1, h, d), normal(*pool), normal(*pool)
lengths = rng.integers(1, mb * bs + 1, size=b).astype(np.int32)
lengths[0], lengths[-1] = 1, mb * bs
table = np.full((b, mb), -1, np.int32)
for i, n in enumerate(lengths):
    used = -(-int(n) // bs)
    table[i, :used] = rng.choice(nb, size=used, replace=False)
# Beside the live rows, one the engine has freed (its table row -1, its
# length still counting) and one parked on the position sentinel with its
# prefilled blocks in place: the kernel walks neither, they are zeros.
free, parked = 2 % b, 5 % b
idle_table, idle_lengths = table.copy(), lengths.copy()
idle_table[free], idle_lengths[free] = -1, 3 * mb * bs
idle_table[parked, 2:], idle_lengths[parked] = -1, mb * bs + 1
live = np.setdiff1d(np.arange(b), [free, parked])
batch = (q, pk, pv, jnp.asarray(idle_table), jnp.asarray(idle_lengths))
got = paged_attention_tpu(*batch, interpret=interpret)
out["paged"] = err(got[live], paged_attention_xla(*batch)[live])
assert not np.asarray(got, np.float32)[[free, parked]].any(), "idle rows"
# A narrow pool, 8 query heads on each of 4 KV heads of 128, where one loop
# iteration of the kernel takes 4 blocks of a row: rows whose last chunk is
# full (4, 8 blocks), one block over (5, 9) and ragged (1, 2, 13), a free
# and a parked row between them.
walked = [5, 1, 0, 9, 4, 0, 13, 2, 8]
gmb, gnb = 13, 64
gpool = pool_shape(gnb, bs, 4, 128)
gq, gk, gv = normal(len(walked), 1, 32, 128), normal(*gpool), normal(*gpool)
gtable = np.full((len(walked), gmb), -1, np.int32)
glengths = np.zeros(len(walked), np.int32)
for i, c in enumerate(walked):
    gtable[i, :c] = rng.choice(gnb, size=c, replace=False)
    glengths[i] = (c - 1) * bs + rng.integers(1, bs + 1)
glengths[2], glengths[5] = 3 * gmb * bs, gmb * bs + 1      # free; parked,
gtable[5, :2] = [7, 11]                             # its chunks in place
batch = (gq, gk, gv, jnp.asarray(gtable), jnp.asarray(glengths))
got = paged_attention_tpu(*batch, interpret=interpret)
glive = np.flatnonzero(walked)
out["paged_grouped"] = err(got[glive], paged_attention_xla(*batch)[glive])
assert not np.asarray(got, np.float32)[[2, 5]].any(), "idle rows"
table, lengths = jnp.asarray(table), jnp.asarray(lengths)
# The decode step's write: each row at its length, the kernel against the
# scatter (exact: both only move values).
k_step, v_step = normal(b, h, d), normal(b, h, d)
at = lengths - 1
want = paged_write(pk, pv, k_step, v_step, table, at)
got = paged_write_sharded(pk, pv, k_step, v_step,
                          table[jnp.arange(b), at // bs], at % bs,
                          interpret=interpret)
out["paged_write"] = max(err(got[0], want[0]), err(got[1], want[1]))
L = shapes["prefill"]
q, k, v = normal(1, L, h, d), normal(1, L, h, d), normal(1, L, h, d)
causal = jnp.tril(jnp.ones((L, L), jnp.bool_))[None, None]
out["flash_causal"] = err(flash_attention(q, k, v, causal=True),
                          _xla_attention(q, k, v, causal))
n = jnp.asarray([L - L // 3], jnp.int32)
pad = (jnp.arange(L)[None, :] < n[:, None])[:, None, None, :]
real = int(n[0])
out["flash_kv_lengths"] = err(
    flash_attention(q, k, v, kv_lengths=n)[:, :real],
    _xla_attention(q, k, v, pad)[:, :real])
print("KERNELS " + json.dumps({
    "errors": out, "device": {"platform": dev[0].platform,
                              "kind": dev[0].device_kind,
                              "count": len(dev)}}))
"""


def kernel_shapes(config: dict) -> dict:
    """The shapes the generate phase serves with `config`."""
    arch = config["arch_kwargs"]
    return {"slots": config["max_slots"], "heads": arch["num_heads"],
            "head_dim": arch["hidden_size"] // arch["num_heads"],
            "blocks": config["cache_blocks"],
            "block_size": config["block_size"],
            "blocks_per_slot": config["max_seq"] // config["block_size"],
            "prefill": max(config["prefill_buckets"])}


def phase_kernels(config: dict, platform: str) -> dict:
    """The Pallas kernels against their XLA formulations on seeded bf16
    inputs at the served shapes, in a child of its own; the paged decode
    kernel with a free and a parked row in its batch, which must come
    back as zeros, and once more on a narrow grouped-query pool, where
    it takes several blocks of a row in one loop iteration."""
    out = run_child(KERNELS_CHILD, [json.dumps(kernel_shapes(config)),
                                    platform], child_env(), 600.0, "kernels")
    record = json.loads(out.split("KERNELS ", 1)[1])
    for kernel, error in record["errors"].items():
        check(error <= KERNEL_ABS_TOL,
              f"{kernel}: max abs err {error} over {KERNEL_ABS_TOL}")
        log(f"kernels: {kernel} max abs err {error:.5f} vs XLA "
            f"(bf16 bound {KERNEL_ABS_TOL})")
    return record["device"]


# -- phases across chips (behind --four-chips; the builder runs these) ---------
def phase_sharded(config: dict, platform: str, tp: int) -> dict:
    """The decoder under `"mesh": {"tp": tp}` against the same decoder on
    one chip: same prompts, first-step log-probabilities within
    LOGPROB_ABS_TOL, and memory spread over the devices."""
    single = phase_generate(config, platform)
    sharded = phase_generate({**config, "mesh": {"tp": tp}}, platform)
    device = sharded["device"]
    check(device["count"] >= tp, f"tp={tp} on {device}")
    for prompt in ("short", "long"):
        a, b = single[prompt], sharded[prompt]
        # Greedy ids are compared and printed, but not held to equality:
        # tp reorders bf16 partial sums, and past a near-tie of a
        # random-init model's logits the two runs part for good.  The
        # first step's log-probabilities have no such history.
        gap = max(abs(x - y) for x, y in zip([a["logprobs"][0]] + a["top"][0],
                                             [b["logprobs"][0]] + b["top"][0]))
        same = next((i for i, (x, y) in enumerate(zip(a["ids"], b["ids"]))
                     if x != y), len(a["ids"]))
        check(gap <= LOGPROB_ABS_TOL,
              f"{prompt} prompt: first-step log-probs differ by {gap:.4f} "
              f"between one chip and tp={tp} (bound {LOGPROB_ABS_TOL})")
        log(f"sharded: {prompt} prompt first-step log-prob gap {gap:.5f} "
            f"(bound {LOGPROB_ABS_TOL}); greedy ids agree for "
            f"{same}/{len(a['ids'])} tokens")
    if platform == "tpu":
        # "Everything on the first device" is the failure to look for:
        # each device must hold its share of the KV pool, none the whole
        # pool, and all about the same.
        pool = sharded["pool_bytes"]
        per_device = sharded["hbm_in_use"][:tp]
        check(pool / tp <= min(per_device) and max(per_device) < pool
              and max(per_device) <= 1.3 * min(per_device),
              f"tp={tp} did not spread memory: {per_device} bytes in use "
              f"per device, KV pool {pool:.0f} bytes")
        log(f"sharded: HBM in use per device {per_device} (KV pool "
            f"{pool:.0f} bytes over {tp}); one chip, no mesh: "
            f"{single['hbm_in_use'][0]}")
    return device


def phase_replicas(config: dict, platform: str, replicas: int,
                   instance: np.ndarray, env_overrides: dict = None) -> list:
    """One `jax` InferenceService at `replicas` replicas behind the
    ingress router, each child pinned to its own chip by the
    orchestrator; every replica names a different device and answers
    predicts of `instance`."""
    import asyncio

    from kfserving_tpu.control.controller import Controller
    from kfserving_tpu.control.router import IngressRouter
    from kfserving_tpu.control.spec import InferenceService, PredictorSpec
    from kfserving_tpu.control.subprocess_orchestrator import (
        SubprocessOrchestrator,
    )
    from kfserving_tpu.protocol import native

    check(native.build(), "building csrc/tensorjson.c failed")
    name = "smoke-replicas"
    model_dir = write_model_dir(name, config)
    body = json.dumps({"instances": [instance.tolist()]}).encode()

    async def run():
        # No recycle policy: no watchdog, no standby pool.
        orch = SubprocessOrchestrator(env_overrides={
            "KFS_PARAM_CACHE": child_env()["KFS_PARAM_CACHE"],
            **(env_overrides or {})})
        router = IngressRouter(Controller(orch))
        await router.start_async()
        try:
            await router.controller.apply(InferenceService(
                name=name, predictor=PredictorSpec(
                    framework="jax", storage_uri=f"file://{model_dir}",
                    min_replicas=replicas, max_replicas=replicas)))
            hosts = [r.host for r in
                     orch.replicas(f"default/{name}/predictor")]
            check(len(hosts) == replicas, f"replicas up: {hosts}")
            loop = asyncio.get_running_loop()
            url = (f"http://127.0.0.1:{router.http_port}"
                   f"/v1/models/{name}:predict")
            for _ in range(4 * replicas):
                out, _ = await loop.run_in_executor(
                    None, http, "POST", url, body)
                check(len(json.loads(out)["predictions"]) == 1, out[:200])
            records = []
            for host in hosts:
                meta, _ = await loop.run_in_executor(
                    None, http, "GET", f"http://{host}/v2")
                text, _ = await loop.run_in_executor(
                    None, http, "GET", f"http://{host}/metrics")
                records.append({
                    "host": host, "device": json.loads(meta)["device"],
                    "answered": gauge(text.decode(),
                                      "kfserving_tpu_engine_execute_count",
                                      model=name)})
            return records
        finally:
            await router.stop_async()
            await orch.shutdown()

    records = asyncio.run(run())
    for r in records:
        check(r["device"]["platform"] == platform, f"replica on {r}")
        check(r["answered"] > 0, f"replica answered nothing: {r}")
        log(f"replicas: {r['host']} device {json.dumps(r['device'])} "
            f"executed {r['answered']:.0f} batches")
    # A pinned child sees its chip as device id 0, so the pin tells the
    # replicas apart; that all of them serve at once tells the chips apart.
    chips = [r["device"]["visible_chips"] for r in records]
    check(None not in chips and len(set(chips)) == replicas,
          f"replicas were not each pinned to their own chip: {chips}")
    if platform == "tpu":
        check(all(r["device"]["count"] == 1 for r in records),
              f"a pinned replica holds more than one chip: {records}")
    return records


# -- command line --------------------------------------------------------------
def one_chip(platform: str) -> dict:
    batch = np.random.default_rng(0).integers(
        0, 256, size=(4, 224, 224, 3)).astype(np.uint8)
    return [phase_predict(RESNET50, platform, batch),
            phase_generate(DECODER, platform)["device"],
            phase_kernels(DECODER, platform)]


def four_chips(platform: str) -> list:
    device = phase_sharded(DECODER, platform, tp=4)
    image = np.random.default_rng(0).integers(
        0, 256, size=(224, 224, 3)).astype(np.uint8)
    phase_replicas(RESNET50, platform, replicas=4, instance=image)
    return [device]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--four-chips", action="store_true",
        help="run only the paths across chips (tp=4 decoder against one "
             "chip; four pinned replicas behind the router)")
    args = parser.parse_args(argv)
    check(os.path.isdir(os.path.join(ROOT, "kfserving_tpu")),
          f"{ROOT} is not the root of a checkout: no kfserving_tpu/ here")
    os.makedirs(WORK, exist_ok=True)
    devices = (four_chips if args.four_chips else one_chip)("tpu")
    named = {(d["platform"], d["kind"], d["count"]) for d in devices}
    check(len(named) == 1, f"phases named different devices: {named}")
    platform, kind, count = named.pop()
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
