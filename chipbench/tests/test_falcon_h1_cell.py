"""The Falcon-H1 cell rehearsed at toy size on the CPU (a toy model with both
mixers in every layer served by the real llmserver, checked against the real
`falcon_h1` reference, driven by the real load generator), the manifest's new
entries, the reader this configuration brought on small traces, and the
benchmark's reference against the repo's own on one job.  What a rehearsal
reads is a count or a check, never a speed."""

import gzip
import json
import os
import subprocess
import sys

import pytest

from chipbench import hybrid_scopes, opsbytes_hybrid, run as bench, schedule

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_hybrid_small.json.gz")
CONFIG = "falcon-h1-34b-6l"
CELL = CONFIG + ".chat-answers"

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(bench.ROOT, "chipbench", "configs",
                       CONFIG + ".json")) as f:
    PUBLISHED = json.load(f)

# The reference takes the multipliers, `rope_theta`, the Mamba heads (32),
# groups (2) and state (256) from its own configuration file, depth and
# epsilon from the job, every other size from the served parameters: a toy
# with those and small widths fits it.
MULTIPLIERS = {key: PUBLISHED[key] for key in (
    "embedding_multiplier", "attention_in_multiplier",
    "attention_out_multiplier", "key_multiplier", "ssm_in_multiplier",
    "ssm_multipliers", "ssm_out_multiplier", "mlp_multipliers",
    "lm_head_multiplier")}
TOY = {
    "name": "toy-falcon-h1", "kind": "generate",
    "n_layer": 3, "n_embd": 32, "n_head": 2, "layer_norm_epsilon": 1e-5,
    "hybrid_override_pattern": "MMM", "mamba_num_heads": 32,
    "mamba_head_dim": 2, "n_groups": 2, "ssm_state_size": 256,
    "server_module": "kfserving_tpu.predictors.llmserver",
    "serving": {"architecture": "falcon_h1_tiny",
                "arch_kwargs": dict(
                    MULTIPLIERS, max_seq=256, hidden_size=64, num_layers=3,
                    num_heads=10, num_kv_heads=2, head_dim=16,
                    intermediate_size=96, mamba_heads=32, mamba_head_dim=2,
                    mamba_d_ssm=64, ssm_groups=2, ssm_state=256,
                    chunk_size=32, rope_theta=PUBLISHED["rope_theta"]),
                "max_slots": 4, "max_seq": 256, "prefill_buckets": [128],
                "block_size": 32, "cache_blocks": 32, "steps_per_call": 4,
                "prefill_rows": 2, "tokenizer": "byte", "ignore_eos": True},
    "warm_rows": [1, 2], "trace_s": 2,
    # float32 on both sides at toy size: they agree to rounding
    "reference": {"module": "falcon_h1", "tolerance": 1e-3},
}
TOY_TRAFFIC = {"loop": "closed", "clients": 6, "block": 6, "requests": 1200,
               "stagger_s": 1.0, "warm_rounds": 1,
               "prompt_tokens": {"dist": "loguniform", "lo": 8, "hi": 120},
               "output_tokens": {"dist": "loguniform", "lo": 8, "hi": 64}}


@pytest.fixture(scope="module")
def rehearsal():
    saved = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
        return bench.measure_cell(cell, TOY, TOY_TRAFFIC, seed=2**31 + 51,
                                  seconds=4.0, trace=False, platform="cpu")
    finally:
        if saved is None:
            os.environ.pop("JAX_PLATFORMS")
        else:
            os.environ["JAX_PLATFORMS"] = saved


def test_the_cell_at_toy_size(rehearsal):
    result = bench.result_of(MANIFEST, rehearsal)
    assert result["correct"] and result["failed"] == 0, result
    assert result["attempted"] > 0
    assert rehearsal["reference"]["gap"] < 1e-3
    assert {"tpot_p50_ms", "setup_s"} <= set(result["metrics"]) \
        <= {"tpot_p50_ms", "setup_s", "tokens_per_s"}
    assert result["device"]["platform"] == "cpu"


def test_the_counter_readers_on_the_rehearsal(rehearsal):
    layers = bench.metrics_of(MANIFEST, "per_layer", "layer_metrics",
                              rehearsal)
    assert layers["compiles_in_window"]["value"] == 0
    assert layers["programs_traced_in_window"]["value"] == 0
    assert 0 < layers["paged_block_fill"]["value"] <= 100
    assert layers["paged_blocks_per_iteration"]["value"] >= 1
    # the trace's metrics need the chip and are left out of the line
    assert not {"mixer_step_share", "ssm_step_share", "ssm_scan_roofline",
                "paged_attn_roofline", "decode_step_device_ms"} & set(layers)


def test_no_server_or_generator_outlives_the_rehearsal(rehearsal):
    found = subprocess.run(
        ["pgrep", "-f", "kfserving_tpu.predictors.llmserver.*toy-falcon-h1"
         "|chipbench.loadgen.*" + CELL],
        capture_output=True, text=True).stdout.split()
    assert found == [], found


# -- the manifest's new entries -------------------------------------------------
def test_the_configuration_is_the_catalog_row_but_for_its_depth():
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry == MANIFEST["configs"][-1]
    assert entry["reduced"] == PUBLISHED["reduced"] == ["num_hidden_layers"]
    assert PUBLISHED["source"] == entry["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Falcon-H1-34B-Instruct")
        assert entry["source"] == row["source_url"]
        differs = {k for k, v in row["config"].items() if PUBLISHED[k] != v}
        assert differs == {"num_hidden_layers"}
        assert PUBLISHED["published"] == {
            "num_hidden_layers": row["config"]["num_hidden_layers"]}
    assert PUBLISHED["num_hidden_layers"] == PUBLISHED["n_layer"] == 6
    kw = PUBLISHED["serving"]["arch_kwargs"]
    # the served model is given the published numbers and no others
    for ours, theirs in (
            ("vocab_size", "vocab_size"), ("hidden_size", "hidden_size"),
            ("num_layers", "num_hidden_layers"),
            ("num_heads", "num_attention_heads"),
            ("num_kv_heads", "num_key_value_heads"),
            ("head_dim", "head_dim"),
            ("intermediate_size", "intermediate_size"),
            ("mamba_heads", "mamba_n_heads"),
            ("mamba_head_dim", "mamba_d_head"),
            ("mamba_d_ssm", "mamba_d_ssm"), ("ssm_groups", "mamba_n_groups"),
            ("ssm_state", "mamba_d_state"), ("conv_kernel", "mamba_d_conv"),
            ("chunk_size", "mamba_chunk_size"), ("rope_theta", "rope_theta"),
            ("rms_norm_eps", "rms_norm_eps")) + tuple(
                (k, k) for k in MULTIPLIERS):
        assert kw[ours] == PUBLISHED[theirs], ours
    # ... and the names the accepted readers read are the same numbers
    assert (PUBLISHED["n_head"], PUBLISHED["n_embd"]) == (
        kw["num_kv_heads"], kw["num_kv_heads"] * kw["head_dim"])
    assert (PUBLISHED["mamba_num_heads"], PUBLISHED["mamba_head_dim"],
            PUBLISHED["ssm_state_size"], PUBLISHED["n_groups"]) == (
        kw["mamba_heads"], kw["mamba_head_dim"], kw["ssm_state"],
        kw["ssm_groups"])
    assert PUBLISHED["hybrid_override_pattern"] == "M" * kw["num_layers"]
    assert PUBLISHED["layer_norm_epsilon"] == PUBLISHED["rms_norm_eps"]


def test_the_cell_is_the_issues_to_the_letter():
    cell, config, traffic = bench.find_cell(MANIFEST, CELL)
    assert cell == MANIFEST["workloads"][-1] and cell["chips"] == 1
    assert (traffic["loop"], traffic["clients"], traffic["block"],
            traffic["stagger_s"], traffic["warm_rounds"]) == (
        "closed", 80, 80, 8.0, 1)
    assert traffic["prompt_tokens"] == {"dist": "loguniform", "lo": 32,
                                        "hi": 512}
    assert traffic["output_tokens"] == {"dist": "loguniform", "lo": 64,
                                        "hi": 1024}
    serving = config["serving"]
    assert {k: serving[k] for k in (
        "max_slots", "max_seq", "prefill_buckets", "block_size",
        "cache_blocks", "steps_per_call", "prefill_rows", "tokenizer")} == {
        "max_slots": 64, "max_seq": 1536, "prefill_buckets": [512],
        "block_size": 128, "cache_blocks": 768, "steps_per_call": 16,
        "prefill_rows": 8, "tokenizer": "byte"}
    # seeded weights reach the byte tokenizer's EOS id by chance: an answer
    # ends at its budget alone (PERF.md §4)
    assert serving["ignore_eos"] is True
    assert config["warm_rows"] == [1, 2, 4, 8] and config["trace_s"] == 3
    # the longest request fits a slot, and a block stands for every position
    prompts = schedule.quantile_lengths(traffic["prompt_tokens"], 80)
    outputs = schedule.quantile_lengths(traffic["output_tokens"], 80)
    assert max(prompts) + max(outputs) <= serving["max_seq"]
    assert max(prompts) <= serving["prefill_buckets"][0]
    assert round(sum(prompts) / 80) == 173 and round(sum(outputs) / 80) == 346
    assert serving["cache_blocks"] * serving["block_size"] == \
        serving["max_slots"] * serving["max_seq"]
    # the three check prompts: one bucket, seconds of reference
    assert [schedule.quantile_lengths(traffic["prompt_tokens"], 10)[i]
            for i in (1, 6, 9)] == [49, 194, 446]


def test_the_new_metric_and_the_lists_the_cell_joined():
    new = MANIFEST["per_layer"][-1]
    assert new == {"name": "mixer_step_share", "unit": "%",
                   "better": "lower", "source": "device_trace",
                   "layer": "model step", "moves": "tpot_p50_ms",
                   "workloads": [CELL]}
    reports = {m["name"] for group in ("end_to_end", "per_layer")
               for m in MANIFEST[group]
               if CELL in m.get("workloads", [CELL])}
    assert {"tpot_p50_ms", "setup_s", "mixer_step_share",
            "decode_step_device_ms", "ssm_step_share", "ssm_scan_roofline",
            "paged_attn_roofline", "paged_block_fill",
            "paged_blocks_per_iteration", "hbm_in_use_gb", "hbm_peak_gb",
            "decode_dispatch_host_ms", "decode_inflight_mean_ms", "ready_s",
            "compiles_in_window", "programs_traced_in_window"} <= reports
    for metric in MANIFEST["per_layer"]:
        if CELL in metric.get("workloads", []):
            assert metric["workloads"][-1] == CELL  # appended, nothing moved
            assert metric["moves"] in ("tpot_p50_ms", "setup_s",
                                       "tokens_per_s")


# -- the new reader, on small traces --------------------------------------------
def test_mixer_step_share_on_a_small_trace():
    """Two decode calls of 100 ms: 30 ms under `ssm.*`, 12 under `attn`
    (the paged kernel by its scope's path), 40 under `mlp` and `head`,
    which `hybrid_scopes` does not list and the share leaves out."""
    at = "jit(decode_fn)/while/body/closed_call/FalconH1LM/layer_2/"
    ms = 1_000_000
    table = hybrid_scopes.reduce({
        "modules": [["jit_decode_fn", 0, 100 * ms],
                    ["jit_decode_fn", 110 * ms, 100 * ms]],
        "ops": [[at + "mamba/ssm.in_proj/in_proj/dot_general:", 1 * ms, 8 * ms],
                [at + "mamba/ssm.conv/mul:", 10 * ms, 2 * ms],
                [at + "mamba/ssm.scan/mul:", 13 * ms, 15 * ms],
                [at + "mamba/ssm.out/out_proj/dot_general:", 30 * ms, 5 * ms],
                [at + "attention/attn/jit(paged_attention_tpu)/pallas_call:",
                 36 * ms, 4 * ms],
                [at + "attention/attn/query/dot_general:", 41 * ms, 8 * ms],
                [at + "mlp/mlp/gate/dot_general:", 50 * ms, 30 * ms],
                ["jit(decode_fn)/while/body/closed_call/FalconH1LM/head/"
                 "lm_head/dot_general:", 81 * ms, 10 * ms]]})
    run = {"config": {"name": "m"}, "cell": {"name": "c"},
           "hybrid_scopes": table}
    read = {name: bench.load_by_path("layer_metrics", name).read(run)
            for name in ("mixer_step_share", "ssm_step_share")}
    assert read["mixer_step_share"] == pytest.approx(100 * 42 / 200)
    assert read["ssm_step_share"] == pytest.approx(100 * 30 / 200)


def test_mixer_step_share_on_the_recorded_trace():
    """The other hybrid's recorded decode calls (trace_hybrid_small): the
    state-space layers' share and the attention layers' `attn`, no more."""
    with gzip.open(RECORDED, "rt") as f:
        recorded = json.load(f)
    table = hybrid_scopes.reduce(recorded["trace"])
    decode = table["jit_decode_fn"]
    run = {"config": {"name": "m"}, "cell": {"name": "c"},
           "hybrid_scopes": table}
    reader = bench.load_by_path("layer_metrics", "mixer_step_share")
    share = reader.read(run)
    assert share == pytest.approx(
        recorded["expect"]["ssm_step_share"]
        + 100 * decode["scopes"]["attn"] / decode["seconds"])
    assert 0 < share < 100


def test_the_reader_gives_nothing_without_these_layers():
    """The parent commit cannot run the cell; a program without `ssm.*`
    scopes (the other decoders) gives None, and raises nothing."""
    reader = bench.load_by_path("layer_metrics", "mixer_step_share")
    run = {"config": {"name": "m"}, "trace_dir": None, "cell": {"name": "c"}}
    assert reader.read(run) is None
    run["hybrid_scopes"] = {"jit_decode_fn": {
        "calls": 3, "whole_calls": 3.0, "seconds": 0.3, "leaf_seconds": 0.3,
        "scopes": {"moe.experts": 0.2, "attn": 0.05}}}
    assert reader.read(run) is None


def test_the_scan_rooflines_bytes_at_this_models_state():
    flops, nbytes = opsbytes_hybrid.decode_ssm_scan(64, 32, 128, 256, 2)
    assert nbytes == 2 * 64 * 4 * 2**20 + 4 * 64 * (
        2 * 32 * 128 + 32 + 2 * 2 * 256)
    assert nbytes / 819e9 > 50 * flops / 197e12  # memory-bound


# -- the benchmark's reference against the repo's own ----------------------------
def test_the_benchmarks_reference_answers_a_job_as_the_repos_own(tmp_path):
    """One job as `kinds/generate.reference_answers` writes it, over a toy
    model's parameters stored as the server's parameter cache stores them,
    answered by the benchmark's reference as a CPU child (blocked over the
    job's sequences, the embedding's rows and the head's columns); the
    repo's own reference (tests/falcon_h1_reference.py: one sequence,
    everything at once) gives the same log-probabilities."""
    code = r"""
import json, os, sys
import jax, jax.numpy as jnp, numpy as np
from flax.traverse_util import flatten_dict
sys.path.insert(0, os.path.join(sys.argv[1], "tests"))
import falcon_h1_reference as own
from kfserving_tpu.models import create_model, init_params
work, published = sys.argv[2], json.load(open(sys.argv[3]))
kw = json.loads(sys.argv[4])
spec = create_model("falcon_h1_tiny", **kw)
flat = {k: np.asarray(v) for k, v in flatten_dict(
    init_params(spec, seed=11)).items()}
entry = os.path.join(work, "params", "digest")
os.makedirs(entry)
leaves, offset = [], 0
with open(os.path.join(entry, "params.bin"), "wb") as f:
    for path, leaf in flat.items():
        f.write(leaf.tobytes())
        leaves.append({"path": list(path), "dtype": leaf.dtype.name,
                       "shape": list(leaf.shape), "offset": offset,
                       "nbytes": leaf.nbytes})
        offset += leaf.nbytes
json.dump({"leaves": leaves}, open(os.path.join(entry, "manifest.json"), "w"))
rng = np.random.default_rng(0)
cases = [{"prompt_ids": [256] + rng.integers(1, 250, n).tolist(),
          "generated_ids": rng.integers(1, 250, 8).tolist(),
          "top_ids": rng.integers(1, 250, 5).tolist()} for n in (9, 40)]
json.dump({"params_dir": os.path.join(work, "params"), "n_layer": 3,
           "layer_norm_epsilon": 1e-5, "cases": cases},
          open(os.path.join(work, "job.json"), "w"))
model = dict(published, num_hidden_layers=3)
answers = []
for case in cases:
    ids = case["prompt_ids"] + case["generated_ids"][:-1]
    rows = np.asarray(jax.nn.log_softmax(own.logits(
        {"/".join(k): v for k, v in flat.items()}, ids, model),
        axis=-1))[len(case["prompt_ids"]) - 1:]
    answers.append({"chosen": [float(rows[j, t]) for j, t in
                               enumerate(case["generated_ids"])],
                    "top": [float(rows[0, t]) for t in case["top_ids"]]})
json.dump(answers, open(os.path.join(work, "own.json"), "w"))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=bench.ROOT)
    subprocess.run(
        [sys.executable, "-c", code, bench.ROOT, str(tmp_path),
         os.path.join(bench.ROOT, "chipbench", "configs", CONFIG + ".json"),
         json.dumps(TOY["serving"]["arch_kwargs"])],
        check=True, env=env, cwd=bench.ROOT, timeout=600)
    subprocess.run(
        [sys.executable, "-m", "chipbench.references.falcon_h1",
         str(tmp_path / "job.json"), str(tmp_path / "theirs.json")],
        check=True, env=env, cwd=bench.ROOT, timeout=600)
    with open(tmp_path / "own.json") as f:
        own = json.load(f)
    with open(tmp_path / "theirs.json") as f:
        theirs = json.load(f)["cases"]
    assert len(own) == len(theirs) == 2
    for mine, other in zip(own, theirs):
        assert mine["chosen"] == pytest.approx(other["chosen"], abs=1e-5)
        assert mine["top"] == pytest.approx(other["top"], abs=1e-5)
