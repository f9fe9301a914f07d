"""The Nemotron-H cell rehearsed at toy size on the CPU (a toy hybrid served
by the real llmserver, checked against the real `nemotron_h` reference,
driven by the real load generator), and the four readers this configuration
brought: the counter on that rehearsal, the three trace readers on a small
recorded trace.  What a rehearsal reads is a count or a check, never a
speed."""

import gzip
import json
import os

import pytest

from chipbench import hybrid_scopes, opsbytes_hybrid, run as bench

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_hybrid_small.json.gz")
CELL = "nemotron-3-nano-16l-ep2.chat-wide"

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

# The reference takes the pattern (its first `n_layer` letters), the Mamba
# heads (64), groups (8) and state (128), experts per token (6), the scaling
# and the share (experts 0-63 of the router's 128) from its own configuration
# file, depth and epsilon from the job, every other size from the served
# parameters: a toy with those and small widths fits it.
TOY = {
    "name": "toy-nemotron", "kind": "generate",
    "n_layer": 6, "n_embd": 32, "n_head": 2, "layer_norm_epsilon": 1e-5,
    "hybrid_override_pattern": "MEMEM*", "hidden_size": 64,
    "moe_intermediate_size": 16, "num_experts": 64,
    "mamba_num_heads": 64, "mamba_head_dim": 2, "n_groups": 8,
    "ssm_state_size": 128,
    "server_module": "kfserving_tpu.predictors.llmserver",
    "serving": {"architecture": "nemotron_h_tiny",
                "arch_kwargs": {
                    "max_seq": 256, "hidden_size": 64, "pattern": "MEMEM*",
                    "num_heads": 4, "num_kv_heads": 2, "head_dim": 16,
                    "mamba_heads": 64, "mamba_head_dim": 2, "ssm_groups": 8,
                    "ssm_state": 128, "chunk_size": 32,
                    "intermediate_size": 16, "shared_intermediate_size": 32,
                    "routed_experts": 128, "experts_held": [0, 64],
                    "experts_per_token": 6},
                "max_slots": 4, "max_seq": 256, "prefill_buckets": [128],
                "block_size": 32, "cache_blocks": 32, "steps_per_call": 4,
                "prefill_rows": 2, "tokenizer": "byte"},
    "warm_rows": [1, 2], "trace_s": 2,
    # float32 on both sides at toy size: they agree to rounding
    "reference": {"module": "nemotron_h", "tolerance": 1e-3},
}
TOY_TRAFFIC = {"loop": "closed", "clients": 6, "block": 6, "requests": 1200,
               "stagger_s": 1.0, "warm_rounds": 2,
               "prompt_tokens": {"dist": "loguniform", "lo": 8, "hi": 120},
               "output_tokens": {"dist": "loguniform", "lo": 4, "hi": 40}}


@pytest.fixture(scope="module")
def rehearsal():
    saved = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
        return bench.measure_cell(cell, TOY, TOY_TRAFFIC, seed=2**31 + 31,
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
    assert set(result["metrics"]) == {"tokens_per_s", "tpot_p50_ms",
                                      "setup_s"}
    assert result["device"]["platform"] == "cpu"


def test_the_counter_readers_on_the_rehearsal(rehearsal):
    layers = bench.metrics_of(MANIFEST, "per_layer", "layer_metrics",
                              rehearsal)
    # 64 of the router's 128 are held: about half of the pairs land here
    assert 25 < layers["moe_pairs_held_share"]["value"] < 75
    assert layers["compiles_in_window"]["value"] == 0
    assert layers["programs_traced_in_window"]["value"] == 0
    assert 0 < layers["paged_block_fill"]["value"] <= 100
    # what moves tokens per second, and what shows the routing's collapse
    assert 0 < layers["slot_occupancy"]["value"] <= 100
    assert 0 < layers["moe_experts_touched"]["value"] <= 64
    assert layers["moe_load_max_over_mean"]["value"] >= 1
    # the trace's metrics need the chip and are left out of the line
    assert not {"ssm_step_share", "ssm_scan_roofline",
                "moe_held_experts_roofline", "moe_step_share"} & set(layers)
    # a model that holds a share is not read by the all-experts roofline
    roofline = next(m for m in MANIFEST["per_layer"]
                    if m["name"] == "moe_experts_roofline")
    assert CELL not in roofline["workloads"]


def test_a_call_cut_by_the_captures_edge_counts_for_its_part():
    """A stub of a decode call at the trace's start (25 ms of 164) is
    1/7 call by `calls` and 0.15 call by `whole_calls`."""
    table = hybrid_scopes.reduce({
        "modules": [["jit_decode_fn", 0, 25_000_000],
                    ["jit_decode_fn", 30_000_000, 164_000_000],
                    ["jit_decode_fn", 200_000_000, 164_000_000]],
        "ops": [["jit(decode_fn)/x/ssm.scan/mul:", 1_000_000, 5_000_000]]})
    decode = table["jit_decode_fn"]
    assert decode["calls"] == 3
    assert decode["whole_calls"] == pytest.approx(353 / 164)
    assert decode["scopes"] == {"ssm.scan": pytest.approx(0.005)}


def test_the_readers_give_nothing_for_a_program_without_these_layers():
    """A parent commit, or the other decoders: no counter, no scope."""
    run = {"config": {"name": "m"},
           "scrapes": {"open": {"metrics": ""}, "close": {"metrics": ""}},
           "trace_dir": None, "cell": {"name": "c"}}
    for name in ("moe_pairs_held_share", "ssm_step_share",
                 "ssm_scan_roofline", "moe_held_experts_roofline"):
        assert bench.load_by_path("layer_metrics", name).read(run) is None
    # a trace whose decode program has expert scopes and no ssm scope
    run["hybrid_scopes"] = {"jit_decode_fn": {
        "calls": 3, "whole_calls": 3.0, "seconds": 0.3, "leaf_seconds": 0.3,
        "scopes": {"moe.experts": 0.2, "attn": 0.05}}}
    for name in ("ssm_step_share", "ssm_scan_roofline",
                 "moe_held_experts_roofline"):
        assert bench.load_by_path("layer_metrics", name).read(run) is None


# -- the trace readers, on a recorded trace ------------------------------------
@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_scope_of():
    at = "jit(decode_fn)/while/body/closed_call/NemotronHLM/layer_4/mixer/"
    assert hybrid_scopes.scope_of(at + "ssm.scan/mul:") == "ssm.scan"
    assert hybrid_scopes.scope_of(at + "ssm.in_proj/in_proj/dot_general:") \
        == "ssm.in_proj"
    assert hybrid_scopes.scope_of(at + "moe.shared/shared_up/dot_general:") \
        == "moe.shared"
    assert hybrid_scopes.scope_of(
        at + "moe.experts/jit(experts_touched)/pallas_call:") == "moe.experts"
    assert hybrid_scopes.scope_of(
        at + "attn/jit(paged_attention_tpu)/pallas_call:") == "attn"
    assert hybrid_scopes.scope_of("moe_experts_touched") == "moe.experts"
    assert hybrid_scopes.scope_of("jit(decode_fn)/while/body/top_k:") is None


def test_reduce_on_the_recorded_trace(recorded):
    table = hybrid_scopes.reduce(recorded["trace"])
    expect = recorded["expect"]
    decode = table["jit_decode_fn"]
    assert decode["calls"] == expect["decode_calls"]
    # whole calls of one program take the same time: 3 of them, within 1%
    assert decode["whole_calls"] == pytest.approx(3.0, rel=0.01)
    assert decode["seconds"] == pytest.approx(expect["decode_seconds"])
    assert decode["leaf_seconds"] <= decode["seconds"]
    assert decode["scopes"]["ssm.scan"] == pytest.approx(
        expect["decode_scan_seconds"])
    assert decode["scopes"]["moe.experts"] == pytest.approx(
        expect["decode_experts_seconds"])
    assert set(decode["scopes"]) <= set(hybrid_scopes.SCOPES)
    assert not table["jit_insert_fn"]["scopes"]


def test_the_trace_readers_on_the_recorded_trace(recorded):
    expect = recorded["expect"]

    def scrape(pairs, touched, layer_steps):
        return {"metrics": "\n".join(
            f'kfserving_tpu_generator_moe_{name}_total{{model="m"{more}}} {v}'
            for name, more, v in (
                ("routed_pairs", ',program="decode"', pairs),
                ("experts_touched", "", touched),
                ("layer_steps", "", layer_steps)))}

    run = {"config": {"name": "m", "hidden_size": 2688,
                      "moe_intermediate_size": 1856,
                      "hybrid_override_pattern": "MEMEM*EMEMEM*EME",
                      "mamba_num_heads": 64, "mamba_head_dim": 64,
                      "ssm_state_size": 128, "n_groups": 8,
                      "serving": {"max_slots": 64, "steps_per_call": 16}},
           "scrapes": {"open": scrape(0, 0, 0),
                       "close": scrape(192 * 1000, 16 * 1000, 1000)},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "hybrid_scopes": hybrid_scopes.reduce(recorded["trace"])}
    read = {name: bench.load_by_path("layer_metrics", name).read(run)
            for name in ("ssm_step_share", "ssm_scan_roofline",
                         "moe_held_experts_roofline")}
    assert read["ssm_step_share"] == pytest.approx(expect["ssm_step_share"])
    steps = run["hybrid_scopes"]["jit_decode_fn"]["whole_calls"] * 16 * 7
    flops, nbytes = opsbytes_hybrid.decode_ssm_scan(64, 64, 64, 128, 8)
    assert nbytes / 819e9 > flops / 197e12  # memory-bound
    assert read["ssm_scan_roofline"] == pytest.approx(
        100 * steps * (nbytes / 819e9) / expect["decode_scan_seconds"])
    # random routers send most rows the same way: 16 of the 64 held
    # experts touched a layer-step in the recorded calls (21 over a window)
    flops, nbytes = opsbytes_hybrid.decode_plain_expert_matmuls(
        pairs=192, touched=16, tokens=64, hidden=2688, width=1856,
        bytes_per_value=2)
    assert nbytes / 819e9 > flops / 197e12  # memory-bound at 64 rows
    assert read["moe_held_experts_roofline"] == pytest.approx(
        100 * steps * (nbytes / 819e9) / expect["decode_experts_seconds"])
    assert all(0 < v <= 100 for v in read.values()), read


def test_operations_and_bytes_of_a_layer_step():
    flops, nbytes = opsbytes_hybrid.decode_plain_expert_matmuls(
        pairs=192, touched=58, tokens=64, hidden=2688, width=1856,
        bytes_per_value=2)
    assert flops == 2 * 2 * 192 * 2688 * 1856
    assert nbytes == 58 * 2 * 2688 * 1856 * 2 + 2 * 64 * 2688 * 2
    flops, nbytes = opsbytes_hybrid.decode_ssm_scan(64, 64, 64, 128, 8)
    assert flops == 5 * 64 * 64 * 64 * 128
    assert nbytes == 2 * 64 * 64 * 64 * 128 * 4 \
        + 4 * 64 * (2 * 64 * 64 + 64 + 2 * 8 * 128)
