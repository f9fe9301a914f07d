"""The OLMoE cell rehearsed at toy size on the CPU (a toy OLMoE served by the
real llmserver, checked against the real `olmoe` reference, driven by the
real load generator), and the four routed-expert readers: the two counters
on that rehearsal, the two trace readers on a small recorded trace.  What a
rehearsal reads is a count or a check, never a speed."""

import gzip
import json
import os

import pytest

from chipbench import moe_scopes, opsbytes_moe, run as bench

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_moe_small.json.gz")
CELL = "olmoe-1b-7b-8l.chat-long"

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

# The reference takes experts per token (8) and the rotary base from its own
# configuration file, depth and epsilon from the job, every other size from
# the served parameters: a toy with 8 of 16 experts a token fits it.
TOY = {
    "name": "toy-olmoe", "kind": "generate",
    "n_layer": 2, "n_embd": 128, "n_head": 4, "layer_norm_epsilon": 1e-5,
    "num_hidden_layers": 2, "hidden_size": 128, "intermediate_size": 64,
    "num_experts": 16, "num_experts_per_tok": 8,
    "server_module": "kfserving_tpu.predictors.llmserver",
    "serving": {"architecture": "olmoe_tiny",
                "arch_kwargs": {"max_seq": 256, "num_experts": 16,
                                "experts_per_token": 8},
                "max_slots": 4, "max_seq": 256, "prefill_buckets": [128],
                "block_size": 32, "cache_blocks": 32, "steps_per_call": 4,
                "tokenizer": "byte"},
    "warm_rows": [1, 2, 4], "trace_s": 2,
    # float32 on both sides at toy size: they agree to rounding
    "reference": {"module": "olmoe", "tolerance": 1e-3},
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
        return bench.measure_cell(cell, TOY, TOY_TRAFFIC, seed=2**31 + 26,
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
    # 4 rows x 8 choices over 16 experts: most are touched, none twice over
    assert 8 <= layers["moe_experts_touched"]["value"] <= 16
    # the mean expert has 2 pairs a layer-step, the busiest at most 4
    assert 1.0 <= layers["moe_load_max_over_mean"]["value"] <= 2.0
    assert layers["compiles_in_window"]["value"] == 0
    assert 0 < layers["slot_occupancy"]["value"] <= 100
    # the trace's metrics need the chip and are left out of the line
    assert not {"moe_step_share", "moe_experts_roofline"} & set(layers)


def test_the_readers_give_nothing_for_a_program_without_experts():
    """A parent commit, or the dense decoder: no counter, no scope."""
    run = {"config": {"name": "m", "num_experts": 64},
           "scrapes": {"open": {"metrics": ""}, "close": {"metrics": ""}},
           "trace_dir": None, "cell": {"name": "c"}}
    for name in ("moe_experts_touched", "moe_load_max_over_mean",
                 "moe_step_share", "moe_experts_roofline"):
        assert bench.load_by_path("layer_metrics", name).read(run) is None


# -- the trace readers, on a recorded trace ------------------------------------
@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_scope_of():
    at = "jit(decode_fn)/while/body/closed_call/OlmoeLM/layer_3/"
    assert moe_scopes.scope_of(
        at + "experts/moe.experts/etf,efh->th/dot_general:") == "moe.experts"
    assert moe_scopes.scope_of(
        at + "attn/jit(paged_attention_tpu)/pallas_call:") == "attn"
    assert moe_scopes.scope_of(at + "experts/moe.router/router/dot_general:") \
        == "moe.router"
    assert moe_scopes.scope_of("jit(decode_fn)/while/body/top_k:") is None
    # XLA's grouped matmul comes without an op_name, under its own name
    assert moe_scopes.scope_of("ragged-dot-none:") == "moe.experts"
    assert moe_scopes.scope_of("ragged-dot-metadata:") == "moe.dispatch"
    assert moe_scopes.scope_of("moe_experts_touched") == "moe.experts"
    assert moe_scopes.scope_of("") is None


def test_reduce_on_the_recorded_trace(recorded):
    table = moe_scopes.reduce(recorded["trace"])
    expect = recorded["expect"]
    decode = table["jit_decode_fn"]
    assert decode["calls"] == expect["decode_calls"]
    assert decode["seconds"] == pytest.approx(expect["decode_seconds"])
    # leaves only: the `while` around the steps is not counted twice
    assert decode["leaf_seconds"] <= decode["seconds"]
    assert decode["leaf_seconds"] > 0.95 * decode["seconds"]
    assert decode["scopes"]["moe.experts"] == pytest.approx(
        expect["decode_experts_seconds"])
    assert set(decode["scopes"]) <= set(moe_scopes.SCOPES)
    assert not table["jit_insert_fn"]["scopes"]
    # the prefill's grouped matmuls are found by the kernel's name
    assert table["jit_prefill_fn"]["scopes"]["moe.experts"] == pytest.approx(
        expect["prefill_experts_seconds"])


def test_the_trace_readers_on_the_recorded_trace(recorded):
    expect = recorded["expect"]
    steps = expect["decode_calls"] * 16 * expect["layers"]

    def scrape(pairs, touched, layer_steps):
        return {"metrics": "\n".join(
            f'kfserving_tpu_generator_moe_{name}_total{{model="m"{more}}} {v}'
            for name, more, v in (
                ("routed_pairs", ',program="decode"', pairs),
                ("experts_touched", "", touched),
                ("layer_steps", "", layer_steps)))}

    run = {"config": {"name": "m", "hidden_size": 2048,
                      "intermediate_size": 1024,
                      "num_hidden_layers": expect["layers"],
                      "serving": {"max_slots": 24, "steps_per_call": 16}},
           "scrapes": {"open": scrape(0, 0, 0),
                       "close": scrape(192 * 1000, 61 * 1000, 1000)},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "moe_scopes": moe_scopes.reduce(recorded["trace"])}
    share = bench.load_by_path("layer_metrics", "moe_step_share").read(run)
    assert share == pytest.approx(expect["moe_step_share"])
    assert 50 < share < 100
    roofline = bench.load_by_path("layer_metrics",
                                  "moe_experts_roofline").read(run)
    flops, nbytes = opsbytes_moe.decode_expert_matmuls(
        pairs=192, touched=61, tokens=24, hidden=2048, width=1024,
        bytes_per_value=2)
    assert nbytes / 819e9 > flops / 197e12  # memory-bound at 24 rows
    assert roofline == pytest.approx(
        100 * steps * (nbytes / 819e9) / expect["decode_experts_seconds"])
    assert 0 < roofline < 100


def test_operations_and_bytes_of_a_layer_step():
    flops, nbytes = opsbytes_moe.decode_expert_matmuls(
        pairs=192, touched=64, tokens=24, hidden=2048, width=1024,
        bytes_per_value=2)
    assert flops == 2 * 3 * 192 * 2048 * 1024
    assert nbytes == 64 * 3 * 2048 * 1024 * 2 + 2 * 24 * 2048 * 2
