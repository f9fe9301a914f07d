"""The Mellum cell: the manifest names it and its files exist; the cell
rehearsed at toy size on the CPU (a toy window model served by the real
llmserver, checked against the real `mellum` reference, driven by the real
load generator) with the counter readers this configuration brought read on
that rehearsal; the trace readers on a small hand-made normalized trace in
the form `moe_scopes.normalize` gives (the paths are those of a recorded
Nemotron-H trace with this model's scopes; a device trace needs the chip);
`opsbytes_window` against hand-worked cases; and the three accepted
readers that would misread this model do not list the cell.  What a
rehearsal reads is a count or a check, never a speed."""

import json
import os

import pytest

from chipbench import opsbytes_window, run as bench, stats, window_scopes

CELL = "mellum2-12b-a2.5b-8l.code-context"
CONFIG = "mellum2-12b-a2.5b-8l"
NEW_READERS = ("attn_window_roofline", "attn_full_roofline",
               "attn_step_share", "window_block_fill", "global_block_fill",
               "kv_window_pool_fill", "moe_routed_experts_roofline")

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def published() -> dict:
    with open(os.path.join(bench.ROOT, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


# -- the manifest and the files ------------------------------------------------
def test_the_manifest_names_the_cell_and_its_files_exist():
    cell, config, traffic = bench.find_cell(MANIFEST, CELL)
    assert cell == {"name": CELL, "config": CONFIG,
                    "traffic": "code-context", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types"]
    for path in ("references/mellum.py", f"limits/{CONFIG}.json",
                 "traffic/code-context.json", "opsbytes_window.py",
                 "window_scopes.py"):
        assert os.path.exists(os.path.join(bench.HERE, path)), path
    for name in NEW_READERS:
        metric = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        reader = bench.load_by_path("layer_metrics", name)
        assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
            metric["unit"], metric["layer"], metric["source"],
            metric["moves"])
    judged = {m["name"]: CELL in m["workloads"]
              for m in MANIFEST["end_to_end"] if "workloads" in m}
    # not on `tokens_per_s`: 2.6 blocks of 80 requests a window spread it by
    # 1.6-2.6% over seeds against the 1% a cell is admitted with (PERF.md W1)
    assert judged == {"tokens_per_s": False, "tpot_p50_ms": True,
                      "request_mean_ms": False}
    for metric in MANIFEST["per_layer"]:
        if CELL in metric["workloads"]:
            assert metric["moves"] in ("tpot_p50_ms", "setup_s"), metric
    limits = bench.load_by_path("kinds", "generate").reference_limits(config)
    assert set(limits) == {"reference_gap_median", "reference_gap"}


def test_the_traffic_is_the_issues():
    _, config, traffic = bench.find_cell(MANIFEST, CELL)
    assert {k: traffic[k] for k in ("loop", "clients", "block", "stagger_s",
                                    "warm_rounds", "prompt_tokens",
                                    "output_tokens")} == {
        "loop": "closed", "clients": 80, "block": 80, "stagger_s": 8.0,
        "warm_rounds": 1,
        "prompt_tokens": {"dist": "loguniform", "lo": 512, "hi": 8192},
        "output_tokens": {"dist": "loguniform", "lo": 128, "hi": 2048}}
    assert traffic["requests"] % traffic["block"] == 0
    serving = config["serving"]
    assert serving["max_slots"] == 64 and serving["block_size"] == 128
    assert serving["steps_per_call"] == 16
    # the longest request fits a slot, the longest prompt the largest bucket
    from chipbench import schedule

    prompts = schedule.quantile_lengths(traffic["prompt_tokens"], 80)
    outputs = schedule.quantile_lengths(traffic["output_tokens"], 80)
    assert max(prompts) <= max(serving["prefill_buckets"]) == 8192
    assert max(prompts) + max(outputs) <= serving["max_seq"] \
        == serving["arch_kwargs"]["max_seq"] == 81 * 128
    assert sum(prompts) / 80 == pytest.approx(2770, rel=0.01)
    assert sum(outputs) / 80 == pytest.approx(693, rel=0.01)
    # every (rows, bucket) pair the harness warms is one the engine takes
    assert config["warm_rows"] == [serving["prefill_rows"]] == [1]


def test_the_configuration_is_the_published_one_but_for_its_depth():
    """Every key of the catalog row's config as published, except those in
    `reduced`, which are the first two periods of the published pattern."""
    config = published()
    guide = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(guide):
        pytest.skip("no catalog beside this checkout")
    with open(guide) as f:
        row = next(r for r in map(json.loads, f)
                   if r["source_url"] == config["source"])
    for key, value in row["config"].items():
        if key in config["reduced"]:
            continue
        assert config[key] == value, key
    assert config["num_hidden_layers"] == 8
    assert config["layer_types"] == row["config"]["layer_types"][:8]
    assert config["mlp_layer_types"] == ["sparse"] * 8
    assert config["intermediate_size"] == 7168  # the dense width, unused
    kw = config["serving"]["arch_kwargs"]
    assert (kw["hidden_size"], kw["num_heads"], kw["num_kv_heads"],
            kw["head_dim"], kw["moe_intermediate_size"], kw["num_experts"],
            kw["experts_per_token"], kw["vocab_size"],
            kw["sliding_window"]) == (2304, 32, 4, 128, 896, 64, 8, 98304,
                                      1024)
    assert kw["rope_parameters"] == row["config"]["rope_parameters"]
    assert kw["layer_types"] == config["layer_types"]
    for key in ("qk_norm", "window_edge", "mtp_head", "weights",
                "tokenizer", "serving"):
        assert key in config["assumed"]
    assert "one chip a layer" in config["deployment"]


def test_the_three_readers_that_would_misread_this_model_do_not_list_it():
    """`moe_experts_roofline` reads `intermediate_size` (7168 here, the
    expert's is 896); `paged_attn_roofline` and `paged_block_fill` count
    every context row in every layer and `n_embd / n_head` for a head."""
    for name in ("moe_experts_roofline", "paged_attn_roofline",
                 "paged_block_fill"):
        metric = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert CELL not in metric["workloads"]
    config = published()
    assert config["n_embd"] // config["n_head"] != config["head_dim"]
    assert config["intermediate_size"] == 8 * config["moe_intermediate_size"]


# -- operations and bytes ------------------------------------------------------
def test_operations_and_bytes_of_a_layer_step():
    # 64 sequences reading 65,536 rows in all: 32 query heads, 4 KV heads
    flops, nbytes = opsbytes_window.grouped_decode_attention(
        rows=65536, sequences=64, query_heads=32, kv_heads=4, head_dim=128,
        bytes_per_value=2)
    assert flops == 2 * 2 * 65536 * 32 * 128
    assert nbytes == (2 * 65536 * 4 * 128 + 2 * 64 * 32 * 128) * 2
    assert nbytes / 819e9 > flops / 197e12  # memory-bound


def test_live_rows_caps_each_sequence_at_the_window():
    # one request of 1000 prompt tokens streaming a token a second from t=10,
    # one of 3000: over [10, 14) the first holds 1001..1004, the second 3001..
    records = [{"prompt_tokens": 1000, "tokens": [10, 11, 12, 13, 14]},
               {"prompt_tokens": 3000, "tokens": [10, 11, 12, 13, 14]},
               {"prompt_tokens": 50, "tokens": []}]
    span = (10, 14)
    whole = opsbytes_window.live_rows(records, span)
    assert whole == pytest.approx((1001 + 1002 + 1003 + 1004
                                   + 3001 + 3002 + 3003 + 3004) / 4)
    assert whole == pytest.approx(stats.live_context_tokens(records, span))
    capped = opsbytes_window.live_rows(records, span, cap=1024)
    assert capped == pytest.approx((1001 + 1002 + 1003 + 1004
                                    + 4 * 1024) / 4)
    # half a window: only what falls inside counts, weighted by its time
    assert opsbytes_window.live_rows(records, (10, 10.5), cap=1024) \
        == pytest.approx(1001 + 1024)


# -- the trace readers ---------------------------------------------------------
AT = "jit(decode_fn)/while/body/closed_call/MellumLM/layer_%d/attn/"
KERNEL = "jit(paged_attention_tpu)/pallas_call:"
MS = 1_000_000


def normalized_trace() -> dict:
    """Two whole decode calls of 100 ms and a stub of 25 ms; in each whole
    call three window layer-steps of 2 ms and one full one of 5 ms (their
    writes 1 ms each), 40 ms of experts, a walk under the first layer."""
    ops = []
    for call in (30 * MS, 140 * MS):
        t = call + MS
        for layer, scope, kernel_ms in ((0, "attn.window", 2),
                                        (1, "attn.window", 2),
                                        (2, "attn.window", 2),
                                        (3, "attn.full", 5)):
            at = AT % layer + scope + "/"
            ops.append([at + "jit(paged_write_tpu)/pallas_call:", t, MS])
            ops.append([at + KERNEL, t + MS, kernel_ms * MS])
            t += (kernel_ms + 1) * MS
        ops.append([AT % 0 + "attn.window/jit(paged_attention_tpu)/reduce_min:",
                    t, MS])
        ops.append([(AT % 0).replace("attn/", "") + "experts/moe.experts/"
                    "moe_experts_touched/pallas_call:", t + MS, 40 * MS])
        ops.append(["jit(decode_fn)/while/body/closed_call/MellumLM/"
                    "rope.tables/cos:", t + 41 * MS, MS])
    return {"modules": [["jit_decode_fn", 0, 25 * MS],
                        ["jit_decode_fn", 30 * MS, 100 * MS],
                        ["jit_decode_fn", 140 * MS, 100 * MS],
                        ["jit_insert_fn", 250 * MS, MS]],
            "ops": ops}


def test_scope_of():
    at = AT % 4
    assert window_scopes.scope_of(at + "attn.window/" + KERNEL) \
        == "attn.window"
    assert window_scopes.scope_of(at + "attn.full/" + KERNEL) == "attn.full"
    assert window_scopes.scope_of(at + "q_norm/mul:") == "attn"
    assert window_scopes.scope_of(
        "jit(decode_fn)/while/body/MellumLM/rope.tables/cos:") \
        == "rope.tables"
    assert window_scopes.scope_of("ragged-dot-none:") == "moe.experts"
    assert window_scopes.scope_of("jit(decode_fn)/while/body/top_k:") is None
    # the accepted reducer still puts both kinds of layer under `attn`
    from chipbench import moe_scopes

    assert moe_scopes.scope_of(at + "attn.window/" + KERNEL) == "attn"


def test_reduce_counts_the_kernels_calls_by_scope():
    decode = window_scopes.reduce(normalized_trace())["jit_decode_fn"]
    assert decode["calls"] == 3
    assert decode["whole_calls"] == pytest.approx(2.25)
    assert decode["kernel"] == {
        "attn.window": {"calls": 6, "seconds": pytest.approx(0.012)},
        "attn.full": {"calls": 2, "seconds": pytest.approx(0.010)}}
    # a scope's seconds hold the write and the walk too
    assert decode["scopes"]["attn.window"] == pytest.approx(0.020)
    assert decode["scopes"]["attn.full"] == pytest.approx(0.012)
    assert decode["scopes"]["moe.experts"] == pytest.approx(0.080)
    assert decode["scopes"]["rope.tables"] == pytest.approx(0.002)


def test_the_trace_readers_on_the_trace():
    config = published()
    records = [{"prompt_tokens": 3000, "tokens": [0.0, 1.0]}] * 64

    def scrape(pairs, touched, layer_steps):
        return {"metrics": "\n".join(
            f'kfserving_tpu_generator_moe_{name}_total{{model="{CONFIG}"'
            f'{more}}} {v}' for name, more, v in (
                ("routed_pairs", ',program="decode"', pairs),
                ("experts_touched", "", touched),
                ("layer_steps", "", layer_steps)))}

    run = {"config": config, "records": records, "trace_window": (0.0, 1.0),
           "scrapes": {"open": scrape(0, 0, 0),
                       "close": scrape(512 * 1000, 58 * 1000, 1000)},
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
           "window_scopes": window_scopes.reduce(normalized_trace())}
    read = {name: bench.load_by_path("layer_metrics", name).read(run)
            for name in ("attn_window_roofline", "attn_full_roofline",
                         "attn_step_share", "moe_routed_experts_roofline")}
    # 64 sequences of 3001 tokens: a window layer reads 1024 rows of each
    _, window_bytes = opsbytes_window.grouped_decode_attention(
        64 * 1024, 64, 32, 4, 128, 2)
    _, full_bytes = opsbytes_window.grouped_decode_attention(
        64 * 3001, 64, 32, 4, 128, 2)
    assert read["attn_window_roofline"] == pytest.approx(
        100 * 6 * (window_bytes / 819e9) / 0.012)
    assert read["attn_full_roofline"] == pytest.approx(
        100 * 2 * (full_bytes / 819e9) / 0.010)
    assert read["attn_step_share"] == pytest.approx(100 * 0.032 / 0.225)
    from chipbench import opsbytes_moe

    flops, nbytes = opsbytes_moe.decode_expert_matmuls(
        pairs=512, touched=58, tokens=64, hidden=2304, width=896,
        bytes_per_value=2)
    assert nbytes / 819e9 > flops / 197e12  # memory-bound at 64 rows
    assert read["moe_routed_experts_roofline"] == pytest.approx(
        100 * 2.25 * 16 * 8 * (nbytes / 819e9) / 0.080)
    # read with the dense width it would claim eight times the bytes
    assert config["intermediate_size"] / config["moe_intermediate_size"] == 8


def test_the_readers_give_nothing_for_a_program_without_these_layers():
    """A parent commit, or the other decoders: no counter, no scope."""
    run = {"config": {"name": "m"}, "slice_scrapes": [],
           "scrapes": {"open": {"metrics": ""}, "close": {"metrics": ""}},
           "trace_dir": None, "cell": {"name": "c"}}
    for name in NEW_READERS:
        assert bench.load_by_path("layer_metrics", name).read(run) is None
    # a trace whose decode program has expert scopes and `attn` alone
    run["window_scopes"] = {"jit_decode_fn": {
        "calls": 3, "whole_calls": 3.0, "seconds": 0.3,
        "scopes": {"moe.experts": 0.2, "attn": 0.05}, "kernel": {}}}
    for name in NEW_READERS:
        assert bench.load_by_path("layer_metrics", name).read(run) is None


# -- the cell at toy size ------------------------------------------------------
# The reference takes the layer pattern (8 layers), the window (1024), the
# rotary sections, experts per token (8) and the norm's epsilon from its own
# configuration file, every size from the served parameters: a toy with those
# and small widths fits it.  Its sequences stay inside the window (the rings
# and their recycling are tests/test_mellum.py's, at a window of 16).
def toy() -> dict:
    config = published()
    return {
        "name": "toy-mellum", "kind": "generate",
        "n_layer": 8, "n_embd": 64, "n_head": 4, "layer_norm_epsilon": 1e-6,
        "hidden_size": 64, "moe_intermediate_size": 16, "num_experts": 16,
        "num_hidden_layers": 8, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 32, "sliding_window": 1024,
        "server_module": "kfserving_tpu.predictors.llmserver",
        "serving": {"architecture": "mellum_tiny",
                    "arch_kwargs": {
                        "max_seq": 256, "hidden_size": 64, "num_layers": 8,
                        "num_heads": 4, "num_kv_heads": 2, "head_dim": 32,
                        "moe_intermediate_size": 16, "num_experts": 16,
                        "experts_per_token": 8, "sliding_window": 1024,
                        "rope_parameters": config["rope_parameters"]},
                    "max_slots": 4, "max_seq": 256,
                    "prefill_buckets": [128], "block_size": 32,
                    "cache_blocks": 32, "steps_per_call": 4,
                    "prefill_rows": 1, "tokenizer": "byte"},
        "warm_rows": [1], "trace_s": 2,
        # float32 on both sides at toy size: they agree to rounding
        "reference": {"module": "mellum", "tolerance": 1e-3},
    }


TOY_TRAFFIC = {"loop": "closed", "clients": 6, "block": 6, "requests": 1200,
               "stagger_s": 1.0, "warm_rounds": 1,
               "prompt_tokens": {"dist": "loguniform", "lo": 8, "hi": 120},
               "output_tokens": {"dist": "loguniform", "lo": 4, "hi": 40}}


@pytest.fixture(scope="module")
def rehearsal():
    saved = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
        return bench.measure_cell(cell, toy(), TOY_TRAFFIC, seed=2**31 + 43,
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
    assert set(result["metrics"]) == {"tpot_p50_ms", "setup_s"}


def test_the_counter_readers_on_the_rehearsal(rehearsal):
    layers = bench.metrics_of(MANIFEST, "per_layer", "layer_metrics",
                              rehearsal)
    assert 0 < layers["window_block_fill"]["value"] <= 100
    assert 0 < layers["global_block_fill"]["value"] <= 100
    # inside the window both kinds of layer read the same rows
    assert layers["window_block_fill"]["value"] == pytest.approx(
        layers["global_block_fill"]["value"])
    assert 0 < layers["kv_window_pool_fill"]["value"] <= 100
    assert layers["compiles_in_window"]["value"] == 0
    assert layers["programs_traced_in_window"]["value"] == 0
    assert layers["decode_dispatch_host_ms"]["value"] > 0
    assert layers["decode_inflight_mean_ms"]["value"] > 0
    # what moves `tokens_per_s` is not this cell's to report
    assert not {"kv_pool_fill", "slot_occupancy", "program_stalls_in_window",
                "moe_experts_touched", "moe_load_max_over_mean"} & set(layers)
    # the trace's metrics need the chip and are left out of the line
    assert not {"attn_window_roofline", "attn_full_roofline",
                "attn_step_share", "moe_routed_experts_roofline",
                "moe_step_share"} & set(layers)
    assert not {"paged_block_fill", "paged_attn_roofline",
                "moe_experts_roofline"} & set(layers)
