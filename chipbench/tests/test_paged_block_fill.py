"""paged_block_fill on expositions written by hand: the counters'
movement between the window's edges, and nothing where a program has no
such counters."""

import json
import os

from chipbench import run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

TOKENS = "kfserving_tpu_generator_decode_kv_context_tokens_total"
BLOCKS = "kfserving_tpu_generator_decode_kv_blocks_walked_total"


def run_of(first: str, last: str) -> dict:
    return {"config": {"name": "m", "serving": {"block_size": 128}},
            "scrapes": {"open": {"metrics": first},
                        "close": {"metrics": last}}}


def exposition(tokens: int, blocks: int, model: str = "m") -> str:
    return (f'{TOKENS}{{model="{model}"}} {tokens}\n'
            f'{BLOCKS}{{model="{model}"}} {blocks}\n')


def test_the_share_of_the_rows_read_that_held_context():
    reader = bench.load_by_path("layer_metrics", "paged_block_fill")
    # 40 blocks of 128 rows walked for 3,840 context tokens: three quarters
    run = run_of(exposition(1000, 10), exposition(4840, 50))
    assert reader.read(run) == 75.0
    # another model's counters are not this cell's
    assert reader.read(run_of(exposition(1000, 10, "other"),
                              exposition(4840, 50, "other"))) is None


def test_nothing_to_read_is_nothing_reported():
    """A parent commit has no such counters; a window with no decode wave
    moves neither."""
    reader = bench.load_by_path("layer_metrics", "paged_block_fill")
    assert reader.read(run_of("", "")) is None
    assert reader.read(run_of(exposition(1000, 10),
                              exposition(1000, 10))) is None
    assert reader.read({"config": {"name": "m"}, "scrapes": {}}) is None


def test_the_manifest_lists_it_for_the_closed_loop_cells():
    entry = [m for m in MANIFEST["per_layer"]
             if m["name"] == "paged_block_fill"]
    # later PRs append their metrics after it and their cells to its list
    assert len(entry) == 1 and entry[0] in MANIFEST["per_layer"]
    assert {"gpt2-large.chat", "olmoe-1b-7b-8l.chat-long"} \
        <= set(entry[0]["workloads"])
