"""prefill_padded_rows_share on expositions written by hand: the two
counters' movement between the window's edges, and nothing where a program
has no such counters."""

import json
import os

from chipbench import run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

ROWS = "kfserving_tpu_engine_prefill_rows_total"
PADDED = "kfserving_tpu_engine_prefill_rows_padded_total"


def run_of(first: str, last: str) -> dict:
    return {"config": {"name": "m"},
            "scrapes": {"open": {"metrics": first},
                        "close": {"metrics": last}}}


def exposition(rows: int, padded: int, model: str = "m") -> str:
    return (f'{ROWS}{{model="{model}"}} {rows}\n'
            f'{PADDED}{{model="{model}"}} {padded}\n')


def test_the_share_of_the_dispatched_rows_that_no_request_filled():
    reader = bench.load_by_path("layer_metrics", "prefill_padded_rows_share")
    # 400 rows dispatched in the window, 76 of them dummies
    assert reader.read(run_of(exposition(120, 20),
                              exposition(520, 96))) == 19.0
    # every row a request's
    assert reader.read(run_of(exposition(120, 20),
                              exposition(520, 20))) == 0.0
    # another model's counters are not this cell's
    assert reader.read(run_of(exposition(120, 20, "other"),
                              exposition(520, 96, "other"))) is None


def test_nothing_to_read_is_nothing_reported():
    """A parent commit has no such counters; a window with no dispatch has
    no share."""
    reader = bench.load_by_path("layer_metrics", "prefill_padded_rows_share")
    assert reader.read(run_of("", "")) is None
    assert reader.read(run_of(
        'kfserving_tpu_engine_prefills{model="m"} 3\n',
        'kfserving_tpu_engine_prefills{model="m"} 9\n')) is None
    assert reader.read(run_of(exposition(120, 20),
                              exposition(120, 20))) is None
    assert reader.read({"config": {"name": "m"}, "scrapes": {}}) is None


def test_the_manifest_lists_it_for_the_closed_loop_cells():
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == "prefill_padded_rows_share"]
    assert entry["workloads"] == ["gpt2-large.chat",
                                  "olmoe-1b-7b-8l.chat-long",
                                  "nemotron-3-nano-16l-ep2.chat-wide"]
    assert entry["moves"] == "tokens_per_s" and entry["better"] == "lower"
    assert (entry["unit"], entry["layer"], entry["source"]) == (
        "%", "GenerationEngine", "program_counter")
