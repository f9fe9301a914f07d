"""prefill_prompts_per_row on expositions written by hand: three counters'
movement between the window's edges, 1.0 for a program that lays one prompt
in a row, and nothing where there is nothing to read."""

import json
import os

from chipbench import run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

NAME = "prefill_prompts_per_row"


def run_of(first: str, last: str) -> dict:
    return {"config": {"name": "m"},
            "scrapes": {"open": {"metrics": first},
                        "close": {"metrics": last}}}


def exposition(requests: int, rows: int, padded: int, model: str = "m") -> str:
    return (f'kfserving_tpu_engine_prefill_requests{{model="{model}"}} '
            f'{requests}\n'
            f'kfserving_tpu_engine_prefill_rows_total{{model="{model}"}} '
            f'{rows}\n'
            f'kfserving_tpu_engine_prefill_rows_padded_total'
            f'{{model="{model}"}} {padded}\n')


def test_prompts_over_the_rows_that_a_prompt_lay_in():
    reader = bench.load_by_path("layer_metrics", NAME)
    # 360 prompts in the window over 240 rows, 40 of them dummies
    assert reader.read(run_of(exposition(100, 120, 20),
                              exposition(460, 360, 60))) == 1.8
    # a program that lays one prompt in a row: every real row one prompt
    assert reader.read(run_of(exposition(100, 120, 20),
                              exposition(420, 500, 80))) == 1.0
    # another model's counters are not this cell's
    assert reader.read(run_of(exposition(100, 120, 20, "other"),
                              exposition(460, 360, 60, "other"))) is None


def test_nothing_to_read_is_nothing_reported():
    reader = bench.load_by_path("layer_metrics", NAME)
    assert reader.read(run_of("", "")) is None
    assert reader.read({"config": {"name": "m"}, "scrapes": {}}) is None
    # no dispatch inside the window
    assert reader.read(run_of(exposition(100, 120, 20),
                              exposition(100, 120, 20))) is None
    # a server without the rows' counters
    assert reader.read(run_of(
        'kfserving_tpu_engine_prefill_requests{model="m"} 3\n',
        'kfserving_tpu_engine_prefill_requests{model="m"} 9\n')) is None


def test_the_manifest_lists_it_where_the_padded_rows_are_listed():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    entry = by_name[NAME]
    assert entry["workloads"] == \
        by_name["prefill_padded_rows_share"]["workloads"]
    assert entry["moves"] == "tokens_per_s" and entry["better"] == "higher"
    reader = bench.load_by_path("layer_metrics", NAME)
    assert (entry["unit"], entry["layer"], entry["source"]) == (
        reader.UNIT, reader.LAYER, reader.SOURCE) == (
        "prompts/row", "GenerationEngine", "program_counter")
