"""The three readers of PR 32 on runs written by hand and on the small
recorded trace: what prefill takes of the device, what the grouped expert
path costs a thousand prompt tokens, and the share of the offered rows its
matmuls visited.  Nothing to read is nothing reported: a parent commit has
neither the counters nor a trace in an untraced run."""

import gzip
import json
import os

import pytest

from chipbench import moe_scopes, run as bench

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_moe_small.json.gz")
with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

OFFERED = "kfserving_tpu_generator_moe_grouped_pair_rows_total"
COMPUTED = "kfserving_tpu_generator_moe_grouped_pair_rows_computed_total"
NEW = ("prefill_busy_share", "moe_grouped_ms_per_ktok",
       "moe_grouped_rows_computed_share")


def reader(name):
    return bench.load_by_path("layer_metrics", name)


def scrapes(first, last, model="m"):
    def text(offered, computed):
        return (f'{OFFERED}{{model="{model}"}} {offered}\n'
                f'{COMPUTED}{{model="{model}"}} {computed}\n')
    return {"open": {"metrics": text(*first)},
            "close": {"metrics": text(*last)}}


@pytest.mark.parametrize("programs,busy,want", [
    ({"jit_prefill_fn": {"count": 9, "seconds": 1.5},
      "jit_decode_fn": {"count": 8, "seconds": 1.2},
      "jit_insert_fn": {"count": 9, "seconds": 0.3}}, 3.0, 50.0),
    # two prefill programs (two buckets) are both prefill
    ({"jit_prefill_fn": {"count": 1, "seconds": 0.5},
      "jit_prefill_fn.1": {"count": 1, "seconds": 0.25},
      "jit_decode_fn": {"count": 8, "seconds": 2.0}}, 3.0, 25.0),
    # a traced part with no prefill dispatch: none of the device's time
    ({"jit_decode_fn": {"count": 8, "seconds": 2.0}}, 2.0, 0.0),
])
def test_prefill_busy_share(programs, busy, want):
    run = {"trace_reduced": {"programs": programs, "busy_s": busy}}
    assert reader("prefill_busy_share").read(run) == pytest.approx(want)


def test_grouped_ms_per_ktok_on_the_recorded_trace():
    with gzip.open(RECORDED, "rt") as f:
        recorded = json.load(f)
    table = moe_scopes.reduce(recorded["trace"])
    scopes = table["jit_prefill_fn"]["scopes"]
    run = {"trace_window": (10.0, 13.0), "moe_scopes": table,
           "records": [{"first": 10.5, "prompt_tokens": 300},
                       {"first": 12.9, "prompt_tokens": 200},
                       {"first": 9.0, "prompt_tokens": 999},   # before it
                       {"first": None, "prompt_tokens": 999}]}
    path = (scopes["moe.dispatch"] + scopes["moe.experts"]
            + scopes["moe.combine"])
    assert path > scopes["moe.experts"] > 0
    # the router, attention and the decode program's scopes are not the path
    assert reader("moe_grouped_ms_per_ktok").read(run) == pytest.approx(
        1e6 * path / 500)


def test_rows_computed_share_is_the_counters_movement():
    read = reader("moe_grouped_rows_computed_share").read
    run = {"config": {"name": "m"},
           "scrapes": scrapes((49152, 49152), (49152 * 11, 49152 * 3))}
    assert read(run) == pytest.approx(20.0)
    # another model's counters are not this cell's
    run["scrapes"] = scrapes((0, 0), (100, 20), model="other")
    assert read(run) is None


@pytest.mark.parametrize("run", [
    # an untraced run; a parent commit's exposition
    {"config": {"name": "m"}, "cell": {"name": "c"}, "trace_reduced": None,
     "trace_window": None, "trace_dir": None, "records": [],
     "scrapes": {"open": {"metrics": ""}, "close": {"metrics": ""}}},
    # a dense decoder's trace: no scope, no counter; no prefill in the window
    {"config": {"name": "m"}, "cell": {"name": "c"},
     "trace_reduced": {"programs": {}, "busy_s": 0.0},
     "trace_window": (1.0, 2.0), "records": [],
     "moe_scopes": {"jit_prefill_fn": {"calls": 1, "seconds": 0.1,
                                       "leaf_seconds": 0.1, "scopes": {}}},
     "scrapes": scrapes((7, 7), (7, 7))},
])
def test_nothing_to_read_is_nothing_reported(run):
    for name in NEW:
        assert reader(name).read(run) is None, name


def test_the_manifest_lists_them_in_order_over_the_cells_they_read():
    """Their order among themselves and after `moe_pairs_held_share`, and
    the cells each was added for: a later PR may append a metric after
    them and a cell to their lists."""
    names = [m["name"] for m in MANIFEST["per_layer"]]
    at = [names.index(name) for name in ("moe_pairs_held_share",) + NEW]
    assert at == sorted(at)
    experts = ["olmoe-1b-7b-8l.chat-long",
               "nemotron-3-nano-16l-ep2.chat-wide"]
    cells = {m["name"]: m["workloads"] for m in MANIFEST["per_layer"]}
    assert cells["prefill_busy_share"][:3] == ["gpt2-large.chat"] + experts
    assert cells["moe_grouped_ms_per_ktok"][:2] == experts
    assert cells["moe_grouped_rows_computed_share"][:2] == experts
