"""parked_step_share on expositions written by hand: the two counters'
movement between the window's edges, and nothing where a program has no
such counter."""

import json
import os

from chipbench import run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

PARKED = "kfserving_tpu_engine_parked_token_steps"
WASTED = "kfserving_tpu_engine_wasted_token_steps"


def run_of(first: str, last: str) -> dict:
    return {"config": {"name": "m"},
            "scrapes": {"open": {"metrics": first},
                        "close": {"metrics": last}}}


def exposition(wasted: int, parked=None, model: str = "m") -> str:
    text = f'{WASTED}{{model="{model}"}} {wasted}\n'
    if parked is not None:
        text += f'{PARKED}{{model="{model}"}} {parked}\n'
    return text


def test_the_share_of_the_dead_steps_that_were_parked():
    reader = bench.load_by_path("layer_metrics", "parked_step_share")
    # 400 dead steps in the window, 300 of them past a budget's end
    assert reader.read(run_of(exposition(100, 60),
                              exposition(500, 360))) == 75.0
    # another model's counters are not this cell's
    assert reader.read(run_of(exposition(100, 60, "other"),
                              exposition(500, 360, "other"))) is None


def test_nothing_to_read_is_nothing_reported():
    """A parent commit counts wasted steps alone; a window with no dead
    step has no share."""
    reader = bench.load_by_path("layer_metrics", "parked_step_share")
    assert reader.read(run_of(exposition(100), exposition(500))) is None
    assert reader.read(run_of(exposition(100, 60),
                              exposition(100, 60))) is None
    assert reader.read({"config": {"name": "m"}, "scrapes": {}}) is None


def test_the_manifest_lists_it_for_the_closed_loop_cells():
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == "parked_step_share"]
    assert entry["workloads"] == ["gpt2-large.chat",
                                  "olmoe-1b-7b-8l.chat-long",
                                  "nemotron-3-nano-16l-ep2.chat-wide"]
    assert entry["moves"] == "tokens_per_s"
