"""sampler_lean_call_share on expositions written by hand: the counter's
series differenced between the window's edges, nothing where a program
has no such counter, and what the manifest says of it."""

import json
import os

from chipbench import run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

CALLS = "kfserving_tpu_engine_sampler_tail_calls_total"
CLOSED_LOOP = ["gpt2-large.chat", "olmoe-1b-7b-8l.chat-long",
               "nemotron-3-nano-16l-ep2.chat-wide",
               "mellum2-12b-a2.5b-8l.code-context",
               "falcon-h1-34b-6l.chat-answers"]


def run_of(first: str, last: str) -> dict:
    return {"config": {"name": "m"},
            "scrapes": {"open": {"metrics": first},
                        "close": {"metrics": last}}}


def exposition(counts: dict, model: str = "m") -> str:
    """`counts`: (program, noise, logprobs) -> the counter's value."""
    return "".join(
        f'{CALLS}{{model="{model}",program="{program}",noise="{noise}",'
        f'logprobs="{logprobs}"}} {value}\n'
        for (program, noise, logprobs), value in counts.items())


def test_the_share_of_decode_dispatches_that_asked_for_one_argmax():
    reader = bench.load_by_path("layer_metrics", "sampler_lean_call_share")
    # every decode dispatch of the window lean; the check prompts' three,
    # served before it, and the prefills' are no part of it
    before = {("decode", "0", "0"): 40, ("decode", "0", "1"): 3,
              ("prefill", "0", "1"): 3, ("prefill", "0", "0"): 20}
    after = {**before, ("decode", "0", "0"): 240,
             ("prefill", "0", "0"): 90}
    assert reader.read(run_of(exposition(before),
                              exposition(after))) == 100.0
    # 200 lean, 40 with a sampled row and 10 asked for log-probabilities
    # (a series born inside the window counts from 0)
    after = {**after, ("decode", "1", "0"): 40, ("decode", "0", "1"): 13}
    assert reader.read(run_of(exposition(before),
                              exposition(after))) == 80.0
    # another model's counters are not this cell's
    assert reader.read(run_of(exposition(before, "other"),
                              exposition(after, "other"))) is None


def test_nothing_to_read_is_nothing_reported():
    """A parent commit has no such counter; a window with no decode
    dispatch has no share."""
    reader = bench.load_by_path("layer_metrics", "sampler_lean_call_share")
    other = 'kfserving_tpu_engine_wasted_token_steps{model="m"} 7\n'
    assert reader.read(run_of(other, other)) is None
    idle = exposition({("decode", "0", "0"): 40})
    assert reader.read(run_of(idle, idle)) is None
    assert reader.read({"config": {"name": "m"}, "scrapes": {}}) is None
    # nothing lean at all in a window that decoded: 0, not nothing
    assert reader.read(run_of(
        exposition({("decode", "0", "0"): 0}),
        exposition({("decode", "0", "0"): 0,
                    ("decode", "1", "0"): 5}))) == 0.0


def test_the_manifest_lists_it_for_the_closed_loop_cells():
    """Wherever it stands in the list: what it says, and that its cells
    are those that report `tpot_p50_ms`."""
    entry, = [m for m in MANIFEST["per_layer"]
              if m["name"] == "sampler_lean_call_share"]
    reader = bench.load_by_path("layer_metrics", "sampler_lean_call_share")
    assert entry == {"name": "sampler_lean_call_share", "unit": reader.UNIT,
                     "better": "higher", "source": reader.SOURCE,
                     "layer": reader.LAYER, "moves": reader.MOVES,
                     "workloads": entry["workloads"]}
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        "%", "model step", "tpot_p50_ms")
    assert entry["workloads"][:5] == CLOSED_LOOP
    judged, = [m for m in MANIFEST["end_to_end"]
               if m["name"] == "tpot_p50_ms"]
    assert set(entry["workloads"]) <= set(judged["workloads"])
