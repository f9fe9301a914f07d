"""paged_blocks_per_iteration on expositions written by hand: blocks walked
over loop iterations between the window's edges, one pool or two, and nothing
where a program has no such counter."""

import json
import os

from chipbench import run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

SERIES = "kfserving_tpu_generator_decode_kv{}_{}_total"


def run_of(first: str, last: str) -> dict:
    return {"config": {"name": "m"},
            "scrapes": {"open": {"metrics": first},
                        "close": {"metrics": last}}}


def exposition(blocks: int, iterations=None, pools=None,
               model: str = "m") -> str:
    """One whole-context layer's two counters, and per pool
    {pool: (blocks, iterations)} for a model with window layers."""
    lines = [f'{SERIES.format("", "blocks_walked")}{{model="{model}"}} '
             f'{blocks}']
    if iterations is not None:
        lines.append(f'{SERIES.format("", "walk_iterations")}'
                     f'{{model="{model}"}} {iterations}')
    for pool, (walked, looped) in (pools or {}).items():
        labels = f'{{model="{model}",pool="{pool}"}}'
        lines.append(f'{SERIES.format("_pool", "blocks_walked")}{labels} '
                     f'{walked}')
        if looped is not None:
            lines.append(f'{SERIES.format("_pool", "walk_iterations")}'
                         f'{labels} {looped}')
    return "\n".join(lines) + "\n"


def test_blocks_over_iterations_between_the_edges():
    reader = bench.load_by_path("layer_metrics", "paged_blocks_per_iteration")
    # a wide pool: an iteration a block
    assert reader.read(run_of(exposition(100, 100),
                              exposition(900, 900))) == 1.0
    # a narrow one: 800 blocks in 250 iterations
    assert reader.read(run_of(exposition(100, 50),
                              exposition(900, 300))) == 3.2
    # another model's counters are not this cell's
    assert reader.read(run_of(exposition(100, 50, model="other"),
                              exposition(900, 300, model="other"))) is None


def test_a_window_model_sums_its_two_pools():
    """One layer of each kind: 2400 blocks in 600 iterations and 900 in
    300 are 3300 in 900; the unlabelled pair (the global layer again) is
    not counted twice."""
    reader = bench.load_by_path("layer_metrics", "paged_blocks_per_iteration")
    first = exposition(10, 4, {"global": (10, 4), "window": (9, 3)})
    last = exposition(2410, 604, {"global": (2410, 604),
                                  "window": (909, 303)})
    assert reader.read(run_of(first, last)) == 3300 / 900


def test_nothing_to_read_is_nothing_reported():
    """A parent commit counts the blocks alone, in one pool or in two; a
    window in which nothing was walked has no ratio."""
    reader = bench.load_by_path("layer_metrics", "paged_blocks_per_iteration")
    assert reader.read(run_of(exposition(100), exposition(900))) is None
    pools = {"global": (5, None), "window": (5, None)}
    later = {"global": (50, None), "window": (45, None)}
    assert reader.read(run_of(exposition(100, None, pools),
                              exposition(900, None, later))) is None
    assert reader.read(run_of(exposition(100, 50),
                              exposition(100, 50))) is None
    assert reader.read({"config": {"name": "m"}, "scrapes": {}}) is None


def test_the_manifest_lists_it_last_for_the_closed_loop_cells():
    entry = MANIFEST["per_layer"][-1]
    assert entry == {
        "name": "paged_blocks_per_iteration", "unit": "blocks",
        "better": "higher", "source": "program_counter", "layer": "kernels",
        "moves": "tpot_p50_ms",
        "workloads": ["gpt2-large.chat", "olmoe-1b-7b-8l.chat-long",
                      "nemotron-3-nano-16l-ep2.chat-wide",
                      "mellum2-12b-a2.5b-8l.code-context"]}
