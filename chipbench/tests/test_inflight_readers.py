"""The five readers of PR 39 on scrapes written by hand: stalls counted in
the window, in-flight time by program, and the highest bucket that grew.
A server without the series (a parent commit) gives nothing to read, and
nothing is reported."""

import json
import os

import pytest

from chipbench import histograms, run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

INFLIGHT = "kfserving_tpu_generator_program_inflight_ms"
LAG = "kfserving_tpu_generator_deliver_lag_ms"
STALLS = "kfserving_tpu_generator_program_stalls_total"
CLOSED = ["gpt2-large.chat", "olmoe-1b-7b-8l.chat-long",
          "nemotron-3-nano-16l-ep2.chat-wide"]
NEW = {"program_stalls_in_window": ("count", "tokens_per_s", CLOSED),
       "decode_inflight_mean_ms": ("ms", "tpot_p50_ms", CLOSED),
       "inflight_max_ms": ("ms", "tokens_per_s", CLOSED),
       "prefill_inflight_mean_ms": ("ms", "request_mean_ms",
                                    ["gpt2-large.chat-paced"]),
       "deliver_lag_max_ms": ("ms", "request_mean_ms",
                              ["gpt2-large.chat-paced"])}
BOUNDS = (50, 100, 250, 1000)


def reader(name):
    return bench.load_by_path("layer_metrics", name)


def histogram(name, own_counts, total_ms, **labels):
    """The exposition of one histogram child whose buckets (BOUNDS, then
    +Inf) hold `own_counts` observations each: cumulative lines, as the
    server renders them, the labels sorted with `le` among them."""
    def line(suffix, value, **more):
        have = ",".join(f'{k}="{v}"'
                        for k, v in sorted({**labels, **more}.items()))
        return f"{name}{suffix}{{{have}}} {value}\n" if have else \
            f"{name}{suffix} {value}\n"

    text, below = f"# TYPE {name} histogram\n", 0
    for bound, own in zip(BOUNDS + ("+Inf",), own_counts):
        below += own
        text += line("_bucket", below, le=bound)
    return text + line("_count", below) + line("_sum", total_ms)


def run_of(open_text, close_text, model="m"):
    return {"config": {"name": model},
            "scrapes": {"open": {"metrics": open_text},
                        "close": {"metrics": close_text}}}


def stalls(model="m", **by_program):
    return "".join(f'{STALLS}{{model="{model}",program="{p}"}} {n}\n'
                   for p, n in sorted(by_program.items()))


PARENT = run_of("kfserving_tpu_generator_dispatch_host_ms_count 3\n",
                "kfserving_tpu_generator_dispatch_host_ms_count 9\n")


@pytest.mark.parametrize("name", sorted(NEW))
def test_the_manifest_lists_the_reader_as_the_issue_has_it(name):
    unit, moves, cells = NEW[name]
    (entry,) = [m for m in MANIFEST["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": unit, "better": "lower",
                     "source": "program_counter",
                     "layer": "GenerationEngine", "moves": moves,
                     "workloads": cells}
    module = reader(name)
    assert (module.UNIT, module.LAYER, module.SOURCE, module.MOVES) == (
        unit, "GenerationEngine", "program_counter", moves)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_parent_without_the_series_reports_nothing(name):
    assert reader(name).read(PARENT) is None
    assert reader(name).read({"config": {"name": "m"}, "scrapes": {}}) \
        is None


def test_no_stall_reads_zero_and_a_stall_is_summed_over_programs():
    read = reader("program_stalls_in_window").read
    at_rest = stalls(decode=0, prefill=0, chunk=0, spec=0)
    assert read(run_of(at_rest, at_rest)) == 0.0
    moved = stalls(decode=1, prefill=2, chunk=0, spec=0)
    assert read(run_of(at_rest, moved)) == 3.0
    # stalls before the window opened are not the window's
    assert read(run_of(moved, moved)) == 0.0
    # another model's engine in the same process is not this cell's
    assert read(run_of(at_rest, stalls(model="other", decode=4))) is None
    both = at_rest + stalls(model="other", decode=4)
    assert read(run_of(at_rest, both)) == 0.0


def test_a_label_set_born_inside_the_window_counts_from_zero():
    read = reader("program_stalls_in_window").read
    assert read(run_of(stalls(decode=0), stalls(decode=0, prefill=1))) \
        == 1.0


def test_inflight_means_are_a_programs_own():
    first = (histogram(INFLIGHT, (0, 0, 4, 0, 0), 600.0, program="decode")
             + histogram(INFLIGHT, (2, 0, 0, 0, 0), 50.0,
                         program="prefill"))
    last = (histogram(INFLIGHT, (0, 0, 14, 0, 0), 2100.0,
                      program="decode")
            + histogram(INFLIGHT, (7, 0, 0, 0, 0), 190.0,
                        program="prefill"))
    run = run_of(first, last)
    assert reader("decode_inflight_mean_ms").read(run) == \
        pytest.approx(150.0)
    assert reader("prefill_inflight_mean_ms").read(run) == \
        pytest.approx(28.0)
    # a program that fetched nothing in the window has no mean
    assert reader("prefill_inflight_mean_ms").read(
        run_of(first, first)) is None


@pytest.mark.parametrize("first,last,want", [
    # cumulative lines: only the 100-250 bucket's own count rose
    ((5, 5, 5, 0, 0), (5, 5, 9, 0, 0), 250.0),
    # a lower bucket grew too: the highest one is read
    ((5, 5, 5, 0, 0), (9, 5, 6, 0, 0), 250.0),
    # counts from before the window do not count
    ((5, 5, 5, 1, 0), (8, 5, 5, 1, 0), 50.0),
    # past the last bound: at least the last bound
    ((0, 0, 0, 0, 0), (1, 0, 0, 0, 1), 1000.0),
    # nothing observed in the window: nothing to read
    ((5, 5, 5, 1, 0), (5, 5, 5, 1, 0), None),
])
def test_the_highest_bucket_that_grew(first, last, want):
    run = run_of(histogram(LAG, first, 1.0), histogram(LAG, last, 2.0))
    assert reader("deliver_lag_max_ms").read(run) == want


def test_inflight_max_is_over_all_programs():
    first = (histogram(INFLIGHT, (3, 3, 3, 0, 0), 1.0, program="decode")
             + histogram(INFLIGHT, (1, 0, 0, 2, 0), 1.0,
                         program="prefill"))
    last = (histogram(INFLIGHT, (3, 9, 3, 0, 0), 1.0, program="decode")
            + histogram(INFLIGHT, (1, 0, 0, 2, 0), 1.0, program="prefill")
            + histogram(INFLIGHT, (0, 0, 1, 0, 0), 1.0, program="chunk"))
    # decode grew at 100, the new chunk child at 250; prefill's 1000 is old
    assert reader("inflight_max_ms").read(run_of(first, last)) == 250.0
    # the deliver-lag histogram beside it is not read for it
    assert reader("inflight_max_ms").read(run_of(
        first, first + histogram(LAG, (0, 0, 0, 0, 5), 1.0))) is None


def test_bucket_counts_undo_the_cumulation_and_keep_children_apart():
    text = (histogram(INFLIGHT, (1, 2, 3, 0, 4), 1.0, program="decode")
            + histogram(INFLIGHT + "_other", (9, 9, 9, 9, 9), 1.0))
    counts = histograms.bucket_counts(text, INFLIGHT)
    assert counts == {(("program", "decode"),): {
        50.0: 1, 100.0: 2, 250.0: 3, 1000.0: 0, float("inf"): 4}}
    assert histograms.bucket_counts(text, INFLIGHT, program="spec") == {}
    assert histograms.summed(text, INFLIGHT + "_count") == 10
