"""The five readers of PR 56 on scrapes written by hand: the share of the
window in which the host starved the device, and the longest collection,
held loop and held interpreter.  A server without the series (a parent
commit) gives nothing to read, and nothing is reported."""

import json
import os

import pytest

from chipbench import run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

STARVED = "kfserving_tpu_generator_device_starved_seconds_total"
HELD = "kfserving_tpu_process_held_ms"
GC = "kfserving_tpu_process_gc_pause_ms"
CLOSED = ["gpt2-large.chat", "olmoe-1b-7b-8l.chat-long",
          "nemotron-3-nano-16l-ep2.chat-wide",
          "mellum2-12b-a2.5b-8l.code-context",
          "falcon-h1-34b-6l.chat-answers",
          "moonlight-16b-a3b-7l.doc-answers"]
JUDGED_ON_TOKENS = ["gpt2-large.chat", "olmoe-1b-7b-8l.chat-long",
                    "nemotron-3-nano-16l-ep2.chat-wide",
                    "falcon-h1-34b-6l.chat-answers"]
NEW = {"device_starved_host_share": ("%", "tpot_p50_ms", CLOSED),
       "device_starved_host_share.paced": ("%", "request_mean_ms",
                                           ["gpt2-large.chat-paced"]),
       "gc_pause_max_ms": ("ms", "tokens_per_s", JUDGED_ON_TOKENS),
       "loop_held_max_ms": ("ms", "tokens_per_s", JUDGED_ON_TOKENS),
       "interpreter_held_max_ms": ("ms", "tokens_per_s", JUDGED_ON_TOKENS)}
BOUNDS = (1, 25, 250, 1000)


def reader(name):
    return bench.load_by_path("layer_metrics", name)


def histogram(name, own_counts, **labels):
    """One histogram child whose buckets (BOUNDS, then +Inf) hold
    `own_counts` observations each, as the server renders it."""
    def line(suffix, value, **more):
        have = ",".join(f'{k}="{v}"'
                        for k, v in sorted({**labels, **more}.items()))
        return f"{name}{suffix}{{{have}}} {value}\n"

    text, below = "", 0
    for bound, own in zip(BOUNDS + ("+Inf",), own_counts):
        below += own
        text += line("_bucket", below, le=bound)
    return text + line("_count", below) + line("_sum", 1.0)


def starved(host, no_work, model="m"):
    return (f'{STARVED}{{cause="host",model="{model}"}} {host}\n'
            f'{STARVED}{{cause="no_work",model="{model}"}} {no_work}\n')


def run_of(open_text, close_text, seconds=50.0, model="m"):
    return {"config": {"name": model},
            "scrapes": {"open": {"t": 100.0, "metrics": open_text},
                        "close": {"t": 100.0 + seconds,
                                  "metrics": close_text}}}


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
    # appended: what the benchmark had keeps its place
    assert [m["name"] for m in MANIFEST["per_layer"]][-5:] == [
        "device_starved_host_share", "device_starved_host_share.paced",
        "gc_pause_max_ms", "loop_held_max_ms", "interpreter_held_max_ms"]


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_parent_without_the_series_reports_nothing(name):
    assert reader(name).read(PARENT) is None
    assert reader(name).read({"config": {"name": "m"}, "scrapes": {}}) \
        is None


@pytest.mark.parametrize("name", ["device_starved_host_share",
                                  "device_starved_host_share.paced"])
def test_the_hosts_share_is_of_the_seconds_between_the_scrapes(name):
    read = reader(name).read
    at_rest = starved(0.0, 0.0)
    assert read(run_of(at_rest, at_rest)) == 0.0
    # 1.5 s of the host's in a window of 50: the wait for work is not in it
    assert read(run_of(starved(0.25, 3.0), starved(1.75, 21.0))) \
        == pytest.approx(3.0)
    assert read(run_of(starved(0.25, 3.0), starved(1.75, 21.0),
                       seconds=25.0)) == pytest.approx(6.0)
    # another model's engine in the same process is not this cell's
    assert read(run_of(at_rest, starved(9.0, 9.0, model="other"))) is None
    assert read(run_of(at_rest + starved(0.0, 0.0, model="other"),
                       starved(0.5, 0.0) + starved(9.0, 9.0, model="other"))
                ) == pytest.approx(1.0)


@pytest.mark.parametrize("name,series,labels", [
    ("gc_pause_max_ms", GC, {"generation": 2}),
    ("loop_held_max_ms", HELD, {"what": "loop"}),
    ("interpreter_held_max_ms", HELD, {"what": "interpreter"})])
def test_the_longest_pause_is_the_highest_bucket_that_grew(
        name, series, labels):
    read = reader(name).read
    first = histogram(series, (40, 2, 1, 0, 0), **labels)
    # only first buckets grew: a clean run
    assert read(run_of(first, histogram(series, (440, 2, 1, 0, 0),
                                        **labels))) == 1.0
    # one observation of 250 ms to a second: the run that strays
    assert read(run_of(first, histogram(series, (440, 2, 1, 1, 0),
                                        **labels))) == 1000.0
    # what was there before the window is not the window's
    assert read(run_of(first, first)) is None


def test_each_pause_reads_its_own_children():
    loop = histogram(HELD, (5, 0, 0, 0, 0), what="loop")
    both = (histogram(HELD, (9, 0, 0, 0, 0), what="loop")
            + histogram(HELD, (0, 0, 3, 0, 0), what="interpreter"))
    run = run_of(loop, both)
    assert reader("loop_held_max_ms").read(run) == 1.0
    assert reader("interpreter_held_max_ms").read(run) == 250.0
    assert reader("gc_pause_max_ms").read(run) is None
    # the collector's is over all generations
    young = histogram(GC, (7, 0, 0, 0, 0), generation=0)
    old = histogram(GC, (0, 1, 0, 0, 0), generation=2)
    assert reader("gc_pause_max_ms").read(
        run_of(young, histogram(GC, (9, 0, 0, 0, 0), generation=0)
               + old)) == 25.0
