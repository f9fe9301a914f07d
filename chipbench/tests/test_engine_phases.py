"""`engine_phases`: device idle gaps put to the engine's spans, on a
hand-made trace whose answers are known and on a small trace recorded on
the chip; and the readers fed by the engine's own counters, in one toy run
per loop kind on the CPU."""

import gzip
import importlib.util
import json
import os

import pytest

from chipbench import engine_phases, trace
from chipbench import run as bench

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "trace_engine_small.json.gz")
WITHOUT_SPANS = os.path.join(HERE, "data", "trace_small.json.gz")
US = 1000  # ns

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

IDLE_READERS = ("idle_launch_share", "idle_prep_share", "idle_loop_share")
COUNTER_READERS = {
    "closed": ("decode_dispatch_host_ms",),
    "open": ("prefill_dispatch_host_ms", "ttft_queued_mean_ms",
             "ttft_dispatch_mean_ms", "ttft_delivery_mean_ms"),
}


def handmade():
    ops = [["fusion.1", 0, 70 * US],
           ["fusion.2", 75 * US, 25 * US],        # 5 us launch gap before it
           ["fusion.9", 400 * US, 100 * US]]      # after a 300 us idle gap
    launcher = [
        ["engine.prep.decode", 0, 10 * US],
        ["engine.launch.decode", 10 * US, 40 * US],
        ["PjitFunction(decode_fn)", 12 * US, 30 * US],
        ["engine.prep.prefill", 85 * US, 35 * US],
        ["engine.launch.prefill", 120 * US, 260 * US],
        ["engine.launch.insert", 385 * US, 25 * US],
        ["engine.prep.decode", 940 * US, 10 * US],  # its last span
    ]
    loop = [
        ["engine.wait.fetch", 300 * US, 150 * US],
        ["engine.deliver", 500 * US, 20 * US],
        ["engine.wait.request", 530 * US, 370 * US],
        ["$selectors.py:1 select", 0, 1000 * US],
    ]
    fetcher = [["engine.fetch", 0, 1000 * US]]  # never asked
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": launcher},
                   {"name": "python3", "events": loop},
                   {"name": "python3", "events": fetcher}]},
    ]}


def test_idle_gaps_are_put_to_launch_prep_and_loop():
    table = engine_phases.reduce(handmade())
    phases = dict(table["idle_by_phase"])
    # 100..400 us: the launching thread's spans by overlap, the loop's wait
    # for the 5 us between two of them
    assert phases["engine.prep.prefill"] == pytest.approx(20e-6)
    assert phases["engine.launch.prefill"] == pytest.approx(260e-6)
    assert phases["engine.launch.insert"] == pytest.approx(15e-6)
    assert phases["engine.wait.fetch"] == pytest.approx(5e-6)
    # 500..1000 us: the launching thread in no span but for 10 us
    assert phases["engine.deliver"] == pytest.approx(20e-6)
    assert phases["engine.wait.request"] == pytest.approx(370e-6)
    assert phases["engine.prep.decode"] == pytest.approx(10e-6)
    # 520..530 and 900..940 no span covers; 950..1000 lies after the
    # launching thread's last span: a span open there is not in the trace
    assert phases[engine_phases.NO_SPAN] == pytest.approx(50e-6)
    assert phases[engine_phases.EDGE] == pytest.approx(50e-6)
    assert phases[engine_phases.SMALL] == pytest.approx(5e-6)
    # spans under which the device was busy, and the fetch thread's, claim
    # nothing
    assert not {"engine.launch.decode", "engine.fetch"} & set(phases)
    assert table["launch_s"] == pytest.approx(275e-6)
    assert table["prep_s"] == pytest.approx(30e-6)
    assert table["loop_s"] == pytest.approx(495e-6)
    assert table["no_span_s"] == pytest.approx(50e-6)
    assert table["edge_s"] == pytest.approx(50e-6)
    assert table["edges_s"] == [0.0, pytest.approx(50e-6)]
    assert table["spans"]["engine.launch.prefill"] == {
        "count": 1, "mean_ms": pytest.approx(0.26)}
    assert set(table["spans"]) == {
        "engine.prep.decode", "engine.launch.decode", "engine.prep.prefill",
        "engine.launch.prefill", "engine.launch.insert"}


def assert_adds_up(normalized):
    """The three shares and the small gaps are the idle time that
    `trace.reduce` gives for the same trace."""
    table = engine_phases.reduce(normalized)
    reduced = trace.reduce(normalized)
    idle_s = reduced["window_s"] - reduced["busy_s"]
    small_s = dict(table["idle_by_phase"]).get(engine_phases.SMALL, 0.0)
    assert table["window_s"] == pytest.approx(reduced["window_s"])
    assert table["idle_s"] == pytest.approx(idle_s, rel=1e-9)
    assert table["launch_s"] + table["prep_s"] + table["loop_s"] + small_s \
        == pytest.approx(idle_s, rel=1e-9)
    assert sum(s for _, s in table["idle_by_phase"]) \
        == pytest.approx(idle_s, rel=1e-9)
    return table, reduced


def test_the_phases_add_up_to_the_idle_time_of_the_hand_made_trace():
    table, reduced = assert_adds_up(handmade())
    assert reduced["busy_s"] == pytest.approx(195e-6)


def test_overlapping_spans_count_nothing_twice():
    normalized = handmade()
    normalized["planes"][1]["lines"][0]["events"].append(
        ["engine.launch.feed", 350 * US, 45 * US])  # over prefill's end
    table, _ = assert_adds_up(normalized)
    phases = dict(table["idle_by_phase"])
    assert phases["engine.launch.prefill"] == pytest.approx(260e-6)
    # what is left of the gap after prefill's end goes to the span that
    # started first: 380..395 to the feed, 395..400 to the insert
    assert phases["engine.launch.feed"] == pytest.approx(15e-6)
    assert phases["engine.launch.insert"] == pytest.approx(5e-6)
    assert "engine.wait.fetch" not in phases


def test_a_trace_without_engine_spans_gives_no_table():
    with gzip.open(WITHOUT_SPANS, "rt") as f:
        normalized = json.load(f)["trace"]
    assert engine_phases.reduce(normalized) is None
    only_host = {"planes": [p for p in handmade()["planes"]
                            if p["name"].startswith("/host")]}
    assert engine_phases.reduce(only_host) is None


def test_reduction_of_the_recorded_trace():
    with gzip.open(RECORDED, "rt") as f:
        recorded = json.load(f)
    table, reduced = assert_adds_up(recorded["trace"])
    for key, want in recorded["expect"].items():
        assert table[key] == pytest.approx(want, rel=1e-9), key
    # on the chip the launches take most of the idle time, and inside the
    # capture the spans leave little of it without a name
    assert table["launch_s"] > 0.9 * table["idle_s"] > table["prep_s"]
    assert table["no_span_s"] < 0.05 * table["idle_s"]
    assert table["edge_s"] > 0
    assert {"engine.launch.decode", "engine.launch.prefill",
            "engine.prep.prefill"} <= set(table["spans"])


def test_without_a_trace_the_idle_readers_read_nothing():
    run = {"trace_dir": None, "trace_reduced": None}
    for name in IDLE_READERS:
        assert bench.load_by_path("layer_metrics", name).read(run) is None
    assert run["engine_phases"] is None


def scrapes(first: str, last: str) -> dict:
    return {"open": {"t": 0.0, "metrics": first},
            "close": {"t": 1.0, "metrics": last}}


def test_histogram_mean_differences_sum_and_count():
    name = "kfserving_tpu_generator_dispatch_host_ms"
    first = (f'{name}_sum{{program="decode"}} 100\n'
             f'{name}_count{{program="decode"}} 10\n'
             f'{name}_sum{{program="prefill"}} 7\n'
             f'{name}_count{{program="prefill"}} 1\n')
    last = (f'{name}_sum{{program="decode"}} 540\n'
            f'{name}_count{{program="decode"}} 12\n'
            f'{name}_sum{{program="prefill"}} 7\n'
            f'{name}_count{{program="prefill"}} 1\n')
    run = {"scrapes": scrapes(first, last)}
    decode = bench.load_by_path("layer_metrics", "decode_dispatch_host_ms")
    prefill = bench.load_by_path("layer_metrics", "prefill_dispatch_host_ms")
    assert decode.read(run) == pytest.approx(220.0)
    assert prefill.read(run) is None  # it did not move in the window
    # a program from before the histogram: nothing to read, nothing raised
    assert decode.read({"scrapes": scrapes("", "")}) is None
    assert bench.load_by_path("layer_metrics", "ttft_queued_mean_ms").read(
        {"scrapes": scrapes("", "")}) is None


def test_programs_traced_and_peak_memory_readers():
    name = 'kfserving_tpu_jax_compile_events_total{event="trace"}'
    traced = bench.load_by_path("layer_metrics", "programs_traced_in_window")
    assert traced.read({"scrapes": scrapes(f"{name} 41\n",
                                           f"{name} 41\n")}) == 0
    assert traced.read({"scrapes": scrapes(f"{name} 41\n",
                                           f"{name} 43\n")}) == 2
    assert traced.read({"scrapes": scrapes("", "")}) is None
    peak = bench.load_by_path("layer_metrics", "hbm_peak_gb")
    assert peak.read({"device_after": {"hbm_peak": [15.2e9, 14e9]}}) \
        == pytest.approx(15.2)
    assert peak.read({"device_after": {"hbm_peak": [None]}}) is None
    assert peak.read({"device_after": {"hbm_in_use": [7e9]}}) is None


def toy_cells():
    """The toy configuration and traffic of test_cells_toy.py, which is
    not edited and not a package: loaded by its path."""
    spec = importlib.util.spec_from_file_location(
        "chipbench_test_cells_toy", os.path.join(HERE, "test_cells_toy.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.TOY, module.TOY_TRAFFIC


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda c: c["name"])
def test_counter_fed_readers_at_toy_size(cell, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    toy, toy_traffic = toy_cells()
    _, _, real_traffic = bench.find_cell(MANIFEST, cell["name"])
    traffic = toy_traffic[real_traffic["loop"]]
    run = bench.measure_cell(cell, toy, traffic, seed=2**31 + 24,
                             seconds=4.0, trace=False, platform="cpu")
    layers = bench.metrics_of(MANIFEST, "per_layer", "layer_metrics", run)
    for name in COUNTER_READERS[real_traffic["loop"]]:
        assert layers[name]["value"] > 0, name
    # JAX traced nothing between the window's edges, by its own count
    assert layers["programs_traced_in_window"]["value"] == 0
    assert layers["compiles_in_window"]["value"] == 0
    # no trace here: the idle shares are left out; the CPU reports no
    # memory statistics, so peak memory is left out or a value
    assert not set(IDLE_READERS) & set(layers)
    assert "hbm_peak" in run["device_after"]
    if "hbm_peak_gb" in layers:
        assert layers["hbm_peak_gb"]["value"] > 0
    if real_traffic["loop"] == "open":
        # the three stages are the engine's own time to first token
        stages = sum(layers[f"ttft_{s}_mean_ms"]["value"]
                     for s in ("queued", "dispatch", "delivery"))
        assert stages == pytest.approx(engine_phases.histogram_mean(
            run, "kfserving_tpu_llm_ttft_ms"), rel=1e-6)
