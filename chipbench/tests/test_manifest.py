"""BENCHMARK.json against the files it names: the harness finds everything
by name, so a name without its file is a broken cell."""

import json
import os

from chipbench import run as bench

ROOT = bench.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)


def cells_of(metric):
    return set(metric.get("workloads",
                          [w["name"] for w in MANIFEST["workloads"]]))


def test_every_cell_has_its_files():
    for cell in MANIFEST["workloads"]:
        _, config, traffic = bench.find_cell(MANIFEST, cell["name"])
        assert config["name"] == cell["config"]
        assert bench.load_by_path("kinds", config["kind"]) is not None
        assert os.path.exists(os.path.join(
            bench.HERE, "references", config["reference"]["module"] + ".py"))
        assert traffic["loop"] in ("closed", "open")


def test_every_metric_has_a_reader_that_agrees_with_the_manifest():
    for metric in MANIFEST["end_to_end"]:
        assert bench.load_by_path("end_to_end", metric["name"]) is not None
    for metric in MANIFEST["per_layer"]:
        reader = bench.load_by_path("layer_metrics", metric["name"])
        assert reader is not None, metric["name"]
        assert (reader.UNIT, reader.LAYER, reader.SOURCE, reader.MOVES) == (
            metric["unit"], metric["layer"], metric["source"],
            metric["moves"]), metric["name"]


def test_a_layer_metric_moves_a_metric_its_cells_report():
    end_to_end = {m["name"]: cells_of(m) for m in MANIFEST["end_to_end"]}
    for metric in MANIFEST["per_layer"]:
        assert cells_of(metric) <= end_to_end[metric["moves"]], metric["name"]
    for cell in MANIFEST["workloads"]:
        reported = [m for m in MANIFEST["end_to_end"]
                    if cell["name"] in cells_of(m)]
        assert {"setup_s"} < {m["name"] for m in reported}
        assert any(cell["name"] in cells_of(m)
                   for m in MANIFEST["per_layer"])


def test_no_queue_made_latency_is_judged():
    """Where callers outnumber slots, a request's times are per-layer."""
    judged = [m for m in MANIFEST["end_to_end"]
              if m["name"].startswith(("first_answer", "request_"))]
    for name in set().union(*map(cells_of, judged)):
        _, config, traffic = bench.find_cell(MANIFEST, name)
        assert traffic["loop"] == "open" or \
            traffic["clients"] <= config["serving"]["max_slots"]
