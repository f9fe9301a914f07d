"""The numbers `correct` compares with the reference, and their limits.

The readings are the cell's own, taken on the chip at its served size (PR 41,
call 9: chipbench/tests/data/nemotron_check_call9.json); what is rehearsed
here is the harness's arithmetic and verdict over them: every sound seed
comes out correct, the control (the reference through float8, put in the
served side's place) and an altered token come out not correct."""

import glob
import json
import os
import statistics

import pytest

from chipbench import run as bench
from chipbench.kinds import generate

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)
with open(os.path.join(bench.HERE, "tests", "data",
                       "nemotron_check_call9.json")) as f:
    CALL_9 = json.load(f)["seeds"]
CONTROL_SEEDS = sorted(s for s, e in CALL_9.items() if "float8" in e)
NEMOTRON = bench.find_cell(MANIFEST, "nemotron-3-nano-16l-ep2.chat-wide")[1]
UNIFORM = -11.7835  # log(1 / 131072): above where an id drawn at random lies


def verdict(served: list, reference: list, config: dict = NEMOTRON) -> dict:
    """What a run whose check read these log-probabilities would say."""
    gaps = generate.reference_gaps(
        [{"chosen": served, "top": []}], [{"chosen": reference, "top": []}])
    run = {"records": [], "window": [0.0, 1.0], "traffic": {"loop": "open"},
           "compiles_in_window": [], "device": {"platform": "tpu"},
           "platform": "tpu",
           "reference": {"gap": max(gaps),
                         "gap_median": statistics.median(gaps),
                         "limits": generate.reference_limits(config),
                         "tolerance": config["reference"]["tolerance"]}}
    return dict(bench.outcome(run), compared=bench.compared(run))


def test_every_gap_is_taken():
    cases = [{"chosen": [-1.0, -2.0], "top": [-1.0, -3.0]},
             {"chosen": [-4.0], "top": [-4.0]}]
    answers = [{"chosen": [-1.5, -2.0], "top": [-1.25, -3.0]},
               {"chosen": [-3.0], "top": [-4.0]}]
    assert generate.reference_gaps(cases, answers) == [
        0.5, 0.0, 0.25, 0.0, 1.0, 0.0]


@pytest.mark.parametrize("config", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_where_a_configuration_s_limits_come_from(config):
    with open(os.path.join(bench.ROOT, config["file"])) as f:
        config = json.load(f)
    limits = generate.reference_limits(config)
    path = os.path.join(bench.HERE, "limits", config["name"] + ".json")
    if not os.path.exists(path):
        assert limits == {"reference_gap": config["reference"]["tolerance"]}
        return
    with open(path) as f:
        entries = json.load(f)
    assert limits == {name: e["limit"] for name, e in entries.items()}
    assert set(limits) <= {"reference_gap", "reference_gap_median"}
    for name, e in entries.items():
        # between its two readings, the more room above the lower; an
        # upper reading is three times the lower or more
        assert e["lower"] < e["limit"] < e["upper"], name
        assert e["upper"] >= 3 * e["lower"], name
        assert e["limit"] / e["lower"] > e["upper"] / e["limit"], name


def test_a_limits_file_is_some_configuration_s():
    names = {c["name"] for c in MANIFEST["configs"]}
    for path in glob.glob(os.path.join(bench.HERE, "limits", "*.json")):
        assert os.path.basename(path)[:-len(".json")] in names, path


@pytest.mark.parametrize("seed", sorted(CALL_9))
def test_a_sound_seed_is_correct(seed):
    said = verdict(CALL_9[seed]["served"], CALL_9[seed]["reference"])
    assert said["correct"], said
    # ... with room: no sound reading passes the entries' `lower`
    with open(os.path.join(bench.HERE, "limits",
                           NEMOTRON["name"] + ".json")) as f:
        entries = json.load(f)
    for name, c in said["compared"].items():
        if name in entries:
            assert c["value"] <= entries[name]["lower"] + 5e-4, (name, c)


@pytest.mark.parametrize("seed", CONTROL_SEEDS)
def test_the_control_is_not_correct(seed):
    """float8 in the served side's place: the median refuses it on every
    seed; the widest gap, which a flipped router decides in any precision,
    would have passed it (that is why the median is compared)."""
    said = verdict(CALL_9[seed]["float8"], CALL_9[seed]["reference"])
    assert not said["correct"], said
    median = said["compared"]["reference_gap_median"]
    assert median["value"] > median["limit"]
    assert said["compared"]["reference_gap"]["value"] < 0.45


@pytest.mark.parametrize("seed", CONTROL_SEEDS)
def test_an_altered_token_is_not_correct(seed):
    """One served token replaced by another id: the reference scores the
    id it is given, and an id drawn at random lies below the uniform."""
    served = list(CALL_9[seed]["served"])
    reference = list(CALL_9[seed]["reference"])
    assert verdict(served, reference)["correct"]
    reference[7] = UNIFORM
    said = verdict(served, reference)
    assert not said["correct"]
    assert said["compared"]["reference_gap"]["value"] > 3.4
    median = said["compared"]["reference_gap_median"]
    assert median["value"] <= median["limit"]  # one answer: not its to see


def test_a_configuration_without_a_file_is_held_to_its_tolerance():
    config = {"name": "no-such-configuration",
              "reference": {"tolerance": 0.12}}
    assert verdict([-1.0, -2.0], [-1.1, -2.0], config)["correct"]
    said = verdict([-1.0, -2.0], [-1.13, -2.0], config)
    assert not said["correct"]
    assert list(said["compared"]) == ["reference_gap", "compiles_in_window"]
