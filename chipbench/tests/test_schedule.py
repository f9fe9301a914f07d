"""Every seed of a cell offers the same work: the same number of requests and
the same multiset of lengths; a closed loop's seed reorders them and draws
the characters, an open loop's draws the characters alone."""

import collections
import json
import os

import pytest

from chipbench import schedule

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "traffic")
SEEDS = [0, 1, 7, 2**31 + 11, 3000000019]


def load(name):
    return schedule.load_traffic(os.path.join(TRAFFIC, name + ".json"))


def lengths(requests):
    return collections.Counter(
        (r["prompt_tokens"], r["output_tokens"]) for r in requests)


def marginals(requests):
    return (sorted(r["prompt_tokens"] for r in requests),
            sorted(r["output_tokens"] for r in requests))


def test_quantiles_cover_the_distribution():
    values = schedule.quantile_lengths(
        {"dist": "loguniform", "lo": 32, "hi": 512}, 32)
    assert len(values) == 32 and values == sorted(values)
    assert 32 <= values[0] < 40 and 480 < values[-1] <= 512
    # mean of a log-uniform on [32, 512] is (512 - 32) / ln 16 = 173
    assert abs(sum(values) / 32 - 173) < 3


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json")))
def test_every_seed_offers_the_same_work(name):
    traffic = load(name)
    offered = []
    for seed in SEEDS:
        if traffic["loop"] == "closed":
            requests = schedule.closed_requests(traffic, seed, 100)
            block = traffic["block"]
            assert len(requests) % block == 0 and len(requests) >= 100
            # every block of a seed holds the same lengths as every other
            blocks = [marginals(requests[i:i + block])
                      for i in range(0, len(requests), block)]
            assert all(b == blocks[0] for b in blocks)
            offered.append((len(requests), blocks[0]))
        else:
            requests = schedule.open_requests(traffic, seed, 50.0)
            window = [r for r in requests if 0 <= r["due_s"] < 50.0]
            assert len(window) == round(traffic["rate_per_s"] * 50.0)
            lead = [r for r in requests if r["due_s"] < 0]
            assert all(-traffic["lead_in_s"] <= r["due_s"] for r in lead)
            assert [r["due_s"] for r in requests] == sorted(
                r["due_s"] for r in requests)
            offered.append((len(requests), marginals(window),
                            marginals(lead)))
        for r in requests:  # byte tokenizer: BOS + one token per character
            assert len(r["prompt"]) + 1 == r["prompt_tokens"]
    assert all(o == offered[0] for o in offered)


def test_a_seed_repeats_and_seeds_differ():
    traffic = load("chat-paced")
    a = schedule.open_requests(traffic, 5, 20.0)
    assert a == schedule.open_requests(traffic, 5, 20.0)
    b = schedule.open_requests(traffic, 6, 20.0)
    # the same arrivals and the same lengths at them for every seed (an open
    # loop's schedule is its work); other text
    for key in ("due_s", "prompt_tokens", "output_tokens"):
        assert [r[key] for r in a] == [r[key] for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    # a closed loop's seed reorders the lengths inside each block
    chat = load("chat")
    c, d = (schedule.closed_requests(chat, s, 64) for s in (5, 6))
    assert [r["output_tokens"] for r in c] != [r["output_tokens"] for r in d]
    json.dumps(a)  # a plan is plain data
