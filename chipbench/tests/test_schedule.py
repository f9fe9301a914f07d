"""Every seed of a cell offers the same work: the same number of requests and
the same multiset of lengths; a closed loop's seed reorders them and draws
the characters, an open loop's draws the characters alone.  A closed loop's
list has no end: its first batch is what it always offered, and whole blocks
of the same work follow for as long as a client asks."""

import collections
import hashlib
import itertools
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


MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC) if f.endswith(".json"))
CLOSED = [name for name in MIXES if load(name)["loop"] == "closed"]


def test_quantiles_cover_the_distribution():
    values = schedule.quantile_lengths(
        {"dist": "loguniform", "lo": 32, "hi": 512}, 32)
    assert len(values) == 32 and values == sorted(values)
    assert 32 <= values[0] < 40 and 480 < values[-1] <= 512
    # mean of a log-uniform on [32, 512] is (512 - 32) / ln 16 = 173
    assert abs(sum(values) / 32 - 173) < 3


@pytest.mark.parametrize("name", MIXES)
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


# -- a closed loop's list has no end (PR 41) ---------------------------------
# sha256 of json.dumps(closed_requests(traffic, seed, traffic["requests"]),
# sort_keys=True), first 16 hex digits, computed with the code as it stood
# before the list lost its end (commit 0f11c5d): lengths and characters.
AS_IT_WAS = {("chat", 7): "e1202579bc13fff7",
             ("chat", 3000000019): "cfec6e0e1b1a4594",
             ("chat-long", 7): "5a6568926a652baa",
             ("chat-long", 3000000019): "931a16f9e07541ce",
             ("chat-wide", 7): "e982a531f018fc9a",
             ("chat-wide", 3000000019): "696a5d529c9b3668"}


def take(stream, n):
    return list(itertools.islice(stream, n))


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name", CLOSED)
def test_a_run_inside_its_first_batch_sends_what_it_always_sent(name, seed):
    traffic = load(name)
    first = schedule.closed_requests(traffic, seed, traffic["requests"])
    assert len(first) == traffic["requests"]  # whole blocks, in these files
    assert take(schedule.closed_stream(traffic, seed), len(first)) == first


@pytest.mark.parametrize("name,seed", sorted(AS_IT_WAS))
def test_the_first_batch_is_byte_for_byte_the_earlier_list(name, seed):
    traffic = load(name)
    first = take(schedule.closed_stream(traffic, seed), traffic["requests"])
    digest = hashlib.sha256(
        json.dumps(first, sort_keys=True).encode()).hexdigest()
    assert digest[:16] == AS_IT_WAS[name, seed]


@pytest.mark.parametrize("name", CLOSED)
def test_past_the_first_batch_every_block_holds_the_same_work(name):
    traffic = load(name)
    block, n = traffic["block"], traffic["requests"]
    offered = []
    for seed in SEEDS:
        stream = schedule.closed_stream(traffic, seed)
        first = take(stream, n)
        later = take(stream, 7 * block + 3)  # seven blocks and part of one
        requests = first + later
        # numbered without a gap, so a record names its request
        assert [r["i"] for r in requests] == list(range(len(requests)))
        for r in later:
            assert len(r["prompt"]) + 1 == r["prompt_tokens"]
            assert r["due_s"] is None
        pieces = [later[i:i + block] for i in range(0, 7 * block, block)]
        assert all(marginals(p) == marginals(first[:block]) for p in pieces)
        # freshly permuted: no later block repeats the order of another
        assert len({tuple((r["prompt_tokens"], r["output_tokens"])
                          for r in p) for p in pieces}) == 7
        offered.append(marginals(pieces[0]))
    assert all(o == offered[0] for o in offered)


@pytest.mark.parametrize("name", CLOSED)
def test_a_seed_repeats_and_seeds_differ_past_the_first_batch(name):
    traffic = load(name)
    n, block = traffic["requests"], traffic["block"]

    def later(seed):
        stream = schedule.closed_stream(traffic, seed)
        take(stream, n)
        return take(stream, 3 * block)

    a, b = later(5), later(6)
    assert a == later(5)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert [r["output_tokens"] for r in a] != [r["output_tokens"] for r in b]
    assert marginals(a) == marginals(b)
    json.dumps(a)  # plain data


def test_no_count_bounds_a_closed_list():
    """Far past any first batch the stream still gives whole blocks; a first
    batch smaller than one block is one block."""
    traffic = dict(load("chat"), requests=1)
    stream = schedule.closed_stream(traffic, 11)
    block = traffic["block"]
    far = take(stream, 300 * block)
    assert [r["i"] for r in far] == list(range(300 * block))
    assert marginals(far[-block:]) == marginals(far[:block])
