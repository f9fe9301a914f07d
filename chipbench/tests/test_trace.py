"""The reduction from a normalized trace to busy time, time per operation
and program, and idle gaps named by what the host did: on a hand-made trace
whose answers are known, and on a small trace recorded on the chip."""

import gzip
import json
import os

import pytest

from chipbench import trace

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_small.json.gz")
US = 1000  # ns


def handmade():
    ops = [
        ["while.3", 0, 100 * US],              # holds the three below
        ["fusion.1", 0, 30 * US],
        ["%paged_attention_tpu.7 = bf16[24,1,20,64]{3,2,1,0:T(8,128)} "
         "custom-call(s32[192]{0} %reshape.9)", 30 * US, 40 * US],
        ["fusion.2", 75 * US, 25 * US],        # 5 us launch gap before it
        ["fusion.9", 400 * US, 100 * US],      # after a 300 us idle gap
    ]
    modules = [["jit_decode_fn(123)", 0, 100 * US],
               ["jit_prefill_fn(77)", 400 * US, 100 * US]]
    launcher = [
        ["$thread.py:54 run", 0, 1000 * US],
        ["PjitFunction(decode_fn)", 0, 50 * US],
        ["$generator.py:1 _enqueue_prefill_group", 85 * US, 330 * US],
        ["PjitFunction(prefill_fn)", 90 * US, 320 * US],
        ["DevicePut", 120 * US, 100 * US],
        ["$queue.py:1 get", 520 * US, 470 * US],
    ]
    waiter = [["$selectors.py:1 select", 0, 1000 * US]]  # launches nothing
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops},
                   {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU",
         "lines": [{"name": "enqueue", "events": launcher},
                   {"name": "loop", "events": waiter}]},
    ]}


def test_union_merges_overlaps():
    assert trace.union_ns([(5, 7), (0, 3), (2, 4), (7, 9)]) == \
        [[0, 4], [5, 9]]


def test_reduction_of_a_hand_made_trace():
    out = trace.reduce(handmade())
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(1000e-6)
    # busy is the union: the while's 100 us and the last fusion's 100 us
    assert out["busy_s"] == pytest.approx(200e-6)
    # time per operation counts leaves: the while is left out
    assert "while" not in out["ops"]
    assert out["ops"]["fusion"] == {"count": 3,
                                    "seconds": pytest.approx(155e-6)}
    assert out["ops"]["paged_attention_tpu bf16[24,1,20,64]"]["count"] == 1
    assert out["programs"]["jit_decode_fn"]["count"] == 1
    assert out["programs"]["jit_prefill_fn"]["seconds"] == \
        pytest.approx(100e-6)
    gaps = dict(out["idle_gaps"])
    # 100..400 us: of the launching thread's frames that cover the gap the
    # innermost is named, not the frames around it, not the DevicePut inside
    # it that covers a third, and not the other thread's select
    assert gaps["PjitFunction(prefill_fn)"] == pytest.approx(300e-6)
    # 500..1000 us: the launching thread waited for work
    assert gaps["$queue.py:1 get"] == pytest.approx(500e-6)
    assert out["device_ops"][0][0] == "fusion"


def test_a_trace_without_a_device_plane_is_refused():
    only_host = {"planes": [p for p in handmade()["planes"]
                            if p["name"].startswith("/host")]}
    with pytest.raises(ValueError):
        trace.reduce(only_host)


def test_an_idle_capture_says_that_the_device_ran_nothing():
    """A capture of a chip with no work holds no device plane (a server
    whose load generator had run out of requests, until PR 41): the
    reduction still fails, and says what that means and what was there."""
    only_host = {"planes": [p for p in handmade()["planes"]
                            if p["name"].startswith("/host")]}
    with pytest.raises(ValueError) as refused:
        trace.reduce(only_host)
    message = str(refused.value)
    assert message.startswith("the device ran nothing during the capture")
    assert "/host:CPU" in message
    with pytest.raises(ValueError, match="the device ran nothing"):
        trace.reduce({"planes": []})


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace in this checkout")
def test_reduction_of_the_recorded_trace():
    with gzip.open(RECORDED, "rt") as f:
        recorded = json.load(f)
    out = trace.reduce(recorded["trace"])
    for key, want in recorded["expect"].items():
        assert out[key] == want, key
    assert 0 < out["busy_s"] < out["window_s"]
    assert any("paged_attention" in name for name in out["ops"])
    assert any("decode_fn" in name for name in out["programs"])
