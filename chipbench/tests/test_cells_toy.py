"""Each cell of BENCHMARK.json, rehearsed end to end at toy size on the CPU:
the real servers as children, the real load generator, the real readers.
What a rehearsal reads is a count or a check, never a speed."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from chipbench import run as bench

with open(os.path.join(bench.ROOT, "BENCHMARK.json")) as f:
    MANIFEST = json.load(f)

TOY = {
    "name": "toy-decoder", "kind": "generate",
    "n_layer": 4, "n_embd": 128, "n_head": 4, "layer_norm_epsilon": 1e-5,
    "server_module": "kfserving_tpu.predictors.llmserver",
    "serving": {"architecture": "decoder_tiny",
                "arch_kwargs": {"max_seq": 256}, "max_slots": 4,
                "max_seq": 256, "prefill_buckets": [64, 128],
                "block_size": 32, "cache_blocks": 32, "steps_per_call": 4,
                "tokenizer": "byte"},
    "warm_rows": [1, 2, 4], "trace_s": 2,
    # float32 on both sides at toy size: they agree to rounding
    "reference": {"module": "gpt2", "tolerance": 1e-3},
}
LENGTHS = {"prompt_tokens": {"dist": "loguniform", "lo": 8, "hi": 120},
           "output_tokens": {"dist": "loguniform", "lo": 4, "hi": 40}}
TOY_TRAFFIC = {
    "closed": {"loop": "closed", "clients": 6, "block": 6, "requests": 1200,
               "stagger_s": 1.0, "warm_rounds": 2, **LENGTHS},
    "open": {"loop": "open", "rate_per_s": 4.0, "lead_in_s": 2.0,
             "tail_s": 2.0, "arrival_seed": 1, **LENGTHS},
}


@pytest.fixture(autouse=True)
def on_the_cpu(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda c: c["name"])
def test_cell_at_toy_size(cell):
    _, _, real_traffic = bench.find_cell(MANIFEST, cell["name"])
    traffic = TOY_TRAFFIC[real_traffic["loop"]]
    run = bench.measure_cell(cell, TOY, traffic, seed=2**31 + 11,
                             seconds=4.0, trace=False, platform="cpu")
    result = bench.result_of(MANIFEST, run)
    assert result["correct"] and result["failed"] == 0, result
    assert result["attempted"] > 0
    wanted = {m["name"] for m in MANIFEST["end_to_end"]
              if cell["name"] in m.get("workloads", [cell["name"]])}
    assert set(result["metrics"]) == wanted
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "compared"  # each number beside its limit
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    if traffic["loop"] == "open":  # the same count of arrivals, every seed
        assert len([r for r in run["records"] if r["phase"] == "window"]) \
            == 16
    # Counters and client clocks are readable here; the trace's metrics need
    # the chip and are left out of the line.
    layers = bench.metrics_of(MANIFEST, "per_layer", "layer_metrics", run)
    assert {"ready_s", "compiles_in_window"} <= set(layers)
    assert layers["compiles_in_window"]["value"] == 0
    assert not {"decode_step_device_ms", "paged_attn_roofline"} & set(layers)
    if real_traffic["loop"] == "closed":
        assert 0 < layers["slot_occupancy"]["value"] <= 100
        assert 0 <= layers["wasted_step_share"]["value"] < 100


def test_a_closed_loop_never_runs_out_of_requests():
    """A first batch of 12 requests under a window that needs hundreds: the
    list goes on in whole blocks, tokens stream up to the window's close at
    an even pace, no note is left, and the cell reads what it reads with a
    first batch that the window never passes: by counts, since a rehearsal
    on a CPU that other processes share reads no speed."""
    cell = next(c for c in MANIFEST["workloads"]
                if c["name"] == "gpt2-large.chat")
    seconds, read = 8, {}
    for first_batch in (12, 1200):
        traffic = dict(TOY_TRAFFIC["closed"], requests=first_batch)
        run = bench.measure_cell(cell, TOY, traffic, seed=2**31 + 41,
                                 seconds=float(seconds), trace=False,
                                 platform="cpu")
        result = bench.result_of(MANIFEST, run)
        assert result["correct"] and result["failed"] == 0, result
        assert result["metrics"]["tokens_per_s"]["value"] > 0
        assert run["notes"] == []
        opened, closed = run["window"]
        tokens = sorted(t for r in run["records"] for t in r["tokens"])
        by_second = [sum(1 for t in tokens if k <= t - opened < k + 1)
                     for k in range(seconds)]
        # no second of the window thins out, the last one least of all
        assert min(by_second) > 20, by_second
        assert min(by_second) > 0.5 * sorted(by_second)[seconds // 2], \
            by_second
        assert closed - tokens[-1] < 0.25  # flowing when the run was cut
        sent = [r["i"] for r in run["records"]]
        assert sent == list(range(len(sent)))  # pulled from the front
        ended = [r for r in run["records"] if r["ok"] and r["tokens"]
                 and opened <= r["tokens"][-1] < closed]
        layers = bench.metrics_of(MANIFEST, "per_layer", "layer_metrics", run)
        read[first_batch] = {
            "sent": len(sent),
            "tokens_a_request": sum(len(r["tokens"]) for r in ended)
            / len(ended),
            "slot_occupancy": layers["slot_occupancy"]["value"],
            "wasted_step_share": layers["wasted_step_share"]["value"]}
    short, long = read[12], read[1200]
    assert short["sent"] > 200 and long["sent"] <= 1200, read
    # The same work in another order keeps the server as full: of the slot-
    # steps it ran in the window, the same share held a live request and the
    # same share ran past a request's end, and a request that ended there was
    # as long.  A caller that waited for its next request would empty a slot.
    assert abs(short["slot_occupancy"] - long["slot_occupancy"]) < 2, read
    assert abs(short["wasted_step_share"] - long["wasted_step_share"]) < 2, \
        read
    assert 0.95 < short["tokens_a_request"] / long["tokens_a_request"] \
        < 1.05, read


def fake_run(**over):
    run = {"records": [], "window": [0.0, 1.0], "traffic": {"loop": "open"},
           "reference": {"gap": 0.01, "tolerance": 0.05},
           "compiles_in_window": [], "device": {"platform": "tpu"},
           "platform": "tpu"}
    run.update(over)
    return run


def test_what_makes_a_run_incorrect():
    assert bench.outcome(fake_run())["correct"]
    assert bench.compared(fake_run()) == {
        "reference_gap": {"value": 0.01, "limit": 0.05},
        "compiles_in_window": {"value": 0, "limit": 0}}
    assert not bench.outcome(fake_run(
        reference={"gap": 0.06, "tolerance": 0.05}))["correct"]
    assert not bench.outcome(fake_run(
        compiles_in_window=["jit(prefill_fn) ..."]))["correct"]
    assert not bench.outcome(fake_run(
        device={"platform": "cpu"}))["correct"]


def test_the_command_line_ends_both_streams_with_what_was_compared(
        monkeypatch, capsys):
    result = {"correct": False, "attempted": 3, "failed": 0, "metrics": {},
              "device": {}, "compared": bench.compared(fake_run(
                  reference={"gap": 0.06, "tolerance": 0.05}))}
    monkeypatch.setattr(bench, "measure_cell", lambda *a, **k: {})
    monkeypatch.setattr(bench, "result_of", lambda manifest, run: result)
    rc = bench.main(["--workload", MANIFEST["workloads"][0]["name"],
                     "--seed", "3000000019", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert rc == 0  # a run that has a result says so in the line alone
    assert json.loads(out.splitlines()[-1]) == result
    assert err.splitlines()[-2:] == [
        "[chipbench] compared reference_gap: 0.06 (limit 0.05)",
        "[chipbench] compared compiles_in_window: 0 (limit 0)"]


def test_the_command_line_gives_no_result_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=bench.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "the cell needs" in proc.stderr


def test_the_command_line_gives_no_result_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(bench.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench.HERE, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
