"""OLMoE (models/olmoe.py, ops/moe.py) against the plain reference
(tests/olmoe_reference.py) at `olmoe_tiny` size on seeded weights.

Everything compares logits or log-probabilities, never sampled tokens
alone: with random weights the largest logit changes on rounding.  Both
sides compute in float32 on the CPU, so they differ by the order of their
sums only: a few 1e-6 on logits of magnitude 4 here.  The tolerance, 1e-4,
is forty times that and a hundredth of what leaving out any term of the
layer would move (dropping the QK-norm's scale, the rotation or one expert
of the top two moves logits by 1e-2 and more).
"""

import asyncio
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.traverse_util import flatten_dict

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import olmoe_reference as reference  # noqa: E402

from kfserving_tpu.engine.generator import GenerationEngine  # noqa: E402
from kfserving_tpu.models import create_model, init_params  # noqa: E402
from kfserving_tpu.models.olmoe import OlmoeLM, olmoe_tiny  # noqa: E402
from kfserving_tpu.ops import moe  # noqa: E402

TOL = 1e-4
MAX_SEQ = 128
BS = 16
MODEL = dict(experts_per_token=2, rope_theta=10000.0)


@pytest.fixture(scope="module")
def tiny():
    spec = create_model("olmoe_tiny", max_seq=MAX_SEQ)
    variables = init_params(spec, seed=3)
    flat = {"/".join(k): np.asarray(v)
            for k, v in flatten_dict(variables).items()}
    return spec.module, variables, flat


def prompt_of(n, stride=7):
    return [(i * stride) % 250 + 1 for i in range(n)]


def ref_log_probs(flat, ids):
    return np.asarray(reference.log_probs(flat, ids, 2, 1e-5, **MODEL))


async def served(engine, prompt, steps):
    """(tokens, chosen log-probabilities, top-5 records) of one greedy
    request through the engine."""
    req = engine.submit(prompt, steps, logprobs=5)
    tokens = [t async for t, _ in engine.stream(req) if t is not None]
    return tokens, req.lp_chosen, req.lp_top


def assert_matches_reference(flat, prompt, tokens, chosen, top):
    """Teacher forcing: the reference's row after the prompt's last token
    scores the first served token, the next row the second, ..."""
    rows = ref_log_probs(flat, prompt + tokens[:-1])[len(prompt) - 1:]
    assert len(tokens) == len(chosen) == len(top) == len(rows)
    for row, token, lp, record in zip(rows, tokens, chosen, top):
        assert token == int(np.argmax(row))
        assert abs(lp - row[token]) < TOL
        for tid, tlp in record:
            assert abs(tlp - row[tid]) < TOL


def engine_of(tiny, **kw):
    module, variables, _ = tiny
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq", MAX_SEQ)
    kw.setdefault("prefill_buckets", [32, 64, MAX_SEQ])
    kw.setdefault("block_size", BS)
    return GenerationEngine(module, variables, name="olmoe-test", **kw)


# -- the model against the reference -----------------------------------------
def test_full_forward_logits(tiny):
    module, variables, flat = tiny
    ids = prompt_of(48)
    want = np.asarray(reference.logits(flat, ids, 2, 1e-5, **MODEL))
    got = np.asarray(module.apply(variables, jnp.asarray([ids])))[0]
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_bucket_padding_changes_nothing_and_is_routed_nowhere(tiny):
    module, variables, flat = tiny
    ids = prompt_of(40)
    want = np.asarray(reference.logits(flat, ids, 2, 1e-5, **MODEL))
    padded = jnp.asarray([ids + [0] * 24, prompt_of(64, 5)])
    (got, _), state = module.apply(
        variables, padded, kv_lengths=jnp.asarray([40, 64]),
        return_cache=True, mutable=["moe"])
    np.testing.assert_allclose(np.asarray(got)[0, :40], want, atol=TOL,
                               rtol=0)
    pairs = np.asarray(module.routed_pairs(state))
    assert pairs.shape == (2, 8)
    assert (pairs.sum(axis=1) == (40 + 64) * 2).all()


def test_router_chooses_the_references_experts(tiny):
    module, variables, flat = tiny
    ids = prompt_of(48)
    routing = []
    reference.logits(flat, ids, 2, 1e-5, routing=routing, **MODEL)
    _, state = module.apply(
        variables, jnp.asarray([ids]), capture_intermediates=(
            lambda mdl, _: mdl.name == "router"), mutable=["intermediates"])
    for i, want in enumerate(routing):
        logits = state["intermediates"][f"layer_{i}"]["experts"]["router"][
            "__call__"][0]
        _, got = moe.route(logits, MODEL["experts_per_token"])
        assert [set(r) for r in np.asarray(got).tolist()] \
            == [set(r) for r in want.tolist()]


# An expert's form and the experts held are arguments of every path:
# OLMoE's (gated, all held), Nemotron-H's (plain relu², a share of the
# router's experts starting at `first`), and the two crossed.
FORMS = [("gated", None), ("plain", None), ("plain", 2), ("gated", 5)]


def layer_of(rng, form, first, e, h, f, k, tokens, router="softmax"):
    """(x, gate or None, up, down, weights, experts) of one expert layer
    whose router runs over `e` experts; with a share, the 4 experts from
    `first` are held."""
    held = e if first is None else 4
    x = jnp.asarray(rng.standard_normal((tokens, h)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((held, h, f)) / h ** 0.5,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((held, f, h)) / f ** 0.5,
                       jnp.float32)
    logits = jnp.asarray(rng.standard_normal((tokens, e)), jnp.float32)
    if router == "softmax":
        weights, experts = moe.route(logits, k)
    else:
        weights, experts = moe.route_sigmoid(
            logits, jnp.asarray(rng.standard_normal(e) * 0.5, jnp.float32),
            k, 2.5)
    return x, (gate if form == "gated" else None), up, down, weights, experts


def plain_sum(x, gate, up, down, weights, experts, valid, first):
    """The layer as a loop over tokens and their chosen experts."""
    x, up, down, weights = (np.asarray(t, np.float64)
                            for t in (x, up, down, weights))
    out = np.zeros_like(x)
    for t, e in np.ndindex(experts.shape):
        at = int(experts[t, e]) - (first or 0)
        if (valid is not None and not valid[t]) or not 0 <= at < len(up):
            continue
        u = x[t] @ up[at]
        if gate is None:
            act = np.maximum(u, 0.0) ** 2
        else:
            g = x[t] @ np.asarray(gate, np.float64)[at]
            act = g / (1 + np.exp(-g)) * u
        out[t] += weights[t, e] * (act @ down[at])
    return out


@pytest.mark.parametrize("form,first", FORMS)
@pytest.mark.parametrize("tokens", [24, 300])
def test_grouped_and_streamed_expert_paths_agree(tokens, form, first):
    rng = np.random.default_rng(tokens)
    e, h, f, k = 8, 32, 48, 3
    x, gate, up, down, probs, experts = layer_of(rng, form, first, e, h, f,
                                                 k, tokens)
    valid = jnp.asarray(rng.random(tokens) < 0.8)
    streamed = moe.experts_streamed(x, gate, up, down, probs, experts,
                                    valid, first)
    grouped = moe.experts_grouped(x, gate, up, down, probs, experts, valid,
                                  first)
    want = plain_sum(x, gate, up, down, probs, np.asarray(experts),
                     np.asarray(valid), first)
    np.testing.assert_allclose(np.asarray(streamed), want, atol=1e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(grouped), np.asarray(streamed),
                               atol=1e-5, rtol=0)
    assert not np.asarray(grouped)[~np.asarray(valid)].any()
    # the dispatcher takes the path that fits the token count
    chosen = moe.routed_experts(x, gate, up, down, probs, experts, valid,
                                first)
    np.testing.assert_allclose(np.asarray(chosen), np.asarray(streamed),
                               atol=1e-5, rtol=0)
    pairs = np.asarray(moe.routed_pairs(experts, up.shape[0], valid, first))
    local = np.asarray(experts)[np.asarray(valid)] - (first or 0)
    assert pairs.tolist() == [int((local == i).sum())
                              for i in range(up.shape[0])]


@pytest.mark.parametrize("form,first", FORMS)
@pytest.mark.parametrize("tokens,valid_share", [(24, None), (5, None),
                                                (40, 0.6)])
def test_touched_experts_kernel_agrees_with_the_grouped_path(tokens,
                                                             valid_share,
                                                             form, first):
    """The Pallas kernel that serves a decode wave on the chip, run by the
    interpreter here: lane-aligned widths, a third of the experts chosen
    by no token (their blocks are never addressed), with and without
    padding tokens; three matrices an expert or two, all experts held or
    four of the twelve.  float32 on both sides, so 1e-5 as above."""
    rng = np.random.default_rng(tokens)
    e, h, f, k = 12, 256, 128, 3
    x, gate, up, down, _, _ = layer_of(rng, form, first, e, h, f, k, tokens)
    logits = rng.standard_normal((tokens, e))
    logits[:, ::3] -= 100.0
    probs, experts = moe.route(jnp.asarray(logits, jnp.float32), k)
    assert len(set(np.asarray(experts).reshape(-1).tolist())) <= 8
    valid = (None if valid_share is None
             else jnp.asarray(rng.random(tokens) < valid_share))
    grouped = moe.experts_grouped(x, gate, up, down, probs, experts, valid,
                                  first)
    touched = moe.experts_touched(x, gate, up, down, probs, experts, valid,
                                  first, interpret=True)
    np.testing.assert_allclose(np.asarray(touched), np.asarray(grouped),
                               atol=1e-5, rtol=0)
    if valid is not None:
        assert not np.asarray(touched)[~np.asarray(valid)].any()


# -- the grouped path: the real pairs' work and no more ------------------------
TILE = 8  # rows a tile of the grouped kernel holds in these tests


def valid_with(per_token, target: int):
    """[T] bool: tokens, taken in order, whose real pairs add up to
    `target` exactly."""
    valid, total = np.zeros(len(per_token), bool), 0
    for i, c in enumerate(per_token):
        if c and total + c <= target:
            valid[i], total = True, total + c
    assert total == target, (total, target)
    return valid


def grouped_case(real, form, first, dtype=jnp.float32):
    """One expert layer of 40 tokens x 3 choices and the `valid` that
    leaves it the real pairs asked for: every pair (no mask), a third
    of the tokens, all of them (a mask that masks nothing), none, or
    tokens whose real pairs end on the edge of the third tile of 8 rows,
    or as little past it as can be (a token of a layer that holds every
    expert brings its three pairs or none)."""
    rng = np.random.default_rng(7)
    e, h, f, k, tokens = 8, 32, 48, 3, 40
    x, gate, up, down, probs, experts = layer_of(rng, form, first, e, h, f,
                                                 k, tokens)
    x, up, down = (a.astype(dtype) for a in (x, up, down))
    gate = None if gate is None else gate.astype(dtype)
    per_token = np.asarray(
        (moe._held(experts, up.shape[0], first, None) < up.shape[0]).sum(-1))
    valid = {"every": None, "third": rng.random(tokens) < 1 / 3,
             "all": np.ones(tokens, bool),
             "none": np.zeros(tokens, bool)}.get(real)
    if real in ("edge", "past"):
        valid = valid_with(per_token, 3 * TILE + (real == "past") * (
            k if first is None else 1))
    n = int(per_token.sum() if valid is None else per_token[valid].sum())
    valid = None if valid is None else jnp.asarray(valid)
    return (x, gate, up, down, probs, experts, valid, first), n


@pytest.mark.parametrize("form,first", FORMS)
@pytest.mark.parametrize("real", ["every", "third", "all", "none", "edge",
                                  "past"])
@pytest.mark.parametrize("way", ["ragged", "kernel"])
def test_the_grouped_path_computes_the_real_pairs(monkeypatch, way, real,
                                                  form, first):
    """Gated and plain experts, all held and a share, by XLA's grouped
    matmul and by the kernel (its interpreter, tiles of 8 rows): every
    real pair and nothing else, whether the real pairs are all of the
    T x k, a third, none at all, or end on a tile's edge or one past
    it.  float32 on both sides."""
    monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", TILE)
    args, n = grouped_case(real, form, first)
    assert real not in ("none", "edge") or n == {"none": 0,
                                                  "edge": 3 * TILE}[real]
    assert real != "past" or 0 < n % TILE <= 3
    want = moe.experts_streamed(*args)
    got = moe.experts_grouped(*args, interpret=way == "kernel")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5,
                               rtol=0)
    if args[6] is not None:
        assert not np.asarray(got)[~np.asarray(args[6])].any()


@pytest.mark.parametrize("form,first", FORMS)
@pytest.mark.parametrize("way", ["ragged", "kernel"])
def test_the_grouped_path_in_bfloat16(monkeypatch, way, form, first):
    """The served precision: bfloat16 operands, float32 sums, an
    expert's output rounded once and the k weighted rows summed in
    float32.  Within 0.03 of the float64 loop on outputs of size 1,
    where a float8 expert output would be 0.1 away."""
    monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", 16)
    args, _ = grouped_case("third", form, first, jnp.bfloat16)
    got = moe.experts_grouped(*args, interpret=way == "kernel")
    assert got.dtype == jnp.bfloat16
    x, gate, up, down, probs, experts, valid, _ = args
    want = plain_sum(*(None if a is None else np.asarray(a, np.float32)
                       for a in (x, gate, up, down)), probs,
                     np.asarray(experts), np.asarray(valid), first)
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(np.asarray(got, np.float32), want, atol=0.03,
                               rtol=0)


@pytest.mark.parametrize("real", ["none", "edge", "past", "third", "every"])
def test_the_grouped_kernel_visits_the_real_rows_tiles_and_no_other(
        monkeypatch, real):
    """The work is bounded by the real pairs, not by T x k: the kernel's
    walk ends at the tile that holds the last real row.  Its result
    takes the rows' place in memory, so a tile it did not visit comes
    back as it went in; in the tiles it visited, rows past the last
    group are zero.  The counters report the extent of those tiles
    (`grouped_rows_real`), not how often the walk visited them."""
    monkeypatch.setattr(moe, "GROUPED_TILE_ROWS", TILE)
    (x, gate, up, down, _, experts, valid, first), n = grouped_case(
        real, "plain", 2)
    e, (t, k) = up.shape[0], experts.shape
    held = moe._held(experts, e, first, valid)
    order = jnp.argsort(held.reshape(-1), stable=True)
    rows = x[order // k]
    out = np.asarray(moe._grouped_tiles(
        rows, [up, down], moe.routed_pairs(held, e), interpret=True))
    visited = moe.grouped_rows_real(n)
    assert visited == -(-n // TILE) * TILE <= t * k
    assert visited - n < TILE
    assert not out[n:visited].any()
    np.testing.assert_array_equal(out[visited:], np.asarray(rows)[visited:])
    if n:
        assert np.abs(out[:n]).max() > 0
    # the counters' arithmetic, on a layer's pairs and on several layers'
    assert moe.grouped_rows_real(np.array([0, 1, 256, 257])).tolist() == [
        0, TILE, 256, 256 + TILE]


def test_the_sigmoid_router_chooses_by_biased_score_and_weighs_by_score():
    """Nemotron-H's router: the k largest of sigmoid(logit) + bias are
    chosen; the weights are scale x their sigmoids over the sum of the
    chosen sigmoids, the bias left out."""
    rng = np.random.default_rng(5)
    logits = rng.standard_normal((40, 16)).astype(np.float32)
    bias = (rng.standard_normal(16) * 0.7).astype(np.float32)
    weights, experts = moe.route_sigmoid(jnp.asarray(logits),
                                         jnp.asarray(bias), 4, 2.5)
    scores = 1 / (1 + np.exp(-logits.astype(np.float64)))
    want = np.argsort(-(scores + bias), axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                  np.sort(want, -1))
    picked = np.take_along_axis(scores, np.asarray(experts), axis=-1)
    np.testing.assert_allclose(
        np.asarray(weights), 2.5 * picked / picked.sum(-1, keepdims=True),
        atol=1e-6, rtol=0)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, atol=1e-5)
    # the bias moved choices, and no weight holds it
    unbiased = np.argsort(-scores, axis=-1)[:, :4]
    assert (np.sort(unbiased, -1) != np.sort(want, -1)).any()
    assert np.asarray(experts).dtype == np.int32


@pytest.mark.parametrize("path", ["streamed", "grouped", "touched"])
def test_the_shares_of_a_layer_add_up_to_the_layer(path):
    """Three chips that hold experts 0-3, 4-7 and 8-11 of a 12-expert
    layer compute parts that sum to what one holder of all 12 computes,
    by every path (plain experts, the sigmoid router)."""
    rng = np.random.default_rng(11)
    e, h, f, k, tokens = 12, 256, 128, 3, 20
    x, _, up, down, weights, experts = layer_of(
        rng, "plain", None, e, h, f, k, tokens, router="sigmoid")
    valid = jnp.asarray(rng.random(tokens) < 0.8)

    def run(up, down, first):
        if path == "touched":
            return moe.experts_touched(x, None, up, down, weights, experts,
                                       valid, first, interpret=True)
        return getattr(moe, "experts_" + path)(x, None, up, down, weights,
                                               experts, valid, first)

    whole = np.asarray(run(up, down, None))
    parts = sum(np.asarray(run(up[i:i + 4], down[i:i + 4], i))
                for i in (0, 4, 8))
    np.testing.assert_allclose(parts, whole, atol=1e-5, rtol=0)
    np.testing.assert_allclose(
        whole, plain_sum(x, None, up, down, weights, np.asarray(experts),
                         np.asarray(valid), None), atol=1e-4, rtol=0)


def test_the_kernel_serves_few_tokens_on_a_tpu_outside_a_mesh(monkeypatch):
    """What `routed_experts` and the grouped path ask at trace time, and
    nothing else: the backend, the ambient mesh and the shapes."""
    from kfserving_tpu.ops import attention

    gate = jax.ShapeDtypeStruct((64, 2048, 1024), jnp.bfloat16)
    assert not moe._kernel_serves(24, gate)  # the CPU
    monkeypatch.setattr(attention, "_tpu_backend", lambda: True)
    assert moe._kernel_serves(24, gate)
    assert moe._kernel_serves(moe.GROUPED_TILE_ROWS, gate)
    # the grouped kernel besides: only the shapes with a record on the
    # chip (Nemotron-H's 4 and 8 rows of 1024), in bfloat16
    rows = lambda n, h: jax.ShapeDtypeStruct((n * 1024, h), jnp.bfloat16)
    up = jax.ShapeDtypeStruct((64, 2688, 1920), jnp.bfloat16)
    assert [n for n in (1, 2, 3, 4, 8, 16)
            if moe._grouped_kernel_serves(rows(n, 2688), 6, up, 2)] == [4, 8]
    assert not moe._grouped_kernel_serves(
        jax.ShapeDtypeStruct((4096, 2688), jnp.float32), 6, up, 2)
    assert not any(moe._grouped_kernel_serves(rows(n, 2048), 8, gate, 3)
                   for n in (1, 2, 4, 8, 16))  # OLMoE: 49 ramps of 100
    assert not moe._kernel_serves(
        24, jax.ShapeDtypeStruct((8, 128, 64), jnp.float32))  # olmoe_tiny
    assert not moe._kernel_serves(
        24, jax.ShapeDtypeStruct((8, 8192, 4096), jnp.bfloat16))  # VMEM
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    with jax.set_mesh(mesh):
        assert not moe._kernel_serves(24, gate)


def test_bfloat16_compute_is_inside_a_bound_that_8_bits_are_not(tiny):
    """The served configuration computes in bfloat16 (8 bits of mantissa).
    Its logits lie within 0.05 of the float32 reference's here; the same
    model with its weights rounded to an 8-bit float (e4m3, 4 bits) is
    0.15 and more away, so the bound tells the two apart."""
    _, variables, flat = tiny
    ids = prompt_of(48)
    want = np.asarray(reference.logits(flat, ids, 2, 1e-5, **MODEL))
    bf16 = OlmoeLM(olmoe_tiny(max_seq=MAX_SEQ, dtype=jnp.bfloat16))
    got = np.asarray(bf16.apply(variables, jnp.asarray([ids])))[0]
    eight = jax.tree.map(
        lambda x: x.astype(jnp.float8_e4m3fn).astype(jnp.float32), variables)
    module = OlmoeLM(olmoe_tiny(max_seq=MAX_SEQ))
    coarse = np.asarray(module.apply(eight, jnp.asarray([ids])))[0]
    bound = 0.05
    assert np.abs(got - want).max() < bound < np.abs(coarse - want).max()


def test_the_two_copies_of_the_reference_agree(tiny):
    from chipbench.references import olmoe as benchmarks_copy

    _, _, flat = tiny
    ids = prompt_of(40)
    ours = np.asarray(reference.logits(flat, ids, 2, 1e-5, **MODEL))
    theirs = np.asarray(benchmarks_copy.logits(flat, ids, 2, 1e-5, **MODEL))
    np.testing.assert_array_equal(ours, theirs)
    # experts per token and the rotary base come from its own file
    assert benchmarks_copy.settings() == {"experts_per_token": 8,
                                          "rope_theta": 10000.0}


# -- through the engine ------------------------------------------------------
async def test_prefill_then_decode_through_the_paged_pool(tiny):
    _, _, flat = tiny
    prompt = prompt_of(37)
    engine = engine_of(tiny, steps_per_call=4)
    try:
        tokens, chosen, top = await served(engine, prompt, 24)
        stats = engine.stats()
    finally:
        await engine.close()
    assert_matches_reference(flat, prompt, tokens, chosen, top)
    # 6 calls of 4 steps over 2 layers; every row of the 4 slots routes
    assert stats["moe_experts_touched_mean"] > 1
    assert stats["moe_load_max_over_mean"] >= 1
    assert stats["active_params"] == engine.module.config.param_counts()[
        "active"]


def counter_value(name: str, model: str) -> float:
    from kfserving_tpu.observability import metrics as obs

    for line in obs.REGISTRY.render().splitlines():
        if line.startswith(name + "{") and f'model="{model}"' in line:
            return float(line.rsplit(" ", 1)[1])
    return 0.0


def test_the_counters_of_the_grouped_path_are_host_arithmetic():
    """What the grouped path was offered and what its matmuls visited,
    from the `pairs` [expert layers, experts] a prefill dispatch returns
    anyway and the dispatch's shape: tokens x choices a layer, and each
    layer's real pairs rounded up to the kernel's tile."""
    from kfserving_tpu.engine.moe_counters import MoeCounters

    model = "grouped-counters-test"
    counters = MoeCounters(model, experts=4, per_token=2)
    assert counters.stats() == {}
    # (2, 512): 2048 rows a layer offered; 300 and 513 real pairs
    counters.note("prefill", {"pairs": jnp.asarray(
        [[100, 0, 200, 0], [256, 256, 1, 0]])}, tokens=1024)
    # 64 tokens: `routed_experts` may take another path, nothing is counted
    counters.note("prefill", {"pairs": jnp.asarray(
        [[9, 9, 9, 9], [9, 9, 9, 9]])}, tokens=64)
    counters.drain()
    tile = moe.GROUPED_TILE_ROWS
    assert tile == 256
    assert counters.grouped_rows == 2 * 2048
    assert counters.grouped_rows_computed == 2 * tile + 3 * tile
    assert counters.pairs["prefill"] == 300 + 513 + 72
    assert counters.stats() == {
        "moe_grouped_rows_computed_share": round(5 * tile / 4096, 4)}
    prefix = "kfserving_tpu_generator_moe_grouped_pair_rows"
    assert counter_value(prefix + "_total", model) == 4096
    assert counter_value(prefix + "_computed_total", model) == 5 * tile
    # a dispatch with no real pair at all visits nothing
    counters.note("prefill", {"pairs": jnp.zeros((2, 4), jnp.int32)},
                  tokens=1024)
    counters.drain()
    assert (counters.grouped_rows, counters.grouped_rows_computed) == (
        4 * 2048, 5 * tile)


async def test_prefill_dispatches_count_what_the_grouped_path_visited(tiny):
    """Three prompts admitted together, each of more than half the
    bucket's blocks so that none shares a row, pad to (4, 128): 512
    tokens, over the 256 that `routed_experts` may give another path, so
    the dispatch is counted: 2 choices x 512 tokens x 2 layers offered,
    the real pairs of 3 prompts visited."""
    engine = engine_of(tiny, prefill_buckets=[MAX_SEQ])
    prompts = [prompt_of(100), prompt_of(90, 5), prompt_of(70, 7)]
    try:
        await asyncio.wait_for(asyncio.gather(*[
            served(engine, p, 4) for p in prompts]), timeout=600)
        stats, counters = engine.stats(), engine._moe
    finally:
        await engine.close()
    assert counters.grouped_rows % (2 * MAX_SEQ * 2) == 0
    assert counters.grouped_rows >= 2 * 2 * MAX_SEQ * 2
    real = 2 * sum(map(len, prompts))  # a layer's, were all admitted at once
    assert 0 < counters.grouped_rows_computed <= 2 * 3 * (
        moe.grouped_rows_real(real))
    assert stats["moe_grouped_rows_computed_share"] == round(
        counters.grouped_rows_computed / counters.grouped_rows, 4) < 1


async def test_chunked_prefill(tiny):
    _, _, flat = tiny
    prompt = prompt_of(75)  # chunks of 32: two whole, one partial
    engine = engine_of(tiny, prefill_chunk_tokens=32)
    try:
        tokens, chosen, top = await served(engine, prompt, 10)
        assert engine.stats()["chunked_prefill"]["chunks_dispatched"] >= 3
    finally:
        await engine.close()
    assert_matches_reference(flat, prompt, tokens, chosen, top)


async def test_a_preempted_request_resumes_on_the_references_logits(tiny):
    _, _, flat = tiny
    prompts = [prompt_of(42, stride) for stride in (3, 5, 11)]
    engine = engine_of(tiny, cache_blocks=10)  # 3 x (42 + 20) needs 12
    try:
        results = await asyncio.wait_for(asyncio.gather(*[
            served(engine, p, 20) for p in prompts]), timeout=300)
        assert engine.stats()["paged"]["preemptions"] >= 1
    finally:
        await engine.close()
    for prompt, (tokens, chosen, top) in zip(prompts, results):
        assert_matches_reference(flat, prompt, tokens, chosen, top)


async def test_a_speculative_verify_step(tiny):
    _, _, flat = tiny
    prompt = [5, 9, 2, 7] * 6  # a suffix the n-gram proposer can replay
    engine = engine_of(tiny, speculative={"tokens": 3})
    try:
        tokens, chosen, top = await served(engine, prompt, 16)
        assert engine.stats()["speculative"]["waves"] >= 1
    finally:
        await engine.close()
    assert_matches_reference(flat, prompt, tokens, chosen, top)


# -- the engine's FLOP and byte model ----------------------------------------
def test_active_parameters_of_the_benchmarked_shapes():
    """8 layers at the published widths: 8 x 67.2 M (attention 16.8 M, 8
    experts of 6.29 M, the router) + the 103 M head = 0.64 B multiplied by
    a token, of 3.56 B held."""
    from kfserving_tpu.models.olmoe import OlmoeConfig

    counts = OlmoeConfig(num_layers=8).param_counts()
    assert counts["total"] == 3_562_604_544
    assert counts["active"] == 641_009_664
    assert counts["per_expert"] == 3 * 2048 * 1024
    assert counts["always_read"] == counts["active"] - 8 * 8 * 3 * 2048 * 1024


def test_the_engines_model_counts_active_parameters(tiny):
    from kfserving_tpu.models.decoder import DecoderLM, decoder_tiny

    module, variables, _ = tiny
    engine = engine_of(tiny)
    counts = module.config.param_counts()
    assert engine._n_params == counts["total"]
    assert engine._flops_matmul_per_token == 2.0 * counts["active"]
    assert engine._param_read_bytes == 4 * counts["always_read"]
    assert engine._expert_read_bytes == 4 * counts["per_expert"]
    engine.shutdown_nowait()
    # the dense decoder's figures are what they were: every parameter
    dense = DecoderLM(decoder_tiny(num_layers=2, max_seq=MAX_SEQ))
    dense_vars = dense.init(jax.random.PRNGKey(0),
                            jnp.zeros((1, 8), jnp.int32))
    engine = GenerationEngine(dense, dense_vars, max_slots=2,
                              max_seq=MAX_SEQ, block_size=BS)
    n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(dense_vars))
    assert engine._moe is None
    # ... and its programs, fetches and stats: five outputs a prefill, no
    # routing beside them, no counter of the expert paths
    ids = jax.ShapeDtypeStruct((1, MAX_SEQ), jnp.int32)
    row = [jax.ShapeDtypeStruct((1,), t) for t in (
        jnp.int32, jnp.float32, jnp.int32, jnp.float32, jnp.int32)]
    asked = jax.ShapeDtypeStruct((), jnp.bool_)   # log-probabilities or not
    assert len(jax.eval_shape(engine._prefill, dense_vars, ids, *row,
                              asked)) == 5
    assert not [k for k in engine.stats() if k.startswith("moe_")]
    assert engine._flops_matmul_per_token == 2.0 * n
    assert engine._param_read_bytes == engine.param_bytes() == 4 * n
    assert engine.stats()["active_params"] == n
    engine.shutdown_nowait()


# -- sharding ----------------------------------------------------------------
def test_tp2_gives_the_logits_of_tp1_and_replicates_no_expert(tiny):
    """QK-norm runs over all heads, which tp splits: the partitioner has to
    sum the squares across the mesh, so GPT-2's test does not imply this."""
    from jax.sharding import Mesh

    from kfserving_tpu.parallel import shard_params
    from kfserving_tpu.parallel.sharding import describe

    module, variables, _ = tiny
    ids = jnp.asarray([prompt_of(48), prompt_of(48, 5)])
    want = np.asarray(module.apply(variables, ids))
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 1, 2),
                ("dp", "sp", "tp"))
    sharded = {"params": shard_params(variables["params"], mesh)}
    with jax.set_mesh(mesh):
        got = np.asarray(jax.jit(module.apply)(sharded, ids))
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    specs = describe(variables["params"])
    kernels = {k: v for k, v in specs.items()
               if k.endswith(("experts/gate", "experts/up", "experts/down"))}
    assert len(kernels) == 6
    assert all("tp" in spec for spec in kernels.values()), kernels
    down = sharded["params"]["layer_0"]["experts"]["down"]
    assert down.sharding.shard_shape(down.shape) == (8, 32, 128)
