"""The device side of the generation engine, built once from the model's
declaration and the engine's sizes; `GenerationEngine`
(engine/generator.py) schedules what is built here.

- `CacheLayout`: the arrays every layer keeps between steps, and the
  facts the host side books by; `place_params`: the parameters beside them.
- `UNSERVED` / `CacheLayout.refusal`: what a kind of layer cannot serve,
  said once.
- `packs_prompts`: which (rows, bucket) prefill programs carry several
  prompts a row.
- `build`: the jitted programs, with their donations.  The benchmark's
  trace reduction and compile-log reader find `decode_fn`, `prefill_fn`
  and `insert_fn` by name (chipbench/trace.py,
  chipbench/kinds/generate.py): the inner functions keep their names.
- `sample`, `mask_to_support`, `logprob_of`, `top_n`: greedy,
  temperature (Gumbel trick), top-k and top-p (nucleus) per slot, on the
  device, so only the [S] int32 token vector crosses the host boundary
  per step — never the [S, V] logits (1.6 MB/step for a GPT-2 vocab).
  Noise is keyed per request from (seed, absolute position): a seeded
  request reproduces exactly no matter how it was scheduled.  The tail
  reads the logits as often as the dispatch's rows asked for: one argmax
  for rows that are all greedy, the noise only where a row samples, top-N
  logprobs only where a request asked (`want_lp`, the predicate by which
  the host fetches them).

Nothing here knows of requests, the tables' host side
(engine/block_pool.py), the metrics registry or the timeline.
"""

import math
from typing import Any, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp

from kfserving_tpu.models.decoder import (
    BothCaches,
    KVCache,
    LatentCache,
    StateCache,
)
from kfserving_tpu.ops import paged_attention
from kfserving_tpu.protocol.errors import InvalidInput

# A kind of layer -> the settings its cache cannot serve, and why.  No
# block stands for a prefix of a model with a layer of either kind (a
# recurrence's state is not rows addressed by position, and a shared
# prefix stands for no ring at its end): every plan of such a model is a
# miss whatever the index holds, registers nothing, and is counted
# (`GenerationEngine._shares_prefixes`).  The settings each rest on
# rewriting, re-reading or moving rows by position; a ring holds a
# position wherever it falls, so the position sentinel that parks a row
# has a place in a live ring and would overwrite it.  A latent row IS
# addressed by position, in one table with one free list: chunks, a verify
# and a shared prefix read and write it as they do K/V rows (absorbed,
# ops/paged_attention.latent_attention); what it lacks is the host tier's
# payload.
UNSERVED: Dict[str, Dict[str, str]] = {
    "latent rows": {
        "host_tier_blocks":
            "a spilled block's payload is written and read back as a K "
            "and a V array a layer (engine/kv_tier.py, the spill and "
            "fault-back paths), and a latent layer keeps one array"},
    "recurrent state": {
        "speculative":
            "a rejected draft token has already moved the "
            "state, and nothing overwrites it",
        "prefill_chunk_tokens":
            "a chunk would have to continue from the state the "
            "chunk before it left",
        "host_tier_blocks":
            "a spilled prefix is K/V blocks, and the state at "
            "its end is kept nowhere"},
    "sliding-window layers": {
        "speculative":
            "a verify wave parks the rows it does not draft for "
            "on a position sentinel, which a ring gives a place "
            "and lets overwrite live keys",
        "prefill_chunk_tokens":
            "a chunk's padding parks on a position sentinel, "
            "which a ring gives a place, and a chunk of more "
            "than a block overwrites keys its first queries "
            "still see",
        "host_tier_blocks":
            "a spilled prefix is the whole-context layers' "
            "blocks, and the rings at its end are kept nowhere"},
}


def parts(kind):
    """(the `KVCache` or `LatentCache`: what lives in a block pool; the
    `StateCache`) of one layer's declaration, None for what the layer does
    not keep."""
    if isinstance(kind, BothCaches):
        return kind.kv, kind.state
    return (kind if isinstance(kind, (KVCache, LatentCache)) else None,
            kind if isinstance(kind, StateCache) else None)


def by_part(kind, on_kv, on_state, *layers):
    """One layer's cache with `on_kv(its KVCache or LatentCache, pools...)`
    in place of its K/V part (a latent layer's pools a one-tuple) and
    `on_state(its StateCache, arrays...)` in place of its
    state; `layers` are caches of that layer of one structure (the engine's
    and a prefill's, say; none where the parts are being made), handed
    over part by part.  A layer that keeps nothing stays the first of
    them."""
    if isinstance(kind, BothCaches):
        return (on_kv(kind.kv, *(layer[0] for layer in layers)),
                on_state(kind.state, *(layer[1] for layer in layers)))
    if isinstance(kind, (KVCache, LatentCache)):
        return on_kv(kind, *layers)
    if isinstance(kind, StateCache):
        return on_state(kind, *layers)
    return layers[0] if layers else ()


def _kept(kind, arrays):
    return arrays


class CacheLayout:
    """The KV cache, a block pool: a shared pool [NB, BS, H*D] a layer
    (ops/paged_attention.py owns the layout) + per-slot block tables, so
    HBM scales with resident tokens and identical prompt prefixes share
    blocks (the vLLM PagedAttention idea, TPU-shaped: static pool/table
    shapes, OOB-sentinel scatters, a Pallas decode kernel that walks the
    table, XLA gather attention elsewhere).

    The model declares what each layer keeps between steps
    (`config.cache_layers()`, models/decoder.py): K/V rows in the block
    pool, arrays of a slot's own (a recurrence's state:
    models/nemotron_h.py), both for the one layer (models/falcon_h1.py:
    the layer's pair of them), latent rows in a pool of one array a layer
    (models/deepseek_v3.py), or nothing.  Every size the engine books or
    counts comes from this declaration: the arrays (`caches`, a layer's
    pools, state or none) and the facts the host side books by are this
    object's attributes."""

    def __init__(self, config, name: str, *, max_slots: int, max_seq: int,
                 prefill_buckets: List[int], block_size: Optional[int],
                 cache_blocks: Optional[int],
                 window_cache_blocks: Optional[int], mesh):
        self.kinds = kinds = list(config.cache_layers())
        kv_layers = [kv for kv, _ in map(parts, kinds) if kv is not None]
        state_layers = [st for _, st in map(parts, kinds) if st is not None]
        geometries = {(type(c), c.heads, c.head_dim) for c in kv_layers}
        windows = {c.window for c in kv_layers}
        if len(geometries) != 1 or None not in windows or len(windows) > 2:
            raise InvalidInput(
                "the engine pages K/V: a model needs at least one K/V "
                "layer that keeps its whole context, all K/V layers of "
                "one geometry (or all of them latent, of one), and its "
                "sliding-window layers of one "
                f"window; {name!r} declares "
                f"{sorted(set(kv_layers), key=str)}")
        (kind_of_pool, heads, head_dim), = geometries
        # A latent pool's block is key and value in one array.
        self.latent = latent = kind_of_pool is LatentCache
        arrays = 1 if latent else 2
        self.pool_name = "latent" if latent else "global"
        self.kv_heads, self.kv_head_dim = heads, head_dim
        self.kv_layers = len(kv_layers)
        self.window_layers = sum(c.window is not None for c in kv_layers)
        # Sliding-window layers keep a ring of blocks a sequence in a
        # pool of their own kind (ops/paged_attention.py); None for a
        # model without any.
        self.window = window = max(windows - {None}, default=None)
        # The kinds of `UNSERVED` the model has.
        self.limits = tuple(kind for kind, has in (
            ("recurrent state", bool(state_layers)),
            ("sliding-window layers", window is not None),
            ("latent rows", latent)) if has)
        # Whether a block can stand for a prompt's prefix.
        self.shares_prefixes = not state_layers and window is None
        # block_size unset is derived from the lengths: 128 wherever the
        # kernels can serve.
        self.block_size = bs = (
            int(block_size) if block_size
            else derive_block_size(max_seq, prefill_buckets))
        if max_seq % bs != 0:
            raise InvalidInput(
                f"max_seq {max_seq} must be a multiple of "
                f"block_size {bs}")
        for b in prefill_buckets:
            if b % bs != 0:
                raise InvalidInput(
                    f"prefill bucket {b} must be a multiple of "
                    f"block_size {bs} (paged insert writes whole "
                    f"blocks)")
        self.blocks_per_slot = max_seq // bs
        # Parity default: a block for every position of every slot.  A
        # smaller cache_blocks is the HBM saving — mixed-length traffic
        # rarely needs S full-length slots at once.
        self.num_blocks = int(cache_blocks
                              or max_slots * self.blocks_per_slot)
        self.pool_shape = (
            paged_attention.latent_pool_shape(self.num_blocks, bs, head_dim)
            if latent else paged_attention.pool_shape(
                self.num_blocks, bs, heads, head_dim))
        # The window pool: every window layer's K and V are
        # [num_window_blocks, BS, H*D], one table [slots, ring] for them
        # all.  A sequence never holds more than its ring, whatever its
        # length, so a ring for every slot can never run out; more than
        # that (`window_cache_blocks`) is room for the blocks of finished
        # requests that wait out the zombie-wave deferral.
        self.ring_columns = self.num_window_blocks = 0
        if window is not None:
            self.ring_columns = paged_attention.ring_blocks(window, bs)
            self.num_window_blocks = int(
                window_cache_blocks or max_slots * self.ring_columns)
            if self.num_window_blocks < self.ring_columns:
                raise InvalidInput(
                    f"window_cache_blocks {self.num_window_blocks} is "
                    f"less than one sequence's ring of "
                    f"{self.ring_columns} blocks (window "
                    f"{window}, block_size {bs})")
        window_pool_shape = paged_attention.pool_shape(
            self.num_window_blocks, bs, heads, head_dim)
        self.dtype = dtype = config.dtype
        # HBM of each pool's arrays over its layers, as the device holds
        # them (a latent row in whole lane tiles), by the pool's name.
        itemsize = jnp.dtype(dtype).itemsize
        self.pool_bytes = {
            self.pool_name: (arrays * (len(kv_layers) - self.window_layers)
                             * math.prod(self.pool_shape) * itemsize),
            "window": (arrays * self.window_layers
                       * math.prod(window_pool_shape) * itemsize)}
        # K and V of one position (a latent row: the numbers it holds,
        # whatever the pool pads them to), over the K/V layers.
        self.kv_bytes_per_token = (arrays * len(kv_layers) * heads * head_dim
                                   * jnp.dtype(dtype).itemsize)
        # Blocks of a row that one loop iteration of the paged decode
        # kernel takes, by pool (global, window): the kernel's own rule
        # on the pool one device holds.
        tp = mesh.shape.get("tp", 1) if mesh is not None else 1
        shards = tp if heads % tp == 0 else 1
        self.walk_chunks = tuple(
            paged_attention.blocks_per_iteration(
                bs, self.pool_shape[2] // shards, dtype, columns, arrays)
            for columns in (self.blocks_per_slot, self.ring_columns or 1))

        def pools(kind):
            shape = (self.pool_shape if kind.window is None
                     else window_pool_shape)
            return tuple(jnp.zeros(shape, dtype) for _ in range(arrays))

        def state(kind):
            """The slots leading ([max_slots, ...]): a slot's row is
            written by the insert that admits a request there and stepped
            by every decode wave, so a reused slot's old state is
            overwritten before anything reads it."""
            return tuple(jnp.zeros((max_slots,) + tuple(shape), dt)
                         for shape, dt in kind.arrays)

        def nbytes(arrays) -> int:
            return sum(int(x.size) * x.dtype.itemsize
                       for x in jax.tree.leaves(arrays))

        # One layer's arrays: its two pools (a latent layer's one), its
        # state, the pair of them, or none.
        self.caches = [by_part(kind, pools, state) for kind in kinds]
        self.cache_bytes = nbytes(self.caches)
        # Of them a recurrence's state, in all and a slot: what admitting
        # a request costs beside `kv_bytes_per_token` a position.
        self.state_bytes_per_slot = sum(
            math.prod(shape) * jnp.dtype(dt).itemsize
            for kind in state_layers for shape, dt in kind.arrays)
        self.state_bytes = max_slots * self.state_bytes_per_slot
        if mesh is not None:
            # Tensor parallelism: the cache shards on the heads axis,
            # exactly like the q/k/v projections that fill it
            # (parallel/sharding.py transformer_rules) — cache writes
            # and decode attention stay device-local per head group;
            # the per-layer psum after the out-projection is the only
            # collective.  The pool's last axis is H*D: splitting it
            # over tp gives the same head groups.
            from jax.sharding import NamedSharding, PartitionSpec

            heads_axis = "tp" if heads % max(tp, 1) == 0 else None
            sharding = NamedSharding(
                mesh, PartitionSpec(None, None, heads_axis))
            replicated = NamedSharding(mesh, PartitionSpec())
            def put(where):
                return lambda _, arrays: tuple(
                    jax.device_put(x, where) for x in arrays)

            self.caches = [by_part(kind, put(sharding), put(replicated),
                                   layer)
                           for kind, layer in zip(kinds, self.caches)]

    def refusal(self, name: str, settings: Dict[str, bool]) -> Optional[str]:
        """Of `settings` (setting -> whether it is on) the first that a
        kind of layer this model has cannot serve, as the `InvalidInput`
        text."""
        for kind in self.limits:
            for setting, why in UNSERVED[kind].items():
                if settings[setting]:
                    return (f"{setting} is not served for {name!r}, a "
                            f"model with {kind}: {why}")
        return None


def packs_prompts(cache_kinds, bucket: int, block_size: int) -> bool:
    """Whether a row of the (rows, `bucket`) prefill program carries as
    many prompts as its blocks hold (`build`'s `prefill_fn`), by what the
    model declares and the sizes are: every cached layer keeps whole-
    context K/V, a state whose recurrence runs in chunks that divide the
    block (a prompt that starts at a block boundary then starts a chunk,
    from a zero state: ops/ssm.py) or both, and the bucket's attention is
    XLA's, which takes any mask.  A ring would have to be inserted a
    prompt at a time, the flash kernel know of segments, and a latent
    layer's expanded prefill take them: those models and buckets keep
    one prompt a row."""
    from kfserving_tpu.ops.attention import masked_prefill_takes_xla

    def packs(kind) -> bool:
        kv, state = parts(kind)
        return ((kv is None
                 or isinstance(kv, KVCache) and kv.window is None)
                and (state is None or block_size % state.chunk == 0))

    return masked_prefill_takes_xla(bucket) and all(
        packs(kind) for kind in cache_kinds if kind is not None)


def derive_block_size(max_seq: int, prefill_buckets: List[int]) -> int:
    """The pool's block size where the caller set none: the largest
    divisor of 128 that divides max_seq and every prefill bucket (a
    block never straddles a slot's end, and the insert writes whole
    blocks).  128, which the Pallas kernels need, wherever the lengths
    are multiples of it; 16 for pow-2 buckets from 16."""
    return math.gcd(128, int(max_seq), *(int(b) for b in prefill_buckets))


def place_params(models, stored, mesh):
    """`stored` (a tree a model of `models`; None for no model) on the
    device: a host leaf handed to a jitted call is transferred again on
    every launch (3.1 GB a launch for gpt2-large, ROADMAP A9).  Under a
    mesh, leaves that arrive sharded (shard_params) keep their shardings
    and whatever is still on the host is replicated.  A leaf rests in
    the dtype its model reads it in, where the model says which that is
    (`config.resident_dtypes`, models/decoder.py): a program handed
    float32 leaves that it multiplies in bfloat16 rebuilds their
    bfloat16 twin on every call (ROADMAP A8)."""
    from kfserving_tpu import startup
    from kfserving_tpu.engine import param_cache

    def read_dtypes(module, variables):
        declare = getattr(getattr(module, "config", None),
                          "resident_dtypes", None)
        if declare is None:
            return jax.tree.map(lambda leaf: leaf.dtype, variables)
        return declare(variables)

    replicated = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec

        replicated = NamedSharding(mesh, PartitionSpec())
    placed = param_cache.place_on_device(stored, replicated, tuple(
        read_dtypes(model, tree) for model, tree in zip(models, stored)))
    startup.mark("params_device")
    return placed


def mask_to_support(logits, top_ks, top_ps):
    """Restrict logits to the top-k / nucleus support.  Both
    knobs are per-row; 0 / 1.0 disable them.  One sort serves
    both masks."""
    v = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[:, ::-1]
    k_eff = jnp.where((top_ks <= 0) | (top_ks >= v), v,
                      top_ks)
    kth = jnp.take_along_axis(sorted_desc,
                              (k_eff - 1)[:, None], axis=-1)
    keep = logits >= kth
    # Nucleus: keep the smallest prefix of the sorted
    # distribution whose mass reaches top_p (the first token
    # is always kept — cumsum-before-it is 0).
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_ps[:, None]
    n_keep = jnp.maximum(jnp.sum(keep_sorted, axis=-1), 1)
    p_thresh = jnp.take_along_axis(
        sorted_desc, (n_keep - 1)[:, None], axis=-1)
    keep &= logits >= p_thresh
    return jnp.where(keep, logits,
                     jnp.finfo(logits.dtype).min)


def sample(base_key, logits, temps, top_ks, top_ps, seeds, noise_pos):
    """logits [B, V] float32.  Noise is keyed per ROW from
    (request seed, absolute position), never from wave or slot
    identity — a request's sampled tokens reproduce exactly
    for a given seed no matter how it was scheduled.

    Rows that are all greedy (`temps` 0) cost one argmax: the keys, the
    support mask, the [B, V] Gumbel draw and the second argmax run under
    one `cond`, taken whole where a row samples (and then every row's
    token is what it has always been)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def drawn():
        def row_key(seed, pos):
            return jax.random.fold_in(
                jax.random.fold_in(base_key, seed), pos)

        keys = jax.vmap(row_key)(seeds, noise_pos)

        def draw(support):
            gumbel = jax.vmap(
                lambda k: jax.random.gumbel(k, (logits.shape[-1],))
            )(keys)
            scaled = support / jnp.maximum(temps, 1e-6)[:, None]
            return jnp.argmax(scaled + gumbel, axis=-1).astype(jnp.int32)

        # Either way the branch gives [B] tokens: a `cond` that handed
        # back the logits, masked or not, would write all [B, V] of them.
        sampled = jax.lax.cond(
            jnp.any((top_ks > 0) | (top_ps < 1.0)),
            lambda: draw(mask_to_support(logits, top_ks, top_ps)),
            lambda: draw(logits))
        return jnp.where(temps <= 0.0, greedy, sampled)

    return jax.lax.cond(jnp.any(temps > 0.0), drawn, lambda: greedy)


def top_n(logits, n: int):
    """The n largest of each row of logits [B, V], largest first, and
    their columns; of equal values the lower column first, which is
    `lax.top_k`'s order and `argmax`'s.  n passes of max and argmax over
    the row less the columns already taken, each one reduction that reads
    the logits where they are.  `lax.top_k` itself on an operand that
    another reduction reads too compiles, for the v5e, to a sort of the
    whole [B, V] array: 21.6 ms at [64, 261120] (PERF.md §6, PR 52)."""
    columns = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    taken = jnp.zeros(logits.shape, bool)
    values, ids = [], []
    for _ in range(n):
        rest = jnp.where(taken, -jnp.inf, logits)
        at = jnp.argmax(rest, axis=-1).astype(jnp.int32)
        values.append(jnp.max(rest, axis=-1))
        ids.append(at)
        taken |= columns == at[:, None]
    return jnp.stack(values, axis=1), jnp.stack(ids, axis=1)


def logprob_of(logits, chosen, n: int, want):
    """Chosen-token logprob + top-N (ids, logprobs) over the
    UNMASKED distribution — diagnostics follow the model, not
    the sampler's support restriction.  Zeros unless `want` (a
    scalar: some row of the dispatch asked for them; the host fetches
    by the same predicate).

    `jax.nn.log_softmax`'s arithmetic, (x - max) - log(sum(exp(x -
    max))), on the top-N logits and the chosen one alone: the [B, V]
    array of log-probabilities is never formed."""
    rows = logits.shape[0]

    def asked():
        top_vals, top_ids = top_n(logits, n)
        peak = top_vals[:, :1]
        log_total = jnp.log(jnp.sum(jnp.exp(logits - peak), axis=-1,
                                    keepdims=True))
        picked = jnp.take_along_axis(
            logits, chosen[:, None].astype(jnp.int32), axis=-1)
        return (((picked - peak) - log_total)[:, 0], top_ids,
                (top_vals - peak) - log_total)

    def unasked():
        return (jnp.zeros((rows,), logits.dtype),
                jnp.zeros((rows, n), jnp.int32),
                jnp.zeros((rows, n), logits.dtype))

    return jax.lax.cond(want, asked, unasked)


def decode_call_stats(chose: Dict[str, Any]) -> Dict[str, Any]:
    """What the routers chose over one decode call, reduced on the
    device (engine/moe_counters.py reads it): `pairs`
    [steps, layers, experts] and, under a share, `elsewhere`
    [steps, layers]."""
    pairs = chose["pairs"]
    stats = {"pairs": pairs.sum(axis=0), "touched": (pairs > 0).sum(),
             "load_max": pairs.max(axis=-1).sum()}
    if "elsewhere" in chose:
        stats["elsewhere"] = chose["elsewhere"].sum()
    return stats


class Programs(NamedTuple):
    """`build`'s jitted programs, by the names the engine binds."""
    decode: Any
    feed_update: Any
    prefill: Any
    chunk_prefill: Any
    insert: Any
    spec_verify: Any      # None without speculative tokens
    gather_blocks: Any    # None without a host tier


def build(module, cache_kinds, steps_per_call: int, logprob_topk: int,
          base_key, spec_tokens: int = 0,
          host_tier: bool = False) -> Programs:
    """The engine's device programs for `module`, whose layers keep
    `cache_kinds`, jitted."""
    lp_n = logprob_topk
    # A model with routed experts (models/olmoe.py) also reports
    # what its routers chose; a dense decoder's programs and
    # fetches are what they were.
    routed = bool(getattr(module.config, "num_experts", 0))
    has_window = any(kv is not None and kv.window is not None
                     for kv, _ in map(parts, cache_kinds))

    def apply(variables, ids, **kw):
        """(module.apply's outputs, what an expert model's routers
        chose or None): `pairs` [expert layers, experts held], and
        `elsewhere` [expert layers] where the model holds a share of
        its experts."""
        if not routed:
            return module.apply(variables, ids, **kw), None
        out, state = module.apply(variables, ids, mutable=["moe"],
                                  **kw)
        chose = {"pairs": module.routed_pairs(state)}
        if hasattr(module, "routed_elsewhere"):
            chose["elsewhere"] = module.routed_elsewhere(state)
        return out, chose

    def by_pool(per_pool, kind):
        """A K/V layer's own of a dispatch's tables (or insert
        destinations): one array for a model whose layers all keep
        their whole context, (whole-context, ring) for one with
        sliding-window layers."""
        if not has_window:
            return per_pool
        return per_pool[0 if kind.window is None else 1]

    def with_table(caches, table):
        """The caches as the model takes them: a K/V layer's pools
        with this dispatch's block table for its pool."""
        return [by_part(kind, lambda kv, pools: pools + (
                            by_pool(table, kv),), _kept, layer)
                for kind, layer in zip(cache_kinds, caches)]

    k_steps = steps_per_call

    def decode_fn(variables, caches, table, tokens, positions,
                  stops, temps, top_ks, top_ps, seeds, want_lp):
        """K decode steps in ONE device dispatch (lax.scan): on a
        high-RTT link each host round trip costs ~an RTT, so
        single-token stepping caps tokens/s at 1/RTT per wave;
        scanning K steps on device multiplies that by K.  Tokens
        feed forward on device; the host sees [S, K] at once (stop
        conditions checked per chunk — at most K-1 wasted steps
        after an EOS/budget stop).  Also returns the final carry's
        feed tokens/positions as device arrays: the pipelined
        scheduler chains dispatch N+1 off them without a host
        round trip.

        `stops` [S] (`GenerationEngine._stop_positions`) is where each
        row's token budget ends.  A row whose feed position has
        reached it owes no token, and the step parks it: its table
        row is all -1 in every pool, so `paged_walk` lists none of its
        blocks and `paged_write` drops its row, and an expert model
        routes it to no expert.  Its tokens and positions go on in
        the carry as a freed slot's always have; the head and the
        sampler's argmax stay dense over the slots.  What else the
        sampler does depends on the wave: `sample` draws noise where a
        row has a temperature, and `logprob_of` works where `want_lp`
        (a scalar: a row of the wave asked for log-probabilities, which
        is when the host fetches them) and gives zeros otherwise."""
        def step(carry, _):
            caches, tokens, positions = carry
            live = positions < stops
            parked = jax.tree.map(
                lambda t: jnp.where(live[:, None], t, -1), table)
            kw = {"valid": live[:, None]} if routed else {}
            (logits, new_caches), pairs = apply(
                variables, tokens[:, None], positions=positions,
                kv_cache=with_table(caches, parked), **kw)
            lg = logits[:, 0]
            # The token being sampled extends a prefix of length
            # positions+1 — the noise index is that length, so
            # prefill (length L) and decode agree on the sequence
            # L, L+1, ... per request.
            nxt = sample(base_key, lg, temps, top_ks, top_ps, seeds,
                         positions + 1)
            lp = logprob_of(lg, nxt, lp_n, want_lp)
            return (new_caches, nxt, positions + 1), (nxt, lp, pairs)

        (caches, next_tokens, next_positions), (toks, lps, pairs) = \
            jax.lax.scan(step, (caches, tokens, positions),
                         None, length=k_steps)
        chosen_lp, top_ids, top_lps = lps
        # scan stacks on axis 0: [K, S, ...] -> [S, K, ...]
        out = (toks.T, caches, next_tokens, next_positions,
               chosen_lp.T, jnp.swapaxes(top_ids, 0, 1),
               jnp.swapaxes(top_lps, 0, 1))
        # Expert models: the call's routing, reduced on the device.
        return out + (decode_call_stats(pairs),) if routed else out

    def feed_update_fn(tokens, positions, slot_arr, new_tokens,
                       new_positions):
        """Scatter newly admitted requests' first feed token and
        position into the device-resident feed arrays (OOB
        sentinel rows drop, like the cache insert)."""
        return (tokens.at[slot_arr].set(new_tokens, mode="drop"),
                positions.at[slot_arr].set(new_positions,
                                           mode="drop"))

    def prefill_fn(variables, ids, lengths, temps, top_ks, top_ps,
                   seeds, want_lp, packed=None):
        """One prefill dispatch: `ids` [B, L], a row the bucket long.

        A row holds one prompt, from its first column (`packed` None):
        `lengths` and the sampling arrays are [B], a dummy row's length
        is 1.  Or, where `packs_prompts` says so, as many prompts as its
        blocks hold, each from a block boundary: `packed` is (`segments`
        [B, L]: which prompt of its row a position belongs to, -1 for
        padding; `positions` [B, L], from 0 again in each; `last`
        [B, P], P the blocks of a row: the column of the last token of
        the prompt that starts at block p, any column where none
        does), and `lengths` and the sampling arrays are [B * P], an
        entry a block, read where a prompt starts.  The model masks a
        prompt's queries to its own keys (`decoder.cached_attention`)
        and starts a recurrence and its convolution again where a
        prompt starts (ops/ssm.py), the head and the sampler run over
        all B * P entries (the head is a weight stream: its rows cost
        nothing that can be read), and what comes back for an entry no
        prompt starts at is thrown away.  A lone prompt is a row with
        one segment: the packed program is the (rows, bucket) program,
        not one beside it.

        Either way K/V leave as [B, L, H*D], a row's blocks in the
        row's order, so `insert_fn` takes them block by block with no
        notion of whose block is whose.  A state leaves a row, [B, ...],
        or packed a prompt, [B * P, ...], an entry for entry of
        `lengths`."""
        # logit_positions: the LM head runs only on each prompt's
        # last real token — sampling never needs the [B, L, V]
        # logits cube, and at a 4096 bucket the full-cube head
        # matmul dominated prefill FLOPs.  Numerically identical
        # per row to slicing the full cube (norm + head are
        # per-position), so the chunked path (which uses the same
        # sliced head) samples the same first token.
        if packed is None:
            where = {"kv_lengths": lengths, "logit_positions": lengths - 1}
        else:
            segments, positions, last = packed
            where = {"segments": segments, "positions": positions,
                     "logit_positions": last}
        (logits, caches), pairs = apply(variables, ids, return_cache=True,
                                        **where)
        # Leave the program as the pool stores them, [B, L, H*D]:
        # the insert is then a scatter of whole blocks, where
        # [B, L, H, D] results (L minor-most on the chip) would be
        # transposed on their way in.
        caches = [by_part(kind, lambda _, rows: tuple(
                              x.reshape(x.shape[:2] + (-1,)) for x in rows),
                          _kept, layer)
                  for kind, layer in zip(cache_kinds, caches)]
        # [B, 1, V], or [B, P, V] packed: an entry a row of the tail.
        last_logits = logits.reshape(-1, logits.shape[-1])
        first_tokens = sample(base_key, last_logits, temps, top_ks, top_ps,
                              seeds, lengths)
        chosen_lp, top_ids, top_lps = logprob_of(last_logits, first_tokens,
                                                 lp_n, want_lp)
        out = (first_tokens, caches, chosen_lp, top_ids, top_lps)
        return out + (pairs,) if routed else out

    def chunk_prefill_fn(variables, caches, table, ids, qpos,
                         last_idx, temps, top_ks, top_ps,
                         seeds, noise_pos, want_lp):
        """One chunk of a cold prompt: ids [1, C] write their
        k/v through the slot's block table at absolute
        positions qpos [1, C] (padding rows of a partial final
        chunk park on an out-of-range sentinel and drop), and
        attend per-query-causally over the pool — earlier
        chunks are already resident, so cross-chunk attention
        reads them exactly like decode does.  The head runs
        only at last_idx; the sampled token matters only for
        the FINAL chunk (it becomes the stream's first token,
        noise-keyed on the full prompt length for parity with
        monolithic prefill) — earlier chunks discard it."""
        logits, new_caches = module.apply(
            variables, ids, positions=qpos,
            kv_cache=with_table(caches, table),
            logit_positions=last_idx)
        lg = logits[:, 0]
        first = sample(base_key, lg, temps, top_ks, top_ps, seeds,
                       noise_pos)
        chosen_lp, top_ids, top_lps = logprob_of(lg, first, lp_n,
                                                 want_lp)
        return first, new_caches, chosen_lp, top_ids, top_lps

    spec_kp1 = spec_tokens + 1

    def spec_verify_fn(variables, caches, table, last_tokens,
                       draft_toks, positions, temps, top_ks,
                       top_ps, seeds, want_lp):
        """Verify K draft tokens per slot in ONE Lq=K+1
        dispatch.  Row i feeds [last_token, draft_0..K-1] at
        absolute positions [L, L+K] (parked rows ride the
        max_seq sentinel: their writes drop / clamp and their
        samples are discarded).  logit_positions asks the LM
        head for ALL K+1 positions — position j's logits see
        exactly the prefix a sequential decode would have at
        step j, so sampling them with the SAME per-row
        (seed, position) noise keys reproduces sequential
        decode's draws bit-exactly.  Exact-match acceptance of
        the longest agreeing prefix is then rejection sampling
        under the slot's deterministic noise key: the target's
        draw at a position is a point, and accept-iff-equal is
        the degenerate (and parity-exact) rejection rule.
        Rollback past the first rejection needs NO cache
        surgery — the host length pointer simply does not
        advance over rejected positions, and the garbage k/v
        written there is overwritten by later waves before any
        query can attend it (writes precede attention in every
        dispatch, and positions advance monotonically)."""
        tokens = jnp.concatenate(
            [last_tokens[:, None], draft_toks], axis=1)
        kv = with_table(caches, table)
        s_rows = tokens.shape[0]
        gather = jnp.broadcast_to(
            jnp.arange(spec_kp1, dtype=jnp.int32)[None, :],
            (s_rows, spec_kp1))
        logits, new_caches = module.apply(
            variables, tokens, positions=positions,
            kv_cache=kv, logit_positions=gather)
        flat = logits.reshape(s_rows * spec_kp1, -1)

        def rep(a):
            return jnp.repeat(a, spec_kp1)

        # noise index = length of the prefix each draw
        # extends: position p's sample starts a prefix of
        # p + 1 tokens — identical keying to decode_fn.
        samples = sample(base_key, flat, rep(temps), rep(top_ks),
                         rep(top_ps), rep(seeds),
                         (positions + 1).reshape(-1))
        chosen_lp, top_ids, top_lps = logprob_of(flat, samples, lp_n,
                                                 want_lp)
        # draft_toks are echoed through so the host reads
        # proposals + verdicts in the same fetch: the draft
        # arm costs ONE host round trip per spec wave, same
        # as a plain decode wave.
        return (samples.reshape(s_rows, spec_kp1), draft_toks,
                new_caches,
                chosen_lp.reshape(s_rows, spec_kp1),
                top_ids.reshape(s_rows, spec_kp1, lp_n),
                top_lps.reshape(s_rows, spec_kp1, lp_n))

    def insert_fn(caches, new_caches, dest_blocks, slots=None):
        """Scatter a prefill batch's k/v into pool blocks.
        dest_blocks [B, chunks] int32; -1 chunks drop (bucket
        padding rows, and prefix-cache hits whose shared blocks
        already hold the data); for a model with sliding-window
        layers a pair of them, the second the rings', which take
        a prompt's last blocks alone.  A state's entries go to their
        slots whole (`slots` int32, an entry for entry of what the
        prefill returned: [B], or [B * P] from a packed one;
        past-the-end for a padding row and for an entry no prompt
        starts at, which drop); a layer that keeps both takes both."""
        def blocks(kv, pools, new):
            if isinstance(kv, LatentCache):
                return (paged_attention.latent_insert(
                    *pools, *new, dest_blocks),)
            return paged_attention.paged_insert(
                *pools, *new, by_pool(dest_blocks, kv), None)

        def rows(_, state, new):
            return tuple(old.at[slots].set(x.astype(old.dtype), mode="drop")
                         for old, x in zip(state, new))

        return [by_part(kind, blocks, rows, layer, new)
                for kind, layer, new in zip(cache_kinds, caches, new_caches)]

    def gather_blocks_fn(caches, idx):
        """Snapshot the k/v of pool blocks `idx` [N] as
        standalone device arrays (NOT donating the caches):
        the spill path fetches the snapshot on the fetch
        executor while later dispatches keep mutating the
        pool — the data dependency pins the pre-overwrite
        contents."""
        return [(k[idx], v[idx]) for k, v in caches]

    return Programs(
        # Donate caches AND the feed arrays: in-place HBM update, one
        # resident pool; the feed tokens/positions chain wave-to-wave
        # entirely on device.  The block table (arg 2) is NOT donated:
        # the host re-sends it per wave (2 KB; it changes at
        # allocation time).
        decode=jax.jit(decode_fn, donate_argnums=(1, 3, 4)),
        feed_update=jax.jit(feed_update_fn, donate_argnums=(0, 1)),
        # One executable per prompt bucket (jit caches by shape).
        prefill=jax.jit(prefill_fn),
        chunk_prefill=jax.jit(chunk_prefill_fn, donate_argnums=(1,)),
        insert=jax.jit(insert_fn, donate_argnums=(0,)),
        spec_verify=(jax.jit(spec_verify_fn, donate_argnums=(1,))
                     if spec_tokens > 0 else None),
        gather_blocks=jax.jit(gather_blocks_fn) if host_tier else None)
