"""GenerationEngine: KV-cache incremental decoding with continuous
batching.

The reference has no generative serving at all — models are opaque
request/response artifacts (reference pkg/apis/serving/v1beta1/
predictor.go:33-59) and its batcher coalesces whole requests
(pkg/batcher/handler.go:129-150).  Token generation breaks that model:
one request is hundreds of sequential device steps, and throughput
comes from batching *steps across requests*, not requests.  This engine
is the TPU-first design for that:

- **a block pool, static shapes**: the KV cache is a shared pool of
  blocks per layer plus a block table per sequence slot
  (engine/programs.py lays it out and builds the programs,
  engine/block_pool.py keeps who holds which block).  The decode step
  is ONE jit-compiled program over all `max_slots` slots, compiled once
  and reused for the life of the server — requests joining or leaving
  never change a shape, so XLA never recompiles; tables ride each
  dispatch as a [S, MB] int32 array.  Identical prompt prefixes share
  blocks via a chain-hash index, pool pressure queues admissions, and
  block release is deferred past in-flight waves (the zombie-wave
  hazard).
- **prefill/decode split**: prompt ingestion runs as a separate
  bucketed forward (suffix-padded, flash-eligible at long L, one
  compile per bucket) that returns the prompt's k/v for every layer;
  a jitted scatter inserts them into the slot's blocks.  Decode then costs
  O(1) tokens per step.  The arrivals that wait at a bucket ride one
  program of power-of-two rows; where every cached layer is whole-
  context K/V or a state whose recurrence starts again at a block
  boundary, a row carries as many prompts as its blocks hold, each
  from a block boundary and masked to itself (`lay_rows`,
  `programs.packs_prompts`).
- **continuous batching, fully asynchronous**: admission enqueues
  prefill + insert + feed-scatter and installs the slot WITHOUT a
  host sync — prompt ingestion rides the same in-flight pipeline as
  decode waves, so an admission burst never stalls live streams by a
  blocking prefill dispatch.  Finished slots free immediately (EOS or
  token budget).  The admission policy is prefill-priority: arrivals
  never wait for the current generation wave to drain (the
  "continuous" in continuous batching).
- **pipelined decode waves**: feed tokens/positions are device-
  resident and chain wave-to-wave through the jit's returned carry;
  the scheduler keeps `pipeline_depth` waves in flight so the D2H
  fetch of wave N overlaps wave N+1's execution — on a high-RTT
  transport the wave period drops from RTT + K steps toward
  max(RTT, K steps).  Stop decisions lag the device by at most
  depth-1 waves (bounded garbage steps, counted in stats).
- **on-device sampling, donated caches** (engine/programs.py): only
  the [S] int32 token vector crosses the host boundary per step, and
  the decode step updates ONE cache pool in place.

Cache HBM is accounted via `cache_bytes()` so the predictor can admit
params + cache against engine/hbm.py's budget.
"""

import asyncio
import concurrent.futures
import functools
import itertools
import logging
import os
import statistics
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, AsyncIterator, Dict, List, Optional, Sequence, Tuple

import numpy as np

from kfserving_tpu.engine import compile_cache
from kfserving_tpu.engine import inflight as inflight_table
from kfserving_tpu.engine.buckets import pow2_buckets
from kfserving_tpu.observability import attribution
from kfserving_tpu.observability import metrics as obs
from kfserving_tpu.observability.profiling import HEARTBEAT, TIMELINE
from kfserving_tpu.observability.profiling.timeline import HOST, LAUNCH
from kfserving_tpu.parallel.mesh import mesh_scope
from kfserving_tpu.protocol.errors import InferenceError, InvalidInput
from kfserving_tpu.reliability import fault_sites, sanitizer
from kfserving_tpu.reliability.faults import FaultInjected, faults

logger = logging.getLogger("kfserving_tpu.engine.generator")

# Monotonic engine ids for the sanitizer's recompile assertion (see
# jax_engine._engine_seq): a model name alone would let a reloaded
# engine inherit its predecessor's warmup declaration.
_generator_seq = itertools.count()


def _dispatch_timed(program: str):
    """Observe the wall time of an enqueue callable (it runs on the
    launching thread) as generator_dispatch_host_ms{program=}; a
    call that raises dispatched nothing and is not observed."""
    def wrap(fn):
        @functools.wraps(fn)
        def timed(self, *args, **kwargs):
            t0 = time.perf_counter()
            out = fn(self, *args, **kwargs)
            obs.generator_dispatch_host_ms().labels(
                program=program).observe(
                    (time.perf_counter() - t0) * 1000.0)
            return out
        return timed
    return wrap


# What `_distribute` counts of decode attention's reads, as (tokens,
# blocks, loop iterations): the whole-context pool's families, and those
# that say which pool of a model with two.
_WALKED = (obs.generator_decode_kv_context_tokens_total,
           obs.generator_decode_kv_blocks_walked_total,
           obs.generator_decode_kv_walk_iterations_total)
_WALKED_BY_POOL = (obs.generator_decode_kv_pool_context_tokens_total,
                   obs.generator_decode_kv_pool_blocks_walked_total,
                   obs.generator_decode_kv_pool_walk_iterations_total)


# eq=False: a request is itself.  Compared by its fields, cancel()'s
# `_pending.remove` would weigh prompt arrays against each other and
# miss a request queued behind another.
@dataclass(eq=False)
class _Request:
    prompt_ids: np.ndarray
    max_new_tokens: int
    temperature: float
    top_k: int = 0            # 0 = off
    top_p: float = 1.0        # 1.0 = off
    seed: int = 0             # folded into the sampling noise key
    logprobs: int = 0         # top-N logprobs per token; 0 = off
    out: asyncio.Queue = field(default_factory=asyncio.Queue)
    cancelled: bool = False
    # Request latency budget (captured from the ambient contextvar at
    # submit): the scheduler expires the request between decode waves
    # with terminal reason "timeout" — partial text is delivered, the
    # slot frees instead of decoding to the token budget.
    deadline: Optional[Any] = None
    # Per-token logprob records appended by the scheduler in emit
    # order (chosen logprob, [(token_id, logprob)] top-N); consumers
    # read them aligned with the token stream.
    lp_chosen: List[float] = field(default_factory=list)
    lp_top: List[List[Tuple[int, float]]] = field(default_factory=list)
    # Telemetry: the submitting request's trace id (rides onto the
    # TTFT / inter-token-latency / tokens-per-second histograms as
    # OpenMetrics exemplars) and emission timestamps.
    trace_id: Optional[str] = None
    submit_t: float = 0.0
    last_emit_t: Optional[float] = None
    # The hand-offs between submit and first emission (perf_counter):
    # taken out of the pending queue, and its prefill (or first chunk)
    # enqueued.  Re-stamped when a preempted request is admitted
    # again; read once, at the first emission (ttft_stage_ms).
    taken_t: float = 0.0
    enqueued_t: float = 0.0
    # Where the prompt lies in the prefill program it was taken for
    # (`lay_rows`): its entry of the program's per-prompt arrays.
    prefill_entry: int = 0
    # -- cost attribution (observability/attribution.py): accumulated
    # by the scheduler across the request's whole life (preemptions
    # included), finalized into ONE record at the terminal event.
    # Device ms are the request's EVEN SHARE of each dispatch's busy
    # interval — additive, so per-request costs sum to engine device
    # time instead of multiply-counting shared waves.
    prefill_device_ms: float = 0.0
    decode_device_ms: float = 0.0
    tokens_out: int = 0
    blocks_held: int = 0          # peak slot-table blocks
    cache_hit_blocks: int = 0     # prompt blocks served by the index
    cache_saved_tokens: int = 0   # hit blocks x block_size
    # Host KV tier (engine/kv_tier.py): prompt blocks faulted back
    # from the host spill tier instead of re-prefilled — kept
    # DISTINCT from the device prefix-cache fields above so the cost
    # record shows which tier earned the savings (the two are
    # additive).  Mutated on the enqueue executor at fault-back
    # drain time; the loop thread awaits the drain before the
    # request can reach any terminal path.
    host_tier_hit_blocks: int = 0
    host_tier_saved_tokens: int = 0
    # Speculative decoding: the draft/verify split of this request's
    # decode device time.  These REFINE decode_device_ms (they are a
    # breakdown of the same busy intervals, not additive terms) — the
    # conservation invariant "prefill + decode sums to engine device
    # time" is untouched.
    spec_draft_ms: float = 0.0
    spec_verify_ms: float = 0.0


@dataclass
class _Active:
    req: _Request
    length: int          # valid cache entries (prompt + generated so far)
    last_token: int      # token to feed at position `length`
    generated: int
    # Content tokens emitted so far — the preemption path re-prefills
    # prompt+tokens to resume a stream exactly (noise is keyed on
    # (seed, absolute position), so the continuation reproduces what
    # an uninterrupted decode would have sampled).
    tokens: List[int] = field(default_factory=list)
    # Why `_emit` ended the request, once it has: "length" is the end
    # the decode program was told of (`_stop_positions`).
    finished: Optional[str] = None
    # -- chunked-prefill state (cold prompts) --------------------------
    # prefilling: the slot holds a cold prompt landing in block-aligned
    # chunks between decode waves — it is NOT decodable yet (decode
    # waves park its feed row on an out-of-range sentinel so their
    # speculative writes drop), and _distribute discards its rows.
    prefilling: bool = False
    chunk_next: int = 0        # next chunk index to dispatch
    chunk_total: int = 0
    chunks_inflight: int = 0   # chunk dispatches not yet fetched
    # Per-block insert destinations from the plan (-1 = prefix-cache
    # hit: the shared block already holds the data; a whole chunk of
    # hits skips its dispatch entirely).
    chunk_dest: List[int] = field(default_factory=list)
    # block index -> (chain, block) fresh full-block registrations,
    # DEFERRED until the chunk that writes the block has dispatched —
    # registering at plan time (the monolithic path's provisional
    # trick) would let a sharer's decode read a block whose chunk has
    # not been enqueued yet.
    chunk_regs: Dict[int, Tuple[bytes, int]] = field(
        default_factory=dict)


def lay_rows(sizes: Sequence[int], per_row: int) -> List[int]:
    """Prompts that take `sizes` consecutive entries each, laid into rows
    of `per_row` entries: each one's first entry, counted over the rows
    (row * per_row + its first entry in the row).  First fit, the largest
    first (of equal ones the earlier): a prompt goes behind what its row
    already holds, so no entry is owned twice and none is left between
    two prompts of a row.  Where a row's entries are its blocks, a prompt
    starts at a block boundary; `per_row` 1 is one prompt a row, in
    order."""
    filled: List[int] = []  # a row's entries taken, from its first
    at = [0] * len(sizes)
    for i in sorted(range(len(sizes)), key=lambda i: -sizes[i]):
        row = next((r for r, n in enumerate(filled)
                    if n + sizes[i] <= per_row), len(filled))
        if row == len(filled):
            filled.append(0)
        at[i] = row * per_row + filled[row]
        filled[row] += sizes[i]
    return at


class GenerationEngine:
    """Continuous-batching token generation over one device/mesh.

    module: a DecoderLM-contract Flax module (models/decoder.py): full
        forward with `return_cache=True` and decode with `kv_cache` +
        `positions`.
    variables: initialized/restored model variables.  Host leaves
        (np arrays, param_cache's memmap views) are placed on the
        engine's device once, here; device arrays are kept as given.
    """

    def __init__(self, module, variables, *,
                 max_slots: int = 8,
                 max_seq: int = 512,
                 prefill_buckets: Optional[List[int]] = None,
                 eos_id: Optional[int] = None,
                 steps_per_call: int = 1,
                 pipeline_depth: int = 2,
                 block_size: Optional[int] = None,
                 cache_blocks: Optional[int] = None,
                 window_cache_blocks: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 host_tier_blocks: Optional[int] = None,
                 host_tier_dir: Optional[str] = None,
                 adaptive_depth: bool = True,
                 speculative: Optional[Dict[str, Any]] = None,
                 rng_seed: int = 0,
                 logprob_topk: int = 5,
                 prefill_rows: Optional[int] = None,
                 mesh=None,
                 name: str = "decoder"):
        import jax
        import jax.numpy as jnp

        self._jax = jax
        self._jnp = jnp
        self.module = module
        self.variables = variables
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        if steps_per_call < 1:
            raise InvalidInput("steps_per_call must be >= 1")
        self.steps_per_call = int(steps_per_call)
        if pipeline_depth < 1:
            raise InvalidInput("pipeline_depth must be >= 1")
        # Decode waves in flight on the device: at depth >= 2 the host
        # fetch of wave N overlaps wave N+1's device execution, so the
        # wave period is max(RTT, K device steps) instead of their sum
        # (jax_engine.py's pipeline_depth, brought to decoding).  The
        # price: EOS/budget/cancel decisions lag the device by up to
        # depth-1 waves — a finishing slot wastes at most
        # (depth-1)*K extra device steps (tracked in stats).
        self.pipeline_depth = int(pipeline_depth)
        # Adaptive depth: stop enqueuing SPECULATIVE waves when every
        # active stream provably finishes (by token budget) within the
        # waves already in flight — those extra waves could only
        # decode garbage (uniform traffic at a fixed depth of 2,
        # where finishes cluster).  Staggered traffic keeps remaining
        # work past the horizon, so depth-2's overlap win is
        # untouched there.
        self.adaptive_depth = bool(adaptive_depth)
        cfg = module.config
        if self.max_seq > cfg.max_seq:
            raise InvalidInput(
                f"engine max_seq {self.max_seq} exceeds the model's "
                f"position table {cfg.max_seq}")
        self.eos_id = eos_id
        self.name = name
        self.mesh = mesh
        buckets = sorted(set(prefill_buckets or
                             pow2_buckets(self.max_seq, 16)))
        if buckets[-1] > self.max_seq:
            raise InvalidInput(
                f"prefill bucket {buckets[-1]} exceeds max_seq "
                f"{self.max_seq}")
        self.prefill_buckets = buckets
        self._rng = jax.random.PRNGKey(rng_seed)
        # Top-N width of the always-computed logprob outputs (fetched
        # from device only when a request asked for them).
        self.logprob_topk = max(1, int(logprob_topk))
        # Default per-request sampling seeds: a deterministic counter —
        # concurrent temperature requests differ from each other, and
        # an explicit seed reproduces exactly.
        self._seed_counter = 0
        # First-dispatch-per-program ledger feeding the KFS_SANITIZE
        # recompile assertion: every (kind, shape-signature) this
        # engine dispatches is noted once through compile_cache —
        # a new program after declared warmup is a violation.  Only
        # touched on the single-threaded enqueue executor.  The
        # source is process-monotonic (never just the model name): a
        # reloaded engine with the same name must not inherit its
        # predecessor's warmup declaration.
        self._dispatched_programs: set = set()
        self.sanitize_source = (
            f"generator:{self.name}:{next(_generator_seq)}")

        # -- the device side (engine/programs.py) ------------------------
        # The cache's arrays and the facts the host books by, from the
        # model's declaration (`config.cache_layers()`) and the sizes
        # above: K/V block pools [NB, BS, H*D] a layer with per-slot
        # block tables, a recurrence's state by slot.
        from kfserving_tpu.engine import programs

        layout = programs.CacheLayout(
            cfg, name, max_slots=self.max_slots, max_seq=self.max_seq,
            prefill_buckets=buckets, block_size=block_size,
            cache_blocks=cache_blocks,
            window_cache_blocks=window_cache_blocks, mesh=mesh)
        self._cache_layers = layout.kinds
        self._caches = layout.caches
        self._cache_dtype = layout.dtype
        self._cache_shape = layout.pool_shape
        self._cache_bytes = layout.cache_bytes
        self.recurrent_state_bytes = layout.state_bytes
        self._state_bytes_per_slot = layout.state_bytes_per_slot
        self.block_size = layout.block_size
        self.blocks_per_slot = layout.blocks_per_slot
        self.num_blocks = layout.num_blocks
        # bucket -> the prompts' entries a row of its prefill program
        # has: its blocks where a row carries as many prompts as they
        # hold (`programs.packs_prompts`), one where it carries one.
        self._row_entries = {
            b: (b // self.block_size
                if programs.packs_prompts(layout.kinds, b,
                                          self.block_size) else 1)
            for b in buckets}
        # Sliding-window layers keep a ring of blocks a sequence in a
        # pool of their own kind; None and 0 for a model without any.
        self._window = layout.window
        self.window_blocks_per_slot = layout.ring_columns
        self.num_window_blocks = layout.num_window_blocks
        self._walk_chunks = layout.walk_chunks
        self._pool_bytes = layout.pool_bytes
        # Whether a block can stand for a prompt's prefix: where not,
        # every plan is a miss, registers nothing, and is counted.
        self._shares_prefixes = layout.shares_prefixes
        obs.generator_recurrent_state_bytes().labels(
            model=name).set(self.recurrent_state_bytes)
        # Host-side paging state (guarded by _block_lock: the
        # enqueue thread allocates while cancel() frees on the
        # loop thread).
        self._block_lock = threading.Lock()
        # Each pool's blocks, tabled by slot (engine/block_pool.py): the
        # whole-context pool, whose blocks prompts share, and the rings'
        # (None for a model without window layers), whose blocks nobody
        # shares.
        from kfserving_tpu.engine.block_pool import BlockPool

        self._pool = BlockPool(layout.pool_name, self.num_blocks,
                               self.max_slots,
                               self.blocks_per_slot,
                               evicted=self._block_evicted_locked)
        self._ring = None if self._window is None else BlockPool(
            "window", self.num_window_blocks, self.max_slots,
            self.window_blocks_per_slot)
        self._pools = [pool for pool in (self._pool, self._ring)
                       if pool is not None]
        # A model whose pools are not the one whole-context K/V pool says
        # which pool a series counts (`pool=`).
        self._names_pools = self._ring is not None or layout.latent
        if self._names_pools:
            for pool in self._pools:
                obs.generator_kv_pool_blocks().labels(
                    model=name, pool=pool.name).set(pool.blocks)
                obs.generator_kv_pool_bytes().labels(
                    model=name, pool=pool.name).set(
                        self._pool_bytes[pool.name])
        # chain-hash -> block id for FULL prompt blocks (prefix
        # reuse); registered blocks that nobody holds linger in the
        # pool (LRU) until allocation pressure evicts.
        self._prefix_index: Dict[bytes, int] = {}
        # Hits per LIVE index entry (reuse depth): the /debug/cache
        # census and the hot-chain top-K read this; entries drop
        # with their index entry on eviction/invalidation.
        self._chain_hits: Dict[bytes, int] = {}
        # Eviction accounting by cause (registry twin:
        # kfserving_tpu_generator_block_evictions_total).
        # Capacity evictions split by fate: spilled (the chain
        # survives in the host KV tier) vs dropped (the drop-on-
        # evict baseline — no tier, no chain, or a failed spill).
        self.block_evictions: Dict[str, int] = {
            "capacity_dropped": 0, "capacity_spilled": 0,
            "index_invalidation": 0, "zombie_deferral": 0}
        self.prefill_tokens_saved = 0
        # (release_at_decode_step, [block ids]) — see
        # _free_slot_state for why release is deferred.
        self._deferred_frees: deque = deque()
        # slot -> provisional prefix registrations of its last
        # plan; confirmed once the prefill enqueues, deregistered
        # if the enqueue fails (the blocks were never written).
        self._plan_regs: Dict[int, List[Tuple[bytes, int]]] = {}
        self.prefix_hits = 0
        self.prefix_misses = 0
        # -- host KV tier (engine/kv_tier.py) ----------------------
        # Capacity-evicted prefix blocks spill to a host-RAM mmap
        # tier instead of being dropped; a returning turn's plan
        # probes device index -> host tier -> re-prefill.  Off by
        # default (host_tier_blocks=0); KFS_KV_TIER_BLOCKS is the
        # env twin for server deployments.
        if host_tier_blocks is None:
            try:
                host_tier_blocks = int(os.environ.get(
                    "KFS_KV_TIER_BLOCKS", "0"))
            except ValueError:
                host_tier_blocks = 0
        self.kv_tier = None
        if host_tier_blocks and int(host_tier_blocks) > 0:
            from kfserving_tpu.engine.kv_tier import HostKVTier

            self.kv_tier = HostKVTier(
                block_bytes=layout.kv_bytes_per_token * self.block_size,
                capacity_blocks=int(host_tier_blocks),
                directory=(host_tier_dir
                           or os.environ.get("KFS_KV_TIER_DIR")),
                model=self.name)
        # Spills awaiting their device gather: (chain, block).
        # Appended under _block_lock at eviction time; drained on
        # the enqueue executor BEFORE any dispatch that could
        # rewrite the evicted block (same-thread FIFO is the
        # ordering proof — the gather's snapshot always precedes
        # the overwrite's dispatch).
        self._spill_pending: List[Tuple[bytes, int]] = []
        # Host-tier fault-backs awaiting their pool insert:
        # (chain, block, request, primary).  primary=False rows
        # are coalesced riders on the same chain's single read.
        self._faultback_pending: List[Tuple[bytes, int, Any,
                                            bool]] = []
        # chain -> destination block of a PENDING (undrained)
        # fault-back: a second plan in the same admission batch
        # shares the block instead of reading the tier twice
        # (single-flight).  Guarded by _block_lock.
        self._faultback_by_chain: Dict[bytes, int] = {}
        self.host_tier_tokens_saved = 0
        # -- chunked prefill -------------------------------------------
        # A cold prompt longer than prefill_chunk_tokens lands in
        # fixed-width chunks that ride the in-flight FIFO between
        # decode waves instead of one monolithic prefill dispatch —
        # live streams see per-chunk stalls, not the whole prompt's
        # device time.  Chunk boundaries align to block_size so the
        # chain-hash prefix index and the block pool are untouched.
        self.prefill_chunk_tokens = (int(prefill_chunk_tokens)
                                     if prefill_chunk_tokens else None)
        if self.prefill_chunk_tokens is not None:
            if self.prefill_chunk_tokens % self.block_size != 0:
                raise InvalidInput(
                    f"prefill_chunk_tokens {self.prefill_chunk_tokens} "
                    f"must be a multiple of block_size "
                    f"{self.block_size} (chunks write whole blocks)")
            if self.prefill_chunk_tokens > self.max_seq:
                raise InvalidInput(
                    f"prefill_chunk_tokens {self.prefill_chunk_tokens} "
                    f"exceeds max_seq {self.max_seq}")
            if self.prefill_chunk_tokens > self.prefill_buckets[-1]:
                # Prompts in (buckets[-1], chunk_tokens] would ride
                # NEITHER path: too long for the monolithic buckets,
                # too short for chunking — and a preempted stream
                # whose merged length lands in that gap could never
                # resume.  Reject the configuration instead of the
                # unlucky prompt.
                raise InvalidInput(
                    f"prefill_chunk_tokens {self.prefill_chunk_tokens} "
                    f"must not exceed the largest prefill bucket "
                    f"{self.prefill_buckets[-1]} (prompts between the "
                    f"two would fit neither the bucketed nor the "
                    f"chunked prefill path)")

        # -- speculative decoding (ROADMAP item 2) ---------------------
        # `speculative` = {"tokens": K >= 1, optional "draft_module",
        # "draft_variables", "draft_window"}.  When None, the
        # KFS_SPECDEC_TOKENS env twin can switch on the n-gram
        # (prompt-lookup) proposer; 0 / unset = off, and the engine is
        # byte-identical to a build without this feature.  With a
        # draft module configured, proposals come from a jitted
        # rolling-window draft scan instead.
        if speculative is None:
            try:
                env_spec = int(os.environ.get("KFS_SPECDEC_TOKENS",
                                              "0"))
            except ValueError:
                env_spec = 0
            if env_spec > 0:
                speculative = {"tokens": env_spec}
        self.spec_tokens = 0
        self._draft_module = None
        self.draft_variables = None
        self._draft_window = 0
        self._spec_draft_fn = None
        if speculative:
            self.spec_tokens = int(speculative.get("tokens", 0))
            if self.spec_tokens < 0:
                raise InvalidInput(
                    "speculative tokens must be >= 0")
        if self.spec_tokens > 0:
            from kfserving_tpu.engine import speculative as spec

            self._ngram = spec.NGramProposer(self.spec_tokens)
            self._draft_module = speculative.get("draft_module")
            self.draft_variables = speculative.get("draft_variables")
            if self._draft_module is not None:
                self._draft_window = int(speculative.get(
                    "draft_window", spec.DEFAULT_DRAFT_WINDOW))
                self._spec_draft_fn = spec.make_draft_proposer(
                    jax, self._draft_module, self.max_slots,
                    self._draft_window, self.spec_tokens)

        # What a kind of layer this model has cannot serve
        # (`programs.UNSERVED`).
        refused = layout.refusal(name, {
            "speculative": self.spec_tokens > 0,
            "prefill_chunk_tokens": self.prefill_chunk_tokens is not None,
            "host_tier_blocks": self.kv_tier is not None})
        if refused:
            raise InvalidInput(refused)
        if "recurrent state" in layout.limits:
            # The recurrence's prefill once hung a v5e, and a hang takes
            # the chip: on a TPU only the shapes that have run are served.
            from kfserving_tpu.ops import ssm

            unproven = ssm.unproven_on_chip(prefill_rows,
                                            self.prefill_buckets)
            if unproven and jax.default_backend() == "tpu":
                raise InvalidInput(
                    f"{name!r} has recurrent state, whose prefill has run "
                    f"on the chip at few shapes only: {unproven}")

        # Parameters are resident from here on, like the pool.
        stored = (self.variables, self.draft_variables)
        self.variables, self.draft_variables = placed = \
            programs.place_params((module, self._draft_module), stored,
                                  mesh)
        from kfserving_tpu.engine import param_cache

        self._params_resident_bytes = param_cache.device_resident_bytes(
            placed)
        narrowed_leaves, self._params_narrowed_bytes = \
            param_cache.narrowed(stored, placed)
        logger.info(
            "%s: parameters resident, %d bytes; %d leaves narrowed to the "
            "dtype they are read in, %d bytes saved", name,
            self._params_resident_bytes, narrowed_leaves,
            self._params_narrowed_bytes)
        del stored, placed

        # A model with routed experts (models/olmoe.py) also reports
        # what its routers chose; a dense decoder's programs and
        # fetches are what they were.
        self._moe = None
        if getattr(cfg, "num_experts", 0):
            from kfserving_tpu.engine.moe_counters import MoeCounters

            self._moe = MoeCounters(name, cfg.num_experts,
                                    cfg.experts_per_token)
        # The jitted programs, under the names the scheduler (and the
        # tests that replace or lower them) use.
        (self._decode, self._feed_update, self._prefill,
         self._chunk_prefill, self._insert, self._spec_verify,
         self._gather_blocks) = programs.build(
            module, self._cache_layers, self.steps_per_call,
            self.logprob_topk, self._rng, self.spec_tokens,
            self.kv_tier is not None)
        # Device-resident feed state: the token each slot feeds next
        # and its position.  Rows of freed slots go stale — that is
        # deliberate; a garbage decode on a free slot is harmless
        # (its tokens are dropped at distribute, OOB cache writes
        # drop, gathers clamp) and admission overwrites the row.
        self._feed_tokens = jnp.zeros(self.max_slots, jnp.int32)
        self._feed_positions = jnp.zeros(self.max_slots, jnp.int32)

        # Two executors with distinct roles: `_executor` owns blocking
        # D2H fetches (each ~an RTT) — TWO workers, because fetches
        # are submitted EAGERLY at enqueue time and a decode wave's
        # tokens must not queue behind a prefill fetch's round trip
        # (results are awaited in FIFO order regardless of completion
        # order).  `_enqueue_executor` owns dispatch enqueues (fast
        # post-compile, but the FIRST call per shape traces + compiles
        # for seconds — that must not freeze the asyncio loop, and
        # must not queue behind an in-flight fetch either, or
        # admission would stall on decode).  Device-side ordering
        # comes from the data-dependency chain on the cache/feed
        # handles, not from host thread order.
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=2,
            thread_name_prefix=f"generator-{name}")
        self._enqueue_executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=1,
            thread_name_prefix=f"generator-enq-{name}")
        # Every launch's row until its fetch returns (engine/inflight.py),
        # and the process heartbeat's look at it, eight times a second
        # from the pipeline's first start to close().
        self._inflight = inflight_table.InflightTable(
            name, (f"generator-enq-{name}_", f"generator-{name}_"))
        self._heartbeat = None
        self._slots: List[Optional[_Active]] = [None] * self.max_slots
        self._pending: deque = deque()
        # Growth starvation: a decodable slot's table cannot cover the
        # horizon and a mid-prefill slot just yielded its blocks — the
        # scheduler HOLDS (no new admissions, no new waves) until the
        # yielded blocks mature through the zombie-deferral window,
        # instead of preempting a stream that already holds context.
        self._growth_starved = False
        self._wakeup: Optional[asyncio.Event] = None
        self._loop_task: Optional[asyncio.Task] = None
        self._closed = False

        # stats
        self.tokens_generated = 0
        self.decode_steps = 0       # device dispatches
        self._token_steps = 0       # dispatches x steps_per_call
        self.prefills = 0           # prefill dispatches
        self.prefill_requests = 0   # requests admitted through them
        self.prefill_rows_dispatched = 0  # their programs' rows
        self.prefill_rows_padded = 0  # the dummy rows among them
        # Both series read 0 from the start: a scrape that lacks them
        # is a server without the counters, not one without a prefill.
        obs.engine_prefill_rows_total().labels(model=self.name)
        obs.engine_prefill_rows_padded_total().labels(model=self.name)
        obs.engine_sampler_tail_calls_total().labels(
            model=self.name, program="decode", noise="0", logprobs="0")
        self.requests_finished = 0
        self.preemptions = 0        # growth-pressure requeues
        self.prefill_chunks = 0     # chunked-prefill dispatches
        self.prefill_chunks_skipped = 0  # whole-chunk prefix hits
        self.chunked_admissions = 0
        # Adaptive-depth accounting: waves the governor refused to
        # enqueue (they could only decode garbage) and the depth the
        # pipeline last ran at.
        self.suppressed_waves = 0
        self._depth_effective = self.pipeline_depth
        # Speculative-decoding accounting (engine twins of the
        # kfserving_tpu_specdec_* registry families).
        self.spec_waves = 0
        self.spec_proposed_tokens = 0   # K per live row per wave
        self.spec_accepted_tokens = 0   # draft tokens that matched
        self.spec_emitted_tokens = 0    # accepted + the bonus draws
        self.spec_fallbacks: Dict[str, int] = {}
        # Bounded accepted-length reservoir for the stats()/cache
        # p50/p99 (full-fidelity histogram lives in the registry).
        self._spec_lengths: deque = deque(maxlen=4096)
        self._spec_draft_s = 0.0
        self._spec_verify_s = 0.0
        self._occupied_slot_steps = 0
        self._wasted_token_steps = 0  # garbage steps past a finish
        # Of them, those past a token budget's end: the decode program
        # had the row parked (no block walked or written, no expert).
        self._parked_token_steps = 0
        # What decode attention had to read, a layer of each pool
        # (_distribute): the tokens of the live rows' contexts (of a
        # window layer min(context, window)), the blocks they lie in,
        # and the paged kernel's loop iterations over those, a row's
        # ceil(blocks / blocks_per_iteration) a step.
        self._walked = {pool.name: np.zeros(3, np.int64)
                        for pool in self._pools}
        # The row count prefill dispatches are held to: configured
        # (`prefill_rows`: the deployment knows what fits beside its
        # parameters), or learned once the runtime has refused one for
        # memory (see _prefill_refused), which also makes every later
        # dispatch wait for its insert.
        if prefill_rows is not None and int(prefill_rows) < 1:
            raise InvalidInput("prefill_rows must be >= 1")
        self.prefill_rows = int(prefill_rows) if prefill_rows else None
        self._prefill_rows_cap: Optional[int] = self.prefill_rows
        self._prefill_refusals = 0
        self.prefix_reuse_refused = 0
        # Union of enqueue->fetch intervals (overlap-corrected at
        # depth >= 2, so the stat stays <= wall clock).
        self._decode_device_s = 0.0
        self._last_fetch_done = 0.0
        # (rows, bucket) -> what that prefill program's last dispatches
        # took on the device, by the fetch workers' clock: a fetch's
        # return less the one before it (or less its own launch, where
        # the device was idle).  A program's first dispatch compiles and
        # leaves an empty record.  `_prefill_rows_to_take` weighs a
        # group's pieces against its padded program by these.
        self._prefill_took_s: Dict[Tuple[int, int], deque] = {}
        self._last_fetched_t = 0.0
        self._decode_wait_s = 0.0     # host blocked in decode fetches
        self._prefill_wait_s = 0.0    # host blocked in prefill fetches
        self._prefill_device_s = 0.0
        # -- roofline accounting (promoted to registry gauges by
        # observability/profiling/roofline.py at /metrics scrape) ------
        # Analytic FLOP model: 2*P matmul FLOPs per token plus
        # attention's 4*layers*heads*head_dim per resident context
        # position (QK^T and AV, 2 FLOPs per MAC each).  Counted over
        # LIVE slots only — garbage waves burn device time without
        # adding useful FLOPs, so decode_mfu is a goodput-weighted
        # floor on chip utilization.
        self._n_params = int(sum(
            int(np.prod(x.shape))
            for x in self._jax.tree.leaves(self.variables)))
        self._param_read_bytes = self.param_bytes()
        self._active_params = self._n_params
        self._expert_read_bytes = 0.0
        if self._moe is not None:
            # A token multiplies by its own experts alone, and a step
            # reads the experts its rows touched (counted on the
            # device, added when the counters arrive) beside what
            # every step reads.
            counts = cfg.param_counts()
            per_param = self._param_read_bytes / self._n_params
            self._active_params = int(counts["active"])
            self._param_read_bytes = counts["always_read"] * per_param
            self._expert_read_bytes = counts["per_expert"] * per_param
        self._flops_matmul_per_token = 2.0 * self._active_params
        # Query heads do the arithmetic, KV heads are what is read.
        self._attn_flops_coeff = (
            4.0 * layout.kv_layers * layout.kv_head_dim
            * getattr(cfg, "num_heads", layout.kv_heads))
        self._kv_bytes_per_token = layout.kv_bytes_per_token
        # Of the K/V layers, the share that reads min(context, window)
        # rows and not the context (`_attended`).
        self._window_layer_share = layout.window_layers / layout.kv_layers
        from kfserving_tpu.engine.jax_engine import device_peak_flops
        from kfserving_tpu.observability.profiling.roofline import (
            device_peak_hbm_bw,
        )

        self._peak_flops = device_peak_flops()
        self._peak_hbm_bw = device_peak_hbm_bw()
        self._decode_flops = 0.0
        self._prefill_flops = 0.0
        self._decode_hbm_bytes = 0.0  # params + resident KV reads
        # Per-prefill-bucket token padding: {bucket: [real, padded]}
        # (updated on the enqueue thread, read by stats(); plain dict
        # ops under the GIL).
        self._prefill_bucket_tokens: Dict[int, List[float]] = {}
        # Growth-HOLD window tracking for the event timeline.
        self._hold_since: Optional[float] = None

    # -- public API --------------------------------------------------------
    def cache_bytes(self) -> int:
        """Every layer's pools and per-slot state."""
        return self._cache_bytes

    def param_bytes(self) -> int:
        jax = self._jax
        return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(self.variables))

    async def generate(self, prompt_ids, max_new_tokens: int = 32,
                       temperature: float = 0.0, **sampling
                       ) -> AsyncIterator[Tuple[int, Optional[str]]]:
        """Yields (token_id, finish_reason) events.  Intermediate
        tokens arrive as (id, None); the stream ends with either
        (id, 'length') — the budget-final token — or (None, 'eos'),
        since EOS is a stop signal, not content.  Engine failures
        surface as InferenceError mid-stream."""
        req = self.submit(prompt_ids, max_new_tokens, temperature,
                          **sampling)
        async for event in self.stream(req):
            yield event

    def submit(self, prompt_ids, max_new_tokens: int = 32,
               temperature: float = 0.0, *, top_k: int = 0,
               top_p: float = 1.0, seed: Optional[int] = None,
               logprobs: int = 0) -> _Request:
        """Validate and enqueue a request NOW (InvalidInput surfaces to
        the caller before any response bytes are committed — the
        streaming route depends on this).  Pair with `stream()`."""
        return self._submit(prompt_ids, max_new_tokens, temperature,
                            top_k=top_k, top_p=top_p, seed=seed,
                            logprobs=logprobs)

    async def stream(self, req: _Request
                     ) -> AsyncIterator[Tuple[Optional[int],
                                              Optional[str]]]:
        while True:
            token, reason = await req.out.get()
            if reason is not None and reason.startswith("error"):
                raise InferenceError(reason)
            yield token, reason
            if reason is not None:
                return

    def cancel(self, req: _Request) -> None:
        """Abandon a request: a consumer that stops caring (client
        disconnect, stop-sequence match) must free the decode slot —
        otherwise the engine decodes to the full token budget for
        nobody.  Runs on the event loop thread (the same thread as all
        slot bookkeeping).  Idempotent; a finished request is a no-op.
        The slot stops being fed at the next wave boundary."""
        if req.cancelled:
            return
        req.cancelled = True
        try:
            self._pending.remove(req)
            req.out.put_nowait((None, "cancelled"))
            self._finalize_cost(req, "cancelled")
            self.requests_finished += 1
            return
        except ValueError:
            pass
        for i, s in enumerate(self._slots):
            if s is not None and s.req is req:
                self._free_slot_state(i)
                self.requests_finished += 1
                req.out.put_nowait((None, "cancelled"))
                self._finalize_cost(req, "cancelled")
                return
        # Neither pending nor active: either already finished (no-op)
        # or mid-prefill on the executor — the install step checks
        # `cancelled` and drops it.

    async def complete(self, prompt_ids, max_new_tokens: int = 32,
                       temperature: float = 0.0, **sampling
                       ) -> Tuple[List[int], str]:
        tokens: List[int] = []
        reason = "length"
        async for token, fin in self.generate(prompt_ids,
                                              max_new_tokens,
                                              temperature,
                                              **sampling):
            if token is not None:
                tokens.append(token)
            if fin is not None:
                reason = fin
        return tokens, reason

    def _submit(self, prompt_ids, max_new_tokens, temperature, *,
                top_k: int = 0, top_p: float = 1.0,
                seed: Optional[int] = None,
                logprobs: int = 0) -> _Request:
        if self._closed:
            raise InvalidInput(f"generator {self.name} is closed")
        ids = np.asarray(prompt_ids, np.int32).reshape(-1)
        if ids.size == 0:
            raise InvalidInput("empty prompt")
        chunked = (self.prefill_chunk_tokens is not None
                   and ids.size > self.prefill_chunk_tokens)
        if ids.size > self.prefill_buckets[-1] and not chunked:
            # Chunked (cold) prompts never ride a prefill bucket —
            # their ceiling is max_seq via the budget clamp below.
            raise InvalidInput(
                f"prompt length {ids.size} exceeds the largest prefill "
                f"bucket {self.prefill_buckets[-1]}")
        need = -(-int(ids.size) // self.block_size)
        if need > self.num_blocks:
            raise InvalidInput(
                f"prompt needs {need} cache blocks but the pool "
                f"holds {self.num_blocks}")
        if max_new_tokens < 1:
            raise InvalidInput("max_new_tokens must be >= 1")
        if not 0.0 < float(top_p) <= 1.0:
            raise InvalidInput("top_p must be in (0, 1]")
        if top_k < 0:
            raise InvalidInput("top_k must be >= 0")
        if logprobs < 0 or logprobs > self.logprob_topk:
            raise InvalidInput(
                f"logprobs must be in [0, {self.logprob_topk}]")
        # Clamp the budget to cache capacity: prompt + generated tokens
        # must fit max_seq.
        budget = min(int(max_new_tokens), self.max_seq - int(ids.size))
        if budget < 1:
            raise InvalidInput(
                f"prompt length {ids.size} leaves no room to generate "
                f"within max_seq {self.max_seq}")
        if seed is None:
            seed = self._seed_counter
            self._seed_counter += 1
        from kfserving_tpu.reliability.deadline import current_deadline
        from kfserving_tpu.tracing import current_request_id

        req = _Request(ids, budget, float(temperature),
                       top_k=int(top_k), top_p=float(top_p),
                       seed=int(seed) & 0x7FFFFFFF,
                       logprobs=int(logprobs),
                       deadline=current_deadline(),
                       trace_id=current_request_id.get(),
                       submit_t=time.perf_counter())
        self._pending.append(req)
        self._ensure_loop()
        return req

    def _ensure_loop(self):
        if self._loop_task is None or self._loop_task.done():
            self._wakeup = asyncio.Event()
            self._loop_task = asyncio.get_running_loop().create_task(
                self._run())
        self._wakeup.set()

    async def close(self):
        self._closed = True
        if self._loop_task is not None:
            if self._wakeup is not None:
                self._wakeup.set()
            try:
                await self._loop_task
            except asyncio.CancelledError:
                pass
        self._unwatch()
        self._executor.shutdown(wait=True)
        self._enqueue_executor.shutdown(wait=True)
        if self.kv_tier is not None:
            self.kv_tier.close()

    def shutdown_nowait(self):
        """Synchronous best-effort teardown (repository unload runs
        outside async context): stop admitting, let the scheduler task
        drain, release the worker threads without joining."""
        self._closed = True
        if self._wakeup is not None:
            self._wakeup.set()
        self._unwatch()
        self._executor.shutdown(wait=False)
        self._enqueue_executor.shutdown(wait=False)
        if self.kv_tier is not None:
            self.kv_tier.close()

    def _unwatch(self) -> None:
        watch, self._heartbeat = self._heartbeat, None
        HEARTBEAT.unwatch(watch)

    def load_gauges(self) -> Dict[str, int]:
        """Instantaneous saturation signal for the autoscaler: a
        generative replica saturates by slot occupancy and pending
        prefill depth, NOT by request count (8 slow streams = '8
        inflight' at the router = invisible saturation)."""
        return {
            "active_slots": sum(1 for s in self._slots
                                if s is not None),
            "pending": len(self._pending),
            "max_slots": self.max_slots,
        }

    def stats(self) -> Dict[str, Any]:
        steps = max(1, self._token_steps)
        out = {
            "tokens_generated": self.tokens_generated,
            "decode_steps": self.decode_steps,
            "token_steps": self._token_steps,
            "steps_per_call": self.steps_per_call,
            "prefills": self.prefills,
            "prefill_requests": self.prefill_requests,
            "prefill_rows_dispatched": self.prefill_rows_dispatched,
            "prefill_rows_padded": self.prefill_rows_padded,
            # Prompts a row that any prompt lay in: above 1 where the
            # programs pack (`programs.packs_prompts`).
            "prefill_prompts_per_row": round(
                self.prefill_requests
                / max(1, self.prefill_rows_dispatched
                      - self.prefill_rows_padded), 4),
            # What each prefill program's last dispatches took (median),
            # which is what decides whether a group splits.
            "prefill_program_ms": {
                f"{rows}x{bucket}": round(
                    1e3 * statistics.median(list(took)), 3)
                for (rows, bucket), took
                in list(self._prefill_took_s.items()) if took},
            "requests_finished": self.requests_finished,
            "slot_occupancy": round(
                self._occupied_slot_steps / (steps * self.max_slots), 4),
            "max_slots": self.max_slots,
            "max_seq": self.max_seq,
            "pipeline_depth": self.pipeline_depth,
            "adaptive_depth": self.adaptive_depth,
            "depth_effective": self._depth_effective,
            "suppressed_waves": self.suppressed_waves,
            "wasted_token_steps": self._wasted_token_steps,
            "parked_token_steps": self._parked_token_steps,
            "kv_block_fill": self._block_fill(self._pool),
            "kv_blocks_per_iteration": round(
                sum(w[1] for w in self._walked.values()) / max(
                    1, sum(w[2] for w in self._walked.values())), 4),
            "prefill_rows_cap": self._prefill_rows_cap or 0,
            "prefill_rows": self.prefill_rows or 0,
            "cache_bytes": self.cache_bytes(),
            "recurrent_state_bytes": self.recurrent_state_bytes,
            # What a request costs the cache: a state a slot and K/V rows
            # a position, each over the layers that keep one (a layer may
            # keep both).
            "state_bytes_per_slot": self._state_bytes_per_slot,
            "kv_bytes_per_token": self._kv_bytes_per_token,
            "params_resident_bytes": self._params_resident_bytes,
            "params_narrowed_bytes": self._params_narrowed_bytes,
            "active_params": self._active_params,
            "decode_device_s": round(self._decode_device_s, 4),
            "decode_wait_s": round(self._decode_wait_s, 4),
            "prefill_wait_s": round(self._prefill_wait_s, 4),
            "prefill_device_s": round(self._prefill_device_s, 4),
            "inflight": self._inflight.rows(),
            "device_starved_s": self._inflight.starved_s(),
        }
        # -- roofline block (promoted to registry gauges by
        # observability/profiling/roofline.py; keys must stay in sync
        # with its consumed-key tables) --------------------------------
        if self._decode_flops > 0 and self._decode_device_s > 0:
            achieved = self._decode_flops / self._decode_device_s
            out["achieved_decode_tflops"] = round(achieved / 1e12, 6)
            if self._peak_flops:
                out["decode_mfu"] = round(
                    achieved / self._peak_flops, 6)
        if self._prefill_flops > 0 and self._prefill_device_s > 0:
            achieved = self._prefill_flops / self._prefill_device_s
            out["achieved_prefill_tflops"] = round(achieved / 1e12, 6)
            if self._peak_flops:
                out["prefill_mfu"] = round(
                    achieved / self._peak_flops, 6)
        if self.tokens_generated + self._wasted_token_steps > 0:
            out["goodput_ratio"] = round(
                self.tokens_generated
                / (self.tokens_generated + self._wasted_token_steps),
                4)
        decode_hbm_bytes = self._decode_hbm_bytes
        if self._moe is not None:
            out.update(self._moe.stats())
            decode_hbm_bytes += (self._moe.touched
                                 * self._expert_read_bytes)
        if decode_hbm_bytes > 0 and self._decode_device_s > 0:
            rate = decode_hbm_bytes / self._decode_device_s
            out["decode_hbm_gb_s"] = round(rate / 1e9, 3)
            if self._peak_hbm_bw:
                out["hbm_bw_util"] = round(
                    min(1.0, rate / self._peak_hbm_bw), 6)
        if self._prefill_bucket_tokens:
            # .copy() is atomic under the GIL; iterating the live dict
            # could race an enqueue-thread insert of a new bucket.
            out["prefill_bucket_pad_waste"] = {
                f"s{b}": round(1.0 - real / padded, 4)
                for b, (real, padded)
                in sorted(self._prefill_bucket_tokens.copy().items())
                if padded > 0}
        with self._block_lock:
            refd = int(np.sum(self._pool.ref > 0))
            resident = sum(s.length for s in self._slots
                           if s is not None)
            # Fragmentation over per-slot TABLE blocks, not refd:
            # a shared prefix block appears in every sharer's
            # table AND every sharer's length, so numerator and
            # denominator count it the same number of times —
            # against refd (which counts it once) the ratio went
            # negative exactly in the shared-prompt regime.
            table_blocks = self._pool.tabled()
            frag = (1.0 - resident
                    / (table_blocks * self.block_size)
                    if table_blocks else 0.0)
            out["paged"] = {
                "block_size": self.block_size,
                "pool_blocks": self.num_blocks,
                # Canonical names, matching the timeline pool
                # counter samples (_record_pool_sample).  The
                # deprecated blocks_free/blocks_reclaimable aliases
                # (ISSUE 13's one-release grace) are gone.
                "free_blocks": len(self._pool.free),
                "reclaimable_blocks": len(self._pool.lingering),
                "prefix_hits": self.prefix_hits,
                "prefix_misses": self.prefix_misses,
                "prefill_tokens_saved": self.prefill_tokens_saved,
                "index_entries": len(self._prefix_index),
                "pool_occupancy_ratio": round(
                    min(1.0, refd / max(1, self.num_blocks)), 4),
                "fragmentation_ratio": round(
                    min(1.0, max(0.0, frag)), 4),
                "evictions": dict(self.block_evictions),
                "preemptions": self.preemptions,
            }
            if self._names_pools:
                # Each pool's own books: capacity, the share of it that
                # slots' tables hold, and of the rows its decode walk
                # read the share that held context.
                out["paged"]["pools"] = {
                    self._pool.name: {
                        "blocks": self.num_blocks,
                        "bytes": self._pool_bytes[self._pool.name],
                        "fill": out["paged"]["pool_occupancy_ratio"],
                        "block_fill": out["kv_block_fill"]}}
            if self._ring is not None:
                out["paged"]["pools"]["window"] = {
                    "blocks": self._ring.blocks,
                    "bytes": self._pool_bytes["window"],
                    "fill": round(
                        self._ring.tabled() / self._ring.blocks, 4),
                    "block_fill": self._block_fill(self._ring),
                    "window": self._window,
                    "blocks_per_slot": self._ring.columns,
                    "recycled": self._ring.recycled}
        if self.kv_tier is not None:
            out["paged"]["host_tier_tokens_saved"] = \
                self.host_tier_tokens_saved
            out["host_tier"] = self.kv_tier.debug()
        if self.prefill_chunk_tokens is not None:
            out["chunked_prefill"] = {
                "chunk_tokens": self.prefill_chunk_tokens,
                "admissions": self.chunked_admissions,
                "chunks_dispatched": self.prefill_chunks,
                "chunks_skipped_shared": self.prefill_chunks_skipped,
            }
        if self.spec_tokens:
            out["speculative"] = self.spec_debug()
        return out

    def _block_fill(self, pool) -> float:
        """Of the rows in the blocks a pool's decode walk read, the
        share that held context."""
        tokens, blocks, _ = self._walked[pool.name]
        return round(tokens / max(1, blocks * self.block_size), 4)

    def spec_debug(self) -> Dict[str, Any]:
        """Speculative-decoding snapshot for stats() and the
        /debug/cache body (the router federates per-replica acceptance
        rates from here, like the prefix census)."""
        lengths = sorted(self._spec_lengths)
        proposed = self.spec_proposed_tokens
        return {
            "tokens": self.spec_tokens,
            "proposer": ("draft" if self._spec_draft_fn is not None
                         else "ngram"),
            "waves": self.spec_waves,
            "proposed_tokens": proposed,
            "accepted_tokens": self.spec_accepted_tokens,
            "emitted_tokens": self.spec_emitted_tokens,
            "acceptance_rate": (round(
                self.spec_accepted_tokens / proposed, 4)
                if proposed else 0.0),
            "accepted_length_p50": _percentile(lengths, 0.50),
            "accepted_length_p99": _percentile(lengths, 0.99),
            "draft_device_s": round(self._spec_draft_s, 4),
            "verify_device_s": round(self._spec_verify_s, 4),
            "draft_param_bytes": self.draft_param_bytes(),
            "fallbacks": dict(self.spec_fallbacks),
        }

    def cache_debug(self, top_k: int = 10) -> Dict[str, Any]:
        """The per-replica `GET /debug/cache` body: prefix-index
        census (entry count, reuse-depth distribution, top-K hot
        chains by hit count) plus the pool occupancy snapshot — the
        exact feed prefix-affinity routing (ROADMAP item 3) and the
        LRU HBM residency manager (item 4) will read, federated by
        the router under the `replica` label."""
        with self._block_lock:
            census = {chain: self._chain_hits.get(chain, 0)
                      for chain in self._prefix_index}
        depths = sorted(census.values())
        hot = sorted(census.items(), key=lambda kv: (-kv[1], kv[0]))
        hot = hot[:max(0, int(top_k))]
        ret = {
            "paged": True,
            "index_entries": len(census),
            "reuse_depth": {
                "p50": _percentile(depths, 0.50),
                "p99": _percentile(depths, 0.99),
                "max": depths[-1] if depths else 0,
                "mean": (round(sum(depths) / len(depths), 3)
                         if depths else 0.0),
            },
            "hot_chains": [{"chain": chain.hex(), "hits": hits}
                           for chain, hits in hot],
            # stats() re-takes the block lock — called OUTSIDE the
            # census hold above.
            "pool": self.stats()["paged"],
        }
        if self.spec_tokens:
            ret["speculative"] = self.spec_debug()
        return ret

    # -- block-pool bookkeeping --------------------------------------------
    # The pools (engine/block_pool.py) change under _block_lock alone:
    # planning, cancel() and deferred frees run on the loop thread, and
    # wave enqueues (enqueue thread) read the tables meanwhile.

    def _block_evicted_locked(self, blk: int,
                              chain: Optional[bytes]) -> None:
        """The whole-context pool took the lingering block `blk`, which
        held `chain`, for an allocation: the chain's fate."""
        if chain is not None and self._prefix_index.get(chain) == blk:
            # Only drop the index entry this block actually backs —
            # a concurrent duplicate admission may have re-pointed
            # the chain at a different (still-resident) block.
            self._prefix_index.pop(chain, None)
            self._chain_hits.pop(chain, None)
        # Fate of the evicted state: spill to the host tier when
        # one is wired (the chain digest is the key; the device
        # gather rides the enqueue executor BEFORE any dispatch
        # can rewrite blk), otherwise — or for an unregistered
        # block — it drops, the baseline.  Spill outcomes resolve
        # asynchronously: the cause counter lands when the tier
        # write commits (capacity_spilled) or fails
        # (capacity_dropped), keeping the split honest under
        # chaos injection.
        if self.kv_tier is None or chain is None:
            self._count_capacity_locked("capacity_dropped", blk)
        elif self.kv_tier.contains(chain):
            # Already host-resident (spilled on a previous
            # eviction and faulted back since): the state is
            # safe, no second copy needed.
            self._count_capacity_locked("capacity_spilled", blk)
        else:
            self._spill_pending.append((chain, blk))

    def _count_capacity_locked(self, cause: str, blk: int) -> None:
        self.block_evictions[cause] += 1
        obs.generator_block_evictions_total().labels(
            model=self.name, cause=cause).inc()
        TIMELINE.record("host", "cache.evict",
                        attrs={"cause": cause, "block": blk})

    def _free_slot_state(self, i: int) -> None:
        """Free slot i AND schedule its blocks' release."""
        self._slots[i] = None
        self._schedule_block_release(i)

    def _deregister_plan(self, slot: int) -> None:
        """Remove a slot's PROVISIONAL prefix registrations (its
        prefill never enqueued, so the registered blocks hold no
        data).  No-op once the plan was confirmed."""
        with self._block_lock:
            dropped = 0
            for chain, blk in self._plan_regs.pop(slot, []):
                if self._prefix_index.pop(chain, None) is not None:
                    dropped += 1
                    self._chain_hits.pop(chain, None)
                self._pool.chain.pop(blk, None)
            self._count_invalidations_locked(dropped)

    def _count_invalidations_locked(self, dropped: int) -> None:
        """Account `dropped` prefix-index entries removed because
        their planned writes never dispatched (plan rollback / enqueue
        failure) — a stale chain surviving here is the share-unwritten-
        blocks bug class, so the count is the telemetry proof the
        invalidation path ran."""
        if dropped <= 0:
            return
        self.block_evictions["index_invalidation"] += dropped
        obs.generator_block_evictions_total().labels(
            model=self.name, cause="index_invalidation").inc(dropped)
        TIMELINE.record("host", "cache.evict",
                        attrs={"cause": "index_invalidation",
                               "entries": dropped})

    def _confirm_plan(self, slot: int) -> None:
        """The slot's prefill is enqueued: its registrations are
        backed by real (dispatched) writes."""
        with self._block_lock:
            self._plan_regs.pop(slot, None)

    def _schedule_block_release(self, slot: int) -> None:
        """Queue a slot's blocks for release.  Release is DEFERRED by
        pipeline_depth waves: dispatches already in flight carry the
        old device table and keep garbage-writing the dead slot's
        tail blocks — releasing (and possibly reallocating) those
        blocks inside that window would let a zombie wave corrupt
        another request's cache."""
        with self._block_lock:
            released = [pool.release(slot) for pool in self._pools]
        if any(released):
            self._deferred_frees.append(
                (self.decode_steps + self.pipeline_depth + 1, released))

    def _process_deferred_frees(self, force: bool = False) -> None:
        released = 0
        while self._deferred_frees and (
                force or self._deferred_frees[0][0] <= self.decode_steps):
            _, matured = self._deferred_frees.popleft()
            released += len(matured[0])
            with self._block_lock:
                for pool, blocks in zip(self._pools, matured):
                    pool.give_back(blocks)
        if released:
            # The normal release path: every slot block matures through
            # the zombie-wave deferral window exactly once.
            self.block_evictions["zombie_deferral"] += released
            obs.generator_block_evictions_total().labels(
                model=self.name, cause="zombie_deferral").inc(released)

    # -- host KV tier: spill & fault-back ----------------------------------
    # Both paths ride the single-worker enqueue executor, whose only
    # submitter is the scheduler loop: submission FIFO there IS device
    # program order, so a gather dispatched before an overwriting
    # insert snapshots pre-overwrite bytes (the XLA data dependency
    # pins them) no matter when its D2H fetch completes, and a
    # fault-back insert dispatched before the plan's own prefill is
    # resident by the time anything reads the block.

    def _drain_spills(self) -> None:
        """Runs on the ENQUEUE executor, before any dispatch that
        could rewrite a spill-pending block: one non-donating gather
        dispatch per <=32-block group snapshots the pending blocks'
        k/v, then the fetch executor D2Hs the snapshot and writes the
        tier — the scheduler loop never touches mmap I/O."""
        if self.kv_tier is None:
            return
        with self._block_lock:
            if not self._spill_pending:
                return
            pending = self._spill_pending
            self._spill_pending = []
        jnp = self._jnp
        with TIMELINE.span(LAUNCH, "engine.spill", blocks=len(pending)):
            for i in range(0, len(pending), 32):
                grp = pending[i:i + 32]
                padded = 1 << (len(grp) - 1).bit_length()
                # Pad to a pow2 gather width (bounded compile count,
                # same discipline as prefill row buckets); pad rows
                # duplicate block 0 and are simply not written to the
                # tier.
                idx = np.asarray(
                    [b for _, b in grp]
                    + [grp[0][1]] * (padded - len(grp)), np.int32)
                self._note_program("kv_gather", padded)
                snap = self._gather_blocks(self._caches,
                                           jnp.asarray(idx))
                self._executor.submit(self._spill_write, grp, snap)

    def _spill_write(self, grp: List[Tuple[bytes, int]], snap) -> None:
        """Fetch-executor side of a spill: D2H the gathered snapshot
        (a sanctioned sync, same contract as wave fetches) and write
        each block's payload into the host tier.  TRANSACTIONAL per
        block: any failure — the `engine.kv_spill` chaos site, a full
        tier, an mmap error — degrades THAT eviction to the
        drop-on-evict baseline, and the tier index only publishes
        after the full payload landed, so a half-spilled chain is
        never readable.  The eviction-cause accounting deferred at
        `_block_evicted_locked` lands here: capacity_spilled when the
        tier committed, capacity_dropped otherwise — the split stays
        honest under chaos."""
        outcomes: List[Tuple[int, str]] = []
        try:
            if faults.configured(fault_sites.ENGINE_KV_SPILL):
                faults.inject_sync(fault_sites.ENGINE_KV_SPILL,
                                   key=self.name)
            with sanitizer.sanctioned_fetch():
                # kfslint: disable=host-sync — sanctioned fetch site:
                # the spill snapshot's D2H join, off-loop on the fetch
                # executor.
                host = [(np.asarray(k), np.asarray(v))
                        for k, v in snap]
            for row, (chain, blk) in enumerate(grp):
                payload = b"".join(
                    part for k, v in host
                    for part in (k[row].tobytes(), v[row].tobytes()))
                ok = self.kv_tier.put(chain, payload)
                outcomes.append((blk, "capacity_spilled" if ok
                                 else "capacity_dropped"))
        except FaultInjected:
            pass  # chaos: remaining blocks degrade to drops below
        except Exception:
            logger.exception("kv spill batch failed")
        finally:
            aborted = len(grp) - len(outcomes)
            if aborted:
                self.kv_tier.note_spill_failure(aborted)
                outcomes.extend(
                    (blk, "capacity_dropped")
                    for _, blk in grp[len(outcomes):])
            with self._block_lock:
                for blk, cause in outcomes:
                    self._count_capacity_locked(cause, blk)

    def _drain_faultbacks(self) -> bool:
        """Runs on the ENQUEUE executor, after planning and before the
        plan's own dispatches: read every pending primary fault-back's
        payload from the host tier and land it in the pool with one
        insert dispatch per <=32-block group.  Returns False on ANY
        failure (the `engine.kv_faultback` chaos site, an entry
        evicted between probe and read, a read error) WITHOUT having
        dispatched anything — the caller rolls the whole plan set back
        and the requests re-admit as plain re-prefills (the chains are
        dropped from the tier, so the replan misses it: transactional
        degradation).  Spills drain FIRST: this very plan's fresh
        dest allocations may have evicted spill-pending blocks, and
        their gather must dispatch before the insert overwrites
        them."""
        self._drain_spills()
        if self.kv_tier is None:
            return True
        with self._block_lock:
            if not self._faultback_pending:
                return True
            pending = self._faultback_pending
            self._faultback_pending = []
        with TIMELINE.span(LAUNCH, "engine.faultback",
                           blocks=len(pending)):
            return self._land_faultbacks(pending)

    def _land_faultbacks(self, pending) -> bool:
        """The body of `_drain_faultbacks` once there is something to
        land: read, insert, publish; False without a dispatch."""
        primaries = [(ch, blk) for ch, blk, _r, prim in pending
                     if prim]
        riders = len(pending) - len(primaries)
        t0 = time.perf_counter()
        payloads: Dict[bytes, bytes] = {}
        try:
            if faults.configured(fault_sites.ENGINE_KV_FAULTBACK):
                faults.inject_sync(fault_sites.ENGINE_KV_FAULTBACK,
                                   key=self.name)
            for ch, _blk in primaries:
                payloads[ch] = self.kv_tier.read(ch)
        except Exception as e:
            # Transactional failure: nothing dispatched, no index
            # entry published.  Drop the chains (their payloads are
            # now suspect / proven unreadable) so the replanned turns
            # MISS the tier and re-prefill from the prompt.
            if not isinstance(e, (FaultInjected, KeyError)):
                logger.warning("kv fault-back failed: %r", e)
            self.kv_tier.note_fault_failure(len(pending))
            with self._block_lock:
                for ch, _blk in primaries:
                    self._faultback_by_chain.pop(ch, None)
            for ch, _blk in primaries:
                self.kv_tier.drop(ch)
                self.kv_tier.end_fault(ch)
            return False
        # Payloads in hand: land them with the same insert program
        # prefill uses (B=1 row, -1 pads drop), then publish the
        # chains to the prefix index — from here the blocks are
        # ordinary shareable device-resident prefix state.
        jnp = self._jnp
        _, bs, width = self._cache_shape   # a block is [BS, H*D]
        dtype = np.dtype(self._cache_dtype)
        per = bs * width * dtype.itemsize
        for i in range(0, len(primaries), 32):
            grp = primaries[i:i + 32]
            padded = 1 << (len(grp) - 1).bit_length()
            layers = [(np.zeros((1, padded * bs, width), dtype),
                       np.zeros((1, padded * bs, width), dtype))
                      for _ in self._caches]
            dest = np.full((1, padded), -1, np.int32)
            for j, (ch, blk) in enumerate(grp):
                pay = payloads[ch]
                dest[0, j] = blk
                for li, (k_new, v_new) in enumerate(layers):
                    off = li * 2 * per
                    k_new[0, j * bs:(j + 1) * bs] = np.frombuffer(
                        pay, dtype, count=bs * width,
                        offset=off).reshape(bs, width)
                    v_new[0, j * bs:(j + 1) * bs] = np.frombuffer(
                        pay, dtype, count=bs * width,
                        offset=off + per).reshape(bs, width)
            self._note_program("kv_faultback", padded)
            self._caches = self._insert(
                self._caches,
                [(jnp.asarray(k), jnp.asarray(v)) for k, v in layers],
                jnp.asarray(dest))
        elapsed_ms = (time.perf_counter() - t0) * 1000.0
        saved = 0
        with self._block_lock:
            for ch, blk in primaries:
                # Publish: the insert is dispatched, so the block is
                # ordinary prefix state.  A concurrent identical
                # admission may have registered the chain first —
                # keep the canonical entry (same rule as
                # _register_chunk_blocks); our block stays private.
                if self._prefix_index.get(ch) is None:
                    self._prefix_index[ch] = blk
                    self._pool.chain[blk] = ch
                self._faultback_by_chain.pop(ch, None)
            for _ch, _blk, req, _prim in pending:
                req.host_tier_hit_blocks += 1
                req.host_tier_saved_tokens += self.block_size
                saved += self.block_size
            self.host_tier_tokens_saved += saved
        for ch, _blk in primaries:
            self.kv_tier.end_fault(ch)
        obs.generator_kv_tier_tokens_saved_total().labels(
            model=self.name).inc(saved)
        self.kv_tier.note_faultback(len(primaries), elapsed_ms)
        if riders:
            self.kv_tier.note_coalesced(riders)
        TIMELINE.record("host", "cache.faultback",
                        attrs={"blocks": len(primaries),
                               "coalesced": riders,
                               "ms": round(elapsed_ms, 3)})
        return True

    # -- durable handoff (ISSUE 19): drain parachute & peer import ---------
    def export_kv(self, budget_s: float = 2.0) -> Dict[str, int]:
        """Drain parachute: export live slots' device KV blocks plus
        the hot prefix-index chains into the host tier under a bounded
        budget, so a successor process (or a peer pulling over
        /kv/chains) can serve the returning conversations as warm
        fault-backs instead of full re-prefills.

        BLOCKING — call off the event loop (the server wraps it in
        run_in_executor on the SIGTERM/announce_swap drain path).  The
        worker rides the single-worker enqueue executor so its gather
        dispatches are FIFO-ordered against any still-inflight wave
        enqueues (the same ordering proof as `_drain_spills`).
        Deadline-aware: candidates are ordered hottest-first (live
        slots, then prefix chains by reuse depth) and whatever the
        budget cannot cover is counted dropped — the export never
        stretches the swap window."""
        zeros = {"exported": 0, "skipped": 0, "dropped": 0,
                 "failed": 0}
        if self.kv_tier is None:
            return zeros
        deadline = time.monotonic() + max(0.0, float(budget_s))
        try:
            fut = self._enqueue_executor.submit(
                self._export_kv_worker, deadline)
        except RuntimeError:
            return zeros  # executor already shut down
        return fut.result()

    def _export_kv_worker(self, deadline: float) -> Dict[str, int]:
        """ENQUEUE-executor side of the drain parachute.  Candidate
        order is the eviction-value order: live slots first (the
        conversation is literally mid-flight — its return is the most
        certain), then registered prefix chains hottest-first by
        reuse depth.  TRANSACTIONAL per block: the tier index only
        publishes complete digest-recorded payloads, and the
        `engine.kv_export` chaos site fails the whole pass BEFORE any
        tier write (every candidate counted outcome=failed — the
        drain degrades to the no-handoff baseline)."""
        import hashlib

        out = {"exported": 0, "skipped": 0, "dropped": 0, "failed": 0}
        t0 = time.perf_counter()
        bs = self.block_size
        cand: List[Tuple[bytes, int]] = []
        seen: set = set()
        with self._block_lock:
            for si, s in enumerate(self._slots):
                if s is None or s.prefilling:
                    continue
                ids = s.req.prompt_ids
                n = int(ids.size)
                ext = max(0, int(s.length) - n)
                if ext > 0 and s.tokens:
                    # The return visit's prompt extends prompt+output,
                    # so chains over the CONCATENATION are what its
                    # plan will probe (same int32 bytes _submit
                    # normalizes to).
                    allids = np.concatenate(
                        [ids, np.asarray(s.tokens[:ext], np.int32)])
                else:
                    allids = ids
                full = min(int(s.length), int(allids.size)) // bs
                chain = b""
                for c in range(full):
                    chain = hashlib.blake2b(
                        chain
                        + allids[c * bs:(c + 1) * bs].tobytes(),
                        digest_size=16).digest()
                    blk = int(self._pool.table[si, c])
                    if blk < 0 or chain in seen:
                        continue
                    seen.add(chain)
                    if self.kv_tier.contains(chain):
                        out["skipped"] += 1
                        continue
                    cand.append((chain, blk))
            hot = sorted(
                ((self._chain_hits.get(ch, 0), ch, blk)
                 for ch, blk in self._prefix_index.items()
                 if ch not in seen),
                key=lambda t: t[0], reverse=True)
            for _depth, ch, blk in hot:
                seen.add(ch)
                if self.kv_tier.contains(ch):
                    out["skipped"] += 1
                    continue
                cand.append((ch, blk))
        try:
            if cand and faults.configured(fault_sites.ENGINE_KV_EXPORT):
                faults.inject_sync(fault_sites.ENGINE_KV_EXPORT,
                                   key=self.name)
        except FaultInjected:
            # Chaos: the whole pass fails BEFORE any tier write.
            out["failed"] = len(cand)
            cand = []
        jnp = self._jnp
        for i in range(0, len(cand), 32):
            if time.monotonic() >= deadline:
                # Budget exhausted: the remaining (coldest) tail is
                # dropped, honestly counted — never stall the swap.
                out["dropped"] += len(cand) - i
                break
            grp = cand[i:i + 32]
            padded = 1 << (len(grp) - 1).bit_length()
            idx = np.asarray(
                [b for _, b in grp]
                + [grp[0][1]] * (padded - len(grp)), np.int32)
            try:
                self._note_program("kv_gather", padded)
                snap = self._gather_blocks(self._caches,
                                           jnp.asarray(idx))
                with sanitizer.sanctioned_fetch():
                    # kfslint: disable=host-sync — sanctioned fetch
                    # site: the drain parachute's D2H join, off-loop
                    # on the enqueue executor during the swap window.
                    host = [(np.asarray(k), np.asarray(v))
                            for k, v in snap]
            except Exception:
                logger.exception("kv export gather failed")
                out["failed"] += len(grp)
                continue
            for row, (chain, _blk) in enumerate(grp):
                payload = b"".join(
                    part for k, v in host
                    for part in (k[row].tobytes(), v[row].tobytes()))
                out["exported" if self.kv_tier.put(chain, payload)
                    else "failed"] += 1
        elapsed_ms = (time.perf_counter() - t0) * 1000.0
        for outcome, count in out.items():
            if count:
                obs.kv_handoff_exported_blocks_total().labels(
                    model=self.name, outcome=outcome).inc(count)
        obs.kv_handoff_export_ms().labels(
            model=self.name).observe(elapsed_ms)
        TIMELINE.record("host", "kv.export",
                        attrs={**out, "ms": round(elapsed_ms, 3)})
        if any(out.values()):
            logger.info(
                "kv export (%s): exported=%d skipped=%d dropped=%d "
                "failed=%d in %.1fms", self.name, out["exported"],
                out["skipped"], out["dropped"], out["failed"],
                elapsed_ms)
        return out

    def kv_import(self, pairs: List[Tuple[bytes, bytes]]
                  ) -> Dict[str, int]:
        """Admit peer-transferred (chain, payload) pairs into the host
        tier (the /kv/reattach pull path; payloads were already
        digest-verified against the wire header by the server).
        BLOCKING but dispatch-free — plain tier writes, safe from any
        executor thread.  TRANSACTIONAL: the `engine.kv_import` chaos
        site rejects the whole batch BEFORE any tier publication
        (every pair counted outcome=failed), so a failed import
        leaves the tier untouched and the returning turn degrades to
        a clean re-prefill."""
        out = {"imported": 0, "skipped": 0, "failed": 0}
        if self.kv_tier is None or not pairs:
            return out
        try:
            if faults.configured(fault_sites.ENGINE_KV_IMPORT):
                faults.inject_sync(fault_sites.ENGINE_KV_IMPORT,
                                   key=self.name)
        except FaultInjected:
            out["failed"] = len(pairs)
            obs.kv_handoff_peer_blocks_total().labels(
                model=self.name, outcome="failed").inc(len(pairs))
            return out
        for chain, payload in pairs:
            if self.kv_tier.contains(chain):
                out["skipped"] += 1
                continue
            out["imported" if self.kv_tier.put(chain, payload)
                else "failed"] += 1
        for outcome, count in out.items():
            if count:
                obs.kv_handoff_peer_blocks_total().labels(
                    model=self.name, outcome=outcome).inc(count)
        TIMELINE.record("host", "kv.import", attrs=dict(out))
        return out

    def _plan_prompt_blocks(self, req: _Request, slot: int,
                            chunk_regs: Optional[Dict[int, Tuple[
                                bytes, int]]] = None,
                            force_miss: bool = False
                            ) -> Optional[List[int]]:
        """Allocate/share blocks for a prompt (loop thread, pre-
        enqueue).  Full chunks probe the prefix index by chain hash —
        causal attention makes k/v for positions [0, m) a pure
        function of the first m tokens, so chunks whose whole-prefix
        chain matches can point at existing blocks instead of storing
        copies.  Returns the per-chunk dest list for the insert
        scatter (-1 = shared hit, write dropped), or None when the
        pool cannot satisfy the request right now (caller leaves it
        pending).

        force_miss (the `generator.prefix_lookup` chaos site, probed
        async by the scheduler loop): skip every index probe — a
        cache-miss storm on demand, which the lookup telemetry must
        count as misses.

        chunk_regs (chunked-prefill admissions): fresh full-block
        registrations land in this dict keyed by block index INSTEAD
        of the prefix index — a chunked prompt's later blocks are
        written by chunk dispatches that may be many waves in the
        future, and registering them now would let a sharer's decode
        read a block no dispatch has been enqueued for yet.  The
        scheduler registers each chunk's blocks when that chunk's
        dispatch enqueues."""
        import hashlib

        bs = self.block_size
        n = int(req.prompt_ids.size)
        full = n // bs
        total = (n + bs - 1) // bs
        pool, ring = self._pool, self._ring
        in_ring = 0 if ring is None else min(total, ring.columns)
        if in_ring and len(ring.free) < in_ring:
            # The rings' pool first, before anything is taken: only this
            # thread allocates, so what is free now is free below.
            return None
        if not self._shares_prefixes:
            # No block stands for a prefix (`programs.UNSERVED`): the
            # plan is a miss whatever the index holds, registers
            # nothing, and says so.
            force_miss = True
            self.prefix_reuse_refused += 1
            obs.generator_prefix_reuse_refused_total().labels(
                model=self.name).inc()
        dest: List[int] = []
        fresh_regs: List[Tuple[bytes, int]] = []
        # Plan-local lookup accounting, flushed to the registry twins
        # outside the block lock (one .labels() resolve per plan, not
        # per block); hit_chains lets the rollback path rewind the
        # reuse-depth census it provisionally advanced.
        plan_hits = 0
        plan_misses = 0
        hit_chains: List[bytes] = []
        depth_obs: List[int] = []
        # Host-tier fault-backs this plan claims: (chain, dest block,
        # primary).  primary=False rows coalesce on a pending fault's
        # block instead of reading the tier again (single-flight).
        plan_host_hits = 0
        host_faults: List[Tuple[bytes, int, bool]] = []
        # Chain digests depend only on the prompt bytes — compute them
        # outside the lock, once, for both the hit probe and the
        # allocation loop below.
        chains: List[bytes] = []
        chain = b""
        for c in range(full):
            chain = hashlib.blake2b(
                chain + req.prompt_ids[c * bs:(c + 1) * bs].tobytes(),
                digest_size=16).digest()
            chains.append(chain)
        with self._block_lock:
            max_hit_blocks = None
            if chunk_regs is not None:
                # Chunk dispatches write EVERY position of their chunk
                # through the slot's table — unlike paged_insert there
                # is no per-block drop mask, so a chunk mixing shared
                # (prefix-hit) and fresh blocks would REWRITE the
                # shared blocks with a different compiled program's
                # (not bit-identical) k/v under a live sharer's reads.
                # Accept hits only as a contiguous prefix rounded DOWN
                # to whole chunks, and never into the final chunk
                # (which always dispatches to sample the first token):
                # all-hit chunks skip their dispatch outright, so the
                # shared blocks they cover are never written.  The
                # probe runs under the SAME lock hold as the
                # allocation loop below — an eviction between the two
                # could otherwise punch a hole in the counted prefix.
                bpc = self.prefill_chunk_tokens // bs
                h = 0
                for c in range(full):
                    if force_miss:
                        break
                    if self._prefix_index.get(chains[c]) is not None:
                        h += 1
                        continue
                    # Probe order: device index above, host tier
                    # here — a host-resident chain counts toward the
                    # contiguous hit prefix (its chunk skips dispatch
                    # after the fault-back lands), re-prefill below.
                    if self.kv_tier is not None and (
                            chains[c] in self._faultback_by_chain
                            or self.kv_tier.contains(chains[c])):
                        h += 1
                        continue
                    break
                n_chunks = -(-n // self.prefill_chunk_tokens)
                max_hit_blocks = min((h // bpc) * bpc,
                                     bpc * (n_chunks - 1))
            for c in range(total):
                host_chain: Optional[bytes] = None
                if c < full:
                    chain = chains[c]
                    hit = (None if force_miss
                           else self._prefix_index.get(chain))
                    if hit is not None and (max_hit_blocks is None
                                            or c < max_hit_blocks):
                        pool.place(slot, c, hit)
                        dest.append(-1)
                        self.prefix_hits += 1
                        plan_hits += 1
                        hit_chains.append(chain)
                        depth = self._chain_hits.get(chain, 0) + 1
                        self._chain_hits[chain] = depth
                        depth_obs.append(depth)
                        continue
                    # Device miss: probe the host tier (probe order
                    # device -> host tier -> re-prefill).  Chunked
                    # plans only accept host hits inside the whole-
                    # chunk hit prefix — exactly where a device hit
                    # would be accepted — because a dispatching chunk
                    # rewrites EVERY block it covers and a fault-back-
                    # registered block may already be shared.
                    if (self.kv_tier is not None and not force_miss
                            and (max_hit_blocks is None
                                 or c < max_hit_blocks)):
                        shared = self._faultback_by_chain.get(chain)
                        if shared is not None:
                            # Single-flight: a pending (undrained)
                            # fault-back already targets this chain —
                            # ride its block instead of reading the
                            # tier twice.
                            pool.place(slot, c, shared)
                            dest.append(-1)
                            plan_host_hits += 1
                            host_faults.append((chain, shared, False))
                            continue
                        if self.kv_tier.begin_fault(chain):
                            host_chain = chain
                blk = pool.alloc()
                if blk is None and host_chain is not None:
                    self.kv_tier.end_fault(host_chain)
                if blk is None:
                    # Roll back: this request waits for freed blocks.
                    # Deregister THIS plan's fresh registrations
                    # first — their blocks were never written, and a
                    # later plan hitting a stale chain would share
                    # all-zero k/v (code-review r5).
                    dropped = 0
                    for ch, b in fresh_regs:
                        if self._prefix_index.pop(ch, None) is not None:
                            dropped += 1
                            self._chain_hits.pop(ch, None)
                        pool.chain.pop(b, None)
                    self._count_invalidations_locked(dropped)
                    pool.give_back(pool.release(slot))
                    # Release this plan's host-tier claims: primaries
                    # unpin their tier entries (eviction may take them
                    # again) and unpublish the coalescing point; the
                    # replan re-probes the tier from scratch.
                    for ch, _b, primary in host_faults:
                        if primary:
                            self.kv_tier.end_fault(ch)
                            self._faultback_by_chain.pop(ch, None)
                    # Rewind the reuse-depth census: the replan will
                    # re-probe these chains and count them again.
                    for ch in hit_chains:
                        d = self._chain_hits.get(ch)
                        if d is not None:
                            if d <= 1:
                                self._chain_hits.pop(ch, None)
                            else:
                                self._chain_hits[ch] = d - 1
                    self._flush_lookup_counters(
                        req, None, plan_hits, plan_misses, depth_obs,
                        plan_host_hits=plan_host_hits)
                    return None
                pool.place(slot, c, blk)
                if host_chain is not None:
                    # Fault-back: the host tier holds this chain's
                    # k/v.  The drain (enqueue executor, FIFO-before
                    # any dispatch that could read the block) inserts
                    # it into `blk`; the plan treats the block as a
                    # hit — dest -1 drops the prefill's own write, and
                    # an all-hit chunk skips its dispatch outright
                    # (the compute saving fault-back exists for).
                    dest.append(-1)
                    plan_host_hits += 1
                    host_faults.append((host_chain, blk, True))
                    self._faultback_by_chain[host_chain] = blk
                    continue
                dest.append(blk)
                if c < full:
                    plan_misses += 1
                    # Freshly written FULL prompt blocks become
                    # shareable (they are never written again: decode
                    # writes land past the prompt).  PROVISIONAL until
                    # the prefill actually enqueues — an enqueue
                    # failure must deregister them.
                    self.prefix_misses += 1
                    if chunk_regs is not None:
                        # A demoted hit (the chain already maps — its
                        # block just wasn't acceptable above) keeps the
                        # canonical index entry; registering this
                        # recompute would churn sharers onto a
                        # duplicate block for no gain.
                        if self._prefix_index.get(chain) is None:
                            chunk_regs[c] = (chain, blk)
                    elif self._shares_prefixes:
                        self._prefix_index[chain] = blk
                        pool.chain[blk] = chain
                        fresh_regs.append((chain, blk))
            if chunk_regs is None:
                self._plan_regs[slot] = fresh_regs
            # The rings take the prompt's last blocks alone: the insert
            # writes those and drops the rest.
            for j in range(total - in_ring, total):
                ring.place(slot, j, ring.alloc())
            if host_faults:
                # Claimed under the lock; the caller MUST drain these
                # (one tier read + one pool insert dispatch on the
                # enqueue executor) before any dispatch of this plan
                # can read the blocks, and roll the whole plan back if
                # the drain fails.
                for ch, b, primary in host_faults:
                    self._faultback_pending.append((ch, b, req,
                                                    primary))
        self._flush_lookup_counters(req, dest, plan_hits, plan_misses,
                                    depth_obs,
                                    plan_host_hits=plan_host_hits)
        return dest

    def _flush_lookup_counters(self, req: _Request,
                               dest: Optional[List[int]],
                               plan_hits: int, plan_misses: int,
                               depth_obs: List[int],
                               plan_host_hits: int = 0) -> None:
        """Flush one plan's lookup accounting to the registry twins
        (one family resolve per plan, outside the per-block loop) and,
        on a successful plan, fold the cache economics into the
        request's cost record and the timeline."""
        if plan_hits:
            obs.generator_prefix_lookups_total().labels(
                model=self.name, outcome="hit").inc(plan_hits)
            fam = obs.generator_prefix_reuse_depth_hits()
            for depth in depth_obs:
                fam.labels(model=self.name).observe(depth)
        if plan_host_hits:
            # Device miss answered by the host tier: counted as its
            # own lookup outcome (token-saved attribution waits for
            # the fault-back to actually COMMIT on the drain — a
            # chaos-failed fault-back re-prefills and saves nothing).
            obs.generator_prefix_lookups_total().labels(
                model=self.name, outcome="host_hit").inc(
                    plan_host_hits)
        if plan_misses:
            obs.generator_prefix_lookups_total().labels(
                model=self.name, outcome="miss").inc(plan_misses)
        if dest is None:
            return
        req.blocks_held = max(req.blocks_held, len(dest))
        if plan_hits:
            saved = plan_hits * self.block_size
            self.prefill_tokens_saved += saved
            req.cache_hit_blocks += plan_hits
            req.cache_saved_tokens += saved
            obs.generator_prefill_tokens_saved_total().labels(
                model=self.name).inc(saved)
            TIMELINE.record("host", "cache.hit",
                            trace_id=req.trace_id,
                            attrs={"blocks": plan_hits,
                                   "tokens_saved": saved})

    def _ensure_block_capacity(self) -> List[int]:
        """Grow active slots' tables to cover the next
        pipeline_depth * K decode steps (device positions run ahead
        of the host by up to that).  Returns slots that could not
        grow — the caller fails those requests."""
        bs = self.block_size
        horizon = self.steps_per_call * self.pipeline_depth + 1
        if self.spec_tokens:
            # A spec wave writes K+1 positions past the host length in
            # one dispatch (spec runs depth-1, but the widest single
            # dispatch sets the write horizon).
            horizon = max(horizon, self.spec_tokens + 2)
        failed: List[int] = []
        with self._block_lock:
            recycled = -sum(pool.recycled for pool in self._pools)
            for i, s in enumerate(self._slots):
                if s is None or s.prefilling:
                    # Mid-chunked-prefill slots hold their whole
                    # prompt's blocks already and decode nothing —
                    # growth starts when the final chunk lands.
                    continue
                need = min((s.length + horizon + bs - 1) // bs,
                           self.blocks_per_slot)
                # A pool that runs out keeps what it took, and the rings
                # are not asked once the whole-context pool has.
                if not all(pool.take(i, need) for pool in self._pools):
                    failed.append(i)
                # Peak residency for the cost record.
                s.req.blocks_held = max(s.req.blocks_held,
                                        int(self._pool.covered[i]))
            recycled += sum(pool.recycled for pool in self._pools)
        if recycled:
            obs.generator_window_blocks_recycled_total().labels(
                model=self.name).inc(recycled)
        return failed

    def _table_device(self):
        """Device copy of the block tables for a dispatch."""
        with self._block_lock:
            # Copy under the lock: cancel() clears rows on the loop
            # thread while waves enqueue on the enqueue thread.
            tables = [pool.snapshot() for pool in self._pools]
        if len(tables) == 1:
            return self._jnp.asarray(tables[0])
        return tuple(self._jnp.asarray(t) for t in tables)

    def _record_pool_sample(self) -> None:
        """Occupancy counter sample for the event timeline (rendered
        as Chrome counter tracks).  Lock-free reads: len() under the
        GIL is atomic and a stale-by-one sample is fine for a
        telemetry series."""
        values = {
            "active_slots": sum(1 for s in self._slots
                                if s is not None),
            "pending": len(self._pending),
            # String attr: the Chrome exporter drops non-numerics from
            # counter series, but multi-engine consumers (the bench
            # cache summary) need to know WHOSE pool a sample
            # describes — untagged samples would blend two engines'
            # pools into one meaningless ratio.
            "engine": self.name,
            "free_blocks": len(self._pool.free),
            "reclaimable_blocks": len(self._pool.lingering),
        }
        TIMELINE.counter("pool", values)

    # -- scheduler ---------------------------------------------------------
    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s is None:
                return i
        return None

    async def _run(self):
        try:
            await self._run_inner()
        except Exception as e:  # decode/device failure: global
            logger.exception("generation scheduler failed")
            self._fail_all(f"error: generation failed: {e}")
        finally:
            # A close()/unload() with work in flight must not strand
            # awaiters on queues that will never receive a terminal
            # event.
            if self._closed:
                self._fail_all("error: generator closed")

    def _fail_all(self, reason: str):
        for i, s in enumerate(self._slots):
            if s is not None:
                s.req.out.put_nowait((None, reason))
                self._free_slot_state(i)
        while self._pending:
            self._pending.popleft().out.put_nowait((None, reason))

    def _bucket_for(self, n: int) -> int:
        return next(b for b in self.prefill_buckets if b >= n)

    def _set_hold(self, held: bool) -> None:
        """Track growth-starvation HOLD transitions: the window from
        the first held iteration to the release is one host-track
        timeline span — the stall a pinned p99 outlier (or a bench
        summary) can attribute instead of inferring."""
        self._growth_starved = held
        if held:
            if self._hold_since is None:
                self._hold_since = time.time()
        elif self._hold_since is not None:
            now = time.time()
            TIMELINE.record("host", "hold", dur_s=now - self._hold_since,
                            t_end=now)
            self._hold_since = None

    async def _probe_prefix_fault(self) -> bool:
        """The `generator.prefix_lookup` chaos site, probed ON the
        loop (async sleeps for injected latency — never a blocking
        sleep on the scheduler): an injected error forces the next
        admission's plan to MISS the whole prefix index, a cache-miss
        storm on demand whose misses the lookup telemetry must count.
        configured() keeps the no-faults hot path at one dict
        lookup."""
        if not faults.configured(fault_sites.GENERATOR_PREFIX_LOOKUP):
            return False
        try:
            await faults.inject(fault_sites.GENERATOR_PREFIX_LOOKUP,
                                key=self.name)
        except FaultInjected:
            return True
        return False

    def _take_prefill_group(self, force_miss: bool = False):
        """Pop the front run of pending requests that share a prefill
        bucket, up to the free slot count — they ride ONE prefill
        dispatch, each with a slot and a block plan of its own.  The
        run is laid into the program's rows (`lay_rows`): a prompt
        takes a row, or, where the program packs
        (`programs.packs_prompts`), the blocks it fills, the next
        prompt starting at the row's next block, so a row carries as
        many prompts as its blocks hold.  How many rows go is asked of
        the rows (`_prompts_to_take`: the deployment's or the runtime's
        cap, see _prefill_refused, and the programs' own timings), and
        the take has the longest front of the run that lies in them:
        the loop's next take has the rest, whole prompts all.  Strict
        FIFO between takes: a different-bucket request at the front is
        never jumped; inside a take the order is the layout's, since
        it is one dispatch either way.  Each taken request's prompt
        blocks are planned (allocated/prefix-shared) HERE on the loop
        thread; a request the pool cannot hold yet stays pending (it
        admits when slots release blocks).  Returns (group, slots,
        bucket, dest_rows); a request's place is its `prefill_entry`."""
        free = [i for i, s in enumerate(self._slots) if s is None]
        sizes: List[int] = []  # the entries of a row each prompt takes
        bucket = 0
        for req in itertools.islice(self._pending, len(free)):
            if self._is_cold(req):
                break  # cold prompts take the chunked path
            b = self._bucket_for(req.prompt_ids.size)
            if sizes and b != bucket:
                break
            bucket = b
            sizes.append(-(-int(req.prompt_ids.size) // self.block_size)
                         if self._row_entries[b] > 1 else 1)
        group: List[_Request] = []
        dest_rows: List[List[int]] = []
        for slot in free[:self._prompts_to_take(sizes, bucket)]:
            plan = self._plan_prompt_blocks(self._pending[0], slot,
                                            force_miss=force_miss)
            if plan is None:
                break  # pool pressure: wait for released blocks
            dest_rows.append(plan)
            group.append(self._pending.popleft())
        del sizes[len(group):]
        keep = self._prompts_to_take(sizes, bucket)
        if keep < len(group):
            # The pool stopped the take short of a power of two of
            # rows: the prompts past the largest one in it go back,
            # plans undone.
            self._requeue_group(group[keep:], free[keep:len(group)])
            del group[keep:], dest_rows[keep:], sizes[keep:]
        now = time.perf_counter()
        for req, at in zip(group, lay_rows(
                sizes, self._row_entries.get(bucket, 1))):
            req.taken_t = now
            req.prefill_entry = at
        return group, free[:len(group)], bucket, dest_rows

    def _prompts_to_take(self, sizes: List[int], bucket: int) -> int:
        """How many of the front run's prompts (`sizes`: the entries of
        a row each takes) the next prefill dispatch carries: the
        longest front that lies in the rows `_prefill_rows_to_take`
        gives the rows they all lie in, held to the row cap."""
        if not sizes:
            return 0
        per_row = self._row_entries[bucket]

        def rows_of(prompts: int) -> int:
            return 1 + max(lay_rows(sizes[:prompts], per_row)) // per_row

        rows = rows_of(len(sizes))
        if self._prefill_rows_cap is not None:
            rows = min(rows, self._prefill_rows_cap)
        keep = self._prefill_rows_to_take(rows, bucket)
        prompts = len(sizes)
        while rows_of(prompts) > keep:
            prompts -= 1
        return prompts

    def _program_rows(self, group: List[_Request], bucket: int) -> int:
        """The rows of the prefill program that the taken `group` lies
        in, before they pad to a power of two."""
        return 1 + (max(req.prefill_entry for req in group)
                    // self._row_entries[bucket])

    def _prefill_rows_to_take(self, rows: int, bucket: int) -> int:
        """How many of the `rows` that a run of same-bucket requests
        lies in the next prefill dispatch carries: all of them, padded
        to the next power of two with dummy rows, or the largest power
        of two in `rows`, through the program of exactly that many (the
        next takes have the rest).  The cut is made where this engine has timed the padded
        program and the program of every power of two in `rows` (7:
        those of 8, 4, 2 and 1 rows), and the pieces together took less
        than the padded one.  Both hold or fail by what the model's
        programs cost on this device: a dense model's grow with their
        rows, so 5 as 4 + 1 is five eighths of a program of 8; an
        expert model's small ones may stream as many experts as its
        large ones and cost as much.  A program is timed from its
        second dispatch on, so a split compiles nothing: the programs a
        process compiles, and when, are those of an engine that always
        pads."""
        top = 1 << rows.bit_length() >> 1
        if top == rows:
            return rows
        pieces = [1 << i for i in range(rows.bit_length()) if rows >> i & 1]
        took = [self._prefill_took_s.get((r, bucket))
                for r in [2 * top] + pieces]
        if not all(took):
            return rows
        padded_s, *pieces_s = map(statistics.median, took)
        return top if sum(pieces_s) < padded_s else rows

    def _requeue_group(self, group: List[_Request],
                       slots: List[int]) -> None:
        """Nothing of this taken group was dispatched: roll its plans
        back and put its requests back at the front of the queue."""
        for slot in slots:
            self._deregister_plan(slot)
            self._schedule_block_release(slot)
        for req in reversed(group):
            self._pending.appendleft(req)

    def _prefill_refused(self, exc: Exception, rows: int) -> bool:
        """True when the runtime refused a prefill launch of more than
        one row for memory; later dispatches are then held to half the
        refused row count.

        The prefill program is the one launch that needs fresh HBM for
        its outputs (every layer's k/v for rows x bucket; the others
        write the donated pool), and nothing was donated or ran, so
        the group can simply be taken again in smaller dispatches.
        Seen on the v5e once launches stopped waiting on a parameter
        transfer, while the decode kernel read a padded twin of every
        layer's pool: with gpt2-large's parameters and pool resident
        (6.0 GiB) and the 16-step decode program's 9.2 GiB of
        temporaries reserved, 0.5 of the chip's 15.75 GiB were left,
        which held a (4, 512) dispatch's outputs and not an (8, 512)
        one's.  With the pool stored [NB, BS, H*D] that program keeps
        1.5 GiB and the refusal has not been seen again."""
        if "RESOURCE_EXHAUSTED" not in str(exc) or rows < 2:
            return False
        padded = 1 << (rows - 1).bit_length()  # the launch's row bucket
        self._prefill_rows_cap = padded // 2
        self._prefill_refusals += 1
        from kfserving_tpu.engine.hbm import device_hbm_stat

        logger.warning(
            "prefill launch of %d rows refused for memory (%s bytes in "
            "use of %s): dispatches held to %d rows from here on: %s",
            padded, device_hbm_stat("bytes_in_use"),
            device_hbm_stat("bytes_limit"), self._prefill_rows_cap, exc)
        return True

    # -- chunked prefill ---------------------------------------------------
    # A COLD prompt (longer than prefill_chunk_tokens) lands in fixed-width, block-aligned chunks that ride the same
    # in-flight FIFO as decode waves — the scheduler alternates chunk
    # and wave dispatches, so live streams stall per-chunk instead of
    # per-prompt.  Carried state: the slot's block table holds every
    # written position's k/v (cross-chunk attention reads it exactly
    # like decode), the next chunk index lives on the _Active, and the
    # final chunk samples the stream's first token on device.

    def _is_cold(self, req: _Request) -> bool:
        return (self.prefill_chunk_tokens is not None
                and int(req.prompt_ids.size) > self.prefill_chunk_tokens)

    def _chunk_shared(self, act: _Active, idx: int) -> bool:
        """True when every block of chunk `idx` was a prefix-cache
        hit — the pool already holds its k/v, so the chunk's dispatch
        can be skipped outright (the monolithic path recomputes and
        drops the writes; chunking turns the hit into saved FLOPs)."""
        bpc = self.prefill_chunk_tokens // self.block_size
        lo = idx * bpc
        hi = min(lo + bpc, len(act.chunk_dest))
        return all(act.chunk_dest[c] == -1 for c in range(lo, hi))

    async def _admit_chunked(self, loop, inflight: deque,
                             force_miss: bool = False) -> bool:
        """Admit the front pending (cold) request onto a free slot in
        chunked mode: plan ALL prompt blocks now (prefix hits share;
        registration of fresh blocks is deferred per chunk), install
        the slot as `prefilling`, and dispatch the first chunk.
        Returns False on pool pressure — the request stays pending."""
        slot = self._free_slot()
        req = self._pending[0]
        chunk_regs: Dict[int, Tuple[bytes, int]] = {}
        with TIMELINE.span(HOST, "engine.admit", trace_id=req.trace_id,
                           slot=slot, rows=1):
            dest = self._plan_prompt_blocks(req, slot,
                                            chunk_regs=chunk_regs,
                                            force_miss=force_miss)
        if dest is None:
            return False
        if (self.kv_tier is not None and self._faultback_pending
                and not await loop.run_in_executor(
                    self._enqueue_executor, self._drain_faultbacks)):
            # Transactional fault-back failure: nothing dispatched —
            # release this plan's blocks (its fresh registrations were
            # deferred into chunk_regs and never published) and leave
            # the request pending.  The immediate replan misses the
            # tier (failed chains dropped) and re-prefills.
            self._schedule_block_release(slot)
            return True
        self._pending.popleft()
        req.taken_t = time.perf_counter()
        n = int(req.prompt_ids.size)
        act = _Active(req=req, length=n, last_token=-1, generated=0,
                      prefilling=True,
                      chunk_total=-(-n // self.prefill_chunk_tokens),
                      chunk_dest=dest, chunk_regs=chunk_regs)
        self._slots[slot] = act
        self.chunked_admissions += 1
        await self._step_chunk(loop, inflight, slot, act)
        req.enqueued_t = time.perf_counter()
        return True

    async def _step_chunk(self, loop, inflight: deque, slot: int,
                          act: _Active) -> None:
        """Dispatch the next chunk of a mid-prefill slot into the
        in-flight FIFO.  Chunks whose every block was a prefix hit are
        skipped (except the final one — it must run to sample the
        first token)."""
        idx = act.chunk_next
        # kfslint: disable=spin-loop — bounded by chunk_total (each
        # pass increments idx); no external coroutine gates the exit.
        while idx < act.chunk_total - 1 and self._chunk_shared(act,
                                                               idx):
            self.prefill_chunks_skipped += 1
            obs.generator_prefill_chunks_total().labels(
                outcome="skipped_shared").inc()
            idx += 1
        final = idx >= act.chunk_total - 1
        act.chunk_next = idx + 1
        try:
            firsts_h, lp_h, seq = await loop.run_in_executor(
                self._enqueue_executor, self._enqueue_chunk,
                slot, act, idx, final)
        except Exception as e:
            # Same contract as a monolithic prefill enqueue failure:
            # fail THIS request, release its blocks (deferred), keep
            # everything else decoding.  Deferred registrations were
            # never published, so no stale chain can alias.
            logger.exception("chunk-prefill enqueue failed")
            if self._slots[slot] is act:
                self._free_slot_state(slot)
                act.req.out.put_nowait(
                    (None, f"error: prefill failed: {e}"))
            return
        if self._slots[slot] is act:
            # Fresh blocks of THIS chunk are now backed by a
            # dispatched write: publish them to the prefix index
            # (a cancel during the enqueue released the blocks — a
            # publish then would alias a future occupant's data).
            self._register_chunk_blocks(act, idx)
            if final:
                # The first token is in the device feed arrays: waves
                # enqueued from here on decode this slot for real.
                act.prefilling = False
        self.prefill_chunks += 1
        obs.generator_prefill_chunks_total().labels(
            outcome="dispatched").inc()
        act.chunks_inflight += 1
        fut = loop.run_in_executor(
            self._executor, self._fetch_joined, seq, "chunk",
            self._fetch_wave, firsts_h, lp_h)
        inflight.append(("chunk", fut, (slot, act, idx, final),
                         time.perf_counter(), seq))

    def _register_chunk_blocks(self, act: _Active, idx: int) -> None:
        if not act.chunk_regs:
            return
        bpc = self.prefill_chunk_tokens // self.block_size
        lo = idx * bpc
        hi = min(lo + bpc, len(act.chunk_dest))
        with self._block_lock:
            for c in range(lo, hi):
                reg = act.chunk_regs.pop(c, None)
                if reg is None:
                    continue
                chain, blk = reg
                if self._prefix_index.get(chain) is not None:
                    # A concurrent identical admission registered this
                    # chain first (both planned before either's chunk
                    # dispatched, so both allocated fresh blocks).
                    # Keep the canonical entry: overwriting would leave
                    # the first block's chain mapping orphaned,
                    # and its eventual eviction used to delete the
                    # survivor's index entry.  Our block stays private
                    # and frees normally.
                    continue
                self._prefix_index[chain] = blk
                self._pool.chain[blk] = chain

    @_dispatch_timed("chunk")
    def _enqueue_chunk(self, slot: int, act: _Active, idx: int,
                       final: bool):
        """Runs on the enqueue executor: park the slot's feed row
        (speculative decode-wave writes for a mid-prefill slot must
        drop — the sentinel is out of every table's range), dispatch
        one chunk forward through the slot's block-table row, and on
        the final chunk scatter the sampled first token into the
        device feed arrays — the very next wave decodes this slot
        without any host round trip."""
        # The admission plan that produced this chunk (or a concurrent
        # one) may have evicted spill-pending blocks this chunk's
        # writes will rewrite: gather first.
        self._drain_spills()
        jnp = self._jnp
        req = act.req
        C = self.prefill_chunk_tokens
        n = int(req.prompt_ids.size)
        start = idx * C
        end = min(start + C, n)
        width = end - start
        with TIMELINE.span(LAUNCH, "engine.prep.chunk",
                           trace_id=req.trace_id, slot=slot):
            # Roofline accounting: this chunk's queries attend the
            # whole resident prefix (positions start..end-1 attend up
            # to their own index) — the same triangular term the
            # monolithic path accrues, sliced per chunk.
            self._prefill_flops += (
                self._flops_matmul_per_token * width
                + self._attn_flops_coeff * width * (start + end) / 2.0)
            ids = np.zeros((1, C), np.int32)
            ids[0, :width] = req.prompt_ids[start:end]
            # Padding queries of a partial final chunk park on the
            # same out-of-range sentinel: their cache writes drop and
            # their logits are never read (last_idx points at the last
            # REAL token).
            qpos = np.full((1, C), self.max_seq, np.int32)
            qpos[0, :width] = np.arange(start, end, dtype=np.int32)
            slot_d = jnp.asarray(np.asarray([slot], np.int32))
            park = (jnp.zeros((1,), jnp.int32),
                    jnp.full((1,), self.max_seq, jnp.int32))
        with self._inflight.launch("feed", rows=1):
            self._feed_tokens, self._feed_positions = \
                self._feed_update(
                    self._feed_tokens, self._feed_positions,
                    slot_d, *park)
        # Slice the table row to the blocks chunks 0..idx cover: the
        # chunk's per-query-causal attention never reads past its own
        # end, and gathering the full max_seq-wide row would make
        # chunk 0 of a 4k prompt do 8x the key work it needs (summed
        # over chunks, ~2x the monolithic prefill's attention FLOPs —
        # eroding the stall win chunking buys).  One compiled program
        # per chunk INDEX (shape (idx+1)*bpc), all of them warmed by
        # the first full-length cold prefill; padding queries still
        # drop via the block_idx >= mb guard in paged_write.
        bpc = C // self.block_size
        nb = min((idx + 1) * bpc, self.blocks_per_slot)
        with mesh_scope(self.mesh):
            with TIMELINE.span(LAUNCH, "engine.prep.chunk",
                               trace_id=req.trace_id, slot=slot):
                self._note_program("chunk", nb)
                with self._block_lock:
                    row = self._pool.table[slot:slot + 1, :nb].copy()
                n_d = jnp.asarray(np.asarray([n], np.int32))
                temps = np.asarray([req.temperature], np.float32)
                want_lp = req.logprobs > 0
                args = [jnp.asarray(row), jnp.asarray(ids),
                        jnp.asarray(qpos),
                        jnp.asarray(np.asarray([max(width - 1, 0)],
                                               np.int32)),
                        jnp.asarray(temps),
                        jnp.asarray(np.asarray([req.top_k], np.int32)),
                        jnp.asarray(np.asarray([req.top_p],
                                               np.float32)),
                        jnp.asarray(np.asarray([req.seed], np.int32)),
                        n_d,
                        jnp.asarray(self._tail_asked("chunk", temps,
                                                     want_lp))]
            with self._inflight.launch(
                    "chunk", trace_id=req.trace_id, slot=slot, rows=1,
                    bucket=nb * self.block_size) as launched:
                (first, self._caches, chosen_lp, top_ids, top_lps) = \
                    self._chunk_prefill(self.variables, self._caches,
                                        *args)
        if final:
            with self._inflight.launch("feed", rows=1):
                self._feed_tokens, self._feed_positions = \
                    self._feed_update(
                        self._feed_tokens, self._feed_positions,
                        slot_d, first, n_d)
        lp_h = (chosen_lp, top_ids, top_lps) if want_lp else None
        return first, lp_h, launched.seq

    async def _run_inner(self):
        loop = asyncio.get_event_loop()
        # The in-flight pipeline: decode waves AND prefill batches
        # share one FIFO of dispatched-but-unfetched device work.
        # Prefill rides it like any wave — admission enqueues prompt
        # forward + cache insert + feed scatter and returns WITHOUT a
        # host sync (the old blocking admission added a full
        # prefill-dispatch of inter-token stall to every live stream).
        # Items: ("decode", fetch_future, snapshot, t0, seq) or
        # ("prefill", fetch_future, (entries, bucket), t0, seq) where
        # entries is [(slot, _Active|None)] in batch order and seq the
        # launch's number in the in-flight table.  Fetch futures are
        # submitted EAGERLY at enqueue (round trips overlap on the
        # 2-worker fetch executor); awaiting in FIFO order preserves
        # delivery order.
        inflight: deque = deque()
        self._inflight.loop_ident = threading.get_ident()
        if self._heartbeat is None and not self._closed:
            self._heartbeat = HEARTBEAT.watch(
                loop, beat=self._inflight.check, rows=self._inflight.rows)
        # A loop that ended for want of work stood waiting until now.
        self._inflight.waiting(False)
        try:
            # KFS_SANITIZE=1: jax.transfer_guard("disallow") armed on
            # this (the scheduler's) thread for the pipeline's whole
            # life — any implicit host<->device transfer inside the
            # decode loop raises, is counted as a forbidden_transfer
            # violation, and fails generation loudly.  The sanctioned
            # fetch/enqueue paths run on executor threads the guard
            # (thread-local) never covers, and additionally wrap
            # themselves in sanitizer.sanctioned_fetch().  Disabled,
            # loop_guard is one env read.
            with sanitizer.loop_guard(self.name):
                await self._run_pipeline(loop, inflight)
        finally:
            # A global failure (or close) can leave eagerly-submitted
            # fetch futures behind; consume their exceptions so a
            # poisoned chain doesn't log 'Future exception was never
            # retrieved' for every orphaned wave.
            for item in inflight:
                item[1].add_done_callback(
                    lambda f: f.cancelled() or f.exception())

    def _record_finish_span(self, req, tokens: int,
                            finished: str) -> None:
        """One completed `generator.generate` span per finished
        generation — EVERY terminal path records it (eos/length AND
        deadline timeouts), because the timed-out request is exactly
        the one the flight recorder pins and must find decode-phase
        evidence for."""
        if req.trace_id is None:
            return
        from kfserving_tpu.tracing import Span, tracer

        duration_s = max(0.0, time.perf_counter() - req.submit_t)
        tracer.record(Span(
            req.trace_id, "generator.generate",
            time.time() - duration_s, duration_s * 1000.0,
            {"tokens": tokens, "finish_reason": finished}))

    def _finalize_cost(self, req: _Request, finished: str) -> None:
        """Fold the request's accumulated accounting into ONE cost
        record (observability/attribution.py): attributed device ms by
        phase, prefill/decode tokens, peak blocks held, cache-saved
        tokens.  Every terminal path calls this — eos/length AND
        timeout/cancel, because the timed-out request is exactly the
        one the flight recorder pins and must find cost evidence
        for."""
        device_ms = {
            "prefill": round(req.prefill_device_ms, 3),
            "decode": round(req.decode_device_ms, 3),
        }
        if self.spec_tokens:
            # Draft/verify REFINE the decode figure (same busy
            # intervals, finer phase) — consumers summing
            # prefill+decode across requests still reconcile against
            # engine device time.
            device_ms["spec_draft"] = round(req.spec_draft_ms, 3)
            device_ms["spec_verify"] = round(req.spec_verify_ms, 3)
        attribution.observe(self.name, req.trace_id, {
            "trace_id": req.trace_id,
            "finish_reason": finished,
            "device_ms": device_ms,
            "prefill_tokens": int(req.prompt_ids.size),
            "decode_tokens": req.tokens_out,
            "blocks_held": req.blocks_held,
            "cache_hit_blocks": req.cache_hit_blocks,
            "cache_saved_tokens": req.cache_saved_tokens,
            "host_tier_hit_blocks": req.host_tier_hit_blocks,
            "host_tier_saved_tokens": req.host_tier_saved_tokens,
        })

    def _expire_deadlines(self) -> None:
        """Between decode waves: requests whose budget ran out get a
        terminal "timeout" event and free their slot (active) or leave
        the queue (pending) — the engine never spends another wave on
        a request nobody is still waiting for."""
        for i, s in enumerate(self._slots):
            if s is not None and s.req.deadline is not None \
                    and s.req.deadline.expired:
                s.req.out.put_nowait((None, "timeout"))
                self._record_finish_span(s.req, s.generated, "timeout")
                self._finalize_cost(s.req, "timeout")
                self._free_slot_state(i)
                self.requests_finished += 1
        if any(r.deadline is not None and r.deadline.expired
               for r in self._pending):
            keep = deque()
            while self._pending:
                r = self._pending.popleft()
                if r.deadline is not None and r.deadline.expired:
                    r.out.put_nowait((None, "timeout"))
                    self._record_finish_span(r, 0, "timeout")
                    self._finalize_cost(r, "timeout")
                    self.requests_finished += 1
                else:
                    keep.append(r)
            self._pending = keep

    async def _run_pipeline(self, loop, inflight: deque):
        while not self._closed:
            self._expire_deadlines()
            admitted = False
            while (not self._growth_starved and self._pending
                   and self._free_slot() is not None):
                force_miss = await self._probe_prefix_fault()
                if self._is_cold(self._pending[0]):
                    # Cold long prompt: chunked admission — one slot,
                    # block-aligned chunks interleaving with decode
                    # waves (strict FIFO preserved: a cold request at
                    # the front is admitted, or blocks the queue on
                    # pool pressure exactly like a group plan would).
                    if not await self._admit_chunked(
                            loop, inflight, force_miss=force_miss):
                        break  # pool pressure: wait for frees
                    admitted = True
                    continue
                with TIMELINE.span(HOST, "engine.admit"):
                    group, slots, bucket, dest_rows = \
                        self._take_prefill_group(force_miss=force_miss)
                if not group:
                    break  # pool pressure: wait for frees
                if (self.kv_tier is not None
                        and self._faultback_pending
                        and not await loop.run_in_executor(
                            self._enqueue_executor,
                            self._drain_faultbacks)):
                    # Transactional fault-back failure (the
                    # `engine.kv_faultback` chaos site, or entries
                    # evicted between probe and read): nothing was
                    # dispatched — roll the whole group's plans back
                    # and re-queue the requests at the front.  Their
                    # replans MISS the tier (the failed chains were
                    # dropped) and fall through to plain re-prefill.
                    self._requeue_group(group, slots)
                    continue
                try:
                    firsts_h, lp_h, seq = await loop.run_in_executor(
                        self._enqueue_executor,
                        self._enqueue_prefill_group,
                        group, slots, bucket, dest_rows)
                except Exception as e:
                    if self._prefill_refused(
                            e, self._program_rows(group, bucket)):
                        self._requeue_group(group, slots)
                        continue
                    # An enqueue-time failure (e.g. OOM compiling a
                    # new bucket) fails THAT group; in-flight slots
                    # keep decoding.  Planned blocks release AND their
                    # provisional prefix registrations deregister —
                    # the blocks were never written, and leaking the
                    # refs/rows would shrink the pool while a stale
                    # chain entry could alias a later occupant's
                    # decode k/v (code-review r5).
                    logger.exception("prefill enqueue failed")
                    for req, slot in zip(group, slots):
                        req.out.put_nowait(
                            (None, f"error: prefill failed: {e}"))
                        self._deregister_plan(slot)
                        self._schedule_block_release(slot)
                    continue
                # Install slots NOW — the first tokens arrive at fetch
                # time, but the device feed arrays already carry them,
                # so the very next decode wave includes these slots.
                entries = []
                enqueued_t = time.perf_counter()
                for req, slot in zip(group, slots):
                    req.enqueued_t = enqueued_t
                    # The prefill is enqueued: this slot's provisional
                    # prefix registrations are backed by dispatched
                    # writes (even for a cancelled row — its blocks
                    # get written and released, staying shareable).
                    self._confirm_plan(slot)
                    if req.cancelled:
                        # Cancelled between submit and here: deliver
                        # the terminal event (cancel() saw it neither
                        # pending nor active) and never occupy a slot.
                        # Planned blocks release (deferred — the just-
                        # enqueued prefill still writes them).
                        req.out.put_nowait((None, "cancelled"))
                        self._finalize_cost(req, "cancelled")
                        self.requests_finished += 1
                        self._schedule_block_release(slot)
                        entries.append((slot, None, req.prefill_entry))
                        continue
                    act = _Active(req=req,
                                  length=req.prompt_ids.size,
                                  last_token=-1, generated=0)
                    self._slots[slot] = act
                    entries.append((slot, act, req.prefill_entry))
                # Eager fetch: the D2H round trip starts NOW and
                # overlaps other fetches; the FIFO await below keeps
                # delivery order.
                fut = loop.run_in_executor(
                    self._executor, self._fetch_joined, seq, "prefill",
                    self._fetch_wave, firsts_h, lp_h)
                inflight.append(("prefill", fut, (entries, bucket),
                                 time.perf_counter(), seq))
                admitted = True
            active = any(s is not None for s in self._slots)
            if not active and not inflight:
                # No zombie dispatches can exist with an empty
                # pipeline: release everything deferred now (otherwise
                # a fully-idle engine would strand blocks until the
                # next wave advanced the counter).
                self._process_deferred_frees(force=True)
                self._inflight.settle()
                # The HOLD's reason is gone with the pipeline empty
                # and the deferred frees landed; left set, it would
                # gate admissions while this branch `continue`s above
                # the only other reset — an await-free spin that
                # starves the event loop with the preempted request
                # parked in pending forever.
                self._set_hold(False)
                if not self._pending:
                    self._wakeup.clear()
                    if admitted:
                        continue
                    # The starved clock books this wait to `no_work`.
                    self._inflight.waiting(True)
                    try:
                        # Held across the await: the loop thread runs
                        # other tasks meanwhile, and the trace's
                        # reduction reads this interval by its name,
                        # not by what nests in it.
                        with TIMELINE.span(HOST, "engine.wait.request"):
                            await asyncio.wait_for(self._wakeup.wait(),
                                                   timeout=1.0)
                    except asyncio.TimeoutError:
                        if not self._pending and not any(
                                s is not None for s in self._slots):
                            # idle: let the loop die; resubmit restarts
                            # (and ends the wait)
                            return
                    self._inflight.waiting(False)
                continue
            # Paged mode: every active slot's table must cover the
            # positions the next pipeline_depth waves can reach.  A
            # slot the pool cannot grow is PREEMPTED, not failed: its
            # request re-queues with prompt = original + generated so
            # far (budget already consumed subtracted) and resumes
            # when blocks free — and because sampling noise is keyed
            # on (seed, absolute position), the resumed stream
            # produces EXACTLY the tokens the uninterrupted one would
            # have.  Only a request that could never fit again
            # (merged sequence exceeds the largest prefill bucket or
            # the whole pool) fails.
            # Mid-prefill slots: dispatch their next chunk into the
            # FIFO.  With live decode streams, ONE chunk in flight per
            # slot — the loop pops one FIFO item per iteration, so
            # chunks and waves alternate and a stream's stall is one
            # chunk's device time, not the whole prompt's.  With no
            # decodable streams there is nobody to stall: keep
            # pipeline_depth chunks in flight so the fetch RTT hides
            # behind the next chunk's compute.  This runs BEFORE the
            # growth pass: a slot whose FINAL chunk lands here becomes
            # decodable, and its table must grow to the decode horizon
            # before this same iteration's wave top-up — a
            # block-aligned prompt's first decode write lands one
            # block past the plan, and a wave carrying the ungrown
            # table would drop it (a cache hole, not a crash).
            decodable_now = any(s is not None and not s.prefilling
                                for s in self._slots)
            chunk_limit = 1 if decodable_now else max(
                2, self.pipeline_depth)
            for slot_i, s in enumerate(list(self._slots)):
                if (s is None or not s.prefilling
                        or self._slots[slot_i] is not s):
                    continue
                while (s.prefilling and s.chunks_inflight < chunk_limit
                       and s.chunk_next < s.chunk_total
                       and self._slots[slot_i] is s):
                    await self._step_chunk(loop, inflight, slot_i, s)
            with TIMELINE.span(HOST, "engine.grow"):
                failed = self._ensure_block_capacity()
            held = False
            if failed:
                # Pool pressure: cold prompts MID-CHUNKED-PREFILL
                # yield their blocks before any live stream is
                # re-prefilled — a prefilling slot has produced
                # nothing yet, so its restart is free (nothing was
                # sampled; a later re-admission replays the same
                # chunks bit-exactly, prefix-skipping the ones whose
                # blocks were registered before preemption), and the
                # freed blocks go to streams that already hold
                # context.
                preempted_prefill = False
                for i, s in enumerate(self._slots):
                    if s is not None and s.prefilling:
                        self._free_slot_state(i)
                        self._pending.appendleft(s.req)
                        self.preemptions += 1
                        preempted_prefill = True
                        TIMELINE.record(
                            "host", "preempt",
                            trace_id=s.req.trace_id, slot=i,
                            attrs={"phase": "prefill"})
                if preempted_prefill or self._deferred_frees:
                    # Blocks are already on their way back (a yield
                    # above, or frees maturing through the zombie-
                    # deferral window): HOLD the failing streams — no
                    # admissions, no new waves — until they land,
                    # instead of preempting streams that hold context
                    # (preempting both sides just re-creates the same
                    # over-committed pool: the ping-pong livelock the
                    # first cut of this path had).
                    held = True
                    failed = []
            self._set_hold(held)
            for i in failed:
                s = self._slots[i]
                if s is None:
                    continue
                merged_len = int(s.req.prompt_ids.size) + len(s.tokens)
                blocks_needed = -(-merged_len // self.block_size)
                # A merged sequence past the largest prefill bucket
                # still resumes when the chunked path can carry it.
                fits = (merged_len <= self.prefill_buckets[-1]
                        or (self.prefill_chunk_tokens is not None
                            and merged_len > self.prefill_chunk_tokens))
                if (not fits or blocks_needed > self.num_blocks
                        or s.req.max_new_tokens - s.generated < 1):
                    s.req.out.put_nowait(
                        (None, "error: kv cache pool exhausted"))
                    self._free_slot_state(i)
                    continue
                s.req.prompt_ids = np.concatenate(
                    [s.req.prompt_ids,
                     np.asarray(s.tokens, np.int32)])
                s.req.max_new_tokens -= s.generated
                self._free_slot_state(i)
                # Front of the queue: a preempted stream resumes
                # before new arrivals take its blocks.
                self._pending.appendleft(s.req)
                self.preemptions += 1
                TIMELINE.record("host", "preempt",
                                trace_id=s.req.trace_id, slot=i,
                                attrs={"phase": "decode"})
            # Keep the device pipeline_depth decode waves deep: wave
            # N+1's feed tokens are wave N's device outputs — no host
            # round trip sits between waves, so the fetch of wave N
            # below overlaps wave N+1's execution.  Prefill/chunk
            # items don't count toward depth (they are admission work
            # riding the same FIFO).
            decodable = [] if held else [
                s for s in self._slots
                if s is not None and not s.prefilling]
            waves = sum(1 for it in inflight
                        if it[0] in ("decode", "spec"))
            if self.spec_tokens > 0 and decodable and waves == 0:
                # Speculative mode runs depth-1: spec waves are
                # host-fed (the proposer needs each slot's committed
                # history), so wave N+1 cannot chain off wave N's
                # device feed — it waits for N's verdicts.  The
                # throughput lever here is K+1 tokens per dispatch,
                # not dispatch overlap; the adaptive-depth governor
                # has nothing to govern at depth 1.
                await self._spec_or_fallback_wave(loop, inflight)
                waves = 1
            elif self.spec_tokens == 0:
                while decodable and waves < self.pipeline_depth:
                    if (self.adaptive_depth and waves >= 1 and all(
                            s.req.max_new_tokens - s.generated
                            <= waves * self.steps_per_call
                            for s in decodable)):
                        # Adaptive depth: every active stream finishes
                        # (by token budget) within the waves already
                        # in flight — a speculative wave here could
                        # only decode garbage (the fixed-depth-2
                        # failure mode when finishes cluster).
                        # Staggered traffic keeps remaining
                        # work past the horizon and still gets the
                        # full configured depth.
                        self.suppressed_waves += 1
                        obs.generator_suppressed_waves_total().inc()
                        TIMELINE.record("host", "wave.suppressed")
                        break
                    kind_, toks_h, lp_h, snap, t0_, seq = \
                        await loop.run_in_executor(
                            self._enqueue_executor, self._enqueue_wave)
                    fut = loop.run_in_executor(
                        self._executor, self._fetch_joined, seq, kind_,
                        self._fetch_wave, toks_h, lp_h)
                    inflight.append((kind_, fut, snap, t0_, seq))
                    waves += 1
            if decodable and waves != self._depth_effective:
                self._depth_effective = waves
                obs.generator_pipeline_depth().set(waves)
            if not inflight:
                # Growth-starved drain reached an empty pipeline: no
                # zombie dispatch can exist, so the yielded blocks are
                # safe to release NOW — the held streams' growth retry
                # succeeds next iteration.
                self._process_deferred_frees(force=True)
                continue
            kind, fut, meta, t0, seq = inflight.popleft()
            t_await = time.perf_counter()
            try:
                # Held across the await, like engine.wait.request.
                with TIMELINE.span(HOST, "engine.wait.fetch", seq=seq):
                    fetched, lp, fetched_t = await fut
                # Host-blocked time is the LOOP-side await, not the
                # worker's span: eager fetches overlap on the worker
                # pool and their spans cover whole-wave latency — the
                # sum would exceed wall clock and lie in A/Bs.
                taken_up = time.perf_counter()
                wait_s = taken_up - t_await
                # From the fetch's return on its worker to here: what
                # the loop, not the device, added to the round trip.
                obs.generator_deliver_lag_ms().observe(
                    (taken_up - fetched_t) * 1000.0)
            except Exception as e:
                if kind == "prefill":
                    # Fail THAT group; in-flight slots keep decoding.
                    # (If the poisoned cache chain breaks later waves,
                    # their fetch error still fails everything.)
                    logger.exception("prefill failed")
                    for slot, act, _ in meta[0]:
                        if act is not None and \
                                self._slots[slot] is act:
                            self._free_slot_state(slot)
                            act.req.out.put_nowait(
                                (None, f"error: prefill failed: {e}"))
                    continue
                if kind == "chunk":
                    slot, act, _idx, _final = meta
                    act.chunks_inflight -= 1
                    logger.exception("chunk prefill failed")
                    if self._slots[slot] is act:
                        self._free_slot_state(slot)
                        act.req.out.put_nowait(
                            (None, f"error: prefill failed: {e}"))
                    continue
                raise
            # Union of busy intervals, NOT per-item spans: at depth>=2
            # the spans of consecutive items overlap, and summing them
            # would exceed wall clock (making depth A/Bs lie).
            now = time.perf_counter()
            busy = now - max(t0, self._last_fetch_done)
            self._last_fetch_done = now
            # The device's time for this program: fetches return in the
            # device's order, each when its program has run.
            took_s = max(0.0, fetched_t - max(t0, self._last_fetched_t))
            self._last_fetched_t = max(fetched_t, self._last_fetched_t)
            # Device-path timeline: one device-track slice per fetched
            # dispatch (the dispatch->fetch busy interval — the same
            # overlap-corrected span the device_s stats accumulate, so
            # the Perfetto view and the committed stats agree), plus
            # per-slot slices carrying each stream's trace id and a
            # pool-occupancy counter sample.
            wall = time.time()
            dev_dur = max(0.0, busy)
            if kind == "spec":
                self._decode_device_s += busy
                self._decode_wait_s += wait_s
                samples, draft, draft_ready_s = fetched
                entries, host_draft_ms = meta
                if self._spec_draft_fn is not None:
                    # The draft program completes before verify in
                    # device order (verify consumes its output), so
                    # the draft handle's ready time splits the busy
                    # interval into draft / verify device slices.
                    draft_ms = min(max(draft_ready_s, 0.0),
                                   dev_dur) * 1000.0
                    TIMELINE.record(
                        "device", "spec.draft",
                        dur_s=draft_ms / 1000.0, t_end=wall,
                        attrs={"k": self.spec_tokens})
                else:
                    # n-gram proposals are host work measured at
                    # proposal time; the whole device interval is
                    # verify.
                    draft_ms = host_draft_ms
                    TIMELINE.record(
                        "host", "spec.draft",
                        dur_s=draft_ms / 1000.0, t_end=wall,
                        attrs={"k": self.spec_tokens})
                verify_ms = dev_dur * 1000.0
                if self._spec_draft_fn is not None:
                    verify_ms = max(0.0, verify_ms - draft_ms)
                TIMELINE.record(
                    "device", "spec.verify",
                    dur_s=verify_ms / 1000.0, t_end=wall,
                    attrs={"k": self.spec_tokens,
                           "rows": len(entries),
                           "wait_ms": round(wait_s * 1000.0, 3)})
                for slot_i, s in entries:
                    if self._slots[slot_i] is s:
                        TIMELINE.record("slot", "spec.decode",
                                        dur_s=dev_dur, t_end=wall,
                                        trace_id=s.req.trace_id,
                                        slot=slot_i)
                self._record_pool_sample()
                with TIMELINE.span(HOST, "engine.deliver",
                                   rows=len(entries),
                                   steps=self.spec_tokens + 1):
                    self._distribute_spec(samples, draft, lp, entries,
                                          device_ms=dev_dur * 1000.0,
                                          draft_ms=draft_ms,
                                          verify_ms=verify_ms)
            elif kind == "decode":
                self._decode_device_s += busy
                self._decode_wait_s += wait_s
                TIMELINE.record(
                    "device", "decode.wave", dur_s=dev_dur, t_end=wall,
                    attrs={"steps": self.steps_per_call,
                           "wait_ms": round(wait_s * 1000.0, 3)})
                for slot_i, s in enumerate(meta):
                    if s is not None and self._slots[slot_i] is s:
                        TIMELINE.record("slot", "decode",
                                        dur_s=dev_dur, t_end=wall,
                                        trace_id=s.req.trace_id,
                                        slot=slot_i)
                self._record_pool_sample()
                with TIMELINE.span(HOST, "engine.deliver",
                                   rows=sum(s is not None for s in meta),
                                   steps=self.steps_per_call):
                    self._distribute(fetched, lp, meta,
                                     device_ms=dev_dur * 1000.0)
            elif kind == "chunk":
                self._prefill_device_s += busy
                self._prefill_wait_s += wait_s
                # The stall THIS chunk inserted between decode
                # fetches — the per-chunk slice of what a monolithic
                # prefill would have injected all at once.
                obs.generator_prefill_chunk_stall_ms().observe(
                    busy * 1000.0)
                slot, act, _idx, final = meta
                TIMELINE.record(
                    "device", "prefill.chunk", dur_s=dev_dur,
                    t_end=wall, trace_id=act.req.trace_id, slot=slot,
                    attrs={"chunk": _idx, "final": final})
                TIMELINE.record("slot", "prefill.chunk",
                                dur_s=dev_dur, t_end=wall,
                                trace_id=act.req.trace_id, slot=slot,
                                attrs={"chunk": _idx})
                act.chunks_inflight -= 1
                # A chunk dispatch serves exactly one request: its
                # whole busy interval is that request's prefill cost.
                act.req.prefill_device_ms += dev_dur * 1000.0
                if final and self._slots[slot] is act:
                    # The final chunk carries the stream's first
                    # sampled token (the feed arrays got it at enqueue
                    # — intervening waves already decoded this slot;
                    # FIFO order delivers this token before theirs).
                    self.prefill_requests += 1
                    rec = _logprob_record(lp, act.req.logprobs, 0)
                    with TIMELINE.span(HOST, "engine.deliver",
                                       trace_id=act.req.trace_id,
                                       slot=slot, rows=1):
                        self._emit(slot, int(fetched[0]), rec)
            else:
                self._prefill_device_s += busy
                self._prefill_wait_s += wait_s
                meta, bucket = meta
                per_row = self._row_entries[bucket]
                self._note_prefill_took(len(fetched) // per_row, bucket,
                                        took_s)
                TIMELINE.record(
                    "device", "prefill.bucket", dur_s=dev_dur,
                    t_end=wall, attrs={"batch": len(meta)})
                for slot_i, act, _ in meta:
                    if act is not None and self._slots[slot_i] is act:
                        TIMELINE.record("slot", "prefill",
                                        dur_s=dev_dur, t_end=wall,
                                        trace_id=act.req.trace_id,
                                        slot=slot_i)
                with TIMELINE.span(HOST, "engine.deliver",
                                   rows=len(meta)):
                    self._finish_prefill(fetched, lp, meta, per_row,
                                         device_ms=dev_dur * 1000.0)
            self._process_deferred_frees()

    def _note_prefill_took(self, rows: int, bucket: int,
                           seconds: float) -> None:
        """One fetched dispatch of the (rows, bucket) prefill program
        took `seconds` on the device.  The first of a program compiled
        inside its launch: it opens the record and is not kept.  Eight
        are: enough for a median that one late fetch does not move."""
        took = self._prefill_took_s.get((rows, bucket))
        if took is None:
            self._prefill_took_s[rows, bucket] = deque(maxlen=8)
        else:
            took.append(seconds)

    def _finish_prefill(self, firsts: np.ndarray, lp, entries,
                        per_row: int, device_ms: float = 0.0):
        """Deliver a fetched prefill batch's first tokens: `firsts` has
        `per_row` entries a row of the program, and `entries` names the
        one (slot, request, entry) each prompt was read at.  A slot
        whose _Active was replaced since enqueue (cancel) discards its
        entry, exactly like _distribute."""
        self.prefills += 1
        rows = len(firsts) // per_row
        # The rows that no prompt lies in.
        padded = rows - len({at // per_row for _, _, at in entries})
        self.prefill_rows_dispatched += rows
        self.prefill_rows_padded += padded
        obs.engine_prefill_rows_total().labels(
            model=self.name).inc(rows)
        obs.engine_prefill_rows_padded_total().labels(
            model=self.name).inc(padded)
        # Even split of the bucket dispatch across the prompts whose
        # cost records are still OPEN (slot unchanged since enqueue).  A
        # cancelled one's record was finalized at cancel time —
        # mutating it would be lost work — so its computed prompt's
        # share redistributes onto the survivors of the same dispatch:
        # device time stays conserved across stored records.
        live = [(slot, act, at) for slot, act, at in entries
                if act is not None and self._slots[slot] is act]
        share_ms = device_ms / len(live) if live else 0.0
        for slot, act, at in live:
            act.req.prefill_device_ms += share_ms
            self.prefill_requests += 1
            self._emit(slot, int(firsts[at]),
                       _logprob_record(lp, act.req.logprobs, at))

    def _note_program(self, kind: str, *signature) -> None:
        """Record one dispatched program shape (enqueue-executor
        thread only).  The first sighting per (kind, signature) flows
        to compile_cache.note_compilation — post-warmup sightings are
        KFS_SANITIZE recompile violations; off, this is a set probe."""
        key = (kind,) + signature
        if key not in self._dispatched_programs:
            self._dispatched_programs.add(key)
            compile_cache.note_compilation(self.sanitize_source, key)

    @_dispatch_timed("decode")
    def _enqueue_wave(self):
        """Dispatch one K-step decode wave (non-blocking: JAX async
        dispatch).  Consumes the device-resident caches + feed arrays
        and replaces them with the wave's output handles."""
        jnp = self._jnp
        # Slot growth for this wave may have evicted spill-pending
        # blocks the wave's decode writes will rewrite: gather first.
        self._drain_spills()
        with mesh_scope(self.mesh):
            with TIMELINE.span(LAUNCH, "engine.prep.decode"):
                self._note_program("decode", self.max_slots,
                                   self.steps_per_call)
                temps, top_ks, top_ps, seeds, want_lp = \
                    self._sampling_arrays()
                table = self._table_device()
                per_slot = [jnp.asarray(a)
                            for a in (self._stop_positions(), temps,
                                      top_ks, top_ps, seeds,
                                      self._tail_asked("decode", temps,
                                                       want_lp))]
            with self._inflight.launch(
                    "decode", rows=self.max_slots,
                    steps=self.steps_per_call) as launched:
                out = self._decode(
                    self.variables, self._caches, table,
                    self._feed_tokens, self._feed_positions, *per_slot)
                (toks, self._caches, self._feed_tokens,
                 self._feed_positions, chosen_lp, top_ids,
                 top_lps) = out[:7]
                if self._moe is not None:
                    self._moe.note(
                        "decode", out[7], layer_steps=(
                            self.steps_per_call
                            * out[7]["pairs"].shape[0]))
        lp_h = (chosen_lp, top_ids, top_lps) if want_lp else None
        self.decode_steps += 1
        # Snapshot records mid-chunked-prefill slots as None: this
        # wave reads their PARKED feed row (out-of-range sentinel —
        # writes drop, tokens are garbage by design).  The flag on the
        # live _Active can flip to decodable before this wave's fetch
        # lands, so the decision must be frozen at enqueue.
        snapshot = [None if (s is not None and s.prefilling) else s
                    for s in self._slots]
        return ("decode", toks, lp_h, snapshot,
                time.perf_counter(), launched.seq)

    def _fetch_joined(self, seq: int, program: str, fetch, *handles):
        """Runs on a fetch worker: `fetch` (`_fetch_wave` or
        `_fetch_spec`) under the in-flight table's `engine.fetch` span,
        which carries the launch's `seq`; the row retires when the
        fetch returns, however it returns.  Gives (fetched, lp, the
        fetch's return on this worker's clock): deliver lag runs from
        there."""
        with self._inflight.fetch(seq, program) as joined:
            fetched, lp = fetch(*handles)
        return fetched, lp, joined.done_t

    def _fetch_wave(self, toks_h, lp_h):
        """Runs on the executor thread: the D2H fetch that joins the
        device timeline (block_until_ready on this transport acks the
        dispatch without joining — only the fetch truly waits).
        Returns (tokens, lp); the caller attributes the wait to decode
        or prefill (this path serves both kinds)."""
        # THE sanctioned generation fetch: the one place device
        # handles become host arrays, on the fetch executor.
        with sanitizer.sanctioned_fetch():
            # kfslint: disable=host-sync — sanctioned fetch site: the
            # wave's D2H join, off-loop on the fetch executor.
            tokens = np.asarray(toks_h)
            lp = None
            if lp_h is not None:
                # kfslint: disable=host-sync — sanctioned fetch site:
                # logprob handles fetched beside their wave's tokens.
                lp = tuple(np.asarray(h) for h in lp_h)
            if self._moe is not None:
                self._moe.drain()
        return tokens, lp

    @_dispatch_timed("prefill")
    def _enqueue_prefill_group(self, group: List[_Request],
                               slots: List[int],
                               bucket: int,
                               dest_rows: List[List[int]]):
        """Runs on the enqueue executor: dispatch one bucket-padded
        prefill for the WHOLE group (a burst of arrivals rides one
        dispatch), chain the cache insert and the device-feed scatter
        off it, and return the first-token handles WITHOUT any host
        sync — prompt ingestion rides the same in-flight pipeline as
        decode waves, so admissions no longer stall live streams by a
        full prefill dispatch.  Each prompt lies where
        `_take_prefill_group` laid it (`prefill_entry`): in a row of
        its own from the row's first column, or, where the program
        packs, from one of the row's block boundaries, behind the
        prompts that share the row (`programs.build`'s `prefill_fn`
        says what the program is then told).  The rows pad to a pow2
        row bucket so compile count stays bounded (where
        `_take_prefill_group` cuts a run at a power of two of rows
        nothing pads); the per-prompt arrays have an entry for every
        place a prompt could start, and those where none does, a
        padding row's among them, carry an out-of-bounds slot sentinel
        the scatters drop."""
        # This group's plans may have evicted spill-pending blocks the
        # insert below will rewrite: gather first.
        self._drain_spills()
        jnp = self._jnp
        b = len(group)
        bs = self.block_size
        per_row = self._row_entries[bucket]
        b_bucket = 1 << (self._program_rows(group, bucket) - 1).bit_length()
        entries = b_bucket * per_row
        # A prompt's row, and the column it starts at.
        places = [(req.prefill_entry // per_row,
                   req.prefill_entry % per_row * bs) for req in group]
        with mesh_scope(self.mesh), \
                TIMELINE.span(LAUNCH, "engine.prep.prefill"):
            ids = np.zeros((b_bucket, bucket), np.int32)
            lengths = np.ones(entries, np.int32)  # no prompt: length 1
            temps = np.zeros(entries, np.float32)
            top_ks = np.zeros(entries, np.int32)
            top_ps = np.ones(entries, np.float32)
            seeds = np.zeros(entries, np.int32)
            slot_arr = np.full(entries, self.max_slots, np.int32)  # OOB
            want_lp = False
            packed = ()
            if per_row > 1:
                # What says where the prompts lie: a position's prompt
                # (numbered by the block it starts at; -1 padding), its
                # position in it, and each prompt's last column.
                segments = np.full((b_bucket, bucket), -1, np.int32)
                positions = np.zeros((b_bucket, bucket), np.int32)
                last = np.zeros((b_bucket, per_row), np.int32)
                packed = ((segments, positions, last),)
            for req, slot, (row, start) in zip(group, slots, places):
                n = req.prompt_ids.size
                ids[row, start:start + n] = req.prompt_ids
                at = req.prefill_entry
                lengths[at] = n
                temps[at] = req.temperature
                top_ks[at] = req.top_k
                top_ps[at] = req.top_p
                seeds[at] = req.seed
                slot_arr[at] = slot
                want_lp = want_lp or req.logprobs > 0
                if packed:
                    segments[row, start:start + n] = start // bs
                    positions[row, start:start + n] = np.arange(n)
                    last[row, start // bs] = start + n - 1
            # Roofline accounting: real-token FLOPs (2P matmul + causal
            # attention's triangular sum) and the bucket's token
            # padding — padded rows/positions burn device time without
            # FLOPs that count, which is exactly what the padding-waste
            # gauge shows.
            for req in group:
                n = int(req.prompt_ids.size)
                self._prefill_flops += (
                    self._flops_matmul_per_token * n
                    + self._attn_flops_coeff * self._attended(n, True))
            rec = self._prefill_bucket_tokens.setdefault(bucket,
                                                         [0.0, 0.0])
            rec[0] += sum(int(r.prompt_ids.size) for r in group)
            rec[1] += b_bucket * bucket
            self._note_program("prefill", b_bucket, bucket)
            ids_d, lengths_d = jnp.asarray(ids), jnp.asarray(lengths)
            packed_d = self._jax.tree.map(jnp.asarray, packed)
            sampling = [jnp.asarray(a)
                        for a in (temps, top_ks, top_ps, seeds,
                                  self._tail_asked("prefill", temps,
                                                   want_lp))]
        # The launch's ring event carries the trace ids of its rows,
        # so a request's spans share its identifier (the profiler's
        # annotation takes the scalars alone).
        with mesh_scope(self.mesh), \
                self._inflight.launch(
                    "prefill", rows=b, bucket=bucket,
                    trace_ids=[r.trace_id for r in group]) as launched:
            out = self._prefill(self.variables, ids_d, lengths_d,
                                *sampling, *packed_d)
            firsts, new_caches, chosen_lp, top_ids, top_lps = out[:5]
            if self._moe is not None:
                self._moe.note("prefill", out[5],
                               tokens=b_bucket * bucket)
        with TIMELINE.span(LAUNCH, "engine.prep.insert"):
            slot_d = jnp.asarray(slot_arr)
            # Per-chunk destination blocks (-1 = shared prefix hit, or
            # a chunk no prompt lies in — the scatter drops those): a
            # row's chunks name, one by one, a block of whichever
            # sequence lies there.
            chunks = bucket // bs
            dest = np.full((b_bucket, chunks), -1, np.int32)
            for (row, start), plan in zip(places, dest_rows):
                dest[row, start // bs:start // bs + len(plan)] = plan
            dest_d = jnp.asarray(dest)
            if self._ring is not None:
                # The rings take each prompt's last blocks alone, block
                # j into the one the plan tabled for it.
                ring = self._ring
                ring_dest = np.full((b_bucket, chunks), -1, np.int32)
                with self._block_lock:
                    for req, slot, (row, _) in zip(group, slots, places):
                        total = -(-int(req.prompt_ids.size) // bs)
                        for j in range(max(0, total - ring.columns),
                                       total):
                            ring_dest[row, j] = ring.at(slot, j)
                dest_d = (dest_d, jnp.asarray(ring_dest))
        with self._inflight.launch("insert", rows=b):
            self._caches = self._insert(self._caches, new_caches,
                                        dest_d, slot_d)
        # The admitted slots' first feed token/position land in the
        # device-resident feed arrays; rows of slots NOT in this group
        # keep their device values (the last enqueued wave's outputs,
        # which the host may not have seen yet).  The next decode wave
        # therefore includes these slots before the host ever sees
        # their first token.
        with self._inflight.launch("feed", rows=b):
            self._feed_tokens, self._feed_positions = \
                self._feed_update(
                    self._feed_tokens, self._feed_positions,
                    slot_d, firsts, lengths_d)
        if self._prefill_refusals:
            # Memory is that tight (see _prefill_refused): nothing else
            # is launched until the insert has consumed this dispatch's
            # k/v, so that two dispatches' outputs are never held at
            # once.
            with TIMELINE.span(LAUNCH, "engine.wait.insert"):
                self._jax.block_until_ready(self._caches)
        lp_h = (chosen_lp, top_ids, top_lps) if want_lp else None
        return firsts, lp_h, launched.seq

    def _sampling_arrays(self):
        """Per-slot sampling parameter arrays for a decode dispatch.
        Feed tokens/positions live on device (the previous wave's
        outputs); only the sampling knobs come from host state."""
        S = self.max_slots
        temps = np.zeros(S, np.float32)
        top_ks = np.zeros(S, np.int32)
        top_ps = np.ones(S, np.float32)
        seeds = np.zeros(S, np.int32)
        want_lp = False
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            temps[i] = s.req.temperature
            top_ks[i] = s.req.top_k
            top_ps[i] = s.req.top_p
            seeds[i] = s.req.seed
            want_lp = want_lp or s.req.logprobs > 0
        return temps, top_ks, top_ps, seeds, want_lp

    def _tail_asked(self, program: str, temps: np.ndarray,
                    want_lp: bool) -> np.bool_:
        """`want_lp` as a dispatch's program takes it, the dispatch
        counted by what its rows ask of the sampler's tail
        (engine/programs.py `sample`, `logprob_of`): noise where a row
        has a temperature, which the program reads off `temps` itself,
        and log-probabilities where a row asked, which it is told here
        by the predicate its caller hands `lp_h` on by."""
        obs.engine_sampler_tail_calls_total().labels(
            model=self.name, program=program,
            noise=str(int(bool((temps > 0.0).any()))),
            logprobs=str(int(want_lp))).inc()
        return np.bool_(want_lp)

    def _stop_positions(self) -> np.ndarray:
        """[max_slots] int32: the feed position at which a decodable
        slot's request owes no further token (`decode_fn` parks the row
        from there).  The prefill gave the first of the budget's tokens
        and left the feed at the prompt's length, and every decode step
        gives one more, so the step at prompt + budget - 2 gives the
        last.  From the request's constants alone, never from
        `generated`: the host lags the device by up to pipeline_depth
        waves.  A preempted request resumes with its tokens merged into
        its prompt and taken off its budget, so its stop is what it
        was.  0, which no position is below, for a free slot and for
        one mid-chunked-prefill."""
        stops = np.zeros(self.max_slots, np.int32)
        for i, s in enumerate(self._slots):
            if s is not None and not s.prefilling:
                stops[i] = (s.req.prompt_ids.size
                            + s.req.max_new_tokens - 1)
        return stops

    def _emit(self, slot: int, token: int, lp_rec=None):
        """Account a newly produced token for `slot` and deliver it (or
        the finish marker) to the request's stream.

        Invariant: `length` counts tokens whose k/v are IN the cache;
        `last_token` is the token the next decode step feeds at
        position `length`.  The produced token's k/v are NOT in the
        cache yet — the step that consumes it writes them (so this
        method never touches `length`)."""
        s = self._slots[slot]
        s.generated += 1
        s.req.tokens_out += 1
        self.tokens_generated += 1
        obs.llm_tokens_total().labels(direction="out").inc()
        # Generation latency series: first emission is TTFT, later
        # ones inter-token gaps; both carry the request's trace id as
        # an exemplar so a slow tail links straight to its trace.
        now = time.perf_counter()
        if s.req.last_emit_t is None:
            req = s.req
            obs.llm_ttft_ms().observe(
                (now - req.submit_t) * 1000.0, trace_id=req.trace_id)
            # The same interval split at its two hand-offs; once per
            # request, as above (a preempted request that had emitted
            # keeps its last_emit_t through the re-admission).
            stages = obs.generator_ttft_stage_ms()
            stages.labels(stage="queued").observe(
                (req.taken_t - req.submit_t) * 1000.0)
            stages.labels(stage="dispatch").observe(
                (req.enqueued_t - req.taken_t) * 1000.0)
            stages.labels(stage="delivery").observe(
                (now - req.enqueued_t) * 1000.0)
        else:
            obs.llm_inter_token_ms().observe(
                (now - s.req.last_emit_t) * 1000.0,
                trace_id=s.req.trace_id)
        s.req.last_emit_t = now
        finished = None
        if self.eos_id is not None and token == self.eos_id:
            finished = "eos"
        elif s.generated >= s.req.max_new_tokens:
            finished = "length"
        if finished == "eos":
            # EOS is a stop signal, not content.
            s.req.out.put_nowait((None, "eos"))
        else:
            if lp_rec is not None:
                # Records align 1:1 with CONTENT tokens (an EOS stop
                # delivers no token, so it records no logprob).
                s.req.lp_chosen.append(lp_rec[0])
                s.req.lp_top.append(lp_rec[1])
            s.tokens.append(token)
            s.req.out.put_nowait((token, finished))
        if finished is not None:
            s.finished = finished
            duration_s = now - s.req.submit_t
            if duration_s > 0:
                obs.llm_tokens_per_second().observe(
                    s.generated / duration_s,
                    trace_id=s.req.trace_id)
            self._record_finish_span(s.req, s.generated, finished)
            self._finalize_cost(s.req, finished)
            self._free_slot_state(slot)
            self.requests_finished += 1
        else:
            s.last_token = token

    def _attended(self, n: int, prefill: bool = False):
        """Key rows a K/V layer reads for a query at context `n`, on
        average over the model's K/V layers (a sliding-window layer
        reads min(n, window)); with `prefill`, summed over a prompt's
        queries 1..n.  `n` itself, or the triangle, for a model without
        window layers."""
        whole = n * (n + 1) / 2.0 if prefill else n
        if self._window is None:
            return whole
        w = min(n, self._window)
        seen = w * (w + 1) / 2.0 + (n - w) * w if prefill else w
        share = self._window_layer_share
        return (1.0 - share) * whole + share * seen

    def _distribute(self, tokens: np.ndarray, lp, snapshot,
                    device_ms: float = 0.0):
        """tokens [S, K]: deliver each slot's chunk in order.  A slot
        only consumes its row if the SAME _Active object that was
        in the slot at enqueue time is still there — a slot freed (or
        freed-and-readmitted) between enqueue and fetch was decoding
        garbage for this wave, and its row is discarded (that waste is
        the pipelining trade; counted in wasted_token_steps).  A slot
        finishing mid-chunk discards its remaining positions — at most
        K-1 steps of waste.  Where the request ended by its token
        budget, the device had those steps' row parked
        (`_stop_positions`): parked_token_steps counts them too."""
        k = tokens.shape[1]
        self._token_steps += k
        starts = []  # each live row's context when the wave began
        ran = []     # and how many of the wave's steps its budget had left
        # Even split of the wave's busy interval across the live
        # streams it decoded: the per-request decode cost sums to the
        # engine's device time (additive attribution), and garbage
        # rows (freed slots) are excluded — their waste already shows
        # in goodput_ratio.
        live = sum(1 for i, s in enumerate(snapshot)
                   if s is not None and self._slots[i] is s)
        share_ms = device_ms / live if live else 0.0
        for i, s in enumerate(snapshot):
            if s is None:
                continue
            if self._slots[i] is not s:
                # Freed (EOS/budget/cancel) after this wave was
                # enqueued: the device decoded K garbage steps for it.
                self._wasted_token_steps += k
                if s.finished == "length":
                    self._parked_token_steps += k
                continue
            self._occupied_slot_steps += k
            s.req.decode_device_ms += share_ms
            s.req.blocks_held = max(
                s.req.blocks_held,
                -(-int(s.length) // self.block_size))
            # Roofline accounting over LIVE rows: matmul FLOPs per fed
            # token plus attention over the slot's resident context
            # (length at wave start — within a K-step wave the drift
            # is < K positions, noise against the ±10% stats bar).
            self._decode_flops += k * (self._flops_matmul_per_token
                                       + self._attn_flops_coeff
                                       * self._attended(s.length))
            starts.append(s.length)
            ran.append(min(k, s.req.max_new_tokens - s.generated))
            n_lp = s.req.logprobs
            for j in range(k):
                if self._slots[i] is not s:
                    # Finished mid-chunk: remaining positions wasted.
                    self._wasted_token_steps += k - j
                    if s.finished == "length":
                        self._parked_token_steps += k - j
                    break
                # Each scanned step wrote the fed token's k/v at the
                # slot's position: the cache grew by one per step.
                s.length += 1
                self._emit(i, int(tokens[i, j]),
                           _logprob_record(lp, n_lp, (i, j)))
        if starts:
            # Step i of the wave attends over L + i + 1 tokens of a row
            # that began it with L, in ceil of that over block_size
            # blocks, as far as the row's budget ran: past it the row
            # is parked and walks nothing, and short of it the device
            # walks on whether or not an EOS ended the row before.
            context = (np.asarray(starts, np.int64)[:, None]
                       + np.arange(1, k + 1))
            context = context[np.arange(k) < np.asarray(ran)[:, None]]
            row_blocks = -(-context // self.block_size)
            for pool, chunk in zip(self._pools, self._walk_chunks):
                # A window layer reads min(context, window) rows, in the
                # columns of its ring that the sequence has reached.
                read = (context if pool is self._pool
                        else np.minimum(context, self._window))
                columns = np.minimum(row_blocks, pool.columns)
                walked = (int(read.sum()), int(columns.sum()),
                          int((-(-columns // chunk)).sum()))
                self._walked[pool.name] += walked
                counted = [(_WALKED, {})] if pool is self._pool else []
                if self._names_pools:
                    counted.append((_WALKED_BY_POOL, {"pool": pool.name}))
                for families, labels in counted:
                    for family, n in zip(families, walked):
                        family().labels(model=self.name, **labels).inc(n)
            # Decode reads every live slot's resident KV plus the full
            # parameter set once per token step — the bandwidth-bound
            # working set the HBM-utilization gauge divides by peak.
            self._decode_hbm_bytes += k * (
                self._param_read_bytes
                + sum(self._attended(n) for n in starts)
                * self._kv_bytes_per_token)

    # -- speculative decoding ----------------------------------------------
    async def _spec_or_fallback_wave(self, loop, inflight) -> None:
        """Enqueue exactly one wave in speculative mode: a draft/verify
        spec wave over the host-feedable slots, or a plain resynced
        decode wave when chaos trips a spec fault site or no slot has
        a host-visible last token yet (a monolithic prefill's first
        token can still be in the FIFO — its device feed row is
        correct, so the plain wave decodes it; the slot joins spec
        waves once the fetch lands).  Either way the OUTPUT tokens are
        bit-identical to non-speculative decode — only the dispatch
        shape differs."""
        eligible = [(i, s) for i, s in enumerate(self._slots)
                    if s is not None and not s.prefilling
                    and s.last_token >= 0]
        fall_site = await self._probe_spec_fault() if eligible else None
        if eligible and fall_site is None:
            ngram = None
            windows = None
            host_ms = 0.0
            if self._spec_draft_fn is not None:
                windows = self._build_draft_windows(eligible)
            else:
                ngram, host_ms = self._propose_ngram(eligible)
            kind_, handles, lp_h, meta_, t0_, seq = \
                await loop.run_in_executor(
                    self._enqueue_executor, self._enqueue_spec_wave,
                    eligible, ngram, windows, host_ms)
            fut = loop.run_in_executor(
                self._executor, self._fetch_joined, seq, kind_,
                self._fetch_spec, handles, lp_h)
            inflight.append((kind_, fut, meta_, t0_, seq))
            return
        if fall_site is not None:
            self._count_spec_fallback(fall_site)
        kind_, toks_h, lp_h, snap, t0_, seq = await loop.run_in_executor(
            self._enqueue_executor, self._enqueue_resynced_wave)
        fut = loop.run_in_executor(
            self._executor, self._fetch_joined, seq, kind_,
            self._fetch_wave, toks_h, lp_h)
        inflight.append((kind_, fut, snap, t0_, seq))

    async def _probe_spec_fault(self) -> Optional[str]:
        """Chaos seams of the speculative path, probed ON the loop
        (async injected latency never blocks the scheduler).  An
        injected error on either seam degrades THIS wave to plain
        non-speculative decode — same tokens, fewer per dispatch.
        configured() keeps the no-faults hot path at two dict
        lookups."""
        if faults.configured(fault_sites.ENGINE_SPEC_DRAFT):
            try:
                await faults.inject(fault_sites.ENGINE_SPEC_DRAFT,
                                    key=self.name)
            except FaultInjected:
                return "draft"
        if faults.configured(fault_sites.ENGINE_SPEC_VERIFY):
            try:
                await faults.inject(fault_sites.ENGINE_SPEC_VERIFY,
                                    key=self.name)
            except FaultInjected:
                return "verify"
        return None

    def _count_spec_fallback(self, site: str) -> None:
        self.spec_fallbacks[site] = \
            self.spec_fallbacks.get(site, 0) + 1
        obs.specdec_fallbacks_total().labels(
            model=self.name, site=site).inc()

    def _spec_history(self, s: _Active) -> List[int]:
        """A slot's committed token stream: prompt + emitted content
        tokens (s.tokens ends with last_token — the _emit invariant),
        which is exactly the prefix the next sampled token extends."""
        return list(s.req.prompt_ids) + s.tokens

    def _propose_ngram(self, eligible) -> Tuple[np.ndarray, float]:
        """Host-side prompt-lookup proposals for the eligible rows.
        Runs on the loop thread: pure numpy/list scanning, no device
        work — its cost is measured and reported as the n-gram arm's
        draft overhead."""
        t0 = time.perf_counter()
        draft = np.zeros((self.max_slots, self.spec_tokens), np.int32)
        for i, s in eligible:
            draft[i] = self._ngram.propose(self._spec_history(s))
        return draft, (time.perf_counter() - t0) * 1000.0

    def _build_draft_windows(self, eligible) -> np.ndarray:
        from kfserving_tpu.engine.speculative import rolling_windows

        return rolling_windows(
            [self._spec_history(s) for _i, s in eligible],
            self.max_slots, [i for i, _s in eligible],
            self._draft_window)

    @_dispatch_timed("spec")
    def _enqueue_spec_wave(self, eligible, ngram, windows,
                           host_draft_ms):
        """Runs on the enqueue executor: dispatch the draft proposer
        (when a draft model is configured) and the K+1-position verify
        as ONE chained device program pair — the verify consumes the
        draft's output handle, so no host round trip separates them
        and the fetch below joins both.  Rows not in `eligible` park
        on the max_seq position sentinel: their writes drop (the
        out-of-range block index) and their samples are discarded."""
        jnp = self._jnp
        self._drain_spills()
        S = self.max_slots
        K = self.spec_tokens
        with TIMELINE.span(LAUNCH, "engine.prep.spec"):
            last = np.zeros(S, np.int32)
            qpos = np.full((S, K + 1), self.max_seq, np.int32)
            for i, s in eligible:
                last[i] = s.last_token
                qpos[i] = s.length + np.arange(K + 1, dtype=np.int32)
            temps, top_ks, top_ps, seeds, want_lp = \
                self._sampling_arrays()
            if windows is not None:
                self._note_program("spec_draft", S, self._draft_window)
                windows_d = jnp.asarray(windows)
            else:
                draft_dev = jnp.asarray(ngram)
        if windows is not None:
            with self._inflight.launch("spec_draft",
                                       rows=len(eligible)):
                draft_dev = self._spec_draft_fn(self.draft_variables,
                                                windows_d)
        with mesh_scope(self.mesh):
            with TIMELINE.span(LAUNCH, "engine.prep.spec"):
                self._note_program("spec_verify", S, K + 1)
                table = self._table_device()
                last_d, qpos_d = jnp.asarray(last), jnp.asarray(qpos)
                sampling = [jnp.asarray(a)
                            for a in (temps, top_ks, top_ps, seeds,
                                      self._tail_asked("spec", temps,
                                                       want_lp))]
            with self._inflight.launch(
                    "spec", rows=len(eligible), steps=K + 1) as launched:
                (samples, draft_echo, self._caches, chosen_lp, top_ids,
                 top_lps) = self._spec_verify(
                    self.variables, self._caches, table, last_d,
                    draft_dev, qpos_d, *sampling)
        self.decode_steps += 1
        self.spec_waves += 1
        lp_h = (chosen_lp, top_ids, top_lps) if want_lp else None
        return ("spec", (samples, draft_echo, windows is not None),
                lp_h, (list(eligible), host_draft_ms),
                time.perf_counter(), launched.seq)

    def _fetch_spec(self, handles, lp_h):
        """Runs on the fetch executor: join the spec wave's device
        work.  The draft handle is readied FIRST — the verify program
        consumes the draft's output, so draft-ready time is the
        draft/verify split point of the wave's busy interval (zero
        extra transfers: block_until_ready moves no data)."""
        samples_h, draft_h, timed_draft = handles
        t0 = time.perf_counter()
        with sanitizer.sanctioned_fetch():
            draft_ready_s = 0.0
            if timed_draft:
                # kfslint: disable=host-sync — sanctioned fetch site:
                # readiness probe that splits draft vs verify device
                # time; the verify fetch below is the real join.
                draft_h.block_until_ready()
                draft_ready_s = time.perf_counter() - t0
            # kfslint: disable=host-sync — sanctioned fetch site: the
            # spec wave's D2H join (verdicts + echoed proposals in one
            # round trip).
            samples = np.asarray(samples_h)
            draft = np.asarray(draft_h)
            lp = None
            if lp_h is not None:
                # kfslint: disable=host-sync — sanctioned fetch site:
                # logprob handles fetched beside their wave's tokens.
                lp = tuple(np.asarray(h) for h in lp_h)
        return (samples, draft, draft_ready_s), lp

    def _enqueue_resynced_wave(self):
        """Runs on the enqueue executor: re-sync the device feed
        arrays from host slot state, then dispatch a plain decode
        wave.  Spec waves are host-fed and do NOT maintain the
        device-resident feed chain, so a fallback to the plain wave
        path must first restore each feedable row (rows whose first
        token is still in the FIFO — last_token < 0 — keep the values
        the prefill enqueue scattered, which are already correct;
        parked/free rows keep their harmless stale values)."""
        jnp = self._jnp
        S = self.max_slots
        slot_arr = np.full(S, self.max_slots, np.int32)  # OOB: keep
        toks = np.zeros(S, np.int32)
        pos = np.zeros(S, np.int32)
        for i, s in enumerate(self._slots):
            if s is not None and not s.prefilling \
                    and s.last_token >= 0:
                slot_arr[i] = i
                toks[i] = s.last_token
                pos[i] = s.length
        self._note_program("feed_resync", S)
        with self._inflight.launch("feed", rows=S):
            self._feed_tokens, self._feed_positions = \
                self._feed_update(
                    self._feed_tokens, self._feed_positions,
                    jnp.asarray(slot_arr), jnp.asarray(toks),
                    jnp.asarray(pos))
        return self._enqueue_wave()

    def _distribute_spec(self, samples: np.ndarray,
                         draft: np.ndarray, lp, entries,
                         device_ms: float = 0.0,
                         draft_ms: float = 0.0,
                         verify_ms: float = 0.0):
        """samples/draft [S, K+1] / [S, K]: commit each live row's
        longest agreeing prefix.  Row acceptance a (1..K+1) counts the
        target's own draws that are safe to emit: draw j extends a
        prefix that is only correct if every earlier draft token
        matched, so emission stops at the first draft/target mismatch
        — the mismatching TARGET draw itself is still correct (it was
        sampled from the true prefix) and is emitted as position a-1.
        All-K agreement emits the K+1'th \"bonus\" draw the verify got
        for free.  No cache rollback: the host length pointer advances
        only over emitted positions, and later waves overwrite the
        rejected positions' k/v before any query can attend them."""
        K = self.spec_tokens
        kp1 = K + 1
        self._token_steps += kp1
        proposer = ("draft" if self._spec_draft_fn is not None
                    else "ngram")
        live = [(i, s) for i, s in entries if self._slots[i] is s]
        dead = len(entries) - len(live)
        if dead:
            # Freed (EOS/budget/cancel) after enqueue: the device
            # verified K+1 garbage positions for those rows.
            self._wasted_token_steps += dead * kp1
        share_ms = device_ms / len(live) if live else 0.0
        draft_share = draft_ms / len(live) if live else 0.0
        verify_share = verify_ms / len(live) if live else 0.0
        accepted_wave = 0
        resident_tokens = 0
        for i, s in live:
            a = 1
            while a <= K and int(draft[i, a - 1]) == \
                    int(samples[i, a - 1]):
                a += 1
            self.spec_proposed_tokens += K
            self.spec_accepted_tokens += a - 1
            accepted_wave += a - 1
            self._spec_lengths.append(a)
            obs.specdec_accepted_length_tokens().labels(
                model=self.name).observe(float(a))
            s.req.decode_device_ms += share_ms
            s.req.spec_draft_ms += draft_share
            s.req.spec_verify_ms += verify_share
            s.req.blocks_held = max(
                s.req.blocks_held,
                -(-int(s.length + a) // self.block_size))
            # Roofline over ACCEPTED tokens only: rejected positions
            # burn device time without useful FLOPs (that waste is the
            # acceptance-rate trade, visible in goodput_ratio).
            self._decode_flops += a * (self._flops_matmul_per_token
                                       + self._attn_flops_coeff
                                       * s.length)
            resident_tokens += s.length
            n_lp = s.req.logprobs
            emitted = 0
            for j in range(a):
                if self._slots[i] is not s:
                    # Finished (EOS/budget) mid-row: the rest of the
                    # agreeing prefix is past the stream's end.
                    break
                s.length += 1
                self._emit(i, int(samples[i, j]),
                           _logprob_record(lp, n_lp, (i, j)))
                emitted += 1
            self.spec_emitted_tokens += emitted
            self._occupied_slot_steps += emitted
            self._wasted_token_steps += kp1 - emitted
        if live:
            obs.specdec_proposed_tokens_total().labels(
                model=self.name, proposer=proposer).inc(len(live) * K)
            obs.specdec_accepted_tokens_total().labels(
                model=self.name, proposer=proposer).inc(accepted_wave)
            obs.specdec_draft_ms().labels(
                model=self.name, proposer=proposer).observe(draft_ms)
            if self.spec_proposed_tokens:
                obs.specdec_acceptance_ratio().labels(
                    model=self.name).set(
                        self.spec_accepted_tokens
                        / self.spec_proposed_tokens)
        self._spec_draft_s += draft_ms / 1000.0
        self._spec_verify_s += verify_ms / 1000.0
        if resident_tokens:
            # One parameter read serves all K+1 positions — the whole
            # point of speculation on a bandwidth-bound decode — while
            # each of the K+1 queries streams the resident KV.
            self._decode_hbm_bytes += (
                self._param_read_bytes
                + kp1 * resident_tokens * self._kv_bytes_per_token)

    def draft_param_bytes(self) -> int:
        """HBM ledger contribution of the configured draft model (0
        when speculation runs the n-gram head or is off)."""
        if self.draft_variables is None:
            return 0
        jax = self._jax
        return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
                   for x in jax.tree.leaves(self.draft_variables))


def _percentile(ordered: List[int], q: float) -> int:
    """The `q` quantile of `ordered` (sorted), 0 of none."""
    if not ordered:
        return 0
    return ordered[min(len(ordered) - 1, int(len(ordered) * q))]


def _logprob_record(lp, n: int, at):
    """`_emit`'s record of the fetched logprob arrays' entry `at` (a
    row, or (row, step)): (the chosen token's logprob, the top `n`
    (token, logprob)); None where the request asked for none."""
    if lp is None or n <= 0:
        return None
    return (float(lp[0][at]), [(int(t), float(p)) for t, p in
                               zip(lp[1][at][:n], lp[2][at][:n])])
