"""GenerativeModel: the decoder-serving predictor.

Extends the predictor plugin boundary (reference pkg/apis/serving/
v1beta1/predictor.go:33-59 — the reference's frameworks are all
request/response; generation is this framework's TPU-native addition)
with KV-cache incremental decoding and continuous batching
(engine/generator.py).

Model directory layout (the `storage_uri` artifact):

    config.json          — required; see GenerativeConfig
    checkpoint.msgpack   — flax.serialization blob (optional: absent ->
                           random init, which tests/benchmarks use)

config.json schema:
    {
      "architecture": "decoder" | "decoder_tiny"    # GPT-2 block
                    | "olmoe" | "olmoe_tiny"        # RoPE, RMSNorm,
                                                    #   QK-norm, SwiGLU,
                                                    #   64 routed experts
                                                    #   (models/olmoe.py)
                    | "nemotron_h" | "nemotron_h_tiny"  # Mamba-2 state
                                                    #   beside the pool,
                                                    #   GQA, a share of
                                                    #   relu² experts
                                                    #   (models/
                                                    #   nemotron_h.py)
                    | "mellum" | "mellum_tiny"      # sliding-window
                                                    #   layers beside
                                                    #   whole-context
                                                    #   ones, two rotary
                                                    #   tables, GQA,
                                                    #   renormalised
                                                    #   experts (models/
                                                    #   mellum.py)
                    | "falcon_h1" | "falcon_h1_tiny"  # attention and
                                                    #   Mamba-2 side by
                                                    #   side in a layer
                                                    #   (models/
                                                    #   falcon_h1.py)
                    | "deepseek_v3" | "deepseek_v3_tiny"  # latent
                                                    #   attention: one
                                                    #   compressed row a
                                                    #   token a layer in
                                                    #   a pool of its
                                                    #   own kind, sigmoid
                                                    #   experts + a
                                                    #   shared one
                                                    #   (models/
                                                    #   deepseek_v3.py;
                                                    #   refuses
                                                    #   host_tier_blocks)
                    | <registered>,                 # all: same engine,
                                                    #   pools and decode
                                                    #   kernels
      "arch_kwargs": {...},
      "max_slots": 8,              # continuous-batching slot count
      "max_seq": 512,              # KV-cache capacity per slot
      "prefill_buckets": [64, 128, 256, 512],
      "max_new_tokens": 64,        # default generation budget
      "temperature": 0.0,          # default sampling temperature
      "tokenizer": "byte",         # "byte" | "hf:<name>"
      "block_size": 128,           # the KV cache is a pool of
      "cache_blocks": 48,          #   blocks of block_size tokens:
                                   #   HBM scales with resident
                                   #   tokens, shared prompt prefixes
                                   #   share blocks.  block_size
                                   #   unset is derived:
                                   #   gcd(128, max_seq, every
                                   #   prefill bucket).  cache_blocks
                                   #   unset holds every position of
                                   #   every slot (max_slots*max_seq
                                   #   / block_size).
                                   #   NOTE: the TPU Pallas paged
                                   #   kernels require block_size to
                                   #   be a multiple of 128 (lane
                                   #   width); other sizes, set or
                                   #   derived, serve correctly on
                                   #   the slower XLA gather path
                                   #   (logged once at load)
      "window_cache_blocks": 648,  # a model with sliding-window
                                   #   layers ("mellum") keeps their
                                   #   K/V in a second pool, a ring of
                                   #   ceil(window/block_size)+1
                                   #   blocks a sequence whatever its
                                   #   length; unset holds a ring for
                                   #   every slot, more leaves room
                                   #   for finished requests' blocks
                                   #   while they wait to be freed.
                                   #   Such a model refuses
                                   #   speculative,
                                   #   prefill_chunk_tokens and
                                   #   host_tier_blocks at load, and
                                   #   shares no prompt prefixes
      "prefill_rows": 8,           # the most rows one prefill
                                   #   dispatch carries (default:
                                   #   every free slot)
      "prefill_chunk_tokens": 512, # chunked prefill:
                                   #   a COLD prompt longer than this
                                   #   lands in block-aligned chunks
                                   #   interleaved with decode waves,
                                   #   so live streams stall one
                                   #   chunk's device time instead of
                                   #   the whole prompt's.  Size it so
                                   #   one chunk's device time ~ one
                                   #   decode wave (steps_per_call
                                   #   decode steps).  Must be a
                                   #   multiple of block_size.
      "host_tier_blocks": 256,     # host KV tier:
                                   #   capacity-evicted prefix blocks
                                   #   spill to a host-RAM mmap tier
                                   #   of this many blocks and fault
                                   #   back on the next turn instead
                                   #   of re-prefilling; 0/absent =
                                   #   off.  host_tier_dir overrides
                                   #   the spill-file location.
      "adaptive_depth": true,      # drop to depth-1 when every live
                                   #   stream finishes within the
                                   #   waves already in flight
      "speculative": {             # speculative decoding (optional;
        "tokens": 4,               #   default off, KFS_SPECDEC_TOKENS
                                   #   is the env twin): propose K
                                   #   tokens per live slot per wave,
                                   #   verify all K+1 positions in ONE
                                   #   target dispatch, commit the
                                   #   longest agreeing prefix —
                                   #   bit-exact with non-speculative
                                   #   decode for greedy AND seeded
                                   #   sampling.
        "draft": {                 #   optional draft model (absent ->
          "architecture": "...",   #   the zero-cost n-gram prompt-
          "arch_kwargs": {...},    #   lookup head proposes); loaded
          "model_dir": "...",      #   beside the target (model_dir
          "window": 32             #   defaults to the target's dir),
        }                          #   registered with the Residency-
      },                           #   Manager as "<name>:draft" and
                                   #   accounted in the HBM ledger.
      "mesh": {"tp": 2}            # within-replica tensor parallelism
    }

Request shapes (both V1 predict and the generate routes):
    {"instances": ["a prompt", {"prompt": "...", "max_tokens": 32,
                                "temperature": 0.7, "top_k": 40,
                                "top_p": 0.95, "seed": 7,
                                "stop": ["\n\n"], "logprobs": 3}]}
    {"text_input": "...", "parameters": {...}}   # v2 generate ext.
Response:
    {"predictions": [{"text": ..., "token_count": n,
                      "finish_reason": "eos"|"length"|"stop",
                      "logprobs": [...]}]}       # logprobs on request
    "logprobs": N asks for each token's log-probability and the N likeliest
    beside it (N <= logprob_topk); while such a request holds a slot, every
    decode wave it rides makes them for all its rows (logprob_topk + 2 more
    passes over the [slots, vocabulary] logits), a wave with no such row none.

Sampling runs on device (top-k/top-p mask-then-sample; seeded noise
keyed on (seed, position) so runs reproduce); stop sequences match
host-side in TEXT space on the decoded tail — the streaming path
holds back any suffix that could begin a stop sequence so clients
never see stop text, even split across K>1 token chunks.

The byte tokenizer (ids = UTF-8 bytes, BOS=256, EOS=257) keeps the
stack dependency-free and lossless for any input; "hf:<name>" resolves
a transformers tokenizer for real checkpoints.
"""

import json
import logging
import os
from typing import Any, AsyncIterator, Dict, List, Optional

import numpy as np

from kfserving_tpu.engine.generator import GenerationEngine
from kfserving_tpu.engine.hbm import HBMManager
from kfserving_tpu.observability import metrics as obs_metrics
from kfserving_tpu.model.model import Model
from kfserving_tpu.protocol import v1
from kfserving_tpu.protocol.errors import InferenceError, InvalidInput
from kfserving_tpu.storage import Storage

logger = logging.getLogger("kfserving_tpu.llm")

BOS_ID = 256
EOS_ID = 257

_warned_block_size = False


def _warn_paged_kernel_ineligible(block_size: int,
                                  derived: bool = False) -> None:
    """One warning per process: a block_size that isn't a 128-multiple
    silently loses the Pallas paged-kernel speedup on TPU (the XLA
    gather fallback serves correctly) — surface the config smell
    instead of hiding a perf cliff (ADVICE r5).  `derived`: the caller
    set none and the engine worked it out from max_seq and the
    prefill buckets."""
    global _warned_block_size
    if _warned_block_size:
        return
    _warned_block_size = True
    logger.warning(
        "block_size=%d%s is not a multiple of 128: the TPU Pallas "
        "paged-attention kernel is ineligible and decode uses the "
        "slower XLA gather path. Use %s to enable it.", block_size,
        " (derived: none was set, and max_seq and the prefill buckets "
        "share no multiple of 128)" if derived else "",
        "128-multiple lengths" if derived
        else "a 128-multiple block_size")


def _find_stop(text: str, stops: List[str]) -> int:
    """Earliest index of any stop sequence in `text`, or -1."""
    idx = -1
    for s in stops:
        i = text.find(s)
        if i >= 0 and (idx < 0 or i < idx):
            idx = i
    return idx


def _holdback_len(text: str, stops: List[str]) -> int:
    """Length of the longest suffix of `text` that is a proper prefix
    of some stop sequence — the streaming path must not emit those
    characters yet, or a stop split across chunks would leak to the
    client before the match completes."""
    hold = 0
    for s in stops:
        for length in range(min(len(s) - 1, len(text)), hold, -1):
            if text.endswith(s[:length]):
                hold = length
                break
    return hold


class IncrementalDecoder:
    """Streaming detokenizer: O(pending-window) work per token and
    emission-stable deltas.

    Slicing re-decoded full text by character index is wrong twice
    over: decode is not append-stable (a UTF-8 sequence split across
    tokens decodes to U+FFFD until its last byte arrives, then the
    SAME index holds a different character — the delta silently drops
    it), and re-decoding everything per token is O(n^2) on the event
    loop.  This decoder keeps a small window of not-yet-emitted
    tokens, re-decodes only that window, and releases text only when
    it can no longer change:

    - a trailing U+FFFD is held (it may be a partial multibyte
      sequence that completes next token; genuine garbage flushes at
      finish),
    - a suffix that is a proper prefix of a stop sequence is held
      (the holdback invariant: emitted text NEVER ends with a stop
      prefix, which also means stop matches only ever appear in the
      unemitted window),
    - the window compacts whenever everything in it has been emitted,
      so per-token work stays O(window), not O(generated-so-far).
    """

    def __init__(self, tokenizer, stops: List[str],
                 history: Optional[List[int]] = None):
        self.tok = tokenizer
        self.stops = stops
        self.max_stop = max((len(s) for s in stops), default=0)
        self._sent: List[str] = []
        self._pending: List[int] = []
        self._p_emitted = ""   # prefix of decode(_pending) already out
        self.degraded = False  # decode rewrote emitted text (exotic
        #                        tokenizer): deltas go best-effort and
        #                        the terminal text must come from a
        #                        full decode
        # Full token history, read only by the degraded path.  Callers
        # that already keep one (and append BEFORE each push) share it
        # via `history` so the fast path never stores a duplicate
        # O(generation) list next to the deliberately-bounded window.
        self._all: List[int] = [] if history is None else history
        self._owns_history = history is None
        self._final: Optional[str] = None  # degraded-stop truncation

    def push(self, token: int):
        """Feed one token; returns (delta, stopped).  `delta` is the
        newly releasable text (possibly empty); `stopped` means a stop
        sequence matched — delta then ends exactly before the match
        and the caller must stop the stream."""
        if self._owns_history:
            self._all.append(token)
        if self.degraded:
            return "", self._degraded_stop()
        self._pending.append(token)
        ptext = self.tok.decode(self._pending)
        if not ptext.startswith(self._p_emitted):
            # Decode rewrote already-emitted text: incremental deltas
            # are no longer trustworthy, but stop matching must NOT
            # silently vanish with them (ADVICE r5) — it falls back to
            # scanning the full re-decoded history each token.
            self.degraded = True
            if self.stops:
                logger.warning(
                    "tokenizer decode rewrote emitted text; stop-"
                    "sequence matching degraded to full re-decode "
                    "(deltas suspended, stops still honored)")
            return "", self._degraded_stop()
        rest = ptext[len(self._p_emitted):]
        if self.stops:
            idx = _find_stop(rest, self.stops)
            if idx >= 0:
                delta = rest[:idx]
                self._emit(delta, ptext)
                return delta, True
            hold = _holdback_len(rest, self.stops)
        else:
            hold = 0
        candidate = rest[:len(rest) - hold] if hold else rest
        while candidate.endswith("�"):
            candidate = candidate[:-1]
        self._emit(candidate, ptext)
        return candidate, False

    def _degraded_stop(self) -> bool:
        """Degraded-mode stop matching.  The common per-token check
        decodes only a bounded token tail (stops are short; the window
        gives each stop char 4x token slack), so a long degraded
        generation stays O(n·window), not O(n²).  Only a tail HIT pays
        one full re-decode — which both confirms the match against the
        authoritative text and yields the exact truncation index for
        `text()`."""
        if not self.stops:
            return False
        window = self.max_stop * 4 + 16
        tail = self.tok.decode(self._all[-window:])
        if _find_stop(tail, self.stops) < 0:
            return False
        full = self.tok.decode(self._all)
        idx = _find_stop(full, self.stops)
        if idx < 0:  # tail boundary artifact, not a real match
            return False
        self._final = full[:idx]
        return True

    def finish(self) -> str:
        """Flush everything still held (no stop matched); returns the
        final delta."""
        if self.degraded:
            return ""
        ptext = self.tok.decode(self._pending)
        if not ptext.startswith(self._p_emitted):
            self.degraded = True
            return ""
        delta = ptext[len(self._p_emitted):]
        self._emit(delta, ptext)
        return delta

    def text(self) -> str:
        """Text emitted so far (== the full truncated output after a
        stop, or the full output after finish()).  After a degraded-
        mode stop this is the truncated full decode; other degraded
        outcomes leave the terminal text to the caller's full decode."""
        if self._final is not None:
            return self._final
        return "".join(self._sent)

    # Tokens of context kept across window compaction: a window that
    # restarted at zero would re-decode its first token without its
    # neighbors, and piece-joining tokenizers (sentencepiece leading-
    # space, BPE cleanup) decode a boundary token differently alone.
    # Keeping a small suffix makes the boundary artifact identical in
    # p_emitted and in every later decode of the same window, so the
    # deltas cancel it out (the vLLM prefix-offset trick).
    _KEEP = 4

    def _emit(self, s: str, ptext: str):
        if s:
            self._sent.append(s)
            self._p_emitted += s
        # Compact: once the whole window is out, shrink it — this is
        # what keeps per-token cost O(window).
        if self._p_emitted == ptext and \
                len(self._pending) > self._KEEP:
            self._pending = self._pending[-self._KEEP:]
            self._p_emitted = self.tok.decode(self._pending)


def _lp_payload(req, tokens: List[int]) -> List[Dict[str, Any]]:
    """Per-token logprob records (aligned with content tokens)."""
    return [
        {"id": int(t), "logprob": c,
         "top": [{"id": i, "logprob": p} for i, p in top]}
        for t, c, top in zip(tokens, req.lp_chosen, req.lp_top)
    ]


class ByteTokenizer:
    """Lossless byte-level tokenizer: ids 0-255 are UTF-8 bytes, 256 is
    BOS, 257 is EOS.  vocab_size 258 — the decoder_tiny config rounds
    its embedding table up to a lane-friendly 384."""

    vocab_size = 258
    bos_id = BOS_ID
    eos_id = EOS_ID

    def encode(self, text: str, add_bos: bool = True) -> List[int]:
        ids = list(text.encode("utf-8"))
        return ([self.bos_id] + ids) if add_bos else ids

    def decode(self, ids: List[int]) -> str:
        data = bytes(i for i in ids if 0 <= i < 256)
        return data.decode("utf-8", errors="replace")


def build_tokenizer(spec: str):
    if spec == "byte":
        return ByteTokenizer()
    if spec.startswith("hf:"):
        from transformers import AutoTokenizer  # baked-in dependency

        tok = AutoTokenizer.from_pretrained(spec[3:])
        if tok.eos_token_id is None:
            logger.warning(
                "tokenizer %s has no eos_token_id: generation will only "
                "stop at the token budget or a stop sequence", spec)

        class _HF:
            vocab_size = tok.vocab_size
            bos_id = tok.bos_token_id
            eos_id = tok.eos_token_id

            def encode(self, text, add_bos=True):
                # add_special_tokens=False: some tokenizers append EOS
                # (or wrap with template tokens) in plain encode(),
                # which would poison the prompt; BOS is added
                # explicitly and only when the tokenizer has one.
                ids = tok.encode(text, add_special_tokens=False)
                if add_bos and tok.bos_token_id is not None:
                    ids = [tok.bos_token_id] + ids
                return ids

            def decode(self, ids):
                return tok.decode(ids, skip_special_tokens=True)

        return _HF()
    raise InvalidInput(f"unknown tokenizer spec {spec!r}")


class GenerativeConfig:
    def __init__(self, architecture: str,
                 arch_kwargs: Optional[Dict] = None,
                 max_slots: int = 8, max_seq: int = 512,
                 prefill_buckets: Optional[List[int]] = None,
                 max_new_tokens: int = 64, temperature: float = 0.0,
                 tokenizer: str = "byte",
                 steps_per_call: int = 1,
                 pipeline_depth: int = 2,
                 logprob_topk: int = 5,
                 block_size: Optional[int] = None,
                 cache_blocks: Optional[int] = None,
                 window_cache_blocks: Optional[int] = None,
                 prefill_chunk_tokens: Optional[int] = None,
                 host_tier_blocks: Optional[int] = None,
                 host_tier_dir: Optional[str] = None,
                 adaptive_depth: bool = True,
                 speculative: Optional[Dict[str, Any]] = None,
                 prefill_rows: Optional[int] = None,
                 ignore_eos: bool = False,
                 exit_with_parent: bool = False,
                 mesh: Optional[Dict[str, int]] = None,
                 **_ignored):
        self.architecture = architecture
        self.arch_kwargs = arch_kwargs or {}
        self.max_slots = int(max_slots)
        self.max_seq = int(max_seq)
        self.prefill_buckets = prefill_buckets
        self.max_new_tokens = int(max_new_tokens)
        self.temperature = float(temperature)
        self.tokenizer = tokenizer
        # Decode steps per device dispatch: on high-RTT transports each
        # dispatch costs ~an RTT, so K steps per call multiplies
        # per-slot tokens/s by up to K (streaming granularity becomes
        # K tokens; at most K-1 wasted steps past an EOS).
        self.steps_per_call = int(steps_per_call)
        # Decode waves in flight (>=2 hides the dispatch RTT behind
        # device compute; 1 = strictly blocking, the A/B baseline).
        self.pipeline_depth = int(pipeline_depth)
        self.logprob_topk = int(logprob_topk)
        # The KV cache's block pool (HBM scales with resident tokens;
        # identical prompt prefixes share blocks).  block_size None =
        # the engine derives it, gcd(128, max_seq, prefill buckets);
        # cache_blocks None = a block for every position of every slot.
        self.block_size = int(block_size) if block_size else None
        self.cache_blocks = (int(cache_blocks) if cache_blocks
                             else None)
        # A model with sliding-window layers keeps those layers' K/V in
        # a pool of their own, a ring of ceil(window / block_size) + 1
        # blocks a sequence; None = a ring for every slot.
        self.window_cache_blocks = (int(window_cache_blocks)
                                    if window_cache_blocks else None)
        # Chunked prefill: cold prompts longer than this
        # land chunk-by-chunk between decode waves; adaptive depth
        # stops speculative waves that could only decode garbage.
        self.prefill_chunk_tokens = (int(prefill_chunk_tokens)
                                     if prefill_chunk_tokens else None)
        # Host KV tier: capacity-evicted prefix blocks
        # spill to a host-RAM mmap tier of this many blocks instead of
        # dropping; 0/None = off (KFS_KV_TIER_BLOCKS is the env twin).
        self.host_tier_blocks = (int(host_tier_blocks)
                                 if host_tier_blocks else None)
        self.host_tier_dir = host_tier_dir
        self.adaptive_depth = bool(adaptive_depth)
        # Speculative decoding: {"tokens": K, optional "draft":
        # {"architecture", "arch_kwargs", "model_dir", "window"}}.
        # None/absent defers to the engine's KFS_SPECDEC_TOKENS env
        # twin (n-gram proposer only); see the module docstring.
        self.speculative = dict(speculative) if speculative else None
        # The most rows one prefill dispatch may carry (None: every
        # free slot): what fits beside the parameters is the
        # deployment's to know, not the runtime's to refuse.
        self.prefill_rows = int(prefill_rows) if prefill_rows else None
        # An answer ends at its token budget or a stop sequence alone:
        # for a replica whose weights are seeded and not trained, where
        # the tokenizer's EOS id is a row of the vocabulary like any
        # other and a load test asks for answers of given lengths.
        self.ignore_eos = bool(ignore_eos)
        # The server ends when the process that started it does, however
        # that one ended (`startup.exit_with_parent`).
        self.exit_with_parent = bool(exit_with_parent)
        self.mesh = mesh or {}

    @classmethod
    def from_file(cls, path: str,
                  overrides: Optional[Dict[str, Any]] = None):
        with open(path) as f:
            data = json.load(f)
        if overrides:
            data.update(overrides)
        if "architecture" not in data:
            raise InvalidInput(
                f"{path} missing required key 'architecture'")
        return cls(**data)


class GenerativeModel(Model):
    """A served decoder with continuous batching and token streaming."""

    def __init__(self, name: str, model_dir: str,
                 config: Optional[GenerativeConfig] = None,
                 hbm: Optional[HBMManager] = None,
                 config_overrides: Optional[Dict[str, Any]] = None,
                 residency=None):
        super().__init__(name)
        self.model_dir = model_dir
        self.config = config
        self.hbm = hbm
        # Optional ResidencyManager: when present, a configured draft
        # model registers beside the target as "<name>:draft" so
        # `kfs models` shows it and the ledger accounts it.
        self.residency = residency
        self.config_overrides = dict(config_overrides or {})
        self.engine: Optional[GenerationEngine] = None
        self.tokenizer = None
        self._draft_handle = None
        # "mmap" | "checkpoint" | "init" once loaded.
        self.param_source: Optional[str] = None

    # -- lifecycle ---------------------------------------------------------
    def load(self) -> bool:
        from kfserving_tpu import startup
        from kfserving_tpu.engine import param_cache
        from kfserving_tpu.models import create_model

        startup.mark("load_start")
        local = Storage.download(self.model_dir)
        startup.mark("download")
        cfg = self.config
        if cfg is None:
            cfg = GenerativeConfig.from_file(
                os.path.join(local, "config.json"),
                overrides=self.config_overrides)
            self.config = cfg
        if cfg.exit_with_parent:
            startup.exit_with_parent()
        self.tokenizer = build_tokenizer(cfg.tokenizer)

        spec = create_model(cfg.architecture, **cfg.arch_kwargs)
        # mmap-first materialization (shared with JaxModel): a standby
        # successor maps the predecessor's persisted host params and
        # its activation cost collapses to the device transfer, which
        # the engine makes once when it is built (or shard_params
        # below, under a mesh).
        variables, self.param_source = param_cache.load_or_materialize(
            cfg.architecture, cfg.arch_kwargs, spec, local)

        mesh = None
        if cfg.mesh:
            from kfserving_tpu.parallel import build_mesh, shard_params
            from kfserving_tpu.parallel.mesh import MeshConfig

            mesh_cfg = MeshConfig(**{k: int(v)
                                     for k, v in cfg.mesh.items()
                                     if k in ("dp", "tp", "sp")})
            if mesh_cfg.num_devices > 1:
                mesh = build_mesh(mesh_cfg)
                variables = {
                    **variables,
                    "params": shard_params(variables["params"], mesh),
                }

        speculative = None
        draft_meta = None
        if cfg.speculative and \
                int(cfg.speculative.get("tokens", 0)) > 0:
            speculative = {"tokens": int(cfg.speculative["tokens"])}
            draft_cfg = cfg.speculative.get("draft")
            if draft_cfg:
                # The draft is just a second model materialized
                # through the same mmap-first path, faulted in beside
                # the target — it shares the target's dir when no
                # model_dir of its own is given (self-draft and
                # co-packaged drafts).
                draft_kwargs = dict(draft_cfg.get("arch_kwargs")
                                    or {})
                draft_spec = create_model(draft_cfg["architecture"],
                                          **draft_kwargs)
                draft_dir = draft_cfg.get("model_dir")
                draft_local = (Storage.download(draft_dir)
                               if draft_dir else local)
                draft_vars, _ = param_cache.load_or_materialize(
                    draft_cfg["architecture"], draft_kwargs,
                    draft_spec, draft_local)
                window = int(draft_cfg.get("window", 0) or 0)
                speculative.update({
                    "draft_module": draft_spec.module,
                    "draft_variables": draft_vars,
                })
                if window:
                    speculative["draft_window"] = window
                draft_meta = (draft_spec.module, window)

        engine = GenerationEngine(
            spec.module, variables,
            max_slots=cfg.max_slots, max_seq=cfg.max_seq,
            prefill_buckets=cfg.prefill_buckets,
            eos_id=(None if cfg.ignore_eos
                    else getattr(self.tokenizer, "eos_id", None)),
            steps_per_call=cfg.steps_per_call,
            pipeline_depth=cfg.pipeline_depth,
            logprob_topk=cfg.logprob_topk,
            block_size=cfg.block_size,
            cache_blocks=cfg.cache_blocks,
            window_cache_blocks=cfg.window_cache_blocks,
            prefill_chunk_tokens=cfg.prefill_chunk_tokens,
            host_tier_blocks=cfg.host_tier_blocks,
            host_tier_dir=cfg.host_tier_dir,
            adaptive_depth=cfg.adaptive_depth,
            speculative=speculative,
            prefill_rows=cfg.prefill_rows,
            mesh=mesh, name=self.name)
        if engine.block_size % 128 != 0:
            _warn_paged_kernel_ineligible(
                engine.block_size, derived=cfg.block_size is None)
        if self.hbm is not None:
            # Generation residency = params + the slot cache pool,
            # plus the draft model's params when speculation runs one
            # — the ledger accounts BOTH models of the pair.
            self.hbm.admit(self.name,
                           engine.param_bytes() + engine.cache_bytes()
                           + engine.draft_param_bytes())
        self.engine = engine
        if draft_meta is not None:
            from kfserving_tpu.engine.speculative import (
                DEFAULT_DRAFT_WINDOW,
                DraftModel,
            )

            module_d, window = draft_meta
            # The engine's placed tree, not the host views it was
            # built from: nothing reads those once they are on the
            # device.
            self._draft_handle = DraftModel(
                f"{self.name}:draft", module_d, engine.draft_variables,
                engine, window=window or DEFAULT_DRAFT_WINDOW)
            if self.residency is not None:
                # Registers directly as resident (ready + engine set)
                # and PINNED: the manager must never evict the draft
                # out from under the serving target.
                self.residency.register(self._draft_handle.name,
                                        self._draft_handle)
        self.ready = True
        return True

    def unload(self) -> None:
        if self.engine is not None:
            self.engine.shutdown_nowait()
            self.engine = None
        if self._draft_handle is not None:
            if self.residency is not None:
                self.residency.deregister(self._draft_handle.name)
            # Unpin: a registration that outlives this unload must not
            # keep vetoing eviction.
            self._draft_handle.release()
            self._draft_handle = None
        if self.hbm is not None:
            self.hbm.release(self.name)
        self.ready = False

    async def close(self) -> None:
        if self.engine is not None:
            await self.engine.close()
            self.engine = None
        await super().close()

    # -- request parsing ---------------------------------------------------
    def _parse_instance(self, inst: Any) -> Dict[str, Any]:
        cfg = self.config
        if isinstance(inst, str):
            inst = {"prompt": inst}
        if not isinstance(inst, dict):
            raise InvalidInput(
                f"generate instance must be a string or object, got "
                f"{type(inst).__name__}")
        if "prompt" not in inst and "text_input" not in inst:
            raise InvalidInput(
                "generate instance needs 'prompt' (or 'text_input')")
        stop = inst.get("stop", [])
        if isinstance(stop, str):
            stop = [stop]
        if not (isinstance(stop, list)
                and all(isinstance(s, str) and s for s in stop)):
            raise InvalidInput(
                "stop must be a non-empty string or a list of them")
        seed = inst.get("seed")
        logprobs = inst.get("logprobs", 0)
        if logprobs is True:
            logprobs = 1
        return {
            "prompt": str(inst.get("prompt", inst.get("text_input"))),
            "max_tokens": int(inst.get("max_tokens",
                                       inst.get("max_new_tokens",
                                                cfg.max_new_tokens))),
            "temperature": float(inst.get("temperature",
                                          cfg.temperature)),
            "top_k": int(inst.get("top_k", 0)),
            "top_p": float(inst.get("top_p", 1.0)),
            "seed": None if seed is None else int(seed),
            "stop": stop,
            "logprobs": int(logprobs),
        }

    def _submit(self, parsed: Dict[str, Any]):
        ids = self.tokenizer.encode(parsed["prompt"])
        # Prompt-side token accounting (the "out" side increments per
        # emitted token in the engine's _emit).
        obs_metrics.llm_tokens_total().labels(direction="in").inc(
            len(ids))
        return self.engine.submit(
            ids, max_new_tokens=parsed["max_tokens"],
            temperature=parsed["temperature"],
            top_k=parsed["top_k"], top_p=parsed["top_p"],
            seed=parsed["seed"], logprobs=parsed["logprobs"])

    async def _run_one(self, parsed: Dict[str, Any]) -> Dict[str, Any]:
        req = self._submit(parsed)
        tokens: List[int] = []
        # tokens is appended BEFORE each push, so the decoder's
        # degraded path can share it instead of duplicating history.
        decoder = IncrementalDecoder(self.tokenizer, parsed["stop"],
                                     history=tokens)
        reason = "length"
        async for token, fin in self.engine.stream(req):
            if token is not None:
                tokens.append(token)
                _, stopped = decoder.push(token)
                if stopped:
                    # Stop sequences live in TEXT space (the tokenizer
                    # may split one across tokens); the match runs
                    # host-side on the decoded window and the engine
                    # slot is cancelled the moment it lands.
                    self.engine.cancel(req)
                    return self._result(req, decoder.text(), tokens,
                                        "stop", parsed)
            if fin is not None:
                reason = fin
        if reason == "timeout" and not tokens:
            # Budget died in the queue before a single token: a clean
            # 504 beats an empty 200.  With partial text, deliver it
            # with finish_reason "timeout" (the client paid for those
            # tokens; the engine freed the slot either way).
            from kfserving_tpu.reliability import DeadlineExceeded

            raise DeadlineExceeded("generation")
        decoder.finish()
        text = (self.tokenizer.decode(tokens) if decoder.degraded
                else decoder.text())
        return self._result(req, text, tokens, reason, parsed)

    def _result(self, req, text: str, tokens: List[int], reason: str,
                parsed: Dict[str, Any]) -> Dict[str, Any]:
        out = {"text": text, "token_count": len(tokens),
               "finish_reason": reason}
        if parsed["logprobs"] > 0:
            out["logprobs"] = _lp_payload(req, tokens)
        return out

    # -- serving entry points ----------------------------------------------
    async def predict(self, request: Any) -> Any:
        if self.predictor_host:
            return await super().predict(request)
        if self.engine is None:
            raise InferenceError(f"model {self.name} not loaded")
        import asyncio

        instances = v1.get_instances(request)
        if not instances:
            raise InvalidInput("generate needs at least one instance")
        parsed = [self._parse_instance(i) for i in instances]
        # Submit all instances at once: the engine's continuous batcher
        # shares decode steps across them (the request-level analogue of
        # the dynamic batcher).  return_exceptions: let every sibling
        # settle before surfacing a failure — an immediate propagate
        # would leave the others decoding unawaited to their full
        # budgets ("Task exception was never retrieved").
        results = await asyncio.gather(*[self._run_one(p)
                                         for p in parsed],
                                       return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException):
                raise r
        return v1.make_response(list(results))

    async def generate(self, request: Any) -> Any:
        """Non-streaming :generate — v2 generate-extension shape in,
        single result out."""
        if self.engine is None:
            raise InferenceError(f"model {self.name} not loaded")
        parsed = self._parse_generate_body(request)
        result = await self._run_one(parsed)
        details = {"token_count": result["token_count"],
                   "finish_reason": result["finish_reason"]}
        if "logprobs" in result:
            details["logprobs"] = result["logprobs"]
        return {"model_name": self.name, "text_output": result["text"],
                "details": details}

    def _parse_generate_body(self, request: Any) -> Dict[str, Any]:
        if isinstance(request, dict) and (
                "text_input" in request or "prompt" in request):
            merged = dict(request)
            merged.update(request.get("parameters") or {})
            return self._parse_instance(merged)
        instances = v1.get_instances(request)
        if not instances:
            raise InvalidInput("generate needs a prompt")
        return self._parse_instance(instances[0])

    async def generate_stream(self, request: Any
                              ) -> AsyncIterator[Dict[str, Any]]:
        """Streaming :generate — an async iterator of per-token events:
        {"token": {"id", "text"}, ...} with a terminal event carrying
        finish_reason + the aggregate text.

        Validation and submission happen HERE, eagerly — before the
        caller commits response headers — so a bad prompt is a clean
        4xx, not a 200 followed by a dropped connection."""
        if self.engine is None:
            raise InferenceError(f"model {self.name} not loaded")
        parsed = self._parse_generate_body(request)
        req = self._submit(parsed)
        stops = parsed["stop"]
        want_lp = parsed["logprobs"] > 0

        finished = False

        async def events():
            nonlocal finished
            collected: List[int] = []
            # collected is appended BEFORE each push (shared history,
            # see IncrementalDecoder.__init__).
            decoder = IncrementalDecoder(self.tokenizer, stops,
                                         history=collected)

            def token_event(token, text_delta):
                event = {"token": {"id": int(token),
                                   "text": text_delta}}
                if want_lp and len(collected) <= len(req.lp_chosen):
                    i = len(collected) - 1
                    event["token"]["logprob"] = req.lp_chosen[i]
                    event["token"]["top_logprobs"] = [
                        {"id": t, "logprob": p}
                        for t, p in req.lp_top[i]]
                return event

            async for token, reason in self.engine.stream(req):
                if token is not None:
                    collected.append(token)
                    delta, stopped = decoder.push(token)
                    if stopped:
                        # Truncate at the match; never emit the stop
                        # text itself.
                        self.engine.cancel(req)
                        finished = True
                        event = token_event(token, delta)
                        event["finish_reason"] = "stop"
                        event["generated_text"] = decoder.text()
                        event["details"] = {
                            "token_count": len(collected)}
                        yield event
                        return
                    event = token_event(token, delta)
                else:
                    event = {}
                if reason is not None:
                    finished = True
                    # Flush anything held back: no stop matched.
                    tail = decoder.finish()
                    if tail:
                        tok = event.setdefault(
                            "token", {"id": None, "text": ""})
                        tok["text"] += tail
                    full = (self.tokenizer.decode(collected)
                            if decoder.degraded else decoder.text())
                    event["finish_reason"] = reason
                    event["generated_text"] = full
                    event["details"] = {"token_count": len(collected)}
                yield event

        def on_close():
            # Consumer abandoned the stream (client disconnect —
            # including before the first event was ever pulled): free
            # the decode slot instead of generating to the budget for
            # nobody.  No-op when the generation finished normally.
            if not finished:
                self.engine.cancel(req)

        from kfserving_tpu.streams import GuardedStream

        return GuardedStream(events(), on_close)

    def engine_stats(self) -> Dict[str, Any]:
        stats = dict(self.engine.stats()) if self.engine else {}
        if self.param_source is not None:
            stats["param_source"] = self.param_source
        return stats

    def metadata(self) -> Dict[str, Any]:
        meta = super().metadata()
        if self.config is not None:
            meta["platform"] = "jax-generate"
            meta["architecture"] = self.config.architecture
            meta["max_slots"] = self.config.max_slots
            meta["max_seq"] = self.config.max_seq
        return meta
