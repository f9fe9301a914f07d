"""JaxModel: the TPU-native predictor.

Plays the role the reference delegates to pytorchserver/TFServing/Triton
(reference python/pytorchserver/pytorchserver/model.py loads a torch class
and predicts per-request with no batching): load a Flax model + params,
compile shape-bucketed executables, and serve V1/V2 predict through the
in-process dynamic batcher.

Model directory layout (the `storage_uri` artifact):

    config.json          — required; see JaxModelConfig
    checkpoint.msgpack   — flax.serialization byte blob of the variables
                           (optional: absent -> random init, which serving
                           tests and synthetic benchmarks use)

config.json schema (all optional except architecture):
    {
      "architecture": "resnet50" | "bert" | "vit_b16" | "mlp" | <registered>,
      "arch_kwargs": {...},            # forwarded to the registry factory
      "max_batch_size": 32,            # bucket ceiling (pow2 buckets)
      "max_latency_ms": 5.0,           # batcher flush deadline
      "seq_buckets": [64, 128, 256],   # seq-len buckets (token models)
      "input_dtype": "uint8"|"float32",# client payload dtype on the wire
      "scale": 0.00392156862,          # on-device input scaling (1/255)
      "output": "logits"|"argmax"|"topk",
      "topk": 5,
      "mesh": {"dp": 1, "tp": 1, "sp": 1}   # within-replica parallelism
    }

Design notes (TPU-first):
- uint8 on the wire + normalize on device: host->HBM bandwidth is the
  serving bottleneck; a float32 image batch is 4x the bytes of the same
  uint8 batch for zero accuracy gain before normalization.
- argmax/topk on device: the response rides back bytes-per-instance instead
  of the full logit row.
- multi-chip replicas are the same code path: params are placed with
  NamedShardings over the config mesh and the bucketed executables become
  SPMD programs (parallel/sharding.py rules).
"""

import json
import logging
import os
from typing import Any, Dict, List, Optional

import numpy as np

from kfserving_tpu.batching import DynamicBatcher
from kfserving_tpu.engine.buckets import BucketPolicy
from kfserving_tpu.engine.hbm import HBMManager
from kfserving_tpu.engine.jax_engine import JaxEngine
from kfserving_tpu.model.model import Model
from kfserving_tpu.protocol import v1
from kfserving_tpu.protocol.errors import InferenceError, InvalidInput
from kfserving_tpu.protocol.v2 import InferRequest, make_response
from kfserving_tpu.storage import Storage

logger = logging.getLogger("kfserving_tpu.jaxserver")

DEFAULT_CONFIG_NAME = "config.json"
CHECKPOINT_NAME = "checkpoint.msgpack"


class JaxModelConfig:
    def __init__(self, architecture: str, arch_kwargs: Optional[Dict] = None,
                 max_batch_size: int = 32, max_latency_ms: float = 5.0,
                 batch_buckets: Optional[List[int]] = None,
                 seq_buckets: Optional[List[int]] = None,
                 input_dtype: str = "float32", scale: Optional[float] = None,
                 output: str = "logits", topk: int = 5,
                 mesh: Optional[Dict[str, int]] = None,
                 warmup: bool = True, pipeline_depth: int = 2,
                 **_ignored):
        self.architecture = architecture
        self.arch_kwargs = arch_kwargs or {}
        self.max_batch_size = max_batch_size
        self.max_latency_ms = max_latency_ms
        # Explicit batch buckets bound compile count (each bucket is one
        # XLA program); default pow2 ladder up to max_batch_size.
        self.batch_buckets = batch_buckets
        self.seq_buckets = seq_buckets
        self.input_dtype = input_dtype
        self.scale = scale
        self.output = output
        self.topk = topk
        self.mesh = mesh or {}
        self.warmup = warmup
        self.pipeline_depth = pipeline_depth

    @classmethod
    def from_file(cls, path: str,
                  overrides: Optional[Dict[str, Any]] = None
                  ) -> "JaxModelConfig":
        """Load config.json, with deployment-time overrides layered on
        top (the control plane's ParallelismSpec injects `mesh` here —
        the artifact stays mesh-agnostic, placement is a spec concern)."""
        with open(path) as f:
            data = json.load(f)
        if overrides:
            data.update(overrides)
        if "architecture" not in data:
            raise InvalidInput(f"{path} missing required key 'architecture'")
        return cls(**data)


class JaxModel(Model):
    """A served JAX/Flax model with bucketed batched execution."""

    def __init__(self, name: str, model_dir: str,
                 config: Optional[JaxModelConfig] = None,
                 hbm: Optional[HBMManager] = None,
                 config_overrides: Optional[Dict[str, Any]] = None,
                 residency=None):
        super().__init__(name)
        self.model_dir = model_dir
        self.config = config
        self.hbm = hbm
        # ResidencyManager (engine/residency.py): when set, this model
        # is demand-paged — register() makes it addressable with no
        # device memory, predict faults it into HBM transparently, and
        # eviction offloads (host mmap params stay) instead of
        # unloading.
        self.residency = residency
        self.config_overrides = dict(config_overrides or {})
        self.engine: Optional[JaxEngine] = None
        self.batcher: Optional[DynamicBatcher] = None
        # Cached admission estimate: a cold fault whose admission finds
        # every victim busy retries load() every ~20 ms (residency
        # admit-wait) — the eval_shape trace must not be re-paid per
        # attempt.
        self._admit_nbytes: Optional[int] = None
        self._local_dir: Optional[str] = None
        # How this model's params were materialized at load: "mmap"
        # (param-cache hit), "checkpoint", or "init".
        self.param_source: Optional[str] = None

    # -- lifecycle ---------------------------------------------------------
    def register(self) -> bool:
        """Declarative registration (residency mode): host-side prep
        only — artifact download + config parse, no device memory, no
        compile.  The model becomes `ready` (addressable; the predict
        path cold-faults the engine in on first use).  Registration of
        N models is O(N) file reads, not N compile grids."""
        from kfserving_tpu import startup

        startup.mark("load_start")
        self._local_dir = Storage.download(self.model_dir)
        startup.mark("download")
        if self.config is None:
            self.config = JaxModelConfig.from_file(
                os.path.join(self._local_dir, DEFAULT_CONFIG_NAME),
                overrides=self.config_overrides)
        if self.residency is not None:
            self.residency.register(self.name, self)
        self.ready = True
        return True

    def load(self) -> bool:
        from kfserving_tpu.models import create_model, init_params

        from kfserving_tpu import startup

        startup.mark("load_start")
        if self.residency is not None and self._local_dir:
            # Residency-managed cold fault: register() already pulled
            # the artifact, and the admit-wait loop retries load()
            # every ~20 ms — re-downloading a REMOTE storage_uri into
            # a fresh temp dir per retry would turn a busy-victim wait
            # into a download storm.
            pass
        else:
            self._local_dir = Storage.download(self.model_dir)
        startup.mark("download")
        cfg = self.config
        if cfg is None:
            cfg = JaxModelConfig.from_file(
                os.path.join(self._local_dir, DEFAULT_CONFIG_NAME),
                overrides=self.config_overrides)
            self.config = cfg

        spec = create_model(cfg.architecture, **cfg.arch_kwargs)

        # Reload is transactional: the new engine/batcher are built aside
        # and swapped in only on success.  During a reload, BOTH
        # generations are physically resident until the swap, so the new
        # one is admitted under a staging key alongside the old entry
        # (zero-downtime path).  When HBM has no headroom for both, fall
        # back to stop-the-world: close the old generation first, then
        # admit and build (downtime, but never device overcommit).
        old_engine = self.engine
        staging_key = f"{self.name}!staging"  # '!' excluded from names
        zero_downtime = True
        if self.hbm is not None:
            import jax

            from kfserving_tpu.engine.hbm import InsufficientHBM

            if self._admit_nbytes is None:
                abstract = jax.eval_shape(
                    lambda: init_params(spec, seed=0))
                self._admit_nbytes = sum(
                    int(np.prod(leaf.shape)) *
                    np.dtype(leaf.dtype).itemsize
                    for leaf in jax.tree.leaves(abstract))
            nbytes = self._admit_nbytes
            if old_engine is None:
                self.hbm.admit(self.name, nbytes)
            else:
                try:
                    # evict=False: staging must never evict live models
                    # (including this model's own serving generation) —
                    # no headroom means the stop-the-world path below.
                    self.hbm.admit(staging_key, nbytes, evict=False)
                except InsufficientHBM:
                    zero_downtime = False
                    self.ready = False
                    self.engine, self.batcher = None, None
                    old_engine.close()
                    old_engine = None
                    self.hbm.release(self.name)
                    self.hbm.admit(self.name, nbytes)
        elif old_engine is None:
            nbytes = None

        try:
            engine, batcher = self._build_engine(spec, cfg)
        except Exception:
            if self.hbm is not None:
                if old_engine is not None:
                    self.hbm.release(staging_key)  # old entry untouched
                else:
                    self.hbm.release(self.name)
            raise
        self.engine, self.batcher = engine, batcher
        self.ready = True
        if old_engine is not None:
            old_engine.close()  # quiesces in-flight work, frees old HBM
            if self.hbm is not None and zero_downtime:
                # Atomic commit: staging entry becomes the model's entry
                # under the manager lock (no release/re-admit window a
                # concurrent admit could claim).
                self.hbm.commit(staging_key, self.name,
                                engine.param_bytes())
        if self.residency is not None:
            # Idempotent for the cold-fault path (the manager already
            # holds this model's record); a direct eager load joins the
            # managed set as resident.
            self.residency.register(self.name, self)
        return True

    def _build_engine(self, spec, cfg):
        import jax.numpy as jnp

        from kfserving_tpu.engine import param_cache
        from kfserving_tpu.models import apply_fn_for
        from kfserving_tpu.parallel import build_mesh, shard_params
        from kfserving_tpu.parallel.mesh import MeshConfig

        from kfserving_tpu import startup

        # Kept for subclasses that need the raw logits path (explainers
        # differentiate through base_apply, not the serving output mode).
        self._spec = spec
        # mmap-first param materialization: a recycle successor (or a
        # cheap canary spawn) maps the predecessor's persisted host
        # bytes instead of re-running init + checkpoint restore:
        # init_params becomes page-cache reads feeding the device
        # transfer.
        variables, param_source = param_cache.load_or_materialize(
            cfg.architecture, cfg.arch_kwargs, spec, self._local_dir,
            checkpoint_name=CHECKPOINT_NAME)
        self.param_source = param_source

        mesh_cfg = MeshConfig(**{k: int(v) for k, v in cfg.mesh.items()
                                 if k in ("dp", "tp", "sp")})
        mesh = None
        if mesh_cfg.num_devices > 1:
            mesh = build_mesh(mesh_cfg)
            with mesh:
                variables = {
                    **variables,
                    "params": shard_params(variables["params"], mesh),
                }
        if mesh is not None and mesh_cfg.sp > 1:
            # Sequence parallelism: rebuild the serving module with ring
            # attention closed over the mesh (models/bert.py attn_fn
            # hook; parameters are attention-impl-independent, so the
            # restored checkpoint applies unchanged).  Architectures
            # without a pluggable attention can't shard the sequence
            # axis — fail at load, not silently serve unsharded.
            from kfserving_tpu.models import create_model
            from kfserving_tpu.parallel.ring_attention import (
                ring_attention_sharded,
            )

            try:
                spec = create_model(
                    cfg.architecture,
                    attn_fn=ring_attention_sharded(mesh),
                    **cfg.arch_kwargs)
            except TypeError as e:
                raise InvalidInput(
                    f"architecture {cfg.architecture!r} does not "
                    f"support sequence parallelism (no pluggable "
                    f"attention hook): {e}")
            self._spec = spec

        base_apply = apply_fn_for(spec)
        self._base_apply = base_apply
        scale = cfg.scale
        output_mode, topk = cfg.output, cfg.topk

        def serve_fn(v, batch):
            x = batch
            if not isinstance(x, dict) and scale is not None:
                x = x.astype(jnp.bfloat16) * scale
            out = base_apply(v, x)
            if output_mode == "argmax":
                return jnp.argmax(out, axis=-1).astype(jnp.int32)
            if output_mode == "topk":
                import jax

                vals, idx = jax.lax.top_k(out, topk)
                return {"values": vals.astype(jnp.float32),
                        "indices": idx.astype(jnp.int32)}
            return out

        seq_buckets = (BucketPolicy(cfg.seq_buckets)
                       if cfg.seq_buckets else None)
        residency_managed = self.residency is not None
        engine = JaxEngine(
            serve_fn, variables,
            batch_buckets=(BucketPolicy(cfg.batch_buckets)
                           if cfg.batch_buckets
                           else BucketPolicy.pow2(cfg.max_batch_size)),
            seq_buckets=seq_buckets,
            pipeline_depth=cfg.pipeline_depth,
            param_source=param_source, mesh=mesh)
        if residency_managed and engine.offloadable:
            # Pin the params in HBM explicitly (one device_put of the
            # mmap views) so residency accounting matches physical
            # placement; the host tree stays as the restore source for
            # every later evict -> fault-in cycle.
            engine.restore()
        try:
            if cfg.warmup:
                example = self._example_instance(spec)
                # Recycle successors trim the grid: the predecessor's
                # persistent compile cache makes on-demand bucket
                # loads cheap, and a fast successor shortens the
                # contention window that drives the swap's p99.
                engine.warmup(example, minimal=(
                    os.environ.get("KFS_MINIMAL_WARMUP", "")
                    not in ("", "0", "false")))
                startup.mark("warmup")
        except Exception:
            engine.close()
            raise

        batcher = DynamicBatcher(
            self._batch_handler,
            # Chunk limit = the largest compiled bucket, so a flush never
            # exceeds what the engine can execute in one call.
            max_batch_size=(max(cfg.batch_buckets) if cfg.batch_buckets
                            else cfg.max_batch_size),
            max_latency_ms=cfg.max_latency_ms,
            key_fn=self._bucket_key if seq_buckets else None,
            # One more than the engine's worker threads so a fresh batch
            # is always staged when a thread frees (the batcher defers
            # flushes past this — small batches coalesce while the
            # engine is busy instead of queueing tiny executions).
            max_inflight=cfg.pipeline_depth + 1,
            # Bucket-aligned flushing: executed batches land exactly on
            # the engine's compiled shapes, so pad waste comes only from
            # drain-out tails (VERDICT r2: 62% of ResNet batch slots were
            # padding with misaligned flushes).
            buckets=engine.batch_buckets.buckets)
        return engine, batcher

    def _example_instance(self, spec):
        cfg = self.config
        if isinstance(spec.example, dict):
            return {k: np.asarray(v)[0] for k, v in spec.example.items()}
        ex = np.asarray(spec.example)[0]
        if cfg.input_dtype == "uint8":
            return np.zeros(ex.shape, np.uint8)
        return ex.astype(cfg.input_dtype)

    def unload(self) -> None:
        if self.residency is not None:
            self.residency.deregister(self.name)
        if self.engine is not None:
            self.engine.close()
            self.engine = None
        if self.hbm is not None:
            self.hbm.release(self.name)
        self.batcher = None
        self.ready = False

    # -- residency hooks (engine/residency.py contract) --------------------
    @property
    def offloadable(self) -> bool:
        """Can this model leave HBM without losing its warm state?
        True once the engine keeps a host-side (mmap-backed) restore
        source — mesh-sharded models return False and are never
        eviction victims."""
        return self.engine is not None and self.engine.offloadable

    def offload(self) -> None:
        """Eviction body: drop device params, keep everything else
        (engine shell, compiled executables, batcher, host mmap
        params).  The model stays `ready` — the next predict faults it
        back in, in milliseconds."""
        if self.engine is not None:
            self.engine.offload()

    def demote(self) -> None:
        """Eviction body for models without a host restore source
        (param cache disabled, mesh-sharded params): drop the engine
        entirely.  The model stays registered and addressable; its
        next predict cold-faults a fresh build."""
        if self.engine is not None:
            self.engine.close()
            self.engine = None
        self.batcher = None

    def fault_in(self) -> None:
        """Warm fault body (blocking; residency executor): re-place
        the host params on device."""
        if self.engine is None:
            raise InferenceError(
                f"model {self.name} has no engine to fault in")
        self.engine.restore()

    def host_bytes(self) -> int:
        """HBM bytes a fault-in of this model will claim."""
        if self.engine is None:
            return 0
        return self.engine.host_param_bytes() or self.engine.param_bytes()

    @property
    def wire_dtype(self):
        """Dtype hint for the server's native V1 JSON parser: uint8
        models take integer image bodies straight to uint8 on the wire
        (tensorjson fast path)."""
        if self.config is not None and self.config.input_dtype == "uint8":
            return "u1"
        return None

    # -- inference ---------------------------------------------------------
    def _bucket_key(self, instance: Any):
        """Seq-bucket key: instances whose (padded) seq length lands in
        different buckets never share a batch."""
        arr = (next(iter(instance.values())) if isinstance(instance, dict)
               else instance)
        arr = np.asarray(arr)
        n = arr.shape[0] if arr.ndim else 1
        bucket = self.engine.seq_buckets.fit(n)
        if bucket is None:
            raise InvalidInput(
                f"sequence length {n} exceeds the largest bucket "
                f"{self.engine.seq_buckets.max}")
        return bucket

    async def _batch_handler(self, instances: List[Any], key=None) -> List[Any]:
        first = instances[0]
        if isinstance(first, dict):
            keys = list(first.keys())
            batch = {}
            for k in keys:
                rows = [np.asarray(inst[k]) for inst in instances]
                if key is not None:  # pad rows to the shared seq bucket
                    rows = [self._pad_seq(r, key) for r in rows]
                batch[k] = np.stack(rows)
            if "attention_mask" in batch:
                self._check_prefix_mask(batch["attention_mask"])
        else:
            rows = [np.asarray(inst) for inst in instances]
            lengths = [r.shape[0] if r.ndim else 1 for r in rows]
            if key is not None:
                rows = [self._pad_seq(r, key) for r in rows]
            batch = np.stack(rows)
            if self.config.input_dtype == "uint8":
                batch = batch.astype(np.uint8)
            if (isinstance(self._spec.example, dict)
                    and "attention_mask" in self._spec.example):
                # Canonicalize bare token rows to the dict signature the
                # model (and warmup) uses, with a synthesized padding
                # mask.  Two birds: seq-padding is no longer attended
                # to, and array requests share the warmed executable
                # instead of compiling a second signature at serve time.
                primary = next(iter(self._spec.example))
                mask = np.zeros(batch.shape[:2], np.int32)
                for i, n in enumerate(lengths):
                    mask[i, :n] = 1
                batch = {primary: batch, "attention_mask": mask}
        out = await self.engine.predict(batch)
        return self._scatter(out, len(instances))

    def _check_prefix_mask(self, mask: np.ndarray) -> None:
        """Models running with prefix_padding (the default for the BERT
        family) interpret attention_mask as suffix padding and serve it
        through the padding-aware flash kernel.  A non-suffix mask
        (e.g. left padding) would be SILENTLY wrong on that path, so
        reject it loudly here on the host — callers with arbitrary mask
        patterns set arch_kwargs.prefix_padding=false (XLA path)."""
        if not self.config.architecture.startswith("bert"):
            return  # other archs don't derive kv_lengths from the mask
        if not self.config.arch_kwargs.get("prefix_padding", True):
            return
        m = np.asarray(mask)
        if m.ndim != 2:
            return
        # suffix form == row values never increase (1s then 0s)
        if np.any(np.diff(m.astype(np.int8), axis=1) > 0):
            raise InvalidInput(
                "attention_mask is not suffix padding (1s then 0s); "
                "this model serves masks as sequence lengths "
                "(prefix_padding). Set arch_kwargs.prefix_padding=false "
                "in the model config to serve arbitrary mask patterns.")

    @staticmethod
    def _pad_seq(row: np.ndarray, bucket: int) -> np.ndarray:
        if row.shape[0] == bucket:
            return row
        pad = [(0, bucket - row.shape[0])] + [(0, 0)] * (row.ndim - 1)
        return np.pad(row, pad)

    @staticmethod
    def _scatter(out: Any, n: int) -> List[Any]:
        if isinstance(out, dict):
            parts = {k: np.asarray(v) for k, v in out.items()}
            return [{k: v[i] for k, v in parts.items()} for i in range(n)]
        arr = np.asarray(out)
        return [arr[i] for i in range(n)]

    async def predict(self, request: Any) -> Any:
        if self.predictor_host:
            return await super().predict(request)
        if self.residency is not None:
            # Demand-paged residency gate: count this request as
            # in-flight (never evict a model with queued work), fault
            # the model into HBM if needed (single-flight, transparent
            # to the caller), and touch the LRU ledger so victims
            # reflect use order.
            async with self.residency.serving(self.name):
                return await self._predict_resident(request)
        return await self._predict_resident(request)

    async def _predict_resident(self, request: Any) -> Any:
        if self.batcher is None:
            raise InferenceError(f"model {self.name} not loaded")
        if isinstance(request, InferRequest) or (
                isinstance(request, dict)
                and isinstance(request.get("inputs"), list)
                and request["inputs"]
                and isinstance(request["inputs"][0], dict)
                and "datatype" in request["inputs"][0]):
            return await self._predict_v2(request)
        instances = v1.get_instances(request)
        result = await self.batcher.submit(instances)
        preds = result.predictions
        # Uniform float32 predictions stay an ndarray so the server's
        # native codec serializes them in one pass (protocol/native.py).
        if preds and isinstance(preds[0], np.ndarray) \
                and preds[0].dtype == np.float32 \
                and all(p.shape == preds[0].shape for p in preds[1:]):
            return v1.make_response(np.stack(preds))
        return v1.make_response([_tolist(p) for p in preds])

    async def _predict_v2(self, request: Any) -> Dict[str, Any]:
        req = (request if isinstance(request, InferRequest)
               else InferRequest.from_dict(request))
        named = req.named_numpy()
        if len(named) == 1:
            batch = next(iter(named.values()))
            instances = [batch[i] for i in range(batch.shape[0])]
        else:
            n = next(iter(named.values())).shape[0]
            instances = [{k: v[i] for k, v in named.items()}
                         for i in range(n)]
        result = await self.batcher.submit(instances)
        preds = result.predictions
        if preds and isinstance(preds[0], dict):
            outputs = {k: np.stack([p[k] for p in preds])
                       for k in preds[0]}
        else:
            outputs = {"output_0": np.stack(preds)}
        return make_response(self.name, outputs, id=req.id)

    # -- metadata ----------------------------------------------------------
    def metadata(self) -> Dict[str, Any]:
        meta = super().metadata()
        if self.engine is not None and self.config is not None:
            meta["platform"] = "jax"
            meta["architecture"] = self.config.architecture
            meta["batch_buckets"] = list(self.engine.batch_buckets.buckets)
            if self.engine.seq_buckets:
                meta["seq_buckets"] = list(self.engine.seq_buckets.buckets)
            meta.update(self._signature_metadata())
        return meta

    def _signature_metadata(self) -> Dict[str, Any]:
        """V2 model-metadata inputs/outputs (required_api.md Model
        Metadata): shapes/dtypes from jax.eval_shape of the serving
        function — abstract evaluation, no device work.  Batch dim
        reports -1 (dynamic; buckets are an engine detail)."""
        try:
            import jax

            from kfserving_tpu.protocol.v2 import datatype_of

            spec = self._spec
            example = spec.example
            if isinstance(example, dict):
                example = {k: np.asarray(v) for k, v in example.items()}
                inputs = [{"name": k,
                           "datatype": datatype_of(np.asarray(v)),
                           "shape": [-1] + list(np.asarray(v).shape[1:])}
                          for k, v in example.items()]
            else:
                example = np.asarray(example)
                if self.config.input_dtype == "uint8":
                    example = example.astype(np.uint8)
                inputs = [{"name": "input_0",
                           "datatype": datatype_of(example),
                           "shape": [-1] + list(example.shape[1:])}]
            out = jax.eval_shape(
                lambda v, x: self.engine._jitted.__wrapped__(v, x)
                if hasattr(self.engine._jitted, "__wrapped__")
                else self.engine._jitted(v, x),
                self.engine.params, example)
            leaves = (out.items() if isinstance(out, dict)
                      else [("output_0", out)])
            outputs = [{"name": k,
                        "datatype": datatype_of(
                            np.empty(0, dtype=leaf.dtype)),
                        "shape": [-1] + list(leaf.shape[1:])}
                       for k, leaf in leaves]
            return {"inputs": inputs, "outputs": outputs}
        except Exception:  # metadata is best-effort, never fatal
            logger.debug("signature metadata unavailable", exc_info=True)
            return {}

    def engine_stats(self) -> Dict[str, Any]:
        stats = dict(self.engine.stats()) if self.engine else {}
        if self.batcher:
            stats.update({
                "batches_flushed": self.batcher.batches_flushed,
                "instances_batched": self.batcher.instances_batched,
            })
            if self.batcher.queue_age_ms:
                # Per-bucket flush-time queue age — exported as labeled
                # series on /metrics (starvation diagnostic).
                stats["bucket_queue_age_max_ms"] = {
                    str(k): v["max"]
                    for k, v in self.batcher.queue_age_ms.items()}
        return stats


def _tolist(x: Any) -> Any:
    if isinstance(x, dict):
        return {k: _tolist(v) for k, v in x.items()}
    arr = np.asarray(x)
    return arr.item() if arr.ndim == 0 else arr.tolist()
