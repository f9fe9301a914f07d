"""JaxEngine: the TPU execution runtime behind a served model.

The reference has no counterpart — it delegates accelerator execution to
third-party servers (TFServing/Triton; SURVEY.md §7.2).  This engine is the
new native heart:

- one jit-compiled executable per (batch-bucket, extra dynamic dims) shape,
  compiled against params already resident in HBM;
- requests are padded up to the nearest bucket and sliced back after;
- execution runs in a worker thread so the asyncio serving loop never blocks
  on device latency (`jax.block_until_ready` happens off-loop);
- optional sharded execution: params placed with a NamedSharding over a
  device mesh make every bucketed executable an SPMD program over ICI
  (tensor parallelism for models larger than one chip);
- warmup() pre-compiles all buckets so readiness gating can include compile
  time (SURVEY.md §5.3 cold-start mitigation), complementing the persistent
  XLA compilation cache (engine/compile_cache.py).
"""

import asyncio
import concurrent.futures
import contextvars
import itertools
import logging
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from kfserving_tpu.engine import compile_cache
from kfserving_tpu.engine.buckets import BucketPolicy
from kfserving_tpu.observability.profiling import TIMELINE
from kfserving_tpu.parallel.mesh import mesh_scope
from kfserving_tpu.reliability import sanitizer

logger = logging.getLogger("kfserving_tpu.engine")

# Monotonic engine ids for the sanitizer's recompile assertion:
# id(self) would recycle addresses across engine unload/load, making
# a fresh engine inherit its predecessor's warmup declaration.
_engine_seq = itertools.count()


def device_peak_flops() -> Optional[float]:
    """Peak dense-matmul FLOP/s of the serving chip (bf16), for MFU.

    Override with KFS_PEAK_FLOPS.  Returns None when unknown (CPU
    backend) — stats then omit the MFU line rather than fake it.
    """
    env = os.getenv("KFS_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    import jax

    kind = jax.devices()[0].device_kind.lower()
    for marker, peak in (("v5 lite", 197e12), ("v5e", 197e12),
                        ("v5p", 459e12), ("v6", 918e12),
                        ("v4", 275e12), ("v3", 123e12), ("v2", 45e12)):
        if marker in kind:
            return peak
    return None


def _params_on_single_device(jax, params) -> bool:
    """True when every param leaf lives on one device — then the engine
    issues an explicit async device_put so batch N+1's host->HBM
    transfer overlaps batch N's compute.  Mesh-sharded params skip the
    explicit put: jit handles SPMD placement."""
    try:
        for leaf in jax.tree.leaves(params):
            sharding = getattr(leaf, "sharding", None)
            device_set = getattr(sharding, "device_set", None)
            if device_set is not None and len(device_set) > 1:
                return False
        return True
    except Exception:
        return False


def _all_host_leaves(jax, params) -> bool:
    """True when every param leaf is a plain host ndarray (the
    mmap-view trees param_cache serves) — the precondition for
    offload()/restore() keeping a zero-copy restore source."""
    try:
        leaves = jax.tree.leaves(params)
        return bool(leaves) and all(
            isinstance(leaf, np.ndarray) for leaf in leaves)
    except Exception:
        return False


def _resize_seq(arr: np.ndarray, seq: int) -> np.ndarray:
    """Clip or tile a single instance's leading (sequence) axis to `seq`
    for warmup shape synthesis."""
    if arr.ndim == 0 or arr.shape[0] == seq:
        return arr
    if arr.shape[0] > seq:
        return arr[:seq]
    reps = (seq + arr.shape[0] - 1) // arr.shape[0]
    return np.concatenate([arr] * reps, axis=0)[:seq]


class JaxEngine:
    """Bucketed, padded, jit-compiled batch execution of `apply_fn(params, x)`.

    apply_fn: a traceable function of (params, batch_array) or
        (params, dict_of_batch_arrays) returning an array / pytree whose
        leading axis is the batch dimension.
    params: model parameters (pytree of jax arrays), already device_put
        (possibly with NamedSharding for multi-chip).
    batch_buckets: BucketPolicy for the leading batch dimension.
    seq_buckets: optional BucketPolicy for axis 1 (sequence length) — used by
        text models; images have static trailing dims.
    """

    def __init__(self, apply_fn: Callable, params: Any,
                 batch_buckets: Optional[BucketPolicy] = None,
                 seq_buckets: Optional[BucketPolicy] = None,
                 dtype: Optional[Any] = None,
                 pad_value: float = 0.0,
                 donate_inputs: bool = False,
                 pipeline_depth: int = 2,
                 blocking_stats: Optional[bool] = None,
                 param_source: Optional[str] = None,
                 mesh=None):
        import jax

        self._jax = jax
        self.params = params
        # The mesh the params are sharded over (None = one device):
        # programs trace and run inside it so the attention
        # dispatchers can shard_map their kernels (parallel/mesh.py).
        self.mesh = mesh
        # Host-side restore source for demand-paged residency
        # (engine/residency.py): when the param tree is entirely host
        # arrays (the mmap-backed views param_cache.load serves), keep
        # the reference — offload() can then drop the device copies and
        # restore() re-place them with one device_put, no re-
        # materialization and no recompile (jit caches by shape/dtype,
        # which a restore never changes).  Mesh-sharded trees are not
        # offloadable (jit owns their SPMD placement).
        self._host_params = (params if _all_host_leaves(jax, params)
                             else None)
        self.batch_buckets = batch_buckets or BucketPolicy.pow2(32)
        self.seq_buckets = seq_buckets
        self.dtype = dtype
        self.pad_value = pad_value
        # jax.jit caches one executable per padded shape signature; the
        # bucket policies bound how many signatures can exist.
        donate = (1,) if donate_inputs else ()
        self._jitted = jax.jit(apply_fn, donate_argnums=donate)
        # pipeline_depth worker threads: device execution is serialized per
        # chip, but the host->HBM transfer of batch N+1 overlaps the compute
        # and result fetch of batch N.  Depth 2 = classic double buffering.
        self._executor = concurrent.futures.ThreadPoolExecutor(
            max_workers=max(1, pipeline_depth),
            thread_name_prefix="jax-engine")
        # Telemetry (lock: _execute_sync runs on pipeline_depth threads)
        import threading

        self._stats_lock = threading.Lock()
        self.compile_count = 0
        self.execute_count = 0
        self.last_execute_ms = 0.0
        self.padded_waste_total = 0.0
        # Device-vs-host breakdown (VERDICT r1 #3): where a request's
        # milliseconds actually go, and achieved FLOP/s vs chip peak.
        self.prepare_ms_total = 0.0   # host: pad/stack/dtype
        self.device_ms_total = 0.0    # dispatch -> block_until_ready
        self.fetch_ms_total = 0.0     # device -> host slice
        self.flops_total = 0.0
        self._flops_by_bucket: Dict[Any, float] = {}
        # Per-(batch,seq)-bucket execution counts + padded-slot waste:
        # which compiled programs traffic actually lands on (seq-bucket
        # coverage is a bench deliverable, BASELINE config #3).
        self._bucket_hits: Dict[Any, int] = {}
        self._bucket_waste: Dict[Any, float] = {}
        self._slots_total = 0
        self._padded_slots_total = 0
        # Shapes this engine has dispatched before: the first dispatch
        # per (batch, seq) bucket pays jit trace+compile (a persistent-
        # XLA-cache hit still costs a load), later ones are cache hits
        # — the compile-cache counter series feeds off this.
        self._compiled_shapes: set = set()
        self._explicit_transfer = _params_on_single_device(jax, params)
        self._peak_flops = device_peak_flops()
        # One host<->device synchronization per batch, not two: the result
        # fetch (np.asarray) already waits for completion, and an explicit
        # block_until_ready first costs a *second* runtime round trip.  The
        # block is only worth paying when attributing device-vs-fetch time
        # (KFS_ENGINE_BLOCKING_STATS=1 or blocking_stats=True).
        if blocking_stats is None:
            blocking_stats = os.getenv(
                "KFS_ENGINE_BLOCKING_STATS", "") not in ("", "0", "false")
        self._blocking_stats = blocking_stats
        self.pipeline_depth = max(1, pipeline_depth)
        # Param provenance ("mmap" | "checkpoint" | "init" | None):
        # lets a scrape tell a mapped-warm successor from a replica
        # that paid full materialization: per-replica evidence that
        # the mmap cache actually engaged.
        self.param_source = param_source
        # Identity for the KFS_SANITIZE recompile assertion: each
        # engine declares its own warmup, so one engine warming never
        # flags another engine serving.  Process-monotonic (never an
        # address): a recycled id would hand a fresh engine its
        # predecessor's warmup declaration.
        self.sanitize_source = f"jax_engine:{next(_engine_seq)}"

    # -- shape plumbing ------------------------------------------------------
    def _pad_to_bucket(self, arr: np.ndarray) -> Tuple[np.ndarray, int]:
        """Pad leading (and optionally seq) dims to bucket sizes."""
        n = arr.shape[0]
        b = self.batch_buckets.fit(n)
        if b is None:
            raise ValueError(
                f"batch of {n} exceeds the largest compiled bucket "
                f"{self.batch_buckets.max}")
        pad = [(0, b - n)] + [(0, 0)] * (arr.ndim - 1)
        if self.seq_buckets is not None and arr.ndim >= 2:
            s = self.seq_buckets.fit(arr.shape[1])
            if s is None:
                raise ValueError(
                    f"sequence length {arr.shape[1]} exceeds the largest "
                    f"bucket {self.seq_buckets.max}")
            pad[1] = (0, s - arr.shape[1])
        if any(p[1] for p in pad):
            arr = np.pad(arr, pad, constant_values=self.pad_value)
        return arr, n

    def _prepare(self, inputs: Any) -> Tuple[Any, int]:
        if isinstance(inputs, dict):
            padded = {}
            n = None
            for k, v in inputs.items():
                arr = np.asarray(v)
                if self.dtype is not None and np.issubdtype(
                        arr.dtype, np.floating):
                    arr = arr.astype(self.dtype)
                p, n_k = self._pad_to_bucket(arr)
                padded[k] = p
                if n is None:
                    n = n_k
                elif n != n_k:
                    raise ValueError("inconsistent batch sizes across inputs")
            return padded, int(n)
        arr = np.asarray(inputs)
        if self.dtype is not None and np.issubdtype(arr.dtype, np.floating):
            arr = arr.astype(self.dtype)
        return self._pad_to_bucket(arr)

    # -- execution -----------------------------------------------------------
    def _execute_sync(self, inputs: Any) -> Any:
        from kfserving_tpu.reliability.deadline import check_deadline
        from kfserving_tpu.tracing import tracer

        # Last stop before device work: the caller's context (and so
        # its deadline) rode into this worker thread via ctx.run — an
        # over-budget request fails 504 here instead of occupying the
        # chip.  Batched executions carry no ambient deadline (the
        # batcher clears it; per-request budgets were settled at the
        # queue edge).
        check_deadline("engine dispatch")
        if self.params is None:
            # Offloaded by the residency manager and not faulted back
            # in: fail loudly — a half-loaded model must never serve
            # (the predict path's ensure_resident() gate is the only
            # legitimate way back to device residency).
            raise RuntimeError(
                "engine params are offloaded from the device "
                "(model is not HBM-resident)")
        with tracer.span("engine.execute") as span:
            t0 = time.perf_counter()
            padded, n = self._prepare(inputs)
            # A bucket warmup never visited (minimal-warmup recycle
            # successors warm only the largest) still records its cost
            # model on first execution — otherwise flops_total/MFU
            # silently collapse on exactly those replicas.
            if self._flops_key(padded) not in self._flops_by_bucket:
                self._record_flops(
                    padded.shape[0] if hasattr(padded, "shape")
                    else len(next(iter(padded.values()))), padded)
            t1 = time.perf_counter()
            if self._explicit_transfer:
                # Async H2D dispatch: with pipeline_depth worker threads,
                # this thread's transfer overlaps another thread's
                # in-flight compute (double buffering across the PCIe
                # hop).
                padded = self._jax.device_put(padded)
            t_transfer = time.perf_counter()
            with mesh_scope(self.mesh):
                out = self._jitted(self.params, padded)
            if self._blocking_stats:
                # Attribution mode: pay the extra sync so device_ms is
                # pure device time and fetch_ms pure D2H.
                out = self._jax.block_until_ready(out)
            t2 = time.perf_counter()
            # THE sanctioned result fetch: this executor thread is
            # where device results become host arrays by design.
            with sanitizer.sanctioned_fetch():
                result = self._jax.tree.map(
                    # kfslint: disable=host-sync — sanctioned fetch
                    # site: the engine's one D2H join, worker thread.
                    lambda a: np.asarray(a)[:n], out)
            t3 = time.perf_counter()
            first = (padded[next(iter(padded))]
                     if isinstance(padded, dict) else padded)
            bucket = first.shape[0]
            flops_key = self._flops_key(padded)
            span.update(batch=n, bucket=int(bucket),
                        prepare_ms=round((t1 - t0) * 1e3, 3),
                        device_ms=round((t2 - t1) * 1e3, 3),
                        fetch_ms=round((t3 - t2) * 1e3, 3))
            # Stage histograms, exemplared with the request's trace id
            # (the contextvar rode into this worker thread): the
            # fleet-wide view of where a request's milliseconds go.
            from kfserving_tpu.observability import metrics as obs
            from kfserving_tpu.tracing import current_request_id

            trace_id = current_request_id.get()
            stage_hist = obs.engine_stage_ms()
            for stage, ms in (("prepare", (t1 - t0) * 1e3),
                              ("transfer", (t_transfer - t1) * 1e3),
                              ("compute", (t2 - t_transfer) * 1e3),
                              ("fetch", (t3 - t2) * 1e3)):
                stage_hist.labels(stage=stage).observe(
                    ms, trace_id=trace_id)
            # Device-dispatch slice on the engine event timeline: the
            # dispatch -> host-visible-result span (pure device time
            # only under blocking_stats; otherwise it includes the
            # runtime round trip — same caveat as device_ms).
            TIMELINE.record("device", "engine.execute",
                            dur_s=t3 - t1, trace_id=trace_id,
                            attrs={"bucket": int(bucket), "batch": n})
            first_dispatch = False
            with self._stats_lock:
                if flops_key not in self._compiled_shapes:
                    self._compiled_shapes.add(flops_key)
                    first_dispatch = True
                    obs.compile_cache_events().labels(
                        outcome="miss").inc()
                    TIMELINE.record(
                        "host", "compile.miss", trace_id=trace_id,
                        attrs={"shape": str(flops_key)})
                else:
                    obs.compile_cache_events().labels(
                        outcome="hit").inc()
                # dispatch -> host-visible result (full device path)
                self.last_execute_ms = (t3 - t1) * 1000.0
                self.execute_count += 1
                self.padded_waste_total += (bucket - n) / bucket
                self.prepare_ms_total += (t1 - t0) * 1e3
                self.device_ms_total += (t2 - t1) * 1e3
                self.fetch_ms_total += (t3 - t2) * 1e3
                self.flops_total += self._flops_by_bucket.get(
                    flops_key, 0.0)
                self._bucket_hits[flops_key] = \
                    self._bucket_hits.get(flops_key, 0) + 1
                self._bucket_waste[flops_key] = \
                    self._bucket_waste.get(flops_key, 0.0) \
                    + (bucket - n) / bucket
                self._slots_total += bucket
                self._padded_slots_total += bucket - n
            if first_dispatch:
                # Sanitizer feed, OUTSIDE the stats lock: a recompile
                # violation's counter+pin work must not convoy the
                # other executor workers behind telemetry.
                compile_cache.note_compilation(self.sanitize_source,
                                               flops_key)
        return result

    async def predict(self, inputs: Any) -> Any:
        """Async batch predict: pads, executes on device off-loop, unpads.

        The caller's context (request-id contextvar) rides into the
        worker thread so engine spans attach to the request's trace.
        """
        loop = asyncio.get_running_loop()
        ctx = contextvars.copy_context()
        return await loop.run_in_executor(
            self._executor, ctx.run, self._execute_sync, inputs)

    def predict_sync(self, inputs: Any) -> Any:
        return self._execute_sync(inputs)

    # -- lifecycle -----------------------------------------------------------
    def warmup(self, example: Any, buckets: Optional[List[int]] = None,
               minimal: bool = False) -> float:
        """Pre-compile every executable a request can hit: all batch
        buckets x all seq buckets (sequence models without the full grid
        warm compile at serve time instead, which turns first requests
        into timeouts).
        Returns total compile seconds.  `example` is a single instance
        (no batch dim) as array or dict of arrays.

        minimal=True warms only the LARGEST batch bucket per seq
        bucket — the recycle-successor mode: the predecessor populated
        the persistent compile cache, so the remaining programs load
        on demand, where the full grid pays a dispatch round trip per
        program inside the successor's load time."""
        start = time.perf_counter()
        batch_buckets = buckets or self.batch_buckets.buckets
        if minimal:
            batch_buckets = [max(batch_buckets)]
        seq_buckets = (self.seq_buckets.buckets
                       if self.seq_buckets is not None else [None])

        def instance_at(seq):
            if seq is None:
                return example
            if isinstance(example, dict):
                return {k: _resize_seq(np.asarray(v), seq)
                        for k, v in example.items()}
            return _resize_seq(np.asarray(example), seq)

        for s in seq_buckets:
            inst = instance_at(s)
            for b in batch_buckets:
                if isinstance(inst, dict):
                    batch = {k: np.stack([np.asarray(v)] * b)
                             for k, v in inst.items()}
                else:
                    batch = np.stack([np.asarray(inst)] * b)
                self._execute_sync(batch)  # also records its flops
                self.compile_count += 1
        dt = time.perf_counter() - start
        # Full-grid warmup closes this engine's shape set: arm the
        # sanitizer's recompile assertion.  A minimal warmup
        # deliberately leaves programs to load on demand — those
        # late loads are the chosen trade, not violations, so the
        # source stays unarmed.
        if not minimal:
            compile_cache.declare_warmup_complete(
                self.sanitize_source)
        # Warmup executes exactly-full batches of every program; leaving
        # them in the traffic counters would report phantom bucket hits
        # and dilute slot_pad_waste toward 0 on short runs.  Timing /
        # MFU totals keep warmup (pre-existing semantics); the
        # batching-quality counters restart at zero.
        with self._stats_lock:
            self._bucket_hits.clear()
            self._bucket_waste.clear()
            self._slots_total = 0
            self._padded_slots_total = 0
        logger.info("warmup compiled %d batch x %d seq buckets in %.1fs",
                    len(batch_buckets), len(seq_buckets), dt)
        return dt

    def _flops_key(self, batch: Any):
        """Stats key: (batch bucket, seq bucket) — per-seq-bucket
        programs have different FLOPs and must not share an entry.
        Shape access only (never np.asarray: the batch may already live
        on device and a copy here would be a hidden D2H transfer)."""
        first = (batch[next(iter(batch))]
                 if isinstance(batch, dict) else batch)
        return (int(first.shape[0]),
                int(first.shape[1]) if self.seq_buckets is not None
                and getattr(first, "ndim", 0) >= 2 else None)

    def _record_flops(self, bucket: int, batch: Any) -> None:
        """XLA's cost model for this bucket's program (feeds the
        achieved-FLOP/s / MFU stats), read from the compiled
        executable: the installed TPU backend answers None for the
        lowered module's analysis and a dict for the executable's.
        Warmup already populated the persistent XLA cache for this
        shape, so the compile() is a cache load."""
        with mesh_scope(self.mesh):
            compiled = self._jitted.lower(self.params, batch).compile()
        # A program with no arithmetic has no "flops" entry.
        self._flops_by_bucket[self._flops_key(batch)] = float(
            compiled.cost_analysis().get("flops", 0.0))

    def param_bytes(self) -> int:
        """Total parameter bytes (HBM residency of this model's weights)."""
        leaves = self._jax.tree.leaves(self.params)
        return sum(getattr(x, "nbytes", 0) for x in leaves)

    def host_param_bytes(self) -> int:
        """Bytes the host-resident restore source would occupy in HBM
        (0 when this engine keeps no host tree — not offloadable)."""
        if self._host_params is None:
            return 0
        return sum(leaf.nbytes
                   for leaf in self._jax.tree.leaves(self._host_params))

    @property
    def offloadable(self) -> bool:
        return self._host_params is not None

    def offload(self) -> bool:
        """Drop the device param copies; the host (mmap-backed) tree
        stays as the restore source.  Returns False when this engine
        keeps no host tree (mesh-sharded params — never a residency
        victim).  The caller (residency manager) guarantees no
        execution is queued or in flight; a straggler that slips past
        fails fast on the params-None guard instead of dereferencing
        freed HBM."""
        if self._host_params is None:
            return False
        params, self.params = self.params, None
        if params is not None and params is not self._host_params:
            for leaf in self._jax.tree.leaves(params):
                delete = getattr(leaf, "delete", None)
                if delete is not None:
                    try:
                        delete()
                    except Exception:  # already deleted / host array
                        pass
        return True

    def restore(self) -> float:
        """Fault the params back into HBM off the host tree: one
        device_put of zero-copy mmap views, synchronized so the
        returned seconds are the true transfer cost.  No recompile —
        the jit cache keys on shapes/dtypes, which a restore never
        changes."""
        if self._host_params is None:
            raise RuntimeError(
                "engine keeps no host params to restore from")
        t0 = time.perf_counter()
        params = self._jax.device_put(self._host_params)
        params = self._jax.block_until_ready(params)
        self.params = params
        return time.perf_counter() - t0

    def close(self, wait: bool = True):
        """Release device references so HBM can be reclaimed.

        wait=True (default) quiesces first: in-flight executions on the
        worker threads finish before param buffers are deleted, so a
        concurrent predict never dereferences freed HBM.  Executions
        submitted after close() fail fast with RuntimeError (executor shut
        down) instead of touching deleted buffers.
        """
        self._executor.shutdown(wait=wait)
        for leaf in self._jax.tree.leaves(self.params):
            if hasattr(leaf, "delete"):
                try:
                    leaf.delete()
                except Exception:  # already deleted / cpu array
                    pass
        self.params = None

    def stats(self) -> Dict[str, float]:
        with self._stats_lock:
            n = self.execute_count
            out = {
                "execute_count": n,
                "compile_count": self.compile_count,
                "pipeline_depth": self.pipeline_depth,
                "last_execute_ms": self.last_execute_ms,
                "avg_pad_waste": (self.padded_waste_total / n
                                  if n else 0.0),
                # Slot-weighted companion: fraction of executed batch
                # SLOTS that were padding.  The unweighted mean above
                # over-counts small deadline flushes (a half-empty b4
                # and a half-empty b128 average the same there).
                "slot_pad_waste": (
                    self._padded_slots_total / self._slots_total
                    if self._slots_total else 0.0),
                "avg_prepare_ms": self.prepare_ms_total / n if n else 0.0,
                "avg_device_ms": self.device_ms_total / n if n else 0.0,
                "avg_fetch_ms": self.fetch_ms_total / n if n else 0.0,
                "blocking_stats": self._blocking_stats,
            }
            if self.param_source is not None:
                out["param_source"] = self.param_source
            # In the default non-blocking mode device_ms is just async
            # dispatch; device work completes inside the fetch wait, so
            # MFU divides by their sum (a floor on true utilization —
            # the sum includes the runtime round trip).
            device_s = (self.device_ms_total
                        if self._blocking_stats
                        else self.device_ms_total
                        + self.fetch_ms_total) / 1e3
            if self.flops_total > 0 and device_s > 0:
                achieved = self.flops_total / device_s
                out["achieved_tflops"] = achieved / 1e12
                if self._peak_flops:
                    out["mfu"] = achieved / self._peak_flops
            if self._bucket_hits:
                out["bucket_hits"] = {
                    (f"b{b}" if s is None else f"b{b}s{s}"): hits
                    for (b, s), hits in sorted(self._bucket_hits.items())}
                out["bucket_pad_waste"] = {
                    (f"b{b}" if s is None else f"b{b}s{s}"):
                        round(waste / self._bucket_hits[key], 4)
                    for key, waste in sorted(self._bucket_waste.items())
                    for b, s in [key]}
        return out
