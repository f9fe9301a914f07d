"""Host-side mmap-able parameter cache: successors map, never re-init.

Without it every recycle successor spends its load time in
`init_params` — re-materializing weights (jitted random init + flax
checkpoint deserialization, both full host copies) that an identical
predecessor process materialized seconds earlier.  The
pod-world has no answer to this (every container restart re-reads the
checkpoint); a single-host fabric does: persist the materialized
variables once, in a layout `np.memmap` can serve, and every successor
maps the SAME page-cache-resident bytes and goes straight to the
device transfer.  This is the load-fully-warm half of
TensorFlow-Serving's aspired-versions lifecycle (arxiv 1712.06139)
applied to process recycling.

Cache layout (one entry per content digest):

    <cache_dir>/<digest>/manifest.json   leaf paths, dtypes, shapes,
                                         byte offsets into params.bin
    <cache_dir>/<digest>/params.bin      all leaves, page-aligned

The digest keys the *content* that determines the materialized
variables: architecture + arch_kwargs + init seed + the checkpoint
file's digest (the artifact's shipped `*.sha256` when present, else a
full file hash).  A new checkpoint or changed config therefore misses
— invalidation is by construction, never by mtime heuristics.

Entries are written atomically (temp dir + rename), loads are
zero-copy views over one read-only memmap, and every outcome lands in
`kfserving_tpu_param_cache_total{outcome=hit|miss|store|error}`.
Knobs: `KFS_PARAM_CACHE` (directory; `0`/`off` disables).
"""

import hashlib
import json
import logging
import os
import shutil
import tempfile
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np

logger = logging.getLogger("kfserving_tpu.param_cache")

ENV_VAR = "KFS_PARAM_CACHE"
DEFAULT_CACHE_DIR = os.path.expanduser("~/.cache/kfserving_tpu/params")
MANIFEST_NAME = "manifest.json"
DATA_NAME = "params.bin"
MANIFEST_VERSION = 1
# Leaf offsets align to the page size so a mapped leaf never shares a
# page with its neighbor's tail (and device DMA gets aligned sources).
_ALIGN = 4096


def cache_dir() -> Optional[str]:
    """The active cache directory, or None when disabled."""
    value = os.environ.get(ENV_VAR, "")
    if value.lower() in ("0", "off", "false", "disabled"):
        return None
    return value or DEFAULT_CACHE_DIR


def _observe(outcome: str) -> None:
    try:
        from kfserving_tpu.observability import metrics as obs

        obs.param_cache_total().labels(outcome=outcome).inc()
    except Exception:  # telemetry must never fail a load
        logger.debug("param-cache metric emit failed", exc_info=True)


def file_digest(path: str) -> str:
    """Digest of a checkpoint file.  Prefers the artifact's shipped
    `<path>.sha256` sidecar (storage verified it at download, and
    re-hashing a multi-GB checkpoint on every boot would give back a
    slice of the very seconds this cache exists to save)."""
    sidecar = path + ".sha256"
    try:
        with open(sidecar) as f:
            token = f.read().split()[0].strip()
        if token:
            return token
    except (OSError, IndexError):
        pass
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def content_key(architecture: str, arch_kwargs: Optional[Dict],
                seed: int = 0,
                checkpoint_digest: Optional[str] = None) -> str:
    """Digest over everything that determines the materialized
    variables — two deployments agreeing on this key may share bytes."""
    blob = json.dumps({
        "architecture": architecture,
        "arch_kwargs": arch_kwargs or {},
        "seed": seed,
        "checkpoint": checkpoint_digest or "none",
        "version": MANIFEST_VERSION,
    }, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:24]


def _flatten(tree: Any, prefix: Tuple[str, ...] = ()
             ) -> Iterator[Tuple[Tuple[str, ...], Any]]:
    """Depth-first (path, leaf) pairs of a nested-dict pytree.  Only
    dicts recurse: any other container is treated as a leaf, and a
    non-arrayable leaf fails the store's try (those trees are simply
    not cached — the flax variable trees this serves are plain nested
    dicts of arrays)."""
    for key in sorted(tree):
        value = tree[key]
        if isinstance(value, dict):
            yield from _flatten(value, prefix + (str(key),))
        else:
            yield prefix + (str(key),), value


def _unflatten(leaves: List[Tuple[Tuple[str, ...], Any]]) -> Dict:
    tree: Dict = {}
    for path, leaf in leaves:
        node = tree
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    return tree


def _resolve_dtype(name: str) -> np.dtype:
    """numpy dtype by name, falling through to ml_dtypes for the
    accelerator types numpy doesn't know (bfloat16, float8_*)."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def store(key: str, variables: Any) -> bool:
    """Persist a materialized variable tree under `key`.  Best-effort:
    returns False (and counts `error`) on any failure — a broken cache
    write must never take down a load that already succeeded."""
    root = cache_dir()
    if root is None or not isinstance(variables, dict):
        return False
    entry = os.path.join(root, key)
    if os.path.exists(os.path.join(entry, MANIFEST_NAME)):
        return True  # a concurrent successor already wrote it
    try:
        leaves = list(_flatten(variables))
        manifest: List[Dict[str, Any]] = []
        offset = 0
        arrays = []
        for path, leaf in leaves:
            arr = np.ascontiguousarray(np.asarray(leaf))
            offset = (offset + _ALIGN - 1) // _ALIGN * _ALIGN
            manifest.append({
                "path": list(path),
                "dtype": arr.dtype.name,
                "shape": list(arr.shape),
                "offset": offset,
                "nbytes": int(arr.nbytes),
            })
            arrays.append((offset, arr))
            offset += arr.nbytes
        os.makedirs(root, exist_ok=True)
        tmp = tempfile.mkdtemp(prefix=f".{key}-", dir=root)
        try:
            with open(os.path.join(tmp, DATA_NAME), "wb") as f:
                for off, arr in arrays:
                    f.seek(off)
                    f.write(arr.tobytes())
                f.flush()
                os.fsync(f.fileno())
            with open(os.path.join(tmp, MANIFEST_NAME), "w") as f:
                json.dump({"version": MANIFEST_VERSION,
                           "total_bytes": offset,
                           "leaves": manifest}, f)
            # Atomic publish: readers see either nothing or a complete
            # entry (rename fails if a racing writer won — their entry
            # is byte-identical, so losing is fine).
            try:
                os.rename(tmp, entry)
            except OSError:
                shutil.rmtree(tmp, ignore_errors=True)
        except Exception:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
    except Exception:
        logger.warning("param-cache store of %s failed", key,
                       exc_info=True)
        _observe("error")
        return False
    _observe("store")
    logger.info("param cache stored %s (%d leaves, %.1f MB)",
                key, len(manifest), offset / 1e6)
    return True


def load(key: str) -> Optional[Dict]:
    """Map a cached variable tree: one read-only memmap of params.bin,
    every leaf a zero-copy view into it.  None on miss or any
    corruption (a corrupt entry is deleted so the next boot re-stores
    it)."""
    root = cache_dir()
    if root is None:
        return None
    entry = os.path.join(root, key)
    manifest_path = os.path.join(entry, MANIFEST_NAME)
    if not os.path.exists(manifest_path):
        _observe("miss")
        return None
    try:
        with open(manifest_path) as f:
            manifest = json.load(f)
        if manifest.get("version") != MANIFEST_VERSION:
            raise ValueError(
                f"manifest version {manifest.get('version')}")
        data = np.memmap(os.path.join(entry, DATA_NAME),
                         dtype=np.uint8, mode="r")
        leaves = []
        for leaf in manifest["leaves"]:
            off, nbytes = leaf["offset"], leaf["nbytes"]
            if off + nbytes > data.size:
                raise ValueError(
                    f"leaf {leaf['path']} overruns params.bin")
            arr = (np.asarray(data[off:off + nbytes])
                   .view(_resolve_dtype(leaf["dtype"]))
                   .reshape(leaf["shape"]))
            leaves.append((tuple(leaf["path"]), arr))
    except Exception:
        logger.warning("param cache entry %s is corrupt; deleting",
                       key, exc_info=True)
        shutil.rmtree(entry, ignore_errors=True)
        _observe("error")
        return None
    _observe("hit")
    logger.info("param cache hit %s (%d leaves, %.1f MB mapped)",
                key, len(leaves), manifest["total_bytes"] / 1e6)
    return _unflatten(leaves)


def load_or_materialize(architecture: str, arch_kwargs: Optional[Dict],
                        spec, local_dir: str,
                        checkpoint_name: str = "checkpoint.msgpack",
                        seed: int = 0) -> Tuple[Dict, str]:
    """The shared predictor load path: (variables, source) where source
    is "mmap" (cache hit — successor skipped materialization
    entirely), "checkpoint" (init + restore, then stored), or "init"
    (random weights, then stored).

    On a hit the arrays are read-only memmap views: HOST arrays.  A
    jitted call transfers a host argument on every launch, so the
    consumer places them once (`place_on_device`, below) and launches
    with the device tree; the host cost of a successor's param phase
    collapses to page-cache reads feeding that one transfer.
    """
    from kfserving_tpu import startup
    from kfserving_tpu.models import init_params

    ckpt_path = os.path.join(local_dir, checkpoint_name)
    ckpt_digest = (file_digest(ckpt_path)
                   if os.path.exists(ckpt_path) else None)
    key = content_key(architecture, arch_kwargs, seed=seed,
                      checkpoint_digest=ckpt_digest)
    cached = load(key)
    if cached is not None:
        startup.mark("params_mmap")
        return cached, "mmap"
    variables = init_params(spec, seed=seed)
    startup.mark("init_params")
    source = "init"
    if ckpt_digest is not None:
        from flax import serialization

        with open(ckpt_path, "rb") as f:
            variables = serialization.from_bytes(variables, f.read())
        logger.info("restored checkpoint %s", ckpt_path)
        startup.mark("checkpoint_restore")
        source = "checkpoint"
    else:
        logger.warning("no checkpoint at %s; serving random init",
                       ckpt_path)
    # Jax arrays (init output) convert to host np arrays inside
    # store().  After a successful store, serve the MAPPED bytes we
    # just wrote rather than the in-process copies: the residency
    # manager needs a host-side (mmap) restore source to demand-page
    # this model in and out of HBM, and the page cache shares the
    # bytes with every successor.  A failed re-load (racing writer,
    # disabled cache) falls back to the in-process copies — the load
    # itself must never depend on the cache.
    if isinstance(variables, dict) and store(key, variables):
        startup.mark("param_cache_store")
        mapped = load(key)
        if mapped is not None:
            return mapped, source
    return variables, source


def _narrows(stored, read) -> bool:
    """A float leaf stored wider than it is read."""
    import jax.numpy as jnp

    return (jnp.issubdtype(stored, jnp.floating)
            and jnp.issubdtype(read, jnp.floating)
            and np.dtype(read).itemsize < np.dtype(stored).itemsize)


def place_on_device(tree: Any, sharding=None, dtypes: Any = None) -> Any:
    """`tree` with every host leaf (`np.ndarray`, so the memmap views
    above too) put on the device in ONE `jax.device_put`, waited for
    so the caller's time-to-ready includes the transfer.  Leaves that
    are already `jax.Array`s stay where they are, shardings included
    (a `shard_params` tree passes through).

    sharding: where host leaves go; None is the default device,
        uncommitted, like a `jnp.zeros` allocation.
    dtypes: a tree like `tree` of the dtype the model's programs read
        each leaf in (a config's `resident_dtypes`), or None.  A float
        leaf stored wider than it is read rests in the read dtype: the
        programs would convert it on every call otherwise, and nothing
        else reads it.  It is narrowed on its devices, by the `convert`
        the programs would have run (round to nearest even), and the
        wide copy this call put there is freed at once; a
        `ShapeDtypeStruct` narrows as a shape.  (Narrowing the mapped
        pages on the host first would halve the transfer and was no
        faster on the chip's machines: PERF.md, PR 42.)  The stored
        bytes (the cache entry, the caller's tree) are not touched.
    """
    import jax

    leaves, treedef = jax.tree.flatten(tree)
    host = [i for i, leaf in enumerate(leaves)
            if isinstance(leaf, np.ndarray)]
    if host:
        placed = jax.device_put([leaves[i] for i in host], sharding)
        for i, leaf in zip(host, placed):
            leaves[i] = leaf
    if dtypes is not None:
        for i, read in enumerate(treedef.flatten_up_to(dtypes)):
            leaf = leaves[i]
            if not _narrows(leaf.dtype, read):
                continue
            if isinstance(leaf, jax.ShapeDtypeStruct):
                leaves[i] = jax.ShapeDtypeStruct(
                    leaf.shape, read, sharding=leaf.sharding)
            else:
                leaves[i] = leaf.astype(read)
                if i in host:
                    leaf.delete()
    return jax.block_until_ready(jax.tree.unflatten(treedef, leaves))


def narrowed(stored: Any, placed: Any) -> Tuple[int, int]:
    """(leaves, bytes) that `place_on_device` narrowed: the leaves of
    `placed` whose dtype is not their `stored` twin's, and the bytes
    that saved."""
    import jax

    saved = [a.size * (a.dtype.itemsize - b.dtype.itemsize)
             for a, b in zip(jax.tree.leaves(stored),
                             jax.tree.leaves(placed))
             if a.dtype != b.dtype]
    return len(saved), sum(saved)


def device_resident_bytes(tree: Any) -> int:
    """Bytes of `tree`'s leaves that are device arrays (global shapes:
    a sharded leaf counts once)."""
    import jax

    return sum(leaf.nbytes for leaf in jax.tree.leaves(tree)
               if isinstance(leaf, jax.Array))
