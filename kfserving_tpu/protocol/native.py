"""Native codec loader + pure-Python fallback.

The C extension (csrc/tensorjson.c) parses dense V1 predict bodies into
contiguous float32 buffers and dumps prediction tensors back to JSON in
one pass.  This wrapper:

- loads `_tensorjson` from csrc/ when built (csrc/setup.py), else exposes
  the same API in pure Python;
- returns numpy views over the parsed buffer (zero-copy reshape).

Fast path eligibility is decided by the caller (server/dataplane): dense
numeric bodies only; anything else (dicts, strings, V2 tensor objects,
CloudEvents) takes the json.loads route unchanged.
"""

import json
import logging
import os
import sys
from typing import Optional, Tuple

import numpy as np

logger = logging.getLogger("kfserving_tpu.native")

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")

_native = None


def _load():
    """Import the extension once; log once, at WARNING, which codec
    serves and why — a machine built from what git holds has no .so
    until `build()` runs, and the pure-Python codec is several times
    slower on the V1 JSON wire."""
    global _native
    if _native is not None:
        return _native
    if _CSRC not in sys.path:
        sys.path.insert(0, _CSRC)
    try:
        import _tensorjson  # type: ignore

        # API probe: parse_v1 must report extra top-level keys (5-tuple)
        # AND accept the dtype hint (2-arg form).  A stale prebuilt .so
        # with either older API would drop keys or raise TypeError on
        # every hinted call, so refuse it.
        probe = _tensorjson.parse_v1(b'{"instances": [1], "x": 1}',
                                     "u1")
        reason = None if len(probe) == 5 else \
            "stale extension (no extra-keys flag)"
    except TypeError:
        reason = "stale extension (no dtype-hint arg)"
    except (ImportError, ValueError) as exc:
        reason = f"extension not loadable ({exc})"
    if reason is None:
        _native = _tensorjson
        logger.warning("tensorjson codec=native (%s)",
                       _tensorjson.__file__)
    else:
        _native = False
        logger.warning("tensorjson codec=python: %s — build it with "
                       "native.build(force=True)", reason)
    return _native


def build(force: bool = False) -> bool:
    """Compile the extension in-place (used by tests/deploy scripts
    and chip_smoke.py); False when the compiler fails."""
    import glob
    import subprocess

    if not force and glob.glob(os.path.join(_CSRC, "_tensorjson*.so")):
        return True
    try:
        subprocess.run(
            [sys.executable, os.path.join(_CSRC, "setup.py")],
            cwd=_CSRC, check=True, capture_output=True, timeout=120)
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            OSError) as e:
        logger.warning("native build failed: %s", e)
        return False
    global _native
    _native = None  # re-probe
    return bool(_load())


def available() -> bool:
    return bool(_load())


_DTYPES = {"u1": np.uint8, "i4": np.int32, "f4": np.float32}


def parse_v1(body: bytes, hint: Optional[str] = None
             ) -> Optional[Tuple[np.ndarray, str]]:
    """Parse a dense V1 body -> (array, key) or None if ineligible.

    hint="u1" (the served model's declared uint8 wire dtype) parses
    integer image bodies straight into uint8 — no int32 intermediate,
    no astype copy downstream.  The hint is advisory: values outside
    [0, 255] emit the normal i4/f4 and the model's own cast handles it.

    Never raises for non-dense bodies: the caller falls back to
    json.loads.
    """
    mod = _load()
    if mod:
        try:
            out = mod.parse_v1(body, hint)
        except ValueError:
            return None
        data, shape, key, dtype, extra = out
        if extra:
            # Body carries other top-level keys (parameters,
            # signature_name, custom fields): a {key: arr} dict would
            # silently drop them before model.preprocess, so fall back
            # to the full json.loads decode.
            return None
        arr = np.frombuffer(data, dtype=_DTYPES[dtype]).reshape(shape)
        return arr, key
    return _parse_v1_py(body, hint)


def _parse_v1_py(body: bytes, hint: Optional[str] = None
                 ) -> Optional[Tuple[np.ndarray, str]]:
    """Pure-Python fallback with identical eligibility rules."""
    try:
        obj = json.loads(body)
    except ValueError:
        return None
    if not isinstance(obj, dict):
        return None
    key = ("instances" if "instances" in obj
           else "inputs" if "inputs" in obj else None)
    if key is None or not isinstance(obj[key], list):
        return None
    if len(obj) > 1:
        # Extra top-level keys must survive to model.preprocess; the
        # {key: arr} fast-path shape would drop them.
        return None
    try:
        arr = np.asarray(obj[key])
    except (ValueError, TypeError):
        return None
    if arr.ndim == 0 or arr.dtype == object:
        return None
    if np.issubdtype(arr.dtype, np.integer):
        if arr.size and (np.abs(arr) > np.iinfo(np.int32).max).any():
            arr = arr.astype(np.float32)
        elif hint == "u1" and (not arr.size or
                               (arr.min() >= 0 and arr.max() <= 255)):
            arr = arr.astype(np.uint8)
        else:
            arr = arr.astype(np.int32)
    elif np.issubdtype(arr.dtype, np.floating):
        arr = arr.astype(np.float32)
    else:
        return None
    return arr, key


def dump_f32(arr: np.ndarray) -> bytes:
    """Serialize a float tensor as a JSON array (bytes)."""
    arr = np.ascontiguousarray(arr, dtype=np.float32)
    mod = _load()
    if mod:
        return mod.dump_f32(arr.tobytes(), tuple(arr.shape))
    return json.dumps(arr.tolist()).encode()


def dump_response(body) -> Optional[bytes]:
    """Fast-serialize `{"predictions": <float32 ndarray>}` responses.

    Returns None when ineligible (other keys, non-array, non-float32 —
    integer class labels must round-trip as ints, not "1.0").
    """
    if not isinstance(body, dict) or set(body) != {"predictions"}:
        return None
    arr = body["predictions"]
    if not isinstance(arr, np.ndarray) or arr.dtype != np.float32 \
            or arr.ndim == 0:
        return None
    return b'{"predictions": ' + dump_f32(arr) + b"}"
