"""The served parameters, as the server's parameter cache holds them on disk.

The servers materialise their seeded parameters once and persist them as
`<dir>/<digest>/manifest.json` + `params.bin` (kfserving_tpu/engine/
param_cache.py documents the layout); the reference maps the same bytes, so
both sides compute with the same weights by construction.  A run gives each
configuration a cache directory of its own, so it holds one entry.
"""

import glob
import json
import os

import numpy as np


def load(cache_dir: str) -> dict:
    """{"a/b/c": array} over every leaf of the one entry in `cache_dir`."""
    manifests = glob.glob(os.path.join(cache_dir, "*", "manifest.json"))
    if len(manifests) != 1:
        raise FileNotFoundError(
            f"{cache_dir} holds {len(manifests)} parameter entries, not one")
    with open(manifests[0]) as f:
        manifest = json.load(f)
    data = np.memmap(os.path.join(os.path.dirname(manifests[0]),
                                  "params.bin"), dtype=np.uint8, mode="r")
    leaves = {}
    for leaf in manifest["leaves"]:
        raw = data[leaf["offset"]:leaf["offset"] + leaf["nbytes"]]
        leaves["/".join(leaf["path"])] = np.asarray(raw).view(
            np.dtype(leaf["dtype"])).reshape(leaf["shape"])
    return leaves
