"""What a save must give back: leaf fingerprints, and an independent reader
of the chunked array files (codec v1, uncompressed) that tiers hold.

A fingerprint of a leaf is two int32 sums over its elements' bit patterns
(bf16 as 16-bit words, float32/int32 as 32-bit words), both wrapping mod
2**32: the plain sum and the sum weighted by position.  The same jitted
function fingerprints the live state on the device at a save and the bytes
read back from each tier, so the two are compared exactly.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

MAGIC = b"CRFT"


def _words(x):
    if x.dtype == jnp.bfloat16 or x.dtype == jnp.float16:
        w = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.int32)
    elif x.dtype.itemsize == 4:
        w = jax.lax.bitcast_convert_type(x, jnp.int32)
    else:
        raise TypeError(f"no fingerprint for dtype {x.dtype}")
    return w.reshape(-1)


def _leaf_fingerprint(x):
    w = _words(x)
    pos = jnp.arange(1, w.shape[0] + 1, dtype=jnp.int32)
    return jnp.stack([jnp.sum(w), jnp.sum(w * pos)])


@jax.jit
def fingerprint(tree):
    """(n_leaves, 2) int32 fingerprints of a pytree's leaves, in flatten
    order."""
    return jnp.stack([_leaf_fingerprint(x)
                      for x in jax.tree_util.tree_leaves(tree)])


def read_v1(path: Path) -> np.ndarray:
    """Decode one array file written by the chunked codec (fmt 1) without
    compression: magic, u64 header length, JSON header, then the chunks'
    stored bytes back to back."""
    import ml_dtypes  # noqa: F401  (registers bfloat16 with numpy)

    raw = Path(path).read_bytes()
    if raw[:4] != MAGIC:
        raise ValueError(f"{path}: bad magic")
    hlen = int.from_bytes(raw[4:12], "little")
    header = json.loads(raw[12:12 + hlen])
    if header.get("fmt") != 1 or header.get("compress") != "none":
        raise ValueError(f"{path}: not an uncompressed v1 file: "
                         f"fmt={header.get('fmt')} "
                         f"compress={header.get('compress')}")
    payload = raw[12 + hlen:]
    stored = sum(c["clen"] for c in header["chunks"])
    if stored != len(payload) or stored != header["nbytes"]:
        raise ValueError(f"{path}: {len(payload)} payload bytes, header "
                         f"says {stored} stored and {header['nbytes']} raw")
    name = header["dtype"]
    dtype = np.dtype(getattr(ml_dtypes, name, name))
    return np.frombuffer(payload, dtype=dtype).reshape(header["shape"])


def read_state_tree(version_dir: Path, key: str, treedef):
    """The pytree checkpointable ``key`` of one version directory, rebuilt
    from its manifest (one unsharded file per leaf) onto ``treedef``."""
    item = Path(version_dir) / key
    manifest = json.loads((item / "tree-0.json").read_text())
    leaves = []
    for spec in manifest["leaves"]:
        if spec["kind"] != "jax" or len(spec["shards"]) != 1:
            raise ValueError(f"{item}: expected one shard per jax leaf, got "
                             f"{spec}")
        arr = read_v1(item / spec["shards"][0]["file"])
        if list(arr.shape) != spec["global_shape"]:
            raise ValueError(f"{item}: leaf shape {arr.shape} != "
                             f"{spec['global_shape']}")
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)
