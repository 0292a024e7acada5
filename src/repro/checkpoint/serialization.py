"""Pytree (de)serialization to per-leaf .npy files + a JSON manifest.

bfloat16 leaves are stored as uint16 bit patterns (numpy-portable) with
the logical dtype recorded in the manifest. Every shard file carries a
crc32 — folded incrementally while the bytes stream out, not computed
over a staged ``BytesIO`` copy — so restore can verify integrity after a
crash or partial flush without save ever holding a serialized duplicate
of a leaf in memory.

Sharded leaves: a jax.Array's host snapshot covers only the shards this
process addresses with ``replica_id == 0``, so on a multi-host mesh each
shard is written exactly once cluster-wide (no N×-duplicated replicated
leaves). A leaf then appears in the manifest as a list of shard files
with their global index ranges; restore reassembles them. Single-shard
leaves keep the seed's flat ``file``/``crc32`` manifest keys, so old
checkpoints load unchanged.
"""

from __future__ import annotations

import io
import json
import zlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _to_numpy(x) -> tuple[np.ndarray, str]:
    arr = np.asarray(x)
    logical = str(arr.dtype)
    if logical == "bfloat16":
        return arr.view(np.uint16), "bfloat16"
    return arr, logical


def _from_numpy(arr: np.ndarray, logical: str) -> np.ndarray:
    if logical == "bfloat16":
        import ml_dtypes

        return arr.view(ml_dtypes.bfloat16)
    return arr


class _CRC32Writer:
    """File-object shim: streams writes through to ``f`` while folding
    each chunk into a running crc32. ``np.save`` onto a non-file object
    writes the payload in bounded buffered chunks, so neither the
    serialized leaf nor its checksum input is ever fully materialized."""

    def __init__(self, f):
        self._f = f
        self.crc = 0
        self.nbytes = 0

    def write(self, b) -> int:
        self._f.write(b)
        self.crc = zlib.crc32(b, self.crc)
        self.nbytes += len(b)
        return len(b)


def _shard_index(shard, shape) -> list[list[int]] | None:
    """JSON-able ``[[start, stop], ...]`` per dim, or None when the shard
    covers the whole (or 0-d) array."""
    if not shape:
        return None
    out = []
    full = True
    for sl, dim in zip(shard.index, shape):
        start, stop, _ = sl.indices(dim)
        out.append([start, stop])
        if start != 0 or stop != dim:
            full = False
    return None if full else out


def _snapshot_leaf(leaf) -> tuple[tuple, str, list]:
    """Device->host snapshot of the parts of ``leaf`` this process must
    write. Returns (global_shape, logical_dtype, [(index, host_arr)]):
    one entry per addressable shard with replica_id 0 (each shard of a
    sharded/replicated array is written by exactly one process), or the
    whole array for plain host values."""
    shards = getattr(leaf, "addressable_shards", None)
    if shards:
        shape = tuple(leaf.shape)
        logical = str(leaf.dtype)
        parts = []
        for s in shards:
            if s.replica_id != 0:
                continue
            arr, logical = _to_numpy(s.data)
            parts.append((_shard_index(s, shape), arr))
        return shape, logical, parts
    arr, logical = _to_numpy(leaf)
    return tuple(arr.shape), logical, [(None, arr)]


def snapshot_tree(tree) -> tuple[dict, list]:
    """Snapshot every leaf to host memory (the only device-blocking part
    of a save). Returns ``(manifest, jobs)`` where each job is
    ``(fname, host_array, shard_entry)`` still to be written —
    ``write_leaf`` fills the entry's ``crc32``/``bytes`` in place, so the
    manifest is complete once every job ran."""
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    manifest: dict[str, Any] = {"leaves": {}}
    jobs = []
    for i, (path, leaf) in enumerate(leaves_with_paths):
        key = _path_str(path)
        shape, logical, parts = _snapshot_leaf(leaf)
        meta: dict[str, Any] = {
            "shape": list(shape),
            "dtype": logical,
            "shards": [],
        }
        single = len(parts) == 1 and parts[0][0] is None
        for j, (idx, arr) in enumerate(parts):
            fname = f"{i:05d}.npy" if single else f"{i:05d}.s{j:02d}.npy"
            entry = {"file": fname, "index": idx, "crc32": None, "bytes": None}
            meta["shards"].append(entry)
            jobs.append((fname, arr, entry))
        manifest["leaves"][key] = meta
    return manifest, jobs


def write_leaf(path: str, arr: np.ndarray,
               open_fn: Callable = open) -> tuple[int, int]:
    """Stream one host array to ``path`` as .npy; returns (crc32, bytes)."""
    with open_fn(path, "wb") as f:
        w = _CRC32Writer(f)
        np.save(w, arr, allow_pickle=False)
    return w.crc & 0xFFFFFFFF, w.nbytes


def write_manifest(manifest: dict, dirpath: str,
                   open_fn: Callable = open) -> None:
    """Commit the manifest (leaf writes must have completed). Leaves with
    one whole-array shard mirror the seed's flat ``file``/``crc32``/
    ``bytes`` keys for backward compatibility."""
    for meta in manifest["leaves"].values():
        sh = meta.get("shards") or []
        if len(sh) == 1 and sh[0]["index"] is None:
            meta["file"] = sh[0]["file"]
            meta["crc32"] = sh[0]["crc32"]
            meta["bytes"] = sh[0]["bytes"]
    with open_fn(f"{dirpath}/manifest.json", "w") as f:
        json.dump(manifest, f)


def save_tree(tree, dirpath: str, open_fn: Callable = open,
              makedirs_fn: Callable | None = None) -> dict:
    """Write every leaf to ``dirpath/<idx>.npy``; returns the manifest.
    (Serial convenience path — CheckpointManager fans the same jobs
    through the transfer-engine pool instead.)"""
    if makedirs_fn is not None:
        makedirs_fn(dirpath, exist_ok=True)
    manifest, jobs = snapshot_tree(tree)
    for fname, arr, entry in jobs:
        crc, n = write_leaf(f"{dirpath}/{fname}", arr, open_fn)
        entry["crc32"], entry["bytes"] = crc, n
    write_manifest(manifest, dirpath, open_fn)
    return manifest


def load_manifest(dirpath: str, open_fn: Callable = open) -> dict:
    with open_fn(f"{dirpath}/manifest.json", "r") as f:
        return json.load(f)


def read_leaf(dirpath: str, key: str, meta: dict, open_fn: Callable = open,
              verify: bool = True) -> np.ndarray:
    """Read + verify + reassemble one leaf's host array from its shard
    files (flat seed-format manifests read as one whole-array shard)."""
    shards = meta.get("shards") or [
        {"file": meta["file"], "index": None, "crc32": meta["crc32"]}
    ]
    parts = []
    for ent in shards:
        with open_fn(f"{dirpath}/{ent['file']}", "rb") as f:
            data = f.read()
        if verify and (zlib.crc32(data) & 0xFFFFFFFF) != ent["crc32"]:
            raise IOError(f"checksum mismatch for {key} in {dirpath}")
        parts.append(
            (ent.get("index"), np.load(io.BytesIO(data), allow_pickle=False))
        )
    if len(parts) == 1 and parts[0][0] is None:
        arr = parts[0][1]
    else:
        arr = np.empty(tuple(meta["shape"]), dtype=parts[0][1].dtype)
        for idx, p in parts:
            sl = (
                tuple(slice(a, b) for a, b in idx)
                if idx is not None
                else tuple(slice(None) for _ in arr.shape)
            )
            arr[sl] = p
    return _from_numpy(arr, meta["dtype"])


def load_tree(template, dirpath: str, open_fn: Callable = open,
              shardings=None, verify: bool = True, pool=None):
    """Load into the structure of ``template`` (a pytree of arrays or
    ShapeDtypeStructs). ``shardings``: optional matching tree of
    jax.sharding.Sharding for elastic restore onto a different mesh.
    ``pool``: optional TransferEngine — leaf reads fan out across its
    workers and each finished leaf's ``device_put`` overlaps the reads
    still in flight."""
    manifest = load_manifest(dirpath, open_fn)
    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(template)
    shard_leaves = (
        jax.tree_util.tree_leaves(shardings) if shardings is not None else None
    )
    items = []
    for path, leaf in leaves_with_paths:
        key = _path_str(path)
        items.append((key, manifest["leaves"][key], leaf))

    def _read(item):
        key, meta, _ = item
        return read_leaf(dirpath, key, meta, open_fn, verify)

    if pool is not None and len(items) > 1:
        futs = [pool.submit(_read, item) for item in items]
        arrs = (f.result() for f in futs)
    else:
        arrs = (_read(item) for item in items)
    out = []
    for i, arr in enumerate(arrs):
        key, meta, leaf = items[i]
        expected = tuple(getattr(leaf, "shape", arr.shape))
        if tuple(arr.shape) != expected:
            raise ValueError(
                f"shape mismatch for {key}: ckpt {arr.shape} vs {expected}"
            )
        if shard_leaves is not None:
            out.append(jax.device_put(arr, shard_leaves[i]))
        else:
            out.append(jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out)
