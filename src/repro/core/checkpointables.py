"""Built-in CRAFT-checkpointable data types (paper §2.2) + extension registry.

Paper default types → JAX analogs:

    POD               → ``Box`` holding int/float/complex/bool/str
    POD array         → ``np.ndarray`` (restored in place)
    POD multi-array   → ``np.ndarray`` (any rank; optional column selection)
    MPI derived type  → pytree of arrays (``PytreeCp``) — the structured-data
                        case; snapshot (``update``) plays the role of MPI_Pack
    CpBase derived    → any user subclass of :class:`repro.core.cpbase.CpBase`

Additionally ``JaxArrayCp`` checkpoints a (possibly sharded) ``jax.Array`` by
saving each addressable shard with its global index — the manifest makes the
file set *topology independent* so a restore may land on a different mesh
(elastic restore, DESIGN.md §2).

The extension mechanism of paper §2.3 (Listing 6's "interface function") is
the :func:`register_adapter` registry: library authors map their type to a
wrapper factory once, after which ``Checkpoint.add()`` works directly on
objects of that type.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Callable, Generic, Optional, TypeVar

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.cpbase import CheckpointError, CpBase, IOContext
from repro.core import reshard, storage, tiers, trace
from repro.core.device_snapshot import DeviceSnapshotter

T = TypeVar("T")


class Box(Generic[T]):
    """Mutable holder — the Python analog of the paper's ``&variable``.

    JAX arrays and Python scalars are immutable, so the library hands out a
    box whose ``.value`` the application reads/writes; ``restart_if_needed``
    restores into the box.
    """

    __slots__ = ("value",)

    def __init__(self, value: T):
        self.value = value

    def __repr__(self) -> str:  # pragma: no cover
        return f"Box({self.value!r})"


# --------------------------------------------------------------------------
# POD
# --------------------------------------------------------------------------
_POD_TYPES = (int, float, complex, bool, str)


class PodCp(CpBase):
    """A single plain-old-data element held in a :class:`Box`."""

    def __init__(self, box: Box):
        if not isinstance(box, Box):
            raise TypeError("PodCp expects a Box")
        self.box = box
        self._buf = box.value

    def update(self) -> None:
        self._buf = self.box.value

    def write(self, dir_path: Path, ctx: IOContext) -> None:
        val = self._buf
        kind = type(val).__name__
        if isinstance(val, complex):
            payload = {"kind": "complex", "re": val.real, "im": val.imag}
        elif isinstance(val, _POD_TYPES):
            payload = {"kind": kind, "value": val}
        else:
            raise CheckpointError(f"not a POD: {type(val)}")
        storage.write_json(dir_path / "pod.json", payload)

    def read(self, dir_path: Path, ctx: IOContext) -> None:
        p = dir_path / "pod.json"
        if not p.exists():
            raise CheckpointError(f"missing {p}")
        payload = storage.read_json(p)
        if payload["kind"] == "complex":
            self.box.value = complex(payload["re"], payload["im"])
        else:
            caster = {"int": int, "float": float, "bool": bool, "str": str}[
                payload["kind"]
            ]
            self.box.value = caster(payload["value"])
        self._buf = self.box.value

    def nbytes(self) -> int:
        return 16


# --------------------------------------------------------------------------
# numpy arrays (POD array / multi-array) — restored IN PLACE like the paper's
# pointer-to-array semantics.
# --------------------------------------------------------------------------
class NdArrayCp(CpBase):
    def __init__(self, arr: np.ndarray, to_cp_col: Optional[int] = None):
        if not isinstance(arr, np.ndarray):
            raise TypeError("NdArrayCp expects np.ndarray")
        self.arr = arr
        self.to_cp_col = to_cp_col  # paper's POD multi-array column selection
        self._buf = self._select().copy()

    def _select(self) -> np.ndarray:
        if self.to_cp_col is None:
            return self.arr
        return self.arr[:, self.to_cp_col]

    def update(self) -> None:
        np.copyto(self._buf, self._select())

    def write(self, dir_path: Path, ctx: IOContext) -> None:
        storage.write_array(dir_path / "array.bin", self._buf, ctx)

    def read(self, dir_path: Path, ctx: IOContext) -> None:
        loaded = storage.read_array(dir_path / "array.bin", ctx)
        target = self._select()
        if loaded.shape != target.shape:
            raise CheckpointError(
                f"shape mismatch: stored {loaded.shape} vs live {target.shape}"
            )
        # no _buf sync here: every write path calls update() first, so the
        # extra copy would only slow the restore hot path down
        target[...] = loaded.astype(target.dtype, copy=False)

    def nbytes(self) -> int:
        return self._buf.nbytes


# --------------------------------------------------------------------------
# jax.Array (possibly sharded) in a Box
# --------------------------------------------------------------------------
def _assign_shard(out: np.ndarray, idx, arr: np.ndarray) -> None:
    """Write a loaded shard into the assembly buffer (rank-0 safe)."""
    if out.ndim == 0:
        out[...] = np.asarray(arr, dtype=out.dtype).reshape(())
    else:
        out[idx] = arr


def _shard_slices(index) -> list:
    """Serialize a shard index (tuple of slices) as [[start, stop], ...]."""
    out = []
    for sl in index:
        out.append([0 if sl.start is None else int(sl.start),
                    None if sl.stop is None else int(sl.stop)])
    return out


# --------------------------------------------------------------------------
# elastic N→M assembly (shared by JaxArrayCp / PytreeCp / ShardCp reads)
# --------------------------------------------------------------------------
def _aux_item_dirs(dir_path: Path, ctx: IOContext) -> list:
    """This item's directory inside each peer version root (``ctx.aux_dirs``),
    as ``[(item_dir, root), ...]`` — only roots where the item exists."""
    if not ctx.aux_dirs or ctx.rel_root is None:
        return []
    try:
        rel = dir_path.relative_to(ctx.rel_root)
    except ValueError:
        return []
    out = []
    for root in ctx.aux_dirs:
        d = Path(root) / rel
        if d.is_dir():
            out.append((d, Path(root)))
    return out


def _collect_manifests(dir_path: Path, ctx: IOContext, pattern: str) -> list:
    """Union of writer manifests across the materialized dir and peer roots.

    Returns ``[(manifest, dir, root), ...]`` ordered by manifest filename;
    ``root`` is None for the main dir.  A manifest present in both (the
    restoring rank's own file, mirrored on a peer) is taken from the main
    dir — its delta refs resolve against ``ctx.base_dirs`` directly.
    """
    found = {}
    for mp in dir_path.glob(pattern):
        found[mp.name] = (storage.read_json(mp), dir_path, None)
    for d, root in _aux_item_dirs(dir_path, ctx):
        for mp in d.glob(pattern):
            if mp.name not in found:
                found[mp.name] = (storage.read_json(mp), d, root)
    return [found[k] for k in sorted(found)]


def _open_range_reader(path: Path, ctx: IOContext, root: Optional[Path]):
    """A :class:`storage.ChunkRangeReader` for a shard file — delta refs in a
    peer-root file resolve against *that* tree's sibling ``v-<B>`` dirs."""
    if root is None:
        return storage.ChunkRangeReader(path, ctx)
    rel = path.relative_to(root)
    bases = None
    if ctx.base_dirs:
        bases = {int(v): Path(root).parent / tiers.version_dir_name(int(v))
                 for v in ctx.base_dirs}
    return storage.ChunkRangeReader(path, ctx, rel=rel, base_dirs=bases)


def _read_aux_array(path: Path, ctx: IOContext, root: Path) -> np.ndarray:
    """Whole-array read of a peer-root file (full-span range read, so v2
    refs chase the peer's base chain instead of ``ctx.base_dirs``)."""
    rdr = _open_range_reader(path, ctx, root)
    payload = bytes(rdr.read(0, rdr.nbytes))
    return storage._restore_shape(payload, rdr.header, path)


def _assemble_whole(ctx: IOContext, gshape: tuple, dtype: np.dtype, exts,
                    where: str) -> np.ndarray:
    """Read every shard file whole into one global host array.

    A lone file spanning the whole leaf with the leaf's dtype is the leaf:
    the writable array ``read_array`` decoded is returned as it is, with no
    second buffer, coverage mask or copy (a read-only memory-tier view is
    still copied).  Each ``restore.assemble`` span carries ``copied_bytes``,
    0 on that path.
    """
    if len(exts) == 1 and exts[0][0] == tuple((0, s) for s in gshape):
        arr = storage.read_array(exts[0][1], ctx)
        if (arr.dtype == dtype and arr.shape == gshape
                and arr.flags.writeable):
            with trace.TRACER.span("restore.assemble", nbytes=arr.nbytes,
                                   copied_bytes=0):
                return arr
        arrs = [arr]
    else:
        arrs = (storage.read_array(path, ctx) for _, path, _ in exts)
    out = np.empty(gshape, dtype=dtype)
    filled = np.zeros(gshape, dtype=bool) if out.size else None
    for (ext, _path, _root), arr in zip(exts, arrs):
        idx = tuple(slice(lo, hi) for lo, hi in ext)
        with trace.TRACER.span("restore.assemble", nbytes=arr.nbytes,
                               copied_bytes=arr.size * out.itemsize):
            _assign_shard(out, idx, arr)
            if filled is not None:
                filled[idx] = True
    if filled is not None and not filled.all():
        raise CheckpointError(
            f"incomplete shard coverage under {where} "
            f"({int(filled.sum())}/{filled.size} elements)"
        )
    return out


def _read_global_leaf(ctx: IOContext, gshape, dtype, sources, live,
                      where: str):
    """Assemble one global array from shard files written on any topology.

    ``sources`` is ``[(index_spec, path, root), ...]`` — one entry per shard
    file across every writer's manifest (``root`` None = materialized main
    dir, else the peer version root the file lives under).  ``ctx.reshard``
    picks the strategy:

    * legacy full assembly — every file is read whole into a global buffer
      (same cost profile as before this module existed);
    * range assembly — each extent the restoring process actually needs is
      mapped onto the writers' extents (:func:`reshard.overlap_runs`) and
      only the overlapping chunk ranges are verified/decoded/fetched.

    ``auto`` takes the range path when the live value is a ``jax.Array``
    whose addressable extents don't span the global array (a real N→M or
    multi-host restore) or when shards live in peer roots; a same-topology
    single-host restore keeps the legacy path.  Returns a ``jax.Array`` on
    the live sharding when ``live`` is one, else the global ndarray.
    """
    gshape = tuple(int(s) for s in gshape)
    dtype = np.dtype(dtype)
    exts = [(reshard.resolve_index(spec, gshape), Path(path), root)
            for spec, path, root in sources]
    full_ext = tuple((0, s) for s in gshape)
    live_is_jax = isinstance(live, jax.Array)
    if live_is_jax and tuple(live.shape) != gshape:
        raise CheckpointError(
            f"shape mismatch: stored {gshape} vs live {tuple(live.shape)} "
            f"({where})"
        )
    dst_exts = None
    if live_is_jax:
        dst_exts = []
        for s in live.addressable_shards:
            e = reshard.resolve_index(s.index, gshape)
            if e not in dst_exts:
                dst_exts.append(e)
    has_aux = any(root is not None for _, _, root in exts)
    mode = getattr(ctx, "reshard", "auto")
    use_range = (mode == "range") or has_aux or (
        mode == "auto" and dst_exts is not None
        and any(e != full_ext for e in dst_exts)
    )
    if not use_range:
        out = _assemble_whole(ctx, gshape, dtype, exts, where)
        if live_is_jax:
            with trace.TRACER.span("restore.place", nbytes=out.nbytes):
                return jax.device_put(out, live.sharding)
        return out
    rdr_cache: dict = {}

    def open_reader(key):
        r = rdr_cache.get(key[0])
        if r is None:
            r = _open_range_reader(key[1], ctx, key[2])
            rdr_cache[key[0]] = r
        return r

    srcs = [(e, (str(p), p, root)) for e, p, root in exts]
    blocks = {}
    for e in (dst_exts if dst_exts is not None else [full_ext]):
        with trace.TRACER.span("restore.assemble"):
            block, covered = reshard.assemble_extent(e, dtype, srcs,
                                                     open_reader)
        if covered is not None and not covered.all():
            raise CheckpointError(
                f"incomplete shard coverage for extent {e} under {where} "
                f"({int(covered.sum())}/{covered.size} elements)"
            )
        blocks[e] = block
    if live_is_jax:
        with trace.TRACER.span("restore.place"):
            shard_arrs = [
                jax.device_put(
                    blocks[reshard.resolve_index(s.index, gshape)], s.device)
                for s in live.addressable_shards
            ]
            return jax.make_array_from_single_device_arrays(
                gshape, live.sharding, shard_arrs)
    return blocks[full_ext]


class JaxArrayCp(CpBase):
    """Checkpoint a (sharded) ``jax.Array`` held in a Box.

    Write: each *addressable* shard goes to ``shard-<r>-<i>.bin`` (r = process
    rank — paper's process-local file naming) plus ``array.json`` recording the
    global shape/dtype and every shard's global index.  Read: shards are
    assembled into the global array and ``device_put`` onto the sharding of
    the *live* box value — which may differ from the writer's topology
    (elastic restore).
    """

    def __init__(self, box: Box, *, device_snapshot: bool = False,
                 chunk_bytes: Optional[int] = None,
                 device_hist: bool = True):
        if not isinstance(box, Box):
            raise TypeError("JaxArrayCp expects a Box holding a jax.Array")
        self.box = box
        self._buf: list = []     # [(index, np.ndarray, device_meta | None)]
        self._meta: dict = {}
        self._snap = (
            DeviceSnapshotter(chunk_bytes or IOContext.chunk_bytes,
                              with_hist=device_hist)
            if device_snapshot else None
        )
        self.update()

    def update(self) -> None:
        arr = self.box.value
        if not isinstance(arr, jax.Array):
            raise CheckpointError(f"Box no longer holds a jax.Array: {type(arr)}")
        shards = arr.addressable_shards
        if self._snap is not None:
            # Fused device pass per shard: digest + dirty mask + entropy on
            # device, then only the dirty chunks cross to the host mirror.
            self._buf = []
            for i, s in enumerate(shards):
                host, dmeta = self._snap.snapshot(i, s.data)
                self._buf.append((s.index, host, dmeta))
        else:
            # Device→host snapshot of every addressable shard — one batched
            # transfer instead of a blocking per-shard np.asarray.
            with trace.TRACER.span("snapshot.device_get", shards=len(shards)):
                hosts = jax.device_get([s.data for s in shards])
            self._buf = [
                (s.index, np.asarray(h), None)
                for s, h in zip(shards, hosts)
            ]
        self._meta = {
            "global_shape": list(arr.shape),
            "dtype": storage._dtype_to_name(arr.dtype),
        }

    def write(self, dir_path: Path, ctx: IOContext) -> None:
        shards_meta = []
        for i, (index, host, dmeta) in enumerate(self._buf):
            fname = f"shard-{ctx.proc_rank}-{i}.bin"
            if dmeta is not None:
                ctx.record_device_meta(
                    storage._manifest_name(dir_path / fname, ctx), dmeta)
            storage.write_array(dir_path / fname, host, ctx)
            shards_meta.append({"file": fname, "index": _shard_slices(index)})
        storage.write_json(
            dir_path / f"array-{ctx.proc_rank}.json",
            {**self._meta, "shards": shards_meta},
        )

    def read(self, dir_path: Path, ctx: IOContext) -> None:
        manifests = _collect_manifests(dir_path, ctx, "array-*.json")
        if not manifests:
            raise CheckpointError(f"no array manifest under {dir_path}")
        meta0 = manifests[0][0]
        gshape = tuple(meta0["global_shape"])
        dtype = storage._dtype_from_name(meta0["dtype"])
        sources = [
            (sh["index"], d / sh["file"], root)
            for m, d, root in manifests
            for sh in m["shards"]
        ]
        live = self.box.value
        value = _read_global_leaf(
            ctx, gshape, dtype, sources, live, str(dir_path))
        if isinstance(live, jax.Array):
            self.box.value = value
        else:  # no live value to infer placement from: single-device put
            self.box.value = jnp.asarray(value)

    def nbytes(self) -> int:
        return sum(h.nbytes for _, h, _ in self._buf)


# --------------------------------------------------------------------------
# pytree of arrays (train states, optimizer states, KV caches, ...)
# --------------------------------------------------------------------------
class PytreeCp(CpBase):
    """Checkpoint an arbitrary pytree held in a Box.

    The tree structure comes from the *live* value at read time (CRAFT
    semantics: state is constructed first, then restored into), so leaves are
    stored by flattened position with shape/dtype validation.  JAX leaves are
    restored onto the live leaf's sharding — restoring onto a different mesh
    reshards transparently.
    """

    def __init__(self, box: Box, *, device_snapshot: bool = False,
                 chunk_bytes: Optional[int] = None,
                 device_hist: bool = True):
        self.box = box
        self._buf: list = []
        self._treedef = None
        self._snap = (
            DeviceSnapshotter(chunk_bytes or IOContext.chunk_bytes,
                              with_hist=device_hist)
            if device_snapshot else None
        )
        self.update()

    def update(self) -> None:
        leaves, treedef = jax.tree_util.tree_flatten(self.box.value)
        self._treedef = treedef
        buf = []
        jax_shards = []      # (buf_item, shard) pairs for one batched D2H
        for i, leaf in enumerate(leaves):
            if isinstance(leaf, jax.Array):
                item = {
                    "kind": "jax",
                    "global_shape": list(leaf.shape),
                    "dtype": storage._dtype_to_name(leaf.dtype),
                    "shards": [],
                }
                for j, s in enumerate(leaf.addressable_shards):
                    if self._snap is not None:
                        host, dmeta = self._snap.snapshot((i, j), s.data)
                        item["shards"].append((s.index, host, dmeta))
                    else:
                        jax_shards.append((item, s))
                buf.append(item)
            elif isinstance(leaf, np.ndarray):
                buf.append({"kind": "np", "data": leaf.copy()})
            else:
                buf.append({"kind": "pod", "data": leaf})
        if jax_shards:
            # One batched device→host transfer for every jax leaf's shards.
            with trace.TRACER.span("snapshot.device_get",
                                   shards=len(jax_shards)):
                hosts = jax.device_get([s.data for _, s in jax_shards])
            for (item, s), h in zip(jax_shards, hosts):
                item["shards"].append((s.index, np.asarray(h), None))
        self._buf = buf

    def write(self, dir_path: Path, ctx: IOContext) -> None:
        manifest = {"n_leaves": len(self._buf), "leaves": []}
        for i, item in enumerate(self._buf):
            if item["kind"] == "jax":
                shards_meta = []
                for j, (index, host, dmeta) in enumerate(item["shards"]):
                    fname = f"leaf{i}-shard-{ctx.proc_rank}-{j}.bin"
                    if dmeta is not None:
                        ctx.record_device_meta(
                            storage._manifest_name(dir_path / fname, ctx),
                            dmeta)
                    storage.write_array(dir_path / fname, host, ctx)
                    shards_meta.append(
                        {"file": fname, "index": _shard_slices(index)}
                    )
                manifest["leaves"].append(
                    {
                        "kind": "jax",
                        "global_shape": item["global_shape"],
                        "dtype": item["dtype"],
                        "shards": shards_meta,
                    }
                )
            elif item["kind"] == "np":
                fname = f"leaf{i}.bin"
                storage.write_array(dir_path / fname, item["data"], ctx)
                manifest["leaves"].append({"kind": "np", "file": fname})
            else:
                manifest["leaves"].append(
                    {"kind": "pod", "value": _pod_json(item["data"])}
                )
        storage.write_json(dir_path / f"tree-{ctx.proc_rank}.json", manifest)

    def read(self, dir_path: Path, ctx: IOContext) -> None:
        # parse every writer's manifest once up front — the per-leaf shard
        # merge below would otherwise re-parse them per leaf (O(leaves²));
        # peer version roots (elastic N→M node-tier restores) contribute
        # their manifests alongside the materialized dir's
        parsed = _collect_manifests(dir_path, ctx, "tree-*.json")
        if not parsed:
            raise CheckpointError(f"no pytree manifest under {dir_path}")
        manifest = parsed[0][0]
        live_leaves, treedef = jax.tree_util.tree_flatten(self.box.value)
        if manifest["n_leaves"] != len(live_leaves):
            raise CheckpointError(
                f"pytree leaf count mismatch: stored {manifest['n_leaves']} "
                f"vs live {len(live_leaves)}"
            )
        new_leaves = []
        for i, (spec, live) in enumerate(zip(manifest["leaves"], live_leaves)):
            if spec["kind"] == "jax":
                gshape = tuple(spec["global_shape"])
                dtype = storage._dtype_from_name(spec["dtype"])
                sources = [    # merge shard sets from all writer procs
                    (sh["index"], d / sh["file"], root)
                    for m, d, root in parsed
                    for sh in m["leaves"][i].get("shards", [])
                ]
                value = _read_global_leaf(
                    ctx, gshape, dtype, sources, live,
                    f"{dir_path} (leaf {i})")
                new_leaves.append(
                    value if isinstance(live, jax.Array)
                    else jnp.asarray(value))
            elif spec["kind"] == "np":
                # every writer stores an identical copy — prefer the
                # materialized dir's, fall back to any peer root's
                _m, d, root = next(
                    (e for e in parsed if e[2] is None), parsed[0])
                if root is None:
                    arr = storage.read_array(d / spec["file"], ctx)
                else:   # replicated leaf only present in a peer's tree
                    arr = _read_aux_array(d / spec["file"], ctx, root)
                # memory-tier reads hand out read-only views of shared
                # buffers; a tree leaf is owned by the application, so copy
                new_leaves.append(arr if arr.flags.writeable else arr.copy())
            else:
                new_leaves.append(_pod_unjson(spec["value"]))
        self.box.value = jax.tree_util.tree_unflatten(treedef, new_leaves)

    def nbytes(self) -> int:
        total = 0
        for item in self._buf:
            if item["kind"] == "jax":
                total += sum(h.nbytes for _, h, _ in item["shards"])
            elif item["kind"] == "np":
                total += item["data"].nbytes
        return total


# --------------------------------------------------------------------------
# one rank's rectangular slice of a global array (host-side domain
# decomposition — the paper's redistributable-domain case)
# --------------------------------------------------------------------------
class ShardCp(CpBase):
    """Checkpoint one rank's block of a global array, held as a host ndarray.

    The on-disk format is :class:`JaxArrayCp`'s (``shard-<rank>-<i>.bin`` +
    ``array-<rank>.json``), so the file set is topology independent: a
    checkpoint written by N ``ShardCp`` ranks restores onto M ranks with any
    other block decomposition — each restoring rank range-reads exactly its
    own extent out of the writers' chunk grids, never assembling the global
    array in memory.  ``box.value`` holds the writable block.
    """

    def __init__(self, box: Box, global_shape, index):
        if not isinstance(box, Box):
            raise TypeError("ShardCp expects a Box holding an ndarray block")
        self.box = box
        self.global_shape = tuple(int(s) for s in global_shape)
        self.index = reshard.resolve_index(index, self.global_shape)
        block = np.asarray(box.value)
        want = tuple(hi - lo for lo, hi in self.index)
        if self.global_shape and tuple(block.shape) != want:
            raise CheckpointError(
                f"block shape {tuple(block.shape)} does not match extent "
                f"{self.index} of global {self.global_shape}"
            )
        self._buf = block.copy()

    def update(self) -> None:
        self._buf = np.asarray(self.box.value).copy()

    def write(self, dir_path: Path, ctx: IOContext) -> None:
        fname = f"shard-{ctx.proc_rank}-0.bin"
        storage.write_array(dir_path / fname, self._buf, ctx)
        storage.write_json(
            dir_path / f"array-{ctx.proc_rank}.json",
            {
                "global_shape": list(self.global_shape),
                "dtype": storage._dtype_to_name(self._buf.dtype),
                "shards": [{
                    "file": fname,
                    "index": [[lo, hi] for lo, hi in self.index],
                }],
            },
        )

    def read(self, dir_path: Path, ctx: IOContext) -> None:
        manifests = _collect_manifests(dir_path, ctx, "array-*.json")
        if not manifests:
            raise CheckpointError(f"no array manifest under {dir_path}")
        meta0 = manifests[0][0]
        gshape = tuple(meta0["global_shape"])
        if gshape != self.global_shape:
            raise CheckpointError(
                f"global shape mismatch: stored {gshape} vs live "
                f"{self.global_shape}"
            )
        dtype = storage._dtype_from_name(meta0["dtype"])
        srcs = [
            (reshard.resolve_index(sh["index"], gshape),
             (str(d / sh["file"]), d / sh["file"], root))
            for m, d, root in manifests
            for sh in m["shards"]
        ]
        rdr_cache: dict = {}

        def open_reader(key):
            r = rdr_cache.get(key[0])
            if r is None:
                r = _open_range_reader(key[1], ctx, key[2])
                rdr_cache[key[0]] = r
            return r

        block, covered = reshard.assemble_extent(
            self.index, dtype, srcs, open_reader)
        if covered is not None and not covered.all():
            raise CheckpointError(
                f"incomplete shard coverage for extent {self.index} under "
                f"{dir_path} ({int(covered.sum())}/{covered.size} elements)"
            )
        self.box.value = block
        self._buf = block.copy()

    def nbytes(self) -> int:
        return self._buf.nbytes


def _pod_json(v):
    if isinstance(v, complex):
        return {"kind": "complex", "re": v.real, "im": v.imag}
    return {"kind": type(v).__name__, "value": v}


def _pod_unjson(d):
    if d["kind"] == "complex":
        return complex(d["re"], d["im"])
    return {"int": int, "float": float, "bool": bool, "str": str, "NoneType": lambda v: None}[
        d["kind"]
    ](d.get("value"))


# --------------------------------------------------------------------------
# getter/setter adapter (for data not reachable via a Box, e.g. an object
# attribute or a library handle)
# --------------------------------------------------------------------------
class FuncCp(CpBase):
    def __init__(self, get: Callable[[], Any], set_: Callable[[Any], None]):
        self._get, self._set = get, set_
        self._inner: Optional[CpBase] = None
        self._box = Box(None)
        self.update()

    def _wrap(self, value) -> CpBase:
        self._box.value = value
        if isinstance(value, jax.Array):
            return JaxArrayCp(self._box)
        if isinstance(value, np.ndarray):
            return NdArrayCp(value)
        if isinstance(value, _POD_TYPES):
            return PodCp(self._box)
        return PytreeCp(self._box)

    def update(self) -> None:
        self._inner = self._wrap(self._get())
        self._inner.update()

    def write(self, dir_path: Path, ctx: IOContext) -> None:
        assert self._inner is not None
        self._inner.write(dir_path, ctx)

    def read(self, dir_path: Path, ctx: IOContext) -> None:
        assert self._inner is not None
        self._inner.read(dir_path, ctx)
        self._set(self._box.value)

    def nbytes(self) -> int:
        return self._inner.nbytes() if self._inner else 0


# --------------------------------------------------------------------------
# extension registry (paper §2.3, Listing 6)
# --------------------------------------------------------------------------
_ADAPTERS: list = []   # [(predicate, factory)]


def register_adapter(predicate: Callable[[Any], bool],
                     factory: Callable[[Any], CpBase]) -> None:
    """Register an ``add()`` adapter for a user/library data type.

    ``predicate(obj)`` decides applicability; ``factory(obj)`` returns the
    checkpointable wrapper.  This is the paper's "interface function inside
    CRAFT" (Listing 6) — after registration, end users can pass their objects
    straight to ``Checkpoint.add()``.
    """
    _ADAPTERS.append((predicate, factory))


def wrap(obj: Any, **kw) -> CpBase:
    """Dispatch an ``add()`` argument to a checkpointable (paper's overloads)."""
    if isinstance(obj, CpBase):
        return obj
    for predicate, factory in _ADAPTERS:
        if predicate(obj):
            return factory(obj)
    if isinstance(obj, Box):
        v = obj.value
        snap_kw = {
            "device_snapshot": kw.get("device_snapshot", False),
            "chunk_bytes": kw.get("chunk_bytes"),
            "device_hist": kw.get("device_hist", True),
        }
        if isinstance(v, jax.Array):
            return JaxArrayCp(obj, **snap_kw)
        if isinstance(v, _POD_TYPES):
            return PodCp(obj)
        return PytreeCp(obj, **snap_kw)
    if isinstance(obj, np.ndarray):
        return NdArrayCp(obj, to_cp_col=kw.get("to_cp_col"))
    if isinstance(obj, jax.Array):
        raise TypeError(
            "jax.Array is immutable — wrap it in repro.core.Box(arr) so the "
            "restored value can be handed back (paper's &ptr analog)"
        )
    if isinstance(obj, _POD_TYPES):
        raise TypeError(
            f"{type(obj).__name__} is immutable — wrap it in repro.core.Box(x)"
        )
    raise TypeError(
        f"don't know how to checkpoint {type(obj)}; subclass CpBase or "
        "register_adapter() it (paper §2.3)"
    )
