"""Asynchronous weight-update engine (paper §6.3 "Data Movement"), built on
a Mooncake-style CPU-resident bucket store.

After each train step the trainer *pushes* bucketized weights once over the
cross-cluster link to the store; inference workers *pull* the newest buckets
on demand over their own links, decoupling weight transfer from rollout.
Live mode stores real jax arrays (flattened into ~bucket_mb chunks); sim
mode tracks only sizes + versions. Transfer-time accounting reproduces the
paper's Table 4 decomposition (push / accumulated pull / exposed pull).

When the store is used and when it is bypassed (``LiveRLRunner``): the
runner always publishes v0, and an FT restore republishes the restored
weights. Per step, the store carries a version only to the engines that
pull from it: a TP group (per-shard chunks), an engine on another chip,
the disaggregated plane's engines off the trainer's chip. An engine with
no mesh on the trainer's own device bypasses it: at the barrier it takes
the trainer's device arrays as they are
(``InferenceEngine.update_params``' device path), and a plane of such
engines alone publishes nothing after v0.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np


@dataclass
class Bucket:
    name: str
    version: int
    nbytes: int
    payload: Any = None        # list of (leaf_index, array) in live mode;
    #                            sharded buckets carry
    #                            (leaf_index, shard_index, n_shards, dim,
    #                             array) entries instead
    sharded: bool = False


@dataclass
class TransferLog:
    push_s: float = 0.0
    pull_s: float = 0.0            # accumulated pull cost
    exposed_pull_s: float = 0.0    # pull cost not hidden by rollout
    pushes: int = 0
    pulls: int = 0


class MooncakeStore:
    """Versioned, bucketized weight store with simple latest-wins semantics."""

    def __init__(self, bucket_mb: int = 1024):
        self.bucket_bytes = bucket_mb * 2 ** 20
        self._lock = threading.Lock()
        self._buckets: Dict[int, List[Bucket]] = {}   # guarded by: _lock
        self._latest: int = -1                        # guarded by: _lock
        self.log = TransferLog()                      # guarded by: _lock

    # ------------------------------------------------------------------
    @property
    def latest_version(self) -> int:
        with self._lock:
            return self._latest

    def _pack(self, entries: List[Tuple], version: int,
              sharded: bool) -> List[Bucket]:
        """Pack (..., array) payload entries into ~bucket_bytes buckets."""
        buckets: List[Bucket] = []
        cur: List[Tuple] = []
        cur_bytes = 0
        for entry in entries:
            nb = int(np.asarray(entry[-1]).nbytes)
            if cur and cur_bytes + nb > self.bucket_bytes:
                buckets.append(Bucket(f"v{version}.b{len(buckets)}",
                                      version, cur_bytes, cur,
                                      sharded=sharded))
                cur, cur_bytes = [], 0
            cur.append(entry)
            cur_bytes += nb
        if cur:
            buckets.append(Bucket(f"v{version}.b{len(buckets)}",
                                  version, cur_bytes, cur,
                                  sharded=sharded))
        return buckets

    def bucketize(self, leaves: List[np.ndarray],
                  version: int) -> List[Bucket]:
        """Split a flat list of arrays into ~bucket_bytes buckets."""
        return self._pack(list(enumerate(leaves)), version, sharded=False)

    def bucketize_sharded(self, leaves: List[np.ndarray], version: int,
                          n_shards: int,
                          chunk_dims: List[Optional[int]]) -> List[Bucket]:
        """Split leaves into per-shard chunks first, THEN into buckets:
        leaf ``i`` with ``chunk_dims[i] = d`` is split into ``n_shards``
        equal chunks along dim ``d`` (the dim an n-way engine group
        shards over its "model" axis — ``sharding.model_axis_dims``);
        ``chunk_dims[i] = None`` leaves replicate and travel whole. An
        engine pulling version v then reads exactly the chunks its
        devices need (``InferenceEngine.update_from_chunks``) instead of
        a monolithic per-leaf array."""
        entries: List[Tuple] = []
        for i, leaf in enumerate(leaves):
            d = chunk_dims[i] if i < len(chunk_dims) else None
            arr = np.asarray(leaf)
            if d is None or arr.shape[d] % n_shards != 0:
                entries.append((i, 0, 1, None, arr))
            else:
                for j, part in enumerate(np.split(arr, n_shards, axis=d)):
                    entries.append((i, j, n_shards, d,
                                    np.ascontiguousarray(part)))
        return self._pack(entries, version, sharded=True)

    def publish(self, buckets: List[Bucket]):
        """Training side: write-once publication of a new version."""
        if not buckets:
            return
        version = buckets[0].version
        with self._lock:
            self._buckets[version] = list(buckets)
            self._latest = max(self._latest, version)
            # retain only the two most recent versions (bounded store)
            for v in [v for v in self._buckets if v < self._latest - 1]:
                del self._buckets[v]
            self.log.pushes += 1

    def publish_sizes(self, version: int, total_bytes: int):
        """Sim mode: publish version metadata without payloads."""
        n = max(1, int(np.ceil(total_bytes / self.bucket_bytes)))
        per = total_bytes // n
        self.publish([Bucket(f"v{version}.b{i}", version, per, None)
                      for i in range(n)])

    def pull_latest(self) -> Optional[List[Bucket]]:
        """Inference side: fetch the newest complete version's buckets."""
        with self._lock:
            if self._latest < 0:
                return None
            self.log.pulls += 1
            return list(self._buckets[self._latest])

    def version_bytes(self, version: Optional[int] = None) -> int:
        with self._lock:
            v = self._latest if version is None else version
            return sum(b.nbytes for b in self._buckets.get(v, []))


def flatten_params(params) -> List[np.ndarray]:
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(params)]


def unflatten_like(params, leaves: List[np.ndarray]):
    import jax
    treedef = jax.tree.structure(params)
    return jax.tree.unflatten(treedef, leaves)


def push_params(store: MooncakeStore, params, version: int) -> int:
    """Live-mode publication of real weights. Returns bytes pushed."""
    leaves = flatten_params(params)
    buckets = store.bucketize(leaves, version)
    store.publish(buckets)
    return sum(b.nbytes for b in buckets)


def pull_params(store: MooncakeStore, like) -> Optional[Tuple[Any, int]]:
    """Live-mode pull: reassemble the latest version into ``like``'s
    structure. Returns (params, version) or None."""
    buckets = store.pull_latest()
    if not buckets:
        return None
    if any(b.sharded for b in buckets):
        raise RuntimeError(
            "store holds a sharded version; pull with pull_param_chunks "
            "(engines assemble shards via update_from_chunks)")
    import jax
    n_leaves = len(jax.tree.leaves(like))
    leaves: List[Optional[np.ndarray]] = [None] * n_leaves
    for b in buckets:
        for i, arr in b.payload:
            leaves[i] = arr
    if any(x is None for x in leaves):
        raise RuntimeError("incomplete bucket set")
    return unflatten_like(like, leaves), buckets[0].version


def push_params_sharded(store: MooncakeStore, params, version: int,
                        n_shards: int,
                        chunk_dims: List[Optional[int]]) -> int:
    """Live-mode publication of real weights as PER-SHARD chunks (§6.3
    data movement at TP scale: the trainer pushes once; each engine
    device pulls only its chunks). Returns bytes pushed."""
    leaves = flatten_params(params)
    buckets = store.bucketize_sharded(leaves, version, n_shards,
                                      chunk_dims)
    store.publish(buckets)
    return sum(b.nbytes for b in buckets)


def pull_param_chunks(store: MooncakeStore, like
                      ) -> Optional[Tuple[List[Tuple], int]]:
    """Live-mode pull of the latest version in CHUNK form: one
    ``(dim, [parts in shard order])`` entry per leaf of ``like`` —
    the input format of ``InferenceEngine.update_from_chunks``. Plain
    (unsharded) buckets degrade to single-part entries, so a mixed plane
    (e.g. an FT restore republishing a dense snapshot) still pulls
    through the one code path. Returns (chunks, version) or None."""
    buckets = store.pull_latest()
    if not buckets:
        return None
    import jax
    n_leaves = len(jax.tree.leaves(like))
    dims: List[Optional[int]] = [None] * n_leaves
    parts: List[Dict[int, np.ndarray]] = [dict() for _ in range(n_leaves)]
    counts = [1] * n_leaves
    for b in buckets:
        for entry in b.payload:
            if b.sharded:
                i, j, n, d, arr = entry
                dims[i] = d
                counts[i] = n
                parts[i][j] = arr
            else:
                i, arr = entry
                parts[i][0] = arr
    chunks: List[Tuple] = []
    for i in range(n_leaves):
        if len(parts[i]) != counts[i]:
            raise RuntimeError(
                f"incomplete bucket set: leaf {i} has {len(parts[i])} of "
                f"{counts[i]} shards")
        chunks.append((dims[i], [parts[i][j] for j in range(counts[i])]))
    return chunks, buckets[0].version
