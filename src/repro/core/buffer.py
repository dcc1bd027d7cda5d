"""SampleBuffer (paper §6.2): buffers scored trajectories for training with
the per-trajectory asynchronous staleness bound alpha.

Invariants (property-tested in tests/test_staleness.py):
- a trajectory with start_version < current_version - alpha is NEVER
  returned by get_batch (it is evicted eagerly);
- with E concurrent environments the buffer holds O(alpha * E) pending
  trajectories across versions (eager eviction bounds growth);
- get_batch blocks until ``batch_size`` valid trajectories exist.

Unlike AReaL, which bounds staleness only at trajectory *start*, RollArt
re-checks the bound every iteration, so long-tail trajectories spanning
multiple versions are aborted (the control plane also aborts their
in-flight generation via LLMProxy).

Fault tolerance (paper §8): the buffer tracks the ``traj_id`` of every
consumed trajectory, and ``put`` drops replays of an already-consumed id
(``total_deduped``). When the FT supervisor restores the rollout plane
from a snapshot taken BEFORE the last few training steps, the replayed
EnvManagers regenerate trajectories the trainer already consumed — the
dedup filter guarantees no ``traj_id`` trains twice.
``snapshot_state``/``restore_state`` serialize the buffer for
rollout-level checkpointing (see ``repro.ft.snapshot``).
"""
from __future__ import annotations

import itertools
import threading
from typing import Callable, Dict, List, Optional

from repro.data.pipeline import Trajectory


class SampleBuffer:
    def __init__(self, alpha: int = 1,
                 on_evict: Optional[Callable[[Trajectory], None]] = None):
        self.alpha = alpha
        self._seq = itertools.count()              # guarded by: _lock
        self._items: List[Trajectory] = []         # guarded by: _lock
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self.on_evict = on_evict
        self.current_version = 0                   # guarded by: _lock
        # traj_ids handed to the trainer
        self._consumed: set = set()                # guarded by: _lock
        # traj_ids currently in _items
        self._buffered: set = set()                # guarded by: _lock
        # stats
        self.total_put = 0                         # guarded by: _lock
        self.total_evicted = 0                     # guarded by: _lock
        # policy tokens of the evicted trajectories (rollout waste)
        self.total_evicted_tokens = 0              # guarded by: _lock
        self.total_consumed = 0                    # guarded by: _lock
        self.total_deduped = 0                     # guarded by: _lock

    # ------------------------------------------------------------------
    def put(self, traj: Trajectory):
        with self._cv:
            if (traj.traj_id in self._consumed
                    or traj.traj_id in self._buffered):
                # replay of a trajectory already trained on — or already
                # buffered awaiting training (a rollout-plane restore from
                # a snapshot older than the completion that produced the
                # first copy): either way it must not train twice
                self.total_deduped += 1
                return
            traj.seq = next(self._seq)
            if self._is_stale(traj, self.current_version):
                self._evict(traj)
                return
            self._items.append(traj)
            self._buffered.add(traj.traj_id)
            self.total_put += 1
            self._cv.notify_all()

    def _is_stale(self, traj: Trajectory, version: int) -> bool:
        return traj.start_version < version - self.alpha

    def _evict(self, traj: Trajectory):   # requires: _lock
        self._buffered.discard(traj.traj_id)
        self.total_evicted += 1
        self.total_evicted_tokens += sum(traj.loss_mask)
        if self.on_evict:
            self.on_evict(traj)

    def set_version(self, version: int):
        """Advance the trainer's weight version; eagerly evict stale."""
        with self._cv:
            self.current_version = version
            keep = []
            for t in self._items:
                if self._is_stale(t, version):
                    self._evict(t)
                else:
                    keep.append(t)
            self._items = keep
            self._cv.notify_all()

    # ------------------------------------------------------------------
    def size(self) -> int:
        with self._lock:
            return len(self._items)

    def stats(self) -> Dict[str, int]:
        """Immutable snapshot of the buffer counters (one lock
        acquisition; the obs plane scrapes this concurrently with
        put/get traffic). Returns a fresh dict every call."""
        with self._lock:
            return {"depth": len(self._items),
                    "current_version": self.current_version,
                    "total_put": self.total_put,
                    "total_evicted": self.total_evicted,
                    "total_evicted_tokens": self.total_evicted_tokens,
                    "total_consumed": self.total_consumed,
                    "total_deduped": self.total_deduped}

    def try_get_batch(self, batch_size: int) -> Optional[List[Trajectory]]:
        """Non-blocking: a batch of the OLDEST valid trajectories, or None."""
        with self._cv:
            self._items = self._evict_stale_locked()
            if len(self._items) < batch_size:
                return None
            # oldest first: version, then numeric arrival order (the
            # lexicographic traj_id would put "t10" before "t2")
            self._items.sort(key=lambda t: (t.start_version, t.seq))
            batch, self._items = (self._items[:batch_size],
                                  self._items[batch_size:])
            self.total_consumed += len(batch)
            for t in batch:
                self._buffered.discard(t.traj_id)
                self._consumed.add(t.traj_id)
            return batch

    def _evict_stale_locked(self) -> List[Trajectory]:   # requires: _lock
        keep = []
        for t in self._items:
            if self._is_stale(t, self.current_version):
                self._evict(t)
            else:
                keep.append(t)
        return keep

    def get_batch(self, batch_size: int,
                  timeout: Optional[float] = None) -> List[Trajectory]:
        """Blocking get_batch (protocol step (1))."""
        with self._cv:
            def ready():
                self._items = self._evict_stale_locked()
                return len(self._items) >= batch_size
            if not self._cv.wait_for(ready, timeout=timeout):
                raise TimeoutError(
                    f"get_batch({batch_size}) timed out with "
                    f"{len(self._items)} buffered")
            self._items.sort(key=lambda t: (t.start_version, t.seq))
            batch, self._items = (self._items[:batch_size],
                                  self._items[batch_size:])
            self.total_consumed += len(batch)
            for t in batch:
                self._buffered.discard(t.traj_id)
                self._consumed.add(t.traj_id)
            return batch

    # ------------------------------------------------------------------
    # rollout-level checkpointing (repro.ft.snapshot)
    # ------------------------------------------------------------------
    def snapshot_state(self) -> Dict:
        """Consistent copy of the buffer for a rollout snapshot. Item
        ``seq`` numbers are preserved so FIFO ordering survives a
        restore."""
        with self._lock:
            # peek-then-recreate: read the next seq value without
            # perturbing the arrival ordering
            nxt = next(self._seq)
            self._seq = itertools.count(nxt)
            return {"items": list(self._items), "seq": nxt,
                    "version": self.current_version,
                    "consumed": set(self._consumed),
                    "total_put": self.total_put,
                    "total_evicted": self.total_evicted,
                    "total_consumed": self.total_consumed,
                    "total_deduped": self.total_deduped}

    def restore_state(self, state: Dict, keep_consumed: bool = False):
        """Rebuild the buffer from ``snapshot_state`` output. With
        ``keep_consumed`` the CURRENT consumed-id set is kept (unioned
        with the snapshot's) — the live-recovery path, where training
        advanced past the snapshot and replayed trajectories must dedup
        against the newer training frontier."""
        with self._cv:
            consumed = set(state["consumed"])
            if keep_consumed:
                consumed |= self._consumed
            self._consumed = consumed
            self._items = [t for t in state["items"]
                           if t.traj_id not in consumed]
            self._buffered = {t.traj_id for t in self._items}
            self.total_deduped += len(state["items"]) - len(self._items)
            self._seq = itertools.count(max(
                state["seq"], 1 + max((t.seq for t in self._items),
                                      default=-1)))
            self.current_version = state["version"]
            self.total_put = state["total_put"]
            self.total_evicted = state["total_evicted"]
            self.total_consumed = state["total_consumed"]
            self._cv.notify_all()
