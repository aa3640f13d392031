"""Async snapshot engine: device→host shard extraction + background writer.

The save path is split at the device/host boundary the way Horovod splits
gradient exchange from compute (PAPERS: 1802.05799) — the part that must
fence the accelerator is made as small as possible, everything else rides a
background thread:

* **extract** (synchronous, inside :meth:`AsyncCheckpointer.save`): each
  process walks its addressable shards, keeps exactly the chunks it owns
  (``replica_id == 0`` — one copy of every distinct chunk globally, shard-
  local writes under ZeRO-3), and pulls them to host in ONE batched
  ``jax.device_get`` (a single transfer program, not per-leaf round trips).
  Once this returns, the train loop may donate/overwrite the state buffers.
* **write + commit** (asynchronous): a daemon writer thread serializes the
  host snapshot through :mod:`tony_tpu.ckpt.format` and commits the step.
  Two snapshot slots are kept (double buffering): a save issued while one
  write is still in flight proceeds immediately into the second slot; only
  a THIRD save stalls until a slot frees. The stall time (slot wait +
  extract) is what the train loop actually pays — the profiler records it
  next to the blocking write time so the overlap is measurable
  (``tony_tpu.profiler.report("ckpt")``).

Writer errors never vanish: they surface on the next ``save``/``wait``.
"""

from __future__ import annotations

import functools
import queue
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from tony_tpu import profiler
from tony_tpu.ckpt import format as fmt


# Per-save channel into the profiler's plan registry.
_record = functools.partial(profiler.record, "ckpt")


def _is_saveable(leaf: Any) -> bool:
    """Array-like leaves (jax/np arrays, np scalars, Python scalars) are
    checkpointed; everything else passes through restore untouched."""
    if isinstance(leaf, (bool, int, float, complex)):
        return True
    return hasattr(leaf, "shape") and hasattr(leaf, "dtype")


def leaf_paths(tree: Any) -> Tuple[List[str], List[Any], Any]:
    """Stable leaf addressing: ``jax.tree_util.keystr`` paths in flatten
    order — the join key between a manifest and any same-structured tree.
    Returns ``(paths, leaves, treedef)`` from ONE traversal."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return ([jax.tree_util.keystr(path) for path, _ in flat],
            [leaf for _, leaf in flat], treedef)


def _leaf_meta(path: str, leaf: Any) -> Dict[str, Any]:
    arr_like = np.asarray(leaf) if isinstance(
        leaf, (bool, int, float, complex)) else leaf
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    return {
        "path": path,
        "shape": [int(s) for s in arr_like.shape],
        "dtype": fmt.dtype_name(arr_like.dtype),
        "spec": fmt.spec_to_json(spec),
    }


def _mesh_meta(leaves: Sequence[Any]) -> Optional[Dict[str, Any]]:
    for leaf in leaves:
        mesh = getattr(getattr(leaf, "sharding", None), "mesh", None)
        if mesh is not None and getattr(mesh, "axis_names", None):
            return {"axis_names": list(mesh.axis_names),
                    "shape": {str(a): int(mesh.shape[a])
                              for a in mesh.axis_names}}
    return None


@dataclass
class Snapshot:
    """One step's host-side copy of this process's owned chunks."""
    step: int
    leaves: List[Dict[str, Any]]                 # manifest leaf metadata
    chunks: List[Tuple[int, List[int], np.ndarray]]
    mesh: Optional[Dict[str, Any]]
    nbytes: int = 0
    extract_s: float = 0.0
    stall_s: float = 0.0
    done: threading.Event = field(default_factory=threading.Event)


def extract_snapshot(tree: Any, step: int) -> Snapshot:
    """Device→host extraction of this process's owned chunks (see module
    docstring for the ownership rule). Returns once every chunk is resident
    on host — the caller may mutate/donate the device buffers after."""
    t0 = time.perf_counter()
    paths, leaves, _ = leaf_paths(tree)
    metas: List[Dict[str, Any]] = []
    # (leaf, start, device-or-host ref, aliases-live-memory)
    pending: List[Tuple[int, List[int], Any, bool]] = []
    proc = jax.process_index()
    for i, (path, leaf) in enumerate(zip(paths, leaves)):
        if not _is_saveable(leaf):
            continue
        metas.append(_leaf_meta(path, leaf))
        li = len(metas) - 1
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            # Host array / scalar: replicated by construction; process 0
            # writes the single global copy. ALWAYS copied below — it
            # aliases a buffer the train loop may mutate in place.
            if proc == 0:
                pending.append((li, [0] * np.ndim(leaf),
                                np.asarray(leaf), True))
            continue
        for shard in shards:
            if shard.replica_id != 0:
                continue
            start = [int(s.start or 0) for s in shard.index]
            pending.append((li, start, shard.data, False))
    # One batched transfer for everything device-side, then copy ONLY
    # what still aliases live memory: host leaves (the caller's arrays),
    # and zero-copy views the CPU backend's device_get hands back (a later
    # donated step rewrites the underlying buffer while the writer thread
    # serializes). TPU device_get returns fresh owned host buffers —
    # re-copying those would double the snapshot's memcpy and its
    # transient memory for nothing.
    datas = jax.device_get([d for _, _, d, _ in pending])

    def _own(data: np.ndarray, aliased: bool) -> np.ndarray:
        data = np.asarray(data)
        if aliased or data.base is not None or not data.flags["OWNDATA"]:
            return np.array(data, copy=True)
        return data

    chunks = [(li, start, _own(data, aliased))
              for (li, start, _, aliased), data in zip(pending, datas)]
    snap = Snapshot(step=int(step), leaves=metas, chunks=chunks,
                    mesh=_mesh_meta(leaves),
                    nbytes=sum(int(a.nbytes) for _, _, a in chunks))
    snap.extract_s = time.perf_counter() - t0
    return snap


def write_snapshot(root: str | Path, snap: Snapshot, *,
                   process_index: Optional[int] = None,
                   num_processes: Optional[int] = None,
                   keep: int = 0,
                   barrier_timeout_s: float = 300.0) -> Optional[Path]:
    """Serialize + commit one snapshot (blocking). Every process writes its
    shard file; process 0 additionally merges the sidecars into the
    manifest and atomically commits the step, then prunes old steps."""
    proc = jax.process_index() if process_index is None else process_index
    n = jax.process_count() if num_processes is None else num_processes
    staging = fmt.tmp_dir(root, snap.step)
    fmt.write_process_file(staging, proc, snap.chunks)
    if proc != 0:
        # Block until process 0's manifest rename lands: a blocking save
        # (and wait()/restore_or's drain) must mean GLOBALLY committed on
        # every process, or latest_step diverges across the gang.
        fmt.wait_committed(root, snap.step, barrier_timeout_s)
        return None
    path = fmt.commit(root, snap.step, leaves=snap.leaves, mesh=snap.mesh,
                      num_processes=n, barrier_timeout_s=barrier_timeout_s)
    if keep:
        fmt.prune(root, keep)
    return path


class AsyncCheckpointer:
    """Double-buffered async checkpoint writer bound to one directory.

    ``save(state, step)`` stalls the caller only for slot acquisition plus
    the device→host extract; serialization, fsync, and the atomic commit
    run on the writer thread so subsequent train steps overlap the I/O.
    ``save(..., block=True)`` degrades to a blocking save.

    One live instance per process per directory: construction sweeps torn
    staging dirs from crashed predecessors, so a second concurrent
    instance on the same directory could reclaim this one's in-flight
    save (use one manager — ``train_loop`` owns its own, user code holding
    a ``Checkpointer`` should not save through both at once).
    """

    def __init__(self, directory: str | Path, *, keep: int = 3,
                 buffers: int = 2, process_index: Optional[int] = None,
                 num_processes: Optional[int] = None,
                 barrier_timeout_s: float = 300.0):
        self.directory = Path(directory)
        self.keep = keep
        self.process_index = jax.process_index() if process_index is None \
            else process_index
        self.num_processes = jax.process_count() if num_processes is None \
            else num_processes
        self.barrier_timeout_s = barrier_timeout_s
        self._slots = threading.BoundedSemaphore(max(1, buffers))
        self._q: "queue.Queue[Optional[Snapshot]]" = queue.Queue()
        self._err_lock = threading.Lock()    # guards _err (writer/caller)
        self._err: Optional[BaseException] = None
        self._closed = False
        self.stats: Dict[str, Any] = {
            "saves": 0, "stall_s": [], "extract_s": [], "write_s": [],
            "nbytes": 0}
        # Reclaim torn staging dirs from a previous (crashed) incarnation —
        # process 0 only: a sibling process may already be staging shard
        # files for a new step, and its tmp dir must not be swept.
        if self.process_index == 0:
            fmt.clean_stale(self.directory)
        self._writer = threading.Thread(target=self._run, daemon=True,
                                        name="ckpt-writer")
        self._writer.start()

    # -- background side ---------------------------------------------------
    def _run(self) -> None:
        while True:
            snap = self._q.get()
            if snap is None:
                self._q.task_done()
                return
            t0 = time.perf_counter()
            try:
                write_snapshot(
                    self.directory, snap,
                    process_index=self.process_index,
                    num_processes=self.num_processes, keep=self.keep,
                    barrier_timeout_s=self.barrier_timeout_s)
                write_s = time.perf_counter() - t0
                self.stats["write_s"].append(write_s)
                _record("async_save", step=snap.step, stall_s=snap.stall_s,
                        extract_s=snap.extract_s, write_s=write_s,
                        nbytes=snap.nbytes, n_chunks=len(snap.chunks),
                        keep=self.keep)
            except BaseException as e:  # noqa: BLE001 — surfaced on save/wait
                with self._err_lock:
                    self._err = e
            finally:
                snap.done.set()
                self._slots.release()
                self._q.task_done()

    def _raise_pending(self) -> None:
        # Swap under the lock: an unlocked read-then-clear could
        # overwrite (and lose) an error the writer banked between the
        # two — the concurrency audit's torn read-modify-write case.
        with self._err_lock:
            err, self._err = self._err, None
        if err is not None:
            raise RuntimeError("checkpoint writer failed") from err

    # -- caller side -------------------------------------------------------
    def save(self, state: Any, step: Optional[int] = None,
             block: bool = False) -> Snapshot:
        """Snapshot ``state`` and enqueue the write. Returns once the host
        copy is complete (state buffers are free to be donated); the commit
        itself lands asynchronously unless ``block``."""
        if self._closed:
            raise RuntimeError("AsyncCheckpointer is closed")
        self._raise_pending()
        if step is None:
            step_leaf = getattr(state, "step", None)
            step = int(jax.device_get(step_leaf)) if step_leaf is not None \
                else 0
        t0 = time.perf_counter()
        self._slots.acquire()          # stalls only when both slots busy
        try:
            snap = extract_snapshot(state, step)
        except BaseException:
            self._slots.release()
            raise
        snap.stall_s = time.perf_counter() - t0
        self.stats["saves"] += 1
        self.stats["stall_s"].append(snap.stall_s)
        self.stats["extract_s"].append(snap.extract_s)
        self.stats["nbytes"] = snap.nbytes
        self._q.put(snap)
        if block:
            snap.done.wait()
            self._raise_pending()
        return snap

    def wait(self) -> None:
        """Block until every enqueued save has committed (or failed)."""
        self._q.join()
        self._raise_pending()

    def latest_step(self) -> Optional[int]:
        return fmt.latest_step(self.directory)

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._q.put(None)
        self._writer.join(timeout=self.barrier_timeout_s + 60.0)
        self._raise_pending()
