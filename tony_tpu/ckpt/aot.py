"""Persisted AOT compile cache: own the executable like PR 3 owns the
checkpoint.

At heavy traffic, autoscale reaction time IS the product: today every
scale-up grant — and every gang restart or elastic resize on the
training side — pays a full trace + XLA compile before producing a
token. This module decouples replica startup from accelerator
compilation (the runtime-decoupling move Arax argues for, PAPERS
2305.01291): a step program compiled once anywhere persists next to the
ckpt manifest, and every later replica of the same (topology, config,
jax/XLA) family deserializes it in milliseconds instead of re-tracing.

One cache entry is one directory::

    <root>/aot_<key>/
        payload.bin     # the serialized executable, chunked
        entry.json      # format tag + FULL fingerprint + chunk table
                        # + the pickled call trees (base64, CRC'd)

committed with the ckpt plane's stage-``.tmp``-then-rename discipline
(:mod:`tony_tpu.ckpt.format`): payload and entry are written (fsynced)
into a per-writer staging dir and ``os.replace``d into place — a
crashed writer leaves a ``.tmp`` orphan, never a half entry, and a
concurrent populate of one key is first-writer-wins (the second rename
fails against the committed directory and its staging is discarded).

``<key>`` is a digest of the fingerprint, but the name is only an
address: ``entry.json`` stores the FULL fingerprint dict and
:meth:`AOTCache.get` requires an exact match — a digest collision, a
hand-edited entry, or any key drift (changed geometry, changed jax
version) rejects to a counted miss. Every payload chunk carries a
CRC32 verified on read (the ChunkReader discipline); corruption of any
byte returns ``None``. The cache may cost a recompile, never a wrong
program.

Jax-free at import by the ckpt package's layering rule (the fingerprint
helpers and the serialize/deserialize calls import lazily): the AM can
name a cache dir in a grant without dragging the compute stack in.
"""

from __future__ import annotations

import base64
import hashlib
import json
import os
import pickle
import shutil
import threading
import zlib
from pathlib import Path
from typing import Any, Dict, List, Optional

from tony_tpu.ckpt.format import TMP_SUFFIX, _atomic_write_json, _fsync_dir

_PREFIX = "aot_"
FORMAT = "tony-aot-v1"

# Payload chunking: per-chunk CRC32 bounds what one flipped bit costs to
# detect (the sidecar idiom) without hashing multi-MB artifacts twice.
CHUNK_BYTES = 1 << 20


# ---------------------------------------------------------------------------
# Fingerprinting: what makes two compiles THE SAME program
# ---------------------------------------------------------------------------

def runtime_fingerprint() -> Dict[str, Any]:
    """The jax/XLA half of a fingerprint: versions, backend platform,
    device kind/count, and the XLA flags env — a serialized executable
    is only valid against the toolchain and device family that built
    it, and any of these changing must be a miss, not a wrong load."""
    import jax
    try:
        import jaxlib
        jaxlib_v = jaxlib.version.__version__
    except Exception:
        jaxlib_v = ""
    devs = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib_v,
        "backend": jax.default_backend(),
        "device_kind": str(devs[0].device_kind) if devs else "",
        "n_devices": len(devs),
        "xla_flags": os.environ.get("XLA_FLAGS", ""),
    }


def mesh_descriptor(mesh: Any) -> Optional[Dict[str, Any]]:
    """Topology half: axis names/sizes plus the device kind the mesh is
    laid over. ``None`` for meshless (single-device) callers."""
    if mesh is None:
        return None
    axes = {str(k): int(v) for k, v in dict(mesh.shape).items()}
    kinds = sorted({str(getattr(d, "device_kind", d))
                    for d in mesh.devices.flat})
    return {"axes": axes, "device_kinds": kinds}


def tree_digest(tree: Any) -> str:
    """Digest of a pytree's SHAPE: treedef + per-leaf shape/dtype/
    sharding. Params/state enter the fingerprint through this — the
    compiled program depends on avals and layouts, not on values, so
    restored weights of the same family hit while a changed model
    geometry (or a resharded state) misses."""
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    h = hashlib.sha256(str(treedef).encode())
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        shard = str(getattr(leaf, "sharding", None))
        h.update(f"{shape}|{dtype}|{shard};".encode())
    return h.hexdigest()


def make_fingerprint(kind: str, *, mesh: Any = None,
                     geometry: Optional[Dict[str, Any]] = None,
                     model: Any = None, tree: Any = None,
                     batch: Any = None,
                     extra: Optional[Dict[str, Any]] = None
                     ) -> Dict[str, Any]:
    """Assemble one step family's full fingerprint: runtime + topology
    + step geometry + model config + state-shape digests. JSON-
    canonicalized so the dict a fresh process derives compares equal to
    the dict :meth:`AOTCache.get` reads back from ``entry.json``."""
    fp: Dict[str, Any] = {"format": FORMAT, "kind": str(kind)}
    fp.update(runtime_fingerprint())
    fp["mesh"] = mesh_descriptor(mesh)
    fp["geometry"] = dict(geometry or {})
    fp["model"] = "" if model is None else str(model)
    if tree is not None:
        fp["tree"] = tree_digest(tree)
    if batch is not None:
        fp["batch"] = tree_digest(batch)
    if extra:
        fp["extra"] = dict(extra)
    # Round-trip through JSON so tuples/np ints normalize to exactly
    # what a later get() will load and compare against.
    return json.loads(json.dumps(fp, sort_keys=True))


def fingerprint_key(fp: Dict[str, Any]) -> str:
    """The entry's directory name stem — an ADDRESS, not the identity:
    ``get`` always re-verifies the stored full fingerprint."""
    blob = json.dumps(fp, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:20]


# ---------------------------------------------------------------------------
# The cache
# ---------------------------------------------------------------------------

class AOTCache:
    """One directory of persisted compiled executables (module
    docstring). ``put`` serializes a ``jax.stages.Compiled``; ``get``
    returns a loaded, callable one — or ``None`` on any corruption,
    key drift, or an unsupported backend (counted; callers re-trace).

    Counters are lifetime and cross-consumer (the serve engine and the
    train stepper each also keep their own): ``hits``/``misses`` per
    ``get``, ``puts`` committed, ``put_races`` lost to a concurrent
    first writer, ``unsupported`` serialize declines."""

    def __init__(self, root: str) -> None:
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.hits = 0
        self.misses = 0
        self.puts = 0
        self.put_races = 0
        self.unsupported = 0

    def _dir(self, fp: Dict[str, Any]) -> Path:
        return self.root / f"{_PREFIX}{fingerprint_key(fp)}"

    def entries(self) -> List[str]:
        """Committed entry keys, sorted (staging orphans excluded)."""
        out = []
        for entry in sorted(os.listdir(self.root)):
            if entry.startswith(_PREFIX) and TMP_SUFFIX not in entry:
                out.append(entry[len(_PREFIX):])
        return out

    # -- read --------------------------------------------------------------
    def get(self, fp: Dict[str, Any], *, in_tree: Any = None,
            out_tree: Any = None) -> Optional[Any]:
        """The loaded ``jax.stages.Compiled`` for ``fp``, or ``None``
        (counted miss) on: no entry, format/fingerprint drift, any
        chunk CRC mismatch, a truncated payload, or a backend that
        declines to deserialize (also counted in ``unsupported``).
        Never mutates the store —
        a poison entry costs a recompile on every consult, not a
        crash (and never a wrong program: the payload only loads
        after the FULL fingerprint matched byte for byte).

        ``in_tree``/``out_tree`` are the caller's own call-tree defs,
        used when the entry carries none (``put`` met an unpicklable
        treedef — e.g. a train state whose static aux data holds local
        functions; the caller derives them from its args and
        ``Lowered.out_info``). An entry without stored trees AND no
        caller trees is a counted miss."""
        d = self._dir(fp)
        try:
            with open(d / "entry.json") as f:
                entry = json.load(f)
            if entry.get("format") != FORMAT:
                raise ValueError("format drift")
            if entry.get("fingerprint") != fp:
                raise ValueError("fingerprint drift")
            payload = bytearray()
            with open(d / "payload.bin", "rb") as f:
                for chunk in entry["chunks"]:
                    f.seek(int(chunk["offset"]))
                    raw = f.read(int(chunk["nbytes"]))
                    if len(raw) != int(chunk["nbytes"]) or \
                            (zlib.crc32(raw) & 0xFFFFFFFF) \
                            != int(chunk["crc32"]):
                        raise ValueError("payload chunk CRC mismatch")
                    payload += raw
            if entry["trees_b64"] is not None:
                trees_raw = base64.b64decode(entry["trees_b64"])
                if (zlib.crc32(trees_raw) & 0xFFFFFFFF) \
                        != int(entry["trees_crc32"]):
                    raise ValueError("call-tree CRC mismatch")
                in_tree, out_tree = pickle.loads(trees_raw)
            elif in_tree is None or out_tree is None:
                raise ValueError("entry has no call trees and the "
                                 "caller supplied none")
        except (OSError, ValueError, KeyError, TypeError,
                pickle.UnpicklingError, EOFError):
            self.misses += 1
            return None
        import jax
        from jax.experimental import serialize_executable as _se
        # Load for the devices the executable was compiled for, in its
        # own order: jax's default is every device of the backend, and
        # a one-device program loaded for eight is called with the
        # wrong shard count.
        by_id = {d.id: d for d in jax.devices()}
        try:
            devices = [by_id[int(i)] for i in entry["device_ids"]]
        except KeyError:
            self.misses += 1
            return None
        try:
            compiled = _se.deserialize_and_load(
                bytes(payload), in_tree, out_tree,
                execution_devices=devices)
        except jax.errors.JaxRuntimeError:
            # The backend declines to load serialized executables.
            self.unsupported += 1
            self.misses += 1
            return None
        self.hits += 1
        return compiled

    # -- write -------------------------------------------------------------
    def put(self, fp: Dict[str, Any], compiled: Any) -> bool:
        """Persist one compiled executable under ``fp``. Returns True
        only when THIS call committed the entry; False when the key was
        already committed (idempotent / lost a concurrent race — both
        counted in ``put_races``) or the backend cannot serialize
        (``unsupported``). Commit is stage-then-rename: a crash leaves
        a ``.tmp`` orphan, never a half entry."""
        final = self._dir(fp)
        if final.exists():
            self.put_races += 1
            return False
        import jax
        from jax.experimental import serialize_executable as _se
        try:
            payload, in_tree, out_tree = _se.serialize(compiled)
        except (ValueError, NotImplementedError,
                jax.errors.JaxRuntimeError):
            # What jax raises for "cannot serialize": no unloaded
            # executable / closed-over constants or refs / a PJRT
            # client without executable serialization.
            self.unsupported += 1
            return False
        payload = bytes(payload)
        device_ids = [int(d.id) for d in
                      compiled._executable._unloaded_executable.device_list]
        try:
            trees_raw = pickle.dumps((in_tree, out_tree))
        except (pickle.PicklingError, AttributeError, TypeError):
            # Treedefs whose static aux data holds local objects (a
            # train state's optax tx) don't pickle; the entry commits
            # payload-only and ``get`` requires caller-derived trees.
            trees_raw = None
        table: List[Dict[str, int]] = []
        for off in range(0, max(1, len(payload)), CHUNK_BYTES):
            raw = payload[off:off + CHUNK_BYTES]
            table.append({"offset": off, "nbytes": len(raw),
                          "crc32": zlib.crc32(raw) & 0xFFFFFFFF})
        # Per-writer staging name: two concurrent populates of ONE key
        # must not tear each other's staging dir — each stages alone,
        # and the os.replace onto an already-committed entry fails
        # (first-writer-wins) with the loser's staging discarded.
        staging = Path(f"{final}{TMP_SUFFIX}.{os.getpid()}"
                       f".{threading.get_ident()}")
        staging.mkdir(parents=True, exist_ok=True)
        with open(staging / "payload.bin", "wb") as f:
            f.write(payload)
            f.flush()
            os.fsync(f.fileno())
        _atomic_write_json(staging / "entry.json", {
            "format": FORMAT, "fingerprint": fp, "chunks": table,
            "device_ids": device_ids,
            "trees_b64": None if trees_raw is None
            else base64.b64encode(trees_raw).decode("ascii"),
            "trees_crc32": None if trees_raw is None
            else zlib.crc32(trees_raw) & 0xFFFFFFFF})
        try:
            os.replace(staging, final)
        except OSError:
            shutil.rmtree(staging, ignore_errors=True)
            self.put_races += 1
            return False
        _fsync_dir(self.root)
        self.puts += 1
        return True

    # -- maintenance (tony aot gc) -----------------------------------------
    def gc(self, *, dry_run: bool = False,
           runtime: Optional[Dict[str, Any]] = None) -> tuple:
        """Drop entries no live config can produce. The criterion is the
        RUNTIME half of the fingerprint (:func:`runtime_fingerprint`):
        an entry whose stored jax/jaxlib/backend/device/XLA-flags tuple
        differs from this process's can never hit again — ``get``
        compares the full fingerprint and the runtime fields come from
        the environment, not the caller — so it is stranded disk, not a
        cache. Geometry/model variation is NOT a drop criterion: other
        topologies of the live runtime are exactly what the cache is
        for. Unreadable entries (torn by an unclean kill before the
        rename discipline, or hand-damaged) are stranded the same way
        and drop too. Staging ``.tmp`` orphans are always reclaimed.

        Returns ``(dropped, kept, freed_bytes)``. ``dry_run`` reports
        without deleting; ``runtime`` overrides the live fingerprint
        (tests)."""
        if runtime is None:
            runtime = runtime_fingerprint()   # lazy jax import
        rt_keys = sorted(runtime)

        def _size(d: Path) -> int:
            try:
                return sum(f.stat().st_size for f in d.rglob("*")
                           if f.is_file())
            except OSError:
                return 0

        dropped, kept, freed = 0, 0, 0
        for name in sorted(os.listdir(self.root)):
            if not name.startswith(_PREFIX):
                continue
            d = self.root / name
            if TMP_SUFFIX in name:
                # A crashed writer's staging dir: never addressable.
                freed += _size(d)
                dropped += 1
                if not dry_run:
                    shutil.rmtree(d, ignore_errors=True)
                continue
            try:
                with open(d / "entry.json") as f:
                    fp = json.load(f).get("fingerprint") or {}
                stale = any(fp.get(k) != runtime[k] for k in rt_keys)
            except (OSError, ValueError):
                stale = True          # unreadable = unhittable
            if stale:
                freed += _size(d)
                dropped += 1
                if not dry_run:
                    shutil.rmtree(d, ignore_errors=True)
            else:
                kept += 1
        if dropped and not dry_run:
            _fsync_dir(self.root)
        return dropped, kept, freed
