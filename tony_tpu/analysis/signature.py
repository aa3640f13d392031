"""Step-signature snapshots: a stable digest of a traced step's program
shape, committed as JSON so structural drift shows up as a reviewable
diff instead of a silent regression.

The digest is everything the perf story rests on and nothing that churns
per run: recursive equation count, the full primitive histogram, the
collective census (count per kind + total payload bytes), the
optimization-barrier count (the prefetch chain), and the donation-aware
live-buffer high-water estimate. All of it is a pure function of the
jaxpr, so two traces of the same code on the same jax pin produce
byte-identical digests — structural claims ("3467 → 890 eqns") become
pin-able as committed files. The shipped pins under ``tests/signatures/``
cover the canonical ``tony analyze`` configs (the small mnist-mlp harness
geometry, e.g. 305 eqns for the fused step).

Regenerating after an INTENDED change: run with ``TONY_UPDATE_SIGNATURES=1``
(or ``tony analyze --signatures tests/signatures --update-signatures``)
and commit the new files.
"""

from __future__ import annotations

import json
import os
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence

UPDATE_ENV = "TONY_UPDATE_SIGNATURES"


def _update_requested() -> bool:
    """Explicitly-false spellings must NOT regenerate: a CI config
    setting ``TONY_UPDATE_SIGNATURES=0`` to disable updates would
    otherwise silently rewrite every pin and pass the drift gate."""
    return os.environ.get(UPDATE_ENV, "").strip().lower() \
        not in ("", "0", "false", "no")


def step_signature(closed: Any,
                   donated: Optional[Sequence[bool]] = None, *,
                   collectives: Optional[Sequence[Any]] = None
                   ) -> Dict[str, Any]:
    """The digest of one closed jaxpr (see module docstring).
    ``collectives`` reuses an already-collected census (the analyze
    entries walk the program for rule 2 anyway)."""
    from tony_tpu.analysis import jaxprwalk as jw  # lazy: jax-backed

    prims = jw.prim_counts(closed)
    colls = jw.collect_collectives(closed) if collectives is None \
        else list(collectives)
    by_kind: Dict[str, int] = {}
    for c in colls:
        by_kind[c.kind] = by_kind.get(c.kind, 0) + 1
    return {
        "eqns": sum(prims.values()),
        "prims": prims,
        "collectives": dict(sorted(by_kind.items())),
        "collective_nbytes": sum(c.nbytes for c in colls),
        "optimization_barriers": prims.get("optimization_barrier", 0),
        "live_high_water_nbytes": jw.live_high_water(closed, donated),
    }


def diff_signature(pinned: Dict[str, Any], current: Dict[str, Any]
                   ) -> List[str]:
    """Human-readable drift lines, empty when identical. Nested dicts
    (prims, collectives) diff per key so a review sees "scan: 1 -> 2",
    not two opaque blobs."""
    lines: List[str] = []
    for key in sorted(set(pinned) | set(current)):
        a, b = pinned.get(key), current.get(key)
        if a == b:
            continue
        if isinstance(a, dict) and isinstance(b, dict):
            for k in sorted(set(a) | set(b)):
                if a.get(k) != b.get(k):
                    lines.append(f"{key}.{k}: {a.get(k, 0)} -> "
                                 f"{b.get(k, 0)}")
        else:
            lines.append(f"{key}: {a} -> {b}")
    return lines


def load_signature(path: str | Path) -> Optional[Dict[str, Any]]:
    p = Path(path)
    if not p.is_file():
        return None
    return json.loads(p.read_text())


def save_signature(path: str | Path, sig: Dict[str, Any]) -> None:
    p = Path(path)
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps(sig, indent=2, sort_keys=True) + "\n")


def check_signature(sig: Dict[str, Any], path: str | Path) -> List[str]:
    """Compare ``sig`` against the committed pin at ``path``.

    Returns drift lines (empty = match). With ``TONY_UPDATE_SIGNATURES=1``
    the pin is rewritten instead and the check passes — the diff then
    lives in git, where it belongs. A missing pin file is reported as
    drift (the snapshot must be committed, not lazily created by CI)."""
    if _update_requested():
        save_signature(path, sig)
        return []
    pinned = load_signature(path)
    if pinned is None:
        return [f"no committed signature at {path} — run with "
                f"{UPDATE_ENV}=1 and commit the file"]
    return diff_signature(pinned, sig)
