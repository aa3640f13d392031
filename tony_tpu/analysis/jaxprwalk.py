"""Closed-jaxpr walking primitives for the static analyzer: recursive
equation enumeration with provenance, collective-equation extraction, and
a donation-aware live-buffer high-water estimate.

Everything here reads ONLY the jaxpr (shapes, dtypes, primitive params,
source info) — no compilation, no execution — so the analyzer runs in
milliseconds on CPU against exactly the program the step will trace on
TPU. The walk recurses through every sub-jaxpr a primitive carries
(``pjit``/``scan``/``shard_map``/``cond``/``while``/``remat``/custom-AD
calls), because the collectives the rules care about live two levels down:
``jit → scan body → shard_map body``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np
from jax.extend import core as jcore

# The manual-collective primitive names (``jax.lax.psum_scatter`` binds
# the ``reduce_scatter`` primitive).
COLLECTIVE_PRIMS: Tuple[str, ...] = (
    "psum", "all_gather", "reduce_scatter", "all_to_all", "ppermute",
    "pmax", "pmin", "pgather")

# Primitive params that carry sub-jaxprs worth descending into. Secondary
# AD thunks (``jvp_jaxpr_fun``, ``fwd``/``bwd`` wrappers) are NOT jaxpr
# values on this jax line, so the natural type check below skips them —
# no equation is counted twice.
_SUB_KEYS: Tuple[str, ...] = (
    "jaxpr", "call_jaxpr", "body_jaxpr", "cond_jaxpr", "branches",
    "fun_jaxpr")


def _as_closed(v: Any) -> Optional[jcore.ClosedJaxpr]:
    if isinstance(v, jcore.ClosedJaxpr):
        return v
    if isinstance(v, jcore.Jaxpr):
        return jcore.ClosedJaxpr(v, ())
    return None


def subjaxprs(eqn: jcore.JaxprEqn) -> List[Tuple[str, jcore.ClosedJaxpr]]:
    """``(param_key, closed_jaxpr)`` for every sub-jaxpr of one equation."""
    out: List[Tuple[str, jcore.ClosedJaxpr]] = []
    for key in _SUB_KEYS:
        v = eqn.params.get(key)
        vals = v if isinstance(v, (list, tuple)) else (v,)
        for item in vals:
            closed = _as_closed(item)
            if closed is not None:
                out.append((key, closed))
    return out


def iter_eqns(closed: jcore.ClosedJaxpr, path: str = ""
              ) -> Iterator[Tuple[str, int, jcore.JaxprEqn]]:
    """Depth-first ``(path, index, eqn)`` over the whole program; ``path``
    names the enclosing primitives (``"scan/shard_map"``), ``index`` the
    equation's position within its own jaxpr."""
    for i, eqn in enumerate(closed.jaxpr.eqns):
        yield path, i, eqn
        for _key, sub in subjaxprs(eqn):
            inner = f"{path}/{eqn.primitive.name}" if path \
                else eqn.primitive.name
            yield from iter_eqns(sub, inner)


def count_eqns(closed: jcore.ClosedJaxpr) -> int:
    return sum(1 for _ in iter_eqns(closed))


def prim_counts(closed: jcore.ClosedJaxpr) -> Dict[str, int]:
    """Recursive primitive histogram (static equation occurrences — a
    scan body counts once, not once per trip)."""
    out: Dict[str, int] = {}
    for _p, _i, eqn in iter_eqns(closed):
        name = eqn.primitive.name
        out[name] = out.get(name, 0) + 1
    return dict(sorted(out.items()))


def aval_nbytes(aval: Any) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(dtype).itemsize


def eqn_out_nbytes(eqn: jcore.JaxprEqn) -> int:
    return sum(aval_nbytes(v.aval) for v in eqn.outvars)


def eqn_axes(eqn: jcore.JaxprEqn) -> Tuple[str, ...]:
    """The mesh axes a collective equation runs over (``psum`` carries
    ``axes``, the rest ``axis_name``; either may be a bare string)."""
    axes = eqn.params.get("axes", eqn.params.get("axis_name", ()))
    if isinstance(axes, str):
        return (axes,)
    return tuple(a for a in tuple(axes) if isinstance(a, str))


def source_of(eqn: jcore.JaxprEqn) -> str:
    """``file:line (fn)`` of the frame that issued the equation — the
    provenance half every finding carries."""
    try:
        from jax._src import source_info_util
        return source_info_util.summarize(eqn.source_info)
    except Exception:  # noqa: BLE001 — provenance is best-effort
        return "<unknown>"


@dataclass(frozen=True)
class CollectiveEqn:
    """One collective equation, with enough provenance to act on."""

    kind: str                  # primitive name (psum/all_gather/...)
    axes: Tuple[str, ...]      # mesh axes it runs over
    nbytes: int                # summed output payload bytes
    path: str                  # enclosing-primitive path ("scan/shard_map")
    index: int                 # equation index within its jaxpr
    src: str                   # issuing source line

    @property
    def provenance(self) -> str:
        where = f"{self.path}[{self.index}]" if self.path \
            else f"[{self.index}]"
        return (f"{where} {self.kind} over {list(self.axes)} "
                f"{self.nbytes} B @ {self.src}")


def collect_collectives(closed: jcore.ClosedJaxpr) -> List[CollectiveEqn]:
    """Every collective equation in the program, in program order."""
    out: List[CollectiveEqn] = []
    for path, i, eqn in iter_eqns(closed):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            out.append(CollectiveEqn(
                kind=eqn.primitive.name, axes=eqn_axes(eqn),
                nbytes=eqn_out_nbytes(eqn), path=path, index=i,
                src=source_of(eqn)))
    return out


def _internal_high_water(closed: jcore.ClosedJaxpr) -> int:
    """High-water bytes of values DEFINED inside this jaxpr (its invars
    and constvars are the caller's buffers — counted at the call site,
    not here)."""
    return _high_water(closed.jaxpr, free_invars=True)


def _high_water(jaxpr: jcore.Jaxpr, *, free_invars: bool,
                donated: Optional[Sequence[bool]] = None) -> int:
    eqns = jaxpr.eqns
    last_use: Dict[Any, int] = {}
    for t, eqn in enumerate(eqns):
        for v in eqn.invars:
            if isinstance(v, jcore.Var):
                last_use[v] = t
    end = len(eqns)
    for v in jaxpr.outvars:
        if isinstance(v, jcore.Var):
            last_use[v] = end               # outputs live to the end

    alive: Dict[Any, int] = {}
    if not free_invars:
        # Program inputs: a donated buffer frees at its last use (XLA may
        # alias it); everything else is the caller's and stays resident
        # for the whole execution.
        flags = list(donated) if donated is not None else []
        flags += [False] * (len(jaxpr.invars) - len(flags))
        for v, don in zip(jaxpr.invars, flags):
            if not don:
                last_use[v] = end
            alive[v] = aval_nbytes(v.aval)
        for v in jaxpr.constvars:
            last_use[v] = end
            alive[v] = aval_nbytes(v.aval)
    high = sum(alive.values())
    for t, eqn in enumerate(eqns):
        base = sum(alive.values())
        # A sub-jaxpr's internal temporaries peak while the caller's live
        # set persists around the call.
        for _key, sub in subjaxprs(eqn):
            high = max(high, base + _internal_high_water(sub))
        for v in eqn.outvars:
            if isinstance(v, jcore.Var):
                alive[v] = aval_nbytes(v.aval)
        high = max(high, sum(alive.values()))
        for v in list(alive):
            if last_use.get(v, -1) <= t:
                del alive[v]
    return high


def live_high_water(closed: jcore.ClosedJaxpr,
                    donated: Optional[Sequence[bool]] = None) -> int:
    """Donation-aware live-buffer high-water ESTIMATE in bytes: a linear
    liveness scan over the equation list (sub-jaxprs contribute their
    internal peak at their call site). It ignores XLA fusion and
    rematerialization, so it is an upper-ish bound useful for regression
    pinning and for measuring what donation buys — not an allocator
    prediction. ``donated`` flags the program's flat invars."""
    return _high_water(closed.jaxpr, free_invars=False, donated=donated)
