"""Concurrency analysis plane: lock discipline, lock order, thread hygiene.

PRs 10-13 made the host side of the stack genuinely multi-threaded —
admission queues, the refcounted/COW paged pool, router failover
dispatch, heartbeat publishers, prefetch and snapshot daemons — while
``tony analyze`` still audited only the *traced* program. This module is
the third pillar next to the jaxpr rules and :mod:`srclint`, covering
the host-side concurrency that now carries production traffic. Three
passes, all jax-free (AST + :mod:`threading` only), so ``make lint``
stays runnable on a gateway host:

1. **Lock discipline** — per class, infer which ``self.*`` attributes
   are *guarded* (mutated inside a ``with self.<lock>:`` block anywhere
   in the class, where ``<lock>`` is an attribute assigned a
   ``threading.Lock``/``RLock``/``Condition``) and flag mutations of a
   guarded attribute outside any lock: the classic lost-update drift
   where one new call site forgets the lock the rest of the class
   holds. Reads are deliberately NOT flagged — single-field telemetry
   reads are benign under the GIL and flagging them would bury the real
   findings; the lint targets torn read-modify-write. A flagged site is
   blessed with an audited pragma (mirroring ``# packsite:``)::

       # lockfree: <why this unlocked mutation is safe>

   A pragma with no reason is itself a finding — a blessing without an
   audit is a suppression.

2. **Lock order** — a static graph of nested ``with self.<lock>:``
   acquisitions across every module, merged with the edges a runtime
   *lock witness* observed (:class:`WitnessLock` — an instrumented
   Lock/RLock/Condition shim recording per-thread acquisition chains
   into the profiler's ``report("locks")`` registry). Cycle detection
   over the merged graph turns a potential deadlock into a NAMED
   finding with the full cycle and the first-observation sites — not a
   hung CI job.

3. **Thread hygiene** — every ``threading.Thread(...)`` construction
   must be ``daemon=True`` or be assigned to a binding that is
   ``.join()``-ed in its owning scope (``self._t`` joined anywhere in
   the class; a local joined in the same function). A non-daemon,
   never-joined thread outlives its owner silently; a daemon thread
   that is never joined dies mid-write at interpreter exit — the audit
   makes the choice explicit. Blessed with ``# threadlife: <reason>``.

Findings diff against a committed baseline
(``tests/signatures/concurrency.json``) so the gate is "no NEW
findings", reviewable like the step-signature pins. Run directly
(``python -m tony_tpu.analysis.concurrency [paths] [--baseline f]``),
via ``make lint``, or as ``tony analyze --concurrency``.
"""

from __future__ import annotations

import ast
import json
import sys
import threading
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from tony_tpu import profiler
# One definition of package-relative display paths and the default lint
# root for BOTH source lints (jax-free like this module) — baseline
# fingerprints and srclint's allowlist must never disagree on what a
# path looks like.
from tony_tpu.analysis.srclint import _package_rel, default_root

LOCKFREE_PRAGMA = "lockfree:"
THREADLIFE_PRAGMA = "threadlife:"

RULE_NAMES: Tuple[str, ...] = ("lock_discipline", "lock_order",
                               "thread_hygiene")

# Attribute assigned one of these constructors => a lock attribute.
_LOCK_FACTORIES = frozenset({"Lock", "RLock", "Condition"})

# Method names that mutate their receiver in place: a call
# ``self.X.append(...)`` counts as a mutation of ``self.X``. Queue
# put/get are excluded — queue.Queue carries its own lock.
_MUTATORS = frozenset({
    "append", "appendleft", "extend", "insert", "remove", "pop",
    "popleft", "popitem", "clear", "update", "add", "discard",
    "setdefault", "sort", "reverse", "move_to_end",
})


@dataclass(frozen=True)
class ConcFinding:
    """One concurrency finding. ``provenance`` is the stable anchor
    (``Class.attr`` / the lock cycle / the thread binding) and —
    together with rule, kind, and file — the baseline fingerprint, so
    unrelated line churn never invalidates a blessing."""

    rule: str          # one of RULE_NAMES
    kind: str          # specific finding kind within the rule
    message: str
    path: str = ""
    line: int = 0
    provenance: str = ""
    blessed: bool = False
    blessed_by: str = ""

    def fingerprint(self) -> str:
        return f"{self.rule}:{self.kind}:{self.path}:{self.provenance}"

    def to_dict(self) -> Dict[str, Any]:
        return {"rule": self.rule, "kind": self.kind,
                "message": self.message, "path": self.path,
                "line": self.line, "provenance": self.provenance,
                "blessed": self.blessed, "blessed_by": self.blessed_by}

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}: [{self.rule}/{self.kind}] "
                f"{self.message}")


# ---------------------------------------------------------------------------
# Pragma anchoring (same contract as srclint: the node's own line(s) or
# the CONTIGUOUS comment block immediately above it — a pragma can never
# bless a later statement).
# ---------------------------------------------------------------------------

def _pragma_reason(lines: Sequence[str], node: ast.AST,
                   pragma: str) -> Optional[str]:
    """The pragma's reason text when present at ``node`` (its own lines
    or the contiguous comment block above); ``""`` when the pragma is
    present but bare; ``None`` when absent."""
    def _scan(line: str) -> Optional[str]:
        i = line.find("#")
        while i >= 0:
            tail = line[i + 1:].strip()
            if tail.startswith(pragma):
                return tail[len(pragma):].strip()
            i = line.find("#", i + 1)
        return None

    start = node.lineno - 1
    end = min(len(lines), getattr(node, "end_lineno", node.lineno))
    for i in range(start, end):
        r = _scan(lines[i])
        if r is not None:
            return r
    i = start - 1
    while i >= 0 and lines[i].lstrip().startswith("#"):
        r = _scan(lines[i])
        if r is not None:
            return r
        i -= 1
    return None


def _bless(findings: List[ConcFinding], f: ConcFinding,
           reason: Optional[str]) -> None:
    """File ``f`` according to its pragma state: absent -> active;
    bare -> an active ``bare_pragma`` finding (a blessing without a
    reason is a suppression); reasoned -> blessed."""
    from dataclasses import replace

    if reason is None:
        findings.append(f)
    elif not reason:
        findings.append(replace(
            f, kind="bare_pragma",
            message=f"pragma carries no reason at a finding it blesses "
                    f"({f.kind}: {f.message})"))
    else:
        findings.append(replace(f, blessed=True, blessed_by=reason))


# ---------------------------------------------------------------------------
# Pass 1 + 2 (static): lock discipline and the static lock-order graph
# ---------------------------------------------------------------------------

def _self_attr(node: ast.AST) -> Optional[str]:
    """``X`` when ``node`` is exactly ``self.X``, else None."""
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


def _lock_attrs(cls: ast.ClassDef) -> Set[str]:
    """Attributes of ``cls`` assigned a threading.Lock/RLock/Condition
    anywhere in the class body (``self.X = threading.Lock()``)."""
    out: Set[str] = set()
    for node in ast.walk(cls):
        if not (isinstance(node, ast.Assign)
                and isinstance(node.value, ast.Call)):
            continue
        func = node.value.func
        name = func.attr if isinstance(func, ast.Attribute) else (
            func.id if isinstance(func, ast.Name) else "")
        if name not in _LOCK_FACTORIES:
            continue
        for tgt in node.targets:
            attr = _self_attr(tgt)
            if attr is not None:
                out.add(attr)
    return out


def _mutation_targets(node: ast.AST) -> List[Tuple[str, str]]:
    """``(attr, how)`` for every DIRECT ``self.X`` mutation this single
    node performs (no recursion): plain/aug/ann assignment, subscript
    store ``self.X[k] = v``, ``del self.X[...]``, and in-place mutator
    calls ``self.X.append(...)``. Mutations through a longer chain
    (``self.X.y[k] = v``) mutate the inner object, not the attribute
    binding, and are out of scope for an attribute-guard lint."""
    out: List[Tuple[str, str]] = []

    def _target(tgt: ast.AST, how: str) -> None:
        attr = _self_attr(tgt)
        if attr is not None:
            out.append((attr, how))
            return
        if isinstance(tgt, ast.Subscript):
            attr = _self_attr(tgt.value)
            if attr is not None:
                out.append((attr, f"{how}[]"))
        elif isinstance(tgt, (ast.Tuple, ast.List)):
            for el in tgt.elts:
                _target(el, how)

    if isinstance(node, ast.Assign):
        for tgt in node.targets:
            _target(tgt, "write")
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        if getattr(node, "value", None) is not None or \
                isinstance(node, ast.AugAssign):
            _target(node.target, "write")
    elif isinstance(node, ast.Delete):
        for tgt in node.targets:
            _target(tgt, "del")
    elif isinstance(node, ast.Call):
        func = node.func
        if isinstance(func, ast.Attribute) and func.attr in _MUTATORS:
            attr = _self_attr(func.value)
            if attr is not None:
                out.append((attr, f".{func.attr}()"))
    return out


@dataclass
class _ClassScan:
    """One class's lock-discipline evidence."""
    qual: str                                   # e.g. "serve/engine.py:ServeEngine"
    lock_attrs: Set[str]
    # attr -> first (lock, line) that guarded a mutation of it
    guarded: Dict[str, Tuple[str, int]]
    # (attr, how, line, node, method) mutations performed while NO lock
    # is held — the method rides into the finding's provenance so a
    # baseline blessing covers ONE audited site's method, not every
    # future unlocked mutation of the attribute anywhere in the class.
    bare: List[Tuple[str, str, int, ast.AST, str]]
    # static acquisition-order edges (outer, inner, "path:line")
    edges: List[Tuple[str, str, str]]


def _scan_class(cls: ast.ClassDef, rel: str,
                lock_attrs: Optional[Set[str]] = None) -> _ClassScan:
    # No early-out on empty lock_attrs: a class may guard exclusively
    # through helper-fetched locks (``with self._part_lock(key):``),
    # which the walk below still recognizes. Callers pass the
    # inheritance-merged set (same-file bases) so a subclass's
    # ``with self._lock:`` over a base-declared lock records real holds.
    if lock_attrs is None:
        lock_attrs = _lock_attrs(cls)
    scan = _ClassScan(qual=f"{rel}:{cls.name}", lock_attrs=lock_attrs,
                      guarded={}, bare=[], edges=[])

    def walk(node: ast.AST, held: Tuple[str, ...], meth: str) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            # A nested function's body runs later — usually on another
            # thread or after the with-block exited — so the lexically
            # enclosing lock is NOT held when it executes.
            body = node.body if isinstance(node.body, list) else [node.body]
            for child in body:
                walk(child, (), meth)
            return
        if isinstance(node, (ast.With, ast.AsyncWith)):
            acquired: List[str] = []
            for item in node.items:
                walk(item.context_expr, held + tuple(acquired), meth)
                attr = _self_attr(item.context_expr)
                if attr not in lock_attrs:
                    # ``with self._host_stage_lock(host):`` — a lock
                    # fetched through a helper whose name says so. The
                    # pseudo-name keeps per-key lock tables inside the
                    # discipline/order passes.
                    attr = None
                    if isinstance(item.context_expr, ast.Call):
                        fattr = _self_attr(item.context_expr.func)
                        if fattr is not None and "lock" in fattr.lower():
                            attr = f"{fattr}()"
                if attr is not None:
                    for h in held + tuple(acquired):
                        if h != attr:
                            scan.edges.append(
                                (f"{cls.name}.{h}", f"{cls.name}.{attr}",
                                 f"{rel}:{node.lineno}"))
                    acquired.append(attr)
            for child in node.body:
                walk(child, held + tuple(acquired), meth)
            return
        for attr, how in _mutation_targets(node):
            if attr in lock_attrs:
                continue
            if held:
                scan.guarded.setdefault(attr, (held[-1], node.lineno))
            else:
                scan.bare.append((attr, how, node.lineno, node, meth))
        for child in ast.iter_child_nodes(node):
            walk(child, held, meth)

    for stmt in cls.body:
        if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # Construction runs before any concurrency exists: __init__ (and
        # the _init_* helpers it delegates to) neither witnesses a guard
        # nor violates one. The underscore-terminated prefix is the
        # whole exemption — a runtime `_initialize_stats()` must NOT
        # slip through as "construction".
        if stmt.name == "__init__" or stmt.name.startswith("_init_"):
            continue
        for child in stmt.body:
            walk(child, (), stmt.name)
    return scan


def lint_source(src: str, rel: str, display_path: str
                ) -> Tuple[List[ConcFinding], List[Tuple[str, str, str]]]:
    """Lock-discipline + thread-hygiene lint of one file's source text;
    returns ``(findings, static lock-order edges)``. Findings carry
    their pragma state resolved (``blessed``/``blessed_by``)."""
    try:
        tree = ast.parse(src)
    except SyntaxError as e:
        return [ConcFinding("lock_discipline", "unparseable",
                            "unparseable file", display_path,
                            e.lineno or 0)], []
    lines = src.splitlines()
    findings: List[ConcFinding] = []
    edges: List[Tuple[str, str, str]] = []
    # Inheritance, same-file: a subclass's lock attrs and guard evidence
    # include its in-file base chain's, so SpecEngine-style hierarchies
    # (subclass methods touching base-guarded state) stay covered. A
    # base defined in ANOTHER module is out of a single-file lint's
    # reach — keep thread-shared mutations in the module that owns the
    # lock, or the discipline pass cannot see the guard.
    classes = [n for n in ast.walk(tree) if isinstance(n, ast.ClassDef)]
    by_name = {c.name: c for c in classes}

    def base_chain(c: ast.ClassDef,
                   seen: Tuple[str, ...] = ()) -> List[ast.ClassDef]:
        out: List[ast.ClassDef] = []
        for b in c.bases:
            if isinstance(b, ast.Name) and b.id in by_name \
                    and b.id not in seen and b.id != c.name:
                base = by_name[b.id]
                out.append(base)
                out.extend(base_chain(base, seen + (c.name, b.id)))
        return out

    chains = {c.name: base_chain(c) for c in classes}
    merged_locks = {
        c.name: set().union(_lock_attrs(c),
                            *[_lock_attrs(b) for b in chains[c.name]])
        for c in classes}
    scans = {c.name: _scan_class(c, rel, lock_attrs=merged_locks[c.name])
             for c in classes}
    for node in classes:
        scan = scans[node.name]
        edges.extend(scan.edges)
        guarded: Dict[str, Tuple[str, int]] = dict(scan.guarded)
        for base in chains[node.name]:
            for attr, ev in scans[base.name].guarded.items():
                guarded.setdefault(attr, ev)
        for attr, how, line, anchor, meth in scan.bare:
            if attr not in guarded:
                continue
            lock, gline = guarded[attr]
            f = ConcFinding(
                "lock_discipline", "unguarded_write",
                f"{node.name}.{attr} is mutated ({how}) in {meth}() "
                f"outside any "
                f"lock, but is guarded by {node.name}.{lock} elsewhere "
                f"(e.g. line {gline}) — a torn read-modify-write loses "
                f"updates; hold the lock or bless with "
                f"'# {LOCKFREE_PRAGMA} <why>'",
                display_path, line, f"{node.name}.{meth}.{attr}")
            _bless(findings, f, _pragma_reason(lines, anchor,
                                               LOCKFREE_PRAGMA))
    findings.extend(_thread_hygiene(tree, lines, display_path))
    return findings, edges


# ---------------------------------------------------------------------------
# Pass 3 (static): thread hygiene
# ---------------------------------------------------------------------------

def _is_thread_ctor(node: ast.Call) -> bool:
    func = node.func
    if isinstance(func, ast.Attribute):
        return func.attr == "Thread" and \
            isinstance(func.value, ast.Name) and \
            func.value.id == "threading"
    return isinstance(func, ast.Name) and func.id == "Thread"


def _joined_self_attrs(scope: ast.AST) -> Set[str]:
    """``X`` for every ``self.X.join(...)`` call anywhere in ``scope``."""
    out: Set[str] = set()
    for node in ast.walk(scope):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "join":
            attr = _self_attr(node.func.value)
            if attr is not None:
                out.add(attr)
    return out


def _joined_names(scope: ast.AST) -> Set[str]:
    """``x`` for every ``x.join(...)`` call anywhere in ``scope``."""
    out: Set[str] = set()
    for node in ast.walk(scope):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "join" and \
                isinstance(node.func.value, ast.Name):
            out.add(node.func.value.id)
    return out


def _thread_hygiene(tree: ast.Module, lines: Sequence[str],
                    display_path: str) -> List[ConcFinding]:
    parents: Dict[ast.AST, ast.AST] = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    findings: List[ConcFinding] = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_thread_ctor(node)):
            continue
        daemon = next((kw.value for kw in node.keywords
                       if kw.arg == "daemon"), None)
        if isinstance(daemon, ast.Constant) and daemon.value is True:
            continue
        # Ownership: the nearest Assign whose value is this call.
        parent = parents.get(node)
        target: Optional[ast.AST] = None
        if isinstance(parent, ast.Assign) and parent.value is node \
                and len(parent.targets) == 1:
            target = parent.targets[0]
        # Enclosing scopes, innermost first.
        scopes: List[ast.AST] = []
        p: Optional[ast.AST] = node
        while p is not None:
            p = parents.get(p)
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
                scopes.append(p)
        attr = _self_attr(target) if target is not None else None
        binding = "<unassigned>"
        joined = False
        if attr is not None:
            binding = f"self.{attr}"
            owner = next((s for s in scopes
                          if isinstance(s, ast.ClassDef)), tree)
            joined = attr in _joined_self_attrs(owner)
        elif isinstance(target, ast.Name):
            binding = target.id
            owner = next((s for s in scopes
                          if isinstance(s, (ast.FunctionDef,
                                            ast.AsyncFunctionDef))), tree)
            joined = target.id in _joined_names(owner)
        if joined:
            continue
        scope_name = ".".join(s.name for s in reversed(scopes)) or \
            "<module>"
        detail = ("daemon is not a literal True"
                  if daemon is not None else "non-daemon")
        f = ConcFinding(
            "thread_hygiene", "unjoined_thread",
            f"threading.Thread bound to {binding} in {scope_name} is "
            f"{detail} and never .join()-ed in its owning scope — it "
            f"outlives teardown silently; make it daemon=True, join it "
            f"on a shutdown path, or bless with "
            f"'# {THREADLIFE_PRAGMA} <why>'",
            display_path, node.lineno, f"{scope_name}.{binding}")
        _bless(findings, f, _pragma_reason(lines, node,
                                           THREADLIFE_PRAGMA))
    return findings


# ---------------------------------------------------------------------------
# The runtime lock witness
# ---------------------------------------------------------------------------

_tls = threading.local()


def _held_stack() -> List[str]:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = []
        _tls.stack = st
    return st


class _WitnessGraph:
    """Process-global observed lock-order graph. New edges bank a fresh
    snapshot into ``tony_tpu.profiler.report("locks")`` (registry
    ``"locks"``, tag ``"witness"``) — banking only on NEW edges keeps
    the steady-state acquire path to one dict hit under this lock."""

    def __init__(self) -> None:
        self._lock = threading.Lock()       # guards _edges/_locks
        self._edges: Dict[Tuple[str, str], Dict[str, Any]] = {}
        self._locks: Set[str] = set()

    def register(self, name: str) -> None:
        with self._lock:
            self._locks.add(name)

    def add_edge(self, src: str, dst: str) -> None:
        tname = threading.current_thread().name
        with self._lock:
            entry = self._edges.get((src, dst))
            fresh = entry is None
            if fresh:
                entry = {"count": 0, "threads": set(),
                         "where": _caller_site()}
                self._edges[(src, dst)] = entry
            entry["count"] += 1
            entry["threads"].add(tname)
        if fresh:
            self.bank()

    def edges(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [{"src": s, "dst": d, "count": e["count"],
                     "threads": sorted(e["threads"]),
                     "where": e["where"]}
                    for (s, d), e in sorted(self._edges.items())]

    def locks(self) -> List[str]:
        with self._lock:
            return sorted(self._locks)

    def reset(self) -> None:
        with self._lock:
            self._edges.clear()
            self._locks.clear()
        self.bank()

    def bank(self, tag: str = "witness") -> None:
        profiler.record("locks", tag, locks=self.locks(),
                        edges=self.edges())


def _caller_site() -> str:
    """First stack frame outside this module — the acquisition site an
    inversion finding names."""
    f = sys._getframe(1)
    while f is not None and f.f_globals.get("__name__") == __name__:
        f = f.f_back
    return f"{f.f_code.co_filename}:{f.f_lineno}" if f is not None else ""


_GRAPH = _WitnessGraph()


def _on_acquire(name: str) -> None:
    st = _held_stack()
    for held in dict.fromkeys(st):
        if held != name:
            _GRAPH.add_edge(held, name)
    st.append(name)


def _on_release(name: str) -> None:
    st = _held_stack()
    for i in range(len(st) - 1, -1, -1):   # non-LIFO release tolerated
        if st[i] == name:
            del st[i]
            return


class WitnessLock:
    """Drop-in ``threading.Lock``/``RLock`` recording per-thread
    acquisition chains into the process-global witness graph. Re-entrant
    holds never self-edge; contention is unchanged (the real lock does
    the blocking, bookkeeping happens after acquisition succeeds)."""

    def __init__(self, name: str, _factory: Any = threading.Lock):
        self.name = str(name)
        self._lk = _factory()
        _GRAPH.register(self.name)

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        ok = self._lk.acquire(blocking, timeout)
        if ok:
            _on_acquire(self.name)
        return ok

    def release(self) -> None:
        self._lk.release()
        _on_release(self.name)

    def __enter__(self) -> "WitnessLock":
        self.acquire()
        return self

    def __exit__(self, *exc: object) -> None:
        self.release()


def Lock(name: str) -> WitnessLock:
    """An instrumented ``threading.Lock``."""
    return WitnessLock(name, threading.Lock)


def RLock(name: str) -> WitnessLock:
    """An instrumented ``threading.RLock``."""
    return WitnessLock(name, threading.RLock)


class WitnessCondition:
    """Instrumented ``threading.Condition`` over a :class:`WitnessLock`:
    ``wait()`` releases the witness hold for its sleep (exactly like the
    real lock) so a waiter's chain never fabricates an edge across the
    wait."""

    def __init__(self, name: str, lock: Optional[WitnessLock] = None):
        self._wl = lock if lock is not None else WitnessLock(
            name, threading.RLock)
        self.name = self._wl.name
        self._cond = threading.Condition(self._wl._lk)

    def acquire(self, *a: Any, **kw: Any) -> bool:
        return self._wl.acquire(*a, **kw)

    def release(self) -> None:
        self._wl.release()

    def __enter__(self) -> "WitnessCondition":
        self.acquire()
        return self

    def __exit__(self, *exc: object) -> None:
        self.release()

    def wait(self, timeout: Optional[float] = None) -> bool:
        _on_release(self.name)
        try:
            return self._cond.wait(timeout)
        finally:
            _on_acquire(self.name)

    def notify(self, n: int = 1) -> None:
        self._cond.notify(n)

    def notify_all(self) -> None:
        self._cond.notify_all()


def Condition(name: str,
              lock: Optional[WitnessLock] = None) -> WitnessCondition:
    """An instrumented ``threading.Condition``."""
    return WitnessCondition(name, lock)


def observed_edges() -> List[Dict[str, Any]]:
    """The witness's observed acquisition-order edges (src held when dst
    was acquired), with counts, thread names, first-observation site."""
    return _GRAPH.edges()


def reset_witness() -> None:
    """Clear the observed graph (tests; a fresh scenario)."""
    _GRAPH.reset()


def bank_witness(tag: str = "witness") -> None:
    """Bank the current observed graph into
    ``tony_tpu.profiler.report("locks")`` under ``tag``."""
    _GRAPH.bank(tag)


# ---------------------------------------------------------------------------
# Cycle detection over the merged static + observed graph
# ---------------------------------------------------------------------------

def find_cycles(edges: Iterable[Tuple[str, str]]) -> List[List[str]]:
    """Simple cycles in the directed graph, each as a closed node path
    ``[a, b, ..., a]``, deduplicated up to rotation. DFS with a path
    stack — lock graphs are tiny, exhaustiveness beats cleverness."""
    adj: Dict[str, List[str]] = {}
    for s, d in edges:
        if d not in adj.setdefault(s, []):
            adj[s].append(d)
        adj.setdefault(d, [])
    seen: Set[Tuple[str, ...]] = set()
    cycles: List[List[str]] = []

    def dfs(node: str, path: List[str], on_path: Set[str]) -> None:
        for nxt in adj.get(node, ()):
            if nxt in on_path:
                i = path.index(nxt)
                cyc = path[i:]
                j = cyc.index(min(cyc))
                key = tuple(cyc[j:] + cyc[:j])
                if key not in seen:
                    seen.add(key)
                    cycles.append(list(key) + [key[0]])
                continue
            path.append(nxt)
            on_path.add(nxt)
            dfs(nxt, path, on_path)
            on_path.discard(nxt)
            path.pop()

    for start in sorted(adj):
        dfs(start, [start], {start})
    return cycles


def check_lock_order(
        static_edges: Sequence[Tuple[str, str, str]] = (),
        observed: Optional[Sequence[Dict[str, Any]]] = None
) -> List[ConcFinding]:
    """Merge the static graph with the witness's observed edges (default:
    the live process-global graph) and return one ``lock_order``
    finding per cycle — a potential deadlock, NAMED, with the
    acquisition sites that contributed each edge."""
    if observed is None:
        observed = observed_edges()
    merged: List[Tuple[str, str]] = []
    origin: Dict[Tuple[str, str], List[str]] = {}
    for s, d, where in static_edges:
        merged.append((s, d))
        origin.setdefault((s, d), []).append(f"static {where}")
    for e in observed:
        key = (e["src"], e["dst"])
        merged.append(key)
        origin.setdefault(key, []).append(
            f"witness {e.get('where', '')} "
            f"(x{e.get('count', 1)}, threads "
            f"{','.join(e.get('threads', []))})")
    findings: List[ConcFinding] = []
    for cyc in find_cycles(merged):
        pairs = list(zip(cyc, cyc[1:]))
        prov = " -> ".join(cyc)
        sites = "; ".join(f"{a}->{b}: {origin[(a, b)][0]}"
                          for a, b in pairs)
        findings.append(ConcFinding(
            "lock_order", "inversion",
            f"potential deadlock: lock-order cycle {prov} ({sites})",
            provenance=prov))
    return findings


# ---------------------------------------------------------------------------
# Baseline (the committed blessings file under tests/signatures/)
# ---------------------------------------------------------------------------

def load_baseline(path: str | Path) -> Dict[str, str]:
    """``fingerprint -> reason`` from the committed baseline; missing
    file means an empty baseline (zero pre-blessed findings)."""
    p = Path(path)
    if not p.is_file():
        return {}
    data = json.loads(p.read_text())
    return {e["fingerprint"]: e.get("reason", "")
            for e in data.get("blessed", [])}


def write_baseline(path: str | Path, findings: Sequence[ConcFinding],
                   reason: str = "baselined at HEAD",
                   existing: Optional[Dict[str, str]] = None) -> None:
    """Rewrite the baseline to bless exactly the CURRENTLY-FIRING
    findings that are not pragma-blessed (pass the findings BEFORE
    :func:`apply_baseline` — pragma state resolved, baseline not yet
    applied), keeping the audited reason of every fingerprint already in
    ``existing`` — a regen adds the new and prunes the stale but never
    silently un-blesses (or re-words) a still-firing audited finding."""
    existing = existing or {}
    entries: Dict[str, str] = {}
    for f in findings:
        if f.blessed:                     # pragma-blessed: no entry needed
            continue
        fp = f.fingerprint()
        entries.setdefault(fp, existing.get(fp, reason))
    Path(path).write_text(json.dumps(
        {"blessed": [{"fingerprint": fp, "reason": entries[fp]}
                     for fp in sorted(entries)]},
        indent=2, sort_keys=True) + "\n")


def apply_baseline(findings: Sequence[ConcFinding],
                   baseline: Dict[str, str]
                   ) -> Tuple[List[ConcFinding], List[ConcFinding]]:
    """Split into (active, blessed): pragma-blessed findings and
    baseline-fingerprint matches land in the second list."""
    from dataclasses import replace

    active: List[ConcFinding] = []
    blessed: List[ConcFinding] = []
    for f in findings:
        if f.blessed:
            blessed.append(f)
        elif f.fingerprint() in baseline:
            blessed.append(replace(
                f, blessed=True, blessed_by=baseline[f.fingerprint()]))
        else:
            active.append(f)
    return active, blessed


# ---------------------------------------------------------------------------
# Tree entry points (mirror srclint's)
# ---------------------------------------------------------------------------



def analyze_tree(root: str | Path
                 ) -> Tuple[List[ConcFinding],
                            List[Tuple[str, str, str]]]:
    """Lint every ``.py`` under ``root``; returns ``(findings, static
    lock-order edges)`` with pragma state resolved per finding."""
    root = Path(root)
    findings: List[ConcFinding] = []
    edges: List[Tuple[str, str, str]] = []
    paths = [root] if root.is_file() else sorted(root.rglob("*.py"))
    for path in paths:
        if "__pycache__" in path.parts:
            continue
        fs, es = lint_source(path.read_text(),
                             _package_rel(path, root), str(path))
        findings.extend(fs)
        edges.extend(es)
    return findings, edges


@dataclass
class ConcReport:
    """One concurrency-analysis run over a tree. ``observed`` is the
    witness-edge set the cycle check actually consumed — the summary and
    the banked record count THAT, not whatever the live global graph
    holds at print time."""
    findings: List[ConcFinding]          # active (unblessed) only
    blessed: List[ConcFinding]
    static_edges: List[Tuple[str, str, str]]
    observed: List[Dict[str, Any]]

    @property
    def ok(self) -> bool:
        return not self.findings

    def summary(self) -> str:
        state = "CLEAN" if self.ok else f"{len(self.findings)} finding(s)"
        return (f"[concurrency] {state} ({len(self.blessed)} blessed, "
                f"{len(self.static_edges)} static lock-order edge(s), "
                f"{len(self.observed)} witnessed)")


def analyze_concurrency(root: Optional[str | Path] = None,
                        baseline_path: Optional[str | Path] = None,
                        include_witness: bool = True) -> ConcReport:
    """The full pass: discipline + hygiene lint over ``root`` (default:
    the installed package), lock-order cycle check over the static graph
    merged with the live witness graph, baseline applied. Banks a
    summary record next to the jaxpr analyzer's
    (``profiler.report("analysis")``, tag ``"concurrency"``)."""
    findings, edges = analyze_tree(root or default_root())
    observed = observed_edges() if include_witness else []
    findings.extend(check_lock_order(edges, observed))
    baseline = load_baseline(baseline_path) if baseline_path else {}
    active, blessed = apply_baseline(findings, baseline)
    report = ConcReport(active, blessed, edges, observed)
    profiler.record("analysis", "concurrency",
                    findings=len(active), blessed=len(blessed),
                    rules=sorted({f.rule for f in active}),
                    static_edges=len(edges),
                    witnessed_edges=len(observed))
    return report


def main(argv: Optional[Sequence[str]] = None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m tony_tpu.analysis.concurrency",
        description="lock-discipline / lock-order / thread-hygiene "
                    "lint (make lint; tony analyze --concurrency)")
    p.add_argument("paths", nargs="*", help="package dirs or files "
                   "(default: the installed tony_tpu)")
    p.add_argument("--baseline", help="committed blessings file "
                   "(tests/signatures/concurrency.json)")
    p.add_argument("--update-baseline", action="store_true",
                   help="rewrite the baseline from the current active "
                        "findings instead of failing on them")
    args = p.parse_args(list(argv) if argv is not None else None)
    roots = [Path(a) for a in args.paths] or [default_root()]
    findings: List[ConcFinding] = []
    edges: List[Tuple[str, str, str]] = []
    for r in roots:
        if not r.exists():
            # A typo'd path must fail the gate, not lint nothing.
            print(f"concurrency: path does not exist: {r}")
            return 2
        fs, es = analyze_tree(r)
        findings.extend(fs)
        edges.extend(es)
    findings.extend(check_lock_order(edges))
    baseline = load_baseline(args.baseline) if args.baseline else {}
    active, blessed = apply_baseline(findings, baseline)
    if args.update_baseline:
        if not args.baseline:
            print("--update-baseline needs --baseline <file>")
            return 2
        # Pre-apply findings + the existing baseline: still-firing
        # blessings keep their audited reasons, only the NEW active
        # findings pick up the placeholder (and stale entries prune).
        write_baseline(args.baseline, findings, existing=baseline)
        kept = sum(1 for f in blessed if f.fingerprint() in baseline)
        print(f"concurrency: baselined {len(active)} new finding(s), "
              f"kept {kept} existing blessing(s), into {args.baseline}")
        return 0
    for f in active:
        print(f)
    if active:
        print(f"concurrency: {len(active)} finding(s)")
        return 1
    print(f"concurrency: clean ({len(blessed)} blessed, "
          f"{len(edges)} static lock-order edge(s))")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
