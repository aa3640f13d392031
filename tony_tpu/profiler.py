"""Observability from inside the program: spans and counters on one
timeline, the trace-time plan registry, and trace collection.

Three halves, one module (imports no jax at module level: the executor,
the AM and the history plane import it too):

* **Spans and counters** (:func:`span`, :func:`count`,
  :func:`watch_builds`). A span always enters a
  ``jax.profiler.TraceAnnotation`` when jax is imported, so with a
  profiler session active it lands on the host plane of the same
  ``.xplane.pb`` as the device operations, on one clock; with none it
  costs a TraceMe enter/exit. The *set-up* spans (:data:`SETUP_SPANS`:
  the process's start, the heavy imports the package causes, the
  rendezvous, the backend's start, the state, the restore, the warm-up,
  the first step and the rungs it tries) and every program jax builds or
  loads (``watch_builds``) are also kept on a bounded in-memory timeline
  with process-local counters, from an origin the process did not choose
  (``t_launch``: when the executor launched it), and
  :func:`write_timeline` puts that in ``timeline.json`` beside the stats
  file the executor names (``TONY_SERVE_STATS``). The executor hands the
  file to the AM, which logs one ``TASK_TIMELINE`` event per task:
  ``tony history show`` then says where a task's start went and how much
  of it lies under no span (:func:`unspanned`), with no profiler
  attached. Per-step and per-iteration spans go to the TraceMe only —
  nothing is appended on the hot path.
* **The plan registry** (:func:`record`, :func:`report`,
  :func:`reset_records` over :data:`KINDS`): what the planners decided at
  jit-trace time, last plan per tag wins.
* **Trace collection** (SURVEY.md §5.1): every task whose job set
  ``tony.task.profiler.enabled`` runs ``jax.profiler.start_server`` on
  the port the JAXRuntime assigned (training tasks from
  ``distributed.initialize``, serve replicas from ``replica.main``), the
  executor pushes ``host:port`` to the AM via ``register_callback_info``,
  and :func:`collect_traces` pulls one synchronized trace over the XLA
  profiler gRPC service into ``<history>/traces/<app_id>/`` — on
  ``tony profile <app_id>`` (any time while the job runs) or
  ``tony.task.profiler.collect-after-s`` (AM-side, once, N seconds after
  the gang reaches RUNNING).

The capture client is xprof's (version-matched to jax's tsl profiler
service in this image); explicit tracer levels are passed because the
defaults collect nothing from a remote jax server.
"""

from __future__ import annotations

import atexit
import contextlib
import copy
import json
import logging
import os
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from tony_tpu import constants

# The timeline's origin: the stamp the executor put into the environment
# as it launched this process, taken out so that no process this one
# starts inherits it. Where there is none (outside a tony task) or it is
# no time before now, the origin is this import.
_T_IMPORT = time.time()
try:
    _T_LAUNCH = float(os.environ.pop(constants.ENV_LAUNCH_TIME, ""))
except ValueError:
    _T_LAUNCH = _T_IMPORT
if not 0 < _T_LAUNCH <= _T_IMPORT:
    _T_LAUNCH = _T_IMPORT

# Tracer levels: host TraceMe spans + python + device. Without these the
# remote session returns "no trace data" (measured, not hypothetical).
_TRACE_OPTIONS = {
    "host_tracer_level": 2,
    "python_tracer_level": 1,
    "device_tracer_level": 1,
}


# ---------------------------------------------------------------------------
# Spans and counters. Names are an interface: benchmark readers, `tony
# history` and the README's Observability section find them by name.

# The set-up spans: kept on the timeline (and so in the job's event log),
# because they happen before any profiler session can start. Each occurs
# once in a task's start but ``tony:import`` (once for each heavy module
# the package is the first to import: `importing`) and
# ``tony:remat_rung`` (once for each rung a cold first step tries, twelve
# at most: the ladder and its floor, merged and fenced);
# ``tony:python_start`` is not entered but made by `timeline` from the
# launch stamp.
SETUP_SPANS = frozenset({
    "tony:python_start", "tony:import", "tony:dist_initialize",
    "tony:backend_init", "tony:create_train_state", "tony:restore",
    "tony:warm", "tony:first_step", "tony:remat_rung"})
TIMELINE_FILE = "timeline.json"
# Set-up spans kept. A task's start records a dozen or two, not a handful
# (five imports and twelve rungs at most, one of each other; a replica one
# more for each restore and warm-up): a bound no start reaches, there for
# the program that calls a spanned function in a loop.
MAX_SPANS = 256
MAX_BUILDS = 2048        # build records kept; the counters never stop
MIN_BUILD_RECORD_S = 1e-3   # a shorter trace or lowering is only counted

# jax.monitoring duration events -> the kind of a build record. Checked
# against jax 0.9.0: backend_compile_duration wraps compile_or_get_cached,
# so it also fires (after cache_retrieval_time_sec, on the same thread)
# for a program that was only loaded from the persistent cache.
_BUILD_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "load",
}
_BUILD_COUNTERS = {"trace": ("programs_traced", "trace_s"),
                   "lower": ("programs_lowered", "lower_s"),
                   "compile": ("programs_compiled", "compile_s"),
                   "load": ("programs_loaded", "load_s")}
_CACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}


class _Timeline:
    """The process's set-up spans, build records and counters."""

    def __init__(self) -> None:
        self.lock = threading.Lock()
        self.spans: List[Dict[str, object]] = []
        self.builds: List[Dict[str, object]] = []
        self.builds_dropped = 0
        self.counters: Dict[str, float] = {}
        self.local = threading.local()   # open set-up spans; pending load

    def open_spans(self) -> List[str]:
        stack = getattr(self.local, "stack", None)
        if stack is None:
            stack = self.local.stack = []
        return stack


_TIMELINE = _Timeline()
_armed: set = set()      # "exit": atexit write registered; "builds": listening


class span(contextlib.ContextDecorator):
    """``with span(name, **attrs):`` (or ``@span(name)`` on a function) —
    a ``jax.profiler.TraceAnnotation`` when jax is imported, and for the
    names in :data:`SETUP_SPANS` also one timeline record ``{name, t0,
    t1, parent, attrs}`` (epoch seconds; ``parent`` is the enclosing
    set-up span of this thread). ``attrs`` may be filled in before the
    span ends (``sp.attrs.update(step=...)``); the TraceMe sees only what
    it was given at the start."""

    def __init__(self, name: str, **attrs) -> None:
        self.name, self.attrs = name, attrs
        self._ann = None

    def _recreate_cm(self) -> "span":
        return span(self.name, **self.attrs)

    def __enter__(self) -> "span":
        prof = getattr(sys.modules.get("jax"), "profiler", None)
        if prof is not None:
            self._ann = prof.TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        if self.name in SETUP_SPANS:
            stack = _TIMELINE.open_spans()
            self._parent = stack[-1] if stack else None
            stack.append(self.name)
            self._t0 = time.time()
        return self

    def __exit__(self, *exc) -> bool:
        if self.name in SETUP_SPANS:
            t1 = time.time()
            _TIMELINE.open_spans().pop()
            with _TIMELINE.lock:
                if len(_TIMELINE.spans) < MAX_SPANS:
                    _TIMELINE.spans.append({
                        "name": self.name, "t0": self._t0, "t1": t1,
                        "parent": self._parent, "attrs": dict(self.attrs)})
            _arm_exit_write()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


def importing(module: str):
    """``with importing("optax"): import optax`` — the set-up span
    ``tony:import`` (attr ``module``) where this statement is the first
    of the process to import ``module``, nothing where the module is
    loaded already. For the third-party imports of 0.2 s or more that the
    package causes (PERF.md §5); a user's own stay unspanned."""
    if module in sys.modules:
        return contextlib.nullcontext()
    return span("tony:import", module=module)


def backend_devices() -> list:
    """``jax.devices()``; where this call is the one that starts the
    backend (the first in the process), it is the set-up span
    ``tony:backend_init`` — TPU start-up is seconds, and jax 0.9.0
    reports it through no ``jax.monitoring`` event."""
    import jax
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        return jax.devices()
    with span("tony:backend_init"):
        return jax.devices()


def count(name: str, n: float = 1) -> None:
    """Bump the process-local counter ``name``."""
    with _TIMELINE.lock:
        _TIMELINE.counters[name] = _TIMELINE.counters.get(name, 0) + n


def count_once(name: str, n: float) -> None:
    """A trace-time fact on the task's timeline: recorded by the first
    trace, not again by init, remat or a re-trace."""
    with _TIMELINE.lock:
        _TIMELINE.counters.setdefault(name, n)


def counters() -> Dict[str, float]:
    with _TIMELINE.lock:
        return dict(_TIMELINE.counters)


def _on_build(event: str, secs: float, **_) -> None:
    kind = _BUILD_EVENTS.get(event)
    if kind is None:
        return
    if kind == "load":
        _TIMELINE.local.loaded = True
    elif kind == "compile" and getattr(_TIMELINE.local, "loaded", False):
        _TIMELINE.local.loaded = False      # the load was the build
        return
    n_name, s_name = _BUILD_COUNTERS[kind]
    count(n_name)
    count(s_name, secs)
    if secs < MIN_BUILD_RECORD_S and kind in ("trace", "lower"):
        return          # every eager jnp call traces a jit: thousands
    with _TIMELINE.lock:
        if len(_TIMELINE.builds) < MAX_BUILDS:
            _TIMELINE.builds.append(
                {"t": time.time(), "kind": kind, "s": secs})
        else:
            _TIMELINE.builds_dropped += 1


def _on_cache_event(event: str, **_) -> None:
    name = _CACHE_EVENTS.get(event)
    if name is not None:
        count(name)


def watch_builds() -> None:
    """Count every program jax traces, lowers, compiles or loads from its
    persistent cache in this process (``programs_traced`` /
    ``programs_lowered`` / ``programs_compiled`` / ``programs_loaded``,
    their ``trace_s`` / ``lower_s`` / ``compile_s`` / ``load_s`` sums,
    ``cache_hits`` / ``cache_misses``) and keep one ``build`` record
    ``{t, kind, s}`` (``t`` = when it ended) on the timeline for each
    compile and load, and for each trace and lowering of a millisecond or
    more.
    One ``jax.monitoring`` listener pair, installed once a process
    (``distributed.initialize``, ``replica.main``); it runs only when
    something is built, so a steady window pays nothing. A nested jit's
    trace is inside its caller's: ``trace_s`` counts it twice, the
    records' intervals do not hide it."""
    if "builds" in _armed:
        return
    _armed.add("builds")
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_build)
    jax.monitoring.register_event_listener(_on_cache_event)
    _arm_exit_write()


def build_totals(c: Optional[Dict[str, float]] = None) -> Dict[str, float]:
    """``{programs_built, build_s}`` of a counters dict (this process's so
    far, by default): programs compiled or loaded, and the seconds of
    tracing, lowering, compiling and loading."""
    c = counters() if c is None else c
    return {"programs_built": c.get("programs_compiled", 0)
            + c.get("programs_loaded", 0),
            "build_s": sum(c.get(k, 0.0) for k in
                           ("trace_s", "lower_s", "compile_s", "load_s"))}


def timeline() -> Dict[str, object]:
    """A copy of what this process has recorded so far. ``t_launch`` is
    when the executor launched the process; from there to this module's
    import is the span ``tony:python_start`` (none where nothing stamped
    the launch: the origin is then the import)."""
    start = [] if _T_LAUNCH == _T_IMPORT else [{
        "name": "tony:python_start", "t0": _T_LAUNCH, "t1": _T_IMPORT,
        "parent": None, "attrs": {}}]
    with _TIMELINE.lock:
        return {"pid": os.getpid(), "written": time.time(),
                "t_launch": _T_LAUNCH,
                "spans": start + [dict(s) for s in _TIMELINE.spans],
                "builds": [dict(b) for b in _TIMELINE.builds],
                "builds_dropped": _TIMELINE.builds_dropped,
                "counters": dict(_TIMELINE.counters)}


def unspanned(tl: Dict[str, object], until: Optional[float] = None
              ) -> Optional[Tuple[float, float]]:
    """``(seconds under no set-up span and no build record, seconds in
    all)`` of a timeline's start: from ``t_launch`` to ``until`` (the end
    of its last span by default). None for a timeline with no origin or
    nothing to end at."""
    t0, spans = tl.get("t_launch"), tl.get("spans") or []
    if t0 is None or not (spans or until is not None):
        return None
    t1 = max(s["t1"] for s in spans) if until is None else until
    held = [(s["t0"], s["t1"]) for s in spans] + [
        (b["t"] - b["s"], b["t"]) for b in tl.get("builds") or []]
    covered, edge = 0.0, t0
    for a, b in sorted(held):
        a, b = max(a, edge), min(b, t1)
        if b > a:
            covered, edge = covered + b - a, b
    return max(t1 - t0 - covered, 0.0), max(t1 - t0, 0.0)


def reset_timeline() -> None:
    """Forget spans, build records and counters (tests); the listeners
    and the exit hook stay installed."""
    global _TIMELINE
    _TIMELINE = _Timeline()


def timeline_path() -> Optional[Path]:
    """``timeline.json`` beside the stats file the executor named, or
    None outside a tony task."""
    stats = os.environ.get(constants.ENV_SERVE_STATS)
    return Path(stats).parent / TIMELINE_FILE if stats else None


def write_timeline(path: Optional[str | Path] = None) -> Optional[Path]:
    """Publish :func:`timeline` through stage-and-rename (the stats
    file's idiom). Called at the end of set-up, when ``train_loop``
    returns and at exit; advisory — an unwritable path never fails the
    task. Returns the path written, or None."""
    target = Path(path) if path else timeline_path()
    if target is None:
        return None
    tmp = target.with_name(f"{target.name}.tmp.{os.getpid()}")
    try:
        tmp.write_text(json.dumps(timeline()))
        os.replace(tmp, target)
    except OSError:
        return None
    return target


def read_timeline(path: str | Path) -> Optional[Dict[str, object]]:
    """What a task published, for the executor's relay: jax-free and
    failure-silent (a torn or absent file is None)."""
    try:
        with open(path) as fh:
            out = json.load(fh)
    except (OSError, ValueError):
        return None
    return out if isinstance(out, dict) else None


def _arm_exit_write() -> None:
    if "exit" not in _armed:
        _armed.add("exit")
        atexit.register(write_timeline)


# ---------------------------------------------------------------------------
# The plan registry. What a planner decided at jit-trace time — once per
# compile, not per step — or an engine measured per save / per delivered
# batch, inspectable next to the xplane traces without parsing HLO. One
# dict of fields per (kind, tag); the last record per tag wins (a
# recompile IS a new plan). Tests read it as the window on the planners;
# the train heartbeat reads "collective" for ``collective_bytes``.
KINDS = (
    # "accum_step", "gpipe", "gpipe_1f1b": bucket count and bytes, ticks,
    # per-level ("ici"/"dcn") plans — parallel/overlap.py, pipeline.py
    "overlap",
    # "<step>.grad.<level>.<op>", "accum.fwd_gather", "moe.dispatch|combine",
    # "<tag>.ppermute", "serve_decode": kind, plane, axes, nbytes (a list,
    # per issue) — parallel/sched.py's record_collective, serve/engine.py
    "collective",
    # "async_save": stall vs background write seconds, bytes, chunks of
    # the last save — ckpt/snapshot.py
    "ckpt",
    # the iterator's tag (default "input"): depth, steps, last/total wait,
    # mean wait and placement ms — data/prefetch.py
    "input",
    # "accum_update", "fused_update": rule, impl (pallas / xla), bucket
    # count and bytes, slot layout — ops/fused_optim.py
    "update",
    # "dense.<name>", "accum_gather", "attach": matmul shapes and impl,
    # scale-window geometry, raw vs int8 wire bytes — ops/quant.py, overlap.py
    "quant",
    # "<engine>", "<engine>_stats", "<engine>_spec", "replica": build-time
    # geometry, live telemetry, draft kind and depth, restore geometry —
    # serve/engine.py, serve/spec.py, serve/replica.py
    "serve",
    # the config name given to `tony analyze`, "concurrency": findings by
    # rule, waived count, signature digest — analysis/core.py, concurrency.py
    "analysis",
    # "witness": instrumented lock names and the observed (held, acquired)
    # edges with counts, threads, first sites — analysis/concurrency.py
    "locks",
    # "train_step": the residuals the backward keeps, the compiled step's
    # bytes against the device's limit, each rung's reading — remat.py
    "remat",
)
_RECORDS: Dict[str, Dict[str, Dict[str, object]]] = {k: {} for k in KINDS}
_RECORD_FAILED: set = set()


def record(kind: str, tag: str, /, **fields) -> None:
    """Bank ``fields`` under ``(kind, tag)``. Never raises: bookkeeping
    must not sink a step or a save, so an unknown kind or any other
    failure is logged once per kind at DEBUG — not per trace.
    ``kind`` and ``tag`` are positional-only: the collective schema has a
    field named ``kind``."""
    try:
        _RECORDS[kind][tag] = dict(fields)
    except Exception:  # noqa: BLE001
        if kind not in _RECORD_FAILED:
            _RECORD_FAILED.add(kind)
            logging.getLogger(__name__).debug(
                "%s profiler record %r failed; further failures "
                "suppressed", kind, tag, exc_info=True)


def report(kind: str) -> Dict[str, Dict[str, object]]:
    """Every record of ``kind`` by tag, as a deep copy — nested per-level
    and per-bucket lists included — so a caller can serialize or mutate
    it without poisoning the live store. An unknown kind is a KeyError."""
    return {k: copy.deepcopy(v) for k, v in _RECORDS[kind].items()}


def reset_records(kind: Optional[str] = None) -> None:
    """Forget the records of ``kind``, or of every kind."""
    for store in ([_RECORDS[kind]] if kind is not None
                  else _RECORDS.values()):
        store.clear()


def _trace_fn():
    """Resolve a capture callable ``(addr, logdir, duration_ms) -> None``.
    Import is deferred and gated: the profiler client is an optional
    dependency and must not tax AM/executor startup."""
    try:
        from xprof.convert import _pywrap_profiler_plugin as pp

        def capture(addr: str, logdir: str, duration_ms: int) -> None:
            pp.trace(addr, logdir, "", True, duration_ms, 3, _TRACE_OPTIONS)

        return capture
    except ImportError:
        return None


def traces_root(history_dir: str | Path, app_id: str) -> Path:
    return Path(history_dir) / "traces" / app_id


def endpoints_from_callback_info(info: Dict[str, str]) -> Dict[str, str]:
    """``{task_id: host:port}`` of live profiler servers, from the per-task
    callback payloads the executors pushed (``register_callback_info``)."""
    out: Dict[str, str] = {}
    for task_id, payload in dict(info).items():
        try:
            parsed = json.loads(payload)
        except ValueError:
            continue
        if isinstance(parsed, dict) and "profiler" in parsed:
            out[task_id] = str(parsed["profiler"])
    return out


def _wait_reachable(addr: str, timeout_s: float) -> bool:
    """Poll until ``host:port`` accepts TCP. The executor pushes the
    endpoint at user-process LAUNCH — the profiler server inside it only
    starts listening after the jax import, seconds later."""
    import socket

    host, _, port = addr.rpartition(":")
    host = host.strip("[]")   # "[::1]:9431" → host "::1"
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            socket.create_connection((host, int(port)), timeout=2.0).close()
            return True
        except OSError:
            time.sleep(0.25)
    return False


def collect_traces(endpoints: Dict[str, str], history_dir: str | Path,
                   app_id: str, duration_ms: int = 2000,
                   wait_reachable_s: float = 60.0, log=print) -> List[Path]:
    """Capture ONE synchronized trace session across every reachable
    endpoint into ``<history>/traces/<app_id>/`` (one capture call over
    the comma-joined address list — per-rank windows align in time, which
    is the whole point of profiling cross-host collectives; a sequential
    per-rank loop would give disjoint windows). A ``manifest.json``
    records task_id → endpoint so the portal can attribute the per-host
    xplane files. Unreachable ranks are reported and dropped from the
    session — a partial profile beats none."""
    capture = _trace_fn()
    if capture is None:
        log("trace collection unavailable: no profiler client (xprof) "
            "importable", file=sys.stderr)
        return []
    live = {}
    for task_id, addr in sorted(endpoints.items()):
        if _wait_reachable(addr, wait_reachable_s):
            live[task_id] = addr
        else:
            log(f"trace capture from {task_id} ({addr}) skipped: "
                f"endpoint not reachable within {wait_reachable_s:.0f}s")
    if not live:
        return []
    # Absolute: the logdir travels inside the profiler RPC and the SERVER
    # (the profiled process, different cwd) writes the xplane files — a
    # relative path silently lands in (or fails under) the wrong tree.
    dest = traces_root(history_dir, app_id).resolve()
    dest.mkdir(parents=True, exist_ok=True)
    (dest / "manifest.json").write_text(json.dumps(live, sort_keys=True))
    # A capture landing in a dead window (the job mid-compile, between
    # steps) legitimately returns zero events; retry a couple of times
    # before giving up — the operator asked for a trace, not for luck.
    # Success means a NEW xplane file: .pb files from an earlier capture
    # into the same dest must not mask an empty session.
    before = {p for p in dest.rglob("*") if p.suffix == ".pb"}
    for attempt in range(3):
        try:
            capture(",".join(live.values()), str(dest), duration_ms)
        except Exception as e:  # noqa: BLE001 — profiling is advisory
            log(f"trace capture from {sorted(live)} failed: {e}")
            return []
        if {p for p in dest.rglob("*") if p.suffix == ".pb"} - before:
            log(f"synchronized trace from {sorted(live)} -> {dest}")
            return [dest]
        log(f"trace capture from {sorted(live)} produced no events "
            f"(attempt {attempt + 1}/3; job idle or compiling?)")
        if attempt < 2:
            time.sleep(2.0)
    return []


def list_traces(history_dir: str | Path,
                app_id: str) -> Dict[str, List[Dict[str, object]]]:
    """Collected trace files per task, for the portal/CLI:
    ``{task_id: [{file, bytes}, ...]}``. Files are attributed to tasks by
    matching the manifest's endpoint (``host_port`` appears in the xplane
    filename); unattributed files land under ``"session"``."""
    root = traces_root(history_dir, app_id)
    if not root.is_dir():
        return {}
    manifest: Dict[str, str] = {}
    mpath = root / "manifest.json"
    if mpath.is_file():
        try:
            manifest = json.loads(mpath.read_text())
        except ValueError:
            pass
    by_task: Dict[str, List[Dict[str, object]]] = {}
    for p in sorted(root.rglob("*")):
        if not p.is_file() or p.name == "manifest.json":
            continue
        entry = {"file": str(p.relative_to(root)), "bytes": p.stat().st_size}
        owner = "session"
        for task_id, addr in manifest.items():
            # Brackets never appear in xplane filenames — "[::1]:9431"
            # must match as "__1_9431", not "[__1]_9431".
            if addr.replace("[", "").replace("]", "") \
                    .replace(":", "_") in p.name:
                owner = task_id.replace(":", "_")
                break
        by_task.setdefault(owner, []).append(entry)
    return by_task
