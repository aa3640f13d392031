"""Observability from inside the program: spans and counters on one
timeline, the trace-time plan registries, and trace collection.

Three halves, one module (imports no jax at module level: the executor,
the AM and the history plane import it too):

* **Spans and counters** (:func:`span`, :func:`count`,
  :func:`add_seconds`, :func:`watch_builds`). A span always enters a
  ``jax.profiler.TraceAnnotation`` when jax is imported, so with a
  profiler session active it lands on the host plane of the same
  ``.xplane.pb`` as the device operations, on one clock; with none it
  costs a TraceMe enter/exit. The few *set-up* spans (:data:`SETUP_SPANS`)
  and every program jax builds or loads (``watch_builds``) are also kept
  on a bounded in-memory timeline with process-local counters, and
  :func:`write_timeline` puts that in ``timeline.json`` beside the stats
  file the executor names (``TONY_SERVE_STATS``). The executor hands the
  file to the AM, which logs one ``TASK_TIMELINE`` event per task:
  ``tony history show`` then says where a task's start went, with no
  profiler attached. Per-step and per-iteration spans go to the TraceMe
  only — nothing is appended on the hot path.
* **Plan registries** (``record_overlap`` ... ``record_locks``): what
  the planners decided at jit-trace time, last plan per tag wins.
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
import json
import logging
import os
import sys
import threading
import time
from pathlib import Path
from typing import Dict, List, Optional

from tony_tpu import constants

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
# because they happen before any profiler session can start.
SETUP_SPANS = frozenset({
    "tony:dist_initialize", "tony:backend_init", "tony:create_train_state",
    "tony:restore", "tony:warm"})
TIMELINE_FILE = "timeline.json"
MAX_SPANS = 256          # set-up spans kept (a task records a handful)
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


def add_seconds(name: str, s: float) -> None:
    """Add ``s`` seconds to the process-local sum ``name``."""
    count(name, float(s))


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
    """A copy of what this process has recorded so far."""
    with _TIMELINE.lock:
        return {"pid": os.getpid(), "written": time.time(),
                "spans": [dict(s) for s in _TIMELINE.spans],
                "builds": [dict(b) for b in _TIMELINE.builds],
                "builds_dropped": _TIMELINE.builds_dropped,
                "counters": dict(_TIMELINE.counters)}


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


def _snapshot(store: Dict[str, Dict[str, object]]
              ) -> Dict[str, Dict[str, object]]:
    """THE report contract, shared by every registry below: a deep copy of
    the store — including nested per-level/per-bucket lists — so callers
    can serialize or mutate a report without poisoning the live records
    (the report schemas had drifted; ``tests/test_sched.py`` pins all of
    them on this one helper)."""
    import copy

    return {k: copy.deepcopy(v) for k, v in store.items()}


# ---------------------------------------------------------------------------
# Overlap-engine instrumentation (the comm/compute overlap tentpole): the
# engine's planners call :func:`record_overlap` at TRACE time — once per
# compile, not per step — so per-bucket collective sizes and schedule tick
# counts are inspectable next to the xplane traces without parsing HLO.
# Keyed by tag ("accum_step", "gpipe", "gpipe_1f1b"); last plan per tag
# wins (a recompile IS a new plan). Hierarchical/ZeRO-3 plans additionally
# carry a ``levels`` list — one entry per reduction level ("ici"/"dcn")
# with the collective op, its mesh axes, and the bytes each bucket moves
# AT THAT LEVEL (the DCN entry shows the scattered-chunk sizes, i.e. what
# actually crosses slices per bucket).
OVERLAP_RECORDS: Dict[str, Dict[str, object]] = {}


def record_overlap(tag: str, **fields) -> None:
    """Bank one overlap plan/schedule record (bucket count & bytes,
    microbatches, reduce op, per-level plans, schedule tick count...)."""
    OVERLAP_RECORDS[tag] = dict(fields)


def overlap_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every recorded overlap plan (deep-copied via
    :func:`_snapshot`: callers serialize this into bench/metrics JSON and
    must not alias the live registry)."""
    return _snapshot(OVERLAP_RECORDS)


def reset_overlap_records() -> None:
    OVERLAP_RECORDS.clear()


# ---------------------------------------------------------------------------
# Unified collective instrumentation (the collective-scheduler tentpole):
# ONE record schema for every inter-chip transfer the step issues —
# forward param gathers, gradient scatter/allreduce buckets, MoE expert
# all_to_all, pipeline ppermute edges — so "every collective is either
# hidden or accounted for" is inspectable from one report instead of four
# plane-specific ones. Writers go through :mod:`tony_tpu.parallel.sched`
# (``record_collective``); keyed by tag, last plan per tag wins. Schema
# (enforced by the sched-side writer, not here):
#   kind   — all_gather | psum_scatter | all_reduce | all_to_all | ppermute
#   plane  — fwd_gather | grad_reduce | moe | pipeline
#   axes   — mesh axes the collective runs over
#   nbytes — per-issue payload bytes (list)
# plus freeform extras (prefetch depth, level, chunk count, measured
# hidden/exposed seconds from the bench legs...).
COLLECTIVE_RECORDS: Dict[str, Dict[str, object]] = {}


def record_collective(tag: str, /, **fields) -> None:
    """Bank one collective schedule record under the unified schema."""
    COLLECTIVE_RECORDS[tag] = dict(fields)


def collective_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every scheduled collective (deep-copied via
    :func:`_snapshot` — same aliasing contract as the other reports)."""
    return _snapshot(COLLECTIVE_RECORDS)


def reset_collective_records() -> None:
    COLLECTIVE_RECORDS.clear()


# ---------------------------------------------------------------------------
# Checkpoint-plane instrumentation (tony_tpu.ckpt): the async snapshot
# engine records per-save timing — the stall the train loop actually paid
# (slot wait + device→host extract) vs the background write/commit time —
# keyed by tag ("async_save", "blocking_save"); last save per tag wins.
# run_ckpt_bench serializes this next to the overlap records so "async
# saves overlap training" is a measured number, not a design claim.
CKPT_RECORDS: Dict[str, Dict[str, object]] = {}


def record_ckpt(tag: str, **fields) -> None:
    """Bank one checkpoint-save record (stall/extract/write seconds,
    payload bytes, chunk count...)."""
    CKPT_RECORDS[tag] = dict(fields)


def ckpt_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every recorded checkpoint save (deep-copied via
    :func:`_snapshot` — same aliasing contract as
    :func:`overlap_report`)."""
    return _snapshot(CKPT_RECORDS)


def reset_ckpt_records() -> None:
    CKPT_RECORDS.clear()


# ---------------------------------------------------------------------------
# Input-plane instrumentation (tony_tpu.data): the prefetching device
# iterator records, per delivered batch, the time the train loop actually
# blocked waiting on the feed (the input stall — the transfer T3 says must
# hide under compute) plus rolling means of wait and host→device placement
# time. Keyed by iterator tag (default "input"); last step per tag wins.
# run_input_bench serializes this next to the overlap/ckpt records so
# "prefetch hides the feed" is a measured number (BENCH_r08).
INPUT_RECORDS: Dict[str, Dict[str, object]] = {}


def record_input(tag: str, **fields) -> None:
    """Bank one input-feed record (prefetch depth, steps, last/total wait
    seconds, mean wait/placement ms...)."""
    INPUT_RECORDS[tag] = dict(fields)


def input_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every recorded input feed (deep-copied via
    :func:`_snapshot` — same aliasing contract as
    :func:`overlap_report`)."""
    return _snapshot(INPUT_RECORDS)


def reset_input_records() -> None:
    INPUT_RECORDS.clear()


# ---------------------------------------------------------------------------
# Fused-optimizer instrumentation (tony_tpu.ops.fused_optim): the update
# plane records, at trace time, the bucket-major update schedule — bucket
# count and per-bucket payload bytes, which kernel path ran (pallas vs the
# pure-XLA fallback), the rule and its slot layout — keyed by tag
# ("accum_update" from the in-region accum path, "fused_update" from the
# standalone step); last plan per tag wins. run_optim_bench serializes
# this next to the overlap records so "one launch per bucket" is an
# inspectable number, not a design claim.
UPDATE_RECORDS: Dict[str, Dict[str, object]] = {}


def record_update(tag: str, /, **fields) -> None:
    """Bank one fused-optimizer update record (rule, impl, bucket count &
    bytes, slot layout, clip/decay config...)."""
    UPDATE_RECORDS[tag] = dict(fields)


def update_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every recorded update schedule (deep-copied via
    :func:`_snapshot` — same aliasing contract as the other reports)."""
    return _snapshot(UPDATE_RECORDS)


def reset_update_records() -> None:
    UPDATE_RECORDS.clear()


# ---------------------------------------------------------------------------
# Quantized-lane instrumentation (tony_tpu.ops.quant): the int8 lane
# records, at trace time, where quantization actually happened — per
# quant_dot call site (shapes, impl, per-channel, int8 vs bf16 operand
# bytes), the quantize-on-gather schedule (bucket count, delayed-scaling
# window, raw vs int8 wire bytes = the 4×-fewer-gather-bytes claim as an
# inspectable number), and the attach-time state geometry. Keyed by tag
# ("dense.<name>", "accum_gather", "attach"); last plan per tag wins.
# run_quant_bench serializes this next to the other records (BENCH_r11).
QUANT_RECORDS: Dict[str, Dict[str, object]] = {}


def record_quant(tag: str, /, **fields) -> None:
    """Bank one quantized-lane record (matmul shapes/impl, scale-window
    geometry, gather bytes saved...)."""
    QUANT_RECORDS[tag] = dict(fields)


def quant_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every recorded quantization site (deep-copied via
    :func:`_snapshot` — same aliasing contract as the other reports)."""
    return _snapshot(QUANT_RECORDS)


def reset_quant_records() -> None:
    QUANT_RECORDS.clear()


# ---------------------------------------------------------------------------
# Serving-plane instrumentation (tony_tpu.serve): the engine records its
# build-time geometry (context extent, block pool size, row block,
# decode buckets, join policy) under the engine tag and its live
# telemetry — the heartbeat triple qps/p99/queue-depth plus rates, and
# since the speculative lane (serve.spec) also tokens_per_forward,
# acceptance_rate, proposed/accepted token counts, and verify-launch
# counts — under "<tag>_stats"; the speculative geometry (draft kind,
# depth k) under "<tag>_spec"; the replica banks restore geometry under
# "replica". Keyed by tag; last record per tag wins. run_serve_bench /
# run_spec_bench serialize this next to the other records
# (BENCH_r12/r13).
SERVE_RECORDS: Dict[str, Dict[str, object]] = {}


def record_serve(tag: str, /, **fields) -> None:
    """Bank one serving-plane record (engine geometry, qps/p50/p99/
    queue-depth telemetry, replica restore geometry...)."""
    SERVE_RECORDS[tag] = dict(fields)


def serve_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every recorded serving-plane entry (deep-copied via
    :func:`_snapshot` — same aliasing contract as the other reports)."""
    return _snapshot(SERVE_RECORDS)


def reset_serve_records() -> None:
    SERVE_RECORDS.clear()


# ---------------------------------------------------------------------------
# Static-analysis instrumentation (tony_tpu.analysis): the jaxpr analyzer
# banks one record per analyzed step — finding counts by rule, waived
# count, the step-signature digest (eqn/collective counts, live-buffer
# high-water estimate) — keyed by analysis tag (the config name passed to
# `tony analyze` / analyze_accum_step); last run per tag wins. This is the
# machine-readable face of `analysis_report()` the ISSUE names alongside
# the existing report family.
ANALYSIS_RECORDS: Dict[str, Dict[str, object]] = {}


def record_analysis(tag: str, /, **fields) -> None:
    """Bank one static-analysis record (findings by rule, waived count,
    signature digest, collective census...)."""
    ANALYSIS_RECORDS[tag] = dict(fields)


def analysis_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every recorded analysis run (deep-copied via
    :func:`_snapshot` — same aliasing contract as the other reports)."""
    return _snapshot(ANALYSIS_RECORDS)


def reset_analysis_records() -> None:
    ANALYSIS_RECORDS.clear()


# ---------------------------------------------------------------------------
# Lock-witness instrumentation (tony_tpu.analysis.concurrency): the runtime
# witness banks the process-global observed lock-order graph — every (held,
# acquired) edge any thread produced through an instrumented
# Lock/RLock/Condition, with counts, thread names, and first-observation
# sites — under tag "witness" (re-banked whenever a NEW edge appears), and
# the concurrency lint banks its summary next to the jaxpr analyzer's in
# analysis_report(). Cycle detection over this graph merged with the static
# nested-`with` graph is what turns a potential deadlock into a named
# finding instead of a hung CI job.
LOCK_RECORDS: Dict[str, Dict[str, object]] = {}


def record_locks(tag: str, /, **fields) -> None:
    """Bank one lock-witness record (instrumented lock names, observed
    acquisition-order edges with counts/threads/sites...)."""
    LOCK_RECORDS[tag] = dict(fields)


def lock_report() -> Dict[str, Dict[str, object]]:
    """Snapshot of every recorded lock-witness entry (deep-copied via
    :func:`_snapshot` — same aliasing contract as the other reports)."""
    return _snapshot(LOCK_RECORDS)


def reset_lock_records() -> None:
    LOCK_RECORDS.clear()


# One guarded entry point for the trace-side recorders (overlap grad sync,
# ckpt snapshot, input prefetch): bookkeeping must never sink a step or a
# save, and a broken wiring is logged once per registry at DEBUG — not per
# trace — so it stays diagnosable without log spam.
_SAFE_RECORD_FAILED: set = set()


def safe_record(kind: str, tag: str, /, **fields) -> None:
    """Record into the ``kind`` registry (``"overlap"``/``"ckpt"``/
    ``"input"``/``"collective"``/``"update"``/``"quant"``/
    ``"serve"``/``"analysis"``/``"locks"``), swallowing any failure."""
    try:
        {"overlap": record_overlap, "ckpt": record_ckpt,
         "input": record_input, "collective": record_collective,
         "update": record_update, "quant": record_quant,
         "serve": record_serve, "analysis": record_analysis,
         "locks": record_locks}[kind](
             tag, **fields)
    except Exception:  # noqa: BLE001
        if kind not in _SAFE_RECORD_FAILED:
            _SAFE_RECORD_FAILED.add(kind)
            logging.getLogger(__name__).debug(
                "%s profiler record %r failed; further failures "
                "suppressed", kind, tag, exc_info=True)


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
        pass
    try:
        from tensorflow.python.profiler import profiler_client

        def capture(addr: str, logdir: str, duration_ms: int) -> None:
            # TF >= 2.16 requires a ProfilerOptions namedtuple (it calls
            # options._asdict()); a plain dict dies inside the client
            # with "'dict' object has no attribute '_asdict'" — measured
            # on this image's TF 2.20, where it broke every capture.
            options: object = _TRACE_OPTIONS
            try:
                from tensorflow.python.profiler.profiler_v2 import (
                    ProfilerOptions)
                options = ProfilerOptions(**{
                    k: v for k, v in _TRACE_OPTIONS.items()
                    if k in ProfilerOptions._fields})
            except ImportError:
                pass
            profiler_client.trace(f"grpc://{addr}", logdir, duration_ms,
                                  options=options)

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
        log("trace collection unavailable: no profiler client "
            "(xprof / tensorflow) importable", file=sys.stderr)
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
