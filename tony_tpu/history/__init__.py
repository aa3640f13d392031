"""History server: the observability portal over jhist event logs (layer L⊥).

Mirrors ``tony-history-server`` (upstream Play-framework app ≈3,000 LoC,
unverified — SURVEY.md §0/§2.2/§3.5): scan the history root's
``finished/``+``intermediate/`` dirs, parse each job's jhist, and render a job
list plus per-job config/events/metrics pages. The reference renders Twirl
templates behind Play; here the same read path (:func:`tony_tpu.events
.list_jobs` / :func:`~tony_tpu.events.read_events`) feeds either a terminal
renderer (``tony history list|show``) or a stdlib ``http.server`` portal
(``tony history serve``) — no web framework dependency.
"""

from __future__ import annotations

import html
import json
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from typing import Any, Dict, List, Optional

from tony_tpu import events as ev
from tony_tpu.util import default_workdir


def default_history_dir() -> Optional[Path]:
    """The client workdir's per-job history dirs don't share one root; the
    conventional root is ``~/.tony-tpu/history``. Per-job
    ``tony.history.location`` overrides are honored by the workdir scan
    (:func:`_job_history_root`), not here."""
    root = Path.home() / ".tony-tpu" / "history"
    return root if root.is_dir() else None


def _job_history_root(jobdir: Path) -> Path:
    """One job's history root: its serialized conf's
    ``tony.history.location`` when set — the key the AM itself honors
    when it writes the jhist (and ``tony profile`` honors when it
    collects traces) — else the conventional ``<jobdir>/history``.
    Before this resolution `tony history` silently missed every job
    whose conf redirected the log."""
    from tony_tpu import constants
    from tony_tpu.conf import HISTORY_LOCATION, TonyConfig

    conf_path = jobdir / constants.TONY_JOB_JSON
    if conf_path.is_file():
        try:
            loc = TonyConfig.load(conf_path).get(HISTORY_LOCATION)
        except (OSError, ValueError):
            loc = None              # unreadable conf: scan falls back
        if loc:
            return Path(loc)
    return jobdir / "history"


def gather_jobs(history_dir: Optional[str | Path]) -> List[Dict[str, Any]]:
    """All jobs under a history root, or — when no single root exists —
    under every job root the client workdir knows: each jobdir's conf is
    resolved FIRST (``tony.history.location``), then the conventional
    ``<jobdir>/history`` fallback. Roots are deduped, so many jobs
    sharing one conf-pointed root list each job once."""
    if history_dir is not None:
        return list(ev.list_jobs(history_dir))
    roots: List[Path] = []
    root = default_history_dir()
    if root is not None:
        roots.append(root)
    workdir = default_workdir()
    if workdir.is_dir():
        for jobdir in sorted(workdir.iterdir()):
            if jobdir.is_dir():
                roots.append(_job_history_root(jobdir))
    jobs: List[Dict[str, Any]] = []
    seen = set()
    for r in roots:
        key = str(r.resolve())
        if key in seen or not r.is_dir():
            continue
        seen.add(key)
        jobs.extend(ev.list_jobs(r))
    return jobs


def find_job(app_id: str,
             history_dir: Optional[str | Path]) -> Optional[Dict[str, Any]]:
    for job in gather_jobs(history_dir):
        if job["app_id"] == app_id:
            return job
    return None


# Cap on rendered TASK_METRICS samples per task: a long job appends one
# sample per task per 5s, and rendering all of them makes the detail page
# O(runtime). Downsampled evenly, always keeping the newest sample.
MAX_TIMELINE_SAMPLES = 256


def _downsample(samples: List[Dict[str, Any]],
                limit: int = MAX_TIMELINE_SAMPLES) -> List[Dict[str, Any]]:
    n = len(samples)
    if n <= limit:
        return samples
    step = n / limit
    picked = [samples[min(n - 1, int(i * step))] for i in range(limit - 1)]
    picked.append(samples[-1])
    return picked


def billing_rollup(records: List[Dict[str, Any]],
                   conf_snapshot: Optional[Dict[str, Any]]) -> Dict[
                       str, Dict[str, float]]:
    """Per-tenant billed-token rollup, integrated reader-side from the
    SERVE_WINDOW ledger: each task's per-tenant ``tokens_per_s`` is
    held constant until its next window (left-Riemann), summed over the
    job, then multiplied by the tenant's QoS weight from the job's conf
    snapshot (``tony.serve.qos.tenants``; unlisted tenants bill at 1.0).
    Integrates over the RAW record stream — the downsampled portal
    timelines would under-integrate long jobs."""
    from tony_tpu.conf import SERVE_QOS_TENANTS
    from tony_tpu.serve.qos import parse_tenants

    weights: Dict[str, float] = {}
    raw = str((conf_snapshot or {}).get(SERVE_QOS_TENANTS, "") or "")
    if raw:
        try:
            weights = parse_tenants(raw)
        except ValueError:
            weights = {}            # malformed snapshot: bill at weight 1
    # tid -> (timestamp, {tenant: tokens_per_s}) of that task's last window.
    last: Dict[str, Any] = {}
    tokens: Dict[str, float] = {}
    for r in records:
        if r["type"] != ev.SERVE_WINDOW:
            continue
        p = r["payload"]
        tid = f"{p['job_type']}:{p['index']}"
        stats = p.get("stats") or {}
        tenants = stats.get("tenants") or {}
        rates = {name: float(t.get("tokens_per_s", 0.0))
                 for name, t in tenants.items() if isinstance(t, dict)}
        prev = last.get(tid)
        if prev is not None:
            dt = max(0.0, float(r["timestamp"]) - prev[0])
            for name, rate in prev[1].items():
                tokens[name] = tokens.get(name, 0.0) + rate * dt
        last[tid] = (float(r["timestamp"]), rates)
    out: Dict[str, Dict[str, float]] = {}
    for name in sorted(tokens):
        w = float(weights.get(name, 1.0))
        out[name] = {"tokens": tokens[name], "weight": w,
                     "billed": tokens[name] * w}
    return out


def job_detail(job: Dict[str, Any]) -> Dict[str, Any]:
    """Parsed view of one job: metadata, final status, per-task rows, events
    (reference: JobDetailPageController's model assembly)."""
    records = ev.read_events(job["path"])
    meta = job.get("metadata") or {}
    final = next((r["payload"] for r in records
                  if r["type"] == ev.APPLICATION_FINISHED), {})
    tasks = [dict(r["payload"], timestamp=r["timestamp"])
             for r in records if r["type"] == ev.TASK_FINISHED]
    # Per-task metrics timeline from the TASK_METRICS samples (reference:
    # the portal's per-task metrics pages over the MetricsRpc history).
    timelines: Dict[str, List[Dict[str, Any]]] = {}
    for r in records:
        if r["type"] == ev.TASK_METRICS:
            p = r["payload"]
            tid = f"{p['job_type']}:{p['index']}"
            timelines.setdefault(tid, []).append(
                {"timestamp": r["timestamp"], **(p.get("metrics") or {})})
    timelines = {tid: _downsample(samples)
                 for tid, samples in timelines.items()}
    # History plane (PR 18): serve latency windows, train step costs,
    # and the autoscaler's self-verifying decision records — all read
    # from the SAME jhist, zero extra collection hooks.
    serve_windows: Dict[str, List[Dict[str, Any]]] = {}
    train_steps: Dict[str, List[Dict[str, Any]]] = {}
    scale_decisions: List[Dict[str, Any]] = []
    for r in records:
        p = r["payload"]
        if r["type"] == ev.SERVE_WINDOW:
            tid = f"{p['job_type']}:{p['index']}"
            serve_windows.setdefault(tid, []).append(
                {"timestamp": r["timestamp"], **(p.get("stats") or {})})
        elif r["type"] == ev.TRAIN_STEP:
            tid = f"{p['job_type']}:{p['index']}"
            train_steps.setdefault(tid, []).append(
                {"timestamp": r["timestamp"],
                 **{k: v for k, v in p.items()
                    if k not in ("job_type", "index")}})
        elif r["type"] == ev.SCALE_DECISION:
            scale_decisions.append(dict(p, timestamp=r["timestamp"]))
    # Elastic resize timeline (PR 19): one record per lifecycle phase
    # (DRAINING / RE-GANG / RESTORING, or DEGRADED) — rendered as the
    # recovery timeline so an operator can see exactly where a
    # preemption's wall time went.
    resizes = [dict(r["payload"], timestamp=r["timestamp"])
               for r in records if r["type"] == ev.RESIZE]
    # Continuous publication timeline (PR 20): PUBLISH (a new manifest
    # pointer became the fleet target) interleaved with the per-replica
    # SWAP outcomes — together they reconstruct which version each
    # replica served when, and what every swap window cost.
    publications = [dict(r["payload"], timestamp=r["timestamp"])
                    for r in records if r["type"] == ev.PUBLISH]
    swaps = [dict(r["payload"], timestamp=r["timestamp"])
             for r in records if r["type"] == ev.SWAP]
    # Where each task's start went (PR 25): its set-up spans, build
    # records and counters as the task itself recorded them
    # (tony_tpu.profiler); the last attempt's record wins.
    task_timelines = {
        f"{r['payload']['job_type']}:{r['payload']['index']}":
        r["payload"].get("timeline") or {}
        for r in records if r["type"] == ev.TASK_TIMELINE}
    serve_windows = {tid: _downsample(s) for tid, s in serve_windows.items()}
    train_steps = {tid: _downsample(s) for tid, s in train_steps.items()}
    # Per-tenant SLO rollup from each task's NEWEST window (qps/queued/
    # blocks are instantaneous — summed across tasks; p99 is the fleet
    # worst; completed is a counter — summed).
    tenant_slo: Dict[str, Dict[str, float]] = {}
    for tid, samples in serve_windows.items():
        last = samples[-1]
        tenants = last.get("tenants") or {}
        if not isinstance(tenants, dict):
            continue
        for name, t in tenants.items():
            if not isinstance(t, dict):
                continue
            agg = tenant_slo.setdefault(name, {
                "qps": 0.0, "tokens_per_s": 0.0, "p99_ms": 0.0,
                "queued": 0.0, "blocks": 0.0, "completed": 0.0})
            for k in ("qps", "tokens_per_s", "queued", "blocks",
                      "completed"):
                agg[k] += float(t.get(k, 0.0))
            agg["p99_ms"] = max(agg["p99_ms"], float(t.get("p99_ms", 0.0)))
    # Replay verdicts: the load-bearing check — each SCALE_DECISION
    # recomputed from its own logged inputs must match the live delta.
    scale_replay: List[Dict[str, Any]] = []
    if scale_decisions:
        from tony_tpu.serve import scaling
        try:
            scale_replay = scaling.replay_decisions(scale_decisions)
        except (KeyError, TypeError, ValueError):
            scale_replay = []       # pre-PR-18 or truncated records
    all_running = next((r for r in records
                        if r["type"] == ev.ALL_TASKS_RUNNING), None)
    # Collected profiler traces live next to the jhist tree:
    # <history>/traces/<app_id>/<task>/... (SURVEY.md §5.1 collection half).
    from tony_tpu.profiler import list_traces
    history_root = Path(job["path"]).parent.parent
    return {
        "app_id": job["app_id"],
        "state": job["state"],
        "metadata": meta,
        "final": final,
        "tasks": tasks,
        "metrics_timelines": timelines,
        "serve_windows": serve_windows,
        "train_steps": train_steps,
        "tenant_slo": tenant_slo,
        "billing": billing_rollup(records, meta.get("config")),
        "resizes": resizes,
        "publications": publications,
        "swaps": swaps,
        "scale_decisions": scale_decisions,
        "scale_replay": scale_replay,
        "traces": list_traces(history_root, job["app_id"]),
        "timelines": task_timelines,
        "submit_to_running_s": (all_running or {}).get(
            "payload", {}).get("submit_to_running_s"),
        "events": records,
    }


# ---------------------------------------------------------------------------
# Terminal rendering (tony history list / show)
# ---------------------------------------------------------------------------

def render_list(jobs: List[Dict[str, Any]]) -> str:
    if not jobs:
        return "no jobs found"
    lines = [f"{'APP ID':<28} {'STATE':<9} {'USER':<10} {'NAME':<24} STARTED"]
    for job in jobs:
        m = job.get("metadata") or {}
        started = m.get("started")
        when = (time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(started))
                if started else "-")
        lines.append(f"{job['app_id']:<28} {job['state']:<9} "
                     f"{m.get('user', '-'):<10} {m.get('app_name', '-'):<24} "
                     f"{when}")
    return "\n".join(lines)


def render_show(detail: Dict[str, Any]) -> str:
    out = [f"application {detail['app_id']} [{detail['state']}]"]
    final = detail["final"]
    if final:
        out.append(f"  status: {final.get('status')}"
                   + (f" — {final['message']}" if final.get("message") else ""))
    if detail.get("submit_to_running_s"):
        out.append(f"  submit→all-running: "
                   f"{detail['submit_to_running_s']:.2f}s")
    m = detail["metadata"]
    if m:
        out.append(f"  user: {m.get('user')}  name: {m.get('app_name')}")
    if detail["tasks"]:
        out.append("  tasks:")
        for t in detail["tasks"]:
            metrics = t.get("metrics") or {}
            mstr = (" " + " ".join(f"{k}={v}" for k, v in sorted(
                metrics.items()))) if metrics else ""
            out.append(f"    {t['job_type']}:{t['index']} {t['status']} "
                       f"exit={t.get('exit_code')}{mstr}"
                       + (f" — {t['diagnostics']}" if t.get("diagnostics") else ""))
    if detail.get("tenant_slo"):
        out.append("  tenant SLO (latest window, fleet rollup):")
        for name, t in sorted(detail["tenant_slo"].items()):
            out.append(f"    {name}: p99={t['p99_ms']:.1f}ms "
                       f"qps={t['qps']:.2f} tok/s={t['tokens_per_s']:.1f} "
                       f"queued={t['queued']:.0f} blocks={t['blocks']:.0f} "
                       f"completed={t['completed']:.0f}")
    if detail.get("serve_windows"):
        out.append("  serve windows:")
        for tid, samples in sorted(detail["serve_windows"].items()):
            last = samples[-1]
            out.append(f"    {tid}: {len(samples)} window(s), last "
                       f"p99={float(last.get('p99_ms', 0.0)):.1f}ms "
                       f"qps={float(last.get('qps', 0.0)):.2f} "
                       f"queue={float(last.get('queue_depth', 0.0)):.0f} "
                       f"rejected="
                       f"{float(last.get('admission_rejections', 0.0)):.0f}")
    if detail.get("train_steps"):
        out.append("  train steps:")
        for tid, samples in sorted(detail["train_steps"].items()):
            last = samples[-1]
            mean_t = sum(float(s.get("step_time_s", 0.0))
                         for s in samples) / len(samples)
            out.append(f"    {tid}: {len(samples)} step(s), mean "
                       f"{mean_t * 1e3:.1f}ms/step, last "
                       f"step={int(last.get('step', 0))} "
                       f"mfu={float(last.get('mfu', 0.0)):.3f} "
                       f"coll={float(last.get('collective_bytes', 0.0)):.0f}B")
    if detail.get("resizes"):
        out.append("  resize timeline:")
        for p in detail["resizes"]:
            when = time.strftime("%H:%M:%S", time.localtime(p["timestamp"]))
            mark = "ok" if p.get("ok") else "FAILED"
            out.append(f"    {when} {p.get('phase')} "
                       f"[{p.get('trigger')}] {p.get('job_type')} "
                       f"{p.get('old_workers')}→{p.get('new_workers')} "
                       f"{float(p.get('wall_s', 0.0)):.2f}s [{mark}]"
                       + (f" — {p['detail']}" if p.get("detail") else ""))
    if detail.get("publications") or detail.get("swaps"):
        out.append("  publication timeline:")
        merged = sorted(
            [("PUBLISH", p) for p in detail.get("publications", [])]
            + [("SWAP", p) for p in detail.get("swaps", [])],
            key=lambda kp: kp[1]["timestamp"])
        for kind, p in merged:
            when = time.strftime("%H:%M:%S", time.localtime(p["timestamp"]))
            if kind == "PUBLISH":
                out.append(f"    {when} PUBLISH v{p.get('version')} "
                           f"(step {p.get('step')})"
                           + (f" — {p['note']}" if p.get("note") else ""))
            else:
                mark = "ok" if p.get("ok") else "FAILED"
                out.append(f"    {when} SWAP {p.get('job_type')}:"
                           f"{p.get('index')} "
                           f"v{p.get('from_version')}→v{p.get('to_version')} "
                           f"(step {p.get('step')}) "
                           f"{float(p.get('wall_s', 0.0)):.2f}s [{mark}]"
                           + (f" — {p['detail']}" if p.get("detail") else ""))
    if detail.get("billing"):
        out.append("  billing (tokens × weight, integrated over windows):")
        for name, b in sorted(detail["billing"].items()):
            out.append(f"    {name}: tokens={b['tokens']:.0f} "
                       f"weight={b['weight']:g} billed={b['billed']:.0f}")
    if detail.get("scale_replay"):
        ok = sum(1 for v in detail["scale_replay"] if v["match"])
        out.append(f"  scale decisions ({ok}/{len(detail['scale_replay'])} "
                   f"replay exactly):")
        for p, v in zip(detail["scale_decisions"], detail["scale_replay"]):
            when = time.strftime("%H:%M:%S", time.localtime(p["timestamp"]))
            mark = "ok" if v["match"] else f"MISMATCH(replay={v['replayed']})"
            out.append(f"    {when} {p.get('job_type')}: delta="
                       f"{p.get('delta'):+d} active={p.get('n_active')} "
                       f"[{mark}]")
    if detail.get("traces"):
        out.append("  traces:")
        for tid, files in sorted(detail["traces"].items()):
            total = sum(f["bytes"] for f in files)
            out.append(f"    {tid}: {len(files)} file(s), {total} bytes")
    if detail.get("timelines"):
        out.append("  task start timelines:")
        for tid, tl in sorted(detail["timelines"].items()):
            out += _render_timeline(tid, tl)
    out.append("  events:")
    for r in detail["events"]:
        when = time.strftime("%H:%M:%S", time.localtime(r["timestamp"]))
        out.append(f"    {when} {r['type']}")
    return "\n".join(out)


def _render_timeline(tid: str, tl: Dict[str, Any]) -> List[str]:
    """One task's set-up spans in start order (nested ones indented
    under what encloses them), what it built, and how much of its start
    — launch to the end of the last span — no span or build covers, as
    text lines."""
    from tony_tpu.profiler import build_totals, unspanned

    spans = sorted(tl.get("spans") or [],
                   key=lambda s: (s.get("t0", 0.0), -s.get("t1", 0.0)))
    c = tl.get("counters") or {}
    totals = build_totals(c)
    out = [f"    {tid}: {len(spans)} span(s); "
           f"{totals['programs_built']:.0f} program(s) "
           f"built or loaded ({c.get('programs_compiled', 0):.0f} compiled, "
           f"{c.get('programs_loaded', 0):.0f} from the cache) in "
           f"{totals['build_s']:.2f}s of tracing, lowering, compiling and "
           f"loading"]
    t_first = tl.get("t_launch") or (spans[0]["t0"] if spans else 0.0)
    open_until: List[float] = []     # ends of the spans enclosing this one
    for sp in spans:
        while open_until and sp["t0"] >= open_until[-1]:
            open_until.pop()
        depth = len(open_until) if sp.get("parent") else 0
        open_until.append(sp["t1"])
        attrs = " ".join(f"{k}={v}" for k, v in sorted(
            (sp.get("attrs") or {}).items()))
        out.append(f"      {'  ' * depth}"
                   f"+{sp['t0'] - t_first:.2f}s {sp['name']} "
                   f"{sp['t1'] - sp['t0']:.2f}s"
                   + (f" ({attrs})" if attrs else ""))
    bare = unspanned(tl)
    if bare is not None:
        out.append(f"      under no span: {bare[0]:.2f}s of {bare[1]:.2f}s")
    return out


def parse_when(s: Optional[str]) -> Optional[float]:
    """``--since``/``--until`` value → epoch seconds: raw epoch floats
    pass through; otherwise local-time ``YYYY-MM-DD`` or ``YYYY-MM-DD
    HH:MM:SS`` (the formats the list/show renderers print, so a window
    can be copied straight off their output). None/empty → None."""
    if not s:
        return None
    try:
        return float(s)
    except ValueError:
        pass
    for fmt in ("%Y-%m-%d %H:%M:%S", "%Y-%m-%dT%H:%M:%S", "%Y-%m-%d"):
        try:
            return time.mktime(time.strptime(s, fmt))
        except ValueError:
            continue
    raise ValueError(f"unparseable time {s!r} (want epoch seconds, "
                     f"YYYY-MM-DD or 'YYYY-MM-DD HH:MM:SS')")


def bill_rows(jobs: List[Dict[str, Any]], tenant: Optional[str] = None, *,
              since: Optional[float] = None,
              until: Optional[float] = None) -> List[Dict[str, Any]]:
    """The billing statement's structured rows — one per (job, tenant).
    ``since``/``until`` (epoch seconds) clip the SERVE_WINDOW ledger to
    a billing window BEFORE the rollup integrates it, so a monthly
    statement bills only that month's tokens however long the job
    ran."""
    rows: List[Dict[str, Any]] = []
    for job in jobs:
        records = ev.read_events(job["path"])
        if since is not None or until is not None:
            records = [
                r for r in records
                if (since is None or r.get("timestamp", 0.0) >= since)
                and (until is None or r.get("timestamp", 0.0) <= until)]
        meta = job.get("metadata") or {}
        for name, b in billing_rollup(records, meta.get("config")).items():
            if tenant is not None and name != tenant:
                continue
            rows.append({"app_id": job["app_id"], "tenant": name,
                         "tokens": b["tokens"], "weight": b["weight"],
                         "billed": b["billed"]})
    return rows


def render_bill(jobs: List[Dict[str, Any]],
                tenant: Optional[str] = None, *,
                since: Optional[float] = None,
                until: Optional[float] = None) -> str:
    """Cross-job billing statement for one tenant (or all tenants when
    ``tenant`` is None): each job's reader-side rollup, then the grand
    total. Pure jhist read — no AM involvement, so it works on finished
    and running jobs alike."""
    rows = bill_rows(jobs, tenant, since=since, until=until)
    who = tenant if tenant is not None else "any tenant"
    if not rows:
        return f"no serve-window ledgers found for {who}"
    out = [f"{'APP ID':<28} {'TENANT':<10} {'TOKENS':>12} "
           f"{'WEIGHT':>7} {'BILLED':>12}"]
    for r in rows:
        out.append(f"{r['app_id']:<28} {r['tenant']:<10} "
                   f"{r['tokens']:>12.0f} {r['weight']:>7g} "
                   f"{r['billed']:>12.0f}")
    total = sum(r["billed"] for r in rows)
    out.append(f"{'TOTAL':<28} {'':<10} {'':>12} {'':>7} {total:>12.0f}")
    return "\n".join(out)


# ---------------------------------------------------------------------------
# HTTP portal (tony history serve) — reference: the Play web app
# ---------------------------------------------------------------------------

_PAGE = """<!doctype html><html><head><title>{title}</title><style>
body{{font-family:sans-serif;margin:2em}}table{{border-collapse:collapse}}
td,th{{border:1px solid #ccc;padding:4px 10px;text-align:left}}
th{{background:#f0f0f0}}a{{text-decoration:none}}
.ok{{color:#070}}.bad{{color:#b00}}</style></head>
<body><h2>{title}</h2>{body}</body></html>"""


def _jobs_page(jobs: List[Dict[str, Any]]) -> str:
    rows = []
    for job in jobs:
        m = job.get("metadata") or {}
        started = m.get("started")
        when = (time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(started))
                if started else "-")
        rows.append(
            f"<tr><td><a href='/jobs/{html.escape(job['app_id'])}'>"
            f"{html.escape(job['app_id'])}</a></td>"
            f"<td>{html.escape(job['state'])}</td>"
            f"<td>{html.escape(str(m.get('user', '-')))}</td>"
            f"<td>{html.escape(str(m.get('app_name', '-')))}</td>"
            f"<td>{when}</td></tr>")
    body = ("<table><tr><th>app id</th><th>state</th><th>user</th>"
            "<th>name</th><th>started</th></tr>" + "".join(rows) + "</table>")
    return _PAGE.format(title="TonY-TPU jobs", body=body)


def _job_page(detail: Dict[str, Any]) -> str:
    final = detail["final"]
    status = final.get("status", detail["state"])
    cls = "ok" if status == "SUCCEEDED" else "bad"
    parts = [f"<p>status: <b class='{cls}'>{html.escape(str(status))}</b>"]
    if final.get("message"):
        parts.append(f" — {html.escape(final['message'])}")
    parts.append("</p><h3>Tasks</h3><table><tr><th>task</th><th>status</th>"
                 "<th>exit</th><th>metrics</th><th>diagnostics</th></tr>")
    for t in detail["tasks"]:
        metrics = ", ".join(f"{k}={v}" for k, v in sorted(
            (t.get("metrics") or {}).items()))
        parts.append(
            f"<tr><td>{html.escape(t['job_type'])}:{t['index']}</td>"
            f"<td>{html.escape(t['status'])}</td>"
            f"<td>{t.get('exit_code')}</td><td>{html.escape(metrics)}</td>"
            f"<td>{html.escape(t.get('diagnostics') or '')}</td></tr>")
    parts.append("</table>")
    if detail.get("submit_to_running_s"):
        parts.append(f"<p>submit→all-running: "
                     f"{detail['submit_to_running_s']:.2f}s</p>")
    if detail.get("metrics_timelines"):
        parts.append("<h3>Metrics timeline</h3>")
        for tid, samples in sorted(detail["metrics_timelines"].items()):
            parts.append(f"<h4>{html.escape(tid)} "
                         f"({len(samples)} samples)</h4>"
                         "<table><tr><th>time</th><th>metrics</th></tr>")
            for s in samples:
                when = time.strftime("%H:%M:%S",
                                     time.localtime(s["timestamp"]))
                vals = ", ".join(f"{k}={v}" for k, v in sorted(s.items())
                                 if k != "timestamp")
                parts.append(f"<tr><td>{when}</td>"
                             f"<td>{html.escape(vals)}</td></tr>")
            parts.append("</table>")
    if detail.get("tenant_slo"):
        parts.append("<h3>Tenant SLO dashboard</h3><table><tr>"
                     "<th>tenant</th><th>p99 ms</th><th>qps</th>"
                     "<th>tok/s</th><th>queued</th><th>blocks</th>"
                     "<th>completed</th></tr>")
        for name, t in sorted(detail["tenant_slo"].items()):
            parts.append(
                f"<tr><td>{html.escape(name)}</td>"
                f"<td>{t['p99_ms']:.1f}</td><td>{t['qps']:.2f}</td>"
                f"<td>{t['tokens_per_s']:.1f}</td>"
                f"<td>{t['queued']:.0f}</td><td>{t['blocks']:.0f}</td>"
                f"<td>{t['completed']:.0f}</td></tr>")
        parts.append("</table>")
    if detail.get("serve_windows"):
        parts.append("<h3>Serve latency windows</h3>")
        for tid, samples in sorted(detail["serve_windows"].items()):
            parts.append(f"<h4>{html.escape(tid)} ({len(samples)} "
                         f"windows)</h4><table><tr><th>time</th>"
                         "<th>qps</th><th>p99 ms</th><th>queue</th>"
                         "<th>rejected</th><th>deferred</th></tr>")
            for s in samples:
                when = time.strftime("%H:%M:%S",
                                     time.localtime(s["timestamp"]))
                parts.append(
                    f"<tr><td>{when}</td>"
                    f"<td>{float(s.get('qps', 0.0)):.2f}</td>"
                    f"<td>{float(s.get('p99_ms', 0.0)):.1f}</td>"
                    f"<td>{float(s.get('queue_depth', 0.0)):.0f}</td>"
                    f"<td>{float(s.get('admission_rejections', 0.0)):.0f}"
                    f"</td>"
                    f"<td>{float(s.get('qos_deferrals', 0.0)):.0f}</td>"
                    f"</tr>")
            parts.append("</table>")
    if detail.get("train_steps"):
        parts.append("<h3>Train step trend</h3>")
        for tid, samples in sorted(detail["train_steps"].items()):
            parts.append(f"<h4>{html.escape(tid)} ({len(samples)} "
                         f"steps)</h4><table><tr><th>time</th>"
                         "<th>step</th><th>step ms</th>"
                         "<th>collective B</th><th>MFU</th></tr>")
            for s in samples:
                when = time.strftime("%H:%M:%S",
                                     time.localtime(s["timestamp"]))
                parts.append(
                    f"<tr><td>{when}</td><td>{int(s.get('step', 0))}</td>"
                    f"<td>{float(s.get('step_time_s', 0.0)) * 1e3:.1f}</td>"
                    f"<td>{float(s.get('collective_bytes', 0.0)):.0f}</td>"
                    f"<td>{float(s.get('mfu', 0.0)):.3f}</td></tr>")
            parts.append("</table>")
    if detail.get("resizes"):
        parts.append("<h3>Resize timeline</h3><table><tr><th>time</th>"
                     "<th>phase</th><th>trigger</th><th>gang</th>"
                     "<th>workers</th><th>wall s</th><th>ok</th>"
                     "<th>detail</th></tr>")
        for p in detail["resizes"]:
            when = time.strftime("%H:%M:%S", time.localtime(p["timestamp"]))
            mark = ("<b class='ok'>ok</b>" if p.get("ok")
                    else "<b class='bad'>failed</b>")
            parts.append(
                f"<tr><td>{when}</td>"
                f"<td>{html.escape(str(p.get('phase')))}</td>"
                f"<td>{html.escape(str(p.get('trigger')))}</td>"
                f"<td>{html.escape(str(p.get('job_type')))}</td>"
                f"<td>{p.get('old_workers')}&rarr;{p.get('new_workers')}"
                f"</td><td>{float(p.get('wall_s', 0.0)):.2f}</td>"
                f"<td>{mark}</td>"
                f"<td>{html.escape(str(p.get('detail') or ''))}</td></tr>")
        parts.append("</table>")
    if detail.get("publications") or detail.get("swaps"):
        parts.append("<h3>Publication timeline</h3><table><tr>"
                     "<th>time</th><th>event</th><th>who</th>"
                     "<th>version</th><th>step</th><th>wall s</th>"
                     "<th>ok</th><th>detail</th></tr>")
        merged = sorted(
            [("PUBLISH", p) for p in detail.get("publications", [])]
            + [("SWAP", p) for p in detail.get("swaps", [])],
            key=lambda kp: kp[1]["timestamp"])
        for kind, p in merged:
            when = time.strftime("%H:%M:%S", time.localtime(p["timestamp"]))
            if kind == "PUBLISH":
                parts.append(
                    f"<tr><td>{when}</td><td>PUBLISH</td><td>train</td>"
                    f"<td>v{p.get('version')}</td><td>{p.get('step')}</td>"
                    f"<td></td><td></td>"
                    f"<td>{html.escape(str(p.get('note') or ''))}</td></tr>")
            else:
                mark = ("<b class='ok'>ok</b>" if p.get("ok")
                        else "<b class='bad'>failed</b>")
                parts.append(
                    f"<tr><td>{when}</td><td>SWAP</td>"
                    f"<td>{html.escape(str(p.get('job_type')))}:"
                    f"{p.get('index')}</td>"
                    f"<td>v{p.get('from_version')}&rarr;"
                    f"v{p.get('to_version')}</td><td>{p.get('step')}</td>"
                    f"<td>{float(p.get('wall_s', 0.0)):.2f}</td>"
                    f"<td>{mark}</td>"
                    f"<td>{html.escape(str(p.get('detail') or ''))}</td>"
                    f"</tr>")
        parts.append("</table>")
    if detail.get("billing"):
        parts.append("<h3>Billing</h3><table><tr><th>tenant</th>"
                     "<th>tokens</th><th>weight</th><th>billed</th></tr>")
        for name, b in sorted(detail["billing"].items()):
            parts.append(
                f"<tr><td>{html.escape(name)}</td>"
                f"<td>{b['tokens']:.0f}</td><td>{b['weight']:g}</td>"
                f"<td>{b['billed']:.0f}</td></tr>")
        parts.append("</table>")
    if detail.get("scale_replay"):
        parts.append("<h3>Autoscale decisions (replayed)</h3><table><tr>"
                     "<th>time</th><th>gang</th><th>delta</th>"
                     "<th>active</th><th>replay</th></tr>")
        for p, v in zip(detail["scale_decisions"], detail["scale_replay"]):
            when = time.strftime("%H:%M:%S", time.localtime(p["timestamp"]))
            if v["match"]:
                verdict = "<b class='ok'>match</b>"
            else:
                verdict = (f"<b class='bad'>mismatch "
                           f"(replay={v['replayed']})</b>")
            parts.append(
                f"<tr><td>{when}</td>"
                f"<td>{html.escape(str(p.get('job_type')))}</td>"
                f"<td>{p.get('delta'):+d}</td><td>{p.get('n_active')}</td>"
                f"<td>{verdict}</td></tr>")
        parts.append("</table>")
    if detail.get("traces"):
        parts.append("<h3>Profiler traces</h3><table><tr><th>task</th>"
                     "<th>file</th><th>bytes</th></tr>")
        for tid, files in sorted(detail["traces"].items()):
            for f in files:
                parts.append(f"<tr><td>{html.escape(tid)}</td>"
                             f"<td><code>{html.escape(str(f['file']))}</code>"
                             f"</td><td>{f['bytes']}</td></tr>")
        parts.append("</table><p>open with: <code>tensorboard --logdir "
                     "&lt;history&gt;/traces/"
                     + html.escape(detail['app_id']) + "/&lt;task&gt;</code>"
                     "</p>")
    parts.append("<h3>Events</h3><table><tr><th>time</th>"
                 "<th>type</th><th>payload</th></tr>")
    for r in detail["events"]:
        when = time.strftime("%Y-%m-%d %H:%M:%S", time.localtime(r["timestamp"]))
        payload = html.escape(json.dumps(r["payload"], sort_keys=True)[:400])
        parts.append(f"<tr><td>{when}</td><td>{html.escape(r['type'])}</td>"
                     f"<td><code>{payload}</code></td></tr>")
    parts.append("</table><h3>Config</h3><table><tr><th>key</th><th>value</th></tr>")
    for k, v in sorted((detail["metadata"].get("config") or {}).items()):
        parts.append(f"<tr><td>{html.escape(k)}</td>"
                     f"<td>{html.escape(str(v))}</td></tr>")
    parts.append("</table><p><a href='/'>← all jobs</a></p>")
    return _PAGE.format(title=f"Job {html.escape(detail['app_id'])}",
                        body="".join(parts))


class HistoryServer:
    """Tiny threaded HTTP portal over a history root."""

    def __init__(self, history_dir: Optional[str | Path],
                 host: str = "127.0.0.1", port: int = 19885):
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _send(self, code: int, body: str,
                      ctype: str = "text/html; charset=utf-8") -> None:
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self) -> None:
                try:
                    if self.path in ("/", "/jobs"):
                        self._send(200, _jobs_page(gather_jobs(outer.history_dir)))
                    elif self.path.startswith("/jobs/"):
                        app_id = self.path[len("/jobs/"):]
                        job = find_job(app_id, outer.history_dir)
                        if job is None:
                            self._send(404, _PAGE.format(
                                title="Not found",
                                body=f"<p>no job {html.escape(app_id)}</p>"))
                        else:
                            self._send(200, _job_page(job_detail(job)))
                    elif self.path == "/api/jobs":
                        self._send(200, json.dumps(
                            gather_jobs(outer.history_dir), default=str),
                            "application/json")
                    else:
                        self._send(404, _PAGE.format(
                            title="Not found", body="<p>404</p>"))
                except BrokenPipeError:
                    pass

        self.history_dir = Path(history_dir) if history_dir else None
        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self._httpd.server_address[1]

    def serve_forever(self) -> None:
        self._httpd.serve_forever()

    def shutdown(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()


def main(args) -> int:
    """CLI entry (``tony history ...``)."""
    history_dir = getattr(args, "history_dir", None)
    if args.action == "list":
        print(render_list(gather_jobs(history_dir)))
        return 0
    if args.action == "show":
        if not args.app_id:
            print("usage: tony history show <app_id>")
            return 2
        job = find_job(args.app_id, history_dir)
        if job is None:
            print(f"no job {args.app_id} found")
            return 1
        print(render_show(job_detail(job)))
        return 0
    if args.action == "bill":
        # The app_id positional doubles as the tenant name: `tony
        # history bill gold` rolls up gold's billed tokens across every
        # job the history scan can see; with no tenant, all tenants.
        try:
            since = parse_when(getattr(args, "since", None))
            until = parse_when(getattr(args, "until", None))
        except ValueError as e:
            print(f"tony history bill: {e}")
            return 2
        jobs = gather_jobs(history_dir)
        tenant = args.app_id or None
        if getattr(args, "json", False):
            print(json.dumps(bill_rows(jobs, tenant, since=since,
                                       until=until),
                             indent=2, sort_keys=True))
        elif getattr(args, "csv", False):
            rows = bill_rows(jobs, tenant, since=since, until=until)
            print("app_id,tenant,tokens,weight,billed")
            for r in rows:
                print(f"{r['app_id']},{r['tenant']},{r['tokens']:.0f},"
                      f"{r['weight']:g},{r['billed']:.0f}")
        else:
            print(render_bill(jobs, tenant, since=since, until=until))
        return 0
    if args.action == "serve":
        # Loopback by default: jhist pages expose full job configs; binding
        # wider is an explicit opt-in (--bind 0.0.0.0).
        server = HistoryServer(history_dir, host=getattr(
            args, "bind", "127.0.0.1") or "127.0.0.1", port=args.port)
        print(f"history portal at http://127.0.0.1:{server.port}/")
        try:
            server.serve_forever()
        except KeyboardInterrupt:
            server.shutdown()
        return 0
    return 2
