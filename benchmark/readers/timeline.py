"""What a task recorded about its own start (tony_tpu.profiler): worker
0's TASK_TIMELINE record in the job's event log. ``args``: ``read`` is
``span`` (seconds of the first set-up span named ``args["span"]``),
``build_s`` (seconds before the window in which the task was tracing,
lowering, compiling or loading some program: the union of the build
records' intervals, so a nested jit's trace is not counted twice) or
``programs_built`` (programs compiled or loaded from the cache before the
window). A program that logs no such record reads None."""

from benchmark import harness, traceread


def task_timeline(art: dict, job_type: str = "worker", index: int = 0):
    """The newest TASK_TIMELINE payload of one task of the run, or None."""
    if not art.get("cell"):
        return None
    events = harness.jhist_events(
        harness.CACHE / "runs" / art["cell"] / "jobs")
    found = [e["payload"]["timeline"] for e in events
             if e.get("type") == "TASK_TIMELINE"
             and e["payload"].get("job_type") == job_type
             and e["payload"].get("index") == index]
    return found[-1] if found else None


def reduce(timeline: dict, t_window: float, args: dict):
    if args["read"] == "span":
        spans = sorted((s for s in timeline.get("spans", [])
                        if s["name"] == args["span"]),
                       key=lambda s: s["t0"])
        return spans[0]["t1"] - spans[0]["t0"] if spans else None
    builds = [b for b in timeline.get("builds", []) if b["t"] < t_window]
    if args["read"] == "programs_built":
        return sum(b["kind"] in ("compile", "load") for b in builds)
    if args["read"] == "build_s":
        return traceread.total(traceread.union(
            [(b["t"] - b["s"], b["t"]) for b in builds]))
    raise ValueError(f"timeline reader: read={args['read']!r}")


def read(art: dict, args: dict):
    timeline = task_timeline(art)
    if timeline is None or not art.get("task"):
        return None
    return reduce(timeline, art["task"]["t_window"], args)
