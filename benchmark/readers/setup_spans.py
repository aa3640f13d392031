"""Where a task's start went by its own set-up spans (tony_tpu.profiler):
worker 0's TASK_TIMELINE record, from the launch stamp the executor gave
the process (``t_launch``) on. ``args``: ``read`` is

``union``      seconds before the window under some span named
               ``args["span"]``: the union of their intervals, so an
               import inside an import is not counted twice;
``unspanned``  seconds of [``t_launch``, the task script's own ``t_init``]
               under no set-up span and no build record: what of the
               start the program cannot name (the script's own imports
               and calls among it).

A program that records no such span, or stamps no launch, reads None."""

from benchmark import traceread
from benchmark.readers import timeline


def clipped(intervals, lo: float, hi: float) -> list:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def reduce(tl: dict, task: dict, args: dict):
    spans = tl.get("spans", [])
    if args["read"] == "union":
        held = clipped(((s["t0"], s["t1"]) for s in spans
                        if s["name"] == args["span"]),
                       float("-inf"), task["t_window"])
        return traceread.total(traceread.union(held)) if held else None
    if args["read"] == "unspanned":
        t_launch = tl.get("t_launch")
        if t_launch is None or not task.get("t_init"):
            return None
        held = [(s["t0"], s["t1"]) for s in spans] + [
            (b["t"] - b["s"], b["t"]) for b in tl.get("builds", [])]
        return (task["t_init"] - t_launch) - traceread.total(traceread.union(
            clipped(held, t_launch, task["t_init"])))
    raise ValueError(f"setup_spans reader: read={args['read']!r}")


def read(art: dict, args: dict):
    tl = timeline.task_timeline(art)
    if tl is None or not art.get("task"):
        return None
    return reduce(tl, art["task"], args)
