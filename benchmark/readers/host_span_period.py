"""Median start-to-start distance (ms) of the program's own host spans
named ``args["span"]`` in the traced run: for ``train_step`` (the
``StepTraceAnnotation`` ``train_loop`` puts around every step call) that
is the loop's period as the program itself sees it. None where the trace
holds fewer than three such spans."""

import statistics


def read(art: dict, args: dict):
    trace = art.get("trace")
    if not trace:
        return None
    starts = sorted(
        start for plane in trace["planes"]
        if plane["name"].startswith("/host:")
        for line in plane["lines"]
        for name, start, _ in line["events"] if name == args["span"])
    if len(starts) < 3:
        return None
    return statistics.median(
        b - a for a, b in zip(starts, starts[1:])) / 1e6
