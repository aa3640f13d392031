"""From a profiler trace to numbers.

``extract`` (the only part that imports jax; run by the process that made
the trace) turns the ``.xplane.pb`` into a compact JSON of events. The
reductions below are plain Python over that JSON, so the parent can run
them, the tests can check them on a recorded trace, and no PR that claims
a gain computes them differently.

Compact form: ``{"planes": [{"name", "lines": [{"name", "events":
[[name, start_ns, dur_ns], ...]}]}]}`` — device planes keep every line,
host planes only events of a microsecond or more; a device operation's
name is its HLO text with the layout annotations removed.
"""

from __future__ import annotations

import glob
import json
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"            # one event per executed HLO op / kernel
HOST_MIN_NS = 1000
NAME_WIDTH = 400                # of an operation's HLO text, layouts removed


def extract(logdir: str, out_path: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [[short(e.name, NAME_WIDTH), int(e.start_ns),
                       int(e.duration_ns)]
                      for e in line.events
                      if device or e.duration_ns >= HOST_MIN_NS]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    out = {"planes": planes}
    with open(out_path, "w") as f:
        json.dump(out, f)
    return out


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def device_planes(trace: dict) -> list:
    return [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]


def op_events(plane: dict) -> list:
    """The leaf device operations of one chip, by start time."""
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return sorted(line["events"], key=lambda e: e[1])
    return []


def union(intervals: list) -> list:
    """Merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def total(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def busy_ns(events: list) -> int:
    return total(union([(s, s + d) for _, s, d in events]))


def window_ns(trace: dict) -> int:
    """First start to last end of any device operation."""
    spans = [(e[1], e[1] + e[2]) for p in device_planes(trace)
             for e in op_events(p)]
    return max(e for _, e in spans) - min(s for s, _ in spans) if spans else 0


def busy_share(trace: dict) -> tuple:
    """(busy seconds averaged over the chips, window seconds)."""
    planes = device_planes(trace)
    win = window_ns(trace)
    if not planes or not win:
        return 0.0, 0.0
    busy = sum(busy_ns(op_events(p)) for p in planes) / len(planes)
    return busy / 1e9, win / 1e9


def self_times(events: list) -> list:
    """[(name, ns)] with the time of nested operations (the body of a
    ``while`` appears inside the ``while`` on the same line) taken out of
    the operation that holds them."""
    out, stack = [], []            # stack of [name, end, self_ns]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= s:
            out.append(tuple(stack.pop()[::2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    out += [tuple(x[::2]) for x in stack]
    return out


def short(name: str, width: int = 96) -> str:
    """An HLO instruction's text without its layout annotations."""
    return re.sub(r"\{[^{}]*\}", "", name)[:width]


def top_ops(trace: dict, n: int = 10) -> list:
    """[[operation, seconds], ...] of the device operations that took most
    time themselves (first chip), same-named operations summed."""
    planes = device_planes(trace)
    if not planes:
        return []
    by_name = {}
    for name, ns in self_times(op_events(planes[0])):
        by_name[name] = by_name.get(name, 0) + ns
    return [[short(k), v / 1e9] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: dict, n: int = 10) -> list:
    """[[what the host was doing, seconds], ...] for the longest gaps
    between device operations on the first chip: the host event (any
    thread) that overlaps the gap most among those not much longer than
    the gap itself (a frame that spans the whole run explains nothing)."""
    planes = device_planes(trace)
    if not planes:
        return []
    busy = union([(s, s + d) for _, s, d in op_events(planes[0])])
    gaps = sorted(((busy[i + 1][0] - busy[i][1], busy[i][1], busy[i + 1][0])
                   for i in range(len(busy) - 1)), reverse=True)[:n]
    host = [e for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for e in line["events"]]
    out = []
    for length, s, e in gaps:
        best, best_key = "host: nothing recorded", (0, 0)
        for name, hs, hd in host:
            overlap = min(e, hs + hd) - max(s, hs)
            if overlap > 0 and hd <= 4 * length \
                    and (overlap, -hd) > best_key:
                best, best_key = name, (overlap, -hd)
        out.append([best, length / 1e9])
    return out
