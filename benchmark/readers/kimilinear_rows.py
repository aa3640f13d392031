"""What a window's training did to the routing of the
``kimi-linear-48b-a3b`` configuration: the rows the held experts were sent
in the window's last step (the task's counter ``args["last"]``) over check
step 1's (``args["first"]``). ``readers/timeline_counter.py`` leaves a
counter of 0 out, and here the last step's count **is** 0 on some seeds
(PERF.md section 6, PR 38: the held range starves within twenty steps), so
this reads 0.0 then. None where the program recorded neither counter (a
parent without the layer) or held no row at step 1."""

from benchmark.readers import timeline


def read(art: dict, args: dict):
    counters = (timeline.task_timeline(art) or {}).get("counters") or {}
    first, last = counters.get(args["first"]), counters.get(args["last"])
    if not first or last is None:
        return None
    return last / first
