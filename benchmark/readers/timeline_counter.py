"""A ratio of the task's own counters (tony_tpu.profiler, worker 0's
TASK_TIMELINE record): counter ``args["counter"]`` over the mean it is
compared with, ``args["total"]`` / (``args["per"]`` counters multiplied,
x the configuration's layers where ``args["per_layer"]``). None where the
program recorded no such counter."""

from benchmark.readers import timeline


def read(art: dict, args: dict):
    found = timeline.task_timeline(art)
    counters = (found or {}).get("counters") or {}
    names = [args["counter"], args["total"], *args["per"]]
    if any(not counters.get(n) for n in names):
        return None
    parts = 1.0
    for n in args["per"]:
        parts *= counters[n]
    if args.get("per_layer"):
        parts *= art["model_cfg"]["layers"]
    return counters[args["counter"]] / (counters[args["total"]] / parts)
