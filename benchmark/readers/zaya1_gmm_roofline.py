"""The grouped expert kernels' share of their roofline in the traced steps
of the ``zaya1-8b`` configuration: the least time the chip could take for
the calls found in the trace over the device time those calls took.

The rows a call multiplied are **counted, not inferred**: the task adds up
the rows the traced steps' held experts were sent (the layers' sown
``moe_rows_held``; counter ``moe:rows_held_traced``), and the program says
how many chunks a layer's call takes (counter ``moe:chunks``). Every call
of a (step, layer, chunk) multiplies that chunk's rows, so the calls found
are charged the mean chunk's rows each — ``rows_held_traced / (steps x
layers x chunks)`` — whatever buffer they were handed, and each held matrix
once a layer's pass (``roofline_keyevl2.grouped_matmul``: that a chunk
reads them again is the implementation's). ``args``: ``names`` (what the
operation's own name may start with) and ``op`` (its HLO opcode). Says on
an earlier line which peak bounds it. None without a trace, off a TPU, for
another configuration, or where the program has no such call or counter."""

from benchmark import roofline, roofline_zaya1, traceread
from benchmark.readers import timeline


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    steps = len((art.get("task") or {}).get("step_walls_s") or ())
    if not art.get("trace") or art["device"].get("platform") != "tpu" \
            or "router_hidden" not in cfg or not steps:
        return None
    counters = (timeline.task_timeline(art) or {}).get("counters") or {}
    rows, chunks = (counters.get(n) for n in ("moe:rows_held_traced",
                                              "moe:chunks"))
    spent = [dur for plane in traceread.device_planes(art["trace"])
             for name, _, dur in traceread.op_events(plane)
             if name.startswith(tuple(args["names"]))
             and f" {args['op']}(" in name]
    if not rows or not chunks or not spent:
        return None
    flops, nbytes = roofline_zaya1.grouped_matmul(
        rows / (steps * cfg["layers"] * chunks), chunks,
        cfg["experts_held"], cfg["hidden"], cfg["ffn"])
    least, bound = roofline.least_seconds(
        flops, nbytes, roofline.peaks(art["device"]["kind"]))
    least *= len(spent)
    print(f"{args['names']} kernels: bound by {bound}, {len(spent)} calls, "
          f"least {least:.4f}s of {sum(spent) / 1e9:.4f}s", flush=True)
    return 100.0 * least / (sum(spent) / 1e9)
