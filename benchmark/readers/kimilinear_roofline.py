"""A kernel family's share of its roofline in the traced steps of the
``kimi-linear-48b-a3b`` configuration: the least time the chip could take
for the calls found in the trace (roofline_kimilinear.py, from the job's
shapes) over the device time those calls took. ``args``: ``kind`` (``kda``,
``mla`` or ``gmm``), ``names`` (what the operation's own name may start
with: a Pallas kernel's operations carry the kernel's name), ``op`` (its
HLO opcode) and, for ``kda`` and ``mla``, ``backward`` (the names that are
backward calls; a family's whole backward is charged once however many
kernels share it: to the first of them).

``kda``: every call is one layer's recurrence over the job's tokens — the
recurrence's own work, whatever the kernel's chunk. ``mla``: the causal
half at the configuration's 192 / 128. ``gmm``: the rows are **counted**
(the task's ``moe:rows_held_traced`` over steps x expert layers x
``moe:chunks``), each held matrix once a layer's pass, as
``readers/zaya1_gmm_roofline.py``. Says on an earlier line which peak
bounds it. None without a trace, off a TPU, for another configuration, or
where the program has no such call (a parent without these kernels)."""

from benchmark import roofline, roofline_kimilinear as rk, traceread
from benchmark.readers import timeline


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    steps = len((art.get("task") or {}).get("step_walls_s") or ())
    if not art.get("trace") or art["device"].get("platform") != "tpu" \
            or "kda_heads" not in cfg or not steps:
        return None
    calls = [(name, dur) for plane in traceread.device_planes(art["trace"])
             for name, _, dur in traceread.op_events(plane)
             if name.startswith(tuple(args["names"]))
             and f" {args['op']}(" in name]
    if not calls:
        return None
    peak = roofline.peaks(art["device"]["kind"])
    tokens = art["job"]["batch"] * art["job"]["seq"]
    if args["kind"] == "gmm":
        counters = (timeline.task_timeline(art) or {}).get("counters") or {}
        rows, chunks, layers = (counters.get(n) for n in (
            "moe:rows_held_traced", "moe:chunks", "model:layers.experts"))
        if not rows or not chunks or not layers:
            return None
        work = rk.grouped_matmul(rows / (steps * layers * chunks), chunks,
                                 cfg["experts_held"], cfg["hidden"],
                                 cfg["ffn"])
        least, bound = roofline.least_seconds(*work, peak)
        least *= len(calls)
    else:
        if args["kind"] == "kda":
            work = lambda backward: rk.kda_recurrence(
                tokens, cfg["kda_heads"], cfg["kda_head_dim"],
                cfg["kda_head_dim"], backward)
        else:
            dims = (art["job"]["batch"], cfg["mla_heads"], art["job"]["seq"],
                    cfg["nope"] + cfg["rope"], cfg["v_dim"], cfg["rope"])
            work = lambda backward: (rk.mla_bwd if backward
                                     else rk.mla_fwd)(*dims)
        back = tuple(args["backward"])
        least, bounds = 0.0, set()
        for name, _ in calls:
            if name.startswith(back) and not name.startswith(back[0]):
                continue             # charged to the backward's first kernel
            t, b = roofline.least_seconds(*work(name.startswith(back)), peak)
            least += t
            bounds.add(b)
        bound = sorted(bounds)
    spent = sum(dur for _, dur in calls) / 1e9
    print(f"{args['names']} kernels: bound by {bound}, {len(calls)} calls, "
          f"least {least:.4f}s of {spent:.4f}s", flush=True)
    return 100.0 * least / spent
