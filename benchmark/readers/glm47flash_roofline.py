"""A kernel family's share of its roofline in the traced steps of the
``glm-4.7-flash`` configuration: the least time the chip could take for
the calls found in the trace (roofline_glm47flash.py, from the job's
shapes) over the device time those calls took. ``args``: ``kind`` (``mla``
or ``gmm``), ``names`` (what the operation's own name may start with: a
Pallas kernel's operations carry the kernel's name), ``op`` (its HLO
opcode) and, for ``mla``, ``backward`` (the names that are backward calls;
the whole backward is charged once however many kernels share it: to the
first of them).

``mla``: every forward call is one block's attention at the **published**
shape — q.k over 192 + 64, p.v over 256, the causal half, the shared key
part's bytes once — whatever the kernels were handed (here each head's
joined 256-wide key); a backward twice that. ``gmm``: the rows are
**counted** (the task's ``moe:rows_held_traced`` over steps x expert
layers, the multi-token-prediction module's among them, x ``moe:chunks``),
each held matrix once a layer's pass, as ``readers/kimilinear_roofline.py``.
Says on an earlier line which peak bounds it. None without a trace, off a
TPU, for another configuration, or where the program has no such call."""

from benchmark import roofline, roofline_glm47flash as rg, traceread
from benchmark.readers import timeline


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    steps = len((art.get("task") or {}).get("step_walls_s") or ())
    if not art.get("trace") or art["device"].get("platform") != "tpu" \
            or "q_rank" not in cfg or not steps:
        return None
    calls = [(name, dur) for plane in traceread.device_planes(art["trace"])
             for name, _, dur in traceread.op_events(plane)
             if name.startswith(tuple(args["names"]))
             and f" {args['op']}(" in name]
    if not calls:
        return None
    peak = roofline.peaks(art["device"]["kind"])
    if args["kind"] == "gmm":
        counters = (timeline.task_timeline(art) or {}).get("counters") or {}
        rows, chunks, layers = (counters.get(n) for n in (
            "moe:rows_held_traced", "moe:chunks", "model:layers.experts"))
        if not rows or not chunks or not layers:
            return None
        work = rg.grouped_matmul(rows / (steps * layers * chunks), chunks,
                                 cfg["experts_held"], cfg["hidden"],
                                 cfg["ffn"])
        least, bound = roofline.least_seconds(*work, peak)
        least *= len(calls)
    else:
        dims = (art["job"]["batch"], cfg["heads"], art["job"]["seq"],
                cfg["nope"] + cfg["rope"], cfg["v_dim"], cfg["rope"])
        back = tuple(args["backward"])
        least, bounds = 0.0, set()
        for name, _ in calls:
            if name.startswith(back) and not name.startswith(back[0]):
                continue             # charged to the backward's first kernel
            t, b = roofline.least_seconds(*(
                rg.mla_bwd if name.startswith(back) else rg.mla_fwd)(*dims),
                peak)
            least += t
            bounds.add(b)
        bound = sorted(bounds)
    spent = sum(dur for _, dur in calls) / 1e9
    print(f"{args['names']} kernels: bound by {bound}, {len(calls)} calls, "
          f"least {least:.4f}s of {spent:.4f}s", flush=True)
    return 100.0 * least / spent
