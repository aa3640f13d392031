"""A kernel family's share of its roofline in a train step of the
``keye-vl-2.0-30b-a3b`` configuration: the least time the chip could take
for the calls found in the trace (``roofline_keyevl2.py``, from each call's
shapes and the configuration) over the device time those calls took.
``args``: ``names`` (what the operation's own name may start with: a Pallas
call carries its device scope's name), ``op`` (its HLO opcode) and ``work``
(``sel_attn``, ``index_scores``, ``head_probs`` or ``grouped_matmul``, which
also reads the task's counter ``moe:rows_held``). Says on an earlier line
which peak bounds it. None without a trace, off a TPU, for another
configuration, or where the program has no such call (or counter)."""

from benchmark import roofline, roofline_keyevl2 as rk, traceread
from benchmark.readers import timeline
from benchmark.readers.flash_roofline import result_shapes


def sel_attn_work(text: str, cfg: dict) -> tuple:
    """Forward calls return (o, log-sum-exp); the backward is two kernels,
    dq (one result) and dk/dv (two), and the whole backward's work is put
    on the dk/dv call."""
    res = result_shapes(text)
    b, t = res[0][1][0], res[0][1][1]
    dims = (b, t, cfg["heads"], cfg["kv_heads"], cfg["head_dim"],
            cfg["index_topk"])
    if any(dtype == "f32" for dtype, _ in res):
        return rk.sel_attn_fwd(*dims)
    if len(res) == 2:
        return rk.sel_attn_bwd(*dims)
    return 0, 0


def index_scores_work(text: str, cfg: dict) -> tuple:
    """One result: the float32 scores [b, rows, keys] of the last ``rows``
    queries of ``keys`` positions."""
    (_, (b, rows, keys)), = result_shapes(text)
    return rk.index_scores(b, rows, keys, cfg["index_heads"],
                           cfg["index_dim"])


def head_probs_work(text: str, cfg: dict) -> tuple:
    (_, (b, rows, keys)), = result_shapes(text)
    return rk.head_probs(b, rows, keys, cfg["heads"], cfg["kv_heads"],
                         cfg["head_dim"], cfg["index_topk"])


def grouped_matmul_work(text: str, cfg: dict) -> tuple:
    """Every call of a step multiplies the same expected rows: the rows the
    step's routing sent to the held experts (``rows_a_call``), whatever
    buffer the call was handed."""
    return rk.grouped_matmul(cfg["rows_a_call"], cfg["chunks"],
                             cfg["experts_held"], cfg["hidden"], cfg["ffn"])


def rows_a_call(art: dict, cfg: dict, names: list):
    """``cfg`` with the rows one grouped call multiplies: the step's
    ``moe:rows_held`` over the layers and a layer's chunks. A chunk's row
    buffer is the first dimension of the two-dimensional results (the
    weight-gradient calls return [experts, ., .]), ``top_k`` rows a
    token. None without the counter."""
    counters = (timeline.task_timeline(art) or {}).get("counters") or {}
    buffers = [res[0][1][0] for res in map(result_shapes, names)
               if res and len(res[0][1]) == 2]
    if not counters.get("moe:rows_held") or not buffers:
        return None
    tokens = art["job"]["seq"] * art["job"].get("batch", 1)
    chunks = max(1, tokens * cfg["top_k"] // buffers[0])
    return dict(cfg, chunks=chunks, rows_a_call=counters["moe:rows_held"]
                / (cfg["layers"] * chunks))


WORK = {"sel_attn": sel_attn_work, "index_scores": index_scores_work,
        "head_probs": head_probs_work, "grouped_matmul": grouped_matmul_work}


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    if not art.get("trace") or art["device"].get("platform") != "tpu" \
            or "index_topk" not in cfg:
        return None
    peak = roofline.peaks(art["device"]["kind"])
    calls = [(name, dur) for plane in traceread.device_planes(art["trace"])
             for name, _, dur in traceread.op_events(plane)
             if name.startswith(tuple(args["names"]))
             and f" {args['op']}(" in name]
    if args["work"] == "grouped_matmul" and calls:
        cfg = rows_a_call(art, cfg, [name for name, _ in calls])
        if cfg is None:
            return None
    least, spent, bounds = 0.0, 0, set()
    for name, dur in calls:
        flops, nbytes = WORK[args["work"]](name, cfg)
        if flops:
            t, bound = roofline.least_seconds(flops, nbytes, peak)
            least += t
            bounds.add(bound)
        spent += dur
    if not spent:
        return None
    print(f"{args['names']} kernels: bound by {sorted(bounds)}, least "
          f"{least:.4f}s of {spent / 1e9:.4f}s", flush=True)
    return 100.0 * least / (spent / 1e9)
