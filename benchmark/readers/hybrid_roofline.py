"""A kernel family's share of its roofline in a train step of the
layer-kind decoder: the least time the chip could take for the calls found
in the trace (``roofline_ssm.py``, from each call's shapes and the
configuration) over the device time those calls took. ``args``: ``name``
(what the operation's own name starts with: a Pallas call carries its
device scope's name), ``op`` (its HLO opcode) and ``work`` (``ssm`` or
``attn``). Says on an earlier line which peak bounds it. None without a
trace, off a TPU, or where the program has no such call."""

from benchmark import roofline, roofline_ssm, traceread
from benchmark.readers.flash_roofline import result_shapes


def ssm_work(text: str, cfg: dict) -> tuple:
    """Forward: (y, boundary states); backward: (d xc, d dt, dB, dC, dA,
    dD). y is [b, t, blocks, 8, 128]."""
    res = result_shapes(text)
    b, t = res[0][1][0], res[0][1][1]
    dims = (b, t, cfg["ssm_expand"] * cfg["hidden"], cfg["ssm_state"])
    if len(res) == 2:
        return roofline_ssm.ssm_scan_fwd(*dims)
    return roofline_ssm.ssm_scan_bwd(*dims)


def attn_work(text: str, cfg: dict) -> tuple:
    """Forward calls return (o, log-sum-exp); the backward is two kernels,
    dq (one result) and dk/dv (two), and the whole backward's work is put
    on the dk/dv call. ``_win`` in the call's name marks a windowed one."""
    res = result_shapes(text)
    b, t = res[0][1][0], res[0][1][1]
    window = cfg["window"] if "_win" in text.split(" = ", 1)[0] else None
    dims = (b, t, cfg["heads"], cfg["kv_heads"], cfg["head_dim"], window)
    if any(dtype == "f32" for dtype, _ in res):
        return roofline_ssm.diff_attn_fwd(*dims)
    if len(res) == 2:
        return roofline_ssm.diff_attn_bwd(*dims)
    return 0, 0


WORK = {"ssm": ssm_work, "attn": attn_work}


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    if not art.get("trace") or art["device"].get("platform") != "tpu" \
            or "kinds" not in cfg:
        return None
    peak = roofline.peaks(art["device"]["kind"])
    least, spent, bounds = 0.0, 0, set()
    for plane in traceread.device_planes(art["trace"]):
        for name, _, dur in traceread.op_events(plane):
            if not (name.startswith(args["name"])
                    and f" {args['op']}(" in name):
                continue
            flops, nbytes = WORK[args["work"]](name, cfg)
            if flops:
                t, bound = roofline.least_seconds(flops, nbytes, peak)
                least += t
                bounds.add(bound)
            spent += dur
    if not spent:
        return None
    print(f"{args['name']} kernels: bound by {sorted(bounds)}, least "
          f"{least:.4f}s of {spent / 1e9:.4f}s", flush=True)
    return 100.0 * least / (spent / 1e9)
