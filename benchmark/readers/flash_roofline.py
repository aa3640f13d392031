"""The flash attention kernels' share of their roofline in a train step:
the least time the chip could take for the calls found in the trace
(roofline.py, from each call's shapes) over the device time those calls
took. ``args``: ``name`` (what the operation's own name starts with: a
Pallas kernel's operations carry the kernel's name) and ``op`` (its HLO
opcode). Says on an earlier line which peak bounds it."""

import re

from benchmark import roofline, traceread

SHAPE = re.compile(r"(bf16|f32|s32)\[([\d,]*)\]")


def shapes(text: str) -> list:
    return [(t, [int(x) for x in dims.split(",") if x])
            for t, dims in SHAPE.findall(text)]


def result_shapes(text: str) -> list:
    """[(dtype, dims)] of an HLO custom call's result(s)."""
    return shapes(text.split(" = ", 1)[1].split(" custom-call(", 1)[0])


def work(text: str, art: dict) -> tuple:
    """(flops, bytes) of one call. Forward calls return
    (o, log-sum-exp); the backward is two kernels, dq (one result) and
    dk/dv (two), and the whole backward's work is put on the dk/dv call."""
    cfg = art["model_cfg"]
    res = result_shapes(text)
    b, s = res[0][1][0], res[0][1][1]
    dims = (b, cfg["heads"], cfg["kv_heads"], s, cfg["head_dim"])
    if any(t == "f32" for t, _ in res):
        return roofline.flash_fwd(*dims)
    if len(res) == 2:
        return roofline.flash_bwd(*dims)
    return 0, 0


def read(art: dict, args: dict):
    if not art.get("trace") or art["device"].get("platform") != "tpu":
        return None
    peak = roofline.peaks(art["device"]["kind"])
    least, spent, bounds = 0.0, 0, set()
    for plane in traceread.device_planes(art["trace"]):
        for name, _, dur in traceread.op_events(plane):
            if not (name.startswith(args["name"])
                    and f" {args['op']}(" in name):
                continue
            flops, nbytes = work(name, art)
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
