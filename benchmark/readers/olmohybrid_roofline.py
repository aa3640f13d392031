"""The delta-rule chunk kernels' share of their roofline in the traced
steps of the ``olmo-hybrid-7b`` configuration: the least time the chip could
take for the calls found in the trace (roofline_olmohybrid.gdn_recurrence,
from the job's shapes) over the device time those calls took. ``args``:
``names`` (what the operation's own name may start with: a Pallas kernel's
operations carry the kernel's name), ``op`` (its HLO opcode) and
``backward`` (the names that are backward calls).

Every call is one layer's recurrence over the job's tokens at the held
heads and the published 96 x 192 state under a scalar decay — the
recurrence's own work, whatever the kernel's chunk and whatever lanes it
pads a head to. Says on an earlier line which peak bounds it. None without
a trace, off a TPU, for another configuration, or where the program has no
such call (a parent without these kernels)."""

from benchmark import roofline, roofline_olmohybrid as ro, traceread


def read(art: dict, args: dict):
    cfg = art.get("model_cfg") or {}
    if not art.get("trace") or art["device"].get("platform") != "tpu" \
            or "gdn_heads" not in cfg:
        return None
    calls = [(name, dur) for plane in traceread.device_planes(art["trace"])
             for name, _, dur in traceread.op_events(plane)
             if name.startswith(tuple(args["names"]))
             and f" {args['op']}(" in name]
    if not calls:
        return None
    peak = roofline.peaks(art["device"]["kind"])
    tokens = art["job"]["batch"] * art["job"]["seq"]
    back = tuple(args["backward"])
    least, bounds = 0.0, set()
    for name, _ in calls:
        t, b = roofline.least_seconds(*ro.gdn_recurrence(
            tokens, cfg["gdn_heads"], cfg["dk"], cfg["dv"],
            name.startswith(back)), peak)
        least += t
        bounds.add(b)
    spent = sum(dur for _, dur in calls) / 1e9
    print(f"{args['names']} kernels: bound by {sorted(bounds)}, "
          f"{len(calls)} calls, least {least:.4f}s of {spent:.4f}s",
          flush=True)
    return 100.0 * least / spent
