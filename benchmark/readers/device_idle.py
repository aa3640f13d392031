"""1 - (union of device-operation intervals / traced window), averaged
over the chips."""

from benchmark import traceread


def read(art: dict, args: dict):
    if not art.get("trace"):
        return None
    busy_s, window_s = traceread.busy_share(art["trace"])
    if not window_s:
        return None
    return 100.0 * (1.0 - busy_s / window_s)
