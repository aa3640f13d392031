"""Device self time (ms per traced step, averaged over the chips) of the
operations under the program's scopes ``args["scopes"]``, each operation
counted under the innermost of ``args["known"]`` on its path
(scoperead.py). None without a trace, and for a program whose operations
carry none of the scopes."""

from benchmark import scoperead


def read(art: dict, args: dict):
    steps = len((art.get("task") or {}).get("step_walls_s") or ())
    table = scoperead.by_scope(art, args["known"])
    if not steps or not any(s in table for s in args["scopes"]):
        return None
    return sum(table.get(s, 0) for s in args["scopes"]) / steps / 1e6
