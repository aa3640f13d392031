"""Device self time by the program's own scopes, from a raw ``.xplane.pb``.

    python3 benchmark/scoperead.py <file.xplane.pb> <scope> [<scope> ...]

prints one JSON object ``{scope: self_ns, ..., "": unattributed_ns}``,
averaged over the chips. The program names its device work with
``jax.named_scope`` (README, Observability); XLA keeps the scope path as
each operation's ``op_name``, and the profiler writes it as the stat
``tf_op`` on the operation's event *metadata*. ``jax.profiler.ProfileData``
shows an event's own stats only, and ``events.json`` keeps the HLO text
only, so this reads the file with tensorflow's ``xplane_pb2`` — in a
process of its own (``by_scope``), because the parent of a run imports
neither jax nor tensorflow.

An operation belongs to the innermost of the given scopes on its path
(``.../lm_head/dot_general`` and ``transpose(jvp(loss))/...`` alike: the
forward, the recomputation and the backward of a scope all carry it); a
fusion XLA built across two scopes carries its root's path. Nested
operations (a ``while`` holds its body on the same line) are taken out of
the operation that holds them, as ``traceread.self_times`` does.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from benchmark import traceread  # noqa: E402


def scope_of(op_name: str, scopes: tuple) -> str:
    """The innermost of ``scopes`` among the components of an op_name
    path (``jit(step)/transpose(jvp(Transformer))/layers/block/mlp/...``),
    or ``""``."""
    for part in reversed(re.split(r"[/()]+", op_name)):
        if part in scopes:
            return part
    return ""


def self_ns_by_scope(events: list, scopes: tuple) -> dict:
    """``events``: [[op_name, start, duration], ...] of one chip's
    operations line -> {scope: self time}."""
    out: dict = {}
    labelled = [[scope_of(name, scopes), s, d] for name, s, d in events]
    for scope, ns in traceread.self_times(labelled):
        out[scope] = out.get(scope, 0) + ns
    return out


def read_xplane(path: str, scopes: tuple) -> dict:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    space = xplane_pb2.XSpace()
    space.ParseFromString(Path(path).read_bytes())
    tables = []
    for plane in space.planes:
        if not plane.name.startswith(traceread.DEVICE_PREFIX):
            continue
        stat_names = {k: v.name for k, v in plane.stat_metadata.items()}
        op_names = {}
        for key, md in plane.event_metadata.items():
            for stat in md.stats:
                if stat_names.get(stat.metadata_id) == "tf_op":
                    op_names[key] = stat.str_value or stat_names.get(
                        stat.ref_value, "")
        for line in plane.lines:
            if line.name != traceread.OPS_LINE:
                continue
            events = [[op_names.get(e.metadata_id, ""), e.offset_ps / 1e3,
                       e.duration_ps / 1e3] for e in line.events]
            if any(name for name, _, _ in events):
                tables.append(self_ns_by_scope(events, scopes))
    if not tables:
        return {}
    return {k: sum(t.get(k, 0) for t in tables) / len(tables)
            for k in set().union(*tables)}


def by_scope(art: dict, scopes: list) -> dict:
    """``{scope: self_ns}`` for the run's trace, read once a run by a child
    process and kept on ``art``; {} where there is no trace, no
    ``xplane_pb2`` or no operation with a scope path (a program without
    scopes)."""
    key = "scope_self_ns:" + ",".join(scopes)
    if key not in art:
        art[key] = {}
        files = sorted(Path(art["trace_events"]).parent.glob(
            "plugins/profile/*/*.xplane.pb")) \
            if art.get("trace_events") else []
        if files:
            done = subprocess.run(
                [sys.executable, str(Path(__file__).resolve()),
                 str(files[-1]), *scopes],
                env=dict(os.environ, JAX_PLATFORMS="cpu",
                         TF_CPP_MIN_LOG_LEVEL="3"),
                capture_output=True, text=True, timeout=300)
            if done.returncode == 0 and done.stdout.strip():
                art[key] = json.loads(done.stdout.strip().splitlines()[-1])
            else:
                print(f"scoperead: no table (rc={done.returncode}): "
                      f"{done.stderr[-400:]}", flush=True)
    return art[key]


if __name__ == "__main__":
    print(json.dumps(read_xplane(sys.argv[1], tuple(sys.argv[2:]))))
