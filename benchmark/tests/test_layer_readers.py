"""The readers of the program's own spans, counters and device scopes, on
small recorded pieces of a chip run (PR 25's first traced run of
``mistral7b.train``): a TASK_TIMELINE event, the program's spans on the
trace's host plane, a {scope: ns} table — and None where the program
recorded nothing (its parent commit)."""

import json
import shutil

import pytest

from benchmark import harness, manifest, scoperead
from benchmark.readers import host_span_period, scope_ms, timeline

DATA = manifest.HERE / "tests" / "data"
KNOWN = manifest.metric_file("optimizer_ms.train")["args"]["known"]


@pytest.fixture
def run_with_timeline(tmp_path, monkeypatch):
    """A run directory whose job logged the recorded TASK_TIMELINE."""
    monkeypatch.setattr(harness, "CACHE", tmp_path)
    jobs = tmp_path / "runs" / "cell" / "jobs" / "app_1" / "history"
    jobs.mkdir(parents=True)
    shutil.copy(DATA / "task_timeline_event.jhist", jobs / "app_1.jhist")
    event = json.loads((DATA / "task_timeline_event.jhist").read_text())
    builds = event["payload"]["timeline"]["builds"]
    # The window opens after the 15th of the 22 recorded builds.
    return {"cell": "cell", "task": {"t_window": builds[15]["t"] - 1e-6}}


def metric(name, art):
    spec = manifest.metric_file(name)
    reader = {"timeline": timeline, "host_span_period": host_span_period,
              "scope_ms": scope_ms}[spec["reader"]]
    return reader.read(art, spec["args"])


def test_state_init_is_the_create_train_state_span(run_with_timeline):
    assert metric("state_init_s.train", run_with_timeline) == \
        pytest.approx(33.095, abs=1e-3)


def test_builds_before_the_window_are_counted_and_timed(run_with_timeline):
    event = json.loads((DATA / "task_timeline_event.jhist").read_text())
    before = event["payload"]["timeline"]["builds"][:15]
    assert metric("programs_built.train", run_with_timeline) == \
        sum(b["kind"] in ("compile", "load") for b in before) > 0
    got = metric("build_s.train", run_with_timeline)
    # A union: a jit traced inside another's trace is not counted twice.
    assert 0 < got <= sum(b["s"] for b in before)
    assert got >= max(b["s"] for b in before)


def test_union_does_not_count_a_nested_build_twice():
    tl = {"builds": [{"t": 10.0, "kind": "trace", "s": 4.0},
                     {"t": 9.0, "kind": "trace", "s": 1.0},     # nested
                     {"t": 12.0, "kind": "compile", "s": 1.5},
                     {"t": 30.0, "kind": "load", "s": 2.0}]}    # in window
    assert timeline.reduce(tl, 20.0, {"read": "build_s"}) == 5.5
    assert timeline.reduce(tl, 20.0, {"read": "programs_built"}) == 1
    assert timeline.reduce(tl, 20.0, {"read": "span", "span": "x"}) is None


@pytest.mark.parametrize("name", ["state_init_s.train", "build_s.train",
                                  "programs_built.train"])
def test_a_job_that_logged_no_timeline_reads_none(name, tmp_path,
                                                  monkeypatch):
    monkeypatch.setattr(harness, "CACHE", tmp_path)
    (tmp_path / "runs" / "cell" / "jobs").mkdir(parents=True)
    assert metric(name, {"cell": "cell", "task": {"t_window": 1.0}}) is None
    assert metric(name, {}) is None


def test_loop_period_is_the_distance_between_train_step_spans():
    trace = json.loads((DATA / "host_plane_excerpt.json").read_text())
    got = metric("loop_step_ms.train", {"trace": trace})
    assert got == pytest.approx(272.70, abs=0.01)     # step_ms read 272.78
    assert metric("loop_step_ms.train", {}) is None
    # The parent's trace has only the benchmark's own spans.
    for plane in trace["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if e[0] != "train_step"]
    assert metric("loop_step_ms.train", {"trace": trace}) is None


def test_scope_metrics_sum_their_scopes_per_traced_step():
    table = json.loads((DATA / "scope_table.json").read_text())
    art = {"task": {"step_walls_s": [0.27] * 6},
           "scope_self_ns:" + ",".join(KNOWN): table}
    assert metric("optimizer_ms.train", art) == \
        pytest.approx(table["optimizer"] / 6e6) == pytest.approx(23.43, abs=.01)
    assert metric("head_loss_ms.train", art) == \
        pytest.approx((table["lm_head"] + table["loss"]) / 6e6)
    # Every nanosecond of the six traced steps is in the table once.
    assert sum(table.values()) / 6e6 == pytest.approx(270.3, abs=0.1)


@pytest.mark.parametrize("art", [
    {}, {"task": {"step_walls_s": [0.27]}, "trace_events": None},
    {"task": {"step_walls_s": None}},
], ids=["nothing", "no-trace", "no-traced-steps"])
def test_scope_metrics_read_none_without_a_trace(art):
    assert metric("optimizer_ms.train", art) is None
    assert metric("head_loss_ms.train", art) is None


def test_innermost_known_scope_wins():
    path = "jit(step)/transpose(jvp(Transformer))/while/body/closed_call/" \
           "checkpoint/layers/block/attn/attn_bwd_dkv/pallas_call:"
    assert scoperead.scope_of(path, tuple(KNOWN)) == "attn_bwd_dkv"
    assert scoperead.scope_of(path, ("attn",)) == "attn"
    assert scoperead.scope_of("jit(step)/jvp(loss)/reduce_max:",
                              tuple(KNOWN)) == "loss"
    assert scoperead.scope_of("jit(step)/optimizer/add:",
                              tuple(KNOWN)) == "optimizer"
    assert scoperead.scope_of("jit(step)/final_norm/mul:", tuple(KNOWN)) == ""
    assert scoperead.scope_of("", tuple(KNOWN)) == ""


def test_nested_operations_leave_the_operation_that_holds_them():
    events = [["jit(step)/while:", 0, 100],                  # holds the body
              ["jit(step)/while/body/layers/block/mlp/dot:", 10, 40],
              ["jit(step)/while/body/lm_head/dot:", 50, 30],
              ["jit(step)/optimizer/add:", 100, 25]]
    assert scoperead.self_ns_by_scope(events, ("mlp", "lm_head",
                                               "optimizer")) == \
        {"": 30, "mlp": 40, "lm_head": 30, "optimizer": 25}


def test_a_raw_xplane_is_read_by_a_child_process(tmp_path):
    """by_scope() on a small .xplane.pb made here: op_name is the stat
    ``tf_op`` of the event's metadata, on the device's 'XLA Ops' line."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    space = xplane_pb2.XSpace()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata[1].name = "tf_op"
    ops = {1: "jit(step)/optimizer/add:", 2: "jit(step)/jvp(loss)/exp:",
           3: ""}
    for key, op_name in ops.items():
        md = plane.event_metadata[key]
        md.name = f"%fusion.{key}"
        if op_name:
            md.stats.add(metadata_id=1, str_value=op_name)
    line = plane.lines.add(name="XLA Ops")
    for key, offset_ps, duration_ps in ((1, 0, 5_000_000), (2, 6_000_000,
                                        3_000_000), (3, 9_000_000, 1_000_000),
                                        (1, 10_000_000, 5_000_000)):
        line.events.add(metadata_id=key, offset_ps=offset_ps,
                        duration_ps=duration_ps)
    space.planes.add(name="/host:CPU")
    out = tmp_path / "trace" / "plugins" / "profile" / "t0"
    out.mkdir(parents=True)
    (out / "host.xplane.pb").write_bytes(space.SerializeToString())
    art = {"trace_events": str(tmp_path / "trace" / "events.json"),
           "task": {"step_walls_s": [0.1, 0.1]}}
    assert scoperead.by_scope(art, ["optimizer", "loss"]) == \
        {"optimizer": 10000.0, "loss": 3000.0, "": 1000.0}
    assert scope_ms.read(art, {"scopes": ["optimizer"],
                               "known": ["optimizer", "loss"]}) == 0.005
    # A program without scopes: operations, but no op_name names one.
    assert scope_ms.read(art, {"scopes": ["mlp"],
                               "known": ["mlp"]}) is None
