"""The trace reducer: on small made-up timelines, and on a trace recorded
on the v5e (two fenced steps of mistral7b.train, PR 24; data/
train_trace_2steps.json, operation names as the extractor shortens them)."""

import pytest

from benchmark import manifest, modelcfg, traceread
from benchmark.readers import device_idle, flash_roofline

RECORDED = traceread.load(str(
    traceread.os.path.join(traceread.os.path.dirname(__file__), "data",
                           "train_trace_2steps.json")))


def trace_of(*chips):
    return {"planes": [{"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Ops", "events": ev}]} for i, ev in enumerate(chips)]}


def test_union_and_busy():
    assert traceread.union([(0, 4), (2, 6), (8, 9)]) == [[0, 6], [8, 9]]
    assert traceread.busy_ns([["a", 0, 4], ["b", 2, 4], ["c", 8, 1]]) == 7


def test_busy_share_averages_the_chips():
    t = trace_of([["a", 0, 10_000]], [["a", 0, 5_000], ["b", 9_000, 1_000]])
    busy_s, window_s = traceread.busy_share(t)
    assert window_s == pytest.approx(10e-6)
    assert busy_s == pytest.approx(8e-6)


def test_self_time_takes_nested_operations_out_of_a_while():
    ev = [["%while.1", 0, 100], ["%fusion.1", 10, 30], ["%fusion.2", 50, 20],
          ["%copy.1", 100, 5]]
    assert dict(traceread.self_times(ev)) == {
        "%while.1": 50, "%fusion.1": 30, "%fusion.2": 20, "%copy.1": 5}


def test_idle_gap_names_what_the_host_was_doing():
    t = trace_of([["a", 0, 100], ["b", 1100, 100]])
    t["planes"].append({"name": "/host:CPU", "lines": [{"name": "python",
        "events": [["whole run", 0, 100_000], ["build_inputs", 150, 900]]}]})
    assert traceread.idle_gaps(t, 1) == [["build_inputs", 1000 / 1e9]]


def test_recorded_trace_busy_share_and_idle():
    busy_s, window_s = traceread.busy_share(RECORDED)
    assert window_s == pytest.approx(0.5429, abs=1e-3)
    assert busy_s == pytest.approx(0.5407, abs=1e-3)
    idle = device_idle.read({"trace": RECORDED}, {})
    assert 0.2 < idle < 0.7


def test_recorded_trace_flash_kernels():
    # 2 steps x 2 layers x (forward, its rematerialisation, dq, dk/dv)
    kernels = [e for e in traceread.op_events(
        traceread.device_planes(RECORDED)[0]) if e[0].startswith("%attn.")]
    art = {"trace": RECORDED,
           "device": {"platform": "tpu", "kind": "TPU v5 lite"},
           "model_cfg": modelcfg.load("mistral-7b-v0.3")}
    args = manifest.metric_file("flash_roofline.train")["args"]
    share = flash_roofline.read(art, args)
    # least time: 2*2*(2 forwards of 137.4 GFLOP + one backward of 343.6)
    # over 197 TFLOP/s = 12.56 ms, of 55.2 ms spent
    assert share == pytest.approx(22.7, abs=0.3)
    assert len(kernels) == 16
    assert sum(e[2] for e in kernels) / 1e9 == pytest.approx(0.0552, abs=2e-4)


def test_recorded_trace_breakdown():
    ops = traceread.top_ops(RECORDED, 10)
    assert len(ops) == 10 and all(not o[0].startswith("%while") or o[1] < 0.01
                                  for o in ops)
    assert ops[0][1] >= ops[-1][1] > 0
