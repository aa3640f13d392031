"""The four readers of a task's start (ISSUE 36) over a hand-made
TASK_TIMELINE record: a launch stamp, the set-up spans a one-chip training
task records and its builds — and None where the program records none of
it (its parent commit), or the job logged no timeline."""

import json

import pytest

from benchmark import harness, manifest
from benchmark.readers import setup_spans, timeline

NEW = ("import_s.train", "backend_init_s.train", "first_step_s.train",
       "init_unspanned_s.train")
T = 1_790_000_000.0          # the launch stamp; everything else is T + s


def span(name, t0, t1, parent=None, **attrs):
    return {"name": name, "t0": T + t0, "t1": T + t1, "parent": parent,
            "attrs": attrs}


def task_timeline():
    """Launch at 0; the script's ``t_init`` at 20, the first step from 22
    to 28, its window from 40. Under no span or build before ``t_init``:
    10-10.5, 13.5-14 and 19-20."""
    init = "tony:dist_initialize"
    return {"pid": 1, "t_launch": T, "spans": [
        span("tony:python_start", 0, 0.5),
        span("tony:import", 0.6, 4.0, init, module="jax"),
        span("tony:backend_init", 4.0, 9.5, init),
        span(init, 0.5, 10.0),
        span("tony:import", 11.0, 12.5, "tony:import",
             module="jax.experimental.pallas"),
        span("tony:import", 10.5, 13.0, module="tony_tpu.models.transformer"),
        span("tony:import", 12.8, 13.5, module="flax.linen"),
        span("tony:create_train_state", 14.0, 18.0),
        span("tony:remat_rung", 22.1, 25.0, "tony:first_step",
             saved="gate,up", bytes=None, fits=False),
        span("tony:first_step", 22.0, 28.0, programs=1),
        span("tony:import", 41.0, 43.0, module="orbax"),    # in the window
    ], "builds": [
        {"t": T + 15.0, "kind": "compile", "s": 0.5},  # in create_train_state
        {"t": T + 19.0, "kind": "load", "s": 2.0},          # 17-19: 1 s bare
        {"t": T + 27.0, "kind": "compile", "s": 4.0},
    ], "counters": {}}


def art_of(tmp_path, monkeypatch, tl):
    """A run directory whose job logged ``tl`` for worker 0."""
    monkeypatch.setattr(harness, "CACHE", tmp_path)
    jobs = tmp_path / "runs" / "cell" / "jobs" / "app_1" / "history"
    jobs.mkdir(parents=True)
    if tl is not None:
        (jobs / "app_1.jhist").write_text(json.dumps({
            "type": "TASK_TIMELINE", "timestamp": T + 30,
            "payload": {"job_type": "worker", "index": 0,
                        "timeline": tl}}) + "\n")
    return {"cell": "cell", "task": {"t_process": T + 0.3, "t_init": T + 20,
                                     "t_window": T + 40}}


def metric(name, art):
    spec = manifest.metric_file(name)
    reader = {"timeline": timeline, "setup_spans": setup_spans}[
        spec["reader"]]
    return reader.read(art, spec["args"])


def test_nested_imports_are_not_counted_twice(tmp_path, monkeypatch):
    art = art_of(tmp_path, monkeypatch, task_timeline())
    # 0.6-4.0 and 10.5-13.5: the pallas import lies inside the models',
    # flax's overlaps its end, the one in the window is not set-up.
    assert metric("import_s.train", art) == pytest.approx(3.4 + 3.0)


def test_backend_and_first_step_are_their_spans(tmp_path, monkeypatch):
    art = art_of(tmp_path, monkeypatch, task_timeline())
    assert metric("backend_init_s.train", art) == pytest.approx(5.5)
    assert metric("first_step_s.train", art) == pytest.approx(6.0)


def test_unspanned_is_the_start_less_spans_and_builds(tmp_path, monkeypatch):
    art = art_of(tmp_path, monkeypatch, task_timeline())
    got = metric("init_unspanned_s.train", art)
    assert got == pytest.approx(0.5 + 0.5 + 1.0)
    # ... which is task_init_s (taken from outside, the script's own
    # stamps) less the spans and builds within it, but for the 0.3 s
    # before the script's first stamp, which python_start covers.
    assert art["task"]["t_init"] - art["task"]["t_process"] - 17.7 == \
        pytest.approx(got)


def test_a_fully_spanned_start_has_no_residual(tmp_path, monkeypatch):
    tl = task_timeline()
    tl["spans"] = [span("tony:python_start", 0, 2.0),
                   span("tony:dist_initialize", 2.0, 12.0),
                   span("tony:create_train_state", 12.0, 21.0)]
    art = art_of(tmp_path, monkeypatch, tl)
    assert metric("init_unspanned_s.train", art) == 0
    assert metric("import_s.train", art) is None      # no such span: None


@pytest.mark.parametrize("name", NEW)
def test_a_job_that_logged_no_timeline_reads_none(name, tmp_path,
                                                  monkeypatch):
    art = art_of(tmp_path, monkeypatch, None)
    assert metric(name, art) is None
    assert metric(name, {}) is None


@pytest.mark.parametrize("name", NEW)
def test_the_parents_timeline_reads_none(name, tmp_path, monkeypatch):
    """Before ISSUE 36 a one-chip task recorded two spans and no launch."""
    tl = {"pid": 1, "spans": [span("tony:dist_initialize", 0.5, 3.4),
                              span("tony:create_train_state", 14.0, 18.0)],
          "builds": task_timeline()["builds"], "counters": {}}
    assert metric(name, art_of(tmp_path, monkeypatch, tl)) is None


def test_the_manifest_holds_the_new_metrics():
    bench = manifest.load()
    assert manifest.validate(bench) == []
    mine = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [m["name"] for m in mine] == list(NEW)
    cells = [c["name"] for c in bench["workloads"]]
    for m in mine:
        assert (m["layer"], m["moves"], m["source"], m["workloads"]) == (
            "task start", "setup_s", "program_span", cells)
