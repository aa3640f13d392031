"""The program's own timeline (tony_tpu.profiler): spans, counters, build
records, and the one path they take out of the process — timeline.json ->
executor -> AM -> TASK_TIMELINE -> ``tony history`` — plus the hooks that
use them in ``train_loop`` and the serve replica."""

from __future__ import annotations

import glob
import json
import logging
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from test_remat import (BATCH, FULL, LIMIT, MISTRAL, NO_WO, STATE,  # noqa: F401
                        FakeCompiler, FakeDevice, memo_dir)
from tony_tpu import constants, events as ev, profiler
from tony_tpu.minipod import MiniPod

ROOT = Path(__file__).resolve().parent.parent
WORKLOADS = Path(__file__).parent / "workloads"


@pytest.fixture(autouse=True)
def fresh_timeline():
    profiler.reset_timeline()
    yield
    profiler.reset_timeline()


def host_events(logdir) -> list:
    """Names of every host-plane event of the newest trace under logdir."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb"))[-1]
    return [e.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


# -- the facility ----------------------------------------------------------

def test_setup_spans_nest_and_name_their_parent():
    with profiler.span("tony:restore") as outer:
        with profiler.span("tony:warm", programs=2):
            pass
        outer.attrs.update(step=7, bytes=1024)
    warm, restore = profiler.timeline()["spans"]
    assert (warm["name"], warm["parent"]) == ("tony:warm", "tony:restore")
    assert (restore["name"], restore["parent"]) == ("tony:restore", None)
    assert restore["attrs"] == {"step": 7, "bytes": 1024}
    assert warm["attrs"] == {"programs": 2}
    assert restore["t0"] <= warm["t0"] <= warm["t1"] <= restore["t1"]
    # Epoch seconds: the clock the event log and the benchmark use.
    assert abs(restore["t1"] - time.time()) < 60


def test_hot_path_spans_never_reach_the_timeline():
    for name in ("train:next_batch", "train:on_step", "train:save",
                 "serve:launch", "serve:emit", "anything_else"):
        assert name not in profiler.SETUP_SPANS
        with profiler.span(name, step=1):
            pass
    assert profiler.timeline()["spans"] == []


def test_span_as_decorator_records_every_call():
    @profiler.span("tony:create_train_state")
    def build(x):
        return x + 1

    assert build(1) == 2 and build(2) == 3
    assert [s["name"] for s in profiler.timeline()["spans"]] == \
        ["tony:create_train_state"] * 2


def test_timeline_is_bounded_and_counters_never_stop():
    for _ in range(profiler.MAX_SPANS + 10):
        with profiler.span("tony:warm"):
            pass
    for _ in range(profiler.MAX_BUILDS + 10):
        profiler._on_build("/jax/core/compile/backend_compile_duration", 0.5)
    got = profiler.timeline()
    assert len(got["spans"]) == profiler.MAX_SPANS
    assert len(got["builds"]) == profiler.MAX_BUILDS
    assert got["builds_dropped"] == 10
    assert got["counters"]["programs_compiled"] == profiler.MAX_BUILDS + 10


def test_counters_count_events_and_seconds():
    profiler.count("saves")
    profiler.count("saves", 2)
    profiler.count("save_stall_s", 0.25)
    profiler.count("save_stall_s", 0.5)
    assert profiler.counters() == {"saves": 3, "save_stall_s": 0.75}


def test_importing_spans_only_the_statement_that_imports_first():
    assert "json" in sys.modules
    with profiler.importing("json"):
        import json as _again  # noqa: F401
    assert profiler.timeline()["spans"] == []
    with profiler.span("tony:dist_initialize"):
        with profiler.importing("tony_tpu_no_such_module"):
            pass
    first, _ = profiler.timeline()["spans"]
    assert (first["name"], first["parent"], first["attrs"]) == (
        "tony:import", "tony:dist_initialize",
        {"module": "tony_tpu_no_such_module"})


def _span(name, t0, t1, parent=None):
    return {"name": name, "t0": t0, "t1": t1, "parent": parent, "attrs": {}}


@pytest.mark.parametrize("tl,until,want", [
    # every second of the start under some span: nothing is left
    ({"t_launch": 10.0, "spans": [_span("tony:python_start", 10.0, 11.0),
                                  _span("tony:dist_initialize", 11.0, 15.0),
                                  _span("tony:first_step", 15.0, 18.0)]},
     None, (0.0, 8.0)),
    # a nested span and a build inside a span are not counted twice; a
    # build outside one is covered; the gaps 12-13 and 16.5-17 are not
    ({"t_launch": 10.0,
      "spans": [_span("tony:dist_initialize", 10.0, 12.0),
                _span("tony:import", 10.5, 11.5, "tony:dist_initialize"),
                _span("tony:first_step", 17.0, 20.0)],
      "builds": [{"t": 11.9, "kind": "compile", "s": 0.5},
                 {"t": 16.5, "kind": "load", "s": 3.5}]},
     None, (1.5, 10.0)),
    # read up to a moment of the reader's choosing: spans are clipped
    ({"t_launch": 10.0, "spans": [_span("tony:first_step", 11.0, 20.0)]},
     14.0, (1.0, 4.0)),
    ({"t_launch": 10.0, "spans": []}, 12.5, (2.5, 2.5)),
    # no origin (a timeline written before there was one), nothing to end at
    ({"spans": [_span("tony:restore", 1.0, 2.0)]}, None, None),
    ({"t_launch": 10.0, "spans": []}, None, None),
])
def test_unspanned_is_the_start_less_the_union_of_spans_and_builds(
        tl, until, want):
    got = profiler.unspanned(tl, until)
    assert got == (want if want is None else pytest.approx(want))


@pytest.mark.parametrize("stamp,ago", [
    ("stamped", 2.0), ("absent", None), ("garbage", None), ("future", None)])
def test_the_timeline_starts_at_the_executors_launch_stamp(stamp, ago):
    """``TONY_LAUNCH_TIME`` is the origin and ``tony:python_start`` runs
    from it to the profiler's import; the first process to read the stamp
    takes it out of the environment. No stamp, or one that is no time
    before now: the origin is the import and there is no such span."""
    now = time.time()
    env = {**os.environ}
    env.pop(constants.ENV_LAUNCH_TIME, None)
    if stamp != "absent":
        env[constants.ENV_LAUNCH_TIME] = {
            "stamped": repr(now - 2.0), "garbage": "soon",
            "future": repr(now + 3600)}[stamp]
    code = ("import json, os\n"
            "from tony_tpu import constants, profiler\n"
            "assert constants.ENV_LAUNCH_TIME not in os.environ\n"
            "with profiler.span('tony:restore'):\n"
            "    pass\n"
            "print(json.dumps(profiler.timeline()))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    tl = json.loads(out.stdout)
    names = [s["name"] for s in tl["spans"]]
    if ago is None:
        assert names == ["tony:restore"]
        assert now <= tl["t_launch"] <= tl["spans"][0]["t0"]
        return
    assert names == ["tony:python_start", "tony:restore"]
    start, restore = tl["spans"]
    assert tl["t_launch"] == start["t0"] == pytest.approx(now - ago)
    assert now <= start["t1"] <= restore["t0"]
    assert (start["parent"], start["attrs"]) == (None, {})


def test_a_cache_load_is_one_build_not_also_a_compile():
    # jax reports a persistent-cache hit as a retrieval AND, around it, a
    # backend_compile duration on the same thread.
    profiler._on_build("/jax/compilation_cache/cache_retrieval_time_sec", .02)
    profiler._on_build("/jax/core/compile/backend_compile_duration", 0.03)
    profiler._on_build("/jax/core/compile/backend_compile_duration", 2.0)
    profiler._on_cache_event("/jax/compilation_cache/cache_hits")
    profiler._on_build("/some/other/event", 9.0)
    c = profiler.counters()
    assert (c["programs_loaded"], c["programs_compiled"]) == (1, 1)
    assert (c["load_s"], c["compile_s"], c["cache_hits"]) == (0.02, 2.0, 1)
    assert [b["kind"] for b in profiler.timeline()["builds"]] == \
        ["load", "compile"]
    assert profiler.build_totals() == {"programs_built": 2, "build_s": 2.02}


def test_short_traces_are_counted_but_not_recorded():
    profiler._on_build("/jax/core/compile/jaxpr_trace_duration", 1e-5)
    profiler._on_build("/jax/core/compile/jaxpr_trace_duration", 0.2)
    profiler._on_build("/jax/core/compile/backend_compile_duration", 1e-5)
    assert profiler.counters()["programs_traced"] == 2
    assert [(b["kind"], b["s"]) for b in profiler.timeline()["builds"]] == \
        [("trace", 0.2), ("compile", 1e-5)]


def test_watch_builds_counts_a_jitted_function_once_per_shape():
    import jax
    import jax.numpy as jnp

    profiler.watch_builds()
    profiler.watch_builds()          # installed once, however often asked

    def built():
        c = profiler.counters()
        return (c.get("programs_compiled", 0) + c.get("programs_loaded", 0),
                c.get("programs_lowered", 0))

    f = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 1.0)
    x4, x8 = jnp.ones((4,)), jnp.ones((8,))      # made before counting
    jax.block_until_ready((x4, x8))
    before, n0 = built(), len(profiler.timeline()["builds"])
    f(x4).block_until_ready()
    f(x4).block_until_ready()        # cached: nothing is built
    once = built()
    f(x8).block_until_ready()
    twice = built()
    assert (once[0] - before[0], once[1] - before[1]) == (1, 1)
    assert (twice[0] - before[0], twice[1] - before[1]) == (2, 2)
    kinds = [b["kind"] for b in profiler.timeline()["builds"][n0:]]
    assert sum(k in ("compile", "load") for k in kinds) == 2


def test_the_timeline_half_leaves_jax_out(tmp_path):
    """The executor, the AM and the history plane import this module, and
    a jax-free task may record and publish a timeline too."""
    code = (
        "import sys\n"
        "import tony_tpu.executor, tony_tpu.am, tony_tpu.history\n"
        "from tony_tpu import profiler\n"
        "with profiler.span('tony:restore'):\n"
        "    profiler.count('saves')\n"
        "path = profiler.write_timeline()\n"
        "assert profiler.read_timeline(path)['counters'] == {'saves': 1}\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True,
        text=True, timeout=120,
        env={**os.environ,
             constants.ENV_SERVE_STATS: str(tmp_path / "serve-stats.json")})
    assert out.returncode == 0, out.stderr
    # ... and the exit hook rewrote it on the way out.
    assert (tmp_path / "timeline.json").is_file()


def test_timeline_file_sits_beside_the_stats_file(tmp_path, monkeypatch):
    monkeypatch.setenv(constants.ENV_SERVE_STATS,
                       str(tmp_path / "serve-stats.json"))
    assert profiler.timeline_path() == tmp_path / "timeline.json"
    with profiler.span("tony:dist_initialize"):
        pass
    assert profiler.write_timeline() == tmp_path / "timeline.json"
    assert sorted(p.name for p in tmp_path.iterdir()) == ["timeline.json"]
    got = profiler.read_timeline(tmp_path / "timeline.json")
    assert [s["name"] for s in got["spans"]] == ["tony:dist_initialize"]
    (tmp_path / "timeline.json").write_text("{torn")
    assert profiler.read_timeline(tmp_path / "timeline.json") is None
    monkeypatch.delenv(constants.ENV_SERVE_STATS)
    assert profiler.timeline_path() is None
    assert profiler.write_timeline() is None


# -- out of the process ----------------------------------------------------

def test_executor_relays_the_timeline_only_when_it_was_rewritten(
        tmp_path, monkeypatch):
    from tony_tpu.executor import TaskExecutor

    ex = TaskExecutor.__new__(TaskExecutor)
    ex.log_dir = tmp_path
    assert ex.timeline_path() == tmp_path / "timeline.json"
    assert ex._timeline_since(0) == (None, 0)
    profiler.count("saves")
    profiler.write_timeline(ex.timeline_path())
    first, mtime = ex._timeline_since(0)
    assert first["counters"] == {"saves": 1} and mtime > 0
    assert ex._timeline_since(mtime) == (None, mtime)
    ex.timeline_path().write_text("{torn")
    os.utime(ex.timeline_path(), ns=(mtime + 10**9, mtime + 10**9))
    assert ex._timeline_since(mtime) == (None, mtime)   # retried next beat


def _session_with_one_worker():
    from tony_tpu.conf import TonyConfig
    from tony_tpu.rpc import ApplicationRpcHandler
    from tony_tpu.session import TonySession

    session = TonySession(TonyConfig({"tony.worker.instances": "1",
                                      "tony.application.executes": "x"}),
                          app_id="app_test")
    session.on_registered("worker", 0, "127.0.0.1", 1)
    return session, ApplicationRpcHandler(session)


def test_heartbeat_and_result_rpcs_carry_the_timeline():
    session, handler = _session_with_one_worker()
    assert handler.rpc_heartbeat("worker", 0) is True
    assert session.task("worker", 0).timeline is None
    handler.rpc_heartbeat("worker", 0, timeline={"spans": [1]},
                          published={"version": 3, "step": 40})
    task = session.task("worker", 0)
    assert task.timeline == {"spans": [1]}
    assert task.published == {"version": 3, "step": 40}
    handler.rpc_heartbeat("worker", 0)                 # keeps the last one
    assert task.timeline == {"spans": [1]}
    handler.rpc_register_execution_result(
        "worker", 0, 0, timeline={"spans": [1, 2]})
    assert task.timeline == {"spans": [1, 2]}
    # A task the AM already marked terminal still delivers its last word.
    session.kill_remaining("test")
    handler.rpc_register_execution_result(
        "worker", 0, constants.EXIT_KILLED, timeline={"spans": [1, 2, 3]})
    assert task.timeline == {"spans": [1, 2, 3]}


def test_task_timeline_survives_log_rotation(tmp_path):
    handler = ev.EventHandler(tmp_path, "app_rot", max_bytes=4000)
    handler.task_timeline("worker", 0, {"spans": [{"name": "tony:restore"}]})
    for i in range(200):
        handler.task_metrics("worker", 0, {"cpu_pct": float(i)})
    assert handler.rotations > 0
    handler.close()
    [job] = ev.list_jobs(tmp_path)
    kept = [r for r in ev.read_events(job["path"])
            if r["type"] == ev.TASK_TIMELINE]
    assert len(kept) == 1
    assert kept[0]["payload"]["timeline"]["spans"][0]["name"] == \
        "tony:restore"


def _job_detail(job):
    from tony_tpu.history import job_detail

    history = Path(job.am.job_dir) / "history"
    [jhist] = (history / "finished").glob("*.jhist")
    return job_detail({"app_id": job.am.app_id, "state": "finished",
                       "path": str(jhist), "metadata": {}})


@pytest.fixture(scope="module")
def timeline_job(tmp_path_factory):
    """One `tony submit` of tests/workloads/timeline_train.py — a
    one-process job that never builds a mesh — through a real executor;
    its history detail."""
    job = MiniPod(tmp_path_factory.mktemp("timeline_job")).run({
        "tony.application.framework": "jax",
        "tony.worker.instances": "1",
        "tony.application.executes": "python timeline_train.py",
        "tony.task.max-missed-heartbeats": "200",
    }, src_dir=WORKLOADS, timeout=180)
    assert job.exit_code == 0, job.session.final_message
    return _job_detail(job)


def _named(timeline, name):
    return [s for s in timeline["spans"] if s["name"] == name]


def test_submitted_jobs_timeline_reaches_tony_history(timeline_job):
    """timeline.json -> register_execution_result -> TASK_TIMELINE ->
    history.job_detail["timelines"]."""
    from tony_tpu.history import render_show

    timeline = timeline_job["timelines"]["worker:0"]
    top = [s["name"] for s in sorted(timeline["spans"],
                                     key=lambda s: s["t0"])
           if s["parent"] is None and s["name"] != "tony:import"]
    assert top == ["tony:python_start", "tony:dist_initialize",
                   "tony:create_train_state", "tony:restore",
                   "tony:first_step"]
    [restore] = _named(timeline, "tony:restore")
    assert restore["attrs"] == {"step": None, "bytes": 0}
    c = timeline["counters"]
    # Compiled or, with jax's persistent cache warm, loaded.
    assert c.get("programs_compiled", 0) + c.get("programs_loaded", 0) >= 1
    assert c["saves"] == 1 and c["save_stall_s"] > 0
    # the dense decoder's flash calls: one block of the 16-token sequence
    # for each of the three kernels, recorded once by the first trace
    assert {k: v for k, v in c.items() if k.startswith("attn:")} == {
        "attn:kv_blocks_visited.dense": 1, "attn:kv_blocks_total.dense": 1,
        "attn:kv_blocks_fetched.dense": 1,
        **{f"attn:block_{side}.{kernel}.dense": 16 for side in "qk"
           for kernel in ("fwd", "dq", "dkv")}}
    assert any(b["kind"] in ("compile", "load") for b in timeline["builds"])
    shown = render_show(timeline_job)
    assert "task start timelines:" in shown
    assert "tony:create_train_state" in shown
    assert "program(s) built or loaded" in shown


def test_the_backend_starts_under_its_span_in_a_job_without_a_mesh(
        timeline_job):
    """The script's own jax.devices() comes after dist.initialize(),
    which has started the backend inside the framework."""
    timeline = timeline_job["timelines"]["worker:0"]
    [init] = _named(timeline, "tony:dist_initialize")
    [backend] = _named(timeline, "tony:backend_init")
    assert backend["parent"] == "tony:dist_initialize"
    assert init["t0"] <= backend["t0"] <= backend["t1"] <= init["t1"]


def test_imports_nest_under_their_importer_and_name_their_module(
        timeline_job):
    timeline = timeline_job["timelines"]["worker:0"]
    imports = _named(timeline, "tony:import")
    modules = [s["attrs"]["module"] for s in imports]
    assert len(set(modules)) == len(modules)        # each imported once
    # dist.initialize() was the first to need jax; the script's own
    # `import jax` after it is no span.
    [init] = _named(timeline, "tony:dist_initialize")
    [jax_import] = [s for s in imports if s["attrs"]["module"] == "jax"]
    assert jax_import["parent"] == "tony:dist_initialize"
    assert init["t0"] <= jax_import["t0"] <= jax_import["t1"] <= init["t1"]
    # what `from tony_tpu import train` and get_model() brought in
    assert {"flax.linen", "tony_tpu.models.transformer",
            "jax.experimental.pallas"} <= set(modules)
    assert "optax" not in modules       # the script's own import came first


def test_every_span_follows_the_launch_and_pythons_start(timeline_job):
    timeline = timeline_job["timelines"]["worker:0"]
    [start] = _named(timeline, "tony:python_start")
    assert timeline["t_launch"] == start["t0"] <= start["t1"]
    others = [s for s in timeline["spans"] if s is not start]
    assert others and all(start["t1"] <= s["t0"] <= s["t1"] for s in others)
    launched = next(r["timestamp"] for r in timeline_job["events"]
                    if r["type"] == ev.TASK_STARTED)
    assert 0 <= timeline["t_launch"] - launched < 60


def test_the_jobs_first_step_carries_what_it_built(timeline_job):
    timeline = timeline_job["timelines"]["worker:0"]
    [first] = _named(timeline, "tony:first_step")
    assert first["parent"] is None
    assert set(first["attrs"]) == {"programs", "trace_s", "lower_s",
                                   "compile_s", "load_s"}
    assert first["attrs"]["programs"] >= 1 and first["attrs"]["trace_s"] > 0
    inside = [b for b in timeline["builds"]
              if first["t0"] <= b["t"] <= first["t1"]
              and b["kind"] in ("compile", "load")]
    assert len(inside) == first["attrs"]["programs"]
    # The CPU reports no memory limit: no ladder, no rung, no memo.
    assert not _named(timeline, "tony:remat_rung")


def test_history_show_says_how_much_of_the_start_is_under_no_span(
        timeline_job):
    import re

    from tony_tpu.history import render_show

    timeline = timeline_job["timelines"]["worker:0"]
    bare, whole = profiler.unspanned(timeline)
    assert 0 <= bare < whole
    found = re.search(r"under no span: ([0-9.]+)s of ([0-9.]+)s",
                      render_show(timeline_job))
    assert found, render_show(timeline_job)
    assert (float(found[1]), float(found[2])) == (
        pytest.approx(bare, abs=0.006), pytest.approx(whole, abs=0.006))
    # nested spans are indented under what encloses them
    assert re.search(r"\n        \+[0-9.]+s tony:backend_init ",
                     render_show(timeline_job))


@pytest.mark.parametrize("kw,t,side,visited,total,fetched", [
    # head size 128: the packed call; the Mistral cell's length (K/V
    # resident: one whole block a head)
    (dict(dim=256, n_heads=2, n_kv_heads=1, max_seq=2048), 2048, 512, 10,
     16, 1),
    # the same past residency: streamed in blocks of 1024, and the map
    # stands still above the diagonal
    (dict(dim=256, n_heads=2, n_kv_heads=1, max_seq=8192), 8192, 1024, 36,
     64, 35),
    # head size 16: the classic layout; one block of a short sequence
    (dict(), 48, 48, 1, 1, 1),
])
def test_dense_attention_records_its_blocks_once(kw, t, side, visited,
                                                 total, fetched):
    """``Attention`` puts the tile shape the kernels' rule gave each of its
    three flash kernels, and the K/V blocks its forward visits and
    fetches, on the task's timeline at trace time — once, however often
    it is traced (the scan traces the block, init and apply trace the
    model)."""
    import jax
    import jax.numpy as jnp

    from tony_tpu.models import get_model

    model = get_model("llama-tiny", attention="flash", **kw)
    toks = jnp.zeros((1, t), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), toks)
    jax.eval_shape(model.apply, params, toks)
    c = {k: v for k, v in profiler.timeline()["counters"].items()
         if k.startswith("attn:")}
    assert c == {
        "attn:kv_blocks_visited.dense": visited,
        "attn:kv_blocks_total.dense": total,
        "attn:kv_blocks_fetched.dense": fetched,
        **{f"attn:block_{s}.{kernel}.dense": side for s in "qk"
           for kernel in ("fwd", "dq", "dkv")}}


@pytest.fixture(scope="module")
def tiny_ckpt(tmp_path_factory):
    """A committed llama-tiny checkpoint for the replica to restore."""
    import jax
    import jax.numpy as jnp
    import optax

    from tony_tpu import ckpt, train
    from tony_tpu.models import get_model

    root = tmp_path_factory.mktemp("ckpt")
    state = train.create_train_state(
        get_model("llama-tiny"), optax.adamw(1e-3),
        jnp.zeros((2, 16), jnp.int32), jax.random.PRNGKey(0))
    mgr = ckpt.AsyncCheckpointer(str(root))
    mgr.save(state, step=1, block=True)
    mgr.close()
    return root


def serve_props(ckpt_dir, **over) -> dict:
    """What `tony serve --model llama-tiny --ckpt_dir ...` writes."""
    from tony_tpu import conf as conf_mod

    props = {
        "tony.application.framework": "standalone",
        conf_mod.APPLICATION_STOP_ON_FAILURE: "false",
        conf_mod.instances_key("serve"): "1",
        conf_mod.command_key("serve"): "python -m tony_tpu.serve.replica",
        conf_mod.SERVE_MODEL: "llama-tiny",
        conf_mod.SERVE_CKPT_DIR: str(ckpt_dir),
        conf_mod.SERVE_CTX_MAX: "64",
        conf_mod.SERVE_BLOCK_SIZE: "8",
        conf_mod.SERVE_MAX_RUNNING: "4",
        "tony.task.max-missed-heartbeats": "400",
    }
    props.update(over)
    return props


def wait_for_replica(job, timeout=180.0):
    """The serve task's heartbeat carries its RPC port once it listens."""
    def port():
        if job.session is None:
            return None
        return job.session.task("serve", 0).serve_metrics.get("rpc_port")
    return int(job.wait_for(port, timeout=timeout, what="replica listening"))


def test_a_killed_serve_task_has_delivered_its_timeline(tmp_path, tiny_ckpt):
    """`tony kill` SIGKILLs executor and replica together: no exit RPC.
    The timeline written at the end of set-up went out with a heartbeat."""
    job = MiniPod(tmp_path).submit(serve_props(tiny_ckpt))
    try:
        wait_for_replica(job)
        job.wait_for(lambda: job.session.task("serve", 0).timeline,
                     timeout=30, what="timeline relayed by a heartbeat")
    finally:
        job.kill()
    job.wait(60)
    detail = _job_detail(job)
    task = next(t for t in detail["tasks"]
                if (t["job_type"], t["index"]) == ("serve", 0))
    assert task["status"] == "KILLED"
    timeline = detail["timelines"]["serve:0"]
    names = [s["name"] for s in timeline["spans"]]
    assert "tony:restore" in names
    restore = next(s for s in timeline["spans"]
                   if s["name"] == "tony:restore")
    assert restore["attrs"]["step"] == 1 and restore["attrs"]["bytes"] > 0
    assert timeline["counters"]["programs_lowered"] >= 0


def test_tony_profile_captures_a_live_replicas_serve_spans(tmp_path,
                                                           tiny_ckpt):
    """A `tony serve` job with tony.task.profiler.enabled gets a profiler
    port (a replica is a "standalone" task), the replica listens on it,
    and a capture taken while it decodes holds the serve:* spans."""
    from tony_tpu.rpc import RpcClient

    if profiler._trace_fn() is None:
        pytest.skip("no profiler client (xprof) importable")
    job = MiniPod(tmp_path).submit(
        serve_props(tiny_ckpt, **{"tony.task.profiler.enabled": "true"}))
    stop = threading.Event()
    try:
        port = wait_for_replica(job)
        endpoints = profiler.endpoints_from_callback_info(
            job.session.task_callback_info)
        assert list(endpoints) == ["serve:0"]
        if not profiler._wait_reachable(endpoints["serve:0"], 30.0):
            pytest.skip("the jax profiler server never bound its port here")

        def traffic():
            with RpcClient(f"127.0.0.1:{port}", timeout=60) as c:
                while not stop.is_set():
                    c.call("generate", tokens=[1, 2, 3, 4, 5],
                           max_new_tokens=12)

        # Build the prefill and decode programs first: a span that is
        # open when the capture ends (a launch that compiles) is lost.
        with RpcClient(f"127.0.0.1:{port}", timeout=120) as c:
            c.call("generate", tokens=[1, 2, 3, 4, 5], max_new_tokens=12)
        t = threading.Thread(target=traffic, daemon=True)
        t.start()
        got = profiler.collect_traces(endpoints, tmp_path / "hist",
                                      job.am.app_id, duration_ms=1500,
                                      wait_reachable_s=5.0)
        stop.set()
        t.join(60)
        assert not t.is_alive()
        assert got, "no trace captured"
        names = set(host_events(got[0]))
        assert {"serve:admit", "serve:build_inputs", "serve:launch",
                "serve:readback", "serve:emit"} <= names, sorted(
                    n for n in names if ":" in n)
    finally:
        stop.set()
        job.kill()
    job.wait(60)


# -- the hooks in the program ----------------------------------------------

def test_train_loop_puts_every_step_on_a_profiler_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    import optax

    from tony_tpu import train
    from tony_tpu.models import get_model

    state = train.create_train_state(
        get_model("llama-tiny"), optax.adamw(1e-3),
        jnp.zeros((2, 16), jnp.int32), jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda logits, b: train.next_token_loss(logits, b["x"]))
    batches = [{"x": jnp.full((2, 16), i, jnp.int32)} for i in range(3)]
    seen = []
    jax.profiler.start_trace(str(tmp_path))
    try:
        state, metrics = train.train_loop(
            state, step, batches=iter(batches), save_final=False,
            on_step=lambda n, m: seen.append(n))
        jax.block_until_ready(metrics["loss"])
    finally:
        jax.profiler.stop_trace()
    names = host_events(tmp_path)
    assert seen == [1, 2, 3]
    assert names.count("train_step") == 3
    assert names.count("train:on_step") == 3
    # Three batches and the call that finds the iterator exhausted.
    assert names.count("train:next_batch") == 4
    assert "train:save" not in names and "train:drain_poll" not in names
    # (and a tony:import each where this test is the process's first to
    # import tony_tpu.train and the models)
    assert [s["name"] for s in profiler.timeline()["spans"]
            if s["name"] != "tony:import"] == \
        ["tony:create_train_state", "tony:first_step"]


@pytest.mark.parametrize("gang", [True, False])
def test_initialize_starts_the_backend_after_the_rendezvous(monkeypatch,
                                                             gang):
    """On a gang the backend may only start once jax.distributed knows
    the processes; alone there is no rendezvous and the backend starts
    all the same, under the span, inside the framework."""
    import jax

    from tony_tpu import distributed, util

    calls = []
    monkeypatch.setattr(util, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(profiler, "watch_builds", lambda: None)
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda **kw: calls.append(("rendezvous", kw["num_processes"])))
    monkeypatch.setattr(profiler, "backend_devices",
                        lambda: calls.append(("backend",
                                              list(profiler._TIMELINE
                                                   .open_spans()))))
    monkeypatch.delenv(constants.ENV_PROFILER_PORT, raising=False)
    for name, value in ((constants.ENV_COORDINATOR_ADDRESS, "localhost:1"),
                        (constants.ENV_NUM_PROCESSES, "2"),
                        (constants.ENV_PROCESS_ID, "1")):
        if gang:
            monkeypatch.setenv(name, value)
        else:
            monkeypatch.delenv(name, raising=False)
    assert distributed.initialize() is gang
    backend = ("backend", ["tony:dist_initialize"])
    assert calls == ([("rendezvous", 2), backend] if gang else [backend])


def test_backend_devices_is_a_span_only_where_it_starts_the_backend(
        monkeypatch):
    import jax
    from jax._src import xla_bridge

    assert profiler.backend_devices() == jax.devices()   # started long ago
    assert profiler.timeline()["spans"] == []
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: False)
    assert profiler.backend_devices() == jax.devices()
    assert [s["name"] for s in profiler.timeline()["spans"]] == \
        ["tony:backend_init"]


def test_first_step_wraps_exactly_the_first_call_and_what_it_built():
    from tony_tpu import train

    compile_ev = "/jax/core/compile/backend_compile_duration"
    trace_ev = "/jax/core/compile/jaxpr_trace_duration"
    load_ev = "/jax/compilation_cache/cache_retrieval_time_sec"
    profiler._on_build(compile_ev, 4.0)          # before the loop: not its
    called = []

    def step_fn(state, batch):
        called.append(time.time())
        if len(called) == 1:
            profiler._on_build(trace_ev, 0.5)
            profiler._on_build(compile_ev, 2.0)
            profiler._on_build(load_ev, 0.25)
            profiler._on_build(compile_ev, 0.26)        # the load, again
        else:
            profiler._on_build(compile_ev, 1.0)         # a later shape
        time.sleep(0.01)
        return state + 1, {"loss": batch}

    state, metrics = train.train_loop(0, step_fn, batches=[10, 11, 12],
                                      save_final=False)
    assert (state, metrics) == (3, {"loss": 12})
    [first] = profiler.timeline()["spans"]
    assert (first["name"], first["parent"]) == ("tony:first_step", None)
    assert first["attrs"] == {"programs": 2, "trace_s": 0.5, "lower_s": 0,
                              "compile_s": 2.0, "load_s": 0.25}
    assert first["t0"] <= called[0] < first["t1"] <= called[1]


def test_a_first_step_that_fails_still_closes_its_span():
    from tony_tpu import train

    def step_fn(state, batch):
        raise RuntimeError("no such step")

    with pytest.raises(RuntimeError, match="no such step"):
        train.train_loop(0, step_fn, batches=[1], save_final=False)
    [first] = profiler.timeline()["spans"]
    assert first["name"] == "tony:first_step"
    assert first["attrs"]["programs"] == 0
    assert profiler._TIMELINE.open_spans() == []


@pytest.fixture
def fake_limit(monkeypatch, memo_dir):
    """tests/test_remat.py's fake device, 15.75 GiB, and an empty memo."""
    from tony_tpu import remat

    monkeypatch.setattr(remat, "_device_of", lambda _state: FakeDevice(LIMIT))


def test_a_cold_first_step_holds_one_rung_span_for_each_rung_tried(
        fake_limit):
    """tests/test_remat.py's fake compiler and fake limit under the real
    loop: a cold start tries rungs, each a child of tony:first_step; the
    start after it reads the memo and tries none."""
    from tony_tpu import remat, train

    def start():
        profiler.reset_timeline()
        step = remat.ChosenStep(FakeCompiler({**MISTRAL, FULL: None}))
        ran, names = train.train_loop(STATE, step, batches=[BATCH, BATCH],
                                      save_final=False)
        assert (ran, names) == ("ran", NO_WO)
        spans = profiler.timeline()["spans"]
        return spans[-1], spans[:-1]

    first, rungs = start()
    assert first["name"] == "tony:first_step"
    assert first["attrs"]["from_memo"] is False
    assert [(r["name"], r["parent"]) for r in rungs] == \
        [("tony:remat_rung", "tony:first_step")] * 2
    assert [r["attrs"] for r in rungs] == [
        {"saved": ",".join(FULL), "bytes": None, "fits": False,
         "prevent_cse": False},
        {"saved": ",".join(NO_WO), "bytes": MISTRAL[NO_WO], "fits": True,
         "prevent_cse": False}]
    assert first["t0"] <= rungs[0]["t0"] <= rungs[0]["t1"] \
        <= rungs[1]["t0"] <= rungs[1]["t1"] <= first["t1"]
    first, rungs = start()
    assert rungs == [] and first["attrs"]["from_memo"] is True


def test_a_rung_whose_trace_fails_closes_its_span_where_it_failed(
        fake_limit):
    from tony_tpu import remat

    class Untraceable(FakeCompiler):
        def __call__(self, saved):
            step = super().__call__(saved)
            step.trace = lambda state, batch: 1 / 0
            return step

    with profiler.span("tony:first_step"):
        with pytest.raises(ZeroDivisionError):
            remat.ChosenStep(Untraceable(MISTRAL))(STATE, BATCH)
        assert profiler._TIMELINE.open_spans() == ["tony:first_step"]
    rung, first = profiler.timeline()["spans"]
    assert (rung["name"], rung["parent"], rung["attrs"]) == (
        "tony:remat_rung", "tony:first_step", {})


def test_device_scopes_are_in_the_programs_the_step_and_kernels_lower_to():
    import jax
    import jax.numpy as jnp
    import optax

    from tony_tpu import train
    from tony_tpu.models import get_model
    from tony_tpu.ops import flash_attention

    state = train.create_train_state(
        get_model("llama-tiny"), optax.adamw(1e-3),
        jnp.zeros((2, 16), jnp.int32), jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda logits, b: train.next_token_loss(logits, b["x"]),
        donate=False)
    text = step.lower(state, {"x": jnp.zeros((2, 16), jnp.int32)}).as_text(
        debug_info=True)
    for scope in ("embed", "mlp", "lm_head", "loss", "optimizer"):
        assert f"/{scope}/" in text or f"({scope})" in text, scope
    q = jnp.zeros((1, 2, 128, 64), jnp.bfloat16)
    grad = jax.jit(jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True).astype(jnp.float32).sum(),
        (0, 1, 2)))
    text = grad.lower(q, q, q).as_text(debug_info=True)
    for scope in ("attn_fwd", "attn_bwd_dq", "attn_bwd_dkv"):
        assert scope in text, scope


def test_profiler_server_failure_is_logged_with_its_port(monkeypatch,
                                                         caplog):
    import jax

    from tony_tpu import distributed as dist

    def refuse(port):
        raise RuntimeError("address in use")

    monkeypatch.setattr(jax.profiler, "start_server", refuse)
    monkeypatch.setenv(constants.ENV_PROFILER_PORT, "9431")
    with caplog.at_level(logging.INFO, logger="tony_tpu.distributed"):
        dist._maybe_start_profiler()
    assert "9431" in caplog.text and "address in use" in caplog.text
    caplog.clear()
    monkeypatch.setattr(jax.profiler, "start_server", lambda port: None)
    with caplog.at_level(logging.INFO, logger="tony_tpu.distributed"):
        dist._maybe_start_profiler()
    assert "listening on port 9431" in caplog.text


@pytest.mark.parametrize("framework", ["jax", "standalone"])
def test_a_profiled_job_gives_every_task_a_profiler_port(framework):
    from tony_tpu.conf import TonyConfig
    from tony_tpu.runtime import TaskContext, get_framework

    def ctx(enabled, port=None):
        conf = TonyConfig({"tony.serve.instances": "1",
                           "tony.task.profiler.enabled": enabled})
        return TaskContext(conf=conf, job_type="serve", index=0,
                           cluster_spec={"serve": ["127.0.0.1:1"]},
                           am_address="127.0.0.1:2", app_id="a",
                           attempt_id=1, profiler_port=port)

    adapter = get_framework(framework).task_adapter()
    assert adapter.need_reserve_profiler_port(ctx("true"))
    assert not adapter.need_reserve_profiler_port(ctx("false"))
    env = adapter.build_task_env(ctx("true", port=9431))
    assert env[constants.ENV_PROFILER_PORT] == "9431"
    assert constants.ENV_PROFILER_PORT not in \
        adapter.build_task_env(ctx("true"))


# -- the serve replica -----------------------------------------------------

@pytest.fixture(scope="module")
def tiny_engine_parts():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from tony_tpu.models import get_model

    model = get_model("llama-tiny", n_layers=2)
    params = nn.unbox(model.init(jax.random.PRNGKey(0),
                                 jnp.zeros((1, 16), jnp.int32)))["params"]
    return model, jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a,
        params)


def make_engine(parts, **kw):
    from tony_tpu.serve import ServeEngine

    model, params = parts
    return ServeEngine(model, params, ctx_max=64, block_size=8, q_block=16,
                       decode_buckets=(2, 4), max_running=4, **kw)


def test_completions_carry_one_time_per_token(tiny_engine_parts):
    from tony_tpu.serve.engine import Request

    eng = make_engine(tiny_engine_parts)
    eng.submit(Request(rid="a", tokens=[1, 2, 3, 4, 5], max_new_tokens=6))
    eng.submit(Request(rid="b", tokens=[7, 8, 9], max_new_tokens=1))
    done = {c.rid: c for c in eng.run()}
    for rid, n in (("a", 6), ("b", 1)):
        c = done[rid]
        assert len(c.tokens) == n == len(c.token_s)
        assert all(a <= b for a, b in zip(c.token_s, c.token_s[1:]))
        assert 0 < c.token_s[0] and c.token_s[-1] <= c.latency_s
        wire = c.wire()
        assert len(wire["token_ms"]) == n
        assert wire["token_ms"][-1] <= wire["latency_ms"]
        json.dumps(wire)


def test_token_ms_rides_rpc_generate_and_the_router(tiny_engine_parts):
    from tony_tpu.serve.engine import Completion, EngineFront
    from tony_tpu.serve.replica import _ReplicaRpcHandler
    from tony_tpu.serve.router import RequestRouter, _wire_completion

    front = EngineFront(make_engine(tiny_engine_parts))

    class Rep:                      # what _ReplicaRpcHandler fronts
        engine = front.engine

        def generate(self, tokens, max_new_tokens, rid=None, conv=None,
                     tenant=None):
            return front.generate(tokens, max_new_tokens, rid=rid)

    wire = _ReplicaRpcHandler(Rep()).rpc_generate([1, 2, 3], 5, rid="r1")
    assert len(wire["token_ms"]) == 5 == len(wire["tokens"])
    assert wire["token_ms"] == sorted(wire["token_ms"])
    # The router passes a replica's wire dict through untouched, and
    # turns an in-process Completion into the same shape.
    assert _wire_completion(wire, "r1") is wire
    router = RequestRouter(block_size=8)
    router.upsert_replica("rep0", client=Rep())
    routed = router.dispatch([1, 2, 3], 4, rid="r2")
    assert len(routed["token_ms"]) == 4 and routed["replica"] == "rep0"
    old = Completion(rid="x", prompt=[1], tokens=[2], logits=None,
                     latency_s=0.5)           # built by older code
    assert old.wire()["token_ms"] == []
    assert _wire_completion(old, "x")["token_ms"] == []


def test_engine_stats_carry_the_new_counters_into_serve_window(
        tiny_engine_parts, tmp_path):
    from tony_tpu import util
    from tony_tpu.serve.engine import Request

    profiler.watch_builds()
    eng = make_engine(tiny_engine_parts)
    eng.restore_s = 1.5
    assert eng.warm() == 2 and eng.warm_s > 0
    eng.submit(Request(rid="a", tokens=[1, 2, 3], max_new_tokens=2))
    eng.run()
    stats = eng.stats()
    assert stats["programs_built"] >= 2 and stats["build_s"] > 0
    assert stats["restore_s"] == 1.5 and stats["warm_s"] == eng.warm_s
    assert stats["memory_peak_bytes"] >= 0
    assert [s["name"] for s in profiler.timeline()["spans"]] == ["tony:warm"]
    # ... and ride rpc_serve_stats -> heartbeat -> SERVE_WINDOW unchanged.
    beat = util.normalize_serve_telemetry(json.loads(json.dumps(stats)))
    handler = ev.EventHandler(tmp_path, "app_sw")
    handler.serve_window("serve", 0, beat)
    handler.close()
    [job] = ev.list_jobs(tmp_path)
    [window] = [r["payload"]["stats"] for r in ev.read_events(job["path"])
                if r["type"] == ev.SERVE_WINDOW]
    for key in ("programs_built", "build_s", "restore_s", "warm_s",
                "memory_peak_bytes"):
        assert window[key] == stats[key], key
