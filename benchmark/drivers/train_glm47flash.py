"""Parent side of a training cell of the ``glm-4.7-flash`` configuration:
``drivers/train.py`` with this architecture's configuration loader and task
script, returning what it returns, so that the model-agnostic readers work
unchanged; ``L_LM`` and ``L_MTP`` are logged and compared apart. One worker
on one chip (on one chip the expert layer runs without its exchange).
jax-free."""

from __future__ import annotations

import json
import shutil
import subprocess
import time
from pathlib import Path

from benchmark import harness, modelcfg_glm47flash as modelcfg
from benchmark.harness import Failed, log


TASK = harness.HERE / "tasks" / "train_glm47flash_task.py"   # submitted


def task_cfg(wl: dict, args, run: Path, seeds: list) -> dict:
    cfg = modelcfg.load(wl["config"])
    job = wl["job"]
    if args.rehearse:
        cfg = modelcfg.tiny(cfg)
        job = dict(job, batch=job["rehearse_batch"], seq=64)
    kwargs = modelcfg.program_kwargs(cfg, job["seq"])
    if args.control:
        kwargs.update(wl["controls"][args.control]["model_kwargs"])
    return {
        "root": str(harness.ROOT), "model_cfg": cfg,
        "program_model": cfg["program"]["model"], "program_kwargs": kwargs,
        "batch": job["batch"], "seq": job["seq"],
        "learning_rate": cfg["program"]["learning_rate"],
        "seconds": args.seconds, "seeds": seeds,
        "trace_dir": str(run / "trace") if args.trace else None,
    }


def run(cell: dict, wl: dict, args, t_start: float) -> dict:
    run_dir = harness.fresh_run_dir(cell["name"])
    job = wl["job"]
    if job["instances"] != 1 or job["tpus_per_worker"] != 1 or job["mesh"]:
        raise Failed("this cell trains on one worker, one chip")
    src, workdir = run_dir / "src", run_dir / "jobs"
    src.mkdir()
    workdir.mkdir()
    shutil.copy(TASK, src / "train_glm47flash_task.py")
    seeds = args.limit_seeds or [args.seed]
    (src / "bench_task.json").write_text(json.dumps(
        task_cfg(wl, args, run_dir, seeds)))
    conf = [f"tony.worker.instances={job['instances']}"]
    if not args.rehearse:
        conf.append(f"tony.worker.tpus={job['tpus_per_worker']}")
    # A cold start walks the residual ladder (a compile of the 16k step a
    # rung) and builds the reference's gradient; every further seed of a
    # limits run is a reference run more.
    timeout = 1700.0 + 300.0 * (len(seeds) - 1)
    cmd = harness.tony(
        "submit", "--framework", "jax", "--src_dir", str(src),
        "--executes", "python train_glm47flash_task.py", "--workdir", str(workdir),
        "--timeout", str(timeout), *[a for c in conf for a in ("--conf", c)])
    t_submit = time.time()
    try:
        with open(run_dir / "client.log", "w") as logf:
            rc = subprocess.run(cmd, env=harness.base_env(), cwd=run_dir,
                                stdout=logf, stderr=subprocess.STDOUT,
                                timeout=timeout + 60).returncode
    except subprocess.TimeoutExpired:
        rc = -1
    finally:
        left = harness.reap(run_dir)
    results, tails = [], ""
    for c in sorted(workdir.glob("*/containers/*")):
        if (c / "stdout.log").is_file():
            results += harness.tagged_lines((c / "stdout.log").read_text())
        for name in ("stderr.log", "executor.log"):
            tails += harness.tail(c / name, 2500)
    if rc != 0 or len(results) != job["instances"]:
        raise Failed(f"tony submit rc={rc}, {len(results)}/{job['instances']}"
                     f" worker result(s)\n--- client\n"
                     f"{harness.tail(run_dir / 'client.log', 1500)}\n"
                     f"--- task\n{tails}")
    if left:
        raise Failed(f"{left} process(es) outlived the job")
    first = min(results, key=lambda r: r["process"])
    chips = job["instances"] * job["tpus_per_worker"]
    device = harness.check_device(first, chips, args.rehearse)
    events = harness.jhist_events(workdir)
    t_running = harness.event_time(events, "ALL_TASKS_RUNNING")
    limits = wl["limits"]
    ok, compared = True, []
    for rec in first["seeds"]:
        for name, value in rec["compared"].items():
            good = value <= limits[name]["limit"]
            compared.append({"seed": rec["seed"], "number": name,
                             "value": value, "limit": limits[name]["limit"],
                             "ok": good})
            ok &= good
        ok &= rec["losses_finite"] and rec["compiled_in_window"] == 0
        log(f"seed {rec['seed']}: L_LM {rec['losses_check']} reference "
            f"{rec['reference_losses']} L_MTP {rec['mtp_losses_check']} "
            f"reference {rec['reference_mtp_losses']} last "
            f"{rec['loss_last']} "
            f"rows_held {rec['moe_rows_held_layers']} reference "
            f"{rec['reference_rows_held_layers']} last step "
            f"{rec['moe_rows_held_last']} "
            f"rows_max_expert {rec['moe_rows_max_expert']} "
            f"finite={rec['losses_finite']} compiled_in_window="
            f"{rec['compiled_in_window']} window_turns_s="
            f"{rec['window_turns_s']} reference_s="
            f"{rec['reference_s']:.1f} worst_leaves={rec['worst_leaves']}")
    for c in compared:
        log("COMPARED " + json.dumps(c))
    if args.limit_seeds:
        for rec in first["seeds"]:
            log("LEAVES " + json.dumps({"seed": rec["seed"],
                                        "t": rec.get("leaf_table")}))
    rec = first["seeds"][-1]
    # Where set-up went (seconds): parent -> submit -> all tasks running ->
    # task process -> state built -> seeded weights -> first step
    # dispatched -> check steps and warm-up done, the window opens.
    stamps = [t_start, t_submit, t_running or t_submit, rec["t_process"],
              rec["t_init"], rec["t_weights"], rec["t_step1"],
              rec["t_window"]]
    log("SETUP " + json.dumps(dict(zip(
        ("to_submit", "launch", "task_start", "task_init", "weights",
         "first_step", "checks_and_warm_up"),
        (round(b - a, 2) for a, b in zip(stamps, stamps[1:]))))))
    # The job's rate: every process steps the same global batch.
    window_s = max(r["seeds"][-1]["window_s"] for r in results)
    tok_s = rec["tokens"] / window_s if window_s else 0.0
    peak = max(r["seeds"][-1]["memory_peak_bytes"] or 0 for r in results)
    return {
        "correct": bool(ok), "attempted": rec["steps"],
        "failed": 0 if rec["losses_finite"] else rec["steps"],
        "device": dict(device, memory_peak_bytes=peak),
        "end_to_end": {"train_tok_s": tok_s,
                       "setup_s": rec["t_window"] - t_start},
        "artifacts": {
            "kind": "train", "cell": cell["name"], "chips": chips,
            "config": wl["config"], "task": rec, "tok_s": tok_s,
            "model_cfg": modelcfg.load(wl["config"]),
            "job": job, "t_start": t_start, "t_submit": t_submit,
            "t_all_running": t_running, "device": device,
            "trace_events": str(run_dir / "trace" / "events.json")
            if args.trace else None,
        },
    }
