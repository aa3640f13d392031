"""``tony`` command-line interface (layer L6).

Mirrors ``tony-cli``'s ``ClusterSubmitter`` (upstream ``tony-cli/src/main/
java/com/linkedin/tony/cli/ClusterSubmitter.java``, unverified — SURVEY.md
§0/§2.2) plus the client flag surface of ``TonyClient#init``. The flags keep
the reference's names so existing TonY job definitions translate directly::

    tony submit --src_dir src/ --executes train.py --conf_file tony.xml \
                --conf tony.worker.instances=2 --framework jax

Subcommands:

* ``submit``  — submit a job and monitor it to completion (exit code = job's)
* ``history`` — list finished/running jobs, or show one job's events
* ``notebook``— single-container notebook session behind the TCP proxy
  (reference: ``NotebookSubmitter``)
* ``version`` — print the framework version
"""

from __future__ import annotations

import argparse
from typing import List, Optional

from tony_tpu import __version__
from tony_tpu import conf as conf_mod
from tony_tpu.conf import TonyConfig


def _parse_conf_overrides(pairs: List[str]) -> dict:
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"--conf expects key=value, got {pair!r}")
        k, _, v = pair.partition("=")
        out[k.strip()] = v.strip()
    return out


def build_conf(args: argparse.Namespace) -> TonyConfig:
    """Effective config from file + CLI switches + ``--conf`` overrides —
    the reference's layering (SURVEY.md §5.6), highest precedence last."""
    cfg = TonyConfig()
    if args.conf_file:
        cfg.merge_file(args.conf_file)
    if getattr(args, "executes", None):
        cfg.set("tony.application.executes", args.executes)
    if getattr(args, "framework", None):
        cfg.set(conf_mod.APPLICATION_FRAMEWORK, args.framework)
    if getattr(args, "name", None):
        cfg.set(conf_mod.APPLICATION_NAME, args.name)
    if getattr(args, "python_venv", None):
        cfg.set(conf_mod.PYTHON_VENV, args.python_venv)
    if getattr(args, "python_binary_path", None):
        cfg.set(conf_mod.PYTHON_BINARY, args.python_binary_path)
    cfg.merge_overrides(_parse_conf_overrides(args.conf or []))
    return cfg


def cmd_submit(args: argparse.Namespace) -> int:
    from tony_tpu.client import TonyClient
    cfg = build_conf(args)
    client = TonyClient(cfg, src_dir=args.src_dir, workdir=args.workdir,
                        am_host=args.am_host, quiet=args.quiet)
    return client.run(timeout=args.timeout)


def cmd_serve(args: argparse.Namespace) -> int:
    """Submit an online-serving job (tony_tpu.serve): N replica
    containers, each restoring the training checkpoint onto its own
    mesh (bf16 dtype policy by default) and running the continuous-
    batching engine behind the control-plane RPC wire. ``--max_replicas``
    above ``--replicas`` arms the AM's heartbeat-driven autoscaler.

    A replica is told it owns chips the way any task is:
    ``--conf tony.<jobtype>.tpus=N`` (jobtype ``serve``, or the role
    names under ``--role``). The scheduler then accounts for the chips
    and the replica's env pins ``JAX_PLATFORMS=tpu``, so a replica that
    cannot get its chip dies instead of serving from the CPU."""
    import json as json_mod
    from pathlib import Path

    from tony_tpu.client import TonyClient

    cfg = TonyConfig()
    if args.conf_file:
        cfg.merge_file(args.conf_file)
    # Replicas are independent jax worlds — no rendezvous gang — so the
    # framework is "standalone"; and a serving fleet should outlive one
    # crashed replica, so fail-fast is off (the autoscaler repairs the
    # floor instead).
    cfg.set(conf_mod.APPLICATION_FRAMEWORK, "standalone")
    cfg.set(conf_mod.APPLICATION_NAME,
            args.name or f"tony-serve-{args.model}")
    cfg.set(conf_mod.APPLICATION_STOP_ON_FAILURE, "false")
    # Disaggregated split (--role prefill=2,decode=4): each role becomes
    # its OWN jobtype — the heterogeneous-gang wiring — sharing the
    # serve.* engine config; the per-jobtype role key tells each replica
    # which half of the handoff protocol it fronts. Validate the spec at
    # SUBMIT: a typo'd role that silently became a colocated gang would
    # serve the wrong topology without a word.
    if args.role:
        roles = {}
        for part in args.role.split(","):
            name, _, count = part.partition("=")
            name = name.strip()
            if name not in ("prefill", "decode", "colocated"):
                raise SystemExit(f"--role: unknown role {name!r} "
                                 f"(prefill|decode|colocated)")
            if name in roles:
                raise SystemExit(f"--role: duplicate role {name!r}")
            try:
                n = int(count)
            except ValueError:
                raise SystemExit(f"--role: need {name}=<count>, got "
                                 f"{part!r}") from None
            if n < 1:
                raise SystemExit(f"--role: {name} needs >= 1 replica, "
                                 f"got {n}")
            roles[name] = n
        if ("prefill" in roles) != ("decode" in roles):
            raise SystemExit("--role: a split fleet needs BOTH a "
                             "prefill and a decode gang (the router "
                             "falls back to colocated only per-request, "
                             "not per-topology)")
        for name, n in roles.items():
            cfg.set(conf_mod.instances_key(name), str(n))
            cfg.set(conf_mod.command_key(name),
                    "python -m tony_tpu.serve.replica")
            cfg.set(conf_mod.serve_role_key(name), name)
    else:
        cfg.set(conf_mod.instances_key("serve"), str(args.replicas))
        cfg.set(conf_mod.command_key("serve"),
                "python -m tony_tpu.serve.replica")
    cfg.set(conf_mod.SERVE_MODEL, args.model)
    if args.model_kwargs:
        json_mod.loads(args.model_kwargs)   # validate at submit, not launch
        cfg.set(conf_mod.SERVE_MODEL_KWARGS, args.model_kwargs)
    # Continuous publication follow mode (tony_tpu.publish): --follow
    # names a TRAIN job's dir (its serialized conf supplies the ckpt
    # dir) or a bare ckpt dir, and arms tony.publish.follow — the AM
    # polls the published pointer and rolls the fleet onto every new
    # version the train gang commits.
    ckpt_dir = args.ckpt_dir
    if getattr(args, "follow", None):
        from tony_tpu import constants

        followed = Path(args.follow).resolve()
        conf_path = followed / constants.TONY_JOB_JSON
        if conf_path.is_file():
            followed_ckpt = TonyConfig.load(conf_path).get(
                conf_mod.CKPT_DIR)
            if not followed_ckpt:
                raise SystemExit(
                    f"--follow: job at {followed} has no "
                    f"{conf_mod.CKPT_DIR} in its conf — nothing to "
                    f"follow")
            ckpt_dir = followed_ckpt
        else:
            ckpt_dir = str(followed)   # bare ckpt dir
        cfg.set(conf_mod.PUBLISH_FOLLOW, "true")
    if not ckpt_dir:
        raise SystemExit("need --ckpt_dir (or --follow <jobdir>)")
    # Absolute: replicas run with a different cwd.
    cfg.set(conf_mod.SERVE_CKPT_DIR, str(Path(ckpt_dir).resolve()))
    cfg.set(conf_mod.SERVE_DTYPE_POLICY, args.dtype_policy)
    cfg.set(conf_mod.SERVE_CTX_MAX, str(args.ctx_max))
    if args.mesh:
        json_mod.loads(args.mesh)
        cfg.set(conf_mod.SERVE_MESH, args.mesh)
    if args.max_replicas is not None:
        cfg.set(conf_mod.SERVE_REPLICAS_MAX, str(args.max_replicas))
    # Speculative decoding lane: --spec_k arms draft-and-verify; a named
    # --draft_model restores a second (smaller) ckpt next to the target,
    # otherwise the self-drafting n-gram fallback runs. Validate the
    # flag COMBINATIONS at submit, not replica launch: a draft flag that
    # silently dropped would serve the wrong lane without a word.
    if args.spec_k and not 1 <= args.spec_k <= 15:
        # The replica's row block is q_block=16 and the k+1 verify rows
        # must fit it (SpecEngine enforces the same bound at launch).
        raise SystemExit(f"--spec_k must be in [1, 15] (k+1 verify rows "
                         f"ride the 16-row block), got {args.spec_k}")
    for flag, val in (("--draft_model_kwargs", args.draft_model_kwargs),
                      ("--draft_ckpt_dir", args.draft_ckpt_dir)):
        if val and not args.draft_model:
            raise SystemExit(f"{flag} needs --draft_model (without one "
                             f"the replica runs the n-gram self-draft "
                             f"and the flag would be silently ignored)")
    # Prefix caching / chunked prefill (tony_tpu.serve PR 13): validate
    # the chunk geometry at submit — the engine would reject a
    # non-row-block multiple at launch, replica by replica.
    if args.prefill_chunk and (args.prefill_chunk <= 0
                               or args.prefill_chunk % 16):
        raise SystemExit(f"--prefill_chunk must be a positive multiple "
                         f"of the 16-row block, got {args.prefill_chunk}")
    # KV memory hierarchy (tony_tpu.serve PR 16): host tier size and the
    # persistent prefix store. Validate at submit — a negative tier or a
    # relative store path (replicas run with a different cwd) would fail
    # replica by replica at launch.
    if args.host_blocks < 0:
        raise SystemExit(f"--host_blocks must be >= 0, got "
                         f"{args.host_blocks}")
    if args.host_blocks:
        cfg.set(conf_mod.SERVE_HOST_BLOCKS, str(args.host_blocks))
    if args.prefix_store:
        cfg.set(conf_mod.SERVE_PREFIX_STORE,
                str(Path(args.prefix_store).resolve()))
    # Replica cold-start plane (tony_tpu.ckpt.aot PR 17): persisted AOT
    # executables + warm-standby pool + the demotion daemon watermark.
    # Same submit-time validation story: the engine rejects a bad
    # watermark at launch, replica by replica; the cache dir must be
    # absolute for the same cwd reason as the prefix store.
    if args.aot_cache:
        cfg.set(conf_mod.SERVE_AOT_CACHE,
                str(Path(args.aot_cache).resolve()))
    if args.warm_standby < 0:
        raise SystemExit(f"--warm_standby must be >= 0, got "
                         f"{args.warm_standby}")
    if args.warm_standby:
        cfg.set(conf_mod.SERVE_WARM_STANDBY, str(args.warm_standby))
    if not 0.0 <= args.demote_watermark <= 1.0:
        raise SystemExit(f"--demote_watermark must be a pool fraction "
                         f"in [0, 1], got {args.demote_watermark}")
    if args.demote_watermark and not args.host_blocks:
        raise SystemExit("--demote_watermark needs --host_blocks > 0 "
                         "(the daemon demotes into the host tier; "
                         "without one the flag would be silently "
                         "ignored)")
    if args.demote_watermark:
        cfg.set(conf_mod.SERVE_DEMOTE_WATERMARK,
                str(args.demote_watermark))
    # QoS / history plane (tony_tpu.serve.qos PR 18): validate the
    # tenant spec at submit — parse_tenants raises on empty names,
    # duplicates, and non-positive weights, which the replica would
    # otherwise reject at launch, replica by replica.
    if args.tenants:
        from tony_tpu.serve.qos import parse_tenants

        try:
            parse_tenants(args.tenants)
        except ValueError as e:
            raise SystemExit(f"--tenants: {e}")
        cfg.set(conf_mod.SERVE_QOS_TENANTS, args.tenants)
    if args.qos_max_queue < 0:
        raise SystemExit(f"--qos_max_queue must be >= 0, got "
                         f"{args.qos_max_queue}")
    if args.qos_max_queue:
        if not args.tenants:
            raise SystemExit("--qos_max_queue needs --tenants (the cap "
                             "is per tenant class; without a spec it "
                             "would be silently ignored)")
        cfg.set(conf_mod.SERVE_QOS_MAX_QUEUE, str(args.qos_max_queue))
    if args.slo_target_ms:
        # Two grammars, one flag: a bare number is the fleet-wide target
        # (the PR 18 lane, byte-identical behavior), while a tenant CSV
        # (gold:200,silver:800) sets PER-TENANT targets — the autoscaler
        # then scales on the worst tenant's p99-vs-target. Same strict
        # parser as --tenants: a typo'd spec must die at submit, not
        # silently autoscale on the wrong signal.
        try:
            target = float(args.slo_target_ms)
        except ValueError:
            from tony_tpu.serve.qos import parse_tenants

            try:
                targets = parse_tenants(args.slo_target_ms)
            except ValueError as e:
                raise SystemExit(f"--slo_target_ms: {e}")
            if any(v <= 0 for v in targets.values()):
                raise SystemExit("--slo_target_ms: per-tenant targets "
                                 "must be > 0 ms")
            cfg.set(conf_mod.SERVE_SLO_TARGETS, args.slo_target_ms)
        else:
            if target < 0:
                raise SystemExit(f"--slo_target_ms must be >= 0, got "
                                 f"{target}")
            if target:
                cfg.set(conf_mod.SERVE_SLO_TARGET_MS, str(target))
    if args.prefix_cache:
        cfg.set(conf_mod.SERVE_PREFIX_CACHE, "true")
    if args.prefill_chunk:
        cfg.set(conf_mod.SERVE_PREFILL_CHUNK, str(args.prefill_chunk))
    if args.spec_k:
        cfg.set(conf_mod.SERVE_SPEC_K, str(args.spec_k))
    if args.draft_model:
        if not args.spec_k:
            raise SystemExit("--draft_model needs --spec_k > 0 (the "
                             "draft depth arms the speculative lane)")
        cfg.set(conf_mod.SERVE_DRAFT_MODEL, args.draft_model)
        if args.draft_model_kwargs:
            json_mod.loads(args.draft_model_kwargs)  # validate at submit
            cfg.set(conf_mod.SERVE_DRAFT_MODEL_KWARGS,
                    args.draft_model_kwargs)
        if args.draft_ckpt_dir:
            cfg.set(conf_mod.SERVE_DRAFT_CKPT_DIR,
                    str(Path(args.draft_ckpt_dir).resolve()))
    cfg.merge_overrides(_parse_conf_overrides(args.conf or []))
    client = TonyClient(cfg, workdir=args.workdir, am_host=args.am_host,
                        quiet=args.quiet)
    return client.run(timeout=args.timeout)


def cmd_route(args: argparse.Namespace) -> int:
    """Run the fleet's request router (tony_tpu.serve.router): a
    gateway-side RPC front that polls the AM's ``serve_endpoints`` verb
    for the live replica set and dispatches ``generate`` calls by
    prefix-cache overlap, queue depth, and p99 — with sticky session
    affinity and failover re-dispatch. Jax-free: runs on any gateway
    host."""
    import threading

    from tony_tpu.serve.router import (RequestRouter, RouterPolicy,
                                       RouterServer)

    policy = RouterPolicy(cache_weight=args.cache_weight,
                          queue_weight=args.queue_weight,
                          p99_weight=args.p99_weight)
    router = RequestRouter(block_size=args.block_size, policy=policy)
    server = RouterServer(router, port=args.port, am_address=args.am,
                          poll_s=args.poll_s)
    server.start()
    print(f"[tony-route] listening on {server.address}, tracking "
          f"replicas via AM {args.am}", flush=True)
    try:
        threading.Event().wait()
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
    return 0


def cmd_publish(args: argparse.Namespace) -> int:
    """Publish a committed checkpoint step for serve fleets to hot-swap
    onto (tony_tpu.publish): stage-and-rename the versioned pointer
    file over the ckpt root. Jax-free — runs anywhere the ckpt dir is
    mounted; the train loop's ``publish_every`` knob does the same
    thing automatically on the save cadence."""
    from tony_tpu.publish import PublishError, latest_publication, \
        publish_step

    try:
        rec = publish_step(args.ckpt_dir, args.step,
                           note=args.note or "")
    except (PublishError, OSError) as e:
        print(f"tony publish: {e}")
        return 1
    print(f"published v{rec['version']} -> step {rec['step']} "
          f"({rec['manifest']})")
    prev = latest_publication(args.ckpt_dir)
    if prev is None or prev["version"] != rec["version"]:
        print("warning: pointer read-back disagrees — concurrent "
              "publisher?")
    return 0


def cmd_aot(args: argparse.Namespace) -> int:
    """AOT-cache maintenance. ``gc`` drops entries whose stored runtime
    fingerprint no live config can produce — a jax/backend upgrade
    strands every old entry (the get() path already refuses them);
    this reclaims the disk."""
    if args.action != "gc":
        return 2
    from tony_tpu.ckpt.aot import AOTCache

    cache = AOTCache(args.cache)
    dropped, kept, freed = cache.gc(dry_run=args.dry_run)
    verb = "would drop" if args.dry_run else "dropped"
    print(f"tony aot gc: {verb} {dropped} stale entr"
          f"{'y' if dropped == 1 else 'ies'} ({freed} bytes), "
          f"{kept} live kept under {args.cache}")
    return 0


def cmd_history(args: argparse.Namespace) -> int:
    from tony_tpu.history import main as history_main
    return history_main(args)


def cmd_notebook(args: argparse.Namespace) -> int:
    from tony_tpu.notebook import main as notebook_main
    return notebook_main(args)


def cmd_azkaban(args: argparse.Namespace) -> int:
    from tony_tpu.azkaban import main as azkaban_main
    return azkaban_main(args)


def cmd_profile(args: argparse.Namespace) -> int:
    """Capture a trace from every rank of a RUNNING job into its history
    dir (reference gap closed per SURVEY.md §5.1: hook + collection)."""
    from pathlib import Path

    from tony_tpu import constants
    from tony_tpu.profiler import collect_traces, endpoints_from_callback_info
    from tony_tpu.rpc import RpcClient, RpcError

    live = _live_am(args)
    if live is None:
        return 1
    job_dir, addr, token = live
    try:
        with RpcClient(addr, token=token, timeout=10.0) as c:
            info = c.call("get_task_callback_info")
    except (RpcError, OSError) as e:
        print(f"AM RPC failed: {e}")
        return 1
    endpoints = endpoints_from_callback_info(info)
    if not endpoints:
        print("no profiler endpoints registered — set "
              "tony.task.profiler.enabled=true on the job")
        return 1
    # The AM's history root (may be overridden by tony.history.location).
    conf_path = job_dir / constants.TONY_JOB_JSON
    history = job_dir / "history"
    if conf_path.is_file():
        loc = TonyConfig.load(conf_path).get(conf_mod.HISTORY_LOCATION)
        if loc:
            history = Path(loc)
    collected = collect_traces(endpoints, history, args.app_id,
                               duration_ms=args.duration_ms)
    return 0 if collected else 1


def _job_dir_of(args: argparse.Namespace):
    from pathlib import Path

    from tony_tpu.util import default_workdir

    workdir = Path(args.workdir) if args.workdir else default_workdir()
    # Resolved: the trace logdir travels inside the profiler RPC and the
    # SERVER (the profiled process, different cwd) may write the xplane
    # files itself — a relative path lands in the wrong tree.
    return (workdir / args.app_id).resolve()


def _live_am(args: argparse.Namespace):
    """(job_dir, am_address, token) of a RUNNING job, or None (reported)
    — the shared resolution for every verb that dials a live AM."""
    job_dir = _job_dir_of(args)
    addr_file = job_dir / "am.address"
    if not addr_file.is_file():
        print(f"no live AM address for {args.app_id} under "
              f"{job_dir.parent} (already finished, or wrong --workdir?)")
        return None
    token_file = job_dir / "am.token"
    try:
        token = token_file.read_text().strip() \
            if token_file.is_file() else None
        addr = addr_file.read_text().strip()
    except OSError as e:   # e.g. 0600 token owned by the submitter
        print(f"cannot read AM credentials under {job_dir}: {e}")
        return None
    return job_dir, addr, token


def cmd_kill(args: argparse.Namespace) -> int:
    """Kill a RUNNING job from outside its submitting client (reference
    analogue: ``yarn application -kill``)."""
    from tony_tpu.rpc import RpcClient, RpcError

    live = _live_am(args)
    if live is None:
        return 1
    _, addr, token = live
    try:
        with RpcClient(addr, token=token, timeout=10.0) as c:
            c.call("finish_application",
                   reason=f"killed via tony kill by {args.reason or 'cli'}")
    except (RpcError, OSError) as e:
        print(f"kill RPC failed: {e}")
        return 1
    print(f"kill requested for {args.app_id}")
    return 0


def cmd_resize(args: argparse.Namespace) -> int:
    """Operator-triggered elastic resize of a RUNNING job's training
    gang: the AM drains the gang (each survivor commits model + data
    cursor), re-gangs at the new worker count, and restores — the
    ``tony_tpu.am.resize`` state machine. Needs the job submitted with
    ``tony.resize.enabled=true``; a disabled job reports the refusal
    here instead of silently ignoring the verb."""
    from tony_tpu.rpc import RpcClient, RpcError

    if args.num_workers < 1:
        print(f"--num_workers must be >= 1, got {args.num_workers}")
        return 1
    live = _live_am(args)
    if live is None:
        return 1
    _, addr, token = live
    try:
        with RpcClient(addr, token=token, timeout=10.0) as c:
            c.call("resize", num_workers=args.num_workers)
    except (RpcError, OSError) as e:
        print(f"resize RPC failed: {e}")
        return 1
    print(f"resize to {args.num_workers} worker(s) requested for "
          f"{args.app_id} (drain -> commit -> re-gang -> restore; "
          f"follow with: tony history show {args.app_id})")
    return 0


def cmd_logs(args: argparse.Namespace) -> int:
    """Print per-container logs of a job on the local substrate
    (reference analogue: ``yarn logs -applicationId``). Remote (tpu-vm)
    containers keep their logs on the worker hosts."""
    from collections import deque

    from tony_tpu import constants

    job_dir = _job_dir_of(args)
    containers = sorted((job_dir / "containers").glob("*")) \
        if (job_dir / "containers").is_dir() else []
    if not containers:
        print(f"no container logs under {job_dir} "
              f"(wrong --workdir, or a remote-substrate job?)")
        return 1
    tail = max(0, args.tail)
    printed_any = False
    for cdir in containers:
        for name in (constants.EXECUTOR_LOG_NAME,
                     constants.USER_STDOUT_NAME, constants.USER_STDERR_NAME):
            f = cdir / name
            if not f.is_file() or f.stat().st_size == 0:
                continue
            printed_any = True
            # Bounded memory either way: deque for --tail, streamed
            # line-by-line otherwise — container logs can be GBs.
            with open(f, errors="replace") as fh:
                if tail:
                    shown = deque(fh, maxlen=tail)
                    print(f"===== {cdir.name}/{name} "
                          f"(last {len(shown)} lines) =====")
                    for line in shown:
                        print(line.rstrip("\n"))
                else:
                    print(f"===== {cdir.name}/{name} =====")
                    for line in fh:
                        print(line.rstrip("\n"))
    if not printed_any:
        # Scripts need 'no logs yet' distinguishable from 'logs shown'.
        print(f"no non-empty logs yet under {job_dir / 'containers'}")
        return 1
    return 0


def cmd_analyze(args: argparse.Namespace) -> int:
    """Run the jaxpr invariant analyzer (and optionally the source lint)
    over the shipped train-step configs — the static half of the tier-1
    gate, runnable anywhere the CPU wheel is (no TPU needed). The import
    is jax-free (the analysis facade is lazy), so ``analysis_cli.main``
    still gets to set the virtual-CPU-mesh env BEFORE jax initializes."""
    from tony_tpu.analysis import cli as analysis_cli

    return analysis_cli.main(args)


def cmd_version(_args: argparse.Namespace) -> int:
    print(f"tony-tpu {__version__}")
    return 0


def make_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tony", description="TonY-TPU: TPU-native distributed-job orchestrator")
    sub = p.add_subparsers(dest="command", required=True)

    s = sub.add_parser("submit", help="submit a job and monitor to completion")
    s.add_argument("--src_dir", help="user source directory to stage")
    s.add_argument("--executes", help="command to run in each task container")
    s.add_argument("--conf_file", help="tony.xml / JSON job config")
    s.add_argument("--conf", action="append", metavar="KEY=VALUE",
                   help="config override (repeatable)")
    s.add_argument("--framework", help="jax|tensorflow|pytorch|horovod|mxnet|standalone")
    s.add_argument("--name", help="application name")
    s.add_argument("--python_venv", help="virtualenv archive/dir to ship")
    s.add_argument("--python_binary_path", help="python interpreter inside the venv")
    s.add_argument("--workdir", help="client work dir (default ~/.tony-tpu/jobs)")
    s.add_argument("--am_host", default="127.0.0.1",
                   help="address executors use to reach the AM")
    s.add_argument("--timeout", type=float, default=None,
                   help="client-side monitor timeout in seconds")
    s.add_argument("--quiet", action="store_true")
    s.set_defaults(fn=cmd_submit)

    sv = sub.add_parser("serve", help="serve a trained checkpoint: replica "
                        "containers with continuous batching and "
                        "heartbeat-driven autoscale")
    sv.add_argument("--model", required=True,
                    help="registered model name (e.g. llama2-7b)")
    sv.add_argument("--model_kwargs", help="JSON dict of model kwargs "
                    "(quant lanes, layer count overrides, ...)")
    sv.add_argument("--ckpt_dir", default=None,
                    help="training checkpoint directory to serve "
                         "(or use --follow)")
    sv.add_argument("--follow", default=None, metavar="JOBDIR|CKPT_DIR",
                    help="follow a train job's continuous publications: "
                         "a job dir (its conf supplies the ckpt dir) or "
                         "a bare ckpt dir — the AM polls the published "
                         "pointer and hot-swaps the fleet onto every "
                         "new version, one replica at a time")
    sv.add_argument("--replicas", type=int, default=1,
                    help="initial replica count (the autoscale floor)")
    sv.add_argument("--max_replicas", type=int, default=None,
                    help="autoscale ceiling (> --replicas arms the "
                         "AM's heartbeat-driven scaler); with --role "
                         "it is the FLEET ceiling, apportioned across "
                         "the gangs proportional to their floors "
                         "(per-gang override: "
                         "tony.serve.replicas.max.<jobtype>)")
    sv.add_argument("--dtype_policy", default="bf16", choices=("bf16", "f32"),
                    help="restore-time cast: f32 master -> serving dtype")
    sv.add_argument("--ctx_max", type=int, default=2048,
                    help="max positions per sequence (KV buffer extent)")
    sv.add_argument("--mesh", help="JSON MeshSpec kwargs for each "
                    "replica's own mesh (e.g. '{\"fsdp\": 2}')")
    sv.add_argument("--prefix_cache", action="store_true",
                    help="arm block-level KV prefix sharing: admissions "
                         "whose prompt chain-matches cached blocks skip "
                         "that prefill outright (bitwise transparent)")
    sv.add_argument("--prefill_chunk", type=int, default=0,
                    help="chunked prefill rows per iteration (a 16-row "
                         "block multiple; 0 = monolithic): long prompts "
                         "interleave with decode instead of stalling it")
    sv.add_argument("--role", default=None, metavar="ROLE=N[,ROLE=N...]",
                    help="disaggregated prefill/decode split: per-role "
                         "gang sizes, e.g. 'prefill=2,decode=4' — each "
                         "role becomes its OWN jobtype (heterogeneous "
                         "gangs in one job) and the router ships KV "
                         "blocks prefill->decode over the RPC wire; "
                         "omit for the classic colocated fleet")
    sv.add_argument("--host_blocks", type=int, default=0,
                    help="pinned host-RAM KV tier size in blocks (0 = "
                         "off): cold published stems demote to host "
                         "instead of dying at LRU eviction, and idle "
                         "conversations park between turns — resumed "
                         "turns skip their re-prefill bitwise")
    sv.add_argument("--prefix_store", default=None, metavar="DIR",
                    help="persistent prefix store directory: hot "
                         "published stems commit to disk through the "
                         "ckpt plane's atomic rename, and fresh or "
                         "scale-up replicas warm their prefix tier "
                         "from the store on start")
    sv.add_argument("--aot_cache", default=None, metavar="DIR",
                    help="persisted AOT compile cache directory: step "
                         "executables compiled once serialize next to "
                         "the ckpt manifest, and every later replica "
                         "of the same (topology, config, jax) family "
                         "deserializes in milliseconds instead of "
                         "re-tracing — the scale-up grant's cold-start "
                         "killer")
    sv.add_argument("--warm_standby", type=int, default=0,
                    help="warm-standby pool size per serve jobtype "
                         "(0 = off): compiled-and-idle replicas held "
                         "ahead of the traffic curve; the AM promotes "
                         "one on scale-up instead of a cold grant "
                         "(per-gang override: "
                         "tony.serve.warm-standby.<jobtype>)")
    sv.add_argument("--demote_watermark", type=float, default=0.0,
                    help="device-pool occupancy fraction above which "
                         "the engine loop pre-demotes cold KV blocks "
                         "into the --host_blocks tier (0 = off): "
                         "eviction pressure is drained ahead of the "
                         "work arriving, like the warm pool itself")
    sv.add_argument("--tenants", default=None, metavar="NAME:W[,NAME:W...]",
                    help="tenant classes with weighted-fair KV-block "
                         "budgets, e.g. gold:3,silver:1 (bare name = "
                         "weight 1); arms per-tenant admission QoS on "
                         "every replica — absent, serving is "
                         "byte-identical to an untagged fleet")
    sv.add_argument("--qos_max_queue", type=int, default=0,
                    help="per-tenant queue cap: past it a tenant's "
                         "submits get typed retryable back-pressure "
                         "(0 = unbounded; needs --tenants)")
    sv.add_argument("--slo_target_ms", default="",
                    metavar="MS|TENANT:MS[,TENANT:MS...]",
                    help="p99 latency target arming SLO-mode "
                         "autoscaling: the gang scales on p99-vs-target "
                         "from the heartbeat latency windows the "
                         "history plane logs (0/empty = queue-depth "
                         "mode); a tenant CSV like gold:200,silver:800 "
                         "sets PER-TENANT targets and the gang scales "
                         "on the worst tenant's p99 (needs the replicas "
                         "publishing per-tenant windows via --tenants)")
    sv.add_argument("--spec_k", type=int, default=0,
                    help="speculative decoding draft depth (0 = off; "
                         "k tokens drafted, verified in ONE target "
                         "forward — greedy outputs stay bitwise "
                         "identical)")
    sv.add_argument("--draft_model", help="registered draft model name "
                    "(omit for the self-drafting n-gram fallback)")
    sv.add_argument("--draft_model_kwargs",
                    help="JSON dict of draft model kwargs")
    sv.add_argument("--draft_ckpt_dir",
                    help="draft model checkpoint dir (default: the "
                         "target's --ckpt_dir)")
    sv.add_argument("--conf_file", help="tony.xml / JSON job config")
    sv.add_argument("--conf", action="append", metavar="KEY=VALUE")
    sv.add_argument("--name", help="application name")
    sv.add_argument("--workdir", help="client work dir")
    sv.add_argument("--am_host", default="127.0.0.1")
    sv.add_argument("--timeout", type=float, default=None)
    sv.add_argument("--quiet", action="store_true")
    sv.set_defaults(fn=cmd_serve)

    rt = sub.add_parser("route", help="run the fleet request router: "
                        "routes generate RPCs over the live replica set "
                        "by prefix-cache overlap and load")
    rt.add_argument("--am", required=True,
                    help="AM RPC address (host:port) to poll for the "
                         "live replica set")
    rt.add_argument("--port", type=int, default=0,
                    help="router RPC port (0 = any)")
    rt.add_argument("--block_size", type=int, default=16,
                    help="fleet KV block size (must match the replicas' "
                         "engine geometry — the chain keys are "
                         "block-aligned)")
    rt.add_argument("--cache_weight", type=float, default=4.0)
    rt.add_argument("--queue_weight", type=float, default=1.0)
    rt.add_argument("--p99_weight", type=float, default=0.5)
    rt.add_argument("--poll_s", type=float, default=2.0,
                    help="AM membership poll interval")
    rt.set_defaults(fn=cmd_route)

    h = sub.add_parser("history", help="list jobs or show one job's events")
    h.add_argument("action", choices=["list", "show", "serve", "bill"],
                   help="list all jobs / show one job / serve the web "
                        "portal / roll up a tenant's billed tokens")
    h.add_argument("app_id", nargs="?",
                   help="application id (for show) or tenant name (for "
                        "bill; omit to bill every tenant)")
    h.add_argument("--history", dest="history_dir",
                   help="history root dir (default: scan client workdir)")
    h.add_argument("--port", type=int, default=19885,
                   help="portal port (for serve)")
    h.add_argument("--bind", default="127.0.0.1",
                   help="portal bind address (default loopback; job configs "
                        "are exposed unauthenticated — widen deliberately)")
    h.add_argument("--json", action="store_true",
                   help="emit the billing rows as JSON (for bill)")
    h.add_argument("--csv", action="store_true",
                   help="emit the billing rows as CSV (for bill)")
    h.add_argument("--since", default=None, metavar="WHEN",
                   help="clip the billing window start: epoch seconds, "
                        "YYYY-MM-DD, or 'YYYY-MM-DD HH:MM:SS' (for bill)")
    h.add_argument("--until", default=None, metavar="WHEN",
                   help="clip the billing window end (same formats; "
                        "for bill)")
    h.set_defaults(fn=cmd_history)

    pb = sub.add_parser("publish", help="publish a committed checkpoint "
                        "step for serve fleets to hot-swap onto")
    pb.add_argument("ckpt_dir", help="checkpoint root (the train job's "
                    "tony.ckpt.dir)")
    pb.add_argument("--step", type=int, default=None,
                    help="committed step to publish (default: newest)")
    pb.add_argument("--note", default="",
                    help="free-form note recorded in the pointer")
    pb.set_defaults(fn=cmd_publish)

    ao = sub.add_parser("aot", help="AOT compile-cache maintenance")
    ao.add_argument("action", choices=["gc"],
                    help="gc: drop entries whose runtime fingerprint no "
                         "live config can produce")
    ao.add_argument("--cache", required=True, metavar="DIR",
                    help="AOT cache directory")
    ao.add_argument("--dry-run", dest="dry_run", action="store_true",
                    help="report what would be dropped, delete nothing")
    ao.set_defaults(fn=cmd_aot)

    n = sub.add_parser("notebook", help="run a notebook/command in one "
                       "container behind a TCP proxy")
    n.add_argument("--src_dir", help="source directory to stage")
    n.add_argument("--executes", required=True,
                   help="notebook/server command; it should bind $TB_PORT")
    n.add_argument("--conf_file", help="tony.xml / JSON job config")
    n.add_argument("--conf", action="append", metavar="KEY=VALUE")
    n.add_argument("--workdir", help="client work dir")
    n.add_argument("--port", type=int, default=0,
                   help="local proxy port (0 = ephemeral)")
    n.set_defaults(fn=cmd_notebook)

    a = sub.add_parser("azkaban", help="submit from an Azkaban-style "
                       ".job properties file")
    a.add_argument("job_file", help="java-properties job file "
                   "(tony.* keys pass through)")
    a.add_argument("--workdir", help="client work dir")
    a.add_argument("--timeout", type=float, default=None)
    a.set_defaults(fn=cmd_azkaban)

    pr = sub.add_parser("profile", help="capture a trace from every rank "
                        "of a running job into its history dir")
    pr.add_argument("app_id", help="application id of a RUNNING job")
    pr.add_argument("--workdir", help="client work dir (default ~/.tony-tpu/jobs)")
    pr.add_argument("--duration_ms", type=int, default=2000,
                    help="trace capture window per rank")
    pr.set_defaults(fn=cmd_profile)

    k = sub.add_parser("kill", help="kill a running job (yarn "
                       "application -kill analogue)")
    k.add_argument("app_id", help="application id of a RUNNING job")
    k.add_argument("--workdir", help="client work dir (default ~/.tony-tpu/jobs)")
    k.add_argument("--reason", help="recorded in the job's final message")
    k.set_defaults(fn=cmd_kill)

    rz = sub.add_parser("resize", help="elastically resize a running "
                        "job's training gang (drain -> commit -> "
                        "re-gang -> restore)")
    rz.add_argument("num_workers", type=int,
                    help="target worker count after the resize")
    rz.add_argument("app_id", help="application id of a RUNNING job")
    rz.add_argument("--workdir", help="client work dir (default ~/.tony-tpu/jobs)")
    rz.set_defaults(fn=cmd_resize)

    lg = sub.add_parser("logs", help="print per-container logs "
                        "(yarn logs analogue, local substrate)")
    lg.add_argument("app_id", help="application id")
    lg.add_argument("--workdir", help="client work dir (default ~/.tony-tpu/jobs)")
    lg.add_argument("--tail", type=int, default=0,
                    help="only the last N lines of each log (0 = all)")
    lg.set_defaults(fn=cmd_logs)

    from tony_tpu.analysis.cli import CONFIG_NAMES  # jax-free constants

    an = sub.add_parser("analyze", help="run the jaxpr sharding/"
                        "collective invariant analyzer over the shipped "
                        "train-step configs")
    an.add_argument("--config", default="all",
                    choices=("all",) + CONFIG_NAMES,
                    help="which canonical config to analyze "
                         "(default: all)")
    an.add_argument("--json", help="also write the full structured "
                    "reports to this path")
    an.add_argument("--signatures", help="directory of committed step-"
                    "signature pins to check against "
                    "(e.g. tests/signatures)")
    an.add_argument("--update-signatures", action="store_true",
                    help="rewrite the signature pins instead of checking "
                         "(commit the diff)")
    an.add_argument("--lint", action="store_true",
                    help="also run the jnp.concatenate/stack pack-site "
                         "source lint (make lint)")
    an.add_argument("--concurrency", action="store_true",
                    help="run the host-side concurrency plane instead "
                         "of the jaxpr configs: lock-discipline lint, "
                         "lock-order deadlock check (static + witness), "
                         "thread-hygiene audit — jax-free")
    an.set_defaults(fn=cmd_analyze)

    v = sub.add_parser("version", help="print version")
    v.set_defaults(fn=cmd_version)
    return p


def main(argv: Optional[List[str]] = None) -> int:
    args = make_parser().parse_args(argv)
    try:
        return args.fn(args)
    except BrokenPipeError:
        # stdout piped into a pager/head that exited; not an error.
        return 0
