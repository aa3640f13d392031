"""ApplicationMaster: the scheduler brain (layer L4).

Mirrors ``com.linkedin.tony.TonyApplicationMaster`` (upstream ``tony-core/src/
main/java/com/linkedin/tony/TonyApplicationMaster.java`` ≈1,500 LoC,
unverified — SURVEY.md §0, call stacks §3.1/§3.3). Responsibilities carried
over, re-mapped from YARN to the :mod:`tony_tpu.scheduler` substrate:

* translate per-jobtype config into container launches (gang allocation);
* serve the control-plane RPC (register / cluster-spec / heartbeat /
  result / metrics) to executors;
* the monitor loop: heartbeat-expiry → LOST, completed-container handling,
  preemption re-request (``tony.container.preemption.max-retries``), gang
  allocation timeout, application timeout;
* success policy via :class:`~tony_tpu.session.TonySession`;
* AM-attempt gang restart (``tony.am.retry-count``) — `jax.distributed` is
  unforgiving about world membership (SURVEY.md §7 hard part #1), so a retry
  tears down the WHOLE gang and relaunches with ``attempt_id + 1``;
* lifecycle event emission to the jhist log (SURVEY.md §3.5).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Dict, Optional

from tony_tpu import conf as conf_mod
from tony_tpu import constants
from tony_tpu.am import resize as resize_mod
from tony_tpu.conf import TonyConfig
from tony_tpu.events import EventHandler
from tony_tpu.rpc import ENV_JOB_TOKEN, ApplicationRpcHandler, RpcServer
from tony_tpu.scheduler import (Container, ContainerLaunch,
                                ContainerScheduler, LocalProcessScheduler)
from tony_tpu.session import JobStatus, TaskStatus, TonySession

AM_ADDRESS_FILE = "am.address"
AM_TOKEN_FILE = "am.token"
FINAL_STATUS_FILE = "final-status.json"
_TICK_S = 0.05


class ApplicationMaster:
    """One AM process/thread: owns the RPC server, the scheduler client, the
    session, and the monitor loop."""

    def __init__(self, conf: TonyConfig, app_id: str, job_dir: str | Path,
                 scheduler: Optional[ContainerScheduler] = None,
                 host: str = "127.0.0.1", quiet: bool = True):
        self.conf = conf
        self.app_id = app_id
        # Resolve: executors run with a different cwd, so every path shipped
        # to them (conf, src) must be absolute.
        self.job_dir = Path(job_dir).resolve()
        self.job_dir.mkdir(parents=True, exist_ok=True)
        if scheduler is None:
            # Config-selected backend (tpu-vm) or fall through to local.
            from tony_tpu.scheduler import scheduler_from_conf
            scheduler = scheduler_from_conf(conf, self.job_dir, host)
        if scheduler is None:
            # Local substrate: enforce chip asks against what this host
            # actually has (reference: GpuDiscoverer feeding the AM's
            # resource accounting) whenever any job type requests tpus.
            total_tpus = 0
            if any(conf.get_int(conf_mod.tpus_key(jt), 0) > 0
                   for jt in conf.job_types()):
                total_tpus = conf.get_int(conf_mod.SCHEDULER_TOTAL_TPUS, 0)
                if total_tpus <= 0:
                    from tony_tpu.discovery import discover_tpus
                    total_tpus = discover_tpus().num_chips
                if total_tpus <= 0:
                    # 0 would mean "unlimited" to the scheduler — the
                    # opposite of what an unsatisfiable ask deserves.
                    raise ValueError(
                        "tony.<jobtype>.tpus requested but no TPU chips "
                        "discovered on this host; set "
                        f"{conf_mod.SCHEDULER_TOTAL_TPUS} to override")
            scheduler = LocalProcessScheduler(
                self.job_dir, host=host, conf=conf, total_tpus=total_tpus)
        self.scheduler = scheduler
        self.host = host
        self.quiet = quiet
        self.token: Optional[str] = None
        self.credentials: Optional[Dict[str, str]] = None
        self.cred_provider = None
        if conf.get_bool(conf_mod.SECURITY_ENABLED, False):
            from tony_tpu import security
            self.cred_provider = security.provider_for(conf)
            # Client-staged credentials win (acquire-at-submit); acquiring
            # here covers AMs launched without a client (MiniPod/tests) and
            # keeps every hop working from the same map.
            self.credentials = security.read_credentials(self.job_dir)
            if self.credentials is None:
                self.credentials = self.cred_provider.acquire(
                    conf, self.job_dir)
                security.write_credentials(self.job_dir, self.credentials)
            self.token = self.credentials.get("token")
            if not self.token:
                # The pre-SPI behavior ALWAYS authenticated the RPC
                # surface when security was on; a provider that ships
                # only external credentials must not silently downgrade.
                raise ValueError(
                    f"{conf_mod.SECURITY_ENABLED} is true but credential "
                    f"provider {type(self.cred_provider).__name__} "
                    f"supplied no 'token' entry to authenticate RPC")
            # Back-compat surface older clients poll for.
            token_path = self.job_dir / AM_TOKEN_FILE
            token_path.write_text(self.token)
            token_path.chmod(0o600)
        from tony_tpu.runtime import get_framework
        self.framework = get_framework(
            conf.get(conf_mod.APPLICATION_FRAMEWORK, "jax"))
        self.session: Optional[TonySession] = None
        self.server: Optional[RpcServer] = None
        self.handler: Optional[ApplicationRpcHandler] = None
        self.events: Optional[EventHandler] = None
        self._containers: Dict[str, Container] = {}   # task_id -> live container
        self.final_status = JobStatus.FAILED
        self.final_message = ""
        self.history_dir: Optional[Path] = None       # set in run()
        self._stop_reason: Optional[str] = None       # set by request_stop
        # Elastic resize (tony_tpu.am.resize): one controller at a time,
        # ticked from the monitor loop; it survives across attempts (the
        # drain ends one attempt, re-gang/restore run in the next).
        self._resize: Optional[resize_mod.ResizeController] = None
        self._resize_count = 0
        self._resize_relaunch = False
        self._operator_resize: Optional[int] = None   # set from RPC thread

    def _log(self, msg: str) -> None:
        if not self.quiet:
            print(f"[tony-am {self.app_id}] {msg}", file=sys.stderr, flush=True)

    def _maybe_refresh_credentials(self) -> None:
        """Periodic provider renewal (reference: delegation-token renewal).
        Providers renew EXTERNAL credentials (ticket/cred files user code
        reads); the in-flight RPC token is job-lifetime — see
        tony_tpu.security. Interval 0 (default) disables the hook."""
        if self.cred_provider is None or self.credentials is None:
            return
        from tony_tpu import security
        interval_s = self.conf.get_int(
            security.CREDENTIAL_REFRESH_INTERVAL_MS, 0) / 1e3
        if interval_s <= 0:
            return
        now = time.monotonic()
        if now < getattr(self, "_next_cred_refresh", 0.0):
            return
        self._next_cred_refresh = now + interval_s
        try:
            renewed = self.cred_provider.refresh(
                self.conf, self.job_dir, dict(self.credentials))
        except Exception as e:  # noqa: BLE001 — provider is plugin code
            self._log(f"credential refresh failed (kept current): {e}")
            return
        if renewed is not None:
            self.credentials = renewed
            security.write_credentials(self.job_dir, renewed)
            self._log("credentials refreshed")

    def request_stop(self, reason: str) -> None:
        """Graceful external stop (SIGTERM from the client's kill fallback).
        Signal-handler safe: only sets a flag — no locks — and the monitor
        loop applies it (KILLED → normal teardown: containers reaped, events
        finalized, final status written)."""
        self._stop_reason = reason

    # -- container plumbing ------------------------------------------------
    def _launch_task(self, session: TonySession, job_type: str,
                     index: int) -> None:
        req = self.conf.container_request(job_type)
        env = {
            constants.ENV_JOB_NAME: job_type,
            constants.ENV_TASK_INDEX: str(index),
            constants.ENV_TASK_NUM: str(session.num_tasks()),
            # The REACHABLE address (matches the am.address file), not
            # RpcServer.address which maps a 0.0.0.0 bind to loopback and
            # would strand remote executors.
            constants.ENV_AM_ADDRESS: f"{self.host}:{self.server.port}",  # type: ignore[union-attr]
            constants.ENV_APP_ID: self.app_id,
            constants.ENV_ATTEMPT_ID: str(session.attempt_id),
            constants.ENV_CONF_PATH: str(self.job_dir / constants.TONY_JOB_JSON),
        }
        src = self.job_dir / "src"
        if src.is_dir():
            env[constants.ENV_SRC_DIR] = str(src)
        res = self.job_dir / "resources"
        if res.is_dir():
            env[constants.ENV_RESOURCES_DIR] = str(res)
        venv = self.conf.get(conf_mod.PYTHON_VENV)
        if venv and Path(venv).exists():
            # Resolve against the AM's cwd (= the client's, which wrote the
            # conf): executors run elsewhere and a relative path would
            # silently localize nothing.
            env[constants.ENV_VENV] = str(Path(venv).resolve())
        if self.credentials is not None and self.cred_provider is not None:
            # The provider decides what ships into containers (reference:
            # tokens packed into every ContainerLaunchContext).
            env.update(self.cred_provider.executor_env(self.credentials))
        elif self.token:
            env[ENV_JOB_TOKEN] = self.token
        container = self.scheduler.launch(ContainerLaunch(
            job_type=job_type, index=index, env=env,
            memory_mb=req.memory_mb, vcores=req.vcores, tpus=req.tpus))
        task = session.task(job_type, index)
        with session.lock:
            task.container_id = container.container_id
            if not task.status.is_terminal:
                task.status = TaskStatus.ALLOCATED
            task.touch()
        self._containers[task.task_id] = container
        self._log(f"launched {task.task_id} in {container.container_id}")

    def _try_launch(self, session: TonySession, job_type: str,
                    index: int) -> None:
        """Launch, converting substrate failures (unsatisfiable resource
        ask, staging error on the ssh substrate) into a task failure the
        success policy sees — not an AM crash (reference: the RM rejecting
        an ask surfaces as a failed container, never kills the AM)."""
        try:
            self._launch_task(session, job_type, index)
        except Exception as e:  # noqa: BLE001 — substrate errors vary
            self._log(f"launch of {job_type}:{index} failed: {e}")
            session.on_task_result(
                job_type, index, constants.EXIT_AM_ERROR,
                f"container launch failed: {e}")

    def _stop_task_containers(self, session: TonySession) -> None:
        for task in session.tasks():
            c = self._containers.get(task.task_id)
            if c is not None and c.is_running:
                self.scheduler.stop_container(c)

    # -- elastic resize ----------------------------------------------------
    def _resize_job_type(self) -> str:
        return self.conf.get(conf_mod.RESIZE_JOB_TYPE) or constants.WORKER

    def _resize_enabled(self, job_type: str) -> bool:
        return (self.conf.get_bool(conf_mod.RESIZE_ENABLED, False)
                and job_type == self._resize_job_type())

    def _resize_timeouts(self) -> resize_mod.ResizeTimeouts:
        return resize_mod.ResizeTimeouts(
            drain_s=self.conf.get_int(
                conf_mod.RESIZE_DRAIN_TIMEOUT_MS, 60000) / 1e3,
            regang_s=self.conf.get_int(
                conf_mod.RESIZE_REGANG_TIMEOUT_MS, 120000) / 1e3,
            restore_s=self.conf.get_int(
                conf_mod.RESIZE_RESTORE_TIMEOUT_MS, 120000) / 1e3)

    def _request_operator_resize(self, n: int) -> None:
        """RPC-thread half of ``tony resize N``: record the ask only —
        the monitor loop owns every session/scheduler mutation, so the
        RPC thread must not trigger the resize itself."""
        self._operator_resize = int(n)

    def _emit_resize_phase(self, spec: resize_mod.ResizeSpec,
                           phase: resize_mod.ResizePhase, wall_s: float,
                           ok: bool, detail: str) -> None:
        self._log(f"resize {phase.value}: "
                  f"{'done' if ok else 'FAILED'} in {wall_s:.2f}s"
                  + (f" ({detail})" if detail else ""))
        if self.events is not None:
            self.events.resize(phase.value, spec.trigger, spec.job_type,
                               spec.old_workers, spec.new_workers,
                               wall_s, ok, detail)

    def _regang_poll(self) -> bool:
        """RE-GANG completes when the NEW attempt's gang barrier seals
        (the draining attempt's session is excluded by its drain flag)."""
        s = self.session
        return (s is not None and not s.draining
                and self.handler is not None
                and self.handler._all_registered_fired
                and s.all_registered())

    def _restore_poll(self) -> bool:
        """RESTORING completes when every tracked task of the resized
        jobtype is RUNNING and heartbeating on the new topology (restore
        CORRECTNESS — element-identical stream, mesh-mapped params — is
        the ckpt/data planes' pinned contract, not re-checked here)."""
        s = self.session
        if s is None or s.draining:
            return False
        jt = self._resize_job_type()
        gang = [t for t in s.tasks() if t.job_type == jt and t.tracked]
        return bool(gang) and all(
            t.status == TaskStatus.RUNNING and t.last_heartbeat is not None
            for t in gang)

    def _trigger_resize(self, session: TonySession, trigger: str,
                        job_type: str, new_workers: int) -> bool:
        """Begin a resize (drain phase starts immediately). False means
        this churn must fall back to the pre-elastic recovery path
        (resize disabled, wrong jobtype, or the resize budget is spent);
        True with a resize already in flight folds the churn into it."""
        if not self._resize_enabled(job_type):
            return False
        if self._resize is not None and self._resize.active:
            return True
        max_resizes = self.conf.get_int(conf_mod.RESIZE_MAX_RESIZES, 8)
        if self._resize_count >= max_resizes:
            self._log(f"resize budget exhausted "
                      f"({self._resize_count}/{max_resizes}); "
                      f"falling back to gang restart")
            return False
        floor = max(1, self.conf.get_int(conf_mod.RESIZE_MIN_WORKERS, 1))
        target = max(int(new_workers), floor)
        spec = resize_mod.ResizeSpec(
            trigger=trigger, job_type=job_type,
            old_workers=self.conf.instances(job_type),
            new_workers=target)
        controller = resize_mod.ResizeController(
            poll={
                resize_mod.ResizePhase.DRAINING:
                    lambda: (self.session is not None
                             and self.session.drain_complete(job_type)),
                resize_mod.ResizePhase.REGANG: self._regang_poll,
                resize_mod.ResizePhase.RESTORING: self._restore_poll,
            },
            enter={resize_mod.ResizePhase.DRAINING: session.request_drain},
            timeouts=self._resize_timeouts(),
            on_phase=self._emit_resize_phase)
        self._resize = controller
        self._resize_count += 1
        self._log(f"resize #{self._resize_count} ({trigger}): "
                  f"{spec.old_workers} -> {target} {job_type}(s); draining")
        controller.start(spec)
        return True

    def _divert_to_resize(self, session: TonySession, task,
                          trigger: str, reason: str) -> bool:
        """Route one task's churn (preemption / lost heartbeat) into the
        resize machine instead of the same-index retry or the fail-fast
        LOST verdict. The churned task goes terminal WITHOUT failing the
        job (mark_scaled_down); survivors drain at the next heartbeat."""
        if not self._resize_enabled(task.job_type):
            return False
        if self._resize is None or not self._resize.active:
            live = [t for t in session.tasks()
                    if t.job_type == task.job_type and t.tracked
                    and not t.status.is_terminal and t is not task]
            if not self._trigger_resize(session, trigger, task.job_type,
                                        len(live)):
                return False
        session.mark_scaled_down(task, reason)
        c = self._containers.get(task.task_id)
        if c is not None and c.is_running:
            self.scheduler.stop_container(c)
        return True

    def _tick_resize(self, session: TonySession) -> None:
        """One monitor-loop observation of the in-flight resize. Ends the
        DRAINING attempt when the commit is durable (run() then re-gangs
        at the new size), and on a terminal verdict either celebrates or
        degrades the job to the full-gang-restart path."""
        c = self._resize
        if c is None or not c.active:
            return
        result = c.tick()
        if result is None:
            if session.draining \
                    and c.phase is not resize_mod.ResizePhase.DRAINING:
                # Drain committed: end this attempt so run() can apply
                # the new topology and relaunch (re-gang).
                self._resize_relaunch = True
            return
        self._resize = None
        spec = result.spec
        if result.ok:
            walls = ", ".join(f"{k} {v:.2f}s"
                              for k, v in result.phase_walls.items())
            self._log(f"resize complete: {spec.old_workers} -> "
                      f"{spec.new_workers} {spec.job_type}(s) ({walls})")
            return
        # Degrade: never a hang, never a torn checkpoint — the gang
        # restart's restore-from-last-commit owns recovery from here.
        self._log(f"resize degraded ({result.reason}); full gang restart")
        session.clear_drain()
        with session.lock:
            if session.job_status == JobStatus.RUNNING:
                session.job_status = JobStatus.FAILED
                session.final_message = f"resize degraded: {result.reason}"

    # -- monitor-loop checks ----------------------------------------------
    def _check_heartbeats(self, session: TonySession) -> None:
        interval_s = self.conf.get_int(
            conf_mod.TASK_HEARTBEAT_INTERVAL_MS, 1000) / 1e3
        max_missed = self.conf.get_int(conf_mod.TASK_MAX_MISSED_HEARTBEATS, 25)
        expiry = interval_s * max_missed
        now = time.monotonic()
        # Before the gang barrier, non-registration is the gang timeout's
        # job; after it, a relaunched (preempted) executor that freezes
        # before registering has no other watchdog, so ALLOCATED tasks are
        # covered too (touch() at launch seeds their clock).
        barrier_passed = (self.handler is not None
                          and self.handler._all_registered_fired)
        watched = (TaskStatus.REGISTERED, TaskStatus.RUNNING) if not \
            barrier_passed else (TaskStatus.ALLOCATED, TaskStatus.REGISTERED,
                                 TaskStatus.RUNNING)
        for task in session.tasks():
            if task.status in watched \
                    and task.last_heartbeat \
                    and now - task.last_heartbeat > expiry:
                if self._divert_to_resize(
                        session, task, "lost",
                        f"missed {max_missed} heartbeats; "
                        f"elastic resize in place of LOST"):
                    self._log(f"task {task.task_id} missed {max_missed} "
                              f"heartbeats -> elastic resize")
                    continue
                self._log(f"task {task.task_id} missed {max_missed} "
                          f"heartbeats -> LOST")
                session.on_task_lost(
                    task, f"missed {max_missed} heartbeats "
                          f"({expiry:.1f}s without contact)")
                c = self._containers.get(task.task_id)
                if c is not None and c.is_running:
                    self.scheduler.stop_container(c)

    def _handle_completed_containers(self, session: TonySession) -> None:
        max_preempt = self.conf.get_int(conf_mod.PREEMPTION_MAX_RETRIES, 3)
        for c in self.scheduler.poll_completed():
            task = session.task_by_container(c.container_id)
            if task is None:
                continue
            live = self._containers.get(task.task_id)
            if live is not None and live.container_id == c.container_id:
                del self._containers[task.task_id]
            if task.status.is_terminal:
                continue
            if c.exit_code == constants.EXIT_PREEMPTED:
                if self._divert_to_resize(
                        session, task, "preempted",
                        "preempted; elastic resize in place of retry"):
                    self._log(f"{task.task_id} preempted -> elastic resize")
                    continue
                task.preemption_retries += 1
                if task.preemption_retries <= max_preempt:
                    self._log(f"{task.task_id} preempted "
                              f"(retry {task.preemption_retries}/{max_preempt})"
                              f" -> re-requesting container")
                    with session.lock:
                        task.host = task.port = None
                        task.status = TaskStatus.REQUESTED
                    self._try_launch(session, task.job_type, task.index)
                else:
                    session.on_task_result(
                        task.job_type, task.index, constants.EXIT_PREEMPTED,
                        f"preempted {task.preemption_retries} times "
                        f"(max {max_preempt})")
            else:
                # Executor died without a result RPC (crash, OOM-kill).
                session.on_task_result(
                    task.job_type, task.index,
                    c.exit_code if c.exit_code else constants.EXIT_FAILURE,
                    f"executor exited with {c.exit_code} without reporting")

    def _log_history_events(self, session: TonySession) -> None:
        """Append each task's latest stats-file window to the jhist log
        (tony_tpu.events SERVE_WINDOW / TRAIN_STEP) — the history
        plane's ONLY collection hook: the payload is the task's already-
        normalized heartbeat dict verbatim (no second bookkeeping path),
        de-duplicated per task so an idle tick appends nothing. A dict
        carrying a train step counter (the train stats writer's schema)
        logs as TRAIN_STEP; everything else is a serve window."""
        if self.events is None:
            return
        if not hasattr(self, "_history_window_sig"):
            self._history_window_sig: Dict[str, str] = {}
        for t in session.tasks():
            m = t.serve_metrics
            if not m or t.status.is_terminal:
                continue
            sig = json.dumps(m, sort_keys=True, default=str)
            if self._history_window_sig.get(t.task_id) == sig:
                continue
            self._history_window_sig[t.task_id] = sig
            if "step" in m and "qps" not in m:
                self.events.train_step(
                    t.job_type, t.index, step=int(m.get("step", 0)),
                    step_time_s=float(m.get("step_time_s", 0.0)),
                    collective_bytes=float(m.get("collective_bytes",
                                                 0.0)),
                    mfu=float(m.get("mfu", 0.0)))
            else:
                self.events.serve_window(t.job_type, t.index, m)

    def _autoscale_serve(self, session: TonySession) -> None:
        """Heartbeat-driven replica scaling for every serving jobtype
        (tony_tpu.serve): feed the replicas' piggybacked qps/p99/queue-
        depth into the pure :func:`tony_tpu.serve.scaling.decide` policy
        and apply the delta — launch an ELASTIC task on scale-up, retire
        the newest elastic replica on scale-down (the conf-declared
        floor is untouchable). Autoscale is off unless the conf raises
        ``tony.serve.replicas.max`` above the static instance count.
        Only runs after the gang barrier: the initial gang must seal its
        cluster spec before membership gets elastic.

        Per-JOBTYPE since the disaggregated split (the first
        heterogeneous-gang consumer): a job's prefill and decode gangs
        are separate serve-role jobtypes, each with its own policy
        instance (floor = its own conf instance count), cooldown clock,
        and samples — a prefill burst scales the prefill gang, the
        decode floor stays put."""
        if self.handler is None or not self.handler._all_registered_fired:
            return
        serve_jts = session.serve_job_types()
        if not serve_jts:
            return
        from tony_tpu.serve import scaling    # jax-free

        if not hasattr(self, "_serve_policy"):
            self._serve_policy: Dict[str, object] = {}
            self._serve_scale_last: Dict[str, Optional[float]] = {}
        for jt in serve_jts:
            if jt not in self._serve_policy:
                # job_type + fleet_floors: on a split fleet the global
                # replicas.max is a FLEET ceiling apportioned across
                # the gangs (scaling.apportion_fleet_max), overridable
                # per gang via tony.serve.replicas.max.<jobtype>.
                self._serve_policy[jt] = scaling.ScalingPolicy.from_conf(
                    self.conf, self.conf.instances(jt), job_type=jt,
                    fleet_floors={j: self.conf.instances(j)
                                  for j in serve_jts})
                self._serve_scale_last[jt] = None
            policy = self._serve_policy[jt]
            # Partition the live gang: warm STANDBYS (heartbeating
            # warm_standby — the cold-start plane's compiled-and-idle
            # pool, tony_tpu.ckpt.aot) are held capacity, not serving
            # replicas. The load policy sees ONLY the active set; the
            # pool has its own target (decide_warm) below.
            live = [t for t in session.tasks()
                    if t.job_type == jt and not t.status.is_terminal]
            warm = [t for t in live
                    if t.serve_metrics.get("warm_standby")]
            active = [t for t in live
                      if not t.serve_metrics.get("warm_standby")]
            # Floor REPAIR runs even when autoscale is off: `tony serve`
            # disables fail-fast on the promise that a crashed replica
            # gets replaced, so below-floor recovery must not hide
            # behind the max>min autoscale arming.
            warm_target = self._serve_warm_target(jt)
            if not policy.enabled and len(active) >= policy.min_replicas \
                    and warm_target <= 0:
                continue
            now = time.monotonic()
            samples = [s for s in session.serve_samples(jt)
                       if not s.get("warm_standby")]
            delta = scaling.decide(policy, len(active), samples, now=now,
                                   last_action=self._serve_scale_last[jt])
            if delta and self.events is not None:
                # The SELF-VERIFYING record (before the applied action
                # updates the cooldown clock): decide()'s complete input
                # next to the delta, so scaling.replay_decisions over
                # the finished log reproduces this exact verdict.
                self.events.scale_decision(
                    jt, delta, len(active), samples, now,
                    self._serve_scale_last[jt],
                    dataclasses.asdict(policy))
            if delta > 0:
                # The grant names the prefix store (when conf declares
                # one): the fresh replica warms its prefix tier from
                # disk instead of recomputing hot stems, so a scale-up
                # replica is useful from its first request.
                store = self.conf.get(
                    conf_mod.SERVE_PREFIX_STORE, "") or ""
                store_note = f", prefix store {store}" if store else ""
                for _ in range(delta):
                    # A warm standby PROMOTES in place of a cold grant:
                    # one RPC flips it active — executables and prefix
                    # stems already hot. Cold launch is the fallback
                    # (no pool, or the promote RPC failed).
                    if warm and self._promote_standby(jt, warm, active):
                        continue
                    task = session.add_task(jt)
                    self._log(f"serve scale-up -> launching elastic "
                              f"replica {task.task_id} "
                              f"({len(active) + 1} active{store_note})")
                    self._try_launch(session, jt, task.index)
                self._serve_scale_last[jt] = now
            elif delta < 0:
                victims = sorted((t for t in active if t.elastic),
                                 key=lambda t: t.index, reverse=True)
                if victims:
                    victim = victims[0]
                    self._log(f"serve scale-down -> retiring elastic "
                              f"replica {victim.task_id} "
                              f"({len(active) - 1} active)")
                    session.mark_scaled_down(
                        victim, "replica scale-down (load below floor)")
                    c = self._containers.get(victim.task_id)
                    if c is not None and c.is_running:
                        self.scheduler.stop_container(c)
                    self._serve_scale_last[jt] = now
            # Warm-pool backfill AFTER the load verdict applied: grants
            # above the configured instance count self-identify as
            # standbys (replica.main), so a backfill launch comes up
            # compiled-and-idle; over-target pools (ceiling shrank, or
            # a promotion left a retiring active) drain newest-first.
            warm_delta = scaling.decide_warm(
                policy, warm_target, len(active), len(warm))
            if warm_delta > 0:
                for _ in range(warm_delta):
                    task = session.add_task(jt)
                    self._log(f"serve warm-pool -> launching standby "
                              f"replica {task.task_id} "
                              f"({len(warm) + 1}/{warm_target} warm)")
                    self._try_launch(session, jt, task.index)
            elif warm_delta < 0:
                pool = sorted((t for t in warm if t.elastic),
                              key=lambda t: t.index, reverse=True)
                for victim in pool[:-warm_delta]:
                    self._log(f"serve warm-pool -> retiring standby "
                              f"replica {victim.task_id}")
                    session.mark_scaled_down(
                        victim, "warm-standby pool over target")
                    c = self._containers.get(victim.task_id)
                    if c is not None and c.is_running:
                        self.scheduler.stop_container(c)

    def _serve_warm_target(self, job_type: str) -> int:
        """Configured warm-standby pool size for one serve jobtype —
        the per-gang ``tony.serve.warm-standby.<jobtype>`` override,
        else the global key, else 0 (pool off)."""
        v = self.conf.get(conf_mod.serve_warm_standby_key(job_type))
        if v is None:
            v = self.conf.get(conf_mod.SERVE_WARM_STANDBY)
        try:
            return int(v or 0)
        except (TypeError, ValueError):
            return 0

    def _promote_standby(self, job_type: str, warm: list,
                         active: list) -> bool:
        """Flip one warm standby active over its promote RPC (oldest
        first — it has donated stems longest). On success the task
        moves from ``warm`` to ``active`` in place so a multi-step
        delta keeps promoting; on RPC failure the standby stays pooled
        (its next heartbeat still says warm) and the caller falls back
        to a cold grant."""
        from tony_tpu.rpc import RpcClient, RpcError

        task = sorted(warm, key=lambda t: t.index)[0]
        port = task.serve_metrics.get("rpc_port")
        if not task.host or not port:
            return False
        try:
            with RpcClient(f"{task.host}:{int(port)}",
                           timeout=5.0) as client:
                client.call("promote")
        except (OSError, ValueError, RpcError) as e:
            self._log(f"serve scale-up -> promote RPC to "
                      f"{task.task_id} failed ({e}); cold-granting")
            return False
        # Reflect the promotion NOW (the replica republished stats, but
        # that lands on the next heartbeat): the session's view flips
        # with it so serve_endpoints routes the promoted replica this
        # tick.
        task.serve_metrics = dict(task.serve_metrics,
                                  warm_standby=0.0)
        warm.remove(task)
        active.append(task)
        self._log(f"serve scale-up -> promoted warm standby "
                  f"{task.task_id} ({len(active)} active, "
                  f"{len(warm)} warm)")
        return True

    def _tick_publication(self, session: TonySession) -> None:
        """Continuous weight publication (tony_tpu.publish /
        serve.swap): watch for a new published manifest and roll the
        serve fleet onto it, ONE replica at a time.

        Target discovery is two-source: the train gang's heartbeats
        carry the publication they staged (``task.published`` — the
        colocated train+serve job needs no extra wiring), and a
        ``tony.publish.follow`` job additionally polls the pointer file
        directly (throttled to ~1s — a follower fleet has no train
        tasks to hear it from). A new target emits ONE PUBLISH event
        and arms the :class:`~tony_tpu.serve.swap.FleetSwapController`;
        each tick then asks the controller who (if anyone) to swap —
        warm standbys first, then actives by index — down-marks that
        replica in place (``swapping=1.0``, the `_promote_standby`
        idiom, so serve_endpoints carries the retire signal THIS tick)
        and fires the ``swap`` RPC on a named daemon thread: the
        monitor loop never blocks on a restore. Each attempt's outcome
        lands as one SWAP event; a failure cools the controller down
        before the next try, and a wedged RPC is reaped at the
        configured timeout."""
        if self.handler is None or not self.handler._all_registered_fired:
            return
        serve_jts = session.serve_job_types()
        if not serve_jts:
            return
        from tony_tpu.serve.swap import FleetSwapController

        if not hasattr(self, "_swap_ctl"):
            self._swap_ctl = FleetSwapController(
                timeout_s=self.conf.get_int(
                    conf_mod.PUBLISH_SWAP_TIMEOUT_MS, 120000) / 1e3)
            self._pub_poll_t = 0.0
        ctl = self._swap_ctl
        best: Optional[tuple] = None
        for t in session.tasks():
            pub = getattr(t, "published", None)
            if pub and (best is None or pub["version"] > best[0]):
                best = (pub["version"], pub["step"])
        if self.conf.get_bool(conf_mod.PUBLISH_FOLLOW, False):
            now = time.monotonic()
            if now - self._pub_poll_t >= 1.0:
                self._pub_poll_t = now
                ckpt_dir = (self.conf.get(conf_mod.SERVE_CKPT_DIR)
                            or self.conf.get(conf_mod.CKPT_DIR))
                if ckpt_dir:
                    from tony_tpu.publish import latest_publication
                    rec = latest_publication(ckpt_dir)
                    if rec and (best is None or rec["version"] > best[0]):
                        best = (rec["version"], rec["step"])
        if best is not None and ctl.set_target(*best):
            self._log(f"publication v{best[0]} (step {best[1]}) -> "
                      f"rolling fleet swap")
            if self.events is not None:
                self.events.publish(best[0], best[1])
        if ctl.target is None:
            return
        wedged = ctl.check_timeout()
        if wedged is not None:
            self._log(f"swap of {wedged[0]}:{wedged[1]} timed out after "
                      f"{ctl.timeout_s:.0f}s")
            if self.events is not None:
                self.events.swap(wedged[0], wedged[1], 0, ctl.target[0],
                                 ctl.target[1], ctl.timeout_s, False,
                                 "swap RPC timed out")
        fleet = []
        by_id: Dict[tuple, object] = {}
        for t in session.tasks():
            m = t.serve_metrics
            if t.job_type not in serve_jts or t.status.is_terminal \
                    or not t.host or not m.get("rpc_port"):
                continue
            rid = (t.job_type, t.index)
            by_id[rid] = t
            fleet.append({"id": rid,
                          "version": int(m.get("weight_version", 0) or 0),
                          "standby": bool(m.get("warm_standby")),
                          "index": t.index})
        rid = ctl.next_replica(fleet)
        if rid is None:
            return
        task = by_id[rid]
        to_version, to_step = ctl.target
        from_version = int(task.serve_metrics.get("weight_version", 0)
                           or 0)
        addr = f"{task.host}:{int(task.serve_metrics['rpc_port'])}"
        # Down-mark in place: the router's next endpoints poll retires
        # this replica for the window; the replica's own post-swap
        # stats republish (swapping back to 0) revives it.
        task.serve_metrics = dict(task.serve_metrics, swapping=1.0)
        ctl.begin(rid)
        self._log(f"swap {task.task_id} v{from_version} -> v{to_version} "
                  f"(step {to_step})")

        def attempt() -> None:
            from tony_tpu.rpc import RpcClient, RpcError

            t0 = time.monotonic()
            ok, detail = True, ""
            try:
                with RpcClient(addr, timeout=ctl.timeout_s) as client:
                    client.call("swap", version=to_version, step=to_step)
            except (OSError, ValueError, RpcError) as e:
                ok, detail = False, str(e)
            ctl.finish(rid, ok)
            if self.events is not None:
                self.events.swap(rid[0], rid[1], from_version, to_version,
                                 to_step, time.monotonic() - t0, ok,
                                 detail)
            self._log(f"swap {task.task_id} -> v{to_version} "
                      + ("ok" if ok else f"FAILED ({detail})"))

        threading.Thread(target=attempt, daemon=True,
                         name=f"tony-swap-{task.task_id}").start()

    def _collect_traces_later(self, session: TonySession,
                              delay_s: float) -> None:
        """Wait for the executors' profiler endpoints to arrive (they're
        pushed after user-process launch, i.e. after the gang barrier),
        let the workload settle for ``delay_s``, then capture one trace
        per rank into ``<history>/traces/<app_id>/``."""
        from tony_tpu import profiler

        deadline = time.monotonic() + 120.0
        endpoints: Dict[str, str] = {}
        while time.monotonic() < deadline and not session.is_done():
            endpoints = profiler.endpoints_from_callback_info(
                session.task_callback_info)
            if endpoints:
                break
            time.sleep(0.25)
        if not endpoints:
            self._log("trace collection: no profiler endpoints appeared")
            return
        time.sleep(delay_s)
        if session.is_done():
            return
        # Re-read after the settle sleep: ranks whose executors pushed
        # their endpoint later than the first one (slow import, another
        # host) must not be excluded from the synchronized session.
        endpoints = profiler.endpoints_from_callback_info(
            session.task_callback_info) or endpoints
        duration_ms = self.conf.get_int(
            "tony.task.profiler.collect-duration-ms", 2000)
        assert self.history_dir is not None
        profiler.collect_traces(
            endpoints, self.history_dir, self.app_id,
            duration_ms=duration_ms,
            log=lambda *a, **k: self._log(" ".join(str(x) for x in a)))

    # -- one attempt -------------------------------------------------------
    def run_attempt(self, attempt_id: int) -> JobStatus:
        conf = self.conf
        session = TonySession(conf, self.app_id, attempt_id=attempt_id)
        self.session = session
        am_adapter = self.framework.am_adapter()
        am_adapter.validate_and_update_config(conf)
        am_adapter.set_session(session)
        if self.handler is None:
            self.handler = ApplicationRpcHandler(session)
        else:
            self.handler.reset(session)
        handler = self.handler

        def on_all_registered() -> None:
            am_adapter.on_all_registered()
            handler.callback_info.update(am_adapter.callback_info())
            # submit → all-RUNNING latency (BASELINE.md secondary metric):
            # the client ships its submit wall-clock in TONY_SUBMIT_TS.
            latency = None
            submit_ts = os.environ.get(constants.ENV_SUBMIT_TS)
            if submit_ts:
                try:
                    latency = time.time() - float(submit_ts)
                except ValueError:
                    pass
            session.all_running_latency_s = latency
            self._log("gang barrier passed: all tasks registered"
                      + (f" ({latency:.2f}s after submit)" if latency else ""))
            if self.events is not None:
                self.events.all_running(session.attempt_id, latency)
            # AM-side automatic trace collection (SURVEY.md §5.1): one
            # capture from every rank's profiler endpoint, N seconds after
            # the endpoints appear, into the history dir next to the jhist.
            collect_after = conf.get("tony.task.profiler.collect-after-s")
            if collect_after is not None and self.history_dir is not None:
                threading.Thread(
                    target=self._collect_traces_later,
                    args=(session, float(collect_after)),
                    daemon=True, name="trace-collect").start()

        handler.on_all_registered = on_all_registered
        handler.on_callback_info = am_adapter.receive_task_callback_info
        if conf.get_bool(conf_mod.RESIZE_ENABLED, False):
            handler.on_resize = self._request_operator_resize
        if self.events is not None:
            handler.on_registered = (
                lambda jt, i: self.events.task_started(
                    jt, i, session.task(jt, i).host or ""))
            handler.on_metrics = (
                lambda jt, i, m: self.events.task_metrics(jt, i, m))
        if self.server is None:
            self.server = RpcServer(handler, host="0.0.0.0",
                                    token=self.token).start()
            # Advertise the reachable address, not the bind-all one.
            (self.job_dir / AM_ADDRESS_FILE).write_text(
                f"{self.host}:{self.server.port}")
        if self.events is not None:
            self.events.application_inited(attempt_id, session.num_tasks())

        self._containers.clear()
        start = time.monotonic()
        gang_timeout_s = conf.get_int(conf_mod.AM_GANG_TIMEOUT_MS, 120000) / 1e3
        app_timeout_s = conf.get_int(conf_mod.APPLICATION_TIMEOUT, 0) / 1e3
        pending = [(jt, i) for jt in conf.job_types()
                   for i in range(conf.instances(jt))]
        launch_pool = ThreadPoolExecutor(
            max_workers=8, thread_name_prefix="launch")
        try:
            while True:
                # Launch whatever the adapter allows (Horovod gates workers
                # on its driver being up — ``canStartTask``). Launches run
                # CONCURRENTLY: on the ssh substrate each launch pays
                # staging + connection latency, and a serial loop makes the
                # submit→all-running latency O(gang size) (SURVEY.md §7
                # hard part #4). The pool is joined before the tick
                # continues so completed-container/heartbeat checks never
                # race a half-launched task.
                still_pending = []
                launching = []
                for jt, i in pending:
                    if am_adapter.can_start_task(jt, i):
                        launching.append(launch_pool.submit(
                            self._try_launch, session, jt, i))
                    else:
                        still_pending.append((jt, i))
                for f in launching:
                    f.result()
                pending = still_pending

                self._handle_completed_containers(session)
                self._check_heartbeats(session)
                if self._operator_resize is not None:
                    n, self._operator_resize = self._operator_resize, None
                    jt = self._resize_job_type()
                    if not self._trigger_resize(session, "operator", jt, n):
                        self._log(f"operator resize to {n} refused")
                self._tick_resize(session)
                self._log_history_events(session)
                self._autoscale_serve(session)
                self._tick_publication(session)
                self._maybe_refresh_credentials()

                if self._stop_reason is not None:
                    with session.lock:
                        if session.job_status == JobStatus.RUNNING:
                            session.job_status = JobStatus.KILLED
                            session.final_message = self._stop_reason

                # Gang timeout applies only before the first barrier pass —
                # a preemption relaunch transiently un-registers one task and
                # must not trip it.
                if not handler._all_registered_fired and \
                        time.monotonic() - start > gang_timeout_s:
                    with session.lock:
                        for t in session.tasks():
                            if t.spec is None and not t.status.is_terminal:
                                session.on_task_lost(
                                    t, f"not registered within gang timeout "
                                       f"({gang_timeout_s:.0f}s)")
                        if session.job_status == JobStatus.RUNNING:
                            session.job_status = JobStatus.FAILED
                            session.final_message = "gang allocation timed out"
                if app_timeout_s and time.monotonic() - start > app_timeout_s:
                    with session.lock:
                        if session.job_status == JobStatus.RUNNING:
                            session.job_status = JobStatus.FAILED
                            session.final_message = (
                                f"application exceeded "
                                f"tony.application.timeout-ms")
                if session.is_done():
                    break
                if self._resize_relaunch:
                    # Drained gang committed; the attempt ends here and
                    # run() relaunches at the new size (normal teardown
                    # below reaps the already-exited containers).
                    break
                time.sleep(_TICK_S)
        finally:
            launch_pool.shutdown(wait=True)
            # Teardown: untracked sidecars and any stragglers die with the job.
            session.kill_remaining(
                f"job finished: {session.job_status.value}")
            self._stop_task_containers(session)
            self.scheduler.poll_completed()
            am_adapter.stop()
            if self.events is not None:
                for t in session.tasks():
                    self.events.task_finished(
                        t.job_type, t.index, t.status.value, t.exit_code,
                        t.diagnostics, t.metrics)
                    if t.timeline:
                        self.events.task_timeline(t.job_type, t.index,
                                                  t.timeline)
        # Checkpoint plane: what the executors reported committed this
        # attempt (heartbeat piggyback) — the step the NEXT attempt's
        # restore_on_start will resume from after a gang restart.
        ckpt_step = session.last_committed_step()
        self._log(f"attempt {attempt_id}: {session.job_status.value} "
                  f"- {session.final_message}"
                  + (f" (last committed ckpt step: {ckpt_step})"
                     if ckpt_step is not None else ""))
        return session.job_status

    # -- whole application -------------------------------------------------
    def run(self) -> int:
        conf = self.conf
        conf.validate()
        conf.save(self.job_dir / constants.TONY_JOB_JSON)
        history = conf.get(conf_mod.HISTORY_LOCATION) or str(
            self.job_dir / "history")
        self.history_dir = Path(history)
        self.events = EventHandler(
            history, self.app_id,
            conf_snapshot=dict(conf.items()),
            app_name=conf.get(conf_mod.APPLICATION_NAME, ""))
        retries = conf.get_int(conf_mod.AM_RETRY_COUNT, 0)
        status = JobStatus.FAILED
        try:
            attempt = 1
            retries_used = 0
            while True:
                status = self.run_attempt(attempt)
                if self._resize_relaunch and self._resize is not None \
                        and self._resize.active:
                    # Elastic re-gang: the drained gang's commit is
                    # durable, so apply the new topology and relaunch —
                    # WITHOUT consuming the gang-restart retry budget
                    # (resizes have their own: tony.resize.max-resizes).
                    self._resize_relaunch = False
                    spec = self._resize.spec
                    conf.set(conf_mod.instances_key(spec.job_type),
                             str(spec.new_workers))
                    conf.save(self.job_dir / constants.TONY_JOB_JSON)
                    ckpt_step = (self.session.last_committed_step()
                                 if self.session else None)
                    self._log(
                        f"resize re-gang: relaunching "
                        f"{spec.new_workers} {spec.job_type}(s)"
                        + (f"; resuming from committed ckpt step "
                           f"{ckpt_step}" if ckpt_step is not None
                           else ""))
                    attempt += 1
                    continue
                if status in (JobStatus.SUCCEEDED, JobStatus.KILLED):
                    break
                if retries_used < retries:
                    retries_used += 1
                    ckpt_step = (self.session.last_committed_step()
                                 if self.session else None)
                    self._log(
                        f"attempt {attempt} failed; gang restart "
                        f"({retries_used}/{retries} retries used)"
                        + (f"; resuming from committed ckpt step "
                           f"{ckpt_step}" if ckpt_step is not None
                           else ""))
                    attempt += 1
                    continue
                break
        finally:
            if self._resize is not None and self._resize.active:
                # A terminal AM must never leave a phase dangling — the
                # degrade verdict (and its RESIZE record) lands before
                # the event log closes.
                self._resize.abandon("application finished")
                self._resize = None
            self.final_status = status
            self.final_message = (self.session.final_message
                                  if self.session else "")
            self.events.application_finished(status.value, self.final_message)
            self.events.close()
            (self.job_dir / FINAL_STATUS_FILE).write_text(
                json.dumps({
                    "status": status.value,
                    "message": self.final_message,
                    "app_id": self.app_id,
                    # Terminal task snapshot so the client can report final
                    # transitions even after the RPC server is gone.
                    "task_infos": (self.session.task_infos()
                                   if self.session else []),
                }))
            self.scheduler.stop()
            if self.server is not None:
                # Give the client one last poll window before the socket dies.
                time.sleep(0.1)
                self.server.stop()
        return (constants.EXIT_SUCCESS if status == JobStatus.SUCCEEDED
                else constants.EXIT_FAILURE)
