"""Container scheduling substrate: the YARN-RM/NM replacement (layer L0).

The reference delegates this layer entirely to Hadoop YARN (SURVEY.md §1 L0);
the AM asks the RM for containers sized ``{memory, vcores, gpus}`` and the NM
launches ``TaskExecutor`` JVMs. Here the same two verbs — allocate/launch and
reap — sit behind :class:`ContainerScheduler`, with two backends:

* :class:`LocalProcessScheduler` — containers are local subprocesses running
  ``python -m tony_tpu.executor``. This is both the MiniPod test substrate
  (the MiniYARNCluster analogue, SURVEY.md §4) and the single-host
  production path on one TPU-VM.
* :class:`TpuVmScheduler` — the multi-host pod-slice backend: same interface,
  launches executors on remote TPU-VM workers (one per host) over SSH.
  Resource semantics follow the ``yarn.io/tpu`` resource-type model from the
  north star: a request carries ``tpus`` and the scheduler places tasks so
  chip assignments never overlap (the JAXRuntime then pins
  ``TPU_VISIBLE_CHIPS`` per task).

Preemption is a first-class verb (``preempt``) because the reference's
failure machinery distinguishes preempted containers (re-request) from
crashed ones (fail-fast) — SURVEY.md §3.3.
"""

from __future__ import annotations

import os
import shlex
import signal
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence

from tony_tpu import constants
from tony_tpu import conf as conf_mod
from tony_tpu.util import child_pythonpath


@dataclass
class ContainerLaunch:
    """One container ask: which task, with what env (reference: the
    ``ContainerLaunchContext`` the AM builds per matched allocation)."""
    job_type: str
    index: int
    env: Dict[str, str]
    memory_mb: int = 1024
    vcores: int = 1
    tpus: int = 0


@dataclass
class Container:
    """A granted container and its lifecycle (reference: YARN ``Container`` +
    completion status)."""
    container_id: str
    job_type: str
    index: int
    host: str
    exit_code: Optional[int] = None
    preempted: bool = False
    _proc: Optional[subprocess.Popen] = field(default=None, repr=False)

    @property
    def is_running(self) -> bool:
        return self.exit_code is None


class ContainerScheduler:
    """Substrate SPI: allocate-and-launch, reap, kill, preempt."""

    def launch(self, launch: ContainerLaunch) -> Container:
        raise NotImplementedError

    def poll_completed(self) -> List[Container]:
        """Containers that exited since the last poll (reference:
        ``onContainersCompleted``)."""
        raise NotImplementedError

    def stop_container(self, container: Container) -> None:
        raise NotImplementedError

    def preempt(self, container_id: str) -> bool:
        """Simulate/execute a scheduler preemption: the container dies and is
        reported with ``preempted=True`` (reference: YARN exit status
        ``PREEMPTED``). Returns False if the container is not running."""
        raise NotImplementedError

    def stop(self, drain_s: float = 5.0) -> None:
        """Tear down everything still running, then drain completions."""
        for c in self._live_containers():
            self.stop_container(c)
        deadline = time.monotonic() + drain_s
        while self._live_containers() and time.monotonic() < deadline:
            self.poll_completed()
            time.sleep(0.05)

    def _live_containers(self) -> List["Container"]:
        raise NotImplementedError


class LocalProcessScheduler(ContainerScheduler):
    """Containers as local subprocesses (MiniYARNCluster analogue).

    Each container gets a working directory ``<job_dir>/containers/<cid>``
    and its executor stdout/stderr tee into ``executor.log`` there. Resource
    numbers (memory/vcores) are recorded, not enforced — exactly like
    MiniYARNCluster's default; ``tpus`` asks are validated against
    ``total_tpus`` so over-subscription fails at launch, mirroring an RM
    rejecting an unsatisfiable resource ask.
    """

    def __init__(self, job_dir: str | Path, host: str = "127.0.0.1",
                 total_tpus: int = 0, conf=None):
        self.job_dir = Path(job_dir)
        self.host = host
        self.conf = conf                      # for docker command wrapping
        self.total_tpus = total_tpus          # 0 = unlimited (no TPU asks)
        self._tpus_in_use = 0
        self._lock = threading.Lock()
        self._running: Dict[str, Container] = {}
        self._next_id = 0

    def _new_cid(self) -> str:
        with self._lock:
            self._next_id += 1
            return f"container_{os.getpid()}_{self._next_id:04d}"

    def launch(self, launch: ContainerLaunch) -> Container:
        if self.total_tpus and launch.tpus:
            with self._lock:
                if self._tpus_in_use + launch.tpus > self.total_tpus:
                    raise RuntimeError(
                        f"unsatisfiable tpu ask: {launch.tpus} requested, "
                        f"{self.total_tpus - self._tpus_in_use} free")
                self._tpus_in_use += launch.tpus
        cid = self._new_cid()
        workdir = self.job_dir / "containers" / cid
        workdir.mkdir(parents=True, exist_ok=True)
        log = open(workdir / constants.EXECUTOR_LOG_NAME, "ab")
        # Curated task env (the YARN launch-context analogue): what the
        # executor needs, distinct from the host environ it also inherits
        # when running un-dockerized.
        task_env = dict(launch.env)
        task_env[constants.ENV_CONTAINER_ID] = cid
        task_env.setdefault(constants.ENV_LOG_DIR, str(workdir))
        task_env["TONY_EXECUTOR_HOST"] = self.host
        env = dict(os.environ)
        env.update(task_env)
        env["PYTHONPATH"] = child_pythonpath(env)
        task_env["PYTHONPATH"] = env["PYTHONPATH"]
        docker_on = self.conf is not None and self.conf.get_bool(
            conf_mod.DOCKER_ENABLED, False)
        argv = [sys.executable, "-m", "tony_tpu.executor"]
        if docker_on:
            argv = docker_wrap_command(self.conf, argv, env=task_env,
                                       workdir=str(workdir),
                                       mounts=[str(self.job_dir)])
        proc = subprocess.Popen(
            argv, env=env, cwd=workdir, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True)
        log.close()
        c = Container(container_id=cid, job_type=launch.job_type,
                      index=launch.index, host=self.host, _proc=proc)
        c._tpus = launch.tpus  # type: ignore[attr-defined]
        with self._lock:
            self._running[cid] = c
        return c

    def poll_completed(self) -> List[Container]:
        done = []
        with self._lock:
            for cid, c in list(self._running.items()):
                rc = c._proc.poll() if c._proc else -1
                if rc is not None:
                    c.exit_code = (constants.EXIT_PREEMPTED if c.preempted
                                   else rc)
                    self._tpus_in_use -= getattr(c, "_tpus", 0)
                    del self._running[cid]
                    done.append(c)
        return done

    def stop_container(self, container: Container) -> None:
        with self._lock:
            c = self._running.get(container.container_id)
        if c is not None and c._proc is not None and c._proc.poll() is None:
            # Kill the whole process group: executor + its user child.
            try:
                os.killpg(c._proc.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass

    def preempt(self, container_id: str) -> bool:
        with self._lock:
            c = self._running.get(container_id)
        if c is None or c._proc is None or c._proc.poll() is not None:
            return False
        c.preempted = True
        try:
            os.killpg(c._proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            return False
        return True

    def running(self) -> List[Container]:
        with self._lock:
            return list(self._running.values())

    _live_containers = running


def scheduler_from_conf(conf, job_dir: str | Path,
                        host: str = "127.0.0.1") -> ContainerScheduler:
    """Build the substrate the config names (reference: the RM is chosen by
    the cluster, not the job; here ``tony.scheduler.backend`` picks
    ``local`` (default) or ``tpu-vm``). ``tony.application.node-blacklist``
    hosts are excluded from placement — the reference's blacklist semantics
    applied at scheduler level."""
    from tony_tpu import conf as conf_mod
    backend = conf.get("tony.scheduler.backend", "local")
    blacklist = set(conf.get_list(conf_mod.APPLICATION_NODE_BLACKLIST))
    if backend == "tpu-vm":
        hosts = [h for h in conf.get_list("tony.scheduler.hosts")
                 if h not in blacklist]
        if not hosts:
            raise ValueError(
                "tony.scheduler.backend=tpu-vm needs tony.scheduler.hosts "
                "(after node-blacklist filtering)")
        return TpuVmScheduler(
            hosts,
            ssh_cmd=conf.get("tony.scheduler.ssh-command", "ssh"),
            remote_python=conf.get("tony.scheduler.remote-python", "python3"),
            remote_workdir=conf.get("tony.scheduler.remote-workdir",
                                    "/tmp/tony-tpu"),
            remote_pythonpath=conf.get("tony.scheduler.remote-pythonpath")
            or None,
            host_tpus=conf.get_int("tony.scheduler.host-tpus", 0))
    if backend != "local":
        raise ValueError(f"unknown tony.scheduler.backend={backend!r}")
    return None  # caller builds LocalProcessScheduler with its own args


def docker_wrap_command(conf, argv: List[str],
                        env: Optional[Dict[str, str]] = None,
                        workdir: Optional[str] = None,
                        mounts: Sequence[str] = ()) -> List[str]:
    """When ``tony.docker.enabled`` is set, wrap an executor launch command
    in ``docker run`` with the configured image (reference: the YARN docker
    runtime env ``YARN_CONTAINER_RUNTIME_TYPE=docker`` — SURVEY.md §2.1
    "Docker support"). Mirrors the YARN launch-context contract: the
    curated task ``env`` rides ``-e`` (not the host's full environ), each
    of ``mounts`` (the job dir, so conf/src/venv localization resolve) is
    bind-mounted at the same path, and ``workdir`` becomes the container
    cwd. The image must provide python + tony_tpu. Applied by
    ``LocalProcessScheduler.launch`` when it was constructed with the job
    config."""
    from tony_tpu import conf as conf_mod
    if not conf.get_bool(conf_mod.DOCKER_ENABLED, False):
        return argv
    image = conf.get(conf_mod.DOCKER_IMAGE, "")
    if not image:
        raise ValueError("tony.docker.enabled=true requires "
                         "tony.docker.containers.image")
    cmd = ["docker", "run", "--rm", "--network=host"]
    for m in mounts:
        cmd += ["-v", f"{m}:{m}"]
    if workdir:
        cmd += ["-w", str(workdir)]
    for key in sorted(env or ()):
        cmd += ["-e", f"{key}={env[key]}"]
    return cmd + [image] + argv


class TpuVmScheduler(ContainerScheduler):
    """Multi-host pod-slice backend: one executor per TPU-VM worker via SSH.

    The contract mirrors ``gcloud compute tpus tpu-vm ssh --worker=N
    --command`` fan-out: ``hosts`` lists worker addresses; the executor env
    rides the SSH command line; completion is detected by the remote shell
    exiting with the executor's code.

    Remote lifecycle: each launch runs the executor under ``setsid`` with
    its pid written to ``pids/<cid>.pid`` on the worker, so kill/preempt can
    reach the *remote process group* (executor + user child) over a second
    SSH exec — terminating only the local SSH client would orphan them.

    Placement: when ``host_tpus`` is set, each host carries that many chips
    and tasks are placed least-loaded-first so chip asks never oversubscribe
    a worker (the ``yarn.io/tpu`` resource-type semantics of the north
    star); with no chip asks, placement balances running task count.

    Exercised end-to-end by the fake-ssh e2e tier (``tests/test_e2e.py``):
    ``ssh_cmd`` pointed at a local shim script runs the full gang/failure/
    preemption matrix against this substrate without a pod.
    """

    def __init__(self, hosts: List[str], ssh_cmd: str = "ssh",
                 remote_python: str = "python3",
                 remote_workdir: str = "/tmp/tony-tpu",
                 remote_pythonpath: Optional[str] = None,
                 host_tpus: int = 0):
        if not hosts:
            raise ValueError("TpuVmScheduler requires at least one host")
        self.hosts = list(hosts)
        self.ssh_cmd = ssh_cmd
        self.remote_python = remote_python
        self.remote_workdir = remote_workdir
        self.remote_pythonpath = remote_pythonpath  # None = pip-installed
        self.host_tpus = host_tpus                  # chips per worker; 0 = off
        self._host_chips: Dict[str, int] = {h: 0 for h in self.hosts}
        self._host_tasks: Dict[str, int] = {h: 0 for h in self.hosts}
        self._running: Dict[str, Container] = {}
        self._lock = threading.Lock()
        self._stage_lock = threading.Lock()      # guards the lock table
        self._host_stage_locks: Dict[str, threading.Lock] = {}
        self._next_id = 0
        self._staged_hosts: set = set()

    def _ssh_argv(self, host: str, remote_sh: str) -> List[str]:
        """argv for one remote exec; ``ssh_cmd`` may carry flags
        (``ssh -i key``) or be a local shim script (tests)."""
        return shlex.split(self.ssh_cmd) + [host, remote_sh]

    def build_stage_command(self, local_dir: str, host: str,
                            remote_subdir: str, items: str = ".") -> str:
        """Shell pipeline staging a local dir (or named items within it)
        onto the worker (the HDFS localization analogue for the SSH
        substrate): tar stream over ssh — no temp files, one round trip."""
        dest = f"{self.remote_workdir}/{remote_subdir}"
        return (f"tar -C {shlex.quote(local_dir)} -cf - {items} | "
                f"{self.ssh_cmd} {host} "
                f"{shlex.quote(f'mkdir -p {dest} && tar -xf - -C {dest}')}")

    def build_remote_command(self, launch: ContainerLaunch, host: str,
                             cid: str = "adhoc") -> List[str]:
        """The SSH argv for one executor launch (separated for testability:
        command construction is covered by unit tests, the network is not).
        Paths in the env that point at client-side staging (conf, src,
        venv) are rewritten to the worker-side copies laid down by
        :meth:`build_stage_command`."""
        env = {**launch.env, "TONY_EXECUTOR_HOST": host}
        wd = self.remote_workdir
        if constants.ENV_CONF_PATH in env:
            env[constants.ENV_CONF_PATH] = (
                f"{wd}/conf/{constants.TONY_JOB_JSON}")
        if constants.ENV_SRC_DIR in env:
            env[constants.ENV_SRC_DIR] = f"{wd}/src"
        if constants.ENV_RESOURCES_DIR in env:
            env[constants.ENV_RESOURCES_DIR] = f"{wd}/resources"
        venv = env.get(constants.ENV_VENV)
        if venv:
            # Archives stage as the file itself; dirs stage as contents.
            if Path(venv).is_file():
                env[constants.ENV_VENV] = (
                    f"{wd}/venv-stage/{Path(venv).name}")
            else:
                env[constants.ENV_VENV] = f"{wd}/venv-stage"
        if self.remote_pythonpath:
            env["PYTHONPATH"] = self.remote_pythonpath
        exports = " ".join(
            f"export {k}={shlex.quote(v)};" for k, v in sorted(env.items()))
        # setsid: the executor becomes leader of a fresh process group whose
        # pgid == its pid, so `kill -- -$(cat pidfile)` reaps it AND the
        # user process it spawned; `wait` propagates the executor's exit
        # code (or 128+SIG after a remote kill) back through ssh.
        remote = (
            f"mkdir -p {wd}/pids && cd {wd} || exit 1; {exports} "
            f"setsid {self.remote_python} -m tony_tpu.executor "
            f"< /dev/null & pid=$!; echo $pid > pids/{cid}.pid; "
            f"wait $pid; rc=$?; rm -f pids/{cid}.pid; exit $rc")
        return self._ssh_argv(host, remote)

    def _host_for(self, launch: ContainerLaunch) -> str:
        """Least-loaded placement with per-host chip accounting (reference:
        the RM matching a resource ask to a node with capacity)."""
        with self._lock:
            if launch.tpus and self.host_tpus:
                if launch.tpus > self.host_tpus:
                    raise RuntimeError(
                        f"unsatisfiable tpu ask: task wants {launch.tpus} "
                        f"chips but hosts have {self.host_tpus}")
                fits = [h for h in self.hosts
                        if self._host_chips[h] + launch.tpus <= self.host_tpus]
                if not fits:
                    raise RuntimeError(
                        f"unsatisfiable tpu ask: {launch.tpus} chips "
                        f"requested, per-host free: "
                        f"{ {h: self.host_tpus - self._host_chips[h] for h in self.hosts} }")
                host = min(fits, key=lambda h: (self._host_chips[h],
                                                self._host_tasks[h]))
                self._host_chips[host] += launch.tpus
            else:
                host = min(self.hosts, key=lambda h: self._host_tasks[h])
            self._host_tasks[host] += 1
        return host

    def _stage(self, local: str, host: str, subdir: str,
               items: str = ".") -> None:
        cmd = self.build_stage_command(local, host, subdir, items=items)
        proc = subprocess.run(cmd, shell=True, timeout=300,
                              capture_output=True, text=True)
        if proc.returncode != 0:
            raise RuntimeError(
                f"staging {local} -> {host}:{self.remote_workdir}/{subdir} "
                f"failed (rc={proc.returncode}): {proc.stderr[-500:]}")

    def _host_stage_lock(self, host: str) -> "threading.Lock":
        with self._stage_lock:
            return self._host_stage_locks.setdefault(host, threading.Lock())

    def _stage_once(self, launch: ContainerLaunch, host: str) -> None:
        """Stage conf + src + venv onto the worker the first time it's
        used. The host is marked staged only after every transfer succeeds;
        a failure raises so the launch (and the job) fails loudly instead
        of executors dying later on a missing-conf error. Serialized PER
        HOST (not globally): the AM launches a gang concurrently, and one
        global lock would make first-time staging to N hosts O(N) in
        transfer time — the exact latency the concurrent launches exist
        to remove."""
        with self._host_stage_lock(host):
            if host in self._staged_hosts:
                return
            conf_path = launch.env.get(constants.ENV_CONF_PATH)
            if conf_path and Path(conf_path).is_file():
                self._stage(str(Path(conf_path).parent), host, "conf",
                            items=Path(conf_path).name)
            src_dir = launch.env.get(constants.ENV_SRC_DIR)
            if src_dir and Path(src_dir).is_dir():
                self._stage(src_dir, host, "src")
            venv = launch.env.get(constants.ENV_VENV)
            if venv and Path(venv).is_file():
                self._stage(str(Path(venv).parent), host, "venv-stage",
                            items=Path(venv).name)
            elif venv and Path(venv).is_dir():
                self._stage(venv, host, "venv-stage")
            res_dir = launch.env.get(constants.ENV_RESOURCES_DIR)
            if res_dir and Path(res_dir).is_dir():
                self._stage(res_dir, host, "resources")
            self._staged_hosts.add(host)

    def launch(self, launch: ContainerLaunch) -> Container:
        host = self._host_for(launch)
        with self._lock:
            self._next_id += 1
            cid = f"container_tpuvm_{self._next_id:04d}"
        try:
            self._stage_once(launch, host)
            proc = subprocess.Popen(
                self.build_remote_command(launch, host, cid=cid),
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
                start_new_session=True)
        except Exception:
            # Release the accounting or gang-restart retries would see the
            # chips as permanently occupied (the scheduler outlives attempts).
            self._release_host(host, launch.tpus)
            raise
        c = Container(container_id=cid, job_type=launch.job_type,
                      index=launch.index, host=host, _proc=proc)
        c._tpus = launch.tpus  # type: ignore[attr-defined]
        with self._lock:
            self._running[cid] = c
        return c

    def _release_host(self, host: str, tpus: int) -> None:
        with self._lock:
            if self.host_tpus and tpus:
                self._host_chips[host] -= tpus
            self._host_tasks[host] -= 1

    def poll_completed(self) -> List[Container]:
        done = []
        with self._lock:
            for cid, c in list(self._running.items()):
                rc = c._proc.poll() if c._proc else -1
                if rc is not None:
                    c.exit_code = (constants.EXIT_PREEMPTED if c.preempted
                                   else rc)
                    if self.host_tpus and getattr(c, "_tpus", 0):
                        self._host_chips[c.host] -= c._tpus
                    self._host_tasks[c.host] -= 1
                    del self._running[cid]
                    done.append(c)
        return done

    def _remote_kill(self, c: Container, sig: str = "KILL") -> bool:
        """Kill the remote executor's whole process group via its pidfile
        (second ssh exec). Returns True when the remote kill ran."""
        pidfile = f"{self.remote_workdir}/pids/{c.container_id}.pid"
        # `kill -s SIG -- -pgid`: the only group-kill spelling both dash
        # and bash builtins accept (`kill -SIG -- -pgid` is rejected by
        # dash, the default /bin/sh on debian-family TPU-VM images). The
        # pidfile is removed here, not only by the launch shell's cleanup —
        # the local ssh client may be torn down before that cleanup runs.
        sh = (f"[ -f {pidfile} ] && pid=$(cat {pidfile}) && "
              f"rm -f {pidfile} && kill -s {sig} -- -$pid 2>/dev/null")
        try:
            proc = subprocess.run(self._ssh_argv(c.host, sh), timeout=30,
                                  stdout=subprocess.DEVNULL,
                                  stderr=subprocess.DEVNULL)
            return proc.returncode == 0
        except (subprocess.TimeoutExpired, OSError):
            return False

    def stop_container(self, container: Container) -> None:
        with self._lock:
            c = self._running.get(container.container_id)
        if c is not None and c._proc is not None and c._proc.poll() is None:
            if not self._remote_kill(c):
                # Remote side unreachable (or already gone): at least drop
                # the local ssh client so the AM's teardown completes.
                try:
                    c._proc.terminate()
                except OSError:
                    pass

    def preempt(self, container_id: str) -> bool:
        with self._lock:
            c = self._running.get(container_id)
        if c is None or c._proc is None or c._proc.poll() is not None:
            return False
        c.preempted = True
        if not self._remote_kill(c):
            c._proc.kill()
        return True

    def _live_containers(self) -> List[Container]:
        with self._lock:
            return list(self._running.values())

    def stop(self, drain_s: float = 10.0) -> None:
        super().stop(drain_s)
