"""Satellite-module tests: azkaban job-file shim, TPU discovery, TPU-VM
scheduler command construction (SURVEY.md §2.2 satellites + §2.1 GPU
discovery analogue)."""

import io
from pathlib import Path

import pytest

from tony_tpu import conf as conf_mod
from tony_tpu.azkaban import job_file_conf, parse_job_file
from tony_tpu.cli import main as cli_main
from tony_tpu.discovery import TpuTopology, _chips_from_env, discover_tpus
from tony_tpu.scheduler import ContainerLaunch, TpuVmScheduler

WORKLOADS = Path(__file__).parent / "workloads"


def test_parse_job_file_properties_format(tmp_path):
    job = tmp_path / "train.job"
    job.write_text(
        "# a comment\n"
        "! another\n"
        "type=TonYJob\n"
        "job.name=nightly-train\n"
        "executes=python train.py \\\n"
        "  --epochs 3\n"
        "tony.worker.instances=4\n"
        "tony.worker.tpus=2\n")
    props = parse_job_file(job)
    assert props["type"] == "TonYJob"
    assert props["executes"] == "python train.py --epochs 3"
    assert props["tony.worker.instances"] == "4"


def test_job_file_conf_translation(tmp_path):
    job = tmp_path / "train.job"
    job.write_text(
        "job.name=nightly\n"
        "framework=jax\n"
        "src.dir=/data/src\n"
        "executes=python train.py\n"
        "tony.worker.instances=2\n")
    cfg, src_dir = job_file_conf(job)
    assert src_dir == "/data/src"
    assert cfg.get(conf_mod.APPLICATION_NAME) == "nightly"
    assert cfg.get(conf_mod.APPLICATION_FRAMEWORK) == "jax"
    assert cfg.get("tony.application.executes") == "python train.py"
    assert cfg.instances("worker") == 2


def test_azkaban_cli_submits_end_to_end(tmp_path):
    job = tmp_path / "smoke.job"
    job.write_text(
        "framework=standalone\n"
        f"src.dir={WORKLOADS}\n"
        "executes=python exit_0.py\n"
        "tony.worker.instances=1\n"
        "tony.task.heartbeat-interval-ms=200\n")
    rc = cli_main(["azkaban", str(job), "--workdir", str(tmp_path / "jobs"),
                   "--timeout", "90"])
    assert rc == 0


def test_discovery_env_paths():
    assert _chips_from_env({"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1"}) == 4
    assert _chips_from_env({"TPU_VISIBLE_CHIPS": "0,1,2"}) == 3
    assert _chips_from_env({}) is None
    topo = discover_tpus()
    assert isinstance(topo, TpuTopology)
    assert topo.num_chips >= 0


def test_discovery_devfs_counts_chips_only(tmp_path):
    """What the one-chip v5e machine exposes: /dev/vfio/0 (the chip's
    IOMMU group) beside the ``vfio`` control node; newer kernels add a
    ``devices`` directory. Only numbered nodes are chips."""
    from tony_tpu.discovery import _chips_from_devfs

    assert _chips_from_devfs(str(tmp_path)) is None
    (tmp_path / "vfio" / "devices").mkdir(parents=True)
    (tmp_path / "vfio" / "vfio").touch()
    assert _chips_from_devfs(str(tmp_path)) is None
    (tmp_path / "vfio" / "0").touch()
    assert _chips_from_devfs(str(tmp_path)) == 1
    for n in (1, 2, 3):
        (tmp_path / "vfio" / str(n)).touch()
    assert _chips_from_devfs(str(tmp_path)) == 4
    (tmp_path / "accel0").touch()
    (tmp_path / "accel_ctl").touch()
    assert _chips_from_devfs(str(tmp_path)) == 1
    # The narrower env wins over the host bounds.
    assert _chips_from_env({"TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1",
                            "TPU_VISIBLE_CHIPS": "0"}) == 1


def test_am_rejects_tpu_ask_on_chipless_host(tmp_path, monkeypatch):
    """tpus>0 with zero discovered chips must fail loudly, not become an
    unlimited-scheduler launch; tony.scheduler.total-tpus overrides."""
    import pytest
    from tony_tpu.am import ApplicationMaster
    from tony_tpu.conf import TonyConfig
    import tony_tpu.discovery as disc
    monkeypatch.setattr(disc, "discover_tpus",
                        lambda: disc.TpuTopology(0, "none"))
    props = {"tony.worker.instances": "1", "tony.worker.tpus": "4",
             "tony.application.framework": "standalone"}
    with pytest.raises(ValueError, match="no TPU chips"):
        ApplicationMaster(TonyConfig(props), "app_t", tmp_path / "j")
    am = ApplicationMaster(
        TonyConfig({**props, "tony.scheduler.total-tpus": "8"}),
        "app_t2", tmp_path / "j2")
    assert am.scheduler.total_tpus == 8


def test_tpuvm_scheduler_fake_ssh_e2e(tmp_path):
    """The multi-host path end-to-end with ssh faked as a local shim: conf +
    src stage over the tar|ssh pipeline, the executor launches 'remotely',
    registers, runs the workload, and the job succeeds."""
    import os
    import stat
    import sys

    from tony_tpu.am import ApplicationMaster
    from tony_tpu.conf import TonyConfig
    from tony_tpu.minipod import MiniPodJob
    from tony_tpu.util import PKG_ROOT

    fake = tmp_path / "fakessh.sh"
    fake.write_text("#!/bin/sh\nshift\nexec sh -c \"$*\"\n")
    fake.chmod(fake.stat().st_mode | stat.S_IEXEC)

    conf = TonyConfig({
        "tony.application.framework": "standalone",
        "tony.worker.instances": "1",
        "tony.application.executes": "python exit_0.py",
        "tony.task.heartbeat-interval-ms": "200",
    })
    job_dir = tmp_path / "job"
    (job_dir / "src").mkdir(parents=True)
    import shutil
    for wl in ("exit_0.py",):
        shutil.copy(WORKLOADS / wl, job_dir / "src" / wl)
    sched = TpuVmScheduler(
        hosts=["localhost"], ssh_cmd=str(fake),
        remote_python=sys.executable,
        remote_workdir=str(tmp_path / "remote"),
        remote_pythonpath=PKG_ROOT)
    am = ApplicationMaster(conf, app_id="app_tpuvm", job_dir=job_dir,
                           scheduler=sched)
    job = MiniPodJob(am).start()
    assert job.wait(timeout=90) == 0
    # The remote workdir really was staged and used.
    assert (tmp_path / "remote" / "src" / "exit_0.py").is_file()
    assert (tmp_path / "remote" / "conf" / "tony-job.json").is_file()


def test_scheduler_from_conf_backends(tmp_path):
    import pytest
    from tony_tpu.conf import TonyConfig
    from tony_tpu.scheduler import scheduler_from_conf
    # local (default) → None: caller builds LocalProcessScheduler.
    assert scheduler_from_conf(TonyConfig(), tmp_path) is None
    # tpu-vm honors hosts and the node blacklist.
    sched = scheduler_from_conf(TonyConfig({
        "tony.scheduler.backend": "tpu-vm",
        "tony.scheduler.hosts": "10.0.0.1,10.0.0.2,10.0.0.3",
        "tony.application.node-blacklist": "10.0.0.2",
    }), tmp_path)
    assert isinstance(sched, TpuVmScheduler)
    assert sched.hosts == ["10.0.0.1", "10.0.0.3"]
    with pytest.raises(ValueError, match="needs tony.scheduler.hosts"):
        scheduler_from_conf(TonyConfig({
            "tony.scheduler.backend": "tpu-vm"}), tmp_path)
    with pytest.raises(ValueError, match="unknown tony.scheduler.backend"):
        scheduler_from_conf(TonyConfig({
            "tony.scheduler.backend": "k8s"}), tmp_path)


def test_tpuvm_scheduler_remote_command():
    sched = TpuVmScheduler(hosts=["10.0.0.1", "10.0.0.2"],
                           remote_workdir="/tmp/tt")
    launch = ContainerLaunch(job_type="worker", index=0,
                             env={"TONY_JOB_NAME": "worker",
                                  "TONY_AM_ADDRESS": "10.0.0.9:1234"})
    argv = sched.build_remote_command(launch, "10.0.0.1", cid="c01")
    assert argv[0] == "ssh" and argv[1] == "10.0.0.1"
    remote = argv[2]
    assert "mkdir -p /tmp/tt" in remote
    assert "export TONY_AM_ADDRESS=10.0.0.9:1234;" in remote
    assert "export TONY_EXECUTOR_HOST=10.0.0.1;" in remote
    # Remote lifecycle contract: setsid + pidfile so a second ssh exec can
    # kill the remote process group; wait propagates the exit code.
    assert "setsid python3 -m tony_tpu.executor" in remote
    assert "pids/c01.pid" in remote
    assert "wait $pid" in remote


def test_tpuvm_chip_accounting_and_venv_rewrite(tmp_path):
    sched = TpuVmScheduler(hosts=["a", "b"], remote_workdir="/tmp/tt",
                           host_tpus=4)
    # 4-chip asks land on distinct hosts; a third cannot fit anywhere.
    l4 = ContainerLaunch(job_type="worker", index=0, env={}, tpus=4)
    h1 = sched._host_for(l4)
    h2 = sched._host_for(l4)
    assert {h1, h2} == {"a", "b"}
    import pytest
    with pytest.raises(RuntimeError, match="unsatisfiable"):
        sched._host_for(l4)
    with pytest.raises(RuntimeError, match="unsatisfiable"):
        sched._host_for(ContainerLaunch(
            job_type="worker", index=9, env={}, tpus=8))
    # Venv paths rewrite to the staged worker-side copy (dir vs archive).
    venv_dir = tmp_path / "venv"
    venv_dir.mkdir()
    argv = sched.build_remote_command(ContainerLaunch(
        job_type="w", index=0, env={"TONY_VENV": str(venv_dir)}), "a")
    assert "export TONY_VENV=/tmp/tt/venv-stage;" in argv[2]
    venv_zip = tmp_path / "venv.tar.gz"
    venv_zip.write_bytes(b"x")
    argv = sched.build_remote_command(ContainerLaunch(
        job_type="w", index=0, env={"TONY_VENV": str(venv_zip)}), "a")
    assert "export TONY_VENV=/tmp/tt/venv-stage/venv.tar.gz;" in argv[2]
    # tony.containers.resources: the staged dir rewrites to the worker copy.
    argv = sched.build_remote_command(ContainerLaunch(
        job_type="w", index=0,
        env={"TONY_RESOURCES_DIR": str(tmp_path)}), "a")
    assert "export TONY_RESOURCES_DIR=/tmp/tt/resources;" in argv[2]


def test_docker_wrap_command_unit():
    import pytest
    from tony_tpu.conf import TonyConfig
    from tony_tpu.scheduler import docker_wrap_command
    argv = ["python", "-m", "tony_tpu.executor"]
    # Disabled (default): passthrough untouched.
    assert docker_wrap_command(TonyConfig(), argv) == argv
    # Enabled: wrapped in docker run with the curated env (-e), job-dir
    # bind mount (-v), container workdir (-w), and the configured image —
    # the YARN launch-context contract, not a bare image invocation.
    conf = TonyConfig({"tony.docker.enabled": "true",
                       "tony.docker.containers.image": "img:1"})
    wrapped = docker_wrap_command(
        conf, argv, env={"TONY_AM_ADDRESS": "h:1", "TONY_JOB_NAME": "w"},
        workdir="/jobs/app1/containers/c1", mounts=["/jobs/app1"])
    assert wrapped[:2] == ["docker", "run"]
    assert wrapped[-3:] == argv
    img_at = wrapped.index("img:1")
    head = wrapped[:img_at]
    assert "-v" in head and "/jobs/app1:/jobs/app1" in head
    assert "-w" in head and "/jobs/app1/containers/c1" in head
    assert "TONY_AM_ADDRESS=h:1" in head and "TONY_JOB_NAME=w" in head
    # Host environ must NOT leak into the container env.
    assert not any(a.startswith("PATH=") for a in head)
    # Enabled without an image: loud failure, not a silent no-op.
    with pytest.raises(ValueError, match="tony.docker.containers.image"):
        docker_wrap_command(
            TonyConfig({"tony.docker.enabled": "true"}), argv)


@pytest.mark.parametrize("remote_pythonpath", ["/opt/tony", None])
def test_remote_interpreter_runs_with_site(remote_pythonpath):
    """Executors start as plain ``python -m`` (site import on), whether
    tony_tpu arrives via remote_pythonpath or is pip-installed remotely:
    a pip-installed remote needs the site import to find tony_tpu at
    all, and skipping it saves nothing measurable."""
    launch = ContainerLaunch(job_type="w", index=0, env={})
    sched = TpuVmScheduler(hosts=["a"], remote_workdir="/tmp/tt",
                           remote_pythonpath=remote_pythonpath)
    remote = sched.build_remote_command(launch, "a")[2]
    assert " -S" not in remote and "-m tony_tpu.executor" in remote
