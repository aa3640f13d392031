"""Whole runs at a tiny size on the CPU, through the real control flow
(`tony submit`, the driver, the check), skipping only the harness's look
for a chip. A sound run comes out correct; the lower-precision control
reads a wider gap; the timed path broken underneath — a step that returns
its state unchanged, a program built inside the window — comes out incorrect.

The limits in the workload file are set from readings at the cell's own
size on the chip. A tiny model in bfloat16 reads wider gaps (64-wide dot
products round relatively harder), so these runs are held to limits of
their own, set the same way: above what sound tiny runs read (loss 0.0012,
gradient 0.0021, change 0.0013), far below what the frozen step reads
(change 1.0).
"""

import shutil
import time
from types import SimpleNamespace

import pytest

from benchmark import manifest
from benchmark.drivers import train

DATA = manifest.HERE / "tests" / "data"
TINY_LIMITS = {"loss_gap": 0.01, "grad_norm_gap": 0.01,
               "param_change_gap": 0.01}


def drive(name, job=None, **over):
    bench = manifest.load()
    args = SimpleNamespace(seed=7, seconds=3.0, trace=0, rehearse=True,
                           control=None, limit_seeds=None)
    vars(args).update(over)
    wl = manifest.workload_file(name)
    wl["limits"] = {k: {"limit": v} for k, v in TINY_LIMITS.items()}
    wl["job"].update(job or {})
    return train.run(manifest.cell(bench, name), wl, args, time.time())


def gaps(res):
    task = res["artifacts"]["task"]
    return task["compared"]


@pytest.fixture(scope="module")
def sound_train():
    return drive("mistral7b.train")


def test_sound_train_run_is_correct(sound_train):
    assert sound_train["correct"] is True
    assert sound_train["attempted"] > 0 and sound_train["failed"] == 0
    assert sound_train["end_to_end"]["train_tok_s"] > 0


def test_the_step_is_built_once(sound_train):
    """Between the first step and the window only the check's own three
    programs are built (first-moment norms, the second copy of the seeded
    weights, the change's norms): a state whose second step differs from its
    first (say, by arrays pinned to a device) would build the step again."""
    task = sound_train["artifacts"]["task"]
    assert task["built_to_window"] - task["built_to_step1"] <= 3


def test_int8_control_reads_a_wider_gradient_gap(sound_train):
    control = drive("mistral7b.train", control="int8")
    assert gaps(control)["grad_norm_gap"] \
        > 3 * gaps(sound_train)["grad_norm_gap"]


@pytest.mark.parametrize("task", ["frozen_step_task.py",
                                  "compiling_step_task.py"])
def test_broken_timed_path_comes_out_incorrect(task, monkeypatch, tmp_path):
    shutil.copy(train.TASK, tmp_path / "real_task.py")
    real_copy = shutil.copy

    def copy_both(src, dst):
        real_copy(tmp_path / "real_task.py", dst.parent / "real_task.py")
        return real_copy(src, dst)

    monkeypatch.setattr(train, "TASK", DATA / task)
    monkeypatch.setattr(train.shutil, "copy", copy_both)
    res = drive("mistral7b.train")
    assert res["correct"] is False
    if task == "frozen_step_task.py":
        assert gaps(res)["param_change_gap"] == pytest.approx(1.0, abs=1e-3)
    else:
        assert all(v <= TINY_LIMITS[k] for k, v in gaps(res).items())
        assert res["artifacts"]["task"]["compiled_in_window"] > 0


@pytest.mark.parametrize("job", [
    {"instances": 4, "mesh": {"dp": 4}, "batch": 16, "rehearse_batch": 4},
    {"tpus_per_worker": 4, "mesh": {"fsdp": 4}, "rehearse_batch": 4},
], ids=["gang-dp4", "worker-fsdp4"])
def test_a_workload_file_alone_makes_a_job_across_devices(job):
    """README's worked example: a cell over a gang or a mesh is a workload
    file's ``job`` and nothing else (virtual CPU devices here)."""
    res = drive("mistral7b.train", job=job)
    assert res["correct"] is True and res["device"]["count"] == 4
