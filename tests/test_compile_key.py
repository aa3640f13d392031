"""The compile cache's key of a job's train step does not hold the sandbox
the executor localised the job's source into (ISSUE 26).

jax strips debug info from a program before it hashes it, but a Pallas
kernel's Mosaic module is serialised WITH its locations into the custom
call's ``backend_config``, an attribute the strip leaves alone. Those
locations name every file of the call stack, the job's script among them,
and ``tony submit`` runs that script from
``<workdir>/app_*/containers/container_*/src/``: without the executor's
``JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX`` no two containers share the
key of the one program that is dearest to compile.

``tests/workloads/compile_key_probe.py`` is run as the executor runs a
job's script — copied into a sandbox, from that directory, under the
environment value ``TaskExecutor.source_prefix_regex`` gives — and lowers
the small train step for the TPU with the flash kernels forced on. A
lowering, not a compile: no chip and no libtpu needed.
"""

import json
import os
import re
import shutil
import subprocess
import sys
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import pytest

from tony_tpu import constants
from tony_tpu.executor import TaskExecutor

ROOT = Path(__file__).resolve().parent.parent
PROBE = ROOT / "tests" / "workloads" / "compile_key_probe.py"


def _probe(script_dir: Path, sandbox: Path) -> dict:
    """Run the probe from ``script_dir`` under the pattern the executor
    of ``sandbox`` would export."""
    script_dir.mkdir(parents=True)
    shutil.copy(PROBE, script_dir)
    env = dict(os.environ, TONY_REPO_ROOT=str(ROOT), JAX_PLATFORMS="cpu")
    env[constants.ENV_JAX_SOURCE_FILE_REGEX] = \
        TaskExecutor.source_prefix_regex(str(sandbox))
    out = subprocess.run(
        [sys.executable, PROBE.name], cwd=script_dir, env=env,
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("KEY ")]
    return json.loads(line[-1][4:])


@pytest.fixture(scope="module")
def keys(tmp_path_factory):
    """The probe from two containers of two applications (paths of
    different lengths) and from a directory outside any sandbox."""
    work = tmp_path_factory.mktemp("work").resolve()
    box_a = work / "app_1790000000000_1" / "containers" / "container_7_0001"
    box_b = work / "app_1790000000001_234567" / "containers" \
        / "container_12345_0002"
    runs = {"a": (box_a / "src", box_a), "b": (box_b / "src", box_b),
            "outside": (work / "checkout" / "tools", box_a)}
    with ThreadPoolExecutor(len(runs)) as pool:
        futures = {name: pool.submit(_probe, *args)
                   for name, args in runs.items()}
        return {name: f.result() for name, f in futures.items()}


def test_probe_lowers_the_four_flash_calls_of_the_step(keys):
    # attn_fwd twice (remat), attn_bwd_dq, attn_bwd_dkv: the calls whose
    # Mosaic modules carry the file names.
    assert {k["tpu_custom_calls"] for k in keys.values()} == {4}


def test_two_containers_share_the_key_of_the_train_step(keys):
    assert keys["a"]["file"] != keys["b"]["file"]
    assert keys["a"]["as_started"] == keys["b"]["as_started"]


def test_without_the_pattern_the_sandbox_path_is_in_the_key(keys):
    """The control: if jax stops embedding the path, the mechanism is dead
    code and this says so. Read on a kernel the script calls itself: jax
    keeps the innermost ten frames of a location, and since PR 30 the
    frames of ``remat.ChosenStep`` lie between the step and its caller, so
    the job's script is no longer among the ten of the library's step."""
    assert keys["a"]["own_without"] != keys["b"]["own_without"]
    assert keys["a"]["own_without"] != keys["a"]["own_as_started"]
    assert keys["a"]["own_as_started"] == keys["b"]["own_as_started"]


def test_the_librarys_step_is_keyed_by_the_librarys_own_call_site(keys):
    """``make_train_step`` returns a step that is traced from one line of
    ``tony_tpu/remat.py`` (tests/test_remat.py pins why): that line, not
    the script's, is the outermost frame in its kernels' locations, so
    its key is the same from any sandbox even without the pattern."""
    assert keys["a"]["without"] == keys["b"]["without"] \
        == keys["a"]["as_started"]


def test_a_program_built_outside_a_sandbox_keeps_its_key(keys):
    """`tony serve` replicas, check children and a script run by hand
    build their programs from files outside any sandbox: their keys are
    the ones they had, so their caches stay warm."""
    assert keys["outside"]["as_started"] == keys["outside"]["without"]


def test_pattern_is_anchored_and_escaped():
    box = "/w/app.1+2/containers/c(1)"
    pat = TaskExecutor.source_prefix_regex(box)
    assert re.sub(pat, "", f"{box}/src/pkg/train.py") == "pkg/train.py"
    assert re.sub(pat, "", f"{box}/venv/lib/x.py") == "venv/lib/x.py"
    assert re.sub(pat, "", f"{box}/src/src/x.py") == "src/x.py"
    # Not a prefix match on a sibling, no match past the start, and the
    # characters of the path mean themselves.
    for other in (f"{box}2/src/train.py", f"/mnt{box}/src/train.py",
                  "/w/appX1+2/containers/c(1)/src/train.py",
                  "/opt/venv/lib/python3.12/site-packages/flax/x.py"):
        assert re.sub(pat, "", other) == other
    assert TaskExecutor.source_prefix_regex(box + "/") == pat
