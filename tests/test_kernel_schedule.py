"""The flash kernels' STATIC SCHEDULE at the Mistral cell's shape (4 x 2048,
32 heads of 128, 8 KV heads), from the TPU compiler's own dump: how many
instruction bundles each kernel's program has per 256 x 256 of score tile.
``tests/test_tpu_compile.py`` pins that the kernels fit; this pins how well
they are packed, at no chip time — a body that starts spilling its score
tile, or a rule that falls back to small blocks, shows here as more bundles
a tile (blocks of 256 x 256 gave 1197 / 1455 / 1855; PERF.md §6 PR 28 has
the table and §7 the recipe).

The dump needs ``LIBTPU_INIT_ARGS`` before libtpu loads, so a child process
compiles (``tests/workloads/flash_schedule_dump.py``); libtpu's dumper
aborts that process after the compile, which is tolerated: each kernel's
``*-final_hlo-static-per-bundle-utilization.txt`` is on disk by then, one
line a bundle. Skipped where the topology cannot be described or no file
appears."""

import re
import subprocess
import sys
from pathlib import Path

import pytest

from tony_tpu.ops import attention as A

B, T, H, HKV, D = 4, 2048, 32, 8, 128
# kernel -> (the file's name holds, bundles a 256 x 256 tile at most)
KERNELS = {"fwd": ("attn_fwd", 700), "dq": ("attn_bwd_dq", 750),
           "dkv": ("attn_bwd_dkv", 1050)}
BUNDLE = re.compile(r"^\d+( \d+){8}\s*$")   # MXU XLU VALU EUP VLOAD ...


@pytest.fixture(scope="module")
def dump(tmp_path_factory):
    out = tmp_path_factory.mktemp("llo")
    child = Path(__file__).parent / "workloads" / "flash_schedule_dump.py"
    proc = subprocess.run(
        [sys.executable, str(child), str(out), *map(str, (B, T, H, HKV, D))],
        capture_output=True, text=True, timeout=600,
        cwd=Path(__file__).resolve().parent.parent)
    assert "Traceback" not in proc.stderr, proc.stderr[-2000:]
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip(f"cannot describe a v5e topology here: {proc.stdout}")
    return out


def bundles(dump: Path, name: str) -> int:
    files = [f for f in dump.glob(
        "*-final_hlo-static-per-bundle-utilization.txt")
        if re.search(rf"{name}_*\.\d+-", f.name)]
    if not files:
        pytest.skip(f"the compiler wrote no schedule for {name} here")
    return sum(1 for line in files[0].read_text().splitlines()
               if BUNDLE.match(line))


@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_bundles_per_score_tile(dump, kernel):
    blocks = A._plan_dispatch(T, T, None, None, True, None, D, 2)[1]
    assert blocks == A.Blocks(*[(512, 512)] * 3)        # the rule's choice
    name, ceiling = KERNELS[kernel]
    bq, bk = getattr(blocks, kernel)
    per_tile = bundles(dump, name) / (bq * bk / (256 * 256))
    assert per_tile <= ceiling, (
        f"{name}: {per_tile:.0f} bundles a 256 x 256 tile at blocks "
        f"{bq} x {bk}, ceiling {ceiling}")
