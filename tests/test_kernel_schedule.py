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
appears.

The KDA chunk kernels (``tests/workloads/kda_schedule_dump.py``, PR 39) are
pinned the same way at the Kimi Linear cell's head (128 x 128, chunk 64,
keep 4, bfloat16), by the bundles a (head, step) cell of four chunks RUNS:
the loops that build the chunks' state-free halves are unrolled (the
forward's only loop; the backward's first), so their bundles are counted
as they stand, and the backward's loop over the chunks' backward — read
from ``*-final_bundles.txt``, where a bundle inside it is marked ``>>`` —
counts ``keep`` times. Two dumps at once collide on ``/tmp/libtpu_lockfile``
(the second child aborts before it compiles) unless
``ALLOW_MULTIPLE_LIBTPU_LOAD`` is set: under ``-n`` with ``--dist loadfile``
this file's tests share a worker and the module fixtures run one after the
other; do not run a dump by hand beside them.

The same kernels under ONE decay a head (``gdn_chunk_fwd`` / ``_bwd``, PR
40) at the Olmo Hybrid cell's head behind its zero lanes (96 x 192 as 128
x 256): no levels and no table, so a cell is smaller than the per-channel
route's though its state is twice as wide.

The fused convolution / SiLU / head-normalisation kernels
(``tests/workloads/conv_schedule_dump.py``, PR 47) are pinned by the
bundles ONE STRIP of their loop runs — 128 rows of 512 channels under the
head's normalisation, 32 rows without — which is what a call's time is made
of: strips a call x bundles a strip x ~1.06 ns (PERF.md section 6, PR 47)."""

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


# ------------------------------------------------------------------ KDA

KDA = dict(tokens=2048, heads=4, head=128, chunk=64, keep=4)
# kernel -> bundles a (head, step) cell of `keep` chunks runs, at most:
# ~5% over what PR 39 reads (8,686 / 14,268; PR 38's kernels 10,067 /
# 20,976 — 46.8 / 96.4 ms a call on the chip over 4096 cells, 1.13 ns a
# bundle; the chip gains more than the count: PERF.md §6 PR 39)
KDA_CEILING = {"kda_chunk_fwd": 9120, "kda_chunk_bwd": 14980}
IN_LOOP = re.compile(r"^\s*(?:0x[0-9a-f]+|\d+)\s+([A-Z]{2})?:\s*(>*)\s*\{")


@pytest.fixture(scope="module")
def kda_dump(tmp_path_factory):
    return _delta_dump(tmp_path_factory.mktemp("llo_kda"), KDA)


def _delta_dump(out, sizes):
    child = Path(__file__).parent / "workloads" / "kda_schedule_dump.py"
    proc = subprocess.run(
        [sys.executable, str(child), str(out), *map(str, sizes.values())],
        capture_output=True, text=True, timeout=600,
        cwd=Path(__file__).resolve().parent.parent)
    assert "Traceback" not in proc.stderr, proc.stderr[-2000:]
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip(f"cannot describe a v5e topology here: {proc.stdout}")
    return out


def kda_bundles(dump: Path, name: str):
    """``(bundles outside the chunk loops, [bundles of each loop's body])``
    of a kernel's final schedule."""
    files = [f for f in dump.glob("*-final_bundles.txt")
             if re.search(rf"{name}_*\.\d+-\d+-final_bundles", f.name)]
    if not files:
        pytest.skip(f"the compiler wrote no schedule for {name} here")
    outside, loops = 0, []
    for line in files[0].read_text().splitlines():
        m = IN_LOOP.match(line)
        if not m:
            continue
        depth = len(m.group(2))
        if depth < 2:
            outside += 1
            continue
        if m.group(1) == "LB" and depth == 2:
            loops.append(0)
        loops[-1] += 1
    return outside, loops


@pytest.mark.parametrize("kernel", sorted(KDA_CEILING))
def test_kda_bundles_a_cell(kda_dump, kernel):
    keep = KDA["keep"]
    outside, loops = kda_bundles(kda_dump, kernel)
    # the halves' loops are unrolled; the backward keeps one loop, which
    # builds no half: the chunks' backward, last to first
    assert len(loops) == kernel.endswith("bwd"), loops
    cell = outside + keep * sum(loops)
    assert cell <= KDA_CEILING[kernel], (
        f"{kernel}: {cell} bundles a cell ({outside} outside the loop, "
        f"{keep} x {loops}), ceiling {KDA_CEILING[kernel]}")


def test_kda_backward_rebuilds_with_the_state_s_products_alone(kda_dump):
    """The backward's first part — every chunk's half and the states — is
    a forward cell without its output: no larger than the forward kernel
    plus the scratch's stores (PR 38's ran three whole forward chunks here
    and built all four halves again in the loop after)."""
    fwd, _ = kda_bundles(kda_dump, "kda_chunk_fwd")
    rebuild, _ = kda_bundles(kda_dump, "kda_chunk_bwd")
    assert rebuild <= fwd + 600, (rebuild, fwd)


# ------------------------------------------------- one decay a head (GDN)

GDN = dict(KDA, value=256)          # 96 x 192 behind zero lanes: 128 x 256
# ~5% over what PR 40 reads (7,348 / 11,272 = 6,808 + 4 x 1,116): the pair
# matrices are one product and a [C, C] factor, no level and no table, but
# the inverse (ten dependent 64^3 float32 products a chunk: PERF.md §5, the
# Kimi row) is the per-channel route's, and the state's products run over
# 256 value lanes
GDN_CEILING = {"gdn_chunk_fwd": 7720, "gdn_chunk_bwd": 11840}


@pytest.fixture(scope="module")
def gdn_dump(tmp_path_factory):
    return _delta_dump(tmp_path_factory.mktemp("llo_gdn"), GDN)


@pytest.mark.parametrize("kernel", sorted(GDN_CEILING))
def test_gdn_bundles_a_cell(gdn_dump, kernel):
    keep = GDN["keep"]
    outside, loops = kda_bundles(gdn_dump, kernel)
    assert len(loops) == kernel.endswith("bwd"), loops
    cell = outside + keep * sum(loops)
    assert cell <= GDN_CEILING[kernel], (
        f"{kernel}: {cell} bundles a cell ({outside} outside the loop, "
        f"{keep} x {loops}), ceiling {GDN_CEILING[kernel]}")
    # a scalar decay's cell is the smaller, at twice the value lanes
    assert GDN_CEILING[kernel] < KDA_CEILING[kernel.replace("gdn", "kda")]


# --------------------------------------- convolution + SiLU + head norm

# shape -> (tokens, heads, head size, unit), {kernel: bundles a strip at
# most}: ~5% over what PR 47 reads. Kimi Linear's q and k: a strip is 128
# rows x 512 channels, 64 float32 registers a value (910 / 1,756: 14 / 27
# bundles a register, bounded by the vector slots — ~52 / ~101 operations a
# register of 4 a bundle); its v, without the norm, 32 rows x 512 (131 /
# 253).
CONV = {"unit_32x128": ((4096, 32, 128, 1), {"delta_conv_fwd": 960,
                                             "delta_conv_bwd": 1850}),
        "plain_32x128": ((4096, 32, 128, 0), {"delta_conv_fwd": 140,
                                              "delta_conv_bwd": 270})}


@pytest.fixture(scope="module", params=sorted(CONV))
def conv_dump(request, tmp_path_factory):
    sizes, ceilings = CONV[request.param]
    out = tmp_path_factory.mktemp("llo_conv_" + request.param)
    child = Path(__file__).parent / "workloads" / "conv_schedule_dump.py"
    proc = subprocess.run(
        [sys.executable, str(child), str(out), *map(str, sizes)],
        capture_output=True, text=True, timeout=600,
        cwd=Path(__file__).resolve().parent.parent)
    assert "Traceback" not in proc.stderr, proc.stderr[-2000:]
    if "NO_TOPOLOGY" in proc.stdout:
        pytest.skip(f"cannot describe a v5e topology here: {proc.stdout}")
    return out, ceilings


@pytest.mark.parametrize("kernel", ["delta_conv_fwd", "delta_conv_bwd"])
def test_delta_conv_bundles_a_strip(conv_dump, kernel):
    """The strips' loop is each kernel's one loop (the first strip, under
    the halo block, stands before it); a body past its ceiling has started
    to spill, or lost the shape rule's strip."""
    dump, ceilings = conv_dump
    _, loops = kda_bundles(dump, kernel)
    assert len(loops) == 1, loops
    assert loops[0] <= ceilings[kernel], (
        f"{kernel}: {loops[0]} bundles a strip, ceiling {ceilings[kernel]}")
