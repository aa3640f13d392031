"""TPU chip discovery: the scheduler-side resource census.

Mirrors ``com.linkedin.tony.util.gpu.GpuDiscoverer`` (upstream
``tony-core/src/main/java/com/linkedin/tony/util/gpu/``, unverified —
SURVEY.md §0/§2.1): the reference shells out to ``nvidia-smi -q -x`` and
parses XML so the AM can schedule/isolate GPUs pre-YARN-3.1. The TPU
equivalent needs no subprocess: chips appear as ``/dev/accel<n>`` (TPU-VM) or
``/dev/vfio/<n>`` device nodes, and the libtpu env describes the host's slice
topology. Device nodes come first because they are what this process can
open: the one-chip v5e machine exposes ``/dev/vfio/0`` alone while its
inherited ``TPU_CHIPS_PER_HOST_BOUNDS`` still reads ``2,2,1``. The count
feeds the scheduler's ``total_tpus`` so over-subscribed
``tony.<jobtype>.tpus`` asks fail at launch like an RM rejecting an
unsatisfiable resource request.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Optional


@dataclass(frozen=True)
class TpuTopology:
    num_chips: int
    source: str          # devfs | env | none


def _chips_from_devfs(dev: str = "/dev") -> Optional[int]:
    accels = glob.glob(f"{dev}/accel[0-9]*")
    if accels:
        return len(accels)
    # One numbered IOMMU group node per chip. Beside them /dev/vfio holds
    # the ``vfio`` control node and, on newer kernels, a ``devices``
    # directory — neither is a chip.
    vfio = [p for p in glob.glob(f"{dev}/vfio/*")
            if os.path.basename(p).isdigit()]
    if vfio:
        return len(vfio)
    return None


def _chips_from_env(env=os.environ) -> Optional[int]:
    visible = env.get("TPU_VISIBLE_CHIPS")      # narrower than the bounds
    if visible:
        return len([c for c in visible.split(",") if c.strip() != ""])
    bounds = env.get("TPU_CHIPS_PER_HOST_BOUNDS")  # e.g. "2,2,1"
    if bounds:
        dims = [int(x) for x in re.findall(r"\d+", bounds)]
        if dims:
            n = 1
            for d in dims:
                n *= d
            return n
    return None


def discover_tpus() -> TpuTopology:
    """Count this host's TPU chips from device nodes, else the libtpu env.
    Never through jax: the caller is the AM, and a control-plane process
    that initialises the runtime takes the chip from the task it is about
    to launch."""
    n = _chips_from_devfs()
    if n is not None:
        return TpuTopology(n, "devfs")
    n = _chips_from_env()
    if n is not None:
        return TpuTopology(n, "env")
    return TpuTopology(0, "none")
