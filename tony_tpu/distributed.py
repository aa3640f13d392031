"""User-facing rendezvous helper for JAX jobs launched by TonY-TPU.

The JAXRuntime exports the coordinator triple (SURVEY.md §2.4 "rendezvous");
user code simply calls::

    import tony_tpu.distributed as dist
    dist.initialize()          # no rendezvous outside a TonY job / for 1 process

which forwards to ``jax.distributed.initialize(coordinator_address,
num_processes, process_id)`` — the TPU-native replacement for ``TF_CONFIG`` /
c10d / Gloo rendezvous.
"""

from __future__ import annotations

import logging
import os
from typing import Optional, Sequence

from tony_tpu import constants, profiler

_log = logging.getLogger(__name__)


def env_spec() -> Optional[tuple[str, int, int]]:
    """(coordinator_address, num_processes, process_id) from the executor env,
    or None when not running under TonY-TPU."""
    addr = os.environ.get(constants.ENV_COORDINATOR_ADDRESS)
    n = os.environ.get(constants.ENV_NUM_PROCESSES)
    pid = os.environ.get(constants.ENV_PROCESS_ID)
    if not addr or n is None or pid is None:
        return None
    return addr, int(n), int(pid)


@profiler.span("tony:dist_initialize")
def initialize(local_device_ids: Optional[Sequence[int]] = None) -> bool:
    """Bring up the JAX coordination service from TonY env. Returns True if
    multi-process init happened, False for the single-process fallback.
    Also starts the per-task profiler server when the JAXRuntime enabled it
    (``tony.task.profiler.enabled`` — SURVEY.md §5.1). As the training
    entry it also turns on jax's persistent compile cache
    (:func:`tony_tpu.util.enable_compile_cache`) and the program's build
    counters (:func:`tony_tpu.profiler.watch_builds`), and it ends by
    starting the backend (:func:`tony_tpu.profiler.backend_devices`:
    after the rendezvous on a gang, and in the single-process fallback
    too), so configure jax — platform, device count — before this call,
    not after it. The whole call is the set-up span
    ``tony:dist_initialize``; inside it the jax import, where this is the
    first to need it, is a ``tony:import`` and the backend's start is
    ``tony:backend_init``."""
    from tony_tpu.util import enable_compile_cache

    with profiler.importing("jax"):
        import jax
    enable_compile_cache()
    profiler.watch_builds()
    _maybe_start_profiler()
    spec = env_spec()
    gang = spec is not None and spec[1] > 1
    if gang:
        addr, num_processes, process_id = spec
        if local_device_ids is None:
            raw = os.environ.get(constants.ENV_LOCAL_DEVICE_IDS)
            if raw:
                local_device_ids = [int(x) for x in raw.split(",")]
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
        )
    profiler.backend_devices()
    return gang


def _maybe_start_profiler() -> None:
    """``jax.profiler.start_server`` on the port the JAXRuntime assigned —
    what ``tony profile`` and ``tony proxy``/TensorBoard capture from.
    Logs once either way: the port, or why nothing listens on it."""
    port = os.environ.get(constants.ENV_PROFILER_PORT)
    if not port:
        return
    import jax
    try:
        jax.profiler.start_server(int(port))
    except Exception as e:  # noqa: BLE001 — port race; profiling is advisory
        _log.warning("profiler server not started on port %s: %s", port, e)
    else:
        _log.info("profiler server listening on port %s", port)


def process_id() -> int:
    spec = env_spec()
    return spec[2] if spec else 0


def num_processes() -> int:
    spec = env_spec()
    return spec[1] if spec else 1
