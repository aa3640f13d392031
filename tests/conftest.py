"""Test harness config.

Control-plane tests are pure Python. Compute-plane tests (models/, parallel/)
run JAX on a virtual 8-device CPU mesh — the MiniYARNCluster analogue for
sharding (SURVEY.md §4): multi-chip layouts compile and execute without TPU
hardware. The env vars must be set before jax initializes its backends, hence
the assignment at import time here.
"""

import os
import sys
from pathlib import Path

# Force (not setdefault): tests run on the virtual CPU mesh whatever the
# session env says (the chip machine exports JAX_PLATFORMS=tpu,cpu), and
# the subprocesses tests start inherit it.
os.environ["JAX_PLATFORMS"] = "cpu"
existing = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in existing:
    os.environ["XLA_FLAGS"] = (
        existing + " --xla_force_host_platform_device_count=8").strip()
# jax's persistent compile cache is ON for tests, at the fixed path
# tony_tpu.util.enable_compile_cache would pick itself, with no size or
# time threshold: hundreds of tests rebuild the same step programs behind
# new closures (every engine, every parametrized grad), and a cold run of
# the tier-1 selection takes half the time when the second builder of a
# program loads it instead of compiling it (the key is the HLO, so a
# changed program never hits). Subprocesses inherit it through the env.
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(
    Path(__file__).resolve().parent.parent / ".jax_cache")
os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import pytest  # noqa: E402

# Suite tiers (VERDICT r4 weak #5: 176 tests had outgrown a single
# undifferentiated run). Marked per MODULE — a test's cost class is set by
# its harness (pure logic vs jax compiles vs live subprocesses), which is
# per-file here. Measured on this host, one pytest process:
#   quick ≈ 35s | jit ≈ 6min (compiles) | e2e ≈ 8min (real processes)
_TIER_BY_MODULE = {
    "test_conf": "quick", "test_session": "quick", "test_rpc": "quick",
    "test_runtimes": "quick", "test_security": "quick",
    "test_executor": "quick", "test_satellites": "quick",
    "test_checkpoint": "jit", "test_ckpt": "jit", "test_data": "jit",
    "test_ops": "jit", "test_fused_optim": "jit", "test_quant": "jit",
    "test_models": "jit",
    "test_moe": "jit", "test_batchnorm": "jit", "test_parallel": "jit",
    "test_pipeline": "jit", "test_overlap": "jit", "test_multislice": "jit",
    "test_sched": "jit",
    "test_analysis": "jit",
    "test_concurrency": "jit",
    "test_serve": "jit",
    "test_spec": "jit",
    "test_route": "jit",
    "test_disagg": "jit",
    "test_kvtier": "jit",
    "test_aot": "jit",
    "test_qos": "jit",
    "test_elastic": "jit",
    "test_publish": "jit",
    "test_tpu_compile": "jit",
    "test_e2e": "e2e", "test_client_cli": "e2e", "test_chip_smoke": "e2e",
}


def pytest_collection_modifyitems(items):
    for item in items:
        # Unmapped modules default to the jit tier (still selected by the
        # documented full tiers) rather than silently carrying no marker —
        # a marker-filtered run must never skip a new file with no signal.
        tier = _TIER_BY_MODULE.get(item.module.__name__, "jit")
        item.add_marker(getattr(pytest.mark, tier))


@pytest.fixture(scope="module")
def no_jax_compile_cache():
    """jax's persistent compile cache off for one module (it is on for the
    suite, see the top of this file): for tests of the repo's OWN
    executable cache, and for compiles that can be written but never read
    back (a described, unattached TPU)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# ---------------------------------------------------------------------------
# Thread-leak guard (the concurrency-analysis plane's test-side half):
# every test must leave no stray NON-daemon thread behind — a non-daemon
# survivor outlives pytest silently and is exactly the shutdown-hygiene
# drift the static audit polices in the package. Daemon threads are not
# policed here (the interpreter reaps them; the audit still requires the
# construction site to declare them), and neither are the long-lived
# helpers below, discovered while landing the guard.
# ---------------------------------------------------------------------------

_THREAD_ALLOWLIST_PREFIXES = (
    # concurrent.futures keeps idle non-daemon workers for reuse and joins
    # them at interpreter exit; the AM's launch pool ("launch_*") is
    # shut down per attempt but its last workers unwind asynchronously.
    "ThreadPoolExecutor",
    "launch",
    # jax/XLA host runtime helpers (platform-dependent; created once per
    # process on first compile, never per test).
    "jax_",
)


@pytest.fixture(autouse=True)
def _thread_leak_guard():
    import threading
    import time

    # Thread OBJECTS, not idents: CPython reuses a dead thread's ident,
    # so an ident snapshot could silently exclude a genuine leak.
    before = set(threading.enumerate())
    yield

    def strays():
        return [t for t in threading.enumerate()
                if t.is_alive() and not t.daemon
                and t not in before
                and t is not threading.current_thread()
                and not any(t.name.startswith(p)
                            for p in _THREAD_ALLOWLIST_PREFIXES)]

    # Grace window: teardown that signalled its threads deserves one
    # scheduler beat to see them unwind before the verdict.
    leaked = strays()
    deadline = time.monotonic() + 2.0
    while leaked and time.monotonic() < deadline:
        for t in leaked:
            t.join(timeout=0.2)
        leaked = strays()
    assert not leaked, (
        f"test leaked non-daemon thread(s): "
        f"{[t.name for t in leaked]} — join them on a teardown path, "
        f"or extend the conftest allowlist with an audited reason")
