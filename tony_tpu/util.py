"""Small shared helpers (reference: the ``com.linkedin.tony.util.Utils``
grab-bag, kept deliberately tiny here — SURVEY.md §2.1)."""

from __future__ import annotations

import os
from pathlib import Path
from typing import Dict

# The repo/package root: parent of the tony_tpu package directory.
PKG_ROOT = str(Path(__file__).resolve().parent.parent)


def default_workdir() -> Path:
    """The client job workdir — TONY_WORK_DIR env or ~/.tony-tpu/jobs.
    Shared by the client (write side) and history CLI (scan side) so
    `tony history` finds what `tony submit` wrote."""
    return Path(os.environ.get("TONY_WORK_DIR",
                               Path.home() / ".tony-tpu" / "jobs"))


def child_pythonpath(env: Dict[str, str]) -> str:
    """PYTHONPATH for a child process that must import ``tony_tpu`` even when
    the parent loaded it off ``sys.path`` (tests / source checkout) rather
    than an installed package: prepend the package root, dedupe.

    Deliberately does NOT carry site-packages: PYTHONPATH reaches the USER
    process, where host site dirs would shadow a job venv's packages."""
    parts = [PKG_ROOT] + [p for p in env.get("PYTHONPATH", "").split(
        os.pathsep) if p and p != PKG_ROOT]
    return os.pathsep.join(parts)


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; first statement of
    every process that compiles (replica, training entry, smoke
    children). The directory is part of the cache key, so it never moves:
    where ``JAX_COMPILATION_CACHE_DIR`` is set jax reads it itself and
    nothing is set in code; otherwise ``<checkout>/.jax_cache``. Returns
    the directory in use.

    The key itself is jax's: the program's text without its debug
    locations, the jaxlib version, the platform, ``XLA_FLAGS`` and
    ``LIBTPU_INIT_ARGS``, the compile options, the devices, the
    compression. A Pallas kernel's Mosaic module keeps its locations (they
    travel in the custom call's ``backend_config``, which the strip of
    debug info leaves alone), so the file names of the call stack ARE in
    the key of a program that holds one. Nothing is done about that here:
    the one path that moves between runs, the container sandbox a task's
    script is copied into, is taken off by the executor that made it
    (``TaskExecutor.source_prefix_regex``, handed to the user process as
    ``JAX_HLO_SOURCE_FILE_CANONICALIZATION_REGEX``), and a script run from
    a fixed directory needs nothing."""
    from_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if from_env:
        return from_env
    import jax

    path = os.path.join(PKG_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def normalize_serve_telemetry(raw: Dict) -> Dict[str, object]:
    """One normalization for the serve heartbeat schema, shared by the
    executor's stats-file reader and the session's heartbeat ingest so
    the two layers cannot drift: scalars become floats, list values
    (the router's ``prefix_digest`` block-key list and the parked-
    conversation ``parked_digest`` list) become string lists, and
    non-numeric strings (the disaggregated replica ``role``
    — the schema's second non-scalar) pass through as strings, and the
    per-tenant ``tenants`` breakdown (tony_tpu.serve.qos — a dict of
    per-tenant dicts of scalars, the schema's ONE sanctioned nesting)
    normalizes recursively. Numeric strings still normalize to float,
    so a stats writer that stringified a counter keeps its historical
    behavior. Raises on anything else (deeper nesting, None), so both
    callers keep their own advisory-telemetry failure handling."""
    def norm(v: object, depth: int) -> object:
        if isinstance(v, (list, tuple)):
            return [str(x) for x in v]
        if isinstance(v, dict):
            if depth >= 3:
                raise TypeError(
                    "serve telemetry nests deeper than the schema's "
                    "tenants breakdown (dict of dicts of scalars)")
            return {str(k): norm(x, depth + 1) for k, x in v.items()}
        if isinstance(v, str):
            try:
                return float(v)
            except ValueError:
                return v
        return float(v)

    return {str(k): norm(v, 1) for k, v in dict(raw).items()}
