"""Jaxpr-level sharding/collective invariant analyzer.

Every guarantee the training stack makes — "no replicated grads ever
materialize" (ZeRO-3), "forward gathers stay inside the prefetch window"
(the collective scheduler), "moment slots are f32 and pad rows inert"
(the fused optimizer) — is enforced by construction and spot-checked by
numerics tests. Nothing inspected the traced program to prove the
invariants still hold after the next refactor. TF-Replicator
(arXiv:1902.00465) argues the framework must own such cross-cutting
correctness properties rather than leave them to each user; this package
closes that loop: a static pass over the step's closed jaxpr,
cross-checked against the SAME planner artifacts the step executes.

Rule suite (see :mod:`tony_tpu.analysis.rules`):

1. **replication-leak** — any ``all_gather`` that materializes a full
   fsdp-sharded buffer outside the planned prefetch live window, plus the
   structural check that the ``optimization_barrier`` prefetch chain is
   intact;
2. **collective audit** — every ``psum``/``psum_scatter``/``all_gather``/
   ``all_to_all``/``ppermute`` equation reconciled against the planner's
   set (unplanned reshards AND planned-but-missing transfers, with
   equation provenance);
3. **dtype policy** — no silent f64, no bf16-carried reductions, f32
   moment slots;
4. **donation** — the state argument (params, opt slots) must be donated,
   or the finding names the argument and its byte cost;
5. **step signature** — a stable program digest pinned as a committed
   JSON snapshot (:mod:`tony_tpu.analysis.signature`).

Findings come back structured with a waiver mechanism
(:class:`Waiver`); each run banks a summary into
``tony_tpu.profiler.report("analysis")`` alongside the existing report
family. ``tony analyze`` (:mod:`tony_tpu.analysis.cli`) runs the suite
over the shipped train-step configs; ``make lint`` runs the companion
source lint (:mod:`tony_tpu.analysis.srclint`).

The facade is LAZY (PEP 562): importing ``tony_tpu.analysis`` touches no
jax. That keeps the jax-free consumers honest — the AST source lint, and
the ``tony analyze`` bootstrap that must set ``XLA_FLAGS`` BEFORE
anything initializes jax — while ``analysis.analyze_accum_step`` etc.
resolve to the jax-backed engine in :mod:`tony_tpu.analysis.core` on
first use.
"""

from __future__ import annotations

import importlib
from typing import Any

__all__ = [
    "AnalysisReport", "CollectiveEqn", "ConcFinding", "ConcReport",
    "Expected", "Finding",
    "SCALAR_NBYTES", "Waiver", "WitnessLock", "analyze_accum_step",
    "analyze_concurrency", "analyze_jaxpr",
    "analyze_serve_step",
    "apply_waivers", "check_lock_order", "check_signature",
    "collect_collectives",
    "diff_signature", "expected_accum_collectives", "live_high_water",
    "step_signature",
]

# name -> owning submodule (None = the name IS a submodule).
_LAZY = {
    "AnalysisReport": "core", "analyze_accum_step": "core",
    "analyze_jaxpr": "core", "analyze_serve_step": "core",
    "CollectiveEqn": "jaxprwalk", "collect_collectives": "jaxprwalk",
    "live_high_water": "jaxprwalk",
    "Expected": "rules", "Finding": "rules", "SCALAR_NBYTES": "rules",
    "Waiver": "rules", "apply_waivers": "rules",
    "expected_accum_collectives": "rules",
    "check_signature": "signature", "diff_signature": "signature",
    "step_signature": "signature",
    # The concurrency plane is jax-free like srclint — the facade keeps
    # it importable from `make lint` / gateway hosts without jax.
    "ConcFinding": "concurrency", "ConcReport": "concurrency",
    "WitnessLock": "concurrency", "analyze_concurrency": "concurrency",
    "check_lock_order": "concurrency",
    "cli": None, "concurrency": None, "core": None, "jaxprwalk": None,
    "rules": None,
    "signature": None, "srclint": None,
}


def __getattr__(name: str) -> Any:
    owner = _LAZY.get(name, "<missing>")
    if owner == "<missing>":
        raise AttributeError(f"module {__name__!r} has no attribute "
                             f"{name!r}")
    if owner is None:
        return importlib.import_module(f"{__name__}.{name}")
    return getattr(importlib.import_module(f"{__name__}.{owner}"), name)


def __dir__():
    return sorted(set(globals()) | set(_LAZY))
