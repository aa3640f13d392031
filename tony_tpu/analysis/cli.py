"""``tony analyze``: run the jaxpr invariant analyzer over the shipped
train-step configurations on the virtual 8-device CPU mesh.

The canonical configs mirror the accum-engine matrix the test suite pins
(mnist-mlp on a ``data=2 × fsdp=4`` mesh, 32 KiB buckets, 4 microbatches):

* ``bucketed``     — replicated params, bucketed-allreduce accumulation;
* ``per_leaf``     — ZeRO-3 with per-leaf forward gathers (the numerics
  pin path);
* ``zero3``        — ZeRO-3 with bucketed + prefetch-chained gathers;
* ``fused_bucket`` — ZeRO-3 with the bucket-resident fused optimizer;
* ``quant``        — ZeRO-3 with the quantized int8 gather wire format
  (delayed-scaling amax state on a QuantTrainState);
* ``serve``        — the serving plane's continuous-batching decode step
  (llama-tiny replica on the full 8-device mesh, replicated params):
  audited against the engine's planner-registered EMPTY collective set —
  a replica's decode must issue zero inter-chip collectives — plus the
  donation check on the paged KV pools;
* ``spec``         — the speculative lane's one-launch k-token
  verification step (a SpecEngine on the same mesh/params layout):
  the verify forward rides the decode step family with k+1 real rows,
  and this config pins THAT program — zero inter-chip collectives,
  KV-pool donation, committed signature — so the speculative hot path
  is audited independently of the decode config's geometry;
* ``route``        — the routed serving tier's chunked-prefill launch
  (a prefix-cache + chunked-prefill engine on the serve layout, traced
  at the ``(1, prefill_chunk)`` chunk shape): chunked prefill promises
  NO new compiled step shapes beyond the declared chunk geometry, and
  this config pins that geometry's program under the same replica-step
  invariants (zero inter-chip collectives, KV-pool donation);
* ``disagg``       — the disaggregated serving tier's PREFILL-ONLY step
  (a prefill-ROLE engine on the serve layout, traced at its chunk
  launch): a prefill replica's entire device program is this one shape
  family — its output is KV + the first token, never a decode loop —
  and this config pins it independently (zero inter-chip collectives,
  KV-pool donation) so the split gang's hot path is audited even when
  no colocated engine exists in the job.

Each run traces (never executes) the step, applies the five rules, and
prints the findings; exit status 1 when any unwaived finding remains.
Signatures pin against ``tests/signatures/<config>.json`` when
``--signatures`` points there (the tier-1 test does this too; the CLI
flag exists so drift can be inspected and regenerated by hand).
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

CONFIG_NAMES: Tuple[str, ...] = ("bucketed", "per_leaf", "zero3",
                                 "fused_bucket", "quant", "serve", "spec",
                                 "route", "disagg")

# Configs that build an ENGINE target (analyze_serve_step) instead of an
# accum stepper — run_config routes them; build_target rejects them.
_SERVE_CONFIGS: Tuple[str, ...] = ("serve", "spec", "route", "disagg")

# The canonical harness geometry — also the committed-signature geometry.
_HIDDEN = 64
_BATCH = 32
_MICROBATCHES = 4
_BUCKET_BYTES = 32 << 10
_FSDP = 4
_DEVICES = 8


def _ensure_cpu_mesh() -> None:
    """The canonical configs need the 8-device virtual CPU mesh; set the
    env BEFORE jax initializes (a no-op when the caller — conftest, a
    TPU job — already configured a backend)."""
    if "jax" in sys.modules:
        return
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={_DEVICES}"
        ).strip()


def build_target(name: str, *, donate: bool = True):
    """``(stepper, state, batch)`` for one canonical config — shared by
    the CLI and tests/test_analysis.py so both analyze the identical
    program (and the committed signatures mean one thing)."""
    _ensure_cpu_mesh()
    import jax
    import jax.numpy as jnp
    import optax

    from tony_tpu import parallel as par
    from tony_tpu import train
    from tony_tpu.models import get_model
    from tony_tpu.ops import fused_optim

    if name not in CONFIG_NAMES or name in _SERVE_CONFIGS:
        raise ValueError(f"unknown analyze config {name!r} for an "
                         f"accum-step target (one of "
                         f"{tuple(n for n in CONFIG_NAMES if n not in _SERVE_CONFIGS)};"
                         f" {_SERVE_CONFIGS} build through "
                         f"build_serve_target/build_spec_target)")
    if len(jax.devices()) < _DEVICES:
        raise RuntimeError(
            f"the canonical analyze configs need {_DEVICES} devices "
            f"(virtual CPU mesh), found {len(jax.devices())} — set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{_DEVICES} before jax initializes")
    model = get_model("mnist-mlp", hidden=_HIDDEN)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (_BATCH, 784))
    y = jax.random.randint(ky, (_BATCH,), 0, 10)
    mesh = par.make_mesh(fsdp=_FSDP)
    if name == "fused_bucket":
        tx: Any = fused_optim.FusedOptimizer(
            rule="adamw", lr=1e-3, bucket_bytes=_BUCKET_BYTES)
    else:
        tx = optax.adamw(1e-3)
    state = train.create_train_state(model, tx, x, kr)
    if name != "bucketed":
        state = train.fsdp_shard_state(state, mesh)
    if name == "quant":
        from tony_tpu.ops import quant as quant_mod

        state = quant_mod.with_gather_quant(state, mesh, window=4,
                                            bucket_bytes=_BUCKET_BYTES)
    stepper = train.make_accum_train_step(
        mesh=mesh, microbatches=_MICROBATCHES,
        bucket_bytes=_BUCKET_BYTES,
        gather="per_leaf" if name == "per_leaf" else "bucketed",
        update="fused_bucket" if name == "fused_bucket" else "optax",
        quant=name == "quant",
        donate=donate)
    return stepper, state, {"x": x, "y": y}


def _serve_layout():
    """The ONE serve analyze layout every serving config builds on:
    llama-tiny, params replicated onto the full 8-device mesh. Shared
    so the four "same-layout" configs (serve/spec/route/disagg) can
    never silently pin against different programs."""
    _ensure_cpu_mesh()
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tony_tpu import parallel as par
    from tony_tpu.models import get_model

    model = get_model("llama-tiny", n_layers=2)
    sample = jnp.zeros((1, 16), jnp.int32)
    params = nn.unbox(model.init(jax.random.PRNGKey(0), sample))["params"]
    mesh = par.make_mesh()
    params = jax.device_put(
        jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                     if a.dtype == jnp.float32 else a, params),
        NamedSharding(mesh, P()))
    return model, params, mesh


def build_serve_target():
    """The canonical serve-decode analyze target: a llama-tiny replica
    engine on the full 8-device mesh with REPLICATED params — the
    layout's whole point is the pin that decode issues zero inter-chip
    collectives (any accidental sharding annotation would insert one)."""
    from tony_tpu.serve import ServeEngine

    model, params, mesh = _serve_layout()
    return ServeEngine(model, params, ctx_max=64, block_size=16,
                       q_block=16, decode_buckets=(4,), max_running=4,
                       mesh=mesh, tag="serve")


def build_spec_target():
    """The canonical speculative-verification analyze target: the same
    llama-tiny replica layout as ``serve`` but through a
    :class:`~tony_tpu.serve.SpecEngine` (n-gram draft — the lane every
    replica can run) at a distinct bucket geometry, traced at the
    verify step. The verify program must satisfy exactly the decode
    invariants: zero inter-chip collectives on the replica mesh and
    donated KV pools."""
    from tony_tpu.serve import SpecEngine

    model, params, mesh = _serve_layout()
    return SpecEngine(model, params, spec_k=4, ctx_max=64, block_size=16,
                      q_block=16, decode_buckets=(8,), max_running=8,
                      mesh=mesh, tag="spec")


def build_route_target():
    """The canonical chunked-prefill analyze target: the ``serve``
    layout (llama-tiny, replicated params, full 8-device mesh) with the
    PR 13 admission features armed — ``prefix_cache=True`` (host-side
    only: adoption must leave the traced program untouched, which the
    byte-unchanged serve/spec pins separately witness) and
    ``prefill_chunk=32`` (two row blocks), traced at the chunk
    launch."""
    from tony_tpu.serve import ServeEngine

    model, params, mesh = _serve_layout()
    return ServeEngine(model, params, ctx_max=64, block_size=16,
                       q_block=16, decode_buckets=(4,), max_running=4,
                       mesh=mesh, tag="route", prefix_cache=True,
                       prefill_chunk=32)


def build_disagg_target():
    """The canonical prefill-only analyze target: the ``route`` layout
    (llama-tiny, replicated params, full 8-device mesh, prefix cache +
    32-row chunks) built as a PREFILL-role engine
    (tony_tpu.serve.disagg). A prefill replica runs exactly one device
    program family — the chunk launch; the KV handoff itself is host
    bytes + the RPC wire, no device collective — so pinning this
    program at the ``(1, chunk)`` shape pins the split gang's entire
    accelerator footprint: zero inter-chip collectives on the replica
    mesh, both KV pools donated."""
    from tony_tpu.serve import ServeEngine

    model, params, mesh = _serve_layout()
    return ServeEngine(model, params, ctx_max=64, block_size=16,
                       q_block=16, decode_buckets=(4,), max_running=4,
                       mesh=mesh, tag="disagg", role="prefill",
                       prefix_cache=True, prefill_chunk=32)


def run_config(name: str, *, waivers: Sequence[Any] = (),
               signature_path: Optional[str] = None):
    """Build + analyze one canonical config; returns the
    :class:`~tony_tpu.analysis.AnalysisReport`."""
    from tony_tpu import analysis

    if name == "serve":
        return analysis.analyze_serve_step(
            build_serve_target(), tag=name, waivers=waivers,
            signature_path=signature_path)
    if name == "spec":
        return analysis.analyze_serve_step(
            build_spec_target(), tag=name, waivers=waivers,
            signature_path=signature_path, step="verify")
    if name == "route":
        return analysis.analyze_serve_step(
            build_route_target(), tag=name, waivers=waivers,
            signature_path=signature_path, step="prefill")
    if name == "disagg":
        return analysis.analyze_serve_step(
            build_disagg_target(), tag=name, waivers=waivers,
            signature_path=signature_path, step="prefill")
    stepper, state, batch = build_target(name)
    return analysis.analyze_accum_step(
        stepper, state, batch, tag=name, waivers=waivers,
        signature_path=signature_path)


def run_concurrency(args: Any) -> int:
    """``tony analyze --concurrency``: the host-side concurrency plane
    (lock-discipline lint, static+witnessed lock-order cycle check,
    thread-hygiene audit) over the installed package. Jax-free — it
    never builds a mesh or traces a step, so it runs on a gateway host
    in milliseconds; the jaxpr configs stay their own invocation.
    ``--signatures <dir>`` points at the committed blessings baseline
    (``<dir>/concurrency.json``); ``--update-signatures`` rewrites it
    from the current findings instead of failing on them."""
    from tony_tpu.analysis import concurrency as conc

    cfg = getattr(args, "config", None)
    if cfg not in (None, "all"):
        # Refuse rather than silently skip the requested jaxpr config.
        print(f"tony analyze --concurrency runs the concurrency plane "
              f"INSTEAD of the jaxpr configs — drop --config {cfg} or "
              f"run the two planes as separate invocations")
        return 2
    baseline = None
    if getattr(args, "signatures", None):
        baseline = os.path.join(args.signatures, "concurrency.json")
    if getattr(args, "update_signatures", False):
        if not baseline:
            print("tony analyze --concurrency --update-signatures needs "
                  "--signatures <dir> (the committed pin directory)")
            return 2
        for flag in ("json", "lint"):
            if getattr(args, flag, None):
                print(f"(--{flag} is ignored with --update-signatures: "
                      f"the regen writes only the baseline)")
        argv = [str(conc.default_root()), "--baseline", baseline,
                "--update-baseline"]
        return conc.main(argv)
    rc = 0
    if getattr(args, "lint", False):
        # --lint composes here exactly as on the jaxpr path: no flag is
        # silently ignored.
        from tony_tpu.analysis import srclint

        violations = srclint.lint_tree(srclint.default_root())
        for v in violations:
            print(f"LINT {v}")
        if violations:
            rc = 1
        else:
            print("[srclint] CLEAN")
    report = conc.analyze_concurrency(baseline_path=baseline)
    for f in report.findings:
        print(f)
    print(report.summary())
    if getattr(args, "json", None):
        # Same contract as the jaxpr path's --json: the structured
        # report lands where the flag points, not silently nowhere.
        with open(args.json, "w") as fh:
            json.dump({"concurrency": {
                "findings": [f.to_dict() for f in report.findings],
                "blessed": [f.to_dict() for f in report.blessed],
                "static_edges": [list(e) for e in report.static_edges],
                "observed_edges": report.observed,
            }}, fh, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return rc if report.ok else 1


def main(args: Any) -> int:
    """``tony analyze`` entry (argparse namespace: config, json,
    signatures, update_signatures, lint, concurrency)."""
    if getattr(args, "concurrency", False):
        return run_concurrency(args)
    _ensure_cpu_mesh()
    update = bool(getattr(args, "update_signatures", False))
    if update and not getattr(args, "signatures", None):
        # Without a pin directory the flag would be a silent no-op —
        # and a user who just "regenerated" commits stale pins.
        print("tony analyze --update-signatures needs --signatures "
              "<dir> (the committed pin directory, e.g. "
              "tests/signatures)")
        return 2
    from tony_tpu.analysis import signature as sig_mod

    names = CONFIG_NAMES if args.config in (None, "all") \
        else (args.config,)
    rc = 0
    reports = []
    # The update request rides the env var the test-suite path also
    # honors — but scoped: leaking it would neuter the drift gate for
    # every later check in this process.
    prior = os.environ.get(sig_mod.UPDATE_ENV)
    if update:
        os.environ[sig_mod.UPDATE_ENV] = "1"
    try:
        for name in names:
            sig_path = None
            if getattr(args, "signatures", None):
                sig_path = os.path.join(args.signatures, f"{name}.json")
            report = run_config(name, signature_path=sig_path)
            reports.append(report)
            print(report.summary())
            if not report.ok:
                rc = 1
    finally:
        if update:
            if prior is None:
                os.environ.pop(sig_mod.UPDATE_ENV, None)
            else:
                os.environ[sig_mod.UPDATE_ENV] = prior
    if getattr(args, "lint", False):
        from tony_tpu.analysis import srclint

        violations = srclint.lint_tree(srclint.default_root())
        for v in violations:
            print(f"LINT {v}")
        if violations:
            rc = 1
        else:
            print("[srclint] CLEAN")
    if getattr(args, "json", None):
        with open(args.json, "w") as fh:
            json.dump({r.tag: r.to_dict() for r in reports}, fh,
                      indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    return rc
