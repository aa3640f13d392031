"""The analyzer engine: report type and the two analyze entries.

Loaded lazily through the :mod:`tony_tpu.analysis` facade (PEP 562) so
jax-free consumers — the AST source lint, the CLI bootstrap that must set
XLA env vars BEFORE jax initializes — can import the package without
paying (or breaking on) a jax import. See the package docstring for the
rule-suite overview.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax

from tony_tpu import profiler
from tony_tpu.analysis import jaxprwalk, rules, signature
from tony_tpu.analysis.jaxprwalk import (CollectiveEqn, collect_collectives,
                                         live_high_water)
from tony_tpu.analysis.rules import (SCALAR_NBYTES, Expected, Finding,
                                     Waiver, apply_waivers,
                                     expected_accum_collectives)
from tony_tpu.analysis.signature import (check_signature, diff_signature,
                                         step_signature)

__all__ = [
    "AnalysisReport", "CollectiveEqn", "Expected", "Finding", "Waiver",
    "analyze_accum_step", "analyze_jaxpr", "analyze_serve_step",
    "apply_waivers", "check_signature", "collect_collectives",
    "diff_signature", "expected_accum_collectives", "live_high_water",
    "step_signature",
]

# Trace-time side channel into the profiler's plan registry.
_record = functools.partial(profiler.record, "analysis")


@dataclass(frozen=True)
class AnalysisReport:
    """One analyzed step: active findings (the gate fails on any), waived
    findings (accepted, with reasons), the full collective census, the
    signature digest, and the config metadata the run saw."""

    tag: str
    findings: Tuple[Finding, ...]
    waived: Tuple[Finding, ...]
    collectives: Tuple[CollectiveEqn, ...]
    signature: Dict[str, Any]
    config: Dict[str, Any]

    @property
    def ok(self) -> bool:
        return not self.findings

    def to_dict(self) -> Dict[str, Any]:
        return {
            "tag": self.tag, "ok": self.ok,
            "findings": [f.to_dict() for f in self.findings],
            "waived": [f.to_dict() for f in self.waived],
            "collectives": [
                {"kind": c.kind, "axes": list(c.axes), "nbytes": c.nbytes,
                 "path": c.path, "index": c.index, "src": c.src}
                for c in self.collectives],
            "signature": dict(self.signature),
            "config": dict(self.config),
        }

    def summary(self) -> str:
        lines = [f"[{self.tag}] {'CLEAN' if self.ok else 'FINDINGS'}: "
                 f"{len(self.findings)} finding(s), {len(self.waived)} "
                 f"waived, {len(self.collectives)} collective eqn(s), "
                 f"{self.signature.get('eqns', 0)} eqns, live high-water "
                 f"~{self.signature.get('live_high_water_nbytes', 0)} B"]
        for f in self.findings:
            lines.append(f"  {f.severity.upper()} {f.rule}/{f.kind}: "
                         f"{f.message}"
                         + (f"\n    at {f.provenance}" if f.provenance
                            else ""))
        for f in self.waived:
            lines.append(f"  waived {f.rule}/{f.kind} ({f.waived_by}): "
                         f"{f.message}")
        return "\n".join(lines)


def _bank(report: AnalysisReport) -> None:
    by_rule: Dict[str, int] = {}
    for f in report.findings:
        by_rule[f.rule] = by_rule.get(f.rule, 0) + 1
    _record(report.tag, ok=report.ok, findings=len(report.findings),
            findings_by_rule=by_rule, waived=len(report.waived),
            collectives=dict(report.signature.get("collectives", {})),
            eqns=report.signature.get("eqns", 0),
            live_high_water_nbytes=report.signature.get(
                "live_high_water_nbytes", 0),
            config=dict(report.config))


def _jaxpr_findings(closed: Any, *, expected: Sequence[Expected],
                    gplan: Optional[Any], gather: str,
                    state: Optional[Any],
                    scalar_nbytes: int = SCALAR_NBYTES
                    ) -> Tuple[List[CollectiveEqn], List[Finding]]:
    """The jaxpr-side rules (1–3), shared by both analyze entries so a
    new rule can never land in one and silently miss the other."""
    colls = collect_collectives(closed)
    findings: List[Finding] = []
    findings += rules.reconcile_collectives(colls, expected,
                                            scalar_nbytes=scalar_nbytes)
    findings += rules.check_prefetch_chain(closed, gplan, gather)
    findings += rules.dtype_findings(closed)
    if state is not None:
        findings += rules.opt_state_findings(state)
    return colls, findings


def analyze_jaxpr(closed: Any, *, expected: Sequence[Expected] = (),
                  gplan: Optional[Any] = None, gather: str = "bucketed",
                  state: Optional[Any] = None,
                  donated: Optional[Sequence[bool]] = None,
                  waivers: Sequence[Waiver] = (), tag: str = "jaxpr",
                  scalar_nbytes: int = SCALAR_NBYTES,
                  config: Optional[Dict[str, Any]] = None
                  ) -> AnalysisReport:
    """Run the jaxpr-side rules (1–3 + signature) over one closed jaxpr —
    the seeded-violation test surface and the building block of
    :func:`analyze_accum_step` (which adds donation, rule 4, from the
    traced function's metadata)."""
    colls, findings = _jaxpr_findings(
        closed, expected=expected, gplan=gplan, gather=gather,
        state=state, scalar_nbytes=scalar_nbytes)
    active, waived = apply_waivers(findings, waivers)
    report = AnalysisReport(
        tag=tag, findings=tuple(active), waived=tuple(waived),
        collectives=tuple(colls),
        signature=step_signature(closed, donated, collectives=colls),
        config=dict(config or {}))
    _bank(report)
    return report


def analyze_serve_step(engine: Any, *, waivers: Sequence[Waiver] = (),
                       tag: str = "serve",
                       signature_path: Optional[str] = None,
                       batch: Optional[int] = None,
                       step: str = "decode") -> AnalysisReport:
    """Analyze a :class:`tony_tpu.serve.ServeEngine` decode step — the
    serving plane's day-one planner registration made auditable.

    Uses the engine's ``decode_traced`` hook (the same jit the loop
    runs) and reconciles the traced program against the engine's
    planner-registered expected collective set — which is EMPTY: a
    replica's decode must issue zero inter-chip collectives (its mesh
    shards memory, never cross-replica math), so any GSPMD-inserted
    reshard/gather surfaces as a rule-2 finding, not a latency mystery.
    Dtype policy (rule 3) and donation (rule 4 — the KV pools must be
    donated or every step doubles the cache's residency) run as on the
    accum steps; ``signature_path`` pins the digest (rule 5).

    ``step="verify"`` audits a :class:`tony_tpu.serve.SpecEngine`'s
    one-launch k-token verification through its ``verify_traced`` hook
    instead — the same rule suite over the speculative lane's hot path
    (zero collectives on a replica mesh, KV-pool donation, pinned
    signature), with the spec geometry in the report config.

    ``step="prefill"`` audits the chunked-prefill launch through
    ``prefill_traced`` — the ``(1, prefill_chunk)`` shape every
    non-final chunk rides. The ``route`` config pins it: chunked
    prefill must introduce no compiled step shape beyond the declared
    chunk geometry, and that program must satisfy the identical
    replica-step invariants (zero inter-chip collectives, donated KV
    pools)."""
    if step == "verify":
        jitted, args = engine.verify_traced(batch)
    elif step == "prefill":
        jitted, args = engine.prefill_traced()
    elif step == "decode":
        jitted, args = engine.decode_traced(batch)
    else:
        raise ValueError(f"unknown serve step {step!r} "
                         f"(decode|verify|prefill)")
    traced = jitted.trace(*args)
    closed = traced.jaxpr
    donate_argnums = tuple(getattr(traced, "donate_argnums", ()) or ())
    donated = _donated_flags(args, donate_argnums)
    if len(donated) != len(closed.jaxpr.invars):
        donated = None                    # static args shifted the map
    colls, findings = _jaxpr_findings(
        closed, expected=engine.expected_collectives(), gplan=None,
        gather="bucketed", state=None)
    # Donation (rule 4), flat-aware: traced.donate_argnums indexes FLAT
    # invars here (params flattens ahead of the pools), so resolve each
    # pool argument's flat span and require every position donated.
    arg_names = ("params", "pool_k", "pool_v", "tokens", "positions",
                 "tables", "flat_idx")
    spans = []
    pos = 0
    for a in args:
        n = len(jax.tree_util.tree_leaves(a))
        spans.append((pos, pos + n))
        pos += n
    donated_set = set(donate_argnums)
    for argnum in (1, 2):
        lo, hi = spans[argnum]
        if not all(i in donated_set for i in range(lo, hi)):
            nbytes = sum(jaxprwalk.aval_nbytes(l) for l in
                         jax.tree_util.tree_leaves(args[argnum]))
            findings.append(Finding(
                rule="donation", kind="undonated_argument",
                severity="error",
                message=(f"argument {argnum} ({arg_names[argnum]!r}, "
                         f"{nbytes} B) is not donated — every decode "
                         f"step would double the KV pool's residency"),
                provenance=f"donate_argnums={donate_argnums}"))
    sig = step_signature(closed, donated, collectives=colls)
    if signature_path is not None:
        for line in check_signature(sig, signature_path):
            findings.append(Finding(
                rule="signature", kind="signature_drift",
                severity="error",
                message=f"step signature drifted from the committed pin: "
                        f"{line}",
                provenance=str(signature_path)))
    active, waived = apply_waivers(findings, waivers)
    config = {
        "plane": f"serve_{step}", "ctx_pad": engine.ctx_pad,
        "block_size": engine.block_size, "q_block": engine.q_block,
        "n_blocks": engine.cache.n_blocks,
        "decode_buckets": list(engine.decode_buckets),
        "donate_argnums": list(donate_argnums),
    }
    if step == "verify":
        config["spec_k"] = int(engine.spec_k)
        config["draft"] = getattr(engine.draft, "kind", "?")
    if step == "prefill":
        config["prefill_chunk"] = engine.prefill_chunk
        config["prefix_cache"] = bool(engine.prefix_cache)
    report = AnalysisReport(
        tag=tag, findings=tuple(active), waived=tuple(waived),
        collectives=tuple(colls), signature=sig, config=config)
    _bank(report)
    return report


def _donated_flags(args: Sequence[Any],
                   donate_argnums: Sequence[int]) -> List[bool]:
    flags: List[bool] = []
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        flags.extend([i in donate_argnums] * n)
    return flags


def analyze_accum_step(stepper: Any, state: Any, batch: Any, *,
                       waivers: Sequence[Waiver] = (), tag: str = "accum",
                       expect_donated: Sequence[int] = (0,),
                       signature_path: Optional[str] = None
                       ) -> AnalysisReport:
    """THE top-level entry: analyze a ``make_accum_train_step`` stepper
    against the plans it will execute for ``state``'s layout.

    Uses the stepper's ``inspect(state)`` hook to recover the jitted
    step, the :class:`~tony_tpu.parallel.overlap.GradBuckets` /
    :class:`~tony_tpu.parallel.sched.GatherPlan` pair, and the config
    knobs; traces (never executes) the step; runs all five rules; banks
    the result into ``profiler.report("analysis")``. ``signature_path``
    additionally pins the digest against a committed snapshot
    (rule 5 — drift becomes a finding)."""
    info = stepper.inspect(state)
    traced = info["jitted"].trace(state, batch)
    closed = traced.jaxpr
    expected = expected_accum_collectives(
        info["plan"], info["gplan"], info["mesh"], gather=info["gather"],
        reduce_op=info["reduce_op"], hierarchy=info["hierarchy"],
        update=info["update"], fused=info.get("fused"),
        quant=bool(info.get("quant")))
    donate_argnums = tuple(getattr(traced, "donate_argnums", ()) or ())
    donated = _donated_flags((state, batch), donate_argnums)
    if len(donated) != len(closed.jaxpr.invars):
        donated = None                    # static args shifted the map
    colls, findings = _jaxpr_findings(
        closed, expected=expected, gplan=info["gplan"],
        gather=info["gather"], state=state)
    findings += rules.donation_findings(traced, (state, batch),
                                        ("state", "batch"),
                                        expect_donated=expect_donated)
    sig = step_signature(closed, donated, collectives=colls)
    if signature_path is not None:
        for line in check_signature(sig, signature_path):
            findings.append(Finding(
                rule="signature", kind="signature_drift",
                severity="error",
                message=f"step signature drifted from the committed pin: "
                        f"{line}",
                provenance=str(signature_path)))
    active, waived = apply_waivers(findings, waivers)
    config = {k: info[k] for k in ("update", "gather", "reduce_op",
                                   "hierarchy", "microbatches",
                                   "bucket_bytes", "donate", "quant")
              if k in info}
    config["donate_argnums"] = list(donate_argnums)
    report = AnalysisReport(
        tag=tag, findings=tuple(active), waived=tuple(waived),
        collectives=tuple(colls), signature=sig, config=config)
    _bank(report)
    return report
