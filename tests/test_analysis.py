"""Static-analysis tier (tony_tpu.analysis): the jaxpr invariant analyzer
— shipped accum-step configs analyze CLEAN with their committed
step-signature pins, and every rule demonstrably FIRES on a seeded
violation (leaf-major gather outside the window, unplanned collective,
bf16 moment slot / bf16 reduction / f64, undonated state, signature
drift) with equation provenance. Plus the waiver mechanism, the profiler
report plumbing, and the pack-site source lint. `make tier1-analysis`
runs this file by marker."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from tony_tpu import analysis, profiler, train
from tony_tpu import parallel as par
from tony_tpu.analysis import cli as acli
from tony_tpu.analysis import rules, srclint
from tony_tpu.analysis import signature as sigmod
from tony_tpu.compat import shard_map
from tony_tpu.parallel import FSDP, overlap
from tony_tpu.parallel.sched import GatherPlan

pytestmark = pytest.mark.analysis

SIG_DIR = Path(__file__).parent / "signatures"

# Targets are trace-only but their construction jits param init — build
# each (config, donate) once per test session.
_TARGETS = {}


def target(name, donate=True):
    key = (name, donate)
    if key not in _TARGETS:
        _TARGETS[key] = acli.build_target(name, donate=donate)
    return _TARGETS[key]


def _seeded_zero3(evil_loss):
    """(closed_jaxpr, plan, gplan, expected) of a ZeRO-3 accum trace
    whose loss_fn is ``evil_loss`` — the seeded-violation surface."""
    stepper, state, batch = target("zero3")
    mesh = stepper.inspect(state)["mesh"]
    specs = overlap.fsdp_param_specs(state.params, mesh)
    plan, gplan = overlap.step_plans(state.params, mesh,
                                     bucket_bytes=32 << 10,
                                     param_specs=specs, prefetch=1)

    def loss(p, mb):
        logits = state.apply_fn({"params": p}, mb["x"])
        return train.cross_entropy_loss(logits, mb["y"]) \
            + evil_loss(p, mb)

    closed = jax.make_jaxpr(lambda s, b: overlap.microbatch_grads(
        loss, s.params, b, mesh, microbatches=4, bucket_bytes=32 << 10,
        param_specs=specs))(state, batch)
    expected = analysis.expected_accum_collectives(plan, gplan, mesh)
    return closed, plan, gplan, expected


class TestShippedConfigsClean:
    """THE acceptance gate: every shipped make_accum_train_step config
    analyzes clean — zero unwaived findings — and matches its COMMITTED
    step-signature pin (regenerate deliberately with
    TONY_UPDATE_SIGNATURES=1 + `tony analyze --update-signatures`, then
    review the diff)."""

    @pytest.mark.parametrize("name", acli.CONFIG_NAMES)
    def test_clean_with_pinned_signature(self, name):
        if name in acli._SERVE_CONFIGS:
            # The serving plane's decode/verify configs build through
            # their own targets (an engine, not an accum stepper) —
            # run_config is the shared entry both this gate and the CLI
            # use.
            report = acli.run_config(
                name, signature_path=SIG_DIR / f"{name}.json")
        else:
            stepper, state, batch = target(name)
            report = analysis.analyze_accum_step(
                stepper, state, batch, tag=name,
                signature_path=SIG_DIR / f"{name}.json")
        assert report.ok, report.summary()
        pinned = sigmod.load_signature(SIG_DIR / f"{name}.json")
        assert pinned is not None, "signature pin not committed"
        assert report.signature == pinned, "\n".join(
            sigmod.diff_signature(pinned, report.signature))

    def test_zero3_census_matches_plan(self):
        """The audit consumed a real plan, not an empty one: the census
        carries the 3 bucketed fwd gathers, 3 scatter reduce_scatters,
        and the intact 2-barrier prefetch chain."""
        stepper, state, batch = target("zero3")
        report = analysis.analyze_accum_step(stepper, state, batch)
        kinds = {}
        for c in report.collectives:
            kinds[c.kind] = kinds.get(c.kind, 0) + 1
        assert kinds["all_gather"] == 3
        assert kinds["reduce_scatter"] == 3
        assert report.signature["optimization_barriers"] == 2
        gplan = stepper.inspect(state)["gplan"]
        assert gplan.n_gather_buckets == 3
        # The window promise is a real bound: prefetch=1 -> the two
        # largest adjacent gathers, strictly less than the total.
        assert 0 < gplan.window_nbytes() < sum(gplan.gather_nbytes)

    def test_report_banked_in_profiler(self):
        profiler.reset_records("analysis")
        stepper, state, batch = target("zero3")
        analysis.analyze_accum_step(stepper, state, batch, tag="bank")
        rep = profiler.report("analysis")
        assert rep["bank"]["ok"] is True
        assert rep["bank"]["findings"] == 0
        assert rep["bank"]["eqns"] > 0
        # Same aliasing contract as every other report family: mutating
        # the snapshot must not poison the live registry.
        rep["bank"]["findings_by_rule"]["poison"] = 1
        assert "poison" not in \
            profiler.report("analysis")["bank"]["findings_by_rule"]


class TestReplicationLeak:
    def test_leaf_major_gather_outside_window_fires(self):
        """Rule 1 seeded violation: the loss gathers a FULL fsdp-sharded
        param leaf itself (leaf-major, outside the planned prefetch
        chain) — the finding is a replication_leak with the seeding
        site's equation provenance."""
        def evil(p, mb):
            leaf = jax.tree.leaves(p)[1]
            return jax.lax.all_gather(leaf, FSDP, tiled=True).sum() * 0

        closed, _plan, gplan, expected = _seeded_zero3(evil)
        report = analysis.analyze_jaxpr(closed, expected=expected,
                                        gplan=gplan)
        leaks = [f for f in report.findings
                 if f.rule == "replication_leak"
                 and f.kind == "unplanned_gather"]
        assert leaks, report.summary()
        assert "test_analysis" in leaks[0].provenance
        assert leaks[0].nbytes > 0

    def test_broken_prefetch_chain_fires(self):
        """Rule 1 structural half: a bucketed plan promising prefetch=1
        over a trace with NO optimization_barrier chain (the per-leaf
        trace stands in for a refactor that dropped the barriers)."""
        stepper, state, batch = target("per_leaf")
        info = stepper.inspect(state)
        traced = info["jitted"].trace(state, batch)
        findings = rules.check_prefetch_chain(
            traced.jaxpr, info["gplan"], "bucketed")
        assert findings
        assert findings[0].kind == "prefetch_chain_broken"

    def test_clean_trace_no_leak(self):
        closed, _plan, gplan, expected = _seeded_zero3(
            lambda p, mb: jnp.float32(0.0))
        report = analysis.analyze_jaxpr(closed, expected=expected,
                                        gplan=gplan)
        assert report.ok, report.summary()


class TestCollectiveAudit:
    def test_unplanned_all_to_all_fires(self):
        """Rule 2 seeded violation: an all_to_all no plane registered —
        unplanned_collective, provenance pointing at the seeding line."""
        def evil(p, mb):
            t = jax.lax.all_to_all(mb["x"].reshape(4, -1), FSDP,
                                   split_axis=0, concat_axis=1,
                                   tiled=True)
            return t.sum() * 0

        closed, _plan, gplan, expected = _seeded_zero3(evil)
        report = analysis.analyze_jaxpr(closed, expected=expected,
                                        gplan=gplan)
        hits = [f for f in report.findings
                if f.kind == "unplanned_collective"
                and "all_to_all" in f.message]
        assert hits, report.summary()
        assert "test_analysis" in hits[0].provenance

    def test_planned_missing_fires(self):
        """A planned transfer that never appears in the trace (stale
        plan) is reported too — the audit is two-sided."""
        closed, _plan, gplan, expected = _seeded_zero3(
            lambda p, mb: jnp.float32(0.0))
        expected = list(expected) + [rules.Expected(
            "all_gather", frozenset({FSDP}), 999424, 1, "fwd_gather",
            "phantom")]
        report = analysis.analyze_jaxpr(closed, expected=expected,
                                        gplan=gplan)
        assert any(f.kind == "planned_missing" and "phantom" in f.message
                   for f in report.findings), report.summary()

    def test_scalar_collectives_auto_accepted(self):
        """Loss/aux psums (4 B) never need waivers."""
        closed, _plan, gplan, expected = _seeded_zero3(
            lambda p, mb: jnp.float32(0.0))
        report = analysis.analyze_jaxpr(closed, expected=expected,
                                        gplan=gplan)
        assert not [f for f in report.findings
                    if f.rule == "collective_audit"]


class TestDtypePolicy:
    def test_bf16_reduction_fires(self):
        """Rule 3 seeded violation: a psum carrying bf16 — reductions
        must accumulate in f32."""
        mesh = par.make_mesh()

        def spmd(x):
            return jax.lax.psum(x, ("data",))

        closed = jax.make_jaxpr(shard_map(
            spmd, mesh, in_specs=(P(),), out_specs=P()))(
                jnp.ones((8, 4), jnp.bfloat16))
        hits = [f for f in rules.dtype_findings(closed)
                if f.kind == "low_precision_reduction"]
        assert hits
        assert "psum" in hits[0].message

    def test_jnp_sum_of_bf16_is_legal(self):
        """jnp.sum upcasts its accumulator to f32 in the jaxpr — the
        rule must accept that (it gates the CARRY dtype, not inputs)."""
        closed = jax.make_jaxpr(lambda x: jnp.sum(x, axis=0))(
            jnp.ones((8, 4), jnp.bfloat16))
        assert not rules.dtype_findings(closed)

    def test_f64_promotion_fires(self):
        with jax.enable_x64(True):
            closed = jax.make_jaxpr(lambda x: x * 2.0)(
                np.ones((4,), np.float64))
        hits = [f for f in rules.dtype_findings(closed)
                if f.kind == "f64_promotion"]
        assert hits

    def test_int8_carried_reduction_fires(self):
        """Rule 3 seeded violation (quantized lane): a psum carrying
        int8 — narrow integer reductions saturate; int8 rides only
        non-accumulating collectives like the quantized gather."""
        mesh = par.make_mesh()

        def spmd(x):
            return jax.lax.psum(x, ("data",))

        closed = jax.make_jaxpr(shard_map(
            spmd, mesh, in_specs=(P(),), out_specs=P()))(
                jnp.ones((8, 4), jnp.int8))
        hits = [f for f in rules.dtype_findings(closed)
                if f.kind == "int_carried_reduction"]
        assert hits
        assert "int8" in hits[0].message and "psum" in hits[0].message

    def test_int8_narrow_accumulation_fires(self):
        """Rule 3 seeded violation: an int8×int8 dot_general without
        preferred_element_type=int32 accumulates in int8."""
        closed = jax.make_jaxpr(lambda a, b: jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ()))))(
                jnp.ones((4, 8), jnp.int8), jnp.ones((8, 4), jnp.int8))
        hits = [f for f in rules.dtype_findings(closed)
                if f.kind == "narrow_int_accumulation"]
        assert hits
        assert "int32" in hits[0].message

    def test_quant_dot_int32_accumulation_blessed(self):
        """The quantized lane's pattern — int8→int32 dot_general with
        f32 rescale — passes rule 3 with ZERO findings (including the
        quantize round/clip and the rescale casts)."""
        from tony_tpu.ops import quant as quant_mod

        closed = jax.make_jaxpr(
            lambda x, w: quant_mod.quant_dot(x, w, impl="xla"))(
                jnp.ones((8, 16), jnp.float32),
                jnp.ones((16, 8), jnp.float32))
        assert not rules.dtype_findings(closed)

    def test_bf16_moment_slot_fires(self):
        """Rule 3 seeded violation: one fused moment-slot bucket cast to
        bf16 — the finding names the exact slot and bucket."""
        _stepper, state, _batch = target("fused_bucket")
        slots = {n: list(bufs)
                 for n, bufs in state.opt_state["slots"].items()}
        slots["mu"][1] = slots["mu"][1].astype(jnp.bfloat16)
        bad = state.replace(opt_state={**state.opt_state,
                                       "slots": slots})
        hits = [f for f in rules.opt_state_findings(bad)
                if f.kind == "non_f32_moments"]
        assert len(hits) == 1
        assert "'mu'" in hits[0].provenance and "[1]" in hits[0].provenance

    def test_f32_slots_clean(self):
        _stepper, state, _batch = target("fused_bucket")
        assert not rules.opt_state_findings(state)


class TestDonation:
    def test_undonated_state_fires_with_byte_cost(self):
        """Rule 4 seeded violation: donate=False — the finding names the
        state argument and its byte cost."""
        stepper, state, batch = acli.build_target("zero3", donate=False)
        report = analysis.analyze_accum_step(stepper, state, batch,
                                             tag="nodonate")
        hits = [f for f in report.findings
                if f.kind == "undonated_argument"]
        assert len(hits) == 1, report.summary()
        assert "'state'" in hits[0].message
        total = sum(
            int(np.prod(np.shape(leaf), dtype=np.int64))
            * np.dtype(leaf.dtype).itemsize
            for leaf in jax.tree.leaves(state)
            if hasattr(leaf, "dtype"))   # step=0 is a python int leaf
        assert hits[0].nbytes == total

    def test_donation_shrinks_live_high_water(self):
        """The satellite's before/after: donating the state (params +
        bucket-resident opt slots) measurably lowers the live-buffer
        estimate, because XLA may alias the update into the inputs."""
        stepper_n, state_n, batch_n = acli.build_target("zero3",
                                                        donate=False)
        hw_n = analysis.analyze_accum_step(
            stepper_n, state_n, batch_n,
            tag="hw_n").signature["live_high_water_nbytes"]
        stepper_d, state_d, batch_d = target("zero3")
        hw_d = analysis.analyze_accum_step(
            stepper_d, state_d, batch_d,
            tag="hw_d").signature["live_high_water_nbytes"]
        assert hw_d < hw_n


class TestWaivers:
    def test_waiver_accepts_named_finding(self):
        def evil(p, mb):
            t = jax.lax.all_to_all(mb["x"].reshape(4, -1), FSDP,
                                   split_axis=0, concat_axis=1,
                                   tiled=True)
            return t.sum() * 0

        closed, _plan, gplan, expected = _seeded_zero3(evil)
        waiver = analysis.Waiver(
            rule="collective_audit", match="all_to_all",
            reason="seeded a2a accepted for this test")
        report = analysis.analyze_jaxpr(closed, expected=expected,
                                        gplan=gplan, waivers=[waiver])
        assert report.ok, report.summary()
        assert any(f.waived and f.waived_by == waiver.reason
                   for f in report.waived)

    def test_waiver_does_not_overmatch(self):
        """A waiver for another rule must not swallow the finding."""
        def evil(p, mb):
            t = jax.lax.all_to_all(mb["x"].reshape(4, -1), FSDP,
                                   split_axis=0, concat_axis=1,
                                   tiled=True)
            return t.sum() * 0

        closed, _plan, gplan, expected = _seeded_zero3(evil)
        report = analysis.analyze_jaxpr(
            closed, expected=expected, gplan=gplan,
            waivers=[analysis.Waiver(rule="dtype_policy",
                                     match="all_to_all", reason="wrong")])
        assert not report.ok


class TestSignature:
    def test_drift_detected(self, tmp_path):
        """Rule 5 seeded violation: a pinned signature whose eqn count
        drifted — the finding carries the per-key diff."""
        stepper, state, batch = target("zero3")
        good = analysis.analyze_accum_step(stepper, state,
                                           batch).signature
        drifted = dict(good)
        drifted["eqns"] = good["eqns"] - 17
        sigmod.save_signature(tmp_path / "pin.json", drifted)
        report = analysis.analyze_accum_step(
            stepper, state, batch,
            signature_path=tmp_path / "pin.json")
        hits = [f for f in report.findings
                if f.kind == "signature_drift"]
        assert hits and "eqns" in hits[0].message

    def test_missing_pin_is_drift(self, tmp_path):
        lines = sigmod.check_signature({"eqns": 1},
                                       tmp_path / "absent.json")
        assert lines and "TONY_UPDATE_SIGNATURES" in lines[0]

    def test_update_env_rewrites(self, tmp_path, monkeypatch):
        monkeypatch.setenv(sigmod.UPDATE_ENV, "1")
        assert sigmod.check_signature({"eqns": 1},
                                      tmp_path / "new.json") == []
        assert sigmod.load_signature(tmp_path / "new.json") == {"eqns": 1}

    def test_signature_deterministic(self):
        stepper, state, batch = target("bucketed")
        info = stepper.inspect(state)
        a = sigmod.step_signature(info["jitted"].trace(state,
                                                       batch).jaxpr)
        b = sigmod.step_signature(info["jitted"].trace(state,
                                                       batch).jaxpr)
        assert a == b


class TestSrclint:
    def test_naked_concat_flagged(self):
        src = "import jax.numpy as jnp\n\ndef f(xs):\n" \
              "    return jnp.concatenate(xs)\n"
        hits = srclint.lint_source(src, "models/foo.py", "foo.py")
        assert len(hits) == 1
        assert "jnp.concatenate" in str(hits[0])

    def test_jax_numpy_spelling_and_stack_flagged(self):
        src = "import jax\n\ndef f(xs):\n" \
              "    return jax.numpy.stack(xs)\n"
        assert srclint.lint_source(src, "train/foo.py", "foo.py")

    def test_pragma_blesses_site(self):
        src = "import jax.numpy as jnp\n\ndef f(xs):\n" \
              "    # packsite: region-local — per-device shard buffers\n" \
              "    return jnp.concatenate(xs)\n"
        assert not srclint.lint_source(src, "models/foo.py", "foo.py")

    def test_approved_pack_planes_pass(self):
        src = "import jax.numpy as jnp\nx = jnp.concatenate([])\n"
        assert not srclint.lint_source(src, "parallel/overlap.py", "o.py")
        assert not srclint.lint_source(src, "ckpt/format.py", "f.py")
        assert srclint.lint_source(src, "parallel/sched.py", "s.py")

    def test_host_numpy_never_flagged(self):
        src = "import numpy as np\nx = np.concatenate([])\n"
        assert not srclint.lint_source(src, "train/foo.py", "foo.py")

    def test_pragma_never_blesses_later_statement(self):
        """A pragma blesses ONLY its own call — an unaudited concat
        stacked right below an audited one must still be flagged."""
        src = ("import jax.numpy as jnp\n\ndef f(xs, ys):\n"
               "    # packsite: region-local — audited site\n"
               "    a = jnp.concatenate(xs)\n"
               "    b = jnp.concatenate(ys)\n"
               "    return a, b\n")
        hits = srclint.lint_source(src, "models/foo.py", "foo.py")
        assert len(hits) == 1 and hits[0].line == 6

    def test_explicit_file_and_subdir_keep_allowlist(self):
        """Linting one approved file (or its parent dir) directly must
        still resolve the package-relative allowlist path."""
        root = srclint.default_root()
        assert not srclint.lint_file(root / "parallel" / "overlap.py",
                                     root / "parallel")
        assert not srclint.lint_tree(root / "parallel")

    def test_package_tree_lints_clean(self):
        """The shipped tree carries no unaudited pack sites — the gate
        `make lint` enforces, pinned here so tier-1 catches it too."""
        assert srclint.lint_tree(srclint.default_root()) == []


class TestCliEntry:
    def test_tony_analyze_runs_clean(self, tmp_path):
        from tony_tpu.cli import main

        out = tmp_path / "report.json"
        rc = main(["analyze", "--config", "zero3",
                   "--signatures", str(SIG_DIR), "--json", str(out)])
        assert rc == 0
        data = json.loads(out.read_text())
        assert data["zero3"]["ok"] is True
        assert data["zero3"]["signature"]["eqns"] > 0

    def test_unknown_config_rejected(self):
        with pytest.raises(ValueError, match="unknown analyze config"):
            acli.build_target("nope")

    def test_update_signatures_needs_dir_and_restores_env(self, tmp_path,
                                                          monkeypatch):
        """--update-signatures without --signatures is a loud error, and
        a successful update run must not leak TONY_UPDATE_SIGNATURES into
        the process (it would neuter every later drift check)."""
        from tony_tpu.cli import main

        monkeypatch.delenv(sigmod.UPDATE_ENV, raising=False)
        assert main(["analyze", "--config", "zero3",
                     "--update-signatures"]) == 2
        sigs = tmp_path / "sigs"
        assert main(["analyze", "--config", "zero3", "--signatures",
                     str(sigs), "--update-signatures"]) == 0
        assert sigmod.UPDATE_ENV not in __import__("os").environ
        assert sigmod.load_signature(sigs / "zero3.json") \
            == sigmod.load_signature(SIG_DIR / "zero3.json")


class TestGatherPlanWindow:
    def test_window_nbytes_semantics(self):
        stepper, state, _batch = target("zero3")
        gplan = stepper.inspect(state)["gplan"]
        sizes = gplan.gather_nbytes
        # prefetch=1: the largest adjacent pair.
        assert gplan.window_nbytes() == max(
            sizes[k] + sizes[k + 1] for k in range(len(sizes) - 1))
        eager = GatherPlan.from_buckets(gplan.plan, prefetch=0)
        assert eager.window_nbytes() == sum(sizes)
