"""Collective-scheduler tier (tony_tpu.parallel.sched): bucketed +
prefetched ZeRO-3 forward gathers pinned bit-exact against the per-leaf
path, the static gather schedule (the hoisted spec test), MoE explicit
per-capacity-chunk all_to_all vs the GSPMD einsum path, pipeline-edge
registration, and the unified collective record schema — on the virtual
8-device CPU mesh. `make tier1-sched` runs this file by marker."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from tony_tpu import parallel as par
from tony_tpu import profiler, train
from tony_tpu.compat import shard_map
from tony_tpu.models import get_model
from tony_tpu.models.moe import MoEMLP
from tony_tpu.parallel import overlap, sched
from tony_tpu.parallel.overlap import GradBuckets
from tony_tpu.parallel.sched import GatherPlan, moe_dispatch_ffn_combine
from tony_tpu.train import fsdp_shard_state

pytestmark = pytest.mark.sched


def _mixed_tree():
    """Sharded + uneven-sharded + replicated + scalar leaves — the full
    menu the gather schedule must sort statically."""
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    params = {"w": jax.random.normal(k[0], (8, 16)),    # even: 8 % 4 == 0
              "u": jax.random.normal(k[1], (6, 16)),    # uneven: 6 % 4
              "b": jax.random.normal(k[2], (16,)),      # replicated
              "s": jnp.float32(0.5)}                    # scalar
    specs = {"w": P("fsdp"), "u": P("fsdp"), "b": P(), "s": P()}
    return params, specs


class TestGatherPlan:
    def test_static_schedule_from_mixed_tree(self):
        """Satellite pin (gather_params hoist): which leaves gather, on
        which dim, in which bucket is resolved at BUILD time — scalars,
        replicated, and uneven leaves land in the static passthrough
        list, never in the traced branch."""
        params, specs = _mixed_tree()
        plan = GradBuckets.plan_sharded(params, specs, shard_size=4,
                                        bucket_bytes=1 << 20)
        gp = GatherPlan.from_buckets(plan, prefetch=1)
        leaves = jax.tree.leaves(params)
        names = sorted(params)                    # flatten order: b,s,u,w
        i_w = names.index("w")
        assert gp.gather_leaves == ((i_w, 0),)
        assert sorted(gp.passthrough) == [i for i in range(len(leaves))
                                          if i != i_w]
        # Only even scatter buckets are gatherable; the padded (uneven)
        # bucket is not.
        assert all(plan._is_scatter(b) and not plan._is_padded(b)
                   for b in gp.gather_buckets)
        assert gp.n_gather_buckets == 1
        assert gp.gather_nbytes == (8 * 16 * 4,)

    def test_rejects_negative_prefetch(self):
        plan = GradBuckets.plan({"w": jnp.zeros((8, 4))}, 1 << 20)
        with pytest.raises(ValueError, match="prefetch"):
            GatherPlan.from_buckets(plan, prefetch=-1)

    def test_plain_plan_has_no_gather_buckets(self):
        plan = GradBuckets.plan({"w": jnp.zeros((8, 4))}, 1 << 20)
        gp = GatherPlan.from_buckets(plan)
        assert gp.n_gather_buckets == 0 and gp.gather_leaves == ()

    @pytest.mark.parametrize("prefetch", [0, 1, 2])
    def test_gather_bitexact_vs_per_leaf(self, prefetch):
        """THE data-movement pin: bucketed gathers (any prefetch depth)
        reproduce every sharded leaf bit-exactly."""
        mesh = par.make_mesh(fsdp=4)
        k = jax.random.split(jax.random.PRNGKey(0), 6)
        params = {f"w{i}": jax.random.normal(k[i], (8, 4 + i))
                  for i in range(6)}
        specs = jax.tree.map(lambda _: P("fsdp"), params)
        plan = GradBuckets.plan_sharded(params, specs, shard_size=4,
                                        bucket_bytes=512)
        assert plan.n_scatter_buckets > 1      # several gather buckets
        gp = GatherPlan.from_buckets(plan, prefetch=prefetch)
        region = jax.tree.map(lambda _: P("fsdp"), params)

        def spmd(p):
            return gp.gather(jax.tree.leaves(p))

        out = jax.jit(shard_map(spmd, mesh, in_specs=(region,),
                                out_specs=[P()] * 6))(params)
        for a, b in zip(out, jax.tree.leaves(params)):
            np.testing.assert_array_equal(np.asarray(jax.device_get(a)),
                                          np.asarray(b))


class TestZero3ForwardGathers:
    def _setup(self, hidden=64):
        mesh = par.make_mesh(fsdp=4)
        model = get_model("mnist-mlp", hidden=hidden)
        kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(kx, (32, 784))
        y = jax.random.randint(ky, (32,), 0, 10)
        state = fsdp_shard_state(
            train.create_train_state(model, optax.sgd(0.1), x, kr), mesh)
        return mesh, state, {"x": x, "y": y}

    def test_bucketed_bitexact_vs_per_leaf(self):
        """THE acceptance pin: ZeRO-3 train-step numerics with bucketed +
        prefetched gathers are BIT-exact against the pre-refactor per-leaf
        path (bucketing is pure data movement)."""
        mesh, state, batch = self._setup()
        specs = overlap.fsdp_param_specs(state.params, mesh)

        def loss_fn(p, mb):
            logits = state.apply_fn({"params": p}, mb["x"])
            return train.cross_entropy_loss(logits, mb["y"])

        def run(mode, prefetch=1):
            # jit: one compile per variant instead of one per eager op.
            return jax.jit(lambda p, b: overlap.microbatch_grads(
                loss_fn, p, b, mesh, microbatches=4,
                bucket_bytes=32 * 1024, param_specs=specs, gather=mode,
                prefetch=prefetch))(state.params, batch)

        l_p, g_p = run("per_leaf")
        for prefetch in (0, 1, 2):
            l_b, g_b = run("bucketed", prefetch)
            assert float(l_b) == float(l_p)
            for a, b in zip(jax.tree.leaves(g_b), jax.tree.leaves(g_p)):
                np.testing.assert_array_equal(
                    np.asarray(jax.device_get(a)),
                    np.asarray(jax.device_get(b)))

    def test_accum_step_gather_modes_match_monolithic(self):
        mesh, state, batch = self._setup()
        mono = train.make_train_step(mesh=mesh, donate=False)
        s1, m1 = mono(state, batch)
        for mode in ("bucketed", "per_leaf"):
            step = train.make_accum_train_step(
                mesh=mesh, microbatches=4, bucket_bytes=32 * 1024,
                gather=mode, donate=False)
            s2, m2 = step(state, batch)
            assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
            for a, b in zip(jax.tree.leaves(s1.params),
                            jax.tree.leaves(s2.params)):
                np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                           np.asarray(jax.device_get(b)),
                                           atol=1e-5)

    def test_rejects_unknown_gather_mode(self):
        mesh, state, batch = self._setup()
        step = train.make_accum_train_step(
            mesh=mesh, microbatches=4, gather="bogus", donate=False)
        with pytest.raises(ValueError, match="gather"):
            step(state, batch)

    def test_mixed_tree_regression(self):
        """Satellite pin (gather_params fix): a params tree mixing
        sharded, uneven-sharded, replicated, and SCALAR leaves goes
        through the ZeRO-3 path and matches full-batch jax.grad."""
        params, specs = _mixed_tree()
        mesh = par.make_mesh(fsdp=4)
        kb = jax.random.split(jax.random.PRNGKey(8), 2)
        batch = {"x": jax.random.normal(kb[0], (32, 16)),
                 "y": jax.random.normal(kb[1], (32, 6))}

        def loss_fn(p, mb):
            out = mb["x"] @ (p["w"].T @ jnp.ones((8, 6)) @ p["u"]
                             + jnp.diag(p["b"])) * p["s"]
            return jnp.mean((out[:, :6] - mb["y"]) ** 2)

        for mode in ("bucketed", "per_leaf"):
            loss, grads = jax.jit(lambda p, b: overlap.microbatch_grads(
                loss_fn, p, b, mesh, microbatches=4,
                bucket_bytes=1 << 20, param_specs=specs,
                gather=mode))(params, batch)
            ref_loss, ref = jax.jit(jax.value_and_grad(
                lambda p: loss_fn(p, batch)))(params)
            # Loss runs ~2e2 here: scale the tolerance (fp reassociation
            # of the microbatch sum), ~1e-7 relative.
            assert abs(float(loss) - float(ref_loss)) \
                < 1e-5 * max(1.0, abs(float(ref_loss)))
            assert np.ndim(jax.device_get(grads["s"])) == 0
            # Microbatched vs full-batch are two programs: grads run up
            # to ~6e2 (d/ds), where a few f32 ulp of reassociation is
            # ~3e-7 relative — hence rtol 1e-6 beside the small-value
            # atol.
            for k in ("w", "u", "b", "s"):
                np.testing.assert_allclose(
                    np.asarray(jax.device_get(grads[k])),
                    np.asarray(ref[k]), rtol=1e-6, atol=1e-4)

    def test_fwd_gather_recorded(self):
        mesh, state, batch = self._setup()
        step = train.make_accum_train_step(
            mesh=mesh, microbatches=4, bucket_bytes=32 * 1024,
            prefetch=2, donate=False)
        profiler.reset_records("collective")
        step(state, batch)
        rec = profiler.report("collective")["accum.fwd_gather"]
        assert rec["kind"] == "all_gather"
        assert rec["plane"] == "fwd_gather"
        assert rec["axes"] == ["fsdp"]
        assert rec["gather"] == "bucketed" and rec["prefetch"] == 2
        assert sum(rec["nbytes"]) > 0


class TestPlanShardedEdgeCases:
    """Satellite pins on the bucket planner itself."""

    def test_single_leaf_larger_than_bucket_bytes(self):
        """One leaf bigger than the threshold gets a scatter bucket of its
        own (nowhere smaller to go) and still round-trips shard-major."""
        params = {"big": jnp.arange(64 * 16, dtype=jnp.float32
                                    ).reshape(64, 16),
                  "small": jnp.ones((8, 4))}
        specs = {"big": P("fsdp"), "small": P("fsdp")}
        plan = GradBuckets.plan_sharded(params, specs, shard_size=4,
                                        bucket_bytes=1024)
        assert plan.n_buckets == 2
        [b_big] = [b for b in range(plan.n_buckets)
                   if plan.bucket_nbytes[b] > plan.threshold]
        assert plan.buckets[b_big] == (0,)         # flatten: big, small
        bufs = plan.pack(params)
        out = plan.leaf_buffers(b_big, bufs[b_big], layout="gathered")
        np.testing.assert_array_equal(np.asarray(out[0]),
                                      np.asarray(params["big"]))

    def test_pure_replicated_tree_falls_back_to_unsharded_plan(self):
        """Zero fsdp-sharded leaves: plan_sharded must degrade to the
        plain plan (no scatter buckets), not crash — and the accum engine
        must run it end to end."""
        k = jax.random.split(jax.random.PRNGKey(1), 2)
        params = {"a": jax.random.normal(k[0], (8, 4)),
                  "b": jax.random.normal(k[1], (16,))}
        specs = jax.tree.map(lambda _: P(), params)
        plan = GradBuckets.plan_sharded(params, specs, shard_size=4,
                                        bucket_bytes=1 << 20)
        base = GradBuckets.plan(params, 1 << 20)
        assert plan.n_scatter_buckets == 0
        assert plan.buckets == base.buckets
        assert plan.bucket_nbytes == base.bucket_nbytes
        assert GatherPlan.from_buckets(plan).n_gather_buckets == 0

        mesh = par.make_mesh(fsdp=4)
        kb = jax.random.split(jax.random.PRNGKey(2), 2)
        batch = {"x": jax.random.normal(kb[0], (32, 8)),
                 "y": jax.random.normal(kb[1], (32, 4))}

        def loss_fn(p, mb):
            return jnp.mean((mb["x"] @ p["a"] + p["b"][:4]
                             - mb["y"]) ** 2)

        loss, grads = jax.jit(lambda p, b: overlap.microbatch_grads(
            loss_fn, p, b, mesh, microbatches=4,
            bucket_bytes=1 << 20, param_specs=specs))(params, batch)
        ref_loss, ref = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, batch)))(params)
        assert abs(float(loss) - float(ref_loss)) < 1e-5
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(ref)):
            np.testing.assert_allclose(np.asarray(jax.device_get(a)),
                                       np.asarray(b), atol=1e-5)


class TestReportAliasing:
    """Satellite pin: every kind's report is a deep copy of the one
    store — mutating a returned report (including its nested
    lists/dicts) must not poison the live records."""

    @pytest.mark.parametrize("kind", profiler.KINDS)
    def test_mutating_report_does_not_poison_store(self, kind):
        profiler.reset_records(kind)
        profiler.record(kind, "t", nested={"deep": [1, 2]},
                        nbytes=[10, 20])
        snap = profiler.report(kind)
        snap["t"]["nested"]["deep"].append(99)
        snap["t"]["nbytes"][0] = -1
        snap["t"]["new_key"] = "poison"
        snap["injected"] = {}
        clean = profiler.report(kind)
        assert clean == {"t": {"nested": {"deep": [1, 2]},
                               "nbytes": [10, 20]}}
        profiler.reset_records(kind)

    def test_unknown_kind_is_logged_once_and_reported_as_keyerror(
            self, monkeypatch, caplog):
        """The writer side never raises (a step must not sink on
        bookkeeping) and says so once per kind; the reader side does."""
        import logging

        monkeypatch.setattr(profiler, "_RECORD_FAILED", set())
        with caplog.at_level(logging.DEBUG, logger="tony_tpu.profiler"):
            profiler.record("no_such_kind", "t1", n=1)
            profiler.record("no_such_kind", "t2", n=2)
        hits = [r for r in caplog.records if "no_such_kind" in r.message]
        assert len(hits) == 1 and hits[0].levelno == logging.DEBUG
        with pytest.raises(KeyError):
            profiler.report("no_such_kind")
        with pytest.raises(KeyError):
            profiler.reset_records("no_such_kind")
        assert "no_such_kind" not in profiler.KINDS

    def test_reset_records_clears_one_kind_or_all(self):
        profiler.reset_records()
        for kind in profiler.KINDS:
            profiler.record(kind, "t", n=1)
        profiler.reset_records("quant")
        assert profiler.report("quant") == {}
        assert all(profiler.report(k) == {"t": {"n": 1}}
                   for k in profiler.KINDS if k != "quant")
        profiler.reset_records()
        assert all(profiler.report(k) == {} for k in profiler.KINDS)


class TestMoEExplicitA2A:
    def _layer_and_vars(self, e=4, d=32, f=64, dtype=jnp.float32):
        import flax.linen as nn

        x = jax.random.normal(jax.random.PRNGKey(2), (8, 16, d), dtype)
        layer = MoEMLP(dim=d, ffn_hidden=f, n_experts=e, top_k=2,
                       dtype=dtype)
        variables = {"params": nn.unbox(
            layer.init(jax.random.PRNGKey(3), x))["params"]}
        return layer, variables, x

    @pytest.mark.parametrize("chunks", [1, 2, 7])
    def test_matches_gspmd_einsum_path(self, chunks):
        """The explicit per-capacity-chunk a2a path must reproduce the
        GSPMD dispatch-einsum path (chunked combine-sum reassociation
        aside) — including chunks > capacity, which clamps."""
        mesh = par.make_mesh(ep=2)
        layer, variables, x = self._layer_and_vars()
        y_ref = layer.apply(variables, x)
        layer_s = MoEMLP(dim=32, ffn_hidden=64, n_experts=4, top_k=2,
                         dtype=jnp.float32, explicit_a2a=True, mesh=mesh,
                         a2a_chunks=chunks)
        profiler.reset_records("collective")
        y = layer_s.apply(variables, x)
        np.testing.assert_allclose(np.asarray(jax.device_get(y)),
                                   np.asarray(jax.device_get(y_ref)),
                                   atol=1e-5)
        rec = profiler.report("collective")
        # Per-issue PER-CHIP payload (same semantics as pipeline edges):
        # [E, B/dp, Cc, D] f32 summed over chunks = E * B/dp * C * D * 4.
        capacity = rec["moe.dispatch"]["capacity"]
        dp = mesh.shape["data"]
        want_total = 4 * (8 // dp) * capacity * 32 * 4
        for tag in ("moe.dispatch", "moe.combine"):
            assert rec[tag]["kind"] == "all_to_all"
            assert rec[tag]["plane"] == "moe"
            assert rec[tag]["axes"] == ["expert"]
            assert len(rec[tag]["nbytes"]) == rec[tag]["chunks"]
            assert sum(rec[tag]["nbytes"]) == want_total

    def test_trains_under_jit_on_ep_mesh(self):
        """The explicit path composes with jit + sharded weights on the
        EP mesh (the make_train_step context it is meant for)."""
        from jax.sharding import NamedSharding

        mesh = par.make_mesh(ep=2)
        layer, variables, x = self._layer_and_vars()
        layer_s = MoEMLP(dim=32, ffn_hidden=64, n_experts=4, top_k=2,
                         dtype=jnp.float32, explicit_a2a=True, mesh=mesh,
                         a2a_chunks=2)
        shard = {"params": {
            k: NamedSharding(mesh, P("expert"))
            if k.startswith("w_") and k != "w_router"
            else NamedSharding(mesh, P())
            for k in variables["params"]}}
        v_sh = jax.device_put(variables, shard)
        x_sh = jax.device_put(x, par.batch_sharding(mesh))
        y_ref = layer.apply(variables, x)

        def f(v, xx):
            return layer_s.apply(v, xx)

        y = jax.jit(f)(v_sh, x_sh)
        np.testing.assert_allclose(np.asarray(jax.device_get(y)),
                                   np.asarray(jax.device_get(y_ref)),
                                   atol=1e-5)

    def test_requires_mesh(self):
        layer, variables, x = self._layer_and_vars()
        bad = MoEMLP(dim=32, ffn_hidden=64, n_experts=4, top_k=2,
                     dtype=jnp.float32, explicit_a2a=True)
        with pytest.raises(ValueError, match="mesh"):
            bad.apply(variables, x)

    def test_rejects_tp_sharded_mesh(self):
        mesh = par.make_mesh(ep=2, tp=2)
        w = jnp.zeros((4, 8, 16))
        with pytest.raises(ValueError, match="model"):
            moe_dispatch_ffn_combine(
                jnp.zeros((4, 4, 8)), jnp.zeros((4, 4, 4, 2)),
                jnp.zeros((4, 4, 4, 2)), (w, w, jnp.zeros((4, 16, 8))),
                mesh)

    def test_rejects_indivisible_experts(self):
        mesh = par.make_mesh(ep=2)
        w = jnp.zeros((3, 8, 16))
        with pytest.raises(ValueError, match="divisible"):
            moe_dispatch_ffn_combine(
                jnp.zeros((4, 4, 8)), jnp.zeros((4, 4, 3, 2)),
                jnp.zeros((4, 4, 3, 2)), (w, w, jnp.zeros((3, 16, 8))),
                mesh)


def test_pipeline_edges_registered():
    """gpipe/gpipe_1f1b register their ppermute ring edges with the
    scheduler: per-tick bytes, forward-only vs forward+reverse."""
    from tony_tpu.parallel import gpipe, gpipe_1f1b, stage_split

    mesh = par.make_mesh(pp=4)
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 8, 8)) * 0.1
    x = jax.random.normal(jax.random.PRNGKey(5), (16, 8))

    def stage_fn(p, mb):
        return jnp.tanh(mb @ p["w"][0])

    profiler.reset_records("collective")
    y1 = gpipe(stage_fn, stage_split({"w": w}, 4), x, mesh,
               microbatches=4)
    y2 = gpipe_1f1b(stage_fn, stage_split({"w": w}, 4), x, mesh,
                    microbatches=4)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-6)
    rec = profiler.report("collective")
    fwd, fb = rec["gpipe.ppermute"], rec["gpipe_1f1b.ppermute"]
    # pp=4 mesh keeps data=2: each DP group's pipeline moves 16/2/4-row
    # microbatches of [*, 8] f32 per edge tick.
    mb_bytes = (16 // 2 // 4) * 8 * 4
    for r in (fwd, fb):
        assert r["kind"] == "ppermute" and r["plane"] == "pipeline"
        assert r["axes"] == ["pipe"]
        assert set(r["nbytes"]) == {mb_bytes}
    assert fwd["directions"] == 1 and fb["directions"] == 2
    assert len(fb["nbytes"]) == 2 * (4 + 4 - 1)


def test_collective_report_covers_all_planes():
    """ACCEPTANCE: every collective a ZeRO-3 + MoE + pipeline step issues
    shows up in one report("collective") — forward gathers, gradient
    scatter/reduce buckets, expert a2a, and pipeline edges."""
    profiler.reset_records("collective")

    # ZeRO-3 accum step (fwd all_gather + grad psum_scatter/all_reduce).
    mesh = par.make_mesh(fsdp=4)
    model = get_model("mnist-mlp", hidden=64)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (32, 784))
    y = jax.random.randint(ky, (32,), 0, 10)
    state = fsdp_shard_state(
        train.create_train_state(model, optax.sgd(0.1), x, kr), mesh)
    step = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                       bucket_bytes=32 * 1024,
                                       donate=False)
    step(state, {"x": x, "y": y})

    # MoE explicit a2a.
    import flax.linen as nn
    mesh_e = par.make_mesh(ep=2)
    xk = jax.random.normal(jax.random.PRNGKey(2), (8, 16, 32),
                           jnp.float32)
    layer = MoEMLP(dim=32, ffn_hidden=64, n_experts=4, top_k=2,
                   dtype=jnp.float32, explicit_a2a=True, mesh=mesh_e)
    variables = {"params": nn.unbox(
        layer.init(jax.random.PRNGKey(3), xk))["params"]}
    layer.apply(variables, xk)

    # Pipeline edges.
    from tony_tpu.parallel import gpipe_1f1b, stage_split
    mesh_p = par.make_mesh(pp=4)
    w = jax.random.normal(jax.random.PRNGKey(4), (4, 8, 8)) * 0.1
    gpipe_1f1b(lambda p, mb: jnp.tanh(mb @ p[0]), stage_split(w, 4),
               jax.random.normal(jax.random.PRNGKey(5), (16, 8)),
               mesh_p, microbatches=4)

    rec = profiler.report("collective")
    kinds = {r["kind"] for r in rec.values()}
    assert {"all_gather", "psum_scatter", "all_to_all",
            "ppermute"} <= kinds
    planes = {r["plane"] for r in rec.values() if "plane" in r}
    assert {"fwd_gather", "grad_reduce", "moe", "pipeline"} <= planes
    # Schema: every record carries kind/axes/nbytes.
    for tag, r in rec.items():
        assert {"kind", "axes", "nbytes"} <= set(r), tag
