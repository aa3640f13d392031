"""Fused-optimizer tier (tony_tpu.ops.fused_optim): the bucket-major
update plane — pallas kernel vs XLA fallback, AdamW/SGD pinned BIT-exact
in f32 against optax (bf16 with documented tolerance), ZeRO-3 scatter
buckets incl. padded uneven shards and multi-dtype trees, bucket-major
global grad norm/clipping vs the per-leaf value, the leaf-major ckpt
round-trip across a changed fsdp topology, and the profiler update
records — on the virtual 8-device CPU mesh. `make tier1-optim` runs this
file by marker."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tony_tpu import ckpt as ckpt_mod
from tony_tpu import parallel as par
from tony_tpu import profiler
from tony_tpu import train as tr
from tony_tpu.models import get_model
from tony_tpu.ops import fused_optim as fo
from tony_tpu.parallel.overlap import GradBuckets
from tony_tpu.train import fsdp_shard_state

pytestmark = pytest.mark.optim


def _bitexact(a, b):
    return np.array_equal(np.asarray(jax.device_get(a)),
                          np.asarray(jax.device_get(b)))


def _tree_leaves_bitexact(a, b):
    return all(_bitexact(x, y) for x, y in
               zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _params(seed=0):
    """Replicated multi-shape tree: matrices, a vector, a scalar."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return {"a": jax.random.normal(ks[0], (12, 8), jnp.float32),
            "b": jax.random.normal(ks[1], (33,), jnp.float32) * 0.3,
            "c": jnp.float32(1.7),
            "d": jax.random.normal(ks[2], (7, 3), jnp.float32)}


def _grads(params, seed=1):
    k = jax.random.PRNGKey(seed)
    return jax.tree.map(
        lambda p: (jnp.sin(p.astype(jnp.float32) + 0.1) * 0.05
                   ).astype(p.dtype), params)


class TestKernel:
    """fused_bucket_update: one launch over one bucket's 1-D buffers."""

    @pytest.mark.parametrize("rule,nslots", [("adamw", 2), ("sgd", 1),
                                             ("adafactor", 1)])
    @pytest.mark.parametrize("n", [1, 300, 9000])
    def test_pallas_interpret_matches_xla_fallback(self, rule, nslots, n):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        g = jax.random.normal(ks[0], (n,), jnp.float32) * 0.1
        p = jax.random.normal(ks[1], (n,), jnp.float32)
        slots = tuple(jnp.zeros((n,), jnp.float32) for _ in range(nslots))
        fused = fo.FusedOptimizer(rule=rule, lr=1e-3, weight_decay=1e-2)
        scal = fused.scalars(jnp.int32(1))
        xp, xs = fo.fused_bucket_update(g, p, slots, scal, rule=rule,
                                        hyper=fused.hyper, impl="xla")
        kp, ks_ = fo.fused_bucket_update(g, p, slots, scal, rule=rule,
                                         hyper=fused.hyper,
                                         interpret=True)
        # Same _rule_math on both paths; only compile-pipeline rewrites
        # (div -> mul-by-reciprocal) can differ, so pin to float ulps.
        np.testing.assert_allclose(np.asarray(kp), np.asarray(xp),
                                   rtol=1e-6, atol=1e-8)
        for a, b in zip(ks_, xs):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-8)

    def test_bad_rule_and_slot_count_raise(self):
        g = jnp.zeros((4,))
        scal = jnp.zeros((4,))
        with pytest.raises(ValueError, match="rule"):
            fo.fused_bucket_update(g, g, (g,), scal, rule="rmsprop",
                                   hyper={})
        fused = fo.FusedOptimizer(rule="adamw")
        with pytest.raises(ValueError, match="slot"):
            fo.fused_bucket_update(g, g, (g,), scal, rule="adamw",
                                   hyper=fused.hyper, impl="xla")
        with pytest.raises(ValueError, match="rule"):
            fo.FusedOptimizer(rule="nope")

    def test_bf16_params_keep_dtype_f32_slots(self):
        g = jnp.ones((50,), jnp.bfloat16) * 0.1
        p = jnp.ones((50,), jnp.bfloat16)
        fused = fo.FusedOptimizer(rule="adamw")
        scal = fused.scalars(jnp.int32(1))
        slots = (jnp.zeros((50,), jnp.float32),) * 2
        np_, ns = fo.fused_bucket_update(g, p, slots, scal, rule="adamw",
                                         hyper=fused.hyper, impl="xla")
        assert np_.dtype == jnp.bfloat16
        assert all(s.dtype == jnp.float32 for s in ns)


class TestOptaxPin:
    """The replicated-tree pin: fused vs optax, both jitted (optax's own
    helpers are inline-jitted, so eager-vs-jit comparisons see XLA's
    div->reciprocal rewrite; under one compile pipeline the op streams
    are identical and the f32 pin is BIT-exact)."""

    @pytest.mark.parametrize("wd", [0.0, 1e-2])
    def test_adamw_bitexact_f32(self, wd):
        params = _params()
        grads = _grads(params)
        fused = fo.FusedOptimizer(rule="adamw", lr=1e-3, weight_decay=wd)
        plan = fused.plan_for(params, None)
        tx = optax.adamw(1e-3, weight_decay=wd)

        fstep = jax.jit(lambda p, s: fo.fused_update_step(
            fused, p, grads, s, plan=plan))

        @jax.jit
        def ostep(p, s):
            u, s2 = tx.update(grads, s, p)
            return optax.apply_updates(p, u), s2

        p1, st = params, fused.init_state(params)
        p2, ost = params, tx.init(params)
        for _ in range(5):
            p1, st, _ = fstep(p1, st)
            p2, ost = ostep(p2, ost)
        assert _tree_leaves_bitexact(p1, p2)
        # The bucket-resident moments convert to optax's, bit-exact.
        lm = fo.slots_to_leaf_major(plan, st["slots"])
        assert _tree_leaves_bitexact(lm["mu"], ost[0].mu)
        assert _tree_leaves_bitexact(lm["nu"], ost[0].nu)

    def test_sgd_momentum_bitexact_f32(self):
        params = _params()
        grads = _grads(params)
        fused = fo.FusedOptimizer(rule="sgd", lr=0.1, momentum=0.9)
        plan = fused.plan_for(params, None)
        tx = optax.sgd(0.1, momentum=0.9)
        fstep = jax.jit(lambda p, s: fo.fused_update_step(
            fused, p, grads, s, plan=plan))

        @jax.jit
        def ostep(p, s):
            u, s2 = tx.update(grads, s, p)
            return optax.apply_updates(p, u), s2

        p1, st = params, fused.init_state(params)
        p2, ost = params, tx.init(params)
        for _ in range(5):
            p1, st, _ = fstep(p1, st)
            p2, ost = ostep(p2, ost)
        assert _tree_leaves_bitexact(p1, p2)

    def test_adamw_bf16_documented_tolerance(self):
        # optax keeps bf16 moments for bf16 params; the fused plane keeps
        # f32 slots and re-rounds only the param write — so the pin is a
        # bf16-ulp tolerance, not bit-exactness (see README).
        params = jax.tree.map(lambda p: p.astype(jnp.bfloat16), _params())
        grads = _grads(params)
        fused = fo.FusedOptimizer(rule="adamw", lr=1e-2, weight_decay=1e-2)
        plan = fused.plan_for(params, None)
        tx = optax.adamw(1e-2, weight_decay=1e-2)
        fstep = jax.jit(lambda p, s: fo.fused_update_step(
            fused, p, grads, s, plan=plan))

        @jax.jit
        def ostep(p, s):
            u, s2 = tx.update(grads, s, p)
            return optax.apply_updates(p, u), s2

        p1, st = params, fused.init_state(params)
        p2, ost = params, tx.init(params)
        for _ in range(3):
            p1, st, _ = fstep(p1, st)
            p2, ost = ostep(p2, ost)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                rtol=1e-2, atol=1e-2)

    def test_adafactor_style_matches_leaf_major_reference(self):
        # The adafactor rule is self-pinned: second-moment-only,
        # elementwise, non-factored — the leaf-major reference is the
        # same math without any bucket layout.
        params = _params()
        grads = _grads(params)
        b2, eps, lr = 0.999, 1e-8, 1e-3
        fused = fo.FusedOptimizer(rule="adafactor", lr=lr, b2=b2, eps=eps)
        plan = fused.plan_for(params, None)
        fstep = jax.jit(lambda p, s: fo.fused_update_step(
            fused, p, grads, s, plan=plan))

        @jax.jit
        def ref(p, nu):
            nu2 = jax.tree.map(
                lambda g, v: (1 - b2) * (g * g) + b2 * v, grads, nu)
            p2 = jax.tree.map(
                lambda pp, g, v: pp + (-lr) * (g / (jnp.sqrt(v) + eps)),
                p, grads, nu2)
            return p2, nu2

        p1, st = params, fused.init_state(params)
        p2, nu = params, jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        for _ in range(3):
            p1, st, _ = fstep(p1, st)
            p2, nu = ref(p2, nu)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-8)

    def test_clip_norm_matches_optax_chain(self):
        params = _params()
        grads = _grads(params)
        fused = fo.FusedOptimizer(rule="adamw", lr=1e-3, clip_norm=0.05)
        plan = fused.plan_for(params, None)
        tx = optax.chain(optax.clip_by_global_norm(0.05),
                         optax.adamw(1e-3, weight_decay=0.0))
        fstep = jax.jit(lambda p, s: fo.fused_update_step(
            fused, p, grads, s, plan=plan))

        @jax.jit
        def ostep(p, s):
            u, s2 = tx.update(grads, s, p)
            return optax.apply_updates(p, u), s2

        p1, st = params, fused.init_state(params)
        p2, ost = params, tx.init(params)
        for _ in range(2):
            p1, st, gnorm = fstep(p1, st)
            p2, ost = ostep(p2, ost)
        # The bucket-major norm differs from the per-leaf one only by fp
        # reassociation, so the clipped trajectories agree to ulps.
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)

    def test_lr_schedule_callable(self):
        params = _params()
        grads = _grads(params)
        fused = fo.FusedOptimizer(rule="sgd", momentum=0.0,
                                  lr=lambda count: 0.1 / count)
        plan = fused.plan_for(params, None)
        st = fused.init_state(params)
        p1, st, _ = fo.fused_update_step(fused, params, grads, st,
                                         plan=plan)
        p2, st, _ = fo.fused_update_step(fused, p1, grads, st, plan=plan)
        # step 1 at lr .1, step 2 at lr .05
        exp = jax.tree.map(lambda p, g: p - 0.1 * g - 0.05 * g,
                           params, grads)
        for a, b in zip(jax.tree.leaves(p2), jax.tree.leaves(exp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def _zero3_tree(mesh):
    """Sharded + UNEVEN-sharded (explicit spec, committed replicated) +
    bf16 + replicated + scalar — the full menu of bucket kinds."""
    ks = jax.random.split(jax.random.PRNGKey(3), 8)
    params = {
        "w1": jax.random.normal(ks[0], (16, 8), jnp.float32),
        "w2": jax.random.normal(ks[1], (6, 8), jnp.float32),   # 6 % 4 != 0
        "w3": jax.random.normal(ks[2], (8, 4), jnp.bfloat16),
        "bias": jax.random.normal(ks[3], (5,), jnp.float32),
        "scale": jnp.float32(1.5),
    }
    specs = {"w1": P("fsdp"), "w2": P("fsdp"), "w3": P("fsdp"),
             "bias": P(), "scale": P()}
    committed = {k: NamedSharding(mesh, P("fsdp")
                                  if k in ("w1", "w3") else P())
                 for k in params}
    params = jax.device_put(params, committed)
    grads = jax.device_put(_grads(params), committed)
    return params, grads, specs


class TestZero3:
    """Scatter-layout updates: shard-domain buckets, padded uneven
    shards, multi-dtype trees — pinned against leaf-major optax."""

    def _setup(self, bucket_bytes=256):
        mesh = par.make_mesh(fsdp=4)
        params, grads, specs = _zero3_tree(mesh)
        fused = fo.FusedOptimizer(rule="adamw", lr=1e-3,
                                  weight_decay=1e-2,
                                  bucket_bytes=bucket_bytes)
        plan = GradBuckets.plan_sharded(params, specs, shard_size=4,
                                        bucket_bytes=bucket_bytes)
        return mesh, params, grads, specs, fused, plan

    def test_sharded_update_bitexact_vs_optax(self):
        mesh, params, grads, specs, fused, plan = self._setup()
        assert plan.n_scatter_buckets >= 2 and sum(plan.bucket_padded) == 1
        st = fused.init_state(params, mesh, plan=plan)
        fstep = jax.jit(lambda p, g, s: fo.fused_update_step(
            fused, p, g, s, mesh, plan=plan, param_specs=specs))
        tx = optax.adamw(1e-3, weight_decay=1e-2)
        host_p, host_g = jax.device_get(params), jax.device_get(grads)

        @jax.jit
        def ostep(p, s):
            u, s2 = tx.update(host_g, s, p)
            return optax.apply_updates(p, u), s2

        p1, p2, ost = params, host_p, tx.init(host_p)
        for _ in range(3):
            p1, st, gnorm = fstep(p1, grads, st)
            p2, ost = ostep(p2, ost)
        for k in params:
            a = np.asarray(jax.device_get(p1[k]))
            b = np.asarray(p2[k])
            if str(params[k].dtype) == "bfloat16":
                np.testing.assert_allclose(a.astype(np.float32),
                                           b.astype(np.float32),
                                           rtol=1e-2, atol=1e-2)
            else:
                assert np.array_equal(a, b), k
        # Sharded layouts preserved: even scatter leaves stay fsdp-
        # sharded, uneven/replicated leaves stay whole.
        assert "fsdp" in str(p1["w1"].sharding.spec)
        assert p1["w2"].shape == (6, 8)
        # Bucket-major norm pins against the per-leaf reduction.
        ref = optax.global_norm(jax.tree.map(
            lambda g: np.asarray(g, np.float32), host_g))
        np.testing.assert_allclose(float(gnorm), float(ref), rtol=1e-4)

    def test_pad_rows_stay_inert(self):
        mesh, params, grads, specs, fused, plan = self._setup()
        st = fused.init_state(params, mesh, plan=plan)
        fstep = jax.jit(lambda p, g, s: fo.fused_update_step(
            fused, p, g, s, mesh, plan=plan, param_specs=specs))
        p1 = params
        for _ in range(3):
            p1, st, _ = fstep(p1, grads, st)
        # Indicator: pack a ones-tree — zeros land exactly on pad rows.
        ones = jax.tree.map(
            lambda p: np.ones(p.shape, np.float32),
            jax.device_get(params))
        ind = plan.pack(ones)
        for b in range(plan.n_buckets):
            if not plan._is_padded(b):
                continue
            mask = np.asarray(ind[b]) == 0
            assert mask.any()          # the pad rows exist
            for name in st["slots"]:
                buf = np.asarray(jax.device_get(st["slots"][name][b]))
                assert not buf[mask].any(), \
                    f"slot {name} bucket {b}: pad rows drifted nonzero"
        # ...and therefore the portable round-trip is the identity.
        back = fo.leaf_major_to_slots(
            plan, fo.slots_to_leaf_major(plan, st["slots"]), mesh)
        for name in back:
            for a, b in zip(st["slots"][name], back[name]):
                assert _bitexact(a, b)

    def test_accum_step_fused_matches_optax_path(self):
        """make_accum_train_step(update='fused_bucket') vs the optax
        path: same microbatched reduce, so the loss is equal and the
        2-step parameter trajectory agrees to 1e-7 absolute — reduce→update
        never leaving the bucket domain changes nothing beyond f32
        reassociation."""
        mesh = par.make_mesh(fsdp=4)
        model = get_model("mnist-mlp", hidden=32)
        kx, ky, kr = jax.random.split(jax.random.PRNGKey(1), 3)
        x = jax.random.normal(kx, (64, 784), jnp.float32)
        y = jax.random.randint(ky, (64,), 0, 10)
        data = {"x": x, "y": y}
        fused = fo.FusedOptimizer(rule="adamw", lr=1e-3,
                                  weight_decay=1e-2,
                                  bucket_bytes=1 << 16)
        sf = fsdp_shard_state(tr.create_train_state(model, fused, x, kr),
                              mesh)
        so = fsdp_shard_state(tr.create_train_state(
            model, optax.adamw(1e-3, weight_decay=1e-2), x, kr), mesh)
        profiler.reset_records("update")
        step_f = tr.make_accum_train_step(
            mesh=mesh, microbatches=4, bucket_bytes=1 << 16,
            update="fused_bucket", donate=False)
        step_o = tr.make_accum_train_step(
            mesh=mesh, microbatches=4, bucket_bytes=1 << 16, donate=False)
        for _ in range(2):
            sf, mf = step_f(sf, data)
            so, mo = step_o(so, data)
        assert float(mf["loss"]) == float(mo["loss"])
        assert float(mf["grad_norm"]) == pytest.approx(
            float(mo["grad_norm"]), rel=1e-6)
        # Two different programs (bucket kernel vs per-leaf optax), so a
        # tolerance, not bits: XLA-CPU contracts their f32 chains
        # differently (measured max |diff| 7.5e-9 after 2 steps on jax
        # 0.9.0). atol 1e-7 is 1e-4 of ONE AdamW step at lr 1e-3 — a
        # wrong moment, bias correction or decay term is >= 1e-5.
        for a, b in zip(jax.tree.leaves(sf.params),
                        jax.tree.leaves(so.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=0, atol=1e-7)
        assert int(sf.opt_state["count"]) == 2 and int(sf.step) == 2
        rec = profiler.report("update")["accum_update"]
        assert rec["rule"] == "adamw" and rec["impl"] in ("pallas", "xla")
        assert rec["n_buckets"] >= 1 and rec["n_scatter_buckets"] >= 1

    def test_accum_step_validates_tx_and_bucket_bytes(self):
        mesh = par.make_mesh(fsdp=2)
        model = get_model("mnist-mlp", hidden=16)
        kx, ky, kr = jax.random.split(jax.random.PRNGKey(1), 3)
        x = jax.random.normal(kx, (16, 784), jnp.float32)
        data = {"x": x, "y": jax.random.randint(ky, (16,), 0, 10)}
        state_o = fsdp_shard_state(tr.create_train_state(
            model, optax.sgd(0.1), x, kr), mesh)
        step = tr.make_accum_train_step(mesh=mesh, microbatches=2,
                                        update="fused_bucket")
        with pytest.raises(ValueError, match="FusedOptimizer"):
            step(state_o, data)
        fused = fo.FusedOptimizer(rule="sgd", lr=0.1,
                                  bucket_bytes=1 << 16)
        state_f = fsdp_shard_state(tr.create_train_state(
            model, fused, x, kr), mesh)
        bad = tr.make_accum_train_step(mesh=mesh, microbatches=2,
                                       bucket_bytes=123,
                                       update="fused_bucket")
        with pytest.raises(ValueError, match="bucket_bytes"):
            bad(state_f, data)
        with pytest.raises(ValueError, match="update mode"):
            tr.make_accum_train_step(mesh=mesh, microbatches=2,
                                     update="nope")

    def test_slot_topology_mismatch_raises(self):
        mesh, params, grads, specs, fused, plan = self._setup()
        st = fused.init_state(params, mesh, plan=plan)
        short = {n: bufs[:-1] for n, bufs in st["slots"].items()}
        with pytest.raises(ValueError, match="bucket"):
            fused.check_slots(plan, short)
        renamed = {"m" if n == "mu" else n: b
                   for n, b in st["slots"].items()}
        with pytest.raises(ValueError, match="slots"):
            fused.check_slots(plan, renamed)


class TestCkptPortability:
    """The leaf-major codec: manifests carry topology-independent opt
    state; bucket-resident buffers rebuild for whatever mesh restores."""

    def _fused_state(self, mesh, fused, seed=1):
        model = get_model("mnist-mlp", hidden=32)
        kx, ky, kr = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = jax.random.normal(kx, (64, 784), jnp.float32)
        y = jax.random.randint(ky, (64,), 0, 10)
        state = fsdp_shard_state(
            tr.create_train_state(model, fused, x, kr), mesh)
        return state, {"x": x, "y": y}

    def test_roundtrip_across_changed_fsdp_topology(self, tmp_path):
        fused = fo.FusedOptimizer(rule="adamw", lr=1e-3,
                                  weight_decay=1e-2, bucket_bytes=1 << 16)
        mesh4 = par.make_mesh(fsdp=4)
        state, data = self._fused_state(mesh4, fused)
        step = tr.make_accum_train_step(
            mesh=mesh4, microbatches=4, bucket_bytes=1 << 16,
            update="fused_bucket", donate=False)
        for _ in range(2):
            state, _ = step(state, data)
        mgr = ckpt_mod.AsyncCheckpointer(tmp_path, keep=2)
        mgr.save(ckpt_mod.encode_portable(state), step=2, block=True)
        mgr.close()

        mesh2 = par.make_mesh(fsdp=2)
        fresh, _ = self._fused_state(mesh2, fused, seed=99)
        restored = ckpt_mod.decode_portable(ckpt_mod.restore_pytree(
            tmp_path, ckpt_mod.encode_portable(fresh), step=2,
            mesh=mesh2), mesh2)
        # Portable forms agree bit-exact across the topology change...
        pa = ckpt_mod.encode_portable(state).opt_state
        pb = ckpt_mod.encode_portable(restored).opt_state
        assert _tree_leaves_bitexact(pa, pb)
        assert _tree_leaves_bitexact(state.params, restored.params)
        assert int(restored.opt_state["count"]) == 2
        # ...and the restored state steps on the NEW topology with the
        # identical result (same math, different scatter layout).
        step2 = tr.make_accum_train_step(
            mesh=mesh2, microbatches=4, bucket_bytes=1 << 16,
            update="fused_bucket", donate=False)
        restored, m2 = step2(restored, data)
        state, m4 = step(state, data)
        assert float(m2["loss"]) == float(m4["loss"])

    def test_train_loop_saves_portable_and_restores_resident(
            self, tmp_path):
        fused = fo.FusedOptimizer(rule="adamw", lr=1e-3,
                                  bucket_bytes=1 << 16)
        mesh = par.make_mesh(fsdp=4)
        state, data = self._fused_state(mesh, fused)
        step = tr.make_accum_train_step(
            mesh=mesh, microbatches=4, bucket_bytes=1 << 16,
            update="fused_bucket", donate=False)
        s1, _ = tr.train_loop(state, step, [data] * 4,
                              ckpt_dir=str(tmp_path), save_every=2,
                              mesh=mesh)
        assert ckpt_mod.committed_steps(tmp_path) == [2, 4]
        # The manifest carries LEAF-major opt-state paths (portable form).
        manifest = ckpt_mod.read_manifest(tmp_path, 4)
        paths = [m["path"] for m in manifest["leaves"]]
        assert any(".opt_state['leaf']['mu']" in p for p in paths)
        assert not any("['slots']" in p for p in paths)
        fresh, _ = self._fused_state(mesh, fused, seed=5)
        s2, _ = tr.train_loop(fresh, step, [], ckpt_dir=str(tmp_path),
                              mesh=mesh)
        assert "slots" in s2.opt_state          # resident again
        assert _tree_leaves_bitexact(s1.params, s2.params)
        assert _tree_leaves_bitexact(
            ckpt_mod.encode_portable(s1).opt_state,
            ckpt_mod.encode_portable(s2).opt_state)

    def test_plain_optax_states_pass_codecs_untouched(self):
        mesh = par.make_mesh(fsdp=2)
        model = get_model("mnist-mlp", hidden=16)
        kx, _, kr = jax.random.split(jax.random.PRNGKey(0), 3)
        x = jax.random.normal(kx, (16, 784), jnp.float32)
        state = fsdp_shard_state(tr.create_train_state(
            model, optax.adamw(1e-3), x, kr), mesh)
        assert ckpt_mod.encode_portable(state) is state
        assert ckpt_mod.decode_portable(state, mesh) is state


class TestRecords:
    def test_fused_update_record_fields(self):
        params = _params()
        fused = fo.FusedOptimizer(rule="sgd", lr=0.1, clip_norm=1.0)
        plan = fused.plan_for(params, None)
        profiler.reset_records("update")
        fo.fused_update_step(fused, params, _grads(params),
                             fused.init_state(params), plan=plan)
        rec = profiler.report("update")["fused_update"]
        assert rec["rule"] == "sgd"
        assert rec["impl"] in ("pallas", "xla")
        assert rec["n_buckets"] == plan.n_buckets
        assert rec["bucket_nbytes"] == list(plan.bucket_nbytes)
        assert rec["slot_names"] == ["trace"]
        assert rec["clip_norm"] == 1.0

    def test_mutating_update_report_does_not_poison_store(self):
        profiler.reset_records("update")
        profiler.record("update", "t", nested={"deep": [1, 2]},
                        bucket_nbytes=[10, 20])
        snap = profiler.report("update")
        snap["t"]["nested"]["deep"].append(99)
        snap["t"]["bucket_nbytes"][0] = -1
        snap["injected"] = {}
        assert profiler.report("update") == {
            "t": {"nested": {"deep": [1, 2]}, "bucket_nbytes": [10, 20]}}
        profiler.reset_records("update")
