"""Overlap-engine tier (comm/compute overlap tentpole): the GradBuckets
planner, bucketed-accumulation numerics vs the monolithic step, the XLA
flag merge, the bench leg, and the profiler's plan records — on the virtual
8-device CPU mesh. The 1F1B-vs-GPipe numerical pins live in
test_pipeline.py next to the schedule they pin."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tony_tpu import parallel as par
from tony_tpu import profiler, train
from tony_tpu.models import get_model
from tony_tpu.parallel import overlap
from tony_tpu.parallel.overlap import (DEFAULT_BUCKET_BYTES, GradBuckets,
                                       microbatch_grads)
from tony_tpu.runtime.jax_runtime import (MULTISLICE_XLA_FLAGS,
                                          OVERLAP_XLA_FLAGS,
                                          overlap_xla_flags)


def _tree():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    return {
        "a": jax.random.normal(k[0], (128, 64)),
        "b": {"w": jax.random.normal(k[1], (256, 256)),
              "bias": jax.random.normal(k[2], (256,))},
        "c": jax.random.normal(k[3], (40,)),
    }


class TestGradBuckets:
    def test_partitions_every_leaf_exactly_once(self):
        tree = _tree()
        plan = GradBuckets.plan(tree, bucket_bytes=64 * 1024)
        seen = sorted(i for b in plan.buckets for i in b)
        assert seen == list(range(len(jax.tree.leaves(tree))))

    def test_respects_byte_threshold(self):
        plan = GradBuckets.plan(_tree(), bucket_bytes=64 * 1024)
        for idxs, nbytes in zip(plan.buckets, plan.bucket_nbytes):
            # A multi-leaf bucket must fit; only a single oversized leaf
            # may exceed (it has nowhere smaller to go).
            assert nbytes <= plan.threshold or len(idxs) == 1
        total = sum(l.size * l.dtype.itemsize
                    for l in jax.tree.leaves(_tree()))
        assert sum(plan.bucket_nbytes) == total

    def test_one_dtype_per_bucket(self):
        tree = dict(_tree(), ints=jnp.zeros((100,), jnp.int32))
        plan = GradBuckets.plan(tree, bucket_bytes=1 << 30)
        for idxs in plan.buckets:
            assert len({plan.dtypes[i] for i in idxs}) == 1

    def test_pack_unpack_roundtrip(self):
        tree = _tree()
        plan = GradBuckets.plan(tree, bucket_bytes=64 * 1024)
        out = plan.unpack(plan.pack(tree))
        for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_plan_under_eval_shape(self):
        abstract = jax.eval_shape(_tree)
        plan = GradBuckets.plan(abstract, bucket_bytes=64 * 1024)
        assert plan.n_buckets >= 1

    def test_rejects_nonpositive_threshold(self):
        with pytest.raises(ValueError, match="positive"):
            GradBuckets.plan(_tree(), bucket_bytes=0)

    def test_rejects_empty_pytree(self):
        """Satellite pin: an empty grad tree must fail at plan time with a
        clear message, not later inside pack/unpack with an opaque
        concatenate error."""
        for empty in ({}, [], {"a": {}}):
            with pytest.raises(ValueError, match="empty"):
                GradBuckets.plan(empty)

    def test_reduce_scatter_pads_group_indivisible_buckets(self):
        """Satellite pin: bucket payloads NOT divisible by the sync group
        (prime-ish leaf sizes) take the padding path and still match the
        per-leaf psum exactly."""
        from jax.sharding import PartitionSpec as P

        from tony_tpu.compat import shard_map

        k = jax.random.split(jax.random.PRNGKey(3), 3)
        tree = {"a": jax.random.normal(k[0], (37,)),
                "b": jax.random.normal(k[1], (13, 7)),
                "c": jax.random.normal(k[2], (5,))}
        mesh = par.make_mesh()
        axes = overlap.sync_axes(mesh)
        # Tiny threshold: several buckets, each needing its own padding.
        plan = GradBuckets.plan(tree, bucket_bytes=256)
        assert plan.n_buckets > 1
        assert any(n % 8 for n in plan.bucket_numel)
        specs = jax.tree.map(lambda _: P(), tree)

        def spmd(t):
            r = jax.lax.axis_index("data").astype(jnp.float32) + 1.0
            t = jax.tree.map(lambda l: l * r, t)
            want = jax.tree.map(lambda l: jax.lax.psum(l, axes), t)
            got = plan.reduce(t, axes, op="reduce_scatter", group_size=8)
            return want, got

        want, got = jax.jit(shard_map(
            spmd, mesh, in_specs=(specs,), out_specs=(specs, specs)))(tree)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


class TestSyncAxes:
    """Satellite pins: the sync-group helpers on meshes that don't carry
    every DP axis (manual meshes from user code)."""

    def test_mesh_missing_fsdp(self):
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
        assert overlap.sync_axes(mesh) == ("data",)
        assert overlap.sync_size(mesh) == 4
        assert overlap.ici_axes(mesh) == ("data",)
        assert overlap.dcn_axis(mesh) is None

    def test_mesh_missing_data(self):
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()).reshape(2, 4), ("fsdp", "model"))
        assert overlap.sync_axes(mesh) == ("fsdp",)
        assert overlap.sync_size(mesh) == 2

    def test_mesh_with_neither_dp_axis(self):
        mesh = jax.sharding.Mesh(
            np.asarray(jax.devices()).reshape(8,), ("model",))
        assert overlap.sync_axes(mesh) == ()
        assert overlap.sync_size(mesh) == 1

    def test_slice_axis_in_sync_group_but_not_ici(self):
        mesh = par.make_mesh(slices=2)
        assert overlap.sync_axes(mesh) == ("slice", "data", "fsdp")
        assert overlap.sync_size(mesh) == 8
        assert overlap.ici_axes(mesh) == ("data", "fsdp")
        assert overlap.dcn_axis(mesh) == "slice"

    def test_single_slice_mesh_has_no_dcn(self):
        assert overlap.dcn_axis(par.make_mesh()) is None

    @pytest.mark.parametrize("op", ["all_reduce", "reduce_scatter"])
    def test_reduce_matches_tree_psum(self, op):
        """Per-bucket reduction must equal the monolithic per-leaf psum —
        for both the allreduce and the RS+AG split (padded buckets)."""
        from tony_tpu.compat import shard_map
        from jax.sharding import PartitionSpec as P

        mesh = par.make_mesh()
        axes = ("data", "fsdp")
        tree = _tree()
        plan = GradBuckets.plan(tree, bucket_bytes=64 * 1024)
        specs = jax.tree.map(lambda _: P(), tree)

        def spmd(t):
            # Give each replica distinct values so the sum is a real test.
            r = jax.lax.axis_index("data").astype(jnp.float32) + 1.0
            t = jax.tree.map(lambda l: l * r, t)
            want = jax.tree.map(lambda l: jax.lax.psum(l, axes), t)
            got = plan.reduce(t, axes, op=op, group_size=8)
            return want, got

        want, got = jax.jit(shard_map(
            spmd, mesh, in_specs=(specs,), out_specs=(specs, specs)))(tree)
        for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)


def _mnist_setup(batch=32, hidden=64):
    model = get_model("mnist-mlp", hidden=hidden)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (batch, 784))
    y = jax.random.randint(ky, (batch,), 0, 10)
    state = train.create_train_state(model, optax.sgd(0.1), x, kr)
    return state, {"x": x, "y": y}


@pytest.mark.parametrize("op", ["all_reduce", "reduce_scatter"])
def test_accum_step_matches_monolithic(op):
    """THE acceptance pin: bucketed-accumulation loss/grad-norm/params must
    match the monolithic make_train_step within 1e-5 on the 8-device DP
    mesh."""
    mesh = par.make_mesh()
    state, batch = _mnist_setup()
    mono = train.make_train_step(mesh=mesh, donate=False)
    accum = train.make_accum_train_step(
        mesh=mesh, microbatches=4, bucket_bytes=32 * 1024, reduce_op=op,
        donate=False)
    s1, m1 = mono(state, batch)
    s2, m2 = accum(state, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    assert abs(float(m1["grad_norm"]) - float(m2["grad_norm"])) < 1e-5
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_accum_step_trains():
    mesh = par.make_mesh()
    state, batch = _mnist_setup()
    step = train.make_accum_train_step(mesh=mesh, microbatches=4)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_accum_step_rejects_indivisible_batch():
    mesh = par.make_mesh()
    state, _ = _mnist_setup()
    bad = {"x": jnp.zeros((24, 784)), "y": jnp.zeros((24,), jnp.int32)}
    step = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                       donate=False)
    with pytest.raises(ValueError, match="24.*not divisible.*32"):
        step(state, bad)


def test_accum_step_requires_mesh():
    with pytest.raises(ValueError, match="mesh"):
        train.make_accum_train_step(microbatches=4)


def test_microbatch_grads_single_bucket_and_many():
    """Bucketing must not change grads: one giant bucket vs per-leaf-ish
    tiny buckets agree with each other."""
    mesh = par.make_mesh()
    state, batch = _mnist_setup()

    def loss_fn(params, mb):
        logits = state.apply_fn({"params": params}, mb["x"])
        return train.cross_entropy_loss(logits, mb["y"])

    def run(bucket_bytes):
        return microbatch_grads(loss_fn, state.params, batch, mesh,
                                microbatches=4, bucket_bytes=bucket_bytes)

    loss_a, grads_a = jax.jit(lambda: run(1 << 30))()
    loss_b, grads_b = jax.jit(lambda: run(1024))()
    assert abs(float(loss_a) - float(loss_b)) < 1e-6
    for a, b in zip(jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_accum_step_reduce_scatter_pads_odd_shapes():
    """Satellite pin: hidden=52 yields bias/logit leaves whose bucket
    payloads don't divide the 8-way sync group — the in-scan
    reduce_scatter padding path must still match the monolithic step."""
    mesh = par.make_mesh()
    state, batch = _mnist_setup(hidden=52)
    mono = train.make_train_step(mesh=mesh, donate=False)
    accum = train.make_accum_train_step(
        mesh=mesh, microbatches=4, bucket_bytes=1024,
        reduce_op="reduce_scatter", donate=False)
    s1, m1 = mono(state, batch)
    s2, m2 = accum(state, batch)
    assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
    assert abs(float(m1["grad_norm"]) - float(m2["grad_norm"])) < 1e-5
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_hierarchical_requires_multislice_mesh():
    mesh = par.make_mesh()
    state, batch = _mnist_setup()
    step = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                       hierarchy="hierarchical",
                                       donate=False)
    with pytest.raises(ValueError, match="multi-slice"):
        step(state, batch)
    with pytest.raises(ValueError, match="hierarchy"):
        train.make_accum_train_step(mesh=mesh, microbatches=4,
                                    hierarchy="bogus",
                                    donate=False)(state, batch)


def _zero3_state(state, mesh):
    """Shard the MLP state into the ZeRO-3 layout on ``mesh``."""
    return train.fsdp_shard_state(state, mesh)


def test_fsdp_shard_state_shards_first_divisible_dim():
    """Each leaf's first fsdp-divisible dimension is the sharded one; a
    leaf with none stays replicated; the optimizer state is created
    fresh, on the resharded params' layout."""
    from flax.training.train_state import TrainState
    from jax.sharding import PartitionSpec as P

    mesh = par.make_mesh(fsdp=4)
    shapes = {"first": (8, 3), "second": (6, 8), "both": (4, 12),
              "none": (3, 5), "scalar": ()}
    state = TrainState.create(
        apply_fn=None, tx=optax.adam(1e-3),
        params={k: jnp.ones(v) for k, v in shapes.items()})
    state = state.replace(step=7)
    out = _zero3_state(state, mesh)
    want = {"first": P("fsdp", None), "second": P(None, "fsdp"),
            "both": P("fsdp", None), "none": P(), "scalar": P()}
    for name, spec in want.items():
        got = out.params[name].sharding
        assert got.mesh == mesh and got.spec == spec, name
        # Each device holds 1/4 of a sharded leaf, all of a replicated one.
        held = out.params[name].addressable_shards[0].data.size
        assert held * (4 if "fsdp" in spec else 1) == \
            max(int(np.prod(shapes[name])), 1), name
        mu = out.opt_state[0].mu[name]
        assert mu.sharding.is_equivalent_to(got, mu.ndim), name
        assert not mu.any()
    assert int(out.step) == 0 and out.tx is state.tx


def test_fsdp_shard_state_rebuilds_fused_optimizer_state():
    """With a FusedOptimizer the bucket-resident state is planned anew
    from the resharded params — scatter buckets ``P("fsdp")`` on the mesh,
    sized by the sharded plan — not carried over from the unsharded
    state it was given."""
    from jax.sharding import PartitionSpec as P

    from tony_tpu.ops import fused_optim as fo

    mesh = par.make_mesh(fsdp=4)
    model = get_model("mnist-mlp", hidden=64)
    x = jax.random.normal(jax.random.PRNGKey(0), (32, 784))
    fused = fo.FusedOptimizer(rule="adamw", lr=1e-3, bucket_bytes=1 << 16)
    state = train.create_train_state(model, fused, x, jax.random.PRNGKey(1))
    out = _zero3_state(state, mesh)
    plan = fused.plan_for(out.params, mesh)
    specs = fused.bucket_specs(plan)
    assert P("fsdp") in specs
    assert set(out.opt_state["slots"]) == set(fused.slot_names)
    for name, bufs in out.opt_state["slots"].items():
        assert [b.shape for b in bufs] == \
            [(n,) for n in plan.bucket_numel], name
        for buf, spec in zip(bufs, specs):
            assert buf.sharding.mesh == mesh and buf.sharding.spec == spec
            assert not buf.any()
    # The state it was given lives on one device, in the unsharded plan.
    old = state.opt_state["slots"][fused.slot_names[0]]
    assert all(len(b.sharding.device_set) == 1 for b in old)
    assert int(out.opt_state["count"]) == 0 and int(out.step) == 0
    assert out.tx is fused


def test_zero3_accum_matches_replicated_and_monolithic():
    """THE ZeRO-3 acceptance pin: fsdp-sharded params auto-detected, grads
    psum_scatter-ed straight into the shard layout, loss/grad-norm/params
    match both the replicated accum step and the monolithic step within
    1e-5 — and the updated params STAY in the shard layout."""
    mesh = par.make_mesh(fsdp=4)           # data=2 x fsdp=4
    state, batch = _mnist_setup()
    mono = train.make_train_step(mesh=mesh, donate=False)
    s1, m1 = mono(state, batch)
    repl = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                       bucket_bytes=32 * 1024,
                                       donate=False)
    s2, m2 = repl(state, batch)
    zstate = _zero3_state(state, mesh)
    zstep = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                        bucket_bytes=32 * 1024,
                                        donate=False)
    s3, m3 = zstep(zstate, batch)
    for m in (m2, m3):
        assert abs(float(m1["loss"]) - float(m["loss"])) < 1e-5
        assert abs(float(m1["grad_norm"]) - float(m["grad_norm"])) < 1e-5
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s3.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    # Sharding inspection: every updated leaf kept its fsdp placement
    # (specs compared with trailing-None dims normalized away).
    def norm(spec):
        t = tuple(spec)
        while t and t[-1] is None:
            t = t[:-1]
        return t

    for old, new in zip(jax.tree.leaves(zstate.params),
                        jax.tree.leaves(s3.params)):
        assert norm(new.sharding.spec) == norm(old.sharding.spec)


def test_zero3_grads_never_leave_shard_layout():
    """Sharding inspection on the grads themselves: microbatch_grads with
    param_specs returns grads carrying the fsdp spec (scatter path), and
    the profiler records the scatter-bucket plan."""
    from jax.sharding import PartitionSpec as P

    mesh = par.make_mesh(fsdp=4)
    state, batch = _mnist_setup()
    zstate = _zero3_state(state, mesh)
    specs = overlap.fsdp_param_specs(zstate.params, mesh)
    assert specs is not None

    def loss_fn(params, mb):
        logits = zstate.apply_fn({"params": params}, mb["x"])
        return train.cross_entropy_loss(logits, mb["y"])

    profiler.reset_records("overlap")
    with jax.sharding.Mesh(mesh.devices, mesh.axis_names):
        loss, grads = jax.jit(lambda p, b: microbatch_grads(
            loss_fn, p, b, mesh, microbatches=4, bucket_bytes=32 * 1024,
            param_specs=specs))(zstate.params, batch)
    spec_leaves = jax.tree.leaves(specs,
                                  is_leaf=lambda x: isinstance(x, P))
    sharded = 0
    for g, spec in zip(jax.tree.leaves(grads), spec_leaves):
        if any("fsdp" in str(e) for e in tuple(spec)):
            assert "fsdp" in str(g.sharding.spec)
            sharded += 1
    assert sharded >= 4
    rec = profiler.report("overlap")["accum_step"]
    assert rec["zero3"] is True
    assert rec["n_scatter_buckets"] >= 1
    assert any(l["op"] == "psum_scatter" and l["axes"] == ["fsdp"]
               for l in rec["levels"])


class TestUnevenZero3:
    """ROADMAP follow-on: leaves whose sharded dim doesn't divide the fsdp
    axis used to raise in plan_sharded — now they pad into dedicated
    scatter buckets and unpad on the way out."""

    def _tree_specs(self):
        from jax.sharding import PartitionSpec as P

        k = jax.random.split(jax.random.PRNGKey(7), 3)
        params = {"w": jax.random.normal(k[0], (8, 16)),     # 8 % 4 == 0
                  "v": jax.random.normal(k[1], (6, 16)),     # 6 % 4 != 0
                  "b": jax.random.normal(k[2], (16,))}
        specs = {"w": P("fsdp"), "v": P("fsdp"), "b": P()}
        return params, specs

    def test_plan_pads_into_own_scatter_bucket(self):
        params, specs = self._tree_specs()
        plan = GradBuckets.plan_sharded(params, specs, shard_size=4,
                                        bucket_bytes=1 << 20)
        # b=replicated, v=padded scatter, w=even scatter — three buckets,
        # and the padded one is separate from the even one.
        assert plan.n_scatter_buckets == 2
        assert sum(plan.bucket_padded) == 1
        i_v = 1                                    # flatten order: b, v, w
        assert plan.shard_pads[i_v] == 2           # 6 → 8 rows
        assert plan.padded_shape(i_v) == (8, 16)
        assert plan.shard_shape(i_v) == (2, 16)
        # The padded extent rides the collective and is budgeted.
        [b_v] = [b for b in range(plan.n_buckets) if plan.bucket_padded[b]]
        assert plan.bucket_nbytes[b_v] == 8 * 16 * 4

    def test_pack_gathered_roundtrip(self):
        """pack (shard-major, zero-padded) → leaf_buffers(gathered) is the
        identity on the uneven leaf — the unpad really unpads."""
        params, specs = self._tree_specs()
        plan = GradBuckets.plan_sharded(params, specs, shard_size=4,
                                        bucket_bytes=1 << 20)
        bufs = plan.pack(params)
        leaves = jax.tree.leaves(params)
        for b in range(plan.n_buckets):
            if not plan.bucket_padded[b]:
                continue
            out = plan.leaf_buffers(b, bufs[b], layout="gathered")
            for i, v in out.items():
                np.testing.assert_array_equal(np.asarray(v),
                                              np.asarray(leaves[i]))

    def test_microbatch_grads_match_full_batch(self, caplog):
        """Numerics pin: uneven ZeRO-3 grads (padded scatter + tail
        gather/unpad) match plain full-batch jax.grad within 1e-6 relative; even
        leaves still exit in the shard layout, uneven ones whole — and
        the lost per-leaf memory saving is warned about loudly."""
        params, specs = self._tree_specs()
        mesh = par.make_mesh(fsdp=4)               # data=2 x fsdp=4
        kb = jax.random.split(jax.random.PRNGKey(8), 2)
        batch = {"x": jax.random.normal(kb[0], (32, 16)),
                 "y": jax.random.normal(kb[1], (32, 6))}

        def loss_fn(p, mb):
            out = mb["x"] @ (p["w"].T @ jnp.ones((8, 6)) @ p["v"]
                             + jnp.diag(p["b"]))
            return jnp.mean((out[:, :6] - mb["y"]) ** 2)

        profiler.reset_records("overlap")
        loss, grads = jax.jit(lambda p, b: microbatch_grads(
            loss_fn, p, b, mesh, microbatches=4,
            bucket_bytes=1 << 20, param_specs=specs))(params, batch)
        ref_loss, ref = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, batch)))(params)
        # Loss runs ~6e2 (one f32 ulp there is 6.1e-5): microbatch-sum
        # reassociation is worth a few ulp, so 1e-6 RELATIVE.
        assert abs(float(loss) - float(ref_loss)) \
            < 1e-6 * max(1.0, abs(float(ref_loss)))
        assert grads["v"].shape == (6, 16)          # whole, unpadded
        assert "fsdp" in str(grads["w"].sharding.spec)
        # Grad magnitudes run ~5e2 here: 1e-4 abs ≈ 2e-7 relative.
        np.testing.assert_allclose(np.asarray(grads["v"]),
                                   np.asarray(ref["v"]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(grads["b"]),
                                   np.asarray(ref["b"]), atol=1e-4)
        np.testing.assert_allclose(np.asarray(jax.device_get(grads["w"])),
                                   np.asarray(ref["w"]), atol=1e-4)
        rec = profiler.report("overlap")["accum_step"]
        assert rec["n_padded_buckets"] == 1
        assert "fsdp-indivisible" in caplog.text

    @pytest.mark.multislice
    def test_uneven_hierarchical_multislice(self):
        """The same pin on a 2-slice mesh: the padded bucket's in-scan
        psum_scatter + DCN allreduce + tail gather still sums over the
        whole sync group."""
        params, specs = self._tree_specs()
        mesh = par.make_mesh(slices=2, fsdp=4)     # slice=2 x fsdp=4
        kb = jax.random.split(jax.random.PRNGKey(9), 2)
        batch = {"x": jax.random.normal(kb[0], (32, 16)),
                 "y": jax.random.normal(kb[1], (32, 6))}

        def loss_fn(p, mb):
            out = mb["x"] @ (p["w"].T @ jnp.ones((8, 6)) @ p["v"]
                             + jnp.diag(p["b"]))
            return jnp.mean((out[:, :6] - mb["y"]) ** 2)

        loss, grads = jax.jit(lambda p, b: microbatch_grads(
            loss_fn, p, b, mesh, microbatches=2,
            bucket_bytes=1 << 20, param_specs=specs))(params, batch)
        ref_loss, ref = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, batch)))(params)
        assert abs(float(loss) - float(ref_loss)) < 1e-5
        # Grad magnitudes run ~5e2 here: 1e-4 abs ≈ 2e-7 relative.
        np.testing.assert_allclose(np.asarray(grads["v"]),
                                   np.asarray(ref["v"]), atol=1e-4)


def test_fsdp_param_specs_detection():
    """Replicated params, fsdp=1 meshes, and non-array leaves all decline
    detection; a llama state created on an fsdp mesh through the logical
    rules opts in automatically."""
    mesh_dp = par.make_mesh()
    state, _ = _mnist_setup()
    assert overlap.fsdp_param_specs(state.params, mesh_dp) is None
    mesh_f = par.make_mesh(fsdp=4)
    assert overlap.fsdp_param_specs(state.params, mesh_f) is None
    assert overlap.fsdp_param_specs(
        {"w": np.zeros((4, 4))}, mesh_f) is None
    zstate = _zero3_state(state, mesh_f)
    specs = overlap.fsdp_param_specs(zstate.params, mesh_f)
    assert specs is not None


def test_profiler_records_bucket_plan():
    profiler.reset_records("overlap")
    mesh = par.make_mesh()
    state, batch = _mnist_setup()
    step = train.make_accum_train_step(mesh=mesh, microbatches=4,
                                       bucket_bytes=32 * 1024, donate=False)
    step(state, batch)
    rec = profiler.report("overlap")
    assert "accum_step" in rec
    assert rec["accum_step"]["n_buckets"] >= 1
    assert sum(rec["accum_step"]["bucket_nbytes"]) == sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(state.params))
    assert rec["accum_step"]["microbatches"] == 4


class TestOverlapXlaFlags:
    def test_all_flags_present_on_empty(self):
        out = overlap_xla_flags()
        for f in OVERLAP_XLA_FLAGS:
            assert f in out

    def test_multislice_adds_dcn_set(self):
        out = overlap_xla_flags(multislice=True)
        for f in OVERLAP_XLA_FLAGS + MULTISLICE_XLA_FLAGS:
            assert f in out
        assert MULTISLICE_XLA_FLAGS[0] not in overlap_xla_flags()

    def test_user_flag_wins(self):
        user = "--xla_tpu_enable_latency_hiding_scheduler=false"
        out = overlap_xla_flags(user)
        assert "--xla_tpu_enable_latency_hiding_scheduler=false" in out
        assert "--xla_tpu_enable_latency_hiding_scheduler=true" not in out

    def test_unrelated_user_flags_kept(self):
        out = overlap_xla_flags("--xla_force_host_platform_device_count=8")
        assert "--xla_force_host_platform_device_count=8" in out
        assert "--xla_tpu_enable_async_collective_fusion=true" in out

    def test_idempotent(self):
        once = overlap_xla_flags()
        assert overlap_xla_flags(once) == once


def test_record_failure_logs_debug_once(monkeypatch, caplog):
    """Satellite pin: a broken profiler wiring must neither sink the step
    nor stay silent — one DEBUG line on the first failure, then quiet."""
    import logging

    class Boom(dict):
        def __setitem__(self, tag, fields):
            raise RuntimeError("profiler wired wrong")

    monkeypatch.setattr(profiler, "_RECORD_FAILED", set())
    monkeypatch.setitem(profiler._RECORDS, "overlap", Boom())
    with caplog.at_level(logging.DEBUG, logger="tony_tpu.profiler"):
        overlap._record("t1", n=1)      # must not raise
        overlap._record("t2", n=2)
    hits = [r for r in caplog.records if "profiler record" in r.message]
    assert len(hits) == 1
    assert hits[0].levelno == logging.DEBUG


def test_train_step_seq_axis_keeps_ring_sharding():
    """Satellite pin: make_train_step(seq_axis=True) constrains the batch
    with the sequence dim on the ring axis (it used to re-constrain
    long-context batches OFF it) and still trains. The rank-1 "w" leaf
    pins the leaf-rank guard: the (batch, seq) spec must not be forced
    onto labels/weights."""
    mesh = par.make_mesh(sp=2)
    model = get_model("llama-tiny")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)
    state = train.create_train_state(
        model, optax.adam(1e-2), tokens, jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda logits, b: train.next_token_loss(logits, b["x"]),
        mesh=mesh, seq_axis=True, donate=False)
    _, metrics = step(state, {"x": tokens, "w": jnp.ones((8,))})
    assert np.isfinite(float(metrics["loss"]))
