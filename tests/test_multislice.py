"""Multi-slice tier (host-simulated 2-slice mesh on the virtual 8-device
CPU backend): the hierarchical ICI/DCN bucketed reduce and its ZeRO-3
combination — the `make tier1` multislice leg (`-m multislice`) gates these
paths explicitly. On one host both levels ride the same transport, so these
are NUMERICS pins (hierarchical == flat == monolithic); the DCN timing
story needs a real multi-slice pod (ROADMAP)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tony_tpu import parallel as par
from tony_tpu import profiler, train
from tony_tpu.models import get_model
from tony_tpu.parallel import overlap
from tony_tpu.train import fsdp_shard_state

pytestmark = pytest.mark.multislice


def _mnist_setup(batch=32, hidden=64):
    model = get_model("mnist-mlp", hidden=hidden)
    kx, ky, kr = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (batch, 784))
    y = jax.random.randint(ky, (batch,), 0, 10)
    state = train.create_train_state(model, optax.sgd(0.1), x, kr)
    return state, {"x": x, "y": y}


def test_two_slice_mesh_shape_and_batch_placement():
    mesh = par.make_mesh(slices=2)
    assert mesh.shape["slice"] == 2 and mesh.shape["data"] == 4
    spec = par.batch_sharding(mesh).spec
    assert spec == jax.sharding.PartitionSpec(("slice", "data", "fsdp"))
    assert overlap.dcn_axis(mesh) == "slice"
    assert overlap.ici_axes(mesh) == ("data", "fsdp")


def test_hierarchical_accum_matches_flat_and_monolithic():
    """THE multi-slice acceptance pin: per-bucket psum_scatter over ICI +
    DCN allreduce inside the scan == flat single-level reduce == the
    monolithic GSPMD step, within 1e-5."""
    mesh = par.make_mesh(slices=2)
    state, batch = _mnist_setup()
    mono = train.make_train_step(mesh=mesh, donate=False)
    hier = train.make_accum_train_step(
        mesh=mesh, microbatches=4, bucket_bytes=32 * 1024, donate=False)
    flat = train.make_accum_train_step(
        mesh=mesh, microbatches=4, bucket_bytes=32 * 1024,
        hierarchy="flat", donate=False)
    s1, m1 = mono(state, batch)
    s2, m2 = hier(state, batch)
    s3, m3 = flat(state, batch)
    for m in (m2, m3):
        assert abs(float(m1["loss"]) - float(m["loss"])) < 1e-5
        assert abs(float(m1["grad_norm"]) - float(m["grad_norm"])) < 1e-5
    for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_hierarchical_profiler_level_records():
    """Per-level bucket plan records: the ICI level carries the full
    bucket bytes (psum_scatter input), the DCN level the scattered-chunk
    bytes — what actually crosses slices per bucket."""
    profiler.reset_records("overlap")
    mesh = par.make_mesh(slices=2)
    state, batch = _mnist_setup()
    step = train.make_accum_train_step(
        mesh=mesh, microbatches=4, bucket_bytes=32 * 1024, donate=False)
    step(state, batch)
    rec = profiler.report("overlap")["accum_step"]
    assert rec["hierarchy"] == "hierarchical"
    by_level = {l["level"]: l for l in rec["levels"]}
    assert by_level["ici"]["op"] == "psum_scatter"
    assert by_level["ici"]["axes"] == ["data", "fsdp"]
    assert by_level["dcn"]["op"] == "all_reduce"
    assert by_level["dcn"]["axes"] == ["slice"]
    ici_group = 4   # data=4 x fsdp=1
    for full, chunk in zip(by_level["ici"]["bucket_nbytes"],
                           by_level["dcn"]["bucket_nbytes"]):
        assert 0 < chunk <= -(-full // ici_group) + 4 * ici_group
    assert sum(by_level["ici"]["bucket_nbytes"]) == sum(
        l.size * l.dtype.itemsize for l in jax.tree.leaves(state.params))


def test_zero3_on_two_slice_mesh():
    """ZeRO-3 x multi-slice: grads psum_scatter over fsdp, psum over the
    intra-slice data axis, DCN allreduce over slice — all inside the scan
    — and the result still matches the monolithic step, with updates in
    the shard layout."""
    mesh = par.make_mesh(slices=2, fsdp=2)    # slice=2 x data=2 x fsdp=2
    state, batch = _mnist_setup()
    mono = train.make_train_step(mesh=mesh, donate=False)
    s1, m1 = mono(state, batch)
    zstate = fsdp_shard_state(state, mesh)
    profiler.reset_records("overlap")
    for hierarchy in ("auto", "flat"):
        step = train.make_accum_train_step(
            mesh=mesh, microbatches=4, bucket_bytes=32 * 1024,
            hierarchy=hierarchy, donate=False)
        s2, m2 = step(zstate, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-5
        assert abs(float(m1["grad_norm"]) - float(m2["grad_norm"])) < 1e-5
        for a, b in zip(jax.tree.leaves(s1.params),
                        jax.tree.leaves(s2.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        assert sum("fsdp" in str(leaf.sharding.spec)
                   for leaf in jax.tree.leaves(s2.params)) >= 4
    rec = profiler.report("overlap")["accum_step"]
    assert rec["zero3"] is True and rec["n_scatter_buckets"] >= 1


def test_zero3_multislice_grad_shardings():
    mesh = par.make_mesh(slices=2, fsdp=2)
    state, batch = _mnist_setup()
    zstate = fsdp_shard_state(state, mesh)
    specs = overlap.fsdp_param_specs(zstate.params, mesh)

    def loss_fn(params, mb):
        logits = zstate.apply_fn({"params": params}, mb["x"])
        return train.cross_entropy_loss(logits, mb["y"])

    with jax.sharding.Mesh(mesh.devices, mesh.axis_names):
        _, grads = jax.jit(lambda p, b: overlap.microbatch_grads(
            loss_fn, p, b, mesh, microbatches=4, bucket_bytes=32 * 1024,
            param_specs=specs))(zstate.params, batch)
    assert sum("fsdp" in str(g.sharding.spec)
               for g in jax.tree.leaves(grads)) >= 4


def test_create_train_state_fsdp_autodetects():
    """A transformer state created through the logical rules on an fsdp
    mesh (embed→fsdp) opts into the ZeRO-3 path with no flag."""
    mesh = par.make_mesh(fsdp=4)
    model = get_model("llama-tiny")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)
    state = train.create_train_state(
        model, optax.adam(1e-2), tokens, jax.random.PRNGKey(0), mesh=mesh)
    specs = overlap.fsdp_param_specs(state.params, mesh)
    assert specs is not None
    flat = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    assert any("fsdp" in str(s) for s in flat)
