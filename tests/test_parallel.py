"""Compute-plane tests: mesh building, sharding rules, ring attention —
on the virtual 8-device CPU mesh (conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu import parallel as par


# THE semantic spec (GQA repeat included) — not a local re-implementation,
# so a change to the canonical mapping fails these tests instead of
# silently diverging.
from tony_tpu.ops import reference_attention  # noqa: E402


def test_mesh_spec_fills_dp():
    mesh = par.make_mesh(tp=2, sp=2)
    assert mesh.shape["data"] == 2  # 8 / (2*2)
    assert mesh.shape["model"] == 2 and mesh.shape["seq"] == 2
    assert mesh.axis_names == par.AXES


def test_mesh_spec_rejects_bad_shape():
    with pytest.raises(ValueError):
        par.MeshSpec(dp=3, tp=2).build(jax.devices())  # 6 != 8


def test_logical_sharding_rules():
    mesh = par.make_mesh(fsdp=2, tp=4)
    s = par.logical_sharding(mesh, "embed", "ffn")
    assert s.spec == jax.sharding.PartitionSpec("fsdp", "model")
    s2 = par.logical_sharding(mesh, "batch", "act_seq", "act_embed")
    assert s2.spec == jax.sharding.PartitionSpec(
        ("slice", "data", "fsdp"), "seq", None)


def test_shard_logical_places_array():
    mesh = par.make_mesh(fsdp=2, tp=4)
    w = par.shard_logical(mesh, jnp.zeros((16, 32)), "embed", "ffn")
    assert w.sharding.spec == jax.sharding.PartitionSpec("fsdp", "model")


def test_logical_sharding_unknown_axis_raises():
    """A typo'd logical axis used to fall through to None and silently
    replicate the dim — it must raise, naming the bad axis."""
    mesh = par.make_mesh(fsdp=2, tp=4)
    with pytest.raises(ValueError, match="embde"):
        par.logical_sharding(mesh, "embde", "ffn")
    with pytest.raises(ValueError, match="allow_unknown"):
        par.constraint(jnp.zeros((4, 4)), mesh, "nope", None)


def test_logical_sharding_allow_unknown_escape_hatch():
    mesh = par.make_mesh(fsdp=2, tp=4)
    s = par.logical_sharding(mesh, "custom_axis", "ffn",
                             allow_unknown=True)
    assert s.spec == jax.sharding.PartitionSpec(None, "model")


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_matches_reference(causal):
    mesh = par.make_mesh(sp=8)
    b, h, t, d = 2, 4, 64, 16
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, h, t, d), jnp.float32)
    v = jax.random.normal(kv, (b, h, t, d), jnp.float32)
    out = par.ring_attention_sharded(q, k, v, mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_grad_flows():
    mesh = par.make_mesh(sp=4, tp=2)
    b, h, t, d = 1, 2, 32, 8
    q = jax.random.normal(jax.random.PRNGKey(1), (b, h, t, d))

    def loss(q):
        return par.ring_attention_sharded(q, q, q, mesh).sum()

    g = jax.jit(jax.grad(loss))(q)   # jit: one compile, not one per op
    assert g.shape == q.shape
    assert bool(jnp.isfinite(g).all())


@pytest.mark.parametrize("causal", [True, False])
def test_ring_attention_gqa_matches_reference(causal):
    """Zero-copy GQA through the ring (r5): K/V carry fewer heads and the
    NARROW blocks rotate — values must match repeat-then-attend, and the
    group fold must keep per-head identity (h -> kv h//reps)."""
    mesh = par.make_mesh(sp=4)
    b, h, hkv, t, d = 2, 4, 2, 64, 16
    key = jax.random.PRNGKey(5)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, h, t, d), jnp.float32)
    k = jax.random.normal(kk, (b, hkv, t, d), jnp.float32)
    v = jax.random.normal(kv, (b, hkv, t, d), jnp.float32)
    out = par.ring_attention_sharded(q, k, v, mesh, causal=causal)
    ref = reference_attention(q, k, v, causal=causal)  # repeats internally
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_ring_attention_gqa_grads_flow():
    mesh = par.make_mesh(sp=4)
    b, h, hkv, t, d = 2, 4, 2, 32, 8
    q = jax.random.normal(jax.random.PRNGKey(1), (b, h, t, d))
    k = jax.random.normal(jax.random.PRNGKey(2), (b, hkv, t, d))
    v = jax.random.normal(jax.random.PRNGKey(3), (b, hkv, t, d))

    def loss(q, k, v):
        return par.ring_attention_sharded(q, k, v, mesh).sum()

    gq, gk, gv = jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v)
    assert gq.shape == q.shape and gk.shape == k.shape and gv.shape == v.shape
    for g in (gq, gk, gv):
        assert bool(jnp.isfinite(g).all())


def test_ring_attention_gqa_rejects_ragged():
    mesh = par.make_mesh(sp=4)
    q = jnp.zeros((2, 4, 32, 8))
    kv = jnp.zeros((2, 3, 32, 8))
    with pytest.raises(ValueError, match="multiple"):
        par.ring_attention_sharded(q, kv, kv, mesh)


def test_ring_attention_gqa_tp_wider_than_kv_heads_falls_back():
    """kv heads that don't divide the model axis (kv=2 over tp=4) cannot
    stay narrow under shard_map — the wrapper must repeat K/V and still be
    exact (the pre-r5 behavior), not raise."""
    mesh = par.make_mesh(tp=4, sp=2)
    b, h, hkv, t, d = 2, 8, 2, 32, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (b, h, t, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, t, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, t, d), jnp.float32)
    out = par.ring_attention_sharded(q, k, v, mesh, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
