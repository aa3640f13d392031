"""Fused BatchNorm(+add)(+ReLU) kernels vs the reference math (reference
tier: op unit tests, SURVEY.md §4; VERDICT r3 #1). Interpret mode on the
CPU mesh — no cell of the benchmark runs them compiled on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tony_tpu.ops.batchnorm import fused_bn_act, pick_block_rows


def ref_bn_act(x, gamma, beta, residual=None, eps=1e-5, relu=True):
    xf = x.astype(jnp.float32)
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(xf, axis=axes)
    var = jnp.mean((xf - mean) ** 2, axis=axes)
    out = (xf - mean) * jax.lax.rsqrt(var + eps) * gamma + beta
    if residual is not None:
        out = out + residual.astype(jnp.float32)
    if relu:
        out = jnp.maximum(out, 0.0)
    return out.astype(x.dtype), mean, var


def rand(key, shape):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)


@pytest.mark.parametrize("relu", [True, False])
@pytest.mark.parametrize("with_residual", [False, True])
def test_fused_bn_matches_reference_fwd_bwd(relu, with_residual):
    n, h, w, c = 4, 8, 8, 16
    x = rand(0, (n, h, w, c))
    gamma = rand(1, (c,)) * 0.5 + 1.0
    beta = rand(2, (c,)) * 0.1
    res = rand(3, (n, h, w, c)) if with_residual else None
    wgt = rand(4, (n, h, w, c))

    def loss_fused(x, gamma, beta, res):
        out, mean, var = fused_bn_act(x, gamma, beta, res, relu=relu,
                                      interpret=True)
        return (out * wgt).sum(), (mean, var)

    def loss_ref(x, gamma, beta, res):
        out, mean, var = ref_bn_act(x, gamma, beta, res, relu=relu)
        return (out * wgt).sum(), (mean, var)

    args = (x, gamma, beta, res)
    diff = (0, 1, 2, 3) if with_residual else (0, 1, 2)
    (lf, (mf, vf)), gf = jax.value_and_grad(
        loss_fused, diff, has_aux=True)(*args)
    (lr, (mr, vr)), gr = jax.value_and_grad(
        loss_ref, diff, has_aux=True)(*args)
    np.testing.assert_allclose(float(lf), float(lr), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(mf), np.asarray(mr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vf), np.asarray(vr),
                               atol=1e-4, rtol=1e-4)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


def test_fused_bn_bf16_inputs():
    n, h, w, c = 2, 4, 4, 32
    x = rand(0, (n, h, w, c)).astype(jnp.bfloat16)
    gamma = jnp.ones((c,))
    beta = jnp.zeros((c,))
    out, mean, var = fused_bn_act(x, gamma, beta, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref, rmean, rvar = ref_bn_act(x, gamma, beta)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(rmean),
                               atol=2e-2, rtol=2e-2)


def test_pick_block_rows_budget_and_divisibility():
    bm = pick_block_rows(1024, 64)
    assert bm is not None and 1024 % bm == 0
    for n_bufs in (3, 5):   # plain and residual dx kernels
        bm = pick_block_rows(18816, 2048, 2, n_bufs)  # batch 384·7², C 2048
        assert bm is not None and 18816 % bm == 0
        # Double-buffered blocks of the worst kernel fit the VMEM budget.
        assert 2 * n_bufs * bm * 2048 * 2 <= 8 << 20
    assert pick_block_rows(17, 64) is None  # prime-ish M: no clean tiling
    # Very wide C: even 16 rows blow the budget — must fall back to XLA,
    # not dispatch a kernel that OOMs VMEM at compile time.
    assert pick_block_rows(1024, 32768) is None


def _rename_fused(tree):
    """Map the plain model's param/stat paths onto the fused model's
    (Bottleneck→FusedBottleneck, BatchNorm→FusedBNAct; numbering and
    explicit names line up by construction)."""
    if not isinstance(tree, dict):
        return tree
    out = {}
    for k, v in tree.items():
        k2 = k.replace("Bottleneck", "FusedBottleneck").replace(
            "BatchNorm", "FusedBNAct")
        out[k2] = _rename_fused(v)
    return out


@pytest.mark.slow
def test_fused_resnet_matches_plain_resnet():
    """Whole-model equivalence: same params ⇒ same logits, same grads,
    same running-stat updates (f32 to isolate kernel math from bf16)."""
    from tony_tpu.models import get_model

    plain = get_model("resnet18-thin", dtype=jnp.float32)
    fused = get_model("resnet18-thin", dtype=jnp.float32, fused_bn=True,
                      bn_interpret=True)
    x = rand(0, (4, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(9), (4,), 0, 10)
    variables = plain.init(jax.random.PRNGKey(1), x, train=False)
    fvars = _rename_fused(variables)

    def loss(model, vars_, x):
        logits, updates = model.apply(
            vars_, x, train=True, mutable=["batch_stats"])
        one_hot = jax.nn.one_hot(y, 10)
        return -(one_hot * jax.nn.log_softmax(logits)).sum(), updates

    (lp, up), gp = jax.value_and_grad(
        lambda v: loss(plain, {"params": v,
                               "batch_stats": variables["batch_stats"]}, x),
        has_aux=True)(variables["params"])
    (lf, uf), gf = jax.value_and_grad(
        lambda v: loss(fused, {"params": v,
                               "batch_stats": fvars["batch_stats"]}, x),
        has_aux=True)(fvars["params"])
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-4)
    flat_p = jax.tree_util.tree_leaves_with_path(_rename_fused(gp))
    flat_f = jax.tree_util.tree_leaves_with_path(gf)
    assert len(flat_p) == len(flat_f)
    for (kp, a), (kf, b) in zip(sorted(flat_p, key=lambda t: str(t[0])),
                                sorted(flat_f, key=lambda t: str(t[0]))):
        assert str(kp) == str(kf)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=5e-3, rtol=5e-3, err_msg=str(kp))
    # Running stats advanced identically.
    sp = jax.tree_util.tree_leaves(_rename_fused(up["batch_stats"]))
    sf = jax.tree_util.tree_leaves(uf["batch_stats"])
    for a, b in zip(sp, sf):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=1e-4)


def test_fused_resnet_eval_path_uses_running_stats():
    from tony_tpu.models import get_model

    fused = get_model("resnet18-thin", dtype=jnp.float32, fused_bn=True,
                      bn_interpret=True)
    x = rand(0, (2, 32, 32, 3))
    variables = fused.init(jax.random.PRNGKey(1), x, train=False)
    out = fused.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert bool(jnp.all(jnp.isfinite(out)))
