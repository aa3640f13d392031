"""Model + train-harness tests on the 8-device CPU mesh: forward shapes,
sharded init, one GSPMD train step per parallelism layout."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tony_tpu import parallel as par
from tony_tpu import remat, train
from tony_tpu.models import get_model
from tony_tpu.models.resnet import resnet50_flops


def test_mnist_models_forward():
    x = jnp.zeros((4, 28 * 28))
    for name in ("mnist-mlp", "mnist-cnn"):
        model = get_model(name)
        params = model.init(jax.random.PRNGKey(0), x)
        out = model.apply(params, x)
        assert out.shape == (4, 10)


def test_resnet_forward_and_bn_state():
    model = get_model("resnet18-thin")
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert "batch_stats" in variables
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert resnet50_flops(32) > 1e11


def test_llama_tiny_forward_loss_decreases():
    model = get_model("llama-tiny")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
    tx = optax.adam(1e-2)
    state = train.create_train_state(
        model, tx, tokens, jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda logits, b: train.next_token_loss(logits, b["x"]))
    losses = []
    for _ in range(5):
        state, metrics = step(state, {"x": tokens})
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


@pytest.mark.parametrize("spec_kw", [
    dict(),                      # pure DP over 8 devices
    dict(fsdp=2, tp=2),          # DP×FSDP×TP
    dict(tp=4),                  # DP×TP
])
def test_llama_sharded_train_step(spec_kw):
    mesh = par.make_mesh(**spec_kw)
    model = get_model("llama-tiny")
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 256)
    tx = optax.adam(1e-3)
    state = train.create_train_state(
        model, tx, tokens, jax.random.PRNGKey(0), mesh=mesh)
    # Params actually sharded per the rules: an ffn kernel splits over model.
    if spec_kw.get("tp", 1) > 1:
        ffn = state.params["layers"]["block"]["mlp"]["w_gate"]["kernel"]
        assert "model" in jax.tree_util.tree_leaves(
            [ffn.sharding.spec])[0] or any(
            "model" == s or (isinstance(s, tuple) and "model" in s)
            for s in ffn.sharding.spec if s)
    step = train.make_train_step(
        loss_of=lambda logits, b: train.next_token_loss(logits, b["x"]),
        mesh=mesh)
    state, metrics = step(state, {"x": tokens})
    assert np.isfinite(float(metrics["loss"]))
    state, metrics2 = step(state, {"x": tokens})
    assert float(metrics2["loss"]) < float(metrics["loss"]) + 1.0


def test_llama_ring_attention_end_to_end():
    mesh = par.make_mesh(sp=4)
    model = get_model("llama-tiny", attention="ring", mesh=mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    tx = optax.sgd(1e-3)
    state = train.create_train_state(
        model, tx, tokens, jax.random.PRNGKey(0), mesh=mesh)
    step = train.make_train_step(
        loss_of=lambda logits, b: train.next_token_loss(logits, b["x"]),
        mesh=mesh)
    state, metrics = step(state, {"x": tokens})
    assert np.isfinite(float(metrics["loss"]))


def test_ring_equals_reference_attention_in_model():
    """Same weights, ring vs reference attention → same logits."""
    mesh = par.make_mesh(sp=4)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 256)
    ref_model = get_model("llama-tiny", attention="reference")
    ring_model = get_model("llama-tiny", attention="ring", mesh=mesh)
    import flax.linen as nn
    variables = nn.unbox(ref_model.init(jax.random.PRNGKey(0), tokens))
    with nn.logical_axis_rules(par.RULES):
        ref_out = ref_model.apply(variables, tokens)
        with jax.set_mesh(mesh):
            ring_out = jax.jit(ring_model.apply)(variables, tokens)
    np.testing.assert_allclose(np.asarray(ref_out), np.asarray(ring_out),
                               atol=2e-4, rtol=2e-4)


def test_resnet_dp_train_step_on_mesh():
    mesh = par.make_mesh()   # 8-way DP
    model = get_model("resnet18-thin", dtype=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 32, 32, 3))
    y = jax.random.randint(jax.random.PRNGKey(1), (8,), 0, 10)

    variables = model.init(jax.random.PRNGKey(2), x)
    import flax.linen as nn

    # BN models carry batch_stats: run a manual step with mutable state.
    def loss_fn(params, batch_stats):
        logits, updates = model.apply(
            {"params": nn.unbox(params), "batch_stats": batch_stats},
            x, train=True, mutable=["batch_stats"])
        return train.cross_entropy_loss(logits, y), updates["batch_stats"]

    with jax.set_mesh(mesh):
        (loss, _), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True))(
            variables["params"], variables["batch_stats"])
    assert np.isfinite(float(loss))


def test_llama_packed_attention_branch_matches_reference():
    """head_dim=128 + flash + no mesh takes the packed-layout attention
    branch (rope seq_axis=1, GQA repeat in packed form); its logits must
    match the classic reference-attention model on the same params."""
    kw = dict(dim=512, n_heads=4, n_kv_heads=2, ffn_hidden=256,
              vocab=128, n_layers=2, max_seq=32, scan_layers=True,
              remat=False)
    flash_model = get_model("llama-tiny", attention="flash", **kw)
    ref_model = get_model("llama-tiny", attention="reference", **kw)
    assert flash_model.cfg.head_dim == 128  # packed branch precondition
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, 128)
    variables = flash_model.init(jax.random.PRNGKey(0), tokens)
    out_flash = flash_model.apply(variables, tokens)
    out_ref = ref_model.apply(variables, tokens)
    np.testing.assert_allclose(np.asarray(out_flash), np.asarray(out_ref),
                               atol=5e-2, rtol=5e-2)


def test_chunked_xent_matches_plain_head():
    """cfg.xent_chunk fuses head+loss without materializing logits; the
    loss AND all shared-param grads must match the plain head + 
    next_token_loss path (the lm_head kernel moves from lm_head/kernel to
    lm_head_kernel — remapped here)."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 256)
    plain = get_model("llama-tiny", dtype=jnp.float32)
    fused = get_model("llama-tiny", dtype=jnp.float32, xent_chunk=8)
    variables = plain.init(jax.random.PRNGKey(0), tokens)
    fparams = dict(variables["params"])
    fparams["lm_head_kernel"] = fparams.pop("lm_head")["kernel"]

    def loss_plain(p):
        logits = plain.apply({"params": p}, tokens)
        return train.next_token_loss(logits, tokens)

    def loss_fused(p):
        return fused.apply({"params": p}, tokens, targets=tokens)

    lp, gp = jax.value_and_grad(loss_plain)(variables["params"])
    lf, gf = jax.value_and_grad(loss_fused)(fparams)
    np.testing.assert_allclose(float(lf), float(lp), rtol=1e-5)
    gp = dict(gp)
    gp["lm_head_kernel"] = gp.pop("lm_head")["kernel"]
    for (kp, a), (kf, b) in zip(
            sorted(jax.tree_util.tree_leaves_with_path(gp),
                   key=lambda t: str(t[0])),
            sorted(jax.tree_util.tree_leaves_with_path(gf),
                   key=lambda t: str(t[0]))):
        assert str(kp) == str(kf)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=1e-5, rtol=1e-4, err_msg=str(kp))
    # 2·16 = 32 rows over chunk=8 → 4 whole chunks; also exercise padding.
    fused_pad = get_model("llama-tiny", dtype=jnp.float32, xent_chunk=7)
    lpad = fused_pad.apply({"params": fparams}, tokens, targets=tokens)
    np.testing.assert_allclose(float(lpad), float(lp), rtol=1e-5)


def test_chunked_xent_through_train_step():
    """The train harness drives the fused-loss model via apply_kwargs_of;
    loss decreases like the plain path."""
    model = get_model("llama-tiny", xent_chunk=8)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 256)
    state = train.create_train_state(
        model, optax.adam(1e-2), tokens, jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda out, batch: out,
        apply_kwargs_of=lambda batch: {"targets": batch["x"]})
    losses = []
    for _ in range(5):
        state, metrics = step(state, {"x": tokens})
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]


def test_s2d_stem_equivalence():
    """The space-to-depth stem is EXACTLY the 7x7/s2 stem: transporting a
    7x7 kernel through s2d_stem_kernel and running the 4x4/s1 conv on the
    packed input reproduces the original conv's output."""
    from tony_tpu.models.resnet import s2d_stem_kernel

    k = jax.random.PRNGKey(3)
    x = jax.random.normal(k, (2, 32, 32, 3), jnp.float32)
    k7 = jax.random.normal(jax.random.PRNGKey(4), (7, 7, 3, 8), jnp.float32)
    ref = jax.lax.conv_general_dilated(
        x, k7, window_strides=(2, 2), padding=[(3, 3), (3, 3)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    n, h, w, c = x.shape
    xp = x.reshape(n, h // 2, 2, w // 2, 2, c)
    xp = xp.transpose(0, 1, 3, 2, 4, 5).reshape(n, h // 2, w // 2, 4 * c)
    out = jax.lax.conv_general_dilated(
        xp, s2d_stem_kernel(k7), window_strides=(1, 1),
        padding=[(2, 1), (2, 1)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_s2d_resnet_trains():
    """The s2d_stem model variant runs a full train step (shapes line up
    through maxpool and the stages) and matches the baseline parameter
    structure apart from the stem kernel shape."""
    model = get_model("resnet18-thin", s2d_stem=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 32, 32, 3), jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(1), x, train=False)
    assert variables["params"]["stem"]["kernel"].shape == (4, 4, 12, 8)
    out, updates = model.apply(variables, x, train=True,
                               mutable=["batch_stats"])
    assert out.shape == (2, 10)


@pytest.mark.parametrize("rung", remat.LADDER + (remat.FLOOR,),
                         ids=lambda r: "+".join(r) or "floor")
def test_remat_rung_trains(rung):
    """Every set of residuals the train step may choose (tony_tpu.remat;
    the ``remat_policy`` option it replaced is gone) trains, on the
    unrolled stack too, and keeps the floor's loss."""
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 16), 0, 256)
    model = get_model("llama-tiny", remat=True, scan_layers=False)
    step = train.make_train_step(
        loss_of=lambda lg, b: train.next_token_loss(lg, b["x"]))
    losses = []
    for names in (rung, remat.FLOOR):
        state = train.create_train_state(
            model, optax.adam(1e-3), tokens, jax.random.PRNGKey(1))
        _, m = step.build(remat.Saved(names))(state, {"x": tokens})
        losses.append(float(m["loss"]))
    assert np.isfinite(losses[0]) and losses[0] == losses[1]
    with pytest.raises(TypeError, match="remat_policy"):
        get_model("llama-tiny", remat=True, remat_policy="dots")
