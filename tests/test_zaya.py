"""The ``zaya1-8b`` path at tiny sizes on the CPU, seeded weights: the system
(``get_model`` -> ``create_train_state`` -> ``make_train_step``) against
the benchmark's plain reference — the loss, every gradient leaf, two AdamW
steps, in float32 and bfloat16 compute — the ``(x, router_state)`` carry
scanned and unrolled, the tied table's gradient, the top-1 gate's gradient,
the **share test** (the two halves' expert outputs add up to the uncut
16-expert layer), the chunk rule, the rotated share of a head and the FLOPs
a token is charged."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import modelcfg_zaya1 as mc
from benchmark import reference, reference_zaya1 as ref
from benchmark import roofline_zaya1, weights_zaya1 as wz
from tony_tpu import train
from tony_tpu.models import get_model, moe
from tony_tpu.models.moe import DroplessMoE
from tony_tpu.models.transformer import rope

CFG = mc.tiny(mc.load("zaya1-8b"))
B, S, LR = 2, 64, 3e-4
LEAVES = sorted(wz.leaf_specs(CFG))
# The first layer's gamma multiplies the zero state before it.
DEAD = ("r_gamma",)
CHUNK_RULE = moe.chunk_tokens       # read before the fixture patches it


@pytest.fixture(scope="module", autouse=True)
def two_chunks():
    """The tiny batch in more than one piece: four chunks of the expert
    layer a batch."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "chunk_tokens", lambda top_k, n_experts: 32)
        yield


def _model(dtype=jnp.float32, **kw):
    kwargs = mc.program_kwargs(CFG, S)
    kwargs.update(remat=False, dtype=dtype, **kw)
    return get_model(CFG["program"]["model"], **kwargs)


def _tokens(seed, n=1):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, CFG["vocab"], (B, S), dtype=np.int32))
            for _ in range(n)]


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both(request):
    """Program (in the parametrised compute dtype) and reference over the
    same seeded weights and batch: the loss and its gradient, leaf by
    leaf, and the tolerance the dtype allows."""
    model = _model(jnp.dtype(request.param))
    w0, (x,) = wz.make_weights(CFG, 7), _tokens(0)
    loss, g = jax.value_and_grad(lambda p: model.apply(
        {"params": p}, x, targets=x))(wz.to_program_tree(w0))
    ref_loss, rg = jax.value_and_grad(lambda w: ref.loss(w, x, CFG))(w0)
    # (loss, widest element of a gradient leaf over the leaf's largest):
    # float32 reads 0 and 1.5e-6; bfloat16 5e-5 and, where a token's
    # expert flips on the rounding of the router's input, 0.26
    tol = {"float32": (2e-5, 1e-4), "bfloat16": (2e-3, 0.4)}[request.param]
    return loss, ref_loss, wz.from_program_tree(g), rg, tol


def test_loss_matches_the_reference(both):
    loss, ref_loss, _, _, tol = both
    assert float(loss) == pytest.approx(float(ref_loss), rel=tol[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both, leaf):
    _, _, g, rg, tol = both
    want = np.asarray(rg[leaf])
    scale = float(np.abs(want).max())
    assert scale > 0, leaf
    assert np.allclose(np.asarray(g[leaf]), want, atol=tol[1] * scale), leaf


@pytest.mark.parametrize("dtype, rel", [("float32", 2e-3), ("bfloat16", 0.1)])
def test_two_adamw_steps_match_the_reference(dtype, rel):
    model, batches = _model(jnp.dtype(dtype)), _tokens(1, 2)
    state = train.create_train_state(
        model, optax.adamw(LR), jnp.zeros((B, S), jnp.int32),
        jax.random.PRNGKey(0))
    state = state.replace(params=wz.to_program_tree(wz.make_weights(CFG, 9)))
    step = train.make_train_step(
        loss_of=lambda loss, b: loss,
        apply_kwargs_of=lambda b: {"targets": b["x"]})
    got = []
    for x in batches:
        state, metrics = step(state, {"x": x})
        got.append(float(metrics["loss"]))
    losses, _, w2 = ref.train_steps(wz.make_weights(CFG, 9), batches, CFG, LR)
    for mine, theirs in zip(got, losses):
        assert mine == pytest.approx(float(theirs), rel=rel / 10)
    seed = wz.make_weights(CFG, 9)
    moved = reference.change_norms(wz.from_program_tree(state.params), seed)
    want = reference.change_norms(w2, seed)
    for leaf in LEAVES:
        assert float(moved[leaf]) == pytest.approx(float(want[leaf]),
                                                   rel=rel), leaf
    # every held expert's rows of step 2, sown for the step's metrics
    stats = jax.tree.leaves(metrics["stats"])
    assert len(stats) == 4 and all(s.shape == (CFG["layers"],) for s in stats)
    assert float(metrics["aux_loss"]) == 0.0      # L = L_LM


def test_scan_and_unrolled_carry_the_router_state_alike():
    """The ``(x, router_state)`` carry through ``nn.scan`` and through the
    Python loop over layers: one loss, one gradient."""
    w0, (x,) = wz.make_weights(CFG, 3), _tokens(2)
    stacked = wz.to_program_tree(w0)
    block = stacked["layers"]["block"]
    unrolled = {k: v for k, v in stacked.items() if k != "layers"}
    for i in range(CFG["layers"]):
        unrolled[f"layer_{i}"] = {"block": jax.tree.map(lambda a: a[i],
                                                        block)}
    run = lambda m, p: jax.value_and_grad(lambda p: m.apply(
        {"params": p}, x, targets=x))(p)
    loss_s, g_s = run(_model(scan_layers=True), stacked)
    loss_u, g_u = run(_model(scan_layers=False), unrolled)
    assert float(loss_s) == pytest.approx(float(loss_u), rel=1e-6)
    for i in range(CFG["layers"]):
        for a, b in zip(jax.tree.leaves(jax.tree.map(
                lambda a: a[i], g_s["layers"]["block"])),
                jax.tree.leaves(g_u[f"layer_{i}"]["block"])):
            assert np.allclose(a, b, atol=1e-6 + 1e-4 * float(
                jnp.abs(b).max()))
    # layer 1 hears layer 0's state: its decay gets a gradient, layer 0's
    # (which multiplies zeros) none
    gamma = g_s["layers"]["block"]["moe_mlp"]["router"]["gamma"]
    assert float(jnp.abs(gamma[0]).max()) == 0.0
    assert float(jnp.abs(gamma[1]).max()) > 0.0


def test_tied_table_gradient_is_the_lookups_plus_the_heads():
    w0, (x,) = wz.make_weights(CFG, 4), _tokens(3)
    tied = wz.to_program_tree(w0)
    g_tied = jax.grad(lambda p: _model().apply(
        {"params": p}, x, targets=x))(tied)["embedding"]
    untied = dict(tied, lm_head_kernel=w0["embed"].T)
    g = jax.grad(lambda p: _model(tie_embeddings=False).apply(
        {"params": p}, x, targets=x))(untied)
    assert float(jnp.abs(g["lm_head_kernel"]).max()) > 0
    assert float(jnp.abs(g["embedding"]).max()) > 0
    assert np.allclose(g_tied, g["embedding"] + g["lm_head_kernel"].T,
                       atol=1e-6)
    with pytest.raises(ValueError, match="xent_chunk"):
        _model(xent_chunk=0).init(jax.random.PRNGKey(0), x)


def test_the_top_1_gate_gives_the_router_a_gradient(both):
    """The gate is the probability itself: every router leaf but the first
    layer's decay learns. A renormalised top-1 gate is the constant 1,
    which ``route_top_k`` refuses instead of training nothing."""
    _, _, g, _, _ = both
    for leaf in wz.ROUTER:
        live = g[leaf][1:] if leaf in DEAD else g[leaf]
        assert float(jnp.abs(live).max()) > 0, leaf
    with pytest.raises(ValueError, match="top_k=1"):
        moe.route_top_k(jnp.ones((4, 8)), jnp.ones((8, 4)), 1)


# -- the share test: DroplessMoE with an MLPRouter against the reference --

D, F, E, R, T = 32, 16, 16, 8, 96
LCFG = {"eps": 1e-5, "experts_held": E, "expert_offset": 0}


def _layer_weights(seed):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2] if len(s)
                               > 1 else 1.0), jnp.float32)
    return {"r_down": n(D, R), "r_bdown": 0.1 * n(R), "r_gamma": 1 + 0.1 * n(R),
            "r_norm": 1 + 0.1 * n(R), "r_w1": n(R, R), "r_b1": 0.1 * n(R),
            "r_w2": n(R, R), "r_b2": 0.1 * n(R), "r_w3": 3 * n(R, E),
            "w_gate": n(E, D, F), "w_up": n(E, D, F), "w_down": n(E, F, D)}


def _share(y, r_prev, w, held, offset):
    layer = DroplessMoE(D, F, E, top_k=1, experts_held=held,
                        expert_offset=offset, dtype=jnp.float32,
                        router_hidden=R)
    params = {"router": {p: w[n] for n, p in wz.ROUTER.items()},
              **{n: w[n][offset:offset + (held or E)]
                 for n in wz.EXPERTS}}
    (out, r), sown = layer.apply({"params": params}, y[None], r_prev[None],
                                 mutable="stats")
    return out[0], r[0], sown["stats"]


@pytest.fixture(scope="module")
def layer_inputs():
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    return y, jnp.asarray(rng.normal(size=(T, R)), jnp.float32), \
        _layer_weights(1)


@pytest.mark.parametrize("offset", [0, 8])
def test_a_half_gives_its_own_experts_part(layer_inputs, offset):
    y, r_prev, w = layer_inputs
    p, r = ref.router(y, r_prev, w, LCFG)
    got, got_r, stats = _share(y, r_prev, w, 8, offset)
    lw = {n: w[n][offset:offset + 8] for n in wz.EXPERTS}
    want = ref.experts(y, p, lw, LCFG, 8, offset)
    chosen = np.asarray(jnp.argmax(p, -1))
    mine = (chosen >= offset) & (chosen < offset + 8)
    assert 0 < mine.sum() < T
    assert np.allclose(got, want, atol=1e-5)
    assert np.allclose(got_r, r, atol=1e-5)       # the state: every row's
    assert int(stats["moe_rows_held"][0]) == int(mine.sum())
    assert float(jnp.abs(got[~mine]).max()) == 0.0   # held elsewhere


def test_the_two_halves_add_up_to_the_uncut_layer(layer_inputs):
    y, r_prev, w = layer_inputs
    p, _ = ref.router(y, r_prev, w, LCFG)
    whole = ref.experts(y, p, w, LCFG, E, 0)
    parts = sum(_share(y, r_prev, w, 8, offset)[0] for offset in (0, 8))
    assert float(jnp.abs(whole).min(axis=-1).max()) > 0   # every token
    assert np.allclose(parts, whole, atol=1e-5)
    assert np.allclose(_share(y, r_prev, w, 0, 0)[0], whole, atol=1e-5)


@pytest.mark.parametrize("top_k, n_experts, want", [
    (8, 128, 1024),      # Keye: 64 rows an expert, the routed-rows cap binds
    (1, 16, 4096),       # ZAYA1: 256 rows an expert
    (2, 8, 1024),        # Mixtral-like: amortised at once
    (1, 256, 8192)])     # never past the cap
def test_chunk_rule(top_k, n_experts, want):
    assert CHUNK_RULE(top_k, n_experts) == want
    assert want * top_k <= moe.ROUTED_MAX
    # a layer that holds half of 16 experts at top-1 never needs a second
    # pass: its buffer is the chunk
    if (top_k, n_experts) == (1, 16):
        assert moe.rows_buffer(want, 1, 8, 16) == want


@pytest.mark.parametrize("fraction, untouched", [(0.5, 4), (0.25, 6)])
def test_rope_rotates_the_leading_share_only(fraction, untouched):
    x = jnp.asarray(np.random.default_rng(5).normal(size=(1, 6, 2, 8)),
                    jnp.float32)
    pos = jnp.arange(6)
    got = rope(x, pos, 5e6, seq_axis=1, fraction=fraction)
    rot = 8 - untouched
    assert np.array_equal(got[..., rot:], x[..., rot:])
    assert np.allclose(got[..., :rot], rope(x[..., :rot], pos, 5e6,
                                            seq_axis=1))
    assert not np.allclose(got[:, 1:, :, :rot], x[:, 1:, :, :rot])
    assert np.allclose(rope(x, pos, 5e6, seq_axis=1, fraction=1.0),
                       rope(x, pos, 5e6, seq_axis=1))


@pytest.mark.parametrize("name", ["zaya1-8b", "tiny"])
def test_flops_per_token_are_the_benchmarks(name):
    """``TransformerConfig.flops_per_token``: the multiplied parameters are
    ``roofline_zaya1.matmul_params`` (latent widths, the per-head
    convolution, the router MLP, the held share of one expert, the tied
    head once); its attention term charges the whole square by the
    module's convention, the benchmark the causal half."""
    cfg = mc.load("zaya1-8b")
    cfg = mc.tiny(cfg) if name == "tiny" else cfg
    seq = 32768 if name != "tiny" else S
    model = get_model("zaya1-8b", **mc.program_kwargs(cfg, seq))
    c = model.cfg
    square = 12 * c.n_layers * c.n_heads * c.head_dim * seq
    assert c.flops_per_token() - square == pytest.approx(
        6 * roofline_zaya1.matmul_params(cfg), rel=1e-9)
    # 4 + 10 of the square's 4 + 8... the kernels' own count, causal half
    assert cfg["layers"] * roofline_zaya1.attention_flops_per_token(
        cfg, seq) == pytest.approx(square * 14 / 24, rel=1e-9)
    dense = dataclasses.replace(c, attn_latent=False, router_hidden=0)
    assert dense.flops_per_token() < c.flops_per_token()
