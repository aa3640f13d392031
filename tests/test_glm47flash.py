"""The ``glm-4.7-flash`` path at tiny sizes on the CPU, seeded weights: the
system (``get_model`` -> ``create_train_state`` -> ``make_train_step``)
against the benchmark's plain reference — both logits, ``L_LM`` and
``L_MTP`` apart, every gradient leaf (the table's and the head's are sums
over their two uses), two AdamW steps, in float32 and bfloat16 compute; the
rotated latent attention at the published 192 + 64 / 256 through the
interpret-mode kernels against a per-head softmax written out here; the
**share test** (the eight ranges of eight experts, the shared expert
counted once, give the uncut layer); the fused head under a cotangent off
a power of two; the FLOPs a token is charged and the parameter count."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import modelcfg_glm47flash as mc
from benchmark import reference, reference_glm47flash as ref
from benchmark import roofline_glm47flash, weights_glm47flash as wg
from tony_tpu import profiler, train
from tony_tpu.models import get_model, hybrid, moe
from tony_tpu.models.moe import DroplessMoE

FULL = mc.load("glm-4.7-flash")
CFG = mc.tiny(FULL)
B, S, LR = 2, 64, 3e-4
LEAVES = sorted(wg.leaf_specs(CFG))
WEIGHT = CFG["mtp_weight"]


def _model(dtype=jnp.float32, **kw):
    return get_model(CFG["program"]["model"], dtype=dtype, remat=False,
                     **{**mc.program_kwargs(CFG, S), **kw})


def _tokens(seed, n=1):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, CFG["vocab"], (B, S), dtype=np.int32))
            for _ in range(n)]


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


@pytest.fixture(scope="module")
def ref_grad():
    """The reference's ``(L_LM, L_MTP)`` and the gradient of ``L_LM + 0.3
    L_MTP`` over the weights of seed 7 and one batch."""
    w0, (x,) = wg.make_weights(CFG, 7), _tokens(0)
    (_, pair), rg = ref.value_and_grad(w0, x, CFG)
    return pair, rg


def test_the_reference_chain_rule_by_hand_is_autodiffs(ref_grad):
    """``reference_glm47flash.value_and_grad`` (a program a block, for the
    chip's memory; what ``train_steps`` and the limits rest on) against
    ``jax.value_and_grad`` of the plain ``loss``: the same float32 sums in
    another order."""
    w0, (x,) = wg.make_weights(CFG, 7), _tokens(0)
    (total, pair), g = jax.value_and_grad(
        lambda w: ref.loss(w, x, CFG), has_aux=True)(w0)
    assert tuple(map(float, pair)) == pytest.approx(
        tuple(map(float, ref_grad[0])), rel=1e-6)
    assert float(total) == pytest.approx(
        float(pair[0]) + WEIGHT * float(pair[1]), rel=1e-6)
    assert set(g) == set(ref_grad[1])
    for leaf in g:          # reads 5e-8 of a leaf's largest element
        assert float(jnp.abs(g[leaf] - ref_grad[1][leaf]).max()) <= 1e-5 * (
            float(jnp.abs(g[leaf]).max()) + 1e-30), leaf


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both(request, ref_grad):
    """Program (in the parametrised compute dtype) and reference over the
    same seeded weights and batch: both losses, the gradient of ``L_LM +
    0.3 L_MTP`` leaf by leaf, and the tolerance the dtype allows."""
    model = _model(jnp.dtype(request.param))
    w0, (x,) = wg.make_weights(CFG, 7), _tokens(0)

    def total(p):
        lm, sown = model.apply({"params": p}, x, targets=x,
                               mutable=["losses", "stats"])
        aux, = sown["losses"]["mtp_loss"]
        return lm + aux, (lm, aux / WEIGHT)

    (_, pair), g = jax.value_and_grad(total, has_aux=True)(
        wg.to_program_tree(w0, CFG))
    # (a loss, a gradient leaf's distance over its length): float32 reads
    # 3e-7 and 2e-5; bfloat16 3e-3 and, where tokens' experts flip on the
    # rounding of the router's input at these widths, up to 0.5
    tol = {"float32": (2e-5, 2e-4), "bfloat16": (5e-3, 0.9)}[request.param]
    return pair, ref_grad[0], wg.from_program_tree(g, CFG), ref_grad[1], tol


@pytest.mark.parametrize("which", [0, 1], ids=["L_LM", "L_MTP"])
def test_each_loss_matches_the_reference(both, which):
    pair, ref_pair, _, _, tol = both
    assert float(pair[which]) == pytest.approx(float(ref_pair[which]),
                                               rel=tol[0])
    assert float(ref_pair[which]) > 1.0


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both, leaf):
    _, _, g, rg, tol = both
    if leaf.endswith("router_bias"):        # selection only: no gradient
        assert float(jnp.abs(g[leaf]).max()) == 0.0 \
            == float(jnp.abs(rg[leaf]).max())
        return
    assert float(jnp.abs(rg[leaf]).max()) > 0, leaf
    assert _rel(g[leaf], rg[leaf]) < tol[1], leaf


def test_the_table_and_the_head_take_two_gradients(ref_grad):
    """``embed`` and ``lm_head`` are used twice a step: the gradient the
    program is held to above is the sum of each loss's, and neither part
    is nothing (the reference alone, one pull-back a loss)."""
    w0, (x,) = wg.make_weights(CFG, 7), _tokens(0)
    _, pull = jax.vjp(lambda w: ref.losses(w, x, CFG), w0)
    parts = [pull((jnp.float32(a), jnp.float32(b)))[0]
             for a, b in ((1, 0), (0, 1))]
    whole = ref_grad[1]
    for leaf in ("embed", "lm_head"):
        assert min(float(jnp.abs(p[leaf]).max()) for p in parts) > 0
        np.testing.assert_allclose(
            whole[leaf], parts[0][leaf] + WEIGHT * parts[1][leaf],
            atol=1e-6)
    # the stack's weights hear the second loss too (through h before the
    # final norm); the main final norm hears only the first, the module
    # only the second
    assert float(jnp.abs(parts[1]["L0.wq_a"]).max()) > 0
    assert float(jnp.abs(parts[1]["final_norm"]).max()) == 0.0
    assert float(jnp.abs(parts[0]["M.w_eh"]).max()) == 0.0


def test_both_logits_match_the_reference():
    w0, (x,) = wg.make_weights(CFG, 3), _tokens(2)
    first, sown = _model(xent_chunk=0).apply(
        {"params": wg.to_program_tree(w0, CFG)}, x,
        mutable=["intermediates"])
    second, = sown["intermediates"]["mtp_logits"]
    for b in range(B):
        want = ref.logits(w0, x[b], CFG)
        # float32 against float32 at highest: reads 2e-6 on logits ~1
        np.testing.assert_allclose(first[b], want[0], atol=2e-5)
        np.testing.assert_allclose(second[b], want[1], atol=2e-5)
    # without the collection the module's logits are not kept: same first
    np.testing.assert_array_equal(first, _model(xent_chunk=0).apply(
        {"params": wg.to_program_tree(w0, CFG)}, x))


@pytest.mark.parametrize("dtype, rel", [("bfloat16", 0.1)])
def test_two_adamw_steps_match_the_reference(dtype, rel):
    model, batches = _model(jnp.dtype(dtype)), _tokens(1, 2)
    step = train.make_train_step(
        loss_of=lambda loss, b: loss,
        apply_kwargs_of=lambda b: {"targets": b["x"]})
    state = train.create_train_state(
        model, optax.adamw(LR), jnp.zeros((B, S), jnp.int32),
        jax.random.PRNGKey(0))
    state = state.replace(params=wg.to_program_tree(
        wg.make_weights(CFG, 9), CFG))
    got = []
    for x in batches:
        state, metrics = step(state, {"x": x})
        got.append((float(metrics["loss"] - metrics["aux_loss"]),
                    float(metrics["aux_loss"]) / WEIGHT))
    pairs, _, w2 = ref.train_steps(wg.make_weights(CFG, 9), batches, CFG, LR)
    for mine, theirs in zip(got, pairs):
        assert mine == pytest.approx(theirs, rel=rel / 10)
    w0 = wg.make_weights(CFG, 9)
    moved = reference.change_norms(wg.from_program_tree(state.params, CFG),
                                   w0)
    want = reference.change_norms(w2, w0)
    for leaf in LEAVES:
        assert float(moved[leaf]) == pytest.approx(float(want[leaf]),
                                                   rel=rel), leaf
    # every expert layer's held rows of step 2, the module's among them
    rows = [int(v) for path, v in jax.tree_util.tree_leaves_with_path(
        metrics["stats"]) if "moe_rows_held" in jax.tree_util.keystr(path)]
    assert len(rows) == CFG["ffns"].count("experts") + 1 and min(rows) > 0


def test_rows_held_are_the_references():
    w0, (x,) = wg.make_weights(CFG, 5), _tokens(4)
    _, sown = _model().apply({"params": wg.to_program_tree(w0, CFG)}, x,
                             targets=x, mutable=["stats", "losses"])
    mine = [int(v) for path, v in jax.tree_util.tree_leaves_with_path(
        sown["stats"]) if "moe_rows_held" in jax.tree_util.keystr(path)]
    assert mine == [int(n) for n in ref.rows_held(w0, x, CFG)]


def test_the_model_counts_what_it_runs_and_refuses_what_it_cannot():
    profiler.reset_timeline()
    x, = _tokens(0)
    model = _model()
    model.apply({"params": wg.to_program_tree(wg.make_weights(CFG, 1), CFG)},
                x, targets=x, mutable=["losses", "stats"])
    facts = profiler.counters()
    for name, want in (("model:layers.mla", 2), ("model:layers.mtp", 1),
                       ("model:layers.experts", 2), ("mtp:weight", WEIGHT),
                       ("head:calls", 2), ("mla:q_rank", CFG["q_rank"]),
                       ("mla:rope_dim", CFG["rope"]),
                       ("mla:qk_dim", CFG["nope"] + CFG["rope"]),
                       ("mla:v_dim", CFG["v_dim"]),
                       ("mla:kv_rank", CFG["kv_rank"]),
                       ("moe:experts_held", CFG["experts_held"])):
        assert facts[name] == want, name
    assert "attn:block_q.fwd.mla" in facts
    with pytest.raises(ValueError, match="whole key"):
        _model(mla_v_dim=48)
    with pytest.raises(ValueError, match="joined keys"):
        _model(mla_nope_dim=32, mla_v_dim=32)
    with pytest.raises(ValueError, match="one more token"):
        _model(mtp_layers=2)
    assert get_model("glm-4.7-flash-tiny").cfg.mtp_layers == 1
    assert get_model("glm-4.7-flash").cfg.layers == hybrid.GLM_FLASH_CUT


# -- the rotated latent attention at the published head ----------------------

H, DN, DS, DV, T, DIM = 2, 192, 64, 256, 128, 128


def _naive_mla(p, u, theta):
    """Head by head, in numpy float64: the low-rank query, the rotation of
    neighbouring pairs, one shared rotated key part, a masked softmax."""
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), p)
    u = np.asarray(u, np.float64)
    norm = lambda x, s: x / np.sqrt((x * x).mean(-1, keepdims=True)
                                    + 1e-5) * s

    def turn(x):                                    # [T, DS]
        ang = np.arange(T)[:, None] * theta ** (
            -np.arange(0, DS, 2) / DS)[None]
        out = np.empty_like(x)
        out[:, 0::2] = x[:, 0::2] * np.cos(ang) - x[:, 1::2] * np.sin(ang)
        out[:, 1::2] = x[:, 0::2] * np.sin(ang) + x[:, 1::2] * np.cos(ang)
        return out

    q = norm(u @ p["wq_a"]["kernel"], p["q_norm"]["scale"]) \
        @ p["wq_b"]["kernel"]
    kva = u @ p["wkv_a"]["kernel"]
    kv = norm(kva[:, :32], p["kv_norm"]["scale"]) @ p["wkv_b"]["kernel"]
    shared = turn(kva[:, 32:])
    out = []
    for h in range(H):
        qh = q[:, h * (DN + DS):(h + 1) * (DN + DS)]
        qh = np.concatenate([qh[:, :DN], turn(qh[:, DN:])], -1)
        kvh = kv[:, h * (DN + DV):(h + 1) * (DN + DV)]
        kh = np.concatenate([kvh[:, :DN], shared], -1)
        s = qh @ kh.T / np.sqrt(DN + DS)
        s = np.where(np.tril(np.ones((T, T), bool)), s, -np.inf)
        e = np.exp(s - s.max(-1, keepdims=True))
        out.append(e / e.sum(-1, keepdims=True) @ kvh[:, DN:])
    return np.concatenate(out, -1) @ p["wo"]["kernel"]


@pytest.fixture(scope="module")
def mla_case():
    cfg = hybrid.HybridConfig(
        dim=DIM, layers=("mla",), norm="rmsnorm", mla_heads=H,
        mla_kv_rank=32, mla_q_rank=48, mla_nope_dim=DN, mla_rope_dim=DS,
        mla_v_dim=DV, mla_rope_theta=1e6, dtype=jnp.float32, interpret=True)
    layer = hybrid.MLA(cfg)
    u = jax.random.normal(jax.random.PRNGKey(1), (1, T, DIM), jnp.float32)
    p = nn.unbox(layer.init(jax.random.PRNGKey(2), u)["params"])
    p["q_norm"]["scale"] = 1 + 0.1 * jax.random.normal(
        jax.random.PRNGKey(3), (48,))
    return layer, p, u


def test_rotated_mla_at_192_64_256_through_the_kernels(mla_case):
    """One small shape, the packed flash kernels in the Pallas interpreter
    at head size 256 (two lane blocks a head: each head's joined key)."""
    layer, p, u = mla_case
    out, _ = layer.apply({"params": p}, u)
    # float32 kernels against float64: reads 3e-6 of outputs ~1
    np.testing.assert_allclose(out[0], _naive_mla(p, u[0], 1e6), atol=5e-5)
    # the rotation is in it: another theta is another output
    assert np.abs(np.asarray(out[0]) - _naive_mla(p, u[0], 1e4)).max() > 1e-3


def test_rotated_mla_gradients_through_the_kernels(mla_case):
    """The kernels' backward against autodiff of the XLA twin (the same
    module off the interpreter): the gradient of every leaf and of u."""
    layer, p, u = mla_case
    twin = hybrid.MLA(layer.cfg.__class__(**{
        **{f.name: getattr(layer.cfg, f.name) for f in
           layer.cfg.__dataclass_fields__.values()}, "interpret": None}))
    w = jax.random.normal(jax.random.PRNGKey(4), (1, T, DIM))
    loss = lambda m: lambda p, u: (m.apply({"params": p}, u)[0] * w).sum()
    got = jax.grad(loss(layer), (0, 1))(p, u)
    want = jax.grad(loss(twin), (0, 1))(p, u)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _rel(a, b) < 1e-4


# -- the share test ------------------------------------------------------------

D, F, E, N, TOPK, SCALE = 32, 16, 64, 128, 4, 1.8
LCFG = {"eps": 1e-5, "top_k": TOPK, "route_scale": SCALE, "shared": 1,
        "experts_held": E, "expert_offset": 0}


def _share(y, w, held, offset, shared=1):
    layer = DroplessMoE(D, F, E, top_k=TOPK, experts_held=held,
                        expert_offset=offset, dtype=jnp.float32,
                        router="sigmoid", route_scale=SCALE, shared=shared)
    params = {n: (a[offset:offset + (held or E)]
                  if n in ("w_gate", "w_up", "w_down") else a)
              for n, a in w.items() if shared or not n.startswith("shared")}
    out, sown = layer.apply({"params": params}, y[None], mutable="stats")
    return out[0], int(sown["stats"]["moe_rows_held"][0])


def test_the_eight_shares_and_the_shared_expert_give_the_uncut_layer():
    """Eight chips holding eight of the 64 experts each, 4 a token at a
    scale of 1.8: their routed parts, plus the shared expert counted ONCE
    (every chip computes it whole; the deployment adds it on one), are the
    layer that holds all 64, and their rows are all the routed rows."""
    rng = np.random.default_rng(12)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(
        s[-2] if len(s) > 1 else 1.0), jnp.float32)
    w = {"w_router": 2 * n(D, E), "router_bias": 0.1 * n(E),
         "w_gate": n(E, D, F), "w_up": n(E, D, F), "w_down": n(E, F, D),
         "shared_gate": n(D, F), "shared_up": n(D, F), "shared_down": n(F, D)}
    y = jnp.asarray(rng.normal(size=(N, D)), jnp.float32)
    whole, _ = _share(y, w, 0, 0, shared=1)
    shares = [_share(y, w, 8, off, shared=0) for off in range(0, E, 8)]
    shared_only = reference.swiglu(y, w["shared_gate"], w["shared_up"],
                                   w["shared_down"])
    # float32 sums in another order: reads 4e-6 on outputs ~1
    np.testing.assert_allclose(sum(out for out, _ in shares) + shared_only,
                               whole, atol=5e-5)
    np.testing.assert_allclose(whole, ref.experts(y, w, LCFG), atol=5e-5)
    assert sum(rows for _, rows in shares) == N * TOPK
    for off, (out, rows) in zip(range(0, E, 8), shares):
        mine = {k: a[off:off + 8] if k in ("w_gate", "w_up", "w_down") else a
                for k, a in w.items()}
        np.testing.assert_allclose(out, ref.experts(
            y, mine, LCFG, held=8, offset=off, shared=False), atol=2e-5)
    _, gates = ref.route(y, w, LCFG)
    np.testing.assert_allclose(gates.sum(-1), SCALE, rtol=1e-5)


def test_chunk_rule_at_4_of_64():
    """The fourth routing shape: 2048 tokens a chunk (a held expert
    expects 128 rows of one), buffers of 2048 rows, at most 4 passes."""
    assert moe.chunk_tokens(4, 64) == 2048
    assert moe.rows_buffer(2048, 4, 8, 64) == 2048
    assert -(-2048 * 4 // moe.rows_buffer(2048, 4, 8, 64)) == 4


# -- the head, twice -----------------------------------------------------------

def _ulp(x, dtype=jnp.bfloat16):
    """One unit in the last place of ``dtype`` at |x| (float32 in)."""
    x = np.abs(np.asarray(x, np.float32))
    bits = jnp.finfo(dtype).nmant
    return np.where(x > 0, 2.0 ** (np.floor(np.log2(np.maximum(
        x, np.finfo(np.float32).tiny))) - bits), 0.0)


@pytest.mark.parametrize("shift", [1, 2])
def test_the_fused_head_under_a_cotangent_of_0_3(shift):
    """``chunked_next_token_xent``'s docstring: at a cotangent off a power
    of two the fused pass scales gradients already rounded to bfloat16 and
    rounds again where autodiff scales before its one rounding — roundings
    of bfloat16 and no more: within 2 ulp at the scale of a row of the
    rows' gradient, and of a column of the head's within one ulp a chunk
    (its sum over the chunks is rounded once a chunk on either path). An
    element that is a small difference of large terms is held to the
    terms' ulp, as any bfloat16 sum is."""
    from tony_tpu.train import _recomputed_xent

    b, t, d, v, chunk = 2, 40, 32, 96, 16
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    hidden = jax.random.normal(k1, (b, t, d), jnp.bfloat16)
    head = jax.random.normal(k2, (d, v), jnp.float32) / np.sqrt(d)
    tokens = jax.random.randint(k3, (b, t), 0, v)
    loss = lambda h, w: WEIGHT * train.chunked_next_token_xent(
        h, w, tokens, chunk, jnp.bfloat16, shift=shift)
    value, (dh, dw) = jax.value_and_grad(loss, (0, 1))(hidden, head)
    # autodiff of the checkpointed scan over the same rows, labels, weights
    rows = hidden[:, :-shift].reshape(-1, d)
    r, n = rows.shape[0], -(-rows.shape[0] // chunk)
    pad = n * chunk - r
    labels = jnp.pad(tokens[:, shift:].reshape(-1), (0, pad))
    weights = jnp.pad(jnp.ones((r,), jnp.float32), (0, pad))

    def plain(h, w):
        rows = jnp.pad(h[:, :-shift].reshape(-1, d), ((0, pad), (0, 0)))
        return WEIGHT * _recomputed_xent(
            rows.reshape(n, chunk, d), w.astype(jnp.bfloat16),
            labels.reshape(n, chunk), weights.reshape(n, chunk), r)
    want, (rh, rw) = jax.value_and_grad(plain, (0, 1))(hidden, head)
    # the scan's float32 sum, last chunk first against first chunk first
    assert float(value) == pytest.approx(float(want), rel=1e-4)
    # the labels are tokens shifted by ``shift``: a plain float32 form
    logits = rows.astype(jnp.float32) @ head.astype(jnp.bfloat16).astype(
        jnp.float32)
    lp = jax.nn.log_softmax(logits, -1)
    plain_loss = -jnp.take_along_axis(
        lp, tokens[:, shift:].reshape(-1)[:, None], -1).mean()
    assert float(value) == pytest.approx(WEIGHT * float(plain_loss),
                                         rel=2e-2)
    assert n == 5
    for got, ref_g, axis, ulps in ((dh, rh, -1, 2), (dw, rw, 0, n)):
        got, ref_g = (np.asarray(a, np.float32) for a in (got, ref_g))
        scale = np.abs(ref_g).max(axis=axis, keepdims=True)
        assert (np.abs(got - ref_g) <= ulps * _ulp(scale)).all()
        assert np.abs(ref_g).max() > 0 and (got != ref_g).any()
    # the rows past the last label take no gradient
    assert float(jnp.abs(dh[:, -shift:].astype(jnp.float32)).max()) == 0.0
    # at a cotangent of 1 the two are the same bits (the docstring's claim)
    one = lambda f: jax.grad(lambda h, w: f(h, w) / WEIGHT, (0, 1))(
        hidden, head)
    for a, b in zip(one(loss), one(plain)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


# -- FLOPs, parameters ---------------------------------------------------------

@pytest.mark.parametrize("name", ["glm-4.7-flash", "tiny"])
def test_flops_per_token_are_the_benchmarks(name):
    cfg = CFG if name == "tiny" else FULL
    seq = S if name == "tiny" else 16384
    model = get_model(cfg["program"]["model"], **mc.program_kwargs(cfg, seq))
    assert model.cfg.flops_per_token(seq) == pytest.approx(
        roofline_glm47flash.train_flops_per_token(cfg, seq), rel=1e-9)


def test_param_count_is_issue_45s_lines():
    count = mc.param_count(FULL)
    assert (count["mla_mixer"], count["one_expert"],
            count["expert_ffn_held"], count["expert_block"],
            count["dense_block"], count["embedding_head_final_norm"],
            count["mtp_module"], count["total"]) == (
        21759232, 9437184, 85065792, 106829120, 84677888, 79300608,
        115223872, 706518848)
    model = get_model(FULL["program"]["model"],
                      **mc.program_kwargs(FULL, 128))
    shapes = nn.unbox(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"])
    assert sum(x.size for x in jax.tree.leaves(shapes)) == count["total"]
    specs = wg.leaf_specs(FULL)
    assert sum(int(np.prod(s)) for s, _ in specs.values()) == count["total"]
    assert jax.tree.structure(jax.tree.map(lambda a: 0, shapes)) == \
        jax.tree.structure(wg.to_program_tree({n: 0 for n in specs}, FULL))
