"""The ``olmo-hybrid-7b`` path at tiny sizes on the CPU, seeded weights: the
system (``get_model`` -> ``create_train_state`` -> ``make_train_step``)
against the benchmark's plain reference — the loss, every gradient leaf, two
AdamW steps, in float32 and bfloat16 compute, through the XLA twin and
through the kernel bodies behind their zero lanes; the **share test** (the
two halves of a mixer's heads add up to the uncut mixer: exactly for the
delta rule, and for attention with the q/k-norm's mean square taken a half
at a time — the one scalar a token two chips would exchange); the norm's
placement; the facts the model counts; the FLOPs and parameters the
benchmark charges."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import modelcfg_olmohybrid as mc
from benchmark import reference, reference_olmohybrid as ref
from benchmark import roofline_olmohybrid, weights_olmohybrid as wo
from tony_tpu import profiler, train
from tony_tpu.models import get_model
from tony_tpu.models import hybrid

CFG = mc.tiny(mc.load("olmo-hybrid-7b"))
B, S, LR = 2, 64, 3e-4
LEAVES = sorted(wo.leaf_specs(CFG))


def _model(dtype=jnp.float32, **kw):
    return get_model(CFG["program"]["model"], dtype=dtype, remat=False,
                     **{**mc.program_kwargs(CFG), **kw})


def _tokens(seed, n=1):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, CFG["vocab"], (B, S), dtype=np.int32))
            for _ in range(n)]


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / (np.linalg.norm(b) + 1e-30))


@pytest.fixture(scope="module", params=[
    ("float32", None), ("bfloat16", None), ("float32", True)],
    ids=["float32", "bfloat16", "float32_kernel_bodies"])
def both(request):
    """Program (in the parametrised compute dtype; the XLA twin, or the
    delta rule's kernel bodies under the Pallas interpreter behind their
    zero lanes) and reference over the same seeded weights and batch: the
    loss and its gradient, leaf by leaf, and the tolerance the dtype
    allows."""
    dtype, interpret = request.param
    model = _model(jnp.dtype(dtype), interpret=interpret)
    w0, (x,) = wo.make_weights(CFG, 7), _tokens(0)
    loss, g = jax.value_and_grad(lambda p: model.apply(
        {"params": p}, x, targets=x))(wo.to_program_tree(w0, CFG))
    ref_loss, rg = jax.value_and_grad(lambda w: ref.loss(w, x, CFG))(w0)
    # (loss, a gradient leaf's distance over its length): float32 reads
    # 1e-7 and 3e-5; bfloat16 2e-3 and, for a decay rate a head (two
    # numbers a layer at these widths), up to 0.45
    tol = {"float32": (2e-5, 1e-4), "bfloat16": (5e-3, 0.9)}[dtype]
    return loss, ref_loss, wo.from_program_tree(g, CFG), rg, tol


def test_loss_matches_the_reference(both):
    loss, ref_loss, _, _, tol = both
    assert float(loss) == pytest.approx(float(ref_loss), rel=tol[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both, leaf):
    _, _, g, rg, tol = both
    assert g[leaf].shape == rg[leaf].shape
    assert float(jnp.abs(rg[leaf]).max()) > 0, leaf
    assert _rel(g[leaf], rg[leaf]) < tol[1], leaf


@pytest.mark.parametrize("dtype, rel", [("float32", 2e-3), ("bfloat16", 0.1)])
def test_two_adamw_steps_match_the_reference(dtype, rel):
    """``tony submit``'s path: two steps of ``make_train_step`` from the
    seeded weights, each leaf's move beside the reference's."""
    model, batches = _model(jnp.dtype(dtype)), _tokens(1, 2)
    step = train.make_train_step(
        loss_of=lambda loss, b: loss,
        apply_kwargs_of=lambda b: {"targets": b["x"]})
    state = train.create_train_state(
        model, optax.adamw(LR), jnp.zeros((B, S), jnp.int32),
        jax.random.PRNGKey(0))
    state = state.replace(params=wo.to_program_tree(
        wo.make_weights(CFG, 9), CFG))
    got = []
    for x in batches:
        state, metrics = step(state, {"x": x})
        got.append(float(metrics["loss"]))
    losses, _, w2 = ref.train_steps(wo.make_weights(CFG, 9), batches, CFG, LR)
    w0 = wo.make_weights(CFG, 9)
    moved = reference.change_norms(wo.from_program_tree(state.params, CFG),
                                   w0)
    want = reference.change_norms(w2, w0)
    for mine, theirs in zip(got, losses):
        assert mine == pytest.approx(float(theirs), rel=rel / 10)
    # a decay rate a head (two numbers a layer here): in bfloat16 its
    # second AdamW step is the ratio of two noisy gradients, so there the
    # three layers' are held as one
    pooled = [leaf for leaf in LEAVES if leaf.endswith(("a_log", "dt_bias"))] \
        if dtype == "bfloat16" else []
    for leaf in LEAVES:
        if leaf not in pooled:
            assert float(moved[leaf]) == pytest.approx(float(want[leaf]),
                                                       rel=rel), leaf
    if pooled:
        norm = lambda d: float(np.linalg.norm([d[leaf] for leaf in pooled]))
        assert norm(moved) == pytest.approx(norm(want), rel=rel), pooled


# -- a share of the heads ----------------------------------------------------

FULL = dict(CFG, heads=4, kv_heads=4, gdn_heads=4)      # nothing cut


def _columns(a, half, heads=4):
    """The columns of ``a [..., heads x d]`` that belong to the heads of
    ``half`` (0: the first two, 1: the last two)."""
    d = a.shape[-1] // heads
    return a[..., half * 2 * d:(half + 1) * 2 * d]


def _half_of(kind, lw, half):
    """What one of two chips holds of the uncut mixer's leaves ``lw``: its
    heads' columns of the in-projections, taps, per-head rates and q/k-norm
    scales, its heads' rows of ``W_o``; the head norm's scale whole."""
    out = {}
    for n, a in lw.items():
        if n == "wo":
            out[n] = _columns(a.T, half).T
        elif n == "o_norm" or n.startswith(("norm", "w_")):
            out[n] = a
        else:
            out[n] = _columns(a, half)
    return out


@pytest.fixture(scope="module", params=["gdn", "attn"])
def shares(request):
    """One mixer, uncut (4 heads) and as the two halves a two-chip split
    holds: the program's module on each, and the reference's."""
    kind = request.param
    w = wo.make_weights(dict(FULL, kinds=[kind]), 3)
    lw = {n: a for n, a in wo.layer_leaves(w, 0).items()
          if n in wo.KERNELS[kind] + wo.SCALES[kind] + wo.BARE[kind]}
    x = jax.random.normal(jax.random.PRNGKey(1), (S, CFG["hidden"]))
    cls = hybrid.GDN if kind == "gdn" else hybrid.Attn

    def program(leaves, held):
        cfg = _model(heads_held=held).cfg
        y, _ = cls(cfg).apply({"params": wo.mixer_tree(kind, leaves)},
                              x[None])
        return y[0]
    halves = [_half_of(kind, lw, h) for h in (0, 1)]
    return kind, x, lw, halves, program


def test_the_halves_add_up_to_the_uncut_mixer(shares):
    """What each of two chips computes from its 2 of 4 heads adds up to the
    layer's output: for the delta rule to the uncut mixer itself (a head
    reads no other head); for attention to the uncut mixer whose q/k-norm
    takes its mean square a half at a time — two chips that wanted the
    published norm over all 30 heads' columns would exchange that one
    scalar a token, and nothing else of the mixer crosses."""
    kind, x, lw, halves, program = shares
    total = sum(program(half, 2) for half in halves)
    if kind == "gdn":
        uncut = ref.gdn(x, lw, FULL)
        assert _rel(program(lw, 0), uncut) < 2e-5     # the program, uncut
    else:
        uncut = ref.attn(x, lw, FULL, groups=2)
        # over the whole width the statistic differs: the halves do not
        # add up to THAT mixer, which is why the configuration says which
        assert _rel(total, ref.attn(x, lw, FULL)) > 1e-3
    assert _rel(total, uncut) < 2e-5
    # and each half is the reference's mixer over that half's leaves
    for half in halves:
        mixer = ref.gdn if kind == "gdn" else ref.attn
        assert _rel(program(half, 2), mixer(x, half, CFG)) < 2e-5


def test_a_half_builds_half_the_columns():
    x = _tokens(2)[0]
    import flax.linen as nn
    shapes = lambda held: jax.tree.map(lambda a: a.shape, nn.meta.unbox(
        jax.eval_shape(lambda: _model(heads_held=held).init(
            jax.random.PRNGKey(0), x))["params"]))
    full, half = shapes(0), shapes(2)
    gdn, attn = full["layer_0"]["gdn"], full["layer_3"]["attn"]
    assert gdn["wq"]["kernel"] == (64, 4 * 12)
    assert half["layer_0"]["gdn"]["wq"]["kernel"] == (64, 2 * 12)
    assert half["layer_0"]["gdn"]["wz"]["kernel"] == (64, 2 * 24)
    assert half["layer_0"]["gdn"]["wo"]["kernel"] == (2 * 24, 64)
    assert half["layer_0"]["gdn"]["a_log"] == (2,)
    assert half["layer_0"]["gdn"]["conv_v"] == (4, 2 * 24)
    assert attn["wq"]["kernel"] == (64, 64)
    assert half["layer_3"]["attn"]["wk"]["kernel"] == (64, 32)
    assert half["layer_3"]["attn"]["wo"]["kernel"] == (32, 64)
    assert half["layer_3"]["attn"]["q_norm"]["scale"] == (32,)
    # the feed-forward and the norms are whole on every chip
    assert half["layer_0"]["mlp"] == full["layer_0"]["mlp"]
    assert half["layer_0"]["norm1"] == full["layer_0"]["norm1"]


@pytest.mark.parametrize("backend, fused", [("tpu", 3), ("cpu", 0)])
def test_the_mixers_count_their_fused_conv_chains(backend, fused,
                                                  monkeypatch):
    """``gdn:conv_fused`` is how many of a mixer's q, k and v chains run
    ``ops.ssm``'s fused kernels — all three on a TPU, none on the CPU
    (``ops.ssm.conv_plan``) — and ``gdn:conv_block`` the steps a block
    of q's holds; published once, however many layers are traced."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    profiler.reset_timeline()
    jax.eval_shape(_model().init, jax.random.PRNGKey(0), _tokens(6)[0])
    c = profiler.timeline()["counters"]
    profiler.reset_timeline()
    assert c["gdn:conv_fused"] == fused
    assert c.get("gdn:conv_block") == (128 if fused else None)


# -- the configuration's switches and the facts counted ----------------------

def test_the_model_counts_its_layers_and_refuses_what_it_cannot_share():
    profiler.reset_timeline()
    x = _tokens(6)[0]
    model = _model()
    model.init(jax.random.PRNGKey(0), x)
    c = profiler.counters()
    profiler.reset_timeline()
    assert (c["model:layers.gdn"], c["model:layers.attn"],
            c["model:layers.kda"]) == (3, 1, 0)
    assert (c["model:heads_held"], c["model:heads_total"]) == (2, 4)
    assert (c["gdn:heads"], c["gdn:key_dim"], c["gdn:value_dim"],
            c["gdn:chunk"], c["gdn:chunks"], c["gdn:states_kept"],
            c["gdn:decay"]) == (2, 12, 24, 8, 8, 4, 1)
    assert "kda:table_rows" not in c        # a scalar decay takes no table
    assert c["attn:kv_blocks_visited.attn"] >= \
        c["attn:kv_blocks_fetched.attn"] >= 1
    assert c["attn:block_q.fwd.attn"] == c["attn:block_k.fwd.attn"]
    with pytest.raises(ValueError, match="one chip"):
        _model(mesh=object())
    with pytest.raises(ValueError, match="share of their"):
        get_model("kimi-linear-tiny", heads_held=1)
    with pytest.raises(ValueError, match="share of their"):
        _model(heads_held=5)
    with pytest.raises(ValueError, match="norm_placement"):
        _model(norm_placement="sandwich")


def test_the_norm_comes_after_the_sublayer():
    """``norm_placement``: the Olmo family's ``x += LN(f(x))`` against the
    pre-norm ``x += f(LN(x))`` over the same leaves."""
    w0, (x,) = wo.make_weights(CFG, 5), _tokens(3)
    params = {"params": wo.to_program_tree(w0, CFG)}
    post = _model().apply(params, x, targets=x)
    pre = _model(norm_placement="pre").apply(params, x, targets=x)
    assert _model().cfg.norm_placement == "post"
    assert float(post) == pytest.approx(float(ref.loss(w0, x, CFG)), rel=2e-5)
    assert abs(float(pre) - float(post)) > 1e-3


def test_beta_spans_zero_to_two_and_the_gate_is_silu():
    """``gdn_neg_eigval`` doubles beta's range (the configuration's
    ``linear_allow_neg_eigval``); without it the same leaves give the
    (0, 1) rule, which the reference takes from its configuration too."""
    w0, (x,) = wo.make_weights(CFG, 5), _tokens(3)
    params = {"params": wo.to_program_tree(w0, CFG)}
    one = _model(gdn_neg_eigval=False).apply(params, x, targets=x)
    assert float(one) == pytest.approx(
        float(ref.loss(w0, x, dict(CFG, neg_eigval=False))), rel=2e-5)
    assert abs(float(one) - float(ref.loss(w0, x, CFG))) > 1e-4


# -- FLOPs, parameters -------------------------------------------------------

@pytest.mark.parametrize("name", ["olmo-hybrid-7b", "tiny"])
def test_flops_per_token_are_the_benchmarks(name):
    cfg = mc.load("olmo-hybrid-7b")
    cfg = mc.tiny(cfg) if name == "tiny" else cfg
    seq = S if name == "tiny" else 16384
    model = get_model(cfg["program"]["model"], **mc.program_kwargs(cfg))
    assert model.cfg.flops_per_token(seq) == pytest.approx(
        roofline_olmohybrid.train_flops_per_token(cfg, seq), rel=1e-12)


def test_param_count_is_issue_40s_table():
    cfg = mc.load("olmo-hybrid-7b")
    count = mc.param_count(cfg)
    assert (count["gdn_mixer"], count["attn_mixer"], count["ffn"],
            count["gdn_layer"], count["attn_layer"],
            count["embedding_head_final_norm"], count["total"]) == (
        44375262, 29495040, 126812160, 171195102, 156314880, 96341760,
        766241946)
    model = get_model(cfg["program"]["model"], **mc.program_kwargs(cfg))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    assert sum(x.size for x in jax.tree.leaves(shapes)) == count["total"]
    specs = wo.leaf_specs(cfg)
    assert sum(int(np.prod(s)) for s, _ in specs.values()) == count["total"]
    assert (model.cfg.heads_held, model.cfg.n_heads, model.cfg.gdn_heads,
            model.cfg.head_dim) == (15, 30, 30, 128)
