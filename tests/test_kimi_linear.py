"""The ``kimi-linear-48b-a3b`` path at tiny sizes on the CPU, seeded
weights: the system (``get_model`` -> ``create_train_state`` ->
``make_train_step``) against the benchmark's plain reference — the loss,
every gradient leaf, two AdamW steps, in float32 and bfloat16 compute; the
flash grids at unequal q/k and v widths against ``reference_attention``;
``route_sigmoid`` against a plain form; the **share test** (the routed parts
of all the shares plus the shared expert counted once add up to the uncut
layer); the FLOPs a token is charged; and all six cells' train steps,
traced at their real sizes with the kernels' branches taken, against the
jaxprs a parent commit traced."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import modelcfg, modelcfg_glm47flash, modelcfg_keyevl2, \
    modelcfg_olmohybrid, modelcfg_phi4flash, modelcfg_zaya1
from benchmark import modelcfg_kimilinear as mc
from benchmark import reference, reference_kimilinear as ref
from benchmark import roofline_kimilinear, weights_kimilinear as wk
from tony_tpu import profiler, train
from tony_tpu.models import get_model, moe
from tony_tpu.models.moe import DroplessMoE
from tony_tpu.ops import attention as A

CFG = mc.tiny(mc.load("kimi-linear-48b-a3b"))
B, S, LR = 2, 64, 3e-4
LEAVES = sorted(wk.leaf_specs(CFG))


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


@pytest.fixture(scope="module", params=["float32", "bfloat16"])
def both(request):
    """Program (in the parametrised compute dtype) and reference over the
    same seeded weights and batch: the loss and its gradient, leaf by
    leaf, and the tolerance the dtype allows."""
    model = _model(jnp.dtype(request.param))
    w0, (x,) = wk.make_weights(CFG, 7), _tokens(0)
    loss, g = jax.value_and_grad(lambda p: model.apply(
        {"params": p}, x, targets=x))(wk.to_program_tree(w0, CFG))
    ref_loss, rg = jax.value_and_grad(lambda w: ref.loss(w, x, CFG))(w0)
    # (loss, a gradient leaf's distance over its length): float32 reads
    # 1e-7 and 4e-6; bfloat16 2e-3 and, where tokens' experts flip on the
    # rounding of the router's input at these widths, up to 0.75
    tol = {"float32": (2e-5, 1e-4), "bfloat16": (5e-3, 0.9)}[request.param]
    return loss, ref_loss, wk.from_program_tree(g, CFG), rg, tol


def test_loss_matches_the_reference(both):
    loss, ref_loss, _, _, tol = both
    assert float(loss) == pytest.approx(float(ref_loss), rel=tol[0])


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both, leaf):
    _, _, g, rg, tol = both
    if leaf.endswith("router_bias"):        # selection only: no gradient
        assert float(jnp.abs(g[leaf]).max()) == 0.0 \
            == float(jnp.abs(rg[leaf]).max())
        return
    assert float(jnp.abs(rg[leaf]).max()) > 0, leaf
    assert _rel(g[leaf], rg[leaf]) < tol[1], leaf


def _two_steps(model, step, batches, seed):
    """Two AdamW steps of the program from the weights of ``seed``: its
    losses, its last metrics, and how far each leaf moved beside how far
    the reference's moved."""
    state = train.create_train_state(
        model, optax.adamw(LR), jnp.zeros((B, S), jnp.int32),
        jax.random.PRNGKey(0))
    state = state.replace(params=wk.to_program_tree(
        wk.make_weights(CFG, seed), CFG))
    got = []
    for x in batches:
        state, metrics = step(state, {"x": x})
        got.append(float(metrics["loss"]))
    losses, _, w2 = ref.train_steps(wk.make_weights(CFG, seed), batches, CFG,
                                    LR)
    w0 = wk.make_weights(CFG, seed)
    moved = reference.change_norms(wk.from_program_tree(state.params, CFG),
                                   w0)
    return got, losses, metrics, moved, reference.change_norms(w2, w0)


@pytest.mark.parametrize("dtype, rel", [("float32", 2e-3), ("bfloat16", 0.1)])
def test_two_adamw_steps_match_the_reference(dtype, rel):
    model, batches = _model(jnp.dtype(dtype)), _tokens(1, 2)
    step = train.make_train_step(
        loss_of=lambda loss, b: loss,
        apply_kwargs_of=lambda b: {"targets": b["x"]})
    got, losses, metrics, moved, want = _two_steps(model, step, batches, 9)
    for mine, theirs in zip(got, losses):
        assert mine == pytest.approx(float(theirs), rel=rel / 10)
    # a decay rate a head (two numbers a layer here): in bfloat16 a leaf's
    # second AdamW step is the ratio of two noisy gradients (over weight
    # seeds 9-13 one layer's reads 0.69-1.35 of the reference's, and 0.94-
    # 1.12 at seed 9), so there the leaf is held over the four layers and
    # three seeds of weights as one (reads 1.007); float32 holds each
    pooled = [leaf for leaf in LEAVES if leaf.endswith("a_log")] \
        if dtype == "bfloat16" else []
    for leaf in LEAVES:
        if leaf not in pooled:
            assert float(moved[leaf]) == pytest.approx(float(want[leaf]),
                                                       rel=rel), leaf
    if pooled:
        runs = [(moved, want)] + [_two_steps(model, step, batches, seed)[3:]
                                  for seed in (10, 11)]
        norm = lambda i: float(np.linalg.norm(
            [run[i][leaf] for run in runs for leaf in pooled]))
        assert norm(0) == pytest.approx(norm(1), rel=rel), pooled
    # every expert layer's held rows of step 2, sown for the step's metrics
    rows = [int(v) for path, v in jax.tree_util.tree_leaves_with_path(
        metrics["stats"]) if "moe_rows_held" in jax.tree_util.keystr(path)]
    assert len(rows) == CFG["ffns"].count("experts") and min(rows) > 0
    assert float(metrics["aux_loss"]) == 0.0      # L = L_LM


@pytest.mark.parametrize("backend, fused", [("tpu", 3), ("cpu", 0)])
def test_the_mixers_count_their_fused_conv_chains(backend, fused,
                                                  monkeypatch):
    """``kda:conv_fused`` is how many of a mixer's q, k and v chains run
    ``ops.ssm``'s fused kernels — all three on a TPU, none on the CPU
    (``ops.ssm.conv_plan``) — and ``kda:conv_block`` the steps a block of
    q's holds; published once, however many layers are traced. The cell's
    own model, abstractly (the tiny one's latent attention has no lane
    blocks for a TPU's kernels)."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    model = get_model("kimi-linear-48b-a3b", **mc.program_kwargs(
        mc.load("kimi-linear-48b-a3b"), 32768))
    profiler.reset_timeline()
    jax.eval_shape(model.init, jax.random.PRNGKey(0),
                   jnp.zeros((1, 32768), jnp.int32))
    c = profiler.timeline()["counters"]
    profiler.reset_timeline()
    assert c["kda:conv_fused"] == fused
    assert c.get("kda:conv_block") == (1024 if fused else None)


def test_rows_held_are_the_references():
    """The (token, choice) pairs the held range is sent, layer by layer:
    the program's sown counts are the plain router's."""
    w0, (x,) = wk.make_weights(CFG, 5), _tokens(4)
    _, sown = _model().apply({"params": wk.to_program_tree(w0, CFG)}, x,
                             targets=x, mutable=["stats"])
    mine = [int(v) for path, v in jax.tree_util.tree_leaves_with_path(
        sown["stats"]) if "moe_rows_held" in jax.tree_util.keystr(path)]
    assert mine == [int(n) for n in ref.rows_held(w0, x, CFG)]


def test_the_model_counts_its_layers_and_refuses_a_mesh():
    profiler.reset_timeline()
    x = _tokens(6)[0]
    model = _model()
    model.init(jax.random.PRNGKey(0), x)
    c = profiler.counters()
    profiler.reset_timeline()
    assert (c["model:layers.kda"], c["model:layers.mla"],
            c["model:layers.experts"]) == (4, 1, 4)
    assert (c["kda:heads"], c["kda:chunk"], c["kda:chunks"],
            c["kda:states_kept"]) == (2, 8, 8, 4)
    # each kernel builds a chunk's state-free half once (keep 2), and a
    # chunk of 8 multiplies 2 x 2 levels + G + G_last - G blocks of table
    assert (c["kda:halves_built.fwd"], c["kda:halves_built.bwd"],
            c["kda:table_rows"]) == (2, 2, 48)
    assert (c["mla:kv_rank"], c["mla:qk_dim"], c["mla:v_dim"]) == (32, 24, 16)
    assert c["moe:shared"] == 1 and c["moe:experts_total"] == 16
    assert c["attn:kv_blocks_visited.mla"] >= \
        c["attn:kv_blocks_fetched.mla"] >= 1
    with pytest.raises(ValueError, match="one chip"):
        _model(mesh=object())
    with pytest.raises(ValueError, match="feed-forward"):
        _model(ffns=("dense",) * 5)
    # pairs are differential attention's: no such layer, no such check
    assert _model(n_heads=3).cfg.n_heads == 3
    with pytest.raises(ValueError, match="pairs heads"):
        get_model("hybrid-tiny", n_heads=3)


# -- the flash grids at unequal q/k and v widths --------------------------

def _mla_inputs(t, h=2, d=128, ds=64, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(t), 6)
    n = lambda k, *shape: jax.random.normal(k, shape, dtype)
    return (n(ks[0], 1, t, h * d), n(ks[1], 1, h, t, ds),
            n(ks[2], 1, t, h * d), n(ks[3], 1, t, ds), n(ks[4], 1, t, h * d),
            jax.random.normal(ks[5], (1, t, h * d)))


@pytest.fixture(scope="module", params=[256, 384, 200],
                ids=["t256", "t384_one_block", "t200_padded"])
def mla_calls(request):
    *args, w = _mla_inputs(request.param)
    h, d = 2, 128
    kernels = lambda *a: A.flash_attention_mla(*a, h, interpret=True)
    q, qs, k, ks, v = args
    to4 = lambda x: x.reshape(1, -1, h, d).transpose(0, 2, 1, 3)
    # packsite: region-local — test operands, one unsharded array.
    q4 = jnp.concatenate([to4(q), qs], -1)
    # packsite: region-local — as above.
    k4 = jnp.concatenate([to4(k), jnp.broadcast_to(
        ks[:, None], (1, h, ks.shape[1], 64))], -1)

    def plain(q, qs, k, ks, v):
        q4_ = jnp.concatenate([to4(q), qs], -1)
        k4_ = jnp.concatenate([to4(k), jnp.broadcast_to(
            ks[:, None], (1, h, ks.shape[1], 64))], -1)
        out = A.reference_attention(q4_, k4_, to4(v), True, 192 ** -0.5)
        return out.transpose(0, 2, 1, 3).reshape(1, -1, h * d)
    assert q4.shape[-1] == k4.shape[-1] == 192
    grads = lambda f: jax.grad(lambda *a: (f(*a) * w).sum(),
                               (0, 1, 2, 3, 4))(*args)
    return kernels(*args), plain(*args), grads(kernels), grads(plain)


def test_flash_at_192_over_128_matches_reference_attention(mla_calls):
    out, want, _, _ = mla_calls
    assert out.shape == want.shape and _rel(out, want) < 2e-6


@pytest.mark.parametrize("arg", range(5), ids=["q", "qs", "k", "ks", "v"])
def test_flash_at_192_over_128_gradients(mla_calls, arg):
    _, _, g, want = mla_calls
    assert g[arg].shape == want[arg].shape
    assert _rel(g[arg], want[arg]) < 5e-6


def test_mla_entry_checks_its_shapes():
    q, qs, k, ks, v, _ = _mla_inputs(128)
    with pytest.raises(ValueError, match="mla shapes"):
        A.flash_attention_mla(q, qs, k, ks[:, :64], v, 2)
    with pytest.raises(ValueError, match="lane blocks"):
        A.flash_attention_mla(q, jnp.concatenate([qs, qs], 1), k, ks, v, 4,
                              interpret=True)
    # the CPU's path is the reference over the concatenated parts
    assert _rel(A.flash_attention_mla(q, qs, k, ks, v, 2),
                A.flash_attention_mla(q, qs, k, ks, v, 2,
                                      interpret=True)) < 2e-6


# -- the sigmoid router ----------------------------------------------------

def _router_inputs(n=64, d=16, e=32, seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    return f(n, d), f(d, e) / 4, 0.3 * f(e)


def test_route_sigmoid_is_the_plain_form():
    x, w, b = _router_inputs()
    experts, gates = moe.route_sigmoid(x, w, b, 4, 2.446)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(w,
                                                               np.float64)))
    order = np.argsort(-(s + np.asarray(b)), axis=-1, kind="stable")[:, :4]
    assert np.array_equal(np.sort(order, -1), np.sort(experts, -1))
    picked = np.take_along_axis(s, np.asarray(experts), -1)
    np.testing.assert_allclose(gates, 2.446 * picked / picked.sum(
        -1, keepdims=True), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.446, rtol=1e-5)


def test_the_bias_moves_the_choice_and_not_the_gate():
    x, w, b = _router_inputs(seed=1)
    e0, g0 = moe.route_sigmoid(x, w, jnp.zeros_like(b), 4)
    e1, g1 = moe.route_sigmoid(x, w, b, 4)
    moved = np.sort(e0, -1) != np.sort(e1, -1)
    assert moved.any() and not moved.all(axis=-1).all()
    # a token whose choice the bias left alone has the gates it had
    same = ~moved.any(axis=-1)
    assert same.any()
    np.testing.assert_allclose(np.sort(g0[same], -1), np.sort(g1[same], -1),
                               rtol=1e-6)
    # and the gate of a moved token is its score's, not score + bias
    s = jax.nn.sigmoid(x @ w)
    picked = jnp.take_along_axis(s, e1, -1)
    np.testing.assert_allclose(g1, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)


def test_the_bias_takes_no_gradient_and_the_router_does():
    x, w, b = _router_inputs(seed=2)
    f = lambda w, b: (moe.route_sigmoid(x, w, b, 4, 2.0)[1]
                      * jnp.arange(4.0)).sum()
    gw, gb = jax.grad(f, (0, 1))(w, b)
    assert float(jnp.abs(gb).max()) == 0.0 and float(jnp.abs(gw).max()) > 0


def test_chunk_rule_at_8_of_256():
    """A third routing shape: the cap on routed rows binds, 32 rows an
    expert a chunk; twice the held share would be 512 rows a pass, under
    the fewest a pass's buffers may hold on the chip (``moe.ROWS_MIN``:
    a step with 512-row buffers halts at its second execution on the v5e,
    PERF.md section 6, PR 38). The other cells' buffers are their own."""
    assert moe.chunk_tokens(8, 256) == 1024
    assert 1024 * 8 == moe.ROUTED_MAX
    assert moe.rows_buffer(1024, 8, 8, 256) == moe.ROWS_MIN == 1024
    assert moe.rows_buffer(1024, 8, 16, 128) == 2048      # Keye's layer
    assert moe.rows_buffer(4096, 1, 8, 16) == 4096        # ZAYA1's
    assert moe.rows_buffer(64, 2, 4, 16) == 64            # under a tile


# -- the share test --------------------------------------------------------

D, F, E, T, TOPK, SCALE = 32, 16, 16, 96, 4, 2.446
LCFG = {"eps": 1e-5, "top_k": TOPK, "route_scale": SCALE, "shared": 1,
        "experts_held": E, "expert_offset": 0}


def _layer_weights(seed):
    rng = np.random.default_rng(seed)
    n = lambda *s: jnp.asarray(rng.normal(size=s) / np.sqrt(s[-2] if len(s)
                               > 1 else 1.0), jnp.float32)
    return {"w_router": 2 * n(D, E), "router_bias": 0.1 * n(E),
            "w_gate": n(E, D, F), "w_up": n(E, D, F), "w_down": n(E, F, D),
            "shared_gate": n(D, F), "shared_up": n(D, F),
            "shared_down": n(F, D)}


def _share(y, w, held, offset, shared=1):
    layer = DroplessMoE(D, F, E, top_k=TOPK, experts_held=held,
                        expert_offset=offset, dtype=jnp.float32,
                        router="sigmoid", route_scale=SCALE, shared=shared)
    params = {n: (a[offset:offset + (held or E)]
                  if n in ("w_gate", "w_up", "w_down") else a)
              for n, a in w.items() if shared or not n.startswith("shared")}
    out, sown = layer.apply({"params": params}, y[None], mutable="stats")
    return out[0], sown["stats"]


@pytest.fixture(scope="module")
def layer_inputs():
    y = jnp.asarray(np.random.default_rng(11).normal(size=(T, D)),
                    jnp.float32)
    return y, _layer_weights(12)


@pytest.mark.parametrize("offset", [0, 4, 8, 12])
def test_a_share_gives_its_own_experts_part(layer_inputs, offset):
    y, w = layer_inputs
    out, stats = _share(y, w, 4, offset, shared=0)
    mine = {n: a[offset:offset + 4] if n in ("w_gate", "w_up", "w_down")
            else a for n, a in w.items()}       # the held experts' leaves
    want = ref.experts(y, mine, LCFG, held=4, offset=offset, shared=False)
    np.testing.assert_allclose(out, want, atol=2e-5)
    chosen, _ = ref.route(y, w, LCFG)
    mine = int(((chosen >= offset) & (chosen < offset + 4)).sum())
    assert int(stats["moe_rows_held"][0]) == mine > 0


def test_the_shares_and_the_shared_expert_add_up_to_the_uncut_layer(
        layer_inputs):
    """Four chips holding four experts each: their routed parts, plus the
    shared expert counted ONCE (every chip computes it whole; the
    deployment adds it on one), are the layer that holds all sixteen."""
    y, w = layer_inputs
    whole, _ = _share(y, w, 0, 0, shared=1)
    routed = sum(_share(y, w, 4, off, shared=0)[0] for off in (0, 4, 8, 12))
    shared_only = reference.swiglu(y, w["shared_gate"], w["shared_up"],
                                   w["shared_down"])
    np.testing.assert_allclose(routed + shared_only, whole, atol=5e-5)
    np.testing.assert_allclose(whole, ref.experts(y, w, LCFG), atol=5e-5)
    # a share with its shared expert is its routed part plus that expert
    one, _ = _share(y, w, 4, 8, shared=1)
    np.testing.assert_allclose(
        one, _share(y, w, 4, 8, shared=0)[0] + shared_only, atol=5e-5)
    # every token's gates add up to the scale over the whole layer
    _, gates = ref.route(y, w, LCFG)
    np.testing.assert_allclose(gates.sum(-1), SCALE, rtol=1e-5)


def test_the_int8_lane_covers_the_shared_expert(layer_inputs):
    y, w = layer_inputs
    layer = lambda quant: DroplessMoE(
        D, F, E, top_k=TOPK, dtype=jnp.float32, router="sigmoid",
        route_scale=SCALE, shared=1, quant=quant).apply({"params": w},
                                                        y[None])[0]
    exact, lane = layer(False), layer(True)
    assert 1e-4 < _rel(lane, exact) < 0.1
    with pytest.raises(ValueError, match="router"):
        DroplessMoE(D, F, E, router="tanh").init(jax.random.PRNGKey(0),
                                                 y[None])


# -- FLOPs, parameters ------------------------------------------------------

@pytest.mark.parametrize("name", ["kimi-linear-48b-a3b", "tiny"])
def test_flops_per_token_are_the_benchmarks(name):
    cfg = mc.load("kimi-linear-48b-a3b")
    cfg = mc.tiny(cfg) if name == "tiny" else cfg
    seq = S if name == "tiny" else 32768
    model = get_model(cfg["program"]["model"], **mc.program_kwargs(cfg, seq))
    assert model.cfg.flops_per_token(seq) == pytest.approx(
        roofline_kimilinear.train_flops_per_token(cfg, seq), rel=1e-9)
    # the recurrence's work knows no chunk: 7 + 14 a state element a step
    flops, nbytes = roofline_kimilinear.kda_recurrence(10, 2, 8, 8)
    assert flops == 10 * 2 * 7 * 64 and nbytes == 10 * 2 * (64 + 32 + 4)
    with pytest.raises(NotImplementedError, match="roofline_ssm"):
        get_model("hybrid-tiny").cfg.flops_per_token(64)


def test_param_count_is_issue_38s_table():
    cfg = mc.load("kimi-linear-48b-a3b")
    count = mc.param_count(cfg)
    assert (count["kda_mixer"], count["mla_mixer"], count["one_expert"],
            count["expert_layer_held"], count["dense_mlp"],
            count["embedding_head_final_norm"], count["total"]) == (
        39518368, 29114880, 7077888, 64291072, 63700992, 94374144, 602450816)
    model = get_model(cfg["program"]["model"], **mc.program_kwargs(cfg, 128))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    assert sum(x.size for x in jax.tree.leaves(shapes)) == count["total"]
    specs = wk.leaf_specs(cfg)
    assert sum(int(np.prod(s)) for s, _ in specs.values()) == count["total"]


# -- the cells keep their programs -------------------------------------------

def _clean(text):
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    text = re.sub(r" at [^\s:]+\.py:\d+", "", text)
    return re.sub(r"/[^\s\"']*/(tony_tpu|benchmark)/", r"\1/", text)


def _step_digest(model, batch, seq, monkeypatch):
    """sha256 of the jaxpr of the cell's loss gradient at its real size,
    traced over abstract parameters with the kernels' branches taken."""
    x = jnp.zeros((batch, seq), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), x))["params"]
    if model.cfg.xent_chunk:
        loss = lambda p, x: model.apply(
            {"params": p}, x, targets=x, mutable=["losses", "stats"])[0]
    else:
        loss = lambda p, x: train.next_token_loss(
            model.apply({"params": p}, x), x)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _clean(str(jax.make_jaxpr(jax.grad(loss))(
        params, jax.ShapeDtypeStruct(x.shape, x.dtype))))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


# Read on a parent commit with this very function: the first four on
# fd57d29 (PR 37), before the Kimi Linear cell existed. A ``pallas_call``
# prints its grid and block shapes, not its index maps: PR 46 changed the
# streamed grids' maps and all six held (``tests/test_flash_maps.py`` holds
# the maps).
PARENT_STEPS = {
    "mistral7b.train": ("ca491cfea9d04c94", lambda: (get_model(
        "llama2-7b", attention="flash", **modelcfg.program_kwargs(
            modelcfg.load("mistral-7b-v0.3"), 2048)), 4, 2048)),
    "phi4flash.train-8k": ("1d47c0f3d20cf15e", lambda: (get_model(
        "hybrid-decoder", **modelcfg_phi4flash.program_kwargs(
            modelcfg_phi4flash.load("phi-4-mini-flash-reasoning"))), 1, 8192)),
    "keyevl2.train-16k": ("502323ac82ab6c7e", lambda: (get_model(
        "keye-vl-2.0-30b-a3b", **modelcfg_keyevl2.program_kwargs(
            modelcfg_keyevl2.load("keye-vl-2.0-30b-a3b"), 16384)), 1, 16384)),
    "zaya1.train-32k": ("c39e9c049f0ce4c8", lambda: (get_model(
        "zaya1-8b", **modelcfg_zaya1.program_kwargs(
            modelcfg_zaya1.load("zaya1-8b"), 32768)), 1, 32768)),
    # Read on 10716a2 (PR 46) and on PR 47's tree alike: the fifth cell
    # without a delta-rule mixer.
    "glm47flash.train-16k": ("63a2b771fb0a1c3e", lambda: (get_model(
        "glm-4.7-flash", **modelcfg_glm47flash.program_kwargs(
            modelcfg_glm47flash.load("glm-4.7-flash"), 16384)), 1, 16384)),
    # These two read anew on PR 47's tree (parent 10716a2, PR 46), on
    # purpose: their mixers' q, k, v chains became ``ops.ssm``'s fused
    # kernels, the only change to either jaxpr (before: bf061bcfb035153c
    # and c193f4a060c51298, read on d43341c, PR 42). The five above are
    # the cells without a delta-rule mixer: the first four hold unedited.
    "kimilinear.train-32k": ("da7f1e02469a2b63", lambda: (get_model(
        "kimi-linear-48b-a3b", **mc.program_kwargs(
            mc.load("kimi-linear-48b-a3b"), 32768)), 1, 32768)),
    "olmohybrid.train-16k": ("73ff26c6b00fdb8c", lambda: (get_model(
        "olmo-hybrid-7b", **modelcfg_olmohybrid.program_kwargs(
            modelcfg_olmohybrid.load("olmo-hybrid-7b"))), 1, 16384)),
}


@pytest.mark.parametrize("cell", sorted(PARENT_STEPS))
def test_the_other_cells_steps_trace_to_the_parents_jaxprs(cell, monkeypatch):
    want, make = PARENT_STEPS[cell]
    assert _step_digest(*make(), monkeypatch) == want
