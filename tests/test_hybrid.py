"""The layer-kind decoder (``tony_tpu.models.hybrid``) against the plain
reference ``benchmark/reference_phi4flash.py`` on seeded weights, at a small
size on the CPU: logits, loss and every gradient leaf of the six-layer
pattern, each mixer alone, the sliced and tied vocabulary, and the normal
training path (``get_model`` -> ``create_train_state`` ->
``make_train_step``), Pallas bodies included under ``interpret=True``.

Tolerances. Program and reference are both run in float32 at ``highest``
matmul precision here, so they differ only in the order of float32
additions (fused vs split projections, the chunked vs the plain scan, the
online vs the one-pass softmax): 2e-5 of the largest logit, 1e-5 on the
loss, 1e-4 of each gradient leaf's largest entry (gradients sum over 96
tokens and six layers). A scan whose state and dt are kept in bfloat16
misses each of them by more than an order of magnitude, and
``test_bfloat16_scan_state_fails_the_tolerances`` holds it to that."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

from benchmark import modelcfg_phi4flash as mc
from benchmark import reference_phi4flash as ref
from benchmark import weights_phi4flash as wp
from tony_tpu import profiler, train
from tony_tpu.models import get_model, hybrid

LOGIT_TOL, LOSS_TOL, GRAD_TOL = 2e-5, 1e-5, 1e-4
CFG = mc.tiny(mc.load("phi-4-mini-flash-reasoning"))
TOKENS = jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, CFG["vocab"])


def program(**kw):
    kwargs = mc.program_kwargs(CFG)
    kwargs.update(xent_chunk=0, remat=False, dtype=jnp.float32)
    kwargs.update(kw)
    return get_model(CFG["program"]["model"], **kwargs)


@pytest.fixture(scope="module")
def weights():
    return wp.make_weights(CFG, 7)


@pytest.fixture(scope="module")
def reference_side(weights):
    with jax.default_matmul_precision("highest"):
        logits = jax.vmap(lambda t: ref.logits(weights, t, CFG))(TOKENS)
        loss, grads = jax.value_and_grad(
            lambda w: ref.loss(w, TOKENS, CFG))(weights)
    return logits, loss, grads


def program_side(weights, **kw):
    model = program(**kw)
    params = wp.to_program_tree(weights, CFG)
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": params}, TOKENS)
        loss, grads = jax.value_and_grad(lambda p: train.next_token_loss(
            model.apply({"params": p}, TOKENS), TOKENS))(params)
    return logits, loss, wp.from_program_tree(grads, CFG)


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want))) / (
        float(jnp.max(jnp.abs(want))) + 1e-30)


def test_seeded_weights_fit_the_programs_tree(weights):
    model = program()
    init = nn.unbox(model.init(jax.random.PRNGKey(0), TOKENS))["params"]
    tree = wp.to_program_tree(weights, CFG)
    assert jax.tree.structure(init) == jax.tree.structure(tree)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree.leaves(init), jax.tree.leaves(tree)))
    back = wp.from_program_tree(tree, CFG)
    assert sorted(back) == sorted(weights)
    assert all(jnp.array_equal(back[n], weights[n]) for n in weights)
    n = sum(a.size for a in jax.tree.leaves(init))
    assert n == mc.param_count(CFG)["total"]


def test_logits_and_loss_match_reference(weights, reference_side):
    logits, loss, _ = program_side(weights)
    assert rel(logits, reference_side[0]) < LOGIT_TOL
    assert abs(float(loss) - float(reference_side[1])) < LOSS_TOL


def test_every_gradient_leaf_matches_reference(weights, reference_side):
    _, _, grads = program_side(weights)
    want = reference_side[2]
    assert sorted(grads) == sorted(want)
    worst = max((rel(grads[n], want[n]), n) for n in want
                if not n.endswith(".bk"))
    assert worst[0] < GRAD_TOL, worst
    # The key bias: a constant added to every key's score leaves a softmax
    # as it was, so its gradient is zero up to rounding on both sides
    # (weights_phi4flash.NOISE_LEAVES; the chip's check leaves it out of
    # the weights' change for that reason).
    for n in (n for n in want if n.endswith(".bk")):
        scale = float(jnp.max(jnp.abs(want[n[:-1] + "q"])))
        assert float(jnp.max(jnp.abs(want[n]))) < 1e-4 * scale
        assert float(jnp.max(jnp.abs(grads[n]))) < 1e-4 * scale


def test_pallas_bodies_match_reference(weights, reference_side):
    """The same comparison through the kernels' bodies (Pallas
    interpreter): the scan kernels and the packed flash kernels, windowed
    and causal, at the pair's head size 2 x 64 = 128."""
    cfg = dict(CFG, hidden=256, heads=4, kv_heads=2, head_dim=64,
               dt_rank=16)
    w = wp.make_weights(cfg, 3)
    kwargs = mc.program_kwargs(cfg)
    kwargs.update(xent_chunk=0, remat=False, dtype=jnp.float32,
                  interpret=True)
    model = get_model(cfg["program"]["model"], **kwargs)
    params = wp.to_program_tree(w, cfg)
    toks = TOKENS[:1, :40]
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: train.next_token_loss(
            model.apply({"params": p}, toks), toks))(params)
        want_loss, want = jax.value_and_grad(
            lambda w: ref.loss(w, toks, cfg))(w)
    grads = wp.from_program_tree(grads, cfg)
    assert abs(float(loss) - float(want_loss)) < LOSS_TOL
    worst = max((rel(grads[n], want[n]), n) for n in want
                if not n.endswith(".bk"))
    assert worst[0] < GRAD_TOL, worst


def test_bfloat16_scan_state_fails_the_tolerances(weights, reference_side):
    logits, _, grads = program_side(weights, scan_dtype="bfloat16")
    assert rel(logits, reference_side[0]) > 10 * LOGIT_TOL
    worst = max(rel(grads[n], reference_side[2][n])
                for n in reference_side[2] if not n.endswith(".bk"))
    assert worst > 10 * GRAD_TOL


def _layer_pair(weights, index):
    """(program layer output, reference layer output) for layer ``index``
    alone on seeded activations, with the streams the kind consumes."""
    kind = CFG["kinds"][index]
    cfg = hybrid.HybridConfig(**{**mc.program_kwargs(CFG), "layers": tuple(
        CFG["kinds"]), "xent_chunk": 0, "remat": False,
        "dtype": jnp.float32})
    t, d, e = 24, CFG["hidden"], mc.d_inner(CFG)
    kv = CFG["kv_heads"] * CFG["head_dim"]
    ks = jax.random.split(jax.random.PRNGKey(index), 4)
    x = jax.random.normal(ks[0], (t, d))
    streams = {"m": jax.random.normal(ks[1], (t, e)),
               "k": jax.random.normal(ks[2], (t, kv)),
               "v": jax.random.normal(ks[3], (t, kv))}
    layer = hybrid.HybridLayer(cfg, kind, index)
    params = wp.to_program_tree(weights, CFG)[f"layer_{index}"]
    consumed = [streams[s][None] for s in hybrid.MIXERS[kind].consumes]
    with jax.default_matmul_precision("highest"):
        got, emitted = layer.apply({"params": params}, x[None], *consumed)
        want, out = ref.layer(
            x, {"m": streams["m"], "kv": (streams["k"], streams["v"])},
            ref.layer_weights(weights, index), kind, index, CFG)
    return kind, got[0], want, emitted, out


@pytest.mark.parametrize("index", range(6), ids=CFG["kinds"])
def test_each_layer_kind_alone(weights, index):
    kind, got, want, emitted, out = _layer_pair(weights, index)
    assert rel(got, want) < LOGIT_TOL
    assert len(emitted) == len(hybrid.MIXERS[kind].emits)
    if kind == "mamba":
        assert rel(emitted[0][0], out["m"]) < LOGIT_TOL
    if kind == "full":
        assert rel(emitted[0][0], out["kv"][0]) < LOGIT_TOL
        assert rel(emitted[1][0], out["kv"][1]) < LOGIT_TOL


def test_sliced_tied_vocabulary(weights, reference_side):
    """One table at both ends, over the slice only: the fused head + loss
    gives the reference's loss, its gradient reaches the table from the
    lookup and from the head, and no logit exists outside the slice."""
    model = program(xent_chunk=32)
    params = wp.to_program_tree(weights, CFG)
    assert "lm_head" not in params and "lm_head_kernel" not in params
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: model.apply(
            {"params": p}, TOKENS, TOKENS))(params)
        logits = model.apply({"params": params}, TOKENS)
    assert logits.shape == (*TOKENS.shape, CFG["vocab"])
    assert abs(float(loss) - float(reference_side[1])) < LOSS_TOL
    assert rel(grads["embedding"], reference_side[2]["embed"]) < GRAD_TOL


def test_trains_through_the_normal_path():
    profiler.reset_timeline()
    model = get_model("hybrid-tiny", xent_chunk=32, remat=True, window=8)
    toks = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, 256)
    state = train.create_train_state(model, optax.adamw(3e-3), toks,
                                     jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda loss, b: loss,
        apply_kwargs_of=lambda b: {"targets": b["x"]})
    losses = []
    for _ in range(4):
        state, metrics = step(state, {"x": toks})
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and all(l == l for l in losses)
    counters = profiler.counters()
    assert counters["model:layers.mamba"] == 2
    assert all(counters[f"model:layers.{k}"] == 1
               for k in ("swa", "full", "gmu", "cross"))
    assert counters["ssm:chunks"] == 4          # 32 steps in chunks of 8
    for kind in ("swa", "full", "cross"):
        assert counters[f"attn:kv_blocks_fetched.{kind}"] <= \
            counters[f"attn:kv_blocks_visited.{kind}"] <= \
            counters[f"attn:kv_blocks_total.{kind}"]


@pytest.mark.parametrize("t,swa,causal", [
    (4096, (512, 15, 64, 1), (512, 36, 64, 1)),     # K/V resident in VMEM
    # streamed, the cell: a row under the window starts on the block the
    # row before stood on, a causal row but the first on block 0
    (8192, (512, 31, 256, 16), (1024, 36, 64, 35)),
])
def test_counters_make_the_skip_countable(t, swa, causal):
    """At a length of several blocks the window visits fewer K/V blocks
    than the causal layers, which visit the triangle; the blocks are the
    kernels' own rule's (the pair's head size 128, bfloat16: 512 a side
    with K/V resident, streamed 1024 for the causal layers and 512 under
    the 512-key window), recorded per kernel."""
    profiler.reset_timeline()
    model = get_model("hybrid-tiny", window=512, dim=256, n_heads=4,
                      n_kv_heads=2)
    jax.eval_shape(model.init, jax.random.PRNGKey(0),
                   jnp.zeros((1, t), jnp.int32))
    c = profiler.counters()
    for kind, (side, visited, total, fetched) in (
            ("swa", swa), ("full", causal), ("cross", causal)):
        assert c[f"attn:kv_blocks_total.{kind}"] == total
        assert c[f"attn:kv_blocks_visited.{kind}"] == visited
        assert c[f"attn:kv_blocks_fetched.{kind}"] == fetched
        for kernel in ("fwd", "dq", "dkv"):
            assert c[f"attn:block_q.{kernel}.{kind}"] == side
            assert c[f"attn:block_k.{kernel}.{kind}"] == side


def test_layer_order_is_checked():
    with pytest.raises(ValueError, match="consumes"):
        get_model("hybrid-tiny", layers=("gmu", "mamba"))
    with pytest.raises(ValueError, match="consumes"):
        get_model("hybrid-tiny", layers=("mamba", "cross"))
    with pytest.raises(ValueError, match="unknown layer kind"):
        get_model("hybrid-tiny", layers=("mamba", "rwkv"))
    with pytest.raises(ValueError, match="one chip"):
        get_model("hybrid-tiny", mesh=object())
    assert get_model("hybrid-tiny", layers=["mamba", "gmu"]).cfg.layers == (
        "mamba", "gmu")
