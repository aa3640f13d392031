"""The ``keye-vl-2.0-30b-a3b`` path at tiny sizes on the CPU, seeded
weights: the system (``get_model`` -> ``create_train_state`` ->
``make_train_step``, float32 compute) against the benchmark's plain
reference — both losses, every gradient leaf, two AdamW steps — the
stop-gradients between the two objectives, the exact threshold against
``jax.lax.top_k``, and the selected-attention grids against the reference
attention with the same membership (Pallas interpreter). And that the
indexer's loss runs once a layer (ISSUE 37): under ``remat.block`` its
gradient, taken in the forward and kept as the three kernels', is the
two-pass path's, and the backward holds none of its work."""

import collections
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from benchmark import modelcfg_keyevl2 as mc
from benchmark import reference, reference_keyevl2 as ref
from benchmark import weights_keyevl2 as wk
from tony_tpu import profiler, remat, train
from tony_tpu.models import get_model, moe
from tony_tpu.ops import attention as att
from tony_tpu.ops import indexer

CFG = mc.tiny(mc.load("keye-vl-2.0-30b-a3b"))
B, S, LR = 2, 64, 3e-4
LEAVES = sorted(wk.leaf_specs(CFG))


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """The tiny sizes in more than one piece: two row blocks of the
    indexer a sequence, four chunks of the expert layer a batch."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(indexer, "ROW_BLOCK", 32)
        patch.setattr(moe, "chunk_tokens", lambda top_k, n_experts: 32)
        yield


def _model(**over):
    kw = mc.program_kwargs(CFG, S)
    kw.update({"remat": False, "dtype": jnp.float32, **over})
    return get_model(CFG["program"]["model"], **kw)


def _tokens(seed, n=1):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, CFG["vocab"], (B, S), dtype=np.int32))
            for _ in range(n)]


@pytest.fixture(scope="module")
def both():
    """Program and reference over the same seeded weights and batch: the
    two losses and the gradient of their sum, leaf by leaf."""
    model, w0, (x,) = _model(), wk.make_weights(CFG, 7), _tokens(0)

    def objective(params):
        lm, sown = model.apply({"params": params}, x, targets=x,
                               mutable="losses")
        kl = sum(leaf.sum() for leaf in jax.tree.leaves(sown))
        return lm + kl, (lm, kl)

    (_, prog_losses), g = jax.value_and_grad(objective, has_aux=True)(
        wk.to_program_tree(w0))
    (_, ref_losses), rg = jax.value_and_grad(
        lambda w: (lambda lm, kl: (lm + kl, (lm, kl)))(
            *ref.losses(w, x, CFG)), has_aux=True)(w0)
    return prog_losses, ref_losses, wk.from_program_tree(g), rg


@pytest.mark.parametrize("which", [0, 1], ids=["L_LM", "L_I"])
def test_losses_match_the_reference(both, which):
    prog, want = both[0][which], both[1][which]
    assert float(want) > 0.1
    assert float(prog) == pytest.approx(float(want), rel=1e-5)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_the_reference(both, leaf):
    got, want = np.asarray(both[2][leaf]), np.asarray(both[3][leaf])
    assert np.linalg.norm(want) > 1e-3, "a dead leaf compares nothing"
    assert np.linalg.norm(got - want) <= 1e-4 * np.linalg.norm(want)


def test_two_adamw_steps_match_the_reference():
    model, batches = _model(), _tokens(1, 2)
    state = train.create_train_state(
        model, optax.adamw(LR), jnp.zeros((B, S), jnp.int32),
        jax.random.PRNGKey(0))
    state = state.replace(params=wk.to_program_tree(wk.make_weights(CFG, 9)))
    step = train.make_train_step(
        loss_of=lambda loss, b: loss,
        apply_kwargs_of=lambda b: {"targets": b["x"]})
    got = []
    for x in batches:
        state, metrics = step(state, {"x": x})
        got.append((float(metrics["loss"] - metrics["aux_loss"]),
                    float(metrics["aux_loss"])))
    pairs, _, w2 = ref.train_steps(wk.make_weights(CFG, 9), batches, CFG, LR)
    for (lm, kl), (rlm, rkl) in zip(got, pairs):
        assert lm == pytest.approx(float(rlm), rel=1e-5)
        assert kl == pytest.approx(float(rkl), rel=1e-4)
    seed = wk.make_weights(CFG, 9)
    moved = reference.change_norms(wk.from_program_tree(state.params), seed)
    want = reference.change_norms(w2, seed)
    for leaf in LEAVES:
        assert float(moved[leaf]) == pytest.approx(float(want[leaf]),
                                                   rel=2e-3), leaf
    # every held expert's rows of step 2, sown for the step's metrics
    stats = jax.tree.leaves(metrics["stats"])
    assert len(stats) == 4 and all(s.shape == (CFG["layers"],) for s in stats)


@pytest.mark.parametrize("blocks", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("objective, zero, live", [
    ("L_LM", wk.INDEX_LEAVES, ("wq", "w_gate", "embed")),
    ("L_I", tuple(n for n in LEAVES if n not in wk.INDEX_LEAVES),
     wk.INDEX_LEAVES)])
def test_each_objective_reaches_its_own_leaves_only(objective, zero, live,
                                                    blocks):
    model, w0, (x,) = _model(remat=blocks), wk.make_weights(CFG, 5), _tokens(2)

    def one(params):
        lm, sown = model.apply({"params": params}, x, targets=x,
                               mutable="losses")
        return lm if objective == "L_LM" else sum(
            leaf.sum() for leaf in jax.tree.leaves(sown))

    g = wk.from_program_tree(jax.grad(one)(wk.to_program_tree(w0)))
    for leaf in zero:
        assert float(jnp.abs(g[leaf]).max()) == 0.0, leaf
    for leaf in live:
        assert float(jnp.abs(g[leaf]).max()) > 0.0, leaf


# ---------------------------------------------------- one pass a layer

def _unrolled(params):
    """The scanned model's stacked tree as the unrolled model's."""
    rest = {k: v for k, v in params.items() if k != "layers"}
    return {**rest, **{f"layer_{i}": jax.tree.map(lambda a: a[i],
                                                  params["layers"])
                       for i in range(CFG["layers"])}}


def _restacked(grads):
    layers = [grads.pop(f"layer_{i}") for i in range(CFG["layers"])]
    return {**grads, "layers": jax.tree.map(lambda *a: jnp.stack(a), *layers)}


def _both_losses(model, rung, x):
    """``params -> (L_LM + L_I, L_I)`` of a step that keeps ``rung``
    (None: no step around the trace)."""
    def objective(params):
        with remat.Saved(rung) if rung is not None \
                else contextlib.nullcontext():
            lm, sown = model.apply({"params": params}, x, targets=x,
                                   mutable="losses")
        kl = sum(leaf.sum() for leaf in jax.tree.leaves(sown))
        return lm + kl, kl
    return objective


@pytest.fixture(scope="module")
def two_pass():
    """The path before ISSUE 37: no remat, the loss differentiated with
    respect to ``qI, w, kI`` and the projections transposed by autodiff in
    the backward."""
    (x,) = _tokens(4)
    params = wk.to_program_tree(wk.make_weights(CFG, 11))
    real = indexer.index_loss
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(indexer, "index_loss", lambda *a, through, **kw: real(
            *a, through=(lambda given: given, a[:3]), **kw))
        (_, kl), g = jax.value_and_grad(
            _both_losses(_model(), None, x), has_aux=True)(params)
    return x, params, kl, g


@pytest.mark.parametrize("scan", [True, False], ids=["scanned", "unrolled"])
@pytest.mark.parametrize("rung", [remat.FLOOR, ("sel",)], ids=["floor", "sel"])
def test_one_pass_gives_the_two_pass_loss_and_gradients(two_pass, rung, scan):
    x, params, want_kl, want = two_pass
    model = _model(remat=True, scan_layers=scan)
    (_, kl), g = jax.jit(jax.value_and_grad(
        _both_losses(model, rung, x), has_aux=True))(
            params if scan else _unrolled(params))
    g = g if scan else _restacked(g)
    assert float(kl) == pytest.approx(float(want_kl), rel=1e-6)
    got, want = wk.from_program_tree(g), wk.from_program_tree(want)
    for leaf in LEAVES:
        a, c = np.asarray(got[leaf]), np.asarray(want[leaf])
        tight = 1e-6 if leaf in wk.INDEX_LEAVES else 1e-5
        assert np.linalg.norm(a - c) <= tight * np.linalg.norm(c), leaf


def _walk(jaxpr, under=()):
    for i, eqn in enumerate(jaxpr.eqns):
        yield eqn, under
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _walk(sub, under + ((eqn.primitive.name, i),))


def _loss_work(model, params, x, rung=remat.FLOOR):
    """Where the loss's work is in ``value_and_grad`` of a step: for each
    enclosing equation (the layers' scans; () for an unrolled model), the
    row blocks the loss walks (one barrier each, and nothing else in the
    model has one) and the matmuls under its scope."""
    jaxpr = jax.make_jaxpr(jax.value_and_grad(
        _both_losses(model, rung, x), has_aux=True))(params)
    blocks, dots = collections.Counter(), collections.Counter()
    for eqn, under in _walk(jaxpr.jaxpr):
        if eqn.primitive.name == "optimization_barrier":
            blocks[under[:1]] += 1
        elif eqn.primitive.name == "dot_general" and "attn_index_loss" in str(
                eqn.source_info.name_stack):
            dots[under[:1]] += 1
    return blocks, dots


@pytest.mark.parametrize("rung", [remat.FLOOR, ("sel",)], ids=["floor", "sel"])
def test_the_loss_runs_once_a_layer_and_not_in_the_backward(rung, monkeypatch):
    (x,) = _tokens(4)
    params = wk.to_program_tree(wk.make_weights(CFG, 11))
    layers, row_blocks = CFG["layers"], S // indexer.ROW_BLOCK
    assert layers == 2 and row_blocks == 2
    # Scanned: the forward's scan holds a layer's loss, the backward's none.
    blocks, dots = _loss_work(_model(remat=True), params, x, rung)
    (fwd, n), = blocks.items()
    assert n == row_blocks and fwd[0][0] == "scan"
    assert set(dots) == {fwd} and dots[fwd] > 0
    # Unrolled: once for each layer, in the forward (under no equation);
    # none in the backward's recomputation of a layer (a ``remat2``).
    unrolled = _model(remat=True, scan_layers=False)
    blocks, dots = _loss_work(unrolled, _unrolled(params), x, rung)
    assert blocks == {(): layers * row_blocks} and set(dots) == {()}
    # What the count sees: a block that does not keep the name runs the
    # loss again where it recomputes the layer.
    real = remat.block
    monkeypatch.setattr(remat, "block", lambda cls, always=(): real(cls))
    blocks, dots = _loss_work(unrolled, _unrolled(params), x, rung)
    again = {under for under in blocks if under}
    assert len(again) == layers and {u[0][0] for u in again} == {"remat2"}
    assert all(blocks[under] == row_blocks and dots[under] for under in again)
    assert blocks[()] == layers * row_blocks


@pytest.mark.parametrize("rung, head_in_forward", [
    (remat.FLOOR, True), (("sel",), False)], ids=["floor", "sel"])
def test_the_kept_gradient_is_on_no_rung(rung, head_in_forward):
    """``index_grad`` is kept by every step and is not of its set: at the
    floor ``kept()`` is empty and the chunked head takes its gradient in
    the forward, as in a model without an indexer."""
    model, (x,) = _model(remat=True), _tokens(4)
    state = train.create_train_state(
        model, optax.adamw(LR), jnp.zeros((B, S), jnp.int32),
        jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda loss, b: loss,
        apply_kwargs_of=lambda b: {"targets": b["x"]})
    saved = remat.Saved(rung)
    kept_at_the_head = []
    real = train.chunked_next_token_xent
    profiler.reset_timeline()
    try:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(train, "chunked_next_token_xent", lambda *a, **kw: (
                kept_at_the_head.append(remat.kept()), real(*a, **kw))[1])
            jax.eval_shape(step.build(saved), state, {"x": x})
        c = profiler.counters()
    finally:
        profiler.reset_timeline()
    assert kept_at_the_head and set(kept_at_the_head) == {rung}
    assert saved.names == rung and "index_grad" in saved.met
    assert c["remat:saved.index_grad"] == 1
    assert saved.effective(remat.LADDER[0]) == ("sel", "q", "k", "v", "wo")
    assert c["index:grad_in_forward"] == 1
    assert ("head:grad_in_forward" in c) == head_in_forward
    assert "index_grad" not in {n for r in remat.LADDER for n in r}


def _top_k_membership(scores, row0, k):
    r, n = scores.shape[1:]
    valid = np.arange(n)[None, :] <= row0 + np.arange(r)[:, None]
    _, idx = jax.lax.top_k(jnp.where(valid, scores, -jnp.inf), min(k, n))
    want = np.zeros(scores.shape, bool)
    for b in range(scores.shape[0]):
        for t in range(r):
            want[b, t, np.asarray(idx[b, t])] = True
    return want & valid


@pytest.mark.parametrize("case", ["random", "rows_shorter_than_k",
                                  "exact_ties", "all_equal", "row_block"])
def test_threshold_selects_what_top_k_selects(case):
    rng = np.random.default_rng(3)
    row0, r, n, k = 0, 96, 96, 24
    scores = rng.normal(size=(2, r, n)).astype(np.float32)
    if case == "rows_shorter_than_k":
        k = 200
    elif case == "exact_ties":          # few distinct values, signed zeros
        scores = rng.integers(-2, 3, size=(2, r, n)).astype(np.float32)
        scores[0, :, ::7] = -0.0
    elif case == "all_equal":
        scores = np.zeros((2, r, n), np.float32)
    elif case == "row_block":           # the last rows of a longer sequence
        row0, r = 64, 32
        scores = scores[:, :r]
    got = np.asarray(indexer.select_topk(jnp.asarray(scores), row0, k))
    assert (got == _top_k_membership(jnp.asarray(scores), row0, k)).all()
    assert (got.sum(-1) == np.minimum(row0 + np.arange(r) + 1, k)).all()


def test_selection_survives_packing():
    keep = jnp.asarray(np.random.default_rng(0).random((2, 48, 300)) < 0.3)
    sel = att.pack_selection(keep)
    assert sel.shape == (2, 1, 48, 128) and sel.dtype == jnp.int32
    assert (np.asarray(att.unpack_selection(sel, 300)) == np.asarray(
        keep)).all()


@pytest.fixture(scope="module")
def grids():
    """Forward and backward of the selected-attention kernels (Pallas
    interpreter, 128-blocks: two k-blocks a word's shift apart) and of the
    reference attention under the same membership."""
    rng = np.random.default_rng(0)
    b, t, h, hkv, d, k = 1, 256, 4, 2, 128, 40
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32).astype(
        jnp.bfloat16)
    q, kk, v = arr(b, t, h * d), arr(b, t, hkv * d), arr(b, t, hkv * d)
    keep = indexer.select_topk(jnp.asarray(
        rng.normal(size=(b, t, t)), jnp.float32), 0, k)
    sel = att.pack_selection(keep)
    g = arr(b, t, h * d)

    def kernel(q, kk, v):
        return att.flash_attention_selected(q, kk, v, sel, h, interpret=True)

    def plain(q, kk, v):
        to4 = lambda x, n: x.reshape(b, t, n, d).transpose(0, 2, 1, 3)
        s = jnp.einsum("bhqd,bhkd->bhqk", to4(q, h).astype(jnp.float32),
                       jnp.repeat(to4(kk, hkv), h // hkv, 1).astype(
                           jnp.float32)) * d ** -0.5
        p = jax.nn.softmax(jnp.where(keep[:, None], s, -jnp.inf), -1)
        out = jnp.einsum("bhqk,bhkd->bhqd", p, jnp.repeat(
            to4(v, hkv), h // hkv, 1).astype(jnp.float32))
        return out.transpose(0, 2, 1, 3).reshape(b, t, h * d), \
            jax.nn.logsumexp(jnp.where(keep[:, None], s, -jnp.inf), -1)

    def run(f):
        loss = lambda *a: (f(*a)[0].astype(jnp.float32) * g).sum()
        return (*f(q, kk, v), *jax.grad(loss, (0, 1, 2))(q, kk, v))

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(att, "selection_blocks",
                      lambda *a: att.Blocks(*[(128, 128)] * 3))
        got = run(kernel)
    return got, run(plain), (q, kk, sel, keep, h)


@pytest.mark.parametrize("i, what, tol", [
    (0, "out", 0.01), (1, "lse", 1e-5), (2, "dq", 0.01), (3, "dk", 0.01),
    (4, "dv", 0.01)])
def test_selected_grid_matches_reference_attention(grids, i, what, tol):
    got, want = (np.asarray(x[i], np.float32) for x in grids[:2])
    assert np.abs(want).max() > 0.1
    assert np.abs(got - want).max() <= tol * np.abs(want).max(), what


def test_head_summed_probabilities_kernel(grids, monkeypatch):
    (_, lse, *_), _, (q, kk, sel, keep, h) = grids
    monkeypatch.setattr(att, "_PROBS_ROWS", 128)     # 2 x 1 tiles
    got = att.selected_head_probs(q, kk, lse, sel, h, interpret=True)
    want = att.selected_head_probs(q, kk, lse, sel, h)
    assert np.allclose(got, want, atol=1e-5)
    assert np.allclose(got.sum(-1), h, atol=1e-3)       # each head sums to 1
    assert (np.asarray(got)[~np.asarray(keep)] == 0).all()


def test_index_scores_kernel_and_loss_backward(monkeypatch):
    monkeypatch.setattr(indexer, "ROW_BLOCK", 128)   # two row blocks
    rng = np.random.default_rng(1)
    b, t, j, e, h, hkv, d, k = 1, 256, 2, 16, 2, 1, 128, 40
    arr = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32).astype(
        jnp.bfloat16)
    qi, ki = arr(b, t, j, e), arr(b, t, e)
    w = jnp.asarray(rng.normal(size=(b, t, j)), jnp.float32)
    q, kk, v = arr(b, t, h * d), arr(b, t, hkv * d), arr(b, t, hkv * d)
    below = np.tril(np.ones((t, t), bool))
    want = indexer.index_scores_reference(qi, w, ki)
    got = indexer.index_scores(qi, w, ki, 0, interpret=True)
    assert np.allclose(np.where(below, got, 0), np.where(below, want, 0),
                       atol=1e-4)
    sel = indexer.select(qi, w, ki, k, interpret=True)
    keep = att.unpack_selection(sel, t)
    assert (np.asarray(keep) == np.asarray(
        indexer.select_topk(want, 0, k))).all()
    _, lse = att.selected_attention_reference(q, kk, v, keep, h)
    probs = att.selected_head_probs(q, kk, lse, sel, h)

    def plain(qi, w, ki):
        s = jnp.einsum("brj,brjk->brk", w, jax.nn.relu(jnp.einsum(
            "brje,bke->brjk", qi.astype(jnp.float32),
            ki.astype(jnp.float32))))
        logq = jax.nn.log_softmax(jnp.where(keep, s, -jnp.inf), -1)
        p = probs / probs.sum(-1, keepdims=True)
        return jnp.sum(jnp.where(p > 0, p * (jnp.log(jnp.where(
            p > 0, p, 1.0)) - logq), 0.0)) / (b * t)

    loss = lambda *a: indexer.index_loss(
        *a, sel, q, kk, lse, h, through=(lambda given: given, a),
        interpret=True)
    assert float(loss(qi, w, ki)) == pytest.approx(
        float(plain(qi, w, ki)), rel=1e-5)
    got = jax.grad(loss, (0, 1, 2))(qi, w, ki)
    want = jax.grad(plain, (0, 1, 2))(qi, w, ki)
    for a, c in zip(got, want):
        a, c = np.asarray(a, np.float32), np.asarray(c, np.float32)
        assert np.abs(a - c).max() <= 0.02 * np.abs(c).max()
