"""The chunked head (``train.chunked_next_token_xent``): differentiated, it
takes its gradient while a chunk's logits are in hand — three matmuls a
chunk, the backward only scales; in a step whose backward keeps named
residuals autodiff rebuilds the logits instead (four). Both forms are held
to ``jax.grad`` of the plain whole-logits form (``train.next_token_loss``),
to the shape of their jaxprs and to the counters that say which was traced;
the first also to a mesh that splits the vocabulary, the rule between them
to what a step's trace can tell."""

import contextlib

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tony_tpu import profiler, remat, train

B, D, V, CHUNK = 2, 32, 96, 8
# rows = B * (T - 1): 32 is four whole chunks, 34 pads the fifth with 6 rows
T_OF = {"whole": 17, "padded": 18}
# max |difference| over the reference's max |value|: float32 to rounding;
# bfloat16 to two ulps of the largest entry (one from ``dlogits`` rounded
# after a float32 softmax that sums in another order, one from the bfloat16
# sum over chunks, which the whole-logits form does not have)
TOL = {jnp.float32: 2e-6, jnp.bfloat16: 2 ** -6}
# the loss: XLA may keep a chunk's logits unrounded where the whole-logits
# form rounds them to bfloat16 (or the other way about)
LOSS_RTOL = {jnp.float32: 2e-6, jnp.bfloat16: 1e-4}


@contextlib.contextmanager
def _step(names, blocks=1, met=None):
    """The trace of a step whose backward keeps ``names``, of a model
    with ``blocks`` remat'd layers that has met ``met`` (all of them
    unless told) by the time it reaches the head."""
    with remat.Saved(names) as saved:
        for _ in range(blocks):
            remat.block(nn.Dense)
        for tag in names if met is None else met:
            remat.name(jnp.zeros(()), tag)
        yield saved


@pytest.fixture(params=["in_forward", "recomputed"])
def form(request):
    """Both forms: a step that keeps nothing (or no step at all), and one
    whose backward keeps ``q``. Tracing happens inside the test."""
    with (_step(("q",)) if request.param == "recomputed"
          else contextlib.nullcontext()):
        yield request.param


def _inputs(rows, dtype, tied):
    k = jax.random.split(jax.random.PRNGKey(7), 3)
    hidden = jax.random.normal(k[0], (B, T_OF[rows], D), dtype)
    table = 0.2 * jax.random.normal(k[1], (V, D) if tied else (D, V))
    tokens = jax.random.randint(k[2], (B, T_OF[rows]), 0, V)
    return hidden, table, tokens


def _head(tied):
    return (lambda w: w.T) if tied else (lambda w: w)


def _chunked(scale, tied, dtype):
    return lambda h, w, t: scale * train.chunked_next_token_xent(
        h, _head(tied)(w), t, CHUNK, dtype)


def _plain(scale, tied, dtype):
    def loss(h, w, t):
        logits = (h @ _head(tied)(w).astype(dtype)).astype(jnp.float32)
        return scale * train.next_token_loss(logits, t)
    return loss


def _gap(got, want):
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return np.abs(got - want).max() / np.abs(want).max()


@pytest.mark.parametrize("scale", [1.0, 0.25, 1 / 3],
                         ids=["1", "quarter", "third"])
@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("rows", ["whole", "padded"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_loss_and_gradients_are_the_whole_logits_forms(form, dtype, rows,
                                                       tied, scale):
    hidden, table, tokens = _inputs(rows, dtype, tied)
    got, (dh, dw) = jax.value_and_grad(
        _chunked(scale, tied, dtype), (0, 1))(hidden, table, tokens)
    want, (rh, rw) = jax.value_and_grad(
        _plain(scale, tied, dtype), (0, 1))(hidden, table, tokens)
    assert (dh.dtype, dw.dtype) == (dtype, jnp.float32)
    np.testing.assert_allclose(float(got), float(want), rtol=LOSS_RTOL[dtype])
    assert _gap(dh, rh) < TOL[dtype] and _gap(dw, rw) < TOL[dtype]
    # the last position has no next token: its rows' gradient is sliced
    # away, not merely small
    assert not np.asarray(dh[:, -1], np.float32).any()


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_both_forms_give_the_same_gradients_bit_for_bit(dtype, tied):
    """Not a nearby result: ``dlogits`` is autodiff's operation for
    operation and the chunks are summed in autodiff's order, so what the
    three-product form hands back is what the four-product form does."""
    hidden, table, tokens = _inputs("padded", dtype, tied)
    grad = lambda: jax.jit(jax.grad(_chunked(1.0, tied, dtype), (0, 1)))(
        hidden, table, tokens)
    three = grad()
    with _step(("q",)):
        four = grad()
    for got, want in zip(three, four):
        np.testing.assert_array_equal(np.asarray(got, np.float32),
                                      np.asarray(want, np.float32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_padded_rows_take_no_gradient_and_give_none(dtype):
    """The three-matmul core over rows it was handed padded: weight 0 makes
    a padded row's ``dlogits`` exactly zero, so its rows' gradient is, and
    the head's gradient is the one the unpadded rows give."""
    k = jax.random.split(jax.random.PRNGKey(3), 3)
    rows = jax.random.normal(k[0], (3, CHUNK, D), dtype)
    w = (0.2 * jax.random.normal(k[1], (D, V))).astype(dtype)
    labels = jax.random.randint(k[2], (3, CHUNK), 0, V)
    weights = jnp.ones((3, CHUNK)).at[2, 3:].set(0.0)
    core = lambda r, w, m, n: train._grad_in_forward_xent(
        r, w, labels, m, n)
    d_rows, d_w = jax.grad(core, (0, 1))(rows, w, weights, 19)
    assert not np.asarray(d_rows[2, 3:], np.float32).any()
    assert np.asarray(d_rows[2, :3], np.float32).any()
    # the same 19 rows, the padding filled with other rows
    other = rows.at[2, 3:].set(rows[0, :5])
    d_rows2, d_w2 = jax.grad(core, (0, 1))(other, w, weights, 19)
    np.testing.assert_array_equal(np.asarray(d_w, np.float32),
                                  np.asarray(d_w2, np.float32))
    np.testing.assert_array_equal(np.asarray(d_rows[:2], np.float32),
                                  np.asarray(d_rows2[:2], np.float32))


def _head_dots(jaxpr, chunk, d, v):
    """``dot_general`` equations over a chunk's three head shapes (either
    way round: a transposed product is the same product), anywhere inside
    ``jaxpr``."""
    shapes = {(chunk, v), (chunk, d), (d, v)}
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shape = tuple(sorted(eqn.outvars[0].aval.shape))
            found += [shape] if shape in shapes else []
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _head_dots(sub, chunk, d, v)
    return found


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
def test_matmuls_a_chunk_differentiated_and_undifferentiated(form, tied):
    hidden, table, tokens = _inputs("padded", jnp.bfloat16, tied)
    f = _chunked(1.0, tied, jnp.bfloat16)
    grad = jax.make_jaxpr(jax.grad(f, (0, 1)))(hidden, table, tokens)
    primal = jax.make_jaxpr(f)(hidden, table, tokens)
    assert _head_dots(primal.jaxpr, CHUNK, D, V) == [(CHUNK, V)]
    assert str(primal).count("scan[") == 1
    three = [(CHUNK, D), (CHUNK, V), (D, V)]
    if form == "in_forward":
        # one scan: no transposed twin, no logits rebuilt in the backward
        assert sorted(_head_dots(grad.jaxpr, CHUNK, D, V)) == three
        assert str(grad).count("scan[") == 1
    else:
        assert sorted(_head_dots(grad.jaxpr, CHUNK, D, V)) == sorted(
            three + [(CHUNK, V)])
        assert str(grad).count("scan[") == 2


def test_vocabulary_split_over_two_devices_gives_the_same_gradients():
    hidden, table, tokens = _inputs("padded", jnp.float32, False)
    f = jax.value_and_grad(_chunked(0.25, False, jnp.float32), (0, 1))
    want, (rh, rw) = jax.jit(f)(hidden, table, tokens)
    mesh = Mesh(np.array(jax.devices()[:2]), ("v",))
    here = lambda spec: NamedSharding(mesh, spec)
    split = jax.jit(f, in_shardings=(here(P()), here(P(None, "v")),
                                     here(P())),
                    out_shardings=(here(P()), (here(P()),
                                               here(P(None, "v")))))
    got, (dh, dw) = split(hidden, table, tokens)
    assert dw.sharding.spec == P(None, "v")
    np.testing.assert_allclose(float(got), float(want), rtol=1e-6)
    np.testing.assert_allclose(dh, rh, atol=1e-7, rtol=1e-5)
    np.testing.assert_allclose(dw, rw, atol=1e-7, rtol=1e-5)


def test_counters_say_which_path_was_traced(form):
    hidden, table, tokens = _inputs("padded", jnp.float32, False)
    f = _chunked(1.0, False, jnp.float32)
    profiler.reset_timeline()
    try:
        jax.eval_shape(f, hidden, table, tokens)
        assert profiler.counters()["head:chunks"] == 5
        assert "head:grad_in_forward" not in profiler.counters()
        jax.eval_shape(jax.grad(f), hidden, table, tokens)
        assert profiler.counters()["head:chunks"] == 5
        assert profiler.counters().get("head:grad_in_forward") == (
            1 if form == "in_forward" else None)
    finally:
        profiler.reset_timeline()


@pytest.mark.parametrize("step, taken", [
    (contextlib.nullcontext, True),               # no step: plain jax.grad
    (lambda: _step(remat.FLOOR), True),           # the ladder's floor
    (lambda: _step(("sel",)), False),             # keyevl2.train-16k's rung
    (lambda: _step(remat.LADDER[0]), False),
    # a rung is the names the model has: none met, none kept
    (lambda: _step(remat.LADDER[0], met=()), True),
    (lambda: _step(remat.LADDER[0], blocks=0), True),   # remat=False
], ids=["no-step", "floor", "sel", "richest", "names-not-met", "no-remat"])
def test_the_gradient_is_taken_in_the_forward_where_the_step_keeps_nothing(
        step, taken):
    hidden, table, tokens = _inputs("padded", jnp.float32, False)
    f = _chunked(1.0, False, jnp.float32)
    profiler.reset_timeline()
    try:
        with step():
            jax.eval_shape(jax.grad(f, (0, 1)), hidden, table, tokens)
        assert ("head:grad_in_forward" in profiler.counters()) == taken
    finally:
        profiler.reset_timeline()


@pytest.mark.parametrize("rung, taken", [(remat.FLOOR, True),
                                         (("gate", "up"), False)],
                         ids=["floor", "gate-up"])
def test_a_train_step_hands_the_head_its_rung(rung, taken):
    """Through ``make_train_step``: the model's trace under the rung's
    ``Saved`` is what the head reads."""
    import optax

    from tony_tpu.models import get_model

    model = get_model("llama-tiny", dtype=jnp.float32, xent_chunk=8,
                      remat=True)
    tokens = jnp.zeros((2, 17), jnp.int32)
    state = train.create_train_state(model, optax.sgd(1.0), tokens,
                                     jax.random.PRNGKey(0))
    step = train.make_train_step(
        loss_of=lambda out, batch: out,
        apply_kwargs_of=lambda batch: {"targets": batch["x"]})
    profiler.reset_timeline()
    try:
        jax.eval_shape(step.build(remat.Saved(rung)), state, {"x": tokens})
        assert profiler.counters()["head:chunks"] == 4
        assert ("head:grad_in_forward" in profiler.counters()) == taken
    finally:
        profiler.reset_timeline()


def test_accumulated_microbatches_hand_the_head_a_fraction():
    """``make_accum_train_step`` differentiates a mean over microbatches:
    the cotangent that reaches the head is 1/k, and the step's gradient
    is the whole batch's."""
    import optax

    from tony_tpu.models import get_model

    model = get_model("llama-tiny", dtype=jnp.float32, xent_chunk=8)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 17), 0, 256)
    make = lambda: train.create_train_state(
        model, optax.sgd(1.0), tokens, jax.random.PRNGKey(0))
    kwargs = dict(loss_of=lambda out, batch: out,
                  apply_kwargs_of=lambda batch: {"targets": batch["x"]})
    whole, _ = train.make_train_step(**kwargs)(make(), {"x": tokens})
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with mesh:
        split, _ = train.make_accum_train_step(
            mesh=mesh, microbatches=2, **kwargs)(make(), {"x": tokens})
    for a, b in zip(jax.tree.leaves(whole.params),
                    jax.tree.leaves(split.params)):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=1e-5)
